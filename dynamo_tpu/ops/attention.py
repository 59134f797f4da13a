"""Attention over a paged KV cache.

The framework's equivalent of the CUDA paged-attention kernels inside the
reference's engines. One entrypoint `paged_attention` serves prefill, chunked
prefill, and decode uniformly: queries are a chunk of C tokens starting at
`start_pos` within each sequence; keys/values live in a block pool indexed by
per-sequence block tables.

Two implementations:
  - XLA path (here): gather pages → dense masked attention. Runs on any
    backend; the correctness oracle for the pallas kernel.
  - pallas TPU kernel (ops/pallas/paged_attention.py): streams pages
    HBM→VMEM with double buffering, flash-style online softmax; selected via
    `use_kernel=True` (engine enables it on TPU backends).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.pallas.paged_attention import (
    DecodePlan,
    decode_plan,
    paged_attention_decode_kernel,
    paged_attention_kernel,
)
from dynamo_tpu.ops.pallas.mla_paged import (
    LatentPlan,
    latent_plan,
    mla_paged_decode,
    query_block,
)
from dynamo_tpu.runtime.device_observe import watched_jit

NEG_INF = -1e30

# The TPU's vector lane count. A bf16 pool whose minor (head) dimension is
# narrower than this is tiled by XLA with the BLOCK index minor-most, and
# every program that hands it to a Pallas call or carries it through a
# ``while`` re-lays the whole pool on the way in and on the way out (96
# whole-pool copies a decode burst at head size 64, ~40 of 54.5 ms: my chip
# runs, PR 25). Held at a full lane tile the default layout is the
# row-major one the kernels read, so a pool is resident as
# [blocks, block, kv_heads, pool_head_dim(D)] and only lanes [:D] carry
# keys or values; the rest stay the zeros they were allocated as.
KV_LANE_TILE = 128


def pool_head_dim(head_dim: int) -> int:
    """The minor dimension a dense per-layer KV pool is held at."""
    return max(head_dim, KV_LANE_TILE)


def pad_head(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """``x`` [..., D] zero-padded on its last axis to a pool's ``width``
    (K/V entering a pool: a chunk, wire blocks); as is where they agree."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def latent_pool_width(cache_width: int) -> int:
    """The minor dimension a latent (MLA) pool is held at: the cache row
    (c_kv beside the shared rotary key) at a whole number of lane tiles.
    XLA tiles the minor dimension in 128 lanes whatever is asked for, so
    576 values a token occupy 640 either way; held at 640 the kernel's
    page tile is the resident one and its contraction runs over whole
    tiles (the lanes past the row are zeros in the pool and in q~)."""
    return -(-cache_width // KV_LANE_TILE) * KV_LANE_TILE


def _takes_decode_kernel(C: int, n_heads: int, k_cache) -> bool:
    """Decode (C=1) and short chunks (speculative verify, chunk tails) take
    the live-span kernel; longer chunks the generic (B, pages) grid."""
    k_values = k_cache["q8"] if isinstance(k_cache, dict) else k_cache
    return C <= 8 and C * (n_heads // k_values.shape[2]) <= 64


def paged_attention_plan(
    C: int, n_heads: int, k_cache, block_tables, start_pos, chunk_lens,
    *, use_kernel: bool, window: Any = 0,
) -> Optional[DecodePlan]:
    """What ``paged_attention`` would derive from positions, table and
    window on every call — the live-span kernel's grid — for a caller that
    attends layer after layer over the same step: derive it once per
    distinct ``window`` and pass it as ``plan``. None where the call takes
    another route (nothing to share). ``k_cache`` is one layer's pool."""
    if not (use_kernel and _takes_decode_kernel(C, n_heads, k_cache)):
        return None
    return decode_plan(k_cache, block_tables, start_pos, chunk_lens, C, window)


def paged_attention(
    q: jnp.ndarray,  # [B, C, n_heads, head_dim]
    k_cache: jnp.ndarray,  # [num_blocks, block_size, n_kv_heads, head_dim]
    v_cache: jnp.ndarray,  # [num_blocks, block_size, n_kv_heads, head_dim]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32 (entries beyond seq = any)
    start_pos: jnp.ndarray,  # [B] int32 — tokens already in cache before chunk
    chunk_lens: jnp.ndarray,  # [B] int32 — valid query tokens in the chunk
    *,
    sm_scale: Optional[float] = None,
    use_kernel: bool = False,
    window: Any = 0,  # sliding window in tokens (int or traced scalar); 0 = full
    logit_cap: float = 0.0,  # cap·tanh(s/cap) score softcap; 0 = off
    plan: Optional[DecodePlan] = None,  # paged_attention_plan() of this step
) -> jnp.ndarray:
    """Returns [B, C, n_heads, head_dim].

    The chunk's own K/V must already be written into the cache (the model
    writes the chunk before attending); causality is enforced by masking key
    position t to t <= start_pos + c for query offset c. ``window`` > 0
    additionally hides keys with t <= start_pos + c - window (Mistral-SWA /
    Gemma-2 alternating-layer sliding windows) — it may be a TRACED scalar
    so a lax.scan over layers can alternate windowed/full layers in one
    compiled body; ``logit_cap`` applies the Gemma-2 score softcap.
    """
    if use_kernel:
        _, C, n_heads, _ = q.shape
        if _takes_decode_kernel(C, n_heads, k_cache):
            # The live-span kernel's grid is the live page groups of the
            # rows with chunk_lens > 0, so an empty slot or a wide table
            # costs nothing (the generic (B, pages) grid runs B×P tiny
            # steps whatever is live).
            return paged_attention_decode_kernel(
                q, k_cache, v_cache, block_tables, start_pos,
                window, chunk_lens, plan,
                sm_scale=sm_scale, logit_cap=logit_cap,
            )
        return paged_attention_kernel(
            q, k_cache, v_cache, block_tables, start_pos, chunk_lens,
            sm_scale=sm_scale, window=window, logit_cap=logit_cap,
        )
    return _paged_attention_xla(
        q, k_cache, v_cache, block_tables, start_pos, chunk_lens, window,
        sm_scale=sm_scale, logit_cap=logit_cap,
    )


# The gather form: the kernels' test reference, the CPU path and the path
# under a mesh.
def _paged_attention_xla_impl(
    q, k_cache, v_cache, block_tables, start_pos, chunk_lens,
    window=0, *, sm_scale=None, logit_cap: float = 0.0,
):
    from dynamo_tpu.ops.kv_quant import dequantize_pages, is_quantized_pool

    def _gather(cache, B, T, n_kv_heads, head_dim):
        if is_quantized_pool(cache):
            pages = cache["q8"][block_tables]  # [B, P, bs, KH, D]
            scales = cache["s"][block_tables]  # [B, P, KH, bs]
            return dequantize_pages(pages, scales).reshape(
                B, T, n_kv_heads, head_dim
            )
        # [..., :head_dim]: a pool held wider than the head (pool_head_dim)
        # never shows its padding lanes to the scores or the output.
        return cache[block_tables][..., :head_dim].reshape(
            B, T, n_kv_heads, head_dim
        )

    B, C, n_heads, head_dim = q.shape
    values = k_cache["q8"] if is_quantized_pool(k_cache) else k_cache
    num_blocks, block_size, n_kv_heads, _ = values.shape
    max_blocks = block_tables.shape[1]
    T = max_blocks * block_size
    q_per_kv = n_heads // n_kv_heads
    scale = sm_scale if sm_scale is not None else head_dim**-0.5

    # Gather pages: [B, max_blocks, block_size, KH, D] → [B, T, KH, D]
    k = _gather(k_cache, B, T, n_kv_heads, head_dim)
    v = _gather(v_cache, B, T, n_kv_heads, head_dim)

    # [B, C, KH, q_per_kv, D]
    qg = q.reshape(B, C, n_kv_heads, q_per_kv, head_dim).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bcghd,btgd->bcght", qg, kf) * scale  # [B,C,KH,G,T]
    if logit_cap > 0.0:
        scores = logit_cap * jnp.tanh(scores / logit_cap)

    t_pos = jax.lax.broadcasted_iota(jnp.int32, (B, C, T), 2)
    c_pos = jax.lax.broadcasted_iota(jnp.int32, (B, C, T), 1)
    limit = start_pos[:, None, None] + c_pos  # key t visible iff t <= start+c
    mask = t_pos <= limit  # [B, C, T]
    w = jnp.asarray(window, jnp.int32)
    mask = mask & ((w <= 0) | (t_pos > limit - w))
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bcght,btgd->bcghd", probs, v.astype(jnp.float32))
    return out.reshape(B, C, n_heads, head_dim).astype(q.dtype)


_paged_attention_xla = watched_jit(
    "ops.paged_attention_xla",
    partial(jax.jit, static_argnames=("sm_scale", "logit_cap"))(
        _paged_attention_xla_impl
    ),
)


def dense_chunk_attention(
    q: jnp.ndarray,  # [B, C, n_heads, head_dim]
    k: jnp.ndarray,  # [B, C, n_kv_heads, head_dim] — the chunk's OWN K
    v: jnp.ndarray,  # [B, C, n_kv_heads, head_dim]
    chunk_lens: jnp.ndarray,  # [B] int32 — valid tokens in the chunk
    *,
    sm_scale: Optional[float] = None,
    window: Any = 0,
    logit_cap: float = 0.0,
) -> jnp.ndarray:
    """First-chunk attention: the whole history IS the in-flight chunk, so
    attend densely over the registers instead of reading the pages just
    written — zero cache DMA. Returns [B, C, n_heads, head_dim].

    This is the fast path for fresh prefills (start_pos == 0, one chunk):
    at the bench shape it removes every per-layer paged read from the
    prefill program (the page DMAs dominated prefill time; the ISL=128
    chunk's dense scores are a [C, C] tile the MXU eats for free).
    Padding key columns (>= chunk_lens) are masked so valid rows are exact;
    padding ROWS produce garbage that callers already ignore (their cache
    writes are dropped and their logits never read)."""
    B, C, H, D = q.shape
    KH = k.shape[2]
    scale = sm_scale if sm_scale is not None else D**-0.5
    if KH != H:  # GQA: repeat kv heads into query-head groups
        rep = H // KH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)  # [B, H, C, D]
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    mask = cols <= rows  # causal within the chunk
    win = jnp.asarray(window, jnp.int32)
    mask = mask & ((win <= 0) | (cols > rows - win))  # sliding window
    valid = cols[None] < chunk_lens[:, None, None]  # padding keys
    # -1e30, NOT -inf: a padding row whose window admits no valid key would
    # softmax to NaN, and the NEXT layer's p @ v turns 0-weight × NaN-value
    # into NaN for EVERY row (0 × NaN = NaN). With a finite sentinel the
    # empty row degrades to a uniform average — garbage but finite, and
    # garbage rows are never read (their cache writes drop, their logits
    # are never selected).
    s = jnp.where((mask[None] & valid)[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def write_chunk_to_cache(
    cache: jnp.ndarray,  # [num_blocks, block_size, KH, pool_head_dim(D)]
    chunk: jnp.ndarray,  # [B, C, KH, D]
    block_tables: jnp.ndarray,  # [B, max_blocks]
    start_pos: jnp.ndarray,  # [B]
    chunk_lens: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """Scatter a chunk of K or V into its pages. Padding positions and
    positions beyond the block table's capacity (multi-step decode overshoot
    past a stop condition) are dropped (out-of-range index + mode='drop')."""
    from dynamo_tpu.ops.kv_quant import is_quantized_pool, quantize_kv_chunk

    B, C = chunk.shape[:2]
    quantized = is_quantized_pool(cache)
    values = cache["q8"] if quantized else cache
    num_blocks, block_size = values.shape[:2]
    capacity = block_tables.shape[1] * block_size
    c_off = jax.lax.broadcasted_iota(jnp.int32, (B, C), 1)
    pos = start_pos[:, None] + c_off  # [B, C]
    valid = (c_off < chunk_lens[:, None]) & (pos < capacity)
    block_idx = jnp.take_along_axis(
        block_tables, jnp.clip(pos // block_size, 0, block_tables.shape[1] - 1), axis=1
    )
    block_idx = jnp.where(valid, block_idx, num_blocks)  # OOB → dropped
    slot = pos % block_size
    if not quantized:
        return cache.at[block_idx, slot].set(
            pad_head(chunk, cache.shape[-1]), mode="drop"
        )
    q8, s = quantize_kv_chunk(chunk)  # [B, C, KH, D], [B, C, KH]
    # scales live [NB, KH, bs]: the two advanced indices surround the KH
    # slice, so the indexed result is [B, C, KH] — exactly s's shape.
    return {
        "q8": cache["q8"].at[block_idx, slot].set(q8, mode="drop"),
        "s": cache["s"].at[block_idx, :, slot].set(s, mode="drop"),
    }


# -- latent attention (MLA) ----------------------------------------------------
# Two forms of one attention. EXPANDED: per-head keys and values are made
# from the latents (a fresh chunk, whose latents are in registers). ABSORBED:
# the key's up-projection is folded into the query and the value's applied
# after the sum, so the H heads attend over the cached rows themselves
# (decode, and a chunk that has context): one key/value head whose key is the
# whole row and whose value is its first ``v_width`` lanes.

# Query positions per block of the XLA forms: bounds the float32 scores at
# [B, block, H, keys].
_MLA_XLA_QUERY_BLOCK = 64


def mla_chunk_attention(
    q: jnp.ndarray,  # [B, C, H, Dqk]
    k: jnp.ndarray,  # [B, C, H, Dqk] — the chunk's own expanded keys
    v: jnp.ndarray,  # [B, C, H, Dv]
    chunk_lens: jnp.ndarray,  # [B]
    *,
    sm_scale: float,
) -> jnp.ndarray:
    """Expanded form over a fresh chunk (every row starts at 0): causal
    attention within the chunk, in blocks of query positions so that the
    scores of 128 heads stay [B, H, block, keys]. Returns [B, C, H, Dv]."""
    B, C, H, _ = q.shape
    qb = query_block(C, _MLA_XLA_QUERY_BLOCK)
    out = []
    for r0 in range(0, C, qb):  # unrolled: a prefill program holds no ``while``
        keys = r0 + qb  # causal: this block sees no key past its last query
        kf = k[:, :keys].astype(jnp.float32)
        vf = v[:, :keys].astype(jnp.float32)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q[:, r0:keys].astype(jnp.float32), kf
        ) * sm_scale
        rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (qb, keys), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (qb, keys), 1)
        valid = (cols[None] < chunk_lens[:, None, None])[:, None]  # [B, 1, qb, keys]
        s = jnp.where((cols <= rows)[None, None] & valid, s, NEG_INF)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vf))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


def _mla_paged_xla_impl(
    q, pool, block_tables, start_pos, chunk_lens, *, v_width: int, sm_scale: float
):
    """Absorbed form, XLA: gather the rows' pages, then blocks of query
    positions against all of them. The oracle for the kernel, and what the
    CPU and a mesh serve from."""
    B, C, H, W = q.shape
    T = block_tables.shape[1] * pool.shape[1]
    rows = pool[block_tables].reshape(B, T, W).astype(jnp.float32)
    qb = query_block(C, _MLA_XLA_QUERY_BLOCK)
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (qb, T), 1)
    out = []
    for r0 in range(0, C, qb):  # unrolled, as above
        s = jnp.einsum(
            "bqhw,btw->bqht", q[:, r0 : r0 + qb].astype(jnp.float32), rows
        ) * sm_scale
        limit = (
            start_pos[:, None, None] + r0
            + jax.lax.broadcasted_iota(jnp.int32, (qb, T), 0)[None]
        )
        s = jnp.where((t_pos[None] <= limit)[:, :, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("bqht,btw->bqhw", p, rows[..., :v_width]))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


_mla_paged_xla = watched_jit(
    "ops.mla_paged_xla",
    partial(jax.jit, static_argnames=("v_width", "sm_scale"))(_mla_paged_xla_impl),
)


def mla_attention_plan(
    C: int, pool, block_tables, start_pos, chunk_lens, *, use_kernel: bool
) -> Optional[LatentPlan]:
    """The absorbed kernel's grid for one forward step, shared by its
    latent-attention layers; None where the XLA form serves."""
    if not use_kernel:
        return None
    return latent_plan(pool, block_tables, start_pos, chunk_lens, C)


def mla_paged_attention(
    q: jnp.ndarray,  # [B, C, H, W] absorbed queries (zeros past the cache row)
    pool: jnp.ndarray,  # [num_blocks, block_size, W] latent rows
    block_tables: jnp.ndarray,
    start_pos: jnp.ndarray,
    chunk_lens: jnp.ndarray,
    *,
    v_width: int,
    sm_scale: float,
    use_kernel: bool = False,
    plan: Optional[LatentPlan] = None,
) -> jnp.ndarray:
    """Absorbed latent attention over the paged latent pool; the chunk's
    own rows must already be written. Returns [B, C, H, v_width]."""
    if use_kernel:
        return mla_paged_decode(
            q, pool, block_tables, start_pos, chunk_lens, plan,
            v_width=v_width, sm_scale=sm_scale,
        )
    return _mla_paged_xla(
        q, pool, block_tables, start_pos, chunk_lens,
        v_width=v_width, sm_scale=sm_scale,
    )
