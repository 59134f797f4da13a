"""Pallas TPU paged-attention kernel.

The performance-critical op of the native engine: attention of a C-token
query chunk against a paged KV cache, serving decode (C=1), chunked prefill,
and full prefill (same contract as ops/attention.py's XLA oracle).

Reference parity: plays the role of the paged-attention CUDA kernels inside
the reference's engines (vLLM/TRT-LLM) that Dynamo orchestrates around; the
reference's own in-tree kernel is lib/llm/src/kernels/block_copy.cu (block
movement), covered here by ops/pallas/block_copy.py.

Two kernels, one contract (the XLA oracle's):

``paged_attention_kernel`` — chunks of more than 8 tokens (chunked
prefill). TPU-first design (not a CUDA translation):
  - The grid is (batch, page-group). The per-sequence block table is a
    scalar-prefetch operand; each grid step DMAs ``pages_per_step`` K/V
    pages selected by BlockSpec index_maps reading the table, so the pallas
    pipeline double-buffers the scattered HBM→VMEM page streams
    automatically — pages never materialize as a dense [B, T, KH, D] gather
    in HBM (the XLA oracle's O(padded-context) HBM-traffic problem).
  - Each page DMA carries ALL kv heads (one [bs, KH, D] transfer — Mosaic
    wants the last two block dims full anyway); the small static KH loop is
    unrolled in the kernel body.
  - Flash-style online softmax: running max / normalizer / weighted
    accumulator live in VMEM scratch across the page-group axis (the
    innermost, sequentially-iterated grid dimension); the output block is
    written once on the last step.
  - Page groups wholly past a sequence's valid length skip all compute via
    pl.when; partially-valid groups are handled by the causal mask.
  - All dots run on the MXU in float32 via preferred_element_type; the cache
    stays bfloat16 in HBM.

``paged_attention_decode_kernel`` — decode (C=1) and short chunks (C ≤ 8:
speculative verify, chunk tails). Its cost is what is LIVE, not the
dispatched shape:
  - The grid is ONE axis whose length is a traced scalar: the number of
    live page groups over the rows with ``chunk_lens > 0``. ``decode_plan``
    derives per-row page bounds ``[poff, pcount)`` and from them the flat
    work list, step t → (row, first page) (ops/pallas/live_pages.py), once
    per forward step; the layers share it. An empty slot, or table width
    past a row's context, is no grid step — the kernel is not told the
    slot count or the table width at all (docs/design_docs/engine.md).
  - A step visits up to ``DECODE_GROUP_PAGES`` consecutive pages of one
    row (fewer where a page is large: ``_decode_group_pages`` keeps the
    step's operands inside a VMEM budget), each a BlockSpec operand whose
    index map reads work list and block table, so the pipeline prefetches
    the next step's pages across row boundaries; the work list is one
    entry longer than the grid's most steps (live_pages.live_work_list: a
    one-entry list halts the core). The pages stay BlockSpec operands
    (not manual DMAs from a whole-pool HBM ref) because Mosaic refuses to
    slice a pool whose minor dimension is narrower than a 128-lane tile:
    head_dim 64, and the int8 pools' [NB, KH, 16] scales.
  - One online-softmax update per step over all its pages' score tiles;
    the steps of a row are consecutive, so q, the output block and the
    flash state stay resident from the row's first step to its last.
  - Same mathematics as the other kernel: f32 dots, f32 softmax, NEG_INF
    mask, softcap, sliding window (a windowed row starts at its first
    in-window page).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.live_pages import (
    live_page_bounds,
    live_work_list,
)

NEG_INF = -1e30
# Live-span decode kernel: the most consecutive pages of one row a grid
# step visits, and the VMEM a step's K and V page operands may take with
# the pipeline's two buffers each (a quarter of the 16 MiB the compiler
# scopes a kernel to on the v5e: 16 pages of every preset at block size 16
# fit; the body's f32 temporaries take their share of the rest).
DECODE_GROUP_PAGES = 16
DECODE_PAGES_VMEM_BYTES = 4 << 20
# Chunk kernel: what a grid step keeps of its queries in VMEM, per (query,
# head) row of D lanes: q and the output block, two pipeline buffers each, in
# the queries' dtype, and the float32 accumulator. Over ``SPLIT_ABOVE`` (the
# compiler scopes a kernel to 16 MiB: such a call never compiled) the chunk's
# query positions are served in blocks that keep at most ``SPLIT_TO``.
CHUNK_ROWS_SPLIT_ABOVE_BYTES = 14 << 20
CHUNK_ROWS_SPLIT_TO_BYTES = 7 << 20


def chunk_query_block(C: int, n_heads: int, head_dim: int, itemsize: int) -> int:
    """Query positions of a chunk that one call of the chunk kernel keeps in
    VMEM at once: all ``C`` where they fit (every shape served before PR 44:
    nothing changes for them), else the largest power-of-two part of C whose
    rows are within ``CHUNK_ROWS_SPLIT_TO_BYTES`` (a 256-token chunk at 64
    heads of 128 lanes is 24 MiB whole: 64 positions a block)."""
    row = n_heads * head_dim * (4 * itemsize + 4)
    if C * row <= CHUNK_ROWS_SPLIT_ABOVE_BYTES:
        return C
    cq = C
    while cq > 8 and cq % 2 == 0 and cq * row > CHUNK_ROWS_SPLIT_TO_BYTES:
        cq //= 2
    return cq


def _kernel(
    # scalar prefetch
    block_tables_ref,  # [B, P_pad] int32 (SMEM)
    start_pos_ref,  # [B] int32
    chunk_lens_ref,  # [B] int32
    window_ref,  # [1] int32 — sliding window (0 = full attention)
    # VMEM blocks: q, then S (k, v) page pairs — int8 caches interleave a
    # [1, KH, bs] scale ref after each page ref (k, ks, v, vs)
    q_ref,  # [1, KH, C*G, D] (host pre-transposed: rows are (c, g), c-major)
    *refs,  # pages..., o_ref, m, l, acc
    sm_scale: float,
    block_size: int,
    n_groups: int,
    pages_per_step: int,
    logit_cap: float = 0.0,
    quantized: bool = False,
):
    S = pages_per_step
    stride = 4 if quantized else 2
    kv_refs = refs[: stride * S]
    o_ref = refs[stride * S]
    m_ref, l_ref, acc_ref = refs[stride * S + 1 :]

    b = pl.program_id(0)
    p = pl.program_id(1)
    num_steps = pl.num_programs(1)

    KH = q_ref.shape[1]
    CG = q_ref.shape[2]
    # A page may be held wider than the head (ops/attention.pool_head_dim):
    # lanes [:D] are the keys and values, the rest are never loaded.
    D = q_ref.shape[3]
    G = n_groups
    W = S * block_size  # keys visited per grid step

    start = start_pos_ref[b]
    clen = chunk_lens_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Highest key position any valid query in this sequence can see is
    # start + clen - 1 (the chunk's own K/V are already in the cache).
    last_needed_page = jnp.maximum(start + clen - 1, 0) // block_size
    win = window_ref[0]
    # With a window, the EARLIEST key any query (offset 0) can see is
    # start - win + 1; earlier page groups skip entirely.
    first_needed_group = jnp.where(
        win > 0, jnp.maximum(start - win + 1, 0) // block_size // S, 0
    )

    @pl.when((p >= first_needed_group) & (p * S <= last_needed_page))
    def _compute():
        # Causal mask across the whole page group, shared by every head:
        # key position t visible to query offset c iff t <= start + c.
        # Rows are (c, g) pairs, c-major.
        c_idx = jax.lax.broadcasted_iota(jnp.int32, (CG, W), 0) // G
        t_idx = p * W + jax.lax.broadcasted_iota(jnp.int32, (CG, W), 1)
        visible = t_idx <= start + c_idx
        visible = visible & ((win <= 0) | (t_idx > start + c_idx - win))

        for h in range(KH):  # static unroll; KH is small (2-8)
            q = q_ref[0, h].astype(jnp.float32)  # [CG, D]
            st = stride
            k = jnp.concatenate(
                [kv_refs[st * s][0, :, h, :D] for s in range(S)], axis=0
            ).astype(jnp.float32)  # [W, D]
            v = jnp.concatenate(
                [kv_refs[st * s + st // 2][0, :, h, :D] for s in range(S)],
                axis=0,
            ).astype(jnp.float32)  # [W, D]
            if quantized:
                # Per-token scales ride the score/prob rows instead of
                # touching the [W, D] pages (ops/kv_quant.py layout).
                ks = jnp.concatenate(
                    [kv_refs[st * s + 1][0, h][None, :] for s in range(S)],
                    axis=1,
                )  # [1, W]
                vs = jnp.concatenate(
                    [kv_refs[st * s + 3][0, h][None, :] for s in range(S)],
                    axis=1,
                )  # [1, W]

            s_mat = (
                jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * sm_scale
            )  # [CG, W]
            if quantized:
                s_mat = s_mat * ks
            if logit_cap > 0.0:
                s_mat = logit_cap * jnp.tanh(s_mat / logit_cap)
            s_mat = jnp.where(visible, s_mat, NEG_INF)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s_mat, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(s_mat - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
            if quantized:
                probs = probs * vs
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                probs, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    @pl.when(p == num_steps - 1)
    def _finalize():
        # Every query row sees at least key t=0 (0 <= start + c always), so
        # l is strictly positive for rows that matter.
        for h in range(KH):
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = out.astype(o_ref.dtype)


def _decode_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,  # [B, P] int32
    start_pos_ref,  # [B] int32
    pcount_ref,  # [B] int32 — live pages end (exclusive); 0 = inactive row
    poff_ref,  # [B] int32 — first live page (sliding window; else 0)
    window_ref,  # [1] int32 — sliding window (0 = full attention)
    step_row_ref,  # [T] int32 — the row grid step t works on
    step_page_ref,  # [T] int32 — the first page of step t's group
    # VMEM blocks: q [1, KH, C*G, D] of the step's row, then S (k, v) page
    # pairs — int8 caches interleave a [1, KH, bs] scale ref after each
    # page ref (k, ks, v, vs)
    q_ref,
    *refs,  # pages..., o_ref, m, l, acc
    sm_scale: float,
    block_size: int,
    n_groups: int,
    group_pages: int,
    logit_cap: float = 0.0,
    quantized: bool = False,
    head_pages: bool = False,
):
    """Live-span kernel for decode (C=1) and SHORT chunks (C ≤ 8, the
    speculative-verify shape). The grid is ONE axis of work steps whose
    length is the number of LIVE page groups — a traced scalar, so dead
    slots and dead table width cost no grid step at all. Step t visits
    ``group_pages`` (S) consecutive pages of one row, ``step_row[t]``,
    starting at ``step_page[t]``; the steps of a row are consecutive, so
    the q and output blocks stay resident for the row and the online-
    softmax state in VMEM scratch is initialised at the row's first group
    and written out at its last. The BlockSpec index maps read the work
    list and the block table, so the pallas pipeline prefetches step t+1's
    pages — of the same row or the next live one — while step t computes.

    Pages of a group past the row's last live page are fetched as copies
    of that last page (clamped index: always a valid block) and hidden by
    the ``t < pcount·bs`` mask. The S pages of a step share ONE online-
    softmax update: their score tiles are independent dots reduced
    together, so the MXU pipelines them (measured on the v5e at the
    qwen2.5-0.5b shape with 8 pages a step: 105 us a call against 136 /
    198 / 228 us with updates of 4 / 2 / 1 pages; 16 pages a step: 90 us;
    my chip runs, PR 25).

    Query rows per head are (c, g) pairs, c-major; key t is visible to
    row (c, g) iff t <= start + c (the chunk's own K/V are already in the
    cache, as in the generic kernel).

    ``head_pages``: every K/V head has a table of its own ([B, KH, P]: the
    pages a sparse layer's indexer selected for that head), so a step holds
    S pages PER HEAD and head h reads its own (``selected_pages_attention``
    says what the positions then mean)."""
    S = group_pages
    stride = 4 if quantized else 2
    KH = q_ref.shape[1]
    n_ops = stride * S * (KH if head_pages else 1)
    kv_refs = refs[:n_ops]
    o_ref = refs[n_ops]
    m_ref, l_ref, acc_ref = refs[n_ops + 1 :]

    def at(s, h):  # the first operand of page s as head h reads it
        return stride * (s * KH + h) if head_pages else stride * s

    CG = q_ref.shape[2]
    D = q_ref.shape[3]  # the head; a page's lanes past it are padding
    G = n_groups
    C = CG // G
    bs = block_size

    t = pl.program_id(0)
    b = step_row_ref[t]
    pstart = step_page_ref[t]
    start = start_pos_ref[b]
    pcount = pcount_ref[b]
    win = window_ref[0]

    @pl.when(pstart == poff_ref[b])
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = 1 if C == 1 else CG  # decode: one shared mask row (broadcast)
    if C == 1:
        limit = start
    else:
        limit = start + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // G
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)

    visible = []
    for s in range(S):
        t_idx = (pstart + s) * bs + lane
        vis = (t_idx <= limit) & (t_idx < pcount * bs)
        visible.append(vis & ((win <= 0) | (t_idx > limit - win)))
    for h in range(KH):  # static unroll; KH is small (2-8)
        q = q_ref[0, h].astype(jnp.float32)  # [CG, D]
        scores = []
        for s in range(S):
            k = kv_refs[at(s, h)][0, :, h, :D].astype(jnp.float32)
            s_mat = (
                jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * sm_scale
            )  # [CG, bs]
            if quantized:
                # Per-token scales ride the score/prob rows instead of
                # touching the [bs, D] pages (ops/kv_quant.py layout).
                s_mat = s_mat * kv_refs[at(s, h) + 1][0, h][None, :]
            if logit_cap > 0.0:
                s_mat = logit_cap * jnp.tanh(s_mat / logit_cap)
            scores.append(jnp.where(visible[s], s_mat, NEG_INF))
        m_prev = m_ref[h]
        m_new = m_prev
        for s_mat in scores:
            m_new = jnp.maximum(m_new, jnp.max(s_mat, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[h] * alpha
        acc = acc_ref[h] * alpha
        for s, s_mat in enumerate(scores):
            probs = jnp.exp(s_mat - m_new)
            l_new = l_new + jnp.sum(probs, axis=-1, keepdims=True)
            if quantized:
                probs = probs * kv_refs[at(s, h) + 3][0, h][None, :]
            v = kv_refs[at(s, h) + stride // 2][0, :, h, :D].astype(
                jnp.float32
            )
            acc = acc + jax.lax.dot_general(
                probs, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        l_ref[h] = l_new
        acc_ref[h] = acc
        m_ref[h] = m_new

    @pl.when(pstart + S >= pcount)
    def _finalize():
        # Every live query row sees at least one key, so l > 0.
        for h in range(KH):
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = out.astype(o_ref.dtype)


class DecodePlan(NamedTuple):
    """What the live-span decode kernel's grid is made of. It depends on
    the step's positions, the table and the layer's window only, so a
    forward step derives it once (``decode_plan``) and every layer with
    that window shares it."""

    tables: jnp.ndarray  # [B, P] int32; an empty slot's row is zeroed
    pcount: jnp.ndarray  # [B] int32 — live pages end; 0 = empty slot
    poff: jnp.ndarray  # [B] int32 — first live page
    step_row: jnp.ndarray  # [T] int32 (live_work_list)
    step_page: jnp.ndarray  # [T] int32
    total: jnp.ndarray  # [] int32 — live page groups: the grid's length


def _decode_group_pages(k_cache, table_width: int) -> int:
    """Pages of one row a grid step visits: ``DECODE_GROUP_PAGES``, fewer
    where the step's K and V page operands, each double-buffered by the
    pipeline, would pass ``DECODE_PAGES_VMEM_BYTES`` (large
    ``--block-size``). An int8 page counts twice: its f32 scale page
    rides along and the body widens it to f32 as it does a bf16 one."""
    from dynamo_tpu.ops.kv_quant import is_quantized_pool

    quantized = is_quantized_pool(k_cache)
    values = k_cache["q8"] if quantized else k_cache
    _, block_size, n_kv_heads, head_dim = values.shape
    page_bytes = (
        block_size * n_kv_heads * max(head_dim, 128) * values.dtype.itemsize
    )
    step_bytes = 4 * page_bytes * (2 if quantized else 1)
    fits = DECODE_PAGES_VMEM_BYTES // step_bytes
    return max(1, min(DECODE_GROUP_PAGES, table_width, fits))


def decode_plan(
    k_cache, block_tables, start_pos, chunk_lens, C: int, window=0
) -> DecodePlan:
    """The live-span kernel's grid for one forward step: per-row page
    bounds, the flat work list over them and its length."""
    values = k_cache["q8"] if isinstance(k_cache, dict) else k_cache
    block_size = values.shape[1]
    P = block_tables.shape[1]
    pcount, poff = live_page_bounds(
        start_pos, chunk_lens, C, window, block_size, P
    )
    total, step_row, step_page = live_work_list(
        pcount, poff, _decode_group_pages(k_cache, P), P
    )
    # An empty slot's table may hold anything; with no live row at all the
    # grid still runs its one step, on row 0: give it a block that exists.
    tables = jnp.where(
        (pcount > poff)[:, None], block_tables.astype(jnp.int32), 0
    )
    return DecodePlan(tables, pcount, poff, step_row, step_page, total)


def _selected_group_pages(k_cache, table_width: int) -> int:
    """Pages a grid step visits PER K/V HEAD where every head has its own
    table: the same VMEM as a step of the one-table form."""
    n_kv_heads = k_cache.shape[2]
    return max(1, _decode_group_pages(k_cache, table_width) // n_kv_heads)


def selected_plan(k_cache, head_tables, n_pages) -> DecodePlan:
    """The live-span kernel's grid over SELECTED pages: ``head_tables``
    [B, KH, W] names, for each row and K/V head, the ``n_pages`` [B] pages
    its query attends over, in ascending order of position; a row with
    ``n_pages`` 0 is no grid step."""
    W = head_tables.shape[-1]
    pcount = n_pages.astype(jnp.int32)
    poff = jnp.zeros_like(pcount)
    total, step_row, step_page = live_work_list(
        pcount, poff, _selected_group_pages(k_cache, W), W
    )
    tables = jnp.where(
        (pcount > 0)[:, None, None], head_tables.astype(jnp.int32), 0
    )
    return DecodePlan(tables, pcount, poff, step_row, step_page, total)


def _paged_attention_decode_kernel_impl(
    q: jnp.ndarray,  # [B, C, n_heads, head_dim], C <= 8
    k_cache,  # [num_blocks, block_size, KH, >= D] — or {"q8", "s"} int8 pool
    v_cache,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    start_pos: jnp.ndarray,  # [B] int32
    window=0,  # sliding window (int or traced scalar); 0 = full
    chunk_lens: Optional[jnp.ndarray] = None,  # [B] int32; 0 = empty slot
    plan: Optional[DecodePlan] = None,  # decode_plan() of the same inputs
    *,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
    logit_cap: float = 0.0,
) -> jnp.ndarray:
    """Decode / short-chunk (C ≤ 8) live-span kernel. Same contract as the
    XLA oracle for rows with ``chunk_lens > 0``; a row with ``chunk_lens``
    0 is never visited (its table and position may be stale) and returns
    zeros. Cost follows the live (row, page) pairs, not B × table width:
    the same rows under a wider table run the same grid steps. With a
    sliding ``window`` a row starts at its first in-window page. ``plan``
    is the caller's ``decode_plan`` of the same table, positions, lengths
    and window (one per forward step, shared by the layers); derived here
    when absent."""
    from dynamo_tpu.ops.kv_quant import is_quantized_pool

    quantized = is_quantized_pool(k_cache)
    B, C, n_heads, head_dim = q.shape
    assert C <= 8, "live-span kernel serves decode / short-chunk steps"
    k_values = k_cache["q8"] if quantized else k_cache
    _, block_size, n_kv_heads, page_dim = k_values.shape  # >= head_dim
    G = n_heads // n_kv_heads
    CG = C * G
    scale = sm_scale if sm_scale is not None else head_dim**-0.5
    if plan is None:
        plan = decode_plan(
            k_cache, block_tables, start_pos, chunk_lens, C, window
        )
    # A plan over selected pages (``selected_plan``) has a table per K/V
    # head: S pages a head a step, each head's its own operands.
    head_pages = plan.tables.ndim == 3
    if head_pages:
        S = _selected_group_pages(k_cache, plan.tables.shape[-1])
    else:
        S = _decode_group_pages(k_cache, block_tables.shape[1])
    live = plan.pcount > plan.poff  # rows the grid visits

    # [B, C, H, D] → [B, KH, C*G, D]; rows (c, g) c-major, as the kernel's
    # causal mask expects.
    q4 = (
        q.reshape(B, C, n_kv_heads, G, head_dim)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, n_kv_heads, CG, head_dim)
    )
    win = jnp.asarray(window, jnp.int32).reshape(1)

    def q_map(t, bt, sp, pc, po, w, srow, spage):
        return (srow[t], 0, 0, 0)

    def page_map(s, ndim, h=None):
        """Index map of an operand that holds page s of the step's group
        (clamped to the row's live pages: always a block that exists), of
        head h's own table where every head has one."""

        def index_map(t, bt, sp, pc, po, w, srow, spage):
            b = srow[t]
            page = jnp.maximum(jnp.minimum(spage[t] + s, pc[b] - 1), 0)
            block = bt[b, page] if h is None else bt[b, h, page]
            return (block,) + (0,) * (ndim - 1)

        return index_map

    in_specs = [pl.BlockSpec((1, n_kv_heads, CG, head_dim), q_map)]
    kv_args = []
    for s, h in (
        (s, h) for s in range(S)
        for h in (range(n_kv_heads) if head_pages else (None,))
    ):
        spec = pl.BlockSpec(
            (1, block_size, n_kv_heads, page_dim), page_map(s, 4, h)
        )
        if quantized:
            s_spec = pl.BlockSpec((1, n_kv_heads, block_size), page_map(s, 3, h))
            in_specs.extend([spec, s_spec, spec, s_spec])
            kv_args.extend(
                [k_cache["q8"], k_cache["s"], v_cache["q8"], v_cache["s"]]
            )
        else:
            in_specs.extend([spec, spec])
            kv_args.extend([k_cache, v_cache])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(jnp.maximum(plan.total, 1),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv_heads, CG, head_dim), q_map),
        scratch_shapes=[
            pltpu.VMEM((n_kv_heads, CG, 1), jnp.float32),
            pltpu.VMEM((n_kv_heads, CG, 1), jnp.float32),
            pltpu.VMEM((n_kv_heads, CG, head_dim), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, sm_scale=scale, block_size=block_size, n_groups=G,
        group_pages=S, logit_cap=logit_cap, quantized=quantized,
        head_pages=head_pages,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv_heads, CG, head_dim), q.dtype),
        interpret=interpret,
    )(
        plan.tables,
        start_pos.astype(jnp.int32),
        plan.pcount,
        plan.poff,
        win,
        plan.step_row,
        plan.step_page,
        q4,
        *kv_args,
    )
    # Rows the grid never visited (empty slots) hold whatever the buffer
    # held: zeros, by contract.
    out = jnp.where(live[:, None, None, None], out, 0)
    out = out.reshape(B, n_kv_heads, C, G, head_dim).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, C, n_heads, head_dim)


def _paged_attention_kernel_impl(
    q: jnp.ndarray,  # [B, C, n_heads, head_dim]
    k_cache,  # [num_blocks, block_size, KH, D] — or {"q8", "s"} int8 pool
    v_cache,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    start_pos: jnp.ndarray,  # [B] int32
    chunk_lens: jnp.ndarray,  # [B] int32
    window=0,  # sliding window (int or traced scalar); 0 = full
    *,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
    # Measured on v5e: 1 page/step wins — Mosaic lowers the in-kernel concat
    # to VMEM copies that cost more than the per-iteration overhead saved.
    # The knob stays for future Mosaic versions / other topologies.
    pages_per_step: int = 1,
    logit_cap: float = 0.0,
) -> jnp.ndarray:
    """Returns [B, C, n_heads, head_dim]; same contract as the XLA oracle
    (ops/attention.py::_paged_attention_xla)."""
    from dynamo_tpu.ops.kv_quant import is_quantized_pool

    quantized = is_quantized_pool(k_cache)
    B, C, n_heads, head_dim = q.shape
    cq = chunk_query_block(C, n_heads, head_dim, q.dtype.itemsize)
    if cq < C:
        # Blocks of query positions as rows of their own: a block is a chunk
        # that starts ``j * cq`` later over the same table (the chunk's K/V
        # is in the cache already, and the mask follows positions), so the
        # kernel serves it as it is and its VMEM holds one block's rows.
        nq = C // cq
        off = jnp.arange(nq, dtype=jnp.int32) * cq
        part = lambda a: jnp.repeat(a.astype(jnp.int32), nq, axis=0)
        out = _paged_attention_kernel_impl(
            q.reshape(B * nq, cq, n_heads, head_dim), k_cache, v_cache,
            part(block_tables), part(start_pos) + jnp.tile(off, B),
            jnp.clip(part(chunk_lens) - jnp.tile(off, B), 0, cq), window,
            sm_scale=sm_scale, interpret=interpret, pages_per_step=pages_per_step,
            logit_cap=logit_cap,
        )
        return out.reshape(B, C, n_heads, head_dim)
    k_values = k_cache["q8"] if quantized else k_cache
    num_blocks, block_size, n_kv_heads, page_dim = k_values.shape
    P = block_tables.shape[1]
    G = n_heads // n_kv_heads
    scale = sm_scale if sm_scale is not None else head_dim**-0.5
    S = max(min(pages_per_step, P), 1)
    win = jnp.asarray(window, jnp.int32).reshape(1)

    # Pad the table width to a multiple of S; padded entries point at page 0
    # whose keys land beyond every sequence's causal limit (masked).
    P_pad = ((P + S - 1) // S) * S
    if P_pad != P:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, P_pad - P)))

    # [B, C, H, D] -> [B, KH, C*G, D]: per-head row blocks, (c, g) c-major.
    # The transpose runs in XLA outside the kernel (fused, cheap) and lets
    # the kernel body index one head with zero in-kernel shape casts (Mosaic
    # rejects (C, G, D) -> (C*G, D) vector reshapes for C > 1).
    q5 = q.reshape(B, C, n_kv_heads, G, head_dim).transpose(0, 2, 1, 3, 4)
    q5 = q5.reshape(B, n_kv_heads, C * G, head_dim)

    def q_map(b, p, bt, sp, cl, w):
        return (b, 0, 0, 0)

    def kv_map_for(s):
        def kv_map(b, p, bt, sp, cl, w):
            return (bt[b, p * S + s], 0, 0, 0)

        return kv_map

    def s_map_for(s):
        def s_map(b, p, bt, sp, cl, w):
            return (bt[b, p * S + s], 0, 0)

        return s_map

    kv_spec = lambda s: pl.BlockSpec(  # noqa: E731
        (1, block_size, n_kv_heads, page_dim), kv_map_for(s)
    )
    in_specs = [pl.BlockSpec((1, n_kv_heads, C * G, head_dim), q_map)]
    kv_args = []
    for s in range(S):
        if quantized:
            sc_spec = pl.BlockSpec((1, n_kv_heads, block_size), s_map_for(s))
            in_specs.extend([kv_spec(s), sc_spec, kv_spec(s), sc_spec])
            kv_args.extend(
                [k_cache["q8"], k_cache["s"], v_cache["q8"], v_cache["s"]]
            )
        else:
            in_specs.extend([kv_spec(s), kv_spec(s)])
            kv_args.extend([k_cache, v_cache])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, P_pad // S),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv_heads, C * G, head_dim), q_map),
        scratch_shapes=[
            pltpu.VMEM((n_kv_heads, C * G, 1), jnp.float32),
            pltpu.VMEM((n_kv_heads, C * G, 1), jnp.float32),
            pltpu.VMEM((n_kv_heads, C * G, head_dim), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _kernel, sm_scale=scale, block_size=block_size, n_groups=G,
        pages_per_step=S, logit_cap=logit_cap, quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (B, n_kv_heads, C * G, head_dim), q.dtype
        ),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        start_pos.astype(jnp.int32),
        chunk_lens.astype(jnp.int32),
        win,
        q5,
        *kv_args,
    )
    out = out.reshape(B, n_kv_heads, C, G, head_dim).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, C, n_heads, head_dim)


# Jitted + watched program objects (DYN001): decorator jits are invisible
# to /debug/compiles; wrapping the jitted impls here gives the pallas
# attention plane compile telemetry and a storm budget keyed on the pow2
# table-width buckets the runner dispatches.
from dynamo_tpu.runtime.device_observe import watched_jit  # noqa: E402

paged_attention_decode_kernel = watched_jit(
    "pallas.paged_attention_decode",
    functools.partial(
        jax.jit,
        static_argnames=("sm_scale", "interpret", "logit_cap"),
    )(_paged_attention_decode_kernel_impl),
)

paged_attention_kernel = watched_jit(
    "pallas.paged_attention",
    functools.partial(
        jax.jit,
        static_argnames=("sm_scale", "interpret", "pages_per_step", "logit_cap"),
    )(_paged_attention_kernel_impl),
)
