"""Block-sparse attention chosen by an indexer over cached compressed keys
(``models/config.SparseIndex``: InfLLM-v2, the MiniCPM4 family), over the
paged K/V pool.

Three pieces, all on the device inside the step:

* **The indexer's cache.** A sparse layer keeps a third array beside K and V,
  ``[blocks, block / stride, KH, pool_head_dim(D)]`` in the pool's dtype,
  UNDER THE K/V POOL'S OWN BLOCK IDS. Compressed key ``e`` is the mean of the
  ``kernel`` = 2 x ``stride`` cached keys that END at token ``e * stride +
  stride - 1`` (e >= 1), and is filed with the page that holds that LAST
  token, at slot ``(token % block) // stride``: a page's compressed keys then
  depend on that page and its chain parent only, never on what follows a
  shared prefix (filed with its window's first token, a key whose window
  runs past a shared page would differ between two requests that share the
  page and diverge after it). ``write_compressed_keys`` completes the
  windows a chunk (or a decode token) ends, from the pool's own K rows.
* **Selection.** ``select_blocks``: for every query an exact softmax over
  the compressed keys whose window ends at or before it, summed over the
  query heads of a K/V head, pooled to blocks by the largest score of a
  window that meets the block; the leading blocks and the blocks of the last
  ``window`` tokens are forced, the best-scored others fill up to ``topk``
  (``lax.top_k``). A query whose sequence (itself included) is shorter than
  ``dense_len`` attends densely.
* **Attention over the selected pages.** On the chip
  (``use_kernel``) the sparse queries become rows of the live-span decode
  kernel whose block table, PER K/V HEAD, is the selected pages in ascending
  order (``paged_attention.selected_plan``): every page before the last is
  wholly visible and the last is the query's own, so the row's position is
  rebased to ``(pages - 1) * block + t % block`` and the kernel's causal mask
  is the right one (these layers rotate nothing: positions enter through the
  mask only). A chunk's queries are such rows each: gathering 64 pages a
  query moves ~2 GB a layer for 256 queries (2.6 ms at the v5e's peak), where
  streaming every live page once under a per-(query, head, block) mask would
  read 64 MB but needs a mask operand the chunk kernel has no lane layout
  for; the gather reuses the one kernel decode needs anyway. Dense queries
  (and every query where the table is narrower than ``dense_len``) go
  through ``ops/attention.paged_attention`` over the table's first
  ``dense_len`` tokens. Elsewhere (the CPU) one XLA form serves both: gather
  the row's pages, mask from positions and the selected set (masked-out
  blocks are out of the softmax).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.attention import NEG_INF, paged_attention, pool_head_dim
from dynamo_tpu.ops.pallas.paged_attention import (
    paged_attention_decode_kernel,
    selected_plan,
)

_F32 = jnp.float32
# Query positions scored against the compressed keys at once: bounds the
# float32 scores at [B, block, heads, compressed keys].
_SELECT_QUERY_BLOCK = 64
# Rows (queries) a call of the decode kernel takes over selected pages: its
# per-head tables live in scalar memory (256 x 2 x 64 ids = 128 KB).
_SELECTED_ROWS_PER_CALL = 256
# Query positions of a chunk the dense chunk kernel takes as one row.
_DENSE_QUERY_BLOCK = 128
_BIG = 1e30


def compressed_pool(num_blocks: int, sparse: Any, n_kv_heads: int, head_dim: int, dtype):
    """The indexer's cache of one sparse layer, zeroed."""
    return jnp.zeros(
        (num_blocks, sparse.keys_per_block, n_kv_heads, pool_head_dim(head_dim)), dtype
    )


def write_compressed_keys(
    kc_pool: jnp.ndarray,  # [blocks, block / stride, KH, Dp]
    k_pool: jnp.ndarray,  # [blocks, block, KH, Dp], this chunk's keys written
    block_tables: jnp.ndarray,  # [B, P]
    start_pos: jnp.ndarray,  # [B]
    chunk_lens: jnp.ndarray,  # [B]
    C: int,
    sparse: Any,
) -> jnp.ndarray:
    """Complete every compressed key whose window ends inside the chunk
    ``[start, start + len)``: the float32 mean of the pool's last ``kernel``
    key rows up to that token (they may lie in the chunk before, and on the
    page before), in the pool's dtype, filed with the window's last token."""
    num_blocks, block = k_pool.shape[:2]
    st, kn = sparse.stride, sparse.kernel
    P = block_tables.shape[1]
    n = -(-C // st)
    start = start_pos.astype(jnp.int32)
    e = start[:, None] // st + jnp.arange(n, dtype=jnp.int32)[None]  # [B, n]
    end = e * st + st - 1  # the window's last token
    valid = (e >= 1) & (end < (start + chunk_lens)[:, None]) & (end < P * block)
    pos = jnp.maximum(end[..., None] - (kn - 1) + jnp.arange(kn, dtype=jnp.int32), 0)
    page = jnp.take_along_axis(
        block_tables, jnp.clip(pos // block, 0, P - 1).reshape(pos.shape[0], -1), axis=1
    ).reshape(pos.shape)
    rows = k_pool[page, pos % block]  # [B, n, kernel, KH, Dp]
    mean = jnp.mean(rows.astype(_F32), axis=2).astype(kc_pool.dtype)
    dst = jnp.take_along_axis(block_tables, jnp.clip(end // block, 0, P - 1), axis=1)
    dst = jnp.where(valid, dst, num_blocks)  # out of range: dropped
    return kc_pool.at[dst, (end % block) // st].set(mean, mode="drop")


def select_blocks(
    q: jnp.ndarray,  # [B, C, H, D]
    kc_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, P]
    start_pos: jnp.ndarray,  # [B]
    sparse: Any,
    *,
    sm_scale: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(selected [B, C, KH, K] table slots in ascending order, the first
    ``count`` of them real; count [B, C]; is_sparse [B, C]: the query's
    sequence is at least ``dense_len`` long). K = min(topk, table width)."""
    B, C, H, D = q.shape
    P = block_tables.shape[1]
    r, st = sparse.keys_per_block, sparse.stride
    KH = kc_pool.shape[2]
    G = H // KH
    E = P * r
    kc = kc_pool[block_tables][..., :D].reshape(B, E, KH, D)
    K = min(sparse.topk, P)
    t_all = start_pos.astype(jnp.int32)[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    e_idx = jnp.arange(E, dtype=jnp.int32)
    m_idx = jnp.arange(P, dtype=jnp.int32)
    qb = C if C <= _SELECT_QUERY_BLOCK or C % _SELECT_QUERY_BLOCK else _SELECT_QUERY_BLOCK
    picked = []
    for r0 in range(0, C, qb):  # unrolled: a prefill program holds no ``while``
        t = t_all[:, r0 : r0 + qb]  # [B, qb]
        qg = q[:, r0 : r0 + qb].reshape(B, qb, KH, G, D)
        s = jnp.einsum("bqkgd,bekd->bqkge", qg, kc, preferred_element_type=_F32) * sm_scale
        ended = (e_idx[None, None] >= 1) & (e_idx[None, None] <= (t[..., None] + 1) // st - 1)
        s = jnp.where(ended[:, :, None, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1).sum(3)  # [B, qb, KH, E], 0 where not ended
        # Block m meets the windows e in [r m, r m + r]: its own page's and
        # the first of the next page's.
        own = a.reshape(B, qb, KH, P, r)
        nxt = jnp.concatenate([own[..., 1:, 0], jnp.zeros_like(own[..., :1, 0])], axis=-1)
        score = jnp.maximum(own.max(-1), nxt)  # [B, qb, KH, P]
        cur = t // sparse.block
        lo = jnp.maximum(t - sparse.window + 1, 0) // sparse.block
        m = m_idx[None, None]
        forced = (m < sparse.init_blocks) | (m >= lo[..., None])
        seen = m <= cur[..., None]
        key = jnp.where(forced[:, :, None], _BIG, score)
        key = jnp.where(seen[:, :, None], key, -_BIG)
        vals, idx = jax.lax.top_k(key, K)  # [B, qb, KH, K]
        real = vals > -_BIG / 2
        # Ascending by position, what is not real last.
        order = jnp.where(real, idx, P + jnp.arange(K, dtype=jnp.int32))
        picked.append(jnp.minimum(jnp.sort(order, axis=-1), P - 1))
    sel = jnp.concatenate(picked, axis=1)
    count = jnp.minimum(t_all // sparse.block + 1, K)
    return sel, count, (t_all + 1) >= sparse.dense_len


def _xla_form(q, k_pool, v_pool, block_tables, start_pos, sel, count, is_sparse,
              *, sm_scale):
    """Gather the rows' pages and mask from positions and the selected set."""
    B, C, H, D = q.shape
    block, KH = k_pool.shape[1], k_pool.shape[2]
    P = block_tables.shape[1]
    T, G = P * block, H // KH
    k = k_pool[block_tables][..., :D].reshape(B, T, KH, D).astype(_F32)
    v = v_pool[block_tables][..., :D].reshape(B, T, KH, D).astype(_F32)
    qg = q.reshape(B, C, KH, G, D).astype(_F32)
    s = jnp.einsum("bckgd,btkd->bckgt", qg, k) * sm_scale
    t_pos = jnp.arange(T, dtype=jnp.int32)
    t_q = start_pos.astype(jnp.int32)[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    causal = t_pos[None, None] <= t_q[..., None]  # [B, C, T]
    real = jnp.arange(sel.shape[-1], dtype=jnp.int32) < count[:, :, None, None]
    chosen = (
        (sel[..., None] == jnp.arange(P, dtype=jnp.int32)) & real[..., None]
    ).any(-2)  # [B, C, KH, P]
    chosen = jnp.repeat(chosen, block, axis=-1)  # per key
    seen = causal[:, :, None] & (chosen | ~is_sparse[:, :, None, None])
    s = jnp.where(seen[:, :, :, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bckgt,btkd->bckgd", p, v)
    return out.reshape(B, C, H, D).astype(q.dtype)


def _selected_rows_kernel(q, k_pool, v_pool, block_tables, start_pos, chunk_lens,
                          sel, count, is_sparse, *, sm_scale, interpret=False):
    """The sparse queries as rows of the live-span decode kernel over their
    selected pages (module docstring); a query that is not sparse, or past
    its row's ``chunk_lens``, is no grid step and reads zeros."""
    B, C, H, D = q.shape
    block = k_pool.shape[1]
    KH, K = sel.shape[2], sel.shape[3]
    t = start_pos.astype(jnp.int32)[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    live = is_sparse & (jnp.arange(C, dtype=jnp.int32)[None] < chunk_lens[:, None])
    ids = jnp.take_along_axis(
        block_tables, sel.reshape(B, C * KH * K), axis=1
    ).reshape(B * C, KH, K)
    pages = jnp.where(live, count, 0).reshape(B * C)
    vstart = ((count - 1) * block + t % block).reshape(B * C)
    qv = q.reshape(B * C, 1, H, D)
    n = B * C
    step = n if n <= _SELECTED_ROWS_PER_CALL or n % _SELECTED_ROWS_PER_CALL else (
        _SELECTED_ROWS_PER_CALL)
    out = []
    for r0 in range(0, n, step):  # unrolled, as above
        rows = slice(r0, r0 + step)
        plan = selected_plan(k_pool, ids[rows], pages[rows])
        out.append(paged_attention_decode_kernel(
            qv[rows], k_pool, v_pool, ids[rows, 0], vstart[rows], 0, None, plan,
            sm_scale=sm_scale, interpret=interpret,
        ))
    return jnp.concatenate(out, axis=0).reshape(B, C, H, D)


def _dense_rows_kernel(q, k_pool, v_pool, block_tables, start_pos, chunk_lens, *,
                       sm_scale):
    """``paged_attention`` on the chip, a long chunk in blocks of
    ``_DENSE_QUERY_BLOCK`` query positions as rows of their own (a block is a
    chunk that starts so much later over the same table: the chunk's K/V is
    in the cache already): the chunk kernel keeps a block's (query, head)
    rows in VMEM, and 256 positions of 32 heads of 128 lanes are 21 MiB of
    the 16 it may use."""
    B, C, H, D = q.shape
    cq = _DENSE_QUERY_BLOCK
    if C <= cq or C % cq:
        return paged_attention(q, k_pool, v_pool, block_tables, start_pos, chunk_lens,
                               sm_scale=sm_scale, use_kernel=True)
    nq = C // cq
    off = jnp.tile(jnp.arange(nq, dtype=jnp.int32) * cq, B)
    part = lambda a: jnp.repeat(a.astype(jnp.int32), nq, axis=0)
    out = paged_attention(
        q.reshape(B * nq, cq, H, D), k_pool, v_pool, part(block_tables),
        part(start_pos) + off, jnp.clip(part(chunk_lens) - off, 0, cq),
        sm_scale=sm_scale, use_kernel=True,
    )
    return out.reshape(B, C, H, D)


def sparse_paged_attention(
    q: jnp.ndarray,  # [B, C, H, D]
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    kc_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, P]
    start_pos: jnp.ndarray,
    chunk_lens: jnp.ndarray,
    sparse: Any,
    *,
    sm_scale: float,
    use_kernel: bool = False,
    want_selection: bool = False,
):
    """Attention of a chunk over the paged pool, every query over the blocks
    its indexer selects (densely under ``dense_len``); the chunk's own K, V
    and compressed keys are written already. Returns [B, C, H, D] (and,
    ``want_selection``, what ``select_blocks`` gave)."""
    C = q.shape[1]
    block = k_pool.shape[1]
    if block != sparse.block:
        raise ValueError(
            f"sparse attention selects blocks of {sparse.block} tokens and the "
            f"pool's page holds {block}: serve this model with --block-size "
            f"{sparse.block}"
        )
    P = block_tables.shape[1]
    if P * block < sparse.dense_len and not want_selection:
        # No query under this table reaches ``dense_len``: plain attention.
        if use_kernel:
            return _dense_rows_kernel(q, k_pool, v_pool, block_tables, start_pos,
                                      chunk_lens, sm_scale=sm_scale)
        return paged_attention(
            q, k_pool, v_pool, block_tables, start_pos, chunk_lens, sm_scale=sm_scale)
    with jax.named_scope("sparse_index"):
        sel, count, is_sparse = select_blocks(
            q, kc_pool, block_tables, start_pos, sparse, sm_scale=sm_scale)
    if not use_kernel:
        out = _xla_form(q, k_pool, v_pool, block_tables, start_pos, sel, count,
                        is_sparse, sm_scale=sm_scale)
    else:
        picked = _selected_rows_kernel(
            q, k_pool, v_pool, block_tables, start_pos, chunk_lens, sel, count,
            is_sparse, sm_scale=sm_scale)
        # Rows with a query under ``dense_len``: the table's first pages.
        Pd = min(P, -(-sparse.dense_len // block))
        some_dense = start_pos + 1 < sparse.dense_len
        dense = _dense_rows_kernel(
            q, k_pool, v_pool, block_tables[:, :Pd],
            jnp.where(some_dense, start_pos, 0),  # (a row with nothing to do stops at page 0)
            jnp.where(some_dense, chunk_lens, 0), sm_scale=sm_scale,
        )
        out = jnp.where(is_sparse[:, :, None, None], picked, dense)
    return (out, (sel, count, is_sparse)) if want_selection else out
