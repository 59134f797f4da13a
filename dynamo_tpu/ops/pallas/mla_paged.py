"""Pallas TPU kernel for absorbed latent attention (MLA) over a paged latent
pool: ``mla_paged_decode`` in a device trace.

A latent-attention layer caches one row a token: the normed latent ``c_kv``
[R] beside the rotary key ``k_r`` that every head shares, ``W`` lanes in all
(the pool holds the row at a whole number of 128-lane tiles, zeros past
R + rope). With the key's up-projection folded into the query and the
value's left for after the sum (``ops/attention.mla_*``), attention is H
query heads over ONE key/value head whose key is the whole cache row and
whose value is its first R lanes:

    scores = q~ . row            (q~ = [q_n W_kb | q_r], W lanes)
    out    = softmax(scores) . row[:R]

so a page is DMA'd ONCE and its tile serves the scores (all W lanes) and the
values (the first R lanes of the same VMEM tile). On a v5e that puts the
kernel at the ridge: per cached token 2 x H x (W + R) FLOP against W x 2
bytes.

  - The grid is the live work list of ``live_pages.live_work_list``: step t
    visits ``group_pages`` consecutive pages of one VIRTUAL row. A virtual
    row is (row b, query block j): ``QB`` consecutive query positions of a
    chunk, all H heads of each, M = QB x H rows of one matmul. Decode is
    C = QB = 1; a question chunk over a cached document is C / QB virtual
    rows a row, each streaming the pages its last query may see, so scores
    never materialise past [M, group tokens] (1,024 queries x 128 heads x
    16 k keys would be 8.6 GB in float32). The list is one entry longer
    than the grid's most steps, never one entry (PR 25).
  - bf16 operands on the MXU, float32 scores, float32 online-softmax state
    (running max, normaliser, accumulator in VMEM scratch for the virtual
    row's consecutive steps); the probabilities are rounded to the pool's
    dtype for the value product, as the pool's rows are.
  - Key t is visible to query c of the chunk iff t <= start + c (the
    chunk's own rows are already in the pool) and t lies on a live page.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.live_pages import live_page_bounds, live_work_list

NEG_INF = -1e30
# Tokens a grid step visits (pages x block size): 1,024 rows of 640 bf16
# lanes are 1.3 MB a buffer. Fewer for a chunk's virtual rows, whose
# [M, tokens] float32 score and probability tiles are the larger tenants.
DECODE_GROUP_TOKENS = 1024
CHUNK_GROUP_TOKENS = 512
QUERY_BLOCK = 8  # query positions of a chunk per virtual row: M = 8 x H
VMEM_LIMIT_BYTES = 64 << 20


class LatentPlan(NamedTuple):
    """The kernel's grid for one forward step (``latent_plan``): it depends
    on positions, lengths and the table only, so the layers share it."""

    tables: jnp.ndarray  # [B, P] int32; a row with nothing live is zeroed
    start: jnp.ndarray  # [Bv] int32 — first query position of a virtual row
    pcount: jnp.ndarray  # [Bv] int32 — its live pages; 0 = nothing to do
    step_row: jnp.ndarray  # [T] int32 (live_work_list over virtual rows)
    step_page: jnp.ndarray  # [T] int32
    total: jnp.ndarray  # [] int32 — the grid's length


def query_block(C: int, most: int = QUERY_BLOCK) -> int:
    """Query positions per virtual row (or per block of the XLA forms): the
    largest power of two up to ``most`` that divides the chunk."""
    qb = most
    while C % qb:
        qb //= 2
    return qb


def group_pages(C: int, block_size: int, table_width: int) -> int:
    tokens = DECODE_GROUP_TOKENS if C == 1 else CHUNK_GROUP_TOKENS
    return max(1, min(tokens // block_size, table_width))


def latent_plan(pool, block_tables, start_pos, chunk_lens, C: int) -> LatentPlan:
    block_size = pool.shape[1]
    B, P = block_tables.shape
    QB = query_block(C)
    n = C // QB
    off = jnp.arange(n, dtype=jnp.int32) * QB  # [n]
    start_v = (start_pos.astype(jnp.int32)[:, None] + off[None]).reshape(B * n)
    lens_v = jnp.clip(chunk_lens.astype(jnp.int32)[:, None] - off[None], 0, QB)
    pcount, poff = live_page_bounds(
        start_v, lens_v.reshape(B * n), QB, 0, block_size, P
    )
    total, step_row, step_page = live_work_list(
        pcount, poff, group_pages(C, block_size, P), P
    )
    tables = jnp.where(
        (chunk_lens > 0)[:, None], block_tables.astype(jnp.int32), 0
    )
    return LatentPlan(tables, start_v, pcount, step_row, step_page, total)


def _kernel(
    # scalar prefetch (SMEM)
    tables_ref,  # [B, P]
    start_ref,  # [Bv]
    pcount_ref,  # [Bv]
    step_row_ref,  # [T]
    step_page_ref,  # [T]
    # VMEM: q [1, M, W] of the step's virtual row, then S pages [1, bs, W]
    q_ref,
    *refs,  # pages..., o_ref, m, l, acc
    sm_scale: float,
    n_heads: int,
    group_pages: int,
    v_width: int,
):
    S = group_pages
    pages = refs[:S]
    o_ref = refs[S]
    m_ref, l_ref, acc_ref = refs[S + 1 :]
    M = q_ref.shape[1]
    bs = pages[0].shape[1]
    QB = M // n_heads

    t = pl.program_id(0)
    v = step_row_ref[t]
    pstart = step_page_ref[t]
    start = start_ref[v]
    pcount = pcount_ref[v]

    @pl.when(pstart == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = 1 if QB == 1 else M  # decode: one mask row, broadcast
    if QB == 1:
        limit = start
    else:  # rows are (c, h), c-major
        limit = start + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // n_heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)

    q = q_ref[0]  # [M, W], the pool's dtype
    scores = []
    for s in range(S):
        s_mat = jax.lax.dot_general(
            q, pages[s][0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [M, bs]
        t_idx = (pstart + s) * bs + lane
        vis = (t_idx <= limit) & (t_idx < pcount * bs)
        scores.append(jnp.where(vis, s_mat, NEG_INF))
    m_prev = m_ref[...]
    m_new = m_prev
    for s_mat in scores:
        m_new = jnp.maximum(m_new, jnp.max(s_mat, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[...] * alpha
    acc = acc_ref[...] * alpha
    for s, s_mat in enumerate(scores):
        probs = jnp.exp(s_mat - m_new)
        l_new = l_new + jnp.sum(probs, axis=-1, keepdims=True)
        page = pages[s][0]
        acc = acc + jax.lax.dot_general(
            probs.astype(page.dtype), page[:, :v_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(pstart + S >= pcount)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _mla_paged_decode_impl(
    q: jnp.ndarray,  # [B, C, H, W] absorbed queries, zero past the row's lanes
    pool: jnp.ndarray,  # [num_blocks, block_size, W]
    block_tables: jnp.ndarray,  # [B, P] int32
    start_pos: jnp.ndarray,  # [B] int32
    chunk_lens: jnp.ndarray,  # [B] int32; 0 = nothing of this row is live
    plan: Optional[LatentPlan] = None,
    *,
    v_width: int,
    sm_scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """[B, C, H, v_width]: per head, the softmax-weighted sum of the first
    ``v_width`` lanes of the rows each query may see. Same contract as
    ``ops/attention._mla_paged_xla`` for positions below ``chunk_lens``;
    a row (or query block) with nothing live is never visited and returns
    zeros. ``plan`` is ``latent_plan`` of the same arguments."""
    B, C, H, W = q.shape
    _, block_size, Wp = pool.shape
    assert Wp == W and W % 128 == 0, (W, Wp)
    QB = query_block(C)
    n = C // QB
    M = QB * H
    P = block_tables.shape[1]
    S = group_pages(C, block_size, P)
    if plan is None:
        plan = latent_plan(pool, block_tables, start_pos, chunk_lens, C)
    q3 = q.astype(pool.dtype).reshape(B * n, M, W)

    def q_map(t, bt, st, pc, srow, spage):
        return (srow[t], 0, 0)

    def page_map(s):
        def index_map(t, bt, st, pc, srow, spage):
            v = srow[t]
            page = jnp.maximum(jnp.minimum(spage[t] + s, pc[v] - 1), 0)
            return (bt[v // n, page], 0, 0)

        return index_map

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(jnp.maximum(plan.total, 1),),
        in_specs=[pl.BlockSpec((1, M, W), q_map)]
        + [pl.BlockSpec((1, block_size, W), page_map(s)) for s in range(S)],
        out_specs=pl.BlockSpec((1, M, v_width), q_map),
        scratch_shapes=[
            pltpu.VMEM((M, 1), jnp.float32),
            pltpu.VMEM((M, 1), jnp.float32),
            pltpu.VMEM((M, v_width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, sm_scale=sm_scale, n_heads=H, group_pages=S, v_width=v_width
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * n, M, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="mla_paged_decode",
    )(
        plan.tables, plan.start, plan.pcount, plan.step_row, plan.step_page,
        q3, *([pool] * S),
    )
    out = jnp.where((plan.pcount > 0)[:, None, None], out, 0)
    return out.reshape(B, C, H, v_width)


from dynamo_tpu.runtime.device_observe import watched_jit  # noqa: E402

mla_paged_decode = watched_jit(
    "pallas.mla_paged_decode",
    functools.partial(
        jax.jit, static_argnames=("v_width", "sm_scale", "interpret")
    )(_mla_paged_decode_impl),
)
