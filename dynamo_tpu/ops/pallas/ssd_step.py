"""Pallas TPU one-token recurrence of a decode burst over the rows that
decode: the state of a slot that does not decode is neither read nor
written.

``ops/mamba2.ssd_step`` updates ``state [slots, H, P, N]`` (float32, 2.1 MB a
row a layer at both served shapes) for EVERY slot: a dead slot is given
``dt = 0``, so its state is multiplied by 1, added 0 and written back, and a
step streams the whole array whatever decodes (1.07 GB a step at 64 slots x 4
Mamba-2 layers where 14 live rows need 0.24). This kernel's grid walks the
list of live slots (PR 25's pattern: the grid is the live work list, here
over rows of recurrent state) and updates them in place:

  - Scalar prefetch: ``step_row`` [slots + 1], the live slots first, every
    entry at or past ``total`` repeating the last of them, and ``total``
    (``live_row_list``: the invariant of ``live_pages.live_work_list``, never
    a list of one entry), derived ONCE a burst from ``active`` and shared by
    every recurrent layer of every step; and ``da = exp(dt * A)`` [slots * H],
    the one scalar a (row, head) multiplies its state by.
  - Grid ``max(total, 1) x (H / ht)`` steps, a traced length: one step is
    ``ht`` heads of one live row. The state is a ``[1, ht, P, N]`` block
    indexed by ``step_row[j]``, aliased in and out: what the grid does not
    visit keeps its bytes. ``dt * x`` rides the same index as ``[1, 1, P, ht]``
    (P on the sublanes, as the state has it; the transpose of a [slots, H, P]
    array is XLA's), ``B`` and ``C`` as ``[1, G, N]``: grouped (Mamba-2: head i
    reads group i // R) or per head (lightning attention: G = H) by shape.
  - Same arithmetic as ``m2.ssd_step``, all float32 on the VPU, nothing on the
    MXU: ``new = state * da + (dt * x) outer B``; ``y = sum_n new * C``. ``y``
    leaves as ``[1, 1, P, ht]`` (a head's column by a lane select; the heads in a
    loop over groups of ``HEAD_UNROLL``, unrolled inside) and the caller
    gets ``[slots, H, P]`` with the rows the grid did not visit ZERO, not
    whatever the buffer held.
  - With no live row the one grid step copies its block through (the
    pipeline writes an output block back whether the body wrote it or not).

``ssd_step_reason`` says why a shape keeps ``m2.ssd_step`` (the CPU path and
the reference of the tests): a state that is not float32, ``N`` no multiple
of the 128 lanes, ``P`` no multiple of the 8 sublanes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.live_pages import live_work_list

_F32 = jnp.float32
LANES, SUBLANES = 128, 8
# One block of state: ht heads of a row. In and out, each twice buffered by
# the pipeline, are four of them in VMEM (8 MiB at a whole served row).
BLOCK_BYTES_MAX = 2 << 20
VMEM_LIMIT_BYTES = 32 << 20
HEAD_UNROLL = 8
NO_KERNELS = "no Pallas kernels here (use_kernel is false)"


class LiveRows(NamedTuple):
    """The slots a decode burst updates (``live_row_list``)."""

    total: jnp.ndarray  # [1] int32
    step_row: jnp.ndarray  # [slots + 1] int32
    mask: jnp.ndarray  # [slots] bool


def live_row_list(active: jnp.ndarray) -> LiveRows:
    """From ``active`` [slots] (non-zero: the row decodes): the live slots in
    order, then every entry at or past ``total`` repeating the last of them
    (slot 0 when none is live). ``live_work_list`` over rows of one page
    under a table of one: its invariant (``slots + 1`` entries, so never a
    list of one; the entry at ``total``, which the pipeline's index maps may
    read one step ahead, names a block the grid already holds; masked sums,
    no gather)."""
    live = active > 0
    pages = live.astype(jnp.int32)
    total, step_row, _ = live_work_list(pages, jnp.zeros_like(pages), 1, 1)
    return LiveRows(total.reshape(1), step_row, live)


def head_tile(H: int, P: int, N: int) -> int:
    """Heads of a row a grid step takes: the most that divide H and keep the
    block within ``BLOCK_BYTES_MAX`` (a whole row at both served shapes;
    ops/pallas/chip_check.py ``ssd_step``, my chip run, PR 47, 14 live rows
    of 64: 98 us a whole row a step, 101 / 113 / 137 at 32 / 16 / 8 heads)."""
    fits = [n for n in range(1, H + 1) if H % n == 0 and n * P * N * 4 <= BLOCK_BYTES_MAX]
    return max(fits) if fits else 1


def ssd_step_reason(use_kernel: bool, state_shape, state_dtype) -> Optional[str]:
    """None where a decode step's recurrence runs through the kernel;
    otherwise why it keeps ``m2.ssd_step`` over every slot. From the caller's
    ``use_kernel`` and the state's shape and dtype alone."""
    if not use_kernel:
        return NO_KERNELS
    _, _, P, N = state_shape
    if jnp.dtype(state_dtype) != jnp.dtype(_F32):
        return f"the state is {jnp.dtype(state_dtype).name}, not float32"
    if N % LANES:
        return f"a state row of {N} does not fill the {LANES} lanes"
    if P % SUBLANES:
        return f"{P} state rows a head are no multiple of {SUBLANES} sublanes"
    return None


def _kernel(total_ref, row_ref, da_ref, dtx_ref, b_ref, c_ref, s_ref, y_ref, o_ref,
            *, n_heads: int, rep: int):
    _, ht, P, _ = s_ref.shape
    nh = n_heads // ht
    # A loop over groups of HEAD_UNROLL heads, unrolled inside: a head's
    # column of dt * x by a masked sum over the lanes. Measured (a scratch
    # sweep, my chip run, PR 47, 14 live rows of 64 x 64 x 128, us a call in
    # a 24-call loop, its dispatch included): this form 132 where a copy of
    # the same blocks takes 130; every head unrolled with the column a
    # static lane slice 149 (the slice's broadcast and the read-out's sum
    # together pass the DMA), one head a loop step 208.
    unroll = HEAD_UNROLL if ht % HEAD_UNROLL == 0 else ht
    t = pl.program_id(0)
    j = t // nh
    h0 = (t % nh) * ht

    @pl.when(j >= total_ref[0])
    def _keep():  # no live row: the block goes back as it came
        o_ref[...] = s_ref[...]

    @pl.when(j < total_ref[0])
    def _update():
        base = row_ref[j] * n_heads + h0
        dtx = dtx_ref[0, 0]  # [P, ht]: a head's dt * x is a column
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, ht), 1)

        def heads(k, y):
            for u in range(unroll):
                i = k * unroll + u
                g = pl.ds((h0 + i) // rep, 1)  # head i of the tile reads its group
                col = jnp.sum(jnp.where(lane == i, dtx, 0.0), axis=1, keepdims=True)
                new = s_ref[0, i] * da_ref[base + i] + col * b_ref[0, g, :]
                o_ref[0, i] = new
                out = jnp.sum(new * c_ref[0, g, :], axis=1, keepdims=True)
                y = jnp.where(lane == i, out, y)
            return y

        y_ref[0, 0] = jax.lax.fori_loop(0, ht // unroll, heads, jnp.zeros((P, ht), _F32))


def _ssd_step_live_impl(
    x: jnp.ndarray,  # [B, H, P]
    dt: jnp.ndarray,  # [B, H] after softplus
    A: jnp.ndarray,  # [H]
    Bm: jnp.ndarray,  # [B, G, N]
    Cm: jnp.ndarray,  # [B, G, N]
    state: jnp.ndarray,  # [B, H, P, N] float32
    total: jnp.ndarray,  # [1] int32 (live_row_list)
    step_row: jnp.ndarray,  # [B + 1] int32
    mask: jnp.ndarray,  # [B] bool
    *,
    heads_a_step: Optional[int] = None,  # chip_check's sweep; served: head_tile
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``m2.ssd_step`` over the rows of ``step_row[:total]``: (y [B, H, P]
    float32 without the D skip, zero on every other row; the state, those
    rows updated in place and no other touched)."""
    Bsz, H, P, N = state.shape
    G = Bm.shape[1]
    ht = heads_a_step or head_tile(H, P, N)
    nh = H // ht
    dtf = dt.astype(_F32)
    da = jnp.exp(dtf * A.astype(_F32)).reshape(Bsz * H)
    # [B, nh, P, ht]: P on the sublanes, as the state has it
    dtx = (dtf[..., None] * x.astype(_F32)).reshape(Bsz, nh, ht, P).transpose(0, 1, 3, 2)

    def row(*block):  # one live row's
        return pl.BlockSpec(
            (1,) + block, lambda t, total, rows, da: (rows[t // nh],) + (0,) * len(block))

    def tile(*block):  # one head tile of one live row
        return pl.BlockSpec(
            (1,) + block,
            lambda t, total, rows, da: (rows[t // nh], t % nh) + (0,) * (len(block) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(jnp.maximum(total[0], 1) * nh,),
        in_specs=[tile(1, P, ht), row(G, N), row(G, N), tile(ht, P, N)],
        out_specs=[tile(1, P, ht), tile(ht, P, N)],
    )
    y, new = pl.pallas_call(
        functools.partial(_kernel, n_heads=H, rep=H // G),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, nh, P, ht), _F32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands count the scalar prefetches: the state is the seventh
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="ssd_step_live",
    )(total, step_row, da, dtx, Bm.astype(_F32), Cm.astype(_F32), state)
    # Rows the grid did not visit hold whatever the buffer did: zero them.
    y = jnp.where(mask[:, None, None, None], y, 0.0)
    return y.transpose(0, 1, 3, 2).reshape(Bsz, H, P), new


from dynamo_tpu.runtime.device_observe import watched_jit  # noqa: E402

ssd_step_live = watched_jit(
    "pallas.ssd_step_live",
    functools.partial(
        jax.jit, static_argnames=("heads_a_step", "interpret"),
        donate_argnames=("state",),
    )(_ssd_step_live_impl),
)
