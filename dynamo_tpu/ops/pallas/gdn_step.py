"""Pallas TPU one-token gated delta rule of a decode burst over the rows that
decode: the state of a slot that does not decode is neither read nor written.

``ops/gated_delta.gdn_step`` updates ``state [slots, H, Dk, Dv]`` (float32,
2.1 MB a row a layer at the served shape) for EVERY slot: a dead slot is given
g = 0 and beta = 0, so its state is multiplied by 1, added 0 and written back,
and a step streams the whole array whatever decodes (0.81 GB a step at 64
slots x 3 layers, read and written, where ten live rows need 0.13). This
kernel's grid walks ops/pallas/ssd_step's list of live slots
(``live_row_list``: one list a burst, shared by every recurrent layer of
every kind) and updates them in place, as ``ssd_step_live`` does:

  - Scalar prefetch: ``total`` and ``step_row`` of the list, and the two
    scalars a (row, head) needs: ``decay = exp(g)`` and ``beta``, each
    [slots * H].
  - Grid ``max(total, 1) x (H / ht)`` steps, a traced length: one step is
    ``ht`` heads of one live row. The state is a ``[1, ht, Dk, Dv]`` block
    indexed by ``step_row[j]``, aliased in and out: what the grid does not
    visit keeps its bytes.
  - **The value axis lies on the lanes, the key axis on the sublanes.** Both
    reductions of the rule, ``k S`` and ``q S``, run over the KEY axis: with
    keys on the sublanes each is a column of k (or q) broadcast over the
    lanes, a multiply, and a sum down the sublanes, which is vector adds of
    whole registers and one 8-sublane fold a lane tile; their results, ``u``
    and ``o``, come out as lane-dense rows [1, Dv], which is what the outer
    product ``k^T u`` (a column times a row) and the output block want. Keys
    on the lanes would make both reductions cross-lane (the XLU, once a state
    row) and leave ``u`` and ``o`` as columns to be transposed. So ``v`` and
    ``o`` ride as ``[1, ht, Dv]`` (natural), and ``k`` and ``q`` as
    ``[1, 1, Dk, ht]`` (Dk on the sublanes as the state has it; the transpose
    of a [slots, H, Dk] array is XLA's), a head's column by a lane select.
  - Same arithmetic as ``gdn_step``, all float32 on the VPU, nothing on the
    MXU: ``S <- decay S; u = beta (v - k S); S <- S + k^T u; o = q S``.
    The caller gets ``o`` with the rows the grid did not visit ZERO.
  - With no live row the one grid step copies its block through (the
    pipeline writes an output block back whether the body wrote it or not).

``gdn_step_reason`` says why a shape keeps ``gdn_step`` over every slot (the
CPU path and the reference of the tests): a state that is not float32, ``Dv``
no multiple of the 128 lanes, ``Dk`` no multiple of the 8 sublanes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.ssd_step import (
    HEAD_UNROLL, LANES, NO_KERNELS, SUBLANES, VMEM_LIMIT_BYTES, head_tile,
)

_F32 = jnp.float32


def gdn_step_reason(use_kernel: bool, state_shape, state_dtype) -> Optional[str]:
    """None where a decode step's delta rule runs through the kernel;
    otherwise why it keeps ``gated_delta.gdn_step`` over every slot. From the
    caller's ``use_kernel`` and the state's shape and dtype alone."""
    if not use_kernel:
        return NO_KERNELS
    _, _, Dk, Dv = state_shape
    if jnp.dtype(state_dtype) != jnp.dtype(_F32):
        return f"the state is {jnp.dtype(state_dtype).name}, not float32"
    if Dv % LANES:
        return f"a state row of {Dv} values does not fill the {LANES} lanes"
    if Dk % SUBLANES:
        return f"{Dk} keys a head are no multiple of {SUBLANES} sublanes"
    return None


def _kernel(total_ref, row_ref, decay_ref, beta_ref, q_ref, k_ref, v_ref, s_ref,
            o_ref, new_ref, *, n_heads: int):
    _, ht, Dk, Dv = s_ref.shape
    nh = n_heads // ht
    unroll = HEAD_UNROLL if ht % HEAD_UNROLL == 0 else ht
    t = pl.program_id(0)
    j = t // nh
    h0 = (t % nh) * ht

    @pl.when(j >= total_ref[0])
    def _keep():  # no live row: the block goes back as it came
        new_ref[...] = s_ref[...]

    @pl.when(j < total_ref[0])
    def _update():
        base = row_ref[j] * n_heads + h0
        qT, kT = q_ref[0, 0], k_ref[0, 0]  # [Dk, ht]: a head's q, k is a column
        v = v_ref[0]  # [ht, Dv]: a head's v is a row
        lane = jax.lax.broadcasted_iota(jnp.int32, (Dk, ht), 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (ht, Dv), 0)

        def heads(n, o):
            for m in range(unroll):
                i = n * unroll + m
                k_col = jnp.sum(jnp.where(lane == i, kT, 0.0), axis=1, keepdims=True)
                q_col = jnp.sum(jnp.where(lane == i, qT, 0.0), axis=1, keepdims=True)
                v_row = jnp.sum(jnp.where(sub == i, v, 0.0), axis=0, keepdims=True)
                S = s_ref[0, i] * decay_ref[base + i]  # [Dk, Dv]
                u = beta_ref[base + i] * (v_row - jnp.sum(k_col * S, axis=0, keepdims=True))
                S = S + k_col * u
                new_ref[0, i] = S
                o = jnp.where(sub == i, jnp.sum(q_col * S, axis=0, keepdims=True), o)
            return o

        o_ref[0] = jax.lax.fori_loop(0, ht // unroll, heads, jnp.zeros((ht, Dv), _F32))


def _gdn_step_live_impl(
    q: jnp.ndarray,  # [B, H, Dk] (gated_delta.prepare_qk)
    k: jnp.ndarray,  # [B, H, Dk]
    v: jnp.ndarray,  # [B, H, Dv]
    g: jnp.ndarray,  # [B, H] log decay
    beta: jnp.ndarray,  # [B, H]
    state: jnp.ndarray,  # [B, H, Dk, Dv] float32
    total: jnp.ndarray,  # [1] int32 (ssd_step.live_row_list)
    step_row: jnp.ndarray,  # [B + 1] int32
    mask: jnp.ndarray,  # [B] bool
    *,
    heads_a_step: Optional[int] = None,  # chip_check's sweep; served: head_tile
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``gated_delta.gdn_step`` over the rows of ``step_row[:total]``: (o [B,
    H, Dv] float32, zero on every other row; the state, those rows updated in
    place and no other touched)."""
    Bsz, H, Dk, Dv = state.shape
    ht = heads_a_step or head_tile(H, Dk, Dv)
    nh = H // ht
    decay = jnp.exp(g.astype(_F32)).reshape(Bsz * H)

    def columns(x):  # [B, H, Dk] -> [B, nh, Dk, ht]: Dk on the sublanes
        return x.astype(_F32).reshape(Bsz, nh, ht, Dk).transpose(0, 1, 3, 2)

    def tile(*block):  # one head tile of one live row
        return pl.BlockSpec(
            (1,) + block,
            lambda t, total, rows, decay, beta: (rows[t // nh], t % nh) + (0,) * (len(block) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(total[0], 1) * nh,),
        in_specs=[tile(1, Dk, ht), tile(1, Dk, ht), tile(ht, Dv), tile(ht, Dk, Dv)],
        out_specs=[tile(ht, Dv), tile(ht, Dk, Dv)],
    )
    o, new = pl.pallas_call(
        functools.partial(_kernel, n_heads=H),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, Dv), _F32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands count the scalar prefetches: the state is the eighth
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="gdn_step_live",
    )(total, step_row, decay, beta.astype(_F32).reshape(Bsz * H),
      columns(q), columns(k), v.astype(_F32), state)
    # Rows the grid did not visit hold whatever the buffer did: zero them.
    return jnp.where(mask[:, None, None], o, 0.0), new


from dynamo_tpu.runtime.device_observe import watched_jit  # noqa: E402

gdn_step_live = watched_jit(
    "pallas.gdn_step_live",
    functools.partial(
        jax.jit, static_argnames=("heads_a_step", "interpret"),
        donate_argnames=("state",),
    )(_gdn_step_live_impl),
)
