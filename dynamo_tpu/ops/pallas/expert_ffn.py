"""Pallas TPU expert FFN whose grid is the list of experts hit.

A decode step routes a few tokens (64 slots) to a few of the experts a chip
holds; the XLA dense form (ops/moe.py::_experts_dense) pushes every token
through EVERY held expert, so it streams all their matrices from HBM
whatever the routing. This kernel streams the matrices of the experts that
got a live token and no others: PR 25's pattern (the grid is the live work
list) applied to expert weights in place of KV pages.

  - Scalar prefetch: the ids of the experts hit first, every entry past
    their count repeating the last (``hit_list``: [Eh + 1], never a list
    of one entry, which halted the v5e core once:
    ops/pallas/live_pages.live_work_list), and the count. The grid is ONE
    axis of ``max(count, 1) x nf`` steps, a traced length: an expert
    nobody chose is no grid step and no DMA.
  - Both matrices are whole-array BlockSpec operands in the layout they
    are RESIDENT in, tiled over the expert width f in ``nf`` tiles of
    [tf, d]: each tile is one contiguous stretch of HBM, and the index
    maps take the expert from the list, so the pallas pipeline
    double-buffers the stream across expert boundaries. ``we_down`` is
    [Eh, f, d]. ``we_up`` is [Eh, d, f], and where f is no multiple of the
    128 lanes (1856 is 14.5 tiles) and d is, XLA holds it with d
    minor-most rather than pad f: its transpose to [Eh, f, d] is then a
    bitcast of the resident bytes, not a copy (a copy of the stack, 660 MB
    a layer-step, is what taking it as [Eh, d, f] cost; PR 33 met the same
    with the KV pool). ops/moe.hit_list_reason keeps every other shape on
    the XLA forms; tests/test_mosaic_compile.py pins that the served
    programs hold no such copy.
  - A step computes ``relu2(x @ up_tile^T)`` [T, tf] and adds ``comb[:, e]
    * (that @ down_tile)`` to the [T, d] float32 result, which stays in
    VMEM with all T tokens and the [T, Eh] combine matrix for the whole
    grid: the dense form's own products over fewer experts, no sort, no
    scatter, nothing dropped. Operands in the weights' dtype (bf16
    served), float32 accumulation, the activation in float32.

Activation: relu2, the only one a served kernel configuration has;
ops/moe.py keeps the XLA forms for every other.

On the chip (ops/pallas/chip_check.py, my chip run, PR 37): 53 us with one
expert hit and 26.5 us for each further one (753 GB/s of the 819 peak) at
64 tokens; with all 64 hit 1,724 us against the dense form's 1,751.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACTIVATIONS = ("relu2",)
# A step's two weight tiles (one of each matrix, [tile, d]), each
# double-buffered by the pipeline, have to fit VMEM beside the resident
# operands: 4 x 464 x 2688 x 2 B = 10 MB at the served widths.
F_TILE_BYTES_MAX = 5 << 19
VMEM_LIMIT_BYTES = 48 << 20


def f_tile(f: int, d: int, itemsize: int) -> int:
    """Rows of the expert width a grid step takes: the largest divisor of f
    that is a multiple of 16 sublanes (a bf16 tile) and whose [tile, d]
    block is within ``F_TILE_BYTES_MAX``; f itself where no such divisor
    exists (small test shapes: one tile)."""
    fits = [n for n in range(16, f + 1, 16)
            if f % n == 0 and n * d * itemsize <= F_TILE_BYTES_MAX]
    return max(fits) if fits else f


def hit_list(load: jnp.ndarray):
    """(ids [Eh + 1] int32, count [1] int32) from the tokens on each held
    expert: ids of the experts with load > 0 first, in order, and every
    entry at or past the count repeating the last of them (expert 0 when
    none is hit: its step runs over a zero combine column)."""
    n_held = load.shape[0]
    hit = load > 0
    count = hit.sum().astype(jnp.int32)
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    at = jnp.minimum(jnp.arange(n_held + 1, dtype=jnp.int32),
                     jnp.maximum(count - 1, 0))
    return order[at], count.reshape(1)


def _kernel(ids_ref, count_ref, x_ref, comb_ref, up_ref, down_ref, o_ref, *, nf: int):
    t = pl.program_id(0)
    e = ids_ref[t // nf]

    @pl.when(t == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    # [T, d] x [tf, d]^T: the up matrix is resident with d minor (see
    # _expert_ffn_impl), so its tile is the transposed right-hand side.
    h = jax.lax.dot_general(
        x_ref[...], up_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    r = jnp.maximum(h, 0.0)
    out = jnp.dot(
        (r * r).astype(down_ref.dtype), down_ref[0],
        preferred_element_type=jnp.float32,
    )
    comb = comb_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, comb.shape, 1)
    col = jnp.sum(jnp.where(lane == e, comb, 0.0), axis=1, keepdims=True)
    o_ref[...] += col * out


def _expert_ffn_impl(
    xs: jnp.ndarray,  # [T, d]
    comb: jnp.ndarray,  # [T, Eh] float32; zero off the routing and on dead rows
    we_up: jnp.ndarray,  # [Eh, d, f]
    we_down: jnp.ndarray,  # [Eh, f, d]
    ids: jnp.ndarray,  # [Eh + 1] int32 (hit_list)
    count: jnp.ndarray,  # [1] int32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Sum over the experts in ``ids[:count]`` of ``comb[:, e] * (relu2(xs @
    we_up[e]) @ we_down[e])``, float32 [T, d]. An expert not on the list is
    not read; its combine column must be zero (it is, where the list is
    ``hit_list`` of the live routing that ``comb`` holds)."""
    T, d = xs.shape
    n_held, _, f = we_up.shape
    tf = f_tile(f, d, we_up.dtype.itemsize)
    nf = f // tf
    # XLA holds a [Eh, d, f] array whose f is no multiple of 128 lanes with
    # d minor-most (no padding): [Eh, f, d] is that array's own bytes, a
    # bitcast and no copy, and both matrices then tile over f the same way.
    up_t = we_up.transpose(0, 2, 1)

    def tile_map(t, ids, n):
        return (ids[t // nf], t % nf, 0)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda t, ids, n: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.maximum(count[0], 1) * nf,),
        in_specs=[
            whole(T, d),
            whole(T, n_held),
            pl.BlockSpec((1, tf, d), tile_map),
            pl.BlockSpec((1, tf, d), tile_map),
        ],
        out_specs=whole(T, d),
    )
    return pl.pallas_call(
        functools.partial(_kernel, nf=nf),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="expert_ffn_hit_list",
    )(ids, count, xs.astype(we_up.dtype), comb.astype(jnp.float32), up_t, we_down)


from dynamo_tpu.runtime.device_observe import watched_jit  # noqa: E402

expert_ffn = watched_jit(
    "pallas.expert_ffn",
    functools.partial(jax.jit, static_argnames=("interpret",))(_expert_ffn_impl),
)
