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
    axis of ``max(count, 1)`` x the steps an expert takes, a traced
    length: an expert nobody chose is no grid step and no DMA.
  - The matrices are whole-array BlockSpec operands in the layout they are
    RESIDENT in, and the index maps take the expert from the list, so the
    pallas pipeline double-buffers the stream across expert boundaries and
    every tile is one contiguous stretch of HBM. ``we_down`` is [Eh, f, d].
    ``we_up`` (and ``we_gate``) is [Eh, d, f], and which axis XLA holds
    minor-most follows from the widths (``f_minor``), so the kernel has two
    forms and reads the shapes to choose:

      * d minor (hybrid cell: d 2688, f 1856). Where f is no multiple of
        the 128 lanes (1856 is 14.5 tiles) and d is, XLA holds the array
        with d minor-most rather than pad f: its transpose to [Eh, f, d] is
        then a bitcast of the resident bytes, not a copy (a copy of the
        stack, 660 MB a layer-step, is what taking it as [Eh, d, f] cost;
        PR 33 met the same with the KV pool). Every matrix tiles over f in
        ``nf`` tiles of [tf, d]; a grid step is one tile of each: ``act(x @
        up_tile^T)`` [T, tf], then ``comb[:, e] * (that @ down_tile)`` added
        to the result. ``max(count, 1) x nf`` steps.
      * f minor (latent cell: d 7680, f 2048). Where f fills the lanes the
        array is resident as written, and a [d, tf] column tile would be
        7,680 strided rows. So an expert is two runs of contiguous tiles:
        ``nd`` steps that each add ``x[:, tile] @ up[tile, :]`` (and the
        gate's) for a [td, f] ROW tile to float32 sums [T, f] in VMEM, the
        activation once, then ``nf`` steps over [tf, d] tiles of the down
        matrix as above. While one run streams, the other matrix's index
        map stands still (no DMA), and the next expert's first tiles are
        fetched during the last step of this one. ``max(count, 1) x (nd +
        nf)`` steps; the tokens go in as ``nd`` tiles [nd, T, td] so that
        a step takes its tile by the leading index.

    ops/moe.hit_list_reason keeps a model width that does not fill the
    lanes on the XLA forms; tests/test_mosaic_compile.py pins, compiling for
    the described v5e, that the served programs of both cells hold no copy
    of a matrix stack.
  - The [T, d] float32 result stays in VMEM with all T tokens and the
    [T, Eh] combine matrix for the whole grid: the dense form's own
    products over fewer experts, no sort, no scatter, nothing dropped.
    Operands in the weights' dtype (bf16 served), float32 accumulation, the
    activation in float32.

Activations (``ACTIVATIONS``): ``relu2`` (two matrices) and ``silu_gated``
(given ``we_gate``: ``silu(x @ gate) * (x @ up)``), in either layout;
ops/moe.py keeps the XLA forms for every other.

On the chip (ops/pallas/chip_check.py). d minor, relu2, 2688 x 1856 x 64
held (my chip run, PR 37): 53 us with one expert hit and 26.5 us for each
further one (753 GB/s of the 819 peak) at 64 tokens; with all 64 hit 1,724
us against the dense form's 1,751. f minor, gated silu, 7680 x 2048 x 16
held, three matrices of 31.5 MB an expert (my chip run, PR 43): 157 us
with one expert hit at the 32 decode slots, 289 us with two at 64 tokens,
and 130 us for each further one (726 GB/s, 89% of the peak); with all 16
hit 2,057 / 2,083 / 2,124 / 2,464 us at 8 / 64 / 128 / 256 tokens against
the dense form's 2,101 / 2,127 / 2,136 / 2,444 (at 256 tokens the FLOPs,
1.96 ms at the bf16 peak, pass the bytes, 1.84 ms: a tie), and with two hit
289 / 295 / 347 us at 64 / 128 / 256 tokens against 2,137-2,444.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACTIVATIONS = ("relu2", "silu_gated")
# A step's weight tiles (one of each matrix), each double-buffered by the
# pipeline, have to fit VMEM beside the resident operands: 4 x 464 x 2688
# x 2 B = 10 MB at the hybrid widths, 2 x (2 x 640 x 2048 + 128 x 7680)
# x 2 B = 14.4 MB at the latent ones.
F_TILE_BYTES_MAX = 5 << 19
VMEM_LIMIT_BYTES = 48 << 20
# The f-minor form keeps, beside its tiles, the up and gate sums [T, f]
# float32 and the activation, and at 256 tokens of width 7680 the tokens
# and the float32 result are 3.9 and 7.9 MB, each held twice. (The d-minor
# form keeps the limit the hybrid cell was measured at: the limit is part
# of its compiled program.)
VMEM_LIMIT_BYTES_F_MINOR = 80 << 20
LANES = 128


def f_tile(f: int, d: int, itemsize: int) -> int:
    """Rows of the expert width a grid step takes: the largest divisor of f
    that is a multiple of 16 sublanes (a bf16 tile) and whose [tile, d]
    block is within ``F_TILE_BYTES_MAX``; f itself where no such divisor
    exists (small test shapes: one tile)."""
    fits = [n for n in range(16, f + 1, 16)
            if f % n == 0 and n * d * itemsize <= F_TILE_BYTES_MAX]
    return max(fits) if fits else f


def lane_tile(n: int, row_bytes: int) -> int:
    """Rows of an axis of ``n`` (a multiple of the 128 lanes) that a grid
    step of the f-minor form takes: the largest divisor of n that is a
    multiple of 128 and whose rows of ``row_bytes`` are within
    ``F_TILE_BYTES_MAX``; 128 where even that is over."""
    fits = [t for t in range(LANES, n + 1, LANES)
            if n % t == 0 and t * row_bytes <= F_TILE_BYTES_MAX]
    return max(fits) if fits else LANES


def f_minor(f: int) -> bool:
    """Whether ``we_up`` [Eh, d, f] is resident as written, f minor-most: f
    fills the 128 lanes. Where it does not and d does, XLA holds the array
    with d minor-most rather than pad f (seen compiling for the described
    v5e, PR 37; tests/test_mosaic_compile.py pins both)."""
    return f % LANES == 0


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


def _activate(up, gate):
    """float32 in, float32 out: relu2 of ``up``, or silu(gate) * up."""
    if gate is None:
        r = jnp.maximum(up, 0.0)
        return r * r
    return gate * jax.nn.sigmoid(gate) * up


def _combine_column(comb_ref, e):
    comb = comb_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, comb.shape, 1)
    return jnp.sum(jnp.where(lane == e, comb, 0.0), axis=1, keepdims=True)


def _kernel(ids_ref, count_ref, x_ref, comb_ref, up_ref, *rest, nf: int):
    """d-minor form: one step is one [tf, d] tile of each matrix."""
    *gate_ref, down_ref, o_ref = rest
    t = pl.program_id(0)
    e = ids_ref[t // nf]

    @pl.when(t == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    # [T, d] x [tf, d]^T: the up (and gate) matrix is resident with d minor
    # (see _expert_ffn_impl), so its tile is the transposed right-hand side.
    def wide(ref):
        return jax.lax.dot_general(
            x_ref[...], ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    h = _activate(wide(up_ref), wide(gate_ref[0]) if gate_ref else None)
    out = jnp.dot(
        h.astype(down_ref.dtype), down_ref[0],
        preferred_element_type=jnp.float32,
    )
    col = _combine_column(comb_ref, e)
    o_ref[...] += col * out


def _kernel_f_minor(
    ids_ref, count_ref, x_ref, comb_ref, up_ref, *rest, nd: int, nf: int, gated: bool
):
    """f-minor form: an expert is ``nd`` steps that add a [td, f] row tile's
    product to the up (and gate) sums, then ``nf`` steps that each take one
    [tf, d] tile of the down matrix."""
    if gated:
        gate_ref, down_ref, o_ref, up_acc, gate_acc, act_ref = rest
    else:
        down_ref, o_ref, up_acc, act_ref = rest
        gate_ref = gate_acc = None
    t = pl.program_id(0)
    e = ids_ref[t // (nd + nf)]
    s = t % (nd + nf)
    tf = act_ref.shape[2]

    @pl.when(t == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    def add(acc, ref):
        part = jnp.dot(x_ref[s], ref[0], preferred_element_type=jnp.float32)

        @pl.when(s == 0)
        def _first():
            acc[...] = part

        @pl.when(s > 0)
        def _next():
            acc[...] += part

    @pl.when(s < nd)
    def _up():
        add(up_acc, up_ref)
        if gated:
            add(gate_acc, gate_ref)

    @pl.when(s == nd - 1)
    def _act():
        h = _activate(up_acc[...], gate_acc[...] if gated else None)
        h = h.astype(act_ref.dtype)
        for j in range(nf):
            act_ref[j] = h[:, j * tf:(j + 1) * tf]

    @pl.when(s >= nd)
    def _down():
        out = jnp.dot(
            act_ref[s - nd], down_ref[0], preferred_element_type=jnp.float32
        )
        col = _combine_column(comb_ref, e)
        o_ref[...] += col * out


def _expert_ffn_impl(
    xs: jnp.ndarray,  # [T, d]
    comb: jnp.ndarray,  # [T, Eh] float32; zero off the routing and on dead rows
    we_up: jnp.ndarray,  # [Eh, d, f]
    we_down: jnp.ndarray,  # [Eh, f, d]
    ids: jnp.ndarray,  # [Eh + 1] int32 (hit_list)
    count: jnp.ndarray,  # [1] int32
    we_gate: Optional[jnp.ndarray] = None,  # [Eh, d, f]: gated silu; None: relu2
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Sum over the experts in ``ids[:count]`` of ``comb[:, e] * (act @
    we_down[e])``, float32 [T, d], where ``act`` is ``relu2(xs @ we_up[e])``
    or, given ``we_gate``, ``silu(xs @ we_gate[e]) * (xs @ we_up[e])``. An
    expert not on the list is not read; its combine column must be zero (it
    is, where the list is ``hit_list`` of the live routing that ``comb``
    holds)."""
    T, d = xs.shape
    n_held, _, f = we_up.shape
    itemsize = we_up.dtype.itemsize
    wide = [we_up] if we_gate is None else [we_up, we_gate]
    xs = xs.astype(we_up.dtype)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda t, ids, n: (0,) * len(shape))

    if f_minor(f):
        td, tf = lane_tile(d, f * itemsize), lane_tile(f, d * itemsize)
        nd, nf = d // td, f // tf
        steps = nd + nf
        kernel = functools.partial(
            _kernel_f_minor, nd=nd, nf=nf, gated=we_gate is not None)
        # The tokens as ``nd`` tiles of the model width, so that a step
        # takes its tile by the leading index.
        xs = xs.reshape(T, nd, td).transpose(1, 0, 2)
        x_spec = whole(nd, T, td)
        # While the down tiles stream the row tile stays where it was (no
        # DMA), and while the row tiles stream the first down tile waits.
        wide_spec = pl.BlockSpec(
            (1, td, f),
            lambda t, ids, n: (ids[t // steps], jnp.minimum(t % steps, nd - 1), 0))
        down_spec = pl.BlockSpec(
            (1, tf, d),
            lambda t, ids, n: (ids[t // steps], jnp.maximum(t % steps - nd, 0), 0))
        scratch = [pltpu.VMEM((T, f), jnp.float32) for _ in wide]
        scratch.append(pltpu.VMEM((nf, T, tf), we_down.dtype))
        vmem_limit = VMEM_LIMIT_BYTES_F_MINOR
    else:
        tf = f_tile(f, d, itemsize)
        nf = steps = f // tf
        kernel = functools.partial(_kernel, nf=nf)
        # XLA holds a [Eh, d, f] array whose f is no multiple of 128 lanes
        # with d minor-most (no padding): [Eh, f, d] is that array's own
        # bytes, a bitcast and no copy, and every matrix then tiles over f
        # the same way.
        wide = [w.transpose(0, 2, 1) for w in wide]
        x_spec = whole(T, d)
        wide_spec = down_spec = pl.BlockSpec(
            (1, tf, d), lambda t, ids, n: (ids[t // nf], t % nf, 0))
        scratch = []
        vmem_limit = VMEM_LIMIT_BYTES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.maximum(count[0], 1) * steps,),
        in_specs=[x_spec, whole(T, n_held), *[wide_spec] * len(wide), down_spec],
        out_specs=whole(T, d),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
        name="expert_ffn_hit_list",
    )(ids, count, xs, comb.astype(jnp.float32), *wide, we_down)


from dynamo_tpu.runtime.device_observe import watched_jit  # noqa: E402

expert_ffn = watched_jit(
    "pallas.expert_ffn",
    functools.partial(jax.jit, static_argnames=("interpret",))(_expert_ffn_impl),
)
