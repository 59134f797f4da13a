"""Pallas TPU expert FFNs whose grid is a work list: the experts hit (a
decode step), the (expert, row tile) pairs of a sorted batch (a prefill
step).

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

A prefill step of more than ``ops/moe.DENSE_TOKENS_MAX`` tokens, and since
PR 51 a smaller chunk over many small experts (``ops/moe.chunk_costs``: the
kernel above runs ALL its rows through every expert hit, and an expert it
takes in two short grid steps, ``hit_list_steps``, hides none of those
products behind its stream), takes the
second kernel of this file, ``expert_ffn_grouped`` (PR 45; the grouped form
it replaces was an ``argsort``, a gather and three ``jax.lax.ragged_dot``
calls with the [T*K, f] intermediate through HBM and, because ``ragged_dot``
wants ``we_up`` f minor, a copy of the whole d-minor stack, 638 MB a layer,
every step). Same pattern, other work list:

  - ops/moe.py sorts the assignments by held expert (dead rows and absent
    experts last) and pads each expert's rows to whole ROW TILES of ``tm``
    rows (``grouped_row_tile``: from the step's static shape), so that a
    tile belongs to one expert. Scalar prefetch: the expert of each tile,
    tiles in order, entries past the count repeating the last
    (``grouped_work_list``; one entry longer than the most steps the grid
    takes), and the count. The grid is ``max(count, 1)`` steps, a traced
    length: an expert nobody chose, a dead row and a row on an absent
    expert are no grid step and no DMA.
  - A step is one row tile through its expert's WHOLE matrices, chunk by
    chunk in loops inside the step (``grouped_chunk``: 464 x 384 at the
    hybrid widths; unrolled, Mosaic took 1.2-1.5 s to compile the kernel
    where it takes 0.15 now, and a prefill ladder of 16 programs holds it 52
    times: ``setup_s`` read +22% warm, my chip run, PR 45): ``act(x @ up)``
    [tm, tf] in float32 never leaves the core, ``that @ down`` is added to
    the tile's float32 result. The matrices are blocks of one expert in the
    layout they are resident in (``we_up`` d minor taken as [Eh, f, d], a
    bitcast; f minor as written), indexed by the tile's expert: the
    pipeline fetches the next expert's during this one's rows, and a second
    tile of the same expert moves no block index and streams NOTHING. So a
    step's weights stream once per expert hit, whatever its tokens (with
    f tiles as the grid's inner part, as in ``_kernel``, every row tile
    would stream its expert again: 24 us of bytes against 13 us of FLOPs
    for a tile of 128 rows at the hybrid widths). What that takes is VMEM
    for an expert twice: 2 x 19.96 MB at the hybrid cell's widths, 2 x 6.29
    MB at the window cell's, of the 128 MiB a v5e core has; the latent
    cell's expert is 94.4 MB and ops/moe.grouped_reason keeps it on
    ``ragged_dot``.
  - Tiles past the count are NOT WRITTEN: ops/moe.py masks them (and each
    expert's padding rows) by never reading them: each token gathers the
    K result rows it owns and sums them weighted, in XLA. No loop anywhere:
    benchmark/trace_names tells a prefill program from a decode burst by a
    ``while``.

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
``expert_ffn_grouped`` (my chip runs, PR 45; ``chip_check --only
expert_ffn_grouped``: one expert layer as ops/moe.py serves it, sort, gathers
and weighted sum included, top-6 of a router 128 wide over 64 held, a third
of the rows dead), d minor, relu2, 2688 x 1856: 2,153 / 3,058 / 4,027 / 6,626
/ 12,075 us at 512 / 1,024 / 2,048 / 4,096 / 8,192 tokens against the
``ragged_dot`` form's 15,770 / 16,818 / 21,021 / 24,963 / 37,866; the kernel
alone over 69 tiles on 62 experts (512 tokens) 1,736 us = 1.24 GB at 714
GB/s, 87% of the peak, and 5.0 ms at 8,192 tokens (17 k padded rows: tiles
of 64 rows use a quarter of the MXU; what stays in XLA is the other half of
that step). f minor, gated silu, 2048 x 512 x 256 held, top-8: 2,491 / 3,179
/ 4,170 us at 512 / 1,024 / 2,048 tokens against 5,010 / 5,792 / 6,842.
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


def hit_list_steps(d: int, f: int, itemsize: int) -> int:
    """Grid steps the hit-list kernel takes ONE expert in, from the widths
    (``_expert_ffn_impl``'s own tiling): 4 at the hybrid cell's, 28 at the
    latent cell's, 2 at the window and delta-rule cells' (2048 x 512: one
    step for the up and gate matrices whole, one for the down matrix)."""
    if f_minor(f):
        return d // lane_tile(d, f * itemsize) + f // lane_tile(f, d * itemsize)
    return f // f_tile(f, d, itemsize)


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


# -- the grouped form: many tokens, a grid of (expert, row tile) pairs -------

# What the grouped kernel may keep in VMEM (a v5e core has 128 MiB): an
# expert's matrices whole, twice (the pipeline fetches the next expert's
# while this one's rows run), beside a row tile's tokens, result and
# intermediate. 2 x 19.96 MB at the hybrid cell's widths, 2 x 6.29 MB at the
# window cell's; the latent cell's expert is 94.4 MB and stays on
# ``ragged_dot`` (ops/moe.grouped_reason says so).
GROUPED_VMEM_BYTES_MAX = 100 << 20
GROUPED_ROW_TILE_MAX = 64


def grouped_row_tile(assignments: int, n_experts: int) -> int:
    """Rows of a tile of the grouped kernel, from the step's static shape:
    16 where an expert expects at most 8 assignments (all of them over the
    router's width), 32 up to 64, 64 above. A tile belongs to one expert and
    each expert's last tile is padded; an expert's matrices stay in VMEM over
    its tiles, so a small tile costs a grid step, not a stream, and what pads
    costs FLOPs and rows of the gather. Measured (scratch sweep of 32-256,
    my chip run, PR 45, us a layer, tiles of 32 / 64 / 128 / 256): hybrid
    widths 512 tokens 2,005 / 2,066 / 2,424 / 2,925, 8,192 tokens 11,053 /
    10,204 / 10,343 / 10,313; window-cell widths 512 tokens 2,957 / 3,603 /
    4,826 / 7,146, 2,048 tokens 4,869 / 5,319 / 6,327 / 8,413. A turn's chunk
    of 64 / 128 / 256 tokens over 256 small experts (4-8 rows an expert:
    scratch sweep, my chip run, PR 51, tiles of 16 / 32): top-8 of 256 1,374
    / 1,457, 1,759 / 1,842, 2,163 / 2,244; top-10 of 512 with 256 held 1,137
    / 1,201, 1,539 / 1,641, 2,010 / 2,113 (the kernel alone 1,997 / 2,118
    over 247 / 231 tiles at 256 tokens: a bf16 tile is 16 sublanes)."""
    per_expert = assignments / max(n_experts, 1)
    return 16 if per_expert <= 8 else 32 if per_expert <= 64 else 64


def grouped_tiles(assignments: int, n_held: int, tm: int) -> int:
    """Most row tiles a step can need: sum over experts of ceil(size / tm)
    <= assignments // tm + one ragged tile for each expert hit."""
    return assignments // tm + min(n_held, assignments)


def grouped_vmem_bytes(tm: int, d: int, f: int, n_matrices: int, itemsize: int) -> int:
    """VMEM the grouped kernel asks for at these widths: every operand block
    twice (the pipeline's two buffers), the float32 intermediates once, and
    room for Mosaic's own scratch."""
    expert = n_matrices * d * f * itemsize
    tile = tm * d * (itemsize + 4)  # tokens in, float32 result out
    inner = (n_matrices - 1) * tm * f * 4 + tm * f * itemsize + tm * d * 4
    return 2 * (expert + tile) + inner + (8 << 20)


def grouped_work_list(sizes: jnp.ndarray, tm: int, n_tiles: int):
    """(tile_expert [n_tiles + 1] int32, n_work [1] int32, first [n_tiles]
    int32, left [n_tiles] int32, pad_before [Eh] int32) from the assignments
    on each held expert: expert e owns ``ceil(sizes[e] / tm)`` row tiles,
    experts in order, so a tile's rows all belong to one expert and an
    expert nobody chose owns none. ``first[t]`` is the rank, among the
    assignments sorted by expert, of tile t's first row and ``left[t]`` the
    real rows from there on (0 for a tile past the list, under ``tm`` for an
    expert's last); ``pad_before[e]`` the padding rows in front of expert
    e's first tile. Entries of ``tile_expert`` at or past ``n_work`` repeat
    the last tile's expert (expert 0 of an empty list): one entry longer
    than the most steps the grid can take, never a list of one entry
    (ops/pallas/live_pages.live_work_list says why). No loop and no lookup
    in a table: offsets by ``cumsum``, a tile's expert and numbers by masked
    sums over [tiles, Eh] (one fusion on the TPU, as ``live_work_list``; a
    gather from a 64-entry table compiles to 64 selects a lookup, and a
    prefill ladder compiles this 52 times)."""
    n_held = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    tiles = (sizes + tm - 1) // tm
    ends = jnp.cumsum(tiles)
    begins = ends - tiles
    offset = jnp.cumsum(sizes) - sizes
    n_work = ends[-1]
    t = jnp.arange(n_tiles + 1, dtype=jnp.int32)[:, None]
    held = jnp.arange(n_held, dtype=jnp.int32)[None, :]

    def of_tile(t, value):
        mine = (begins[None, :] <= t) & (t < ends[None, :])
        return jnp.sum(jnp.where(mine, value(t - begins[None, :]), 0), axis=1)

    tile_expert = of_tile(jnp.minimum(t, n_work - 1), lambda k: held)
    first = of_tile(t[:-1], lambda k: offset[None, :] + k * tm)
    left = of_tile(t[:-1], lambda k: sizes[None, :] - k * tm)
    return tile_expert, n_work.reshape(1), first, left, begins * tm - offset


GROUPED_CHUNK_MAX = 512


def grouped_chunk(n: int, align: int) -> int:
    """Width of a chunk of an axis of ``n`` inside a grid step of the grouped
    kernel: the largest divisor of n that is a multiple of ``align`` (16
    sublanes of a bf16 tile, or the 128 lanes) within ``GROUPED_CHUNK_MAX``;
    n itself where none is (small test shapes)."""
    fits = [t for t in range(align, min(n, GROUPED_CHUNK_MAX) + 1, align) if n % t == 0]
    return max(fits) if fits else n


def _grouped_kernel(
    tile_expert_ref, n_work_ref, x_ref, up_ref, *rest, d_minor: bool, tf: int, td: int
):
    """One grid step is one row tile through its expert's whole matrices,
    which are in VMEM; a second tile of the same expert finds them there
    (the block index did not move: no DMA). Inside the step the products go
    chunk by chunk, ``f // tf`` chunks of the expert width and, within each,
    ``d // td`` chunks of the model width, in loops (not unrolled: a step's
    program is one [tm, td] x [td, tf] product and its transpose, not
    [tm, d] x [d, f]; Mosaic compiles the kernel ten times sooner, and a
    prefill ladder holds it four times a program): ``act(x @ up)`` [tm, tf]
    in float32 never leaves the core, and ``that @ down`` is added to the
    tile's float32 result chunk by chunk."""
    *gate_ref, down_ref, o_ref = rest
    tm, d = x_ref.shape
    f = down_ref.shape[1]
    wide_refs = [up_ref, *gate_ref]

    def f_chunk(j, carry):
        fs = pl.multiple_of(j * tf, tf)

        def wide_part(ref, ks):
            x = x_ref[:, pl.ds(ks, td)]
            if d_minor:  # the matrix is resident as [f, d]: contract both on d
                return jax.lax.dot_general(
                    x, ref[0, pl.ds(fs, tf), pl.ds(ks, td)],
                    (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                )
            return jnp.dot(
                x, ref[0, pl.ds(ks, td), pl.ds(fs, tf)],
                preferred_element_type=jnp.float32,
            )

        def wide_chunk(k, sums):
            ks = pl.multiple_of(k * td, td)
            return tuple(acc + wide_part(ref, ks) for acc, ref in zip(sums, wide_refs))

        sums = jax.lax.fori_loop(
            0, d // td, wide_chunk,
            tuple(jnp.zeros((tm, tf), jnp.float32) for _ in wide_refs),
        )
        h = _activate(sums[0], sums[1] if gate_ref else None).astype(down_ref.dtype)

        def down_chunk(k, carry):
            ks = pl.multiple_of(k * td, td)
            part = jnp.dot(
                h, down_ref[0, pl.ds(fs, tf), pl.ds(ks, td)],
                preferred_element_type=jnp.float32,
            )

            @pl.when(j == 0)
            def _first():
                o_ref[:, pl.ds(ks, td)] = part

            @pl.when(j > 0)
            def _next():
                o_ref[:, pl.ds(ks, td)] += part

            return carry

        return jax.lax.fori_loop(0, d // td, down_chunk, carry)

    jax.lax.fori_loop(0, f // tf, f_chunk, 0)


def _expert_ffn_grouped_impl(
    rows: jnp.ndarray,  # [n_tiles * tm, d]: tokens sorted by expert, tiles padded
    we_up: jnp.ndarray,  # [Eh, d, f]
    we_down: jnp.ndarray,  # [Eh, f, d]
    tile_expert: jnp.ndarray,  # [n_tiles + 1] int32 (grouped_work_list)
    n_work: jnp.ndarray,  # [1] int32
    we_gate: Optional[jnp.ndarray] = None,  # [Eh, d, f]: gated silu; None: relu2
    *,
    tm: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """float32 [n_tiles * tm, d]: row tile i < ``n_work`` is ``act(rows_i @
    we_up[e]) @ we_down[e]`` for ``e = tile_expert[i]``, the dense form's own
    products (operands in the weights' dtype, float32 accumulation, the
    activation in float32). Tiles at or past ``n_work`` are NOT WRITTEN:
    whatever the buffer held is not a result, and no matrix is read for
    them. The matrices go in as they are resident (``f_minor``): ``we_up``
    [Eh, d, f] with d minor-most is taken as its transpose [Eh, f, d], a
    bitcast."""
    M, d = rows.shape
    _, _, f = we_up.shape
    wide = [we_up] if we_gate is None else [we_up, we_gate]
    d_minor = not f_minor(f)
    if d_minor:
        wide = [w.transpose(0, 2, 1) for w in wide]
    wide_block = (1, f, d) if d_minor else (1, d, f)

    def expert(i, tile_expert, n):
        return (tile_expert[i], 0, 0)

    def tile(i, tile_expert, n):
        return (i, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.maximum(n_work[0], 1),),
        in_specs=[pl.BlockSpec((tm, d), tile),
                  *[pl.BlockSpec(wide_block, expert)] * len(wide),
                  pl.BlockSpec((1, f, d), expert)],
        out_specs=pl.BlockSpec((tm, d), tile),
    )
    kernel = functools.partial(
        _grouped_kernel, d_minor=d_minor, td=grouped_chunk(d, LANES),
        tf=grouped_chunk(f, 16 if d_minor else LANES))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(
                grouped_vmem_bytes(tm, d, f, len(wide) + 1, we_up.dtype.itemsize),
                GROUPED_VMEM_BYTES_MAX),
        ),
        interpret=interpret,
        name="expert_ffn_grouped",
    )(tile_expert, n_work, rows.astype(we_up.dtype), *wide, we_down)


from dynamo_tpu.runtime.device_observe import watched_jit  # noqa: E402

expert_ffn = watched_jit(
    "pallas.expert_ffn",
    functools.partial(jax.jit, static_argnames=("interpret",))(_expert_ffn_impl),
)

expert_ffn_grouped = watched_jit(
    "pallas.expert_ffn_grouped",
    functools.partial(jax.jit, static_argnames=("tm", "interpret"))(
        _expert_ffn_grouped_impl),
)
