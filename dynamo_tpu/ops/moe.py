"""Mixture-of-experts FFN: dropless, told which experts it holds.

Every routed token is computed: there is no capacity and nothing is dropped
(until PR 36 a GShard dispatch here dropped what overflowed a capacity
factor). The layer routes over ALL ``spec.n_experts`` (the router keeps its
published width), computes the part of the result that the experts in
``spec.held_`` give, and adds the shared expert where the spec has one. What
an absent expert would have added is left out: that is one chip's share of
an expert-parallel group, and the shares of the whole group, the shared
expert counted once, add up to the uncut layer. Under an ``ep`` mesh the
expert matrices are sharded on their first axis and GSPMD partitions the
dense form's einsums by itself: no exchange of tokens, every shard sees the
whole batch, as the two chips of the served deployment do.

Four forms of the held experts' part. ``form_of`` chooses from what it is
given (the caller's ``use_kernel``, the step's static shape [B, C], the
matrices, the spec): ``hit_list_reason`` says why a step is not the first,
``grouped_reason`` why one the first gave up is not the third; where no
kernel serves, the second up to ``DENSE_TOKENS_MAX`` tokens a step and the
fourth above. No flag, no name of a model:

* hit list (a decode step of any width up to 256 slots, and a prefill chunk
  of up to 256 tokens whose experts are wide enough to hide the chunk's
  products behind their stream, on one TPU chip): the dense form's own
  products, float32 accumulation, over the experts that got a LIVE token
  and no others, by a Pallas kernel whose grid is that list
  (ops/pallas/expert_ffn.py). A step streams from HBM the
  matrices of the experts hit, which is what ``want_stats`` counts: a dead
  slot (``row_mask`` false) routes to no expert. Every one of the step's T
  rows goes through every expert hit, whatever that expert got: right for a
  decode step, whose few live rows hit few experts, and what
  ``chunk_costs`` prices for a prefill chunk. The kernel reads ``relu2``
  experts (two matrices) and ``silu_gated`` ones (three), with ``we_up`` /
  ``we_gate`` in either layout XLA holds them in: d minor-most where f does
  not fill the 128 lanes (the hybrid cell, 2688 x 1856), f minor-most where
  it does (the latent cell, 7680 x 2048); a model width that does not fill
  the lanes stays on the XLA forms.
* dense (few tokens, everywhere else; quantized matrices at any count):
  every token through every held expert with a [T, E_held] weight matrix
  that is zero off the routing and on dead rows. The weights of all held
  experts stream once, and the einsums are static shapes that GSPMD
  partitions over an ``experts`` sharded axis by itself.
* grouped kernel (a prefill step of more than 256 tokens on one TPU chip,
  since PR 45; since PR 51 also a chunk of up to 256 over many small
  experts, as a turn of the window and delta-rule cells, where the hit list
  would run all T rows through each of ~250 experts hit and this kernel the
  4-8 an expert got, padded to one tile): assignments sorted by held
  expert, dead rows and absent
  experts last; each expert's rows padded to whole row tiles, so that a
  tile belongs to one expert; ONE Pallas kernel whose grid is the list of
  (expert, row tile) pairs (``expert_ffn_grouped``): a step is ``act(x_tile
  @ up) @ down`` with the expert's matrices whole in VMEM, read in the
  layout they are resident in (no copy of the stack), the [rows, f]
  intermediate never in HBM, float32 accumulation; an expert's second tile
  streams nothing. What is not live is no grid step. Each token's
  weighted sum over its K result rows stays in XLA, as a gather.
* grouped XLA (everything the kernel refuses: the CPU, a mesh, an
  activation or width it does not read, an expert whose matrices do not
  fit VMEM whole, as the latent cell's 3 x 31.5 MB): the same sort, one
  ``jax.lax.ragged_dot`` per matrix with the [T*K, f] intermediate through
  HBM (and, where ``we_up`` is resident d minor, a copy of the stack in
  front of it), scattered back weighted.

Shapes: T = B*C tokens, E router width, Eh experts held, K experts a token,
f expert width. ``lp`` holds ``router_w`` [d, E], optionally ``router_bias``
[E] (the score-correction bias: it chooses, it does not weigh), ``we_up``
[Eh, d, f], ``we_down`` [Eh, f, d], ``we_gate`` [Eh, d, f] for a gated
activation, and ``ws_up`` / ``ws_down`` (/ ``ws_gate``) for the shared
expert, whose output is multiplied by ``sigmoid(x ws_gate_scalar)`` ([d, 1])
where the layer has one.
"""

from __future__ import annotations

import math
from typing import TYPE_CHECKING, Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.pallas.expert_ffn import (
    ACTIVATIONS, GROUPED_ROW_TILE_MAX, GROUPED_VMEM_BYTES_MAX, expert_ffn,
    expert_ffn_grouped, grouped_row_tile, grouped_tiles,
    grouped_vmem_bytes, grouped_work_list, hit_list, hit_list_steps,
)
from dynamo_tpu.ops.quant import qeinsum

if TYPE_CHECKING:  # models/ imports this module: the spec is data, named only
    from dynamo_tpu.models.config import ExpertsSpec

# Token count up to which every token may go through every expert hit (the
# hit-list kernel, where ``hit_list_reason`` finds nothing against it) or
# through every held expert (the dense form); above it the grouped forms
# serve. At the hybrid cell's widths (d 2688, f 1856, 64 held) the dense
# form's FLOPs pass the time the weights take to stream at about 256 tokens
# on a v5e, and up to there the kernel with all 64 experts hit is no slower
# than the dense form (chip_check's expert_ffn rows at 64, 128 and 256
# tokens: 1,724 / 1,725 / 1,931 us against 1,751 / 1,786 / 2,188, my chip
# run, PR 37). The ridge is a property of the chip, not of the widths (an
# expert's FLOPs over its bytes is the token count, whatever d and f), and
# at the latent cell's widths (7680 x 2048, 16 held, three matrices) the
# kernel with all 16 hit reads 2,083 / 2,124 / 2,464 us at 64 / 128 / 256
# tokens against 2,127 / 2,136 / 2,444 (a tie at 256, where a step with two
# experts hit takes 347 us; my chip run, PR 43), so one bound serves both.
DENSE_TOKENS_MAX = 256

# Below that bound, which kernel a PREFILL chunk takes (``chunk_costs``; a
# decode step, C = 1, always keeps the hit list: its slots are mostly dead
# and it reads the 16-25 experts its live rows hit). The hit-list kernel runs
# all T rows of the step through every expert hit, whatever the expert got;
# the grouped kernel runs the assignments, ~T x top_k / n_experts an expert,
# padded to a row tile. Both stream an expert hit once, so what parts them is
# whether those T rows of products hide behind the stream. An expert the hit
# list takes in several tiles (``hit_list_steps``: 4 at the hybrid widths,
# 28 at the latent ones) overlaps a tile's products with the next tile's
# DMA: max(stream, products), and the two cross at the chip's ridge,
# ``RIDGE_TOKENS`` rows (v5e, bf16: 197 TFLOP/s over 819 GB/s, two FLOPs and
# two bytes a weight). A SMALL expert is two short grid steps there (one
# tile a matrix) with nothing to overlap, so its products add to its
# stream, stream x (1 + T / ridge); in the grouped kernel it is one resident
# block whose successor streams during its one padded tile of rows. The
# grouped form pays a sort and two gathers around the kernel, the bytes of
# the padded rows in and out (``moved``). Held against chip_check (my chip
# run, PR 51, ``expert_ffn_grouped`` rows, both forms over ONE routing, a
# third of the rows dead, us a layer, hit list -> grouped at the tile these
# steps now take, ``grouped_row_tile`` 16):
#   [256 held, 2048 x 512] x 3, top-8 of 256: 64 tokens (147 experts hit)
#     1,593 -> 1,374; 128 (191 hit) 2,351 -> 1,759; 256 (228 hit) 3,518 ->
#     2,163. Top-10 of 512, 256 held: 64 (119 hit) 1,301 -> 1,137; 128 (163)
#     2,011 -> 1,539; 256 (211) 3,252 -> 2,010. An expert hit costs the hit
#     list 10.8 / 12.3 / 15.4 us at 64 / 128 / 256 tokens (its stream is 7.7
#     at the peak) and the grouped form 9.3-9.8 all in, at any of them.
#   [64 held, 2688 x 1856] x 2, top-6 of 128: 128 tokens (57 hit) 1,552 ->
#     1,643; 256 (56 hit) 1,704 -> 1,680: products hidden, the hit list stays
#     (``CHUNK_MARGIN``: a difference no row decides changes no program).
RIDGE_TOKENS = 240
CHUNK_MARGIN = 1.05

_HI = jax.lax.Precision.HIGHEST


def route(
    xs: jnp.ndarray, lp: Dict[str, Any], spec: ExpertsSpec
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(weights [T, K] float32, expert ids [T, K]) over the full router
    width. Scores in float32 at ``highest`` precision: a choice between two
    near-equal experts must not turn on a rounded product."""
    logits = jnp.dot(
        xs.astype(jnp.float32), lp["router_w"].astype(jnp.float32), precision=_HI
    )
    if spec.routing == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        choose = scores
    elif spec.routing == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choose = scores
    elif spec.routing == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        choose = scores + lp["router_bias"].astype(jnp.float32)
    else:
        raise ValueError(f"unknown routing law {spec.routing!r}")
    _, top_i = jax.lax.top_k(choose, spec.top_k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if spec.norm_topk:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    return top_w * spec.scale, top_i


def _activate(up: jnp.ndarray, gate: Optional[jnp.ndarray], spec: ExpertsSpec):
    if spec.activation == "relu2":
        r = jax.nn.relu(up)
        return r * r
    if spec.activation == "silu_gated":
        return jax.nn.silu(gate) * up
    raise ValueError(f"unknown expert activation {spec.activation!r}")


NO_KERNELS = "no Pallas kernels here (use_kernel is false)"


def _kernel_refusal(lp: Dict[str, Any], spec: ExpertsSpec) -> Optional[str]:
    """What either Pallas kernel cannot read, whatever the token count."""
    if isinstance(lp["we_up"], dict):
        return "quantized expert matrices"
    if spec.activation not in ACTIVATIONS:
        return f"activation {spec.activation} is not in the kernel"
    n_held, d, f = lp["we_up"].shape
    if not n_held:
        return "no expert held"
    if d % 128:
        # The kernels read we_up (and we_gate) through the resident layout:
        # f minor-most where f fills the 128 lanes, d minor-most where it
        # does not and d does (expert_ffn.f_minor). Neither: XLA pads.
        return f"widths d {d}, f {f}: d does not fill the 128 lanes"
    return None


def _n_matrices(spec: ExpertsSpec) -> int:
    return 3 if spec.activation == "silu_gated" else 2


def chunk_costs(
    step: Tuple[int, int], lp: Dict[str, Any], spec: ExpertsSpec
) -> Tuple[float, float]:
    """(hit list, grouped kernel): what a step of ``step`` = [B, C] static
    tokens costs a layer through each kernel, in bytes of HBM time (FLOPs
    counted as the bytes that take as long: ``RIDGE_TOKENS``). From the
    shapes and the spec alone; the comment above ``RIDGE_TOKENS`` says what
    each term is and which ``chip_check`` rows it was held against."""
    T = step[0] * step[1]
    A = T * spec.top_k
    n_held, d, f = lp["we_up"].shape
    itemsize = lp["we_up"].dtype.itemsize
    per_expert = A / spec.n_experts  # assignments a held expert expects
    streamed = n_held * min(1.0, per_expert) * _n_matrices(spec) * d * f * itemsize
    products = T / RIDGE_TOKENS  # of T rows, beside one stream of the expert
    listed = streamed * (
        max(1.0, products) if hit_list_steps(d, f, itemsize) > 2 else 1.0 + products)
    tm = grouped_row_tile(A, spec.n_experts)
    padded = tm * math.ceil(per_expert / tm)  # rows the grouped kernel runs an expert
    # the padded rows gathered in (tokens) and out (float32), each token's K rows
    moved = grouped_tiles(A, n_held, tm) * tm * d * (itemsize + 4) + A * d * 4
    return listed, streamed * max(1.0, padded / RIDGE_TOKENS) + moved


def hit_list_reason(
    use_kernel: bool, step: Tuple[int, int], lp: Dict[str, Any], spec: ExpertsSpec
) -> Optional[str]:
    """None where the held experts' part of a step of ``step`` = [B, C]
    static tokens goes through the hit-list kernel; otherwise why not: no
    kernel, a step over ``DENSE_TOKENS_MAX`` tokens, matrices the kernel does
    not read, or a prefill chunk (C > 1) that ``chunk_costs`` prices dearer
    through the hit list than through the grouped kernel."""
    if not use_kernel:
        return NO_KERNELS
    B, C = step
    if B * C > DENSE_TOKENS_MAX:
        return f"{B * C} tokens a step is over {DENSE_TOKENS_MAX}"
    why = _kernel_refusal(lp, spec)
    if why is None and C > 1 and grouped_reason(use_kernel, lp, spec) is None:
        listed, grouped = chunk_costs(step, lp, spec)
        if listed > CHUNK_MARGIN * grouped:
            return (f"a chunk of {B * C} tokens through every expert hit prices "
                    f"{listed / grouped:.1f} times the grouped kernel")
    return why


def grouped_reason(
    use_kernel: bool, lp: Dict[str, Any], spec: ExpertsSpec
) -> Optional[str]:
    """None where a step the hit list gave up (more than ``DENSE_TOKENS_MAX``
    tokens, or a prefill chunk ``chunk_costs`` sends here) goes through the
    grouped kernel; otherwise why it keeps the XLA forms. From the caller's
    ``use_kernel``, the matrices and the spec alone."""
    if not use_kernel:
        return NO_KERNELS
    why = _kernel_refusal(lp, spec)
    if why is not None:
        return why
    # The kernel keeps an expert's matrices whole in VMEM, so that the
    # second row tile of an expert streams nothing.
    _, d, f = lp["we_up"].shape
    matrices = _n_matrices(spec)
    need = grouped_vmem_bytes(
        GROUPED_ROW_TILE_MAX, d, f, matrices, lp["we_up"].dtype.itemsize)
    if need > GROUPED_VMEM_BYTES_MAX:
        return (f"an expert's {matrices} matrices of {d} x {f}, twice, are "
                f"{need >> 20} MiB of VMEM, over {GROUPED_VMEM_BYTES_MAX >> 20}")
    return None


def form_of(
    use_kernel: bool, step: Tuple[int, int], lp: Dict[str, Any], spec: ExpertsSpec
) -> Tuple[str, Optional[str]]:
    """(form, why not a kernel) of a step of ``step`` = [B, C] static tokens
    (C = 1: a decode step): ``hit_list`` where ``hit_list_reason`` has
    nothing against it, else ``grouped_kernel`` where ``grouped_reason`` has
    nothing against that; where no kernel serves, ``dense`` up to
    ``DENSE_TOKENS_MAX`` tokens (and for quantized matrices, which neither
    ``ragged_dot`` nor a kernel takes), ``grouped_xla`` above. What
    ``moe_ffn`` branches on, the runner logs and the engine counts prefill
    tokens by."""
    why = hit_list_reason(use_kernel, step, lp, spec)
    if why is None:
        return "hit_list", None
    grouped_why = grouped_reason(use_kernel, lp, spec)
    if grouped_why is None:
        return "grouped_kernel", None
    if step[0] * step[1] <= DENSE_TOKENS_MAX:
        return "dense", why
    return ("dense" if isinstance(lp["we_up"], dict) else "grouped_xla"), grouped_why


FORMS = ("hit_list", "dense", "grouped_kernel", "grouped_xla")


def form_in_use(
    use_kernel: bool, step: Tuple[int, int], lp: Dict[str, Any], spec: ExpertsSpec
) -> str:
    """Which form a step of ``step`` = [B, C] tokens takes, for the log:
    ``pallas hit list``, ``pallas grouped``, or ``xla dense|grouped, <why
    not the kernel>``."""
    form, why = form_of(use_kernel, step, lp, spec)
    if why is None:
        return {"hit_list": "pallas hit list", "grouped_kernel": "pallas grouped"}[form]
    return f"xla {form.split('_')[0]}, {why}"


def _experts_dense(xs, comb, lp, spec):
    """Every token through every held expert; ``comb`` [T, Eh] weighs."""
    up = qeinsum("td,edf->etf", xs, lp["we_up"])
    gate = qeinsum("td,edf->etf", xs, lp["we_gate"]) if "we_gate" in lp else None
    out = qeinsum("etf,efd->etd", _activate(up, gate, spec), lp["we_down"])
    return jnp.einsum("te,etd->td", comb.astype(out.dtype), out)


def _experts_grouped(xs, top_w, local, valid, lp, spec, n_held):
    """Assignments sorted by held expert, grouped matmuls, weighted sum."""
    T, K = local.shape
    flat = jnp.where(valid, local, n_held).reshape(T * K)  # absent ones last
    order = jnp.argsort(flat)
    token_of = order // K
    sizes = jnp.bincount(flat, length=n_held + 1)[:n_held].astype(jnp.int32)
    rows = xs[token_of]
    up = jax.lax.ragged_dot(rows, lp["we_up"], sizes)
    gate = (
        jax.lax.ragged_dot(rows, lp["we_gate"], sizes) if "we_gate" in lp else None
    )
    out = jax.lax.ragged_dot(_activate(up, gate, spec), lp["we_down"], sizes)
    w = jnp.where(valid, top_w, 0.0).reshape(T * K)[order]
    # Rows past the last group belong to absent experts: whatever the
    # grouped matmul left there is not a result.
    out = jnp.where((w != 0.0)[:, None], out.astype(jnp.float32) * w[:, None], 0.0)
    return jnp.zeros((T, xs.shape[-1]), jnp.float32).at[token_of].add(out)


def _experts_grouped_kernel(xs, top_w, local, valid, lp, spec, n_held):
    """The grouped form through ops/pallas/expert_ffn.expert_ffn_grouped:
    assignments sorted by held expert as above, each expert's rows padded to
    whole row tiles (dead rows and absent experts sort last and own no
    tile), one kernel over the (expert, row tile) pairs, then each token's
    weighted sum over the K result rows it owns: a gather, where the XLA
    form scatter-adds (13.1 -> 10.2 ms a layer at 8,192 tokens, 2.4 -> 2.0
    at 512, hybrid widths, my chip run, PR 45)."""
    T, K = local.shape
    A = T * K
    tm = grouped_row_tile(A, spec.n_experts)
    n_tiles = grouped_tiles(A, n_held, tm)
    flat = jnp.where(valid, local, n_held).reshape(A)  # absent ones last
    order = jnp.argsort(flat)
    sizes = jnp.bincount(flat, length=n_held + 1)[:n_held].astype(jnp.int32)
    tile_expert, n_work, first, left, pad_before = grouped_work_list(sizes, tm, n_tiles)
    # Row r of tile t is the (first[t] + r)-th sorted assignment, or padding
    # from left[t] on (every tile past the work list is padding).
    r = jnp.arange(tm, dtype=jnp.int32)[None, :]
    src = jnp.where(r < left[:, None], order[jnp.clip(first[:, None] + r, 0, A - 1)], 0)
    out = expert_ffn_grouped(
        xs[src.reshape(-1) // K], lp["we_up"], lp["we_down"], tile_expert, n_work,
        lp["we_gate"] if spec.activation == "silu_gated" else None,
        tm=tm,
    )
    # Where the i-th sorted assignment sits in the padded layout: i plus the
    # padding in front of its expert, a step function of i that rises at
    # each expert's offset (a masked sum over [A, Eh], no table lookup);
    # assignment (t, k) is the argsort(order)-th sorted one. One that is not
    # valid reads row 0 and weighs nothing: the padding rows and the tiles
    # the kernel never wrote are no one's.
    offset = jnp.cumsum(sizes) - sizes
    step = jnp.diff(pad_before, prepend=0)
    i = jnp.arange(A, dtype=jnp.int32)
    at_sorted = i + jnp.sum(
        jnp.where(i[:, None] >= offset[None, :], step[None, :], 0), axis=1)
    at = at_sorted[jnp.argsort(order)].reshape(T, K)
    picked = jnp.where(valid[..., None], out[jnp.where(valid, at, 0)], 0.0)
    return jnp.einsum("tk,tkd->td", jnp.where(valid, top_w, 0.0), picked)


def _combine(top_w, local, valid, n_held):
    """[T, Eh] float32: the weight of each held expert in each token's
    result, zero off the routing."""
    hot = jax.nn.one_hot(jnp.where(valid, local, n_held), n_held, dtype=jnp.float32)
    return (top_w[..., None] * hot).sum(1)


def _shared_expert(xs, lp, spec):
    up = qeinsum("td,df->tf", xs, lp["ws_up"])
    gate = qeinsum("td,df->tf", xs, lp["ws_gate"]) if "ws_gate" in lp else None
    out = qeinsum("tf,fd->td", _activate(up, gate, spec), lp["ws_down"]).astype(
        jnp.float32
    )
    if "ws_gate_scalar" in lp:  # the shared expert's own sigmoid gate, a scalar a token
        out = out * jax.nn.sigmoid(jnp.einsum(
            "td,do->to", xs, lp["ws_gate_scalar"], preferred_element_type=jnp.float32))
    return out


def moe_ffn(
    x: jnp.ndarray,  # [B, C, d]
    lp: Dict[str, Any],
    spec: ExpertsSpec,
    *,
    row_mask: Optional[jnp.ndarray] = None,  # [B, C] bool: live tokens
    want_stats: bool = False,
    use_kernel: bool = False,
):
    """Held experts' part of the routed result plus the shared expert.
    A token ``row_mask`` calls dead is routed to no expert: its row of the
    result holds the shared expert's part alone and it counts on no
    expert. Returns [B, C, d], and with ``want_stats`` also a float32 [3]:
    held experts that got a live token, most tokens on one held expert,
    tokens on held experts (the mean is that over the experts held)."""
    B, C, d = x.shape
    T = B * C
    xs = x.reshape(T, d)
    lo, hi = spec.held_
    n_held = hi - lo
    top_w, top_i = route(xs, lp, spec)
    local = top_i - lo
    valid = (local >= 0) & (local < n_held)
    if row_mask is not None:
        valid = valid & row_mask.reshape(T, 1)
    form, _ = form_of(use_kernel, (B, C), lp, spec)
    hit_listed = form == "hit_list"
    if hit_listed or want_stats:  # tokens on each held expert, [Eh] float32
        load = jnp.zeros((n_held + 1,), jnp.float32).at[
            jnp.where(valid, local, n_held).reshape(-1)
        ].add(1.0)[:n_held]
    if hit_listed:
        y = expert_ffn(
            xs, _combine(top_w, local, valid, n_held), lp["we_up"],
            lp["we_down"], *hit_list(load),
            lp["we_gate"] if spec.activation == "silu_gated" else None,
        )
    elif form == "dense":
        comb = _combine(top_w, local, valid, n_held)
        y = _experts_dense(xs, comb, lp, spec).astype(jnp.float32)
    elif form == "grouped_kernel":
        y = _experts_grouped_kernel(xs, top_w, local, valid, lp, spec, n_held)
    else:
        y = _experts_grouped(xs, top_w, local, valid, lp, spec, n_held)
    if spec.shared_d_ff:
        y = y + _shared_expert(xs, lp, spec)
    y = y.astype(x.dtype).reshape(B, C, d)
    if not want_stats:
        return y
    return y, jnp.stack([(load > 0).sum().astype(jnp.float32), load.max(), load.sum()])
