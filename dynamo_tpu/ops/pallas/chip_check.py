"""Compile every Pallas entry point on the chip and check it against XLA.

``python -m dynamo_tpu.ops.pallas.chip_check`` is chip_smoke.py's kernel
stage (its own process: the smoke's parent never touches JAX). For each
attention shape the worker's builtin presets produce it lowers
``paged_attention_decode_kernel`` and ``paged_attention_kernel`` with
``interpret=False`` over bf16 and int8-KV pools, and ``expert_ffn`` (the
hit-list expert kernel)
against ops/moe.py's dense form at the hybrid configuration's served widths
(64 tokens over 1, 8, 19, 29 and all 64 held experts hit, and 128 and 256
tokens with all hit) and at the latent configuration's (three matrices of
7680 x 2048, 16 held: 8, 64, 128 and 256 tokens with 2 and all 16 hit, and
the 32 decode slots on one expert): the rows that decide how many tokens
the kernel serves, each with both forms' ``us/call``; and
``expert_ffn_grouped`` (a family of its own: the grouped expert kernel of a
prefill step) beside ``ragged_dot``, both as ops/moe.py serves them, at the
three served widths from 512 to 8,192 tokens a step; and ``ssd_step`` (the
live-row state-update kernel of a recurrent layer's decode step against
``ops/mamba2.ssd_step`` over every slot, at the two served shapes); and
``sampler`` (no kernel: ``ops/sampling.sample_tokens`` beside the form without
its arg-max branch, at the five cells' slots and vocabularies). The decode kernel gets four more rows: the tail of
a prefix-hit prefill (one row, four tokens, eight pages), the
benchmark cell's decode at head_dim 64 (64 slots, a third live with ragged
contexts, the others empty with a stale position, 128 pages of table; bf16
and int8) and the all-live control at 8B width (32 rows within 200 tokens
of a 128-page table). One last row, ``kv_pool_layout``, is not a kernel's:
the serving pool's resident layout (``pool_layout_job``). Each compiled call is compared with
``_paged_attention_xla`` under ``jax.default_matmul_precision("highest")``.

Decode rows that compiled are then timed, one after another with the chip
to themselves (``us/call``: 24 calls chained in one jitted loop, best of
five); the interpreter rehearsal times nothing.

A refusal is RECORDED here (kernel, shape, first line of the compiler's
message), never served around: the table goes to CHANGES.md, a refused
preset gets its reason into the runner's start-up choice
(``DeviceRunner._choose_attention``) so its worker is not routed to the
kernel, and a refused or disagreeing kernel on the smoke model's own path
(``required`` rows) fails the stage.

Tolerance, one for every row: outputs are bf16 (8 significand bits, ulp
2^-8 relative), values are O(1), and the kernels accumulate in f32 in a
different order than the reference, so |kernel − reference| ≤ 4 bf16 ulps
of the largest magnitude in the output: ``atol = 4 · 2^-8 · max|ref|``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import tiny_config
from dynamo_tpu.runtime.device_observe import watched_jit
from dynamo_tpu.utils.jax_env import (
    configure_compile_cache,
    require_serving_platform,
)

BLOCK_SIZE = 16
ULP_BF16 = 2.0**-8


def _first_line(exc: BaseException) -> str:
    text = f"{type(exc).__name__}: {exc}".strip()
    for line in text.splitlines():
        if line.strip():
            return line.strip()[:300]
    return type(exc).__name__


def _agrees(out, ref, ulps: float) -> Optional[str]:
    """None when ``out`` is finite, shaped like ``ref`` and within the
    bound; otherwise what is wrong."""
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if out.shape != ref.shape:
        return f"shape {out.shape} != reference {ref.shape}"
    if not np.isfinite(out).all():
        return "non-finite values"
    atol = ulps * ULP_BF16 * max(float(np.abs(ref).max()), 1.0)
    err = float(np.abs(out - ref).max())
    if err > atol:
        return f"max |kernel-reference| {err:.4g} > {atol:.4g}"
    return None


def _attention_shapes() -> List[Dict[str, Any]]:
    """Distinct attention shapes of the worker's builtin presets. A preset
    with a sliding window or a softcap contributes a row that exercises
    them."""
    from dynamo_tpu.worker.__main__ import BUILTIN_CONFIGS

    seen: Dict[tuple, Dict[str, Any]] = {}
    for name, make in BUILTIN_CONFIGS.items():
        if name in ("tiny", "tiny-swa", "tiny-gdn"):
            continue  # float32 test shapes, never a TPU worker
        c = make()
        windows = [w for w in c.layer_windows() if w]
        key = (
            c.n_heads, c.n_kv_heads, c.head_dim_,
            bool(windows), float(c.attn_logit_softcap or 0.0),
        )
        row = seen.setdefault(key, {
            "presets": [], "H": c.n_heads, "KH": c.n_kv_heads,
            "D": c.head_dim_,
            # A window shorter than the test history so it masks keys.
            "window": min(windows[0], 100) if windows else 0,
            "softcap": float(c.attn_logit_softcap or 0.0),
        })
        row["presets"].append(name)
    return list(seen.values())


def _attention_inputs(
    shape, C: int, quantized: bool, B: int, P: int, rows: str = "uniform",
    block_size: int = BLOCK_SIZE,
):
    """``rows``: "uniform" — every row live, ``start`` uniform over the
    table; "ragged" — a third of the slots live, contexts lognormal around
    300 tokens as the benchmark's chat traffic gives, the others EMPTY
    (chunk_lens 0) with a stale position; "full" — every row live and
    within 200 tokens of the table's end (nothing for a live-span kernel
    to skip)."""
    from dynamo_tpu.ops.kv_quant import quantize_kv_chunk

    H, KH, D = shape["H"], shape["KH"], shape["D"]
    rng = np.random.default_rng(H * 1000 + D + C)
    NB = B * P + 1
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.bfloat16)
    pools = []
    for _ in range(2):
        dense = jnp.asarray(
            rng.standard_normal((NB, block_size, KH, D)), jnp.bfloat16
        )
        if quantized:
            q8, s = quantize_kv_chunk(dense)
            dense = {"q8": q8, "s": s.transpose(0, 2, 1)}
        pools.append(dense)
    tables = jnp.asarray(
        rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32)
    )
    T = P * block_size
    lens = np.full((B,), C, np.int32)
    if rows == "ragged":
        live = np.zeros(B, bool)
        live[rng.permutation(B)[: (B + 2) // 3]] = True
        ctx = np.clip(rng.lognormal(np.log(300), 0.6, B), 1, T - C)
        start = np.where(live, ctx, 4 * T).astype(np.int32)
        lens = np.where(live, C, 0).astype(np.int32)
    elif rows == "full":
        # (a chunk longer than the 200 tokens of slack ends inside it)
        low = max(T - 200, 0) if C < 200 else max(T - C - 200, 0)
        start = rng.integers(low, T - C, B).astype(np.int32)
    else:
        start = rng.integers(0, T - C, B).astype(np.int32)
    return q, pools[0], pools[1], tables, jnp.asarray(start), jnp.asarray(lens)


def _timed(row: Dict[str, Any], run, reference, ulps: float) -> Dict[str, Any]:
    """Compile+run one kernel call, then its reference; fill the row."""
    t0 = time.monotonic()
    try:
        out = jax.block_until_ready(run())
    except Exception as exc:  # the table's purpose: record the refusal
        row.update(status="refused", message=_first_line(exc))
    else:
        with jax.default_matmul_precision("highest"):
            bad = _agrees(out, reference(), ulps)
        row.update(
            status="compiled" if bad is None else "disagrees",
            message=bad or "",
        )
    row["seconds"] = round(time.monotonic() - t0, 1)
    return row


TIMED_CALLS = 24


def _build_timed_calls(call):
    """``TIMED_CALLS`` calls of one kernel chained through q in one jitted
    loop, so dispatch is paid once."""

    def chained(q, *rest):
        def body(_, qq):
            return qq + (call(qq, *rest) * 1e-6).astype(qq.dtype)

        return jax.lax.fori_loop(0, TIMED_CALLS, body, q)

    return watched_jit("chip_check.timed_calls", jax.jit(chained))


def _us_per_call(call, q, *rest) -> float:
    """Device time of one kernel call: best of five timed loops."""
    many = _build_timed_calls(call)
    jax.block_until_ready(many(q, *rest))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(many(q, *rest))
        best = min(best, time.perf_counter() - t0)
    return round(best / TIMED_CALLS * 1e6, 1)


def _us_per_call_two_lengths(build, first, carried: int) -> float:
    """Device time of one call as the difference of two chained loops
    (``TIMED_CALLS`` and five times as many, best of five each): a loop's one
    dispatch and read-back (~0.7 ms from this host, 28 us a call over 24
    calls) is in neither. ``build(calls)`` gives the jitted loop, which
    donates its argument; ``first()`` its first argument, and element
    ``carried`` of its result the next one."""

    def seconds(calls):
        many = build(calls)
        carry = jax.block_until_ready(many(first()))
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            carry = jax.block_until_ready(many(carry[carried]))
            best = min(best, time.perf_counter() - t0)
        return best

    short, long = seconds(TIMED_CALLS), seconds(5 * TIMED_CALLS)
    return round((long - short) / (4 * TIMED_CALLS) * 1e6, 1)


def _attention_job(interpret: bool, B: int, P: int):
    """The function that makes one paged-attention row of the table."""
    from dynamo_tpu.ops.attention import _paged_attention_xla
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel,
        paged_attention_kernel,
    )

    def job(shape, kernel_name, C, quantized, B=B, P=P, rows="uniform",
            block_size=BLOCK_SIZE):
        q, k, v, tables, start, lens = _attention_inputs(
            shape, C, quantized, B, P, rows, block_size
        )
        live = np.asarray(lens) > 0
        row = {
            "kernel": kernel_name,
            "shape": (
                f"H{shape['H']} KH{shape['KH']} D{shape['D']} C{C} "
                f"B{B} P{P} {'int8' if quantized else 'bf16'}-KV"
                + (f" window{shape['window']}" if shape["window"] else "")
                + (f" softcap{shape['softcap']:g}" if shape["softcap"] else "")
                + (f" {rows} {int(live.sum())} live" if rows != "uniform" else "")
                + (f" bs{block_size}" if block_size != BLOCK_SIZE else "")
            ),
            "presets": shape["presets"],
            # The smoke worker (qwen3-8b) serves a bf16 pool.
            "required": "qwen3-8b" in shape["presets"] and not quantized,
        }
        kw = dict(logit_cap=shape["softcap"], interpret=interpret)

        def decode(q, k, v, tables, start, lens):
            return paged_attention_decode_kernel(
                q, k, v, tables, start, shape["window"], lens, **kw
            )

        def run():
            if kernel_name == "paged_attention_decode":
                # empty slots return zeros, and the reference is not asked
                # about them (their stale position is past its table)
                return decode(q, k, v, tables, start, lens)[live]
            return paged_attention_kernel(
                q, k, v, tables, start, lens, shape["window"], **kw
            )

        def reference():
            return _paged_attention_xla(
                q[live], k, v, tables[live], start[live], lens[live],
                shape["window"], logit_cap=shape["softcap"],
            )

        row = _timed(row, run, reference, ulps=4)
        if C == 1 and row["status"] == "compiled" and not interpret:
            # timed by main() AFTER the pool: rows compile side by side,
            # and a timing must have the chip to itself
            row["time"] = functools.partial(
                _us_per_call, decode, q, k, v, tables, start, lens
            )
        return row

    return job


def attention_jobs(interpret: bool, B: int, P: int):
    job = _attention_job(interpret, B, P)
    shapes = _attention_shapes()
    jobs = [
        functools.partial(job, shape, kernel_name, C, quantized)
        for shape in shapes
        for kernel_name, C in (("paged_attention_decode", 1),
                               ("paged_attention", 16))
        for quantized in (False, True)
    ]
    # The benchmark cell's decode (64 slots, a third live, the widest table
    # its traffic meets) and the all-live control at 8B width.
    cell = dict(B=B, P=P) if interpret else dict(B=64, P=128)
    full = dict(B=B, P=P) if interpret else dict(B=32, P=128)
    for shape in shapes:
        if "qwen2.5-0.5b" in shape["presets"]:
            # the tail of a prefix-hit prefill: one row, four tokens, a
            # table narrower than a page group (a work list of one entry
            # halted the core: live_pages.live_work_list, PR 25)
            jobs.append(functools.partial(
                job, shape, "paged_attention_decode", 4, False, B=1, P=8,
            ))
            jobs += [
                functools.partial(
                    job, shape, "paged_attention_decode", 1, quantized,
                    rows="ragged", **cell,
                )
                for quantized in (False, True)
            ]
        if "qwen3-8b" in shape["presets"]:
            jobs.append(functools.partial(
                job, shape, "paged_attention_decode", 1, False,
                rows="full", **full,
            ))
            # The same rows and tokens under --block-size 128: a page is 8
            # times as heavy, so a grid step holds fewer of them.
            jobs += [
                functools.partial(
                    job, shape, "paged_attention_decode", 1, quantized,
                    rows="full", B=full["B"], P=max(full["P"] // 8, 1),
                    block_size=8 * BLOCK_SIZE,
                )
                for quantized in (False, True)
            ]
    return jobs


def swa_attention_jobs(interpret: bool):
    """The live-span decode kernel at the two head counts of a model that
    mixes sliding-window and full attention layers over pages of 128 tokens
    (Laguna-XS.2: 8 K/V heads of 128; 64 query heads in a sliding layer,
    window 512, whose table is the window's view of five slots; 48 in a full
    layer, over a 32 k context), each against the XLA form and timed."""
    job = _attention_job(interpret, B=4, P=4)
    base = {"KH": 8, "D": 128, "softcap": 0.0, "presets": ["laguna-xs.2-pp8"]}
    if interpret:
        return [
            functools.partial(job, dict(base, H=16, KH=2, D=128, window=40),
                              "paged_attention_decode", 1, False, B=4, P=5,
                              rows="full", block_size=16),
        ]
    slide, full = dict(base, H=64, window=512), dict(base, H=48, window=0)
    return [
        functools.partial(job, slide, "paged_attention_decode", 1, False, B=B, P=5,
                          rows="full", block_size=128)
        for B in (8, 64)
    ] + [
        functools.partial(job, full, "paged_attention_decode", 1, False, B=B, P=256,
                          rows="full", block_size=128)
        for B in (1, 8)
    ] + [
        # a turn's chunk over the window's view, and over a 32 k context
        functools.partial(job, slide, "paged_attention", 256, False, B=1, P=7,
                          rows="full", block_size=128),
        functools.partial(job, full, "paged_attention", 256, False, B=1, P=256,
                          rows="full", block_size=128),
    ]


_DRAWING = threading.Lock()  # rows run side by side: one draw of a stack


@functools.cache
def _drawn_experts(n_held, d, f, gated, dtype):
    keys = jax.random.split(jax.random.PRNGKey(37), 3)
    lp = {
        "we_up": jax.random.normal(keys[0], (n_held, d, f), dtype) * d**-0.5,
        "we_down": jax.random.normal(keys[1], (n_held, f, d), dtype) * f**-0.5,
    }
    if gated:
        lp["we_gate"] = jax.random.normal(keys[2], (n_held, d, f), dtype) * d**-0.5
    return lp


def _expert_weights(n_held, d, f, activation, dtype):
    """One stack of expert matrices a width, shared by every row (and both
    expert families) that reads it."""
    with _DRAWING:
        return _drawn_experts(n_held, d, f, activation == "silu_gated", dtype)


def expert_ffn_jobs(interpret: bool):
    """The hit-list expert kernel against the XLA dense form
    (ops/moe._experts_dense, what it replaces in a decode step), bf16, at
    the two served configurations' widths, which are the kernel's two
    layouts and two activations: the hybrid cell's (d 2688, f 1856, 64
    held, relu2, top-6: ``we_up`` resident with d minor) and the latent
    cell's (d 7680, f 2048, 16 held, gated silu, top-8: three matrices, f
    minor). ``hit`` experts get the tokens' choices; the others are never
    read. The one-expert row is ``required``: a one-entry work list halted
    the core once (PR 25)."""
    from dynamo_tpu.models.config import ExpertsSpec
    from dynamo_tpu.ops import moe
    from dynamo_tpu.ops.pallas.expert_ffn import expert_ffn, hit_list

    dtype = jnp.float32 if interpret else jnp.bfloat16

    def family(preset, d, f, n_held, K, activation, rows):
        spec = ExpertsSpec(n_experts=n_held, top_k=K, d_ff=f, activation=activation)
        weights = functools.partial(_expert_weights, n_held, d, f, activation, dtype)

        def job(T, hit):
            rng = np.random.default_rng(T * 100 + hit)
            xs = jnp.asarray(rng.standard_normal((T, d)), dtype)
            comb = np.zeros((T, n_held), np.float32)
            chosen = rng.permutation(n_held)[:hit]
            picks = np.full((T, K), n_held, np.int32)  # n_held: no expert
            for t in range(T):
                mine = rng.permutation(chosen)[:K]
                comb[t, mine] = rng.random(len(mine)) + 0.1
                picks[t, : len(mine)] = mine
            comb[:, chosen] += (comb[:, chosen].sum(0) == 0) * 0.5  # each one hit
            comb = jnp.asarray(comb)
            ids, count = hit_list(comb.sum(0))
            row = {
                "kernel": "expert_ffn",
                "shape": f"T{T} d{d} f{f} held{n_held} hit{hit} {activation} "
                         f"{dtype.__name__}",
                "presets": [preset],
                "required": hit == 1,
            }

            def kernel(xs, comb, lp, ids, count):
                return expert_ffn(
                    xs, comb, lp["we_up"], lp["we_down"], ids, count,
                    lp.get("we_gate"), interpret=interpret,
                ).astype(xs.dtype)

            def dense(xs, comb, lp, ids, count):
                return moe._experts_dense(xs, comb, lp, spec)

            row = _timed(
                row, lambda: kernel(xs, comb, weights(), ids, count),
                lambda: dense(xs, comb, weights(), ids, count), ulps=4,
            )
            if row["status"] == "compiled" and not interpret:

                def grouped(xs, comb, lp, ids, count):
                    # the grouped form over the same picks (their weights
                    # from comb; the rows comb padded to "each one hit" stay
                    # out: a timing, not a comparison)
                    local = jnp.asarray(picks)
                    valid = local < n_held
                    top_w = jnp.take_along_axis(
                        comb, jnp.minimum(local, n_held - 1), axis=1)
                    return moe._experts_grouped(
                        xs, top_w, local, valid, lp, spec, n_held).astype(xs.dtype)

                def both():
                    args = (xs, comb, weights(), ids, count)
                    row["message"] = (
                        f"xla dense {_us_per_call(dense, *args)} us/call, "
                        f"xla grouped {_us_per_call(grouped, *args)} us/call")
                    return _us_per_call(kernel, *args)

                row["time"] = both
            return row

        return [functools.partial(job, T, hit) for T, hit in rows]

    if interpret:
        return (
            family("tiny-hybrid", 128, 48, 8, 2, "relu2",
                   [(16, 1), (16, 3), (16, 8), (32, 8)])
            + family("tiny-mla", 128, 256, 4, 2, "silu_gated",
                     [(16, 1), (16, 2), (32, 4)])
        )
    return (
        family("nemotron-3-nano-30b-a3b-ep2", 2688, 1856, 64, 6, "relu2",
               [(64, 1), (64, 8), (64, 19), (64, 29), (64, 64), (128, 64), (256, 64)])
        + family("openpangu-ultra-moe-718b-ep16", 7680, 2048, 16, 8, "silu_gated",
                 [(32, 1)] + [(T, hit) for T in (8, 64, 128, 256) for hit in (2, 16)])
        # Laguna-XS.2: 256 small experts held whole (d 2048, f 512, gated
        # silu, f minor): two grid steps an expert, many short steps where
        # the other two cells take few long ones.
        + family("laguna-xs.2-pp8", 2048, 512, 256, 8, "silu_gated",
                 [(64, 1), (64, 16), (64, 57), (64, 128), (64, 256), (128, 128),
                  (128, 256), (256, 256)]
                 # a turn's chunk of 64 / 128 / 256 tokens, a third of the
                 # rows dead: the experts ``expert_ffn_grouped``'s rows at
                 # the same tokens hit (PR 51: the two forms side by side)
                 + [(64, 147), (128, 191), (256, 228)])
        # Qwen3-Next at EP-2: the same tile, top-10 of a router 512 wide
        # with 256 held, so a token brings ~5 assignments here.
        + family("qwen3-next-80b-a3b-ep2", 2048, 512, 256, 10, "silu_gated",
                 [(64, 119), (128, 163), (256, 211), (256, 256)])
    )


def expert_ffn_grouped_jobs(interpret: bool):
    """The grouped expert kernel (a prefill step) against ``ragged_dot``,
    both as ops/moe.py serves them, sort and scatter included, at the four
    served expert shapes: top-K over the router's whole width with uneven
    expert popularity (lognormal, as random routers give: the most loaded
    held expert gets several times the mean), a third of the rows dead as a
    padded batch has them, the experts past ``n_held`` absent. Where
    ``moe.grouped_reason`` keeps a width on ``ragged_dot`` the row says why
    and times that alone. Up to ``moe.DENSE_TOKENS_MAX`` tokens the row also
    times the hit-list kernel over the SAME routing: the two forms
    ``moe.form_of`` chooses between for a turn's chunk, side by side. The
    ``required`` rows are a work list of ONE row tile (PR 25) and a step
    whose every row is dead (a prefix-hit family's empty sibling)."""
    from dynamo_tpu.models.config import ExpertsSpec
    from dynamo_tpu.ops import moe
    from dynamo_tpu.ops.pallas.expert_ffn import (
        expert_ffn, expert_ffn_grouped, grouped_row_tile, hit_list,
    )

    dtype = jnp.float32 if interpret else jnp.bfloat16
    if interpret:
        moe.expert_ffn_grouped = functools.partial(expert_ffn_grouped, interpret=True)

    def family(preset, d, f, n_held, n_experts, K, activation, tokens):
        spec = ExpertsSpec(n_experts=n_experts, top_k=K, d_ff=f,
                           activation=activation, held=(0, n_held))
        weights = functools.partial(_expert_weights, n_held, d, f, activation, dtype)

        def job(T, one_tile=False, all_dead=False):
            rng = np.random.default_rng(T + n_held)
            xs = jnp.asarray(rng.standard_normal((T, d)), dtype)
            popularity = rng.normal(0.0, 1.0, n_experts)
            local = np.argsort(
                -(popularity + rng.gumbel(size=(T, n_experts))), axis=1)[:, :K]
            live = rng.random(T) < 2 / 3
            valid = (local < n_held) & live[:, None]
            if one_tile or all_dead:
                valid[:] = False
            if one_tile:  # three assignments in all, on held expert 1
                valid[:3, 0], local[:3, 0] = True, 1
            top_w = jnp.asarray(rng.random((T, K)) + 0.1, jnp.float32)
            local, valid = jnp.asarray(local.astype(np.int32)), jnp.asarray(valid)
            sizes = np.bincount(np.asarray(local)[np.asarray(valid)], minlength=n_held)
            row = {
                "kernel": "expert_ffn_grouped",
                "shape": f"T{T} d{d} f{f} held{n_held}/{n_experts} top{K} "
                         f"{activation} {dtype.__name__} "
                         f"tm{grouped_row_tile(T * K, n_experts)}: {int(sizes.sum())} rows on "
                         f"{int((sizes > 0).sum())} experts, most {int(sizes.max())}",
                "presets": [preset],
                "required": one_tile or all_dead,
            }

            def kernel(xs, lp):
                return moe._experts_grouped_kernel(
                    xs, top_w, local, valid, lp, spec, n_held).astype(xs.dtype)

            def grouped(xs, lp):
                return moe._experts_grouped(
                    xs, top_w, local, valid, lp, spec, n_held).astype(xs.dtype)

            why = moe.grouped_reason(True, weights(), spec)
            if why is not None:
                # (a width the kernel is not built for is no failed row)
                row.update(status="refused", message=why, seconds=0.0, required=False)
                if not interpret:
                    row["time"] = lambda: row.update(
                        message=f"{why}; xla grouped "
                        f"{_us_per_call(grouped, xs, weights())} us/call")
                return row
            def dense(xs, lp):
                # 1,024 tokens at a time: [Eh, T, f] in float32 is the
                # size of the stack at 8,192 (``ragged_dot``, a Mosaic
                # kernel itself, does not lower at ``highest`` precision)
                comb = moe._combine(top_w, local, valid, n_held)
                return jnp.concatenate([
                    moe._experts_dense(xs[i:i + 1024], comb[i:i + 1024], lp, spec)
                    for i in range(0, T, 1024)])

            row = _timed(row, lambda: kernel(xs, weights()),
                         lambda: dense(xs, weights()), ulps=4)
            if row["status"] == "compiled" and not interpret:

                def listed(xs, lp):
                    comb = moe._combine(top_w, local, valid, n_held)
                    return expert_ffn(
                        xs, comb, lp["we_up"], lp["we_down"], *hit_list(comb.sum(0)),
                        lp.get("we_gate")).astype(xs.dtype)

                def both():
                    row["message"] = (
                        f"xla grouped {_us_per_call(grouped, xs, weights())} us/call")
                    if T <= moe.DENSE_TOKENS_MAX:
                        row["message"] += (
                            f", hit list {_us_per_call(listed, xs, weights())} us/call")
                    return _us_per_call(kernel, xs, weights())

                row["time"] = both
            return row

        return [functools.partial(job, T) for T in tokens] + [
            functools.partial(job, tokens[0], True),
            functools.partial(job, tokens[1], all_dead=True)]

    if interpret:
        return (
            family("tiny-hybrid", 128, 48, 8, 16, 2, "relu2", [320, 512])
            + family("tiny-swa", 128, 128, 8, 8, 2, "silu_gated", [320, 64])
        )
    return (
        # (64, 128 and 256 tokens: where the two kernels cross at each
        # shape, what ops/moe.form_of's rule for a turn's chunk rests on)
        family("nemotron-3-nano-30b-a3b-ep2", 2688, 1856, 64, 128, 6, "relu2",
               [512, 256, 128, 1024, 2048, 4096, 8192])
        + family("laguna-xs.2-pp8", 2048, 512, 256, 256, 8, "silu_gated",
                 [512, 256, 64, 128, 1024, 2048])
        + family("qwen3-next-80b-a3b-ep2", 2048, 512, 256, 512, 10, "silu_gated",
                 [512, 256, 64, 128])
        + family("openpangu-ultra-moe-718b-ep16", 7680, 2048, 16, 256, 8,
                 "silu_gated", [512, 2048])
    )


def mla_jobs(interpret: bool):
    """The absorbed latent-attention kernel (``mla_paged_decode``) against
    its XLA oracle at the served widths: 128 heads over a latent pool of
    640-lane rows (c_kv 512 + rotary key 64, lanes past 576 zero) in
    128-token pages. Decode rows at the cell's context, a ONE-row decode
    (``required``: a one-entry work list halted the core once, PR 25), a
    question chunk over a cached document, and two steps whose every row is
    empty (``required``: what the engine runs to compile a prefix-hit prefill
    program's sibling rows buckets, admission.run_pending_family). The timed rows print the
    kernel's own roofline: bytes of the live pages at the HBM peak against
    the FLOPs at the bf16 peak."""
    from dynamo_tpu.ops.attention import _mla_paged_xla
    from dynamo_tpu.ops.pallas.mla_paged import mla_paged_decode

    H, R, W, bs = (4, 128, 256, 16) if interpret else (128, 512, 640, 128)
    dtype = jnp.float32 if interpret else jnp.bfloat16
    scale = 192**-0.5

    def job(B, C, context, P, required=False, empty=False):
        rng = np.random.default_rng(B * 1000 + C)
        NB = B * P + 1
        pool = jnp.zeros((NB, bs, W), dtype).at[..., : R + 64].set(
            jnp.asarray(rng.standard_normal((NB, bs, R + 64)), dtype))
        tables = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)
        q = jnp.zeros((B, C, H, W), dtype).at[..., : R + 64].set(
            jnp.asarray(rng.standard_normal((B, C, H, R + 64)), dtype))
        start = jnp.asarray(context - rng.integers(0, bs, B), jnp.int32)
        lens = jnp.full((B,), C, jnp.int32)
        if empty:  # a family warm-up's step: every row of length 0, no work
            start, lens = jnp.zeros_like(start), jnp.zeros_like(lens)
        live = (lens > 0)[:, None, None, None]  # an empty row's result is no result
        row = {
            "kernel": "mla_paged_decode",
            "shape": f"B{B} C{C} H{H} ctx{0 if empty else context} P{P} bs{bs} W{W} "
                     f"{dtype.__name__}" + (" every row empty" if empty else ""),
            "presets": ["openpangu-ultra-moe-718b-ep16"],
            "required": required,
        }

        def kernel(q, pool, tables, start, lens):
            out = mla_paged_decode(q, pool, tables, start, lens, v_width=R,
                                   sm_scale=scale, interpret=interpret)
            return jnp.where(live, out, 0)

        def xla(q, pool, tables, start, lens):
            out = _mla_paged_xla(q, pool, tables, start, lens, v_width=R, sm_scale=scale)
            return jnp.where(live, out, 0)

        args = (q, pool, tables, start, lens)
        row = _timed(row, lambda: kernel(*args), lambda: xla(*args), ulps=8)
        if row["status"] == "compiled" and not interpret and not empty:

            def timed():
                # (the timed loop feeds each call's result back into q)
                us = _us_per_call(
                    lambda *a: jnp.pad(kernel(*a), [(0, 0)] * 3 + [(0, W - R)]), *args)
                keys = float(np.asarray(start).sum() + B * C)
                bytes_, flops = keys * W * 2, 2.0 * keys * C * H * (W + R)
                least = max(bytes_ / 819e9, flops / 197e12) * 1e6
                row["message"] = (
                    f"least {least:.0f} us ({bytes_ / 819e9 * 1e6:.0f} bytes, "
                    f"{flops / 197e12 * 1e6:.0f} flops): {100 * least / us:.1f}% of roofline")
                return us

            row["time"] = timed
        return row

    if interpret:
        return [functools.partial(job, 3, 1, 40, 4), functools.partial(job, 1, 1, 40, 4, True),
                functools.partial(job, 2, 16, 40, 4),
                functools.partial(job, 2, 16, 40, 4, True, True)]
    return [
        functools.partial(job, 24, 1, 16500, 136),
        functools.partial(job, 32, 1, 16500, 136),
        functools.partial(job, 1, 1, 16500, 136, True),
        functools.partial(job, 1, 128, 16384, 136),
        functools.partial(job, 2, 256, 16384, 136),
        functools.partial(job, 2, 128, 16384, 136, True, True),
        functools.partial(job, 8, 256, 16384, 136, True, True),
    ]


def _build_ssd_step_xla(x, dt, A, Bm, Cm):
    """``ops/mamba2.ssd_step`` over every slot (``dt`` 0 on the dead ones),
    jitted over the state alone."""
    from dynamo_tpu.ops import mamba2 as m2

    return watched_jit(
        "chip_check.ssd_step_xla",
        jax.jit(lambda state: m2.ssd_step(x, dt, A, Bm, Cm, state)))


def _build_gdn_step_xla(q, k, v, g, beta):
    """``ops/gated_delta.gdn_step`` over every slot (``g`` and ``beta`` 0 on
    the dead ones), jitted over the state alone."""
    from dynamo_tpu.ops import gated_delta as gd

    return watched_jit(
        "chip_check.gdn_step_xla",
        jax.jit(lambda state: gd.gdn_step(q, k, v, g, beta, state)))


def _build_state_calls(step, calls: int):
    """``calls`` calls of a state update (state -> (y, state)) chained
    through the donated state in one jitted loop."""

    def chained(state):
        def body(_, carry):
            y, new = step(carry[1])
            return carry[0] + y[0, 0, 0], new

        return jax.lax.fori_loop(0, calls, body, (jnp.float32(0), state))

    return watched_jit(
        "chip_check.timed_state_calls", jax.jit(chained, donate_argnums=(0,)))


def _state_kernel_row(row, kernel, xla, state, dead, moved: float, interpret: bool):
    """Fill a live-row state kernel's row of the table: ``kernel`` and ``xla``
    (state -> (out, state), the kernel's call donating) compared on the chip:
    the live rows' state and output to float32 rounding, the dead rows' state
    BIT FOR BIT and their output zero; then, not under the interpreter, both
    timed as the difference of two chained loops, with the ``moved`` bytes
    (the live rows' state, read and written) as a share of 819 GB/s."""
    t0 = time.monotonic()
    try:
        out, new = jax.block_until_ready(kernel(state + 0.0))  # the call donates
    except Exception as exc:
        row.update(status="refused", message=_first_line(exc))
    else:
        out_ref, new_ref = xla(state)
        out, new, out_ref, new_ref, old = (
            np.asarray(a) for a in (out, new, out_ref, new_ref, state))
        bad = None
        if not np.array_equal(new[dead], old[dead]):
            bad = "a dead row's state moved"
        elif out[dead].any():
            bad = "a dead row's output is not zero"
        # (the XLA form's output of a dead row is its old state's read-out)
        for name, got, ref in (("state", new, new_ref), ("output", out[~dead], out_ref[~dead])):
            if bad is not None or not got.size:
                continue
            err = float(np.abs(got - ref).max())
            if not np.isfinite(got).all() or err > 1e-5 * max(float(np.abs(ref).max()), 1.0):
                bad = f"{name}: max |kernel-reference| {err:.4g}"
        row.update(status="compiled" if bad is None else "disagrees", message=bad or "")
    row["seconds"] = round(time.monotonic() - t0, 1)
    if row["status"] == "compiled" and not interpret:

        def us_per_call(step):
            # (the loop's dispatch is as much as one live row's update)
            return _us_per_call_two_lengths(
                functools.partial(_build_state_calls, step), lambda: state + 0.0, 1)

        def timed():
            us = us_per_call(kernel)
            row["message"] = (
                f"{moved / 1e6:.1f} MB moved, {100 * moved / (us * 1e-6) / 819e9:.1f}% of "
                f"819 GB/s; xla over every slot {us_per_call(xla)} us/call")
            return us

        row["time"] = timed
    return row


def ssd_step_jobs(interpret: bool):
    """The live-row state-update kernel (``ssd_step_live``) against
    ``ops/mamba2.ssd_step`` over every slot (dead slots given dt = 0: what it
    replaces in a decode step), float32, at the two served shapes: the hybrid
    cell's Mamba-2 layers (64 slots, 64 heads x 64 x 128, B and C in 8
    groups) and the sparse cell's lightning layers (32 slots, 32 heads x 128
    x 128, B and C per head), a scattered set of rows live. Compared on the
    chip: the live rows' state and y to float32 rounding, the dead rows'
    state BIT FOR BIT and their y zero. The one-live-row case is
    ``required`` (a one-entry work list halted the core once, PR 25). Timed
    rows print the bytes the live rows' state moves (read and written) and
    their share of 819 GB/s beside the XLA form's us/call; both forms timed
    as the difference of two chained loops (24 and 120 calls), so that the
    loop's one dispatch is in neither."""
    from dynamo_tpu.ops.pallas.ssd_step import live_row_list, ssd_step_live

    def job(preset, slots, H, P, N, G, live, heads_a_step=None):
        rng = np.random.default_rng(slots * 1000 + live)
        f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
        x, Bm, Cm = f32(slots, H, P), f32(slots, G, N), f32(slots, G, N)
        dt = jax.nn.softplus(f32(slots, H))
        A = -jnp.exp(f32(H))
        state = f32(slots, H, P, N)
        active = np.zeros(slots, np.int32)
        active[rng.permutation(slots)[:live]] = 1
        rows = live_row_list(jnp.asarray(active))
        dead = active == 0
        row = {
            "kernel": "ssd_step_live",
            "shape": f"slots{slots} H{H} P{P} N{N} G{G} live{live} float32"
                     + (f" heads_a_step{heads_a_step}" if heads_a_step else ""),
            "presets": [preset],
            "required": live == 1,
        }

        def kernel(state):
            return ssd_step_live(x, dt, A, Bm, Cm, state, *rows,
                                 heads_a_step=heads_a_step, interpret=interpret)

        xla = _build_ssd_step_xla(x, jnp.where(rows.mask[:, None], dt, 0.0), A, Bm, Cm)
        return _state_kernel_row(
            row, kernel, xla, state, dead, 2.0 * live * H * P * N * 4, interpret)

    if interpret:
        return [functools.partial(job, "tiny-hybrid", 6, 8, 16, 128, 2, live)
                for live in (0, 1, 3, 6)] + [
            functools.partial(job, "tiny-sala", 5, 4, 8, 128, 4, live, 2) for live in (1, 5)]
    hybrid = ("nemotron-3-nano-30b-a3b-ep2", 64, 64, 64, 128, 8)
    sala = ("minicpm-sala-pp4", 32, 32, 128, 128, 32)
    return (
        [functools.partial(job, *hybrid, live) for live in (1, 14, 20, 64)]
        + [functools.partial(job, *sala, live) for live in (1, 10, 32)]
        # the head tile: a whole row a step is what is served
        + [functools.partial(job, *hybrid, 14, hs) for hs in (32, 16, 8)]
        + [functools.partial(job, *sala, 10, hs) for hs in (16, 8)]
    )


def gdn_step_jobs(interpret: bool):
    """The live-row gated-delta-rule kernel (``gdn_step_live``) against
    ``ops/gated_delta.gdn_step`` over every slot (dead slots given g = 0 and
    beta = 0: what it replaces in a decode step), float32, at the served shape
    (64 slots, 32 value heads x 128 keys x 128 values), a scattered set of
    rows live. Compared and timed as ``ssd_step_jobs`` does: the live rows'
    state and output to float32 rounding, the dead rows' state BIT FOR BIT and
    their output zero; the one-live-row case is ``required``."""
    from dynamo_tpu.ops import gated_delta as gd
    from dynamo_tpu.ops.pallas.gdn_step import gdn_step_live
    from dynamo_tpu.ops.pallas.ssd_step import live_row_list

    def job(preset, slots, H, Dk, Dv, live, heads_a_step=None):
        rng = np.random.default_rng(slots * 1000 + live)
        f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
        q, k = gd.prepare_qk(f32(slots, H, Dk), f32(slots, H, Dk), 1)
        v, state = f32(slots, H, Dv), f32(slots, H, Dk, Dv)
        active = np.zeros(slots, np.int32)
        active[rng.permutation(slots)[:live]] = 1
        rows = live_row_list(jnp.asarray(active))
        on = rows.mask[:, None]
        g = jnp.where(on, -0.05 * jax.nn.softplus(f32(slots, H)), 0.0)
        beta = jnp.where(on, jax.nn.sigmoid(f32(slots, H)), 0.0)
        dead = active == 0
        row = {
            "kernel": "gdn_step_live",
            "shape": f"slots{slots} H{H} Dk{Dk} Dv{Dv} live{live} float32"
                     + (f" heads_a_step{heads_a_step}" if heads_a_step else ""),
            "presets": [preset],
            "required": live == 1,
        }

        def kernel(state):
            return gdn_step_live(q, k, v, g, beta, state, *rows,
                                 heads_a_step=heads_a_step, interpret=interpret)

        xla = _build_gdn_step_xla(q, k, v, g, beta)
        return _state_kernel_row(
            row, kernel, xla, state, dead, 2.0 * live * H * Dk * Dv * 4, interpret)

    if interpret:
        return [functools.partial(job, "tiny-gdn", 6, 4, 16, 128, live) for live in (0, 1, 3, 6)]
    served = ("qwen3-next-80b-a3b-ep2", 64, 32, 128, 128)
    return (
        [functools.partial(job, *served, live) for live in (1, 10, 64)]
        + [functools.partial(job, *served, 10, hs) for hs in (16, 8)]
    )


def gdn_attention_jobs(interpret: bool):
    """Both paged-attention kernels at a head of 256 lanes (two lane tiles),
    16 query heads over 2 K/V heads, pages of 128 tokens (Qwen3-Next's one
    full layer of four): the decode kernel over tables of 32 pages (a 4 k
    row) and 264 (a 33 k row), the chunk kernel for a turn's 256 queries over
    both, each against the XLA form and the decode rows timed."""
    job = _attention_job(interpret, B=4, P=4)
    shape = {"H": 16, "KH": 2, "D": 256, "window": 0, "softcap": 0.0,
             "presets": ["qwen3-next-80b-a3b-ep2"]}
    if interpret:
        return [functools.partial(job, dict(shape, H=4, D=256), "paged_attention_decode", 1,
                                  False, B=4, P=5, rows="full", block_size=16)]
    return [
        functools.partial(job, shape, "paged_attention_decode", 1, False, B=B, P=P,
                          rows="full", block_size=128)
        for B, P in ((10, 32), (10, 264), (64, 32))
    ] + [
        functools.partial(job, shape, "paged_attention", 256, False, B=1, P=P,
                          rows="full", block_size=128)
        for P in (32, 264)
    ]


def _build_sampler_call(name: str, sample):
    return watched_jit(f"chip_check.{name}", jax.jit(sample))


def _build_sampler_calls(sample, calls: int):
    """``calls`` sampler calls chained in one jitted loop. Each call's picked
    token is struck out of its row's logits (a 1-element write a row, in
    place), so that no call is loop-invariant and none can be hoisted."""

    def chained(logits):
        rows = jnp.arange(logits.shape[0])
        low = jnp.finfo(logits.dtype).min

        def body(_, carry):
            logits, _ = carry
            toks = sample(logits)
            return logits.at[rows, toks].set(low), toks

        return jax.lax.fori_loop(
            0, calls, body, (logits, jnp.zeros(logits.shape[0], jnp.int32)))

    return watched_jit(
        "chip_check.timed_sampler_calls", jax.jit(chained, donate_argnums=(0,)))


def sampler_jobs(interpret: bool):
    """``ops/sampling.sample_tokens`` alone (no kernel of ours: the table's
    one XLA-only family), at the five cells' slots and vocabularies, bfloat16
    logits, a third of the slots live and the dead ones holding the start-up
    temperature 1.0: every live row greedy (the arg-max branch), one live row
    sampling, every live row sampling (both the candidates' branch), each
    beside the form it replaces, ``sample_candidates`` over every slot
    whatever the temperatures. Compared on the chip: a row that samples and a
    greedy row of a sampling call get the parent's token exactly; a row of
    the arg-max branch gets numpy's arg-max (the lowest index of the largest
    logit), and a live one's logit is the one the parent's token has (the two
    may differ in index where logits tie). Timed as the difference of two chained loops
    (24 and 120 calls)."""
    from dynamo_tpu.ops import sampling

    def job(preset, slots, vocab, mix):
        rng = np.random.default_rng(slots * 1000003 + vocab)
        logits = jnp.asarray(rng.standard_normal((slots, vocab)), jnp.bfloat16)
        live = np.zeros(slots, bool)
        live[rng.permutation(slots)[: max(slots // 3, 2)]] = True
        temp = np.where(live, 0.0, 1.0).astype(np.float32)  # stale on the dead
        if mix == "one sampled":
            temp[np.flatnonzero(live)[0]] = 0.8
        elif mix == "all sampled":
            temp[live] = 0.8
        key = jax.random.PRNGKey(vocab)
        temp, salts = jnp.asarray(temp), jnp.arange(slots, dtype=jnp.int32)
        pos = jnp.full((slots,), 7, jnp.int32)
        top_k, top_p = jnp.full((slots,), 40, jnp.int32), jnp.full((slots,), 0.95, jnp.float32)
        row = {
            "kernel": "sample_tokens",
            "shape": f"slots{slots} vocab{vocab} bfloat16 live{int(live.sum())} {mix}",
            "presets": [preset],
            "required": False,
        }

        def new(logits):
            return sampling.sample_tokens(
                logits, key, temp, top_k, top_p, salts=salts, positions=pos,
                live=jnp.asarray(live))

        def parent(logits):
            return sampling.sample_candidates(
                logits, None, temp, top_k, top_p, None,
                sampling.fold_row_keys(key, salts, pos))

        new = _build_sampler_call("sample_tokens", new)
        parent = _build_sampler_call("sample_candidates", parent)
        t0 = time.monotonic()
        try:
            got, want = np.asarray(new(logits)), np.asarray(parent(logits))
        except Exception as exc:
            row.update(status="refused", message=_first_line(exc))
        else:
            host = np.asarray(logits.astype(jnp.float32))
            at = lambda toks: host[np.arange(slots), toks]
            if mix == "all greedy":
                ok = (np.array_equal(got, host.argmax(-1))
                      and np.array_equal(at(got)[live], at(want)[live]))
            else:
                ok = np.array_equal(got, want)
            bad = None if ok else (
                f"{int((got != want)[live].sum())} of {int(live.sum())} live rows' "
                "tokens are not the parent's")
            row.update(status="compiled" if bad is None else "disagrees",
                       message=bad or "")
        row["seconds"] = round(time.monotonic() - t0, 1)
        if row["status"] == "compiled" and not interpret:

            def us_per_call(sample):
                return _us_per_call_two_lengths(
                    functools.partial(_build_sampler_calls, sample), lambda: logits + 0, 0)

            def timed():
                us = us_per_call(new)
                row["message"] = (
                    f"{slots * vocab * 2 / 1e6:.1f} MB of logits; parent form "
                    f"(candidates over every slot) {us_per_call(parent)} us/call")
                return us

            row["time"] = timed
        return row

    mixes = ("all greedy", "one sampled", "all sampled")
    if interpret:
        return [functools.partial(job, "tiny", 6, 1000, mix) for mix in mixes]
    cells = [("qwen2.5-0.5b", 64, 151936), ("laguna-xs.2-pp8", 64, 100352),
             ("nemotron-3-nano-30b-a3b-ep2", 64, 65536),
             ("minicpm-sala-pp4", 32, 73448),
             ("openpangu-ultra-moe-718b-ep16", 32, 19200)]
    return [functools.partial(job, *cell, mix) for cell in cells for mix in mixes]


def whole_array_ops(hlo_text: str, array) -> List[str]:
    """Opcodes of a compiled program's optimised HLO whose result is an array
    of ``array``'s shape and dtype, other than those that move none of it: a
    resident pool re-laid for a kernel's operand shows as ``copy``, a
    recurrent state passed through XLA whole as ``fusion``."""
    import re

    dtype = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(array.dtype).name]
    dims = ",".join(str(d) for d in array.shape)
    ops = re.findall(rf"= {dtype}\[{re.escape(dims)}\][^ ]* ([a-z\-]+)\(", hlo_text)
    return [op for op in ops
            if op not in ("parameter", "get-tuple-element", "bitcast", "tuple")]


def whole_pool_copies(hlo_text: str, pool) -> int:
    """``copy`` instructions whose result is a whole per-layer KV pool
    (``pool``: its shape and dtype)."""
    return whole_array_ops(hlo_text, pool).count("copy")


def pool_layout_job(interpret: bool):
    """``kv_pool_layout``: the serving pool of a head-size-64 model is
    resident in the layout the kernels read. On the chip, at the shape of
    qwen2.5-0.5b with 2,048 blocks and 64 slots: the optimised HLO of the
    runner's decode burst and prefill step holds no copy of a whole
    per-layer pool, the donated pools alias in and out (aliased bytes =
    resident bytes, plus the few small carries), and the logits of a
    ragged two-row prefill and 16 greedy steps are equal, bit for bit,
    with the pool held at the lane tile and at the logical head size. The
    interpreter rehearsal runs the same control flow at the tiny shape on
    the XLA path and says nothing about layouts."""
    from dynamo_tpu.engines.tpu.engine import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import qwen2_500m_config
    from dynamo_tpu.runtime.device_observe import tree_device_bytes

    cfg = tiny_config() if interpret else qwen2_500m_config()
    NB, S, P, steps = (64, 4, 4, 4) if interpret else (2048, 64, 64, 16)
    lens = np.array([40, 23] if interpret else [200, 137], np.int32)
    C = 64 if interpret else 256

    def check() -> str:
        runner = DeviceRunner(JaxEngineArgs(
            config=cfg, num_kv_blocks=NB, max_num_seqs=S,
            max_model_len=P * BLOCK_SIZE, prefill_chunk=C,
        ))
        uk = runner.use_kernel
        pool = runner.k_cache[0]
        resident = tree_device_bytes((runner.k_cache, runner.v_cache))
        st, i32 = runner.slot_state, np.int32
        decode = runner._build_decode_fn().lower(
            runner.params, runner.lora, runner.k_cache, runner.v_cache,
            st["tokens"], st["pos"], st["active"], runner.slot_tables[:, :P],
            st["salts"], runner.rng, st["temp"], st["topk"], st["topp"],
            st["adapter_ids"],
        ).compile()
        B = len(lens)
        prefill = runner._build_step_fn(first_chunk=True).lower(
            runner.params, runner.lora, runner.k_cache, runner.v_cache,
            np.zeros((B, C), i32), np.zeros(B, i32), lens,
            np.zeros((B, C // BLOCK_SIZE), i32), np.zeros(B, i32), runner.rng,
            np.ones(B, np.float32), np.zeros(B, i32), np.ones(B, np.float32),
            np.zeros(B, i32), None, None,
        ).compile()
        copies = [whole_pool_copies(c.as_text(), pool) for c in (decode, prefill)]
        aliased = decode.memory_analysis().alias_size_in_bytes

        def _build_forward(first_chunk):
            return watched_jit("chip_check.pool_layout", jax.jit(
                lambda p, t, s, l, bt, k, v: llama.forward_paged(
                    p, cfg, t, s, l, bt, k, v, use_kernel=uk,
                    first_chunk=first_chunk,
                ),
                donate_argnums=(5, 6),
            ))

        first, step = _build_forward(True), _build_forward(False)
        rng = np.random.default_rng(31)
        toks = rng.integers(0, cfg.vocab_size, (B, C)).astype(i32)
        tables = np.arange(1, 1 + B * P, dtype=i32).reshape(B, P)

        def serve(k, v):
            out = []
            logits, k, v = first(
                runner.params, toks, np.zeros(B, i32), lens, tables, k, v
            )
            out.append(np.asarray(logits, np.float32))
            pos = lens.copy()
            for _ in range(steps):
                tok = out[-1].argmax(-1).astype(i32)[:, None]
                logits, k, v = step(
                    runner.params, tok, pos, np.ones(B, i32), tables, k, v
                )
                out.append(np.asarray(logits, np.float32))
                pos = pos + 1
            return np.stack(out)

        shape = pool.shape[:-1] + (cfg.head_dim_,)
        logical = lambda: tuple(  # noqa: E731
            jnp.zeros(shape, pool.dtype) for _ in range(cfg.n_layers)
        )
        served = serve(runner.k_cache, runner.v_cache)  # donated: last use
        reference = serve(logical(), logical())
        differ = int((served != reference).sum())
        message = (
            f"{pool.dtype.name}{list(pool.shape)} for a head of "
            f"{cfg.head_dim_}; whole-pool copies decode/prefill "
            f"{copies[0]}/{copies[1]}; aliased {aliased} B of {resident} B "
            f"resident; {differ} of {served.size} logits differ from the "
            f"logical pool's, max |difference| "
            f"{float(np.abs(served - reference).max()):g} "
            f"(max |logit| {float(np.abs(reference).max()):.4g})"
        )
        bad = differ or not np.isfinite(served).all()
        if not interpret:  # layouts and aliasing are the chip's
            bad = bad or any(copies) or not (
                resident <= aliased < resident + (1 << 20)
            )
        if bad:
            raise AssertionError(message)
        return message

    def job():
        row = {
            "kernel": "kv_pool_layout",
            "shape": (
                f"{cfg.name} NB{NB} S{S} P{P}: decode burst, prefill step, "
                f"{int(lens[0])}+{int(lens[1])}-token prefill + {steps} "
                "greedy steps"
            ),
            "presets": [cfg.name],
            "required": True,
        }
        t0 = time.monotonic()
        try:
            row.update(status="compiled", message=check())
        except AssertionError as exc:
            row.update(status="disagrees", message=str(exc))
        except Exception as exc:  # recorded like a kernel's refusal
            row.update(status="refused", message=_first_line(exc))
        row["seconds"] = round(time.monotonic() - t0, 1)
        return row

    return job


def main() -> int:
    ap = argparse.ArgumentParser("pallas kernels: compile on the chip, check vs XLA")
    ap.add_argument(
        "--interpret", action="store_true",
        help="CPU rehearsal of this script's control flow: Pallas "
        "interpreter, small batch and widths. Says nothing about Mosaic.",
    )
    ap.add_argument("--out", default=None, help="also write the table as JSON here")
    ap.add_argument(
        "--only", default=None, metavar="FAMILY",
        help="only this family's rows: a builder's partial table",
    )
    args = ap.parse_args()

    configure_compile_cache()
    platform = require_serving_platform()
    if (platform == "tpu") == args.interpret:
        print(
            f"platform is {platform}: run with --interpret on the CPU and "
            "without it on the chip", file=sys.stderr,
        )
        return 2
    if args.interpret:
        families = {
            "paged_attention": lambda: attention_jobs(True, B=4, P=4),
            "expert_ffn": lambda: expert_ffn_jobs(True),
            "expert_ffn_grouped": lambda: expert_ffn_grouped_jobs(True),
            "mla_paged_decode": lambda: mla_jobs(True),
            "ssd_step": lambda: ssd_step_jobs(True),
            "gdn_step": lambda: gdn_step_jobs(True),
            "paged_attention_gdn": lambda: gdn_attention_jobs(True),
            "sampler": lambda: sampler_jobs(True),
            "paged_attention_swa": lambda: swa_attention_jobs(True),
        }
    else:
        from dynamo_tpu.worker.__main__ import build_parser

        worker = build_parser().parse_args([])
        families = {
            "paged_attention": lambda: attention_jobs(
                False, B=worker.max_num_seqs, P=16),
            "expert_ffn": lambda: expert_ffn_jobs(False),
            "expert_ffn_grouped": lambda: expert_ffn_grouped_jobs(False),
            "mla_paged_decode": lambda: mla_jobs(False),
            "ssd_step": lambda: ssd_step_jobs(False),
            "gdn_step": lambda: gdn_step_jobs(False),
            "paged_attention_gdn": lambda: gdn_attention_jobs(False),
            "sampler": lambda: sampler_jobs(False),
            "paged_attention_swa": lambda: swa_attention_jobs(False),
        }
    families["kv_pool_layout"] = lambda: []  # one row, after the timings
    if args.only is not None and args.only not in families:
        ap.error(f"--only takes one of {', '.join(families)}")
    jobs = [job for name, make in families.items()
            if args.only in (None, name) for job in make()]
    # Mosaic and XLA compile on the host with the GIL released: rows
    # compile side by side instead of one after another (32 rows took
    # 673 s in sequence on the chip machine, my chip run, PR 21).
    t0 = time.monotonic()
    with ThreadPoolExecutor(max_workers=max(1, (os.cpu_count() or 2) - 1)) as pool:
        rows = list(pool.map(lambda job: job(), jobs))
    wall = time.monotonic() - t0
    for r in rows:
        timer = r.pop("time", None)
        r["us_per_call"] = timer() if timer else None
    # After the timings: it holds a model and three sets of pools.
    if args.only in (None, "kv_pool_layout"):
        rows.append({"us_per_call": None, **pool_layout_job(args.interpret)()})

    dev = jax.devices()[0]
    print(f"kernel table on {dev.platform} / {dev.device_kind}"
          + (" (Pallas INTERPRETER — not Mosaic)" if args.interpret else ""))
    print("| kernel | shape | presets | result | compile+check s | us/call |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        result = r["status"] + (f": {r['message']}" if r["message"] else "")
        print(f"| {r['kernel']} | {r['shape']} | {', '.join(r['presets'])} "
              f"| {result} | {r['seconds']} | {r['us_per_call'] or ''} |")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({
                "platform": dev.platform, "device_kind": dev.device_kind,
                "interpret": args.interpret, "rows": rows,
            }, f, indent=1)
    broken = [
        r for r in rows
        if r["status"] == "disagrees"
        or (r["required"] and r["status"] != "compiled")
    ]
    for r in broken:
        print(f"FAILED: {r['kernel']} {r['shape']}: {r['status']} "
              f"{r['message']}", file=sys.stderr)
    print(json.dumps({
        "kernel_rows": len(rows),
        "compiled": sum(r["status"] == "compiled" for r in rows),
        "refused": sum(r["status"] == "refused" for r in rows),
        "disagrees": sum(r["status"] == "disagrees" for r in rows),
        "wall_seconds": round(wall, 1),
    }))
    return 1 if broken else 0


if __name__ == "__main__":
    sys.exit(main())
