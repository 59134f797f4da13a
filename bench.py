"""Benchmark: aggregated serving throughput of the native JAX engine.

Runs on the TPU JAX sees and exits non-zero when there is none, or when
any leg raises (the leg's error still lands in the JSON). AIPerf-
style fixed ISL/OSL/concurrency workload (BASELINE.md measurement plan,
config 1: Qwen2.5-0.5B-shape aggregated worker, random weights — weights
don't affect throughput; config 2 proxy: Llama-3-8B int8 on the same chip,
run as the "secondary" leg unless BENCH_SECONDARY=0).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
supporting fields:

  - ``anchor``: the baseline this run is judged against — a DERIVED
    bandwidth-roofline estimate of A100-80G + vLLM decode throughput for
    the SAME model/batch/context (BASELINE.md north star is "≥ A100-vLLM
    tokens/sec/chip"; the reference publishes no in-tree number for these
    shapes, so the anchor is computed from public hardware specs and a
    stated efficiency factor instead of invented). Formula in the JSON.
  - ``mfu`` / ``hbm_util``: this chip's achieved fraction of v5e peak
    compute (197 TFLOP/s bf16) and of its decode bandwidth roofline
    (819 GB/s HBM) — absolute efficiency, independent of any anchor.
  - ``secondary``: the 8B-int8 leg's numbers.

Knob reference (env): BENCH_ISL/OSL/CONCURRENCY/REQUESTS, BENCH_MODEL
(qwen2.5-0.5b | llama3-8b | llama3-3b | qwen3-8b | gemma3-1b | gemma2-2b |
mixtral-8x7b — the qwen3/gemma shapes ride the megakernel's epilogue path),
BENCH_QUANT=int8,
BENCH_BLOCK_SIZE/KV_BLOCKS/PREFILL_CHUNK/PREFILL_BATCH/DECODE_STEPS,
BENCH_USE_KERNEL, BENCH_SPEC=ngram (speculative decoding),
BENCH_PIPELINE_DEPTH (decode-tick pipelining; 2 default, 1 = synchronous),
BENCH_SECONDARY=0 (skip the 8B-int8 leg), BENCH_DISAGG=0 / BENCH_OVERLOAD=0
/ BENCH_DRAIN=0 / BENCH_CRASH=0 (skip the disagg / overload-armor /
SIGTERM-drain / kill-9-crash legs), BENCH_PROJECTION=0 (skip the modeled
70B tp8 projection leg: per-layer step measured on the chip, collective
term modeled), BENCH_ELASTICITY=0
(skip the sim-clocked elasticity leg: planner ramp convergence,
scale-down re-prefill, select_worker cost at 10 vs 100 workers — pure
CPU arithmetic, lands on any backend), BENCH_KVREUSE=0 (skip the
KV-reuse leg: shared-prefix mix through a tiny real engine — hit rate
by tier, prefill tokens saved, TTFT delta vs cold-cache control; lands
on any backend), BENCH_TICKBUDGET=0 (skip the tick-budgeter leg:
prefill-heavy wave over a steady decode population, budgeted vs
aggregated p99 ITL + throughput; lands on any backend).
"""

from __future__ import annotations

import asyncio
import json
import os
import time

import numpy as np

import jax

from dynamo_tpu.utils.jax_env import configure_compile_cache

# Persistent XLA compilation cache: first bench run pays the compiles,
# subsequent runs (and driver re-runs) hit the cache.
configure_compile_cache()

ISL = int(os.environ.get("BENCH_ISL", 128))
OSL = int(os.environ.get("BENCH_OSL", 64))
CONCURRENCY = int(os.environ.get("BENCH_CONCURRENCY", 256))
REQUESTS = int(os.environ.get("BENCH_REQUESTS", 512))
VERBOSE = os.environ.get("BENCH_VERBOSE") == "1"

# Public hardware specs the roofline anchor/metrics derive from. The
# decode roofline and the per-device_kind peaks table live in
# runtime/roofline.py — ONE formula shared with the always-on perf
# ledger's achieved-fraction gauge — and are imported below; only the
# A100 anchor model stays bench-local.
A100_80G_BW = 2039e9  # B/s (SXM)
# Achieved-bandwidth fraction granted to the A100+vLLM anchor. Optimistic
# for the anchor (generous to the baseline): well-tuned decode sustains
# ~40-60% of peak HBM bandwidth end-to-end; we grant 60%.
ANCHOR_EFF = 0.6
# Per-layer decode-step latency floor granted to the anchor: small models
# are kernel-launch/overhead-bound on GPUs, not bandwidth-bound (~7-10
# kernels per decoder layer × ~30-40µs launch+sync each). Without this
# term a 0.5B "anchor" would claim 200k+ tok/s — far beyond anything vLLM
# reports. 0.3 ms/layer ≈ the well-tuned end of small-model GPU serving.
ANCHOR_LAYER_FLOOR_S = 0.3e-3
# Public on-demand list prices (GCP, us-central, mid-2024 era): the
# per-chip comparison is bandwidth-lopsided (A100-80G has 2.5× the HBM
# bandwidth of a v5e), so the JSON also reports throughput per dollar.
A100_80G_USD_HR = 3.67
V5E_USD_HR = 1.20


# Shared pure-arithmetic roofline model (runtime/roofline.py): param
# counts, decode step bytes, and the published peaks keyed by
# device_kind — the perf ledger grades live windows against the same
# math these legs report.
from dynamo_tpu.runtime.roofline import (  # noqa: E402
    active_param_count as _active_param_count,
    decode_step_bytes as _decode_step_bytes,
    device_peaks as _device_peaks,
    param_count as _param_count,
)


def _measured_peaks():
    """Published peaks of the device this run measures on. A device that
    is not in the table is an error, not a default."""
    kind = jax.devices()[0].device_kind
    peaks = _device_peaks(kind)
    if peaks is None:
        raise RuntimeError(
            f"no published peaks for device_kind {kind!r} in "
            "runtime/roofline.DEVICE_PEAKS — mfu/hbm_util would be "
            "another chip's numbers"
        )
    return peaks


def _record_stamp(preset: str | None, quant: str | None) -> dict:
    """Provenance stamp for every emitted record (ISSUE 19): schema
    version, backend/host/preset fingerprint, git rev — cross-round
    comparison (`dynamo-tpu bench compare`) is only sound when both
    records prove they measured the same thing."""
    import socket
    import subprocess

    from dynamo_tpu.bench.compare import BENCH_SCHEMA_VERSION

    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:
        rev = None
    try:
        backend = jax.default_backend()
    except Exception:
        backend = None
    return {
        "schema_version": BENCH_SCHEMA_VERSION,
        "git_rev": rev,
        "fingerprint": {
            "backend": backend,
            "host": socket.gethostname(),
            "preset": preset,
            "quant": quant,
        },
    }


def _sentinel_epilogue(out: dict) -> None:
    """Run the regression sentinel against the newest usable previous
    round's BENCH_*.json (when present): attach the typed report to the
    record and print the human table to stderr (stdout stays ONE JSON
    line). Never raises — a broken epilogue must not cost the round its
    perf record."""
    import glob
    import sys as _sys

    try:
        from dynamo_tpu.bench.compare import (
            compare_records,
            format_report,
            unwrap_record,
        )

        here = os.path.dirname(os.path.abspath(__file__))
        ref = ref_path = None
        for p in sorted(glob.glob(os.path.join(here, "BENCH_*.json")),
                        reverse=True):
            try:
                with open(p, "r", encoding="utf-8") as f:
                    doc = unwrap_record(json.load(f))
            except (OSError, ValueError):
                doc = None
            if doc is not None:
                ref, ref_path = doc, os.path.basename(p)
                break
        if ref is None:
            return
        report = compare_records(ref, out)
        report["reference_path"] = ref_path
        report["candidate_path"] = "(this run)"
        out["sentinel"] = report
        print(format_report(report), file=_sys.stderr)
    except Exception as exc:
        out["sentinel"] = {"error": f"{type(exc).__name__}: {exc}"}


def _anchor_toks_per_sec(cfg, batch: int, avg_ctx: float, quant: str | None) -> float:
    """Derived A100-80G + vLLM decode estimate for the same workload:
    per-step time = max(bandwidth roofline, kernel-launch floor)."""
    step_bytes = _decode_step_bytes(cfg, batch, avg_ctx, quant)
    step_s = max(
        step_bytes / (A100_80G_BW * ANCHOR_EFF),
        cfg.n_layers * ANCHOR_LAYER_FLOOR_S,
    )
    return batch / step_s


def _fault_activity_start() -> dict:
    from dynamo_tpu.runtime import faults

    return faults.activity_snapshot()


def _fault_plane_record(activity_before: dict) -> dict:
    """Fault-plane counters for one leg (deltas since the leg started):
    a chaos-free bench run must show zero retries, breaker opens, and
    migrations — a nonzero here is a self-healing path activating
    SPURIOUSLY, which is itself a perf regression (every retry is wire
    time, every migration a re-prefill). The overload-plane counters
    (sheds / brownout transitions / deadline expiries) extend the same
    contract: under-capacity legs must record ZERO for all three."""
    from dynamo_tpu.runtime import faults

    snap = faults.plane_snapshot()
    delta = {
        k: v - activity_before.get(k, 0)
        for k, v in snap["activity"].items()
    }
    return {
        "armed": snap["armed"],
        "injections": snap["injections"],
        "pull_retries": delta.get("pull_retries", 0),
        "breaker_opens": delta.get("breaker_opens", 0),
        "migrations": delta.get("migrations", 0),
        "sheds": delta.get("sheds", 0),
        "brownout_transitions": delta.get("brownout_transitions", 0),
        "deadline_expired": delta.get("deadline_expired", 0),
        # Parser plane (ISSUE 15): the degradation ladder / parse-error
        # frames activating on a clean-corpus leg would be the jail
        # mangling healthy traffic — same zero-spurious contract.
        "parser_degraded": delta.get("parser_degraded", 0),
        "parser_exceptions": delta.get("parser_exceptions", 0),
    }


def _kv_reuse_start() -> dict:
    """Snapshot the KV-reuse plane's counters before a leg."""
    from dynamo_tpu.runtime.kv_reuse_observe import global_plane

    m = global_plane().metrics
    return {
        "hits": {t: m.hits.value(tier=t) for t in sorted(m._known_tiers)},
        "misses": m.misses.value(),
        "reused": m.reused_tokens.value(),
        "recomputed": m.recomputed_tokens.value(),
        "saved_s": m.seconds_saved.value(),
    }


def _kv_reuse_record(before: dict) -> dict:
    """KV-reuse deltas for one leg: hit rate by tier, reused vs recomputed
    prefill tokens, and the plane's priced prefill-seconds-saved. On the
    random-prompt decode legs hit_rate reads ~0 — the number exists so a
    cache win (or an accounting regression) is visible NEXT TO the tok/s
    headline, not in a separate tool."""
    after = _kv_reuse_start()
    hits = {
        t: after["hits"].get(t, 0) - before["hits"].get(t, 0)
        for t in after["hits"]
    }
    hits = {t: n for t, n in hits.items() if n > 0}
    misses = after["misses"] - before["misses"]
    lookups = sum(hits.values()) + misses
    return {
        "hit_rate": round(sum(hits.values()) / lookups, 4) if lookups else 0.0,
        "hit_rate_by_tier": {
            t: round(n / lookups, 4) for t, n in hits.items()
        } if lookups else {},
        "hits": {t: int(n) for t, n in hits.items()},
        "misses": int(misses),
        "tokens_saved": int(after["reused"] - before["reused"]),
        "tokens_recomputed": int(after["recomputed"] - before["recomputed"]),
        "prefill_seconds_saved": round(after["saved_s"] - before["saved_s"], 4),
    }


def _trajectory_start() -> dict:
    """Snapshot the trajectory plane's counters before a leg (the SLO
    verdicts + span ingest deltas the zero-spurious record reads)."""
    from dynamo_tpu.runtime.trajectory import global_store

    store = global_store()
    return {
        "spans": store.spans_ingested,
        "dropped": store.spans_dropped,
        "good": store.slo.good_streams,
        "breached": store.slo.breached_streams,
    }


def _trajectory_record(before: dict) -> dict:
    """Trajectory/SLO record for one leg: goodput + multi-window burn rate
    + per-phase p99 contribution from the process-global SloTracker, span
    ingest/drop deltas (bench legs drive engines with traceless contexts,
    so a nonzero span delta here is trajectory machinery activating
    SPURIOUSLY on the hot path — same contract as fault_plane), and the
    measured per-span export cost (the trajectory-overhead delta the <1%
    observe bar covers, see _prof_gap.py)."""
    import time as _time

    from dynamo_tpu.runtime.context import Context as _Ctx
    from dynamo_tpu.runtime.trajectory import global_store
    from dynamo_tpu.utils.tracing import Tracer as _Tracer
    from dynamo_tpu.utils.tracing import export_span as _export_span

    store = global_store()
    slo = store.slo.snapshot()
    tracer = _Tracer(path="", otlp=False)  # never ship synthetic spans
    ctx = _Ctx(
        baggage={"traceparent": "00-" + "a" * 32 + "-" + "b" * 16 + "-01"}
    )
    n = 2000
    t0 = _time.perf_counter()
    for _ in range(n):
        _export_span(
            "engine.decode", ctx, start_mono=0.0, end_mono=0.001,
            tracer=tracer, generated=8,
        )
    span_us = (_time.perf_counter() - t0) / n * 1e6
    return {
        "spans_ingested": store.spans_ingested - before["spans"],
        "spans_dropped": store.spans_dropped - before["dropped"],
        "good_streams": store.slo.good_streams - before["good"],
        "breached_streams": store.slo.breached_streams - before["breached"],
        "goodput": slo["goodput"],
        "burn_rate": slo["burn_rate"],
        "phase_p99_ms": slo["phase_p99_ms"],
        "trajectory_span_us": round(span_us, 3),
        # 3 retrospective phase spans per traced request, all at stream
        # end — the whole trajectory delta a served request pays.
        "trajectory_request_us": round(3 * span_us, 3),
    }


async def run_leg(model_name: str, quant: str | None, spec: str | None,
                  concurrency: int | None = None, requests: int | None = None,
                  kv_quant: str | None = None, isl: int | None = None,
                  osl: int | None = None):
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import (
        gemma2_2b_config,
        gemma3_1b_config,
        llama3_3b_config,
        llama3_8b_config,
        mixtral_8x7b_config,
        qwen2_500m_config,
        qwen3_8b_config,
    )
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.device_observe import global_compile_watcher

    # Per-leg compile deltas: the watcher is process-global, so snapshot
    # BEFORE the leg's engine exists (its programs compile during warmup).
    compile_before = global_compile_watcher().totals()
    fault_activity0 = _fault_activity_start()
    trajectory0 = _trajectory_start()
    kv_reuse0 = _kv_reuse_start()

    cfg = {
        "qwen2.5-0.5b": qwen2_500m_config,
        "llama3-3b": llama3_3b_config,
        "llama3-8b": llama3_8b_config,
        "qwen3-8b": qwen3_8b_config,
        "gemma3-1b": gemma3_1b_config,
        "gemma2-2b": gemma2_2b_config,
        "mixtral-8x7b": mixtral_8x7b_config,
    }[model_name]()
    # Measured sweep (kernel × block size × concurrency) on the real chip:
    # 128-token pages give the decode kernel large contiguous page DMAs
    # (32-token pages: 5.8k tok/s; 64: 7.0k; 128: 7.6k; 256 over-pads at
    # ISL=128 and drops to 5.0k). Concurrency 256 beats 384/512 on ITL
    # without losing aggregate throughput.
    block_size = int(os.environ.get("BENCH_BLOCK_SIZE", 128))
    concurrency = concurrency or CONCURRENCY
    requests = requests or REQUESTS
    isl = isl or ISL
    osl = osl or OSL
    kv_quant = kv_quant or os.environ.get("BENCH_KV_QUANT") or None
    # 8B int8 on one 16 GB chip: ~8 GB of weights leave ~3 GB for KV, which
    # must cover concurrency × ceil((ISL+OSL)/block) blocks WITH headroom —
    # undersizing thrashes preemption-by-recompute (measured: 256-seq batch
    # on 256 blocks → 625 tok/s, TTFT 32s).
    default_blocks = 65536 // block_size
    if model_name in ("llama3-8b", "qwen3-8b"):
        # int8 KV halves bytes/token -> double the token budget fits the
        # same ~3 GB beside 8 GB of int8 weights
        budget = 49152 if kv_quant == "int8" else 24576
        default_blocks = budget // block_size
    engine = JaxEngine(
        JaxEngineArgs(
            config=cfg,
            block_size=block_size,
            num_kv_blocks=int(os.environ.get("BENCH_KV_BLOCKS", default_blocks)),
            max_num_seqs=concurrency,
            max_model_len=max(512, isl + osl + 64),
            prefill_chunk=int(os.environ.get("BENCH_PREFILL_CHUNK", 128)),
            # One admission dispatch for the whole wave: prefill rows are
            # near-free to batch (measured Bp 8→128 = 2.4× cost for 16×
            # rows) and fewer admission rounds stop prefill from stealing
            # decode ticks (measured 9.4k → 11.0k tok/s, ITL 20.9 → 15.4ms).
            prefill_batch=int(os.environ.get("BENCH_PREFILL_BATCH", concurrency)),
            enable_prefix_caching=True,
            decode_steps=int(os.environ.get("BENCH_DECODE_STEPS", 64)),
            use_kernel=(
                None if (uk := os.environ.get("BENCH_USE_KERNEL")) is None
                else uk == "1"
            ),
            # BENCH_QUANT=int8 → weight-only int8. At ≥3B shapes int8 BEATS
            # bf16 (measured 3B: 16.2 vs 22.5 ms/step — decode is weight-
            # bandwidth-bound and int8 halves the stream); at 0.5B the
            # weights are too small for bandwidth to matter.
            quantization=quant,
            spec_mode=spec,
            kv_cache_dtype=kv_quant,
            # Decode-tick pipelining (docs/design_docs/decode_pipelining.md):
            # 2 double-buffers bursts so readback + emit hide under device
            # compute; 1 reproduces the pre-pipelining synchronous ticks.
            pipeline_depth=int(os.environ.get("BENCH_PIPELINE_DEPTH", 2)),
        )
    )

    rng = np.random.default_rng(0)

    # BENCH_PROMPT=repeat: prompts are a repeated short pattern — the
    # lookup-friendly workload (extractive/templated traffic) where
    # speculative decoding should win; default is worst-case random.
    repeat_prompts = os.environ.get("BENCH_PROMPT") == "repeat"

    def make_req(i: int) -> PreprocessedRequest:
        if repeat_prompts:
            pattern = rng.integers(10, cfg.vocab_size - 10, size=8).tolist()
            toks = (pattern * (isl // 8 + 1))[:isl]
        else:
            toks = rng.integers(10, cfg.vocab_size - 10, size=isl).tolist()
        return PreprocessedRequest(
            token_ids=toks,
            request_id=f"bench-{i}",
            sampling=SamplingOptions(
                temperature=0.0 if spec else 1.0, top_p=None if spec else 0.95
            ),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )

    async def run_one(req):
        t0 = time.monotonic()
        ttft = None
        n = 0
        async for out in engine.generate(req, Context()):
            if out.token_ids:
                if ttft is None:
                    ttft = time.monotonic() - t0
                n += len(out.token_ids)
        return n, ttft, time.monotonic() - t0

    async def run_wave(count, offset):
        sem = asyncio.Semaphore(concurrency)

        async def limited(i):
            async with sem:
                return await run_one(make_req(offset + i))

        return await asyncio.gather(*(limited(i) for i in range(count)))

    # Warmup wave triggers all jit compiles (prefill buckets + decode buckets).
    if VERBOSE:
        print(f"[{model_name}] warmup wave...", flush=True)
    t0 = time.monotonic()
    await run_wave(concurrency, offset=10_000)
    engine.hbm.snapshot()  # sample the post-warmup ledger (peak tracking)
    if VERBOSE:
        print(f"[{model_name}] warmup done in {time.monotonic()-t0:.1f}s; "
              f"stats={engine.stats()}", flush=True)

    t0 = time.monotonic()
    results = await run_wave(requests, offset=0)
    wall = time.monotonic() - t0
    await engine.stop()
    stats = engine.stats()
    # Device-plane regressions this leg: compile time/program count (a
    # recompile storm shows up as compile_s exploding while tok/s sags)
    # and the HBM ledger's footprint (accounting drift / unplanned growth).
    hbm_bytes = engine.hbm.total_bytes()
    hbm_peak_bytes = engine.hbm.peak_bytes
    compile_after = global_compile_watcher().totals()
    compile_s = round(
        compile_after["compile_seconds"] - compile_before["compile_seconds"], 2
    )
    compiles = compile_after["compiles"] - compile_before["compiles"]
    # Process-CUMULATIVE distinct watched sites (program names are reused
    # across legs, so a per-leg delta would read ~0 after leg 1).
    compiled_programs = compile_after["programs"]
    recompile_storms = compile_after["storms"] - compile_before["storms"]
    # Host-gap aggregate: mean host-injected device wait per decode
    # dispatch (0 when the next burst was already in flight) — the number
    # the pipeline_depth knob exists to shrink.
    gap_count, gap_sum = engine.step_metrics.host_gap_stats()
    host_gap_ms = round(1000 * gap_sum / gap_count, 3) if gap_count else None

    # Drop every reference to the engine's device arrays BEFORE the next
    # leg allocates (an un-GC'd 8 GB int8 tree plus the next leg's engine
    # is over HBM: measured RESOURCE_EXHAUSTED cascade).
    import gc

    del engine
    gc.collect()

    # Which decode path served: bursts on the fused megakernel vs the XLA
    # decode program (the runner picks one at start).
    mk_fused = int(stats.get("mk_fused_bursts", 0))
    mk_fallback = int(stats.get("mk_fallback_bursts", 0))
    fused_coverage = (
        round(mk_fused / (mk_fused + mk_fallback), 4)
        if (mk_fused + mk_fallback) else None
    )

    total_tokens = sum(r[0] for r in results)
    ttfts = sorted(r[1] for r in results if r[1] is not None)
    if not ttfts:
        raise RuntimeError(
            f"leg produced no successful requests ({len(results)} issued)"
        )
    itls = sorted(
        (r[2] - r[1]) / max(r[0] - 1, 1) for r in results if r[1] is not None
    )
    toks_per_sec = total_tokens / wall
    avg_ctx = isl + osl / 2
    step_bytes = _decode_step_bytes(cfg, concurrency, avg_ctx, quant)
    # Our own decode roofline on this chip (ignores prefill: decode
    # dominates the wall at OSL=64) and compute utilization.
    peaks = _measured_peaks()
    roofline = concurrency * peaks.hbm_bytes_per_s / step_bytes
    flops_per_tok = 2 * _active_param_count(cfg)
    return {
        "model": cfg.name,
        "quant": quant,
        "kv_quant": kv_quant,
        "isl": isl,
        "osl": osl,
        "concurrency": concurrency,
        "toks_per_sec_per_chip": round(toks_per_sec / jax.device_count(), 2),
        "total_tokens": total_tokens,
        "wall_s": round(wall, 2),
        "p50_ttft_ms": round(1000 * ttfts[len(ttfts) // 2], 1),
        "p50_itl_ms": round(1000 * itls[len(itls) // 2], 2),
        "pipeline_depth": stats.get("pipeline_depth"),
        "host_gap_ms": host_gap_ms,
        "mk_fused_bursts": mk_fused,
        "mk_fallback_bursts": mk_fallback,
        "fused_coverage": fused_coverage,
        "compile_s": compile_s,
        # compiles = this leg's compilation events (signatures);
        # compiled_programs = process-cumulative distinct watched sites;
        # recompile_storms = this leg's budget violations.
        "compiles": compiles,
        "compiled_programs": compiled_programs,
        "recompile_storms": recompile_storms,
        "hbm_ledger_bytes": hbm_bytes,
        "hbm_ledger_peak_bytes": hbm_peak_bytes,
        "anchor_toks_per_sec": round(
            _anchor_toks_per_sec(cfg, concurrency, avg_ctx, quant), 1
        ),
        "mfu": round(
            toks_per_sec * flops_per_tok / peaks.bf16_flops_per_s, 4
        ),
        "hbm_util": round(toks_per_sec / roofline, 4),
        "fault_plane": _fault_plane_record(fault_activity0),
        "trajectory": _trajectory_record(trajectory0),
        "kv_reuse": _kv_reuse_record(kv_reuse0),
        **(
            {
                "spec_proposed": stats.get("spec_proposed", 0),
                "spec_accepted": stats.get("spec_accepted", 0),
            }
            if spec
            else {}
        ),
    }


async def run_disagg_leg(isl: int = 512, osl: int = 64, concurrency: int = 4,
                         requests: int = 12):
    """Disaggregated P/D measurement — the north-star metric's missing
    number (BASELINE.md: 'disaggregated Llama-3-70B'; ref methodology
    docs/benchmarks/benchmarking.md). One chip timeshares a prefill engine
    and a decode engine wired through the real runtime endpoints + chunked
    KV transfer (disagg/handlers.py). Two measurements:

      1. ``transfer``: an IDLE-PATH pull of one prompt's KV through the
         real kv endpoint (export gather → wire → import scatter), timed
         directly — the unambiguous achieved rate.
      2. serving comparison at low concurrency vs an aggregated control:
         TTFT delta (= transfer + routing overhead) and ITL delta (decode
         ticks degraded by concurrent pulls). Low concurrency because the
         two engines TIMESHARE one chip here — queueing at high
         concurrency measures the missing second chip, not the transfer
         (the ``one_chip_timeshared`` field flags this).

    The model is the 0.5B bench shape: two 8B engines cannot share one
    16 GB chip, and every cost this leg measures (gather, serialize, wire,
    scatter, overlap) is mechanism."""
    from dynamo_tpu.disagg import (
        DecodeHandler,
        KvTransferHandler,
        PrefillHandler,
        PrefillRouter,
    )
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import qwen2_500m_config
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.pipeline import build_pipeline

    fault_activity0 = _fault_activity_start()
    cfg = qwen2_500m_config()

    def mk_engine():
        return JaxEngine(
            JaxEngineArgs(
                config=cfg,
                block_size=128,
                num_kv_blocks=256,
                max_num_seqs=concurrency,
                max_model_len=isl + osl + 64,
                prefill_chunk=min(512, isl),
                prefill_batch=concurrency,
                decode_steps=32,
            )
        )

    rng = np.random.default_rng(7)
    V = cfg.vocab_size

    def mk_req(i):
        return PreprocessedRequest(
            token_ids=rng.integers(10, V - 10, size=isl).tolist(),
            request_id=f"disagg-{i}",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )

    async def run_wave(gen_fn, count):
        sem = asyncio.Semaphore(concurrency)

        async def one(i):
            async with sem:
                t0 = time.monotonic()
                ttft, n = None, 0
                async for out in gen_fn(mk_req(i)):
                    ids = (
                        out.token_ids if hasattr(out, "token_ids")
                        else out.get("token_ids")
                    ) or []
                    if ids and ttft is None:
                        ttft = time.monotonic() - t0
                    n += len(ids)
                return n, ttft, time.monotonic() - t0

        t0 = time.monotonic()
        res = await asyncio.gather(*(one(i) for i in range(count)))
        return res, time.monotonic() - t0

    def stats(res, wall):
        ttfts = sorted(r[1] for r in res if r[1] is not None)
        itls = sorted(
            (r[2] - r[1]) / max(r[0] - 1, 1) for r in res if r[1] is not None
        )
        toks = sum(r[0] for r in res)
        return {
            "toks_per_sec": round(toks / wall, 1),
            "p50_ttft_ms": round(1000 * ttfts[len(ttfts) // 2], 1),
            "p50_itl_ms": round(1000 * itls[len(itls) // 2], 2),
        }

    # -- aggregated control -------------------------------------------------
    agg = mk_engine()
    try:
        await run_wave(lambda r: agg.generate(r, Context()), concurrency)
        res, wall = await run_wave(
            lambda r: agg.generate(r, Context()), requests
        )
        agg_stats = stats(res, wall)
    finally:
        await agg.stop()

    # -- disaggregated ------------------------------------------------------
    rt = DistributedRuntime.detached()
    prefill_engine, decode_engine = mk_engine(), mk_engine()
    ns = rt.namespace("bench-disagg")
    served = []
    try:
        pc = ns.component("prefill")
        served.append(
            await pc.endpoint("generate").serve_endpoint(
                PrefillHandler(prefill_engine, worker_id=1).generate,
                instance_id=1,
            )
        )
        served.append(
            await pc.endpoint("kv").serve_endpoint(
                KvTransferHandler(prefill_engine).generate, instance_id=1
            )
        )

        async def kv_client():
            return await pc.endpoint("kv").client()

        dc = ns.component("backend")
        decode_handler = DecodeHandler(
            decode_engine, kv_client_factory=kv_client
        )
        served.append(
            await dc.endpoint("generate").serve_endpoint(
                decode_handler.generate, instance_id=2
            )
        )
        decode_client = await dc.endpoint("generate").client()

        async def prefill_client():
            return await pc.endpoint("generate").client()

        pipeline = build_pipeline(
            [PrefillRouter(prefill_client, threshold_tokens=64)],
            decode_client,
        )

        async def gen(r):
            async for out in pipeline.generate(r.to_dict(), Context()):
                yield out

        await run_wave(gen, concurrency)  # warm both engines + transfer

        # -- idle-path transfer microbench: one prompt's KV, timed alone --
        from dynamo_tpu.llm.protocols.common import DisaggregatedParams
        from dynamo_tpu.tokens.blocks import compute_block_hashes

        xfer_rates = []
        for trial in range(3):
            prompt = rng.integers(10, V - 10, size=isl).tolist()
            pre_req = mk_req(10_000 + trial)
            pre_req.token_ids = prompt
            pre_req.stop.max_tokens = 1  # prefill only
            async for _ in prefill_engine.generate(pre_req, Context()):
                pass
            dp = DisaggregatedParams(
                worker_id=1, prefilled_tokens=isl,
                kv_transfer={
                    "block_hashes": compute_block_hashes(prompt, 128),
                    "block_size": 128,
                },
            )
            b0 = decode_handler.bytes_pulled
            t0 = time.monotonic()
            pulled = await decode_handler._pull_blocks(dp)
            dt = time.monotonic() - t0
            nbytes = decode_handler.bytes_pulled - b0
            if pulled and nbytes:
                xfer_rates.append(nbytes / dt)
        xfer_mb_s = round(max(xfer_rates) / 1e6, 1) if xfer_rates else None

        res, wall = await run_wave(gen, requests)
        dis_stats = stats(res, wall)
        return {
            "mode": "disaggregated P/D",
            "one_chip_timeshared": True,
            "model": "qwen2.5-0.5b",
            "isl": isl,
            "osl": osl,
            "concurrency": concurrency,
            "aggregated": agg_stats,
            "disagg": dis_stats,
            "ttft_delta_ms": round(
                dis_stats["p50_ttft_ms"] - agg_stats["p50_ttft_ms"], 1
            ),
            "itl_delta_ms": round(
                dis_stats["p50_itl_ms"] - agg_stats["p50_itl_ms"], 2
            ),
            "transfer_idle_mb_per_s": xfer_mb_s,
            "blocks_pulled": decode_handler.blocks_pulled,
            "transfer_failures": decode_handler.transfer_failures,
            # Wire-format v2 telemetry: serialized bytes actually pulled,
            # split by wire dtype (int8 pools ship {q8, scales} ≈ 0.53x
            # the dense bf16 bytes), and the measured per-(src prefill
            # worker → this decode worker) bandwidth EWMA the router's
            # link-cost model consumes.
            "wire_bytes": decode_handler.bytes_pulled,
            "wire_bytes_by_dtype": dict(decode_handler.wire_bytes_by_dtype),
            "wire_dtype": max(
                decode_handler.wire_bytes_by_dtype,
                key=decode_handler.wire_bytes_by_dtype.get,
                default=None,
            ),
            "link_bandwidth_mb_per_s": {
                str(src): round(bw / 1e6, 1)
                for src, bw in decode_handler.link_bandwidth().items()
            },
            # Chaos-free proof: retries/breaker/migration counters must be
            # zero when no fault plan is armed (self-healing sat idle).
            "fault_plane": _fault_plane_record(fault_activity0),
            "pull_retries": decode_handler.pull_retries,
            "breaker_opens": decode_handler.breaker_opens,
            "pull_fallbacks": decode_handler.pull_fallbacks,
        }
    finally:
        for s in served:
            await s.shutdown()
        await prefill_engine.stop()
        await decode_engine.stop()
        await rt.shutdown()


async def run_overload_leg(isl: int = 64, osl: int = 32,
                           concurrency: int = 16):
    """Overload-armor measurement (ISSUE 8): an OPEN-LOOP arrival ramp
    through the admission controller, calibrated against the engine's own
    measured capacity. Two sub-legs share one engine + controller config:

      * ``under_capacity`` (0.5× the calibrated request rate) — the
        zero-spurious-activation contract: NO sheds, NO brownout
        transitions, NO deadline expiries (same contract as the PR 7
        chaos-free fault-plane check);
      * ``over_capacity`` (4× the calibrated rate) — the armor working:
        queue depth stays bounded at the configured cap, the excess sheds
        with typed reasons, deadline-carrying requests that expire
        mid-queue are shed before prefill, and every ADMITTED stream
        completes with its full output.
    """
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import qwen2_500m_config
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.overload import (
        OverloadConfig,
        OverloadController,
        OverloadShedError,
    )

    fault_activity0 = _fault_activity_start()
    cfg = qwen2_500m_config()
    engine = JaxEngine(
        JaxEngineArgs(
            config=cfg,
            block_size=64,
            num_kv_blocks=2048,
            max_num_seqs=concurrency,
            max_model_len=isl + osl + 64,
            prefill_chunk=64,
            prefill_batch=concurrency,
            decode_steps=16,
        )
    )
    rng = np.random.default_rng(11)

    def mk_req(i):
        return PreprocessedRequest(
            token_ids=rng.integers(10, cfg.vocab_size - 10, size=isl).tolist(),
            request_id=f"ovl-{i}",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )

    async def run_one(req, ctrl=None, deadline_s=None):
        """→ ('ok', tokens) | ('shed', reason) | ('error', kind)."""
        ctx = Context(
            deadline=(time.monotonic() + deadline_s) if deadline_s else None
        )
        ticket = None
        try:
            if ctrl is not None:
                ticket = await ctrl.admit(ctx, request_id=req.request_id)
            n = 0
            async for out in engine.generate(req, ctx):
                if out.error:
                    return ("error", out.error_kind or "other")
                n += len(out.token_ids or [])
            return ("ok", n)
        except OverloadShedError as exc:
            return ("shed", exc.reason)
        finally:
            if ticket is not None:
                ctrl.release(ticket)

    try:
        # Calibrate: one closed-loop wave → sustainable requests/sec
        # (also triggers every compile so the ramp measures serving, not
        # XLA).
        await asyncio.gather(*(run_one(mk_req(10_000 + i)) for i in range(concurrency)))
        t0 = time.monotonic()
        results = await asyncio.gather(
            *(run_one(mk_req(20_000 + i)) for i in range(2 * concurrency))
        )
        calib_wall = time.monotonic() - t0
        assert all(r[0] == "ok" for r in results)
        capacity_rps = (2 * concurrency) / calib_wall

        async def ramp(rate_rps, n_requests, ctrl, deadline_s):
            tasks = []
            interval = 1.0 / rate_rps
            for i in range(n_requests):
                tasks.append(
                    asyncio.ensure_future(
                        run_one(mk_req(30_000 + i), ctrl, deadline_s)
                    )
                )
                await asyncio.sleep(interval)
            outcomes = await asyncio.gather(*tasks)
            counts: dict = {}
            for kind, detail in outcomes:
                key = kind if kind == "ok" else f"{kind}:{detail}"
                counts[key] = counts.get(key, 0) + 1
            return counts, outcomes

        def mk_ctrl():
            return OverloadController(
                OverloadConfig(
                    max_concurrency=concurrency,
                    max_queue_depth=2 * concurrency,
                    max_queue_delay_s=20.0,
                )
            )

        # Deadlines scale with MEASURED service time so the leg is about
        # the armor, not the host's speed: generous under capacity
        # (nothing may expire), ~2 service waves over capacity (the
        # queue tail expires, the admitted head completes).
        service_s = calib_wall
        # Under capacity: nothing may activate. No deadlines — the
        # zero-spurious contract must hold on any hardware.
        under_ctrl = mk_ctrl()
        under_counts, under_out = await ramp(
            capacity_rps * 0.5, 2 * concurrency, under_ctrl,
            deadline_s=None,
        )
        under_snap = under_ctrl.snapshot()

        # 4× capacity: bounded queue, typed sheds, admitted work intact.
        over_ctrl = mk_ctrl()
        over_counts, over_out = await ramp(
            capacity_rps * 4.0, 6 * concurrency, over_ctrl,
            deadline_s=max(15.0, 2.5 * service_s),
        )
        over_snap = over_ctrl.snapshot()
        ok_complete = all(
            detail == osl for kind, detail in over_out if kind == "ok"
        )
        return {
            "model": cfg.name,
            "isl": isl,
            "osl": osl,
            "concurrency": concurrency,
            "calibrated_capacity_rps": round(capacity_rps, 2),
            "under_capacity": {
                "offered_x": 0.5,
                "outcomes": under_counts,
                "sheds": sum(under_snap["sheds"].values()),
                "brownout_transitions": sum(
                    under_snap["transitions"].values()
                ),
                "deadline_expired": under_snap["deadline_expired"],
                "peak_queue_depth": under_snap["peak_queue_depth"],
                # THE contract: zero activations off the saturation path.
                "zero_spurious": (
                    not under_snap["sheds"] and not under_snap["transitions"]
                ),
            },
            "over_capacity": {
                "offered_x": 4.0,
                "outcomes": over_counts,
                "sheds_by_reason": over_snap["sheds"],
                "deadline_expired": over_snap["deadline_expired"],
                "peak_queue_depth": over_snap["peak_queue_depth"],
                "queue_bounded": (
                    over_snap["peak_queue_depth"] <= 2 * concurrency
                ),
                "admitted_streams_complete": ok_complete,
                "engine_deadline_sheds": engine.deadline_sheds,
            },
            "fault_plane": _fault_plane_record(fault_activity0),
        }
    finally:
        await engine.stop()
        import gc

        del engine
        gc.collect()


async def run_drain_leg(isl: int = 64, osl: int = 48, concurrency: int = 8):
    """Rolling-restart measurement (ISSUE 9): SIGTERM a worker mid-load and
    prove users never see it. Two in-process engines (same seed/config —
    the rolling-restart fleet invariant) serve one Migration-wrapped client
    wave; mid-wave the process SIGTERMs ITSELF, the loop signal handler
    triggers the source's DrainController, live decodes hand off to the
    peer over the wire-v2 path, and the record carries the contract:
    ``dropped_requests == 0``, handoff bytes, re-prefill tokens (only the
    fallback rung pays any), and the worst mid-stream stall a client saw.
    """
    import signal as _signal

    from dynamo_tpu.disagg import HandoffHandler
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.migration import Migration
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import qwen2_500m_config
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.drain import DrainController

    fault_activity0 = _fault_activity_start()
    cfg = qwen2_500m_config()

    def mk_engine():
        return JaxEngine(
            JaxEngineArgs(
                config=cfg,
                block_size=64,
                num_kv_blocks=2048,
                max_num_seqs=concurrency,
                max_model_len=isl + osl + 64,
                prefill_chunk=64,
                prefill_batch=concurrency,
                decode_steps=8,
            )
        )

    source, peer = mk_engine(), mk_engine()

    class _LocalHandoffClient:
        """Controller-facing view of the peer's handoff endpoint."""

        def __init__(self, handlers):
            self._handlers = handlers

        @property
        def instance_ids(self):
            return sorted(self._handlers)

        def direct(self, request, instance_id, context=None):
            return self._handlers[instance_id].generate(
                request, context or Context()
            )

        async def close(self):
            pass

    handoff_client = _LocalHandoffClient({2: HandoffHandler(peer)})

    async def handoff_client_factory():
        return handoff_client

    controller = DrainController(
        source,
        worker_id=1,
        handoff_client_factory=handoff_client_factory,
        deadline_s=60.0,
    )
    loop = asyncio.get_running_loop()
    loop.add_signal_handler(_signal.SIGTERM, controller.trigger)

    class _DrainAwareClient:
        """Stands in for the KV router: places on the source until its
        draining bit flips, then on the peer — exactly what KvScheduler
        does once the draining load report lands."""

        async def generate(self, request, context):
            eng = peer if source.draining else source
            async for out in eng.generate(request, context):
                yield out

    mig = Migration(migration_limit=3)
    client = _DrainAwareClient()
    rng = np.random.default_rng(23)

    def mk_req(i):
        return PreprocessedRequest(
            token_ids=rng.integers(10, cfg.vocab_size - 10, size=isl).tolist(),
            request_id=f"drain-{i}",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )

    async def run_one(req):
        """→ (tokens, max inter-output stall seconds, error|None)."""
        n = 0
        last = time.monotonic()
        stall = 0.0
        try:
            async for out in mig.generate(req, Context(), client):
                now = time.monotonic()
                stall = max(stall, now - last)
                last = now
                err = out.get("error") if isinstance(out, dict) else out.error
                if err:
                    return (n, stall, str(err))
                toks = (
                    out.get("token_ids") if isinstance(out, dict)
                    else out.token_ids
                )
                n += len(toks or [])
        except Exception as exc:
            return (n, stall, f"{type(exc).__name__}: {exc}")
        return (n, stall, None)

    try:
        # Warm both engines (compiles must not masquerade as drain stall).
        await asyncio.gather(
            *(collect_silent(source, mk_req(10_000 + i)) for i in range(2)),
            *(collect_silent(peer, mk_req(20_000 + i)) for i in range(2)),
        )
        reprefill0 = mig.metrics.reprefill_tokens.value()
        t0 = time.monotonic()
        tasks = [
            asyncio.ensure_future(run_one(mk_req(i)))
            for i in range(2 * concurrency)
        ]
        # Let the first wave reach steady decode, then kill the worker.
        await asyncio.sleep(1.0)
        os.kill(os.getpid(), _signal.SIGTERM)
        results = await asyncio.gather(*tasks)
        await controller.drain()  # join (SIGTERM already triggered it)
        wall = time.monotonic() - t0
        dropped = sum(1 for _n, _s, err in results if err is not None)
        short = sum(1 for n, _s, err in results if err is None and n != osl)
        status = controller.status()
        return {
            "model": cfg.name,
            "isl": isl,
            "osl": osl,
            "concurrency": concurrency,
            "streams": len(results),
            "wall_s": round(wall, 3),
            # THE contract: a planned restart drops nothing.
            "dropped_requests": dropped + short,
            "handed_off": status["handoffs"],
            "handoff_bytes": status["handoff_bytes"],
            "reprefill_fallbacks": status["reprefill_fallbacks"],
            "requeued": status["requeued"],
            # Tokens the fallback rung re-prefilled (handoffs pay ZERO).
            "reprefill_tokens": int(
                mig.metrics.reprefill_tokens.value() - reprefill0
            ),
            "max_midstream_stall_s": round(
                max((s for _n, s, _e in results), default=0.0), 3
            ),
            "drain_duration_s": status.get("duration_s"),
            "fault_plane": _fault_plane_record(fault_activity0),
        }
    finally:
        loop.remove_signal_handler(_signal.SIGTERM)
        await source.stop()
        await peer.stop()
        import gc

        del source, peer
        gc.collect()


async def run_crash_leg(isl: int = 64, osl: int = 48, concurrency: int = 8,
                        config_fn=None):
    """Crash-plane measurement (ISSUE 10): an UNPLANNED worker death
    mid-load — no drain, no handoff, the worker simply goes silent the way
    a kill -9'd process does. The liveness tracker (missed load reports)
    declares it dead, evicts it, and aborts its in-flight streams with the
    typed worker_lost error; Migration re-prefills them on the peer. The
    record carries the contract: ``lost_requests == 0``, the measured
    detection-to-abort latency (bounded by dead_after × interval, nothing
    TCP), the re-prefilled tokens the unplanned path paid (unlike drain's
    zero-re-prefill handoff), and the warm-restart numbers — checkpoint
    restore wall time + the prefill tokens a shared-prefix request costs
    on the restarted worker (near-zero = warm rejoin works)."""
    import tempfile

    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.migration import Migration
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import qwen2_500m_config
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.liveness import (
        LivenessConfig,
        LivenessTracker,
        WorkerLostError,
    )

    fault_activity0 = _fault_activity_start()
    cfg = (config_fn or qwen2_500m_config)()

    def mk_engine():
        return JaxEngine(
            JaxEngineArgs(
                config=cfg,
                # Small blocks so the warm shared prefix commits several
                # cache blocks: the restore half of the record
                # (restored_blocks / warm_prefill_tokens) needs a
                # non-empty checkpoint even at small ISL.
                block_size=16,
                num_kv_blocks=2048,
                max_num_seqs=concurrency,
                max_model_len=isl + osl + 64,
                prefill_chunk=64,
                prefill_batch=concurrency,
                decode_steps=8,
            )
        )

    source, peer = mk_engine(), mk_engine()
    rt = DistributedRuntime.detached()
    ckpt_dir = tempfile.mkdtemp(prefix="bench-crash-ckpt-")

    class _Crashable:
        """Engine front that goes SILENT when killed — exactly what the
        frontend observes of a kill -9'd worker (no FIN, no error)."""

        def __init__(self, engine):
            self.engine = engine
            self.dead = asyncio.Event()

        async def generate(self, request, context):
            async for out in self.engine.generate(request, context):
                if self.dead.is_set():
                    await asyncio.Event().wait()  # never returns
                yield out

    crash_src = _Crashable(source)
    ep = rt.namespace("bench").component("backend").endpoint("generate")
    served = [
        await ep.serve_endpoint(crash_src.generate, instance_id=1),
        await ep.serve_endpoint(peer.generate, instance_id=2),
    ]
    client = await ep.client()
    await client.wait_for_instances()
    client.enable_stream_aborts()

    kill_at = [0.0]
    detection = {}

    def on_dead(wid, _inc):
        # Order matters: evict BEFORE abort so migration re-dispatches
        # land on the peer, never back on the corpse.
        client.evict_instance(wid)
        n = client.abort_instance(
            wid, WorkerLostError(f"worker {wid} dead (missed reports)")
        )
        detection["latency_s"] = time.monotonic() - kill_at[0]
        detection["aborted_streams"] = n

    tracker = LivenessTracker(
        LivenessConfig(interval_s=0.1, suspect_after=2, dead_after=4),
        on_dead=on_dead,
    )
    alive = {1: True, 2: True}

    async def liveness_loop():
        while True:
            for wid, ok in alive.items():
                if ok:
                    tracker.observe_report(wid, 1000 + wid)
            tracker.evaluate()
            await asyncio.sleep(0.05)

    liveness_task = asyncio.ensure_future(liveness_loop())

    mig = Migration(migration_limit=3)
    rng = np.random.default_rng(29)
    shared_prefix = rng.integers(10, cfg.vocab_size - 10, size=isl).tolist()

    def mk_req(i, prefix=None):
        toks = list(prefix) if prefix else rng.integers(
            10, cfg.vocab_size - 10, size=isl
        ).tolist()
        return PreprocessedRequest(
            token_ids=toks,
            request_id=f"crash-{i}",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )

    async def run_one(req):
        n = 0
        last = time.monotonic()
        stall = 0.0
        try:
            async for out in mig.generate(req, Context(), client):
                now = time.monotonic()
                stall = max(stall, now - last)
                last = now
                err = out.get("error") if isinstance(out, dict) else out.error
                if err:
                    return (n, stall, str(err))
                toks = (
                    out.get("token_ids") if isinstance(out, dict)
                    else out.token_ids
                )
                n += len(toks or [])
        except Exception as exc:
            return (n, stall, f"{type(exc).__name__}: {exc}")
        return (n, stall, None)

    try:
        # Warm both engines; seed the source's prefix cache with the
        # shared prefix so the restart checkpoint carries something warm.
        await asyncio.gather(
            collect_silent(source, mk_req(10_000, prefix=shared_prefix)),
            collect_silent(source, mk_req(10_001)),
            collect_silent(peer, mk_req(20_000)),
            collect_silent(peer, mk_req(20_001)),
        )
        await source.save_checkpoint(ckpt_dir)

        reprefill0 = mig.metrics.reprefill_tokens.value()
        t0 = time.monotonic()
        tasks = [
            asyncio.ensure_future(run_one(mk_req(i)))
            for i in range(2 * concurrency)
        ]
        await asyncio.sleep(1.0)  # first wave mid-decode
        # kill -9: the source goes silent and its reports stop. Nothing
        # cooperative happens from here on.
        alive[1] = False
        crash_src.dead.set()
        kill_at[0] = time.monotonic()
        results = await asyncio.gather(*tasks)
        wall = time.monotonic() - t0
        lost = sum(1 for n, _s, err in results if err is not None or n != osl)

        # Warm restart: a fresh engine restores the dead worker's
        # checkpoint, then serves a shared-prefix request.
        restarted = mk_engine()
        try:
            t_r = time.monotonic()
            restored_blocks = await restarted.load_checkpoint(ckpt_dir)
            restore_ms = (time.monotonic() - t_r) * 1000
            await collect_silent(
                restarted, mk_req(30_000, prefix=shared_prefix)
            )
            warm_prefill_tokens = restarted.stats().get("prefill_tokens", 0)
        finally:
            await restarted.stop()

        return {
            "model": cfg.name,
            "isl": isl,
            "osl": osl,
            "concurrency": concurrency,
            "streams": len(results),
            "wall_s": round(wall, 3),
            # THE contract: an unplanned death loses nothing.
            "lost_requests": lost,
            "detection_ms": round(detection.get("latency_s", 0.0) * 1000, 1),
            "detection_budget_ms": int(
                tracker.config.detection_budget_s * 1000
            ),
            "aborted_streams": detection.get("aborted_streams", 0),
            "reprefill_tokens": int(
                mig.metrics.reprefill_tokens.value() - reprefill0
            ),
            "max_midstream_stall_s": round(
                max((s for _n, s, _e in results), default=0.0), 3
            ),
            "restore_ms": round(restore_ms, 1),
            "restored_blocks": restored_blocks,
            "warm_prefill_tokens": int(warm_prefill_tokens),
            "fault_plane": _fault_plane_record(fault_activity0),
        }
    finally:
        liveness_task.cancel()
        from dynamo_tpu.runtime.tasks import reap_task

        await reap_task(liveness_task, "bench liveness loop")
        for s in served:
            await s.shutdown(grace_period=1)
        await rt.shutdown(grace_period=1)
        await source.stop()
        await peer.stop()
        import gc

        del source, peer
        gc.collect()


async def run_elasticity_leg(seed: int = 29):
    """Elasticity-loop measurement (ISSUE 13), sim-clocked
    (planner/simfleet.py — the REAL KvScheduler + LivenessTracker +
    Planner + ElasticController around simulated workers, so the leg is
    pure CPU arithmetic and lands on any backend):

      * ramp 1× → 4× → 1× open-loop load: adjustment intervals from each
        rate shift until desired == ready (convergence), both directions;
      * scale-down cost: drain-attributed re-prefilled tokens (the
        zero-re-prefill handoff contract — must be 0) + zero lost
        streams token-exact over the whole ramp;
      * per-request ``select_worker`` cost at 10 vs 100 workers, wall
        time AND candidates actually scored (the pruned-candidate path's
        sub-linear-growth contract).
    """
    from dynamo_tpu.planner import (
        ElasticConfig,
        ElasticController,
        Planner,
        PlannerConfig,
        SimConfig,
        SimFleet,
        profile_interpolators,
    )
    from dynamo_tpu.router.protocols import LoadSnapshot
    from dynamo_tpu.router.scheduler import KvScheduler
    from dynamo_tpu.tokens.radix import OverlapScores

    fault_activity0 = _fault_activity_start()
    cfg = SimConfig(seed=seed, worker_max_conc=4, base_itl_s=0.02,
                    base_ttft_s=0.1, isl=128, osl=32, launch_delay_s=0.6)
    base_rate = 30.0  # ≈ 5 SLA-sized workers; 4× ≈ 19
    shifts = (15.0, 35.0)

    def rate(t):
        if t < shifts[0]:
            return base_rate
        if t < shifts[1]:
            return base_rate * 4
        if t < 55.0:
            return base_rate
        return 0.0

    fleet = SimFleet(cfg, n_workers=5, rate_fn=rate)
    ctl = ElasticController(
        fleet,
        config=ElasticConfig(scale_up_after=1, scale_down_after=3,
                             cooldown_intervals=1,
                             actuation_deadline_s=20.0),
    )
    planner = Planner(
        PlannerConfig(adjustment_interval_s=1.0,
                      itl_target_s=cfg.base_itl_s * 2, ttft_target_s=2.0,
                      min_replicas=2, max_replicas=64,
                      total_chip_budget=128),
        *profile_interpolators(cfg),
        ctl, fleet.metrics_source, disagg=False, metrics=ctl.metrics,
    )
    timeline = []
    for _ in range(58):
        fleet.run(1.0)
        plan = await planner.step()
        timeline.append(
            (fleet.now, plan.decode if plan else None,
             fleet.ready_count("decode"))
        )
    fleet.settle(180.0)
    problems = fleet.verify_streams()

    def convergence_intervals(shift_t):
        """Intervals from the rate shift until desired == ready and it
        STAYS matched through the next 3 intervals (or the window end)."""
        idxs = [i for i, (t, _w, _h) in enumerate(timeline) if t > shift_t]
        for n, i in enumerate(idxs):
            window = timeline[i:i + 3]
            if all(w is not None and w == h for _t, w, h in window):
                return n + 1
        return None

    def probe(n_workers, requests=2000):
        sched = KvScheduler(seed=seed)
        for wid in range(1, n_workers + 1):
            sched.update_load(LoadSnapshot(
                worker_id=wid, active_blocks=(wid % 37) * 5,
                total_blocks=4096,
            ))
        cands = [(wid, 0) for wid in range(1, n_workers + 1)]
        t0 = time.perf_counter()
        for _ in range(requests):
            sched.select_worker(9, OverlapScores(), cands)
        wall = time.perf_counter() - t0
        return {
            "workers": n_workers,
            "us_per_request": round(wall / requests * 1e6, 2),
            "candidates_scored_per_request": round(
                sched.logit_evals / sched.selections, 2
            ),
        }

    small, large = probe(10), probe(100)
    return {
        "sim_seed": seed,
        "arrivals": fleet.arrivals,
        "lost_streams": len(problems),
        "convergence_intervals_up": convergence_intervals(shifts[0]),
        "convergence_intervals_down": convergence_intervals(shifts[1]),
        "peak_workers": max(h for _t, _w, h in timeline),
        "scale_ups": ctl.scale_ups,
        "scale_downs": ctl.scale_downs,
        "holds": ctl.holds,
        "workers_drained": len(ctl.drained_workers),
        "handoff_streams": fleet.handoff_streams,
        # THE elasticity contract: scaling down re-prefills NOTHING.
        "scale_down_reprefill_tokens": fleet.drain_reprefill_tokens,
        "reprefill_tokens_total": fleet.reprefill_tokens,
        "liveness_false_positives": len(fleet.false_positive_deaths),
        "correction_factor_itl": round(planner.feedback_itl.value, 3),
        "select_worker_cost": {"small": small, "large": large},
        "fault_plane": _fault_plane_record(fault_activity0),
    }


async def run_tool_call_leg(n_deltas: int = 48, delta_sleep_s: float = 0.002,
                            seed: int = 17):
    """Tool-call streaming leg (ISSUE 15), pure CPU — a scripted pipeline
    behind the REAL HttpService + incremental jail, so the leg lands on
    any backend:

      * time-to-first-tool-call-byte: one hermes call whose arguments
        span ``n_deltas`` paced deltas. Measured at the SSE wire: wall
        time to the first chunk carrying tool_calls argument bytes
        (incremental jail, O(delta)) vs wall time to stream end — the
        EARLIEST the old buffer-to-flush jail could have emitted the
        call (O(call length)). The ratio is the headline.
      * malformed recovery: seeded truncated/broken calls across the
        marker dialects — every stream must complete ([DONE] reached,
        degraded content or sealed call), zero dropped; plus one
        fault-armed stream proving the typed terminal error frame
        (error_kind=tool_call_parse).

    The clean sub-leg's fault_plane record extends the zero-spurious
    contract: parser_degraded / parser_exceptions must be ZERO there.
    """
    import random

    import aiohttp

    from dynamo_tpu.http import HttpService, ModelManager
    from dynamo_tpu.llm import ModelDeploymentCard
    from dynamo_tpu.llm.protocols.common import (
        FinishReason,
        PostprocessedOutput,
    )
    from dynamo_tpu.parsers.observe import parser_plane
    from dynamo_tpu.runtime import fault_names as fn
    from dynamo_tpu.runtime.faults import FaultPlan, armed

    fault_activity0 = _fault_activity_start()

    class PacedPipeline:
        def __init__(self, deltas, pace_s=0.0):
            self.deltas, self.pace_s = deltas, pace_s

        async def generate(self, request, context):
            yield {"annotation": "_prompt_tokens", "value": 3}
            for i, text in enumerate(self.deltas):
                if self.pace_s:
                    await asyncio.sleep(self.pace_s)
                yield PostprocessedOutput(
                    text=text, token_ids=[i], cumulative_tokens=i + 1,
                    finish_reason=(
                        FinishReason.EOS
                        if i == len(self.deltas) - 1 else None
                    ),
                )

    async def serve(deltas, pace_s=0.0):
        manager = ModelManager()
        manager.register(
            "bench-tools", PacedPipeline(deltas, pace_s),
            ModelDeploymentCard(name="bench-tools", context_length=512),
        )
        service = HttpService(manager, host="127.0.0.1", port=0)
        port = await service.start()
        return service, port

    async def stream_once(port, collect_first_args=True):
        t0 = time.perf_counter()
        first_args_t = None
        saw_done = False
        error_frame = None
        n_args_chunks = 0
        content_chars = 0
        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                json={
                    "model": "bench-tools",
                    "messages": [{"role": "user", "content": "x"}],
                    "tools": [{"type": "function",
                               "function": {"name": "f"}}],
                    "stream": True,
                },
            )
            async for line in r.content:
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                if line == "data: [DONE]":
                    saw_done = True
                    continue
                payload = json.loads(line[6:])
                if "error" in payload:
                    error_frame = payload["error"]
                    continue
                delta = payload["choices"][0]["delta"]
                content_chars += len(delta.get("content", ""))
                for entry in delta.get("tool_calls", []):
                    if (entry.get("function") or {}).get("arguments"):
                        n_args_chunks += 1
                        if first_args_t is None:
                            first_args_t = time.perf_counter() - t0
        return {
            "first_args_s": first_args_t,
            "end_s": time.perf_counter() - t0,
            "saw_done": saw_done,
            "error_frame": error_frame,
            "args_chunks": n_args_chunks,
            "content_chars": content_chars,
        }

    # -- sub-leg 1: time-to-first-tool-call-byte ---------------------------
    args_body = ", ".join(f'"k{i}": {i}' for i in range(n_deltas))
    call_text = (
        '<tool_call>{"name": "f", "arguments": {' + args_body
        + '}}</tool_call>'
    )
    step = max(1, len(call_text) // n_deltas)
    deltas = [call_text[i:i + step] for i in range(0, len(call_text), step)]
    service, port = await serve(deltas, pace_s=delta_sleep_s)
    try:
        clean = await stream_once(port)
    finally:
        await service.stop(grace_period=1)
    assert clean["saw_done"] and clean["error_frame"] is None
    # The zero-spurious record is cut HERE: the clean sub-leg must show
    # zero parser-plane activations.
    clean_fault_record = _fault_plane_record(fault_activity0)

    # -- sub-leg 2: malformed recovery -------------------------------------
    malformed = [
        '<tool_call>{"name": "f", "arguments": {"a": [1, 2',
        '<tool_call>{"name": "f", "arguments": {"a": 1]]}',
        '[TOOL_CALLS]{"name": "f", "argu',
        '<｜DSML｜function_calls><｜DSML｜invoke name="x">'
        '<｜DSML｜parameter name="k" string="true">v',
        '<|channel|>commentary to=functions.f <|message|>{"a": ',
        '<tool_call><function=f><parameter=k>v',
    ]
    rng = random.Random(seed)
    completed = 0
    degrades_before = sum(parser_plane().degrades.values())
    for text in malformed:
        n = rng.randint(1, min(6, len(text) - 1))
        cuts = sorted(rng.sample(range(1, len(text)), n))
        parts, last = [], 0
        for c in cuts:
            parts.append(text[last:c])
            last = c
        parts.append(text[last:])
        service, port = await serve(parts)
        try:
            res = await stream_once(port)
        finally:
            await service.stop(grace_period=1)
        if res["saw_done"] and res["error_frame"] is None:
            completed += 1
    # MEASURED ladder activations (the parser plane's counters), not an
    # assumption — a regression that silently passed malformed text
    # through would read degraded < streams here.
    degraded = sum(parser_plane().degrades.values()) - degrades_before

    # -- sub-leg 3: injected parser death → typed frame --------------------
    service, port = await serve(["safe ", '<tool_call>{"name": "f"'])
    plan = FaultPlan.from_dict({
        "seed": seed,
        "rules": [{"point": fn.PARSER_JAIL_FEED, "kind": "error",
                   "at": [2]}],
    })
    try:
        with armed(plan):
            res = await stream_once(port)
    finally:
        await service.stop(grace_period=1)
    typed_frame_ok = (
        res["error_frame"] is not None
        and res["error_frame"].get("error_kind") == "tool_call_parse"
    )

    plane = parser_plane()
    return {
        # O(delta) vs O(call length): first argument byte vs stream end.
        "ttfcb_ms": round(clean["first_args_s"] * 1e3, 2),
        "stream_end_ms": round(clean["end_s"] * 1e3, 2),
        "ttfcb_speedup_vs_flush_jail": round(
            clean["end_s"] / max(clean["first_args_s"], 1e-9), 2
        ),
        "args_chunks_streamed": clean["args_chunks"],
        "call_deltas": len(deltas),
        "malformed_streams": len(malformed),
        "malformed_completed": completed,
        "malformed_dropped": len(malformed) - completed,
        "malformed_degraded": degraded,
        "parse_error_frame_typed": typed_frame_ok,
        "parser_plane": plane.snapshot(),
        # Zero-spurious contract (clean sub-leg only): parser_degraded
        # and parser_exceptions must both read 0 here.
        "fault_plane": clean_fault_record,
    }


async def run_kv_reuse_leg(n_prefixes: int = 6, requests: int = 36,
                           isl: int = 96, osl: int = 8, seed: int = 23):
    """KV-reuse leg (ISSUE 16): a tiny REAL engine (prefix caching on)
    under a shared-prefix traffic mix vs a cold-cache control — lands on
    any backend:

      * hit rate by tier + reused/recomputed prefill tokens + priced
        prefill-seconds-saved, read from the KV-reuse plane's counters
        (the same numbers /debug/kvcache serves);
      * p50 TTFT delta: shared-prefix wave vs the control wave of
        distinct random prompts (the cache's actual latency win);
      * top-prefix coherence: the sketch's hot anchors must cover the
        shared prefixes the leg just replayed.
    """
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import tiny_config
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.kv_reuse_observe import global_plane

    fault_activity0 = _fault_activity_start()
    block_size = 8
    rng = np.random.default_rng(seed)
    # Prefix length is a whole number of blocks so the replayed prefix
    # is fully matchable; the 2-block suffix keeps every request distinct.
    prefix_len = (isl - 2 * block_size) // block_size * block_size
    prefixes = [
        rng.integers(10, 200, size=prefix_len).tolist()
        for _ in range(n_prefixes)
    ]

    async def sub_leg(shared: bool) -> dict:
        engine = JaxEngine(
            JaxEngineArgs(
                config=tiny_config(),
                block_size=block_size,
                num_kv_blocks=1024,
                max_num_seqs=8,
                max_model_len=isl + osl + 2 * block_size,
                prefill_chunk=32,
                enable_prefix_caching=True,
                decode_steps=4,
            )
        )
        before = _kv_reuse_start()
        ttfts: list = []

        async def run_one(i: int) -> None:
            if shared:
                toks = (
                    prefixes[i % n_prefixes]
                    + rng.integers(10, 200, size=isl - prefix_len).tolist()
                )
            else:
                toks = rng.integers(10, 200, size=isl).tolist()
            request = PreprocessedRequest(
                token_ids=toks,
                request_id=f"kvreuse-{'warm' if shared else 'cold'}-{i}",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            t0 = time.monotonic()
            ttft = None
            async for out in engine.generate(request, Context()):
                if out.token_ids and ttft is None:
                    ttft = time.monotonic() - t0
            if ttft is not None:
                ttfts.append(ttft)

        sem = asyncio.Semaphore(4)

        async def limited(i: int) -> None:
            async with sem:
                await run_one(i)

        if shared:
            # Prime wave: first touch of each prefix is the unavoidable
            # cold miss — measured TTFTs start after it.
            await asyncio.gather(*(limited(i) for i in range(n_prefixes)))
            ttfts.clear()
        await asyncio.gather(
            *(limited(n_prefixes + i) for i in range(requests))
        )
        await engine.stop()
        record = _kv_reuse_record(before)
        record["p50_ttft_ms"] = round(
            1000 * sorted(ttfts)[len(ttfts) // 2], 2
        )
        return record

    async def tier_sub_leg() -> dict:
        """Speculative-vs-serialized onboard (ISSUE 17): prime the host
        tier, drop the device cache so every warm request must walk
        G2→G1, then replay the warm wave twice — once hintless (admission
        onboards serially, the pre-17 critical path) and once with the
        router hint stamped (the walk overlaps the queue wait). Columns:
        the TTFT pair, prefetch_hits/prefetch_wasted, and the measured
        onboard_overlap_ms the speculation bought. The serialized wave
        doubles as the zero-spurious control: no hint, no prefetch.

        max_num_seqs is deliberately small: the speculation's win IS the
        queue wait it overlaps — with no queue, both waves pay the same
        walk and the hint buys nothing."""
        from dynamo_tpu.kvbm import HostTier, TieredKvManager

        engine = JaxEngine(
            JaxEngineArgs(
                config=tiny_config(),
                block_size=block_size,
                num_kv_blocks=1024,
                max_num_seqs=2,
                max_model_len=isl + osl + 2 * block_size,
                prefill_chunk=32,
                enable_prefix_caching=True,
                decode_steps=4,
            )
        )
        kvbm = TieredKvManager(HostTier(4096))
        kvbm.attach(engine)

        def pv(outcome: str) -> int:
            return int(kvbm.metrics.prefetches.value(outcome=outcome))

        async def wave(tag: str, hint: bool) -> float:
            ttfts: list = []

            async def run_one(i: int) -> None:
                toks = (
                    prefixes[i % n_prefixes]
                    + rng.integers(10, 200, size=isl - prefix_len).tolist()
                )
                request = PreprocessedRequest(
                    token_ids=toks,
                    request_id=f"kvtier-{tag}-{i}",
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=osl, ignore_eos=True),
                )
                if hint:
                    request.estimated_prefix_hit_blocks = (
                        prefix_len // block_size
                    )
                t0 = time.monotonic()
                ttft = None
                async for out in engine.generate(request, Context()):
                    if out.token_ids and ttft is None:
                        ttft = time.monotonic() - t0
                if ttft is not None:
                    ttfts.append(ttft)

            # More offered concurrency than engine slots: requests QUEUE,
            # which is exactly the window speculation overlaps.
            sem = asyncio.Semaphore(8)

            async def limited(i: int) -> None:
                async with sem:
                    await run_one(i)

            await asyncio.gather(*(limited(i) for i in range(requests)))
            return round(1000 * sorted(ttfts)[len(ttfts) // 2], 2)

        try:
            # Prime: one pass commits every prefix; write-through offload
            # lands the blocks in the host tier.
            await wave("prime", hint=False)
            await asyncio.sleep(0.3)
            spurious = sum(
                pv(o) for o in ("claimed", "revoked", "skipped", "error")
            )
            engine.pool.clear()  # blocks now live ONLY in the tier
            serialized_ms = await wave("serial", hint=False)
            spurious += sum(
                pv(o) for o in ("claimed", "revoked", "skipped", "error")
            )
            engine.pool.clear()
            speculative_ms = await wave("spec", hint=True)
            n_overlap, overlap_s = kvbm.metrics.prefetch_overlap.snapshot_total()
            return {
                "tier_blocks": len(kvbm.tier),
                "p50_ttft_ms_serialized": serialized_ms,
                "p50_ttft_ms_speculative": speculative_ms,
                "speculative_ttft_delta_ms": round(
                    serialized_ms - speculative_ms, 2
                ),
                "prefetch_hits": pv("claimed"),
                "prefetch_wasted": int(
                    kvbm.metrics.prefetch_blocks.value(outcome="wasted")
                ),
                "onboard_overlap_ms": round(1000 * overlap_s, 2),
                "onboard_overlap_count": int(n_overlap),
                # Hintless traffic must never speculate: nonzero here is
                # the prefetch plane activating spuriously.
                "spurious_prefetches": int(spurious),
            }
        finally:
            await kvbm.close()
            await engine.stop()

    def eviction_ab_sub_leg(capacity: int = 64, n_keys: int = 256,
                            draws: int = 4000) -> dict:
        """Popularity-vs-LRU eviction A/B at equal capacity: the same
        zipf-skewed single-block stream against a plain-LRU host tier and
        against one scored by the REAL manager bridge (sketch → protected
        prefixes). The popularity side must hold the heavy hitters
        through cold-key bursts LRU lets evict them."""
        from dynamo_tpu.kvbm import HostTier, OffloadFilter, TieredKvManager
        from dynamo_tpu.runtime.kv_reuse_observe import KvReusePlane

        ab_rng = np.random.default_rng(seed + 1)
        ranks = np.minimum(ab_rng.zipf(1.2, size=draws), n_keys) - 1
        keys = (
            (np.arange(1, n_keys + 1, dtype=np.uint64)
             * np.uint64(0x9E3779B97F4A7C15))
            & np.uint64(0x7FFFFFFFFFFFFFFF)
        ).astype(np.int64)
        payload = np.zeros(1, dtype=np.int8)

        def run(policy: str) -> float:
            host = HostTier(capacity)
            plane = KvReusePlane(capacity=n_keys)
            kvbm = None
            if policy == "popularity":
                kvbm = TieredKvManager(
                    host, plane=plane,
                    filter=OffloadFilter(min_frequency=10**9),
                )
            hits = 0
            for j, r in enumerate(ranks):
                h = int(keys[r])
                if j == draws // 2:
                    # Let the protected-map rebuild throttle expire so
                    # the second half runs with a sketch-warmed scorer.
                    time.sleep(0.55)
                if host.contains(h):
                    hits += 1
                    host.get(h)
                    plane.sketch.touch(h, tokens=block_size)
                else:
                    host.put(h, payload, payload)
                    if kvbm is not None:
                        kvbm.notify_commit(h, 1)
            if kvbm is not None:
                for name in list(kvbm.metrics._tier_sources):
                    kvbm.metrics.unwatch_tier(name)
                plane.forget_tier_source(kvbm._plane_label)
            return hits / draws

        lru_rate = run("lru")
        pop_rate = run("popularity")
        return {
            "capacity_blocks": capacity,
            "distinct_keys": n_keys,
            "draws": draws,
            "hit_rate_lru": round(lru_rate, 4),
            "hit_rate_popularity": round(pop_rate, 4),
            "popularity_wins": bool(pop_rate > lru_rate),
        }

    warm = await sub_leg(shared=True)
    cold = await sub_leg(shared=False)
    tier = await tier_sub_leg()
    eviction_ab = eviction_ab_sub_leg()
    top = global_plane().sketch.top(n_prefixes)
    return {
        "n_prefixes": n_prefixes,
        "requests_per_sub_leg": requests,
        "isl": isl,
        "osl": osl,
        "hit_rate": warm["hit_rate"],
        "hit_rate_by_tier": warm["hit_rate_by_tier"],
        "prefill_tokens_saved": warm["tokens_saved"],
        "prefill_seconds_saved": warm["prefill_seconds_saved"],
        "p50_ttft_ms_warm": warm["p50_ttft_ms"],
        "p50_ttft_ms_cold": cold["p50_ttft_ms"],
        "ttft_delta_ms": round(
            cold["p50_ttft_ms"] - warm["p50_ttft_ms"], 2
        ),
        "cold_control": cold,
        "tier_onboard": tier,
        "eviction_ab": eviction_ab,
        "top_prefixes_tracked": len(top),
        "fault_plane": _fault_plane_record(fault_activity0),
    }


async def run_tick_budget_leg(decode_streams: int = 4, decode_isl: int = 64,
                              decode_osl: int = 512, wave_n: int = 3,
                              wave_isl: int = 2048, wave_osl: int = 16,
                              seed: int = 31):
    """Tick-budgeter leg (ISSUE 18): a prefill-heavy wave (ISL-2048) lands
    on a steady decode population (OSL-512) inside ONE tiny real engine —
    lands on any backend:

      * aggregated mode (budgeter off): each admission prefills to
        COMPLETION inside its tick, so the wave stalls every decode
        stream for the full multi-thousand-token prefill — p99 ITL blows
        through the SLA band;
      * budgeted mode (TickBudgeter on): per-tick prefill is capped at
        the live budget, the parked remainder resumes next tick behind a
        decode burst — p99 ITL holds inside the band at ≥0.9× aggregated
        throughput (the wave finishes a few ticks later; no work is
        dropped).

    The SLA band is derived from the leg's own measurements — steady
    p50 plus one prefill chunk-round stall amortized over a decode
    burst, ×3 slack — so the contract is about interleaving, not host
    speed: the band is the structural floor any intra-chip interleaver
    pays (one possibly-overdrawn round per tick), which budgeted mode
    holds and prefill-to-completion blows through by orders of
    magnitude.
    """
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import tiny_config
    from dynamo_tpu.runtime.context import Context

    fault_activity0 = _fault_activity_start()
    cfg = tiny_config()
    rng = np.random.default_rng(seed)
    decode_prompts = [
        rng.integers(10, 200, size=decode_isl).tolist()
        for _ in range(decode_streams)
    ]
    wave_prompts = [
        rng.integers(10, 200, size=wave_isl).tolist() for _ in range(wave_n)
    ]
    # Warmup-only long prompt: distinct tokens (the measured wave must not
    # ride the prefix cache) but the same SHAPE class — decoding at wave
    # context length compiles the wide block-table-bucket decode program
    # outside the measured window.
    warm_prompt = rng.integers(10, 200, size=wave_isl).tolist()

    def mk_args(**over):
        base = dict(
            config=cfg,
            block_size=16,
            num_kv_blocks=1024,
            max_num_seqs=decode_streams + wave_n,
            max_model_len=wave_isl + decode_osl + 64,
            prefill_chunk=64,
            prefill_batch=2,
            decode_steps=8,
        )
        base.update(over)
        return JaxEngineArgs(**base)

    # Prompts sized to a full prefill round (prefill_batch × chunk
    # rows' worth of tokens) — timed on the warmed aggregated engine to
    # calibrate the SLA band's chunk-round term. Distinct prompts per
    # pass so the second can't ride the prefix cache.
    calib_prompts = [
        rng.integers(10, 200, size=2 * 64).tolist() for _ in range(2)
    ]

    async def sub_leg(args, sla_s=None, calibrate=False):
        """One mixed-traffic pass → (itl samples, stats, wall, tokens).

        ITL samples are (t, seconds/token) reap-gap measurements taken
        client-side on the DECODE population only; the wave's streams
        contribute load, not samples."""
        engine = JaxEngine(args)
        samples: list = []  # (monotonic t, per-token gap s)
        total_tokens = [0]

        async def decode_one(i):
            req = PreprocessedRequest(
                token_ids=decode_prompts[i],
                request_id=f"tb-decode-{i}",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=decode_osl, ignore_eos=True),
            )
            last = None
            async for out in engine.generate(req, Context()):
                n = len(out.token_ids or [])
                now = time.monotonic()
                if n and last is not None:
                    samples.append((now, (now - last) / n))
                if n:
                    last = now
                    total_tokens[0] += n

        async def wave_one(i):
            req = PreprocessedRequest(
                token_ids=wave_prompts[i],
                request_id=f"tb-wave-{i}",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=wave_osl, ignore_eos=True),
            )
            async for out in engine.generate(req, Context()):
                total_tokens[0] += len(out.token_ids or [])

        try:
            # Warmup: trigger the compiles outside the measured window —
            # the decode-population shapes AND a wave-length stream (its
            # 2048-token context decodes in a wider block-table bucket;
            # without this the first wave join pays that compile inside
            # the measured wave, in both modes).
            warm_req = PreprocessedRequest(
                token_ids=warm_prompt,
                request_id="tb-warm-wave",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=8, ignore_eos=True),
            )

            async def warm_wave():
                async for _ in engine.generate(warm_req, Context()):
                    pass

            await asyncio.gather(decode_one(0), warm_wave())
            samples.clear()
            total_tokens[0] = 0
            t0 = time.monotonic()
            decoders = [
                asyncio.ensure_future(decode_one(i))
                for i in range(decode_streams)
            ]
            # Let the population reach steady state, then land the wave.
            await asyncio.sleep(0.0)
            while not samples:
                await asyncio.sleep(0.01)
            steady_until = time.monotonic() + 0.25
            while time.monotonic() < steady_until:
                await asyncio.sleep(0.01)
            wave_at = time.monotonic()
            await asyncio.gather(
                *(wave_one(i) for i in range(wave_n)), *decoders
            )
            wall = time.monotonic() - t0
            round_s = 0.0
            if calibrate:
                # Time one round-sized prefill on the warmed, now-idle
                # engine: the per-tick stall an interleaver cannot avoid.
                # Two passes — the first absorbs any compile this exact
                # ragged shape still owes; the second is the number.
                for attempt in range(2):
                    creq = PreprocessedRequest(
                        token_ids=calib_prompts[attempt],
                        request_id=f"tb-calib-{attempt}",
                        sampling=SamplingOptions(temperature=0.0),
                        stop=StopConditions(max_tokens=1, ignore_eos=True),
                    )
                    c0 = time.monotonic()
                    async for _ in engine.generate(creq, Context()):
                        pass
                    round_s = time.monotonic() - c0
            stats = engine.stats()
            return {
                "round_s": round_s,
                "steady": [s for t, s in samples if t < wave_at],
                "wave": [s for t, s in samples if t >= wave_at],
                "wall_s": wall,
                "tokens": total_tokens[0],
                "prefill_budget_tokens": stats.get(
                    "prefill_budget_tokens", 0
                ),
                "budget_state": stats.get("budget_state", 0),
                "budget_rollovers": stats.get("budget_rollovers", 0),
            }
        finally:
            await engine.stop()

    def pct(vals, q):
        if not vals:
            return 0.0
        vals = sorted(vals)
        return vals[min(int(q * len(vals)), len(vals) - 1)]

    # Aggregated control first: its pre-wave steady phase + a calibrated
    # chunk-round cost define the SLA band both modes are judged against.
    # Band = 3 × (steady p99 + one chunk-round stall). Steady p99 (not
    # p50) folds the host's scheduling-noise floor into the baseline — a
    # p99-vs-p99 contract. The round term is the structural ITL floor of
    # ANY intra-chip interleaver (the budget check runs before each
    # round, so one round may overdraw); it enters un-amortized as grace
    # for the under-load overheads an idle-engine calibration can't see,
    # and is still ~wave_isl/round ≈ 50× below the prefill-to-completion
    # stall, so the aggregated breach stays structural. Host-speed
    # independent: a slower host inflates both terms and the measured
    # gaps together.
    agg = await sub_leg(mk_args(), calibrate=True)
    sla_s = 3.0 * (pct(agg["steady"], 0.99) + agg["round_s"])
    bud = await sub_leg(
        mk_args(
            tick_budget_enabled=True,
            # Strict-ITL posture: start at the floor and let proven
            # headroom earn budget back, with the ceiling sized so even a
            # fully-grown budget admits at most ONE [prefill_batch, chunk]
            # round per tick — the budgeted run sits inside the band by
            # construction, not by racing the control loop. The AIMD
            # shrink path itself is proven by tests/test_tick_budget.py;
            # this leg's contract is the interleave.
            tick_budget_floor_tokens=64,
            tick_budget_ceiling_tokens=128,
            tick_budget_policy=0.0,
            tick_budget_itl_slo_s=sla_s,
        ),
        sla_s=sla_s,
    )
    agg_p99 = pct(agg["wave"], 0.99)
    bud_p99 = pct(bud["wave"], 0.99)
    agg_tps = agg["tokens"] / agg["wall_s"]
    bud_tps = bud["tokens"] / bud["wall_s"]
    ratio = bud_tps / agg_tps if agg_tps > 0 else 0.0
    return {
        "decode_streams": decode_streams,
        "decode_osl": decode_osl,
        "wave_n": wave_n,
        "wave_isl": wave_isl,
        "sla_itl_ms": round(1000 * sla_s, 3),
        "calib_round_ms": round(1000 * agg["round_s"], 3),
        "aggregated": {
            "p99_itl_ms": round(1000 * agg_p99, 3),
            "toks_per_s": round(agg_tps, 1),
            "itl_samples": len(agg["wave"]),
        },
        "budgeted": {
            "p99_itl_ms": round(1000 * bud_p99, 3),
            "toks_per_s": round(bud_tps, 1),
            "itl_samples": len(bud["wave"]),
            "prefill_budget_tokens": bud["prefill_budget_tokens"],
            "budget_state": bud["budget_state"],
            "budget_rollovers": bud["budget_rollovers"],
        },
        # THE contract: the budgeter holds the band the aggregated mode
        # blows through, at ≥0.9× the aggregated throughput.
        "sla_held": bool(bud_p99 <= sla_s),
        "aggregated_breached": bool(agg_p99 > sla_s),
        "throughput_ratio": round(ratio, 3),
        "throughput_ratio_ok": bool(ratio >= 0.9),
        "fault_plane": _fault_plane_record(fault_activity0),
    }


# v5e inter-chip ICI: public spec is 400 Gbps/chip each direction
# (~50 GB/s); 45 GB/s effective grants the usual ~90% achieved link rate.
# Used ONLY by the 70B tp8 projection's collective term (one chip cannot
# measure an 8-chip ring; every other projection input is measured).
V5E_ICI_BW = 45e9


def run_70b_projection_leg(batch: int = 64, ctx_tokens: int = 640,
                           tp: int = 8, block_size: int = 16):
    """Modeled Llama-3-70B tp8 decode projection (ROADMAP item 1: the
    v5e-64 north star finally gets a number attached). The model is

        step_s = L × per_layer_s  +  L × comms_s
        tok/s  = batch / step_s   (÷ tp for the per-chip figure)

    where ``per_layer_s`` is MEASURED on this chip by running the fused
    decode megakernel at the exact per-chip tp8 shard shape (d=8192
    activations resident, heads/kv-heads/d_ff divided by tp → H=8, KH=1,
    d_ff=3584, int8 weights ≈ 107 MB/layer, 80 layers ≈ 8.6 GB/chip) over
    a ``ctx_tokens`` history, and ``comms_s`` is the per-layer pair of
    tensor-parallel all-reduces ([batch, d] bf16 after o-proj and after
    down-proj) on the v5e ICI ring: 2 × 2(tp−1)/tp × bytes / ICI_BW —
    the one term a single chip cannot measure, taken from the public link
    rate and recorded next to the measured inputs.
    """
    import jax.numpy as jnp

    from dynamo_tpu.models.config import ModelConfig, llama3_70b_config
    from dynamo_tpu.ops.pallas.fused_layer import supports_reason

    full = llama3_70b_config()
    shard = ModelConfig(
        name="llama-3-70b-tp8-shard",
        vocab_size=1024,  # irrelevant to the per-layer measurement
        d_model=full.d_model,
        n_layers=1,
        n_heads=full.n_heads // tp,
        n_kv_heads=max(full.n_kv_heads // tp, 1),
        head_dim=full.head_dim_,
        d_ff=full.d_ff // tp,
        rope_theta=full.rope_theta,
        dtype=jnp.bfloat16,
    )
    assert supports_reason(shard, lora=False, quantized_weights=True) is None

    D = shard.head_dim_
    HD = shard.n_heads * D
    KHD = shard.n_kv_heads * D
    wbytes_layer = (
        shard.d_model * HD + 2 * shard.d_model * KHD + HD * shard.d_model
        + 3 * shard.d_model * shard.d_ff
    )  # int8 = 1 byte/param
    kv_bytes_layer = batch * ctx_tokens * shard.n_kv_heads * D * 2 * 2
    pages = ctx_tokens // block_size

    from dynamo_tpu.models.quantize import init_quantized_params
    from dynamo_tpu.ops.pallas.fused_layer import fused_decoder_layer
    from dynamo_tpu.ops.rope import rope_table

    params = init_quantized_params(shard, 0)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    NB = batch * pages + 8
    k_pool = jnp.zeros((NB, block_size, shard.n_kv_heads, D), jnp.bfloat16)
    v_pool = jnp.zeros_like(k_pool)
    tables = jnp.asarray(
        (np.arange(batch * pages, dtype=np.int32) % NB).reshape(
            batch, pages
        )
    )
    start_pos = jnp.full((batch,), ctx_tokens - 1, jnp.int32)
    cos, sin = rope_table(start_pos[:, None], D, shard.rope_theta)
    x = jnp.zeros((batch, shard.d_model), jnp.bfloat16)

    def run():
        return fused_decoder_layer(
            x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables,
            start_pos, eps=shard.rms_norm_eps, sm_scale=D**-0.5,
            batch_block=4,
        )

    jax.block_until_ready(run())  # compile
    n = 30
    t0 = time.perf_counter()
    out = None
    for _ in range(n):
        out = run()
    jax.block_until_ready(out)
    per_layer_s = (time.perf_counter() - t0) / n

    # Two per-layer TP all-reduces of the [batch, d] bf16 activations.
    ar_bytes = batch * shard.d_model * 2
    comms_s_layer = 2 * (2 * (tp - 1) / tp) * ar_bytes / V5E_ICI_BW
    L = full.n_layers
    step_s = L * (per_layer_s + comms_s_layer)
    toks_per_sec = batch / step_s
    return {
        "model": full.name,
        "tp": tp,
        "batch": batch,
        "ctx_tokens": ctx_tokens,
        "measured_per_layer": True,
        "per_layer_ms": round(per_layer_s * 1000, 4),
        "comms_ms_per_layer": round(comms_s_layer * 1000, 4),
        "weight_bytes_per_layer": wbytes_layer,
        "kv_bytes_per_layer": kv_bytes_layer,
        "ici_bw_bytes_per_s": V5E_ICI_BW,
        "formula": (
            "step_s = 80 x (per_layer_s + 2 x 2(tp-1)/tp x "
            "batch*d*2 / ICI_BW); tok/s = batch / step_s"
        ),
        "projected_step_ms": round(step_s * 1000, 3),
        "projected_toks_per_sec": round(toks_per_sec, 1),
        "projected_toks_per_sec_per_chip": round(toks_per_sec / tp, 1),
        "anchor_toks_per_sec": round(
            _anchor_toks_per_sec(full, batch, ctx_tokens, "int8") / tp, 1
        ),
        "note": (
            "per-layer compute measured on ONE chip at the tp8 shard "
            "shape (comms term modeled from the public ICI rate)"
        ),
    }


async def collect_silent(engine, req):
    """Drain one warmup stream, ignoring its outputs."""
    from dynamo_tpu.runtime.context import Context

    async for _ in engine.generate(req, Context()):
        pass


def _leg_error(failed: list, name: str, exc: Exception) -> dict:
    """A leg that raised still lands in the JSON, and fails the run."""
    failed.append(name)
    return {"error": f"{type(exc).__name__}: {exc}"}


async def run_bench() -> list:
    """Run every leg, print the one JSON line, return the legs that
    raised (non-empty → the process exits non-zero)."""
    failed: list = []
    model_name = os.environ.get("BENCH_MODEL", "qwen2.5-0.5b")
    quant = os.environ.get("BENCH_QUANT") or None
    spec = os.environ.get("BENCH_SPEC") or None
    primary = await run_leg(model_name, quant, spec)

    secondary = None
    if (
        os.environ.get("BENCH_SECONDARY", "1") != "0"
        and model_name == "qwen2.5-0.5b"
        and jax.default_backend() == "tpu"
    ):
        # BASELINE config-2 proxy: the largest BASELINE-relevant dense shape
        # one 16 GB chip serves — Llama-3-8B weight-only int8. Concurrency
        # sized to the KV that fits beside 8 GB of weights.
        try:
            secondary = await run_leg(
                "llama3-8b", "int8", None, concurrency=64, requests=128
            )
        except Exception as exc:
            secondary = _leg_error(failed, "secondary", exc)

    value = primary["toks_per_sec_per_chip"]
    out = {
        "metric": (
            f"aggregated decode throughput ({primary['model']}-shape, "
            f"ISL={ISL}, OSL={OSL})"
        ),
        "value": value,
        "unit": "tokens/sec/chip",
        # vs the DERIVED anchor (see module docstring): A100-80G HBM
        # bandwidth roofline × 0.6 achieved-bandwidth for the same
        # model/batch/context — not an invented constant.
        "vs_baseline": round(value / primary["anchor_toks_per_sec"], 4),
        "anchor": {
            "source": (
                "derived A100-80G + vLLM-class decode estimate: per-step "
                "time = max(step_bytes / (2039 GB/s x 0.6 achieved), "
                "n_layers x 0.3ms kernel-launch floor) for the same "
                "model/batch/context; per-chip is bandwidth-lopsided "
                "(A100 HBM = 2.5x v5e), so vs_baseline_per_dollar uses "
                "public on-demand prices (A100 $3.67/hr, v5e $1.20/hr)"
            ),
            "formula": (
                "B / max((w_bytes + B*ctx*kv_bytes)/(BW*eff), L*3e-4)"
            ),
            "toks_per_sec": primary["anchor_toks_per_sec"],
        },
        "vs_baseline_per_dollar": round(
            (value / V5E_USD_HR)
            / (primary["anchor_toks_per_sec"] / A100_80G_USD_HR), 4,
        ),
        "total_tokens": primary["total_tokens"],
        "wall_s": primary["wall_s"],
        "p50_ttft_ms": primary["p50_ttft_ms"],
        "p50_itl_ms": primary["p50_itl_ms"],
        "pipeline_depth": primary["pipeline_depth"],
        "host_gap_ms": primary["host_gap_ms"],
        # Share of decode bursts on the fused megakernel (see run_leg).
        "fused_coverage": primary["fused_coverage"],
        "mk_fused_bursts": primary["mk_fused_bursts"],
        "mk_fallback_bursts": primary["mk_fallback_bursts"],
        # Device-plane trajectory (ISSUE 4): compile + memory regressions
        # are perf regressions the tok/s headline can hide for one run.
        "compile_s": primary["compile_s"],
        "compiles": primary["compiles"],
        "compiled_programs": primary["compiled_programs"],
        "recompile_storms": primary["recompile_storms"],
        "hbm_ledger_bytes": primary["hbm_ledger_bytes"],
        "hbm_ledger_peak_bytes": primary["hbm_ledger_peak_bytes"],
        "mfu": primary["mfu"],
        "hbm_util": primary["hbm_util"],
        "n_chips": jax.device_count(),
        "backend": jax.default_backend(),
        **_record_stamp(model_name, quant),
        **{
            k: primary[k]
            for k in ("spec_proposed", "spec_accepted")
            if k in primary
        },
    }
    if secondary is not None:
        if "anchor_toks_per_sec" in secondary:
            secondary["vs_baseline"] = round(
                secondary["toks_per_sec_per_chip"]
                / secondary["anchor_toks_per_sec"], 4,
            )
        out["secondary"] = secondary

    if (
        os.environ.get("BENCH_SECONDARY_LONG", "1") != "0"
        and model_name == "qwen2.5-0.5b"
        and jax.default_backend() == "tpu"
    ):
        # Decode-dominated 8B leg (ISL 128 / OSL 512, int8 KV): the regime
        # the ITL SLA + decode anchor actually measure — at OSL 64 the
        # prefill wall dominates.
        try:
            # requests = 2 FULL waves: a partial tail wave at OSL=512
            # decodes half-empty for ~13s and halves the reported rate
            long_leg = await run_leg(
                "llama3-8b", "int8", None, concurrency=64, requests=128,
                kv_quant="int8", osl=512,
            )
            if "anchor_toks_per_sec" in long_leg:
                long_leg["vs_baseline"] = round(
                    long_leg["toks_per_sec_per_chip"]
                    / long_leg["anchor_toks_per_sec"], 4,
                )
            out["secondary_long"] = long_leg
        except Exception as exc:
            out["secondary_long"] = _leg_error(failed, "secondary_long", exc)

    if (
        os.environ.get("BENCH_DISAGG", "1") != "0"
        and model_name == "qwen2.5-0.5b"
        and jax.default_backend() == "tpu"
    ):
        try:
            out["disagg"] = await run_disagg_leg()
        except Exception as exc:
            out["disagg"] = _leg_error(failed, "disagg", exc)

    if (
        os.environ.get("BENCH_OVERLOAD", "1") != "0"
        and model_name == "qwen2.5-0.5b"
        and jax.default_backend() == "tpu"
    ):
        # Overload-armor leg (ISSUE 8): open-loop ramp past calibrated
        # capacity; the under-capacity sub-leg carries the
        # zero-spurious-activation contract (no sheds, no brownout
        # transitions), the 4x sub-leg proves bounded queueing + typed
        # shedding.
        try:
            out["overload"] = await run_overload_leg()
        except Exception as exc:
            out["overload"] = _leg_error(failed, "overload", exc)

    if (
        os.environ.get("BENCH_DRAIN", "1") != "0"
        and model_name == "qwen2.5-0.5b"
        and jax.default_backend() == "tpu"
    ):
        # Drain leg (ISSUE 9): SIGTERM a worker mid-load; dropped==0,
        # handoff bytes, re-prefill tokens, worst mid-stream stall.
        try:
            out["drain"] = await run_drain_leg()
        except Exception as exc:
            out["drain"] = _leg_error(failed, "drain", exc)

    if os.environ.get("BENCH_PROJECTION", "1") != "0":
        # Modeled 70B tp8 projection (ROADMAP item 1): measured per-layer
        # megakernel step on TPU (roofline-modeled elsewhere) × 80-layer
        # arithmetic + ICI collective cost. Always recorded.
        try:
            out["projection_70b_tp8"] = run_70b_projection_leg()
        except Exception as exc:
            out["projection_70b_tp8"] = _leg_error(failed, "projection_70b_tp8", exc)

    if (
        os.environ.get("BENCH_CRASH", "1") != "0"
        and model_name == "qwen2.5-0.5b"
        and jax.default_backend() == "tpu"
    ):
        # Crash leg (ISSUE 10): a worker goes silent mid-load (the kill -9
        # shape); lost_requests must be 0, detection latency bounded by the
        # missed-report budget, re-prefilled tokens + warm-restart
        # restore_ms recorded.
        try:
            out["crash"] = await run_crash_leg()
        except Exception as exc:
            out["crash"] = _leg_error(failed, "crash", exc)

    if os.environ.get("BENCH_TOOLCALL", "1") != "0":
        # Tool-call streaming leg (ISSUE 15): time-to-first-tool-call-byte
        # O(delta) vs the old O(call-length) flush jail, malformed-call
        # recovery with zero dropped streams, and the typed parse-error
        # frame — pure CPU through the real HttpService, lands on any
        # backend.
        try:
            out["tool_call"] = await run_tool_call_leg()
        except Exception as exc:
            out["tool_call"] = _leg_error(failed, "tool_call", exc)

    if os.environ.get("BENCH_KVREUSE", "1") != "0":
        # KV-reuse leg (ISSUE 16): shared-prefix traffic through a tiny
        # real engine — hit rate by tier, prefill tokens/seconds saved,
        # and the TTFT delta vs a cold-cache control. Lands on any
        # backend.
        try:
            out["kv_reuse_leg"] = await run_kv_reuse_leg()
        except Exception as exc:
            out["kv_reuse_leg"] = _leg_error(failed, "kv_reuse_leg", exc)

    if os.environ.get("BENCH_TICKBUDGET", "1") != "0":
        # Tick-budgeter leg (ISSUE 18): ISL-2048 prefill wave over a
        # steady OSL-512 decode population — budgeted mode holds p99 ITL
        # inside the SLA band the aggregated mode blows through, at
        # ≥0.9× aggregated throughput. Tiny real engine; lands on any
        # backend.
        try:
            out["tick_budget"] = await run_tick_budget_leg()
        except Exception as exc:
            out["tick_budget"] = _leg_error(failed, "tick_budget", exc)

    if os.environ.get("BENCH_ELASTICITY", "1") != "0":
        # Elasticity leg (ISSUE 13): sim-clocked planner ramp (1×→4×→1×
        # convergence intervals), zero-re-prefill scale-down, and
        # select_worker per-request cost at 10 vs 100 workers. Pure CPU
        # arithmetic driving the real control plane — lands on any
        # backend.
        try:
            out["elasticity"] = await run_elasticity_leg()
        except Exception as exc:
            out["elasticity"] = _leg_error(failed, "elasticity", exc)

    # Sentinel epilogue (ISSUE 19): judge this round against the previous
    # usable BENCH_*.json when one exists. Table to stderr, report into
    # the record; stdout stays one JSON line and rc stays the round's.
    _sentinel_epilogue(out)
    print(json.dumps(out))
    return failed


def _require_tpu() -> None:
    """A measurement path that finds no chip fails: no skip record, no CPU
    re-exec. jax.devices() raises when the platform cannot initialise."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX selected platform {platform!r}"
        )


if __name__ == "__main__":
    import sys as _sys

    _require_tpu()
    failed_legs = asyncio.run(run_bench())
    if failed_legs:
        print(f"legs raised: {', '.join(failed_legs)}", file=_sys.stderr)
        _sys.exit(1)
