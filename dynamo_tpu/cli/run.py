"""``dynamo_tpu.cli run``: drive an engine without a cluster.

Reference parity: lib/llm/src/entrypoint/input.rs (Input::Text :31 —
interactive REPL; Input::Stdin — one prompt per line; Input::Batch — JSONL
file in, JSONL out with latency stats; Input::Http — OpenAI server over the
local pipeline). The engine is in-process: the mocker, a builtin random-init
config, or a local HF checkpoint directory.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from typing import Any, Optional, Tuple

from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.utils.logging import configure_logging, get_logger

logger = get_logger(__name__)


def add_run_args(parser: argparse.ArgumentParser) -> None:
    from dynamo_tpu import config

    parser.add_argument(
        "--input", default="text",
        help="text (REPL) | stdin | batch:FILE.jsonl | http",
    )
    parser.add_argument(
        "--model", default="mock",
        help="'mock', a builtin config name (tiny, qwen2.5-0.5b, ...), or a "
        "local HF model directory",
    )
    parser.add_argument("--served-model-name", default=None)
    parser.add_argument("--max-tokens", type=int, default=64)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--http-port", type=int, default=8080)
    parser.add_argument(
        "--block-size", type=int, default=config.KV_BLOCK_SIZE.get()
    )
    parser.add_argument("--num-kv-blocks", type=int, default=512)
    parser.add_argument("--max-model-len", type=int, default=2048)
    parser.add_argument("--out", default=None,
                        help="batch mode: output JSONL path (default stdout)")


def build_engine_and_card(args) -> Tuple[Any, ModelDeploymentCard, Any]:
    """Returns (engine, card, tokenizer)."""
    from dynamo_tpu.llm.tokenizer import tiny_tokenizer

    name = args.served_model_name or args.model
    if args.model == "mock":
        from dynamo_tpu.engines.mock import MockEngine, MockEngineArgs

        engine = MockEngine(MockEngineArgs(speedup_ratio=10.0))
        card = ModelDeploymentCard(name=name, context_length=args.max_model_len)
        return engine, card, tiny_tokenizer()

    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.utils.jax_env import configure_compile_cache
    from dynamo_tpu.worker.__main__ import BUILTIN_CONFIGS

    configure_compile_cache()
    model_path = None
    if args.model in BUILTIN_CONFIGS:
        config = BUILTIN_CONFIGS[args.model]()
        params = None
        tokenizer = tiny_tokenizer()
    else:
        from dynamo_tpu.llm.tokenizer import HFTokenizer
        from dynamo_tpu.models.config import ModelConfig
        from dynamo_tpu.models.hf_loader import load_hf_checkpoint

        model_path = args.model
        config = ModelConfig.from_model_dir(args.model)
        params = load_hf_checkpoint(args.model, config)
        tokenizer = HFTokenizer.from_pretrained_dir(args.model)
    engine = JaxEngine(
        JaxEngineArgs(
            config=config,
            block_size=args.block_size,
            num_kv_blocks=args.num_kv_blocks,
            max_model_len=args.max_model_len,
        ),
        params,
    )
    card = ModelDeploymentCard(
        name=name, model_path=model_path, context_length=args.max_model_len,
        kv_block_size=args.block_size,
        eos_token_ids=list(config.eos_token_ids),
    )
    return engine, card, tokenizer


async def _generate_text(pipeline, model: str, prompt: str, args) -> Tuple[str, int, float]:
    """One completion through the pipeline; returns (text, tokens, seconds)."""
    body = {
        "model": model,
        "prompt": prompt,
        "max_tokens": args.max_tokens,
        "temperature": args.temperature,
        "stream": True,
    }
    start = time.monotonic()
    parts = []
    n = 0
    async for item in pipeline.generate(body, Context()):
        if isinstance(item, dict):
            continue  # annotations
        if item.error:
            raise RuntimeError(item.error)
        parts.append(item.text)
        n += len(item.token_ids)
    return "".join(parts), n, time.monotonic() - start


async def run_text(pipeline, model: str, args) -> None:
    """Interactive REPL (ref: Input::Text)."""
    print(f"dynamo-tpu REPL — model {model}; Ctrl-D to exit", flush=True)
    loop = asyncio.get_running_loop()
    while True:
        try:
            line = await loop.run_in_executor(None, input, "> ")
        except EOFError:
            break
        if not line.strip():
            continue
        try:
            text, n, dt = await _generate_text(pipeline, model, line, args)
        except RuntimeError as exc:
            print(f"error: {exc}", file=sys.stderr, flush=True)
            continue
        print(text, flush=True)
        print(f"  [{n} tokens in {dt:.2f}s]", file=sys.stderr, flush=True)


async def run_stdin(pipeline, model: str, args) -> None:
    """One prompt per stdin line, completion per line out (ref: Input::Stdin)."""
    for line in sys.stdin:
        line = line.rstrip("\n")
        if not line:
            continue
        text, _, _ = await _generate_text(pipeline, model, line, args)
        print(text, flush=True)


async def run_batch(pipeline, model: str, args, batch_path: str) -> None:
    """JSONL in ({'text': ...} or {'prompt': ...}), JSONL out with stats
    (ref: Input::Batch)."""
    out_f = open(args.out, "w") if args.out else sys.stdout
    total_tokens = 0
    start = time.monotonic()
    n_requests = 0
    try:
        with open(batch_path) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                doc = json.loads(line)
                prompt = doc.get("text") or doc.get("prompt") or ""
                text, n, dt = await _generate_text(pipeline, model, prompt, args)
                total_tokens += n
                n_requests += 1
                out_f.write(
                    json.dumps(
                        {"prompt": prompt, "text": text, "tokens": n,
                         "latency_s": round(dt, 4)}
                    )
                    + "\n"
                )
                out_f.flush()
    finally:
        if args.out:
            out_f.close()
    wall = time.monotonic() - start
    print(
        f"batch done: {n_requests} requests, {total_tokens} tokens in "
        f"{wall:.2f}s ({total_tokens / max(wall, 1e-9):.1f} tok/s)",
        file=sys.stderr, flush=True,
    )


async def run_http(pipeline, card: ModelDeploymentCard, args) -> None:
    """Single-process OpenAI server over the local pipeline (in=http)."""
    from dynamo_tpu.http import HttpService, ModelManager
    from dynamo_tpu.runtime.trajectory import global_store
    from dynamo_tpu.utils.tracing import set_service

    # Trajectory plane, dev-mode wiring: attach the store to the tracer
    # BEFORE the first request so /debug/trajectory sees every span (the
    # worker/frontend mains do the same eagerly).
    set_service("dev-http")
    global_store()
    manager = ModelManager()
    manager.register(card.name, pipeline, card)
    service = HttpService(manager, host="0.0.0.0", port=args.http_port)
    port = await service.start()
    print(f"http server on :{port} serving {card.name}", flush=True)
    try:
        await asyncio.Event().wait()
    finally:
        await service.stop(grace_period=5)


# -- observe: device-plane snapshot of a running worker ----------------------


def add_observe_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "what", nargs="?", default=None,
        choices=[None, "trajectory", "kvcache"],
        help="optional sub-view: 'trajectory' pretty-prints one stitched "
        "request trajectory (GET /debug/trajectory/{trace_id}); 'kvcache' "
        "pretty-prints the KV-reuse plane (GET /debug/kvcache)",
    )
    parser.add_argument(
        "trace_id", nargs="?", default=None,
        help="trace id for the trajectory sub-view (omit to list "
        "recent + slow trajectories)",
    )
    parser.add_argument("--top-k", type=int, default=15,
                        help="ranked prefixes to show in the kvcache view")
    parser.add_argument("--host", default="127.0.0.1",
                        help="system-server host of the running worker")
    parser.add_argument("--port", type=int, default=None,
                        help="system-server port (default: DYN_TPU_SYSTEM_PORT)")
    parser.add_argument("--flight-limit", type=int, default=24,
                        help="newest flight-recorder events to show")
    parser.add_argument("--json", action="store_true",
                        help="dump the raw endpoint JSON instead of tables")


def add_drain_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--host", default="127.0.0.1",
                        help="system-server host of the running worker")
    parser.add_argument("--port", type=int, default=None,
                        help="system-server port (default: DYN_TPU_SYSTEM_PORT)")
    parser.add_argument("--deadline-s", type=float, default=None,
                        help="drain budget override (default: the worker's "
                        "DYN_TPU_DRAIN_DEADLINE_S)")
    parser.add_argument("--status", action="store_true",
                        help="report drain state only; do not trigger")
    parser.add_argument("--json", action="store_true",
                        help="dump the raw status JSON")


async def main_drain(args) -> None:
    """Operator-facing drain trigger: POST /drain on a running worker's
    system server and wait for the live-handoff drain to finish (the same
    path SIGTERM and the k8s preStop hook take). With --status, report
    the current state without triggering."""
    import aiohttp

    from dynamo_tpu import config

    port = args.port if args.port is not None else config.SYSTEM_PORT.get()
    base = f"http://{args.host}:{port}"
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=10)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        try:
            if args.status:
                resp = await session.get(f"{base}/drain")
            else:
                body = {}
                if args.deadline_s is not None:
                    body["deadline_s"] = args.deadline_s
                resp = await session.post(f"{base}/drain", json=body)
            async with resp:
                if resp.status != 200:
                    raise SystemExit(
                        f"{'GET' if args.status else 'POST'} {base}/drain -> "
                        f"{resp.status}: {await resp.text()}"
                    )
                status = await resp.json()
        except aiohttp.ClientError as exc:
            raise SystemExit(f"cannot reach system server at {base}: {exc}")

    if args.json:
        print(json.dumps(status, indent=2))
        return
    print(f"state: {status.get('state')}")
    for key in (
        "handoffs", "reprefill_fallbacks", "requeued", "peer_refusals",
        "handoff_bytes", "live_relays", "checkpointed", "duration_s",
    ):
        if key in status:
            print(f"  {key:<20} {status[key]}")


def _fmt_bytes(n) -> str:
    if not isinstance(n, (int, float)):
        return "?"
    # Negative values are meaningful (unaccounted_bytes < 0 = the ledger
    # overcounts the allocator) — keep the sign visible.
    sign, n = ("-", -n) if n < 0 else ("", n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return (
                f"{sign}{int(n)} B" if unit == "B" else f"{sign}{n:.1f} {unit}"
            )
        n /= 1024
    return f"{sign}{n:.1f} TiB"


async def main_observe_trajectory(args) -> None:
    """Pretty-print one stitched request trajectory (or the recent/slow
    index): phases, per-hop spans across processes, retries, skew flags,
    and the dominant phase — 'why was THIS request slow' in one command."""
    import aiohttp

    from dynamo_tpu import config

    port = args.port if args.port is not None else config.SYSTEM_PORT.get()
    base = f"http://{args.host}:{port}"
    path = (
        f"/debug/trajectory/{args.trace_id}"
        if args.trace_id else "/debug/trajectory"
    )
    async with aiohttp.ClientSession() as session:
        try:
            async with session.get(base + path) as r:
                if r.status != 200:
                    raise SystemExit(
                        f"GET {base}{path} -> {r.status}: {await r.text()}"
                    )
                doc = await r.json()
        except aiohttp.ClientError as exc:
            raise SystemExit(f"cannot reach system server at {base}: {exc}")
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    if not args.trace_id:
        print(f"== trajectories ({base}{path})")
        for row in doc.get("traces") or []:
            print(
                f"  {row['trace_id']}  {row['total_ms']:>9.1f} ms  "
                f"dominant={row['dominant_phase']:<13} "
                f"procs={len(row['processes'])} spans={row['span_count']}"
                f"{'  SKEW' if row.get('skew_flagged') else ''}"
            )
        slow = doc.get("slow") or []
        if slow:
            print("  -- slow/error ring --")
            for row in slow:
                print(
                    f"  {row['trace_id']}  {row['total_ms']:>9.1f} ms  "
                    f"dominant={row['dominant_phase']} "
                    f"[{row.get('retained', 'slow')}]"
                )
        return
    print(f"== trajectory {doc.get('trace_id')} ({base}{path})")
    print(
        f"  total {doc.get('total_ms', 0):.1f} ms across "
        f"{len(doc.get('processes') or [])} processes "
        f"({', '.join(doc.get('processes') or [])})"
        f"{'  [residual clock skew flagged]' if doc.get('skew_flagged') else ''}"
    )
    phases = doc.get("phases") or {}
    print("  phases:")
    for phase, ms in phases.items():
        marker = "  <- dominant" if phase == doc.get("dominant_phase") else ""
        print(f"    {phase:<14} {ms:>9.1f} ms{marker}")
    if doc.get("summary"):
        # Slow-ring hit: the full span set aged out of the recent ring;
        # the retained summary still names the bottleneck.
        print(
            f"  (summary only — {doc.get('span_count', 0)} spans aged out "
            "of the recent ring)"
        )
        return
    print("  spans:")
    for s in doc.get("spans") or []:
        attrs = s.get("attributes") or {}
        detail = " ".join(
            f"{k}={v}" for k, v in attrs.items()
            if k in ("worker", "src", "peer", "attempts", "retries",
                     "overlap_blocks", "candidates_scored", "queued_s",
                     "outcome", "adopted", "model")
        )
        flags = []
        if s.get("skew_flagged"):
            flags.append(f"skew={s.get('skew_ms')}ms")
        if str(s.get("status", "ok")) != "ok":
            flags.append(str(s["status"]))
        print(
            f"    {s.get('offset_ms', 0):>9.1f} +{s.get('duration_ms', 0):>8.1f} ms"
            f"  [{s.get('proc', '?'):<16}] {s.get('name', '?'):<22} "
            f"{detail}{('  ' + ' '.join(flags)) if flags else ''}"
        )
    events = doc.get("events") or []
    if events:
        print("  events:")
        for ev in events:
            detail = " ".join(
                f"{k}={v}" for k, v in ev.items()
                if k not in ("trace_id", "ring", "kind", "t_wall", "offset_ms")
            )
            print(
                f"    {ev.get('offset_ms', 0):>9.1f} ms  "
                f"{ev.get('ring', '?')}/{ev.get('kind', '?')} {detail}"
            )


async def main_observe_kvcache(args) -> None:
    """Pretty-print the KV-reuse plane of a running worker: hit rate by
    tier, cache ROI (reused vs recomputed prefill tokens, prefill seconds
    saved), sketch health, and the ranked hot-prefix table — 'is the
    prefix cache earning its memory' in one command."""
    import aiohttp

    from dynamo_tpu import config

    port = args.port if args.port is not None else config.SYSTEM_PORT.get()
    base = f"http://{args.host}:{port}"
    top_k = max(int(getattr(args, "top_k", 15) or 15), 1)
    async with aiohttp.ClientSession() as session:
        async def get(path):
            async with session.get(base + path) as r:
                if r.status != 200:
                    raise SystemExit(
                        f"GET {base}{path} -> {r.status}: {await r.text()}"
                    )
                return await r.json()

        try:
            doc = await get(f"/debug/kvcache?top_k={top_k}")
            prefixes = await get(f"/debug/kvcache/prefixes?k={top_k}")
        except aiohttp.ClientError as exc:
            raise SystemExit(f"cannot reach system server at {base}: {exc}")

    if args.json:
        print(json.dumps({"kvcache": doc, "prefixes": prefixes}, indent=2))
        return

    print(f"== kv reuse ({base}/debug/kvcache)")
    hits = doc.get("hits") or {}
    misses = doc.get("misses", 0)
    total = sum(hits.values()) + misses
    overall = (sum(hits.values()) / total) if total else 0.0
    per_tier = " ".join(
        f"{t}={r:.3f}" for t, r in (doc.get("hit_rate") or {}).items()
    )
    print(
        f"  hit rate {overall:.3f}  "
        f"(hits={sum(hits.values())} misses={misses}"
        f"{'; by tier: ' + per_tier if per_tier else ''})"
    )
    print(
        f"  prefill tokens  reused={doc.get('reused_prefill_tokens', 0)}  "
        f"recomputed={doc.get('recomputed_prefill_tokens', 0)}"
    )
    print(
        f"  prefill saved   {doc.get('prefill_seconds_saved', 0.0):.3f} s  "
        f"(cost/token {doc.get('prefill_cost_per_token_s', 0.0):.2e} s)"
    )
    sketch = doc.get("sketch") or {}
    print(
        f"  sketch          {sketch.get('tracked', 0)}/"
        f"{sketch.get('capacity', 0)} tracked  "
        f"replacements={sketch.get('replacements', 0)}  "
        f"half_life={sketch.get('half_life_s', 0.0):.0f}s"
    )
    tiers = doc.get("tiers") or {}
    for label, view in tiers.items():
        print(f"  [{label}]")
        for tier, stats in (view or {}).items():
            if not isinstance(stats, dict):
                continue
            detail = " ".join(
                f"{k}={stats[k]}" for k in
                ("blocks", "stored", "hits", "misses", "evicted")
                if k in stats
            )
            print(f"    {tier:<8} {detail}")
    rows = prefixes.get("prefixes") or []
    print(f"\n== hot prefixes (top {top_k}; {base}/debug/kvcache/prefixes)")
    if not rows:
        print("  (no tracked prefixes)")
    for row in rows:
        tier_mix = ",".join(
            f"{t}:{n}" for t, n in (row.get("tiers") or {}).items()
        )
        print(
            f"  {row.get('anchor', '?')}  score={row.get('score', 0.0):>10.2f} "
            f"(+/-{row.get('score_error', 0.0):.2f})  hits={row.get('hits', 0):>6} "
            f"tokens={row.get('tokens_from_cache', 0):>9} "
            f"age={row.get('age_s', 0.0):>7.1f}s  {tier_mix}"
        )


async def main_observe(args) -> None:
    """One-shot pretty snapshot of /debug/memory, /debug/compiles and
    /debug/flight from a running worker's system server — the operator's
    'what is the device plane doing right now' view without curl + jq."""
    import aiohttp

    from dynamo_tpu import config

    if getattr(args, "what", None) == "trajectory":
        await main_observe_trajectory(args)
        return
    if getattr(args, "what", None) == "kvcache":
        await main_observe_kvcache(args)
        return

    port = args.port if args.port is not None else config.SYSTEM_PORT.get()
    base = f"http://{args.host}:{port}"
    async with aiohttp.ClientSession() as session:
        async def get(path):
            async with session.get(base + path) as r:
                if r.status != 200:
                    raise SystemExit(
                        f"GET {base}{path} -> {r.status}: {await r.text()}"
                    )
                return await r.json()

        try:
            memory = await get("/debug/memory")
            compiles = await get("/debug/compiles")
            flight = await get(f"/debug/flight?limit={args.flight_limit}")
        except aiohttp.ClientError as exc:
            raise SystemExit(f"cannot reach system server at {base}: {exc}")

    if args.json:
        print(json.dumps(
            {"memory": memory, "compiles": compiles, "flight": flight},
            indent=2,
        ))
        return

    print(f"== device memory ({base}/debug/memory)")
    for source, cats in (memory.get("sources") or {}).items():
        print(f"  [{source}]")
        for category, nbytes in sorted(cats.items()):
            print(f"    {category:<16} {_fmt_bytes(nbytes):>12}")
    print(f"  ledger total       {_fmt_bytes(memory.get('ledger_total_bytes')):>12}")
    if "device_bytes_in_use" in memory:
        print(f"  device in use      {_fmt_bytes(memory['device_bytes_in_use']):>12}")
        print(f"  unaccounted        {_fmt_bytes(memory['unaccounted_bytes']):>12}")
    hwc = memory.get("host_weight_cache") or {}
    for tier, usage in hwc.items():
        print(
            f"  weight cache {tier:<5} {_fmt_bytes(usage.get('bytes')):>12}"
            f"  ({usage.get('entries', 0)} entries)"
        )

    print(f"\n== compiled programs ({base}/debug/compiles)")
    header = f"  {'program':<32} {'compiles':>8} {'sigs':>6} {'storms':>6} {'seconds':>9}"
    print(header)
    for name, st in (compiles.get("programs") or {}).items():
        print(
            f"  {name:<32} {st['compiles']:>8} {st['signatures']:>6} "
            f"{st['storms']:>6} {st['compile_seconds']:>9.2f}"
        )
    totals = compiles.get("totals") or {}
    print(
        f"  {'TOTAL':<32} {totals.get('compiles', 0):>8} "
        f"{totals.get('signatures', 0):>6} {totals.get('storms', 0):>6} "
        f"{totals.get('compile_seconds', 0.0):>9.2f}"
    )

    print(f"\n== flight recorder (newest {args.flight_limit}; {base}/debug/flight)")
    events = flight.get("events") or []
    if not events:
        print("  (no events)")
    for ev in events:
        extras = {
            k: v for k, v in ev.items()
            if k not in ("seq", "t_mono", "ring", "kind")
        }
        detail = " ".join(f"{k}={v}" for k, v in extras.items())
        print(
            f"  {ev.get('t_mono', 0):>14.3f} {ev.get('ring', '?'):<7} "
            f"{ev.get('kind', '?'):<12} {detail}"
        )


async def main_run(args) -> None:
    configure_logging()
    from dynamo_tpu.llm.entrypoint import build_local_pipeline

    engine, card, tokenizer = build_engine_and_card(args)
    pipeline = build_local_pipeline(card, engine, tokenizer=tokenizer)
    mode = args.input
    try:
        if mode == "text":
            await run_text(pipeline, card.name, args)
        elif mode == "stdin":
            await run_stdin(pipeline, card.name, args)
        elif mode.startswith("batch:"):
            await run_batch(pipeline, card.name, args, mode.split(":", 1)[1])
        elif mode == "http":
            await run_http(pipeline, card, args)
        else:
            raise SystemExit(
                f"unknown --input {mode!r} (text | stdin | batch:FILE | http)"
            )
    finally:
        stop = getattr(engine, "stop", None)
        if stop is not None:
            await stop()
