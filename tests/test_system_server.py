"""Per-worker system status server + engine sleep/wake + runtime LoRA
load/unload (ref: lib/runtime/src/system_status_server.rs; vllm handlers.py
sleep :286 / wake_up :317 / LoRA load :453)."""

import asyncio

import aiohttp
import pytest

from dynamo_tpu.runtime.system_server import (
    SystemStatusServer,
    attach_engine,
    engine_stats_prometheus,
)

from tests.test_jax_engine import make_engine, req, run_one
from tests.test_lora import write_adapter


async def _get(port, path):
    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{port}{path}") as r:
            return r.status, await r.json()


async def _post(port, path, body=None):
    async with aiohttp.ClientSession() as s:
        async with s.post(f"http://127.0.0.1:{port}{path}", json=body or {}) as r:
            return r.status, await r.json()


async def _delete(port, path):
    async with aiohttp.ClientSession() as s:
        async with s.delete(f"http://127.0.0.1:{port}{path}") as r:
            return r.status, await r.json()


async def test_engine_sleep_wake_cycle():
    """Sleep frees the KV cache (after draining actives) and wake restores
    serving with identical greedy output. The KV-event callback must fire
    on the event-loop thread (the real publisher creates asyncio tasks)."""
    import threading

    engine, events = make_engine()
    loop_thread = threading.get_ident()
    event_threads = []
    orig_append = events.append
    engine.pool._on_event = lambda e: (
        event_threads.append(threading.get_ident()), orig_append(e)
    )
    try:
        out1 = await run_one(engine, req(range(10, 22), max_tokens=5))
        toks1 = [t for o in out1 for t in o.token_ids]

        await engine.sleep(level=1)
        assert engine.sleep_level == 1
        assert engine._k_cache is None
        assert any(e.kind == "cleared" for e in events)
        assert all(t == loop_thread for t in event_threads)

        await engine.wake()
        assert engine.sleep_level == 0
        out2 = await run_one(engine, req(range(10, 22), max_tokens=5))
        toks2 = [t for o in out2 for t in o.token_ids]
        assert toks1 == toks2
    finally:
        await engine.stop()


async def test_engine_sleep_level2_offloads_weights():
    engine, _ = make_engine()
    try:
        out1 = await run_one(engine, req(range(5, 15), max_tokens=4))
        toks1 = [t for o in out1 for t in o.token_ids]
        await engine.sleep(level=2)
        assert engine.params is None
        assert engine._host_params is not None
        await engine.wake()
        out2 = await run_one(engine, req(range(5, 15), max_tokens=4))
        assert toks1 == [t for o in out2 for t in o.token_ids]
    finally:
        await engine.stop()


async def test_engine_sleep_queues_requests_until_wake():
    engine, _ = make_engine()
    try:
        await engine.sleep()
        gen = asyncio.create_task(run_one(engine, req(range(20, 30), max_tokens=3)))
        await asyncio.sleep(0.2)
        assert not gen.done()  # queued while asleep
        await engine.wake()
        out = await asyncio.wait_for(gen, 60)
        assert len([t for o in out for t in o.token_ids]) == 3
    finally:
        await engine.stop()


async def test_system_server_routes():
    engine, _ = make_engine()
    server = SystemStatusServer(host="127.0.0.1", port=0)
    attach_engine(server, engine)
    await server.start()
    try:
        status, body = await _get(server.port, "/health")
        assert status == 200 and body["status"] == "healthy"

        status, body = await _get(server.port, "/live")
        assert status == 200

        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{server.port}/metrics") as r:
                text = await r.text()
        assert "dynamo_tpu_engine_kv_usage" in text

        status, body = await _post(server.port, "/engine/stats")
        assert status == 200 and "active_seqs" in body

        status, body = await _post(server.port, "/engine/nope")
        assert status == 404 and "routes" in body

        # sleep → health shows asleep detail → wake
        status, body = await _post(server.port, "/engine/sleep", {"level": 1})
        assert status == 200 and body["sleeping"]
        status, body = await _get(server.port, "/health")
        assert status == 200 and "asleep" in body["details"]["engine"]
        status, body = await _post(server.port, "/engine/wake")
        assert status == 200 and not body["sleeping"]
    finally:
        await server.stop()
        await engine.stop()


async def test_runtime_lora_load_unload(tmp_path):
    root = str(tmp_path / "adapters")
    write_adapter(root, "hot-a", seed=3)
    write_adapter(root, "hot-b", seed=4)
    engine, _ = make_engine()
    server = SystemStatusServer(host="127.0.0.1", port=0)
    attach_engine(server, engine)
    await server.start()
    try:
        status, body = await _get(server.port, "/v1/loras")
        assert status == 200 and body["loras"] == []

        status, body = await _post(
            server.port, "/v1/loras", {"name": "hot-a", "path": f"{root}/hot-a"}
        )
        assert status == 201
        status, body = await _post(
            server.port, "/v1/loras", {"name": "hot-b", "path": f"{root}/hot-b"}
        )
        assert status == 201
        status, body = await _get(server.port, "/v1/loras")
        assert body["loras"] == ["hot-a", "hot-b"]
        assert engine._lora_index == {"hot-a": 1, "hot-b": 2}

        # duplicate load conflicts
        status, _ = await _post(
            server.port, "/v1/loras", {"name": "hot-a", "path": f"{root}/hot-a"}
        )
        assert status == 409

        # adapter requests route through the freshly loaded stack
        out = await run_one(
            engine, req(range(10, 20), max_tokens=3, lora_name="hot-a")
        )
        assert len([t for o in out for t in o.token_ids]) == 3

        # unload keeps the other adapter's index stable
        status, _ = await _delete(server.port, "/v1/loras/hot-a")
        assert status == 200
        assert engine._lora_index == {"hot-b": 2}
        status, _ = await _delete(server.port, "/v1/loras/hot-a")
        assert status == 404

        # reload fills the freed slot 1
        status, _ = await _post(
            server.port, "/v1/loras", {"name": "hot-a", "path": f"{root}/hot-a"}
        )
        assert status == 201
        assert engine._lora_index == {"hot-a": 1, "hot-b": 2}
    finally:
        await server.stop()
        await engine.stop()


def test_stats_prometheus_format():
    text = engine_stats_prometheus(
        {
            "kv_usage": 0.5,
            "active_seqs": 3,
            "kvbm": {"offloaded": 7, "host": {"hits": 1}, "label": "x"},
            "name": "x",
        }
    )
    assert "# TYPE dynamo_tpu_engine_kv_usage gauge" in text
    assert "# HELP dynamo_tpu_engine_kv_usage" in text
    assert "dynamo_tpu_engine_active_seqs 3.0" in text
    # nested kvbm stats flatten into dynamo_tpu_engine_kvbm_* gauges
    # instead of being silently dropped (ISSUE 1 satellite)
    assert "dynamo_tpu_engine_kvbm_offloaded 7.0" in text
    # ...but only one level deep, and never non-numeric values
    assert "hits" not in text and "x" not in text and "name" not in text


async def test_metrics_concatenates_sources_and_survives_failure():
    """/metrics joins every register_metrics source; one source throwing
    must not take out the others (ISSUE 1 satellite)."""
    server = SystemStatusServer(host="127.0.0.1", port=0)
    server.register_metrics(lambda: "# TYPE a counter\na_total 1")

    def broken():
        raise RuntimeError("boom")

    server.register_metrics(broken)
    server.register_metrics(lambda: "# TYPE b gauge\nb 2")
    await server.start()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{server.port}/metrics") as r:
                assert r.status == 200
                text = await r.text()
        assert "a_total 1" in text and "b 2" in text
    finally:
        await server.stop()


async def test_metrics_openmetrics_negotiation_renders_exemplars():
    """An Accept: application/openmetrics-text scrape switches
    metrics_core sources into OpenMetrics mode (trace-id exemplars on
    histogram buckets); plain sources still render."""
    from dynamo_tpu.runtime import metric_names as mn
    from dynamo_tpu.runtime.metrics_core import MetricsRegistry

    reg = MetricsRegistry()
    hist = reg.histogram(mn.DISAGG_TRANSFER_DURATION, "transfer time")
    hist.observe(0.02, trace_id="ab" * 16)
    server = SystemStatusServer(host="127.0.0.1", port=0)
    server.register_metrics(reg.render)
    server.register_metrics(lambda: "plain_gauge 7")
    await server.start()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{server.port}/metrics") as r:
                plain = await r.text()
            async with s.get(
                f"http://127.0.0.1:{server.port}/metrics",
                headers={"Accept": "application/openmetrics-text"},
            ) as r:
                om = await r.text()
                assert "openmetrics-text" in r.content_type
        assert "trace_id" not in plain and "plain_gauge 7" in plain
        assert f'# {{trace_id="{"ab" * 16}"}}' in om
        assert "plain_gauge 7" in om
        assert om.rstrip().endswith("# EOF")
    finally:
        await server.stop()


async def test_every_debug_route_returns_json_against_mock_engine():
    """Every static /debug/* route the server registers must answer 200
    with well-formed JSON even when the attached engine exposes no
    device-plane state (mock engines, partial attaches) — the operator's
    snapshot tooling (dynamo-tpu observe) must never 500 on a plain
    worker."""
    from dynamo_tpu.engines.mock import MockEngine, MockEngineArgs

    engine = MockEngine(MockEngineArgs())
    server = SystemStatusServer(host="127.0.0.1", port=0)
    # MockEngine lacks the LoRA/flight/hbm surface — attach_engine must
    # cope, registering only what exists.
    attach_engine(server, engine)
    await server.start()
    try:
        app = server._runner.app  # noqa: SLF001 - route table introspection
        debug_paths = sorted(
            r.resource.canonical
            for r in app.router.routes()
            if r.method == "GET"
            and r.resource.canonical.startswith("/debug/")
            and "{" not in r.resource.canonical
        )
        assert set(debug_paths) == {
            "/debug/requests", "/debug/traces", "/debug/memory",
            "/debug/compiles", "/debug/flight", "/debug/trajectory",
            "/debug/kvcache", "/debug/kvcache/prefixes",
        }
        for path in debug_paths:
            status, body = await _get(server.port, path)
            assert status == 200, (path, body)
            assert isinstance(body, dict), path
        # The parametrized trajectory route answers a clean 404 for an
        # unknown trace even on a partial/mock attach.
        status, body = await _get(server.port, "/debug/trajectory/deadbeef")
        assert status == 404 and "error" in body
        status, body = await _post(
            server.port, "/debug/profile", {"action": "status"}
        )
        assert status == 200 and "active" in body
        status, body = await _post(
            server.port, "/debug/profile", {"action": "bogus"}
        )
        assert status == 400
        # Bad 'seconds' must be rejected BEFORE any capture starts (an
        # after-start failure would orphan an unbounded trace).
        status, body = await _post(
            server.port, "/debug/profile",
            {"action": "start", "seconds": "60s"},
        )
        assert status == 400 and "seconds" in body["error"]
        status, body = await _post(
            server.port, "/debug/profile", {"action": "status"}
        )
        assert status == 200 and body["active"] is False
    finally:
        await server.stop()
        await engine.stop()


async def test_debug_device_routes_reflect_live_engine():
    """After serving one request, /debug/memory shows the ledger's real
    categories, /debug/compiles shows the watched decode program, and
    /debug/flight carries the merged engine+runner event history."""
    engine, _ = make_engine()
    server = SystemStatusServer(host="127.0.0.1", port=0)
    attach_engine(server, engine)
    await server.start()
    try:
        await run_one(engine, req(range(10, 22), max_tokens=4))

        status, body = await _get(server.port, "/debug/memory")
        assert status == 200
        cats = body["sources"]["engine"]
        assert cats["kv_cache"] > 0 and cats["params"] > 0
        assert body["ledger_total_bytes"] >= cats["kv_cache"] + cats["params"]
        split = body["sources"]["kv_pool_detail"]
        assert (
            split["active_bytes"] + split["cached_bytes"]
            + split["free_bytes"] == split["total_bytes"]
        )
        assert isinstance(body["devices"], list) and body["devices"]
        # chip_smoke.py reads the device the PROCESS holds from these rows.
        assert all(
            d["platform"] == "cpu" and d["device_kind"] == "cpu"
            for d in body["devices"]
        )

        status, body = await _get(server.port, "/engine/stats")
        assert status == 200
        assert body["decode_path"] == "xla"
        assert body["attention_impl"] == "xla"
        assert "cpu" in body["attention_reason"]

        status, body = await _get(server.port, "/debug/compiles")
        assert status == 200
        progs = body["programs"]
        assert "runner.decode_state" in progs
        assert progs["runner.decode_state"]["budget"] is not None
        assert body["totals"]["compiles"] >= 1

        status, body = await _get(server.port, "/debug/flight")
        assert status == 200
        assert set(body["rings"]) == {"engine", "runner"}
        kinds = {e["kind"] for e in body["events"]}
        assert {"admit", "dispatch", "reap", "finish", "decode"} <= kinds
        ts = [e["t_mono"] for e in body["events"]]
        assert ts == sorted(ts)  # merged across rings by timestamp

        # filters: ?kind= and ?limit=
        status, body = await _get(server.port, "/debug/flight?kind=reap&limit=2")
        assert status == 200
        assert body["events"]
        assert all(e["kind"] == "reap" for e in body["events"])
        assert len(body["events"]) <= 2

        # metrics surface the flight/ledger families with real samples
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{server.port}/metrics") as r:
                text = await r.text()
        from dynamo_tpu.runtime import metric_names as mn

        assert f'{mn.RUNTIME_FLIGHT_EVENTS_TOTAL}{{ring="engine",kind="admit"}}' in text
        assert mn.RUNTIME_HBM_BYTES + '{category="kv_cache"}' in text
        assert mn.RUNTIME_COMPILES_TOTAL in text
    finally:
        await server.stop()
        await engine.stop()


async def test_metrics_merges_duplicate_families_across_sources():
    """Two same-kind subsystem objects (each a private metrics_core
    registry) registered on one server must not emit duplicate # HELP/
    # TYPE blocks for the shared family — Prometheus rejects repeated or
    interleaved metadata. Samples from both land under one block."""
    from dynamo_tpu.runtime import metric_names as mn
    from dynamo_tpu.runtime.metrics_core import MetricsRegistry

    regs = []
    for worker in ("w0", "w1"):
        reg = MetricsRegistry()
        c = reg.counter(mn.ROUTER_DECISIONS_TOTAL, "decisions", ["worker"])
        c.inc(worker=worker)
        regs.append(reg)
    server = SystemStatusServer(host="127.0.0.1", port=0)
    for reg in regs:
        server.register_metrics(reg.render)
    await server.start()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{server.port}/metrics") as r:
                text = await r.text()
            async with s.get(
                f"http://127.0.0.1:{server.port}/metrics",
                headers={"Accept": "application/openmetrics-text"},
            ) as r:
                om = await r.text()
    finally:
        await server.stop()
    family = mn.ROUTER_DECISIONS_TOTAL[: -len("_total")]
    for body, name in ((text, mn.ROUTER_DECISIONS_TOTAL), (om, family)):
        assert body.count(f"# TYPE {name} counter") == 1
        assert body.count(f"# HELP {name} ") == 1
        assert f'{mn.ROUTER_DECISIONS_TOTAL}{{worker="w0"}} 1' in body
        assert f'{mn.ROUTER_DECISIONS_TOTAL}{{worker="w1"}} 1' in body
    # metadata must not interleave: both samples follow the single block
    lines = [l for l in text.splitlines() if mn.ROUTER_DECISIONS_TOTAL in l]
    assert [l.startswith("#") for l in lines] == [True, True, False, False]
