"""Device-plane observability (runtime/device_observe.py): compile
telemetry + recompile-storm detection, HBM ledger, flight recorder,
profiler control, and the engine stats-snapshot consistency fix."""

import asyncio
import contextlib
import json
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.runtime.device_observe import (
    RECENT_COMPILES,
    CompileWatcher,
    FlightRecorder,
    GcWatcher,
    HbmLedger,
    ProfilerControl,
    describe_call,
    dump_flight,
    global_compile_watcher,
    global_gc_watcher,
    tree_device_bytes,
    watched_jit,
)

from tests.test_jax_engine import make_engine, req, run_one


# -- compile telemetry -------------------------------------------------------


def test_watched_jit_counts_compiles_not_cache_hits():
    watcher = CompileWatcher()
    fn = watched_jit("t.add", jax.jit(lambda x: x + 1), watcher=watcher)
    fn(jnp.zeros(4))
    fn(jnp.ones(4))  # same signature: cache hit, no new compile
    st = watcher.snapshot()["programs"]["t.add"]
    assert st["compiles"] == 1
    assert st["signatures"] == 1
    assert st["compile_seconds"] > 0
    fn(jnp.zeros(8))  # new shape: one more signature
    st = watcher.snapshot()["programs"]["t.add"]
    assert st["compiles"] == 2 and st["signatures"] == 2
    assert st["storms"] == 0  # far below the 256-signature default budget
    # results pass through untouched
    assert np.asarray(fn(jnp.zeros(2))).tolist() == [1.0, 1.0]


def test_watched_jit_forwards_wrapped_attributes():
    fn = watched_jit("t.fwd", jax.jit(lambda x: x * 2), watcher=CompileWatcher())
    fn(jnp.zeros(3))
    assert fn._cache_size() == 1  # jit surface still reachable through it


def test_recompile_storm_fires_on_unbucketed_shapes():
    """A fresh signature per call (the unbucketed-shape bug) must cross
    the budget, bump the storm counter, and log a warning — while calls
    within the budget stay silent. (The dynamo_tpu logger doesn't
    propagate, so capture with an attached handler instead of caplog.)"""
    import logging

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture(level=logging.WARNING)
    logging.getLogger("dynamo_tpu").addHandler(handler)
    try:
        watcher = CompileWatcher()
        fn = watched_jit(
            "t.storm", jax.jit(lambda x: x.sum()), budget=3, watcher=watcher
        )
        for n in range(1, 4):  # 3 signatures: at the budget, no storm
            fn(jnp.zeros(n))
        assert watcher.snapshot()["programs"]["t.storm"]["storms"] == 0
        assert not any("recompile storm" in m for m in records)
        for n in range(4, 7):  # every further fresh shape is a storm event
            fn(jnp.zeros(n))
    finally:
        logging.getLogger("dynamo_tpu").removeHandler(handler)
    st = watcher.snapshot()["programs"]["t.storm"]
    assert st["storms"] == 3
    assert st["signatures"] == 6
    assert any("recompile storm" in m for m in records)


def test_per_instance_budget_not_shared_across_program_objects():
    """Two jit objects sharing a watch name (engine restart, per-variant
    decode programs) each get their own budget headroom: N engines warming
    up is not a storm."""
    watcher = CompileWatcher()
    a = watched_jit("t.shared", jax.jit(lambda x: x), budget=2, watcher=watcher)
    b = watched_jit("t.shared", jax.jit(lambda x: x), budget=2, watcher=watcher)
    for fn in (a, b):
        fn(jnp.zeros(1))
        fn(jnp.zeros(2))
    st = watcher.snapshot()["programs"]["t.shared"]
    assert st["signatures"] == 4  # aggregated totals
    assert st["storms"] == 0  # but no instance crossed ITS budget


@contextlib.contextmanager
def warning_lines():
    """WARNING lines of the dynamo_tpu logger (it does not propagate)."""
    lines = []

    class Capture(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    handler = Capture(level=logging.WARNING)
    logging.getLogger("dynamo_tpu").addHandler(handler)
    try:
        yield lines
    finally:
        logging.getLogger("dynamo_tpu").removeHandler(handler)


def test_a_compile_is_named_with_its_shapes_and_seconds():
    watcher = CompileWatcher()
    fn = watched_jit(
        "t.named", jax.jit(lambda p, x, n: x * n + p["w"].sum(), static_argnums=2),
        watcher=watcher,
    )
    before = watcher.compiles
    fn({"w": jnp.zeros((3, 5), jnp.float32)}, jnp.zeros((2, 8), jnp.int32), 3)
    fn({"w": jnp.zeros((3, 5), jnp.float32)}, jnp.ones((2, 8), jnp.int32), 3)  # a hit
    recent = watcher.snapshot()["recent"]
    assert len(recent) == 1 and watcher.compiles == before + 1
    event = recent[0]
    assert event["program"] == "t.named"
    assert event["signature"] == "(float32[3,5]), int32[2,8], 3"
    assert event["seconds"] > 0 and event["serving"] is False
    assert event["t_mono"] > 0 and event["t_wall"] > 1e9
    assert watcher.snapshot()["totals"]["compiles"] == watcher.compiles
    json.dumps(watcher.snapshot())  # the route serves it as it is


@pytest.mark.parametrize(
    "args, kwargs, want",
    [
        ((np.zeros((4, 2), np.int8), 7, None, True), {}, "int8[4,2], 7, None, True"),
        # parameters and pools are their type and size, not a page of shapes
        (({k: np.zeros(2, np.float32) for k in "abcde"},), {},
         "<dict: 5 arrays>"),
        (([np.zeros(1, np.int32), np.zeros((), np.float32)],), {"nb": 8},
         "(int32[1], float32[]), nb=8"),
        ((object(), ()), {}, "object, tuple"),
    ],
)
def test_describe_call(args, kwargs, want):
    assert describe_call(args, kwargs) == want


def test_describe_call_is_cut_to_600_characters():
    text = describe_call(tuple(np.zeros((i + 1,), np.float32) for i in range(200)), {})
    assert len(text) == 600 and text.endswith("...")


def test_the_recent_ring_holds_the_last_64_compiles():
    watcher = CompileWatcher()
    fn = watched_jit(
        "t.ring", jax.jit(lambda x: x + 1), budget=1000, watcher=watcher)
    for n in range(1, RECENT_COMPILES + 7):
        fn(np.zeros(n, np.float32))
    recent = watcher.snapshot()["recent"]
    assert RECENT_COMPILES == 64 and len(recent) == 64
    assert recent[0]["signature"] == "float32[7]"
    assert recent[-1]["signature"] == f"float32[{RECENT_COMPILES + 6}]"
    assert watcher.compiles == RECENT_COMPILES + 6  # the count is not the ring's


def test_a_compile_after_start_up_is_logged_and_one_before_is_not():
    watcher = CompileWatcher()
    fn = watched_jit("t.ladder", jax.jit(lambda x: x - 1), watcher=watcher)
    with warning_lines() as lines:
        fn(np.zeros(3, np.float32))
        assert not [l for l in lines if "serving path" in l]
        watcher.start_up_ended()
        fn(np.zeros(3, np.float32))  # compiled before: a hit, no line
        fn(np.zeros((5, 2), np.float32))
    said = [l for l in lines if "serving path" in l]
    assert len(said) == 1
    assert said[0].startswith(
        "compiled on the serving path: t.ladder float32[5,2] in ")
    assert [e["serving"] for e in watcher.recent] == [False, True]


async def test_a_program_the_ladder_does_not_hold_is_named_where_an_operator_looks():
    """A worker that compiled its ladder, then met a program the ladder
    does not hold (here: the decode burst, and a prefill over a prefix
    hit): `/debug/compiles` names each under ``recent`` with its shapes,
    and the worker's log has a line for each."""
    import aiohttp

    from dynamo_tpu.runtime.system_server import SystemStatusServer, attach_engine

    engine, _ = make_engine(prefill_batch=2)
    server = SystemStatusServer(host="127.0.0.1", port=0)
    attach_engine(server, engine)
    await server.start()
    watcher = global_compile_watcher()
    try:
        report = await engine.compile_prefill_ladder()
        at_start, t_start = watcher.compiles, time.monotonic()
        assert report["startup_compiles"] == watcher.totals()["compiles"] == at_start
        with warning_lines() as lines:
            await run_one(engine, req(range(10, 30), max_tokens=6))
            await run_one(engine, req(range(10, 30), max_tokens=6))  # a prefix hit
        async with aiohttp.ClientSession() as http:
            async with http.get(
                    f"http://127.0.0.1:{server.port}/debug/compiles") as r:
                body = await r.json()
    finally:
        await server.stop()
        await engine.stop()
    assert set(body) == {"programs", "totals", "recent"}
    after = [e for e in body["recent"] if e["t_mono"] >= t_start]
    assert len(after) == body["totals"]["compiles"] - at_start > 0
    assert all(e["serving"] for e in after)
    names = {e["program"] for e in after}
    assert any("decode" in n for n in names), names
    assert all(e["signature"] and e["seconds"] > 0 for e in after)
    said = [l for l in lines if l.startswith("compiled on the serving path: ")]
    assert len(said) == len(after)
    for event in after:
        assert any(f"{event['program']} {event['signature']} in " in l for l in said)


def test_gc_watcher_times_each_collection_by_generation(monkeypatch):
    import gc

    from dynamo_tpu.runtime import device_observe

    t = [10.0]
    monkeypatch.setattr(device_observe.time, "perf_counter", lambda: t[0])
    w = GcWatcher()
    for generation, seconds in ((0, 0.001), (2, 0.25), (0, 0.002)):
        w.on_gc("start", {"generation": generation})
        t[0] += seconds
        w.on_gc("stop", {"generation": generation, "collected": 0, "uncollectable": 0})
        t[0] += 1.0  # between collections: nobody's pause
    assert w.seconds == pytest.approx(0.253)
    assert w.collections == [2, 0, 1]
    body = w.registry.render()
    from dynamo_tpu.runtime import metric_names as mn

    assert f'{mn.RUNTIME_GC_COLLECTIONS_TOTAL}{{generation="0"}} 2' in body
    assert f'{mn.RUNTIME_GC_COLLECTIONS_TOTAL}{{generation="1"}} 0' in body
    assert f'{mn.RUNTIME_GC_PAUSE_SECONDS_TOTAL}{{generation="2"}} 0.25' in body
    assert w.on_gc not in gc.callbacks  # the process's hook is another's


def test_the_process_has_one_gc_hook_and_it_counts():
    import gc

    w = global_gc_watcher()
    assert global_gc_watcher() is w and gc.callbacks.count(w.on_gc) == 1
    before = w.collections[2], w.seconds
    gc.collect()
    assert w.collections[2] == before[0] + 1 and w.seconds > before[1]
    from dynamo_tpu.runtime.device_observe import render_runtime_metrics
    from dynamo_tpu.runtime import metric_names as mn

    assert mn.RUNTIME_GC_PAUSE_SECONDS_TOTAL + '{generation="2"}' in render_runtime_metrics()


async def test_engine_device_plane_lifecycle():
    """One engine, three device-plane assertions (shared to keep the CPU
    suite's compile bill down):

    1. pow2 warmup budget: normal serving through the width-bucketed
       decode path must not trip the decode program's signature budget
       (the table_width_bucket expected-count assertion);
    2. HBM ledger: live kv/params/slot-state bytes, self-consistent pool
       split, kv_cache → 0 across sleep and restored on wake;
    3. flight recorder: the tick loop + runner rings carry the full
       admit → dispatch → reap → finish (and sync/decode) event history.
    """
    storms_before = (
        global_compile_watcher().program("runner.decode_state").storms
    )
    engine, _ = make_engine()
    try:
        await run_one(engine, req(range(10, 26), max_tokens=8))
        await run_one(engine, req(range(30, 40), max_tokens=6))

        prog = global_compile_watcher().program("runner.decode_state")
        assert prog.compiles >= 1  # the decode program really is watched
        assert prog.storms == storms_before  # bucketed warmup: in budget

        snap = engine.hbm.snapshot()
        assert snap["kv_cache"] > 0
        assert snap["params"] > 0
        assert snap["slot_state"] > 0
        split = engine.kv_pool_bytes_breakdown()
        assert (
            split["active_bytes"] + split["cached_bytes"]
            + split["free_bytes"] == split["total_bytes"]
        )

        kinds = set(engine.flight.counts)
        assert {"admit", "dispatch", "reap", "finish"} <= kinds
        runner_kinds = set(engine.runner.flight.counts)
        assert "decode" in runner_kinds  # transfer_log folds into the ring
        assert "slot_sync" in runner_kinds
        admits = [e for e in engine.flight.snapshot() if e["kind"] == "admit"]
        assert admits and admits[0]["request_id"] == "r"
        reaps = [e for e in engine.flight.snapshot() if e["kind"] == "reap"]
        # 7 + 5 of the 8 + 6 generated tokens come from decode reaps (each
        # request's first token is sampled by the admission prefill).
        assert sum(e["tokens"] for e in reaps) == 12

        # sleep(1) frees the KV cache: the ledger must see it vanish
        await engine.sleep(level=1)
        assert engine.hbm.snapshot()["kv_cache"] == 0
        await engine.wake()
        assert engine.hbm.snapshot()["kv_cache"] == snap["kv_cache"]
    finally:
        await engine.stop()


# -- HBM ledger --------------------------------------------------------------


def test_tree_device_bytes_counts_array_leaves():
    tree = {
        "a": jnp.zeros((4, 4), jnp.float32),
        "b": (np.zeros(8, np.int32), None),
        "c": {"q8": jnp.zeros(16, jnp.int8)},
        "d": 7,  # scalar leaf: no nbytes, contributes 0
    }
    assert tree_device_bytes(tree) == 64 + 32 + 16
    assert tree_device_bytes(None) == 0


def test_hbm_ledger_snapshot_peak_and_broken_source():
    ledger = HbmLedger()
    arrs = {"k": np.zeros(1024, np.uint8)}
    ledger.register("kv", lambda: arrs["k"].nbytes)

    def broken():
        raise RuntimeError("boom")

    ledger.register("bad", broken)
    snap = ledger.snapshot()
    assert snap["kv"] == 1024
    assert snap["bad"] == -1  # visible as unknown, not silently zero
    assert ledger.total_bytes() == 1024
    assert ledger.peak_bytes == 1024
    arrs["k"] = np.zeros(64, np.uint8)
    assert ledger.total_bytes() == 64
    assert ledger.peak_bytes == 1024  # peak is sticky


# -- flight recorder ---------------------------------------------------------


def test_flight_ring_wraps_and_counts():
    fr = FlightRecorder("t", capacity=4)
    for i in range(6):
        fr.record("tick", i=i)
    events = fr.snapshot()
    assert [e["i"] for e in events] == [2, 3, 4, 5]  # oldest 2 overwritten
    assert [e["seq"] for e in events] == [2, 3, 4, 5]
    assert fr.counts["tick"] == 6
    assert fr.overwritten == 2
    assert fr.snapshot(limit=2)[0]["i"] == 4
    # every event carries ring, kind, and a monotonic timestamp
    assert all(e["ring"] == "t" and e["kind"] == "tick" for e in events)
    assert all(
        a["t_mono"] <= b["t_mono"] for a, b in zip(events, events[1:])
    )


def test_dump_flight_writes_merged_json(tmp_path):
    a = FlightRecorder("a")
    b = FlightRecorder("b")
    a.record("x", n=1)
    b.record("y", n=2)
    a.record("z", n=3)
    path = dump_flight({"a": a, "b": b}, dump_dir=str(tmp_path), reason="test")
    assert path and os.path.exists(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "test"
    assert sorted(doc["rings"]) == ["a", "b"]
    kinds = [e["kind"] for e in doc["events"]]
    assert sorted(kinds) == ["x", "y", "z"]
    ts = [e["t_mono"] for e in doc["events"]]
    assert ts == sorted(ts)  # merged ordering is by timestamp


# -- profiler control --------------------------------------------------------


def test_profiler_control_cycle(tmp_path, monkeypatch):
    """State machine over a stubbed jax.profiler (a REAL start/stop trace
    costs ~14s of CPU suite time; the live-profiler path is exercised by
    POST /debug/profile in the verify drive, not tier-1)."""
    import jax.profiler as jp

    calls = []
    options = []

    def start_trace(d, profiler_options=None):
        calls.append(("start", d))
        options.append(profiler_options)

    monkeypatch.setattr(jp, "start_trace", start_trace)
    monkeypatch.setattr(jp, "stop_trace", lambda: calls.append(("stop",)))
    ctl = ProfilerControl()
    assert ctl.stop() == {"ok": False, "error": "no active capture"}
    started = ctl.start(str(tmp_path / "trace"))
    assert started["ok"] and started["generation"] == 1
    # double start conflicts while active
    again = ctl.start()
    assert not again["ok"] and "active" in again["error"]
    stopped = ctl.stop()
    assert stopped["ok"] and stopped["dir"] == str(tmp_path / "trace")
    assert ctl.captures == 1
    assert calls == [("start", str(tmp_path / "trace")), ("stop",)]
    # The capture holds TraceMe events (the engine's annotations) and no
    # Python tracer: the latter made a 4 s capture a 215 MB file.
    assert options[0].python_tracer_level == 0
    assert options[0].host_tracer_level == 2

    # degraded stop that may have left the session live keeps the capture
    # active (retryable); an "already ended" error clears it
    assert ctl.start()["generation"] == 2

    def boom():
        raise RuntimeError("export write failed")

    monkeypatch.setattr(jp, "stop_trace", boom)
    res = ctl.stop()
    assert not res["ok"] and res["still_active"]
    assert ctl.status()["active"]

    def ended():
        raise RuntimeError("No trace has been started")

    monkeypatch.setattr(jp, "stop_trace", ended)
    res = ctl.stop()
    assert not res["ok"] and not res["still_active"]
    assert not ctl.status()["active"]
    assert ctl.captures == 1  # failed stops never count as captures


def test_profiler_degraded_start(monkeypatch):
    """A backend whose profiler refuses to start degrades to a structured
    no-op: nothing raised, nothing counted, nothing left active."""
    import jax.profiler as jp

    def no_backend(d, **kw):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jp, "start_trace", no_backend)
    ctl = ProfilerControl()
    started = ctl.start()
    assert not started["ok"] and started["degraded"]
    assert ctl.captures == 0
    assert not ctl.status()["active"]


# -- engine stats snapshot (torn-read fix) -----------------------------------


async def test_stats_snapshot_and_abort_dump(tmp_path, monkeypatch):
    """One engine, three assertions (shared to bound suite compile time):

    1. cross-thread stats() hammering mid-generation only ever sees
       internally consistent dicts (the torn-read fix);
    2. while the loop runs, stats() returns the boundary snapshot —
       mid-tick mutations are invisible until the next publish, and a
       stopped engine computes live again;
    3. _abort_inflight dumps the merged flight rings to JSON.
    """
    monkeypatch.setenv("DYN_TPU_FLIGHT_DUMP_DIR", str(tmp_path))
    engine, _ = make_engine()
    seen = []
    stop = False

    def reader():
        import time as _time

        while not stop:
            seen.append(engine.stats())
            _time.sleep(0.001)

    import threading

    t = threading.Thread(target=reader)
    try:
        t.start()
        await run_one(engine, req(range(10, 26), max_tokens=16))
        stop = True
        t.join()
        assert seen
        keys = set(seen[-1])
        for s in seen:
            assert set(s) == keys
            assert 0 <= s["kv_usage"] <= 1
            assert 0 <= s["active_seqs"] <= engine.args.max_num_seqs
            assert s["inflight_bursts"] <= engine._pipeline_depth()

        # Let the pipelined tail drain (a speculative burst may still be
        # in flight right after the stream finishes) AND its reap publish
        # the post-drain snapshot.
        for _ in range(200):
            if (
                not engine._inflight
                and engine.stats().get("inflight_bursts") == 0
            ):
                break
            await asyncio.sleep(0.01)
        live = engine._compute_stats()
        snap = engine.stats()
        assert snap == live  # quiescent: snapshot is current

        # Simulate a mid-tick mutation without a publish: a cross-thread
        # stats() reader must keep seeing the previous consistent snapshot.
        engine.steps += 1000
        assert engine.stats()["decode_steps"] == snap["decode_steps"]
        engine._publish_stats()
        assert engine.stats()["decode_steps"] == snap["decode_steps"] + 1000
        engine.steps -= 1000
        engine._publish_stats()

        engine._abort_inflight()
        dumps = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert len(dumps) == 1
        with open(tmp_path / dumps[0]) as f:
            doc = json.load(f)
        assert doc["reason"] == "abort_inflight"
        assert {"engine", "runner"} == set(doc["rings"])
        assert any(e["kind"] == "abort" for e in doc["events"])
    finally:
        stop = True
        if t.is_alive():
            t.join(timeout=5)
        await engine.stop()
    # loop stopped: stats() computes live again
    engine.steps += 7
    assert engine.stats()["decode_steps"] == snap["decode_steps"] + 7
