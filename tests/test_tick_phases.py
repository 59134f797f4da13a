"""The engine tick seen from inside (engines/metrics.py
``EngineStepMetrics.phase``): phase spans that partition the scheduler
loop's wall time, request phases as histograms, the spans in a profiler
capture, a profiler route that exports off the event loop, and the
benchmark reader that turns the phase counters into shares."""

import asyncio
import glob
import json
import os
import sys
import time

import aiohttp
import pytest

from dynamo_tpu.engines import metrics as engine_metrics
from dynamo_tpu.engines.metrics import EngineStepMetrics
from dynamo_tpu.runtime import metric_names as mn
from dynamo_tpu.runtime.device_observe import ProfilerControl, global_profiler
from dynamo_tpu.runtime.system_server import SystemStatusServer

from tests.test_jax_engine import make_engine, req, run_one

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmark")


def _phase_seconds(sm: EngineStepMetrics) -> dict:
    return {p: sm.tick_phase.snapshot_total(phase=p)[1] for p in mn.TICK_PHASES}


# -- the helper, without an engine --------------------------------------------


class _Clock:
    def __init__(self) -> None:
        self.t = 100.0

    def __call__(self) -> float:
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(engine_metrics.time, "monotonic", c)
    return c


def test_nested_phases_are_exclusive(clock):
    """Entering a phase suspends the enclosing one; leaving resumes it: the
    seconds of a nested block are counted once, under the innermost name."""
    sm = EngineStepMetrics()
    with sm.tick():
        clock.t += 1.0  # tick.sched
        with sm.phase("tick.admit"):
            clock.t += 2.0
            with sm.phase("tick.prefill_wait", rows=2):
                clock.t += 4.0
            clock.t += 8.0  # tick.admit again
        clock.t += 16.0  # tick.sched again
    got = _phase_seconds(sm)
    assert got["tick.sched"] == 17.0
    assert got["tick.admit"] == 10.0
    assert got["tick.prefill_wait"] == 4.0
    assert sum(got.values()) == 31.0
    assert sm.tick_duration.snapshot_total() == (1, 31.0)


@pytest.mark.parametrize("idle_phase", mn.TICK_PHASES_IDLE)
def test_an_iteration_that_went_idle_is_no_tick(clock, idle_phase):
    sm = EngineStepMetrics()
    with sm.tick():
        clock.t += 0.5
        with sm.phase(idle_phase):
            clock.t += 3.0
    assert sm.tick_duration.snapshot_total() == (0, 0.0)
    assert _phase_seconds(sm)[idle_phase] == 3.0
    with sm.tick():
        clock.t += 0.25
    assert sm.tick_duration.snapshot_total() == (1, 0.25)


def test_phase_survives_an_exception_and_refuses_unknown_names(clock):
    sm = EngineStepMetrics()
    with pytest.raises(KeyError):
        sm.phase("tick.nonsense")
    with pytest.raises(RuntimeError):
        with sm.tick():
            with sm.phase("tick.emit"):
                clock.t += 1.0
                raise RuntimeError("boom")
    assert sm._scope is None  # nothing left open
    assert _phase_seconds(sm)["tick.emit"] == 1.0
    assert sm.tick_duration.snapshot_total() == (1, 1.0)


def test_annotations_degrade_without_jax(monkeypatch):
    """engines/metrics.py imports without JAX, and the helper still counts."""
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    sm = EngineStepMetrics()
    with sm.annotate("device.decode_read", rows=1):
        pass
    with sm.tick():
        with sm.phase("tick.decode_wait", rows=1):
            pass
    assert sm._trace_annotation is False
    assert sm.tick_phase.count(phase="tick.decode_wait") == 1


@pytest.mark.parametrize(
    "stamps, want",
    [
        # queue 2 s, prefill 3 s, decode 5 s over 11 tokens
        (((0.0, 2.0), (2.0, 5.0), (5.0, 10.0), 10),
         {"queue": 2.0, "prefill": 3.0, "decode": 5.0, "tokens": 10}),
        # cancelled in the queue: never admitted, nothing generated
        (((0.0, 4.0), None, None, -1),
         {"queue": 4.0, "prefill": None, "decode": None, "tokens": 0}),
        # preempted: the re-prefill restamped its start after the first
        # output (clamped to 0); one token out, a decode phase of no tokens
        (((1.0, 1.0), (9.0, 3.0), (3.0, 3.0), 0),
         {"queue": 0.0, "prefill": 0.0, "decode": 0.0, "tokens": 0}),
    ],
)
def test_observe_request_counts(stamps, want):
    sm = EngineStepMetrics()
    sm.observe_request(*stamps)
    for phase in mn.REQUEST_PHASES:
        count, total = sm.request_phase.snapshot_total(phase=phase)
        assert (count, total) == ((0, 0.0) if want[phase] is None else (1, want[phase]))
    # a counter nothing incremented renders no sample
    line = [l for l in sm.render().splitlines()
            if l.startswith(mn.ENGINE_REQUEST_DECODE_TOKENS_TOTAL)]
    assert (float(line[0].split()[-1]) if line else 0) == want["tokens"]


# -- over a short run of the CPU engine ---------------------------------------


async def test_phases_partition_the_loop_and_show_in_a_capture(tmp_path):
    """One engine, four assertions (shared to bound suite compile time):

    1. the phases partition the loop: sum of phase seconds is within 2% of
       sum of tick seconds + idle, and no two recorded segments overlap;
    2. the request-phase histograms move by one count per finished stream
       and the token counter by generated - 1;
    3. a sub-second capture through ProfilerControl (Python tracer off)
       holds tick.decode_wait and device.decode_read events with their
       ``rows`` stat on lines of a /host:CPU plane;
    4. every phase the run went through is in the vocabulary.
    """
    engine, _ = make_engine(pipeline_depth=2, decode_steps=4)
    sm = engine.step_metrics
    segments = []
    real_end = sm._end_segment

    def recording_end():
        name, t0 = sm._scope.name, sm._t0
        real_end()
        segments.append((name, t0, time.monotonic()))

    sm._end_segment = recording_end
    try:
        await run_one(engine, req(range(10, 22), max_tokens=6))  # compiles
        before = {p: sm.request_phase.count(phase=p) for p in mn.REQUEST_PHASES}
        ctl = ProfilerControl()
        started = ctl.start(str(tmp_path / "trace"))
        assert started["ok"], started
        reqs = [req(range(5 + i, 15 + 4 * i), max_tokens=12 + i) for i in range(6)]
        outs = await asyncio.gather(*(run_one(engine, r) for r in reqs))
        stopped = ctl.stop()
        assert stopped["ok"], stopped
        await asyncio.sleep(0.12)  # let the loop go idle at least once
    finally:
        await engine.stop()

    # 1. partition
    phases = _phase_seconds(sm)
    n_ticks, tick_s = sm.tick_duration.snapshot_total()
    idle_s = phases["tick.idle"]
    assert n_ticks > 0 and idle_s > 0
    assert abs(sum(phases.values()) - (tick_s + idle_s)) <= 0.02 * (tick_s + idle_s)
    segments.sort(key=lambda s: s[1])
    for (_, _, end), (_, start, _) in zip(segments, segments[1:]):
        assert start >= end - 1e-6, "two phases were open at once"
    # the recorded segments cover the loop's life with no hole between them
    covered = sum(e - s for _, s, e in segments)
    assert covered >= 0.98 * (segments[-1][2] - segments[0][1])
    # 4. vocabulary; the run met admission, prefill, decode and idle
    seen = {name for name, _, _ in segments}
    assert seen <= set(mn.TICK_PHASES)
    assert {"tick.sched", "tick.admit", "tick.prefill_build", "tick.prefill_wait",
            "tick.install", "tick.decode_build", "tick.decode_dispatch",
            "tick.decode_wait", "tick.emit", "tick.idle"} <= seen

    # 2. one count per finished stream, generated - 1 tokens each
    for p in mn.REQUEST_PHASES:
        assert sm.request_phase.count(phase=p) - before[p] == len(reqs)
    generated = [sum(len(o.token_ids) for o in out) for out in outs]
    assert generated == [12 + i for i in range(6)]
    counter = [l for l in sm.render().splitlines()
               if l.startswith(mn.ENGINE_REQUEST_DECODE_TOKENS_TOTAL)]
    assert float(counter[0].split()[-1]) == (6 - 1) + sum(g - 1 for g in generated)

    # 3. the capture
    from jax.profiler import ProfileData

    files = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert len(files) == 1
    found = {}
    python_tracer_events = 0
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for event in line.events:
                # the Python tracer names its events "$file:line function"
                python_tracer_events += event.name.startswith("$")
                if event.name in ("tick.decode_wait", "device.decode_read"):
                    found.setdefault(event.name, []).append(
                        (line.name, dict(event.stats)))
    assert python_tracer_events == 0
    assert set(found) == {"tick.decode_wait", "device.decode_read"}
    for rows in found.values():
        assert all(1 <= stats["rows"] <= 4 for _, stats in rows)


# -- what the device waits for -------------------------------------------------


def _starved_seconds(sm: EngineStepMetrics) -> dict:
    sm.render()  # the counters mirror the loop's plain floats at a scrape
    return {p: sm.device_starved.value(phase=p) for p in mn.TICK_PHASES}


@pytest.mark.parametrize(
    "inflight, phase, want",
    [
        # a burst in flight: the device has work whatever the host does
        (1, "tick.emit", 0.0),
        (2, "tick.decode_build", 0.0),
        (1, "tick.idle", 0.0),
        # a device wait is the device's time by definition, even when the
        # burst it reads was popped off the in-flight window first
        (0, "tick.decode_wait", 0.0),
        (0, "tick.drain", 0.0),
        (0, "tick.prefill_wait", 0.0),
        # nothing handed over and not read back: idle, or the host's turn
        (0, "tick.idle", 2.0),
        (0, "tick.admit", 2.0),
        (0, "tick.decode_dispatch", 2.0),
    ],
)
def test_a_segment_is_starved_when_the_device_holds_nothing(
        clock, inflight, phase, want):
    sm = EngineStepMetrics(inflight=lambda: inflight)
    with sm.phase(phase):
        clock.t += 2.0
    got = _starved_seconds(sm)
    assert got.pop(phase) == want
    assert set(got.values()) == {0.0}
    assert _phase_seconds(sm)[phase] == 2.0


def test_starved_seconds_follow_the_window_and_nested_phases_count_once(clock):
    """In-flight depth is read where a segment begins: the outer phase's
    two halves are two segments, and a nested block's seconds are counted
    once, under the innermost name, like the phase seconds themselves."""
    inflight = [0]
    sm = EngineStepMetrics(inflight=lambda: inflight[0])
    with sm.tick():
        clock.t += 1.0  # tick.sched, empty
        with sm.phase("tick.admit"):
            clock.t += 2.0  # empty
            with sm.phase("tick.prefill_wait"):
                clock.t += 4.0  # a device wait: never
            clock.t += 8.0  # tick.admit again, still empty
        with sm.phase("tick.decode_dispatch"):
            clock.t += 16.0  # began empty: the enqueue itself counts
            inflight[0] = 1
        clock.t += 32.0  # tick.sched again, a burst in flight
        with sm.phase("tick.decode_wait"):
            inflight[0] = 0  # popped, being read
            clock.t += 64.0
        with sm.phase("tick.emit"):
            clock.t += 128.0  # the last burst is read: empty again
    got = _starved_seconds(sm)
    assert got["tick.sched"] == 1.0
    assert got["tick.admit"] == 10.0
    assert got["tick.decode_dispatch"] == 16.0
    assert got["tick.emit"] == 128.0
    assert sum(got.values()) == 155.0
    assert sum(_phase_seconds(sm).values()) == 255.0


# -- what a decoding row waits for ---------------------------------------------


def _frames(sm: EngineStepMetrics) -> dict:
    sm.render()
    return {
        "interval": {k: sm.frame_interval.snapshot_total(kind=k) for k in mn.FRAME_KINDS},
        "row_s": {p: sm.frame_row_seconds.value(phase=p) for p in mn.TICK_PHASES},
        "stalls": {k: sm.frame_stalls.value(kind=k) for k in mn.FRAME_KINDS},
    }


def _burst(sm, clock, rows, wait=0.008, emit=0.002):
    """One reaped burst as the engine's loop times it: the readback wait,
    then the emission, inside which the frame is noted."""
    with sm.phase("tick.decode_wait"):
        clock.t += wait
    with sm.phase("tick.emit"):
        clock.t += emit / 2
        noted = sm.observe_frame(rows)
        clock.t += emit / 2
    return noted


def test_frame_interval_kind_follows_the_prefill_wait(clock):
    sm = EngineStepMetrics()
    with sm.tick():
        assert _burst(sm, clock, 3) is None  # the first frame: no interval yet
        assert _burst(sm, clock, 3) is None
        with sm.phase("tick.admit"):  # an admission lies between two frames
            clock.t += 0.001
            with sm.phase("tick.prefill_build"):
                clock.t += 0.002
                with sm.phase("tick.prefill_wait"):
                    clock.t += 0.040
            with sm.phase("tick.install"):
                clock.t += 0.003
        assert _burst(sm, clock, 4) is None
        assert _burst(sm, clock, 4) is None
    got = _frames(sm)
    # an interval runs from one emission's start to the next one's
    count, total = got["interval"]["decode"]
    assert count == 2 and total == pytest.approx(0.020)
    count, total = got["interval"]["prefill"]
    assert count == 1 and total == pytest.approx(0.056)
    # rows x seconds, by phase: the three rows of the first interval, the
    # four that got the frame after the admission, the four of the last
    assert got["row_s"]["tick.prefill_wait"] == pytest.approx(4 * 0.040)
    assert got["row_s"]["tick.admit"] == pytest.approx(4 * 0.001)
    assert got["row_s"]["tick.decode_wait"] == pytest.approx((3 + 4 + 4) * 0.008)
    assert sum(got["row_s"].values()) == pytest.approx(
        3 * 0.010 + 4 * 0.056 + 4 * 0.010)
    assert got["stalls"] == {"decode": 0.0, "prefill": 0.0}


def test_an_idle_stretch_drops_the_interval_and_an_admission_does_not(clock):
    sm = EngineStepMetrics()
    with sm.tick():
        _burst(sm, clock, 1)
        _burst(sm, clock, 1)
    with sm.tick():  # the last row finished: the loop waits for a request
        sm.forget_frame()
        with sm.phase("tick.idle"):
            clock.t += 30.0
    with sm.tick():
        with sm.phase("tick.admit"):
            with sm.phase("tick.prefill_wait"):
                clock.t += 0.050
        assert _burst(sm, clock, 1) is None  # no frame before it: no interval
        _burst(sm, clock, 1)
    got = _frames(sm)
    assert got["interval"]["decode"][0] == 2
    assert got["interval"]["prefill"][0] == 0
    assert got["row_s"]["tick.idle"] == 0.0
    assert got["stalls"] == {"decode": 0.0, "prefill": 0.0}


def test_a_stalled_interval_is_counted_and_described(clock):
    sm = EngineStepMetrics()
    with sm.tick():
        _burst(sm, clock, 5)
        with sm.phase("tick.install"):
            clock.t += 0.6  # the forced sleep
        noted = _burst(sm, clock, 5)
    assert noted == {
        "interval_s": 0.61, "frame_kind": "decode", "rows": 5,
        "phases": {"tick.install": 0.6, "tick.decode_wait": 0.008, "tick.emit": 0.002},
        "compiles": 0, "gc_s": pytest.approx(0.0, abs=0.05),
    }
    assert _frames(sm)["stalls"] == {"decode": 1.0, "prefill": 0.0}
    assert engine_metrics.FRAME_STALL_SECONDS == 0.5


def test_a_stall_leaves_one_flight_record_and_one_log_line(clock):
    """The engine's half: a 0.6 s sleep forced into a phase between two
    frames leaves a ``stall`` record on the flight ring that names the
    phase, and a WARNING line (the worker's log is kept beside every
    benchmark run)."""
    from tests.test_device_observe import warning_lines

    engine, _ = make_engine()
    sm = engine.step_metrics
    with warning_lines() as lines, sm.tick():
        with sm.phase("tick.emit"):
            engine._note_frame(2)
        with sm.phase("tick.prefill_build"):
            clock.t += 0.6
        with sm.phase("tick.prefill_wait"):
            clock.t += 0.05
        with sm.phase("tick.emit"):
            engine._note_frame(3)
            engine._note_frame(3)  # the next frame, at once: no stall
    stalls = [e for e in engine.flight.snapshot() if e["kind"] == "stall"]
    assert len(stalls) == 1
    assert stalls[0]["interval_s"] == 0.65 and stalls[0]["frame_kind"] == "prefill"
    assert stalls[0]["rows"] == 3 and stalls[0]["waiting"] == 0
    assert list(stalls[0]["phases"]) == ["tick.prefill_build", "tick.prefill_wait"]
    assert {"compiles", "gc_s"} <= set(stalls[0])
    said = [l for l in lines if l.startswith("stall: ")]
    assert len(said) == 1
    assert "0.650 s between two frames (prefill)" in said[0]
    assert "tick.prefill_build 0.600 s" in said[0] and "3 rows waiting" in said[0]


def test_every_new_series_is_in_the_first_scrape_at_zero():
    body = EngineStepMetrics().render()
    for phase in mn.TICK_PHASES:
        for family in (mn.ENGINE_DEVICE_STARVED_SECONDS_TOTAL,
                       mn.ENGINE_FRAME_ROW_SECONDS_TOTAL):
            assert f'{family}{{phase="{phase}"}} 0' in body.splitlines()
    for kind in mn.FRAME_KINDS:
        lines = body.splitlines()
        assert f'{mn.ENGINE_FRAME_STALLS_TOTAL}{{kind="{kind}"}} 0' in lines
        assert f'{mn.ENGINE_FRAME_INTERVAL}_count{{kind="{kind}"}} 0' in lines
        assert f'{mn.ENGINE_FRAME_INTERVAL}_sum{{kind="{kind}"}} 0.0' in lines
        assert f'{mn.ENGINE_FRAME_INTERVAL}_bucket{{kind="{kind}",le="10"}} 0' in lines
    assert "host_gap" not in body


def test_phase_helper_cost_is_microseconds():
    """Always on, inside the decode loop: a phase costs microseconds, not
    a log line or a lock wait. The best of five batches, so that a worker
    preempted once does not fail it; it measured about 3 us, the bound is
    50."""
    sm = EngineStepMetrics()
    n, best = 400, float("inf")
    with sm.tick():
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with sm.phase("tick.decode_wait", rows=3, nb=8):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
    assert best < 50e-6


# -- the profiler route --------------------------------------------------------


async def test_profile_route_exports_off_the_event_loop(monkeypatch):
    """``stop`` exports the trace (seconds on a chip): the route runs it in
    a thread, so /health answers while a slow stop_trace sleeps."""
    import jax.profiler as jp

    monkeypatch.setattr(jp, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jp, "stop_trace", lambda: time.sleep(1.5))
    profiler = global_profiler()
    server = SystemStatusServer(host="127.0.0.1", port=0)
    await server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        async with aiohttp.ClientSession() as http:
            async with http.post(base + "/debug/profile",
                                 json={"action": "start"}) as r:
                assert r.status == 200 and (await r.json())["ok"]
            t0 = time.monotonic()
            stop = asyncio.ensure_future(
                http.post(base + "/debug/profile", json={"action": "stop"}))
            await asyncio.sleep(0.05)  # the stop is now sleeping in its thread
            async with http.get(base + "/health") as r:
                assert r.status in (200, 503)
            answered = time.monotonic() - t0
            reply = await stop
            body = await reply.json()
            took = time.monotonic() - t0
        assert reply.status == 200 and body["ok"]
        assert answered < 1.0 and took >= 1.4, (answered, took)
        assert not profiler.status()["active"]
    finally:
        if profiler.status()["active"]:
            profiler.stop()
        await server.stop()


# -- the benchmark's reader ----------------------------------------------------


class _Ctx:
    def __init__(self, before: str, after: str) -> None:
        sys.path.insert(0, BENCH)
        import prom

        self.snapshots = {"window_start": {"worker0": prom.parse(before)},
                          "drained": {"worker0": prom.parse(after)}}

    def targets(self, which):
        return ["worker0"]


_BEFORE = """
dynamo_tpu_engine_tick_phase_seconds_sum{phase="tick.admit"} 1.0
dynamo_tpu_engine_tick_phase_seconds_sum{phase="tick.decode_wait"} 10.0
dynamo_tpu_engine_tick_phase_seconds_sum{phase="tick.idle"} 100.0
dynamo_tpu_engine_request_decode_tokens_total 50
"""
_AFTER = """
dynamo_tpu_engine_tick_phase_seconds_sum{phase="tick.admit"} 2.0
dynamo_tpu_engine_tick_phase_seconds_sum{phase="tick.decode_wait"} 13.0
dynamo_tpu_engine_tick_phase_seconds_sum{phase="tick.idle"} 150.0
dynamo_tpu_engine_tick_phase_seconds_sum{phase="tick.emit"} 0.5
dynamo_tpu_engine_request_decode_tokens_total 150
"""
_FAMILY = "dynamo_tpu_engine_tick_phase_seconds_sum"


def _terms(*phases):
    return [{"metric": _FAMILY, "labels": {"phase": p}} for p in phases]


@pytest.mark.parametrize(
    "params, want",
    [
        # (1 + 0.5) host seconds of (1 + 3 + 0.5) non-idle: a term that only
        # the later snapshot holds started from nothing
        ({"num": _terms("tick.admit", "tick.emit"),
          "den": _terms("tick.admit", "tick.emit", "tick.decode_wait"),
          "scale": 100.0}, 100.0 * 1.5 / 4.5),
        # a term nothing exports counts for nothing
        ({"num": _terms("tick.admit", "tick.install"),
          "den": _terms("tick.admit", "tick.decode_wait")}, 1.0 / 4.0),
        # terms of two families: seconds over tokens
        ({"num": _terms("tick.decode_wait"),
          "den": [{"metric": "dynamo_tpu_engine_request_decode_tokens_total"}],
          "scale": 1000.0}, 1000.0 * 3.0 / 100.0),
        # a program from before the counters: left out, not zero, not raised
        ({"num": _terms("tick.install"), "den": _terms("tick.admit")}, None),
        ({"num": _terms("tick.admit"), "den": _terms("tick.install")}, None),
    ],
)
def test_prometheus_ratio_reader(params, want):
    ctx = _Ctx(_BEFORE, _AFTER)
    from readers import prometheus_ratio

    got = prometheus_ratio.read({"target": "workers", **params}, ctx)
    assert got == pytest.approx(want) if want is not None else got is None


# -- what the benchmark reads of the program -----------------------------------
#
# One case per ``benchmark/layer_metrics/*.json`` whose reader touches the
# program (the ``client`` readers time the load generator's own clock and
# name nothing here). The files are read, never edited: a family, label,
# route, flag or program name they hold that the program stopped exporting
# would read nothing, for ever, in silence.


def _layer_metric(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


PROGRAM_READ = sorted(
    os.path.basename(path)[: -len(".json")]
    for path in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))
    if _layer_metric(os.path.basename(path)[: -len(".json")])["reader"] != "client"
)


def _terms(params: dict) -> list:
    return params.get("num", []) + params.get("den", []) or [params]


def _series(body: str, family: str, labels: dict) -> list:
    return [
        line for line in body.splitlines()
        if line.split("{")[0].split(" ")[0] in
        (family, family + "_sum", family + "_count", family + "_bucket")
        and all(f'{k}="{v}"' in line for k, v in labels.items())
    ]


@pytest.fixture(scope="module")
def served():
    """What a worker and a frontend expose once they have served: a tiny
    engine behind a system server with two overlapping streams (the second
    is admitted mid-decode, so the pipeline is drained once), and an HTTP
    service that streamed one completion. Scraped once for all cases."""
    from dynamo_tpu.http import HttpService, ModelManager
    from dynamo_tpu.llm import ModelDeploymentCard
    from dynamo_tpu.llm.protocols.common import FinishReason, PostprocessedOutput
    from dynamo_tpu.runtime.system_server import attach_engine

    class Scripted:
        async def generate(self, request, context):
            yield {"annotation": "_prompt_tokens", "value": 3}
            yield PostprocessedOutput(text="a", token_ids=[1], cumulative_tokens=1)
            yield PostprocessedOutput(
                finish_reason=FinishReason.LENGTH, cumulative_tokens=1)

    async def scrape(session, port, path):
        async with session.get(f"http://127.0.0.1:{port}{path}") as r:
            assert r.status == 200, path
            return await (r.json() if path.startswith("/debug/") else r.text())

    async def hybrid_scrape(config):
        # The metrics that belong to a hybrid or a latent-attention
        # configuration's cells read families only such an engine moves: a
        # tiny one, one stream twice (the repeat resumes from a
        # recurrent-state snapshot, or is a prefix hit over cached latents).
        engine, _ = make_engine(
            config=config, block_size=16, prefill_chunk=64, decode_steps=4)
        server = SystemStatusServer(host="127.0.0.1", port=0)
        attach_engine(server, engine)
        await server.start()
        try:
            for _ in range(2):
                await run_one(engine, req(range(10, 50), max_tokens=8))
            async with aiohttp.ClientSession() as s:
                return await scrape(s, server.port, "/metrics")
        finally:
            await server.stop()
            await engine.stop()

    async def run():
        from dynamo_tpu.models.config import (
            tiny_gdn_config, tiny_hybrid_config, tiny_mla_config, tiny_sala_config,
            tiny_swa_config)

        hybrid_body = await hybrid_scrape(tiny_hybrid_config())
        mla_body = await hybrid_scrape(tiny_mla_config())
        swa_body = await hybrid_scrape(tiny_swa_config())
        sala_body = await hybrid_scrape(tiny_sala_config())
        gdn_body = await hybrid_scrape(tiny_gdn_config())
        engine, _ = make_engine(decode_steps=4)
        server = SystemStatusServer(host="127.0.0.1", port=0)
        attach_engine(server, engine)
        manager = ModelManager()
        manager.register(
            "scripted", Scripted(), ModelDeploymentCard(name="scripted", context_length=64))
        service = HttpService(manager, host="127.0.0.1", port=0)
        await server.start()
        http_port = await service.start()
        try:
            first = asyncio.ensure_future(
                run_one(engine, req(range(10, 26), max_tokens=24)))
            while engine.generated_tokens < 4:
                await asyncio.sleep(0.005)
            await run_one(engine, req(range(40, 56), max_tokens=4))
            await first
            routes = {
                (r.method, r.resource.canonical)
                for r in server._runner.app.router.routes()  # noqa: SLF001
            }
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"http://127.0.0.1:{http_port}/v1/completions",
                    json={"model": "scripted", "prompt": "x", "stream": True},
                ) as r:
                    assert r.status == 200
                    await r.read()
                return {
                    "workers_hybrid": hybrid_body,
                    "workers_mla": mla_body,
                    "workers_swa": swa_body,
                    "workers_sala": sala_body,
                    "workers_gdn": gdn_body,
                    "workers": await scrape(s, server.port, "/metrics"),
                    "frontend": await scrape(s, http_port, "/metrics"),
                    "routes": routes,
                    "/debug/memory": await scrape(s, server.port, "/debug/memory"),
                    "/debug/compiles": await scrape(s, server.port, "/debug/compiles"),
                    "programs": {
                        "decode": engine.runner._build_decode_fn()._fn.__name__,  # noqa: SLF001
                        "prefill": engine.runner._build_step_fn()._fn.__name__,  # noqa: SLF001
                    },
                }
        finally:
            await service.stop(grace_period=1)
            await server.stop()
            await engine.stop()

    return asyncio.run(run())


@pytest.mark.parametrize("name", PROGRAM_READ)
def test_layer_metric_file_reads_what_the_program_exports(name, served):
    from dynamo_tpu.ops.moe import FORMS as MOE_FORMS
    from dynamo_tpu.worker.__main__ import build_parser

    spec = _layer_metric(name)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[name]
    keys = ("unit", "better", "source", "layer", "moves")
    assert {k: spec[k] for k in keys} == {k: entry[k] for k in keys}

    params = spec["params"]
    families = set(mn.ALL_ENGINE) | set(mn.ALL_FRONTEND) | {
        mn.KVCACHE_REUSED_TOKENS_TOTAL, mn.KVCACHE_RECOMPUTED_TOKENS_TOTAL}
    labels = (set(mn.TICK_PHASES) | set(mn.REQUEST_PHASES)
              | set(mn.FRAME_KINDS) | {"used", "total", "window", "sparse", "dense"}
              | {"updated", "slots"} | {"greedy", "full"} | {"1", "0"}
              | {"live", "padded"}
              | set(MOE_FORMS))
    # A metric listed for a hybrid configuration's cells alone is read off a
    # hybrid engine's scrape: a dense engine never moves its families.
    workers = "workers"
    # (one of both cells with recurrent layers too: the hybrid engine has them)
    if entry.get("workloads") and all(
            "nemotron" in w or "minicpm-sala" in w for w in entry["workloads"]):
        workers = "workers_hybrid"
    if entry.get("workloads") and all("openpangu" in w for w in entry["workloads"]):
        workers = "workers_mla"
    if entry.get("workloads") and all("laguna" in w for w in entry["workloads"]):
        workers = "workers_swa"
    if entry.get("workloads") and all("minicpm-sala" in w for w in entry["workloads"]):
        workers = "workers_sala"
    if entry.get("workloads") and all("qwen3-next" in w for w in entry["workloads"]):
        workers = "workers_gdn"
    flags = {o for a in build_parser()._actions for o in a.option_strings}  # noqa: SLF001
    for key in ("per_flag", "percent_of_worker_flag"):
        assert params.get(key) in flags | {None}, (name, key)

    if spec["reader"].startswith("prometheus_"):
        body = served["frontend" if params["target"] == "frontend" else workers]
        for term in _terms(params):
            family = term["metric"]
            for suffix in ("_sum", "_count"):
                if family.endswith(suffix) and family not in families:
                    family = family[: -len(suffix)]
            assert family in families, (name, term)
            assert set((term.get("labels") or {}).values()) <= labels, (name, term)
            assert _series(body, family, term.get("labels") or {}), (name, term)
    elif spec["reader"] == "route_json":
        assert ("GET", params["path"]) in served["routes"], name
        at = [served[params["path"]]]
        for key in params["pointer"]:
            # the CPU backend keeps no memory statistics: there the walk
            # ends at the null the route serves in their place
            at = [x for x in at if x is not None]
            assert key == "*" or all(key in x for x in at), (name, key)
            at = [v for x in at for v in (x if key == "*" else [x[key]])]
    else:
        assert spec["reader"] in (
            "trace", "hybrid_roofline", "mla_roofline", "swa_roofline", "sala_roofline",
            "gdn_roofline")
        assert ("POST", "/debug/profile") in served["routes"]
        for key, family in params.items():
            if key.endswith("_metric"):
                # ``rows_metric`` is read under ``rows_labels``: the served
                # scrape must hold that very series, label values included
                series_labels = params.get(key[: -len("metric")] + "labels") or {}
                assert family in families, (name, key)
                assert _series(served[workers], family, series_labels), (name, key)
        if "program" in params:
            sys.path.insert(0, BENCH)
            import trace_reduce

            rule = trace_reduce.load_names()["programs"][params["program"]]
            assert "jit_" + served["programs"][params["program"]] in rule["module"]


def test_host_share_phases_are_the_whole_non_idle_set():
    p = _layer_metric("engine.tick_host_share")["params"]
    assert {t["labels"]["phase"] for t in p["num"]} == set(mn.TICK_PHASES_HOST)
    assert {t["labels"]["phase"] for t in p["den"]} == \
        set(mn.TICK_PHASES_HOST) | set(mn.TICK_PHASES_DEVICE_WAIT)


def test_starved_shares_split_the_phases_that_can_starve():
    """Each tick phase that can be starved is in exactly one of the three
    starved shares, each share is over all eleven phases' seconds, and the
    row-wait share is over all eleven too."""
    shares = [_layer_metric("engine.device_starved_" + part + "_share")["params"]
              for part in ("idle", "prefill", "decode")]
    nums = [t["labels"]["phase"] for p in shares for t in p["num"]]
    assert sorted(nums) == sorted(mn.TICK_PHASES_HOST + mn.TICK_PHASES_IDLE)
    for p in shares:
        assert {t["metric"] for t in p["num"]} == {mn.ENGINE_DEVICE_STARVED_SECONDS_TOTAL}
        assert {t["metric"] for t in p["den"]} == {mn.ENGINE_TICK_PHASE + "_sum"}
        assert sorted(t["labels"]["phase"] for t in p["den"]) == sorted(mn.TICK_PHASES)
    rows = _layer_metric("engine.row_wait_prefill_share")["params"]
    assert sorted(t["labels"]["phase"] for t in rows["den"]) == sorted(mn.TICK_PHASES)
    assert {t["labels"]["phase"] for t in rows["num"]} == {
        "tick.admit", "tick.prefill_build", "tick.prefill_wait", "tick.install"}
    for name in PROGRAM_READ:
        if _layer_metric(name)["params"].get("to") == "window_end":
            assert _layer_metric(name)["params"]["from"] == "window_start"


def _roofline_reader_cases():
    """``benchmark/tests/test_decode_roofline_reader.py``, loaded by path:
    the benchmark keeps the cases beside its reader (tier-1 is not its to
    edit); here they count. The file imports no JAX and reads no chip."""
    import importlib.util

    path = os.path.join(BENCH, "tests", "test_decode_roofline_reader.py")
    spec = importlib.util.spec_from_file_location("benchmark_roofline_reader_cases", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ROOFLINE_READER_CASES = (
    "bursts_in_the_drain", "burst_not_counted", "no_counters", "no_decode_burst",
    "one_of_two_idle", "pages_before_capture", "polls_all_idle", "two_workers",
    "the_share_is_a_lower_estimate",
)


@pytest.mark.parametrize("case", ROOFLINE_READER_CASES)
def test_decode_roofline_reader_on_made_up_captures(case):
    """What ``kernel.decode_roofline`` prints from the capture's own
    counters (PR 32), on captures made up without a chip: the benchmark's
    own nine cases, none missing and none new."""
    cases = _roofline_reader_cases()
    assert set(ROOFLINE_READER_CASES[:-1]) == set(cases.CASES)
    if case in cases.CASES:
        cases.test_decode_roofline_share(case)
    else:
        getattr(cases, "test_" + case)()


def test_recorded_fixture_holds_the_spans_and_the_named_programs():
    """``benchmark/fixtures/served_tick.xplane.pb`` (a chip capture through
    /debug/profile, cut to 0.15 s): what the gap-attribution work will test
    against. The host spans are where its expectation says, and the
    benchmark's reducer tells the renamed programs apart with the merged
    ``trace_names/*.json``."""
    import collections

    from jax.profiler import ProfileData

    sys.path.insert(0, BENCH)
    import trace_reduce

    with open(os.path.join(BENCH, "fixtures", "served_tick.expect.json")) as f:
        expect = json.load(f)
    pd = ProfileData.from_file(os.path.join(BENCH, "fixtures", "served_tick.xplane.pb"))
    planes = {p.name: p for p in pd.planes}
    spans = {}
    for line in planes[expect["host_plane"]].lines:
        counts = collections.Counter(
            e.name for e in line.events if e.name.startswith(("tick.", "device.")))
        role = "scheduler" if any(n.startswith("tick.") for n in counts) else "device_thread"
        assert line.name == expect["lines"][role]["line"]
        spans[role] = dict(counts)
    assert spans["scheduler"] == expect["lines"]["scheduler"]["spans"]
    assert spans["device_thread"] == expect["lines"]["device_thread"]["spans"]
    assert set(spans["scheduler"]) <= set(mn.TICK_PHASES)
    assert set(spans["device_thread"]) <= set(mn.DEVICE_SPANS)
    assert sum(spans["scheduler"].values()) == expect["tick_events"]

    got = trace_reduce.reduce_plane(
        planes[expect["device_plane"]], trace_reduce.load_names(), 1e9)
    kinds = {name.split("(")[0]: row["kind"] for name, row in got["modules"].items()}
    assert kinds["jit_decode_burst"] == "decode"
    assert kinds["jit_prefill_step"] == "prefill"
    # whole executions inside the window (the first one began before its
    # first recorded operation and is not counted)
    assert got["programs"]["prefill"]["count"] >= 1
    assert got["programs"]["decode"]["count"] == 1


async def test_dispatch_counts_live_pages_and_table_slots():
    """Every dispatched decode burst adds the pages its active rows'
    contexts reach to ``decode_live_pages_total`` and ``max_num_seqs x
    width bucket`` to ``decode_table_slots_total``: one stream of 10 prompt
    tokens, bursts of 4 steps, 4-token pages, one burst in flight."""
    engine, _ = make_engine(pipeline_depth=1, decode_steps=4)
    sm = engine.step_metrics
    dispatched = []
    real = sm.observe_decode_pages
    sm.observe_decode_pages = lambda live, slots: (
        dispatched.append((live, slots)), real(live, slots)
    )
    try:
        out = await run_one(engine, req(range(10, 20), max_tokens=9))
    finally:
        await engine.stop()
    assert sum(len(o.token_ids) for o in out) == 9
    # the first token comes from the prefill; burst i then attends over
    # 10 + 4 (i + 1) tokens: 14 -> 4 pages in a 4-wide table, 18 -> 5 in 8
    assert dispatched[:2] == [(4, 4 * 4), (5, 4 * 8)]
    assert sm.decode_live_pages.value() == sum(l for l, _ in dispatched)
    assert sm.decode_table_slots.value() == sum(s for _, s in dispatched)
    text = sm.render()
    assert f"{mn.ENGINE_DECODE_LIVE_PAGES_TOTAL} " in text
    assert f"{mn.ENGINE_DECODE_TABLE_SLOTS_TOTAL} " in text
