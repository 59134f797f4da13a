"""Engine step-loop metrics (runtime/metric_names.py ALL_ENGINE families).

Reference parity: the reference's backend ForwardPassMetrics / engine-side
Prometheus gauges — but for the step loop itself: how long each device
dispatch takes, how full the batch is, and how many tokens each step moved,
split prefill vs decode. These are the signals the planner's SLA math and
the ROADMAP's autoscaling direction need (step time × occupancy = achieved
throughput; prefill-vs-decode token mix = P/D balance).

One instance per engine object on a private registry (see
runtime/metrics_core.py for why not prometheus_client's global registry);
``render`` plugs into ``SystemStatusServer.register_metrics`` — wired by
``attach_engine`` for any engine exposing a ``step_metrics`` attribute.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Optional


class _PhaseScope:
    """One ``with step_metrics.phase(name)`` block. Entering suspends the
    enclosing scope's segment and leaving resumes it, so at any instant the
    time belongs to exactly one phase: the innermost one open."""

    __slots__ = ("_m", "name", "attrs", "_outer")

    def __init__(self, metrics: "EngineStepMetrics", name: str, attrs: dict) -> None:
        self._m = metrics
        self.name = name
        self.attrs = attrs
        self._outer: Optional[_PhaseScope] = None

    def __enter__(self) -> "_PhaseScope":
        m = self._m
        self._outer = m._scope
        if self._outer is not None:
            m._end_segment()
        m._begin_segment(self)
        return self

    def __exit__(self, *exc: Any) -> None:
        m = self._m
        m._end_segment()
        if self._outer is not None:
            m._begin_segment(self._outer)


class _TickScope:
    """One iteration of the scheduler loop: ``tick.sched`` underneath
    whatever the iteration opens, and one ``tick_seconds`` observation when
    it leaves without having gone idle."""

    __slots__ = ("_m", "_base", "_t0", "_idle0")

    def __init__(self, metrics: "EngineStepMetrics") -> None:
        self._m = metrics
        self._base = _PhaseScope(metrics, "tick.sched", {})

    def __enter__(self) -> "_TickScope":
        self._t0 = time.monotonic()
        self._idle0 = self._m._idle_segments
        self._base.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._base.__exit__(*exc)
        if self._m._idle_segments == self._idle0:
            self._m.tick_duration.observe(time.monotonic() - self._t0)


class EngineStepMetrics:
    def __init__(self) -> None:
        from dynamo_tpu.runtime import metric_names as mn
        from dynamo_tpu.runtime.metrics_core import COUNT_BUCKETS, MetricsRegistry

        self.registry = MetricsRegistry()
        self.step_duration = self.registry.histogram(
            mn.ENGINE_STEP_DURATION,
            "Device step wall time (one dispatch), by phase (prefill|decode)",
            ["phase"],
        )
        self.batch_occupancy = self.registry.histogram(
            mn.ENGINE_BATCH_OCCUPANCY,
            "Sequences packed into one device step, by phase",
            ["phase"],
            buckets=COUNT_BUCKETS,
        )
        self.prefill_tokens = self.registry.histogram(
            mn.ENGINE_STEP_PREFILL_TOKENS,
            "Prompt tokens processed per prefill step",
            buckets=COUNT_BUCKETS,
        )
        self.decode_tokens = self.registry.histogram(
            mn.ENGINE_STEP_DECODE_TOKENS,
            "Tokens emitted per decode step (fused multi-iteration burst)",
            buckets=COUNT_BUCKETS,
        )
        # Decode-tick pipelining (dispatch/reap split): host_gap is the
        # device wait the host injected between the previous burst's
        # readback completing and the next dispatch being enqueued — 0
        # whenever another burst was already queued on the device. The
        # depth-1 vs depth-2 comparison of this family IS the overlap win.
        self.host_gap = self.registry.histogram(
            mn.ENGINE_HOST_GAP,
            "Host-injected device wait between decode bursts "
            "(0 = the next burst was already in flight)",
        )
        self.inflight_depth = self.registry.histogram(
            mn.ENGINE_INFLIGHT_DEPTH,
            "Decode bursts in flight on the device at each dispatch "
            "(including the one being dispatched)",
            buckets=COUNT_BUCKETS,
        )

        # The tick seen from inside: see phase() below.
        self.tick_phase = self.registry.histogram(
            mn.ENGINE_TICK_PHASE,
            "Scheduler-loop wall time by phase; the phases are exclusive and "
            "partition the loop's wall time",
            ["phase"],
        )
        self.tick_duration = self.registry.histogram(
            mn.ENGINE_TICK,
            "Wall time of one scheduler-loop iteration that did not go idle",
        )
        self.request_phase = self.registry.histogram(
            mn.ENGINE_REQUEST_PHASE,
            "Per finished stream: queue (enqueue to admission), prefill "
            "(admission to first output), decode (first output to end)",
            ["phase"],
        )
        self.request_decode_tokens = self.registry.counter(
            mn.ENGINE_REQUEST_DECODE_TOKENS_TOTAL,
            "Tokens of the decode phase over finished streams "
            "(generated - 1 each)",
        )
        self.decode_live_pages = self.registry.counter(
            mn.ENGINE_DECODE_LIVE_PAGES_TOTAL,
            "KV pages the active rows' contexts reach, summed over "
            "dispatched decode bursts",
        )
        self.decode_table_slots = self.registry.counter(
            mn.ENGINE_DECODE_TABLE_SLOTS_TOTAL,
            "Slots of the dispatched block table (max_num_seqs x table "
            "width bucket), summed over dispatched decode bursts",
        )
        self._phases = frozenset(mn.TICK_PHASES)
        self._idle_phases = frozenset(mn.TICK_PHASES_IDLE)
        self._request_phases = mn.REQUEST_PHASES
        self._scope: Optional[_PhaseScope] = None  # the open phase, if any
        self._t0 = 0.0
        self._annotation: Any = None
        self._idle_segments = 0
        self._trace_annotation: Any = None  # jax.profiler.TraceAnnotation, once

    # -- the tick seen from inside ------------------------------------------

    def annotate(self, name: str, **attrs: Any) -> Any:
        """A host span in the profiler's own trace, on the device trace's
        clock (``jax.profiler.TraceAnnotation``): under a microsecond when
        no capture is active. Without JAX, a null context."""
        cls = self._trace_annotation
        if cls is None:
            try:
                from jax.profiler import TraceAnnotation as cls
            except ImportError:
                cls = False
            self._trace_annotation = cls
        if cls is False:
            return contextlib.nullcontext()
        return cls(name, **attrs)

    def phase(self, name: str, **attrs: Any) -> _PhaseScope:
        """``with step_metrics.phase("tick.admit", rows=3):`` — a profiler
        annotation of that name, and on exit its monotonic duration added
        to ``tick_phase_seconds{phase}``. Only the scheduler task opens
        phases; a phase that awaits stays open across the await. The name
        must be one of metric_names.TICK_PHASES."""
        if name not in self._phases:
            raise KeyError(f"unknown tick phase {name!r}")
        return _PhaseScope(self, name, attrs)

    def tick(self) -> _TickScope:
        return _TickScope(self)

    def _begin_segment(self, scope: _PhaseScope) -> None:
        self._scope = scope
        self._annotation = self.annotate(scope.name, **scope.attrs)
        self._annotation.__enter__()
        self._t0 = time.monotonic()

    def _end_segment(self) -> None:
        scope = self._scope
        if scope is None:  # unbalanced exit: nothing open, nothing to close
            return
        dt = time.monotonic() - self._t0
        self._annotation.__exit__(None, None, None)
        name = scope.name
        self._scope = None
        self.tick_phase.observe(dt, phase=name)
        if name in self._idle_phases:
            self._idle_segments += 1

    def observe_request(self, queue, prefill, decode, decode_tokens: int) -> None:
        """One finished stream: the (start, end) monotonic stamps of its
        queue / prefill / decode phase spans, None for a phase it never
        reached. A preempted stream's re-prefill restamps its prefill
        start after its first output: clamped, never a negative."""
        for phase, span in zip(self._request_phases, (queue, prefill, decode)):
            if span is not None:
                self.request_phase.observe(
                    max(0.0, span[1] - span[0]), phase=phase
                )
        if decode is not None:
            self.request_decode_tokens.inc(max(decode_tokens, 0))

    def observe_prefill(self, duration_s: float, occupancy: int, tokens: int) -> None:
        self.step_duration.observe(duration_s, phase="prefill")
        self.batch_occupancy.observe(occupancy, phase="prefill")
        self.prefill_tokens.observe(tokens)

    def observe_decode(self, duration_s: float, occupancy: int, tokens: int) -> None:
        self.step_duration.observe(duration_s, phase="decode")
        self.batch_occupancy.observe(occupancy, phase="decode")
        self.decode_tokens.observe(tokens)

    def observe_host_gap(self, gap_s: float) -> None:
        self.host_gap.observe(gap_s)

    def observe_inflight(self, depth: int) -> None:
        self.inflight_depth.observe(depth)

    def observe_decode_pages(self, live_pages: int, table_slots: int) -> None:
        self.decode_live_pages.inc(live_pages)
        self.decode_table_slots.inc(table_slots)

    def render(self, openmetrics: bool = False) -> str:
        return self.registry.render(openmetrics=openmetrics)

    def register_metrics(self, server: Any) -> None:
        """Expose this engine's step families on a SystemStatusServer."""
        server.register_metrics(self.render)
