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
from typing import Any, Callable, Dict, Optional

# An interval between two frames of a decoding row longer than this is a
# stall: counted, and put on the record by the engine. A constant, not a knob.
FRAME_STALL_SECONDS = 0.5


class _PhaseScope:
    """One ``with step_metrics.phase(name)`` block. Entering suspends the
    enclosing scope's segment and leaving resumes it, so at any instant the
    time belongs to exactly one phase: the innermost one open."""

    __slots__ = ("_m", "name", "attrs", "waits", "_outer")

    def __init__(self, metrics: "EngineStepMetrics", name: str, attrs: dict,
                 waits: bool = False) -> None:
        self._m = metrics
        self.name = name
        self.attrs = attrs
        self.waits = waits  # a device-wait phase: never starved
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
    def __init__(self, inflight: Callable[[], int] = lambda: 0) -> None:
        """``inflight``: how many decode bursts the engine has handed the
        device and not yet read back (the engine's ``len`` of them)."""
        from dynamo_tpu.runtime import metric_names as mn
        from dynamo_tpu.runtime.device_observe import (
            global_compile_watcher,
            global_gc_watcher,
        )
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
        # What the device waits for and what a decoding row waits for: the
        # same segments, counted twice more (see _begin_segment and
        # observe_frame). The counters mirror plain dicts at render.
        self.device_starved = self.registry.counter(
            mn.ENGINE_DEVICE_STARVED_SECONDS_TOTAL,
            "Scheduler-loop wall time in segments that are no device wait "
            "and began with no decode burst in flight, by phase: a lower "
            "bound on the time the device held no program",
            ["phase"],
        )
        self.frame_interval = self.registry.histogram(
            mn.ENGINE_FRAME_INTERVAL,
            "Interval between two reaped bursts that each gave a live row "
            "a frame; kind=prefill when the loop awaited a prefill step in "
            "between, else decode",
            ["kind"],
        )
        self.frame_row_seconds = self.registry.counter(
            mn.ENGINE_FRAME_ROW_SECONDS_TOTAL,
            "Rows that got a frame x the seconds of the interval before it "
            "in each tick phase: what live decode rows waited for",
            ["phase"],
        )
        self.frame_stalls = self.registry.counter(
            mn.ENGINE_FRAME_STALLS_TOTAL,
            f"Frame intervals over {FRAME_STALL_SECONDS} s, each also a "
            "'stall' flight record and a WARNING line of the worker",
            ["kind"],
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
        # A hybrid model's second kind of state and its experts' load
        # (metric_names.py says what each counts); never touched otherwise.
        self.moe_experts_hit = self.registry.counter(
            mn.ENGINE_MOE_EXPERTS_HIT_TOTAL,
            "Held experts that got a token, summed over the steps and "
            "expert layers of reaped decode bursts",
        )
        self.moe_expert_slots = self.registry.counter(
            mn.ENGINE_MOE_EXPERT_SLOTS_TOTAL,
            "Layer-steps x experts held, summed over reaped decode bursts",
        )
        self.moe_max_expert_tokens = self.registry.counter(
            mn.ENGINE_MOE_MAX_EXPERT_TOKENS_TOTAL,
            "Most tokens on one held expert, summed over layer-steps",
        )
        self.moe_mean_expert_tokens = self.registry.counter(
            mn.ENGINE_MOE_MEAN_EXPERT_TOKENS_TOTAL,
            "Mean tokens on a held expert, summed over layer-steps",
        )
        self.moe_assignments = self.registry.counter(
            mn.ENGINE_MOE_ASSIGNMENTS_TOTAL,
            "Top-k choices of the live rows of reaped decode bursts, summed "
            "over steps and expert layers: held=1 those that fell on experts "
            "held here, held=0 on the absent ones",
            ["held"],
        )
        self.moe_prefill_tokens = self.registry.counter(
            mn.ENGINE_MOE_PREFILL_TOKENS_TOTAL,
            "Live prompt tokens of reaped prefill steps through expert "
            "layers, by the form the step's static token count takes",
            ["form"],
        )
        self.ssm_decode_rows = self.registry.counter(
            mn.ENGINE_SSM_DECODE_ROWS_TOTAL,
            "Rows of recurrent state the steps of dispatched decode bursts "
            "pass: state=updated the rows read and written (the live rows "
            "under the live-row kernel, every slot under the XLA form), "
            "state=slots every slot",
            ["state"],
        )
        self.sampler_decode_steps = self.registry.counter(
            mn.ENGINE_SAMPLER_DECODE_STEPS_TOTAL,
            "Steps of dispatched decode bursts by the sampler's branch: "
            "path=greedy no live row samples (arg-max of the logits), "
            "path=full a live row does (candidates, sort, noise)",
            ["path"],
        )
        for path in ("greedy", "full"):  # both series from start-up
            self.sampler_decode_steps.inc(0, path=path)
        self.prefill_positions = self.registry.counter(
            mn.ENGINE_PREFILL_POSITIONS_TOTAL,
            "Positions of dispatched prefill steps (rows bucket x chunk "
            "bucket each): kind=live the prompt tokens among them, "
            "kind=padded the rest",
            ["kind"],
        )
        for kind in ("live", "padded"):  # both series from start-up
            self.prefill_positions.inc(0, kind=kind)
        self.prefill_dispatches = self.registry.counter(
            mn.ENGINE_PREFILL_DISPATCHES_TOTAL,
            "Dispatched prefill steps by static shape: rows bucket, chunk bucket",
            ["rows", "chunk"],
        )
        self.ssm_state_slots = self.registry.gauge(
            mn.ENGINE_SSM_STATE_SLOTS,
            "Per-sequence recurrent-state slots (one per decode row)",
            ["state"],
        )
        self.ssm_snapshots = self.registry.gauge(
            mn.ENGINE_SSM_SNAPSHOTS,
            "Recurrent-state snapshots kept for prefix reuse", ["state"],
        )
        self.ssm_snapshot_hits = self.registry.counter(
            mn.ENGINE_SSM_SNAPSHOT_HITS_TOTAL,
            "Admissions that resumed their recurrent state from a snapshot",
        )
        self.ssm_snapshot_evictions = self.registry.counter(
            mn.ENGINE_SSM_SNAPSHOT_EVICTIONS_TOTAL,
            "Snapshots evicted to make room (least recently used first)",
        )
        # Two page groups (metric_names.py says what each counts); never
        # touched by a model with one.
        self.kv_group_blocks = self.registry.gauge(
            mn.ENGINE_KV_GROUP_BLOCKS,
            "Blocks of each page group of a model with two", ["group", "state"],
        )
        self.window_pages_released = self.registry.counter(
            mn.ENGINE_WINDOW_PAGES_RELEASED_TOTAL,
            "Window-group pages given back behind a running sequence's window",
        )
        self.window_pages_dead = self.registry.counter(
            mn.ENGINE_WINDOW_PAGES_DEAD_TOTAL,
            "Window-group pages live rows hold wholly behind their window, "
            "summed over dispatched decode bursts",
        )
        self.window_pages_held = self.registry.counter(
            mn.ENGINE_WINDOW_PAGES_HELD_TOTAL,
            "Window-group pages live rows hold, summed over dispatched "
            "decode bursts",
        )
        self.decode_window_live_pages = self.registry.counter(
            mn.ENGINE_DECODE_WINDOW_LIVE_PAGES_TOTAL,
            "Window-group pages the active rows attend over, summed over "
            "dispatched decode bursts",
        )
        self.prefix_hits_cut_by_window = self.registry.counter(
            mn.ENGINE_PREFIX_HITS_CUT_BY_WINDOW_TOTAL,
            "Prefix hits shortened because the window group no longer held "
            "the window in front of the resume position",
        )
        # Sparse attention layers (metric_names.py says what each counts);
        # never touched by a model without them.
        self.sparse_pages_selected = self.registry.counter(
            mn.ENGINE_SPARSE_PAGES_SELECTED_TOTAL,
            "Pages a sparse attention layer's kernel visits for the rows of "
            "dispatched decode bursts",
        )
        self.sparse_pages_live = self.registry.counter(
            mn.ENGINE_SPARSE_PAGES_LIVE_TOTAL,
            "Pages those rows hold (what dense attention would visit)",
        )
        self.sparse_rows = self.registry.counter(
            mn.ENGINE_SPARSE_ROWS_TOTAL,
            "Rows of dispatched decode bursts by the path their sparse "
            "layers take", ["path"],
        )
        # phase name -> is it a device wait (the vocabulary and its one
        # class the counts below ask about, in one lookup)
        self._phases = {
            name: name in mn.TICK_PHASES_DEVICE_WAIT for name in mn.TICK_PHASES
        }
        self._idle_phases = frozenset(mn.TICK_PHASES_IDLE)
        self._request_phases = mn.REQUEST_PHASES
        self._inflight = inflight
        self._compile_watcher = global_compile_watcher()
        self._gc_watcher = global_gc_watcher()
        self._starved = False  # the open segment began with the device empty
        # Seconds of closed segments by phase, and the two counts made of
        # them; plain floats on the loop's path, mirrored at render.
        self._phase_s: Dict[str, float] = dict.fromkeys(mn.TICK_PHASES, 0.0)
        self._starved_s: Dict[str, float] = dict.fromkeys(mn.TICK_PHASES, 0.0)
        self._frame_row_s: Dict[str, float] = dict.fromkeys(mn.TICK_PHASES, 0.0)
        self._stalls: Dict[str, int] = dict.fromkeys(mn.FRAME_KINDS, 0)
        # (seconds by phase, compiles, collector seconds) at the last frame
        self._frame_mark: Optional[tuple] = None
        # Every series of the three from the first scrape: a share over
        # phases must not read a phase not met yet as a family not there.
        for name in mn.TICK_PHASES:
            self.tick_phase.touch(phase=name)
        for kind in mn.FRAME_KINDS:
            self.frame_interval.touch(kind=kind)
        self.registry.on_render(self._refresh)
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
        waits = self._phases.get(name)
        if waits is None:
            raise KeyError(f"unknown tick phase {name!r}")
        return _PhaseScope(self, name, attrs, waits)

    def tick(self) -> _TickScope:
        return _TickScope(self)

    def _begin_segment(self, scope: _PhaseScope) -> None:
        self._scope = scope
        self._starved = not scope.waits and not self._inflight()
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
        self._phase_s[name] += dt
        if self._starved:
            self._starved_s[name] += dt
        if name in self._idle_phases:
            self._idle_segments += 1

    def observe_frame(self, rows: int) -> Optional[Dict[str, Any]]:
        """A reaped burst just gave ``rows`` live rows a frame. The interval
        since the last such frame is the difference of the closed segments'
        seconds by phase (so it runs from the start of that emission to the
        start of this one, and its phases add up to it exactly). Returns
        what the engine puts on the record when the interval is a stall."""
        mark = (
            tuple(self._phase_s.values()),
            self._compile_watcher.compiles,
            self._gc_watcher.seconds,
        )
        last, self._frame_mark = self._frame_mark, mark
        if last is None:
            return None
        spent = {
            name: now - then
            for name, then, now in zip(self._phase_s, last[0], mark[0])
            if now > then
        }
        interval = sum(spent.values())
        kind = "prefill" if "tick.prefill_wait" in spent else "decode"
        self.frame_interval.observe(interval, kind=kind)
        for name, seconds in spent.items():
            self._frame_row_s[name] += rows * seconds
        if interval <= FRAME_STALL_SECONDS:
            return None
        self._stalls[kind] += 1
        top = sorted(spent.items(), key=lambda item: -item[1])[:3]
        return {
            "interval_s": round(interval, 4),
            "frame_kind": kind,
            "rows": rows,
            "phases": {name: round(seconds, 4) for name, seconds in top},
            "compiles": mark[1] - last[1],
            "gc_s": round(mark[2] - last[2], 4),
        }

    def forget_frame(self) -> None:
        """The loop went idle with no live row: the next frame's interval
        would span the wait for a request, which no row waited through."""
        self._frame_mark = None

    def _refresh(self) -> None:
        for name in self._phase_s:
            self.device_starved.set_total(self._starved_s[name], phase=name)
            self.frame_row_seconds.set_total(self._frame_row_s[name], phase=name)
        for kind, count in self._stalls.items():
            self.frame_stalls.set_total(count, kind=kind)

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

    def observe_prefill(
        self, duration_s: float, occupancy: int, tokens: int, rows: int, chunk: int
    ) -> None:
        """One dispatched prefill step of static shape [rows, chunk] with
        ``tokens`` prompt tokens among its positions, ``occupancy`` of its
        rows still prefilling."""
        self.step_duration.observe(duration_s, phase="prefill")
        self.batch_occupancy.observe(occupancy, phase="prefill")
        self.prefill_tokens.observe(tokens)
        self.prefill_positions.inc(tokens, kind="live")
        self.prefill_positions.inc(rows * chunk - tokens, kind="padded")
        self.prefill_dispatches.inc(1, rows=str(rows), chunk=str(chunk))

    def observe_decode(self, duration_s: float, occupancy: int, tokens: int) -> None:
        self.step_duration.observe(duration_s, phase="decode")
        self.batch_occupancy.observe(occupancy, phase="decode")
        self.decode_tokens.observe(tokens)

    def observe_inflight(self, depth: int) -> None:
        self.inflight_depth.observe(depth)

    def observe_decode_pages(self, live_pages: int, table_slots: int) -> None:
        self.decode_live_pages.inc(live_pages)
        self.decode_table_slots.inc(table_slots)

    def observe_moe(self, hit: float, slots: float, most: float, mean: float) -> None:
        self.moe_experts_hit.inc(hit)
        self.moe_expert_slots.inc(slots)
        self.moe_max_expert_tokens.inc(most)
        self.moe_mean_expert_tokens.inc(mean)

    def observe_moe_assignments(self, held: float, choices: float) -> None:
        self.moe_assignments.inc(held, held="1")
        self.moe_assignments.inc(max(choices - held, 0.0), held="0")

    def observe_moe_prefill(self, tokens_by_form: Dict[str, int]) -> None:
        for form, tokens in tokens_by_form.items():
            self.moe_prefill_tokens.set_total(tokens, form=form)

    def observe_kv_groups(self, groups: Dict[str, Dict[str, int]],
                          released: int, cut: int) -> None:
        for group, states in groups.items():
            for state, n in states.items():
                self.kv_group_blocks.set(n, group=group, state=state)
        self.window_pages_released.set_total(released)
        self.prefix_hits_cut_by_window.set_total(cut)

    def observe_sparse(self, selected: int, live: int, rows: Dict[str, int]) -> None:
        self.sparse_pages_selected.inc(selected)
        self.sparse_pages_live.inc(live)
        for path, n in rows.items():
            self.sparse_rows.inc(n, path=path)

    def observe_window_pages(self, live: int, held: int, dead: int) -> None:
        self.decode_window_live_pages.inc(live)
        self.window_pages_held.inc(held)
        self.window_pages_dead.inc(dead)

    def observe_sampler_steps(self, steps: int, any_sampled: bool) -> None:
        self.sampler_decode_steps.inc(steps, path="full" if any_sampled else "greedy")

    def observe_ssm_decode(self, updated: int, slots: int) -> None:
        self.ssm_decode_rows.inc(updated, state="updated")
        self.ssm_decode_rows.inc(slots, state="slots")

    def observe_ssm(self, slots_used: int, slots_total: int,
                    snaps_used: int, snaps_total: int) -> None:
        self.ssm_state_slots.set(slots_used, state="used")
        self.ssm_state_slots.set(slots_total, state="total")
        self.ssm_snapshots.set(snaps_used, state="used")
        self.ssm_snapshots.set(snaps_total, state="total")

    def render(self, openmetrics: bool = False) -> str:
        return self.registry.render(openmetrics=openmetrics)

    def register_metrics(self, server: Any) -> None:
        """Expose this engine's step families on a SystemStatusServer."""
        server.register_metrics(self.render)
