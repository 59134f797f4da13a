"""Device/runtime observability plane: compile telemetry, HBM ledger,
engine flight recorder, on-demand profiler capture.

PR 1 built the *serving-plane* observability layer (per-object metric
registries, request timelines, trace exemplars); this module is the
*device plane* — the reference Dynamo treats runtime-level metrics as a
first-class layer next to the serving metrics (PAPER layer map), and the
PR 2/3 decode path (width-bucketed programs, pipelined ticks) created
exactly the failure classes that are invisible
without it: a silent recompile storm, HBM-accounting drift, or a tick
pipeline wedging with no record of the events that led there.

Five parts, all designed to stay OFF the tick thread's critical path:

  1. **Compile telemetry** (``watched_jit`` / ``CompileWatcher``): every
     ``jax.jit`` program site wraps its compiled callable; per program we
     track compile count, distinct-signature count, compile wall-time, and
     a recompile-storm detector (counter + warning when one program object
     crosses its signature budget — the pow2 ``table_width_bucket``
     programs get an explicit expected-count budget from the runner).
     Steady-state cost per dispatch is two ``_cache_size()`` C++ calls and
     two ``perf_counter()`` reads — no locks, no tree flattening. A call
     that DID compile is named: program, the shapes it was called with and
     its seconds go into the watcher's ``recent`` ring (``/debug/compiles``),
     and once the worker's start-up ladder has ended
     (``CompileWatcher.start_up_ended``) into a WARNING line as well.
  2. **HBM ledger** (``HbmLedger``): structural byte accounting per
     category (KV pools, params, decode slot state, slot tables, LoRA
     stacks, processor state), sampled at scrape/snapshot time and
     cross-checked against ``device.memory_stats()`` where the backend
     provides it (TPU does; the CPU client returns None).
  3. **Flight recorder** (``FlightRecorder``): a preallocated,
     SINGLE-WRITER ring of typed engine events with monotonic timestamps.
     One ring per writer thread (the engine tick loop owns one, the
     device-thread runner owns another); ``/debug/flight`` merges them by
     timestamp. Append is O(1) into a preallocated slot — no locks, no
     allocation beyond the event tuple itself.
  4. **Profiler control** (``ProfilerControl``): ``POST /debug/profile``
     wraps ``jax.profiler.start_trace``/``stop_trace`` with graceful
     no-op degradation when the backend/profiler is unavailable.
  5. **Garbage-collector pauses** (``GcWatcher``): the process's one
     ``gc.callbacks`` hook, start to stop of each collection, by
     generation. A stalled frame interval reads its seconds from here.

Every Prometheus name comes from runtime/metric_names.py (``ALL_RUNTIME``)
— the lint test rejects inline literals. Metric values mirror the plain
host-side counters via ``on_render`` hooks, so the hot path never touches
a metrics lock; render-time sampling pays it instead.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from dynamo_tpu.runtime import metric_names as mn
from dynamo_tpu.runtime.metrics_core import MetricsRegistry
from dynamo_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Compile wall-times span ~10 ms (tiny scatter) to minutes (a served
# program of many layers) — latency DEFAULT_BUCKETS top out at 60 s and
# start at 1 ms.
COMPILE_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
    120.0, 300.0,
)

# Default per-program-object distinct-signature budget for sites without
# an explicit one: generous enough for legitimate multi-axis bucketing
# (the prefill program specializes on pow2 chunk × pow2 width × pow2 row
# buckets), small enough that a per-request shape leak — a fresh context
# length per call — still trips it within a few hundred requests.
DEFAULT_SIGNATURE_BUDGET = 256


class _ProgramStats:
    """Aggregated per-NAME compile stats. Several jit objects may share a
    name (the runner rebuilds its decode program per variant and per
    engine instance); totals aggregate, while the storm budget is judged
    per WatchedJit instance — a fresh engine recompiling its own programs
    is warmup, not a storm."""

    __slots__ = (
        "name", "compiles", "signatures", "storms", "compile_seconds",
        "last_compile_seconds", "budget", "_watcher",
    )

    def __init__(self, name: str, watcher: "CompileWatcher") -> None:
        self.name = name
        self.compiles = 0
        self.signatures = 0
        self.storms = 0
        self.compile_seconds = 0.0
        self.last_compile_seconds = 0.0
        self.budget: Optional[int] = None
        self._watcher = watcher

    def on_compile(self, n: int, dt: float, signature: str) -> None:
        self.compiles += n
        self.signatures += n
        self.compile_seconds += dt
        self.last_compile_seconds = dt
        # Histogram and ring take their time — fine: compiles are rare by
        # definition (a program that compiles on the hot path is the storm
        # we detect).
        self._watcher.on_compile(self.name, n, dt, signature)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "compiles": self.compiles,
            "signatures": self.signatures,
            "storms": self.storms,
            "compile_seconds": round(self.compile_seconds, 4),
            "last_compile_seconds": round(self.last_compile_seconds, 4),
            "budget": self.budget,
        }


class WatchedJit:
    """Wrapper around one compiled (``jax.jit``) callable that attributes
    cache growth to its program name.

    Detection uses the jit object's own ``_cache_size()`` (a C++
    attribute read) — a call during which the cache grew IS a compile, and
    its wall time is compile-dominated. No signature hashing on the hot
    path; a fallback signature set exists only for jit-like callables
    without ``_cache_size`` (older/newer jax, test doubles).

    Unknown attributes forward to the wrapped callable so call sites can
    keep using ``_cache_size`` / ``clear_cache`` / ``lower`` directly.
    """

    __slots__ = ("_fn", "_stats", "_sigs", "_budget", "_seen", "_fast")

    def __init__(
        self, stats: _ProgramStats, fn: Callable, budget: Optional[int] = None
    ) -> None:
        self._fn = fn
        self._stats = stats
        self._sigs = 0  # distinct signatures THIS program object compiled
        self._budget = budget
        self._fast = hasattr(fn, "_cache_size")
        self._seen: Optional[set] = None if self._fast else set()

    @property
    def signatures(self) -> int:
        return self._sigs

    def __call__(self, *args, **kwargs):
        fn = self._fn
        if self._fast:
            before = fn._cache_size()
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            grew = fn._cache_size() - before
            if grew > 0:
                self._on_compile(grew, time.perf_counter() - t0, args, kwargs)
            return out
        key = _abstract_signature(args, kwargs)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        if key not in self._seen:
            self._seen.add(key)
            self._on_compile(1, time.perf_counter() - t0, args, kwargs)
        return out

    def _on_compile(self, n: int, dt: float, args, kwargs) -> None:
        self._sigs += n
        st = self._stats
        st.on_compile(n, dt, describe_call(args, kwargs))
        budget = self._budget if self._budget is not None else st.budget
        if budget is None:
            budget = DEFAULT_SIGNATURE_BUDGET
        if self._sigs > budget:
            st.storms += 1
            logger.warning(
                "recompile storm: program %r has compiled %d distinct "
                "signatures (budget %d) — dispatched shapes are not "
                "bucketing; every new signature pays a full XLA compile "
                "on the serving path",
                st.name, self._sigs, budget,
            )

    def __getattr__(self, item: str):
        return getattr(object.__getattribute__(self, "_fn"), item)


def _abstract_signature(args, kwargs) -> Tuple:
    """Cheap (shape, dtype) signature for the no-``_cache_size`` fallback.
    Non-array leaves degrade to their type — good enough for telemetry."""
    import jax

    def leaf_key(x):
        shape = getattr(x, "shape", None)
        if shape is not None:
            return (tuple(shape), str(getattr(x, "dtype", "?")))
        return (type(x).__name__, x if isinstance(x, (int, bool, str)) else None)

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (str(treedef), tuple(leaf_key(l) for l in leaves))


def describe_call(args, kwargs) -> str:
    """The call a program compiled for, for a person to read, in at most
    600 characters: each argument as ``dtype[shape]``, a scalar as its
    value, a pytree of more than four arrays (parameters, pools) as its
    type and leaf count. Shape and dtype are metadata, valid on a donated
    buffer after the call."""
    import jax

    def one(x) -> str:
        shape = getattr(x, "shape", None)
        if shape is not None:
            return f"{getattr(x, 'dtype', '?')}[{','.join(map(str, shape))}]"
        if x is None or isinstance(x, (bool, int, float, str)):
            return repr(x)
        leaves = jax.tree_util.tree_leaves(x)
        if not leaves or (len(leaves) == 1 and leaves[0] is x):
            return type(x).__name__
        if len(leaves) > 4:
            return f"<{type(x).__name__}: {len(leaves)} arrays>"
        return "(" + ", ".join(one(leaf) for leaf in leaves) + ")"

    parts = [one(a) for a in args]
    parts += [f"{k}={one(v)}" for k, v in kwargs.items()]
    text = ", ".join(parts)
    return text if len(text) <= 600 else text[:597] + "..."


# How many compile events ``/debug/compiles`` keeps under ``recent``.
RECENT_COMPILES = 64


class CompileWatcher:
    """Per-process compile-telemetry registry (program name → stats).

    Metrics mirror the plain counters at render time (``on_render``), so
    dispatch-path increments are lock-free attribute bumps under the GIL.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        self.registry = registry or MetricsRegistry()
        self._lock = threading.Lock()  # program-creation only, never hot
        self._programs: Dict[str, _ProgramStats] = {}
        # Every compile named, newest last; ``compiles`` is the running
        # count behind ``totals()["compiles"]``, one attribute read for
        # whoever wants the compiles inside an interval.
        self.recent: "collections.deque[Dict[str, Any]]" = collections.deque(
            maxlen=RECENT_COMPILES
        )
        self.compiles = 0
        self._serving = False
        self._warming = threading.local()  # family_warm_up, per thread
        self._hist = self.registry.histogram(
            mn.RUNTIME_COMPILE_SECONDS,
            "Wall time of calls that compiled a new program signature "
            "(trace + XLA compile + first execute)",
            ["program"],
            buckets=COMPILE_BUCKETS,
        )
        self._compiles = self.registry.counter(
            mn.RUNTIME_COMPILES_TOTAL,
            "jit program compilations observed per watched program site",
            ["program"],
        )
        self._signatures = self.registry.gauge(
            mn.RUNTIME_COMPILE_SIGNATURES,
            "Distinct compiled signatures per watched program site",
            ["program"],
        )
        self._storms = self.registry.counter(
            mn.RUNTIME_RECOMPILE_STORMS_TOTAL,
            "Signature-budget violations (a program object compiling more "
            "distinct signatures than its shape-bucketing budget allows)",
            ["program"],
        )
        self.registry.on_render(self._refresh)

    def _refresh(self) -> None:
        for name, st in list(self._programs.items()):
            self._compiles.set_total(st.compiles, program=name)
            self._signatures.set(st.signatures, program=name)
            self._storms.set_total(st.storms, program=name)

    def program(self, name: str) -> _ProgramStats:
        st = self._programs.get(name)
        if st is None:
            with self._lock:
                st = self._programs.get(name)
                if st is None:
                    st = _ProgramStats(name, self)
                    self._programs[name] = st
        return st

    def start_up_ended(self) -> None:
        """The worker compiled what it means to before serving (the
        engine's ``compile_prefill_ladder`` ended): from here on a compile
        stalls live streams, and is logged as one."""
        self._serving = True

    @contextlib.contextmanager
    def family_warm_up(self):
        """What this thread compiles inside is a warm-up the engine chose
        to run (the sibling rows buckets of a recurring prefix-hit prefill
        program, one a tick: engines/tpu/admission.py), not a program a
        request met first: an INFO line, ``family`` in ``recent``."""
        self._warming.family = True
        try:
            yield
        finally:
            self._warming.family = False

    def on_compile(self, program: str, n: int, dt: float, signature: str) -> None:
        self._hist.observe(dt, program=program)
        self.compiles += n
        family = getattr(self._warming, "family", False)
        self.recent.append({
            "program": program,
            "signature": signature,
            "seconds": round(dt, 4),
            "t_mono": round(time.monotonic(), 6),
            "t_wall": round(time.time(), 3),
            "serving": self._serving and not family,
            "family": family,
        })
        if family:
            logger.info(
                "compiled for the family of %s %s in %.3f s",
                program, signature, dt,
            )
        elif self._serving:
            logger.warning(
                "compiled on the serving path: %s %s in %.3f s",
                program, signature, dt,
            )

    def set_budget(self, name: str, budget: Optional[int]) -> None:
        """Default per-instance signature budget for every WatchedJit that
        shares ``name`` and didn't set its own."""
        self.program(name).budget = budget

    def snapshot(self) -> Dict[str, Any]:
        # Materialize the shared dict in one C-level call before touching
        # Python code: writer threads may insert new programs mid-scrape.
        programs = {
            name: st.to_dict()
            for name, st in sorted(list(self._programs.items()))
        }
        return {
            "programs": programs,
            "totals": self.totals(),
            "recent": list(self.recent),
        }

    def totals(self) -> Dict[str, Any]:
        stats = list(self._programs.values())
        return {
            "programs": len(stats),
            "compiles": sum(s.compiles for s in stats),
            "signatures": sum(s.signatures for s in stats),
            "storms": sum(s.storms for s in stats),
            "compile_seconds": round(sum(s.compile_seconds for s in stats), 4),
        }


def watched_jit(
    name: str,
    fn: Callable,
    *,
    budget: Optional[int] = None,
    watcher: Optional[CompileWatcher] = None,
) -> WatchedJit:
    """Wrap an already-jitted callable with compile telemetry under
    ``name``. ``budget``: per-instance distinct-signature budget (None =
    the watcher's per-name default, which itself defaults to unbudgeted)."""
    w = watcher if watcher is not None else global_compile_watcher()
    return WatchedJit(w.program(name), fn, budget)


# ---------------------------------------------------------------------------
# Garbage-collector pauses
# ---------------------------------------------------------------------------


class GcWatcher:
    """Start to stop of each collection of the garbage collector, by
    generation. A collection stops every thread of the process (it runs
    under the GIL), so its seconds are a pause of the scheduler loop and
    of the device thread alike. The families mirror the plain counters at
    render. Counts once ``on_gc`` is among ``gc.callbacks``."""

    GENERATIONS = (0, 1, 2)

    def __init__(self) -> None:
        self.pause_seconds = [0.0 for _ in self.GENERATIONS]
        self.collections = [0 for _ in self.GENERATIONS]
        self._t0 = 0.0
        self.registry = MetricsRegistry()
        self._pause_metric = self.registry.counter(
            mn.RUNTIME_GC_PAUSE_SECONDS_TOTAL,
            "Seconds inside collections of the garbage collector "
            "(every thread of the process stands still for them)",
            ["generation"],
        )
        self._collections_metric = self.registry.counter(
            mn.RUNTIME_GC_COLLECTIONS_TOTAL,
            "Collections of the garbage collector", ["generation"],
        )
        self.registry.on_render(self._refresh)

    @property
    def seconds(self) -> float:
        return sum(self.pause_seconds)

    def on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        generation = info["generation"]
        self.pause_seconds[generation] += time.perf_counter() - self._t0
        self.collections[generation] += 1

    def _refresh(self) -> None:
        for generation in self.GENERATIONS:
            self._pause_metric.set_total(
                self.pause_seconds[generation], generation=generation
            )
            self._collections_metric.set_total(
                self.collections[generation], generation=generation
            )


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


def tree_device_bytes(tree: Any) -> int:
    """Sum ``.nbytes`` over every array-like leaf of a pytree. Works on
    jax arrays (including donated-and-replaced references — nbytes is
    shape metadata, valid even on deleted buffers), numpy mirrors, and
    int8 pool dicts; None and scalar leaves contribute 0."""
    if tree is None:
        return 0
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            try:
                total += int(nb)
            except (TypeError, ValueError):
                pass  # exotic nbytes (property raising, non-numeric)
    return total


def device_memory_stats() -> List[Dict[str, Any]]:
    """Per-device ``memory_stats()`` where the backend provides it (TPU
    reports bytes_in_use / bytes_limit; the CPU client returns None)."""
    out: List[Dict[str, Any]] = []
    try:
        import jax

        devices = jax.devices()
    except Exception as exc:  # backend init failure: degrade, don't 500
        return [{"error": f"{type(exc).__name__}: {exc}"}]
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        out.append(
            {
                "id": getattr(d, "id", None),
                "platform": getattr(d, "platform", None),
                "device_kind": getattr(d, "device_kind", None),
                "memory_stats": stats,
            }
        )
    return out


class HbmLedger:
    """Structural device-memory accounting: category → byte-count sampler.

    Samplers run at snapshot/scrape time only (never on the tick thread)
    and read live object references — a category whose sampler throws
    reports -1 (visible as "unknown" rather than silently zero). The
    ledger also tracks the peak total it has ever observed."""

    def __init__(self) -> None:
        self._sources: Dict[str, Callable[[], int]] = {}
        self.peak_bytes = 0
        self.registry = MetricsRegistry()
        self._gauge = self.registry.gauge(
            mn.RUNTIME_HBM_BYTES,
            "Structural device-memory bytes per ledger category "
            "(sampled from live engine state at scrape time)",
            ["category"],
        )
        self._device_gauge = self.registry.gauge(
            mn.RUNTIME_HBM_DEVICE_BYTES,
            "Backend-reported device memory (device.memory_stats(), "
            "absent on backends that do not provide it)",
            ["device", "kind"],
        )
        self.registry.on_render(self._refresh)

    def register(self, category: str, fn: Callable[[], int]) -> None:
        self._sources[category] = fn

    def snapshot(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        # list() first: samplers run Python code (thread-switch points),
        # and a concurrent register() must not break the iteration.
        for category, fn in list(self._sources.items()):
            try:
                out[category] = int(fn())
            except Exception:
                out[category] = -1
        total = sum(v for v in out.values() if v > 0)
        if total > self.peak_bytes:
            self.peak_bytes = total
        return out

    def total_bytes(self) -> int:
        return sum(v for v in self.snapshot().values() if v > 0)

    def _refresh(self) -> None:
        for category, nbytes in self.snapshot().items():
            self._gauge.set(nbytes, category=category)
        for dev in device_memory_stats():
            stats = dev.get("memory_stats")
            if not stats:
                continue
            for kind in ("bytes_in_use", "bytes_limit", "peak_bytes_in_use"):
                if kind in stats:
                    self._device_gauge.set(
                        stats[kind], device=str(dev.get("id")), kind=kind
                    )


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Preallocated single-writer ring of typed engine events.

    Contract: ``record`` is called from EXACTLY ONE thread per recorder
    (the engine tick loop owns one ring, the device-thread runner owns
    another); readers (``snapshot``, the metrics refresh) may run on any
    thread and tolerate a concurrently advancing write index — a torn
    read can at worst miss or double-see the newest event, never corrupt
    the ring. Append is an index store + tuple build: O(1), no locks, no
    list growth."""

    def __init__(self, name: str, capacity: int = 2048) -> None:
        self.name = name
        self.capacity = int(capacity)
        self._ring: List[Optional[Tuple[float, str, Optional[dict]]]] = (
            [None] * self.capacity
        )
        self._n = 0  # total events ever recorded (monotonic)
        self.counts: Dict[str, int] = {}
        self.registry = MetricsRegistry()
        self._events = self.registry.counter(
            mn.RUNTIME_FLIGHT_EVENTS_TOTAL,
            "Flight-recorder events per ring and kind",
            ["ring", "kind"],
        )
        self._overwritten = self.registry.counter(
            mn.RUNTIME_FLIGHT_OVERWRITTEN_TOTAL,
            "Flight-recorder events overwritten by ring wrap (history "
            "older than the ring capacity is gone)",
            ["ring"],
        )
        self.registry.on_render(self._refresh)

    def record(self, kind: str, **fields: Any) -> None:
        i = self._n
        self._ring[i % self.capacity] = (
            time.monotonic(), kind, fields or None
        )
        self._n = i + 1
        self.counts[kind] = self.counts.get(kind, 0) + 1

    @property
    def total(self) -> int:
        return self._n

    @property
    def overwritten(self) -> int:
        return max(0, self._n - self.capacity)

    def snapshot(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Events oldest→newest as dicts (``seq`` is the global event
        index, ``t_mono`` the monotonic timestamp)."""
        n = self._n
        start = max(0, n - self.capacity)
        if limit is not None:
            start = max(start, n - int(limit))
        out: List[Dict[str, Any]] = []
        for i in range(start, n):
            ev = self._ring[i % self.capacity]
            if ev is None:
                continue
            ts, kind, fields = ev
            d: Dict[str, Any] = {
                "seq": i, "t_mono": round(ts, 6), "ring": self.name,
                "kind": kind,
            }
            if fields:
                d.update(fields)
            out.append(d)
        return out

    def _refresh(self) -> None:
        for kind, count in list(self.counts.items()):
            self._events.set_total(count, ring=self.name, kind=kind)
        self._overwritten.set_total(self.overwritten, ring=self.name)


def dump_flight(
    recorders: Dict[str, "FlightRecorder"],
    *,
    dump_dir: Optional[str] = None,
    reason: str = "abort",
) -> Optional[str]:
    """Write every ring's events (merged, timestamp-ordered) to a JSON
    file; returns the path or None on failure. Used by the engine's
    ``_abort_inflight`` so a wedged/failed tick leaves a post-mortem even
    if nobody is scraping ``/debug/flight``."""
    try:
        if not dump_dir:
            from dynamo_tpu import config as _cfg

            dump_dir = _cfg.FLIGHT_DUMP_DIR.get() or None
        if not dump_dir:
            import tempfile

            dump_dir = tempfile.gettempdir()
        os.makedirs(dump_dir, exist_ok=True)
        events: List[Dict[str, Any]] = []
        for rec in recorders.values():
            events.extend(rec.snapshot())
        events.sort(key=lambda e: e["t_mono"])
        path = os.path.join(
            dump_dir,
            f"dynamo_tpu_flight_{os.getpid()}_{int(time.time() * 1000)}.json",
        )
        with open(path, "w") as f:
            json.dump(
                {
                    "reason": reason,
                    "rings": sorted(recorders),
                    "events": events,
                },
                f,
            )
        return path
    except Exception:
        logger.exception("flight-recorder dump failed")
        return None


# ---------------------------------------------------------------------------
# On-demand profiler capture
# ---------------------------------------------------------------------------


class ProfilerControl:
    """Start/stop ``jax.profiler`` traces on demand (POST /debug/profile).

    Degrades to a structured no-op when the profiler is unavailable
    (missing backend support, already-active capture from another tool):
    every path returns a JSON-able dict, never raises. ``start`` and
    ``stop`` block (``stop`` exports the trace: seconds), so a caller on
    an event loop runs them in a thread, as the route does."""

    def __init__(self) -> None:
        self._lock = threading.Lock()  # admin path only
        self._active_dir: Optional[str] = None
        self._t_start = 0.0
        self.captures = 0
        # Monotonic capture generation: bumped on every successful start,
        # so a bounded capture's auto-stop timer can tell "my capture is
        # still the active one" apart from "a NEWER capture reuses my
        # dir" (dir equality cannot).
        self.generation = 0
        self.registry = MetricsRegistry()
        self._captures_metric = self.registry.counter(
            mn.RUNTIME_PROFILER_CAPTURES_TOTAL,
            "Completed on-demand jax.profiler captures",
        )
        self.registry.on_render(
            lambda: self._captures_metric.set_total(self.captures)
        )

    def status(self) -> Dict[str, Any]:
        return {
            "active": self._active_dir is not None,
            "dir": self._active_dir,
            "captures": self.captures,
            "generation": self.generation,
        }

    def start(self, log_dir: Optional[str] = None) -> Dict[str, Any]:
        with self._lock:
            if self._active_dir is not None:
                return {
                    "ok": False,
                    "error": "capture already active",
                    "dir": self._active_dir,
                }
            if not log_dir:
                import tempfile

                # Hyphenated prefix: the metric-name lint greps for
                # dynamo_tpu_* snake literals.
                log_dir = tempfile.mkdtemp(prefix="dynamo-tpu-profile-")
            try:
                import jax.profiler

                # TraceMe events only (host_tracer_level 2): the engine's
                # tick.* / device.* annotations, on the device trace's
                # clock. The Python tracer (on by default) made a 4 s
                # capture a 215 MB file that took 51 s to export.
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                jax.profiler.start_trace(log_dir, profiler_options=options)
            except Exception as exc:
                logger.warning("profiler start degraded to no-op: %s", exc)
                return {
                    "ok": False,
                    "degraded": True,
                    "error": f"{type(exc).__name__}: {exc}",
                }
            self._active_dir = log_dir
            self._t_start = time.monotonic()
            self.generation += 1
            return {"ok": True, "dir": log_dir, "generation": self.generation}

    def stop(self) -> Dict[str, Any]:
        with self._lock:
            if self._active_dir is None:
                return {"ok": False, "error": "no active capture"}
            log_dir = self._active_dir
            duration = time.monotonic() - self._t_start
            try:
                import jax.profiler

                jax.profiler.stop_trace()
            except Exception as exc:
                # A transient stop failure (export write error) may leave
                # jax's trace session live — keep the capture marked
                # active so the operator can RETRY the stop, unless the
                # error says the session already ended (then clearing is
                # the only way to un-wedge start()).
                msg = str(exc).lower()
                ended = (
                    "no trace" in msg or "not started" in msg
                    or "no active" in msg
                )
                if ended:
                    self._active_dir = None
                logger.warning("profiler stop degraded to no-op: %s", exc)
                return {
                    "ok": False,
                    "degraded": True,
                    "dir": log_dir,
                    "still_active": not ended,
                    "error": f"{type(exc).__name__}: {exc}",
                }
            self._active_dir = None
            self.captures += 1
            return {
                "ok": True, "dir": log_dir, "duration_s": round(duration, 3)
            }


# ---------------------------------------------------------------------------
# Process globals (mirrors lifecycle.global_lifecycle / tracing.global_tracer)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_WATCHER: Optional[CompileWatcher] = None
_PROFILER: Optional[ProfilerControl] = None
_GC: Optional[GcWatcher] = None


def _init_globals() -> None:
    global _WATCHER, _PROFILER, _GC
    with _LOCK:
        if _WATCHER is not None:
            return
        _PROFILER = ProfilerControl()
        _GC = GcWatcher()
        gc.callbacks.append(_GC.on_gc)
        _WATCHER = CompileWatcher()


def global_compile_watcher() -> CompileWatcher:
    """Process-global compile telemetry (jit sites are module-level and
    per-runner; one watcher sees them all)."""
    if _WATCHER is None:
        _init_globals()
    return _WATCHER  # type: ignore[return-value]


def global_profiler() -> ProfilerControl:
    if _PROFILER is None:
        _init_globals()
    return _PROFILER  # type: ignore[return-value]


def global_gc_watcher() -> GcWatcher:
    """The process's one ``gc.callbacks`` hook, installed at first use."""
    if _GC is None:
        _init_globals()
    return _GC  # type: ignore[return-value]


def render_runtime_metrics(openmetrics: bool = False) -> str:
    """Prometheus text for the process-global runtime families (compile
    watcher + profiler + garbage collector). Registered on every
    SystemStatusServer — the device plane is per-process, like the
    lifecycle/tracer debug rings."""
    parts = [
        global_compile_watcher().registry.render(openmetrics=openmetrics),
        global_profiler().registry.render(openmetrics=openmetrics),
        global_gc_watcher().registry.render(openmetrics=openmetrics),
    ]
    return "\n".join(p for p in parts if p)
