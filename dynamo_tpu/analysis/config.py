"""dynlint configuration: the repo's invariants as data.

``repo_config()`` is THE statement of what PRs 1-4 promised; fixtures and
tests build narrower configs pointing at their own trees. Paths are posix,
relative to the linted root (for the repo config: the ``dynamo_tpu``
package directory)."""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Optional, Tuple


@dataclass(frozen=True)
class JitDisciplineConfig:
    """DYN001. ``builder_name_re``: enclosing functions allowed to
    construct jits (cached program builders); anything else needs either
    module level, a memo-guard (``if key not in cache`` / ``is None``
    ancestor test), or a reasoned suppression."""

    watch_wrapper: str = "watched_jit"
    builder_name_re: str = r"^(__init__|_?build_\w*|_?make_\w*)$"

    def is_builder(self, name: str) -> bool:
        return re.match(self.builder_name_re, name) is not None


@dataclass(frozen=True)
class HotPathConfig:
    """DYN002. ``roots``: (module rel path, qualname) the decode hot loop
    enters through. ``scope``: modules whose functions participate in the
    name-based call graph — the decode plane, deliberately excluding
    runtime/metrics_core.py (its histogram lock is a PR 3/4 decision: one
    uncontended lock per observe, render pays the rest). ``boundaries``:
    sanctioned host-transfer funnels where traversal and bans stop
    (the pipelined readback helper IS the one allowed sync point).
    ``device_roots``: names that hold device arrays — np.asarray/float/int
    over an expression touching one of these is a blocking device sync."""

    roots: FrozenSet[Tuple[str, str]] = frozenset(
        {
            ("engines/tpu/engine.py", "JaxEngine._decode_tick"),
            ("engines/tpu/runner.py", "DeviceRunner.sync_slots"),
            ("engines/tpu/runner.py", "DeviceRunner.sync_tables"),
            ("engines/tpu/runner.py", "DeviceRunner.decode_dispatch"),
            ("engines/tpu/runner.py", "DeviceRunner.decode_read"),
        }
    )
    scope: FrozenSet[str] = frozenset(
        {
            "engines/tpu/engine.py",
            "engines/tpu/runner.py",
            "engines/metrics.py",
            "runtime/device_observe.py",
            # The fault plane's tick seams (fault_point at dispatch/reap)
            # are IN the hot loop — the disabled-plane path must stay a
            # bare flag check, and this scope entry makes the linter walk
            # through faults.py to prove it.
            "runtime/faults.py",
            # Tick budgeter (PR 18): observe_decode runs at every reap —
            # this scope entry makes the linter prove it stays deque-and-
            # arithmetic only. The control law itself is fenced behind the
            # TickBudgeter.evaluate boundary below.
            "engines/tpu/tick_budget.py",
        }
    )
    boundaries: FrozenSet[Tuple[str, str]] = frozenset(
        {
            # The one sanctioned blocking readback: overlapped D2H copies
            # at reap.
            ("engines/tpu/runner.py", "DeviceRunner._get_all"),
            # Program-CREATION helper: runs once per (program, variant)
            # under a double-checked creation lock, never on a steady
            # dispatch (WatchedJit.__call__ is lock-free).
            ("runtime/device_observe.py", "watched_jit"),
            # AIMD control law: time-gated to eval_interval_s (admission
            # side of the tick, never per-reap); may log and emit flight
            # events, so traversal stops here rather than whitelisting
            # those in the decode plane.
            ("engines/tpu/tick_budget.py", "TickBudgeter.evaluate"),
        }
    )
    device_roots: FrozenSet[str] = frozenset(
        {
            "slot_state",
            "slot_tables",
            "k_cache",
            "v_cache",
            "carry_tok",
            "carry_pos",
            "handles",
            "proc_state",
        }
    )
    # Lock attributes the hot path may take (none today; metrics_core is
    # out of scope rather than whitelisted so the list stays honest).
    allowed_locks: FrozenSet[str] = frozenset()


@dataclass(frozen=True)
class SilentSwallowConfig:
    """DYN003. Exception names considered 'broad': catching one of these
    (alone or in a tuple) with a do-nothing body is a silent swallow."""

    broad_names: FrozenSet[str] = frozenset({"Exception", "BaseException"})


@dataclass(frozen=True)
class MetricClosureConfig:
    """DYN004. ``metric_names_rel``: the single module allowed to define
    metric names (loaded by file path — no package import, the linter
    stays jax-free). ``constructor_methods`` / ``constructor_classes``:
    call shapes that register a metric family. ``dynamic_emitters``:
    helper functions whose non-literal call covers every name the helper
    itself defined in the names module (the system server renders the
    engine stats dict through ``engine_gauge(key)`` instead of
    constructing gauge objects)."""

    prefix: str = "dynamo_tpu_"
    metric_names_rel: str = "runtime/metric_names.py"
    constructor_methods: FrozenSet[str] = frozenset(
        {"counter", "gauge", "histogram"}
    )
    constructor_classes: FrozenSet[str] = frozenset(
        {"Counter", "Gauge", "Histogram"}
    )
    dynamic_emitters: FrozenSet[str] = frozenset({"engine_gauge"})


@dataclass(frozen=True)
class RingWriterConfig:
    """DYN005. ``owners``: ring name -> (module rel path, owning class).
    Appends (``<recv>.flight.record(...)``) must resolve to ``self.flight``
    inside the owning class; anything else is a cross-thread write the
    single-writer ring contract cannot survive."""

    ring_attrs: FrozenSet[str] = frozenset({"flight", "kv_flight"})
    recorder_class: str = "FlightRecorder"
    owners: Dict[str, Tuple[str, str]] = field(
        default_factory=lambda: {
            "engine": ("engines/tpu/engine.py", "JaxEngine"),
            "runner": ("engines/tpu/runner.py", "DeviceRunner"),
            # Faultline rings (PR 7): pull retry/breaker history, stream
            # migrations, canary transitions — each single-writer on its
            # owner's event loop.
            "disagg": ("disagg/handlers.py", "DecodeHandler"),
            "migration": ("llm/migration.py", "Migration"),
            "health": ("runtime/health.py", "CanaryHealthChecker"),
            # Overload plane (PR 8): admission sheds + brownout state
            # transitions; single writer: the frontend's event loop.
            "overload": ("runtime/overload.py", "OverloadController"),
            # Drain plane (PR 9): handoff/fallback/requeue history; single
            # writer: the draining worker's event loop.
            "drain": ("runtime/drain.py", "DrainController"),
            # KVBM integrity events (tier corruption); single writer: the
            # manager's event loop (onboard + offload spill paths).
            "kvbm": ("kvbm/manager.py", "TieredKvManager"),
            # KV-reuse plane (PR 16): offload bursts, onboards, tier
            # evictions, sketch replacements; single writer: the manager's
            # event loop (same loop as the kvbm ring).
            "kvcache": ("kvbm/manager.py", "TieredKvManager"),
            # Crash plane (PR 10): worker suspect/dead/rejoin transitions
            # + stale-incarnation drops; single writer: the consuming
            # frontend's event loop (worker_monitor pump + evaluate task).
            "liveness": ("runtime/liveness.py", "LivenessTracker"),
            # Elasticity plane (PR 12): plan-state transitions, holds,
            # scale actuations, drains; single writer: the planner's
            # event loop.
            "planner": ("planner/elastic.py", "ElasticController"),
            # Trajectory plane (PR 13): span/event ingest + slow-capture
            # history; single writer: the frontend's event loop
            # (collector pump + local tracer listener).
            "trajectory": ("runtime/trajectory.py", "TrajectoryStore"),
            # Parser plane (PR 15): tool-call jail commits, completed
            # calls, degradation-ladder activations, parser exceptions;
            # single writer: the frontend's event loop (every jail lives
            # inside an SSE handler there).
            "parser": ("parsers/observe.py", "ParserPlane"),
        }
    )


@dataclass(frozen=True)
class AsyncLifecycleConfig:
    """DYN007. The three async-plane bug classes the last ten PRs kept
    re-fixing, as config:

    ``get_event_loop`` is banned outright — outside a running loop it
    binds (or on 3.12+ raises about) a dead loop that never runs the
    task; ``asyncio.get_running_loop()`` fails loudly at the call site
    instead (the PR 12 Planner lesson, now machine-checked).

    ``create_task`` results must be retained: a bare expression-statement
    discards the only strong reference, so the task is garbage-collected
    mid-flight and its failure is silently dropped. Store it, await it,
    gather it, or route it through ``runtime/tasks.py::reap_task``.

    ``blocking_calls`` / ``blocking_prefixes``: synchronous calls that
    stall the event loop when they appear lexically inside an ``async
    def`` body (nearest enclosing function is async — a nested sync def
    or a lambda handed to ``run_in_executor`` is its own boundary and
    exempt). ``blocking_allowlist`` holds the blessed boundaries as
    (module rel path, enclosing async qualname): every entry is a
    reviewed decision that the call is small, local, and cheaper than an
    executor hop."""

    blocking_calls: FrozenSet[str] = frozenset(
        {
            "time.sleep",
            "subprocess.run",
            "subprocess.call",
            "subprocess.check_call",
            "subprocess.check_output",
            "subprocess.Popen",
            "socket.create_connection",
            "open",
            "io.open",
        }
    )
    blocking_prefixes: Tuple[str, ...] = ("requests.", "urllib.request.")
    blocking_allowlist: FrozenSet[Tuple[str, str]] = frozenset(
        {
            # File-backend discovery: a local-fs dev/test backend by
            # design (discovery/file.py docstring); writes are one small
            # JSON document, atomic-rename, on a control-plane cadence.
            ("runtime/discovery/file.py", "FileDiscovery.put"),
            ("runtime/discovery/file.py", "FileDiscovery.create_lease"),
            ("runtime/discovery/file.py", "FileDiscovery.keep_alive"),
            ("runtime/discovery/file.py", "FileDiscovery.revoke_lease"),
            # Event-plane replay serving: seeks a local append-only log at
            # an indexed offset on the (rare) late-subscriber resync path,
            # never on the publish hot path.
            ("runtime/events/zmq_plane.py", "EventBroker._serve_replay"),
            # Checkpoint manifest commit: a <1 KB JSON + atomic rename;
            # the heavy block data rides gather_and_write under the
            # engine's device executor, not this open().
            ("engines/tpu/kv_checkpoint.py", "save_checkpoint"),
            # Stream recorder: small JSONL lines appended under the
            # recorder lock; documented at the call site as
            # interleaving-safe and failure-disabling.
            ("llm/recorder.py", "StreamRecorder._write"),
            # CLI batch driver: single-user tool, file I/O IS the job.
            ("cli/run.py", "run_batch"),
        }
    )


@dataclass(frozen=True)
class KnobClosureConfig:
    """DYN008. The DYN004/DYN006 mirror for configuration: every
    ``DYN_TPU_*`` environment read resolves through the knob registry
    (``config.py`` ``ALL_KNOBS``: name, default, parser), every declared
    knob has at least one reader, and a literal env-name string at a call
    site is a finding — a renamed or dead knob can never silently diverge
    from the docs. The knobs module is loaded BY FILE PATH (no package
    import — it is dependency-free by design and the linter must run
    without jax installed)."""

    knobs_rel: str = "config.py"
    prefix: str = "DYN_TPU_"
    # Call shapes that read the environment: <...>.get / getenv calls and
    # environ[...] subscripts are matched against these terminal names.
    env_callables: FrozenSet[str] = frozenset({"getenv"})
    environ_names: FrozenSet[str] = frozenset({"environ"})


@dataclass(frozen=True)
class ImportLayeringConfig:
    """DYN009. The declared layer DAG, bottom-up: a module may import
    (at module level) only from its own or a LOWER layer. ``layers`` maps
    layer name -> path prefixes (a trailing '/' matches a directory; an
    exact file name matches a root module); every module must map to
    exactly one layer. ``lazy_obligations`` are known import-cycle
    seams that must stay function-local imports — the PR 7 faults.py /
    metrics_core rule, previously enforced only by a comment. Imports
    under ``if TYPE_CHECKING:`` are annotations-only and exempt."""

    package: str = "dynamo_tpu"
    layers: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
        ("foundation", ("utils/", "config.py", "_version.py", "__init__.py")),
        ("runtime", ("runtime/",)),
        (
            "planes",
            (
                "disagg/",
                "discd/",
                "engines/",
                "frontend/",
                "gateway/",
                "global_router/",
                "grpc/",
                "http/",
                "kvbm/",
                "llm/",
                "lora/",
                "mocker/",
                "models/",
                "multimodal/",
                "native/",
                "ops/",
                "parallel/",
                "parsers/",
                "planner/",
                "profiler/",
                "router/",
                "tokens/",
                "worker/",
            ),
        ),
        ("surface", ("analysis/", "cli/", "deploy/")),
    )
    lazy_obligations: Tuple[Tuple[str, str, str], ...] = (
        (
            "runtime/faults.py",
            "runtime/metrics_core.py",
            "distributed.py imports faults for fault_point and "
            "metrics_core imports utils.logging — a module-level import "
            "here closes the cycle when utils.logging is the first entry "
            "into the runtime package (PR 7); FaultPlane.__init__ imports "
            "it lazily",
        ),
        (
            "utils/logging.py",
            "runtime/context.py",
            "the formatter needs current_context() per record, but "
            "utils.logging is the first import of half the tree — a "
            "module-level import would drag the runtime package into "
            "every foundation import (and the DAG bans the direction)",
        ),
    )


@dataclass(frozen=True)
class FaultPointConfig:
    """DYN006. ``fault_names_rel``: the single module allowed to declare
    fault-point names (loaded by file path — no package import, the
    linter stays jax-free). ``call_names``: the functions whose first
    argument is a point name (``fault_point`` and any alias)."""

    fault_names_rel: str = "runtime/fault_names.py"
    call_names: FrozenSet[str] = frozenset({"fault_point", "fault_payload"})


@dataclass(frozen=True)
class LintConfig:
    jit: JitDisciplineConfig = field(default_factory=JitDisciplineConfig)
    hot_path: Optional[HotPathConfig] = field(default_factory=HotPathConfig)
    swallow: SilentSwallowConfig = field(default_factory=SilentSwallowConfig)
    metrics: Optional[MetricClosureConfig] = field(
        default_factory=MetricClosureConfig
    )
    rings: Optional[RingWriterConfig] = field(default_factory=RingWriterConfig)
    faults: Optional[FaultPointConfig] = field(
        default_factory=FaultPointConfig
    )
    async_lifecycle: Optional[AsyncLifecycleConfig] = field(
        default_factory=AsyncLifecycleConfig
    )
    knobs: Optional[KnobClosureConfig] = field(
        default_factory=KnobClosureConfig
    )
    layering: Optional[ImportLayeringConfig] = field(
        default_factory=ImportLayeringConfig
    )


def repo_config() -> LintConfig:
    """The dynamo_tpu package's invariants (defaults above ARE the repo
    config; fixtures construct their own)."""
    return LintConfig()


def portable_config() -> LintConfig:
    """Rules meaningful on ANY tree: DYN001 (jit discipline), DYN003
    (silent swallow), and DYN007 (async lifecycle — asyncio semantics are
    universal; the repo's blessed-boundary paths simply won't match a
    foreign tree). The repo-specific passes — hot-path roots, the
    metric-name registry, ring ownership, the fault-point registry, the
    knob registry, the layer DAG — are tied to dynamo_tpu's layout and
    would only emit config-mismatch noise on a foreign ``--root``; they
    are disabled here."""
    return LintConfig(
        hot_path=None,
        metrics=None,
        rings=None,
        faults=None,
        knobs=None,
        layering=None,
    )
