"""Metric-name lint: every emitted Prometheus name comes from
runtime/metric_names.py (ref: metrics/prometheus_names.rs rationale —
dashboards, the planner's scrape source, and emitters must never drift).

Two halves over ONE name registry (runtime/metric_names.py):
  * runtime half (here): any ``dynamo_tpu_*`` string literal outside
    metric_names.py fails, and the live device-observe emitters must
    cover exactly ALL_RUNTIME;
  * static half (dynamo_tpu/analysis rule DYN004, asserted clean below):
    constructor sites resolve into ALL_* families and every family entry
    has an emitter — see tests/test_dynlint.py for the rule's own
    fixtures.
"""

import os
import re

PKG = os.path.join(os.path.dirname(__file__), "..", "dynamo_tpu")

# String literals that LOOK like metric names ('dynamo_tpu_' + snake tail).
LITERAL_RE = re.compile(r"""["']dynamo_tpu_[a-z0-9_]*["']""")

# The single place allowed to define dynamo_tpu_* literals.
DEFINING_FILE = os.path.join("runtime", "metric_names.py")

# Non-metric literals that legitimately share the prefix.
ALLOWED_LITERALS = {
    '"dynamo_tpu_context"',  # runtime/context.py ContextVar name
    '"dynamo_tpu_"',  # analysis/config.py: DYN004's name-prefix config
}


def _py_files():
    for root, _dirs, files in os.walk(PKG):
        for fname in files:
            if fname.endswith(".py"):
                yield os.path.join(root, fname)


def test_no_metric_name_literals_outside_metric_names():
    violations = []
    for path in _py_files():
        rel = os.path.relpath(path, PKG)
        if rel == DEFINING_FILE:
            continue
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                for m in LITERAL_RE.findall(line):
                    if m.replace("'", '"') in ALLOWED_LITERALS:
                        continue
                    violations.append(f"{rel}:{lineno}: {m}")
    assert not violations, (
        "string-literal metric names outside runtime/metric_names.py "
        "(import the constant instead):\n" + "\n".join(violations)
    )


def test_all_family_tuples_are_canonical_and_exported():
    """The ALL_* tuples exist, are importable from dynamo_tpu.runtime, and
    contain only names defined in metric_names.py."""
    from dynamo_tpu import runtime as rt
    from dynamo_tpu.runtime import metric_names as mn

    defined = {
        v for v in vars(mn).values()
        if isinstance(v, str) and v.startswith("dynamo_tpu_")
    }
    families = ("ALL_FRONTEND", "ALL_ROUTER", "ALL_KVBM", "ALL_KVCACHE",
                "ALL_DISAGG", "ALL_ENGINE", "ALL_RUNTIME", "ALL_MIGRATION",
                "ALL_FAULTS", "ALL_OVERLOAD", "ALL_DRAIN", "ALL_LIVENESS",
                "ALL_PLANNER", "ALL_SLO", "ALL_PARSER")
    for family in families:
        tup = getattr(rt, family)
        assert tup and isinstance(tup, tuple)
        for name in tup:
            assert name in defined, f"{family} contains undefined {name}"
    # families don't collide
    all_names = [n for f in families for n in getattr(rt, f)]
    assert len(all_names) == len(set(all_names))


def test_runtime_family_covers_device_observe_emitters():
    """Every metric runtime/device_observe.py registers must be pinned in
    ALL_RUNTIME (the device-plane tentpole's lint anchor)."""
    from dynamo_tpu.runtime import metric_names as mn
    from dynamo_tpu.runtime.device_observe import (
        CompileWatcher,
        FlightRecorder,
        GcWatcher,
        HbmLedger,
        ProfilerControl,
    )

    emitted = set()
    for obj in (
        CompileWatcher(), HbmLedger(), FlightRecorder("lint"),
        ProfilerControl(), GcWatcher(),
    ):
        emitted.update(m.name for m in obj.registry._metrics)
    assert emitted == set(mn.ALL_RUNTIME)


def test_static_metric_closure_is_clean():
    """The static half (dynlint DYN004) over the same registry: every
    constructor site's name is pinned in an ALL_* family and every family
    entry has an emitter. Rule fixtures live in tests/test_dynlint.py;
    this asserts the PACKAGE satisfies the closure."""
    from dynamo_tpu.analysis import run_lint

    findings = run_lint(os.path.abspath(PKG), rule_ids=["DYN004"])
    assert findings == [], "\n".join(f.render() for f in findings)


# -- the tick-phase vocabulary (EngineStepMetrics.phase) ----------------------

_PHASE_CALL_RE = re.compile(r"""["'](tick\.[a-z_]+|device\.[a-z_]+)["']""")


def _phase_literals():
    """Every ``tick.*`` / ``device.*`` string literal under engines/."""
    found = {}
    for path in _py_files():
        rel = os.path.relpath(path, PKG)
        if not rel.startswith("engines" + os.sep):
            continue
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                for name in _PHASE_CALL_RE.findall(line):
                    found.setdefault(name, []).append(f"{rel}:{lineno}")
    return found


def test_tick_phases_are_classed_exactly_once():
    """Every phase name is host, device-wait or idle, and only one of them;
    the three classes make up TICK_PHASES."""
    from dynamo_tpu.runtime import metric_names as mn

    classes = (mn.TICK_PHASES_HOST, mn.TICK_PHASES_DEVICE_WAIT, mn.TICK_PHASES_IDLE)
    names = [n for cls in classes for n in cls]
    assert len(names) == len(set(names)), "a phase is in two classes"
    assert set(names) == set(mn.TICK_PHASES) and len(mn.TICK_PHASES) == len(names)
    assert all(n.startswith("tick.") for n in names)
    assert {mn.ENGINE_TICK_PHASE, mn.ENGINE_TICK, mn.ENGINE_REQUEST_PHASE,
            mn.ENGINE_REQUEST_DECODE_TOKENS_TOTAL} <= set(mn.ALL_ENGINE)


def test_every_phase_the_engine_opens_is_in_the_vocabulary_and_used():
    from dynamo_tpu.runtime import metric_names as mn

    found = _phase_literals()
    known = set(mn.TICK_PHASES) | set(mn.DEVICE_SPANS)
    unknown = {n: where for n, where in found.items() if n not in known}
    assert not unknown, f"phase names outside metric_names.py: {unknown}"
    unused = known - set(found)
    assert not unused, f"names in the vocabulary that nothing opens: {unused}"
