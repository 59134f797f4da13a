"""faultline: a process-global, seeded, deterministic fault-injection plane.

The fault-tolerance machinery (request migration, canary health, disagg
retry/breaker) is only trustworthy if the failures it absorbs can be
*produced on demand* — FlowKV's observation (PAPERS.md) is that transfer
failures and stragglers must be absorbed by the scheduler, and the only way
to prove a scheduler absorbs a failure is to inject that failure in a test
that replays bit-identically. This module is the seam: subsystems call
``fault_point(<declared name>)`` at the places a real deployment fails
(wire send/recv, per-chunk KV pulls, engine tick dispatch/reap, lease
renewal, canary probes, tier IO) and an armed :class:`FaultPlane` decides —
deterministically — whether that hit raises.

Design rules:

  * **Disabled is free.** ``fault_point`` is a module-global ``None`` check
    when no plane is armed — no locks, no logging, no allocation. The
    dispatch/reap seams sit on the decode hot path, and dynlint DYN002
    walks through this module to prove the purity holds.
  * **Schedules are (seed, operation-count), never wall-clock.** A rule
    fires at the Nth hit of a point, every Nth hit, or with probability p
    drawn from a per-point ``random.Random(f"{seed}:{point}")`` stream —
    so the same plan over the same workload produces the identical
    injection trace regardless of host speed, and a failing chaos run
    replays exactly (asserted by tests/test_faultline.py).
  * **Closed name set.** Every point name comes from
    runtime/fault_names.py; arming a plan that names an undeclared point
    fails fast, and dynlint DYN006 statically closes call sites over the
    same registry.

The module also aggregates process-wide *recovery activity* counters
(``note_activity``): retries, breaker transitions, migrations. The soak
tests read them so a chaos-free run proves zero spurious activations of
the self-healing paths.
"""

from __future__ import annotations

import random
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from dynamo_tpu.runtime import metric_names as mn
from dynamo_tpu.runtime.fault_names import ALL_FAULT_POINTS


class InjectedFault(Exception):
    """Marker mixin: every exception the plane raises derives from this,
    so tests (and post-mortems) can tell injected chaos from organic
    failures while production code still sees the native type."""


class InjectedConnectionError(InjectedFault, ConnectionError):
    pass


class InjectedTimeoutError(InjectedFault, TimeoutError):
    pass


class InjectedError(InjectedFault, RuntimeError):
    pass


_KINDS = {
    "connection": InjectedConnectionError,
    "timeout": InjectedTimeoutError,
    "error": InjectedError,
}

# Data-mutating kind: instead of raising, a firing "corrupt" rule flips one
# bit of the payload passing through a ``fault_payload`` seam (deterministic:
# bit 0 of the middle byte), modeling silent storage/wire corruption. Only
# seams that carry a payload (``fault_payload``) can apply it; at a plain
# ``fault_point`` a firing corrupt rule is recorded in the trace but mutates
# nothing (there is nothing to mutate).
CORRUPT_KIND = "corrupt"


def corrupt_bytes(data: bytes, flip: int = 0) -> bytes:
    """The deterministic corruption transform: bit ``flip % 8`` of the
    middle byte. Exposed so tests can predict the exact corrupted form.
    ``flip`` distinguishes stacked applications on one hit — the flip is
    an involution, so two rules flipping the SAME bit would silently
    restore the pristine payload while the trace claims two injections."""
    if not data:
        return data
    buf = bytearray(data)
    buf[len(buf) // 2] ^= 1 << (flip % 8)
    return bytes(buf)


@dataclass(frozen=True)
class FaultRule:
    """One trigger on one point. ``at`` are 1-based hit indices; ``every``
    fires on every Nth hit; ``p`` draws per hit from the point's seeded
    stream (the draw happens on EVERY hit, fire or not, so replay stays
    aligned). ``times`` bounds total fires (None = unbounded)."""

    point: str
    at: Tuple[int, ...] = ()
    every: int = 0
    p: float = 0.0
    kind: str = "connection"
    times: Optional[int] = None

    def __post_init__(self) -> None:
        if self.point not in ALL_FAULT_POINTS:
            raise ValueError(
                f"undeclared fault point {self.point!r} — add it to "
                "runtime/fault_names.py (DYN006 closes call sites over "
                "the same registry)"
            )
        if self.kind not in _KINDS and self.kind != CORRUPT_KIND:
            raise ValueError(
                f"unknown fault kind {self.kind!r} "
                f"(one of {sorted([*_KINDS, CORRUPT_KIND])})"
            )
        # Tolerate list specs from JSON plans.
        if not isinstance(self.at, tuple):
            object.__setattr__(self, "at", tuple(self.at))

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FaultRule":
        unknown = set(d) - set(cls.__dataclass_fields__)
        if unknown:
            # A typo'd trigger field ("evry") would otherwise arm a rule
            # with all-default triggers that never fires — a chaos run
            # passing vacuously. Same fail-fast contract as point names.
            raise ValueError(
                f"unknown FaultRule field(s) {sorted(unknown)} "
                f"(valid: {sorted(cls.__dataclass_fields__)})"
            )
        return cls(**d)


@dataclass(frozen=True)
class FaultPlan:
    """A seed plus an ordered rule list — the full chaos schedule. The
    plan (not the plane) is what a failing run's repro ships."""

    seed: int = 0
    rules: Tuple[FaultRule, ...] = ()

    def __post_init__(self) -> None:
        if not isinstance(self.rules, tuple):
            object.__setattr__(self, "rules", tuple(self.rules))

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FaultPlan":
        return cls(
            seed=int(d.get("seed", 0)),
            rules=tuple(
                FaultRule.from_dict(r) for r in d.get("rules", [])
            ),
        )


@dataclass
class _RuleState:
    fired: int = 0


class FaultPlane:
    """Armed chaos: per-point hit counters + rule evaluation + the
    injection trace. ``hit`` is the only method on a hot path; it bumps a
    dict counter, evaluates the (usually absent) rules for the point, and
    either returns or raises. No locks anywhere — per-point hit streams
    are single-threaded at every installed seam, and the GIL makes the
    counter bumps safe for cross-point concurrency."""

    def __init__(self, plan: FaultPlan) -> None:
        # Deferred import: this module is imported by runtime/distributed.py
        # (for the module-level fault_point), and metrics_core pulls
        # utils.logging — importing it at module level closes an import
        # cycle when utils.logging is the process's first entry into the
        # runtime package. A plane is only built for chaos runs.
        from dynamo_tpu.runtime.metrics_core import MetricsRegistry

        self.plan = plan
        self.hits: Dict[str, int] = {}
        self.injected: Dict[str, int] = {}
        # (point, hit index, rule index, kind) per injection — the replay
        # identity two runs of the same plan must agree on.
        self.trace: List[Tuple[str, int, int, str]] = []
        self._rules: Dict[str, List[Tuple[int, FaultRule, _RuleState]]] = {}
        self._rng: Dict[str, random.Random] = {}
        for i, rule in enumerate(plan.rules):
            self._rules.setdefault(rule.point, []).append(
                (i, rule, _RuleState())
            )
            if rule.p:
                # Seeded per POINT (not per rule): the stream advances one
                # draw per hit per p-rule, in declaration order, so the
                # trace is a pure function of (plan, per-point hit counts).
                self._rng.setdefault(
                    rule.point, random.Random(f"{plan.seed}:{rule.point}")
                )
        self.registry = MetricsRegistry()
        self._armed_gauge = self.registry.gauge(
            mn.FAULTS_ARMED,
            "1 while a fault plan is armed in this process (chaos runs "
            "only; production scrapes must read 0)",
        )
        self._injections = self.registry.counter(
            mn.FAULTS_INJECTIONS_TOTAL,
            "Faults injected by the armed plan, per declared point",
            ["point"],
        )
        self.registry.on_render(self._refresh)

    def _refresh(self) -> None:
        self._armed_gauge.set(1 if _PLANE is self else 0)
        for point, n in list(self.injected.items()):
            self._injections.set_total(n, point=point)

    def hit(self, name: str, info: Dict[str, Any]) -> None:
        self._eval(name, info, None)

    def hit_payload(self, name: str, data: bytes, info: Dict[str, Any]) -> bytes:
        """Payload-carrying hit (``fault_payload`` seams): raising kinds
        raise exactly like ``hit``; a firing "corrupt" rule returns the
        deterministically bit-flipped payload instead."""
        out = self._eval(name, info, data)
        return data if out is None else out

    def _eval(
        self, name: str, info: Dict[str, Any], data: Optional[bytes]
    ) -> Optional[bytes]:
        n = self.hits.get(name, 0) + 1
        self.hits[name] = n
        rules = self._rules.get(name)
        if not rules:
            return None
        rng = self._rng.get(name)
        corrupted: Optional[bytes] = None
        n_corrupt = 0
        for idx, rule, state in rules:
            fire = n in rule.at
            if rule.every and n % rule.every == 0:
                fire = True
            if rule.p and rng is not None:
                # One draw per hit per p-rule keeps replays aligned even
                # when another rule already decided to fire.
                draw = rng.random() < rule.p
                fire = fire or draw
            if not fire:
                continue
            if rule.times is not None and state.fired >= rule.times:
                continue
            state.fired += 1
            self.injected[name] = self.injected.get(name, 0) + 1
            self.trace.append((name, n, idx, rule.kind))
            if rule.kind == CORRUPT_KIND:
                # Mutate-and-continue: later raising rules on the same hit
                # still evaluate (a plan may corrupt AND kill one point).
                # At a payload-less seam there is nothing to mutate — the
                # trace entry still records the scheduled fire.
                if data is not None:
                    # Stacked corrupt rules on one hit flip DIFFERENT bits
                    # (flip=0, 1, …): corrupt_bytes is an involution, so
                    # re-flipping bit 0 would restore the pristine payload
                    # while the trace claims two injections.
                    corrupted = corrupt_bytes(
                        data if corrupted is None else corrupted, n_corrupt
                    )
                    n_corrupt += 1
                continue
            raise _KINDS[rule.kind](
                f"injected {rule.kind} fault at {name} "
                f"(hit {n}, rule {idx}{', ' + repr(info) if info else ''})"
            )
        return corrupted

    def snapshot(self) -> Dict[str, Any]:
        return {
            "seed": self.plan.seed,
            "hits": dict(self.hits),
            "injected": dict(self.injected),
            "trace": [list(t) for t in self.trace],
        }


_PLANE: Optional[FaultPlane] = None

# Process-wide recovery-activity counters (retry/breaker/migration events),
# counted whether or not a plane is armed: the chaos-free tests read them
# to prove the self-healing paths sat idle.
_ACTIVITY: Dict[str, int] = {}


def fault_point(name: str, **info: Any) -> None:
    """Declare-and-maybe-fail one named operation. Disabled cost: one
    module-global load and a None check."""
    plane = _PLANE
    if plane is not None:
        plane.hit(name, info)


def fault_payload(name: str, data: bytes, **info: Any) -> bytes:
    """Payload-carrying seam variant: behaves exactly like ``fault_point``
    for raising kinds, and additionally lets a "corrupt" rule flip one bit
    of ``data`` (deterministically) before returning it. One hit per call —
    a seam uses EITHER fault_point OR fault_payload, never both, so hit
    schedules stay stable. Disabled cost: a None check, data untouched."""
    plane = _PLANE
    if plane is None:
        return data
    return plane.hit_payload(name, data, info)


def arm(plan: FaultPlan) -> FaultPlane:
    """Install ``plan`` as the process's fault plane (replacing any)."""
    global _PLANE
    _PLANE = FaultPlane(plan)
    return _PLANE


def disarm() -> None:
    global _PLANE
    _PLANE = None


def active_plane() -> Optional[FaultPlane]:
    return _PLANE


@contextmanager
def armed(plan: FaultPlan) -> Iterator[FaultPlane]:
    plane = arm(plan)
    try:
        yield plane
    finally:
        if _PLANE is plane:
            disarm()


def note_activity(kind: str, n: int = 1) -> None:
    """Record one recovery-path activation (e.g. ``pull_retries``,
    ``breaker_opens``, ``migrations``). GIL-atomic dict bump — callable
    from any thread, cheap enough for error paths."""
    _ACTIVITY[kind] = _ACTIVITY.get(kind, 0) + n


def activity_snapshot() -> Dict[str, int]:
    return dict(_ACTIVITY)


def reset_activity() -> None:
    _ACTIVITY.clear()


def plane_snapshot() -> Dict[str, Any]:
    """Fault-plane state for tests / debug surfaces: armed flag,
    per-point injections, and the recovery-activity counters."""
    plane = _PLANE
    return {
        "armed": plane is not None,
        "injections": dict(plane.injected) if plane is not None else {},
        "activity": activity_snapshot(),
    }
