"""Elasticity-loop suite (ISSUE 13): the self-correcting planner actuating
through the drain/crash planes, proven at fleet scale.

Layers:

  * ElasticController unit behavior — the steady→scaling_up/scaling_down→
    converged state machine, hysteresis/cooldown holds, readyz-gated
    scale-up, drain-with-handoff scale-down, spot preemption on the same
    path;
  * the fleet-scale chaos soak — ≥50 mock workers (planner/simfleet.py:
    real KvScheduler + LivenessTracker + Planner + ElasticController,
    simulated workers/clock) under bursty open-loop traffic with seeded
    kills, restarts, a drain, an overload wave, and injected faults at
    the planner.observe/planner.apply seams, asserting zero lost streams
    token-exact, zero liveness false positives, zero drain-attributed
    re-prefill, and per-request scheduling cost that does NOT grow with
    worker count (the pruned-candidate select_worker path);
  * the @slow soak doubles the fleet to 100 workers and the chaos rounds.
"""

import asyncio

import pytest

from dynamo_tpu.planner import (
    ElasticConfig,
    ElasticController,
    Planner,
    PlannerConfig,
    SimConfig,
    SimFleet,
    profile_interpolators,
)
from dynamo_tpu.planner.elastic import (
    CONVERGED,
    SCALING_DOWN,
    SCALING_UP,
    STEADY,
)
from dynamo_tpu.runtime.faults import (
    FaultPlan,
    FaultRule,
    InjectedFault,
    armed,
)


def sim_config(**over) -> SimConfig:
    """Soak-calibrated sim: the ITL SLA (2× base) crosses on the RISING
    part of the degradation curve, so the feedback fixed point is smooth
    and one worker's SLA-compliant concurrency is 2× its sweet spot."""
    kw = dict(seed=11, worker_max_conc=4, base_itl_s=0.02, base_ttft_s=0.1,
              isl=128, osl=32, report_interval_s=0.25, substep_s=0.05,
              launch_delay_s=0.6)
    kw.update(over)
    return SimConfig(**kw)


def build_loop(
    cfg: SimConfig,
    n_workers: int,
    rate_fn,
    *,
    profile_error: float = 1.0,
    planner_over=None,
    elastic_over=None,
):
    fleet = SimFleet(cfg, n_workers=n_workers, rate_fn=rate_fn)
    prefill, decode = profile_interpolators(cfg, error=profile_error)
    e_kw = dict(scale_up_after=1, scale_down_after=3, cooldown_intervals=1,
                actuation_deadline_s=30.0)
    e_kw.update(elastic_over or {})
    ctl = ElasticController(fleet, config=ElasticConfig(**e_kw))
    p_kw = dict(
        adjustment_interval_s=1.0,
        itl_target_s=cfg.base_itl_s * 2,  # crossing at 2× sweet conc
        ttft_target_s=2.0,
        min_replicas=2,
        max_replicas=max(n_workers * 2, 16),
        total_chip_budget=max(n_workers * 4, 64),
    )
    p_kw.update(planner_over or {})
    planner = Planner(
        PlannerConfig(**p_kw), prefill, decode, ctl, fleet.metrics_source,
        disagg=False, metrics=ctl.metrics,
    )
    return fleet, planner, ctl


async def drive(fleet, planner, intervals: int, *, interval_s: float = 1.0):
    """The planner loop, sim-time: world advances, planner steps. Injected
    faults at the planner seams are counted, not fatal (the production
    _run loop catches and continues the same way)."""
    injected = 0
    for _ in range(intervals):
        fleet.run(interval_s)
        try:
            await planner.step()
        except InjectedFault:
            injected += 1
    return injected


# ---------------------------------------------------------------------------
# ElasticController behavior
# ---------------------------------------------------------------------------


async def test_scale_down_executes_as_drain_with_handoff():
    """Planner-initiated scale-down of workers with in-flight decodes
    completes via live handoff: zero drain-attributed re-prefilled
    tokens, zero lost streams, every stream token-exact vs the oracle."""
    cfg = sim_config()
    # Load that needs ~3 workers, offered to 8: the planner wants down.
    fleet, planner, ctl = build_loop(cfg, 8, lambda t: 6.0)
    injected = await drive(fleet, planner, 12)
    assert injected == 0
    assert ctl.scale_downs >= 1, ctl.status()
    assert len(fleet.retired) >= 1
    # The zero-re-prefill elasticity contract: retirement moved live
    # streams over the handoff path, re-prefilling nothing.
    assert fleet.drain_reprefill_tokens == 0
    assert ctl.reprefill_tokens_from_scaling == 0
    fleet.settle()
    assert fleet.verify_streams() == []
    # Token-exactness is only meaningful if drains actually moved live
    # decodes (otherwise the assert above is vacuous).
    assert fleet.handoff_streams > 0
    assert (
        ctl.metrics.scale_down_drains.value(mode="planned")
        == len(ctl.drained_workers)
    )


async def test_scale_up_counts_replicas_only_after_ready():
    """A scale-up only converges once the launched replicas pass the
    readyz gate (launch_delay models engine start + warm restore)."""
    cfg = sim_config(launch_delay_s=1.5)
    ramp = lambda t: 4.0 if t < 3 else 30.0
    fleet, planner, ctl = build_loop(cfg, 2, ramp)
    await drive(fleet, planner, 10)
    assert ctl.scale_ups >= 1
    # Every launched worker the controller counted went through the
    # ready gate: applied counts equal the fleet's READY count, and the
    # pending gauge is drained.
    assert ctl.applied["decode"] == fleet.ready_count("decode")
    assert ctl.metrics.scale_up_pending.value(pool="decode") == 0
    transitions = [e for e in ctl.flight.snapshot() if e["kind"] == "state"]
    names = [e["to"] for e in transitions]
    assert "scaling_up" in names and "converged" in names
    fleet.settle()
    assert fleet.verify_streams() == []


async def test_spot_preemption_rides_the_drain_path():
    cfg = sim_config()
    fleet, planner, ctl = build_loop(cfg, 4, lambda t: 8.0)
    fleet.run(3.0)  # build up in-flight decodes
    victim = max(fleet.load_view("decode"), key=fleet.load_view("decode").get)
    ok = await ctl.preempt("decode", victim)
    assert ok
    assert ctl.preemptions == 1
    assert ctl.metrics.scale_down_drains.value(mode="preemption") == 1
    assert victim in fleet.retired
    assert fleet.drain_reprefill_tokens == 0
    assert fleet.handoff_streams > 0
    fleet.settle()
    assert fleet.verify_streams() == []


async def test_hysteresis_absorbs_oscillating_load():
    """Load oscillating 5× second-to-second must not flap the fleet:
    the predictor smooths the fast oscillation and the streak/cooldown
    hysteresis absorbs what leaks through, so after a bounded settling
    phase (initial trend overshoot corrected down in ≤2 steps) the
    oscillating TAIL causes zero further actuations — suppressions land
    in the holds counter, not in fleet churn."""
    from dynamo_tpu.planner import FeedbackConfig

    cfg = sim_config()
    osc = lambda t: 40.0 if int(t) % 2 == 0 else 8.0
    # Feedback off: this test isolates the hysteresis machinery from
    # factor-driven corrections.
    fleet, planner, ctl = build_loop(
        cfg, 4, osc,
        planner_over=dict(feedback=FeedbackConfig(decay=0.0)),
    )
    await drive(fleet, planner, 11)
    assert ctl.scale_ups <= 2 and ctl.scale_downs <= 2, ctl.status()
    ups0, downs0 = ctl.scale_ups, ctl.scale_downs
    size0 = fleet.ready_count("decode")
    await drive(fleet, planner, 10)
    # The oscillation keeps going; the fleet does not.
    assert (ctl.scale_ups, ctl.scale_downs) == (ups0, downs0), ctl.status()
    assert fleet.ready_count("decode") == size0
    assert ctl.holds > 0
    assert ctl.metrics.holds.value() == ctl.holds
    fleet.settle()
    assert fleet.verify_streams() == []


async def test_sustained_shift_does_actuate_after_streak():
    """The counterpart: a sustained drop IS acted on, exactly once the
    scale_down_after streak fills — not on the first low interval."""
    cfg = sim_config()
    shift = lambda t: 24.0 if t < 6 else 5.0
    fleet, planner, ctl = build_loop(cfg, 2, shift)
    await drive(fleet, planner, 6)
    high_water = fleet.ready_count("decode")
    assert ctl.scale_downs == 0  # streak not filled yet
    await drive(fleet, planner, 8)
    assert ctl.scale_downs >= 1
    assert fleet.ready_count("decode") < high_water
    fleet.settle()
    assert fleet.verify_streams() == []


async def test_chaos_free_ramp_converges_both_ways_and_heals_nothing():
    """Open-loop load 1x -> 4x -> 1x with nothing injected: after each
    shift the plan and the ready fleet meet and stay met (within the
    window, both directions), the fleet grew and shrank, scaling down
    re-prefilled nothing, no stream was lost, and no worker that kept
    reporting was ever declared dead."""
    cfg = sim_config(seed=29)
    shifts = (15.0, 35.0)

    def rate(t):
        if t < shifts[0] or shifts[1] <= t < 55.0:
            return 30.0  # about five SLA-sized workers
        return 120.0 if t < shifts[1] else 0.0

    fleet, planner, ctl = build_loop(
        cfg, 5, rate,
        planner_over=dict(max_replicas=64, total_chip_budget=128),
        elastic_over=dict(actuation_deadline_s=20.0),
    )
    timeline = []
    for _ in range(58):
        fleet.run(1.0)
        plan = await planner.step()
        timeline.append(
            (fleet.now, plan.decode if plan else None, fleet.ready_count("decode"))
        )
    fleet.settle(180.0)

    def converged_after(shift_t):
        """Intervals from the shift until desired == ready for three in a row."""
        after = [i for i, (t, _w, _h) in enumerate(timeline) if t > shift_t]
        for n, i in enumerate(after):
            if all(w is not None and w == h for _t, w, h in timeline[i:i + 3]):
                return n + 1
        return None

    up, down = converged_after(shifts[0]), converged_after(shifts[1])
    assert up is not None and up <= shifts[1] - shifts[0], timeline
    assert down is not None and down <= 58 - shifts[1], timeline
    start = timeline[0][2]
    assert max(h for _t, _w, h in timeline) >= 2 * start
    assert timeline[-1][2] < max(h for _t, _w, h in timeline)
    assert ctl.scale_ups >= 1 and ctl.scale_downs >= 1
    assert len(ctl.drained_workers) >= 1
    assert fleet.verify_streams() == []
    assert fleet.drain_reprefill_tokens == 0
    assert fleet.false_positive_deaths == []
    assert fleet.migrated_streams == 0  # nothing died: nothing migrated


async def test_state_machine_transitions_and_gauge():
    cfg = sim_config()
    fleet, planner, ctl = build_loop(cfg, 2, lambda t: 4.0 if t < 3 else 26.0)
    assert ctl.state == STEADY
    await drive(fleet, planner, 8)
    seen = {
        e["to"] for e in ctl.flight.snapshot() if e["kind"] == "state"
    }
    assert {"scaling_up", "converged"} <= seen
    # After convergence + cooldown with stable load the gauge returns to
    # steady.
    await drive(fleet, planner, 6)
    assert ctl.state in (STEADY, CONVERGED)
    assert ctl.metrics.state.value() == ctl.state
    rendered = ctl.metrics.render()
    assert "dynamo_tpu_planner_state" in rendered
    assert "dynamo_tpu_planner_transitions_total" in rendered


# ---------------------------------------------------------------------------
# Fleet-scale chaos soak
# ---------------------------------------------------------------------------


def _soak(n_workers: int, duration_s: float, chaos_rounds: int, seed: int):
    """One soak run. Rate is calibrated so the steady plan sits near
    ``n_workers``; chaos (kills + restarts + a drain + an overload wave)
    is seeded; the planner runs the whole time with faults injected at
    its own observe/apply seams."""
    cfg = sim_config(seed=seed)
    sla_conc = cfg.worker_max_conc * 2  # ITL-SLA crossing per worker
    stream_s = cfg.osl * cfg.base_itl_s * 2
    steady = n_workers * sla_conc / stream_s * 0.85
    burst_until = duration_s * 0.6

    def rate(t):
        if t < duration_s * 0.2:
            return steady * 0.5
        if t < burst_until:
            return steady  # the burst the planner must ride
        if t < duration_s:
            return steady * 0.5
        return 0.0

    fleet = SimFleet(cfg, n_workers=n_workers, rate_fn=rate)
    prefill, decode = profile_interpolators(cfg)
    ctl = ElasticController(
        fleet,
        config=ElasticConfig(scale_up_after=1, scale_down_after=3,
                             cooldown_intervals=1, actuation_deadline_s=20.0),
    )
    planner = Planner(
        PlannerConfig(
            adjustment_interval_s=1.0, itl_target_s=cfg.base_itl_s * 2,
            ttft_target_s=2.0, min_replicas=max(n_workers // 4, 2),
            max_replicas=n_workers * 2, total_chip_budget=n_workers * 4,
        ),
        prefill, decode, ctl, fleet.metrics_source,
        disagg=False, metrics=ctl.metrics,
    )
    # Seeded chaos: kills mid-burst (each restarted inside the run),
    # one operator drain, one overload wave — all on the sim clock.
    events = []
    t0 = duration_s * 0.25
    for i in range(chaos_rounds):
        t_kill = t0 + i * 2.5
        events.append((t_kill, "kill", None))
        events.append((t_kill + 1.6, "restart", None))
    # The operator drain fires in the calm warm-up phase: a drain INTO a
    # saturated fleet honestly falls to the re-prefill rung (capacity
    # refusals), which is the planner's SLA-breach guard's job to avoid
    # commanding — the chaos event tests the handoff path itself.
    events.append((duration_s * 0.15, "drain", None))
    events.append((duration_s * 0.5, "overload", (2.0, 2.0)))
    fleet.schedule_chaos(events)

    async def run():
        injected = 0
        intervals = int(duration_s) + 4
        # Fault the planner's own seams mid-soak: the control loop itself
        # is chaos-tested, not just the data plane under it.
        plan = FaultPlan(seed=seed, rules=(
            FaultRule(point="planner.observe", at=(5,)),
            FaultRule(point="planner.apply", at=(4,), kind="error"),
        ))
        with armed(plan) as plane:
            for _ in range(intervals):
                fleet.run(1.0)
                try:
                    await planner.step()
                except InjectedFault:
                    injected += 1
            assert plane.injected.get("planner.observe", 0) == 1
            assert plane.injected.get("planner.apply", 0) == 1
        assert injected == 2
        fleet.settle(240.0)

    asyncio.run(run())
    return fleet, ctl


def _assert_soak(fleet: SimFleet, ctl: ElasticController, n_workers: int):
    cfg = fleet.cfg
    # Zero lost streams, token-exact vs the never-disturbed oracle —
    # through kills, restarts, drains, planner churn, and the overload
    # wave.
    assert fleet.verify_streams() == []
    assert fleet.arrivals > n_workers * 10  # the soak actually soaked
    # Liveness false-positive rate exactly zero: nothing alive-and-
    # reporting was ever declared dead.
    assert fleet.false_positive_deaths == []
    # Every seeded kill was detected inside the missed-report budget
    # (+1 report interval of sweep granularity).
    budget = (
        fleet.tracker.config.detection_budget_s + cfg.report_interval_s
    )
    assert fleet.detection_latencies, "no kill was ever detected"
    assert max(fleet.detection_latencies) <= budget + 1e-6
    # Elastic scale-down + the operator drain paid ZERO re-prefill.
    assert fleet.drain_reprefill_tokens == 0
    assert fleet.handoff_streams > 0
    # Kill-9 migrations are the only re-prefill source, and they happened.
    assert fleet.migrated_streams > 0
    # Bounded per-request scheduling cost: at this fleet size the pruned
    # path scores a CONSTANT number of candidates per request — nowhere
    # near the worker count.
    sched = fleet.scheduler
    evals_per_req = sched.logit_evals / max(sched.selections, 1)
    assert evals_per_req <= 16, (
        f"{evals_per_req:.1f} candidates scored/request at "
        f"{n_workers}+ workers — pruning regressed"
    )
    # The planner stayed live through its own injected faults and kept
    # the fleet converging (applies kept happening after the injections).
    assert ctl.metrics.applies.value() >= 10


def test_fleet_soak_50_workers():
    """Tier-1 slice: 50 mock workers, 2 kill/restart rounds, a drain, an
    overload wave, planner-seam faults — sim-clocked, seconds of wall."""
    fleet, ctl = _soak(n_workers=50, duration_s=20.0, chaos_rounds=2,
                       seed=1301)
    _assert_soak(fleet, ctl, 50)


@pytest.mark.slow
def test_fleet_soak_100_workers():
    """The full soak: 100 workers, 4 chaos rounds, longer burst."""
    fleet, ctl = _soak(n_workers=100, duration_s=30.0, chaos_rounds=4,
                       seed=1302)
    _assert_soak(fleet, ctl, 100)


def test_scheduling_cost_does_not_grow_with_fleet():
    """The select_worker ceiling fix, measured structurally: candidates
    SCORED per request at 100 workers must not exceed the 10-worker
    count (pruning makes big fleets cheaper per request, not costlier)."""
    from dynamo_tpu.router.protocols import LoadSnapshot
    from dynamo_tpu.router.scheduler import KvScheduler
    from dynamo_tpu.tokens.radix import OverlapScores

    def evals_per_request(n_workers: int) -> float:
        sched = KvScheduler(seed=5)
        for wid in range(1, n_workers + 1):
            sched.update_load(LoadSnapshot(
                worker_id=wid, active_blocks=wid * 3, total_blocks=4096,
            ))
        candidates = [(wid, 0) for wid in range(1, n_workers + 1)]
        for _ in range(200):
            sched.select_worker(17, OverlapScores(), candidates)
        return sched.logit_evals / sched.selections

    small = evals_per_request(10)
    large = evals_per_request(100)
    assert large <= small + 1, (small, large)


async def test_partial_scale_up_does_not_double_launch():
    """A scale-up whose warm-up outlives the actuation deadline leaves
    PENDING replicas; subsequent actuations must count them against the
    shortfall instead of launching them again (overshooting the fleet
    and feeding the overshoot into drain churn)."""
    from dynamo_tpu.planner import ReplicaPlan

    cfg = sim_config(launch_delay_s=5.0)
    fleet = SimFleet(cfg, n_workers=2, rate_fn=lambda t: 0.0)
    ctl = ElasticController(
        fleet,
        config=ElasticConfig(scale_up_after=1, scale_down_after=3,
                             cooldown_intervals=0,
                             actuation_deadline_s=1.0),
        disagg=False,
    )
    plan = ReplicaPlan(prefill=0, decode=8)
    await ctl.apply(plan)  # launches 6; deadline 1s < 5s warm-up
    assert len(fleet.workers) == 8
    assert ctl.metrics.scale_up_pending.value(pool="decode") == 6
    await ctl.apply(plan)  # pending replicas must NOT be launched again
    await ctl.apply(plan)
    assert len(fleet.workers) == 8
    fleet.run(6.0)  # warm-up completes
    await ctl.apply(plan)
    assert fleet.ready_count("decode") == 8
    assert ctl.metrics.scale_up_pending.value(pool="decode") == 0
