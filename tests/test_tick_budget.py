"""Tick budgeter (ISSUE 18): the SLA-driven intra-chip prefill/decode
middle mode.

Covered here:

  * the AIMD state machine under a fake clock — a burn spike shrinks the
    budget within ONE evaluation window, hysteresis holds both directions
    (no flapping on oscillating load), the starvation floor is honored,
    overdraft debt and watermark rollovers settle correctly;
  * the ``engine.budget.apply`` fault seam — an injected fault skips the
    adjustment (counted, evented), never corrupts the budget;
  * the brownout-ladder rung — with a lever registered the budget squeeze
    fires BEFORE the healthy→brownout transition (proven by flight-ring
    event order) and releases LAST on recovery;
  * observability threading — stats() keys, LoadSnapshot/LoadPublisher
    advertisement, scheduler budget-pressure deflection, planner
    rebalance-before-launch hold;
  * the watermark-hold regression — a watermark-held engine keeps full
    decode cadence and rolls the unspent prefill budget into decode.

The bit-identical determinism contract (budgeter on vs off × pipeline
depth 1 vs 2) lives in tests/test_decode_pipeline.py next to the rest of
the stream-signature suite.
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.engines.tpu.tick_budget import (
    BUDGET_STATE_ADAPTIVE,
    BUDGET_STATE_FLOOR,
    BUDGET_STATE_OFF,
    BUDGET_STATE_THROUGHPUT,
    TickBudgetConfig,
    TickBudgeter,
)
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import tiny_config
from dynamo_tpu.planner import (
    DecodeInterpolator,
    MetricsSnapshot,
    Planner,
    PlannerConfig,
    PrefillInterpolator,
)
from dynamo_tpu.router.protocols import LoadSnapshot
from dynamo_tpu.router.publisher import LoadPublisher
from dynamo_tpu.router.scheduler import KvRouterConfig, KvScheduler
from dynamo_tpu.runtime import fault_names as fn
from dynamo_tpu.runtime import faults
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect
from dynamo_tpu.runtime.overload import (
    BROWNOUT,
    HEALTHY,
    OverloadConfig,
    OverloadController,
)
from dynamo_tpu.tokens.radix import OverlapScores


@pytest.fixture(autouse=True)
def _disarmed():
    faults.disarm()
    yield
    faults.disarm()


def mk_budgeter(now, *, events=None, **cfg_over):
    """Fake-clocked budgeter: floor 128, ceiling 1024, policy 0.5 →
    initial budget 576 (mid-band), SLO 20ms, shrink within one window."""
    defaults = dict(
        floor_tokens=128,
        ceiling_tokens=1024,
        policy=0.5,
        itl_slo_s=0.020,
        eval_interval_s=0.25,
        shrink_after=1,
        grow_after=4,
        min_itl_samples=4,
        itl_window=16,
    )
    defaults.update(cfg_over)
    return TickBudgeter(
        TickBudgetConfig(**defaults),
        clock=lambda: now[0],
        on_event=(
            (lambda kind, **f: events.append((kind, f)))
            if events is not None
            else None
        ),
    )


def feed(b, now, itl_s, n=8):
    """n decode reaps at a steady per-token cadence of ``itl_s``. Resets
    the reap cadence first (as the engine's idle path does) so the gap
    since the previous feed doesn't masquerade as a giant ITL sample."""
    b.note_idle()
    for _ in range(n):
        b.observe_decode(itl_s, occupancy=1, tokens=1, now=now[0])
        now[0] += itl_s


# -- state machine (fake clock) -----------------------------------------------


class TestStateMachine:
    def test_burn_spike_shrinks_within_one_window(self):
        now = [0.0]
        b = mk_budgeter(now)
        start = b.budget_tokens
        assert b.state == BUDGET_STATE_ADAPTIVE
        feed(b, now, 0.050)  # every sample breaches 20ms → burn 10.0
        now[0] += 0.25
        b.evaluate()
        assert b.budget_tokens == max(128, start // 2)
        assert b.shrinks == 1

    def test_repeated_shrinks_stop_at_the_starvation_floor(self):
        now = [0.0]
        b = mk_budgeter(now)
        for _ in range(10):
            feed(b, now, 0.050)
            now[0] += 0.25
            b.evaluate()
        assert b.budget_tokens == 128  # floor honored, never below
        assert b.state == BUDGET_STATE_FLOOR

    def test_growth_needs_a_filled_streak_then_reaches_ceiling(self):
        now = [0.0]
        b = mk_budgeter(now)
        start = b.budget_tokens
        feed(b, now, 0.005)  # clean: burn 0
        for i in range(3):
            now[0] += 0.25
            b.evaluate()
            assert b.budget_tokens == start, f"grew after {i + 1} evals"
        now[0] += 0.25
        b.evaluate()  # 4th clean evaluation: additive increase
        assert b.budget_tokens == min(1024, start + 512)
        for _ in range(8):
            feed(b, now, 0.005, n=2)
            now[0] += 0.25
            b.evaluate()
        assert b.budget_tokens == 1024  # capped at the ceiling
        assert b.state == BUDGET_STATE_THROUGHPUT

    def test_oscillating_burn_does_not_flap(self):
        """Alternating breach/clean windows never fill either streak
        (each evaluation resets the other side): the budget parks."""
        now = [0.0]
        b = mk_budgeter(now, shrink_after=2)
        start = b.budget_tokens
        for _ in range(12):
            feed(b, now, 0.050, n=16)  # window all-breach
            now[0] += 0.25
            b.evaluate()
            feed(b, now, 0.005, n=16)  # window all-clean
            now[0] += 0.25
            b.evaluate()
        assert b.budget_tokens == start
        assert b.shrinks == 0 and b.grows == 0

    def test_dead_band_holds_and_resets_streaks(self):
        now = [0.0]
        b = mk_budgeter(now, slo_target=0.9, burn_shrink=1.0, burn_grow=0.5)
        start = b.budget_tokens
        # 1 breach in 16 samples → burn 0.0625/0.1 = 0.625: dead band.
        feed(b, now, 0.005, n=15)
        feed(b, now, 0.050, n=1)
        for _ in range(10):
            now[0] += 0.25
            b.evaluate()
        assert b.budget_tokens == start

    def test_eval_interval_gates_the_streaks(self):
        """Back-to-back evaluate() calls inside one interval are no-ops:
        hysteresis denominates time, not tick rate."""
        now = [0.0]
        b = mk_budgeter(now, shrink_after=3)
        feed(b, now, 0.050)
        for _ in range(50):  # same instant: only the first one counts
            b.evaluate()
        assert b.shrinks == 0

    def test_no_samples_means_no_movement(self):
        now = [0.0]
        b = mk_budgeter(now)
        start = b.budget_tokens
        for _ in range(10):
            now[0] += 0.25
            b.evaluate()
        assert b.budget_tokens == start

    def test_stale_samples_age_out(self):
        now = [0.0]
        b = mk_budgeter(now, itl_sample_ttl_s=5.0)
        feed(b, now, 0.050)
        now[0] += 10.0  # idle gap: every sample is past the TTL
        b.evaluate()
        assert b.shrinks == 0  # an idle engine must not testify

    def test_tick_grant_debt_and_idle(self):
        now = [0.0]
        b = mk_budgeter(now)
        budget = b.budget_tokens
        assert b.tick_grant(decode_active=False) is None  # unbounded
        grant = b.tick_grant(decode_active=True)
        assert grant == budget
        b.add_debt(100)  # last round overdrew
        assert b.tick_grant(decode_active=True) == budget - 100
        assert b.tick_grant(decode_active=True) == budget  # debt settled

    def test_rollover_counters(self):
        now = [0.0]
        b = mk_budgeter(now)
        b.note_rollover(64)
        b.note_rollover(0)
        assert b.rollovers == 1 and b.rolled_tokens == 64

    def test_pressure_squeeze_and_release(self):
        now = [0.0]
        events = []
        b = mk_budgeter(now, events=events)
        b.set_pressure(True)
        b.set_pressure(True)  # idempotent
        assert b.budget_tokens == 128
        assert b.state == BUDGET_STATE_FLOOR
        assert b.squeezes == 1
        b.set_pressure(False)
        # Release re-enters the control law FROM the floor: growth must
        # be re-earned, not restored.
        assert b.budget_tokens == 128
        kinds = [k for k, _ in events]
        assert kinds == ["budget_squeeze", "budget_release"]

    def test_fault_seam_skips_the_adjustment_cleanly(self):
        now = [0.0]
        events = []
        b = mk_budgeter(now, events=events)
        start = b.budget_tokens
        plan = faults.FaultPlan(
            seed=7,
            rules=(faults.FaultRule(point=fn.ENGINE_BUDGET_APPLY, at=(1,)),),
        )
        with faults.armed(plan):
            feed(b, now, 0.050)
            now[0] += 0.25
            b.evaluate()
            # Injection landed: the budget is UNTOUCHED, the skip counted.
            assert b.budget_tokens == start
            assert b.skipped_applies == 1 and b.shrinks == 0
            assert [k for k, _ in events] == ["budget_skip"]
            # The next adjustment (fault spent) commits normally.
            feed(b, now, 0.050)
            now[0] += 0.25
            b.evaluate()
        assert b.shrinks == 1
        assert b.budget_tokens == max(128, start // 2)

    def test_floor_above_ceiling_rejected(self):
        with pytest.raises(ValueError):
            TickBudgeter(
                TickBudgetConfig(floor_tokens=1024, ceiling_tokens=512)
            )


# -- brownout-ladder rung (fake clock) ----------------------------------------


class TestBrownoutRung:
    def _controller(self):
        now = [0.0]
        cfg = OverloadConfig(
            itl_sla_s=0.020,
            shed_itl_factor=3.0,
            min_itl_samples=4,
            itl_window=16,
            brownout_after=3,
            recover_after=4,
            brownout_max_tokens=256,
        )
        return OverloadController(cfg, clock=lambda: now[0]), now

    def _feed(self, c, itl_s, n=16):
        for _ in range(n):
            c.observe_itl(itl_s)

    def test_budget_squeeze_fires_before_brownout_and_releases_last(self):
        c, now = self._controller()
        bnow = [0.0]
        budgeter = mk_budgeter(bnow)
        c.on_budget_pressure(budgeter.set_pressure)
        # Breach: the FIRST filled streak squeezes the budget — the state
        # stays HEALTHY, max_tokens stays unclamped.
        self._feed(c, 0.030)
        for _ in range(3):
            now[0] += 1.0
            state = c.evaluate()
        assert state == HEALTHY
        assert budgeter.pressure is True
        assert budgeter.budget_tokens == 128
        assert c.clamp_max_tokens(4096) == 4096
        assert c.snapshot()["budget_squeezed"] is True
        # The breach persists: the NEXT filled streak escalates to
        # brownout (now the max_tokens clamp engages).
        for _ in range(3):
            now[0] += 1.0
            state = c.evaluate()
        assert state == BROWNOUT
        assert c.clamp_max_tokens(4096) == 256
        # Flight-ring order IS the rung-ordering proof: squeeze strictly
        # before the healthy→brownout transition.
        events = [
            e
            for e in c.flight.snapshot()
            if e["kind"] in ("budget_squeeze", "budget_release", "state")
        ]
        assert events[0]["kind"] == "budget_squeeze"
        assert events[1]["kind"] == "state"
        assert (events[1]["frm"], events[1]["to"]) == ("healthy", "brownout")
        # Recovery: clean ITLs step the STATE down first; the squeeze
        # releases only after a further filled streak at healthy.
        self._feed(c, 0.005)
        for _ in range(4):
            now[0] += 1.0
            c.evaluate()
        assert c.state == HEALTHY
        assert budgeter.pressure is True  # squeeze outlives the step-down
        for _ in range(4):
            now[0] += 1.0
            c.evaluate()
        assert budgeter.pressure is False
        events = [
            e
            for e in c.flight.snapshot()
            if e["kind"] in ("budget_squeeze", "budget_release", "state")
        ]
        assert [e["kind"] for e in events] == [
            "budget_squeeze",
            "state",
            "state",
            "budget_release",
        ]
        assert c.snapshot()["budget_squeezes"] == 1

    def test_without_levers_the_ladder_is_unchanged(self):
        c, now = self._controller()
        self._feed(c, 0.030)
        for _ in range(3):
            now[0] += 1.0
            state = c.evaluate()
        assert state == BROWNOUT  # first filled streak transitions
        assert c.snapshot()["budget_squeezes"] == 0

    def test_lever_exception_does_not_break_the_ladder(self):
        c, now = self._controller()

        def broken(_on):
            raise RuntimeError("lever died")

        c.on_budget_pressure(broken)
        self._feed(c, 0.030)
        for _ in range(3):
            now[0] += 1.0
            state = c.evaluate()
        assert state == HEALTHY  # squeeze attempted, ladder intact
        assert c.snapshot()["budget_squeezed"] is True


# -- observability threading ---------------------------------------------------


def _eng_args(**over):
    defaults = dict(
        config=tiny_config(),
        block_size=4,
        num_kv_blocks=64,
        max_num_seqs=4,
        max_model_len=96,
        prefill_chunk=32,
        decode_steps=4,
    )
    defaults.update(over)
    return JaxEngineArgs(**defaults)


def _req(tokens, max_tokens=8, rid="r"):
    return PreprocessedRequest(
        token_ids=list(tokens),
        request_id=rid,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens),
    )


class TestObservability:
    async def test_stats_expose_budget_gauges(self):
        engine = JaxEngine(
            _eng_args(
                tick_budget_enabled=True,
                tick_budget_floor_tokens=32,
                tick_budget_ceiling_tokens=256,
                tick_budget_policy=1.0,
            )
        )
        try:
            await collect(engine.generate(_req(range(10, 20)), Context()))
            s = engine.stats()
            assert s["prefill_budget_tokens"] == 256
            assert s["budget_state"] == BUDGET_STATE_THROUGHPUT
            assert s["prefill_chunk_tokens"] == 32
            assert s["budget_rollovers"] == 0
        finally:
            await engine.stop()

    async def test_stats_report_off_when_disabled(self):
        engine = JaxEngine(_eng_args())
        try:
            s = engine.stats()
            assert s["budget_state"] == BUDGET_STATE_OFF
            assert s["prefill_budget_tokens"] == 0
        finally:
            await engine.stop()

    def test_load_publisher_advertises_the_budget(self):
        pub = LoadPublisher(
            None,
            "ns",
            "comp",
            worker_id=7,
            stats_fn=lambda: {
                "total_blocks": 100,
                "free_blocks": 60,
                "prefill_budget_tokens": 512,
                "budget_state": BUDGET_STATE_ADAPTIVE,
            },
            interval_s=1.0,
        )
        snap = pub.snapshot()
        assert snap.prefill_budget_tokens == 512
        assert snap.budget_state == BUDGET_STATE_ADAPTIVE
        # Wire roundtrip, including a pre-budgeter peer's dict.
        again = LoadSnapshot.from_dict(snap.to_dict())
        assert again.budget_state == BUDGET_STATE_ADAPTIVE
        legacy = LoadSnapshot.from_dict({"worker_id": 3})
        assert legacy.prefill_budget_tokens == 0
        assert legacy.budget_state == BUDGET_STATE_OFF


# -- placement deflection -------------------------------------------------------


class TestSchedulerDeflection:
    def _snap(self, wid, **over):
        fields = dict(
            worker_id=wid,
            active_blocks=10,
            total_blocks=100,
            queue_depth=0,
        )
        fields.update(over)
        return LoadSnapshot(**fields)

    def test_floor_state_deflects_prefill(self):
        sched = KvScheduler(KvRouterConfig(budget_pressure_weight=2.0))
        sched.update_load(self._snap(1, budget_state=BUDGET_STATE_FLOOR))
        sched.update_load(self._snap(2))
        # Tie on load; worker 1 would win the (logit, key) tie-break if
        # the budget term didn't price its prefill up.
        chosen = sched.select_worker(10, OverlapScores())
        assert chosen == (2, 0)

    def test_weight_zero_disables_the_term(self):
        sched = KvScheduler(KvRouterConfig(budget_pressure_weight=0.0))
        sched.update_load(self._snap(1, budget_state=BUDGET_STATE_FLOOR))
        sched.update_load(self._snap(2))
        assert sched.select_worker(10, OverlapScores()) == (1, 0)

    def test_overlap_can_still_beat_the_pressure(self):
        """The term scales the MISS blocks: a budgeted worker holding the
        whole prefix has nothing to prefill and stays the right answer."""
        sched = KvScheduler(KvRouterConfig(budget_pressure_weight=2.0))
        sched.update_load(self._snap(1, budget_state=BUDGET_STATE_FLOOR))
        sched.update_load(self._snap(2))
        overlaps = OverlapScores(scores={(1, 0): 10}, matched_blocks=10)
        assert sched.select_worker(10, overlaps) == (1, 0)

    def test_throughput_state_carries_no_pressure(self):
        sched = KvScheduler(KvRouterConfig(budget_pressure_weight=2.0))
        sched.update_load(
            self._snap(1, budget_state=BUDGET_STATE_THROUGHPUT)
        )
        sched.update_load(self._snap(2))
        assert sched.select_worker(10, OverlapScores()) == (1, 0)


# -- planner rebalance hold ------------------------------------------------------


class _NullConnector:
    async def apply(self, plan):
        pass


def _planner(**cfg_over):
    cfg_kwargs = dict(
        adjustment_interval_s=0.05,
        itl_target_s=0.02,
        ttft_target_s=0.5,
        max_replicas=16,
        total_chip_budget=64,
    )
    cfg_kwargs.update(cfg_over)
    prefill = PrefillInterpolator(
        isl=[128, 512, 1024],
        ttft_s=[0.1, 0.4, 0.9],
        tokens_per_s=[1280, 1280, 1137],
    )
    decode = DecodeInterpolator(
        concurrency=[1, 4, 8, 16],
        itl_s=[0.005, 0.010, 0.020, 0.045],
        tokens_per_s=[200, 400, 400, 355],
    )
    snaps = {"snap": MetricsSnapshot()}

    async def metrics():
        return snaps["snap"]

    planner = Planner(
        PlannerConfig(**cfg_kwargs),
        prefill,
        decode,
        _NullConnector(),
        metrics,
    )
    return planner, snaps


class TestPlannerRebalance:
    async def _seed(self, planner, snaps, rate):
        snaps["snap"] = MetricsSnapshot(
            request_rate=rate, mean_isl=512, mean_osl=64
        )
        return await planner.step()

    async def test_fat_budgets_hold_the_launch_once(self):
        planner, snaps = _planner()
        low = await self._seed(planner, snaps, 1.0)
        assert low is not None
        # Demand jumps AND ITL breaches, but the fleet's budgeters are
        # fat (headroom 1.0): rebalance intra-chip, don't launch.
        snaps["snap"] = MetricsSnapshot(
            request_rate=20.0,
            mean_isl=512,
            mean_osl=64,
            p50_itl_s=0.030,
            prefill_budget_frac=1.0,
        )
        held = await planner.step()
        assert held.decode == low.decode
        assert "budget-rebalance" in held.reason
        # Budgets spent to the floor, ITL still breaching: scale out.
        snaps["snap"] = MetricsSnapshot(
            request_rate=20.0,
            mean_isl=512,
            mean_osl=64,
            p50_itl_s=0.030,
            prefill_budget_frac=0.0,
        )
        scaled = await planner.step()
        assert scaled.decode > low.decode
        assert "budget-rebalance" not in scaled.reason

    async def test_no_budget_signal_scales_as_before(self):
        planner, snaps = _planner()
        low = await self._seed(planner, snaps, 1.0)
        snaps["snap"] = MetricsSnapshot(
            request_rate=20.0, mean_isl=512, mean_osl=64, p50_itl_s=0.030
        )
        scaled = await planner.step()
        assert scaled.decode > low.decode

    async def test_healthy_itl_never_holds(self):
        planner, snaps = _planner()
        low = await self._seed(planner, snaps, 1.0)
        snaps["snap"] = MetricsSnapshot(
            request_rate=20.0,
            mean_isl=512,
            mean_osl=64,
            p50_itl_s=0.005,
            prefill_budget_frac=1.0,
        )
        scaled = await planner.step()
        assert scaled.decode > low.decode


# -- watermark hold keeps decode cadence (regression) ----------------------------


class TestWatermarkRollover:
    async def test_watermark_held_engine_keeps_decoding(self):
        """KV watermark holds admission while a stream decodes: the tick
        must spend its slack on decode (rollover), never idle — the
        running stream finishes its full output and the unspent prefill
        budget is counted as rolled over."""
        engine = JaxEngine(
            _eng_args(
                num_kv_blocks=16,
                max_num_seqs=2,
                max_model_len=64,
                admit_kv_high_watermark=0.30,
                tick_budget_enabled=True,
                tick_budget_floor_tokens=32,
                tick_budget_ceiling_tokens=128,
            )
        )
        try:
            a = _req(range(10, 26), max_tokens=24, rid="a")
            b = _req(range(30, 46), max_tokens=4, rid="b")

            async def submit_b_late():
                # Wait until A occupies a slot (its blocks put usage at
                # 5/16 ≥ 0.30 → B is watermark-held until A frees them).
                while not any(s is not None for s in engine._slots):
                    await asyncio.sleep(0.002)
                return await collect(engine.generate(b, Context()))

            a_out, b_out = await asyncio.gather(
                collect(engine.generate(a, Context())), submit_b_late()
            )
            a_toks = [t for o in a_out for t in (o.token_ids or [])]
            b_toks = [t for o in b_out for t in (o.token_ids or [])]
            assert len(a_toks) == 24  # full cadence: A never starved
            assert len(b_toks) == 4  # held work still completes after
            assert engine.stats()["budget_rollovers"] > 0
        finally:
            await engine.stop()


# -- interleaving: what the budget is for ------------------------------------


class TestInterleaving:
    async def _wave_on_a_decoding_stream(self, **budget):
        """Stream a decodes; long-prompt b (80 tokens, chunk 32) arrives
        once a has emitted. Returns the tick loop's event kinds from b's
        arrival to b's install, and both streams' lengths."""
        engine = JaxEngine(_eng_args(max_num_seqs=2, **budget))
        try:
            a_outs = []

            async def consume_a():
                async for o in engine.generate(
                    _req(range(10, 20), max_tokens=40, rid="a"), Context()
                ):
                    a_outs.append(o)

            async def submit_b_after_two():
                while sum(1 for o in a_outs if o.token_ids) < 2:
                    await asyncio.sleep(0.002)
                return await collect(
                    engine.generate(
                        _req(range(100, 180), max_tokens=6, rid="b"), Context()
                    )
                )

            _, b_outs = await asyncio.gather(consume_a(), submit_b_after_two())
            events = engine.flight.snapshot()
            admit_b = next(
                i for i, e in enumerate(events)
                if e["kind"] == "admit" and e.get("request_id") == "b"
            )
            return (
                [e["kind"] for e in events[:admit_b]],
                sum(len(o.token_ids or []) for o in a_outs),
                sum(len(o.token_ids or []) for o in b_outs),
            )
        finally:
            await engine.stop()

    async def test_a_decode_burst_runs_behind_every_parked_chunk(self):
        """Budgeted, the long prefill parks at chunk boundaries and each
        resume waits behind a decode burst: between any two parks the
        running stream was reaped. Unbudgeted, the same prefill runs to
        completion inside its tick and never parks. Neither mode drops
        work."""
        kinds, a_n, b_n = await self._wave_on_a_decoding_stream(
            tick_budget_enabled=True, tick_budget_floor_tokens=16,
            tick_budget_ceiling_tokens=64, tick_budget_policy=0.0,
        )
        parks = [i for i, k in enumerate(kinds) if k == "prefill_pause"]
        assert len(parks) >= 2, kinds
        for lo, hi in zip(parks, parks[1:]):
            assert "reap" in kinds[lo:hi], kinds[lo:hi + 1]
        assert "reap" in kinds[parks[-1]:], "b installed with no burst behind its last park"
        assert (a_n, b_n) == (40, 6)

        kinds, a_n, b_n = await self._wave_on_a_decoding_stream()
        assert "prefill_pause" not in kinds
        assert (a_n, b_n) == (40, 6)
