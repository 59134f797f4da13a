"""Overload armor (runtime/overload.py + wiring): bounded EDF admission,
deadline propagation end to end, engine-side shed/backpressure, the
brownout state machine, and the structured client-visible error taxonomy.

The two acceptance scenarios:

  * saturation — at several times the sustainable offered load the queue
    stays bounded, excess requests get typed 429 + Retry-After, a request
    whose deadline is (or goes) dead is NEVER admitted to an engine, and
    every admitted stream completes token-exact;
  * brownout — a p50-ITL SLA breach drives healthy→brownout (spec decode
    suspended, max_tokens clamped) and recovery re-arms with hysteresis,
    every transition on the "overload" flight ring and metric families.
"""

import asyncio
import time

import aiohttp
import pytest

from dynamo_tpu.disagg.errors import DisaggTransferError
from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.http import HttpService, ModelManager
from dynamo_tpu.llm.migration import Migration
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    PostprocessedOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import tiny_config
from dynamo_tpu.runtime import fault_names as fn
from dynamo_tpu.runtime import faults
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.discovery import MemoryDiscovery
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import collect
from dynamo_tpu.runtime.network.tcp import TcpRequestPlane
from dynamo_tpu.runtime.overload import (
    BROWNOUT,
    HEALTHY,
    SHED,
    OverloadConfig,
    OverloadController,
    OverloadShedError,
)


@pytest.fixture(autouse=True)
def _disarmed():
    faults.disarm()
    yield
    faults.disarm()


# -- admission controller (unit) ---------------------------------------------


class TestAdmission:
    async def test_edf_orders_grants_by_deadline(self):
        c = OverloadController(OverloadConfig(max_concurrency=1))
        first = await c.admit(Context(), request_id="first")
        late = asyncio.ensure_future(
            c.admit(Context(deadline=time.monotonic() + 60), request_id="late")
        )
        await asyncio.sleep(0.01)
        soon = asyncio.ensure_future(
            c.admit(Context(deadline=time.monotonic() + 5), request_id="soon")
        )
        none = asyncio.ensure_future(c.admit(Context(), request_id="none"))
        await asyncio.sleep(0.01)
        assert not late.done() and not soon.done() and not none.done()
        # EDF: the NEAREST deadline wins the freed slot, deadline-less last.
        c.release(first)
        await asyncio.sleep(0.01)
        assert soon.done() and not late.done() and not none.done()
        c.release(await soon)
        await asyncio.sleep(0.01)
        assert late.done() and not none.done()
        c.release(await late)
        c.release(await none)
        assert c.snapshot()["admitted"] == 4
        assert c.snapshot()["sheds"] == {}

    async def test_bounded_queue_sheds_429_with_retry_after(self):
        c = OverloadController(
            OverloadConfig(max_concurrency=1, max_queue_depth=1)
        )
        t = await c.admit(Context())
        waiter = asyncio.ensure_future(c.admit(Context()))
        await asyncio.sleep(0.01)
        with pytest.raises(OverloadShedError) as ei:
            await c.admit(Context())
        assert ei.value.reason == "queue_full"
        assert ei.value.status == 429
        assert ei.value.retry_after is not None and ei.value.retry_after > 0
        c.release(t)
        c.release(await waiter)
        assert c.sheds == {"queue_full": 1}
        assert any(
            e["kind"] == "shed" and e["reason"] == "queue_full"
            for e in c.flight.snapshot()
        )

    async def test_predicted_delay_sheds_before_queueing(self):
        c = OverloadController(
            OverloadConfig(max_concurrency=1, max_queue_depth=100,
                           max_queue_delay_s=0.5)
        )
        # Teach the estimator a 1s service time.
        t = await c.admit(Context())
        t.t_admit -= 1.0  # the request "took" 1s
        c.release(t)
        held = await c.admit(Context())
        # Position 0 waits ~1 predicted second > the 0.5s bound → shed
        # without ever entering the queue.
        with pytest.raises(OverloadShedError) as ei:
            await c.admit(Context())
        assert ei.value.reason == "predicted_delay"
        assert ei.value.status == 429
        assert ei.value.retry_after >= 1.0
        c.release(held)

    async def test_dead_on_arrival_and_mid_queue_expiry_shed_504(self):
        c = OverloadController(OverloadConfig(max_concurrency=1))
        with pytest.raises(OverloadShedError) as ei:
            await c.admit(Context(deadline=time.monotonic() - 0.1))
        assert ei.value.reason == "deadline_expired" and ei.value.status == 504
        # Mid-queue expiry: budget runs out while waiting for capacity.
        held = await c.admit(Context())
        with pytest.raises(OverloadShedError) as ei:
            await c.admit(Context(deadline=time.monotonic() + 0.05))
        assert ei.value.reason == "deadline_expired" and ei.value.status == 504
        c.release(held)
        snap = c.snapshot()
        assert snap["deadline_expired"] == 2
        assert c.metrics.deadline_expired.value() == 2

    async def test_expired_waiter_is_shed_at_grant_not_admitted(self):
        """A queued waiter whose deadline passes is refused at GRANT time
        too (belt and braces around the wait_for timeout): capacity flows
        to the next live waiter instead."""
        c = OverloadController(OverloadConfig(max_concurrency=1))
        held = await c.admit(Context())
        dead_ctx = Context()
        dead_ctx.set_deadline(time.monotonic() + 0.02)
        dying = asyncio.ensure_future(c.admit(dead_ctx))
        live = asyncio.ensure_future(c.admit(Context()))
        await asyncio.sleep(0.06)  # the 20ms budget expires in-queue
        c.release(held)
        with pytest.raises(OverloadShedError) as ei:
            await dying
        assert ei.value.reason == "deadline_expired"
        ticket = await live
        c.release(ticket)
        assert c.snapshot()["admitted"] == 2  # held + live, never dying

    async def test_abandoned_waiters_do_not_grow_the_heap_unboundedly(self):
        """Short-deadline arrivals that expire while long streams hold
        every slot must not accumulate in the EDF heap forever (grants —
        the lazy reap point — only happen on release)."""
        c = OverloadController(
            OverloadConfig(max_concurrency=1, max_queue_depth=10_000)
        )
        held = await c.admit(Context())
        for i in range(300):
            with pytest.raises(OverloadShedError):
                await c.admit(
                    Context(deadline=time.monotonic() + 0.001),
                    request_id=f"d{i}",
                )
        assert c._queued == 0
        assert len(c._heap) <= 128  # compacted, not 300 dead entries
        c.release(held)

    async def test_cancelled_waiter_vacates_its_queue_slot(self):
        """A client that disconnects mid-queue (task cancellation) must
        give its queue slot back — the live-waiter count drives the
        queue_full shed and the depth gauge."""
        c = OverloadController(OverloadConfig(max_concurrency=1))
        held = await c.admit(Context())
        w = asyncio.ensure_future(c.admit(Context()))
        await asyncio.sleep(0.01)
        assert c._queued == 1
        w.cancel()
        await asyncio.sleep(0.01)
        assert w.cancelled()
        assert c._queued == 0
        c.release(held)
        assert c._active == 0

    async def test_fault_seam_expires_a_specific_queued_request(self):
        """overload.admit chaos seam: an injected timeout at hit N expires
        exactly the Nth QUEUED admission — deterministic mid-queue expiry,
        bit-identical on replay (no wall clocks involved)."""

        async def run():
            c = OverloadController(OverloadConfig(max_concurrency=1))
            held = await c.admit(Context())  # fast path: no seam hit
            results = []

            async def one(tag):
                try:
                    t = await c.admit(Context(), request_id=tag)
                    results.append((tag, "admitted"))
                    c.release(t)
                except OverloadShedError as exc:
                    results.append((tag, exc.reason))

            tasks = [asyncio.ensure_future(one(f"q{i}")) for i in range(3)]
            await asyncio.sleep(0.02)
            c.release(held)
            await asyncio.gather(*tasks)
            return results, list(faults.active_plane().trace)

        plan = faults.FaultPlan(seed=3, rules=(
            faults.FaultRule(point=fn.OVERLOAD_ADMIT, at=(2,), kind="timeout"),
        ))
        with faults.armed(plan):
            r1, t1 = await run()
        with faults.armed(plan):
            r2, t2 = await run()
        assert r1 == r2 and t1 == t2  # bit-identical replay
        assert ("q1", "deadline_expired") in r1  # exactly the 2nd queued
        assert ("q0", "admitted") in r1 and ("q2", "admitted") in r1
        assert t1 == [(fn.OVERLOAD_ADMIT, 2, 0, "timeout")]


# -- brownout state machine (acceptance: fake clock) -------------------------


class TestBrownout:
    def _controller(self, occupancy=None):
        now = [0.0]
        cfg = OverloadConfig(
            itl_sla_s=0.020, shed_itl_factor=3.0,
            min_itl_samples=4, itl_window=16,
            brownout_after=3, recover_after=4,
            brownout_max_tokens=256,
        )
        c = OverloadController(
            cfg, clock=lambda: now[0],
            occupancy_source=(lambda: occupancy[0]) if occupancy else None,
        )
        return c, now

    def _feed(self, c, itl_s, n=16):
        for _ in range(n):
            c.observe_itl(itl_s)

    async def test_itl_breach_drives_brownout_then_shed_then_recovery(self):
        c, now = self._controller()
        engine = JaxEngine(JaxEngineArgs(
            config=tiny_config(), block_size=4, num_kv_blocks=16,
            max_num_seqs=2, max_model_len=64, spec_mode="ngram",
        ))
        try:
            c.on_transition(lambda _o, new: engine.set_spec_suspended(new > 0))
            assert engine._pipeline_depth() == 1  # spec engine, sync tick
            # Healthy ITLs: no transition no matter how many evaluations.
            self._feed(c, 0.010)
            for _ in range(10):
                now[0] += 1.0
                assert c.evaluate() == HEALTHY
            # SLA breached (30ms > 20ms): hysteresis holds for 2 evals...
            self._feed(c, 0.030)
            now[0] += 1.0
            assert c.evaluate() == HEALTHY
            now[0] += 1.0
            assert c.evaluate() == HEALTHY
            # ...and trips on the 3rd consecutive breach.
            now[0] += 1.0
            assert c.evaluate() == BROWNOUT
            # Brownout actions: spec decode off, max_tokens clamped.
            assert engine._spec_suspended is True
            assert engine._pipeline_depth() == 2  # fused path pipelines again
            assert not c.spec_enabled()
            assert c.clamp_max_tokens(4096) == 256
            assert c.clamp_max_tokens(None) == 256
            assert c.clamp_max_tokens(8) == 8
            # Not critical (30 < 3×20=60): brownout holds, no shed.
            for _ in range(6):
                now[0] += 1.0
                assert c.evaluate() == BROWNOUT
            # Catastrophic ITL (100ms > 60ms) escalates after hysteresis.
            self._feed(c, 0.100)
            states = []
            for _ in range(3):
                now[0] += 1.0
                states.append(c.evaluate())
            assert states[-1] == SHED
            # Shed state refuses NEW admissions 503 (admitted streams run).
            with pytest.raises(OverloadShedError) as ei:
                await c.admit(Context())
            assert ei.value.reason == "brownout_shed"
            assert ei.value.status == 503
            # Recovery: clean ITLs step DOWN one state per filled streak —
            # a single good evaluation must NOT flap the state back.
            self._feed(c, 0.005)
            now[0] += 1.0
            assert c.evaluate() == SHED  # 1 good eval: no flap
            for _ in range(3):
                now[0] += 1.0
                c.evaluate()
            assert c.state == BROWNOUT  # one step down after 4 clean
            assert engine._spec_suspended is True  # still degraded
            for _ in range(4):
                now[0] += 1.0
                c.evaluate()
            assert c.state == HEALTHY
            assert engine._spec_suspended is False  # spec re-armed
            assert c.clamp_max_tokens(4096) == 4096
            # Every transition on the overload flight ring + families.
            trans = [
                (e["frm"], e["to"])
                for e in c.flight.snapshot() if e["kind"] == "state"
            ]
            assert trans == [
                ("healthy", "brownout"), ("brownout", "shed"),
                ("shed", "brownout"), ("brownout", "healthy"),
            ]
            assert c.metrics.transitions.value(to="brownout") == 2
            assert c.metrics.transitions.value(to="shed") == 1
            assert c.metrics.transitions.value(to="healthy") == 1
            assert c.transitions == {"brownout": 2, "shed": 1, "healthy": 1}
        finally:
            await engine.stop()

    async def test_one_critical_sample_atop_a_breach_streak_does_not_shed(self):
        """brownout → shed needs brownout_after CONSECUTIVE critical
        evaluations: a long non-critical breach streak plus ONE noisy
        critical window (a GC or compile pause inflating the p50 for a
        single evaluation) must not slam the frontend to SHED."""
        c, now = self._controller()
        self._feed(c, 0.030)
        for _ in range(3):
            now[0] += 1.0
            c.evaluate()
        assert c.state == BROWNOUT
        # Sustained non-critical breach: the streak grows far past
        # brownout_after without escalating.
        for _ in range(5):
            now[0] += 1.0
            assert c.evaluate() == BROWNOUT
        # One critical window (100ms > 3×20ms)...
        self._feed(c, 0.100)
        now[0] += 1.0
        assert c.evaluate() == BROWNOUT  # 1 < brownout_after: holds
        # ...then back to merely-breached: still brownout, never shed.
        self._feed(c, 0.030)
        for _ in range(4):
            now[0] += 1.0
            assert c.evaluate() == BROWNOUT
        assert c.transitions.get("shed", 0) == 0

    async def test_shed_recovers_after_traffic_stops_via_sample_ttl(self):
        """A SHED controller that stopped admitting gets no fresh ITL
        samples — the congested-era window must AGE OUT (itl_sample_ttl_s)
        so recovery evidence can accumulate, not testify against recovery
        forever (permanent-lockout regression)."""
        c, now = self._controller()
        self._feed(c, 0.100)  # way past 3×SLA
        for _ in range(6):
            now[0] += 1.0
            c.evaluate()
        assert c.state == SHED
        # No new samples ever arrive (nothing is admitted). Advance past
        # the TTL: the stale p50 decays to unknown → clean evaluations.
        now[0] += c.config.itl_sample_ttl_s + 1.0
        for _ in range(4):
            now[0] += 1.0
            c.evaluate()
        assert c.state == BROWNOUT
        for _ in range(4):
            now[0] += 1.0
            c.evaluate()
        assert c.state == HEALTHY

    async def test_rapid_evaluations_are_one_hysteresis_step(self):
        """evaluate() calls inside min_eval_interval_s must not advance
        the streaks — hysteresis denominates time, not request rate."""
        c, now = self._controller()
        self._feed(c, 0.030)
        # 100 evaluations at the same fake instant: at most ONE step.
        for _ in range(100):
            c.evaluate()
        assert c.state == HEALTHY
        # Properly spaced evaluations still trip after brownout_after.
        for _ in range(3):
            now[0] += 1.0
            c.evaluate()
        assert c.state == BROWNOUT

    async def test_occupancy_watermark_alone_can_brown_out(self):
        occ = [0.5]
        c, now = self._controller(occupancy=occ)
        for _ in range(5):
            now[0] += 1.0
            assert c.evaluate() == HEALTHY
        occ[0] = 0.97  # past occupancy_high
        for _ in range(2):
            now[0] += 1.0
            c.evaluate()
        now[0] += 1.0
        assert c.evaluate() == BROWNOUT


# -- router backpressure ------------------------------------------------------


class TestRouterBackpressure:
    def _snap(self, wid, *, active=0, total=100, queue=0, wm=1.0):
        from dynamo_tpu.router.protocols import LoadSnapshot

        return LoadSnapshot(
            worker_id=wid, active_blocks=active, total_blocks=total,
            queue_depth=queue, kv_high_watermark=wm,
        )

    def test_queue_depth_penalty_flips_placement(self):
        from dynamo_tpu.router.scheduler import KvRouterConfig, KvScheduler
        from dynamo_tpu.tokens.radix import OverlapScores

        sched = KvScheduler(KvRouterConfig(queue_depth_weight=4.0))
        a, b = (1, 0), (2, 0)
        # A is slightly less block-loaded but has a deep admission queue.
        sched.update_load(self._snap(1, active=10, queue=20))
        sched.update_load(self._snap(2, active=20, queue=0))
        chosen = sched.select_worker(
            4, OverlapScores(scores={}), [a, b]
        )
        assert chosen == b  # 10 + 4×20 = 90 loses to 20
        # Same state, penalty off: the raw block load wins again.
        sched0 = KvScheduler(KvRouterConfig(queue_depth_weight=0.0))
        sched0.update_load(self._snap(1, active=10, queue=20))
        sched0.update_load(self._snap(2, active=20, queue=0))
        assert sched0.select_worker(4, OverlapScores(scores={}), [a, b]) == a

    def test_saturated_worker_deflected_until_all_are(self):
        from dynamo_tpu.router.scheduler import KvRouterConfig, KvScheduler
        from dynamo_tpu.tokens.radix import OverlapScores

        sched = KvScheduler(KvRouterConfig())
        a, b = (1, 0), (2, 0)
        # A advertises a 0.9 watermark and sits past it (96%): even with a
        # big prefix-overlap win it is deflected to the unsaturated B.
        sched.update_load(self._snap(1, active=96, wm=0.9))
        sched.update_load(self._snap(2, active=50, wm=0.9))
        chosen = sched.select_worker(
            8, OverlapScores(scores={a: 8}), [a, b]
        )
        assert chosen == b
        # All saturated: least-loaded still wins (shedding is the
        # frontend's job, the router must always produce a placement).
        sched.update_load(self._snap(2, active=97, wm=0.9))
        chosen = sched.select_worker(
            8, OverlapScores(scores={a: 8}), [a, b]
        )
        assert chosen == a  # overlap win matters again among equals
        # A worker that never advertised a watermark is never "saturated".
        sched2 = KvScheduler(KvRouterConfig())
        sched2.update_load(self._snap(1, active=99, wm=1.0))
        assert not sched2._workers[a].saturated()


# -- deadline propagation -----------------------------------------------------


async def test_deadline_rides_the_tcp_request_plane():
    """The wire carries REMAINING seconds and the server re-anchors them:
    a worker-side handler sees (approximately) the client's budget."""
    disco = MemoryDiscovery()
    worker_rt = DistributedRuntime(
        discovery=disco, request_plane=TcpRequestPlane(), bus="ovl-tcp"
    )
    frontend_rt = DistributedRuntime(
        discovery=disco, request_plane=TcpRequestPlane(), bus="ovl-tcp"
    )

    async def handler(request, context):
        yield {"remaining": context.time_remaining()}

    ep = worker_rt.namespace("n").component("c").endpoint("gen")
    served = await ep.serve_endpoint(handler)
    client = (
        await frontend_rt.namespace("n").component("c").endpoint("gen").client()
    )
    try:
        out = await collect(
            client.generate({}, Context(deadline=time.monotonic() + 5.0))
        )
        assert out and 3.0 < out[0]["remaining"] <= 5.0
        # No deadline → no budget on the far side.
        out = await collect(client.generate({}, Context()))
        assert out[0]["remaining"] is None
    finally:
        await client.close()
        await served.shutdown(grace_period=1)
        await frontend_rt.shutdown(grace_period=1)
        await worker_rt.shutdown(grace_period=1)


async def test_deadline_rides_the_http_request_plane():
    """DYN_TPU_REQUEST_PLANE=http parity: the X-Dynamo-Deadline-S header
    carries REMAINING seconds, re-anchored server-side — selecting the
    HTTP plane must not silently drop the client's budget."""
    from dynamo_tpu.runtime.network.http_plane import HttpRequestPlane

    disco = MemoryDiscovery()
    worker_rt = DistributedRuntime(
        discovery=disco, request_plane=HttpRequestPlane(), bus="ovl-http"
    )
    frontend_rt = DistributedRuntime(
        discovery=disco, request_plane=HttpRequestPlane(), bus="ovl-http"
    )

    async def handler(request, context):
        yield {"remaining": context.time_remaining()}

    ep = worker_rt.namespace("n").component("c").endpoint("gen")
    served = await ep.serve_endpoint(handler)
    client = (
        await frontend_rt.namespace("n").component("c").endpoint("gen").client()
    )
    try:
        out = await collect(
            client.generate({}, Context(deadline=time.monotonic() + 5.0))
        )
        assert out and 3.0 < out[0]["remaining"] <= 5.0
        # No deadline → no budget on the far side.
        out = await collect(client.generate({}, Context()))
        assert out[0]["remaining"] is None
    finally:
        await client.close()
        await served.shutdown(grace_period=1)
        await frontend_rt.shutdown(grace_period=1)
        await worker_rt.shutdown(grace_period=1)


# -- engine-side shed + backpressure ------------------------------------------


def _engine(**over):
    defaults = dict(
        config=tiny_config(), block_size=4, num_kv_blocks=64,
        max_num_seqs=4, max_model_len=128, prefill_chunk=32, decode_steps=4,
    )
    defaults.update(over)
    return JaxEngine(JaxEngineArgs(**defaults))


def _req(tokens, max_tokens=8, rid="r"):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens),
    )


async def test_engine_sheds_expired_deadline_before_prefill():
    """A request whose deadline died in the queue is shed AT DEQUEUE with
    a typed error — zero prefill tokens are ever spent on it."""
    engine = _engine()
    try:
        ctx = Context(deadline=time.monotonic() - 0.5)
        outs = await collect(engine.generate(_req(range(10, 26)), ctx))
        assert outs
        last = outs[-1]
        assert last.error and "deadline" in last.error
        assert last.error_kind == "timeout"
        assert last.finish_reason == FinishReason.ERROR
        assert engine.prefill_tokens == 0  # shed BEFORE prefill
        assert engine.deadline_sheds == 1
        assert engine.stats()["deadline_sheds"] == 1
        assert any(
            e["kind"] == "deadline_shed" for e in engine.flight.snapshot()
        )
    finally:
        await engine.stop()


async def test_engine_plain_cancellation_stays_quiet_cancelled():
    engine = _engine()
    try:
        ctx = Context()
        ctx.stop_generating(reason="client-gone")
        outs = await collect(engine.generate(_req(range(10, 18)), ctx))
        assert outs[-1].finish_reason == FinishReason.CANCELLED
        assert outs[-1].error is None
        assert engine.deadline_sheds == 0
    finally:
        await engine.stop()


async def test_admission_holds_at_high_watermark_instead_of_preempting():
    """Past admit_kv_high_watermark with live occupants the engine HOLDS
    the waiting queue (no admission, no preemption storm); the held
    request admits once the occupant finishes and completes normally."""
    engine = _engine(num_kv_blocks=16, admit_kv_high_watermark=0.3)
    try:
        a_ctx = Context()
        a_task = asyncio.ensure_future(
            collect(engine.generate(_req(range(10, 34), max_tokens=40, rid="a"), a_ctx))
        )
        # Wait until A is running (its 6 prompt blocks = 0.375 > 0.3
        # from the moment of admission — no decode-growth race).
        for _ in range(200):
            await asyncio.sleep(0.01)
            if engine.stats()["active_seqs"] == 1:
                break
        assert engine.stats()["active_seqs"] == 1
        b_task = asyncio.ensure_future(
            collect(engine.generate(_req(range(40, 56), max_tokens=4, rid="b"), Context()))
        )
        # B must be HELD (queued), not admitted and not preempting A.
        # Observed on the live deque: the published stats snapshot only
        # refreshes at tick boundaries, which the first decode compile
        # can delay by seconds on CPU.
        saw_held = False
        for _ in range(600):
            await asyncio.sleep(0.05)
            held = (
                len(engine._waiting) == 1
                and sum(1 for s in engine._slots if s is not None) == 1
            )
            if held:
                saw_held = True
                break
            if b_task.done():
                break
        assert saw_held, "B was admitted past the high watermark"
        assert engine.preemptions == 0
        a_out = await a_task
        b_out = await b_task
        assert sum(len(o.token_ids or []) for o in a_out) == 40
        assert sum(len(o.token_ids or []) for o in b_out) == 4
        assert engine.preemptions == 0  # backpressure, not a storm
    finally:
        await engine.stop()



async def test_armor_in_front_of_a_real_engine_sheds_before_any_prefill():
    """The controller in front of a JaxEngine, ten arrivals at once against
    two slots and a queue of two: the excess is shed typed before it costs
    the engine a token, a budget that dies in the queue (the admission
    seam's injected timeout) never reaches the engine either, and every
    admitted stream is the engine's own full-length greedy stream."""
    engine = _engine()
    ctrl = OverloadController(
        OverloadConfig(max_concurrency=2, max_queue_depth=2,
                       max_queue_delay_s=60.0)
    )
    prompts = [list(range(10 + 9 * i, 26 + 9 * i)) for i in range(10)]

    async def one(i):
        ctx = Context()
        try:
            ticket = await ctrl.admit(ctx, request_id=f"r{i}")
        except OverloadShedError as exc:
            return ("shed", exc.reason)
        try:
            outs = await collect(
                engine.generate(_req(prompts[i], max_tokens=12, rid=f"r{i}"), ctx)
            )
            assert not any(o.error for o in outs), outs
            return ("ok", [t for o in outs for t in (o.token_ids or [])])
        finally:
            ctrl.release(ticket)

    try:
        want = []
        for i in range(5):
            outs = await collect(
                engine.generate(_req(prompts[i], max_tokens=12), Context())
            )
            want.append([t for o in outs for t in (o.token_ids or [])])
        prefill0 = engine.prefill_tokens

        # 0,1 take the slots; the seam expires 2 as it joins the queue;
        # 3 and 4 queue; 5..9 find the queue full.
        plan = faults.FaultPlan(seed=3, rules=(
            faults.FaultRule(point=fn.OVERLOAD_ADMIT, at=(1,), kind="timeout"),
        ))
        with faults.armed(plan):
            got = await asyncio.gather(*(one(i) for i in range(10)))
        assert got[:2] == [("ok", want[0]), ("ok", want[1])]
        assert got[2] == ("shed", "deadline_expired")
        assert got[3:5] == [("ok", want[3]), ("ok", want[4])]
        assert got[5:] == [("shed", "queue_full")] * 5
        # four streams reached the engine; prefix hits may spare tokens,
        # a shed request can add none
        assert engine.prefill_tokens - prefill0 <= 4 * len(prompts[0])
        assert engine.deadline_sheds == 0
        assert {e["request_id"] for e in engine.flight.snapshot()
                if e["kind"] == "admit"} <= {"r", "r0", "r1", "r3", "r4"}
        snap = ctrl.snapshot()
        assert snap["sheds"] == {"queue_full": 5, "deadline_expired": 1}
        assert snap["admitted"] == 4
        assert snap["queue_depth"] == 0
        assert ctrl.peak_queue_depth <= 2
    finally:
        await engine.stop()

# -- HTTP frontend: saturation acceptance + error taxonomy --------------------


class StubPipeline:
    """Stands in for the assembled pipeline behind ModelManager: a
    deterministic token stream with a controlled per-token latency.
    Records which requests actually STARTED generating — the saturation
    test's proof that shed/expired requests never reached an engine."""

    def __init__(self, tokens=6, itl_s=0.0):
        self.tokens = tokens
        self.itl_s = itl_s
        self.started = []
        self.remaining_seen = []
        self.fail_with = None  # exception raised before the first item

    async def generate(self, body, context):
        if self.fail_with is not None:
            raise self.fail_with
        self.started.append(context.id)
        self.remaining_seen.append(context.time_remaining())
        yield {"annotation": "_prompt_tokens", "value": 3}
        for i in range(self.tokens):
            if self.itl_s:
                await asyncio.sleep(self.itl_s)
            yield PostprocessedOutput(
                text=f"t{i} ", token_ids=[100 + i], cumulative_tokens=i + 1
            )
        yield PostprocessedOutput(
            finish_reason=FinishReason.LENGTH, cumulative_tokens=self.tokens
        )


async def _start_service(stub, overload=None):
    manager = ModelManager()
    card = ModelDeploymentCard(name="stub", context_length=512)
    manager.register("stub", stub, card)
    service = HttpService(
        manager, host="127.0.0.1", port=0, overload=overload
    )
    port = await service.start()
    return service, port


EXPECTED_TEXT = "t0 t1 t2 t3 t4 t5 "


async def test_http_saturation_bounded_queue_typed_sheds_token_exact():
    """THE saturation acceptance: offered load far past capacity. The
    queue stays bounded, excess sheds 429 + Retry-After, deadline-carrying
    requests whose budget dies mid-queue shed 504 BEFORE reaching the
    engine, and every admitted stream completes token-exact."""
    # ≥ 600 ms of service, 250 ms deadlines: the burst, posted at 70 ms,
    # has 180 ms to reach the server before the deadlines free two queue
    # slots (at 30 ms / 60 ms that margin was 40 ms, and a loaded CI host
    # once let 4 of the burst queue: 6 x 200 for 4)
    stub = StubPipeline(tokens=6, itl_s=0.1)
    ctrl = OverloadController(
        OverloadConfig(max_concurrency=2, max_queue_depth=4,
                       max_queue_delay_s=30.0)
    )
    service, port = await _start_service(stub, overload=ctrl)
    url = f"http://127.0.0.1:{port}/v1/completions"

    async def post(session, **kw):
        body = {"model": "stub", "prompt": "x", "max_tokens": 6}
        async with session.post(url, json=body, **kw) as resp:
            return resp.status, dict(resp.headers), await resp.json()

    try:
        async with aiohttp.ClientSession() as s:
            # 2 fillers occupy both slots.
            fillers = [asyncio.ensure_future(post(s)) for _ in range(2)]
            await asyncio.sleep(0.05)
            # 2 deadline-carrying requests queue (EDF-first) with a budget
            # far smaller than the fillers' remaining service time.
            dead = [
                asyncio.ensure_future(
                    post(s, headers={"x-dynamo-deadline-ms": "250"})
                )
                for _ in range(2)
            ]
            await asyncio.sleep(0.02)
            # 8 more: 2 fill the remaining queue slots, 6 shed queue_full.
            burst = [asyncio.ensure_future(post(s)) for _ in range(8)]
            all_results = await asyncio.gather(*fillers, *dead, *burst)
        by_status = {}
        for status, headers, body in all_results:
            by_status.setdefault(status, []).append((headers, body))
        assert len(by_status.get(200, [])) == 4  # 2 fillers + 2 queued
        assert len(by_status.get(504, [])) == 2  # both deadlines expired
        assert len(by_status.get(429, [])) == 6  # the excess, typed
        # Typed 429s carry Retry-After + the shed reason.
        for headers, body in by_status[429]:
            assert "Retry-After" in headers
            assert body["error"]["error_kind"] == "queue_full"
            assert body["error"]["type"] == "overloaded"
        for _headers, body in by_status[504]:
            assert body["error"]["type"] == "deadline_exceeded"
            assert body["error"]["error_kind"] == "timeout"
        # Every 200 is token-exact against the deterministic stub.
        for _headers, body in by_status[200]:
            assert body["choices"][0]["text"] == EXPECTED_TEXT
            assert body["usage"]["completion_tokens"] == 6
        # No shed/expired request EVER started on the engine, and no
        # request was admitted with an expired deadline.
        assert len(stub.started) == 4
        assert all(r is None for r in stub.remaining_seen)
        # Queue stayed bounded the whole time.
        assert ctrl.peak_queue_depth <= 4
        snap = ctrl.snapshot()
        assert snap["sheds"]["queue_full"] == 6
        assert snap["sheds"]["deadline_expired"] == 2
        assert snap["queue_depth"] == 0  # fully drained
        assert ctrl.metrics.shed.value(reason="queue_full") == 6
    finally:
        await service.stop(grace_period=1)


async def test_http_under_capacity_zero_sheds_zero_transitions():
    """The zero-spurious-activation contract: under-capacity traffic
    through the same armor sheds nothing and never leaves healthy."""
    stub = StubPipeline(tokens=6)
    ctrl = OverloadController(
        OverloadConfig(max_concurrency=4, max_queue_depth=8)
    )
    service, port = await _start_service(stub, overload=ctrl)
    try:
        async with aiohttp.ClientSession() as s:
            for _ in range(6):
                async with s.post(
                    f"http://127.0.0.1:{port}/v1/completions",
                    json={"model": "stub", "prompt": "x", "max_tokens": 6},
                ) as resp:
                    assert resp.status == 200
                    body = await resp.json()
                    assert body["choices"][0]["text"] == EXPECTED_TEXT
        snap = ctrl.snapshot()
        assert snap["sheds"] == {}
        assert snap["transitions"] == {}
        assert snap["state"] == "healthy"
        assert snap["admitted"] == 6
    finally:
        await service.stop(grace_period=1)


async def test_http_deadline_header_lands_in_engine_context():
    stub = StubPipeline(tokens=2)
    service, port = await _start_service(stub)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{port}/v1/completions",
                json={"model": "stub", "prompt": "x", "max_tokens": 2},
                headers={"x-dynamo-deadline-ms": "5000"},
            ) as resp:
                assert resp.status == 200
            # The body key works for header-less clients and is stripped.
            async with s.post(
                f"http://127.0.0.1:{port}/v1/completions",
                json={"model": "stub", "prompt": "x", "max_tokens": 2,
                      "deadline_ms": 4000},
            ) as resp:
                assert resp.status == 200
            async with s.post(
                f"http://127.0.0.1:{port}/v1/completions",
                json={"model": "stub", "prompt": "x", "deadline_ms": -5},
            ) as resp:
                assert resp.status == 400  # validated, not a 500
        assert len(stub.remaining_seen) == 2
        assert 3.0 < stub.remaining_seen[0] <= 5.0
        assert 2.0 < stub.remaining_seen[1] <= 4.0
    finally:
        await service.stop(grace_period=1)


# -- structured error taxonomy (satellite: a test per transport) --------------


async def test_sse_stream_emits_terminal_typed_error_event():
    """Streaming transport: a mid-stream terminal failure (the
    migration-exhausted shape — PostprocessedOutput.error + error_kind)
    surfaces as a typed SSE error frame, not a dropped stream."""

    class FailingPipeline(StubPipeline):
        async def generate(self, body, context):
            yield {"annotation": "_prompt_tokens", "value": 3}
            yield PostprocessedOutput(
                text="ok ", token_ids=[1], cumulative_tokens=1
            )
            yield PostprocessedOutput(
                error="stream failed after 3 migrations: link down",
                error_kind="connection",
                finish_reason=FinishReason.ERROR,
            )

    service, port = await _start_service(FailingPipeline())
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                json={"model": "stub", "stream": True,
                      "messages": [{"role": "user", "content": "hi"}]},
            ) as resp:
                assert resp.status == 200  # headers were long sent
                frames = []
                async for line in resp.content:
                    line = line.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        import json as _json

                        frames.append(_json.loads(line[len("data: "):]))
        errors = [f["error"] for f in frames if "error" in f]
        assert errors, "no terminal SSE error event"
        assert errors[-1]["error_kind"] == "connection"
        assert errors[-1]["type"] == "upstream_error"
        assert "migrations" in errors[-1]["message"]
    finally:
        await service.stop(grace_period=1)


async def test_unary_json_carries_error_kind_and_typed_status():
    """Unary transport: strict-mode DisaggTransferError → 502 +
    error_kind=disagg; an engine-side deadline shed → 504 +
    error_kind=timeout. Neither is a bare 500 anymore."""
    stub = StubPipeline()
    stub.fail_with = DisaggTransferError("pull failed; fallback disabled")
    service, port = await _start_service(stub)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{port}/v1/completions",
                json={"model": "stub", "prompt": "x"},
            ) as resp:
                assert resp.status == 502
                body = await resp.json()
                assert body["error"]["error_kind"] == "disagg"
                assert body["error"]["type"] == "upstream_error"

            class TimeoutPipeline(StubPipeline):
                async def generate(self, body, context):
                    yield PostprocessedOutput(
                        error="deadline expired before admission",
                        error_kind="timeout",
                        finish_reason=FinishReason.ERROR,
                    )

            service.models.register(
                "stub-t", TimeoutPipeline(),
                ModelDeploymentCard(name="stub-t", context_length=512),
            )
            async with s.post(
                f"http://127.0.0.1:{port}/v1/completions",
                json={"model": "stub-t", "prompt": "x"},
            ) as resp:
                assert resp.status == 504
                body = await resp.json()
                assert body["error"]["error_kind"] == "timeout"
                assert body["error"]["type"] == "deadline_exceeded"
    finally:
        await service.stop(grace_period=1)


async def test_responses_endpoint_rides_the_overload_plane():
    """/v1/responses maps onto the chat generation pipeline, so it rides
    the same armor as chat/completions: a mid-queue-expired deadline is a
    typed 504 that never reaches the engine, excess sheds 429, brownout
    clamps the output budget, and shed state refuses 503 — the overload
    plane has no tunnel-through endpoint."""

    class RecordingPipeline(StubPipeline):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.bodies = []

        async def generate(self, body, context):
            self.bodies.append(body)
            async for item in super().generate(body, context):
                yield item

    stub = RecordingPipeline(tokens=4, itl_s=0.05)  # ≥ 200ms service time
    ctrl = OverloadController(
        OverloadConfig(
            max_concurrency=1, max_queue_depth=1,
            brownout_max_tokens=256, recover_after=100,
        )
    )
    service, port = await _start_service(stub, overload=ctrl)
    url = f"http://127.0.0.1:{port}/v1/responses"

    async def post(session, extra=None, **kw):
        body = {"model": "stub", "input": "hi", **(extra or {})}
        async with session.post(url, json=body, **kw) as resp:
            return resp.status, dict(resp.headers), await resp.json()

    try:
        async with aiohttp.ClientSession() as s:
            filler = asyncio.ensure_future(post(s))
            await asyncio.sleep(0.05)
            # 60ms budget vs the filler's ≥200ms: expires mid-queue.
            dying = asyncio.ensure_future(
                post(s, headers={"x-dynamo-deadline-ms": "60"})
            )
            await asyncio.sleep(0.02)
            # The queue slot is taken: the next arrival sheds queue_full.
            status, headers, body = await post(s)
            assert status == 429
            assert "Retry-After" in headers
            assert body["error"]["error_kind"] == "queue_full"
            status, _h, body = await dying
            assert status == 504
            assert body["error"]["type"] == "deadline_exceeded"
            status, _h, body = await filler
            assert status == 200 and body["status"] == "completed"
        # Shed/expired requests never started on the engine, and the
        # admission slot drained back.
        assert len(stub.started) == 1
        assert ctrl._active == 0 and ctrl.snapshot()["queue_depth"] == 0
        # Brownout: the chat body the engine sees is clamped.
        ctrl._state = BROWNOUT
        async with aiohttp.ClientSession() as s:
            status, _h, _b = await post(s, extra={"max_output_tokens": 4096})
            assert status == 200
        assert stub.bodies[-1]["max_tokens"] == 256
        # Shed state refuses NEW responses admissions with a typed 503.
        ctrl._state = SHED
        async with aiohttp.ClientSession() as s:
            status, _h, body = await post(s)
        assert status == 503
        assert body["error"]["error_kind"] == "brownout_shed"
    finally:
        await service.stop(grace_period=1)


async def test_migration_exhaustion_labels_error_kind():
    """The Migration operator stamps its terminal error with the failure
    reason so the frontend taxonomy has something to render."""

    class DyingEngine:
        async def generate(self, request, context):
            raise ConnectionResetError("worker died")
            yield  # pragma: no cover

    m = Migration(migration_limit=1)
    outs = await collect(
        m.generate(_req(range(4)).to_dict(), Context(), DyingEngine())
    )
    last = outs[-1]
    assert last.error and last.finish_reason == FinishReason.ERROR
    assert last.error_kind == "connection"
    assert m.metrics.exhausted.value() == 1
