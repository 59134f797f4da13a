"""Live-handoff drain (ISSUE 9): zero-re-prefill request migration and
coordinated rolling restarts.

The shared claim: a PLANNED worker shutdown (SIGTERM / POST /drain /
preStop) is invisible to clients — in-flight decodes continue bit-identical
on a peer with zero re-prefilled tokens (the handoff rung), and every
failure of that rung falls down a ladder (re-prefill migration → typed
requeue) that still completes the stream token-exact. Plus the integrity
satellite: persisted KV (checkpoint + disk-tier spills) carries CRC32s and
corruption becomes a counted miss, never a crash.
"""

import asyncio
import io
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from dynamo_tpu.disagg.handoff import (
    HandoffHandler,
    HandoffTicket,
    pack_handoff,
    unpack_handoff,
)
from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.llm.migration import Migration
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import tiny_config
from dynamo_tpu.router.protocols import LoadSnapshot
from dynamo_tpu.router.scheduler import KvScheduler
from dynamo_tpu.runtime import fault_names as fn
from dynamo_tpu.runtime import faults
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.drain import (
    DRAINED,
    DrainController,
    WorkerDrainingError,
)
from dynamo_tpu.runtime.engine import collect
from dynamo_tpu.tokens.radix import OverlapScores


@pytest.fixture(autouse=True)
def _disarmed():
    faults.disarm()
    yield
    faults.disarm()


def make_engine(**over):
    defaults = dict(
        config=tiny_config(),
        block_size=4,
        num_kv_blocks=64,
        max_num_seqs=4,
        max_model_len=256,
        prefill_chunk=32,
        decode_steps=4,
    )
    defaults.update(over)
    return JaxEngine(JaxEngineArgs(**defaults))


def req(tokens, max_tokens=64, **sampling):
    s = dict(temperature=0.0)
    s.update(sampling)
    return PreprocessedRequest(
        token_ids=list(tokens),
        request_id=f"r{hash(tuple(tokens)) & 0xFFFF:x}-{max_tokens}",
        sampling=SamplingOptions(**s),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )


def toks_of(outs):
    out = []
    for o in outs:
        t = o.get("token_ids") if isinstance(o, dict) else o.token_ids
        out.extend(t or [])
    return out


class LocalHandoffClient:
    """In-process stand-in for the component 'handoff' endpoint client."""

    def __init__(self, handlers):
        self._handlers = dict(handlers)
        self.closed = False

    @property
    def instance_ids(self):
        return sorted(self._handlers)

    def direct(self, request, instance_id, context=None):
        return self._handlers[instance_id].generate(
            request, context or Context()
        )

    async def close(self):
        self.closed = True


def make_controller(source, peers, **over):
    client = LocalHandoffClient(peers)

    async def factory():
        return client

    kw = dict(
        worker_id=1, handoff_client_factory=factory, deadline_s=30.0,
    )
    kw.update(over)
    return DrainController(source, **kw)


# ---------------------------------------------------------------------------
# The tentpole claim: bit-identical continuation, zero re-prefill
# ---------------------------------------------------------------------------


async def test_handoff_continues_bit_identical_with_zero_reprefill():
    """A mid-decode SAMPLED stream (temperature 0.8 + logprobs — the
    strictest identity check) handed off between two engines equals the
    never-migrated oracle token-for-token AND logprob-for-logprob, the
    peer prefills ZERO tokens for it, and the Migration operator records
    no re-prefill (reprefill_tokens_total unchanged)."""
    oracle = make_engine(seed=5)
    source = make_engine(seed=5)
    peer = make_engine(seed=5)
    try:
        prompt = list(range(40, 56))

        def mk():
            return req(prompt, max_tokens=80, temperature=0.8, top_k=20,
                       logprobs=2)

        want_t, want_lp = [], []
        async for out in oracle.generate(mk(), Context()):
            want_t.extend(out.token_ids or [])
            for step in out.logprobs or []:
                want_lp.append(step[0].logprob)

        ctrl = make_controller(source, {2: HandoffHandler(peer)})
        mig = Migration(migration_limit=3)
        got_t, got_lp = [], []
        got_some = asyncio.Event()

        async def consume():
            async for out in mig.generate(mk(), Context(), source):
                assert not out.error, out.error
                got_t.extend(out.token_ids or [])
                for step in out.logprobs or []:
                    got_lp.append(step[0].logprob)
                if len(got_t) >= 3:
                    got_some.set()

        task = asyncio.create_task(consume())
        await got_some.wait()
        peer_prefill0 = peer.prefill_tokens
        status = await ctrl.drain()
        await task

        assert got_t == want_t
        assert got_lp == want_lp
        assert len(got_t) == 80
        # Zero re-prefilled tokens anywhere: the peer's prefill counter
        # never moved for the adopted stream, and the migration operator
        # saw no failure at all.
        assert peer.prefill_tokens == peer_prefill0
        assert mig.metrics.reprefill_tokens.value() == 0
        assert mig.metrics.migrations.value(reason="drain") == 0
        assert status["handoffs"] == 1
        assert status["reprefill_fallbacks"] == 0
        assert status["handoff_bytes"] > 0
        assert ctrl.state == DRAINED
        assert peer.handoffs_adopted == 1
        assert source.handoffs_exported == 1
        kinds = [e["kind"] for e in peer.flight.snapshot()]
        assert "handoff_adopt" in kinds and "handoff_install" in kinds
    finally:
        for e in (oracle, source, peer):
            await e.stop()


async def test_drain_under_concurrent_load_drops_nothing():
    """Full drain under load: more streams than slots (so the waiting
    queue is live too). Every client stream completes full-length and
    token-exact (greedy) through the ladder — handoffs for the admitted,
    typed requeue + migration for the waiting — inside the deadline."""
    oracle = make_engine(seed=9)
    source = make_engine(seed=9)
    peer = make_engine(seed=9)
    try:
        prompts = [list(range(10 + 7 * i, 26 + 7 * i)) for i in range(6)]
        want = []
        for p in prompts:
            want.append(toks_of(
                await collect(oracle.generate(req(p, 48), Context()))
            ))

        ctrl = make_controller(source, {2: HandoffHandler(peer)})
        mig = Migration(migration_limit=3)

        class DrainAwareClient:
            """The KvScheduler role: place on the source until its
            draining bit flips, then on the peer."""

            async def generate(self, request, context):
                eng = peer if source.draining else source
                async for out in eng.generate(request, context):
                    yield out

        client = DrainAwareClient()
        results = {}

        async def run_one(i):
            outs = await collect(
                mig.generate(req(prompts[i], 48), Context(), client)
            )
            results[i] = outs

        tasks = [asyncio.create_task(run_one(i)) for i in range(6)]
        # Let the first admission wave reach decode, then pull the plug.
        while source.generated_tokens < 8:
            await asyncio.sleep(0.01)
        t0 = time.monotonic()
        status = await ctrl.drain()
        await asyncio.gather(*tasks)

        for i in range(6):
            outs = results[i]
            errs = [
                o.error if not isinstance(o, dict) else o.get("error")
                for o in outs
            ]
            assert not any(errs), (i, errs)
            assert toks_of(outs) == want[i], f"stream {i} diverged"
        assert status["handoffs"] >= 1
        assert status["requeued"] >= 1
        # Every stream resolved through the ladder — or finished naturally
        # while earlier handoffs were in flight (decode never pauses).
        assert status["handoffs"] + status["reprefill_fallbacks"] + \
            status["requeued"] <= 6
        assert time.monotonic() - t0 < ctrl.deadline_s
        assert ctrl.state == DRAINED
        # Requeued/fallback streams paid re-prefill; handoffs paid none —
        # peer adoption count proves the zero-re-prefill rung actually ran.
        assert peer.handoffs_adopted == status["handoffs"]
    finally:
        for e in (oracle, source, peer):
            await e.stop()


async def test_every_trigger_joins_the_one_drain():
    """The signal handler, POST /drain and a preStop hook may all fire.
    The first trigger starts the drain; every later one gets the same
    task, an awaiter that gives up does not abort it, the live stream is
    handed off once and completes token-exact."""
    oracle = make_engine(seed=5)
    source = make_engine(seed=5)
    peer = make_engine(seed=5)
    try:
        prompt = list(range(40, 56))
        want = toks_of(await collect(oracle.generate(req(prompt, 40), Context())))

        ctrl = make_controller(source, {2: HandoffHandler(peer)})
        mig = Migration(migration_limit=3)
        got = []
        got_some = asyncio.Event()

        async def consume():
            async for out in mig.generate(req(prompt, 40), Context(), source):
                assert not out.error, out.error
                got.extend(out.token_ids or [])
                if len(got) >= 3:
                    got_some.set()

        task = asyncio.create_task(consume())
        await got_some.wait()
        first = ctrl.trigger()  # what loop.add_signal_handler(SIGTERM, ...) calls
        assert ctrl.trigger() is first
        assert ctrl.trigger(deadline_s=1.0) is first
        assert ctrl.deadline_s == 30.0  # a late override is refused, not applied
        impatient = asyncio.create_task(ctrl.drain())
        await asyncio.sleep(0)
        impatient.cancel()
        with pytest.raises(asyncio.CancelledError):
            await impatient
        assert not first.cancelled()
        status = await ctrl.drain()
        await task

        assert first.done() and ctrl.state == DRAINED
        assert got == want
        assert status["handoffs"] == 1
        assert peer.handoffs_adopted == 1
        assert source.handoffs_exported == 1
        assert mig.metrics.reprefill_tokens.value() == 0
        assert (await ctrl.drain()) == status  # a trigger after the end changes nothing
    finally:
        for e in (oracle, source, peer):
            await e.stop()


# ---------------------------------------------------------------------------
# The ladder under seeded chaos
# ---------------------------------------------------------------------------


async def test_chaos_drain_export_and_import_deaths_heal_token_exact():
    """Seeded kills at BOTH handoff seams mid-drain: stream A's export
    dies on the source, stream B's adopt dies on the peer. Both fall to
    the re-prefill rung and complete token-exact through Migration; the
    drain still converges inside its deadline."""
    oracle = make_engine(seed=13)
    source = make_engine(seed=13)
    peer = make_engine(seed=13)
    try:
        prompts = [list(range(30 + 9 * i, 46 + 9 * i)) for i in range(2)]
        want = []
        for p in prompts:
            want.append(toks_of(
                await collect(oracle.generate(req(p, 48), Context()))
            ))

        ctrl = make_controller(source, {2: HandoffHandler(peer)})
        mig = Migration(migration_limit=3)

        class DrainAwareClient:
            async def generate(self, request, context):
                eng = peer if source.draining else source
                async for out in eng.generate(request, context):
                    yield out

        client = DrainAwareClient()
        results = {}

        async def run_one(i):
            results[i] = await collect(
                mig.generate(req(prompts[i], 48), Context(), client)
            )

        tasks = [asyncio.create_task(run_one(i)) for i in range(2)]
        # BOTH streams must be mid-decode (the schedule kills one export
        # and one adoption — a still-waiting stream would requeue instead).
        while (
            len(source.active_request_ids()) < 2
            or source.generated_tokens < 4
        ):
            await asyncio.sleep(0.01)

        plan = faults.FaultPlan(seed=7, rules=(
            # First detached stream: the source cannot read its own pool.
            faults.FaultRule(
                point=fn.DRAIN_HANDOFF_EXPORT, at=(1,), kind="error",
            ),
            # Second stream: the peer dies mid-adoption.
            faults.FaultRule(
                point=fn.DRAIN_HANDOFF_IMPORT, at=(1,), kind="connection",
            ),
        ))
        with faults.armed(plan) as plane:
            t0 = time.monotonic()
            status = await ctrl.drain()
            await asyncio.gather(*tasks)
        assert plane.injected.get(fn.DRAIN_HANDOFF_EXPORT, 0) == 1
        assert plane.injected.get(fn.DRAIN_HANDOFF_IMPORT, 0) == 1

        for i in range(2):
            outs = results[i]
            assert not any(
                (o.error if not isinstance(o, dict) else o.get("error"))
                for o in outs
            )
            assert toks_of(outs) == want[i], f"stream {i} diverged"
        assert status["handoffs"] == 0
        assert status["reprefill_fallbacks"] == 2
        # Every fallback surfaced as a migratable drain error and was
        # re-dispatched with its tokens carried.
        assert mig.metrics.migrations.value(reason="drain") == 2
        assert mig.metrics.reprefill_tokens.value() > 0
        assert time.monotonic() - t0 < ctrl.deadline_s
        assert ctrl.state == DRAINED
    finally:
        for e in (oracle, source, peer):
            await e.stop()


async def test_chaos_wire_death_mid_relay_heals_via_reprefill():
    """The wire seam: the handoff itself succeeds, then the source↔peer
    relay dies mid-continuation (injected mid-stream). The client stream
    heals through the re-prefill rung — the frontend re-dispatches with
    every token it already saw (including relayed ones) carried."""
    oracle = make_engine(seed=31)
    source = make_engine(seed=31)
    peer = make_engine(seed=31)
    try:
        prompt = list(range(60, 76))
        want = toks_of(
            await collect(oracle.generate(req(prompt, 64), Context()))
        )

        inner = HandoffHandler(peer)

        class DiesMidRelay:
            """Wire stand-in: kills the relay stream after a few items."""

            def __init__(self):
                self.items = 0

            async def generate(self, request, context):
                async for item in inner.generate(request, context):
                    yield item
                    self.items += 1
                    if self.items == 3:
                        raise faults.InjectedConnectionError(
                            "relay wire died"
                        )

        ctrl = make_controller(source, {2: DiesMidRelay()})
        mig = Migration(migration_limit=3)

        class DrainAwareClient:
            async def generate(self, request, context):
                eng = peer if source.draining else source
                async for out in eng.generate(request, context):
                    yield out

        outs = {}
        got_some = asyncio.Event()

        async def run_one():
            collected = []
            async for o in mig.generate(
                req(prompt, 64), Context(), DrainAwareClient()
            ):
                collected.append(o)
                if len(toks_of(collected)) >= 3:
                    got_some.set()
            outs["r"] = collected

        task = asyncio.create_task(run_one())
        await got_some.wait()
        await ctrl.drain()
        await task

        collected = outs["r"]
        assert not any(
            (o.error if not isinstance(o, dict) else o.get("error"))
            for o in collected
        )
        assert toks_of(collected) == want
        # The handoff rung RAN (peer adopted), then the wire died and the
        # stream still completed — via migration with carried tokens (a
        # relay death is a real connection failure, labeled as such).
        assert peer.handoffs_adopted == 1
        assert mig.metrics.migrations.value(reason="connection") == 1
    finally:
        for e in (oracle, source, peer):
            await e.stop()


async def test_peer_shape_mismatch_refusal_walks_ladder():
    """A peer that cannot install the blocks verbatim (different block
    size) REFUSES the ticket; the source falls to re-prefill and the
    stream completes on that same peer through migration (same weights,
    greedy — still token-exact vs the oracle)."""
    oracle = make_engine(seed=3)
    source = make_engine(seed=3)
    # Same seed (identical weights) but a different block geometry →
    # deterministic refusal while re-prefill serving still works.
    peer = make_engine(seed=3, block_size=8)
    try:
        prompt = list(range(80, 96))
        want = toks_of(
            await collect(oracle.generate(req(prompt, 48), Context()))
        )
        ctrl = make_controller(source, {2: HandoffHandler(peer)})
        mig = Migration(migration_limit=3)

        class DrainAwareClient:
            async def generate(self, request, context):
                eng = peer if source.draining else source
                async for out in eng.generate(request, context):
                    yield out

        result = {}
        got_some = asyncio.Event()

        async def run_one():
            collected = []
            async for o in mig.generate(
                req(prompt, 48), Context(), DrainAwareClient()
            ):
                collected.append(o)
                if toks_of(collected):
                    got_some.set()
            result["r"] = collected

        task = asyncio.create_task(run_one())
        await got_some.wait()
        status = await ctrl.drain()
        await task

        assert toks_of(result["r"]) == want
        assert status["handoffs"] == 0
        assert status["peer_refusals"] == 1
        assert status["reprefill_fallbacks"] == 1
        assert peer.handoffs_adopted == 0
        refusals = [
            e for e in ctrl.flight.snapshot() if e["kind"] == "peer_refusal"
        ]
        assert refusals and "block_size" in refusals[0]["reason"]
    finally:
        for e in (oracle, source, peer):
            await e.stop()


async def test_new_requests_bounce_typed_while_draining():
    """The race window between begin_drain and the router seeing the
    load report: a request arriving at a draining engine raises the typed
    migratable WorkerDrainingError immediately — no silent queueing."""
    engine = make_engine(seed=1)
    try:
        await engine.start()
        engine.begin_drain()
        with pytest.raises(WorkerDrainingError):
            await collect(engine.generate(req(range(10, 20), 8), Context()))
        engine.end_drain()
        outs = await collect(engine.generate(req(range(10, 20), 8), Context()))
        assert len(toks_of(outs)) == 8
    finally:
        await engine.stop()


# ---------------------------------------------------------------------------
# Router: the draining bit deflects placement
# ---------------------------------------------------------------------------


def test_scheduler_deflects_draining_worker():
    sched = KvScheduler()
    draining = (1, 0)
    serving = (2, 0)
    # The draining worker looks BETTER on every other axis: idle, full
    # prefix overlap — and still loses placement.
    sched.update_load(LoadSnapshot(
        worker_id=1, active_blocks=0, total_blocks=100, draining=True,
    ))
    sched.update_load(LoadSnapshot(
        worker_id=2, active_blocks=80, total_blocks=100,
    ))
    overlaps = OverlapScores(scores={draining: 10, serving: 0})
    chosen = sched.select_worker(10, overlaps, [draining, serving])
    assert chosen == serving
    # Drain ends (fresh report without the bit): the worker is placeable
    # again and its overlap win counts.
    sched.update_load(LoadSnapshot(
        worker_id=1, active_blocks=0, total_blocks=100,
    ))
    assert sched.select_worker(10, overlaps, [draining, serving]) == draining
    # Full-fleet restart: every candidate draining still places somewhere.
    sched.update_load(LoadSnapshot(
        worker_id=1, active_blocks=0, total_blocks=100, draining=True,
    ))
    sched.update_load(LoadSnapshot(
        worker_id=2, active_blocks=80, total_blocks=100, draining=True,
    ))
    assert sched.select_worker(10, overlaps, [draining, serving]) is not None


def test_load_snapshot_drain_bit_round_trips():
    snap = LoadSnapshot(worker_id=7, draining=True)
    assert LoadSnapshot.from_dict(snap.to_dict()).draining is True
    # Pre-drain publishers omit the field entirely.
    legacy = {k: v for k, v in snap.to_dict().items() if k != "draining"}
    assert LoadSnapshot.from_dict(legacy).draining is False


async def test_tcp_err_kinds_keep_drain_refusals_migratable():
    """A WorkerDrainingError raised by a remote handler must re-raise as
    a MIGRATABLE error on the tcp client — not the old flat RuntimeError
    (which would dead-end the frontend's Migration)."""
    from dynamo_tpu.llm.migration import MIGRATABLE
    from dynamo_tpu.runtime.discovery import MemoryDiscovery
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.network.tcp import TcpRequestPlane

    disco = MemoryDiscovery()
    worker_rt = DistributedRuntime(
        discovery=disco, request_plane=TcpRequestPlane(), bus="drain-tcp"
    )
    client_rt = DistributedRuntime(
        discovery=disco, request_plane=TcpRequestPlane(), bus="drain-tcp"
    )

    class DrainingEngine:
        async def generate(self, request, context):
            raise WorkerDrainingError("worker is draining; re-dispatch")
            yield  # pragma: no cover

    served = None
    try:
        ep = worker_rt.namespace("d").component("backend").endpoint("generate")
        served = await ep.serve_endpoint(
            DrainingEngine().generate, instance_id=1
        )
        client = await client_rt.namespace("d").component(
            "backend"
        ).endpoint("generate").client()
        await client.wait_for_instances()
        with pytest.raises(MIGRATABLE) as exc_info:
            await collect(client.generate({"token_ids": [1, 2]}, Context()))
        assert isinstance(exc_info.value, WorkerDrainingError)
    finally:
        if served is not None:
            await served.shutdown(grace_period=1)
        await client_rt.shutdown(grace_period=1)
        await worker_rt.shutdown(grace_period=1)


# ---------------------------------------------------------------------------
# Integrity satellite: CRC32 + the corrupt fault kind
# ---------------------------------------------------------------------------


def _tier_block(shape=(2, 4, 2, 8)):
    rng = np.random.default_rng(0)
    return (
        rng.standard_normal(shape).astype(np.float32),
        rng.standard_normal(shape).astype(np.float32),
    )


def test_disk_tier_crc_makes_manual_corruption_a_counted_miss(tmp_path):
    from dynamo_tpu.kvbm.integrity import corruption_counts
    from dynamo_tpu.kvbm.tiers import DiskTier

    tier = DiskTier(str(tmp_path), capacity_blocks=8)
    k, v = _tier_block()
    tier.put(0xAB, k, v)
    got = tier.get(0xAB)
    assert got is not None
    np.testing.assert_array_equal(got[0], k)

    # Flip one payload byte on disk (past the zip headers).
    path = tier._path(0xAB)
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0x40
    open(path, "wb").write(bytes(raw))

    before = corruption_counts().get("disk", 0)
    corrupted = []
    tier.on_corruption = lambda h, detail: corrupted.append((h, detail))
    assert tier.get(0xAB) is None  # counted miss, not a crash
    assert tier.stats.corrupt == 1
    assert corruption_counts().get("disk", 0) == before + 1
    assert corrupted and corrupted[0][0] == 0xAB
    # Entry + file dropped: the next get is a plain miss.
    assert not tier.contains(0xAB)
    assert not os.path.exists(path)


def test_disk_tier_truncated_spill_is_corruption(tmp_path):
    from dynamo_tpu.kvbm.tiers import DiskTier

    tier = DiskTier(str(tmp_path), capacity_blocks=8)
    k, v = _tier_block()
    tier.put(0xCD, k, v)
    path = tier._path(0xCD)
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[: len(raw) // 3])  # torn write
    assert tier.get(0xCD) is None
    assert tier.stats.corrupt == 1


def test_corrupt_fault_kind_is_deterministic_and_crc_catches_it(tmp_path):
    """The new 'corrupt' kind flips one bit of the payload at a
    kvbm.tier.* seam — the CRC turns it into a counted miss, and the
    injection trace replays bit-identically."""
    from dynamo_tpu.kvbm.tiers import DiskTier

    def run(root):
        tier = DiskTier(str(root), capacity_blocks=8)
        k, v = _tier_block()
        plan = faults.FaultPlan(seed=3, rules=(
            faults.FaultRule(
                point=fn.KVBM_TIER_READ, at=(2,), kind="corrupt",
            ),
        ))
        with faults.armed(plan) as plane:
            tier.put(0x11, k, v)
            assert tier.get(0x11) is not None  # read 1: clean
            assert tier.get(0x11) is None  # read 2: corrupted → miss
            trace = list(plane.trace)
        return trace, tier.stats.corrupt

    t1, c1 = run(tmp_path / "a")
    t2, c2 = run(tmp_path / "b")
    assert t1 == t2 == [(fn.KVBM_TIER_READ, 2, 0, "corrupt")]
    assert c1 == c2 == 1


def test_corrupt_fault_kind_on_write_seam(tmp_path):
    """Corruption injected at the WRITE seam persists to disk; the read
    CRC still catches it (silent-storage-damage model)."""
    from dynamo_tpu.kvbm.tiers import DiskTier

    tier = DiskTier(str(tmp_path), capacity_blocks=8)
    k, v = _tier_block()
    plan = faults.FaultPlan(seed=3, rules=(
        faults.FaultRule(point=fn.KVBM_TIER_WRITE, at=(1,), kind="corrupt"),
    ))
    with faults.armed(plan):
        tier.put(0x22, k, v)
    assert tier.get(0x22) is None
    assert tier.stats.corrupt == 1


def test_stacked_corrupt_rules_flip_different_bits():
    """Two corrupt rules firing on ONE hit must deliver a payload that is
    still corrupt: the flip is an involution, so re-flipping the same bit
    would restore the pristine bytes while the trace claims two
    injections. Stacked applications flip bit 0 then bit 1."""
    data = b"pristine-payload"
    expected = faults.corrupt_bytes(faults.corrupt_bytes(data, 0), 1)
    assert expected != data
    plan = faults.FaultPlan(seed=0, rules=(
        faults.FaultRule(point=fn.KVBM_TIER_READ, at=(1,), kind="corrupt"),
        faults.FaultRule(point=fn.KVBM_TIER_READ, every=1, kind="corrupt"),
    ))
    with faults.armed(plan) as plane:
        out = plane.hit_payload(fn.KVBM_TIER_READ, data, {})
        assert len(plane.trace) == 2
    assert out == expected


def test_corrupt_rule_arms_and_raising_kinds_still_raise(tmp_path):
    from dynamo_tpu.kvbm.tiers import DiskTier

    # Raising kinds keep their old behavior through the payload seam.
    tier = DiskTier(str(tmp_path), capacity_blocks=8)
    k, v = _tier_block()
    tier.put(0x33, k, v)
    plan = faults.FaultPlan(seed=0, rules=(
        faults.FaultRule(point=fn.KVBM_TIER_READ, at=(1,), kind="connection"),
    ))
    with faults.armed(plan):
        with pytest.raises(ConnectionError):
            tier.get(0x33)
    # And an unknown kind still fails fast at arm time.
    with pytest.raises(ValueError):
        faults.FaultRule(point=fn.KVBM_TIER_READ, kind="corrput")


async def test_checkpoint_crc_corruption_restores_cold_not_garbage(tmp_path):
    """A corrupted checkpoint data file restores ZERO blocks (counted
    miss + engine flight event), never crashes, never installs KV."""
    from dynamo_tpu.kvbm.integrity import corruption_counts

    ckpt = str(tmp_path / "ckpt")
    saver = make_engine(seed=2)
    try:
        outs = await collect(saver.generate(req(range(20, 36), 24), Context()))
        assert len(toks_of(outs)) == 24
        result = await saver.save_checkpoint(ckpt)
        assert result["blocks"] > 0
    finally:
        await saver.stop()

    # Clean restore first: the CRC stamp verifies.
    clean = make_engine(seed=2)
    try:
        assert await clean.load_checkpoint(ckpt) > 0
    finally:
        await clean.stop()

    # Corrupt the data file (middle byte of the npz payload).
    data_file = next(
        p for p in os.listdir(ckpt) if p.startswith("kv_blocks")
    )
    path = os.path.join(ckpt, data_file)
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0x01
    open(path, "wb").write(bytes(raw))

    before = corruption_counts().get("checkpoint", 0)
    victim = make_engine(seed=2)
    try:
        assert await victim.load_checkpoint(ckpt) == 0  # cold, not a crash
        assert victim.pool.cached_blocks == 0
        assert corruption_counts().get("checkpoint", 0) == before + 1
        assert any(
            e["kind"] == "ckpt_corrupt" for e in victim.flight.snapshot()
        )
    finally:
        await victim.stop()

    # Truncation (worker SIGKILLed mid-write / disk full): np.load raises
    # zipfile.BadZipFile — a plain Exception, NOT an OSError — which must
    # also land on the counted-miss path, not escape as a crash.
    open(path, "wb").write(bytes(raw[: len(raw) // 3]))
    truncated = make_engine(seed=2)
    try:
        assert await truncated.load_checkpoint(ckpt) == 0
        assert truncated.pool.cached_blocks == 0
        assert corruption_counts().get("checkpoint", 0) == before + 2
    finally:
        await truncated.stop()


# ---------------------------------------------------------------------------
# Ticket plumbing
# ---------------------------------------------------------------------------


def test_handoff_ticket_packs_through_msgpack():
    import msgpack

    from dynamo_tpu.disagg.wire import KvWireBlocks

    rng = np.random.default_rng(1)
    wire = KvWireBlocks.dense(
        rng.standard_normal((2, 2, 4, 2, 8)).astype(np.float32),
        rng.standard_normal((2, 2, 4, 2, 8)).astype(np.float32),
    )
    ticket = HandoffTicket(
        request={"token_ids": [1, 2, 3]}, generated=[4, 5], salt=7,
        hash_salt=0, pos=4, committed_hashes=[11], n_blocks=2,
        model="tiny", block_size=4, n_layers=2, n_kv_heads=2, head_dim=8,
        seed=0,
    )
    raw = msgpack.packb(
        pack_handoff(ticket, wire), use_bin_type=True
    )
    t2, w2 = unpack_handoff(msgpack.unpackb(raw, raw=False))
    assert t2 == ticket
    np.testing.assert_array_equal(w2.k, wire.k)


async def test_handoff_handler_refuses_malformed_tickets():
    engine = make_engine(seed=0)
    try:
        from dynamo_tpu.disagg.wire import KvWireBlocks

        cfg = engine.config
        good = dict(
            request={"token_ids": [1, 2, 3, 4]}, generated=[5], salt=1,
            hash_salt=0, pos=4, committed_hashes=[], n_blocks=1,
            model=cfg.name, block_size=4, n_layers=cfg.n_layers,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_, seed=0,
        )
        wire = KvWireBlocks.dense(
            np.zeros((1, cfg.n_layers, 4, cfg.n_kv_heads, cfg.head_dim_),
                     np.float32),
            np.zeros((1, cfg.n_layers, 4, cfg.n_kv_heads, cfg.head_dim_),
                     np.float32),
        )
        handler = HandoffHandler(engine)

        async def first_reply(**over):
            t = HandoffTicket(**{**good, **over})
            agen = handler.generate(pack_handoff(t, wire), Context())
            reply = await agen.__anext__()
            await agen.aclose()
            return reply

        for bad in (
            {"model": "other"},
            {"seed": 99},
            {"block_size": 8},
            {"pos": 7},  # inconsistent with prompt+generated
            {"n_blocks": 3},  # != ceil(pos / block_size)
            {"request": {"token_ids": []}},
        ):
            reply = await first_reply(**bad)
            assert reply["accepted"] is False, bad
        reply = await first_reply()
        assert reply["accepted"] is True
    finally:
        await engine.stop()


# ---------------------------------------------------------------------------
# Satellite: worker signal handling (subprocess)
# ---------------------------------------------------------------------------


def test_worker_sigterm_drains_and_exits_cleanly(tmp_path):
    """SIGTERM (k8s pod deletion) must run the drain + the finally block —
    the seed worker died instantly, skipping the KV checkpoint and every
    graceful shutdown step. Double SIGINT is the force-exit escape hatch
    (exercised implicitly: one SIGTERM here must suffice for exit 0)."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dynamo_tpu.worker",
            "--model", "tiny", "--block-size", "4", "--num-kv-blocks", "32",
            "--max-num-seqs", "2", "--max-model-len", "64",
            "--kv-checkpoint-dir", str(tmp_path / "ckpt"),
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )
    try:
        deadline = time.monotonic() + 120
        ready = False
        lines = []
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            lines.append(line)
            if "worker serving" in line:
                ready = True
                break
        assert ready, "worker never came up:\n" + "".join(lines)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
        lines.append(out)
        assert proc.returncode == 0, "".join(lines)
        assert "SIGTERM: draining" in "".join(lines)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
