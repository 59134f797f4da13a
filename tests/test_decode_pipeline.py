"""Device-resident decode state + double-buffered tick pipelining.

The determinism contract under test: with a fixed engine seed, the FULL
token/logprob stream of every request is bit-identical at pipeline_depth=1
(fully synchronous dispatch→read→emit) and pipeline_depth=2 (burst N+1
dispatched from the device carry while burst N is read back and emitted) —
across stop conditions firing mid-pipeline, logprobs and logits-processor
rows, mid-stream admission, and preemption-by-recompute. No test relies on
timing: sampling noise is keyed on (seed, sequence salt, token index), so
WHICH burst serves a token never changes its value.

Also covered: the steady-state H2D contract (no re-upload of
pos/temp/topk/topp/adapter_ids/block_tables on unchanged ticks — the
transfer-counting assertions on DeviceRunner.transfer_log), pipeline
draining around sleep/wake, and SPMD lockstep of the dispatch/reap split.
"""

import asyncio
import threading

import numpy as np
import pytest

from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import tiny_config
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect


def make_engine(depth, **over):
    defaults = dict(
        config=tiny_config(),
        block_size=4,
        num_kv_blocks=64,
        max_num_seqs=4,
        max_model_len=96,
        prefill_chunk=32,
        decode_steps=4,
        pipeline_depth=depth,
    )
    defaults.update(over)
    return JaxEngine(JaxEngineArgs(**defaults))


def req(tokens, max_tokens=8, temperature=0.0, rid="r", **kw):
    sampling = kw.pop("sampling", None) or SamplingOptions(
        temperature=temperature
    )
    return PreprocessedRequest(
        token_ids=list(tokens),
        request_id=rid,
        sampling=sampling,
        stop=StopConditions(max_tokens=max_tokens),
        **kw,
    )


def stream_sig(outs):
    """(token ids, finish reason, exact logprob floats) of one stream."""
    toks = [t for o in outs for t in (o.token_ids or [])]
    reason = outs[-1].finish_reason
    logps = [
        (lp.token_id, lp.logprob)
        for o in outs
        if o.logprobs
        for entry in o.logprobs
        for lp in entry
    ]
    return (toks, reason, logps)


async def _run_mixed_scenarios(depth):
    """One engine per depth serves three scenarios back to back: a mixed
    batch (greedy + sampled + logprobs + logits-processor rows, staggered
    stop conditions so rows finish mid-pipeline), then an EOS stop, then a
    max_tokens=1 edge. Returns every stream's signature."""
    engine = make_engine(depth)
    sigs = []
    try:
        reqs = [
            req(range(10, 20), max_tokens=11, rid="greedy"),
            req(
                range(20, 30), max_tokens=9, rid="sampled",
                sampling=SamplingOptions(temperature=0.9, top_p=0.9),
            ),
            req(
                range(30, 40), max_tokens=15, rid="logprobs",
                sampling=SamplingOptions(temperature=0.7, logprobs=2),
            ),
            req(
                range(40, 50), max_tokens=15, rid="procs",
                sampling=SamplingOptions(
                    temperature=1.0, repetition_penalty=1.3
                ),
            ),
        ]
        outs = await asyncio.gather(
            *(collect(engine.generate(r, Context())) for r in reqs)
        )
        sigs.extend(stream_sig(o) for o in outs)

        # EOS firing mid-burst: probe the greedy continuation, then stop
        # on its first token with room for 50.
        probe = await collect(
            engine.generate(req(range(50, 60), max_tokens=3), Context())
        )
        first = probe[0].token_ids[0]
        sigs.append(stream_sig(probe))
        eos_out = await collect(
            engine.generate(
                PreprocessedRequest(
                    token_ids=list(range(50, 60)),
                    request_id="eos",
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=50),
                    eos_token_ids=[first],
                ),
                Context(),
            )
        )
        assert eos_out[-1].finish_reason == FinishReason.EOS
        sigs.append(stream_sig(eos_out))

        # max_tokens=1: the whole request is the prefill-sampled token.
        one = await collect(
            engine.generate(req(range(60, 70), max_tokens=1), Context())
        )
        sigs.append(stream_sig(one))
        if depth >= 2:
            # The pipelined engine really pipelined: the inflight-depth
            # histogram saw more total depth than observations (some
            # dispatch found another burst already in flight).
            count, total = engine.step_metrics.inflight_depth.snapshot_total()
            assert count > 0 and total > count
    finally:
        await engine.stop()
    return sigs


async def test_depth2_stream_bitwise_matches_depth1():
    sig1 = await _run_mixed_scenarios(1)
    sig2 = await _run_mixed_scenarios(2)
    assert sig1 == sig2


async def test_midstream_admission_bitwise_identical():
    """A request admitted while another is mid-decode (pipeline drained at
    the admission barrier) gets the identical stream at both depths, and
    the running request is unperturbed."""

    async def run(depth):
        engine = make_engine(depth, max_num_seqs=2)
        try:
            ctx = Context()
            a_outs = []
            b_sig = None

            async def consume_a():
                async for o in engine.generate(
                    req(
                        range(10, 20), max_tokens=20, rid="a",
                        sampling=SamplingOptions(temperature=0.8),
                    ),
                    ctx,
                ):
                    a_outs.append(o)

            async def submit_b_after_two():
                while len([o for o in a_outs if o.token_ids]) < 2:
                    await asyncio.sleep(0.005)
                return await collect(
                    engine.generate(
                        req(
                            range(40, 50), max_tokens=10, rid="b",
                            sampling=SamplingOptions(temperature=0.9),
                        ),
                        Context(),
                    )
                )

            _, b_out = await asyncio.gather(consume_a(), submit_b_after_two())
            b_sig = stream_sig(b_out)
            return (stream_sig(a_outs), b_sig)
        finally:
            await engine.stop()

    assert await run(1) == await run(2)


async def test_preemption_recompute_bitwise_identical():
    """Pool sized so decode growth preempts one sequence mid-stream at the
    SAME reap boundary regardless of depth (constant 2-burst lookahead +
    drain-before-preempt). The preempted sequence recomputes and its
    stream — including the sampled row — is bit-identical."""

    async def run(depth):
        engine = make_engine(
            depth, max_num_seqs=2, num_kv_blocks=8, max_model_len=64
        )
        try:
            reqs = [
                req(range(10, 18), max_tokens=14, rid="a"),
                req(
                    range(20, 28), max_tokens=18, rid="b",
                    sampling=SamplingOptions(temperature=0.8),
                ),
            ]
            outs = await asyncio.gather(
                *(collect(engine.generate(r, Context())) for r in reqs)
            )
            return [stream_sig(o) for o in outs], engine.preemptions
        finally:
            await engine.stop()

    sig1, pre1 = await run(1)
    sig2, pre2 = await run(2)
    assert pre1 > 0 and pre2 > 0, "scenario no longer triggers preemption"
    assert pre1 == pre2
    assert sig1 == sig2


async def test_sleep_wake_drains_pipeline():
    engine = make_engine(2, max_num_seqs=2)
    try:
        out = await collect(
            engine.generate(req(range(10, 20), max_tokens=6), Context())
        )
        assert len([t for o in out for t in o.token_ids]) == 6
        await engine.sleep(1)
        assert engine.sleep_level == 1
        assert len(engine._inflight) == 0, "sleep left bursts in flight"
        await engine.wake()
        out2 = await collect(
            engine.generate(req(range(10, 20), max_tokens=6), Context())
        )
        assert stream_sig(out) == stream_sig(out2)
    finally:
        await engine.stop()


async def test_steady_state_ticks_move_zero_host_state():
    """Acceptance: steady-state decode dispatches re-upload NOTHING — no
    pos/temp/topk/topp/adapter_ids/block_tables rows, not even the token
    (it rides the donated device carry). The runner's transfer log must
    show consecutive decode dispatches with no sync entries between them
    once the block table stops growing."""
    engine = make_engine(
        2, block_size=32, num_kv_blocks=8, max_model_len=64, decode_steps=4
    )
    try:
        out = await collect(
            engine.generate(req(range(10, 14), max_tokens=14), Context())
        )
        assert len([t for o in out for t in o.token_ids]) == 14
        log = engine.runner.transfer_log
        kinds = [k for k, _ in log]
        assert "decode" in kinds
        # The first dispatch reconciles the install (slot + table sync).
        first_decode = kinds.index("decode")
        assert "slot_sync" in kinds[:first_decode]
        assert "table_sync" in kinds[:first_decode]
        # Steady state: at least two consecutive decode dispatches with no
        # H2D sync of any slot state between them.
        best_run = run = 0
        for k in kinds:
            run = run + 1 if k == "decode" else 0
            best_run = max(best_run, run)
        assert best_run >= 2, f"no pure-dispatch steady state: {kinds}"
    finally:
        await engine.stop()


def test_spmd_dispatch_reap_split_stays_lockstep():
    """Two runners joined by a loopback SPMD channel: the leader drives
    the PIPELINED op sequence (state sync → two dispatches back to back →
    reads). The follower replays dispatches WITHOUT reading results; its
    device-resident carry (tokens/pos) must track the leader's exactly."""
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.engines.tpu.spmd import make_follower
    from dynamo_tpu.runtime.network.spmd_channel import SpmdBroadcaster

    def mk_runner():
        return DeviceRunner(
            JaxEngineArgs(
                config=tiny_config(), block_size=4, num_kv_blocks=32,
                max_num_seqs=4, max_model_len=64, decode_steps=2, seed=5,
            )
        )

    leader, follower_runner = mk_runner(), mk_runner()
    bcast = SpmdBroadcaster(0, num_followers=1, host="127.0.0.1")
    follower = make_follower("127.0.0.1", bcast.port)
    bcast.wait_for_followers()
    leader.set_broadcaster(bcast)

    errors = []

    def follow_loop():
        from dynamo_tpu.engines.tpu.spmd import follow

        try:
            follow(follower_runner, follower)
        except Exception as exc:  # pragma: no cover - surfaced via assert
            errors.append(exc)

    t = threading.Thread(target=follow_loop, daemon=True)
    t.start()

    from dynamo_tpu.ops.logits_process import MAX_BIAS_SLOTS

    S = 4
    rows = {
        "tokens": np.array([7, 8, 9, 10], np.int32),
        "pos": np.array([4, 4, 4, 0], np.int32),
        "active": np.array([1, 1, 1, 0], np.int32),
        "temp": np.zeros(S, np.float32),
        "topk": np.zeros(S, np.int32),
        "topp": np.ones(S, np.float32),
        "adapter_ids": np.zeros(S, np.int32),
        "salts": np.array([1, 2, 3, 0], np.int32),
        "minp": np.zeros(S, np.float32),
        "rep": np.ones(S, np.float32),
        "pres": np.zeros(S, np.float32),
        "freq": np.zeros(S, np.float32),
        "bias_ids": np.full((S, MAX_BIAS_SLOTS), -1, np.int32),
        "bias_vals": np.zeros((S, MAX_BIAS_SLOTS), np.float32),
    }
    tables = np.zeros((S, 16), np.int32)
    for s in range(S):
        tables[s, :4] = np.arange(4 * s, 4 * s + 4)

    leader.sync_slots(list(range(S)), rows)
    leader.sync_tables(list(range(S)), tables)
    # Pipelined: dispatch burst 0 AND burst 1 before reading either.
    h0 = leader.decode_dispatch(2)
    h1 = leader.decode_dispatch(2)
    toks0, _, _, _ = leader.decode_read(h0)
    toks1, _, _, _ = leader.decode_read(h1)

    bcast.send("stop")
    t.join(timeout=60)
    assert not errors, errors
    assert not t.is_alive(), "follower did not stop"

    # Lockstep: the follower never read anything back, but its carry is
    # bit-identical to the leader's.
    lead_state = {
        k: np.asarray(v) for k, v in leader.slot_state.items()
    }
    foll_state = {
        k: np.asarray(v) for k, v in follower_runner.slot_state.items()
    }
    for k in lead_state:
        np.testing.assert_array_equal(lead_state[k], foll_state[k], err_msg=k)
    np.testing.assert_array_equal(
        np.asarray(leader.slot_tables), np.asarray(follower_runner.slot_tables)
    )
    # The carry advanced: two bursts × 2 steps for the three active rows.
    assert list(lead_state["pos"][:3]) == [8, 8, 8]
    assert list(lead_state["tokens"][:3]) == [
        int(toks1[0, -1]), int(toks1[1, -1]), int(toks1[2, -1])
    ]
    assert toks0.shape == (S, 2) and toks1.shape == (S, 2)


async def test_runner_abort_resync_regenerates_identical_tokens():
    """Failure path: when a tick fails with bursts in flight, the engine
    drops them and marks everything dirty; the retried bursts re-run from
    the host mirrors and (position-keyed RNG) regenerate the same
    tokens."""
    # A penalty-using sampled request: the abort path must also roll back
    # the device-side logits-processor counts, not just tokens/pos.
    the_req = lambda: req(  # noqa: E731 — same salt needs a fresh engine
        range(10, 20), max_tokens=10,
        sampling=SamplingOptions(temperature=0.8, repetition_penalty=1.4),
    )
    clean = make_engine(2, max_num_seqs=2)
    try:
        base = stream_sig(
            await collect(clean.generate(the_req(), Context()))
        )
    finally:
        await clean.stop()

    engine = make_engine(2, max_num_seqs=2)
    try:
        # One-shot fault injected into the reap path mid-stream: the tick
        # machinery drops the in-flight bursts, resyncs from the host
        # mirrors, and the retried bursts must regenerate the same stream.
        real_read = engine.runner.decode_read
        state = {"fired": False}

        def flaky_read(handles):
            if not state["fired"] and engine.generated_tokens > 4:
                state["fired"] = True
                raise RuntimeError("synthetic transient readback failure")
            return real_read(handles)

        engine.runner.decode_read = flaky_read
        out2 = await collect(engine.generate(the_req(), Context()))
        engine.runner.decode_read = real_read
        assert state["fired"], "fault never fired; scenario too short"
        assert stream_sig(out2) == base
    finally:
        await engine.stop()


# -- tick budgeter (ISSUE 18): budgeted streams stay bit-identical ------------

BUDGET_ARGS = dict(
    tick_budget_enabled=True,
    tick_budget_floor_tokens=16,
    tick_budget_ceiling_tokens=64,
    tick_budget_policy=0.0,
)


async def _run_budgeted_admission(depth, **over):
    """Stream a decodes while long-prompt b (80 tokens = 3 chunk rounds)
    is admitted mid-stream; a 16-token budget parks b's prefill at a
    chunk boundary and resumes it across later ticks. Returns both
    stream signatures plus how many times the prefill parked."""
    engine = make_engine(depth, max_num_seqs=2, **over)
    try:
        a_outs = []

        async def consume_a():
            async for o in engine.generate(
                req(
                    range(10, 20), max_tokens=30, rid="a",
                    sampling=SamplingOptions(temperature=0.8),
                ),
                Context(),
            ):
                a_outs.append(o)

        async def submit_b_after_two():
            while len([o for o in a_outs if o.token_ids]) < 2:
                await asyncio.sleep(0.005)
            return await collect(
                engine.generate(
                    req(
                        range(100, 180), max_tokens=10, rid="b",
                        sampling=SamplingOptions(temperature=0.9),
                    ),
                    Context(),
                )
            )

        _, b_out = await asyncio.gather(consume_a(), submit_b_after_two())
        parks = sum(
            1 for e in engine.flight.snapshot()
            if e["kind"] == "prefill_pause"
        )
        return (stream_sig(a_outs), stream_sig(b_out)), parks
    finally:
        await engine.stop()


async def test_budgeter_on_vs_off_bitwise_identical_across_depths():
    """The tentpole determinism contract: budgeter on vs off, at depth 1
    vs 2, across a mid-stream admission whose prefill parks at a chunk
    boundary — every stream bit-identical, and the budgeted runs REALLY
    parked (the scenario exercises the resume path, not a no-op)."""
    base, _ = await _run_budgeted_admission(1)
    for depth in (1, 2):
        sig_off, _ = await _run_budgeted_admission(depth)
        assert sig_off == base
        sig_on, parks = await _run_budgeted_admission(depth, **BUDGET_ARGS)
        assert sig_on == base
        assert parks > 0, "budget never parked the prefill; scenario dead"


async def test_budgeted_preemption_bitwise_identical():
    """Preemption-by-recompute under a tick budget: the preempted row's
    re-prefill is budgeted too (parked/resumed like any admission), and
    the recomputed stream stays bit-identical to the unbudgeted run."""

    async def run(depth, **over):
        engine = make_engine(
            depth, max_num_seqs=2, num_kv_blocks=8, max_model_len=64, **over
        )
        try:
            reqs = [
                req(range(10, 18), max_tokens=14, rid="a"),
                req(
                    range(20, 28), max_tokens=18, rid="b",
                    sampling=SamplingOptions(temperature=0.8),
                ),
            ]
            outs = await asyncio.gather(
                *(collect(engine.generate(r, Context())) for r in reqs)
            )
            return [stream_sig(o) for o in outs], engine.preemptions
        finally:
            await engine.stop()

    base, pre0 = await run(1)
    assert pre0 > 0, "scenario no longer triggers preemption"
    for depth in (1, 2):
        sigs, pre = await run(depth, **BUDGET_ARGS)
        assert pre > 0
        assert sigs == base


async def test_budget_squeeze_mid_prefill_is_a_clean_resume():
    """A brownout squeeze landing while a prefill is parked shrinks the
    next tick's grant mid-prompt; the chunk boundary must be a clean
    resume point — the stream is bit-identical to the unsqueezed and
    unbudgeted runs."""

    async def run(depth, squeeze):
        engine = make_engine(
            depth, max_num_seqs=2,
            tick_budget_enabled=True,
            tick_budget_floor_tokens=16,
            tick_budget_ceiling_tokens=64,
            tick_budget_policy=1.0,  # 2 rounds/tick: parks at round 3
        )
        try:
            a_outs = []

            async def consume_a():
                async for o in engine.generate(
                    req(
                        range(10, 20), max_tokens=30, rid="a",
                        sampling=SamplingOptions(temperature=0.8),
                    ),
                    Context(),
                ):
                    a_outs.append(o)

            async def submit_b_after_two():
                while len([o for o in a_outs if o.token_ids]) < 2:
                    await asyncio.sleep(0.005)
                return await collect(
                    engine.generate(
                        req(
                            range(100, 180), max_tokens=10, rid="b",
                            sampling=SamplingOptions(temperature=0.9),
                        ),
                        Context(),
                    )
                )

            async def squeeze_when_parked():
                if not squeeze:
                    return
                for _ in range(2000):
                    if engine._pending_prefill is not None:
                        engine.set_budget_pressure(True)
                        return
                    await asyncio.sleep(0.001)

            _, b_out, _ = await asyncio.gather(
                consume_a(), submit_b_after_two(), squeeze_when_parked()
            )
            return (stream_sig(a_outs), stream_sig(b_out))
        finally:
            await engine.stop()

    base = await run(1, squeeze=False)
    assert await run(1, squeeze=True) == base
    assert await run(2, squeeze=True) == base


# -- one decode path: a dispatch error propagates, nothing else serves --------


def _runner():
    from dynamo_tpu.engines.tpu.runner import DeviceRunner

    return DeviceRunner(JaxEngineArgs(
        config=tiny_config(), block_size=4, num_kv_blocks=64, max_num_seqs=4,
        max_model_len=96, prefill_chunk=32, decode_steps=4,
    ))


def _dispatch(runner, program, nb):
    """One decode burst, or one prefill step of four tokens a row, under a
    block table of ``nb`` pages: each width is a program of its own."""
    S = 4
    f32, i32 = np.float32, np.int32
    rows = (np.zeros(S, f32), np.zeros(S, i32), np.ones(S, f32), np.zeros(S, i32))
    tables = np.arange(S * nb, dtype=i32).reshape(S, nb)
    if program == "decode":
        return runner.run_decode(
            np.zeros(S, i32), np.zeros(S, i32), np.ones(S, i32), tables, *rows)
    return runner.run_step(
        np.ones((S, 4), i32), np.zeros(S, i32), np.full(S, 4, i32), tables, *rows)


@pytest.mark.parametrize("when", ["first dispatch", "wider bucket"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_dispatch_error_propagates_and_no_other_path_serves(monkeypatch, program, when):
    """An error raised while a program is traced and compiled reaches the
    caller whatever its words (there is no classifier deciding which errors
    may be served around), at the very first dispatch and at a wider, not
    yet compiled table-width bucket after a narrower one served. The runner
    keeps the attention implementation it chose at start, and the bucket
    that compiled keeps dispatching."""
    from dynamo_tpu.models import llama

    runner = _runner()
    impl = (runner.attention_impl, runner.use_kernel)
    if when == "wider bucket":
        assert _dispatch(runner, program, nb=1)[0].shape[0] == 4
        error, words, nb = RuntimeError, "Mosaic lowering failed: scoped VMEM over budget", 2
    else:
        error, words, nb = ValueError, "socket closed: transient wire error", 1

    def boom(*a, **k):
        raise error(words)

    with monkeypatch.context() as patched:
        # forward_paged resolves it when the new width is traced
        patched.setattr(llama, "decoder_layer", boom)
        with pytest.raises(error, match=words.split(":")[0]):
            _dispatch(runner, program, nb=nb)
    assert (runner.attention_impl, runner.use_kernel) == impl
    assert _dispatch(runner, program, nb=1)[0].shape[0] == 4


def test_decode_program_compiles_once_per_table_width_bucket():
    """``runner.decode_state`` grows by one program per DISTINCT table width
    and stays flat on a repeat (what ``/debug/compiles`` counts): with
    ``table_width_bucket`` collapsing widths into pow2 buckets, the program
    count is bounded by the bucket count, not by context length."""
    from dynamo_tpu.runtime.device_observe import global_compile_watcher

    def compiles():
        programs = global_compile_watcher().snapshot()["programs"]
        return programs.get("runner.decode_state", {}).get("compiles", 0)

    runner = _runner()
    base, seen = compiles(), set()
    for nb in (1, 1, 2, 2):
        _dispatch(runner, "decode", nb)
        seen.add(nb)
        assert compiles() - base == len(seen)
