"""The rows-bucket family of a prefix-hit prefill program (admission.py
``_note_prefix_hit_round`` / ``run_pending_family``): a chunk round over
cached context is in no start-up ladder; once such a shape RECURS the engine
compiles its sibling rows buckets itself, one a scheduler tick, through the
callable the rounds call and with rows of length 0. On the CPU at the tiny
stand-ins: what is compiled, when, and that such a run touches nothing.
"""

import asyncio
import logging

import jax
import numpy as np
import pytest

from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import tiny_config, tiny_hybrid_config, tiny_mla_config
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.device_observe import CompileWatcher, global_compile_watcher
from dynamo_tpu.runtime.engine import collect

class _Captured(logging.Handler):
    """The dynamo_tpu logger does not propagate: caplog sees nothing of it."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.records = []

    def emit(self, record):
        self.records.append(record)

    def __enter__(self):
        logging.getLogger("dynamo_tpu").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("dynamo_tpu").removeHandler(self)


DOC = np.random.default_rng(43).integers(3, 500, 96).tolist()  # six blocks of 16


def _engine(config=None, **kw):
    base = dict(config=config or tiny_mla_config(), block_size=16, num_kv_blocks=128,
                max_num_seqs=8, max_model_len=512, prefill_chunk=256)
    base.update(kw)
    return JaxEngine(JaxEngineArgs(**base))


def _ask(rid, doc=DOC, n=3, question=8, logprobs=None):
    """The document, then a question no other ask shares."""
    tail = np.random.default_rng(abs(hash(rid)) % 2**31).integers(3, 500, question)
    return PreprocessedRequest(
        token_ids=list(doc) + tail.tolist(), request_id=rid,
        sampling=SamplingOptions(temperature=0.0, logprobs=logprobs),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )


async def _serve(engine, *rids, **kw):
    return await asyncio.gather(
        *(collect(engine.generate(_ask(rid, **kw), Context())) for rid in rids))


async def _family_whole(engine, programs):
    for _ in range(400):
        if engine._admitter.family_programs >= programs and not engine._admitter.family_pending:
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"family not whole: {engine.stats()}")


def _prefill_compiles():
    programs = global_compile_watcher().snapshot()["programs"]
    return programs.get("runner.prefill_step", {}).get("compiles", 0)


def _spy_on_steps(engine, monkeypatch):
    """(rows bucket, chunk, table width, first_chunk) of every prefill step."""
    seen, real = [], engine._run_step

    def spy(tokens, start, lens, tables, *a, **kw):
        first_chunk = a[8] if len(a) > 8 else kw.get("first_chunk", False)
        seen.append((len(tokens), len(tokens[0]), len(tables[0]), bool(first_chunk)))
        return real(tokens, start, lens, tables, *a, **kw)

    monkeypatch.setattr(engine, "_run_step", spy)
    return seen


async def test_one_prefix_hit_starts_nothing_and_a_second_brings_the_family(monkeypatch):
    """The probe repeat's case, then traffic that hits prefixes: ONE ask over
    a cached document at rows 1 starts nothing; the second meeting of the
    shape puts its sibling rows buckets down, each later tick compiles at
    most one, and asks at rows 2, 4 and 8 then compile nothing."""
    engine = _engine()
    admitter = engine._admitter
    per_call = []
    real = admitter.run_pending_family

    async def counted():
        before = admitter.family_programs
        ran = await real()
        per_call.append(admitter.family_programs - before)
        assert ran == bool(per_call[-1])
        return ran

    monkeypatch.setattr(admitter, "run_pending_family", counted)
    seen = _spy_on_steps(engine, monkeypatch)
    try:
        await _serve(engine, "fresh")  # one chunk, first_chunk: no meeting
        assert admitter._families == {}
        await _serve(engine, "hit-1")
        assert seen[-1] == (1, 128, 8, False)
        stats = engine.stats()
        assert stats["prefill_family_pending"] == 0 == stats["prefill_family_programs"]
        assert [f.met for f in admitter._families.values()] == [1]

        await _serve(engine, "hit-2")
        await _family_whole(engine, 3)
        stats = engine.stats()
        assert stats["prefill_family_programs"] == 3
        assert stats["prefill_family_pending"] == 0
        assert max(per_call) == 1 and sum(per_call) == 3  # at most one a tick
        family = [s for s in seen if s[0] > 1]
        assert family == [(2, 128, 8, False), (4, 128, 8, False), (8, 128, 8, False)]

        compiled = _prefill_compiles()
        for rows in (2, 4, 8):
            await _serve(engine, *(f"rows{rows}-{i}" for i in range(rows)))
        assert _prefill_compiles() == compiled
        assert {s[0] for s in seen[-6:]} >= {2, 4, 8}  # the buckets were really met
        assert engine.stats()["prefill_family_programs"] == 3  # and nothing ran twice
    finally:
        await engine.stop()


@pytest.mark.parametrize("model", ["tiny-mla", "tiny-hybrid"])
async def test_a_long_fresh_prompts_own_chunks_are_no_recurring_hit(model, monkeypatch):
    """A resident context being built: one fresh prompt of many chunks meets
    its later-chunk shape round after round and hits no prefix, so it brings
    no sibling (the sparse cell's 65,536-token contexts are built at a table
    width no ask resumes at: three programs a width that nothing ran); two
    asks over it are the recurring hit, at THEIR shape."""
    config = {"tiny-mla": tiny_mla_config, "tiny-hybrid": tiny_hybrid_config}[model]()
    engine = _engine(config, prefill_chunk=128, max_model_len=1024)
    seen = _spy_on_steps(engine, monkeypatch)
    doc = np.random.default_rng(47).integers(3, 500, 504).tolist()  # + 8: four chunks, 32 blocks
    try:
        await _serve(engine, "build", doc=doc)
        later = [s for s in seen if not s[3]]
        assert len(later) >= 3 and {s[:3] for s in later} == {(1, 128, 32)}
        # ... and its first round, which reads no page, is the start-up
        # ladder's program of (rows 1, chunk 128): a table of the chunk's 8 blocks.
        assert [s for s in seen if s[3]] == [(1, 128, 8, True)]
        assert (1, 128, 8) in engine._admitter.prefill_ladder()
        for _ in range(5):
            await asyncio.sleep(0.06)  # idle ticks
        assert engine._admitter._families == {} and not engine._admitter.family_pending
        await _serve(engine, "hit-1", doc=doc, question=24)
        await _serve(engine, "hit-2", doc=doc, question=24)
        await _family_whole(engine, 3)
        assert set(engine._admitter._families) == {(128, 64, False)}  # 528 tokens: 33 blocks
        assert engine.stats()["prefill_family_programs"] == 3
    finally:
        await engine.stop()


async def test_a_rows_bucket_met_before_its_turn_is_not_run_again(monkeypatch):
    """A batch of two asks that arrives while the siblings are pending
    compiles its own program (as before); the family leaves that one out."""
    engine = _engine()
    admitter = engine._admitter
    try:
        await _serve(engine, "fresh")
        await _serve(engine, "hit-1")
        held = asyncio.Event()
        real = admitter.run_pending_family

        async def hold():  # the loop ticks, the family waits
            return await real() if held.is_set() else False

        monkeypatch.setattr(admitter, "run_pending_family", hold)
        await _serve(engine, "hit-2")
        assert [p[0] for p in admitter.family_pending] == [2, 4, 8]
        await _serve(engine, "pair-a", "pair-b")
        held.set()
        engine._wake.set()
        await _family_whole(engine, 2)
        assert engine.stats()["prefill_family_programs"] == 2
    finally:
        await engine.stop()


async def test_a_family_run_writes_no_page_and_emits_no_token():
    engine = _engine()
    try:
        await _serve(engine, "fresh")
        pools = [np.asarray(p) for p in jax.tree.leaves(engine.runner.k_cache)]
        assert any(np.abs(p).max() > 0 for p in pools)
        before = (engine.pool.free_blocks, engine.pool.cached_blocks,
                  engine.prefill_tokens, engine.generated_tokens)
        toks, logps, *_ = await engine._admitter._run_empty_step(
            4, 128, 8, first_chunk=False)
        assert len(toks) == 4
        after = [np.asarray(p) for p in jax.tree.leaves(engine.runner.k_cache)]
        for was, now in zip(pools, after):
            np.testing.assert_array_equal(was, now)
        assert before == (engine.pool.free_blocks, engine.pool.cached_blocks,
                          engine.prefill_tokens, engine.generated_tokens)
        # and the document is still a hit with the logits it had
        again = await _serve(engine, "fresh")
        assert again[0][0].token_ids
    finally:
        await engine.stop()


@pytest.mark.parametrize("model", ["tiny", "tiny-hybrid", "tiny-mla"])
async def test_fresh_prompts_and_one_repeat_never_enter_the_branch(model, monkeypatch):
    """The dense and hybrid cells' traffic: fresh prompts that fit one chunk,
    and the harness's one probe repeat. No family, whatever the model."""
    config = {"tiny": tiny_config, "tiny-hybrid": tiny_hybrid_config,
              "tiny-mla": tiny_mla_config}[model]()
    engine = _engine(config)
    ran = []
    real = engine._admitter._run_empty_step

    async def spy(*a, **kw):
        ran.append((a, kw))
        return await real(*a, **kw)

    monkeypatch.setattr(engine._admitter, "_run_empty_step", spy)
    try:
        rng = np.random.default_rng(7)
        for i in range(4):
            doc = rng.integers(3, 500, int(rng.integers(70, 200))).tolist()
            await _serve(engine, f"fresh-{i}", doc=doc)
        await _serve(engine, "fresh-3", doc=doc)  # the probe repeat: one prefix hit
        for _ in range(5):
            await asyncio.sleep(0.06)  # idle ticks
        stats = engine.stats()
        assert stats["prefill_family_programs"] == 0 == stats["prefill_family_pending"]
        assert not ran
        assert sum(f.met for f in engine._admitter._families.values()) <= 1
    finally:
        await engine.stop()


async def test_top_logprobs_asks_are_a_family_of_their_own():
    """The variant is part of the shape: two plain hits say nothing of the
    top-logprobs program, and two of those bring its own siblings."""
    engine = _engine()
    try:
        await _serve(engine, "fresh")
        await _serve(engine, "top-1", logprobs=1)
        await _serve(engine, "plain-1")
        assert engine.stats()["prefill_family_pending"] == 0
        assert sorted(k[2] for k in engine._admitter._families) == [False, True]
        await _serve(engine, "top-2", logprobs=1)
        await _family_whole(engine, 3)
        assert engine._admitter._families[(128, 8, True)].ran == {1, 2, 4, 8}
        assert engine._admitter._families[(128, 8, False)].ran == {1}
    finally:
        await engine.stop()


async def test_a_failed_family_run_is_dropped_and_serving_goes_on(monkeypatch):
    engine = _engine()
    try:
        await _serve(engine, "fresh")
        await _serve(engine, "hit-1")

        async def broken(*a, **kw):
            raise RuntimeError("no such program")

        monkeypatch.setattr(engine._admitter, "_run_empty_step", broken)
        with _Captured() as log:
            await _serve(engine, "hit-2")
            for _ in range(100):
                if not engine._admitter.family_pending:
                    break
                await asyncio.sleep(0.05)
            await asyncio.sleep(0.15)  # an idle tick publishes the stats
        assert engine.stats()["prefill_family_programs"] == 0
        assert engine.stats()["prefill_family_pending"] == 0
        assert sum("compiles at first use" in r.getMessage() for r in log.records) == 3
        out = await _serve(engine, "hit-3")
        assert out[0][-1].finish_reason is not None and not out[0][-1].error
    finally:
        await engine.stop()


def test_a_family_compile_is_an_info_line_not_a_serving_path_warning():
    watcher = CompileWatcher()
    watcher.start_up_ended()
    with _Captured() as log:
        with watcher.family_warm_up():
            watcher.on_compile("runner.prefill_step", 1, 4.2, "int32[2,128]")
        watcher.on_compile("runner.prefill_step", 1, 4.4, "int32[4,128]")
    family, serving = log.records
    assert family.levelno == logging.INFO
    assert family.getMessage().startswith(
        "compiled for the family of runner.prefill_step int32[2,128] in 4.2")
    assert serving.levelno == logging.WARNING
    assert serving.getMessage().startswith("compiled on the serving path: runner.prefill_step")
    assert [(r["family"], r["serving"]) for r in watcher.recent] == [
        (True, False), (False, True)]
    assert watcher.totals()["compiles"] == 0 and watcher.compiles == 2  # counted as any
