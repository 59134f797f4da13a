"""Speculative decoding (prompt-lookup / n-gram): greedy output must be
token-identical to the plain fused-decode path — speculation changes
latency, never content. (Engine role of vLLM-style spec decode, TPU-shaped:
one [B, K+1]-token verify dispatch, no draft model.)"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect

from tests.test_jax_engine import make_engine, req, run_one


class TestProposal:
    def _seq(self, tokens):
        from dynamo_tpu.engines.tpu.engine import _Sequence

        return _Sequence(
            request=None, context=None, queue=None,
            prompt=list(tokens), all_tokens=list(tokens),
        )

    def _engine(self, **over):
        engine, _ = make_engine(spec_mode="ngram", spec_ngram=2, spec_k=3, **over)
        return engine

    def test_repeating_pattern_proposes_continuation(self):
        engine = self._engine()
        # ... 1 2 3 4 1 2 → trailing (1, 2) last occurred at the start,
        # followed by 3 4 1 — that's the proposal.
        seq = self._seq([1, 2, 3, 4, 1, 2])
        assert engine._propose(seq) == [3, 4, 1]

    def test_no_match_no_proposal(self):
        engine = self._engine()
        seq = self._seq([1, 2, 3, 4, 5, 6])
        assert engine._propose(seq) == []

    def test_most_recent_occurrence_wins(self):
        engine = self._engine()
        # (7, 8) occurs twice; the LATER occurrence's continuation (9) wins.
        seq = self._seq([7, 8, 1, 7, 8, 9, 5, 7, 8])
        assert engine._propose(seq)[0] == 9 or engine._propose(seq) == []
        # deterministic check: index maps the n-gram to its last position
        prop = engine._propose(seq)
        assert prop[:1] == [9]

    def test_incremental_index_extends(self):
        engine = self._engine()
        seq = self._seq([1, 2, 3])
        engine._propose(seq)
        seq.all_tokens.extend([1, 2])  # now the (1,2) ngram has history
        assert engine._propose(seq) == [3, 1, 2][: engine.args.spec_k]


async def _greedy_tokens(engine, prompt, n):
    out = await run_one(engine, req(prompt, max_tokens=n))
    return [t for o in out for t in o.token_ids]


@pytest.mark.parametrize("prompt", [
    list(range(10, 26)),                      # arbitrary
    [5, 6, 7, 8] * 5,                         # repetitive (proposals fire)
])
async def test_spec_matches_plain_greedy(prompt):
    plain, _ = make_engine()
    spec, _ = make_engine(spec_mode="ngram", spec_ngram=2, spec_k=3)
    try:
        want = await _greedy_tokens(plain, prompt, 12)
        got = await _greedy_tokens(spec, prompt, 12)
        assert got == want
    finally:
        await plain.stop()
        await spec.stop()


async def test_spec_accepts_on_looping_output():
    """Tiny random models loop; a looping greedy continuation is exactly
    what prompt-lookup predicts, so acceptances must accumulate."""
    spec, _ = make_engine(spec_mode="ngram", spec_ngram=2, spec_k=3)
    try:
        prompt = [9, 4] * 8
        await _greedy_tokens(spec, prompt, 48)
        assert spec.spec_proposed > 0
        # acceptance depends on the random model's loop; proposal machinery
        # must at least have engaged. (Equivalence is the hard guarantee,
        # asserted above.)
        assert spec.spec_accepted >= 0
    finally:
        await spec.stop()


async def test_default_temperature_completes_under_spec():
    """temperature=None means the DEFAULT (1.0, sampled). Since r5 the
    rejection-sampling verify serves sampled rows EXACTLY (distribution
    preservation is asserted in tests/test_spec_sampling.py), so sampled
    requests may engage the spec path — they must simply complete."""
    spec, _ = make_engine(spec_mode="ngram")
    try:
        r = PreprocessedRequest(
            token_ids=[5, 6, 7, 8] * 3,
            request_id="default-temp",
            sampling=SamplingOptions(),  # temperature unset
            stop=StopConditions(max_tokens=5, ignore_eos=True),
        )
        out = await collect(spec.generate(r, Context()))
        assert len([t for o in out for t in o.token_ids]) == 5
    finally:
        await spec.stop()


async def test_sampling_request_completes():
    """A temperature>0 request in the batch is served by the
    rejection-sampling verify (or the fused path when nothing proposes)
    and still completes."""
    spec, _ = make_engine(spec_mode="ngram")
    try:
        r = PreprocessedRequest(
            token_ids=[5, 6, 7, 8] * 3,
            request_id="sampled",
            sampling=SamplingOptions(temperature=0.9, top_p=0.9),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
        out = await collect(spec.generate(r, Context()))
        assert len([t for o in out for t in o.token_ids]) == 6
    finally:
        await spec.stop()


async def test_spec_respects_max_model_len():
    spec, _ = make_engine(spec_mode="ngram", max_model_len=32)
    try:
        prompt = [3, 4] * 12  # 24 tokens; room for 8 more
        out = await run_one(spec, req(prompt, max_tokens=64))
        toks = [t for o in out for t in o.token_ids]
        assert len(prompt) + len(toks) <= 32
        assert out[-1].finish_reason is not None
    finally:
        await spec.stop()


async def test_spec_under_tp_mesh_matches_unsharded():
    """Speculative decoding under a tp=2 mesh: the all-positions-logits
    verify program must shard like the rest of the engine and stay
    token-identical to the unsharded plain-greedy path."""
    import jax

    from dynamo_tpu.parallel import MeshConfig, ShardingRules, make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    # Long enough that the model's own loop forms and proposals fire
    # (tiny random models converge to short cycles).
    prompt = [9, 4] * 8
    n_tokens = 48

    plain, _ = make_engine(max_model_len=256)
    try:
        want = await _greedy_tokens(plain, prompt, n_tokens)
    finally:
        await plain.stop()

    mesh = make_mesh(MeshConfig(tp=2))
    spec, _ = make_engine(
        mesh=mesh, rules=ShardingRules(), max_model_len=256,
        spec_mode="ngram", spec_ngram=2, spec_k=3,
    )
    try:
        got = await _greedy_tokens(spec, prompt, n_tokens)
        assert got == want
        # The sharded verify program must actually have run — a silent
        # fallback to the plain path would make this test vacuous.
        assert spec.spec_proposed > 0
    finally:
        await spec.stop()


async def test_spec_concurrent_batch_equivalence():
    plain, _ = make_engine()
    spec, _ = make_engine(spec_mode="ngram", spec_ngram=2, spec_k=3)
    try:
        prompts = [[5, 6, 7, 8] * 4, list(range(30, 46)), [9, 9, 9, 9] * 4]
        want = await asyncio.gather(
            *(_greedy_tokens(plain, p, 8) for p in prompts)
        )
        got = await asyncio.gather(
            *(_greedy_tokens(spec, p, 8) for p in prompts)
        )
        assert got == want
    finally:
        await plain.stop()
        await spec.stop()
