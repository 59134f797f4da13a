"""Sliding-window and full attention layers in one model (``laguna``) over a
cache that knows the difference: two page groups for one sequence, per-layer
head counts, two rotary laws, the per-head output gate. Small sizes, CPU,
seeded.

(a) system against the plain reference; (b) the window page group through the
engine: prefill + prefix hit + decode past the window, its bound, reuse of
released pages, the prefix rule, preemption; (c) one-group models allocate as
before; (d) the cut is a pipeline stage; (e) ``from_hf_config`` and the
benchmark's files; (f) the benchmark's copy of the reference and its child;
(g) refusals by mechanism; (h) what the engine says of the groups.
"""

import asyncio
import functools
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.engines.tpu.block_pool import WindowPages
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models import laguna_reference as ref
from dynamo_tpu.models.config import (
    LAGUNA_XS2_HF,
    ModelConfig,
    laguna_xs2_pp8_config,
    tiny_config,
    tiny_hybrid_config,
    tiny_swa_config,
)
from dynamo_tpu.ops import attention
from dynamo_tpu.ops.pallas import paged_attention as pa
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect

ROOT = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
NAME = "laguna-xs.2-pp8"
BENCH_REF = os.path.join(ROOT, "benchmark", "references", NAME + ".py")
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", NAME + ".json")
STEPS = 8
WINDOW = 8  # tiny-swa's, over pages of 4


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """``use_kernel`` on the CPU: both paged-attention kernels and the
    hit-list expert kernel in interpret mode."""
    from dynamo_tpu.ops import moe
    from dynamo_tpu.ops.pallas import expert_ffn

    monkeypatch.setattr(
        attention, "paged_attention_decode_kernel",
        functools.partial(pa._paged_attention_decode_kernel_impl, interpret=True))
    monkeypatch.setattr(
        attention, "paged_attention_kernel",
        functools.partial(pa._paged_attention_kernel_impl, interpret=True))
    monkeypatch.setattr(
        moe, "expert_ffn", functools.partial(expert_ffn._expert_ffn_impl, interpret=True))


# -- (a) the system against the reference ---------------------------------------


@pytest.fixture(scope="module")
def tiny():
    c = tiny_swa_config()
    p = llama.init_params(c, jax.random.PRNGKey(0))
    L = ref.describe_layers(c)
    rng = np.random.default_rng(0)
    lens = [45, 32, 20]
    seqs = [rng.integers(0, c.vocab_size, n + STEPS) for n in lens]
    refs = [ref.reference_forward(p, L, s, c.rms_norm_eps) for s in seqs]
    return dict(c=c, p=p, L=L, lens=lens, seqs=seqs, refs=refs)


def _serve(tiny, use_kernel):
    """Three prompts of unequal length and a padding row, prefilled in TWO
    chunks (32 fresh, then 16 over the pools: every sliding layer through
    its window's view of the table), then 8 forced decode steps: the logits
    of every prompt position and every step. Two tables a row: the full
    group's and the window group's, ids of their own."""
    c, p, lens, seqs = tiny["c"], tiny["p"], tiny["lens"], tiny["seqs"]
    B = 4
    k, v = llama.init_kv_cache(c, 64, 4, layered=True, window_blocks=80)
    assert [a.shape[0] for a in k] == [64, 80, 80, 80, 64]  # two pool shapes
    ssm = hybrid.init_ssm_state(c, B)
    toks = np.zeros((B, 48), np.int32)
    for r, (s, n) in enumerate(zip(seqs, lens)):
        toks[r, :n] = s[:n]
    full = np.arange(64).reshape(4, 16)
    tab = jnp.asarray(np.stack([full, 79 - full], axis=1), jnp.int32)  # [B, 2, 16]
    cl = np.asarray(lens + [0], np.int32)
    l1 = np.minimum(cl, 32)
    out1 = llama.forward_paged(
        p, c, jnp.asarray(toks[:, :32]), jnp.zeros(B, jnp.int32), jnp.asarray(l1),
        tab, k, v, ssm=ssm, first_chunk=True, all_logits=True)
    out2 = llama.forward_paged(
        p, c, jnp.asarray(toks[:, 32:]), jnp.asarray(l1), jnp.asarray(cl - l1),
        tab, out1[1], out1[2], ssm=ssm, all_logits=True, use_kernel=use_kernel)
    prompt = np.concatenate([np.asarray(out1[0]), np.asarray(out2[0])], axis=1)
    k, v, steps = out2[1], out2[2], []
    for t in range(STEPS):
        tok = np.asarray([s[n + t] for s, n in zip(seqs, lens)] + [0], np.int32)
        out = llama.forward_paged(
            p, c, jnp.asarray(tok[:, None]), jnp.asarray(cl + t),
            jnp.asarray((cl > 0).astype(np.int32)), tab, k, v, ssm=ssm,
            use_kernel=use_kernel)
        k, v = out[1], out[2]
        steps.append(np.asarray(out[0]))
    return prompt, np.stack(steps, axis=1)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_system_matches_reference(tiny, path, interpreted_kernel):
    """Float32 throughout at toy widths, so the tolerance is float32
    summation order (1e-4 on logits of order 1), nothing of the model."""
    prompt, steps = _serve(tiny, use_kernel=path == "kernel")
    for r, (n, want) in enumerate(zip(tiny["lens"], tiny["refs"])):
        _close(prompt[r, :n], want["logits"][:n], 1e-4)
        _close(steps[r], want["logits"][n : n + STEPS], 1e-4)


def test_window_view_reads_no_slot_behind_the_window():
    """The view of a windowed layer's table holds the slots from the first
    visible key's page on: a released slot (any id) is not in it, and a slot
    past the table's end writes nowhere."""
    table = jnp.asarray([[90 + i for i in range(16)]], jnp.int32)
    read, write, start = hybrid._window_view(
        table, jnp.asarray([41], jnp.int32), WINDOW, 1, 4, 200)
    # keys (33, 41]: pages 8.., three slots for one token
    assert read.tolist() == [[98, 99, 100]] and write.tolist() == read.tolist()
    assert start.tolist() == [41 - 32]
    read, write, _ = hybrid._window_view(
        table, jnp.asarray([63], jnp.int32), WINDOW, 1, 4, 200)
    assert read.tolist() == [[104, 105, 0]] and write.tolist() == [[104, 105, 200]]


def test_chunk_kernel_in_query_blocks_is_the_kernel(monkeypatch):
    """A chunk whose query rows pass the kernel's VMEM is served in blocks of
    query positions, each a chunk of its own that starts later over the same
    table: the same numbers as the XLA form (here the bound is lowered so that
    a toy chunk splits four ways)."""
    monkeypatch.setattr(pa, "CHUNK_ROWS_SPLIT_ABOVE_BYTES", 1 << 12)
    monkeypatch.setattr(pa, "CHUNK_ROWS_SPLIT_TO_BYTES", 1 << 12)
    B, C, H, KH, D, P, bs = 2, 32, 4, 2, 16, 12, 4
    assert pa.chunk_query_block(C, H, D, 4) == 8
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B * P, bs, KH, D)), jnp.float32) for _ in range(2))
    tables = jnp.asarray(rng.permutation(B * P).reshape(B, P), jnp.int32)
    start, lens = jnp.asarray([9, 3], jnp.int32), jnp.asarray([32, 21], jnp.int32)
    for window in (0, WINDOW):
        got = pa._paged_attention_kernel_impl(q, k, v, tables, start, lens, window, interpret=True)
        want = attention._paged_attention_xla(q, k, v, tables, start, lens, window)
        _close(got[0], want[0], 1e-5)
        _close(got[1, :21], want[1, :21], 1e-5)


# -- (b) the window page group through the engine ---------------------------------


def _req(tokens, rid, n=12):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        sampling=SamplingOptions(temperature=0.0, logprobs=1),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )


def _sig(outs):
    toks = [t for o in outs for t in o.token_ids]
    lps = [lp[0].logprob for o in outs for lp in (o.logprobs or [])]
    return toks, lps


def _engine(**kw):
    base = dict(config=tiny_swa_config(), block_size=4, num_kv_blocks=128, max_num_seqs=4,
                max_model_len=512, prefill_chunk=16)
    base.update(kw)
    return JaxEngine(JaxEngineArgs(**base))


async def _against_reference(engine, L, prompt, rid, n):
    toks, lps = _sig(await collect(engine.generate(_req(prompt, rid, n=n), Context())))
    seq = np.asarray(list(prompt) + toks[:-1], np.int32)
    P = len(prompt)
    want = ref.reference_forward(engine.runner.params, L, seq, engine.config.rms_norm_eps,
                                 positions=np.arange(P - 1, P - 1 + n))
    logp = jax.nn.log_softmax(want["logits"], -1)
    chosen = np.take_along_axis(np.asarray(logp), np.asarray(toks)[:, None], 1)[:, 0]
    _close(lps, chosen, 1e-4)  # float32 at toy widths: summation order only
    assert toks == np.asarray(logp.argmax(-1)).tolist()
    return toks, lps


async def test_served_prefill_prefix_hit_and_decode_past_three_windows(tiny):
    """Through admission, both page groups and the compiled programs: a
    context of five chunks (the window group turns over), the context again
    with a fresh turn (a prefix hit over both groups), each decoded past
    three windows; every chosen token's log-probability against the
    reference's full forward pass."""
    engine = _engine()
    rng = np.random.default_rng(4)
    doc, turn = rng.integers(3, 500, 70).tolist(), rng.integers(3, 500, 9).tolist()
    try:
        await _against_reference(engine, tiny["L"], doc, "a", 3 * WINDOW + 6)
        assert engine.window.released >= 70 // 4 - 3  # given back during the prefill
        computed = engine.prefill_tokens
        await _against_reference(engine, tiny["L"], doc + turn, "b", 3 * WINDOW + 6)
        assert engine.prefill_tokens - computed == len(doc + turn) - 68  # 17 pages hit
        assert engine.window.cut_hits == 0
        groups = engine.stats()["kv_groups"]
        assert groups["window"]["used"] == 0 and groups["full"]["cached"] > groups["window"]["cached"]
    finally:
        await engine.stop()


async def _tokens_of(engine, prompts, n, together=False):
    reqs = [_req(p, f"s{i}", n=n) for i, p in enumerate(prompts)]
    if together:
        outs = await asyncio.gather(*(collect(engine.generate(r, Context())) for r in reqs))
    else:
        outs = [await collect(engine.generate(r, Context())) for r in reqs]
    return [_sig(o) for o in outs]


async def test_a_group_that_keeps_whole_contexts_gives_the_same_tokens():
    """The window group as it is sized and released, against one that keeps
    every page of every context: identical tokens and log-probabilities."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(3, 500, n).tolist() for n in (70, 33)]
    real = _engine()
    whole = _engine()
    whole.window = WindowPages(whole.args.num_window_blocks, 4, 1 << 40)
    try:
        got = await _tokens_of(real, prompts, 5 * WINDOW)
        want = await _tokens_of(whole, prompts, 5 * WINDOW)
        assert real.window.released > 0 and whole.window.released == 0
    finally:
        await real.stop()
        await whole.stop()
    for (tg, lg), (tw, lw) in zip(got, want):
        assert tg == tw
        np.testing.assert_allclose(lg, lw, rtol=1e-5, atol=1e-6)


async def test_no_row_passes_its_bound_and_rows_reuse_each_others_pages():
    """Over a decode of 40 windows no live row ever holds more window-group
    pages than the spec's bound, whatever its length; two interleaved
    sequences take pages the other gave back, and neither's logits change."""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(3, 500, n).tolist() for n in (37, 21)]
    n = 40 * WINDOW
    alone = _engine()
    try:
        want = await _tokens_of(alone, prompts, n)
    finally:
        await alone.stop()
    engine = _engine()
    bound = WindowPages.row_bound(WINDOW, 4, max(16, 2 * 8))
    held, owners = [], {}
    reused = set()
    advance = engine._window_advance

    def watched(seq, pos, upto):
        taken = advance(seq, pos, upto)
        held.append(engine.window.held(seq.win_ids))
        for i in taken or ():
            page = seq.win_ids[i]
            if owners.get(page, seq.request.request_id) != seq.request.request_id:
                reused.add(page)
            owners[page] = seq.request.request_id
        return taken

    engine._window_advance = watched
    try:
        got = await _tokens_of(engine, prompts, n, together=True)
        assert max(held) <= bound and len(held) > 2 * n // 8
        assert reused, "no page passed from one running sequence to the other"
        assert engine.window.released >= 2 * (n // 4 - bound)
    finally:
        await engine.stop()
    for (tg, lg), (tw, lw) in zip(got, want):
        assert tg == tw
        np.testing.assert_allclose(lg, lw, rtol=1e-4, atol=1e-5)


async def test_a_chain_whose_window_tail_was_released_matches_where_both_groups_hold():
    """The full group holds every block of a served chain; the window group
    only its prompt's trailing window and its end. A prompt that resumes in
    the middle of the chain's generated part is cut back to the last position
    where both hold (the first prompt's end), and the cut is counted."""
    engine = _engine()
    rng = np.random.default_rng(9)
    doc = rng.integers(3, 500, 70).tolist()
    try:
        toks, _ = _sig(await collect(engine.generate(_req(doc, "a", n=30), Context())))
        computed = engine.prefill_tokens
        again, _ = _sig(await collect(engine.generate(_req(doc + toks[:20], "b", n=10), Context())))
        # 22 blocks of the 90-token prompt are resident in the full group; the
        # window in front of block 22 went as "a" decoded past it: resume at
        # block 17, the end of a's prompt's last whole page.
        assert engine.prefill_tokens - computed == 90 - 68
        assert engine.window.cut_hits == 1
        assert again == toks[20:30]  # the same continuation, recomputed
        assert "dynamo_tpu_engine_prefix_hits_cut_by_window_total 1" in engine.step_metrics.render()
    finally:
        await engine.stop()


def test_cut_match_and_release_rules():
    """WindowPages alone: a match holds where the window in front of the
    resume position is cached; pinned pages and the prompt's tail go back to
    the cache behind the window, a sequence's other pages are freed."""
    win = WindowPages(16, 4, WINDOW)
    hashes = list(range(100, 112))
    pages: list = []
    assert win.advance(pages, [], 0, win.prompt_tail(40), 0, 39) == list(range(10))
    for i in range(10):
        win.commit(pages, i, hashes[i], hashes[i - 1] if i else None)
    assert win.prompt_tail(40) == (8, 10)
    # the first decode step: the pages behind (32, 40] are freed for good
    assert win.advance(pages, hashes[:10], 0, (8, 10), 40, 47) == [10, 11]
    assert win.released == 8 and win.pool.cached_blocks == 0 and win.pool.free_blocks == 12
    # decode to position 60: the prompt's tail (pages 8, 9) goes to the cache
    win.advance(pages, hashes[:10], 0, (8, 10), 60, 60)
    assert win.pool.cached_blocks == 2 and win.released == 12 and win.held(pages) == 4
    assert win.cut_match(hashes, 10, 48) == 10  # resume at 40: pages 8, 9 are here
    assert win.cut_match(hashes, 9, 48) == 0 and win.cut_hits == 1  # at 36: page 7 is not
    got = win.pin_tail(hashes, 10, 48)
    assert got[:8] == [-1] * 8 and win.pool.cached_blocks == 0
    win.release_all(got, hashes[:10])
    win.release_all(pages, hashes[:10])
    assert win.pool.cached_blocks == 2 and win.pool.active_blocks == 0


async def test_a_recurring_prefix_hit_brings_its_family_at_the_tables_own_width():
    """Two tables a row are [rows, 2, width]: the family of a recurring
    prefix-hit prefill program (PR 43) is keyed, and its siblings compiled, at
    the table's WIDTH (my first chip runs of PR 44 keyed it at the group
    axis, 2: the siblings that mattered compiled inside measured windows)."""
    engine = _engine(prefill_chunk=128, block_size=16, max_model_len=1024)
    rng = np.random.default_rng(12)
    doc = rng.integers(3, 500, 200).tolist()
    seen, real = [], engine._run_step

    def spy(tokens, start, lens, tables, *a, **kw):
        seen.append((len(tokens), np.asarray(tables).shape))
        return real(tokens, start, lens, tables, *a, **kw)

    engine._run_step = spy
    try:
        await collect(engine.generate(_req(doc, "alone", n=4), Context()))
        for i in range(2):
            turn = rng.integers(3, 500, 9).tolist()
            await collect(engine.generate(_req(doc + turn, f"hit{i}", n=4), Context()))
        for _ in range(400):
            if engine._admitter.family_programs >= 3 and not engine._admitter.family_pending:
                break
            await asyncio.sleep(0.05)
        assert set(engine._admitter._families) == {(128, 16, True)}  # 209 tokens: 14 pages -> 16; asks with logprobs
        assert sorted(s for s in seen if s[0] > 1) == [(2, (2, 2, 16)), (4, (4, 2, 16)), (8, (8, 2, 16))]
    finally:
        await engine.stop()


async def test_preempted_windowed_row_recomputes_to_the_same_logits():
    prompts = [list(range(10, 42)), list(range(50, 82))]

    async def run(num_kv_blocks, together):
        engine = _engine(num_kv_blocks=num_kv_blocks, max_num_seqs=2, max_model_len=128,
                         prefill_chunk=32)
        try:
            return await _tokens_of(engine, prompts, 40, together), engine.preemptions
        finally:
            await engine.stop()

    alone, none = await run(64, together=False)
    crowded, preempted = await run(28, together=True)
    assert none == 0 and preempted > 0
    for (ta, la), (tc, lc) in zip(alone, crowded):
        assert ta == tc
        np.testing.assert_allclose(la, lc, rtol=1e-4, atol=1e-5)


# -- (c) one-group models allocate as before ----------------------------------------

# Block ids in the order the pool handed them out on this admission script,
# recorded at the parent commit (1d1acd8): two streams at once, then a third
# that hits the first's prefix.
RECORDED = {
    "tiny": [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 21,
             22, 23, 18, 19, 22, 23, 18, 19, 6, 7],
    "tiny-hybrid": [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20,
                    21, 22, 23, 18, 19, 22, 23, 18, 19, 6, 7, 8],
}


@pytest.mark.parametrize("model", sorted(RECORDED))
async def test_one_group_models_allocate_exactly_as_before(model):
    config = {"tiny": tiny_config, "tiny-hybrid": tiny_hybrid_config}[model]()
    engine = JaxEngine(JaxEngineArgs(
        config=config, block_size=4, num_kv_blocks=24, max_num_seqs=2, max_model_len=96,
        prefill_chunk=16))
    assert engine.window is None and engine._block_tables.shape == (2, 24)
    log, alloc = [], engine.pool.alloc

    def recorded():
        log.append(alloc())
        return log[-1]

    engine.pool.alloc = recorded
    rng = np.random.default_rng(11)
    a, b = rng.integers(3, 500, 21).tolist(), rng.integers(3, 500, 13).tolist()
    plain = lambda t, rid, n: PreprocessedRequest(
        token_ids=t, request_id=rid, sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=n, ignore_eos=True))
    try:
        await asyncio.gather(collect(engine.generate(plain(a, "a", 30), Context())),
                             collect(engine.generate(plain(b, "b", 22), Context())))
        await collect(engine.generate(plain(a + b[:5], "c", 9), Context()))
    finally:
        await engine.stop()
    assert log == RECORDED[model]


# -- (d) the cut is a pipeline stage --------------------------------------------------


def test_five_layer_stage_is_the_first_five_layers_of_a_nine_layer_model():
    """The stage's output (its logits are the head over it) equals the
    reference's hidden state after layer 4 of a nine-layer ``tiny-swa`` with
    the same first five layers: the cut is a pipeline stage, nothing else."""
    nine, five = tiny_swa_config(n_layers=9), tiny_swa_config()
    p9 = llama.init_params(nine, jax.random.PRNGKey(3))
    p5 = dict(p9, layers=p9["layers"][:10])
    toks = np.random.default_rng(2).integers(3, 500, 40)
    want = ref.reference_forward(p9, ref.describe_layers(nine), toks, nine.rms_norm_eps)
    after_layer_4 = want["hidden"][10]
    k, v = llama.init_kv_cache(five, 16, 4, layered=True)
    tab = jnp.asarray(np.stack([np.arange(16), np.arange(16)])[None], jnp.int32)
    out = llama.forward_paged(
        p5, five, jnp.asarray(toks[None]), jnp.zeros(1, jnp.int32),
        jnp.asarray([40], jnp.int32), tab, k, v, ssm=hybrid.init_ssm_state(five, 1),
        first_chunk=True, all_logits=True)
    head = ref._head(after_layer_4, p5["final_norm"], p5["lm_head"], five.rms_norm_eps)
    _close(out[0][0], head, 1e-4)


# -- (e) from_hf_config and the benchmark's files ------------------------------------------


def test_from_hf_config_yields_the_forty_layer_spec():
    c = ModelConfig.from_hf_config(LAGUNA_XS2_HF)
    attn, experts = c.specs_of("attention"), c.specs_of("experts")
    assert len(attn) == 40 and len(experts) == 39 and len(c.specs_of("dense_ffn")) == 1
    assert [s.window for s in attn[:5]] == [0, 512, 512, 512, 0]
    assert [s.n_heads for s in attn[:5]] == [48, 64, 64, 64, 48]
    full, slide = attn[0], attn[1]
    assert full.rope.rotary_dim == 64 and full.rope.yarn == (64.0, 4096, 64.0, 1.0)
    assert slide.rope.rotary_dim == 128 and slide.rope.yarn is None and slide.rope.theta == 10000.0
    assert full.gate and slide.gate
    e = experts[0]
    assert (e.n_experts, e.top_k, e.d_ff, e.routing, e.scale, e.shared_d_ff) == (
        256, 8, 512, "sigmoid", 2.5, 512)
    groups = c.cache_groups
    assert [(g.name, len(g.layers), g.window) for g in groups] == [("full", 10, 0), ("window", 30, 512)]
    # the whole model is the published 33.4 B
    shapes = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0)))
    assert 33.3e9 < sum(a.size for a in jax.tree.leaves(shapes)) < 33.5e9


def test_yarn_frequencies_blend_between_the_two_correction_dims():
    from dynamo_tpu.ops.rope import rope_freqs

    law = laguna_xs2_pp8_config().specs_of("attention")[0].rope
    f = np.asarray(rope_freqs(law))
    plain = 1.0 / (500000.0 ** (np.arange(0, 64, 2) / 64))
    assert f.shape == (32,)
    _close(f[:6], plain[:6], 1e-6)  # the fast lanes rotate as they did
    _close(f[-8:], plain[-8:] / 64.0, 1e-6)  # the slow ones stretched 64-fold
    assert np.all(np.diff(f) < 0) and np.all(f <= plain * 1.000001) and np.all(f >= plain / 64.001)


def test_benchmark_configuration_file_agrees_with_the_preset():
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"]["num_hidden_layers"] == 40
    for key, value in LAGUNA_XS2_HF.items():
        if key in ("num_hidden_layers", "layer_types", "mlp_layer_types",
                   "num_attention_heads_per_layer"):
            continue
        assert cfg[key] == value, key
    assert cfg["layer_types"] == LAGUNA_XS2_HF["layer_types"][:5]
    preset = laguna_xs2_pp8_config()
    from_file = ModelConfig.from_hf_config({k: cfg[k] for k in LAGUNA_XS2_HF})
    assert from_file.layer_specs == preset.layer_specs and len(preset.layer_specs) == 10
    args = cfg["serving"]["workers"][0]["args"]
    assert args[args.index("--model") + 1] == preset.name == cfg["name"]


def test_the_full_group_holds_two_layers_pages_a_token_where_one_table_held_five():
    """Two pool shapes: the full group's blocks in 2 layers, the window
    group's in 3. One block id for all five layers would hold the full
    group's blocks five times: 5/2 of the full group's bytes."""
    engine = _engine()
    pool = engine.runner.kv_pool
    full, win = pool["groups"]["full"], pool["groups"]["window"]
    assert (full["layers"], win["layers"]) == (2, 3)
    assert full["shape"][0] == 128 and win["shape"][0] == engine.args.num_window_blocks == 40
    assert [a.shape[0] for a in engine.runner.k_cache] == [128, 40, 40, 40, 128]
    per_layer = 2 * int(np.prod(full["shape"])) * 4  # K and V, float32 here
    assert full["bytes"] == 2 * per_layer and pool["one_block_id_bytes"] == 5 * per_layer
    asyncio.run(engine.stop())


# -- (f) the benchmark's copy of the reference and its child -----------------------------------


def _marked(path):
    text = open(path).read()
    return text[text.index("# --- reference: begin"):text.index("# --- reference: end")]


def test_benchmark_copy_of_the_reference_agrees(tiny):
    assert _marked(BENCH_REF) == _marked(ref.__file__)
    spec = importlib.util.spec_from_file_location("laguna_child", BENCH_REF)
    child = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(child)
    with open(CONFIG_FILE) as f:
        assert child.describe(json.load(f)) == ref.describe_layers(laguna_xs2_pp8_config())
    got = child.reference_forward(tiny["p"], tiny["L"], tiny["seqs"][1], tiny["c"].rms_norm_eps)
    _close(got["logits"], tiny["refs"][1]["logits"], 1e-6)


@pytest.mark.parametrize("degrade", ["softmax_bf16", "kv_int8", "window_511", "window_513",
                                     "rope_all_lanes", "no_gate"])
def test_reference_degraded_reads_apart(tiny, degrade):
    got = ref.reference_forward(tiny["p"], tiny["L"], tiny["seqs"][0], tiny["c"].rms_norm_eps,
                                degrade=degrade)
    apart = float(np.abs(np.asarray(got["logits"]) - np.asarray(tiny["refs"][0]["logits"])).max())
    assert apart > (1e-3 if degrade in ("softmax_bf16", "kv_int8") else 1e-2)


def test_reference_in_blocks_is_the_reference(tiny):
    got = ref.reference_forward(tiny["p"], tiny["L"], tiny["seqs"][0][:48], tiny["c"].rms_norm_eps,
                                kv_group=1, query_block=16, attention_of=(2, 8))
    want = ref.reference_forward(tiny["p"], tiny["L"], tiny["seqs"][0][:48], tiny["c"].rms_norm_eps)
    _close(got["logits"], want["logits"], 1e-5)
    assert got["attention"][2].shape == (48, 16, 16) and got["attention"][8].shape == (48, 12, 16)


def test_reference_child_agrees_in_a_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, BENCH_REF, "--config", CONFIG_FILE, "--seed", "7"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "agrees" in out.stdout and "prefix hits cut 0" in out.stdout


def test_reference_child_compares_nothing_off_its_device():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORM_NAME"] = "cpu"
    out = subprocess.run(
        [sys.executable, BENCH_REF, "--config", CONFIG_FILE, "--seed", "7"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2 and "NOTHING COMPARED" in out.stdout


# -- (g) refusals by mechanism ------------------------------------------------------------------


@pytest.mark.parametrize("mechanism", ["disaggregation wire", "KVBM tiers", "KV checkpoint",
                                       "int8 KV"])
def test_mechanisms_that_carry_one_block_list_refuse_a_window_page_group(mechanism):
    import dataclasses

    from dynamo_tpu.disagg import wire
    from dynamo_tpu.engines.tpu import kv_checkpoint
    from dynamo_tpu.kvbm import tiers

    c = tiny_swa_config()
    if mechanism == "int8 KV":
        with pytest.raises(ValueError, match="quantized KV pool.*two page groups"):
            _engine(kv_cache_dtype="int8")
        return
    check = {"disaggregation wire": wire.check_config, "KVBM tiers": tiers.check_config,
             "KV checkpoint": kv_checkpoint.check_config}[mechanism]
    with pytest.raises(ValueError, match=f"{mechanism}.*two page groups.*sliding-window layers"):
        check(c)
    check(dataclasses.replace(c, layer_specs=None))  # a dense model passes


# -- (h) what the engine says of the groups ---------------------------------------------------------


def test_engine_stats_and_metrics_name_the_page_groups():
    engine = _engine()
    try:
        stats = engine.stats()
        assert set(stats["kv_groups"]) == {"full", "window"}
        assert stats["kv_groups"]["window"]["total"] == engine.args.num_window_blocks
        text = engine.step_metrics.render()
        for series in ('kv_group_blocks{group="window",state="total"} 40',
                       'kv_group_blocks{group="full",state="used"} 0',
                       "window_pages_released_total 0", "prefix_hits_cut_by_window_total 0"):
            assert "dynamo_tpu_engine_" + series in text, series
        dense = JaxEngine(JaxEngineArgs(config=tiny_config(), block_size=4, num_kv_blocks=16,
                                        max_num_seqs=2, max_model_len=32))
        assert "kv_groups" not in dense.stats()
        assert "kv_group_blocks{" not in dense.step_metrics.render()
        asyncio.run(dense.stop())
    finally:
        asyncio.run(engine.stop())
