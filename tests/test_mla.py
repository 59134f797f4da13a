"""Latent attention (``pangu_ultra_moe``): one latent pool a layer, the
expanded and the absorbed form, sandwich norms, plain sigmoid routing over a
held share. Small sizes, CPU, seeded.

(a) system against the plain reference; (b) absorbed = expanded, XLA and the
kernel; (c) prefix reuse over cached latents and preemption by recompute;
(d) ``from_hf_config`` and the benchmark's files; (e) the benchmark's copy of
the reference and its child; (f) refusals by mechanism; (g) what the engine
says of the pool.
"""

import asyncio
import dataclasses
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
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models import pangu_ultra_moe_reference as ref
from dynamo_tpu.models.config import (
    OPENPANGU_ULTRA_MOE_718B_HF,
    ModelConfig,
    openpangu_ultra_moe_ep16_config,
    tiny_mla_config,
)
from dynamo_tpu.ops import attention
from dynamo_tpu.ops.pallas import mla_paged
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect

ROOT = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
NAME = "openpangu-ultra-moe-718b-ep16"
BENCH_REF = os.path.join(ROOT, "benchmark", "references", NAME + ".py")
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", NAME + ".json")
STEPS = 8


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """``use_kernel`` on the CPU: the Pallas kernels (the absorbed attention
    and, since its experts are gated silu at a model width of 128, the
    hit-list expert kernel) in interpret mode."""
    from dynamo_tpu.ops import moe
    from dynamo_tpu.ops.pallas import expert_ffn

    monkeypatch.setattr(
        attention, "mla_paged_decode",
        functools.partial(mla_paged._mla_paged_decode_impl, interpret=True))
    monkeypatch.setattr(
        moe, "expert_ffn",
        functools.partial(expert_ffn._expert_ffn_impl, interpret=True))


# -- (a) the system against the reference ---------------------------------------


@pytest.fixture(scope="module")
def tiny():
    c = tiny_mla_config()
    p = llama.init_params(c, jax.random.PRNGKey(0))
    L = ref.describe_layers(c)
    rng = np.random.default_rng(0)
    lens = [45, 32, 20]
    seqs = [rng.integers(0, c.vocab_size, n + STEPS) for n in lens]
    refs = [ref.reference_forward(p, L, s, c.rms_norm_eps, c.rope_theta) for s in seqs]
    return dict(c=c, p=p, L=L, lens=lens, seqs=seqs, refs=refs)


def _serve(tiny, use_kernel):
    """Three prompts of unequal length and a padding row, prefilled in TWO
    chunks (32 fresh, then 16 over the pool), then 8 forced decode steps:
    the logits of every prompt position and every step."""
    c, p, lens, seqs = tiny["c"], tiny["p"], tiny["lens"], tiny["seqs"]
    B = 4
    k, v = llama.init_kv_cache(c, 64, 16, layered=True)
    assert v == () and k[0].shape == (64, 16, 128)  # one latent pool a layer, no V
    ssm = hybrid.init_ssm_state(c, B)
    toks = np.zeros((B, 48), np.int32)
    for r, (s, n) in enumerate(zip(seqs, lens)):
        toks[r, :n] = s[:n]
    tab = jnp.asarray(np.arange(64).reshape(4, 16), jnp.int32)
    cl = np.asarray(lens + [0], np.int32)
    l1 = np.minimum(cl, 32)
    out1 = llama.forward_paged(
        p, c, jnp.asarray(toks[:, :32]), jnp.zeros(B, jnp.int32), jnp.asarray(l1),
        tab, k, v, ssm=ssm, first_chunk=True, all_logits=True)
    out2 = llama.forward_paged(
        p, c, jnp.asarray(toks[:, 32:]), jnp.asarray(l1), jnp.asarray(cl - l1),
        tab, out1[1], out1[2], ssm=ssm, all_logits=True, use_kernel=use_kernel)
    prompt = np.concatenate([np.asarray(out1[0]), np.asarray(out2[0])], axis=1)
    k, steps = out2[1], []
    for t in range(STEPS):
        tok = np.asarray([s[n + t] for s, n in zip(seqs, lens)] + [0], np.int32)
        out = llama.forward_paged(
            p, c, jnp.asarray(tok[:, None]), jnp.asarray(cl + t),
            jnp.asarray((cl > 0).astype(np.int32)), tab, k, (), ssm=ssm,
            use_kernel=use_kernel)
        k = out[1]
        steps.append(np.asarray(out[0]))
    return prompt, np.stack(steps, axis=1)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_system_matches_reference(tiny, path, interpreted_kernel):
    prompt, steps = _serve(tiny, use_kernel=path == "kernel")
    for r, (n, want) in enumerate(zip(tiny["lens"], tiny["refs"])):
        _close(prompt[r, :n], want["logits"][:n], 1e-4)
        _close(steps[r], want["logits"][n : n + STEPS], 1e-4)


# -- (b) absorbed = expanded ---------------------------------------------------------


@pytest.mark.parametrize("case", ["decode_rows", "chunk_over_context", "dead_row",
                                  "query_blocks_of_eight"])
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_absorbed_form_equals_expanded_form(case, path):
    """H heads over the cache rows themselves (key: the whole row; value:
    its first R lanes) against per-head keys and values expanded from the
    same latents, both through the pool's pages."""
    H, R, dr, dn, dv, bs, W = 4, 32, 16, 16, 16, 16, 128
    C, starts, lens = {
        "decode_rows": (1, [37, 5, 90], [1, 1, 1]),
        "chunk_over_context": (4, [30, 0, 61], [4, 3, 4]),
        "dead_row": (1, [37, 0, 12], [1, 0, 1]),
        "query_blocks_of_eight": (16, [40, 3, 17], [16, 9, 16]),
    }[case]
    B = len(starts)
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    pool = jnp.zeros((32, bs, W)).at[..., : R + dr].set(
        jax.random.normal(ks[0], (32, bs, R + dr)))
    tables = jnp.asarray(np.random.default_rng(1).permutation(32)[: B * 8].reshape(B, 8), jnp.int32)
    q_n = jax.random.normal(ks[1], (B, C, H, dn))
    q_r = jax.random.normal(ks[2], (B, C, H, dr))
    w_kb = jax.random.normal(ks[3], (R, H, dn)) * R**-0.5
    w_vb = jax.random.normal(ks[4], (R, H, dv)) * R**-0.5
    start, cl = jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32)
    scale = (dn + dr) ** -0.5
    q_abs = jnp.concatenate([jnp.einsum("bchk,rhk->bchr", q_n, w_kb), q_r], -1)
    q_abs = attention.pad_head(q_abs, W)
    if path == "kernel":
        o_lat = mla_paged._mla_paged_decode_impl(
            q_abs, pool, tables, start, cl, v_width=R, sm_scale=scale, interpret=True)
    else:
        o_lat = attention.mla_paged_attention(
            q_abs, pool, tables, start, cl, v_width=R, sm_scale=scale)
    got = jnp.einsum("bchr,rhk->bchk", o_lat, w_vb)
    # expanded, straightforwardly, from the same pages
    rows = pool[tables].reshape(B, 8 * bs, W)
    k_n = jnp.einsum("btr,rhk->bthk", rows[..., :R], w_kb)
    v = jnp.einsum("btr,rhk->bthk", rows[..., :R], w_vb)
    s = (jnp.einsum("bchk,bthk->bcht", q_n, k_n)
         + jnp.einsum("bchk,btk->bcht", q_r, rows[..., R : R + dr])) * scale
    seen = jnp.arange(8 * bs)[None, None] <= (start[:, None] + jnp.arange(C)[None])[..., None]
    want = jnp.einsum("bcht,bthk->bchk", jax.nn.softmax(
        jnp.where(seen[:, :, None], s, -jnp.inf), -1), v)
    for b in range(B):
        _close(got[b, : lens[b]], want[b, : lens[b]], 1e-4)
        if path == "kernel" and lens[b] == 0:
            assert not np.asarray(o_lat[b]).any()  # never visited: zeros


def test_expanded_chunk_attention_in_query_blocks():
    """A fresh chunk longer than one query block: the blocked scores are the
    unblocked ones."""
    B, C, H, D = 2, 128, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (B, C, H, D)) for kk in ks)
    lens = jnp.asarray([128, 70], jnp.int32)
    got = attention.mla_chunk_attention(q, k, v, lens, sm_scale=D**-0.5)
    want = attention.dense_chunk_attention(q, k, v, lens, sm_scale=D**-0.5)
    for b, n in enumerate([128, 70]):
        _close(got[b, :n], want[b, :n], 1e-5)


# -- (c) the served path: prefix reuse and preemption ------------------------------------


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


def _engine(on_kv_event=None, **kw):
    base = dict(config=tiny_mla_config(), block_size=16, num_kv_blocks=64, max_num_seqs=4,
                max_model_len=512, prefill_chunk=64)
    base.update(kw)
    return JaxEngine(JaxEngineArgs(**base), on_kv_event=on_kv_event)


async def test_served_prefill_then_decode_matches_the_reference(tiny):
    """Through admission, the block pool and the compiled programs: a prompt
    of three chunks, then decode bursts; the log-probability of every chosen
    token against the reference's full forward pass."""
    engine = _engine()
    c = tiny["c"]
    prompt = np.random.default_rng(4).integers(3, 500, 150).tolist()
    try:
        toks, lps = _sig(await collect(engine.generate(_req(prompt, "a", n=17), Context())))
        seq = np.asarray(prompt + toks[:-1], np.int32)
        want = ref.reference_forward(engine.runner.params, tiny["L"], seq, c.rms_norm_eps,
                                     c.rope_theta, positions=np.arange(149, 149 + 17))
        logp = jax.nn.log_softmax(want["logits"], -1)
        chosen = np.take_along_axis(np.asarray(logp), np.asarray(toks)[:, None], 1)[:, 0]
        _close(lps, chosen, 1e-4)
        assert toks == np.asarray(logp.argmax(-1)).tolist()
    finally:
        await engine.stop()


async def test_repeated_document_is_a_prefix_hit_with_the_fresh_logits():
    from dynamo_tpu.runtime.kv_reuse_observe import global_plane

    events = []
    engine = _engine(on_kv_event=events.append)
    rng = np.random.default_rng(5)
    doc, q1 = rng.integers(3, 500, 160).tolist(), rng.integers(3, 500, 21).tolist()
    try:
        fresh = _sig(await collect(engine.generate(_req(doc + q1, "a"), Context())))
        stored = [h for e in events if e.kind == "stored" for h in e.block_hashes]
        assert len(stored) >= 11  # announced as committed: no snapshot stands between
        before = global_plane().snapshot()["reused_prefill_tokens"]
        computed = engine.prefill_tokens
        again = _sig(await collect(engine.generate(_req(doc + q1, "b"), Context())))
        assert global_plane().snapshot()["reused_prefill_tokens"] - before == 176
        assert engine.prefill_tokens - computed == 5  # the tail over cached latents
        assert again[0] == fresh[0]
        np.testing.assert_allclose(again[1], fresh[1], rtol=1e-4, atol=1e-5)
        text = engine.step_metrics.render()
        assert "dynamo_tpu_engine_moe_experts_hit_total" in text
        assert "dynamo_tpu_engine_decode_live_pages_total" in text
    finally:
        await engine.stop()


async def test_preempted_sequence_recomputes_to_the_same_logits():
    prompts = [list(range(10, 42)), list(range(50, 82))]

    async def run(num_kv_blocks, together):
        engine = _engine(num_kv_blocks=num_kv_blocks, max_num_seqs=2, max_model_len=128,
                         prefill_chunk=32)
        try:
            reqs = [_req(p, f"s{i}", n=40) for i, p in enumerate(prompts)]
            if together:
                outs = await asyncio.gather(
                    *(collect(engine.generate(r, Context())) for r in reqs))
            else:
                outs = [await collect(engine.generate(r, Context())) for r in reqs]
            return [_sig(o) for o in outs], engine.preemptions
        finally:
            await engine.stop()

    alone, none = await run(64, together=False)
    crowded, preempted = await run(8, together=True)
    assert none == 0 and preempted > 0
    for (ta, la), (tc, lc) in zip(alone, crowded):
        assert ta == tc
        np.testing.assert_allclose(la, lc, rtol=1e-4, atol=1e-5)


# -- (d) from_hf_config and the benchmark's files ------------------------------------------


def test_from_hf_config_yields_three_dense_and_58_expert_layers():
    cfg = ModelConfig.from_hf_config(OPENPANGU_ULTRA_MOE_718B_HF)
    kinds = [s.kind for s in cfg.layer_specs]
    assert len(kinds) == 122 and kinds[0::2] == ["mla"] * 61
    assert kinds[1::2] == ["dense_ffn"] * 3 + ["experts"] * 58
    assert all(s.post_norm for s in cfg.layer_specs)  # sandwich_norm
    m, d, e = (cfg.specs_of(k)[0] for k in ("mla", "dense_ffn", "experts"))
    assert (m.n_heads, m.q_rank, m.kv_rank, m.nope_dim, m.rope_dim, m.v_dim) == (
        128, 1536, 512, 128, 64, 128)
    assert (m.cache_width, m.qk_dim, d.d_ff) == (576, 192, 18432)
    assert (e.n_experts, e.top_k, e.d_ff, e.shared_d_ff, e.scale) == (256, 8, 2048, 2048, 2.5)
    assert e.routing == "sigmoid" and e.activation == "silu_gated" and e.norm_topk
    assert cfg.rope_theta == 25_600_000 and cfg.has_latent_cache and not cfg.has_recurrent_state
    cut = openpangu_ultra_moe_ep16_config()
    assert [s.kind for s in cut.layer_specs] == ["mla", "dense_ffn"] + ["mla", "experts"] * 4
    assert cut.specs_of("experts")[0].held_ == (0, 16) and cut.vocab_size == 19200
    shapes = jax.eval_shape(lambda: llama.init_params(cut, jax.random.PRNGKey(0)))
    assert round(sum(a.size for a in jax.tree.leaves(shapes)) / 1e6) == 4919
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(cut, 2560, 128, layered=True))
    assert v == () and [a.shape for a in k] == [(2560, 128, 640)] * 5


def test_benchmark_configuration_file_agrees_with_the_preset():
    with open(CONFIG_FILE) as f:
        file = json.load(f)
    for key, value in OPENPANGU_ULTRA_MOE_718B_HF.items():
        if key not in file["reduced"]:
            assert file[key] == value, key
    cut = openpangu_ultra_moe_ep16_config()
    assert file["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "n_routed_experts", "vocab_size"]
    assert file["num_hidden_layers"] * 2 == cut.n_layers and file["first_k_dense_replace"] == 1
    assert file["vocab_size"] == cut.vocab_size
    e = cut.specs_of("experts")[0]
    assert tuple(file["experts_held"]) == e.held_ and file["n_routed_experts"] == e.n_held
    assert file["experts_routed_over"] == e.n_experts
    for key in ("reduced_how", "assumed", "deployment"):
        assert file[key]


# -- (e) the benchmark's copy of the reference and its child ---------------------------------


def _marked(path):
    with open(path) as f:
        text = f.read()
    return text[text.index("# --- reference: begin"): text.index("# --- reference: end")]


def test_benchmark_copy_of_the_reference_agrees(tiny):
    assert _marked(BENCH_REF) == _marked(ref.__file__)
    spec = importlib.util.spec_from_file_location("bench_reference_mla", BENCH_REF)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    seq, c = tiny["seqs"][2][:24], tiny["c"]
    got = copy.reference_forward(tiny["p"], tiny["L"], seq, c.rms_norm_eps, c.rope_theta,
                                 head_group=2, query_block=8, ffn_block=64)
    _close(got["logits"], tiny["refs"][2]["logits"][:24], 1e-5)  # blocking changes nothing


@pytest.mark.parametrize("degrade", ["latent_int8", "softmax_bf16", "no_rope_key"])
def test_reference_degraded_reads_apart(tiny, degrade):
    """Each lower-precision or dropped-term reading of the reference differs
    from the reference by more than the system does."""
    c, seq = tiny["c"], tiny["seqs"][0]
    low = ref.reference_forward(tiny["p"], tiny["L"], seq, c.rms_norm_eps, c.rope_theta,
                                degrade=degrade)
    err = float(jnp.abs(low["logits"] - tiny["refs"][0]["logits"]).max())
    assert err > 1e-3, err


def test_reference_child_agrees_in_a_rehearsal(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location("bench_reference_child_mla", BENCH_REF)
    child = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(child)
    monkeypatch.setattr(sys, "argv", [BENCH_REF, "--config", CONFIG_FILE, "--seed", "3600000011"])
    assert child.main() == 0, capsys.readouterr().out
    out = capsys.readouterr().out
    assert "asked again: reused" in out and "agrees" in out


def test_reference_child_compares_nothing_off_its_device():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run([sys.executable, BENCH_REF, "--config", CONFIG_FILE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 2, done.stdout[-2000:] + done.stderr[-2000:]
    assert "NOTHING COMPARED" in done.stdout


# -- (f) refusals by mechanism ------------------------------------------------------------


@pytest.mark.parametrize("mechanism", ["disaggregation wire", "KVBM tiers", "KV checkpoint",
                                       "int8 KV", "engine export"])
def test_mechanisms_that_carry_only_kv_refuse_a_latent_cache(mechanism):
    c = tiny_mla_config()
    if mechanism == "int8 KV":
        with pytest.raises(ValueError, match="quantized KV pool.*ONE latent pool per layer"):
            _engine(kv_cache_dtype="int8")
        return
    if mechanism == "engine export":
        async def run():
            engine = _engine()
            try:
                with pytest.raises(ValueError, match="disaggregation wire.*latent pool"):
                    await engine.export_blocks_wire_async([1])
                with pytest.raises(ValueError, match="KV checkpoint.*latent pool"):
                    await engine.save_checkpoint("/nonexistent")
            finally:
                await engine.stop()

        asyncio.run(run())
        return
    from dynamo_tpu.disagg import wire
    from dynamo_tpu.engines.tpu import kv_checkpoint
    from dynamo_tpu.kvbm import tiers

    check = {"disaggregation wire": wire.check_config, "KVBM tiers": tiers.check_config,
             "KV checkpoint": kv_checkpoint.check_config}[mechanism]
    with pytest.raises(ValueError, match=f"{mechanism}.*ONE latent pool per layer"):
        check(c)
    check(dataclasses.replace(c, layer_specs=None))  # a dense model passes


# -- (g) what the engine says of the pool -----------------------------------------------------


def test_engine_stats_name_the_latent_pool_and_the_attention_form():
    async def run():
        engine = _engine()
        try:
            return engine.stats()
        finally:
            await engine.stop()

    stats = asyncio.run(run())
    assert stats["latent_pool"]["shape"] == [64, 16, 128]
    assert stats["latent_pool"]["dtype"] == "float32" and stats["latent_pool"]["gb"] >= 0
    assert stats["mla_attention"].startswith("xla: platform is cpu")
    assert stats["expert_ffn"].startswith("xla dense, no Pallas kernels here")
    assert "ssm_state_slots" not in stats
