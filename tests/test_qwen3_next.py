"""Qwen3-Next (``qwen3_next``): Gated DeltaNet layers as recurrent state
beside gated softmax attention, softmax-routed experts with a gated shared
expert, zero-centred norms. Small sizes (``tiny-gdn``: float32, blocks of 16),
CPU, seeded; every comparison is of LOGITS (or log-probabilities), never
tokens.

(a) the served forward through the cache (prefill in chunks, then decode)
against the plain reference's full forward; (b) a chunk cut anywhere; (c) the
engine: fresh, chunked, a prefix hit through a snapshot; (d) the EP-2 share
test; (e) what the tolerance catches; (f) ``from_hf_config`` and the preset;
(g) the benchmark's copy of the reference; (h) refusals; (i) the counter pair.
"""

import dataclasses
import importlib.util
import os

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
from dynamo_tpu.models import qwen3_next_reference as ref
from dynamo_tpu.models.config import (
    QWEN3_NEXT_80B_A3B_HF,
    ModelConfig,
    qwen3_next_ep2_config,
    tiny_gdn_config,
)
from dynamo_tpu.ops.moe import moe_ffn
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect

ROOT = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
BENCH_REF = os.path.join(ROOT, "benchmark", "references", "qwen3-next-80b-a3b-ep2.py")
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b-ep2.json")
BLOCK, T, CHUNK, N_DECODE = 16, 200, 64, 12
# float32 on both sides: what is left is the order of float32 sums (the
# chunked delta rule against the token-by-token one, the paged gather against
# the dense mask, the grouped experts against the loop): 5e-6 measured at
# logits of magnitude 4.8, 1e-6 of it (the reason (e) exists: against that
# scale a bfloat16 state reads 0.31 (a token's experts re-route under it), a
# dropped attention gate 0.25, a dropped beta, decay, L2 norm or shared gate
# and a plain-read norm 0.8 to 1.3).
TOL = 2e-5


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), np.abs(got - want).max()


def _params(c, seed=0):
    """Seeded weights with every norm weight MOVED off its neutral value (a
    zero-centred weight of 0 and a plain weight of 1 read the same)."""
    p = llama.init_params(c, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    move = lambda w: w + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype)
    for lp in p["layers"]:
        for name in ("norm", "q_norm", "k_norm", "o_norm"):
            if name in lp:
                lp[name] = move(lp[name])
    p["final_norm"] = move(p["final_norm"])
    return p


def _serve(c, p, toks, chunks):
    """The system's forward over ``toks`` cut into ``chunks`` (a list of
    lengths; a chunk is padded to a multiple of the scan block), logits at
    every position; then (logits, k, v, ssm)."""
    k, v = hybrid.init_kv_cache(c, 64, BLOCK)
    ssm = hybrid.init_ssm_state(c, 1)
    table = jnp.arange(1, 33, dtype=jnp.int32)[None]
    logits, at = [], 0
    for n in chunks:
        width = -(-n // BLOCK) * BLOCK if n > 1 else 1
        ids = np.zeros((1, width), np.int32)
        ids[0, :n] = toks[at:at + n]
        lg, k, v, ssm, _, _ = hybrid.forward(
            p, c, jnp.asarray(ids), jnp.array([at]), jnp.array([n]), table, k, v, ssm,
            first_chunk=(at == 0), all_logits=True)
        logits.append(np.asarray(lg[0, :n]))
        at += n
    return np.concatenate(logits), k, v, ssm


@pytest.fixture(scope="module")
def tiny():
    c = tiny_gdn_config()
    p = _params(c)
    toks = np.random.default_rng(0).integers(3, 512, T + N_DECODE).astype(np.int32)
    layers = ref.describe_layers(c)
    want = ref.reference_forward(p, layers, toks, c.rms_norm_eps)
    return dict(c=c, p=p, toks=toks, layers=layers, ref=want)


# -- (a), (b) the system against the reference ---------------------------------------------------


def test_chunked_prefill_then_cached_decode_matches_the_reference(tiny):
    """Three chunks of 64 and a ragged one of 8 (the first over its own
    registers, the rest over the cache and the carried state), then twelve
    one-token steps: logits at every position against the full forward."""
    got, *_ = _serve(tiny["c"], tiny["p"], tiny["toks"], [64, 64, 64, 8] + [1] * N_DECODE)
    _close(got, tiny["ref"]["logits"])


@pytest.mark.parametrize("chunks", [[200], [16, 184], [37, 64, 99], [128, 1, 71]],
                         ids=lambda c: "+".join(map(str, c)))
def test_a_chunk_cut_anywhere_gives_the_same_result(tiny, chunks):
    got, _, _, ssm = _serve(tiny["c"], tiny["p"], tiny["toks"][:T], chunks)
    _close(got, tiny["ref"]["logits"][:T])
    whole = ref.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][:T], tiny["c"].rms_norm_eps)
    gdn = [i for i, L in enumerate(tiny["layers"]) if L["kind"] == "gated_delta"]
    for n, i in enumerate(gdn):  # the state and the conv tail a later chunk starts from
        _close(ssm["S"][n][0], whole["carry"][i]["S"], 1e-5)
        _close(ssm["conv"][n][0], whole["carry"][i]["conv"], 1e-5)


# -- (c) the engine ---------------------------------------------------------------------------------


def _req(tokens, rid, n):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        sampling=SamplingOptions(temperature=0.0, logprobs=1),
        stop=StopConditions(max_tokens=n, ignore_eos=True))


def _sig(outs):
    assert not [o.error for o in outs if o.error]
    return ([t for o in outs for t in o.token_ids],
            [lp[0].logprob for o in outs for lp in (o.logprobs or [])])


async def test_engine_serves_fresh_chunked_and_as_a_prefix_hit_through_a_snapshot():
    """300 tokens of context in chunks of 64 across page and snapshot
    boundaries, 20 tokens decoded past it; then the context + a fresh turn:
    K/V pages of the one full layer and a snapshot (matrix + conv tail of
    three layers) serve the first 256 tokens. Every served log-probability
    against the reference's full forward (the uncut run)."""
    c = tiny_gdn_config()
    engine = JaxEngine(JaxEngineArgs(config=c, block_size=BLOCK, num_kv_blocks=128, max_num_seqs=4,
                                     max_model_len=1024, prefill_chunk=CHUNK))
    engine.runner.params = _params(c)
    rng = np.random.default_rng(1)
    ctx, turn = rng.integers(3, 500, 300).tolist(), rng.integers(3, 500, 40).tolist()
    try:
        assert (engine.snapshots.capacity, engine.snapshots.stride_blocks) == (32, 4)
        assert engine.runner.ssd_step.startswith("xla every slot")
        fresh = _sig(await collect(engine.generate(_req(ctx, "a", 20), Context())))
        assert engine.snapshots.used == 4  # 64, 128, 192, 256
        before = engine.prefill_tokens
        hit = _sig(await collect(engine.generate(_req(ctx + turn, "b", 20), Context())))
        assert engine.prefill_tokens - before == 340 - 256 and engine.snapshots.hits == 1
        text = engine.step_metrics.render()
        assert "dynamo_tpu_engine_ssm_snapshot_hits_total 1" in text
        params = engine.runner.params
    finally:
        await engine.stop()
    layers = ref.describe_layers(c)
    for prompt, (toks, lps) in ((ctx, fresh), (ctx + turn, hit)):
        seq = np.asarray(prompt + toks[:-1], np.int32)
        at = len(prompt) - 1 + np.arange(len(toks))
        want = jax.nn.log_softmax(
            ref.reference_forward(params, layers, seq, c.rms_norm_eps, positions=at)["logits"], -1)
        chosen = np.asarray(jnp.take_along_axis(want, jnp.asarray(toks)[:, None], -1)[:, 0])
        assert len(lps) == 20
        np.testing.assert_allclose(lps, chosen, atol=TOL, rtol=0)


# -- (d) the share test ------------------------------------------------------------------------------


@pytest.mark.parametrize("tokens", [24, 300], ids=["dense form", "grouped form"])
def test_the_two_ep2_shares_add_up_to_the_uncut_layer(tokens):
    """Each chip of a pair holds half the experts and the whole shared
    expert: the two shares' outputs, the shared expert counted once, are the
    layer with every expert held; and each share is the reference's."""
    c = tiny_gdn_config()
    spec = dataclasses.replace(c.specs_of("experts")[0], held=None)
    whole = hybrid.init_params(dataclasses.replace(
        c, layer_specs=(spec,), n_layers=1), jax.random.PRNGKey(3))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, tokens, c.d_model), jnp.float32)
    uncut = moe_ffn(x, whole, spec)
    shares = []
    for lo, hi in ((0, 8), (8, 16)):
        lp = dict(whole, **{m: whole[m][lo:hi] for m in ("we_up", "we_gate", "we_down")})
        shares.append(moe_ffn(x, lp, spec.holding(lo, hi)))
        L = dict(kind="experts", top_k=spec.top_k, held=(lo, hi), shared_share=1.0)
        with jax.default_matmul_precision("highest"):
            _close(shares[-1][0], ref.ref_experts(x[0], lp, L))
    none_held = dict(whole, **{m: whole[m][:0] for m in ("we_up", "we_gate", "we_down")})
    shared_once = moe_ffn(x, none_held, spec.holding(0, 0))  # the shared expert alone
    _close(shares[0] + shares[1] - shared_once, uncut)
    assert float(jnp.abs(shared_once).max()) > 1e-3  # (it is there to be counted)


# -- (e) what the tolerance catches -------------------------------------------------------------------


@pytest.mark.parametrize("degrade", [
    "state_bf16", "no_beta", "no_decay", "no_l2norm", "norm_plain", "no_shared_gate",
    "no_attn_gate"])
def test_the_tolerance_fails_a_lower_precision_and_every_dropped_law(tiny, degrade):
    """The served logits against the reference DEGRADED one way: each must
    read over the tolerance (a bfloat16 state by 50x, a dropped law by
    1,000x; they read 10,000x and more), or the comparison above would pass
    a program with that fault."""
    got, *_ = _serve(tiny["c"], tiny["p"], tiny["toks"][:T], [64, 64, 64, 8])
    low = ref.reference_forward(
        tiny["p"], tiny["layers"], tiny["toks"][:T], tiny["c"].rms_norm_eps, degrade=degrade)
    err = np.abs(got - np.asarray(low["logits"])).max() / max(1.0, np.abs(got).max())
    assert err > (50 if degrade == "state_bf16" else 1000) * TOL, err


# -- (f) the published configuration ---------------------------------------------------------------


def test_from_hf_config_reads_the_published_layers():
    c = ModelConfig.from_hf_config(QWEN3_NEXT_80B_A3B_HF)
    kinds = [s.kind for s in c.layer_specs]
    assert len(kinds) == 96 and kinds[1::2] == ["experts"] * 48
    assert kinds[0:8:2] == ["gated_delta"] * 3 + ["attention"] and kinds[::2] == kinds[0:8:2] * 12
    gdn, attn, experts = c.layer_specs[0], c.layer_specs[6], c.layer_specs[1]
    assert (gdn.n_heads, gdn.n_k_heads, gdn.head_dim, gdn.k_dim, gdn.conv_kernel) == (32, 16, 128, 128, 4)
    assert (gdn.conv_channels, gdn.scan_block, gdn.snapshot_every) == (8192, 64, 4096)
    assert (attn.n_heads, attn.n_kv_heads, attn.head_dim) == (16, 2, 256)
    assert attn.gate and attn.gate_lanes and attn.qk_norm
    assert (attn.rope.rotary_dim, attn.rope.theta, attn.rope.yarn) == (64, 1e7, None)
    assert (experts.n_experts, experts.top_k, experts.d_ff, experts.shared_d_ff) == (512, 10, 512, 512)
    assert experts.routing == "softmax" and experts.norm_topk and experts.shared_gate
    assert c.rmsnorm_unit_offset and c.rms_norm_eps == 1e-6 and c.vocab_size == 151936
    with pytest.raises(ValueError, match="rope_scaling"):
        ModelConfig.from_hf_config(dict(QWEN3_NEXT_80B_A3B_HF, rope_scaling={"factor": 2}))


def test_the_preset_is_one_period_of_one_ep2_share_at_the_published_widths():
    c = qwen3_next_ep2_config()
    assert [s.kind for s in c.layer_specs[::2]] == ["gated_delta"] * 3 + ["attention"]
    assert all(s.held == (0, 256) and s.n_experts == 512 for s in c.specs_of("experts"))
    assert (c.vocab_size, c.d_model, c.name) == (75968, 2048, "qwen3-next-80b-a3b-ep2")
    shapes = jax.eval_shape(lambda: hybrid.init_params(c, jax.random.PRNGKey(0)))
    per_layer = [sum(a.size for a in jax.tree.leaves(lp)) for lp in shapes["layers"]]
    assert per_layer[0] == 33_720_512 and per_layer[6] == 27_265_536 and per_layer[1] == 809_504_768
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 3_677_613_120
    assert hybrid.ssm_state_bytes(c) == 3 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)  # 6.44 MB
    state = jax.eval_shape(lambda: hybrid.init_ssm_state(c, 64))
    assert [a.shape for a in state["S"]] == [(64, 32, 128, 128)] * 3
    assert [a.shape for a in state["conv"]] == [(64, 3, 8192)] * 3


def test_the_decay_draw_keeps_a_state_for_hundreds_of_tokens():
    """``exp(g)`` a token spans roughly 0.9 to 0.999 over the heads at the
    bias alone (the configuration's file states the law): a state that forgot
    in ten tokens would hide its own errors."""
    lp = hybrid.init_params(tiny_gdn_config(), jax.random.PRNGKey(0))["layers"][0]
    rate = np.exp(np.asarray(lp["A_log"])) * np.log1p(np.exp(np.asarray(lp["dt_bias"])))
    assert (np.exp(-rate) > 0.9).all() and (np.exp(-rate) < 0.9995).all()


# -- (g) the benchmark's copy of the reference --------------------------------------------------------


def _marked(path):
    with open(path) as f:
        text = f.read()
    return text[text.index("# --- reference: begin"): text.index("# --- reference: end")]


def test_benchmark_copy_of_the_reference_agrees(tiny):
    assert _marked(BENCH_REF) == _marked(ref.__file__)
    spec = importlib.util.spec_from_file_location("bench_reference_gdn", BENCH_REF)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    got = copy.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][:96], tiny["c"].rms_norm_eps)
    _close(got["logits"], tiny["ref"]["logits"][:96], 1e-5)


def test_reference_continues_a_prefix_it_computed(tiny):
    """The benchmark's child computes a long context once and continues it
    twice: a prefix's float32 keys, values, states and conv tails, then the
    suffix (padded on the right), is the full forward; and the last attention
    sublayer may compute only the compared queries, in blocks."""
    eps = tiny["c"].rms_norm_eps
    head = ref.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][:120], eps)
    at = np.array([0, 57, 79])
    tail = np.concatenate([tiny["toks"][120:T], np.zeros(16, np.int32)])
    out = ref.reference_forward(tiny["p"], tiny["layers"], tail, eps, carry=head["carry"],
                                positions=at, query_block=2, token_block=32,
                                last_queries_only=True, length=T - 120)
    _close(out["logits"], tiny["ref"]["logits"][120 + at], 1e-5)
    whole = ref.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][:T], eps)
    _close(out["carry"][0]["S"], whole["carry"][0]["S"], 1e-5)
    _close(out["carry"][0]["conv"], whole["carry"][0]["conv"], 1e-5)


def test_reference_child_agrees_in_a_rehearsal(monkeypatch, capsys):
    """The comparison that decides the cell's ``correct``, as the harness's
    CPU rehearsal runs it: the engine's own rows (two contexts built alone,
    two asks at once) against the reference."""
    import sys

    spec = importlib.util.spec_from_file_location("bench_reference_child_gdn", BENCH_REF)
    child = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(child)
    monkeypatch.setattr(sys, "argv", [BENCH_REF, "--config", CONFIG_FILE, "--seed", "5000000011"])
    assert child.main() == 0, capsys.readouterr().out
    out = capsys.readouterr().out
    assert "hits 2" in out and "agrees" in out


def test_reference_child_compares_nothing_off_its_device():
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run([sys.executable, BENCH_REF, "--config", CONFIG_FILE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 2, done.stdout[-2000:] + done.stderr[-2000:]
    assert "NOTHING COMPARED" in done.stdout


# -- (h) refusals -------------------------------------------------------------------------------------


@pytest.mark.parametrize("mechanism", ["the disaggregation wire", "the KVBM tiers",
                                       "the KV checkpoint", "a device mesh", "int8 KV"])
def test_mechanisms_that_carry_only_kv_refuse_the_third_recurrent_kind(mechanism):
    from dynamo_tpu.models.config import refuse_hybrid

    c = tiny_gdn_config()
    with pytest.raises(ValueError, match="Gated DeltaNet") as err:
        if mechanism == "int8 KV":  # the runner's own refusal, through the same words
            JaxEngine(JaxEngineArgs(config=c, block_size=16, num_kv_blocks=64, max_num_seqs=2,
                                    max_model_len=256, prefill_chunk=64, kv_cache_dtype="int8"))
        else:
            refuse_hybrid(c, mechanism)
    assert "matrix transition" in str(err.value) and "conv tail" in str(err.value)


def test_disagg_kvbm_and_checkpoints_refuse_by_name_of_mechanism():
    """The three entry points themselves, each in its own words."""
    from dynamo_tpu.disagg import wire
    from dynamo_tpu.engines.tpu import kv_checkpoint
    from dynamo_tpu.kvbm import tiers

    c = tiny_gdn_config()
    for module, mechanism in ((tiers, "the KVBM tiers"), (kv_checkpoint, "the KV checkpoint"),
                              (wire, "the disaggregation wire")):
        with pytest.raises(ValueError, match=f"{mechanism} moves paged K/V blocks only.*"
                                             "3 Gated DeltaNet layers"):
            module.check_config(c)


# -- (i) the counter pair -----------------------------------------------------------------------------


async def test_decode_bursts_count_choices_on_held_and_on_absent_experts():
    c = tiny_gdn_config()
    engine = JaxEngine(JaxEngineArgs(config=c, block_size=BLOCK, num_kv_blocks=64, max_num_seqs=4,
                                     max_model_len=256, prefill_chunk=CHUNK, decode_steps=4))
    try:
        text = engine.step_metrics.render()  # both series from start-up
        assert 'dynamo_tpu_engine_moe_assignments_total{held="1"} 0' in text
        assert 'dynamo_tpu_engine_moe_assignments_total{held="0"} 0' in text
        await collect(engine.generate(_req(range(10, 40), "a", 9), Context()))
        series = {}
        for line in engine.step_metrics.render().splitlines():
            if line.startswith("dynamo_tpu_engine_moe_assignments_total{"):
                series[line.split('"')[1]] = float(line.rsplit(" ", 1)[1])
        # two bursts of 4 steps x 4 expert layers x top 4 x one live row
        assert series["1"] + series["0"] == 2 * 4 * 4 * 4
        assert 0 < series["1"] < series["1"] + series["0"]
    finally:
        await engine.stop()
