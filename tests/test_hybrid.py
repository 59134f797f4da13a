"""Hybrid models (``nemotron_h``): Mamba-2 state beside paged K/V, dropless
experts held as a share, attention without positions. Small sizes, CPU, seeded.

(a) system against the plain reference; (b) chunked scan = sequential
recurrence; (c) dropless; (d) the share test; (e) prefix reuse from a state
snapshot and preemption by recompute; (f) ``from_hf_config``; (g) the
benchmark's copy of the reference; (h) refusals by mechanism.
"""

import asyncio
import dataclasses
import functools
import importlib.util
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
from dynamo_tpu.models import nemotron_h_reference as ref
from dynamo_tpu.models.config import (
    NEMOTRON_3_NANO_30B_A3B_HF,
    ExpertsSpec,
    ModelConfig,
    nemotron3_nano_ep2_config,
    tiny_hybrid_config,
)
from dynamo_tpu.ops import mamba2 as m2
from dynamo_tpu.ops import moe
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect

ROOT = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
BENCH_REF = os.path.join(ROOT, "benchmark", "references", "nemotron-3-nano-30b-a3b-ep2.py")
STEPS = 8


# -- (a) the system against the reference ---------------------------------------


@pytest.fixture(scope="module")
def tiny():
    """One ragged batch through the system's forward: three prompts of
    unequal length and a padding row, prefilled in TWO chunks (32 then 16
    tokens wide) through the paged K/V and the recurrent state, then a burst
    of 8 forced decode steps; and the reference's logits for the same."""
    c = tiny_hybrid_config()
    p = llama.init_params(c, jax.random.PRNGKey(0))
    L = ref.describe_layers(c)
    rng = np.random.default_rng(0)
    lens = [45, 32, 20]
    seqs = [rng.integers(0, c.vocab_size, n + STEPS) for n in lens]
    refs = [ref.reference_forward(p, L, s, c.rms_norm_eps) for s in seqs]
    B = 4
    k, v = llama.init_kv_cache(c, 64, 16, layered=True)
    ssm = hybrid.init_ssm_state(c, B)
    toks = np.zeros((B, 48), np.int32)
    for r, (s, n) in enumerate(zip(seqs, lens)):
        toks[r, :n] = s[:n]
    tab = jnp.asarray(np.arange(64).reshape(4, 16), jnp.int32)
    cl = np.asarray(lens + [0], np.int32)
    l1 = np.minimum(cl, 32)
    out1 = llama.forward_paged(
        p, c, jnp.asarray(toks[:, :32]), jnp.zeros(B, jnp.int32), jnp.asarray(l1),
        tab, k, v, ssm=ssm, first_chunk=True)
    out2 = llama.forward_paged(
        p, c, jnp.asarray(toks[:, 32:]), jnp.asarray(l1), jnp.asarray(cl - l1),
        tab, out1[1], out1[2], ssm=out1[3])
    k, v, ssm = out2[1], out2[2], out2[3]
    after_prefill = ssm
    pos, act = jnp.asarray(cl), jnp.asarray([1, 1, 1, 0], jnp.int32)
    decode, stats = [], []
    for t in range(STEPS):
        tk = jnp.asarray([seqs[r][lens[r] + t] for r in range(3)] + [0], jnp.int32)
        out = llama.forward_paged(
            p, c, tk[:, None], pos, act, tab, k, v, ssm=ssm, want_moe_stats=True)
        k, v, ssm = out[1], out[2], out[3]
        pos = pos + act
        decode.append(out[0])
        stats.append(out[5])
    return dict(c=c, p=p, L=L, lens=lens, seqs=seqs, refs=refs, out1=out1, out2=out2,
                after_prefill=after_prefill, ssm=ssm, decode=decode, stats=stats)


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("case", ["prompt_in_two_chunks", "ragged_batch", "padding_row",
                                  "decode_burst", "state_after_burst", "expert_load"])
def test_system_matches_reference(tiny, case):
    t = tiny
    lens, refs = t["lens"], t["refs"]
    if case == "prompt_in_two_chunks":  # row 0: 32 + 13 tokens
        _close(t["out2"][0][0], refs[0]["logits"][lens[0] - 1])
    elif case == "ragged_batch":  # rows 1, 2 end in the first chunk, padded after
        _close(t["out1"][0][1], refs[1]["logits"][lens[1] - 1])
        _close(t["out1"][0][2], refs[2]["logits"][lens[2] - 1])
        for r in (1, 2):  # the second chunk (length 0) left their state alone
            for m in range(2):
                _close(t["out2"][3]["S"][m][r], t["out1"][3]["S"][m][r], 0)
    elif case == "padding_row":
        for leaf in jax.tree.leaves(t["after_prefill"]):
            assert not np.asarray(leaf[3]).any()
        for leaf in jax.tree.leaves(t["ssm"]):  # inactive through the burst
            assert not np.asarray(leaf[3]).any()
    elif case == "decode_burst":
        for step, lg in enumerate(t["decode"]):
            for r in range(3):
                _close(lg[r], refs[r]["logits"][lens[r] + step])
    elif case == "state_after_burst":
        for r in range(3):
            for m in range(2):
                _close(t["ssm"]["S"][m][r], refs[r]["S"][m])
                _close(t["ssm"]["conv"][m][r], refs[r]["conv"][m])
    else:  # per step over 2 expert layers: held experts hit <= 4 each, pairs <= 3 rows x 3
        for st in t["stats"]:
            hit, most, pairs = (float(x) for x in st)
            assert 0 < hit <= 2 * 4 and 0 < most <= 2 * 3 and most <= pairs <= 2 * 9


# -- (b) chunked scan = sequential recurrence ------------------------------------


@pytest.mark.parametrize("length", [16, 48, 23, 37, 1])
def test_chunked_scan_equals_sequential_recurrence(length):
    rng = np.random.default_rng(length)
    B, H, P, G, N, Q = 2, 4, 8, 2, 8, 16
    T = -(-length // Q) * Q
    x = rng.standard_normal((B, T, H, P)).astype(np.float32)
    dt = np.abs(rng.standard_normal((B, T, H))).astype(np.float32) * 0.3
    dt[:, length:] = 0.0  # padded positions
    A = -np.abs(rng.standard_normal(H)).astype(np.float32) - 0.1
    Bm = rng.standard_normal((B, T, G, N)).astype(np.float32)
    Cm = rng.standard_normal((B, T, G, N)).astype(np.float32)
    S0 = rng.standard_normal((B, H, P, N)).astype(np.float32)
    y, ends = m2.ssd_chunk_scan(*map(jnp.asarray, (x, dt, A, Bm, Cm, S0)), chunk=Q)
    S, ys = jnp.asarray(S0), []
    for t in range(length):
        yt, S = m2.ssd_step(x[:, t], dt[:, t], jnp.asarray(A), Bm[:, t], Cm[:, t], S)
        ys.append(yt)
    _close(y[:, :length], jnp.stack(ys, 1), 1e-4)
    _close(ends[:, -1], S, 1e-4)  # padding left the state where the last token did


# -- (c) dropless ------------------------------------------------------------------


def _loop_moe(x, lp, spec):
    """Per-token loop oracle over ALL experts of the router (no capacity)."""
    T, d = x.shape
    w, idx = moe.route(x, lp, spec)
    out = np.zeros((T, d))
    for t in range(T):
        for k in range(spec.top_k):
            e = int(idx[t, k])
            h = np.asarray(x[t], np.float64)
            up = h @ np.asarray(lp["we_up"][e], np.float64)
            if spec.activation == "relu2":
                a = np.square(np.maximum(up, 0))
            else:
                g = h @ np.asarray(lp["we_gate"][e], np.float64)
                a = g / (1 + np.exp(-g)) * up
            out[t] += float(w[t, k]) * (a @ np.asarray(lp["we_down"][e], np.float64))
    return out


@pytest.fixture
def hit_list_calls(monkeypatch):
    """``moe_ffn(use_kernel=True)`` on the CPU: the hit-list kernel under
    the Pallas interpreter. Yields the (ids, count) of each call made."""
    from dynamo_tpu.ops.pallas.expert_ffn import expert_ffn

    calls = []

    def interpreted(xs, comb, up, down, ids, count, gate=None):
        calls.append((np.asarray(ids), int(count[0])))
        return expert_ffn(xs, comb, up, down, ids, count, gate, interpret=True)

    monkeypatch.setattr(moe, "expert_ffn", interpreted)
    return calls


@pytest.mark.parametrize("form", ["dense", "grouped", "hit_list"])
def test_dropless_when_every_token_chooses_one_expert(form, monkeypatch, hit_list_calls):
    """All 24 tokens tie on expert 0 (a zero router): the capacity-factor op
    this replaced computed one and dropped the rest. Every one is computed."""
    rng = np.random.default_rng(1)
    # the kernel reads relu2 experts of a model width that fills the lanes
    kernel = form == "hit_list"
    d, E, f, T = 128 if kernel else 8, 4, 16, 24
    spec = ExpertsSpec(n_experts=E, top_k=1, d_ff=f,
                       activation="relu2" if kernel else "silu_gated")
    lp = dict(
        router_w=jnp.zeros((d, E), jnp.float32),
        we_gate=jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, jnp.float32),
        we_up=jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, jnp.float32),
        we_down=jnp.asarray(rng.standard_normal((E, f, d)) * 0.2, jnp.float32),
    )
    x = jnp.asarray(rng.standard_normal((1, T, d)), jnp.float32)
    monkeypatch.setattr(moe, "DENSE_TOKENS_MAX", 0 if form == "grouped" else 256)
    y, stats = moe.moe_ffn(x, lp, spec, want_stats=True, use_kernel=kernel)
    assert [n for _, n in hit_list_calls] == ([1] if kernel else [])
    want = _loop_moe(x[0], lp, spec)
    _close(y[0], want, 1e-4)
    assert (np.abs(want).max(-1) > 1e-6).all()  # no token's output is zero
    assert [float(s) for s in stats] == [1.0, T, T]  # one expert hit, by all


HIT_CASES = {
    # name: (experts the router may choose, of 8; [2, 6) are held)
    "one_expert_hit": (0, 3),
    "a_few_hit": (1, 2, 4, 7),
    "all_held_hit": tuple(range(8)),
}


@pytest.mark.parametrize("dead_rows", [False, True], ids=["all_live", "dead_rows"])
@pytest.mark.parametrize("case", sorted(HIT_CASES))
def test_hit_list_kernel_is_the_dense_form_over_the_experts_hit(
    case, dead_rows, hit_list_calls
):
    """The hit-list form against the per-token loop and against the dense
    form, holding experts [2, 6) of a router 8 wide: its list is the held
    experts that a LIVE token chose, ids first, the last repeated; a dead
    row, which alone chooses held expert 5, adds nothing to it and counts
    on no expert."""
    rng = np.random.default_rng(7)
    d, E, f, T, K, lo, hi = 128, 8, 48, 12, 2, 2, 6
    allowed = [e for e in HIT_CASES[case] if not (dead_rows and e == 5)]
    spec = ExpertsSpec(n_experts=E, top_k=K, d_ff=f, routing="sigmoid_bias",
                       activation="relu2", held=(lo, hi))
    live = np.ones(T, bool)
    x = rng.standard_normal((T, d)).astype(np.float32)
    router_w = rng.standard_normal((d, E)).astype(np.float32) * 0.1
    bias = np.full(E, -100.0, np.float32)
    bias[allowed] = 0.0
    if dead_rows:
        # feature 0 sends the dead rows, and only them, to expert 5
        live[[1, 6, 11]] = False
        x[:, 0] = np.where(live, -8.0, 8.0)
        router_w[0], router_w[1:, 5], bias[5] = 0.0, 0.0, 0.0
        router_w[0, 5] = 4.0
    lp = dict(
        router_w=jnp.asarray(router_w), router_bias=jnp.asarray(bias),
        we_up=jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32),
        we_down=jnp.asarray(rng.standard_normal((E, f, d)) * 0.2, jnp.float32),
    )
    held = {k: (v[lo:hi] if k.startswith("we_") else v) for k, v in lp.items()}
    x = jnp.asarray(x)
    _, top_i = moe.route(x, lp, spec)
    top_i = np.asarray(top_i)
    if dead_rows:
        assert (top_i[~live] == 5).any(1).all() and not (top_i[live] == 5).any()
    want_hit = sorted({int(e) - lo for e in top_i[live].ravel() if lo <= e < hi})

    mask = jnp.asarray(live).reshape(1, T)
    y, stats = moe.moe_ffn(x[None], held, spec, row_mask=mask, want_stats=True,
                           use_kernel=True)
    (ids, count), = hit_list_calls
    assert count == len(want_hit) == {"one_expert_hit": 1}.get(case, count)
    assert case != "all_held_hit" or count == hi - lo - dead_rows
    assert list(ids[:count]) == want_hit and (ids[count:] == ids[count - 1]).all()
    assert len(ids) == hi - lo + 1  # never a list of one entry

    dense, dense_stats = moe.moe_ffn(x[None], held, spec, row_mask=mask, want_stats=True)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(dense_stats))
    assert float(stats[0]) == count
    _close(y[0], dense[0], 1e-5)
    assert float(jnp.abs(y[0][~live]).max(initial=0.0)) == 0.0  # routed nowhere
    # the loop oracle computes every expert of the router: the held share of it
    absent = dict(lp, we_down=lp["we_down"].at[lo:hi].set(0.0))
    want = _loop_moe(x, lp, spec) - _loop_moe(x, absent, spec)
    _close(y[0][live], want[live], 1e-4)


GATED_CASES = {
    # name: (d, f, activation): the kernel's two layouts (``we_up`` resident f
    # minor where f fills the 128 lanes, d minor where it does not) and its two
    # activations, several tiles of each matrix an expert
    "f_minor_gated": (256, 256, "silu_gated"),
    "f_minor_relu2": (256, 384, "relu2"),
    "d_minor_gated": (128, 48, "silu_gated"),
    "d_minor_relu2": (128, 48, "relu2"),
}


@pytest.mark.parametrize("hit", ["none", "one", "two", "all"])
@pytest.mark.parametrize("case", sorted(GATED_CASES))
def test_expert_kernel_with_a_gate_matrix_is_the_dense_form(case, hit, monkeypatch):
    """``expert_ffn`` under the Pallas interpreter against ``_experts_dense``:
    silu(x @ gate) * (x @ up) or relu2(x @ up), then the down product, over
    the experts on the list and no others (an expert off the list holds NaN: it
    is never read), dead rows adding nothing, an empty list giving zeros."""
    from dynamo_tpu.ops.pallas import expert_ffn as ef

    d, f, activation = GATED_CASES[case]
    E, T, K = 6, 12, 2
    rng = np.random.default_rng(11)
    chosen = {"none": [], "one": [3], "two": [1, 4], "all": list(range(E))}[hit]
    spec = ExpertsSpec(n_experts=E, top_k=K, d_ff=f, activation=activation)
    gated = activation == "silu_gated"
    lp = {k: rng.standard_normal(shape).astype(np.float32) * 0.1
          for k, shape in (("we_up", (E, d, f)), ("we_down", (E, f, d)))}
    if gated:
        lp["we_gate"] = rng.standard_normal((E, d, f)).astype(np.float32) * 0.1
    dense_lp = {k: jnp.asarray(v.copy()) for k, v in lp.items()}
    # What the kernel may not read (an empty list is one step over expert 0
    # under a zero combine column: ``hit_list``).
    unread = [e for e in range(E) if e not in (chosen or [0])]
    for k in lp:
        lp[k][unread] = np.nan
    lp = {k: jnp.asarray(v) for k, v in lp.items()}
    xs = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    comb = np.zeros((T, E), np.float32)
    for t in range(T):
        for e in rng.permutation(chosen)[:K]:
            comb[t, e] = rng.random() + 0.1
    comb[[2, 9]] = 0.0  # dead rows
    if chosen:
        comb[0, chosen] = 0.5  # every expert of the case is hit
    comb = jnp.asarray(comb)
    ids, count = ef.hit_list(comb.sum(0))
    assert int(count[0]) == len(chosen)
    # several tiles an expert: [128, f] row tiles, [128, d] / [16, d] down tiles
    monkeypatch.setattr(ef, "F_TILE_BYTES_MAX", 128 * max(d, f) * 4 if ef.f_minor(f)
                        else 16 * d * 4)
    y = ef._expert_ffn_impl(xs, comb, lp["we_up"], lp["we_down"], ids, count,
                            lp.get("we_gate"), interpret=True)
    want = moe._experts_dense(xs, comb, dense_lp, spec)
    assert y.dtype == jnp.float32 and bool(jnp.isfinite(y).all())
    _close(y, want, 1e-4)
    assert float(jnp.abs(y[jnp.asarray([2, 9])]).max()) == 0.0
    assert (float(jnp.abs(y).max()) > 1e-3) == bool(chosen)


@pytest.mark.parametrize("n,row_bytes,want", [
    (7680, 2048 * 2, 640),  # the latent widths: [640, 2048] row tiles of up and gate
    (2048, 7680 * 2, 128),  # and [128, 7680] tiles of down
    (256, 1 << 30, 128),    # no tile fits: one lane tile
    (256, 4, 256),          # everything fits: the whole axis
])
def test_lane_tile_divides_the_axis_in_lane_multiples(n, row_bytes, want):
    from dynamo_tpu.ops.pallas.expert_ffn import lane_tile

    assert lane_tile(n, row_bytes) == want and n % want == 0


# -- (d) the share test -------------------------------------------------------------


def _share_setup(model="nemotron_h"):
    """(uncut spec, its weights, x, the uncut reference's output, the
    shares' held ranges) of one expert layer: ``nemotron_h`` (8 experts in
    2 shares, relu2, a correction bias) or ``pangu_ultra_moe`` (16 experts
    in 16 shares of one, gated silu, plain sigmoid scores)."""
    if model == "nemotron_h":
        c, at, shares, reference = tiny_hybrid_config(), 1, ((0, 4), (4, 8)), ref
    else:
        from dynamo_tpu.models import pangu_ultra_moe_reference as reference
        from dynamo_tpu.models.config import tiny_mla_config

        c, at, shares = tiny_mla_config(), 3, tuple((e, e + 1) for e in range(16))
    spec = dataclasses.replace(c.layer_specs[at], held=None, post_norm=False)  # every expert
    full = dataclasses.replace(c, layer_specs=(spec,), n_layers=1)
    lp = llama.init_params(full, jax.random.PRNGKey(3))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, c.d_model))
    L = dict(kind="experts", top_k=spec.top_k, scale=spec.scale, held=(0, spec.n_experts))
    with jax.default_matmul_precision("highest"):
        want = reference.ref_experts(x.reshape(-1, c.d_model), lp, L).reshape(x.shape)
    return spec, lp, x, want, shares


@pytest.mark.parametrize("model,form", [("nemotron_h", "xla"), ("nemotron_h", "hit_list"),
                                        ("pangu_ultra_moe", "xla"),
                                        ("pangu_ultra_moe", "hit_list")])
def test_shares_add_up_to_the_uncut_layer(model, form, hit_list_calls):
    """Each share returns its own experts' part plus the shared expert, so
    the shared expert is in the sum once a share: counted once, the parts are
    the uncut reference's output (two shares of four experts; the sixteen
    shares of one expert of an expert-parallel group of sixteen)."""
    spec, lp, x, want, shares = _share_setup(model)
    kernel = form == "hit_list"
    parts = []
    for lo, hi in shares:
        held = {k: (v[lo:hi] if k.startswith("we_") else v) for k, v in lp.items()}
        parts.append(moe.moe_ffn(x, held, spec.holding(lo, hi), use_kernel=kernel))
    shared_only = moe.moe_ffn(
        x, {k: (v[:0] if k.startswith("we_") else v) for k, v in lp.items()},
        spec.holding(0, 0), use_kernel=kernel)
    assert len(hit_list_calls) == (len(shares) if kernel else 0)  # no expert held: no kernel
    _close(sum(parts) - (len(shares) - 1) * shared_only, want, 1e-4)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3  # the shares differ


def test_expert_parallel_shards_sum_to_the_uncut_layer():
    """The mesh path: the expert matrices sharded over ``ep`` on their first
    axis, everything else replicated, the same ``moe_ffn`` under jit: GSPMD
    partitions it and the result is the uncut layer's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.parallel import MeshConfig, make_mesh

    spec, lp, x, want, _ = _share_setup()
    mesh = make_mesh(MeshConfig(ep=2), jax.devices()[:2])
    put = lambda k, v: jax.device_put(
        v, NamedSharding(mesh, P("ep") if k.startswith("we_") else P()))
    sharded = {k: put(k, v) for k, v in lp.items()}
    assert len(sharded["we_up"].sharding.device_set) == 2
    got = jax.jit(lambda x, lp: moe.moe_ffn(x, lp, spec))(x, sharded)
    _close(got, want, 1e-4)


# -- (e) snapshots: prefix reuse and preemption by recompute -------------------------


def _req(tokens, rid, n=12, **sampling):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        sampling=SamplingOptions(temperature=0.0, logprobs=1, **sampling),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )


def _sig(outs):
    toks = [t for o in outs for t in o.token_ids]
    lps = [lp[0].logprob for o in outs for lp in (o.logprobs or [])]
    return toks, lps


def _engine(on_kv_event=None, **kw):
    base = dict(config=tiny_hybrid_config(), block_size=16, num_kv_blocks=64, max_num_seqs=4,
                max_model_len=512, prefill_chunk=128)
    base.update(kw)
    return JaxEngine(JaxEngineArgs(**base), on_kv_event=on_kv_event)


async def test_repeated_prompt_resumes_from_a_snapshot():
    from dynamo_tpu.runtime.kv_reuse_observe import global_plane

    events = []
    engine = _engine(on_kv_event=events.append)
    prompt = np.random.default_rng(5).integers(3, 500, 100).tolist()
    try:
        fresh = _sig(await collect(engine.generate(_req(prompt, "a"), Context())))
        stored = [h for e in events if e.kind == "stored" for h in e.block_hashes]
        assert len(stored) == 6  # 96 of 100 tokens: six whole blocks, each under a snapshot
        before = global_plane().snapshot()["reused_prefill_tokens"]
        tokens_before = engine.prefill_tokens
        again = _sig(await collect(engine.generate(_req(prompt, "b"), Context())))
        assert global_plane().snapshot()["reused_prefill_tokens"] - before == 96
        assert engine.prefill_tokens - tokens_before == 4  # only the tail was computed
        assert engine.snapshots.hits == 1
        assert again[0] == fresh[0]
        np.testing.assert_allclose(again[1], fresh[1], rtol=1e-4, atol=1e-5)
        text = engine.step_metrics.render()
        assert "dynamo_tpu_engine_ssm_snapshot_hits_total 1" in text
        assert 'dynamo_tpu_engine_ssm_state_slots{state="total"} 4' in text
        assert "dynamo_tpu_engine_moe_experts_hit_total" in text
    finally:
        await engine.stop()


async def test_snapshot_store_is_bounded_and_evictions_retract(monkeypatch):
    from dynamo_tpu.engines.tpu import block_pool

    monkeypatch.setattr(block_pool, "SSM_SNAPSHOT_ENTRIES", 4)
    events = []
    engine = _engine(on_kv_event=events.append)
    rng = np.random.default_rng(6)
    try:
        for i in range(3):
            await collect(engine.generate(
                _req(rng.integers(3, 500, 70).tolist(), f"r{i}", n=4), Context()))
        assert engine.snapshots.used == 4 and engine.snapshots.evictions == 3 * 4 - 4
        removed = [h for e in events if e.kind == "removed" for h in e.block_hashes]
        assert len(removed) == engine.snapshots.evictions
    finally:
        await engine.stop()


async def test_preempted_sequence_recomputes_to_the_same_logits():
    """A pool too small for two growing sequences preempts one mid-stream;
    its recompute rebuilds the recurrent state and its stream (tokens and
    their log-probabilities) is what it is when it runs alone."""
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


# -- (f) from_hf_config ---------------------------------------------------------------


def test_from_hf_config_yields_the_52_layer_spec():
    cfg = ModelConfig.from_hf_config(NEMOTRON_3_NANO_30B_A3B_HF)
    kinds = "".join({"mamba2": "M", "experts": "E", "attention": "*"}[s.kind]
                    for s in cfg.layer_specs)
    assert kinds == NEMOTRON_3_NANO_30B_A3B_HF["hybrid_override_pattern"] and len(kinds) == 52
    assert (kinds.count("M"), kinds.count("E"), kinds.count("*")) == (23, 23, 6)
    m, e, a = (cfg.specs_of(k)[0] for k in ("mamba2", "experts", "attention"))
    assert (m.d_inner, m.conv_channels, m.in_width, m.state_size) == (4096, 6144, 10304, 128)
    assert (e.n_experts, e.top_k, e.d_ff, e.shared_d_ff, e.scale) == (128, 6, 1856, 3712, 2.5)
    assert e.routing == "sigmoid_bias" and e.activation == "relu2" and e.held_ == (0, 128)
    assert (a.n_heads, a.n_kv_heads, a.head_dim, a.positions) == (32, 2, 128, "none")
    cut = nemotron3_nano_ep2_config()
    assert [s.kind[0] for s in cut.layer_specs] == list("memema" "eme")
    assert cut.specs_of("experts")[0].held_ == (0, 64) and cut.vocab_size == 65536
    shapes = jax.eval_shape(lambda: llama.init_params(cut, jax.random.PRNGKey(0)))
    assert round(sum(a.size for a in jax.tree.leaves(shapes)) / 1e6) == 3166
    assert hybrid.ssm_state_bytes(cut) == 8_536_064  # 8.54 MB a sequence


def test_benchmark_configuration_file_agrees_with_the_preset():
    import json

    with open(os.path.join(ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b-ep2.json")) as f:
        file = json.load(f)
    for key, value in NEMOTRON_3_NANO_30B_A3B_HF.items():
        if key not in file["reduced"]:
            assert file[key] == value, key
    cut = nemotron3_nano_ep2_config()
    assert (file["num_hidden_layers"], file["vocab_size"]) == (cut.n_layers, cut.vocab_size)
    assert tuple(file["experts_held"]) == cut.specs_of("experts")[0].held_
    assert file["experts_routed_over"] == cut.specs_of("experts")[0].n_experts


# -- (g) the benchmark's copy of the reference -----------------------------------------


def _marked(path):
    with open(path) as f:
        text = f.read()
    return text[text.index("# --- reference: begin"): text.index("# --- reference: end")]


def test_benchmark_copy_of_the_reference_agrees(tiny):
    assert _marked(BENCH_REF) == _marked(ref.__file__)
    spec = importlib.util.spec_from_file_location("bench_reference", BENCH_REF)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    seq = tiny["seqs"][2][:20]
    got = copy.reference_forward(tiny["p"], tiny["L"], seq, tiny["c"].rms_norm_eps)
    _close(got["logits"], tiny["refs"][2]["logits"][:20], 1e-5)


def test_reference_states_at_a_length_ignore_the_padding(tiny):
    """The benchmark's child pads every served sequence to one length and
    takes the state at the sequence's own: tail and S are those of the
    unpadded sequence, whatever follows."""
    seq, n = tiny["seqs"][0], 29
    other = np.concatenate([seq[:n], np.random.default_rng(1).integers(0, 500, len(seq) - n)])
    cut = ref.reference_forward(tiny["p"], tiny["L"], seq[:n], tiny["c"].rms_norm_eps)
    for padded in (seq, other):
        got = ref.reference_forward(tiny["p"], tiny["L"], padded, tiny["c"].rms_norm_eps,
                                    length=jnp.int32(n))
        _close(got["logits"][:n], cut["logits"], 1e-5)
        for m in range(len(cut["S"])):
            _close(got["S"][m], cut["S"][m], 1e-5)
            _close(got["conv"][m], cut["conv"][m], 1e-5)


CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b-ep2.json")


def test_reference_child_agrees_in_a_rehearsal(monkeypatch, capsys):
    """The comparison that decides the cell's ``correct``, as the harness's
    CPU rehearsal runs it: the engine's own waves against the reference."""
    spec = importlib.util.spec_from_file_location("bench_reference_child", BENCH_REF)
    child = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(child)
    monkeypatch.setattr(sys, "argv", [BENCH_REF, "--config", CONFIG_FILE, "--seed", "3600000011",
                                      "--rows", "3"])
    assert child.main() == 0, capsys.readouterr().out
    out = capsys.readouterr().out
    assert "snapshot hits and reused tokens of the resumed rows [(1, " in out and "agrees" in out


def test_reference_child_compares_nothing_off_its_device():
    """No rehearsal asked for (no JAX_PLATFORMS=cpu from the harness) and no
    chip: JAX falls back to the CPU without an error, and the child must
    not take that for a rehearsal."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run([sys.executable, BENCH_REF, "--config", CONFIG_FILE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 2, done.stdout[-2000:] + done.stderr[-2000:]
    assert "NOTHING COMPARED" in done.stdout


# -- (h) refusals by mechanism ------------------------------------------------------------


@pytest.mark.parametrize("mechanism", ["disaggregation wire", "KVBM tiers", "KV checkpoint",
                                       "engine export"])
def test_mechanisms_that_carry_only_kv_refuse_a_hybrid_configuration(mechanism):
    c = tiny_hybrid_config()
    if mechanism == "engine export":
        async def run():
            engine = _engine()
            try:
                with pytest.raises(ValueError, match="disaggregation wire.*recurrent state"):
                    await engine.export_blocks_wire_async([1])
                with pytest.raises(ValueError, match="KV checkpoint.*recurrent state"):
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
    with pytest.raises(ValueError, match=f"{mechanism}.*recurrent state"):
        check(c)
    check(dataclasses.replace(c, layer_specs=None))  # a dense model passes


# -- (i) which expert form a step takes, and what says so ---------------------------------


FORM_CASES = {
    # name: (use_kernel, step [B, C], d, f, held, activation, quantized) -> the form's first words
    "decode_slots_on_the_chip": ((True, (64, 1), 128, 48, 4, "relu2", False), "pallas hit list"),
    "small_prefill_on_the_chip": ((True, (1, 256), 128, 48, 4, "relu2", False),
                                  "pallas hit list"),
    "no_kernels_here": ((False, (64, 1), 128, 48, 4, "relu2", False), "xla dense, no Pallas"),
    "a_prefill_chunk": ((True, (1, 512), 128, 48, 4, "relu2", False), "pallas grouped"),
    "a_prefill_chunk_off_the_chip": ((False, (1, 512), 128, 48, 4, "relu2", False),
                                     "xla grouped, no Pallas"),
    "quantized_matrices": ((True, (64, 1), 128, 48, 4, "relu2", True), "xla dense, quantized"),
    "gated_experts": ((True, (64, 1), 128, 48, 4, "silu_gated", False), "pallas hit list"),
    "unknown_activation": ((True, (64, 1), 128, 48, 4, "gelu", False), "xla dense, activation gelu"),
    "latent_cell_widths": ((True, (32, 1), 7680, 2048, 16, "silu_gated", False), "pallas hit list"),
    "latent_cell_document_chunk": ((True, (1, 256), 7680, 2048, 16, "silu_gated", False),
                                   "pallas hit list"),
    "hybrid_cell_widths": ((True, (64, 1), 2688, 1856, 64, "relu2", False), "pallas hit list"),
    "no_expert_held": ((True, (64, 1), 128, 48, 0, "relu2", False), "xla dense, no expert held"),
    "narrow_model_width": ((True, (64, 1), 64, 48, 4, "relu2", False), "xla dense, widths d 64"),
    "expert_width_fills_lanes": ((True, (64, 1), 128, 256, 4, "relu2", False), "pallas hit list"),
}


@pytest.mark.parametrize("case", sorted(FORM_CASES))
def test_expert_form_follows_what_moe_ffn_is_given(case):
    """``form_in_use`` (the runner's log line) and ``hit_list_reason`` (what
    ``moe_ffn`` branches on) from the arguments alone: no flag, no name."""
    (use_kernel, step, d, f, held, act, quantized), want = FORM_CASES[case]
    spec = ExpertsSpec(n_experts=8, top_k=2, d_ff=f, activation=act, held=(0, held))
    up = jax.ShapeDtypeStruct((held, d, f), jnp.bfloat16)
    lp = {"we_up": {"q8": up, "s": None} if quantized else up}
    form = moe.form_in_use(use_kernel, step, lp, spec)
    assert form.startswith(want)
    assert (moe.hit_list_reason(use_kernel, step, lp, spec) is None) == (
        form == "pallas hit list")


@pytest.mark.parametrize("preset,step,want", [
    ("nemotron-3-nano-30b-a3b-ep2", (64, 1), None),
    ("nemotron-3-nano-30b-a3b-ep2", (1, 256), None),
    ("nemotron-3-nano-30b-a3b-ep2", (1, 512), "512 tokens a step is over 256"),
    ("openpangu-ultra-moe-718b-ep16", (32, 1), None),
    ("openpangu-ultra-moe-718b-ep16", (1, 256), None),
    ("openpangu-ultra-moe-718b-ep16", (2, 1024), "2048 tokens a step is over 256"),
])
def test_both_served_expert_configurations_take_the_kernel(preset, step, want):
    """``hit_list_reason`` at the two cells' published widths, from the
    spec and the matrices' shapes alone: the hybrid cell's relu2 experts
    (d-minor ``we_up``) and the latent cell's gated-silu ones (f-minor, three
    matrices) go through the kernel up to 256 tokens a step."""
    from dynamo_tpu.models.config import (
        nemotron3_nano_ep2_config, openpangu_ultra_moe_ep16_config,
    )

    config = {"nemotron-3-nano-30b-a3b-ep2": nemotron3_nano_ep2_config,
              "openpangu-ultra-moe-718b-ep16": openpangu_ultra_moe_ep16_config}[preset]()
    spec = next(s for s in config.layer_specs if isinstance(s, ExpertsSpec))
    lo, hi = spec.held_
    lp = {"we_up": jax.ShapeDtypeStruct((hi - lo, config.d_model, spec.d_ff), jnp.bfloat16)}
    assert moe.hit_list_reason(True, step, lp, spec) == want
    assert moe.hit_list_reason(False, step, lp, spec).startswith("no Pallas kernels")


@pytest.mark.parametrize("model", ["tiny-hybrid", "tiny-moe", "tiny"])
def test_engine_stats_name_the_expert_form(model):
    """``/engine/stats`` says beside ``attention_reason`` which form the
    decode step's expert layers take; on the CPU none is the kernel."""
    from dynamo_tpu.models.config import tiny_config, tiny_moe_config

    config = {"tiny-hybrid": tiny_hybrid_config, "tiny-moe": tiny_moe_config,
              "tiny": tiny_config}[model]()

    async def run():
        engine = JaxEngine(JaxEngineArgs(
            config=config, block_size=16, num_kv_blocks=32, max_num_seqs=4,
            max_model_len=128, prefill_chunk=32,
        ))
        try:
            return engine.stats()
        finally:
            await engine.stop()

    stats = asyncio.run(run())
    assert "cpu" in stats["attention_reason"]
    want = {"tiny-hybrid": "xla dense, no Pallas kernels here",
            "tiny-moe": "xla, the stacked layer loop takes no kernel", "tiny": None}[model]
    assert stats["expert_ffn"] == want or stats["expert_ffn"].startswith(want)
