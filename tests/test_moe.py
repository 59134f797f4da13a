"""MoE: routing math, expert-parallel sharding, engine serving (VERDICT #9;
ref: the reference's MoE model class, recipes/deepseek-r1 + Qwen3-MoE —
here the dropless expert op, ops/moe.py; what it replaced, a capacity
factor that dropped tokens, went with its two tests: tests/test_hybrid.py
has the dropless and the share tests in their place)."""

import asyncio

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
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig, tiny_moe_config
from dynamo_tpu.ops.moe import moe_ffn
from dynamo_tpu.parallel import MeshConfig, ShardingRules, make_mesh, shard_params
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect


def reference_moe(x, router_w, we_gate, we_up, we_down, top_k, norm_topk):
    """Per-token loop oracle (no capacity drops)."""
    B, C, d = x.shape
    E = router_w.shape[-1]
    out = np.zeros((B, C, d), dtype=np.float64)
    probs = np.asarray(jax.nn.softmax(x.astype(jnp.float32) @ router_w, axis=-1))
    for b in range(B):
        for c in range(C):
            order = np.argsort(-probs[b, c])[:top_k]
            w = probs[b, c, order]
            if norm_topk:
                w = w / w.sum()
            for e, we in zip(order, w):
                h = np.asarray(x[b, c], dtype=np.float64)
                gate = np.asarray(jax.nn.silu(jnp.asarray(h @ np.asarray(we_gate[e], dtype=np.float64))))
                up = h @ np.asarray(we_up[e], dtype=np.float64)
                out[b, c] += we * ((gate * up) @ np.asarray(we_down[e], dtype=np.float64))
    return out


def test_moe_ffn_matches_reference_loop():
    rng = np.random.default_rng(0)
    B, C, d, E, f, K = 2, 3, 8, 4, 16, 2
    x = jnp.asarray(rng.standard_normal((B, C, d)), dtype=jnp.float32)
    router_w = jnp.asarray(rng.standard_normal((d, E)), dtype=jnp.float32)
    we_gate = jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, dtype=jnp.float32)
    we_up = jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, dtype=jnp.float32)
    we_down = jnp.asarray(rng.standard_normal((E, f, d)) * 0.2, dtype=jnp.float32)
    from dynamo_tpu.models.config import ExpertsSpec

    y = moe_ffn(
        x, dict(router_w=router_w, we_gate=we_gate, we_up=we_up, we_down=we_down),
        ExpertsSpec(n_experts=E, top_k=K, d_ff=f),
    )
    ref = reference_moe(x, router_w, we_gate, we_up, we_down, K, True)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)


def test_moe_forward_ep_sharded_matches_unsharded():
    cfg = tiny_moe_config()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    k, v = llama.init_kv_cache(cfg, 16, 4)
    toks = jnp.asarray([[5, 6, 7, 8], [9, 10, 11, 12]], dtype=jnp.int32)
    table = jnp.asarray(np.arange(16, dtype=np.int32).reshape(2, 8))
    start = jnp.zeros(2, jnp.int32)
    lens = jnp.full((2,), 4, jnp.int32)

    base, _, _ = llama.forward_paged(params, cfg, toks, start, lens, table, k, v)

    mesh = make_mesh(MeshConfig(ep=2, tp=2, dp=2))
    rules = ShardingRules()
    sp = shard_params(params, llama.param_logical_axes(cfg), rules, mesh)
    k2 = jax.device_put(k, rules.sharding(mesh, *llama.kv_cache_logical_axes()))
    v2 = jax.device_put(v, rules.sharding(mesh, *llama.kv_cache_logical_axes()))
    sharded, _, _ = jax.jit(
        lambda p, kc, vc: llama.forward_paged(
            p, cfg, toks, start, lens, table, kc, vc
        )
    )(sp, k2, v2)
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(sharded), rtol=2e-4, atol=2e-4
    )


async def test_engine_serves_moe_model():
    engine = JaxEngine(
        JaxEngineArgs(
            config=tiny_moe_config(), block_size=4, num_kv_blocks=64,
            max_num_seqs=4, max_model_len=128, prefill_chunk=32,
        )
    )

    def req(tokens, rid):
        return PreprocessedRequest(
            token_ids=list(tokens), request_id=rid,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=5, ignore_eos=True),
        )

    try:
        solo = await collect(engine.generate(req(range(10, 22), "a"), Context()))
        toks_solo = [t for o in solo for t in o.token_ids]
        assert len(toks_solo) == 5
        outs = await asyncio.gather(
            *(
                collect(engine.generate(req(range(5 + i, 17 + i), f"r{i}"), Context()))
                for i in range(3)
            )
        )
        for out in outs:
            assert not any(o.error for o in out)
            assert len([t for o in out for t in o.token_ids]) == 5
    finally:
        await engine.stop()


def test_hf_config_ingestion_moe():
    cfg = ModelConfig.from_hf_config(
        {
            "architectures": ["Qwen3MoeForCausalLM"],
            "vocab_size": 1024,
            "hidden_size": 64,
            "num_hidden_layers": 2,
            "num_attention_heads": 4,
            "num_key_value_heads": 2,
            "intermediate_size": 128,
            "num_experts": 8,
            "num_experts_per_tok": 2,
            "moe_intermediate_size": 32,
            "norm_topk_prob": True,
            "eos_token_id": 3,
        }
    )
    assert cfg.is_moe and cfg.n_experts == 8 and cfg.moe_d_ff_ == 32
    mix = ModelConfig.from_hf_config(
        {
            "architectures": ["MixtralForCausalLM"],
            "vocab_size": 1024,
            "hidden_size": 64,
            "num_hidden_layers": 2,
            "num_attention_heads": 4,
            "intermediate_size": 128,
            "num_local_experts": 8,
            "num_experts_per_tok": 2,
        }
    )
    assert mix.n_experts == 8


# -- the grouped Pallas kernel (ops/pallas/expert_ffn.expert_ffn_grouped) ----

TM = 8  # the row tile these cases run at: groups are sized against it


@pytest.fixture
def grouped_calls(monkeypatch):
    """``moe_ffn(use_kernel=True)`` over more than ``DENSE_TOKENS_MAX``
    tokens on the CPU: the grouped kernel under the Pallas interpreter.
    Yields the (tile_expert, n_work, tm) of each call made."""
    from dynamo_tpu.ops import moe
    from dynamo_tpu.ops.pallas.expert_ffn import expert_ffn_grouped

    calls = []

    def interpreted(rows, up, down, tile_expert, n_work, gate=None, *, tm):
        calls.append((np.asarray(tile_expert), int(n_work[0]), tm))
        return expert_ffn_grouped(
            rows, up, down, tile_expert, n_work, gate, tm=tm, interpret=True)

    monkeypatch.setattr(moe, "expert_ffn_grouped", interpreted)
    return calls


GROUP_CASES = {
    # name: (rows on each of the 4 held experts, dead rows, rows on absent experts)
    "empty_groups": ([0, 5, 0, 3], 0, 0),
    "one_row": ([1, 0, 0, 0], 0, 0),
    "exactly_a_tile": ([TM, 0, TM, 0], 0, 0),
    "a_tile_and_one_row": ([TM + 1, 2, 0, 2 * TM + 1], 0, 0),
    "every_row_on_one_expert": ([0, 0, 29, 0], 0, 0),
    "dead_rows": ([3, TM, 4, 1], 11, 0),
    "absent_experts": ([2, 9, 0, TM], 0, 13),
    "dead_rows_and_absent_experts": ([TM + 3, 0, 1, 6], 7, 9),
    "nothing_live": ([0, 0, 0, 0], 5, 4),
}


@pytest.mark.parametrize("activation", ["relu2", "silu_gated"])
@pytest.mark.parametrize("case", sorted(GROUP_CASES))
def test_grouped_kernel_is_the_dense_form_on_the_same_routing(
    case, activation, grouped_calls, monkeypatch
):
    """The grouped form through the kernel against ``_experts_dense`` on the
    same routing, holding experts [2, 6) of a router 8 wide, top-1 so that a
    group's size is what the case says: every group is padded to whole
    tiles of ``TM`` rows, an expert nobody chose owns no tile, and a dead
    row (``valid`` false) or a row on an absent expert costs no tile and
    adds nothing. relu2 reads ``we_up`` d minor (f 48 does not fill the
    lanes), gated silu f minor (f 128 does)."""
    from dynamo_tpu.models.config import ExpertsSpec
    from dynamo_tpu.ops import moe

    sizes, dead, absent = GROUP_CASES[case]
    rng = np.random.default_rng(45)
    d, f, lo, hi = 128, 48 if activation == "relu2" else 128, 2, 6
    n_held = hi - lo
    spec = ExpertsSpec(n_experts=8, top_k=1, d_ff=f, routing="sigmoid",
                       activation=activation, held=(lo, hi))
    # (token's expert over the full router, live?) in a shuffled order
    picks = [(lo + e, True) for e, n in enumerate(sizes) for _ in range(n)]
    picks += [(lo + int(rng.integers(n_held)), False) for _ in range(dead)]
    picks += [(int(rng.choice([0, 1, 6, 7])), True) for _ in range(absent)]
    picks = [picks[i] for i in rng.permutation(len(picks))]
    T = len(picks)
    top_i = jnp.asarray([[e] for e, _ in picks], jnp.int32)
    live = jnp.asarray([[alive] for _, alive in picks])
    local = top_i - lo
    valid = (local >= 0) & (local < n_held) & live
    top_w = jnp.asarray(rng.random((T, 1)) + 0.5, jnp.float32)
    xs = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    lp = dict(
        we_up=jnp.asarray(rng.standard_normal((n_held, d, f)) * 0.1, jnp.float32),
        we_gate=jnp.asarray(rng.standard_normal((n_held, d, f)) * 0.1, jnp.float32),
        we_down=jnp.asarray(rng.standard_normal((n_held, f, d)) * 0.1, jnp.float32),
    )
    monkeypatch.setattr(moe, "grouped_row_tile", lambda assignments, n_experts: TM)
    y = moe._experts_grouped_kernel(xs, top_w, local, valid, lp, spec, n_held)
    want = moe._experts_dense(
        xs, moe._combine(top_w, local, valid, n_held), lp, spec)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
    # rows that are dead or on an absent expert get nothing
    off = ~np.asarray(valid)[:, 0]
    assert not np.asarray(y)[off].any()
    assert sum(sizes) == 0 or np.abs(np.asarray(y)[~off]).max() > 1e-3
    # the work list: ceil(size / TM) tiles an expert, in order, the last
    # repeated past the count; one entry longer than the most tiles
    [(tile_expert, n_work, tm)] = grouped_calls
    tiles = [-(-n // TM) for n in sizes]
    assert (n_work, tm) == (sum(tiles), TM)
    assert len(tile_expert) == T // TM + min(n_held, T) + 1
    owners = [e for e, n in enumerate(tiles) for _ in range(n)] or [0]
    assert list(tile_expert[:max(n_work, 1)]) == owners
    assert set(tile_expert[max(n_work, 1):]) <= {owners[-1]}


@pytest.mark.parametrize("activation,f", [("relu2", 160), ("silu_gated", 256)])
def test_grouped_kernel_goes_chunk_by_chunk_inside_a_step(
    activation, f, grouped_calls, monkeypatch
):
    """Widths over ``GROUPED_CHUNK_MAX`` (held at 128 here): a grid step
    loops over two chunks of the expert width (80 of 160 d minor, 128 of 256
    f minor) and, within each, two of the model width (128 of 256), and is
    still the dense form's result."""
    from dynamo_tpu.models.config import ExpertsSpec
    from dynamo_tpu.ops import moe
    from dynamo_tpu.ops.pallas import expert_ffn

    monkeypatch.setattr(expert_ffn, "GROUPED_CHUNK_MAX", 128)
    assert expert_ffn.grouped_chunk(256, 128) == 128
    assert expert_ffn.grouped_chunk(f, 16 if f == 160 else 128) == f // 2
    rng = np.random.default_rng(9)
    T, d, n_held, K = 40, 256, 4, 2
    spec = ExpertsSpec(n_experts=n_held, top_k=K, d_ff=f, routing="sigmoid",
                       activation=activation)
    local = jnp.asarray(
        np.argsort(rng.random((T, n_held)), axis=1)[:, :K].astype(np.int32))
    valid = jnp.asarray(rng.random((T, K)) < 0.8)
    top_w = jnp.asarray(rng.random((T, K)) + 0.5, jnp.float32)
    xs = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    lp = dict(
        we_up=jnp.asarray(rng.standard_normal((n_held, d, f)) * 0.1, jnp.float32),
        we_gate=jnp.asarray(rng.standard_normal((n_held, d, f)) * 0.1, jnp.float32),
        we_down=jnp.asarray(rng.standard_normal((n_held, f, d)) * 0.1, jnp.float32),
    )
    y = moe._experts_grouped_kernel(xs, top_w, local, valid, lp, spec, n_held)
    want = moe._experts_dense(xs, moe._combine(top_w, local, valid, n_held), lp, spec)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert len(grouped_calls) == 1


@pytest.mark.parametrize("tokens,n_experts,want", [
    (512 * 6, 128, 32), (1024 * 6, 128, 32), (2048 * 6, 128, 64),
    (8192 * 6, 128, 64), (512 * 8, 256, 32), (2048 * 8, 256, 32), (24, 8, 16),
    (128 * 8, 256, 16), (256 * 8, 256, 16), (256 * 10, 512, 16), (512 * 10, 512, 32),
    (341 * 6, 128, 32),
])
def test_grouped_row_tile_follows_the_assignments_an_expert_expects(
    tokens, n_experts, want
):
    from dynamo_tpu.ops.pallas.expert_ffn import grouped_row_tile

    assert grouped_row_tile(tokens, n_experts) == want


@pytest.mark.parametrize("d,f,itemsize,want", [
    (2688, 1856, 2, 4), (7680, 2048, 2, 28), (2048, 512, 2, 2), (128, 1024, 4, 2),
    (128, 48, 4, 1),
])
def test_hit_list_steps_are_the_kernels_own_tiling(d, f, itemsize, want):
    """Grid steps an expert takes in the hit-list kernel, from the widths:
    what ``ops/moe.chunk_costs`` reads to tell an expert whose products hide
    behind its stream (several tiles) from a small one (two short steps)."""
    from dynamo_tpu.ops.pallas.expert_ffn import hit_list_steps

    assert hit_list_steps(d, f, itemsize) == want


HYBRID = (2688, 1856, 64, 128, 6, "relu2")  # d, f, held, router width, top-k, activation
LATENT = (7680, 2048, 16, 256, 8, "silu_gated")
WINDOW = (2048, 512, 256, 256, 8, "silu_gated")
DELTA_RULE = (2048, 512, 256, 512, 10, "silu_gated")
TOY = (128, 48, 4, 8, 2, "relu2")

GROUPED_FORM_CASES = {
    # name: ((use_kernel, step [B, C], widths, quantized), form, the log line's start)
    "hybrid_cell_two_prompts": (
        (True, (2, 256), HYBRID, False), "grouped_kernel", "pallas grouped"),
    "hybrid_cell_eight_long_prompts": (
        (True, (8, 1024), HYBRID, False), "grouped_kernel", "pallas grouped"),
    "window_cell_two_turns": (
        (True, (2, 256), WINDOW, False), "grouped_kernel", "pallas grouped"),
    "latent_cell_two_questions": (
        (True, (2, 256), LATENT, False),
        "grouped_xla", "xla grouped, an expert's 3 matrices of 7680 x 2048, twice, are"),
    "no_kernels_here": (
        (False, (2, 256), HYBRID, False),
        "grouped_xla", "xla grouped, no Pallas kernels here"),
    "unknown_activation": (
        (True, (1, 512), TOY[:5] + ("gelu",), False),
        "grouped_xla", "xla grouped, activation gelu is not in the kernel"),
    "no_expert_held": (
        (True, (1, 512), (128, 48, 0, 8, 2, "relu2"), False),
        "grouped_xla", "xla grouped, no expert held"),
    "narrow_model_width": (
        (True, (1, 512), (64, 48, 4, 8, 2, "relu2"), False),
        "grouped_xla", "xla grouped, widths d 64, f 48: d does not fill"),
    "quantized_matrices_stay_dense": (
        (True, (1, 512), TOY, True), "dense", "xla dense, quantized expert matrices"),
    "a_lone_prompt_keeps_the_hit_list": (
        (True, (1, 256), HYBRID, False), "hit_list", "pallas hit list"),
    # PR 51: a turn's chunk of at most 256 tokens over 256 small experts
    # streams them once; the same shapes' decode steps keep the hit list, and
    # so does every step of the wide experts
    "window_cell_a_turn": (
        (True, (1, 256), WINDOW, False), "grouped_kernel", "pallas grouped"),
    "window_cell_two_short_turns": (
        (True, (2, 128), WINDOW, False), "grouped_kernel", "pallas grouped"),
    "window_cell_a_short_turn": (
        (True, (1, 128), WINDOW, False), "grouped_kernel", "pallas grouped"),
    "delta_rule_cell_a_turn": (
        (True, (1, 256), DELTA_RULE, False), "grouped_kernel", "pallas grouped"),
    "delta_rule_cell_two_short_turns": (
        (True, (2, 128), DELTA_RULE, False), "grouped_kernel", "pallas grouped"),
    "delta_rule_cell_a_short_turn": (
        (True, (1, 128), DELTA_RULE, False), "grouped_kernel", "pallas grouped"),
    "window_cell_decode_slots": (
        (True, (64, 1), WINDOW, False), "hit_list", "pallas hit list"),
    "window_cell_256_decode_slots": (
        (True, (256, 1), WINDOW, False), "hit_list", "pallas hit list"),
    "delta_rule_cell_decode_slots": (
        (True, (64, 1), DELTA_RULE, False), "hit_list", "pallas hit list"),
    "delta_rule_cell_256_decode_slots": (
        (True, (256, 1), DELTA_RULE, False), "hit_list", "pallas hit list"),
    "hybrid_cell_a_short_prompt": (
        (True, (1, 128), HYBRID, False), "hit_list", "pallas hit list"),
    "hybrid_cell_two_short_prompts": (
        (True, (2, 128), HYBRID, False), "hit_list", "pallas hit list"),
    "latent_cell_a_document_chunk": (
        (True, (1, 256), LATENT, False), "hit_list", "pallas hit list"),
    "a_turn_where_no_kernel_serves": (
        (False, (1, 256), WINDOW, False), "dense", "xla dense, no Pallas kernels here"),
    "a_turn_over_quantized_matrices": (
        (True, (1, 256), WINDOW, True), "dense", "xla dense, quantized expert matrices"),
}


@pytest.mark.parametrize("case", sorted(GROUPED_FORM_CASES))
def test_grouped_form_follows_what_moe_ffn_is_given(case):
    """``form_of`` (what ``moe_ffn`` branches on and the engine counts
    prefill tokens by), ``form_in_use`` (the log line) and
    ``grouped_reason``'s words for each refusal, from the step's static
    shape, the matrices' shapes and the spec alone."""
    from dynamo_tpu.models.config import ExpertsSpec
    from dynamo_tpu.ops import moe

    (use_kernel, step, (d, f, held, width, top_k, act), quantized), form, line = \
        GROUPED_FORM_CASES[case]
    spec = ExpertsSpec(
        n_experts=width, top_k=top_k, d_ff=f, activation=act, held=(0, held))
    up = jax.ShapeDtypeStruct((held, d, f), jnp.bfloat16)
    lp = {"we_up": {"q8": up, "s": None} if quantized else up}
    got, why = moe.form_of(use_kernel, step, lp, spec)
    assert got == form and got in moe.FORMS
    assert moe.form_in_use(use_kernel, step, lp, spec).startswith(line)
    assert (why is None) == form.endswith(("hit_list", "kernel"))
    if form != "hit_list":
        assert moe.grouped_reason(use_kernel, lp, spec) == why


@pytest.mark.parametrize("use_kernel", [True, False], ids=["kernel", "xla"])
def test_moe_ffn_takes_the_grouped_form_for_a_prefill_batch(use_kernel, grouped_calls):
    """``moe_ffn`` end to end over 288 tokens (more than ``DENSE_TOKENS_MAX``)
    with dead rows, holding [2, 6) of 8 experts: with ``use_kernel`` one
    grouped-kernel call, without it ``ragged_dot``; both are the per-token
    loop's result, and a dead row's is the shared expert's alone (none
    here: zero)."""
    from dynamo_tpu.models.config import ExpertsSpec
    from dynamo_tpu.ops import moe

    rng = np.random.default_rng(3)
    B, C, d, E, f, K, lo, hi = 3, 96, 128, 8, 48, 2, 2, 6
    spec = ExpertsSpec(n_experts=E, top_k=K, d_ff=f, routing="sigmoid",
                       activation="relu2", held=(lo, hi))
    lp = dict(
        router_w=jnp.asarray(rng.standard_normal((d, E)) * 0.1, jnp.float32),
        we_up=jnp.asarray(rng.standard_normal((hi - lo, d, f)) * 0.1, jnp.float32),
        we_down=jnp.asarray(rng.standard_normal((hi - lo, f, d)) * 0.1, jnp.float32),
    )
    x = jnp.asarray(rng.standard_normal((B, C, d)), jnp.float32)
    mask = jnp.asarray(np.arange(C)[None, :] < np.asarray([96, 40, 71])[:, None])
    assert moe.form_of(use_kernel, (B, C), lp, spec)[0] == (
        "grouped_kernel" if use_kernel else "grouped_xla")
    y = moe.moe_ffn(x, lp, spec, row_mask=mask, use_kernel=use_kernel)
    assert len(grouped_calls) == (1 if use_kernel else 0)
    top_w, top_i = moe.route(x.reshape(B * C, d), lp, spec)
    want = np.zeros((B * C, d), np.float64)
    for t in np.flatnonzero(np.asarray(mask).reshape(-1)):
        for w, e in zip(np.asarray(top_w[t]), np.asarray(top_i[t])):
            if lo <= e < hi:
                h = np.maximum(np.asarray(x.reshape(-1, d)[t], np.float64)
                               @ np.asarray(lp["we_up"][e - lo], np.float64), 0.0) ** 2
                want[t] += w * (h @ np.asarray(lp["we_down"][e - lo], np.float64))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, d), want, rtol=1e-4, atol=1e-4)
    assert not np.asarray(y)[~np.asarray(mask)].any()
    if use_kernel:
        # 288 tokens x 2 picks over 8 experts expect 72 rows an expert: tiles of 64
        assert grouped_calls[0][2] == 64


@pytest.mark.parametrize("tokens,live", [
    (128, "two_thirds"), (256, "two_thirds"), (256, "no_row"),
])
def test_a_turn_over_many_small_experts_takes_the_grouped_kernel(
    tokens, live, grouped_calls
):
    """``moe_ffn`` over ONE chunk of 128 / 256 tokens (at most
    ``DENSE_TOKENS_MAX``), holding the middle half [16, 48) of a router 64
    wide, experts the hit-list kernel takes in two grid steps (128 x 1024,
    f minor): ``form_of`` prices every row through every expert hit over the
    grouped kernel, so the step is ONE grouped call of 16-row tiles (an
    expert expects 4 or 8 rows), a third of its rows dead, and is the
    per-token loop's result to the 288-token case's tolerance; with every row
    dead (a prefix-hit family's empty
    sibling) the work list is empty and the result zero. The same tokens as
    a decode step ([T, 1]) keep the hit list."""
    from dynamo_tpu.models.config import ExpertsSpec
    from dynamo_tpu.ops import moe

    rng = np.random.default_rng(51)
    d, E, f, K, lo, hi = 128, 64, 1024, 2, 16, 48
    spec = ExpertsSpec(n_experts=E, top_k=K, d_ff=f, routing="sigmoid",
                       activation="relu2", held=(lo, hi))
    lp = dict(
        router_w=jnp.asarray(rng.standard_normal((d, E)) * 0.1, jnp.float32),
        we_up=jnp.asarray(rng.standard_normal((hi - lo, d, f)) * 0.1, jnp.float32),
        we_down=jnp.asarray(rng.standard_normal((hi - lo, f, d)) * 0.05, jnp.float32),
    )
    x = jnp.asarray(rng.standard_normal((1, tokens, d)), jnp.float32)
    mask = jnp.asarray(
        (np.arange(tokens) < (2 * tokens // 3 if live == "two_thirds" else 0))[None, :])
    assert moe.form_of(True, (1, tokens), lp, spec) == ("grouped_kernel", None)
    assert moe.form_of(True, (tokens, 1), lp, spec) == ("hit_list", None)
    y = moe.moe_ffn(x, lp, spec, row_mask=mask, use_kernel=True)
    [(tile_expert, n_work, tm)] = grouped_calls
    assert tm == 16
    top_w, top_i = moe.route(x.reshape(tokens, d), lp, spec)
    want = np.zeros((tokens, d), np.float64)
    hit = set()
    for t in np.flatnonzero(np.asarray(mask).reshape(-1)):
        for w, e in zip(np.asarray(top_w[t]), np.asarray(top_i[t])):
            if lo <= e < hi:
                hit.add(int(e))
                h = np.maximum(np.asarray(x[0, t], np.float64)
                               @ np.asarray(lp["we_up"][e - lo], np.float64), 0.0) ** 2
                want[t] += w * (h @ np.asarray(lp["we_down"][e - lo], np.float64))
    np.testing.assert_allclose(np.asarray(y)[0], want, rtol=1e-4, atol=1e-4)
    assert not np.asarray(y)[~np.asarray(mask)].any()
    # a tile an expert hit at least (few rows each: most of a tile is padding)
    assert len(hit) <= n_work <= len(hit) + 2 * tokens // 3 * K // 16
    assert (n_work == 0) == (live == "no_row")
    assert live == "no_row" or np.abs(want).max() > 1e-2


@pytest.mark.parametrize("rows,chunk,want", [
    (1, 128, "grouped_kernel"), (2, 128, "grouped_kernel"), (1, 256, "grouped_kernel"),
    (2, 256, "grouped_kernel"), (1, 16, "hit_list"),
])
def test_the_prefill_counters_label_is_the_programs_branch(rows, chunk, want, monkeypatch):
    """``runner.prefill_expert_form(rows, chunk)`` (the label the engine
    counts a reaped prefill step's tokens under) against the form ``moe_ffn``
    takes when the runner's prefill program of that rows and chunk bucket is
    TRACED (``jax.eval_shape``: no kernel runs), with the kernels on, for a
    toy configuration whose experts the rule sends to the grouped kernel at
    128, 256 and 512 static tokens and leaves on the hit list at 16 (half of
    the held experts expected hit: the gathers outweigh the products): the
    label is the program's own branch, at every expert layer."""
    import types

    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models import hybrid
    from dynamo_tpu.models.config import AttentionSpec, ExpertsSpec, tiny_hybrid_config
    from dynamo_tpu.ops import moe

    experts = ExpertsSpec(n_experts=64, top_k=2, d_ff=1024, routing="sigmoid",
                          activation="relu2", held=(16, 48))
    attn = AttentionSpec(n_heads=2, n_kv_heads=1, head_dim=128, positions="none")
    cfg = tiny_hybrid_config(layer_specs=(attn, experts, attn, experts), n_layers=4,
                             n_heads=2, n_kv_heads=1, head_dim=128)
    args = JaxEngineArgs(config=cfg, block_size=16, num_kv_blocks=64, max_num_seqs=4,
                         max_model_len=1024, prefill_chunk=256, use_kernel=True)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    runner = types.SimpleNamespace(
        config=cfg, args=args, use_kernel=True, hybrid=True, params=params,
        _prefill_expert_forms={}, _decode_sig_budget=None)
    label = DeviceRunner.prefill_expert_form(runner, rows, chunk)

    traced = []
    form_of = moe.form_of

    def recorded(use_kernel, step, lp, spec):
        given = form_of(use_kernel, step, lp, spec)
        traced.append((step, given[0]))
        return given

    monkeypatch.setattr(moe, "form_of", recorded)
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 64, 16, layered=True))
    empty = jax.eval_shape(lambda: hybrid.init_ssm_state(cfg, rows))
    arr = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    jax.eval_shape(
        DeviceRunner._build_step_fn_hybrid(runner, False, 0, False)._fn,
        params, k, v, empty, empty, arr((rows, chunk), i32), arr((rows,), i32),
        arr((rows,), i32), arr((rows, 16), i32), arr((rows, 0), i32),
        arr((rows,), i32), arr((2,), jnp.uint32), arr((rows,), f32),
        arr((rows,), i32), arr((rows,), f32),
    )
    assert traced == [((rows, chunk), label)] * 2
    assert label == want


@pytest.mark.parametrize("model", ["tiny-hybrid", "tiny-moe", "tiny"])
def test_engine_counts_prefill_tokens_by_expert_form(model):
    """``dynamo_tpu_engine_moe_prefill_tokens_total{form}``: all four series
    at 0 from start-up in an engine with expert layers (none in one
    without), then the live prompt tokens of each reaped prefill step under
    the form its STATIC token count takes: a 20-token prompt is one step of
    a 32-token bucket (dense on the CPU), a 300-token one a step of 512
    (over ``DENSE_TOKENS_MAX``: grouped, ``ragged_dot`` on the CPU). The same
    numbers under ``/engine/stats``."""
    from dynamo_tpu.models.config import tiny_config, tiny_hybrid_config
    from dynamo_tpu.ops.moe import FORMS

    config = {"tiny-hybrid": tiny_hybrid_config, "tiny-moe": tiny_moe_config,
              "tiny": tiny_config}[model]()
    family = "dynamo_tpu_engine_moe_prefill_tokens_total"

    def req(n, rid):
        return PreprocessedRequest(
            token_ids=list(np.random.default_rng(n).integers(3, 500, n)),
            request_id=rid, sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=2, ignore_eos=True),
        )

    async def run():
        engine = JaxEngine(JaxEngineArgs(
            config=config, block_size=16, num_kv_blocks=64, max_num_seqs=4,
            max_model_len=1024, prefill_chunk=512,
        ))
        try:
            start = engine.stats()
            at_start = engine.step_metrics.render()
            await collect(engine.generate(req(20, "short"), Context()))
            await collect(engine.generate(req(300, "long"), Context()))
            return start, at_start, engine.stats(), engine.step_metrics.render()
        finally:
            await engine.stop()

    start, at_start, after, text = asyncio.run(run())
    if model == "tiny":
        assert "moe_prefill_tokens" not in start and "moe_prefill_tokens" not in after
        assert family + "{" not in at_start and family + "{" not in text
        return
    assert start["moe_prefill_tokens"] == dict.fromkeys(FORMS, 0)
    for form in FORMS:
        assert f'{family}{{form="{form}"}} 0' in at_start
    want = {"hit_list": 0, "dense": 20, "grouped_kernel": 0, "grouped_xla": 300}
    assert after["moe_prefill_tokens"] == want
    for form, n in want.items():
        assert f'{family}{{form="{form}"}} {n}' in text
