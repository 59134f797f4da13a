"""Parity tests: fused-layer decode megakernel vs the XLA decoder_layer
oracle (models/llama.py), interpret mode on CPU.

The megakernel attends to history pages + the in-register current token;
the oracle writes the token to the cache first and attends to pages only —
identical math, different orders, so outputs must agree to bf16 tolerance.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quantize import quantize_params
from dynamo_tpu.ops.attention import write_chunk_to_cache
from dynamo_tpu.ops.pallas.fused_layer import (
    fused_decoder_layer,
    supports,
    supports_reason,
)
from dynamo_tpu.ops.rope import rope_table


def _cfg(**overrides):
    base = dict(
        name="fused-test",
        d_model=256,
        n_layers=1,
        n_heads=4,
        n_kv_heads=2,
        d_ff=512,
        vocab_size=128,
        head_dim=128,
        rope_theta=10000.0,
        dtype=jnp.bfloat16,
    )
    base.update(overrides)
    return ModelConfig(**base)


def _qwen3_cfg():
    """Qwen3-shaped knobs at the test miniature: qk-norm, no bias."""
    return _cfg(name="fused-qwen3", qk_norm=True, rms_norm_eps=1e-6)


def _gemma3_cfg(window=24):
    """Gemma-3-shaped knobs at the test miniature: qk-norm, GeGLU,
    unit-offset norms, post-norms, query scale, sliding window on every
    other layer (the n_layers=1 slice used here is the WINDOWED kind)."""
    return _cfg(
        name="fused-gemma3",
        qk_norm=True,
        act_fn="gelu_tanh",
        rmsnorm_unit_offset=True,
        post_norms=True,
        query_scale=128.0,
        rms_norm_eps=1e-6,
        sliding_window=window,
    )


def _gemma2_cfg():
    """Gemma-2-shaped knobs: softcap + post-norms + GeGLU, no qk-norm."""
    return _cfg(
        name="fused-gemma2",
        act_fn="gelu_tanh",
        rmsnorm_unit_offset=True,
        post_norms=True,
        attn_logit_softcap=30.0,
        query_scale=128.0,
        sliding_window=32,
    )


def _layer_params(cfg, seed=0, scramble=False):
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    axes = llama.param_logical_axes(cfg)
    qparams, _ = quantize_params(params, axes)
    # one layer, axis 0 stripped
    lp = jax.tree.map(lambda a: a[0], qparams["layers"])
    if scramble:
        lp = _scramble_epilogues(lp, seed=seed + 100)
    return lp


def _scramble_epilogues(lp, seed=7):
    """Replace the init-time NEUTRAL epilogue params (unit norm weights,
    zero biases — which would hide a missing epilogue entirely) with
    non-trivial values, so parity actually exercises every epilogue."""
    r = np.random.default_rng(seed)
    out = dict(lp)
    for k in ("q_norm", "k_norm", "attn_post_norm", "mlp_post_norm",
              "attn_norm", "mlp_norm"):
        if k in out:
            out[k] = jnp.asarray(
                r.uniform(0.5, 1.5, out[k].shape).astype(np.float32)
            ).astype(out[k].dtype)
    for k in ("bq", "bk", "bv"):
        if k in out:
            out[k] = jnp.asarray(
                (r.standard_normal(out[k].shape) * 0.1).astype(np.float32)
            ).astype(out[k].dtype)
    return out


def _setup(cfg, B=8, BS=16, P=2, seed=1, start=None):
    rng = np.random.default_rng(seed)
    NB = B * P + 4
    d = cfg.d_model
    KH, D = cfg.n_kv_heads, cfg.head_dim_
    x = jnp.asarray(rng.standard_normal((B, d)).astype(np.float32) * 0.3).astype(
        jnp.bfloat16
    )
    k_pool = jnp.asarray(
        rng.standard_normal((NB, BS, KH, D)).astype(np.float32) * 0.2
    ).astype(jnp.bfloat16)
    v_pool = jnp.asarray(
        rng.standard_normal((NB, BS, KH, D)).astype(np.float32) * 0.2
    ).astype(jnp.bfloat16)
    tables = jnp.asarray(
        rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32)
    )
    if start is None:
        # varied positions: page boundaries, zero history, mid-page —
        # clamped to the table's page capacity (positions past BS*P don't
        # exist)
        start = [0, 1, BS - 1, BS, BS + 3, 2 * BS - 1, 7, BS + BS // 2][:B]
    sp = np.minimum(np.asarray(start, dtype=np.int32), BS * P - 1)
    start_pos = jnp.asarray(sp)
    return x, k_pool, v_pool, tables, start_pos


def _oracle(cfg, lp, x, k_pool, v_pool, tables, start_pos, win=0):
    """XLA decoder_layer on the same inputs (write-then-attend)."""
    B = x.shape[0]
    pos = start_pos[:, None]
    cos, sin = rope_table(pos, cfg.head_dim_, cfg.rope_theta)
    chunk = jnp.ones((B,), jnp.int32)
    x_out, k_c, v_c = llama.decoder_layer(
        cfg, lp, {}, jnp.asarray(win, jnp.int32), x[:, None, :], cos, sin,
        k_pool, v_pool, tables, start_pos, chunk,
        use_kernel=False, adapter_ids=None,
    )
    return x_out[:, 0], k_c, v_c


def _sm_scale(cfg):
    return (
        cfg.query_scale**-0.5
        if cfg.query_scale is not None
        else cfg.head_dim_**-0.5
    )


def _fused(cfg, lp, x, k_pool, v_pool, tables, start_pos, win=0,
           batch_block=4):
    """fused_decoder_layer with the config's epilogue statics applied —
    the exact call shape models/llama.py forward_paged makes."""
    pos = start_pos[:, None]
    cos, sin = rope_table(pos, cfg.head_dim_, cfg.rope_theta)
    return fused_decoder_layer(
        x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables, start_pos,
        eps=cfg.rms_norm_eps, sm_scale=_sm_scale(cfg),
        batch_block=batch_block, interpret=True,
        window=(jnp.asarray(win, jnp.int32) if win else None),
        act_fn=cfg.act_fn,
        unit_offset=cfg.rmsnorm_unit_offset,
        softcap=float(cfg.attn_logit_softcap or 0.0),
    )


def test_supports_gate():
    cfg = _cfg()
    assert supports(cfg, lora=False, quantized_weights=True)
    assert not supports(cfg, lora=True, quantized_weights=True)
    assert not supports(cfg, lora=False, quantized_weights=False)


def test_supports_no_longer_gates_family_knobs():
    """The r11 epilogues: every knob the acceptance list names is now
    in-kernel, so supports() must pass configs carrying ANY mix of them
    — and still exclude what is genuinely unimplemented (MoE)."""
    from dynamo_tpu.models.config import tiny_moe_config

    for cfg in (_qwen3_cfg(), _gemma3_cfg(), _gemma2_cfg(),
                _cfg(qkv_bias=True), _cfg(rmsnorm_unit_offset=True),
                _cfg(act_fn="gelu_tanh"), _cfg(sliding_window=64),
                _cfg(attn_logit_softcap=50.0), _cfg(post_norms=True)):
        assert supports(cfg, lora=False, quantized_weights=True), (
            cfg.name,
            supports_reason(cfg, lora=False, quantized_weights=True),
        )
    assert not supports(
        tiny_moe_config(), lora=False, quantized_weights=True
    )


# Presets the megakernel can NOT serve, with the reason fragment that
# supports_reason must carry. The docs' supports() matrix
# (docs/design_docs/megakernel_paged_streaming.md) renders this table; a
# NEW preset must either pass supports() or be added here with a reason —
# it can never silently drift to the ~1/3-roofline XLA path.
DOCUMENTED_PRESET_EXCLUSIONS = {
    "tiny-llama": "head_dim",       # 32: not a multiple of the MXU lane
    "tiny-moe": "MoE",              # routed experts excluded
    "mixtral-8x7b": "MoE",
    "qwen2.5-0.5b": "head_dim",     # 64: not a multiple of the MXU lane
    # one mixer per layer, recurrent state beside paged K/V
    "tiny-hybrid": "hybrid",
    "nemotron-3-nano-30b-a3b-ep2": "hybrid",
    # one latent pool a layer, no K and no V page
    "tiny-mla": "latent",
    "openpangu-ultra-moe-718b-ep16": "latent",
    # two page groups: a second block table a row, pages behind the window released
    "tiny-swa": "two page groups",
    "laguna-xs.2-pp8": "two page groups",
    # one mixer per layer: sparse attention over a cached indexer beside
    # lightning attention's recurrent state
    "tiny-sala": "hybrid",
    "minicpm-sala-pp4": "hybrid",
}


def test_supports_matrix_covers_every_preset():
    """Every named preset in models/config.py (the all_presets registry)
    either rides the fused path or matches a documented exclusion — new
    presets can't silently decode on the slow path."""
    from dynamo_tpu.models.config import all_presets

    presets = all_presets().values()
    assert len(presets) >= 10  # the registry actually enumerates
    for cfg in presets:
        reason = supports_reason(cfg, lora=False, quantized_weights=True)
        if cfg.name in DOCUMENTED_PRESET_EXCLUSIONS:
            frag = DOCUMENTED_PRESET_EXCLUSIONS[cfg.name]
            assert reason is not None and frag in reason, (cfg.name, reason)
        else:
            assert reason is None, (
                f"preset {cfg.name!r} silently drifted off the fused "
                f"path: {reason} — fix the kernel or document the "
                "exclusion in DOCUMENTED_PRESET_EXCLUSIONS + the design "
                "doc matrix"
            )
    # The headline families of this PR are affirmatively ON the path.
    for name in ("qwen3-8b", "gemma-3-1b", "gemma-2-2b", "llama-3-8b"):
        assert name not in DOCUMENTED_PRESET_EXCLUSIONS


def test_window_page_bounds_semantics():
    """window_page_bounds: wlo is the first VISIBLE key (max(0, pos−W+1)),
    poff its page — including the straddle case where pos−W lands
    mid-page (the boundary page is streamed and masked in-kernel)."""
    from dynamo_tpu.ops.pallas.live_pages import window_page_bounds

    BS = 16
    start = jnp.asarray([0, 5, 100, 100, 64, 200], jnp.int32)
    #                 W: full  windows below
    wlo, poff = window_page_bounds(start, 0, BS)
    assert np.all(np.asarray(wlo) == 0) and np.all(np.asarray(poff) == 0)

    wlo, poff = window_page_bounds(start, 40, BS)
    exp_wlo = np.maximum(np.asarray(start) - 40 + 1, 0)
    np.testing.assert_array_equal(np.asarray(wlo), exp_wlo)
    np.testing.assert_array_equal(np.asarray(poff), exp_wlo // BS)
    # pos=100, W=40 → first visible key 61, mid-page on page 3 (straddle)
    assert int(wlo[2]) == 61 and int(poff[2]) == 3 and 61 % BS != 0
    # window covering the whole history → page 0
    wlo, poff = window_page_bounds(start, 512, BS)
    assert np.all(np.asarray(poff) == 0)


@pytest.mark.parametrize(
    "mkcfg", [_qwen3_cfg, _gemma3_cfg, _gemma2_cfg],
    ids=["qwen3", "gemma3", "gemma2"],
)
def test_epilogue_parity_short(mkcfg):
    """Qwen3-/Gemma-shaped configs on the fused path vs the XLA oracle at
    short contexts, with randomized epilogue params (neutral init values
    would hide a missing epilogue) and window boundaries that straddle a
    page edge (pos−W mid-page)."""
    cfg = mkcfg()
    win = int(cfg.sliding_window or 0)
    # starts include: zero history, page edges, mid-page, and (with the
    # windowed configs) positions whose pos−W lands mid-page.
    start = [0, 1, 15, 16, 19, 31, 45, 63]
    _parity(cfg, 8, 4, start, seed=11, win=win, scramble=True)


def test_head_dim_256_parity():
    """head_dim 256 — REAL Gemma-2/3 geometry (supports() now admits
    D % 128 == 0, so the presets auto-enable): covers the D=256 rope
    half-split (128), TQ=256/HPT=1 head tiling, and [1, 256] qk-norm
    weight broadcasting, none of which the D=128 miniatures touch."""
    cfg = _cfg(
        name="fused-d256", n_heads=2, n_kv_heads=1, head_dim=256,
        qk_norm=True, act_fn="gelu_tanh", rmsnorm_unit_offset=True,
        post_norms=True, query_scale=256.0, sliding_window=24,
        rms_norm_eps=1e-6,
    )
    assert supports(cfg, lora=False, quantized_weights=True), (
        supports_reason(cfg, lora=False, quantized_weights=True)
    )
    start = [0, 15, 19, 31, 45, 48, 55, 63]
    _parity(cfg, 8, 4, start, seed=31, win=24, scramble=True)


def test_window_parity_straddles_page_edge():
    """The boundary page: pos−W mid-page means the first live page is
    PARTIALLY masked in-kernel. Windows chosen so wlo % BS != 0 for the
    interesting rows, on the plain llama-shaped config (window is
    orthogonal to the other epilogues)."""
    cfg = _cfg()
    for win in (17, 40):
        start = [0, 20, 33, 47, 48, 55, 60, 63]
        _parity(cfg, 8, 4, start, seed=13 + win, win=win)


@pytest.mark.parametrize("P", [1, 2, 3])
def test_fused_layer_matches_oracle(P):
    cfg = _cfg()
    lp = _layer_params(cfg)
    x, k_pool, v_pool, tables, start_pos = _setup(cfg, P=P)

    ref_x, ref_k, ref_v = _oracle(
        cfg, lp, x, k_pool, v_pool, tables, start_pos
    )

    pos = start_pos[:, None]
    cos, sin = rope_table(pos, cfg.head_dim_, cfg.rope_theta)
    got_x, k_new, v_new = fused_decoder_layer(
        x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables, start_pos,
        eps=cfg.rms_norm_eps, sm_scale=cfg.head_dim_**-0.5,
        batch_block=4, interpret=True,
    )

    a = np.asarray(got_x, dtype=np.float32)
    b = np.asarray(ref_x, dtype=np.float32)
    scale = np.max(np.abs(b)) + 1e-6
    assert np.max(np.abs(a - b)) / scale < 4e-2, (
        np.max(np.abs(a - b)) / scale
    )

    # the kernel's current-token K/V must equal what the oracle wrote into
    # the pools at each row's (table, start) slot
    B = x.shape[0]
    BS = k_pool.shape[1]
    for b_i in range(B):
        pg = int(tables[b_i, int(start_pos[b_i]) // BS])
        off = int(start_pos[b_i]) % BS
        np.testing.assert_allclose(
            np.asarray(k_new[b_i], dtype=np.float32),
            np.asarray(ref_k[pg, off], dtype=np.float32),
            atol=3e-2, rtol=3e-2,
        )
        np.testing.assert_allclose(
            np.asarray(v_new[b_i], dtype=np.float32),
            np.asarray(ref_v[pg, off], dtype=np.float32),
            atol=3e-2, rtol=3e-2,
        )


def test_fused_layer_then_write_matches_pool_update():
    """write_chunk_to_cache(k_new/v_new) must reproduce the oracle pools."""
    cfg = _cfg()
    lp = _layer_params(cfg)
    x, k_pool, v_pool, tables, start_pos = _setup(cfg)
    _, ref_k, ref_v = _oracle(cfg, lp, x, k_pool, v_pool, tables, start_pos)

    pos = start_pos[:, None]
    cos, sin = rope_table(pos, cfg.head_dim_, cfg.rope_theta)
    _, k_new, v_new = fused_decoder_layer(
        x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables, start_pos,
        eps=cfg.rms_norm_eps, sm_scale=cfg.head_dim_**-0.5,
        batch_block=4, interpret=True,
    )
    ones = jnp.ones((x.shape[0],), jnp.int32)
    k_after = write_chunk_to_cache(
        k_pool, k_new[:, None], tables, start_pos, ones
    )
    v_after = write_chunk_to_cache(
        v_pool, v_new[:, None], tables, start_pos, ones
    )
    np.testing.assert_allclose(
        np.asarray(k_after, dtype=np.float32),
        np.asarray(ref_k, dtype=np.float32),
        atol=3e-2, rtol=3e-2,
    )
    np.testing.assert_allclose(
        np.asarray(v_after, dtype=np.float32),
        np.asarray(ref_v, dtype=np.float32),
        atol=3e-2, rtol=3e-2,
    )


def _parity(cfg, B, P, start, seed=2, batch_block=4, win=0, scramble=False):
    """Fused kernel vs XLA oracle on one shape; returns max relative err.
    ``win`` > 0 runs both paths with that sliding window; ``scramble``
    randomizes the epilogue params (neutral init values would hide a
    missing epilogue)."""
    lp = _layer_params(cfg, scramble=scramble)
    x, k_pool, v_pool, tables, start_pos = _setup(
        cfg, B=B, P=P, seed=seed, start=start
    )
    ref_x, _, _ = _oracle(
        cfg, lp, x, k_pool, v_pool, tables, start_pos, win=win
    )
    got_x, _, _ = _fused(
        cfg, lp, x, k_pool, v_pool, tables, start_pos, win=win,
        batch_block=batch_block,
    )
    a = np.asarray(got_x, dtype=np.float32)
    b = np.asarray(ref_x, dtype=np.float32)
    scale = np.max(np.abs(b)) + 1e-6
    err = np.max(np.abs(a - b)) / scale
    assert err < 4e-2, err
    return err


def test_table_width_buckets_bounded():
    """As contexts grow, dispatched table widths collapse into ~log2(cap)
    pow2 buckets — the compiled-program-count bound for the decode and
    spec-verify dispatches. (The jit-cache-growth companion lives in
    test_zlongctx_fused.py with the other long-context checks.)"""
    import math

    from dynamo_tpu.engines.tpu.engine import table_width_bucket

    cap = 256  # 4096 tokens at block_size 16
    buckets = {table_width_bucket(n, cap) for n in range(1, cap + 1)}
    assert len(buckets) <= int(math.log2(cap)) + 1, sorted(buckets)
    assert max(buckets) == cap  # the top bucket still reaches capacity
    assert table_width_bucket(0, cap) == 1
    for n in range(1, cap + 1):
        # a bucket always covers the width that requested it
        assert n <= table_width_bucket(n, cap) <= cap


async def test_engine_megakernel_matches_xla_decode():
    """Full engine on CPU (interpret mode): greedy decode with the
    megakernel ON must match the XLA decode path token-for-token on a
    megakernel-eligible config."""
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.engine import collect

    cfg = _cfg()  # d=256, D=128, KH=2 — supports() eligible

    async def run(use_mk):
        e = JaxEngine(JaxEngineArgs(
            config=cfg, block_size=16, num_kv_blocks=64, max_num_seqs=4,
            max_model_len=64, quantization="int8", use_megakernel=use_mk,
        ))
        assert e.runner.use_megakernel == use_mk
        try:
            req = PreprocessedRequest(
                token_ids=[3, 4, 5, 6, 7, 8], request_id=f"mk{use_mk}",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=10),
            )
            outs = await collect(e.generate(req, Context()))
            return [t for d in outs for t in d.token_ids]
        finally:
            await e.stop()

    base = await run(False)
    fused = await run(True)
    assert len(base) == 10
    assert fused == base, (fused, base)


@pytest.mark.parametrize("family", ["qwen3", "gemma3"])
async def test_engine_megakernel_matches_xla_family_shapes(family):
    """Full engine on CPU (interpret mode): greedy decode with the
    megakernel ON must match the XLA decode path token-for-token on
    Qwen3- and Gemma-3-shaped configs — the families this PR moves onto
    the fused path. The gemma shape mixes a WINDOWED and a GLOBAL layer
    (sliding_window_pattern=2) so the traced-window-operand program
    sharing and the dual behavior are both exercised end-to-end, and the
    coverage counters must show the bursts rode the fused path."""
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.engine import collect

    if family == "qwen3":
        cfg = _cfg(name="e2e-qwen3", n_layers=2, qk_norm=True)
    else:
        cfg = _cfg(
            name="e2e-gemma3", n_layers=2, qk_norm=True,
            act_fn="gelu_tanh", rmsnorm_unit_offset=True, post_norms=True,
            query_scale=128.0, sliding_window=24, sliding_window_pattern=2,
        )
        assert cfg.layer_windows() == [24, 0]  # windowed + global mix

    async def run(use_mk):
        e = JaxEngine(JaxEngineArgs(
            config=cfg, block_size=16, num_kv_blocks=64, max_num_seqs=4,
            max_model_len=96, quantization="int8", use_megakernel=use_mk,
        ))
        assert e.runner.use_megakernel == use_mk
        try:
            req = PreprocessedRequest(
                token_ids=[3, 4, 5, 6, 7, 8, 9, 10], request_id=f"f{use_mk}",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=10),
            )
            outs = await collect(e.generate(req, Context()))
            if use_mk:
                assert e.runner.mk_fused_bursts > 0, "never dispatched fused"
                assert e.runner.mk_fallback_bursts == 0
                assert e.stats()["mk_fused_bursts"] > 0
                assert e.stats()["decode_path"] == "fused"
            return [t for d in outs for t in d.token_ids]
        finally:
            await e.stop()

    base = await run(False)
    fused = await run(True)
    assert len(base) == 10
    assert fused == base, (fused, base)


async def test_megakernel_compile_error_fails_the_request(monkeypatch):
    """If Mosaic rejects the fused kernel at first dispatch the error
    reaches the caller: nothing demotes the engine to the XLA decode
    program behind a request that asked nothing of the sort. On the one
    installation there is, a kernel the runner selected either compiles
    or is a bug."""
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.ops.pallas import fused_layer
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.engine import collect

    def boom(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: says no")

    # llama imports it lazily inside forward_paged — patch the source module
    monkeypatch.setattr(fused_layer, "fused_decoder_layer", boom)
    e = JaxEngine(JaxEngineArgs(
        config=_cfg(), block_size=16, num_kv_blocks=64, max_num_seqs=4,
        max_model_len=64, quantization="int8", use_megakernel=True,
    ))
    assert e.runner.use_megakernel
    try:
        req = PreprocessedRequest(
            token_ids=[3, 4, 5, 6], request_id="fb",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=6),
        )
        outs = await collect(e.generate(req, Context()))
        errors = [o.error for o in outs if o.error]
        assert errors and "Mosaic" in errors[0], outs
        # Only the prefill's first token can have streamed: no decode
        # burst was served from another implementation.
        assert sum(len(o.token_ids) for o in outs) <= 1, outs
        assert e.runner.use_megakernel
        assert e.runner.mk_fallback_bursts == 0
        assert e.runner.mk_fused_bursts == 0
    finally:
        await e.stop()


def test_decode_path_is_chosen_once_with_a_reason():
    """The runner decides attention implementation and decode path at
    start from what it can observe, and says why (the worker's start-up
    log line and stats() carry the strings chip_smoke.py prints)."""
    from dynamo_tpu.engines.tpu import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner

    choose_attn = DeviceRunner._choose_attention
    choose_path = DeviceRunner._choose_decode_path
    ok = JaxEngineArgs(config=_cfg(), max_num_seqs=4, quantization="int8")

    assert choose_attn(ok, "cpu", None) == (
        False, "platform is cpu (Mosaic lowers on TPU only)"
    )
    assert choose_attn(ok, "tpu", None)[0] is True
    use, why = choose_attn(ok, "tpu", object())  # any mesh
    assert use is False and "mesh" in why
    forced = JaxEngineArgs(config=_cfg(), use_kernel=True)
    with pytest.raises(ValueError, match="mesh"):
        choose_attn(forced, "tpu", object())

    assert choose_path(ok, "cpu", None) == (False, "platform is cpu")
    use, why = choose_path(ok, "tpu", object())
    assert use is False and why == "ineligible: device mesh present"
    bf16 = JaxEngineArgs(config=_cfg(), max_num_seqs=4)
    assert choose_path(bf16, "tpu", None) == (
        False, "ineligible: weights not int8-quantized"
    )
    explicit = JaxEngineArgs(
        config=_cfg(), max_num_seqs=4, quantization="int8",
        use_megakernel=True,
    )
    assert choose_path(explicit, "cpu", None)[0] is True
    # All eight width buckets lower on the installed Mosaic (chip_smoke.py's
    # kernel table, PR 21), so an eligible configuration gets it on TPU.
    assert choose_path(ok, "tpu", None) == (
        True, "platform is tpu and the configuration is eligible"
    )
