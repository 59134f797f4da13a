"""Long-context megakernel checks: interpret-mode parity past the removed
MAX_TABLE_PAGES=16 ceiling, trace/compile-cost regressions for the dynamic
page loop, and the narrowed one-shot fallback's runtime behavior.

Named test_z* DELIBERATELY: these are the suite's heaviest interpret-mode
compiles (~2 min total on the 1-core CI host), and the tier-1 run sits at
the edge of its wall-clock budget — sorting them last keeps the broad
suite's coverage ahead of them. Run directly when touching the kernel:

    pytest tests/test_zlongctx_fused.py -q

Companion design doc: docs/design_docs/megakernel_paged_streaming.md.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.pallas.fused_layer import fused_decoder_layer
from dynamo_tpu.ops.rope import rope_table

from test_fused_layer import (
    _cfg,
    _fused,
    _gemma3_cfg,
    _layer_params,
    _oracle,
    _parity,
    _qwen3_cfg,
    _setup,
)


@pytest.mark.parametrize("ctx", [256, 1024, 4096])
def test_fused_layer_long_context_parity(ctx):
    """The old static unroll capped tables at MAX_TABLE_PAGES=16 (256
    tokens at BS=16); the dynamic page loop must match the XLA oracle at
    any table width — here 16, 64 and 256 pages, with rows at the context
    edge, mid-context, near-zero and zero history."""
    cfg = _cfg()
    BS = 16
    P = ctx // BS
    start = [ctx - 1, ctx // 2, 3, 0]
    _parity(cfg, 4, P, start, seed=2 + ctx)


def test_fused_layer_ragged_batch_parity():
    """Short and long rows mixed in one long-context batch: the per-row
    early exit (short rows skip their dead pages entirely — no stream, no
    mask) must not perturb numerics for either kind, across waves with
    different max page counts."""
    cfg = _cfg()
    start = [0, 3, 16, 255, 1024, 2047, 4095, 500]
    _parity(cfg, 8, 256, start, seed=3)


@pytest.mark.parametrize("ctx", [256, 1024, 4096])
@pytest.mark.parametrize(
    "mkcfg", [_qwen3_cfg, _gemma3_cfg], ids=["qwen3", "gemma3"]
)
def test_fused_epilogue_long_context_parity(mkcfg, ctx):
    """Qwen3- and Gemma-3-shaped configs on the fused path at 256/1k/4k-
    token tables, epilogue params randomized, rows at the context edge,
    mid-context, near-zero and zero history. The gemma config's window
    (24) puts pos−W mid-page at the edge rows — the straddled boundary
    page is masked in-kernel while everything before it is skipped."""
    cfg = mkcfg()
    BS = 16
    P = ctx // BS
    win = int(cfg.sliding_window or 0)
    start = [ctx - 1, ctx // 2, 3, 0]
    _parity(cfg, 4, P, start, seed=17 + ctx, win=win, scramble=True)


def test_fused_epilogue_ragged_window_parity():
    """Short and long rows mixed in one long-context WINDOWED batch: the
    per-row live page range (poff..pcount) differs per row inside one
    wave, so skip-below-window, skip-past-history and the masked boundary
    page all coexist — numerics must hold for every kind."""
    cfg = _gemma3_cfg(window=100)
    start = [0, 3, 16, 255, 1024, 2047, 4095, 500]
    _parity(cfg, 8, 256, start, seed=19, win=100, scramble=True)


def test_windowed_rows_stream_only_live_pages():
    """THE page-step proof: fully-dead pages (before the window's first
    page, or past the history) are NEVER STREAMED — not streamed-then-
    masked. Dead pages' pool content is poisoned with NaN: a kernel that
    streams them cannot hide it (masked scores zero the weights, but
    0 × NaN = NaN through the p·V accumulate — the XLA oracle, which
    gathers the full table and masks, is shown to produce NaN on the same
    poisoned pool). The fused output must be bit-identical to the clean
    run."""
    from dynamo_tpu.ops.pallas.live_pages import (
        history_pcounts,
        window_page_bounds,
    )

    cfg = _cfg()
    BS, P, B, win = 16, 8, 4, 40
    lp = _layer_params(cfg)
    start = [127, 100, 70, 0]
    x, k_pool, v_pool, tables, start_pos = _setup(
        cfg, B=B, P=P, seed=23, start=start
    )
    clean_x, clean_k, clean_v = _fused(
        cfg, lp, x, k_pool, v_pool, tables, start_pos, win=win
    )

    # Poison every page OUTSIDE each row's live range [poff, pcount).
    wlo, poff = window_page_bounds(start_pos, win, BS)
    pcounts = history_pcounts(start_pos, BS, P)
    kp = np.asarray(k_pool, np.float32)
    vp = np.asarray(v_pool, np.float32)
    n_dead = 0
    for b in range(B):
        for p in range(P):
            if not (int(poff[b]) <= p < int(pcounts[b])):
                kp[int(tables[b, p])] = np.nan
                vp[int(tables[b, p])] = np.nan
                n_dead += 1
    assert n_dead > 0
    kpj = jnp.asarray(kp).astype(k_pool.dtype)
    vpj = jnp.asarray(vp).astype(v_pool.dtype)

    got_x, got_k, got_v = _fused(
        cfg, lp, x, kpj, vpj, tables, start_pos, win=win
    )
    assert np.isfinite(np.asarray(got_x, np.float32)).all()
    np.testing.assert_array_equal(
        np.asarray(got_x, np.float32), np.asarray(clean_x, np.float32)
    )
    np.testing.assert_array_equal(
        np.asarray(got_k, np.float32), np.asarray(clean_k, np.float32)
    )
    np.testing.assert_array_equal(
        np.asarray(got_v, np.float32), np.asarray(clean_v, np.float32)
    )

    # Self-validation: a stream-then-mask implementation CANNOT pass this
    # test — the XLA oracle (which gathers the whole table and masks)
    # produces NaN on the same poisoned pool.
    ref_x, _, _ = _oracle(
        cfg, lp, x, kpj, vpj, tables, start_pos, win=win
    )
    assert np.isnan(np.asarray(ref_x, np.float32)).any(), (
        "poison did not reach the stream-and-mask path; the proof is void"
    )


def test_window_value_shares_one_compiled_program():
    """The window rides a TRACED scalar operand: Gemma-3's 5:1
    local/global layer mix (window W on some layers, 0 on others) must
    share ONE compiled program per width bucket — the jit cache grows on
    the first windowed call and stays flat across window VALUES."""
    cfg = _gemma3_cfg()
    lp = _layer_params(cfg)
    x, k_pool, v_pool, tables, start_pos = _setup(
        cfg, B=4, P=8, seed=29, start=[0, 1, 2, 3]
    )
    s0 = fused_decoder_layer._cache_size()
    for win in (24, 0, 512, 7):
        # win=0 still passes the operand (jnp scalar), as forward_paged
        # does for a model with ANY windowed layer.
        pos = start_pos[:, None]
        cos, sin = rope_table(pos, cfg.head_dim_, cfg.rope_theta)
        fused_decoder_layer(
            x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables, start_pos,
            eps=cfg.rms_norm_eps, sm_scale=cfg.query_scale**-0.5,
            batch_block=4, interpret=True,
            window=jnp.asarray(win, jnp.int32),
            act_fn=cfg.act_fn, unit_offset=cfg.rmsnorm_unit_offset,
            softcap=0.0,
        )
    assert fused_decoder_layer._cache_size() - s0 == 1, (
        "window VALUE changed the compiled-program count — it must ride "
        "the operand, not the trace"
    )


def _count_eqns(jaxpr) -> int:
    """Total equation count including nested jaxprs (pjit bodies, the
    pallas kernel jaxpr, fori_loop/cond branches)."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else [val]
            for v in vals:
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    total += _count_eqns(inner)
                elif hasattr(v, "eqns"):
                    total += _count_eqns(v)
    return total


def test_trace_size_independent_of_table_width():
    """Compile-cost regression for the dynamic page loop: the traced
    program's equation count must NOT scale with the table width (the old
    kernel unrolled (B/BQ)*P page-steps, so P=64 traced ~4x the bodies of
    P=16 and pages past 16 were rejected outright)."""
    import functools as ft

    cfg = _cfg()
    lp = _layer_params(cfg)

    def trace_eqns(P):
        x, k_pool, v_pool, tables, start_pos = _setup(
            cfg, B=4, P=P, seed=4, start=[1, 5, 9, 13]
        )
        pos = start_pos[:, None]
        cos, sin = rope_table(pos, cfg.head_dim_, cfg.rope_theta)
        f = ft.partial(
            fused_decoder_layer,
            eps=cfg.rms_norm_eps, sm_scale=cfg.head_dim_**-0.5,
            batch_block=4, interpret=True,
        )
        jaxpr = jax.make_jaxpr(f)(
            x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables, start_pos
        )
        return _count_eqns(jaxpr.jaxpr)

    n_small, n_large = trace_eqns(8), trace_eqns(64)
    assert n_large <= n_small + 2, (n_small, n_large)


def test_compiled_program_count_tracks_width_buckets():
    """The jit cache grows once per DISTINCT table width and stays flat on
    repeats — with table_width_bucket collapsing widths into pow2 buckets
    (tests/test_fused_layer.py::test_table_width_buckets_bounded), the
    compiled-program count is bounded by the bucket count, not by context
    length."""
    cfg = _cfg()
    lp = _layer_params(cfg)
    s0 = fused_decoder_layer._cache_size()
    seen = set()
    for P in (8, 8, 32, 32):
        x, k_pool, v_pool, tables, start_pos = _setup(
            cfg, B=4, P=P, seed=5, start=[0, 1, 2, 3]
        )
        pos = start_pos[:, None]
        cos, sin = rope_table(pos, cfg.head_dim_, cfg.rope_theta)
        fused_decoder_layer(
            x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables, start_pos,
            eps=cfg.rms_norm_eps, sm_scale=cfg.head_dim_**-0.5,
            batch_block=4, interpret=True,
        )
        seen.add(P)
        assert fused_decoder_layer._cache_size() - s0 == len(seen)


def _mk_runner():
    from dynamo_tpu.engines.tpu import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner

    args = JaxEngineArgs(
        config=_cfg(), block_size=16, num_kv_blocks=64, max_num_seqs=4,
        max_model_len=64, quantization="int8", use_megakernel=True,
    )
    r = DeviceRunner(args)
    assert r.use_megakernel
    return r


def _raw_decode(r, nb=1):
    S = 4
    return r.run_decode(
        np.zeros(S, np.int32), np.zeros(S, np.int32),
        np.ones(S, np.int32), np.zeros((S, nb), np.int32),
        np.zeros(S, np.float32), np.zeros(S, np.int32),
        np.ones(S, np.float32), np.zeros(S, np.int32),
    )


def test_decode_error_at_first_dispatch_propagates(monkeypatch):
    """An error from the fused decode dispatch reaches the caller whatever
    its shape — there is no classifier deciding which errors may be
    swallowed — and the runner keeps the path it chose at start."""
    from dynamo_tpu.ops.pallas import fused_layer

    r = _mk_runner()

    def boom(*a, **k):
        raise ValueError("socket closed: transient wire error")

    monkeypatch.setattr(fused_layer, "fused_decoder_layer", boom)
    with pytest.raises(ValueError):
        _raw_decode(r)
    assert r.use_megakernel
    assert r.mk_fused_bursts == 0 and r.mk_fallback_bursts == 0


def test_compile_error_at_first_dispatch_propagates(monkeypatch):
    """A compile-shaped error (Mosaic's words) at the very first dispatch
    propagates too: the kernel the runner selected either lowers or is a
    bug, and no XLA burst is served in its place."""
    from dynamo_tpu.ops.pallas import fused_layer

    r = _mk_runner()

    def boom(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(fused_layer, "fused_decoder_layer", boom)
    with pytest.raises(RuntimeError, match="Mosaic"):
        _raw_decode(r)
    assert r.use_megakernel
    assert r.mk_fallback_bursts == 0


def test_compile_error_at_wider_bucket_propagates(monkeypatch):
    """Each pow2 table-width bucket compiles at its first dispatch. A
    lowering failure at a wider, never-compiled bucket (the first
    long-context request tripping a VMEM limit the short-context program
    never hit) propagates — it does not route that bucket to the XLA
    program — and the bucket that already compiled keeps dispatching
    fused."""
    from dynamo_tpu.ops.pallas import fused_layer

    r = _mk_runner()
    toks, _, _, _ = _raw_decode(r, nb=1)
    assert toks.shape[0] == 4
    assert r.mk_bursts_by_variant == {"w1": 1}

    real = fused_layer.fused_decoder_layer

    def boom(*a, **k):
        raise RuntimeError("Mosaic lowering failed: scoped VMEM over budget")

    monkeypatch.setattr(fused_layer, "fused_decoder_layer", boom)
    # nb=2 forces a fresh trace (new table width) so the patch takes hold
    with pytest.raises(RuntimeError, match="VMEM"):
        _raw_decode(r, nb=2)
    assert r.mk_fallback_bursts == 0, "a burst was served from XLA"
    assert r.mk_bursts_by_variant == {"w1": 1}
    monkeypatch.setattr(fused_layer, "fused_decoder_layer", real)
    toks, _, _, _ = _raw_decode(r, nb=1)
    assert toks.shape[0] == 4
    assert r.mk_bursts_by_variant == {"w1": 2}


async def test_engine_megakernel_past_old_table_ceiling():
    """A prompt past the old 256-token ceiling (decode table bucket of 32
    pages > the removed MAX_TABLE_PAGES=16) must decode THROUGH the
    megakernel — mk_bursts_by_variant shows a fused dispatch actually ran at
    that width, i.e. no silent width gate — and match the XLA path
    token-for-token."""
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.engine import collect

    cfg = _cfg()
    prompt = [(i % 90) + 3 for i in range(300)]

    async def run(use_mk):
        e = JaxEngine(JaxEngineArgs(
            config=cfg, block_size=16, num_kv_blocks=128, max_num_seqs=4,
            max_model_len=4096, quantization="int8", use_megakernel=use_mk,
        ))
        assert e.runner.use_megakernel == use_mk  # eligible at 4096
        try:
            req = PreprocessedRequest(
                token_ids=prompt, request_id=f"long-mk{use_mk}",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=8),
            )
            outs = await collect(e.generate(req, Context()))
            if use_mk:
                assert e.runner.mk_fallback_bursts == 0
                widths = [
                    int(k[1:]) for k in e.runner.mk_bursts_by_variant
                ]
                assert widths, "megakernel never ran"
                # the decode table bucket exceeded the old 16-page ceiling
                assert max(widths) > 16
            return [t for d in outs for t in d.token_ids]
        finally:
            await e.stop()

    base = await run(False)
    fused = await run(True)
    assert len(base) == 8
    assert fused == base, (fused, base)
