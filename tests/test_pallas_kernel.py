"""Pallas paged-attention kernel vs the XLA oracle.

Runs the kernel in interpret mode (CPU CI); the same kernel compiles via
Mosaic on real TPU (exercised by bench.py and the driver's bench run).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.ops.attention import _paged_attention_xla, paged_attention
from dynamo_tpu.ops.pallas.paged_attention import paged_attention_kernel


CASES = [
    # B, C, H, KH, D, bs, P, maxstart
    (2, 1, 4, 2, 64, 16, 4, 40),     # decode, GQA 2
    (3, 8, 8, 4, 64, 16, 4, 30),     # chunked prefill
    (1, 16, 14, 2, 64, 16, 8, 0),    # full prefill, GQA 7 (qwen2-0.5b shape)
    (4, 1, 8, 8, 128, 32, 2, 50),    # MHA, head_dim 128
    (2, 4, 6, 3, 64, 8, 6, 20),      # odd group count
]


@pytest.mark.parametrize("B,C,H,KH,D,bs,P,maxstart", CASES)
def test_kernel_matches_xla_oracle(B, C, H, KH, D, bs, P, maxstart):
    rng = np.random.default_rng(B * 1000 + C)
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B * P + 4, bs, KH, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B * P + 4, bs, KH, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(B * P + 2)[: B * P].reshape(B, P).astype(np.int32))
    start = jnp.asarray(rng.integers(0, maxstart + 1, B).astype(np.int32))
    cl = jnp.asarray(rng.integers(1, C + 1, B).astype(np.int32))

    ref = np.asarray(_paged_attention_xla(q, k, v, bt, start, cl))
    out = np.asarray(paged_attention_kernel(q, k, v, bt, start, cl, interpret=True))

    assert out.shape == ref.shape
    for b in range(B):
        n = int(cl[b])  # rows past chunk_len are padding; not compared
        np.testing.assert_allclose(out[b, :n], ref[b, :n], atol=2e-5, rtol=2e-5)


DECODE_CASES = [
    # B, H, KH, D, bs, P, maxstart, batch_block
    (16, 14, 2, 64, 32, 8, 200, 8),  # qwen2-0.5b decode shape
    (9, 8, 4, 64, 16, 4, 50, 8),     # B > BQ and not a multiple: pad branch
    (8, 8, 8, 128, 32, 2, 40, 4),    # MHA head_dim 128
    (2, 4, 2, 64, 16, 6, 0, 8),      # position 0 (single visible key)
]


@pytest.mark.parametrize("B,H,KH,D,bs,P,maxstart,BQ", DECODE_CASES)
def test_decode_kernel_matches_xla_oracle(B, H, KH, D, bs, P, maxstart, BQ):
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel,
    )

    rng = np.random.default_rng(B * 77 + H)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B * P + 4, bs, KH, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B * P + 4, bs, KH, D)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(B * P + 2)[: B * P].reshape(B, P).astype(np.int32)
    )
    start = jnp.asarray(
        rng.integers(0, min(maxstart, P * bs - 1) + 1, B).astype(np.int32)
    )
    cl = jnp.ones(B, jnp.int32)

    ref = np.asarray(_paged_attention_xla(q, k, v, bt, start, cl))
    out = np.asarray(
        paged_attention_decode_kernel(
            q, k, v, bt, start, interpret=True, batch_block=BQ
        )
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_use_kernel_failure_propagates(monkeypatch):
    """use_kernel=True reaches the Pallas kernel or raises: a kernel that
    cannot run is never served from the XLA gather path behind the
    caller's back (the import is at module top; there is no loader to
    swallow an error)."""
    import dynamo_tpu.ops.attention as attn

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(attn, "paged_attention_decode_kernel", refuse)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 1, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((4, 16, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((4, 16, 2, 64)), jnp.float32)
    bt = jnp.zeros((1, 2), jnp.int32)
    start = jnp.zeros((1,), jnp.int32)
    cl = jnp.ones((1,), jnp.int32)
    with pytest.raises(RuntimeError, match="Mosaic"):
        paged_attention(q, k, v, bt, start, cl, use_kernel=True)
    out = paged_attention(q, k, v, bt, start, cl, use_kernel=False)
    ref = _paged_attention_xla(q, k, v, bt, start, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("window", [4, 16, 40])
def test_decode_kernel_sliding_window_matches_oracle(window):
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel,
    )

    rng = np.random.default_rng(window)
    B, H, KH, D, bs, P = 5, 4, 2, 64, 16, 6
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B * P + 2, bs, KH, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B * P + 2, bs, KH, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(B * P + 2)[: B * P].reshape(B, P).astype(np.int32))
    start = jnp.asarray(rng.integers(0, P * bs - 1, B).astype(np.int32))
    cl = jnp.ones((B,), jnp.int32)

    ref = np.asarray(
        _paged_attention_xla(q, k, v, bt, start, cl, window)
    )
    out = np.asarray(
        paged_attention_decode_kernel(
            q, k, v, bt, start, window, interpret=True, batch_block=2
        )
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_prefill_kernel_window_and_softcap_match_oracle():
    rng = np.random.default_rng(99)
    B, C, H, KH, D, bs, P = 3, 8, 4, 2, 64, 16, 4
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B * P + 2, bs, KH, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B * P + 2, bs, KH, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(B * P + 2)[: B * P].reshape(B, P).astype(np.int32))
    start = jnp.asarray([0, 13, 30], jnp.int32)
    cl = jnp.asarray([8, 8, 5], jnp.int32)
    for window, cap in ((6, 0.0), (0, 5.0), (10, 5.0)):
        ref = np.asarray(
            _paged_attention_xla(q, k, v, bt, start, cl, window, logit_cap=cap)
        )
        out = np.asarray(
            paged_attention_kernel(
                q, k, v, bt, start, cl, window, interpret=True, logit_cap=cap
            )
        )
        for b in range(B):
            n = int(cl[b])
            np.testing.assert_allclose(
                out[b, :n], ref[b, :n], atol=2e-5, rtol=2e-5
            )


class TestDenseChunkAttention:
    """First-chunk dense attention must match the paged path exactly (same
    math, zero page reads) across GQA, windows, caps, and ragged rows."""

    @pytest.mark.parametrize("H,KH,window,cap", [
        (4, 4, 0, 0.0),      # MHA full
        (8, 2, 0, 0.0),      # GQA
        (4, 4, 5, 0.0),      # sliding window
        (4, 2, 0, 30.0),     # logit cap (Gemma-2)
    ])
    def test_matches_paged(self, H, KH, window, cap):
        from dynamo_tpu.ops.attention import (
            dense_chunk_attention,
            paged_attention,
            write_chunk_to_cache,
        )

        B, C, D = 3, 16, 32
        NB, BS = 16, 8
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, C, KH, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, C, KH, D)), jnp.float32)
        lens = jnp.asarray([16, 9, 1], jnp.int32)  # ragged rows
        start = jnp.zeros((B,), jnp.int32)
        tables = jnp.asarray(
            np.arange(B * 2, dtype=np.int32).reshape(B, 2)
        )
        k_c = jnp.zeros((NB, BS, KH, D), jnp.float32)
        v_c = jnp.zeros((NB, BS, KH, D), jnp.float32)
        k_c = write_chunk_to_cache(k_c, k, tables, start, lens)
        v_c = write_chunk_to_cache(v_c, v, tables, start, lens)
        want = paged_attention(
            q, k_c, v_c, tables, start, lens, window=window, logit_cap=cap,
        )
        got = dense_chunk_attention(
            q, k, v, lens, window=window, logit_cap=cap,
        )
        w = np.asarray(want)
        g = np.asarray(got)
        for b, n in enumerate([16, 9, 1]):
            np.testing.assert_allclose(
                g[b, :n], w[b, :n], rtol=2e-5, atol=2e-5,
                err_msg=f"row {b} (len {n}) diverges",
            )

    def test_empty_window_padding_rows_stay_finite_across_layers(self):
        """Regression: a padding row whose sliding window admits no valid
        key must not NaN — at the NEXT layer 0-weight × NaN-value poisons
        every row (0 × NaN = NaN)."""
        from dynamo_tpu.ops.attention import dense_chunk_attention

        B, C, H, D = 1, 32, 4, 16
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
        lens = jnp.asarray([21], jnp.int32)
        # layer 1: window 8 → rows 29.. see no valid key (cols (21..29]∩[0,21)=∅)
        o1 = dense_chunk_attention(x, x, x, lens, window=8)
        assert bool(jnp.isfinite(o1).all()), "layer-1 output not finite"
        # layer 2 consumes layer 1's output as k/v: all rows must stay finite
        o2 = dense_chunk_attention(o1, o1, o1, lens, window=0)
        assert bool(jnp.isfinite(o2[:, :21]).all()), "valid rows poisoned"
        assert bool(jnp.isfinite(o2).all())


def test_blocked_kernel_short_chunk_parity():
    """C>1 (speculative-verify shape) through the batch-blocked kernel:
    parity vs the XLA oracle, per-row causality intact."""
    import numpy as np
    from dynamo_tpu.ops.attention import _paged_attention_xla, write_chunk_to_cache
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel,
    )

    B, C, KH, G, D, BS, P = 4, 5, 2, 2, 128, 16, 3
    H = KH * G
    NB = B * P + 2
    rng = np.random.default_rng(9)
    hist = jnp.asarray(
        rng.standard_normal((B, BS * P, KH, D)).astype(np.float32)
    ).astype(jnp.bfloat16)
    tables = jnp.asarray(
        rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32)
    )
    start = jnp.asarray([3, 17, 29, 40], jnp.int32)
    lens = jnp.full((B,), C, jnp.int32)

    def fill(f):
        cache = jnp.zeros((NB, BS, KH, D), jnp.bfloat16)
        return write_chunk_to_cache(
            cache, hist * f, tables, jnp.zeros((B,), jnp.int32),
            jnp.full((B,), BS * P, jnp.int32),
        )

    q = jnp.asarray(
        rng.standard_normal((B, C, H, D)).astype(np.float32)
    ).astype(jnp.bfloat16)
    kb, vb = fill(1.0), fill(0.5)
    ref = _paged_attention_xla(q, kb, vb, tables, start, lens)
    out = paged_attention_decode_kernel(
        q, kb, vb, tables, start, interpret=True, batch_block=2
    )
    err = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()
    assert float(err) < 2e-2, float(err)

    # sliding window too
    ref_w = _paged_attention_xla(q, kb, vb, tables, start, lens, 8)
    out_w = paged_attention_decode_kernel(
        q, kb, vb, tables, start, 8, interpret=True, batch_block=2
    )
    err_w = jnp.abs(out_w.astype(jnp.float32) - ref_w.astype(jnp.float32)).max()
    assert float(err_w) < 2e-2, float(err_w)
