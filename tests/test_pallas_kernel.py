"""Pallas paged-attention kernel vs the XLA oracle.

Runs the kernel in interpret mode (CPU CI); the same kernel compiles via
Mosaic on real TPU (exercised by chip_smoke.py's kernels stage and the
driver's benchmark run).
"""

import numpy as np
import pytest

import jax
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


def _decode_kernel_grouped(S, *args, **static):
    """The decode kernel held to at most S pages a grid step, so that small
    tables still give rows of several steps. (Served, S follows from the
    page's bytes: ``_decode_group_pages``.)"""
    import functools
    from unittest import mock

    from dynamo_tpu.ops.pallas import paged_attention as pa

    with mock.patch.object(pa, "DECODE_GROUP_PAGES", S):
        return jax.jit(functools.partial(
            pa._paged_attention_decode_kernel_impl, interpret=True, **static
        ))(*args)


DECODE_CASES = [
    # B, H, KH, D, bs, P, maxstart, pages a grid step
    (16, 14, 2, 64, 32, 8, 200, 8),  # qwen2-0.5b decode shape
    (9, 8, 4, 64, 16, 4, 50, 3),     # group not a power of two
    (8, 8, 8, 128, 32, 2, 40, 4),    # MHA head_dim 128; group > table
    (2, 4, 2, 64, 16, 6, 0, 8),      # position 0 (single visible key)
    (6, 4, 2, 64, 16, 6, 95, 1),     # one page a grid step
    (6, 4, 2, 64, 16, 6, 95, 2),     # a row of several steps
]


@pytest.mark.parametrize("B,H,KH,D,bs,P,maxstart,S", DECODE_CASES)
def test_decode_kernel_matches_xla_oracle(B, H, KH, D, bs, P, maxstart, S):
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
        _decode_kernel_grouped(S, q, k, v, bt, start)
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def _live_span_inputs(B, C, H, KH, D, bs, P, live, starts, seed, quantized=False):
    """Pools, tables and positions for the live-span cases. ``live`` rows
    get ``starts``; the others are EMPTY slots: chunk_lens 0, a stale
    position far past the table and a table of block ids that do not
    exist, as a freed slot's device state may hold."""
    from dynamo_tpu.ops.kv_quant import quantize_kv_chunk

    rng = np.random.default_rng(seed)
    NB = B * P + 3
    dtype = jnp.bfloat16 if quantized else jnp.float32
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), dtype)
    pools = []
    for _ in range(2):
        pool = jnp.asarray(rng.standard_normal((NB, bs, KH, D)), dtype)
        if quantized:
            q8, s = quantize_kv_chunk(pool)
            pool = {"q8": q8, "s": s.transpose(0, 2, 1)}
        pools.append(pool)
    tables = rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32)
    live = np.asarray(live, bool)
    start = np.where(live, np.asarray(starts), 0).astype(np.int32)
    lens = np.where(live, C, 0).astype(np.int32)
    stale_tables = np.where(live[:, None], tables, 10**6).astype(np.int32)
    stale_start = np.where(live, start, 10**5).astype(np.int32)
    return q, pools, tables, start, lens, stale_tables, stale_start


def _rows(B, n_live, seed):
    live = np.zeros(B, bool)
    live[np.random.default_rng(seed).permutation(B)[:n_live]] = True
    return live


LIVE_SPAN_CASES = {
    # name: (B, C, H, KH, D, bs, P, n_live, window, cap, quantized, S)
    "ragged-dead-slots": (12, 1, 4, 2, 64, 16, 6, 5, 0, 0.0, False, 4),
    "qwen2.5-0.5b-64-slots-22-live": (64, 1, 14, 2, 64, 16, 8, 22, 0, 0.0, False, 8),
    "window": (8, 1, 4, 2, 64, 16, 6, 5, 20, 0.0, False, 4),
    "window-short-of-a-page": (8, 1, 4, 2, 64, 16, 6, 5, 5, 0.0, False, 2),
    "softcap": (8, 1, 4, 2, 64, 16, 6, 5, 0, 5.0, False, 8),
    "window-softcap-c4": (6, 4, 4, 2, 64, 16, 6, 4, 24, 5.0, False, 4),
    "int8": (8, 1, 4, 2, 128, 16, 5, 5, 0, 0.0, True, 4),
    "int8-window-c2": (8, 2, 4, 2, 128, 16, 5, 5, 20, 0.0, True, 8),
    "c2": (6, 2, 8, 4, 64, 16, 5, 4, 0, 0.0, False, 4),
    "c4": (6, 4, 6, 3, 64, 8, 6, 4, 0, 0.0, False, 3),
    "c8": (6, 8, 8, 2, 64, 16, 5, 4, 0, 0.0, False, 8),
}


@pytest.mark.parametrize("case", sorted(LIVE_SPAN_CASES))
def test_decode_kernel_live_span(case):
    """The live-span body against the XLA oracle: live rows agree; EMPTY
    slots (chunk_lens 0) holding a stale position and a table of block ids
    that do not exist are never visited, return zeros, and change nothing
    for the live rows."""
    B, C, H, KH, D, bs, P, n_live, window, cap, quantized, S = (
        LIVE_SPAN_CASES[case]
    )
    live = _rows(B, n_live, seed=len(case))
    rng = np.random.default_rng(B + C)
    starts = rng.integers(0, P * bs - C + 1, B)
    q, (k, v), tables, start, lens, stale_tables, stale_start = (
        _live_span_inputs(B, C, H, KH, D, bs, P, live, starts, seed=B * C,
                          quantized=quantized)
    )
    full = jnp.full((B,), C, jnp.int32)
    ref = np.asarray(_paged_attention_xla(
        q, k, v, tables, start, full, window, logit_cap=cap
    ).astype(jnp.float32))
    out = np.asarray(_decode_kernel_grouped(
        S, q, k, v, stale_tables, stale_start, window, lens, logit_cap=cap,
    ).astype(jnp.float32))
    tol = 2e-2 if quantized else 2e-5
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert np.isfinite(out).all()
    assert (out[~live] == 0).all()


@pytest.mark.parametrize("C,window", [(1, 0), (1, 24), (4, 0)])
def test_decode_kernel_is_table_width_independent(C, window):
    """The same rows under a table padded from P to 2P columns of garbage
    block ids run the same grid steps: bit-identical outputs."""
    B, H, KH, D, bs, P = 8, 4, 2, 64, 16, 4
    live = _rows(B, 6, seed=C + window)
    starts = np.random.default_rng(C).integers(0, P * bs - C + 1, B)
    q, (k, v), tables, start, lens, _, _ = _live_span_inputs(
        B, C, H, KH, D, bs, P, live, starts, seed=3
    )
    wide = np.concatenate([tables, np.full_like(tables, 10**6)], axis=1)
    narrow_out = _decode_kernel_grouped(
        2, q, k, v, tables, start, window, lens
    )
    wide_out = _decode_kernel_grouped(2, q, k, v, wide, start, window, lens)
    assert np.array_equal(np.asarray(narrow_out), np.asarray(wide_out))


@pytest.mark.parametrize("start", [0, 15, 16, 31, 32, 63])
def test_decode_kernel_page_boundaries(start):
    """A history that ends exactly on a page boundary (the current token is
    a page's last slot, or the first of a fresh page), and start = 0."""
    B, H, KH, D, bs, P = 3, 4, 2, 64, 16, 4
    q, (k, v), tables, st, lens, _, _ = _live_span_inputs(
        B, 1, H, KH, D, bs, P, [True] * B, [start] * B, seed=start
    )
    ref = np.asarray(_paged_attention_xla(q, k, v, tables, st, lens))
    out = np.asarray(_decode_kernel_grouped(2, q, k, v, tables, st, 0, lens))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_decode_kernel_with_no_live_row_returns_zeros():
    """Every slot empty (garbage tables, stale positions): the grid runs
    its one step on a block that exists and the output is zeros."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel,
    )

    B, H, KH, D, bs, P = 4, 4, 2, 64, 16, 4
    q, (k, v), _, _, lens, stale_tables, stale_start = _live_span_inputs(
        B, 1, H, KH, D, bs, P, [False] * B, [0] * B, seed=1
    )
    out = paged_attention_decode_kernel(
        q, k, v, stale_tables, stale_start, 0, lens, interpret=True
    )
    assert (np.asarray(out) == 0).all()


@pytest.mark.parametrize("C,P", [(1, 1), (4, 8), (8, 16)])
def test_decode_kernel_single_row_short_table(C, P):
    """One row under a table no wider than a group — the tail of a
    prefix-hit prefill (B 1, C 4, start 96 in the benchmark's repeated
    probe). On the chip a work list of ONE entry halted the core: the list
    is one entry longer than the grid's steps (live_work_list)."""
    from dynamo_tpu.ops.pallas.live_pages import (
        live_page_bounds,
        live_work_list,
    )
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel,
    )

    H, KH, D, bs = 14, 2, 64, 16
    start = P * bs - C - 2
    q, (k, v), tables, st, lens, _, _ = _live_span_inputs(
        1, C, H, KH, D, bs, P, [True], [start], seed=C
    )
    ref = np.asarray(_paged_attention_xla(q, k, v, tables, st, lens))
    out = np.asarray(paged_attention_decode_kernel(
        q, k, v, tables, st, 0, lens, interpret=True
    ))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    pcount, poff = live_page_bounds(st, lens, C, 0, bs, P)
    total, rows, pages = live_work_list(pcount, poff, 16, P)
    assert int(total) == 1 and rows.shape[0] == 2
    assert (np.asarray(rows) == 0).all() and (np.asarray(pages) == 0).all()


@pytest.mark.parametrize("B,P,S", [(1, 8, 16), (4, 32, 16), (3, 5, 2), (64, 128, 16)])
def test_live_work_list_is_one_longer_than_the_longest_grid(B, P, S):
    """The invariant the grid rests on: with every row full the grid takes
    its most steps, the list still has one entry more, and every entry at
    or past the grid's end repeats the last step — so the index maps the
    pipeline evaluates one step ahead name a block it already holds."""
    from dynamo_tpu.ops.pallas.live_pages import (
        live_page_bounds,
        live_work_list,
    )

    bs = 16
    for n_live in (B, max(B // 2, 1), 0):
        lens = jnp.asarray([1] * n_live + [0] * (B - n_live), jnp.int32)
        start = jnp.full((B,), P * bs - 1, jnp.int32)
        pcount, poff = live_page_bounds(start, lens, 1, 0, bs, P)
        total, rows, pages = live_work_list(pcount, poff, S, P)
        total = int(total)
        assert total == n_live * -(-P // S)
        assert rows.shape[0] == B * -(-P // S) + 1 > total
        last = max(total - 1, 0)
        assert (np.asarray(rows[last:]) == int(rows[last])).all()
        assert (np.asarray(pages[last:]) == int(pages[last])).all()


GROUP_PAGES_CASES = {
    # name: (bs, KH, D, quantized, P, pages a step)
    "qwen2.5-0.5b-bs16": (16, 2, 64, False, 64, 16),
    "qwen3-8b-bs16": (16, 8, 128, False, 128, 16),
    "qwen3-8b-int8-bs16": (16, 8, 128, True, 128, 16),
    "gemma-2-9b-d256-bs16": (16, 8, 256, False, 128, 16),
    "qwen3-8b-bs64": (64, 8, 128, False, 32, 8),
    "qwen3-8b-bs128": (128, 8, 128, False, 16, 4),
    "qwen3-8b-int8-bs128": (128, 8, 128, True, 16, 4),
    "gemma-d256-bs64": (64, 4, 256, False, 32, 8),
    "narrow-table": (16, 8, 128, False, 4, 4),
    "one-huge-page": (1024, 8, 256, False, 8, 1),
}


@pytest.mark.parametrize("case", sorted(GROUP_PAGES_CASES))
def test_decode_group_pages_follow_the_page_bytes(case):
    """Pages a grid step visits: 16 at the served block size, fewer as
    --block-size grows, so the step's double-buffered K and V operands
    stay inside the VMEM budget (16 pages of 256 KiB each did not compile
    for the v5e: tests/test_mosaic_compile.py)."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        DECODE_PAGES_VMEM_BYTES,
        _decode_group_pages,
    )

    bs, KH, D, quantized, P, want = GROUP_PAGES_CASES[case]
    if quantized:
        pool = {"q8": jax.ShapeDtypeStruct((64, bs, KH, D), jnp.int8),
                "s": jax.ShapeDtypeStruct((64, KH, bs), jnp.float32)}
    else:
        pool = jax.ShapeDtypeStruct((64, bs, KH, D), jnp.bfloat16)
    S = _decode_group_pages(pool, P)
    assert S == want
    page = bs * KH * D * (1 if quantized else 2)
    assert S == 1 or 4 * S * page <= DECODE_PAGES_VMEM_BYTES


@pytest.mark.parametrize("window", [0, 24])
def test_decode_kernel_takes_the_callers_plan(window):
    """A plan derived once by the caller (a forward step, for all its
    layers) gives the bits the kernel's own derivation gives."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        decode_plan,
        paged_attention_decode_kernel,
    )

    B, C, H, KH, D, bs, P = 8, 2, 4, 2, 64, 16, 6
    live = _rows(B, 5, seed=window)
    starts = np.random.default_rng(window).integers(0, P * bs - C + 1, B)
    q, (k, v), _, _, lens, tables, start = _live_span_inputs(
        B, C, H, KH, D, bs, P, live, starts, seed=11
    )
    plan = decode_plan(k, tables, start, lens, C, window)
    own = paged_attention_decode_kernel(
        q, k, v, tables, start, window, lens, interpret=True
    )
    given = paged_attention_decode_kernel(
        q, k, v, tables, start, window, lens, plan, interpret=True
    )
    assert np.array_equal(np.asarray(own), np.asarray(given))


def test_live_work_list_covers_live_pages_once():
    """The grid's work list: every live page of every live row in exactly
    one step, rows in order, nothing for an empty slot."""
    from dynamo_tpu.ops.pallas.live_pages import (
        live_page_bounds,
        live_work_list,
    )

    bs, P, S = 16, 8, 4
    start = jnp.asarray([0, 15, 16, 70, 127, 500, 40], jnp.int32)
    lens = jnp.asarray([1, 1, 1, 1, 1, 1, 0], jnp.int32)
    pcount, poff = live_page_bounds(start, lens, 1, 40, bs, P)
    assert pcount.tolist() == [1, 1, 2, 5, 8, 8, 0]  # clamped to the table
    assert poff.tolist() == [0, 0, 0, 1, 5, 28, 0]
    total, rows, pages = live_work_list(pcount, poff, S, P)
    steps = [(int(r), int(p)) for r, p in zip(rows[: int(total)], pages)]
    assert steps == [(0, 0), (1, 0), (2, 0), (3, 1), (4, 5)]


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
        _decode_kernel_grouped(2, q, k, v, bt, start, window)
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
    """C>1 (speculative-verify shape) through the live-span decode kernel:
    parity vs the XLA oracle, per-row causality intact."""
    import numpy as np
    from dynamo_tpu.ops.attention import _paged_attention_xla, write_chunk_to_cache
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
    out = _decode_kernel_grouped(2, q, kb, vb, tables, start)
    err = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()
    assert float(err) < 2e-2, float(err)

    # sliding window too
    ref_w = _paged_attention_xla(q, kb, vb, tables, start, lens, 8)
    out_w = _decode_kernel_grouped(2, q, kb, vb, tables, start, 8)
    err_w = jnp.abs(out_w.astype(jnp.float32) - ref_w.astype(jnp.float32)).max()
    assert float(err_w) < 2e-2, float(err_w)


def test_forward_derives_the_decode_plan_once_per_window(monkeypatch):
    """forward_paged over per-layer pools derives the decode kernel's grid
    once per step and distinct window, not once per layer, and the layers
    that share it give the logits the XLA path gives."""
    import functools

    import dynamo_tpu.ops.attention as attn
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import tiny_config

    c = tiny_config(
        n_layers=4, head_dim=64, sliding_window=24, sliding_window_pattern=2
    )
    assert c.layer_windows() == [24, 0, 24, 0]
    monkeypatch.setattr(
        attn, "paged_attention_decode_kernel",
        functools.partial(attn.paged_attention_decode_kernel, interpret=True),
    )
    planned = []
    real_plan = attn.decode_plan

    def counting_plan(k_cache, tables, start, lens, C, window=0):
        planned.append(int(window))
        return real_plan(k_cache, tables, start, lens, C, window)

    monkeypatch.setattr(attn, "decode_plan", counting_plan)

    B, bs, P, NB = 4, 16, 4, 24
    rng = np.random.default_rng(5)
    params = llama.init_params(c, jax.random.PRNGKey(0))
    tables = jnp.asarray(rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32))
    prompt = jnp.asarray(rng.integers(0, c.vocab_size, (B, 40)).astype(np.int32))
    zeros = jnp.zeros((B,), jnp.int32)
    # rows 0-2 live with 40, 33 and 17 tokens of history; row 3 an empty slot
    hist = jnp.asarray([40, 33, 17, 0], jnp.int32)
    lens = jnp.asarray([1, 1, 1, 0], jnp.int32)
    tok = jnp.asarray(rng.integers(0, c.vocab_size, (B, 1)).astype(np.int32))

    logits = {}
    for use_kernel in (False, True):
        kc, vc = llama.init_kv_cache(c, NB, bs, layered=True)
        _, kc, vc = llama.forward_paged(
            params, c, prompt, zeros, hist, tables, kc, vc, first_chunk=True
        )
        planned.clear()
        logits[use_kernel], _, _ = llama.forward_paged(
            params, c, tok, hist, lens, tables, kc, vc, use_kernel=use_kernel
        )
        assert sorted(planned) == ([0, 24] if use_kernel else [])
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(logits[True])[live], np.asarray(logits[False])[live],
        atol=2e-4, rtol=2e-4,
    )


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


def test_attention_is_chosen_once_with_a_reason():
    """The runner decides the attention implementation at start from what
    it can observe, and says why (the worker's start-up log line and
    stats() carry the strings chip_smoke.py prints)."""
    from dynamo_tpu.engines.tpu import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models.config import tiny_config

    choose_attn = DeviceRunner._choose_attention
    ok = JaxEngineArgs(config=tiny_config(), max_num_seqs=4, quantization="int8")

    assert choose_attn(ok, "cpu", None) == (
        False, "platform is cpu (Mosaic lowers on TPU only)"
    )
    assert choose_attn(ok, "tpu", None)[0] is True
    use, why = choose_attn(ok, "tpu", object())  # any mesh
    assert use is False and "mesh" in why
    forced = JaxEngineArgs(config=tiny_config(), use_kernel=True)
    with pytest.raises(ValueError, match="mesh"):
        choose_attn(forced, "tpu", object())


# The live-span decode kernel at contexts its other cases never reach (their
# widest table is 8 pages): up to 256 pages of 16 tokens, every row at the
# context's end, with and without a sliding window; and rows of under a page
# beside one 4,096-token row.
LONG_CONTEXT_CASES = {
    f"ctx{ctx}-window{window}": ([ctx - 1, ctx - 2, ctx - 17, ctx // 2], window)
    for ctx in (256, 1024, 4096) for window in (0, 1024)
}
LONG_CONTEXT_CASES["ragged-4096"] = ([4095, 3, 0, 15], 0)
LONG_CONTEXT_CASES["ragged-4096-window1024"] = ([4095, 3, 0, 15], 1024)


@pytest.mark.parametrize("case", sorted(LONG_CONTEXT_CASES))
def test_decode_kernel_long_context(case):
    starts, window = LONG_CONTEXT_CASES[case]
    B, C, H, KH, D, bs = 5, 1, 4, 2, 128, 16
    P = -(-(max(starts) + 1) // bs)
    live = np.asarray([True] * 4 + [False])  # the last slot is empty
    q, (k, v), tables, start, lens, stale_tables, stale_start = (
        _live_span_inputs(B, C, H, KH, D, bs, P, live, starts + [0],
                          seed=P + window)
    )
    ref = np.asarray(_paged_attention_xla(
        q, k, v, tables, start, jnp.full((B,), C, jnp.int32), window
    ))
    out = np.asarray(_decode_kernel_grouped(  # 16 pages a step: as served
        16, q, k, v, stale_tables, stale_start, window, lens))
    np.testing.assert_allclose(out[live], ref[live], atol=2e-5, rtol=2e-5)
    assert (out[~live] == 0).all()
