"""Fused-layer decode megakernel (pallas TPU).

ONE pallas program per decoder layer for the C=1 decode path: RMS-norm →
int8-streamed qkv (+fused RoPE) → paged attention (history pages + the
in-register current token) → int8-streamed o-proj → residual → RMS-norm →
int8-streamed gate/up/act/mul/down → residual. Weights stay in HBM and
stream through VMEM tiles with manual double-buffered DMAs; KV pages stream
in per-(wave, page) steps whose first DMAs are issued during the qkv weight
stream, so page-issue latency hides under matmul compute.

History pages are driven by a DYNAMIC page loop (r6): the per-row block
tables and page counts live in SMEM (scalar-prefetch operands, available
before the body runs), each batch wave runs a ``fori_loop`` bounded by the
wave's live page range, and every DMA/compute step is gated per row on its
own scalar-prefetched bounds. Trace/compile size is therefore independent
of the table width — long contexts (4k+ tokens) compile the same program
as short ones — and short rows in a long-context batch skip their dead
pages entirely (no stream, no mask) instead of streaming-then-masking up
to the table capacity. Table widths are pow2-bucketed by the engine
(engines/tpu/engine.py::table_width_bucket), so XLA holds a handful of
programs per shape, one per bucket.

Architecture epilogues (r11): the family knobs that used to force the
~1/3-roofline XLA fallback are now in-kernel, so Qwen3 and Gemma-2/3
decode on the fused path:

  - **qk-norm** — per-head RMSNorm on the q/k projection columns before
    RoPE (Qwen3/Gemma-3 order: norm → rope), a few VPU ops on vectors
    already live in registers plus two [1, D] norm-weight operands;
  - **attention logit softcap** — ``cap·tanh(s/cap)`` on scores before
    masking (Gemma-2), a static-float epilogue on both the page loop and
    the current-token column;
  - **post-norms** — Gemma-2/3's extra RMSNorms after the attention and
    FFN blocks; the o-proj phase accumulates into a [B, d] f32 scratch so
    the full row is normed before the residual add (the FFN side reuses
    the down-proj accumulator that already exists);
  - **sliding window** — each row's dynamic page loop STARTS at
    ``floor((pos−W)/BS)`` instead of page 0 (per-row SMEM page offsets,
    same predicate style as the page counts) and the boundary page is
    masked in-kernel, so a windowed row streams strictly fewer pages than
    full attention — a perf win, not just coverage. The window rides a
    TRACED scalar operand, so Gemma-3's 5:1 local/global layer mix shares
    ONE compiled program per width bucket;
  - **GeGLU / unit-offset RMSNorm / qkv-bias** — a static activation
    switch (tanh-gelu vs SiLU), ``(1 + w)`` norm weights, and per-column
    bias adds on the qkv tiles.

Why this exists (r5): the per-layer XLA decode structure leaves inter-op
glue (fusions and copies between every matmul), a standalone attention
kernel bound by DMA issue and not by page bytes, and weight matmuls a
pallas mixed int8 dot can beat (PERF.md holds what has been measured).
Fusing the whole layer removes the glue,
overlaps attention page fetches with weight streaming, and keeps the
residual in VMEM across phases.

Reference parity: plays the role of the fused decode kernels inside the
engines the reference orchestrates (vLLM/TRT-LLM fused attention+GEMM
paths serve Qwen3/Gemma natively); the reference repo itself carries no
TPU equivalent.

Scope (v3): C=1 decode, dense FFN, int8 weights ({"q8","s"} per
ops/quant.py), bf16 KV pools, head_dim a multiple of 128. Excluded (and
documented in supports_reason): MoE FFNs and LoRA deltas — both fall back
to the XLA path. The XLA path (models/llama.py::decoder_layer) remains the
fallback for every other configuration and stays the numerics oracle;
parity is asserted in interpret mode at 256/1k/4k-token contexts, ragged
short+long batches, and page-straddling window boundaries
(tests/test_fused_layer.py, tests/test_zlongctx_fused.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.live_pages import (
    history_pcounts,
    window_page_bounds,
)

NEG_INF = -1e30

_SUPPORTED_ACTS = ("silu", "gelu_tanh")


def _tiles_for(d: int, HD: int, KHD: int, F: int, D: int):
    """(TQ, TO, TF) weight-streaming tile widths for these dims, or None
    when no feasible split exists. Each tile is the LARGEST lane-aligned
    divisor under the VMEM cap: TQ covers whole heads (multiple of D) and
    must divide both the q and k/v projection widths so every qkv col tile
    lives entirely inside one of wq/wk/wv; TO/TF are multiples of the
    128-lane MXU width dividing d / d_ff (Gemma shapes like d=1152 or
    d_ff=6912 need 384 — the old min(512, ·) rule rejected them)."""

    def div_tile(n: int, cap: int, step: int) -> Optional[int]:
        t = (cap // step) * step
        while t >= step:
            if n % t == 0:
                return t
            t -= step
        return None

    tq = None
    t = (256 // D) * D if D else 0
    while t >= D > 0:
        if HD % t == 0 and KHD % t == 0:
            tq = t
            break
        t -= D
    to = div_tile(d, 512, 128)
    tf = div_tile(F, 512, 128)
    if tq is None or to is None or tf is None:
        return None
    return tq, to, tf


def supports_reason(
    config, *, lora: bool, quantized_weights: bool
) -> Optional[str]:
    """Why the megakernel can NOT serve this config (None = it can).

    Every knob the kernel does not implement must surface here — an
    auto-enabled config can never crash at first decode instead of
    falling back — and the docs' supports() matrix + the supports-matrix
    preset test render these exact strings. qk-norm, sliding windows,
    logit softcap, post-norms, unit-offset RMSNorm, qkv-bias and GeGLU
    are in-kernel epilogues since r11 and are deliberately absent."""
    c = config
    if getattr(c, "has_latent_cache", False):
        return (
            "latent (MLA) cache: the kernel streams a K and a V page of one "
            "head width per layer, and this model keeps one latent pool a "
            "layer (c_kv beside the shared rotary key) and no V pool"
        )
    if getattr(c, "window_group", None) is not None:
        return (
            "two page groups: the kernel follows ONE block table a row, and "
            "this model's sliding-window layers keep a page group of their "
            "own whose pages behind the window are released"
        )
    if getattr(c, "is_hybrid", False):
        return (
            "hybrid model (one mixer per layer: the kernel fuses attention "
            "and a dense FFN over paged K/V, and knows neither state-space "
            "mixers and their recurrent state nor routed experts)"
        )
    if not quantized_weights:
        return "weights not int8-quantized (the kernel streams int8 tiles)"
    if lora:
        return "LoRA adapters active (per-request delta einsums excluded)"
    if c.is_moe:
        return "MoE FFN (routed experts excluded; dense FFN only)"
    if c.act_fn not in _SUPPORTED_ACTS:
        return f"unsupported activation {c.act_fn!r} (silu/gelu_tanh only)"
    D = c.head_dim_
    if D <= 0 or D % 128 != 0:
        return f"head_dim {D} not a multiple of the 128-lane MXU width"
    if (c.n_heads % c.n_kv_heads) != 0:
        return "n_heads not a multiple of n_kv_heads (GQA grouping)"
    d, HD, KHD, F = c.d_model, c.n_heads * D, c.n_kv_heads * D, c.d_ff
    if _tiles_for(d, HD, KHD, F, D) is None:
        return (
            "no lane-aligned weight-streaming tile split for "
            f"(d={d}, HD={HD}, KHD={KHD}, d_ff={F})"
        )
    return None


def supports(config, *, lora: bool, quantized_weights: bool) -> bool:
    """Static eligibility of the megakernel for a model config — True when
    :func:`supports_reason` finds nothing to exclude."""
    return (
        supports_reason(config, lora=lora, quantized_weights=quantized_weights)
        is None
    )


def _fused_layer_kernel(
    *refs,
    eps: float,
    sm_scale: float,
    B: int,
    d: int,
    H: int,
    KH: int,
    D: int,
    F: int,
    P: int,
    BS: int,
    TQ: int,
    TO: int,
    TF: int,
    BQ: int,
    qk_norm: bool,
    qkv_bias: bool,
    post_norms: bool,
    act_fn: str,
    softcap: float,
    unit_offset: bool,
):
    # Positional refs vary with the static epilogue flags; parse in the
    # exact order _fused_decoder_layer_impl assembles them.
    it = iter(refs)
    # SMEM (scalar-prefetch: available before the body runs, so they drive
    # every page DMA's index and the dynamic loop bounds)
    tables_ref = next(it)  # [B, P] int32
    start_ref = next(it)  # [B] int32
    pcount_ref = next(it)  # [B] int32 — history pages: ceil(start / BS)
    wlo_ref = next(it)  # [B] int32 — first VISIBLE key index (window low)
    poff_ref = next(it)  # [B] int32 — first live page: wlo // BS
    # VMEM
    x_ref = next(it)  # [B, d] bf16 residual stream
    cos_ref = next(it)  # [B, D] f32 rope table at each row's position
    sin_ref = next(it)  # [B, D] f32
    anorm_ref = next(it)  # [1, d] attn-norm weight
    mnorm_ref = next(it)  # [1, d] mlp-norm weight
    qnorm_ref = knorm_ref = None
    if qk_norm:
        qnorm_ref = next(it)  # [1, D] per-head q-norm weight
        knorm_ref = next(it)  # [1, D]
    bq_ref = bk_ref = bv_ref = None
    if qkv_bias:
        bq_ref = next(it)  # [1, H*D]
        bk_ref = next(it)  # [1, KH*D]
        bv_ref = next(it)  # [1, KH*D]
    apost_ref = mpost_ref = None
    if post_norms:
        apost_ref = next(it)  # [1, d] post-attention norm weight
        mpost_ref = next(it)  # [1, d] post-FFN norm weight
    wqs_ref = next(it)  # [1, H*D] f32 — per-output-col int8 scales
    wks_ref = next(it)  # [1, KH*D]
    wvs_ref = next(it)  # [1, KH*D]
    wos_ref = next(it)  # [1, d]
    wgs_ref = next(it)  # [1, F]
    wus_ref = next(it)  # [1, F]
    wds_ref = next(it)  # [1, d]
    # ANY (HBM)
    wq_ref = next(it)  # [d, H*D] int8
    wk_ref = next(it)  # [d, KH*D]
    wv_ref = next(it)  # [d, KH*D]
    wo_ref = next(it)  # [H*D, d]
    wg_ref = next(it)  # [d, F]
    wu_ref = next(it)  # [d, F]
    wd_ref = next(it)  # [F, d]
    k_pool_ref = next(it)  # [NB, BS, KH, D] bf16 (HBM)
    v_pool_ref = next(it)
    # outputs (VMEM)
    xo_ref = next(it)  # [B, d]
    kn_ref = next(it)  # [B, KH, D] current-token K (post-rope)
    vn_ref = next(it)  # [B, KH, D]

    G = H // KH
    HD = H * D
    KHD = KH * D
    HPT = TQ // D  # heads covered per qkv tile
    NQT = (HD + 2 * KHD) // TQ  # qkv col tiles (wq cols, then wk, then wv)
    NOT_ = d // TO
    NFT = F // TF
    NW = B // BQ  # attention waves
    half = D // 2

    def w1(ref, dtype=jnp.float32):
        """Norm weight with the family's unit offset applied (Gemma stores
        w - 1; effective scale is 1 + w)."""
        w = ref[...].astype(dtype)
        return w + 1.0 if unit_offset else w

    def capped(s):
        """Gemma-2 attention logit softcap (static float; 0 = off)."""
        if softcap > 0.0:
            return softcap * jnp.tanh(s / softcap)
        return s

    def qkv_src(t):
        """(weight ref, scale ref, bias ref, col offset, kind, head offset)
        for qkv col tile t of the concatenated [d, HD+2*KHD] projection."""
        off = t * TQ
        if off < HD:
            return wq_ref, wqs_ref, bq_ref, off, "q", off // D
        if off < HD + KHD:
            off -= HD
            return wk_ref, wks_ref, bk_ref, off, "k", off // D
        off -= HD + KHD
        return wv_ref, wvs_ref, bv_ref, off, "v", off // D

    def body(h_ref, attn4_ref, wsem):
        # ---- phase 0: attn norm (VPU) ----
        xf = x_ref[...].astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        h_ref[...] = (xf * jax.lax.rsqrt(var + eps)).astype(jnp.bfloat16) * (
            w1(anorm_ref, jnp.bfloat16)
        )

        def rope(v):  # [B, D] f32
            lo = v[:, :half]
            hi = v[:, half:]
            rot = jnp.concatenate([-hi, lo], axis=1)
            return v * cos_ref[...] + rot * sin_ref[...]

        def head_norm(col, wref):
            """Qwen3/Gemma-3 per-head RMSNorm over head_dim, BEFORE RoPE
            (HF attention order: norm → rope). col: [B, D] f32."""
            hv = jnp.mean(col * col, axis=-1, keepdims=True)
            return col * jax.lax.rsqrt(hv + eps) * w1(wref)

        def wave_lo(w):
            """Wave's first live page (min over rows; 0 without windows)."""
            lo = poff_ref[w * BQ]
            for j in range(1, BQ):
                lo = jnp.minimum(lo, poff_ref[w * BQ + j])
            return lo

        # ---- phases 1+2 share the page-staging scratch: qkv streaming
        # issues wave 0's first page DMAs so their latency hides under
        # matmuls ----
        def qkv_and_attention(q4_ref, fl_m, fl_l, fl_acc, pages, psem):
            # THREE page-step slots: page pp+2 is issued while page pp is
            # being consumed, and lands in the slot that held page pp-1
            # (already consumed) — an issued DMA never targets a buffer
            # with pending reads, so no DMA/vector ordering assumption is
            # needed. Slots are indexed dynamically (pp % 3): the page loop
            # is a fori_loop over scalar-prefetched counts, not an unroll.
            def page_dma(slot, w, pp, j, which):
                pool = k_pool_ref if which == 0 else v_pool_ref
                page = tables_ref[w * BQ + j, pp]
                return pltpu.make_async_copy(
                    pool.at[page],
                    pages.at[slot, j, which],
                    psem.at[slot, j, which],
                )

            def row_needs(w, pp, j):
                """Is page pp LIVE for row j of wave w? Live = inside
                [poff, pcount): below pcount the row has history there,
                and at or past poff the page holds at least one key inside
                the sliding window. The SAME SMEM-derived predicate gates
                issue (pp+2), wait (pp) and compute (pp), so conditional
                start/wait pairs always match — and a short OR windowed
                row does nothing at all for its dead pages (no stream, no
                mask): windowed layers stream strictly fewer pages than
                full attention."""
                b = w * BQ + j
                return jnp.logical_and(
                    pp >= poff_ref[b], pp < pcount_ref[b]
                )

            def issue_page(w, pp):
                slot = pp % 3  # derived here so issue/wait can't desync
                for j in range(BQ):

                    @pl.when(row_needs(w, pp, j))
                    def _(j=j):
                        page_dma(slot, w, pp, j, 0).start()
                        page_dma(slot, w, pp, j, 1).start()

            def wait_page(w, pp, j):
                slot = pp % 3

                @pl.when(row_needs(w, pp, j))
                def _():
                    page_dma(slot, w, pp, j, 0).wait()
                    page_dma(slot, w, pp, j, 1).wait()

            # ---- phase 1: qkv weight streaming + fused RoPE ----
            def phase_qkv(wbuf):
                def w_dma(slot, t):
                    ref, _, _, off, _, _ = qkv_src(t)
                    return pltpu.make_async_copy(
                        ref.at[:, pl.ds(off, TQ)], wbuf.at[slot],
                        wsem.at[slot],
                    )

                w_dma(0, 0).start()
                lo0 = wave_lo(0)
                issue_page(0, lo0)
                if P > 1:
                    issue_page(0, lo0 + 1)

                h = h_ref[...]
                for t in range(NQT):  # static: tile→(ref, head) per tile
                    slot = t % 2
                    if t + 1 < NQT:
                        w_dma((t + 1) % 2, t + 1).start()
                    w_dma(slot, t).wait()
                    _, sref, bref, off, kind, h0 = qkv_src(t)
                    y = jax.lax.dot_general(
                        h, wbuf[slot], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * sref[0, pl.ds(off, TQ)][None, :]
                    if qkv_bias:
                        y = y + bref[0, pl.ds(off, TQ)][None, :]
                    for i in range(HPT):  # rope + scatter per covered head
                        col = y[:, i * D:(i + 1) * D]
                        hh = h0 + i
                        if kind == "q":
                            if qk_norm:
                                col = head_norm(col, qnorm_ref)
                            q4_ref[:, hh // G, hh % G, :] = rope(col)
                        elif kind == "k":
                            if qk_norm:
                                col = head_norm(col, knorm_ref)
                            kn_ref[:, hh, :] = rope(col).astype(kn_ref.dtype)
                        else:
                            vn_ref[:, hh, :] = col.astype(vn_ref.dtype)

            pl.run_scoped(phase_qkv, wbuf=pltpu.VMEM((2, d, TQ), jnp.int8))

            # ---- phase 2: paged attention, page-granular flash pipeline.
            # DYNAMIC page loop per wave: the fori_loop runs over the
            # wave's LIVE page range [min poff, max pcount) — scalar-
            # prefetched bounds, so the traced program holds ONE page-step
            # body per wave regardless of table width OR window value, and
            # a windowed wave starts at its first in-window page instead
            # of page 0. Batch waves stay a static unroll: NW = B/BQ is
            # small and fixed by the batch shape, and static j/kh indices
            # keep the proven static-index style of
            # ops/pallas/paged_attention.py inside the loop body. ----
            def att_wave(w):
                npg = pcount_ref[w * BQ]
                for j in range(1, BQ):
                    npg = jnp.maximum(npg, pcount_ref[w * BQ + j])
                lo = wave_lo(w)

                fl_m[...] = jnp.full_like(fl_m, NEG_INF)
                fl_l[...] = jnp.zeros_like(fl_l)
                fl_acc[...] = jnp.zeros_like(fl_acc)

                def page_step(pp, carry):
                    slot = pp % 3
                    issue_page(w, pp + 2)

                    for j in range(BQ):
                        b = w * BQ + j
                        start = start_ref[b]
                        wlo = wlo_ref[b]
                        wait_page(w, pp, j)

                        # Skip rows for whom this page is dead (history
                        # ends before it, or the sliding window starts
                        # after it) — the DMA was never issued (row_needs)
                        # and the flash state is untouched, so traffic +
                        # compute track the LIVE span, not table capacity.
                        @pl.when(row_needs(w, pp, j))
                        def _(j=j, b=b, start=start, wlo=wlo):
                            for kh in range(KH):
                                q = q4_ref[b, kh]  # [G, D]
                                kpg = pages[slot, j, 0, :, kh, :].astype(
                                    jnp.float32
                                )
                                vpg = pages[slot, j, 1, :, kh, :].astype(
                                    jnp.float32
                                )
                                s = capped(jax.lax.dot_general(
                                    q, kpg, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                ) * sm_scale)  # [G, BS]
                                t_idx = pp * BS + jax.lax.broadcasted_iota(
                                    jnp.int32, (G, BS), 1
                                )
                                # causal + window: visible history keys
                                # are t in [wlo, start) — wlo is 0 when
                                # the layer has no window, and masks the
                                # straddled boundary page when pos−W
                                # lands mid-page.
                                s = jnp.where(
                                    (t_idx < start) & (t_idx >= wlo),
                                    s, NEG_INF,
                                )
                                m = fl_m[j, kh]
                                m_new = jnp.maximum(
                                    m, jnp.max(s, -1, keepdims=True)
                                )
                                alpha = jnp.exp(m - m_new)
                                p_ = jnp.exp(s - m_new)
                                fl_l[j, kh] = fl_l[j, kh] * alpha + jnp.sum(
                                    p_, -1, keepdims=True
                                )
                                fl_acc[j, kh] = fl_acc[j, kh] * alpha + (
                                    jax.lax.dot_general(
                                        p_, vpg, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32,
                                    )
                                )
                                fl_m[j, kh] = m_new

                    return carry

                jax.lax.fori_loop(lo, npg, page_step, 0)

                # Next wave's first pages start streaming while this wave
                # finalizes — the cross-wave analogue of hiding wave 0's
                # prologue under the qkv weight stream. Every DMA this
                # wave issued was waited inside the loop (matched
                # row_needs predicates), so no slot has pending traffic.
                if w + 1 < NW:
                    nlo = wave_lo(w + 1)
                    issue_page(w + 1, nlo)
                    if P > 1:
                        issue_page(w + 1, nlo + 1)

                # wave finalize: current-token column + normalize + store.
                # The current token (t = start) is always inside the
                # window (W >= 1), so no extra mask here.
                for j in range(BQ):
                    b = w * BQ + j
                    for kh in range(KH):
                        q = q4_ref[b, kh]  # [G, D]
                        kcur = kn_ref[pl.ds(b, 1), kh, :].astype(
                            jnp.float32
                        )  # [1, D]
                        vcur = vn_ref[pl.ds(b, 1), kh, :].astype(
                            jnp.float32
                        )
                        s_c = capped(jax.lax.dot_general(
                            q, kcur, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        ) * sm_scale)  # [G, 1]
                        m = fl_m[j, kh]
                        m_new = jnp.maximum(m, s_c)
                        alpha = jnp.exp(m - m_new)
                        p_c = jnp.exp(s_c - m_new)
                        l = fl_l[j, kh] * alpha + p_c
                        acc = fl_acc[j, kh] * alpha + p_c * vcur
                        out = acc / jnp.maximum(l, 1e-30)
                        attn4_ref[pl.ds(b, 1), kh, :, :] = out.reshape(
                            1, G, D
                        ).astype(attn4_ref.dtype)

            for _w in range(NW):
                att_wave(_w)

        pl.run_scoped(
            qkv_and_attention,
            q4_ref=pltpu.VMEM((B, KH, G, D), jnp.float32),
            fl_m=pltpu.VMEM((BQ, KH, G, 1), jnp.float32),
            fl_l=pltpu.VMEM((BQ, KH, G, 1), jnp.float32),
            fl_acc=pltpu.VMEM((BQ, KH, G, D), jnp.float32),
            pages=pltpu.VMEM((3, BQ, 2, BS, KH, D), jnp.bfloat16),
            psem=pltpu.SemaphoreType.DMA((3, BQ, 2)),
        )

        # ---- phase 3: o-proj streaming + (post-norm →) residual.
        # Without post-norms each output tile folds straight into the
        # residual. WITH them (Gemma-2/3) the RMSNorm needs the FULL
        # projected row before the residual add, so tiles accumulate into
        # a [B, d] f32 scratch and the norm+residual run after the
        # stream. ----
        def phase_o(obuf, ao_ref):
            def o_dma(slot, t):
                return pltpu.make_async_copy(
                    wo_ref.at[:, pl.ds(t * TO, TO)], obuf.at[slot],
                    wsem.at[slot],
                )

            o_dma(0, 0).start()
            attn = attn4_ref[...].reshape(B, HD).astype(jnp.bfloat16)
            for t in range(NOT_):
                slot = t % 2
                if t + 1 < NOT_:
                    o_dma((t + 1) % 2, t + 1).start()
                o_dma(slot, t).wait()
                y = jax.lax.dot_general(
                    attn, obuf[slot], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * wos_ref[0, pl.ds(t * TO, TO)][None, :]
                if post_norms:
                    ao_ref[:, pl.ds(t * TO, TO)] = y
                else:
                    xo_ref[:, pl.ds(t * TO, TO)] = (
                        x_ref[:, pl.ds(t * TO, TO)].astype(jnp.float32) + y
                    ).astype(xo_ref.dtype)
            if post_norms:
                a = ao_ref[...]
                pv = jnp.mean(a * a, axis=-1, keepdims=True)
                normed = (a * jax.lax.rsqrt(pv + eps)).astype(
                    jnp.bfloat16
                ) * w1(apost_ref, jnp.bfloat16)
                xo_ref[...] = (
                    x_ref[...].astype(jnp.float32)
                    + normed.astype(jnp.float32)
                ).astype(xo_ref.dtype)

        if post_norms:
            pl.run_scoped(
                phase_o,
                obuf=pltpu.VMEM((2, HD, TO), jnp.int8),
                ao_ref=pltpu.VMEM((B, d), jnp.float32),
            )
        else:
            pl.run_scoped(
                lambda obuf: phase_o(obuf, None),
                obuf=pltpu.VMEM((2, HD, TO), jnp.int8),
            )

        # ---- phase 4: mlp norm ----
        x2 = xo_ref[...].astype(jnp.float32)
        var2 = jnp.mean(x2 * x2, axis=-1, keepdims=True)
        h_ref[...] = (x2 * jax.lax.rsqrt(var2 + eps)).astype(jnp.bfloat16) * (
            w1(mnorm_ref, jnp.bfloat16)
        )

        # ---- phases 5+6: gate/up then down (nested: gu activations stay
        # live while the gate/up weight buffers are freed) ----
        def phase_gu(wbuf, gu_ref):
            def gu_dma(slot, t, which):
                ref = wg_ref if which == 0 else wu_ref
                return pltpu.make_async_copy(
                    ref.at[:, pl.ds(t * TF, TF)], wbuf.at[slot, which],
                    wsem.at[slot * 2 + which],
                )

            gu_dma(0, 0, 0).start()
            gu_dma(0, 0, 1).start()
            h2 = h_ref[...]

            def gu_loop(t):
                slot = t % 2
                nxt = (t + 1) % 2

                if t + 1 < NFT:
                    gu_dma(nxt, t + 1, 0).start()
                    gu_dma(nxt, t + 1, 1).start()

                gu_dma(slot, t, 0).wait()
                gu_dma(slot, t, 1).wait()
                g = jax.lax.dot_general(
                    h2, wbuf[slot, 0], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * wgs_ref[0, pl.ds(t * TF, TF)][None, :]
                u = jax.lax.dot_general(
                    h2, wbuf[slot, 1], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * wus_ref[0, pl.ds(t * TF, TF)][None, :]
                if act_fn == "gelu_tanh":  # Gemma GeGLU
                    act = jax.nn.gelu(g, approximate=True)
                else:
                    act = g * jax.lax.logistic(g)
                gu_ref[:, pl.ds(t * TF, TF)] = (act * u).astype(jnp.bfloat16)

            for _t in range(NFT):
                gu_loop(_t)

            def phase_down(dbuf, acc_ref):
                def d_dma(slot, t):
                    return pltpu.make_async_copy(
                        wd_ref.at[pl.ds(t * TF, TF), :], dbuf.at[slot],
                        wsem.at[4 + slot],
                    )

                d_dma(0, 0).start()
                acc_ref[...] = jnp.zeros_like(acc_ref)

                def d_loop(t):
                    slot = t % 2
                    nxt = (t + 1) % 2

                    if t + 1 < NFT:
                        d_dma(nxt, t + 1).start()

                    d_dma(slot, t).wait()
                    acc_ref[...] += jax.lax.dot_general(
                        gu_ref[:, pl.ds(t * TF, TF)], dbuf[slot],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )

                for _t in range(NFT):
                    d_loop(_t)
                mlp = acc_ref[...] * wds_ref[...]
                if post_norms:
                    pv = jnp.mean(mlp * mlp, axis=-1, keepdims=True)
                    mlp = (
                        (mlp * jax.lax.rsqrt(pv + eps)).astype(jnp.bfloat16)
                        * w1(mpost_ref, jnp.bfloat16)
                    ).astype(jnp.float32)
                xo_ref[...] = (
                    xo_ref[...].astype(jnp.float32) + mlp
                ).astype(xo_ref.dtype)

            pl.run_scoped(
                phase_down,
                dbuf=pltpu.VMEM((2, TF, d), jnp.int8),
                acc_ref=pltpu.VMEM((B, d), jnp.float32),
            )

        pl.run_scoped(
            phase_gu,
            wbuf=pltpu.VMEM((2, 2, d, TF), jnp.int8),
            gu_ref=pltpu.VMEM((B, F), jnp.bfloat16),
        )

    pl.run_scoped(
        body,
        h_ref=pltpu.VMEM((B, d), jnp.bfloat16),
        attn4_ref=pltpu.VMEM((B, KH, G, D), jnp.bfloat16),
        wsem=pltpu.SemaphoreType.DMA((6,)),
    )


def _fused_decoder_layer_impl(
    x: jnp.ndarray,  # [B, d] bf16 residual
    cos: jnp.ndarray,  # [B, D] f32 (already the layer's local/global table)
    sin: jnp.ndarray,  # [B, D] f32
    lp: Dict[str, Any],  # one layer's params (quantized tree)
    k_pool: jnp.ndarray,  # [NB, BS, KH, D] bf16
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, P] int32
    start_pos: jnp.ndarray,  # [B] int32
    *,
    eps: float,
    sm_scale: float,
    batch_block: int = 4,
    interpret: Optional[bool] = None,
    pcounts: Optional[jnp.ndarray] = None,  # [B] int32 (history_pcounts)
    window: Optional[jnp.ndarray] = None,  # scalar int32 (0/None = full)
    act_fn: str = "silu",
    unit_offset: bool = False,
    softcap: float = 0.0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run one fused decoder layer. Returns (x_out [B, d], k_new [B, KH, D],
    v_new [B, KH, D]); the caller scatters k_new/v_new into the pools
    (ops/attention.write_chunk_to_cache) AFTER the call — the kernel
    attends to history pages plus the in-register current token. Rows
    whose history is shorter than the table width skip their dead pages
    via the scalar-prefetched per-row page counts (``pcounts``, derived
    per step via :func:`history_pcounts` when not supplied); the table
    width P may be anything (one compiled program per distinct P — callers
    should bucket widths, see engines/tpu/engine.py::table_width_bucket).

    Epilogue knobs: ``window`` is a TRACED scalar (windowed and global
    layers of one model share a compiled program; per-row live page
    bounds are derived here via :func:`window_page_bounds` and ride the
    SMEM scalar-prefetch path like ``pcounts``); the presence of q/k
    norm weights, qkv biases and post-norm weights in ``lp`` selects the
    matching in-kernel epilogues; ``act_fn``/``unit_offset``/``softcap``
    are static switches (one compiled variant per model family, not per
    layer)."""
    if interpret is None:
        # CPU (tests, dryruns): Mosaic doesn't lower there — emulate.
        interpret = jax.default_backend() != "tpu"
    B, d = x.shape
    NB, BS, KH, D = k_pool.shape
    HD = lp["wq"]["q8"].shape[1]
    F = lp["w_gate"]["q8"].shape[1]
    H = HD // D
    P = block_tables.shape[1]
    BQ = batch_block
    assert B % BQ == 0, (B, BQ)

    KHD = KH * D
    tiles = _tiles_for(d, HD, KHD, F, D)  # same derivation supports() gates
    assert tiles is not None, (d, HD, KHD, F, D)
    TQ, TO, TF = tiles

    qk_norm = "q_norm" in lp
    qkv_bias = "bq" in lp
    post_norms = "attn_post_norm" in lp

    kernel = functools.partial(
        _fused_layer_kernel,
        eps=eps, sm_scale=sm_scale,
        B=B, d=d, H=H, KH=KH, D=D, F=F, P=P, BS=BS,
        TQ=TQ, TO=TO, TF=TF, BQ=BQ,
        qk_norm=qk_norm, qkv_bias=qkv_bias, post_norms=post_norms,
        act_fn=act_fn, softcap=float(softcap), unit_offset=unit_offset,
    )
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)  # noqa: E731
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)  # noqa: E731
    hbm = lambda: pl.BlockSpec(memory_space=pl.ANY)  # noqa: E731

    two_d = lambda a: a.reshape(1, -1)  # noqa: E731 — Mosaic wants >=2D

    start32 = start_pos.astype(jnp.int32)
    # Per-row history page count: the scalar-prefetch operand that bounds
    # the kernel's dynamic page loop and gates every page DMA per row.
    if pcounts is None:
        pcounts = history_pcounts(start32, BS, P)
    pcounts = pcounts.astype(jnp.int32)
    # Sliding-window live range: first visible key + its page, per row
    # (zeros when the layer has no window — the full-attention case).
    if window is None:
        wlo = jnp.zeros_like(start32)
        poff = jnp.zeros_like(start32)
    else:
        wlo, poff = window_page_bounds(start32, window, BS)

    extra_vmem = []
    if qk_norm:
        extra_vmem += [two_d(lp["q_norm"]), two_d(lp["k_norm"])]
    if qkv_bias:
        extra_vmem += [two_d(lp["bq"]), two_d(lp["bk"]), two_d(lp["bv"])]
    if post_norms:
        extra_vmem += [
            two_d(lp["attn_post_norm"]), two_d(lp["mlp_post_norm"]),
        ]

    out = pl.pallas_call(
        kernel,
        in_specs=(
            [smem()] * 5
            + [vmem()] * (12 + len(extra_vmem))
            + [hbm()] * 9
        ),
        out_specs=(vmem(), vmem(), vmem()),
        out_shape=(
            jax.ShapeDtypeStruct((B, d), x.dtype),
            jax.ShapeDtypeStruct((B, KH, D), x.dtype),
            jax.ShapeDtypeStruct((B, KH, D), x.dtype),
        ),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        start32,
        pcounts,
        wlo.astype(jnp.int32),
        poff.astype(jnp.int32),
        x, cos.astype(jnp.float32), sin.astype(jnp.float32),
        two_d(lp["attn_norm"]), two_d(lp["mlp_norm"]),
        *extra_vmem,
        two_d(lp["wq"]["s"]), two_d(lp["wk"]["s"]), two_d(lp["wv"]["s"]),
        two_d(lp["wo"]["s"]),
        two_d(lp["w_gate"]["s"]), two_d(lp["w_up"]["s"]),
        two_d(lp["w_down"]["s"]),
        lp["wq"]["q8"], lp["wk"]["q8"], lp["wv"]["q8"], lp["wo"]["q8"],
        lp["w_gate"]["q8"], lp["w_up"]["q8"], lp["w_down"]["q8"],
        k_pool, v_pool,
    )
    return out


# Jitted + watched program object (DYN001): the megakernel's signature
# count tracks (pow2 table-width bucket × variant) — exactly what the
# runner budgets via set_budget, and what a per-request width leak would
# blow through (the recompile-storm signal the runtime detector pages on).
from dynamo_tpu.runtime.device_observe import watched_jit  # noqa: E402

fused_decoder_layer = watched_jit(
    "pallas.fused_decoder_layer",
    functools.partial(
        jax.jit,
        static_argnames=(
            "eps", "sm_scale", "batch_block", "interpret",
            "act_fn", "unit_offset", "softcap",
        ),
    )(_fused_decoder_layer_impl),
)
