"""Pure-arithmetic decode roofline model (shared by bench.py and the
perf ledger).

One statement of the bandwidth math bench's ``run_70b_projection_leg``
and anchor derivation have always used: a fused decode step must stream
the full (active) weight set plus every sequence's KV history from HBM,
so the step-time floor is ``bytes_moved / HBM_BW`` and the throughput
roofline is ``batch / step_time``. Factored out of bench.py so the
always-on perf ledger (runtime/perf_ledger.py) can report a live
achieved-fraction-of-roofline gauge against the SAME model bench grades
rounds with — two surfaces, one formula.

Dependency-free by design (no jax import): ``cfg`` is duck-typed on the
plain-int attributes ModelConfig carries (d_model, n_layers, head_dim_,
n_heads, n_kv_heads, d_ff, vocab_size, tie_word_embeddings, is_moe,
moe_d_ff_, n_experts, n_experts_per_tok), so the module loads on boxes
where the serving deps don't.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional


class DevicePeaks(NamedTuple):
    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    int8_ops_per_s: float


# Published peaks of one chip, keyed by the ``device_kind`` JAX reports.
# A device that is not here has no roofline: callers get None, never
# another chip's numbers.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": DevicePeaks(819e9, 197e12, 393e12),
}


def device_peaks(device_kind: Optional[str]) -> Optional[DevicePeaks]:
    return DEVICE_PEAKS.get(device_kind or "")


def param_count(cfg) -> int:
    """Matmul-weight parameter count from the config (analytic)."""
    d, L, hd = cfg.d_model, cfg.n_layers, cfg.head_dim_
    H, KH, ff = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    per_layer = d * H * hd + 2 * d * KH * hd + H * hd * d  # wq wk wv wo
    if cfg.is_moe:
        eff = cfg.moe_d_ff_
        per_layer += cfg.n_experts * 3 * d * eff + d * cfg.n_experts
    else:
        per_layer += 3 * d * ff
    total = L * per_layer + cfg.vocab_size * d
    if not cfg.tie_word_embeddings:
        total += d * cfg.vocab_size
    return total


def active_param_count(cfg) -> int:
    """Params touched per token (MoE reads only top-k experts)."""
    if not cfg.is_moe:
        return param_count(cfg)
    d, L, hd = cfg.d_model, cfg.n_layers, cfg.head_dim_
    H, KH, eff = cfg.n_heads, cfg.n_kv_heads, cfg.moe_d_ff_
    per_layer = (
        d * H * hd + 2 * d * KH * hd + H * hd * d
        + cfg.n_experts_per_tok * 3 * d * eff + d * cfg.n_experts
    )
    total = L * per_layer + cfg.vocab_size * d
    if not cfg.tie_word_embeddings:
        total += d * cfg.vocab_size
    return total


def decode_step_bytes(
    cfg, batch: int, avg_ctx: float, quant: Optional[str]
) -> float:
    """HBM bytes one fused decode step must move: the full (active)
    weight stream plus every sequence's KV history."""
    wbytes = active_param_count(cfg) * (1 if quant == "int8" else 2)
    kv_per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim_ * 2
    return wbytes + batch * avg_ctx * kv_per_tok


def decode_roofline_toks_per_sec(
    cfg,
    batch: int,
    avg_ctx: float,
    quant: Optional[str],
    hbm_bw: float,
) -> float:
    """Bandwidth-roofline decode throughput (tokens/s, whole chip) for
    this model/batch/context: ``batch / (step_bytes / hbm_bw)``."""
    step_bytes = decode_step_bytes(cfg, batch, avg_ctx, quant)
    if step_bytes <= 0:
        return 0.0
    return batch * hbm_bw / step_bytes


def make_roofline_fn(
    cfg, quant: Optional[str], device_kind: Optional[str]
) -> Optional[Callable[[int, float], float]]:
    """Close over a config and the device's published bandwidth:
    ``(batch, avg_ctx) -> roofline tok/s``, or None for a device without
    published peaks (the perf ledger then reports no roofline share). The
    shape the perf ledger stores at configure time — the ledger itself
    stays model-agnostic."""
    peaks = device_peaks(device_kind)
    if peaks is None:
        return None

    def fn(batch: int, avg_ctx: float) -> float:
        return decode_roofline_toks_per_sec(
            cfg, batch, avg_ctx, quant, hbm_bw=peaks.hbm_bytes_per_s
        )
    return fn
