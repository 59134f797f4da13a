"""The least time one decode step of a latent-attention (``pangu_ultra_moe``)
configuration can take on a chip, and the least time of its absorbed
attention kernel alone. ``roofline.py`` (dense decoder) and
``roofline_hybrid.py`` (``nemotron_h``) beside this file do not apply: the
cache here is ONE latent row a token a layer, and attention runs in the
absorbed form over it.

``cfg`` is the configuration file's JSON object. Per step, streamed once: the
latent-attention matrices of every layer, the leading dense layers' FFN, the
routers (float32, at the width they keep), the shared experts and the head's
slice; per expert layer the experts that got a token (from the program's
counters) times one expert's bytes; every row's latent history in every layer
at its LOGICAL width (``kv_lora_rank + qk_rope_head_dim`` values: the pool's
padding to whole lane tiles is the program's, so it can only lower a share).
The embedding is looked up, not streamed. FLOPs: two per parameter and row
for the matrices, two per parameter and routed (token, held expert) pair, and
the absorbed attention: per row, cached token, layer and head, the score over
the whole row and the value sum over its latent part.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import roofline


def latent_width(cfg: Dict[str, Any]) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: Dict[str, Any]) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (
        d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk + d * latent_width(cfg)
        + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + h * cfg["v_head_dim"] * d
    )


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_counts(cfg: Dict[str, Any]) -> Tuple[int, int]:
    dense = min(int(cfg["first_k_dense_replace"]), int(cfg["num_hidden_layers"]))
    return dense, int(cfg["num_hidden_layers"]) - dense


def attention_least_seconds(cfg: Dict[str, Any], rows: float, mean_ctx: float,
                            device_kind: str) -> Tuple[float, str, float, float]:
    """The absorbed kernel over ONE layer for one step: (seconds, which
    bound, bytes, FLOPs)."""
    peak = roofline.peaks_for(device_kind)
    keys = rows * mean_ctx
    nbytes = keys * latent_width(cfg) * int(cfg["serving"]["kv_bytes_per_value"])
    flops = 2.0 * keys * cfg["num_attention_heads"] * (latent_width(cfg) + cfg["kv_lora_rank"])
    t_bytes, t_flops = nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"]
    return max(t_bytes, t_flops), ("hbm" if t_bytes >= t_flops else "flops"), nbytes, flops


def decode_step_least_seconds(
    cfg: Dict[str, Any], rows: float, mean_ctx: float, experts_hit: float,
    expert_tokens: float, device_kind: str,
) -> Tuple[float, str, Dict[str, float]]:
    """max(bytes / peak, FLOPs / peak) of one decode step of ``rows``
    sequences at ``mean_ctx`` tokens each, with ``experts_hit`` held experts
    touched and ``expert_tokens`` (token, held expert) pairs per expert
    layer. Returns (seconds, "hbm" | "flops", the byte terms)."""
    peak = roofline.peaks_for(device_kind)
    n_dense, n_exp = layer_counts(cfg)
    layers = n_dense + n_exp
    d, w = cfg["hidden_size"], int(cfg["serving"]["weight_bytes_per_param"])
    shared = 3 * d * cfg["moe_intermediate_size"] * int(cfg.get("n_shared_experts", 0))
    dense_params = (
        layers * attention_params(cfg) + n_dense * 3 * d * cfg["intermediate_size"]
        + n_exp * shared + cfg["vocab_size"] * d
    )
    router = 4 * d * int(cfg.get("experts_routed_over", cfg["n_routed_experts"]))
    _, _, latent_bytes, attn_flops = attention_least_seconds(cfg, rows, mean_ctx, device_kind)
    terms = {
        "dense_weights": dense_params * w + n_exp * router,
        "experts_hit": n_exp * experts_hit * expert_params(cfg) * w,
        "latent_history": layers * latent_bytes,
    }
    t_bytes = sum(terms.values()) / peak["hbm_bytes_per_s"]
    flops = (
        2 * dense_params * rows + 2 * n_exp * expert_tokens * expert_params(cfg)
        + layers * attn_flops
    )
    t_flops = flops / peak["bf16_flops_per_s"]
    return (t_bytes, "hbm", terms) if t_bytes >= t_flops else (t_flops, "flops", terms)
