"""The least time one decode step of a ``laguna`` configuration (sliding-window
and full attention layers mixed, small sigmoid-routed experts) can take on a
chip, and the least time of its paged-attention decode kernel over the step's
attention layers. ``roofline.py`` (dense decoder), ``roofline_hybrid.py``
(``nemotron_h``) and ``roofline_mla.py`` (``pangu_ultra_moe``) beside this file
do not apply: the head counts differ by layer, and the K/V a step reads is two
page groups' (the full layers' grows with the context, the sliding layers' is
the window's whatever the context).

``cfg`` is the configuration file's JSON object. Per step, streamed once: every
layer's attention matrices (q, k, v, o at that layer's head count, and its
per-head output gate), the dense layers' FFN, the routers (float32), the shared
experts and the head; per expert layer the experts that got a token (from the
program's counters) times one expert's bytes; the K and V pages the step's rows
attend over, counted by the program per dispatched burst and per page group
(``full pages x block x kv_heads x head_dim x 2 (K and V) x bytes`` for each
full layer, the same over the window group's live pages for each sliding
layer). The embedding is looked up, not streamed. FLOPs: two per parameter and
row for the matrices, two per parameter and routed (token, expert) pair, and
attention's score and value products per row, key, layer and head.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import roofline


def layers_of(cfg: Dict[str, Any]):
    """[(query heads, is sliding, is dense FFN)] of the layers held."""
    n = int(cfg["num_hidden_layers"])
    return [
        (int(cfg["num_attention_heads_per_layer"][i]),
         cfg["layer_types"][i] == "sliding_attention",
         cfg["mlp_layer_types"][i] == "dense")
        for i in range(n)
    ]


def attention_params(cfg: Dict[str, Any], heads: int) -> int:
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    gate = d * heads if cfg.get("gating") else 0
    return 2 * d * heads * hd + 2 * d * kv * hd + gate


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def page_bytes(cfg: Dict[str, Any], block_size: int) -> int:
    """K and V of one page in one layer."""
    return (2 * block_size * cfg["num_key_value_heads"] * cfg["head_dim"]
            * int(cfg["serving"]["kv_bytes_per_value"]))


def attention_least_seconds(
    cfg: Dict[str, Any], full_pages: float, window_pages: float, block_size: int,
    device_kind: str,
) -> Tuple[float, str, float, float]:
    """The decode kernel over ALL the step's attention layers: (seconds,
    which bound, bytes, FLOPs) for ``full_pages`` / ``window_pages`` live
    pages (summed over the step's rows) in each full / sliding layer."""
    peak = roofline.peaks_for(device_kind)
    nbytes = flops = 0.0
    for heads, sliding, _ in layers_of(cfg):
        pages = window_pages if sliding else full_pages
        nbytes += pages * page_bytes(cfg, block_size)
        flops += 4.0 * pages * block_size * heads * cfg["head_dim"]
    t_bytes, t_flops = nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"]
    return max(t_bytes, t_flops), ("hbm" if t_bytes >= t_flops else "flops"), nbytes, flops


def decode_step_least_seconds(
    cfg: Dict[str, Any], rows: float, full_pages: float, window_pages: float,
    block_size: int, experts_hit: float, expert_tokens: float, device_kind: str,
) -> Tuple[float, str, Dict[str, float]]:
    """max(bytes / peak, FLOPs / peak) of one decode step of ``rows``
    sequences whose live pages sum to ``full_pages`` in a full layer and
    ``window_pages`` in a sliding one, with ``experts_hit`` experts touched and
    ``expert_tokens`` (token, expert) pairs per expert layer. Returns
    (seconds, "hbm" | "flops", the byte terms)."""
    peak = roofline.peaks_for(device_kind)
    d, w = cfg["hidden_size"], int(cfg["serving"]["weight_bytes_per_param"])
    held = layers_of(cfg)
    n_dense = sum(1 for _, _, dense in held if dense)
    n_exp = len(held) - n_dense
    dense_params = (
        sum(attention_params(cfg, heads) for heads, _, _ in held)
        + n_dense * 3 * d * cfg["intermediate_size"]
        + n_exp * 3 * d * int(cfg.get("shared_expert_intermediate_size", 0))
        + cfg["vocab_size"] * d
    )
    router = 4 * d * int(cfg["num_experts"])
    _, _, kv_bytes, attn_flops = attention_least_seconds(
        cfg, full_pages, window_pages, block_size, device_kind)
    terms = {
        "dense_weights": dense_params * w + n_exp * router,
        "experts_hit": n_exp * experts_hit * expert_params(cfg) * w,
        "kv_pages": kv_bytes,
    }
    t_bytes = sum(terms.values()) / peak["hbm_bytes_per_s"]
    flops = 2 * dense_params * rows + 2 * n_exp * expert_tokens * expert_params(cfg) + attn_flops
    t_flops = flops / peak["bf16_flops_per_s"]
    return (t_bytes, "hbm", terms) if t_bytes >= t_flops else (t_flops, "flops", terms)
