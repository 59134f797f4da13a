"""The least time one decode step of a ``qwen3_next`` configuration (Gated
DeltaNet layers as recurrent state beside gated softmax attention, routed
experts of which the chip holds a share in every layer) can take on a chip,
and the least time of a live-row state-update kernel over the step's
recurrent layers. The other ``roofline*.py`` files beside this one do not
apply: every layer here carries experts, three layers of four read and write a
state matrix a head instead of a cache, and the one attention layer's head is
256 lanes.

``cfg`` is the configuration file's JSON object. Per step, streamed once: the
Gated DeltaNet and attention matrices, the routers (float32, at the width the
router keeps), the shared experts and the head's slice; per layer the experts
that got a token (from the program's counters, not from a law of large
numbers) times one expert's bytes; every row's K/V history in the attention
layers; every row's recurrent state in the Gated DeltaNet layers (the
float32 matrix and the conv tail), read and written. The embedding is looked
up, not streamed. FLOPs: two per parameter and row for the dense matrices,
two per parameter and routed (token, held expert) pair, the attention scores
and values, and the delta rule (6 per state value: decay, k S, the outer
product's multiply and add, q S's multiply and add).

``state_update_least_seconds`` is the kernel's own bound and takes the bytes
of one row's matrix in one layer, so that it serves any live-row state kernel
(``gdn_step_live`` here; ``ssd_step_live`` has no such row yet: a later
``benchmark`` PR can point a metric of that kernel at this function).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import roofline


def layers_of(cfg: Dict[str, Any]):
    """["gdn" | "attention"] of the layers held: layer i is full attention
    when (i + 1) % full_attention_interval == 0."""
    every = int(cfg["full_attention_interval"])
    return ["attention" if (i + 1) % every == 0 else "gdn"
            for i in range(int(cfg["num_hidden_layers"]))]


def gdn_params(cfg: Dict[str, Any]) -> int:
    """One Gated DeltaNet mixer: in_proj_qkvz, in_proj_ba, the conv's taps,
    A_log and dt_bias, the output norm, out_proj."""
    d = cfg["hidden_size"]
    kw = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    hv = cfg["linear_num_value_heads"]
    vw = hv * cfg["linear_value_head_dim"]
    conv = 2 * kw + vw
    return (d * (conv + vw) + d * 2 * hv + conv * cfg["linear_conv_kernel_dim"] + 2 * hv
            + cfg["linear_value_head_dim"] + vw * d)


def attention_params(cfg: Dict[str, Any]) -> int:
    """q and its per-lane gate (one doubled matrix as published), k, v, o,
    the q/k norms."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd + h * hd * d + 2 * hd


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"] + cfg["hidden_size"]


def router_bytes(cfg: Dict[str, Any]) -> int:
    """float32, at the width the router keeps (all experts of the model)."""
    return 4 * cfg["hidden_size"] * int(cfg["published"]["num_experts"])


def state_matrix_bytes(cfg: Dict[str, Any]) -> int:
    """One sequence's float32 matrices in one Gated DeltaNet layer."""
    return (4 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def conv_tail_bytes(cfg: Dict[str, Any]) -> int:
    kw = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vw = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return ((cfg["linear_conv_kernel_dim"] - 1) * (2 * kw + vw)
            * int(cfg["serving"]["weight_bytes_per_param"]))


def kv_bytes_per_token_layer(cfg: Dict[str, Any]) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * int(cfg["serving"]["kv_bytes_per_value"])


def state_update_least_seconds(
    matrix_bytes: float, rows: float, layers: int, device_kind: str,
) -> Tuple[float, float]:
    """A live-row state kernel over a step's ``layers`` recurrent layers:
    (seconds, bytes): every live row's matrix read and written, at the HBM
    peak (its FLOPs, a handful a value, are a hundredth of that time)."""
    nbytes = 2.0 * layers * rows * matrix_bytes
    return nbytes / roofline.peaks_for(device_kind)["hbm_bytes_per_s"], nbytes


def decode_step_least_seconds(
    cfg: Dict[str, Any], rows: float, mean_ctx: float, experts_hit: float,
    expert_tokens: float, device_kind: str,
) -> Tuple[float, str, Dict[str, float]]:
    """max(bytes / peak, FLOPs / peak) of one decode step of ``rows``
    sequences at ``mean_ctx`` tokens each, with ``experts_hit`` held experts
    touched and ``expert_tokens`` (token, held expert) pairs per expert
    layer. Returns (seconds, "hbm" | "flops", the byte terms)."""
    peak = roofline.peaks_for(device_kind)
    held = layers_of(cfg)
    n_g, n_a, n_e = held.count("gdn"), held.count("attention"), len(held)
    w = int(cfg["serving"]["weight_bytes_per_param"])
    d = cfg["hidden_size"]
    dense_params = (
        n_g * gdn_params(cfg) + n_a * attention_params(cfg)
        + n_e * shared_expert_params(cfg) + cfg["vocab_size"] * d
    )
    terms = {
        "dense_weights": dense_params * w + n_e * router_bytes(cfg),
        "experts_hit": n_e * experts_hit * expert_params(cfg) * w,
        "kv_history": n_a * rows * mean_ctx * kv_bytes_per_token_layer(cfg),
        "state": 2.0 * n_g * rows * (state_matrix_bytes(cfg) + conv_tail_bytes(cfg)),
    }
    t_bytes = sum(terms.values()) / peak["hbm_bytes_per_s"]
    flops = (
        2.0 * dense_params * rows + 2.0 * n_e * rows * d * int(cfg["published"]["num_experts"])
        + 2.0 * n_e * expert_tokens * expert_params(cfg)
        + 4.0 * n_a * rows * mean_ctx * cfg["num_attention_heads"] * cfg["head_dim"]
        + 6.0 * n_g * rows * state_matrix_bytes(cfg) / 4
    )
    t_flops = flops / peak["bf16_flops_per_s"]
    return (t_bytes, "hbm", terms) if t_bytes >= t_flops else (t_flops, "flops", terms)
