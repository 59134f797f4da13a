"""The least time one decode step can take on a chip, from the
configuration alone. The arithmetic is ``dynamo_tpu/runtime/roofline.py``'s
(weights streamed once per step plus every row's KV history), copied here so
that no PR can change the yardstick, and divided by DEVICE time from the
trace, never by a host-clock step time.

``cfg`` is the configuration file's JSON object (Hugging Face key names).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

HERE = os.path.dirname(os.path.realpath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip. An unknown device is an error, not a
    default: it would be graded against another chip's numbers."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} in peaks.json")
    return table[device_kind]


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters in the matrices a decode step multiplies by: q, k, v, o,
    the gated feed-forward (three matrices), and the output head. The
    embedding table is looked up, not streamed; with tied embeddings the same
    table is the head and is streamed once."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head_dim = cfg.get("head_dim") or d // heads
    per_layer = (
        d * heads * head_dim + 2 * d * kv_heads * head_dim + heads * head_dim * d
        + 3 * d * cfg["intermediate_size"]
    )
    return layers * per_layer + cfg["vocab_size"] * d


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or d // heads
    kv_width = int(cfg["serving"]["kv_bytes_per_value"])
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * head_dim * kv_width


def decode_step_least_seconds(cfg: Dict[str, Any], rows: float, mean_ctx: float,
                              device_kind: str) -> Tuple[float, str]:
    """max(FLOPs / peak, bytes / peak) for one decode step of ``rows``
    sequences at ``mean_ctx`` tokens of context each. Returns the seconds
    and which of the two bounds it ("hbm" or "flops")."""
    peak = peaks_for(device_kind)
    weight_bytes = matmul_params(cfg) * int(cfg["serving"]["weight_bytes_per_param"])
    kv_bytes = rows * mean_ctx * kv_bytes_per_token(cfg)
    t_bytes = (weight_bytes + kv_bytes) / peak["hbm_bytes_per_s"]
    # Weight-only int8: the codes are widened and multiplied in bf16, so the
    # compute peak that applies is the bf16 one.
    attn_flops = 2 * 2 * rows * mean_ctx * cfg["num_hidden_layers"] * cfg["hidden_size"]
    t_flops = (2 * matmul_params(cfg) * rows + attn_flops) / peak["bf16_flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
