"""The least time one decode step of a ``minicpm_sala`` configuration (block-
sparse attention chosen by an indexer over compressed keys, beside lightning
attention as recurrent state; dense FFNs) can take on a chip, and the least
time of its paged-attention decode kernel over the step's sparse layers. The
other ``roofline*.py`` files beside this one do not apply: what a row reads of
its K/V is what its indexer selected, not what it holds, and most layers read
and write a state matrix a head instead of a cache.

``cfg`` is the configuration file's JSON object. Per step, streamed once:
every layer's mixer matrices (q, k, v, o and the per-lane gate; the sparse
layers' k and v at their K/V heads) and its FFN, and the head; per sparse
layer the K and V of the pages the step's rows attend over (the pages their
indexers SELECTED, all its pages for a row under ``dense_len``), counted by
the program per dispatched burst, and the compressed keys the sparse-path
rows score (one row of ``head_dim`` a K/V head every ``stride`` tokens of
their contexts); per lightning layer every row's state, read and written
(``heads x head_dim^2`` float32). The embedding is looked up, not streamed.
FLOPs: two per parameter and row for the matrices, attention's score and value
products per row, key, layer and head, the indexer's scores, and the state's
decay-update and read (4 per state value).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import roofline


def layers_of(cfg: Dict[str, Any]):
    """["sparse" | "lightning"] of the layers held."""
    kinds = {"minicpm4": "sparse", "lightning-attn": "lightning"}
    return [kinds[m] for m in cfg["mixer_types"][: int(cfg["num_hidden_layers"])]]


def mixer_params(cfg: Dict[str, Any], kind: str) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    if kind == "sparse":
        h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        return 2 * d * h * hd + 2 * d * kv * hd + d * h * hd  # q, o; k, v; the per-lane gate
    h, hd = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return 5 * d * h * hd  # q, k, v, o and the per-lane gate


def ffn_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def page_bytes(cfg: Dict[str, Any], block_size: int) -> int:
    """K and V of one page in one sparse layer."""
    return (2 * block_size * cfg["num_key_value_heads"] * cfg["head_dim"]
            * int(cfg["serving"]["kv_bytes_per_value"]))


def index_bytes_per_page(cfg: Dict[str, Any], block_size: int) -> int:
    """The compressed keys filed with one page in one sparse layer."""
    stride = int(cfg["assumed"]["sparse_config"]["stride"])
    return (block_size // stride * cfg["num_key_value_heads"] * cfg["head_dim"]
            * int(cfg["serving"]["kv_bytes_per_value"]))


def state_bytes(cfg: Dict[str, Any]) -> int:
    """One sequence's state in one lightning layer (float32)."""
    return 4 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def attention_least_seconds(
    cfg: Dict[str, Any], selected_pages: float, block_size: int, device_kind: str,
) -> Tuple[float, str, float, float]:
    """The decode kernel over the step's sparse layers: (seconds, which
    bound, bytes, FLOPs) for ``selected_pages`` pages visited (summed over the
    step's rows) in each sparse layer."""
    peak = roofline.peaks_for(device_kind)
    n_sparse = layers_of(cfg).count("sparse")
    nbytes = n_sparse * selected_pages * page_bytes(cfg, block_size)
    flops = (4.0 * n_sparse * selected_pages * block_size
             * cfg["num_attention_heads"] * cfg["head_dim"])
    t_bytes, t_flops = nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"]
    return max(t_bytes, t_flops), ("hbm" if t_bytes >= t_flops else "flops"), nbytes, flops


def decode_step_least_seconds(
    cfg: Dict[str, Any], rows: float, selected_pages: float, scored_pages: float,
    block_size: int, device_kind: str,
) -> Tuple[float, str, Dict[str, float]]:
    """max(bytes / peak, FLOPs / peak) of one decode step of ``rows``
    sequences that visit ``selected_pages`` pages in a sparse layer and whose
    sparse-path rows hold ``scored_pages`` pages (whose compressed keys the
    indexer scores). Returns (seconds, "hbm" | "flops", the byte terms)."""
    peak = roofline.peaks_for(device_kind)
    d, w = cfg["hidden_size"], int(cfg["serving"]["weight_bytes_per_param"])
    held = layers_of(cfg)
    n_sparse, n_light = held.count("sparse"), held.count("lightning")
    params = (
        sum(mixer_params(cfg, kind) for kind in held) + len(held) * ffn_params(cfg)
        + cfg["vocab_size"] * d
    )
    _, _, kv_bytes, attn_flops = attention_least_seconds(cfg, selected_pages, block_size, device_kind)
    terms = {
        "weights": params * w,
        "kv_pages": kv_bytes,
        "compressed_keys": n_sparse * scored_pages * index_bytes_per_page(cfg, block_size),
        "state": 2.0 * n_light * rows * state_bytes(cfg),
    }
    t_bytes = sum(terms.values()) / peak["hbm_bytes_per_s"]
    stride = int(cfg["assumed"]["sparse_config"]["stride"])
    index_flops = (2.0 * n_sparse * scored_pages * (block_size // stride)
                   * cfg["num_attention_heads"] * cfg["head_dim"])
    flops = 2 * params * rows + attn_flops + index_flops + n_light * rows * state_bytes(cfg)
    t_flops = flops / peak["bf16_flops_per_s"]
    return (t_bytes, "hbm", terms) if t_bytes >= t_flops else (t_flops, "flops", terms)
