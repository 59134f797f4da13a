"""Int8 KV-cache quantization (per-token-per-head dynamic scales).

The decode step is HBM-bound on two streams: weights and KV history. Int8
weights halve the first (ops/quant.py); this halves the second, and halves
the bytes of a page in HBM, so a fixed pool holds twice the tokens.

Layout: a quantized pool is a dict
    {"q8": int8 [num_blocks, block_size, KH, D],
     "s":  float32 [num_blocks, KH, block_size]}
The scale array keeps block_size on the LANE axis so a kernel page-ref
slice ``s[0, h]`` is one dense lane vector — the dequant then rides the
existing score/prob multiplies (scores ×= s_k[t], probs ×= s_v[t]) instead
of touching the [bs, D] page itself.

Scales are per (token, head): absmax over head_dim / 127, computed at
write time (write_chunk_to_cache). This is the standard int8-KV recipe
(reference serves FP8-KV through its engines — e.g. vLLM's
kv_cache_dtype=fp8 path the recipes enable); per-token scaling keeps the
rounding error ~0.4% of each token's own magnitude, which parity tests
bound end-to-end.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple, Union

import jax.numpy as jnp

KVPool = Union[jnp.ndarray, Dict[str, jnp.ndarray]]


def is_quantized_pool(pool: Any) -> bool:
    return isinstance(pool, dict) and "q8" in pool


def quantize_kv_chunk(
    chunk: jnp.ndarray,  # [B, C, KH, D] float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """→ (q8 [B, C, KH, D] int8, scales [B, C, KH] float32)."""
    xf = chunk.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)  # [B, C, KH]
    s = jnp.maximum(amax, 1e-8) / 127.0
    q8 = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q8, s


def dequantize_pages(
    q8: jnp.ndarray,  # [..., bs, KH, D] int8 (gathered pages)
    s: jnp.ndarray,  # [..., KH, bs] float32 (gathered scales)
    dtype: Any = jnp.float32,
) -> jnp.ndarray:
    """Dense dequant for the XLA-oracle / export paths."""
    s_t = jnp.swapaxes(s, -1, -2)[..., None]  # [..., bs, KH, 1]
    return (q8.astype(jnp.float32) * s_t).astype(dtype)


def dequantize_pool(pool: KVPool, dtype: Any = jnp.bfloat16) -> jnp.ndarray:
    """Whole-pool dequant → [num_blocks, bs, KH, D] (checkpoint/export)."""
    if not is_quantized_pool(pool):
        return pool.astype(dtype) if pool.dtype != dtype else pool
    return dequantize_pages(pool["q8"], pool["s"], dtype)
