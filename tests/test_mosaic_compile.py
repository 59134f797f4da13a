"""The decode paged-attention kernel compiled by Mosaic for a described
TPU v5e, at the served shapes: the chip's compiler is installed here and
compiles for a chip that is not attached, so a lowering the interpreter
accepts and Mosaic refuses (a slice not aligned to the tiling, too much
VMEM) fails here, at no chip time. Nothing runs: this says nothing about
results or speed (tests/test_pallas_kernel.py, ops/pallas/chip_check.py).
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    # Described inside a fixture, never at import: only one process may
    # load the TPU's library, and every xdist worker imports this file.
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep these out of it.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


DECODE_SHAPES = {
    # name: (B, C, H, KH, D, P, quantized, softcap, block size)
    "qwen2.5-0.5b-bf16": (64, 1, 14, 2, 64, 64, False, 0.0, 16),
    "qwen2.5-0.5b-int8": (64, 1, 14, 2, 64, 128, True, 0.0, 16),
    "qwen3-8b-bf16": (32, 1, 32, 8, 128, 128, False, 0.0, 16),
    "qwen3-8b-int8": (32, 1, 32, 8, 128, 128, True, 0.0, 16),
    "qwen3-8b-verify-c4": (16, 4, 32, 8, 128, 16, False, 0.0, 16),
    "qwen2.5-0.5b-prefix-hit-tail": (1, 4, 14, 2, 64, 8, False, 0.0, 16),
    "gemma-2-2b-softcap": (16, 1, 8, 4, 256, 32, False, 50.0, 16),
    # --block-size above the served 16: a page weighs more, a step holds
    # fewer (16 pages of 256 KiB, K and V, two buffers each, are the
    # whole 16 MiB of VMEM: RESOURCE_EXHAUSTED before the budget).
    "qwen3-8b-bf16-bs128": (32, 1, 32, 8, 128, 16, False, 0.0, 128),
    "qwen3-8b-int8-bs128": (32, 1, 32, 8, 128, 16, True, 0.0, 128),
    "qwen3-8b-verify-c8-bs128": (16, 8, 32, 8, 128, 16, False, 0.0, 128),
    "gemma-2-2b-softcap-bs64": (16, 1, 8, 4, 256, 32, False, 50.0, 64),
    "gemma-2-9b-d256-bs128": (16, 1, 16, 8, 256, 16, False, 50.0, 128),
}


@pytest.mark.parametrize("name", sorted(DECODE_SHAPES))
def test_decode_kernel_compiles_for_v5e(one_chip, name):
    from dynamo_tpu.ops.pallas.paged_attention import (
        _paged_attention_decode_kernel_impl,
    )

    B, C, H, KH, D, P, quantized, cap, bs = DECODE_SHAPES[name]
    NB = 65536 // bs

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if quantized:
        pool = {"q8": sds((NB, bs, KH, D), jnp.int8),
                "s": sds((NB, KH, bs), jnp.float32)}
    else:
        pool = sds((NB, bs, KH, D), jnp.bfloat16)
    fn = jax.jit(functools.partial(
        _paged_attention_decode_kernel_impl, logit_cap=cap
    ))
    compiled = fn.lower(
        sds((B, C, H, D), jnp.bfloat16), pool, pool, sds((B, P), jnp.int32),
        sds((B,), jnp.int32), sds((), jnp.int32), sds((B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
