"""The decode paged-attention kernel and the hit-list expert kernel compiled
by Mosaic for a described TPU v5e, at the served shapes: the chip's compiler is installed here and
compiles for a chip that is not attached, so a lowering the interpreter
accepts and Mosaic refuses (a slice not aligned to the tiling, too much
VMEM) fails here, at no chip time. Nothing runs: this says nothing about
results or speed (tests/test_pallas_kernel.py, ops/pallas/chip_check.py).
"""

import functools

import numpy as np

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
    # Laguna-XS.2 over pages of 128: a sliding layer (64 query heads, 8 a
    # K/V head) over its window's view of five slots, a full layer (48, 6 a
    # K/V head) over a 32 k table; the window is the traced scalar.
    "laguna-sliding-h64-view5": (64, 1, 64, 8, 128, 5, False, 0.0, 128),
    "laguna-full-h48-32k": (64, 1, 48, 8, 128, 256, False, 0.0, 128),
}

# Shapes whose bf16 page is held at a 128-lane tile for a head of 64
# (ops/attention.pool_head_dim: the pool the worker serves since PR 33):
# the kernel's page operand is [1, bs, KH, 128] and it loads lanes [:64].
WIDE_PAGE = {"qwen2.5-0.5b-bf16-page128": "qwen2.5-0.5b-bf16",
             "qwen2.5-0.5b-prefix-hit-tail-page128": "qwen2.5-0.5b-prefix-hit-tail"}
for _wide, _logical in WIDE_PAGE.items():
    DECODE_SHAPES[_wide] = DECODE_SHAPES[_logical]


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
        pool = sds((NB, bs, KH, 128 if name in WIDE_PAGE else D), jnp.bfloat16)
    fn = jax.jit(functools.partial(
        _paged_attention_decode_kernel_impl, logit_cap=cap
    ))
    compiled = fn.lower(
        sds((B, C, H, D), jnp.bfloat16), pool, pool, sds((B, P), jnp.int32),
        sds((B,), jnp.int32), sds((), jnp.int32), sds((B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page", [64, 128])
def test_chunk_kernel_compiles_for_v5e_at_head_64(one_chip, page):
    """The generic (B, pages) kernel at the shape a prefix-hit tail of the
    benchmark cell now takes (one row, a 128-token chunk: chunks pad up to
    admission.PREFILL_CHUNK_FLOOR), over the logical and the wide page."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        _paged_attention_kernel_impl,
    )

    B, C, H, KH, D, P, bs = 1, 128, 14, 2, 64, 32, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((4096, bs, KH, page), jnp.bfloat16)
    compiled = jax.jit(_paged_attention_kernel_impl).lower(
        sds((B, C, H, D), jnp.bfloat16), pool, pool, sds((B, P), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("heads,pages", [(64, 7), (48, 256)])
def test_chunk_kernel_compiles_for_v5e_at_laguna_heads(one_chip, heads, pages):
    """The generic (B, pages) kernel for a turn's 256-token chunk at
    Laguna-XS.2's two head counts over pages of 128: a sliding layer over its
    window's view (7 slots), a full layer over a 32 k table."""
    from dynamo_tpu.ops.pallas.paged_attention import _paged_attention_kernel_impl

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((768, 128, 8, 128), jnp.bfloat16)
    compiled = jax.jit(_paged_attention_kernel_impl).lower(
        sds((1, 256, heads, 128), jnp.bfloat16), pool, pool, sds((1, pages), jnp.int32),
        sds((1,), jnp.int32), sds((1,), jnp.int32), sds((), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


EXPERT_WIDTHS = {
    # name: (d, f, held, gated). Hybrid: f is 14.5 lane tiles, so XLA hands
    # ``we_up`` over as it is resident, d minor (the kernel's transpose is
    # a bitcast) and Mosaic takes [464, 2688] tiles of both matrices.
    # Latent: f fills the lanes, three matrices f minor as written, [640,
    # 2048] row tiles of up and gate, [128, 7680] tiles of down.
    "hybrid": (2688, 1856, 64, False),
    "latent": (7680, 2048, 16, True),
    # Laguna: 256 small experts held whole, f fills the lanes: whole [2048,
    # 512] matrices of up and gate in one step, [512, 2048] of down in the
    # next; the combine matrix is [T, 256].
    "laguna": (2048, 512, 256, True),
}


@pytest.mark.parametrize("widths,tokens", [
    ("hybrid", 64), ("hybrid", 128), ("hybrid", 256),
    ("latent", 32), ("latent", 128), ("latent", 256),
    ("laguna", 64), ("laguna", 256),
])
def test_expert_kernel_compiles_for_v5e_at_the_served_widths(one_chip, widths, tokens):
    """ops/pallas/expert_ffn.py at the two served configurations' widths,
    bf16, for a decode burst's slots and the small prefill shapes: the
    program holds no copy of a matrix stack and no temporary the size of
    one."""
    from dynamo_tpu.ops.pallas.chip_check import whole_pool_copies
    from dynamo_tpu.ops.pallas.expert_ffn import _expert_ffn_impl

    d, f, held, gated = EXPERT_WIDTHS[widths]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up, down = sds((held, d, f), jnp.bfloat16), sds((held, f, d), jnp.bfloat16)
    compiled = jax.jit(_expert_ffn_impl).lower(
        sds((tokens, d), jnp.bfloat16), sds((tokens, held), jnp.float32), up, down,
        sds((held + 1,), jnp.int32), sds((1,), jnp.int32), *([up] if gated else []),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert whole_pool_copies(text, up) == whole_pool_copies(text, down) == 0
    # (the f-minor form hands the tokens over as tiles of the model width)
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20) + tokens * d * 2


# sha256 (first 16 hex digits) of the d-minor kernel's jaxpr at the hybrid
# cell's widths, by tokens a step: recorded at PR 42's tree (the kernel as PR
# 37 measured it). The optimised HLO of that cell's served programs differed
# between that tree and PR 43's only in the debug locations inside the
# serialized kernel body; this pins the body itself (grid, index maps, block
# shapes, compiler parameters, every operation in order). A deliberate change
# to the d-minor form records new digests AND measures the hybrid cell.
HYBRID_KERNEL_JAXPR = {64: "de2a61bcb891a99f", 128: "2055237fd83f27dd",
                       256: "7c33da4c7d189412"}


@pytest.mark.parametrize("tokens", sorted(HYBRID_KERNEL_JAXPR))
def test_hybrid_expert_kernel_is_the_program_it_was(tokens):
    import hashlib
    import re

    from dynamo_tpu.ops.pallas.expert_ffn import _expert_ffn_impl

    d, f, held = 2688, 1856, 64
    sds = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(_expert_ffn_impl)(
        sds((tokens, d), jnp.bfloat16), sds((tokens, held), jnp.float32),
        sds((held, d, f), jnp.bfloat16), sds((held, f, d), jnp.bfloat16),
        sds((held + 1,), jnp.int32), sds((1,), jnp.int32),
    ))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert "dynamo_tpu" not in text  # no path of a checkout in what is hashed
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == HYBRID_KERNEL_JAXPR[tokens]


def _grouped_kernel_shapes(widths, tokens, top_k, router_width, sds):
    """The grouped kernel's operands for a prefill step of ``tokens`` tokens
    at a served configuration's widths, as ops/moe.py hands them over."""
    from dynamo_tpu.ops.pallas.expert_ffn import grouped_row_tile, grouped_tiles

    d, f, held, gated = EXPERT_WIDTHS[widths]
    tm = grouped_row_tile(tokens * top_k, router_width)
    n_tiles = grouped_tiles(tokens * top_k, held, tm)
    up, down = sds((held, d, f), jnp.bfloat16), sds((held, f, d), jnp.bfloat16)
    return tm, (sds((n_tiles * tm, d), jnp.bfloat16), up, down,
                sds((n_tiles + 1,), jnp.int32), sds((1,), jnp.int32),
                *([up] if gated else []))


@pytest.mark.parametrize("widths,tokens,top_k,router_width", [
    ("hybrid", 512, 6, 128), ("hybrid", 2048, 6, 128), ("hybrid", 8192, 6, 128),
    ("laguna", 512, 8, 256), ("laguna", 2048, 8, 256),
    # a turn's chunk (PR 51): 128 and 256 tokens at the window cell's router
    # and at the delta-rule cell's (the same tile, top-10 of 512)
    ("laguna", 128, 8, 256), ("laguna", 256, 8, 256),
    ("laguna", 128, 10, 512), ("laguna", 256, 10, 512),
])
def test_grouped_expert_kernel_compiles_for_v5e_at_the_served_widths(
    one_chip, widths, tokens, top_k, router_width
):
    """ops/pallas/expert_ffn.expert_ffn_grouped at the hybrid cell's widths
    (d minor, relu2: an expert's two matrices whole in VMEM, 2 x 19.96 MB)
    and the window cell's (f minor, gated silu), bf16, at the row tile the
    step's shape gives: Mosaic takes it, and the program holds no copy of a
    matrix stack."""
    import functools

    from dynamo_tpu.ops.pallas.chip_check import whole_pool_copies
    from dynamo_tpu.ops.pallas.expert_ffn import _expert_ffn_grouped_impl

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tm, operands = _grouped_kernel_shapes(widths, tokens, top_k, router_width, sds)
    compiled = jax.jit(
        functools.partial(_expert_ffn_grouped_impl, tm=tm)).lower(*operands).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "expert_ffn_grouped" in text
    assert whole_pool_copies(text, operands[1]) == whole_pool_copies(text, operands[2]) == 0
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


# sha256 (first 16 hex digits) of the grouped kernel's jaxpr at the hybrid
# cell's widths, by tokens a step: the kernel as PR 45 measured it (grid,
# index maps, block shapes, compiler parameters, every operation in order).
# A deliberate change records new digests AND measures the hybrid cell.
HYBRID_GROUPED_KERNEL_JAXPR = {512: "1bc39c2a809b33ff", 2048: "3cc8b586840dafd9",
                               8192: "34d6624e85450986"}


@pytest.mark.parametrize("tokens", sorted(HYBRID_GROUPED_KERNEL_JAXPR))
def test_hybrid_grouped_kernel_is_the_program_it_was(tokens):
    import functools
    import hashlib
    import re

    from dynamo_tpu.ops.pallas.expert_ffn import _expert_ffn_grouped_impl

    tm, operands = _grouped_kernel_shapes(
        "hybrid", tokens, 6, 128, jax.ShapeDtypeStruct)
    text = str(jax.make_jaxpr(
        functools.partial(_expert_ffn_grouped_impl, tm=tm))(*operands))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert "dynamo_tpu" not in text  # no path of a checkout in what is hashed
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == HYBRID_GROUPED_KERNEL_JAXPR[tokens])


@functools.cache
def _dense_program(one_chip, program, model):
    """(compiled, a layer's K pool, resident bytes) of one served program of
    a two-layer qwen2.5-0.5b, or of a two-layer qwen3-8b with int8 weights
    under the worker's default slots (the quick-start deployment), as the
    runner builds it."""
    import dataclasses
    import types

    from dynamo_tpu.engines.tpu.engine import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import qwen2_500m_config, qwen3_8b_config
    from dynamo_tpu.models.quantize import quantize_params

    int8 = model == "qwen3-8b-int8"
    full = qwen3_8b_config() if int8 else qwen2_500m_config()
    cfg = dataclasses.replace(full, n_layers=2, vocab_size=8192)
    NB, S = 2048, (16 if int8 else 64)
    args = JaxEngineArgs(
        config=cfg, num_kv_blocks=NB, max_num_seqs=S, max_model_len=2048,
        prefill_chunk=1024, use_kernel=True,
        quantization="int8" if int8 else None,
    )
    runner = types.SimpleNamespace(  # what the program builders read
        config=cfg, args=args, use_kernel=True,
        multihost=False, _decode_sig_budget=None,
        _constrain_out=lambda *a: a if len(a) > 1 else a[0],
    )

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def drawn():
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        return quantize_params(params)[0] if int8 else params

    params = dict(jax.eval_shape(drawn))
    params["layers"] = jax.eval_shape(
        lambda p: [jax.tree.map(lambda a: a[l], p) for l in range(cfg.n_layers)],
        params["layers"],
    )
    params = jax.tree.map(on_chip, params)
    k, v = jax.tree.map(on_chip, jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, NB, 16, layered=True)
    ))
    resident = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in k + v)
    i32, f32 = jnp.int32, jnp.float32

    def rows(B):
        return [arr((B,), i32), arr((2,), jnp.uint32), arr((B,), f32),
                arr((B,), i32), arr((B,), f32), arr((B,), i32)]

    if program == "decode_burst":
        fn = DeviceRunner._build_decode_fn(runner)
        lowered = fn.lower(
            params, None, k, v, arr((S,), i32), arr((S,), i32),
            arr((S,), i32), arr((S, 32), i32), *rows(S),
        )
    else:
        fresh = program == "prefill_fresh"
        B, C, P = (4, 256, 16) if fresh else (1, 128, 32)
        fn = DeviceRunner._build_step_fn(runner, first_chunk=fresh)
        lowered = fn.lower(
            params, None, k, v, arr((B, C), i32), arr((B,), i32),
            arr((B,), i32), arr((B, P), i32), *rows(B), None, None,
        )
    return lowered.compile(), k[0], resident


@pytest.mark.parametrize("program", ["decode_burst", "prefill_fresh", "prefill_tail"])
@pytest.mark.parametrize("model", ["qwen2.5-0.5b", "qwen3-8b-int8"])
def test_served_programs_hold_no_whole_pool_copy(one_chip, model, program):
    """The decode burst and the prefill step of a two-layer qwen2.5-0.5b,
    compiled for the v5e as the runner builds them: the optimised HLO holds
    no copy of a whole per-layer pool (96 a program with the pool at its
    logical head size of 64: the resident layout was not the kernels'),
    and the donated pools alias in and out. And of what ``worker --model
    qwen3-8b --quantization int8`` decodes and prefills with since PR 49
    (the programs every cell is judged on), at the model's layer widths:
    Mosaic takes the kernels at 32 heads over 8 of 128 lanes beside the int8
    matmuls."""
    from dynamo_tpu.ops.pallas.chip_check import whole_pool_copies

    compiled, pool, resident = _dense_program(one_chip, program, model)
    text = compiled.as_text()
    # a fresh prompt attends inside its chunk: no paged kernel there
    assert ("tpu_custom_call" in text) == (program != "prefill_fresh")
    assert ("s8[" in text) == (model == "qwen3-8b-int8")
    assert whole_pool_copies(text, pool) == 0
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert resident <= aliased < resident + (1 << 20)


HYBRID_PROGRAMS = {
    # name: (fresh prompts?, rows, chunk, table width); None: the decode burst
    "decode_burst": None,
    "prefill_fresh": (True, 4, 512, 32),
    "prefill_fresh_2x256": (True, 2, 256, 16),
    "prefill_fresh_8x1024": (True, 8, 1024, 64),
    "prefill_tail": (False, 1, 128, 16),
}


@functools.cache
def _hybrid_program(one_chip, program):
    """One served program of the hybrid configuration at its PUBLISHED widths
    (one layer of each kind: Mamba-2, experts with 64 of 128 held as the cell
    serves them, attention), as the runner builds it: (compiled, params,
    donated arguments)."""
    import types

    from dynamo_tpu.engines.tpu.engine import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models import hybrid, llama
    from dynamo_tpu.models.config import (
        NEMOTRON_3_NANO_30B_A3B_HF, ModelConfig, cut_hybrid,
    )

    full = ModelConfig.from_hf_config(NEMOTRON_3_NANO_30B_A3B_HF)
    cfg = cut_hybrid(full, n_layers=6, experts_held=(0, 64), vocab_rows=8192, name="cut")
    cfg = dataclasses_replace_layers(cfg, (0, 1, 5))  # M, E, *
    NB, S, SNAP = 2048, 64, 16
    args = JaxEngineArgs(
        config=cfg, num_kv_blocks=NB, max_num_seqs=S, max_model_len=2048,
        prefill_chunk=1024, use_kernel=True,
    )
    runner = types.SimpleNamespace(config=cfg, args=args, use_kernel=True,
                                   _decode_sig_budget=None)
    build_decode = lambda: DeviceRunner._build_decode_fn_hybrid(runner, False, False)
    build_step = lambda fresh: DeviceRunner._build_step_fn_hybrid(runner, False, 0, fresh)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = lambda f: jax.tree.map(on_chip, jax.eval_shape(f))
    params = shapes(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    k, v = shapes(lambda: llama.init_kv_cache(cfg, NB, 16, layered=True))
    store = shapes(lambda: hybrid.init_ssm_state(cfg, SNAP))
    i32, f32 = jnp.int32, jnp.float32

    def rows(B):
        return [arr((B,), i32), arr((2,), jnp.uint32), arr((B,), f32),
                arr((B,), i32), arr((B,), f32)]

    if program == "decode_burst":
        state = shapes(lambda: hybrid.init_ssm_state(cfg, S))
        lowered = build_decode().lower(
            params, k, v, state, arr((S,), i32), arr((S,), i32), arr((S,), i32),
            arr((S, 32), i32), *rows(S),
        )
        donated = (k, v, state)
    else:
        fresh, B, C, P = HYBRID_PROGRAMS[program]
        state = shapes(lambda: hybrid.init_ssm_state(cfg, B))
        lowered = build_step(fresh).lower(
            params, k, v, store, state, arr((B, C), i32), arr((B,), i32),
            arr((B,), i32), arr((B, P), i32), arr((B, C // 64), i32), *rows(B),
        )
        donated = (k, v, store, state)
    return lowered.compile(), params, donated


@pytest.mark.parametrize("program", sorted(HYBRID_PROGRAMS))
def test_hybrid_served_programs_compile_for_the_chip(one_chip, program):
    """The decode burst and the prefill step of the hybrid configuration,
    compiled for the v5e as the runner builds them: the paged-attention
    kernels lower at head 128 with 16 Q per KV head, the scan, the grouped
    expert kernel and the snapshot scatter compile, and the donated pools,
    recurrent state and snapshot store alias in and out."""
    from dynamo_tpu.ops.pallas.chip_check import whole_pool_copies

    compiled, params, donated = _hybrid_program(one_chip, program)
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the paged-attention kernel
    # benchmark/trace_names tells the two programs apart by a ``while``: the
    # burst is one, and a prefill step (scan, sort, grouped kernel) holds none
    assert (" while(" in text) == (program == "decode_burst")
    # The burst's 64 slots and the tail's 128 tokens go through the hit-list
    # expert kernel, 512 to 8,192 fresh tokens through the grouped one (no
    # ``ragged_dot`` since PR 45), and no program re-lays a stack of expert
    # matrices on the way to either: ``we_up`` is read d minor, as resident.
    grouped = program.startswith("prefill_fresh")
    assert ("expert_ffn_hit_list" in text) == (not grouped)
    assert ("expert_ffn_grouped" in text) == grouped
    assert "ragged-dot" not in text and "ragged_dot" not in text
    experts = params["layers"][1]
    copies = [whole_pool_copies(text, experts[k]) for k in ("we_up", "we_down")]
    assert copies == [0, 0]
    resident = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(donated))
    assert compiled.memory_analysis().alias_size_in_bytes >= resident


SSD_STEP_SHAPES = {
    # name: (slots, H, P, N, G): the two served shapes and a one-slot engine
    "mamba2-64-slots": (64, 64, 64, 128, 8),
    "lightning-32-slots": (32, 32, 128, 128, 32),
    "lightning-one-slot": (1, 32, 128, 128, 32),
}


@pytest.mark.parametrize("name", sorted(SSD_STEP_SHAPES))
def test_state_update_kernel_compiles_for_v5e(one_chip, name):
    """``ssd_step_live`` at the served widths (a whole row of state a grid
    step: 2 MiB in, 2 MiB out, each twice buffered) and with one slot (a list
    of two entries): Mosaic lowers the lane slices, the sublane broadcasts
    and the float32 scalar prefetch, and the state aliases in and out."""
    from dynamo_tpu.ops.pallas.ssd_step import _ssd_step_live_impl

    B, H, P, N, G = SSD_STEP_SHAPES[name]
    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    compiled = jax.jit(_ssd_step_live_impl, donate_argnums=(5,)).lower(
        sds((B, H, P)), sds((B, H)), sds((H,)), sds((B, G, N)), sds((B, G, N)),
        sds((B, H, P, N)), sds((1,), jnp.int32), sds((B + 1,), jnp.int32),
        sds((B,), jnp.bool_),
    ).compile()
    assert "ssd_step_live" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes == B * H * P * N * 4


@pytest.mark.parametrize("cell", ["hybrid", "sala"])
def test_decode_burst_updates_the_live_rows_state_in_place(one_chip, cell):
    """The decode bursts of the two cells with recurrent layers: every such
    layer's recurrence is the live-row kernel, the slots' state aliases in
    and out, and the optimised HLO holds NO operation whose result is a whole
    ``f32[slots, H, P, N]`` state (the XLA form's multiply and add over every
    slot, a copy for the kernel's operand)."""
    from dynamo_tpu.models import hybrid
    from dynamo_tpu.ops.pallas.chip_check import whole_array_ops

    if cell == "hybrid":
        compiled, _, (_, _, state) = _hybrid_program(one_chip, "decode_burst")
    else:
        compiled, _ = _sala_program(one_chip, "decode_burst")
        state = jax.eval_shape(
            lambda: hybrid.init_ssm_state(_sala_config(), 32))
    text = compiled.as_text()
    assert "ssd_step_live" in text
    assert {a.shape for a in state["S"]} == {
        (64, 64, 64, 128) if cell == "hybrid" else (32, 32, 128, 128)}
    assert whole_array_ops(text, state["S"][0]) == []
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in state["S"])
    assert compiled.memory_analysis().alias_size_in_bytes >= held


def dataclasses_replace_layers(cfg, keep):
    import dataclasses

    return dataclasses.replace(
        cfg, n_layers=len(keep), layer_specs=tuple(cfg.layer_specs[i] for i in keep))


MLA_SHAPES = {
    # name: (B, C, P): the absorbed kernel at the served widths (128 heads over
    # rows of 640 lanes in pages of 128 tokens, a table of 136 pages)
    "decode_32_rows": (32, 1, 136),
    "decode_one_row": (1, 1, 136),
    "question_chunk_128": (1, 128, 136),
    "question_chunks_256_x8": (8, 256, 136),
}


@pytest.mark.parametrize("name", sorted(MLA_SHAPES))
def test_mla_kernel_compiles_for_v5e_at_the_served_widths(one_chip, name):
    """ops/pallas/mla_paged.py: the page tile read once for scores (640
    lanes) and values (its first 512), M = query block x 128 heads rows a
    virtual row, the work list in SMEM."""
    from dynamo_tpu.ops.pallas.mla_paged import _mla_paged_decode_impl

    B, C, P = MLA_SHAPES[name]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(functools.partial(_mla_paged_decode_impl, v_width=512, sm_scale=192**-0.5))
    compiled = fn.lower(
        sds((B, C, 128, 640), jnp.bfloat16), sds((2560, 128, 640), jnp.bfloat16),
        sds((B, P), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.int32),
    ).compile()
    assert "mla_paged_decode" in compiled.as_text()


@functools.cache
def _mla_program(one_chip, program, depth):
    """(compiled, donated shapes) of one served program of the openPangu
    configuration at its published widths, ``depth`` expert layers after the
    leading dense one, as the runner builds it."""
    import dataclasses
    import types

    from dynamo_tpu.engines.tpu.engine import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models import hybrid, llama
    from dynamo_tpu.models.config import openpangu_ultra_moe_ep16_config

    cfg = openpangu_ultra_moe_ep16_config()
    keep = 2 * (1 + depth)
    cfg = dataclasses.replace(cfg, n_layers=keep, layer_specs=cfg.layer_specs[:keep])
    NB, S, P, bs = 2560, 32, 136, 128
    args = JaxEngineArgs(
        config=cfg, block_size=bs, num_kv_blocks=NB, max_num_seqs=S, max_model_len=P * bs,
        prefill_chunk=256, use_kernel=True,
    )
    runner = types.SimpleNamespace(config=cfg, args=args, use_kernel=True,
                                   _decode_sig_budget=None)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = lambda f: jax.tree.map(on_chip, jax.eval_shape(f))
    params = shapes(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    k, v = shapes(lambda: llama.init_kv_cache(cfg, NB, bs, layered=True))
    store = shapes(lambda: hybrid.init_ssm_state(cfg, 16))
    i32, f32 = jnp.int32, jnp.float32

    def rows(B):
        return [arr((B,), i32), arr((2,), jnp.uint32), arr((B,), f32),
                arr((B,), i32), arr((B,), f32)]

    if program == "decode_burst":
        state = shapes(lambda: hybrid.init_ssm_state(cfg, S))
        lowered = DeviceRunner._build_decode_fn_hybrid(runner, False, False).lower(
            params, k, v, state, arr((S,), i32), arr((S,), i32), arr((S,), i32),
            arr((S, P), i32), *rows(S),
        )
    else:
        fresh = program.startswith("prefill_fresh")
        B = 1 if program.endswith("_one_row") else 8
        C, width = 256, (2 if fresh else P)
        state = shapes(lambda: hybrid.init_ssm_state(cfg, B))
        lowered = DeviceRunner._build_step_fn_hybrid(runner, False, 0, fresh).lower(
            params, k, v, store, state, arr((B, C), i32), arr((B,), i32),
            arr((B,), i32), arr((B, width), i32), arr((B, 0), i32), *rows(B),
        )
    return lowered.compile(), (params, k)


@pytest.mark.parametrize("program", [
    "decode_burst", "prefill_fresh", "prefill_tail",
    "prefill_fresh_one_row", "prefill_tail_one_row",
])
def test_mla_served_programs_compile_for_the_chip(one_chip, program):
    """The decode burst, the fresh prefill step and a batch of question
    chunks over a full table, of the openPangu configuration at its published
    widths (the dense layer and one expert layer), compiled for the v5e as
    the runner builds them: the absorbed kernel lowers in the burst and the
    chunk-with-context step, the fresh step holds none (expanded form), the
    latent pools alias in and out, and no program copies a whole pool. The
    burst's 32 slots and a one-row step's 256 tokens (a document's chunk, a
    question over it) go through the hit-list expert kernel, its three
    matrices read as they are resident: no copy of an expert stack; eight
    rows of 256 go through the grouped form."""
    from dynamo_tpu.ops.pallas.chip_check import whole_pool_copies

    compiled, (params, k) = _mla_program(one_chip, program, depth=1)
    text = compiled.as_text()
    assert ("mla_paged_decode" in text) == (not program.startswith("prefill_fresh"))
    assert (" while(" in text) == (program == "decode_burst")
    assert whole_pool_copies(text, k[0]) == 0
    resident = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in k)
    assert compiled.memory_analysis().alias_size_in_bytes >= resident
    hit_listed = program == "decode_burst" or program.endswith("_one_row")
    assert ("expert_ffn_hit_list" in text) == hit_listed
    # an expert of 94 MB does not fit VMEM whole: ``ops/moe.grouped_reason``
    # keeps eight rows of 256 on ``ragged_dot``
    assert "expert_ffn_grouped" not in text
    assert ("ragged-dot" in text) == (not hit_listed)
    if hit_listed:
        experts = params["layers"][3]
        assert [whole_pool_copies(text, experts[m])
                for m in ("we_up", "we_gate", "we_down")] == [0, 0, 0]


@functools.cache
def _laguna_program(one_chip, program):
    """One served program of the Laguna-XS.2 stage at its published widths
    (layers 0-2: full + dense, two sliding expert layers; then the last full
    expert layer), as the runner builds it, two tables a row."""
    import dataclasses
    import types

    from dynamo_tpu.engines.tpu.engine import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models import hybrid, llama
    from dynamo_tpu.models.config import laguna_xs2_pp8_config

    cfg = dataclasses_replace_layers(laguna_xs2_pp8_config(), [0, 1, 2, 3, 8, 9])
    NB, S, P, bs = 3328, 64, 256, 128
    args = JaxEngineArgs(
        config=cfg, block_size=bs, num_kv_blocks=NB, max_num_seqs=S, max_model_len=P * bs,
        prefill_chunk=256, use_kernel=True,
    )
    runner = types.SimpleNamespace(config=cfg, args=args, use_kernel=True,
                                   _decode_sig_budget=None)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = lambda f: jax.tree.map(on_chip, jax.eval_shape(f))
    params = shapes(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    k, v = shapes(lambda: llama.init_kv_cache(
        cfg, NB, bs, layered=True, window_blocks=args.num_window_blocks))
    store = shapes(lambda: hybrid.init_ssm_state(cfg, 16))
    i32, f32 = jnp.int32, jnp.float32

    def rows(B):
        return [arr((B,), i32), arr((2,), jnp.uint32), arr((B,), f32),
                arr((B,), i32), arr((B,), f32)]

    if program == "decode_burst":
        state = shapes(lambda: hybrid.init_ssm_state(cfg, S))
        lowered = DeviceRunner._build_decode_fn_hybrid(runner, False, False).lower(
            params, k, v, state, arr((S,), i32), arr((S,), i32), arr((S,), i32),
            arr((S, 2, P), i32), *rows(S),
        )
    else:
        fresh = program == "prefill_fresh"
        B, C, width = (8, 256, 2) if fresh else (1, 256, P)
        state = shapes(lambda: hybrid.init_ssm_state(cfg, B))
        lowered = DeviceRunner._build_step_fn_hybrid(runner, False, 0, fresh).lower(
            params, k, v, store, state, arr((B, C), i32), arr((B,), i32),
            arr((B,), i32), arr((B, 2, width), i32), arr((B, 0), i32), *rows(B),
        )
    return lowered.compile(), (params, k, args)


@pytest.mark.parametrize("program", ["decode_burst", "prefill_fresh", "prefill_tail"])
def test_laguna_served_programs_compile_for_the_chip(one_chip, program):
    """The decode burst, a batch of fresh first chunks and a turn's chunk over
    a 32 k table, of the Laguna-XS.2 stage at its published widths, compiled
    for the v5e as the runner builds them: both paged kernels lower at 48 and
    64 query heads, the two page groups' pools (two shapes) alias in and out,
    no program copies a whole pool or an expert stack, the burst's 64 slots
    go through the hit-list expert kernel at [256, 2048, 512], a turn's 256
    tokens (since PR 51) and eight fresh rows through the grouped one, and a
    prefill program holds no ``while``."""
    from dynamo_tpu.ops.pallas.chip_check import whole_pool_copies

    compiled, (params, k, args) = _laguna_program(one_chip, program)
    text = compiled.as_text()
    assert {a.shape[0] for a in k} == {3328, args.num_window_blocks} and args.num_window_blocks == 768
    assert ("tpu_custom_call" in text) and (" while(" in text) == (program == "decode_burst")
    assert whole_pool_copies(text, k[0]) == whole_pool_copies(text, k[1]) == 0
    resident = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in k) * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= resident
    # every prefill step goes through the grouped kernel (f minor, gated
    # silu: an expert's three matrices whole in VMEM): 8 rows of 256 since
    # PR 45 (no ``ragged_dot``), a lone turn of 256 since PR 51
    hit_listed = program == "decode_burst"
    assert ("expert_ffn_hit_list/pallas_call" in text) == hit_listed
    assert ("expert_ffn_grouped/pallas_call" in text) == (not hit_listed)
    assert "ragged-dot" not in text
    experts = params["layers"][3]
    assert [whole_pool_copies(text, experts[m])
            for m in ("we_up", "we_gate", "we_down")] == [0, 0, 0]


SALA_SELECTED = {
    # name: (rows, table slots a K/V head): the live-span decode kernel over
    # SELECTED pages at MiniCPM-SALA's widths (32 query heads over 2 K/V heads
    # of 128, pages of 64): a table per (row, K/V head) in scalar memory.
    "decode_32_rows": (32, 64),
    "chunk_256_queries": (256, 64),
}


@pytest.mark.parametrize("name", sorted(SALA_SELECTED))
def test_selected_pages_kernel_compiles_for_v5e(one_chip, name):
    """ops/sparse_attention.py's call of the decode kernel: every (row, K/V
    head) visits the 64 pages its indexer selected; a decode burst's 32 slots
    and the 256 queries of a turn's chunk (each a row of its own)."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        _paged_attention_decode_kernel_impl,
        selected_plan,
    )

    B, W = SALA_SELECTED[name]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, pool, tables, pages, start):
        plan = selected_plan(pool, tables, pages)
        return _paged_attention_decode_kernel_impl(
            q, pool, pool, tables[:, 0], start, 0, None, plan, sm_scale=128**-0.5)

    pool = sds((11264, 64, 2, 128), jnp.bfloat16)
    compiled = jax.jit(call).lower(
        sds((B, 1, 32, 128), jnp.bfloat16), pool, sds((B, 2, W), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _sala_config():
    from dynamo_tpu.models.config import minicpm_sala_pp4_config

    return dataclasses_replace_layers(minicpm_sala_pp4_config(), [0, 1, 2, 3, 14, 15])


@functools.cache
def _sala_program(one_chip, program):
    """One served program of the MiniCPM-SALA stage at its published widths
    (a sparse layer, a lightning layer and the last sparse layer, each with
    its FFN), as the runner builds it, over a 66,560-token table."""
    import types

    from dynamo_tpu.engines.tpu import block_pool
    from dynamo_tpu.engines.tpu.engine import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models import hybrid, llama

    cfg = _sala_config()
    NB, S, P, bs = 11264, 32, 1040, 64
    args = JaxEngineArgs(
        config=cfg, block_size=bs, num_kv_blocks=NB, max_num_seqs=S, max_model_len=P * bs,
        prefill_chunk=256, use_kernel=True,
    )
    runner = types.SimpleNamespace(config=cfg, args=args, use_kernel=True,
                                   _decode_sig_budget=None)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = lambda f: jax.tree.map(on_chip, jax.eval_shape(f))
    params = shapes(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    k, v = shapes(lambda: llama.init_kv_cache(cfg, NB, bs, layered=True))
    entries = block_pool.snapshot_entries(cfg, NB, bs, S)
    store = shapes(lambda: hybrid.init_ssm_state(cfg, entries))
    i32, f32 = jnp.int32, jnp.float32

    def rows(B):
        return [arr((B,), i32), arr((2,), jnp.uint32), arr((B,), f32),
                arr((B,), i32), arr((B,), f32)]

    if program == "decode_burst":
        state = shapes(lambda: hybrid.init_ssm_state(cfg, S))
        lowered = DeviceRunner._build_decode_fn_hybrid(runner, False, False).lower(
            params, k, v, state, arr((S,), i32), arr((S,), i32), arr((S,), i32),
            arr((S, P), i32), *rows(S),
        )
    else:
        fresh = program == "prefill_fresh"
        B, C, width = (8, 256, 4) if fresh else (8, 256, P)
        state = shapes(lambda: hybrid.init_ssm_state(cfg, B))
        lowered = DeviceRunner._build_step_fn_hybrid(runner, False, 0, fresh).lower(
            params, k, v, store, state, arr((B, C), i32), arr((B,), i32),
            arr((B,), i32), arr((B, width), i32), arr((B, C // 64), i32), *rows(B),
        )
    return lowered.compile(), (k, v, store, entries)


@pytest.mark.parametrize("program", ["decode_burst", "prefill_fresh", "prefill_tail"])
def test_sala_served_programs_compile_for_the_chip(one_chip, program):
    """The decode burst, a batch of fresh first chunks and eight turns'
    chunks over 66,560-token tables, of the MiniCPM-SALA stage at its
    published widths, compiled for the v5e as the runner builds them: the
    decode kernel lowers over selected pages and over the dense rows' own, the
    chunk kernel in blocks of 128 query positions (256 of 32 heads are 21 MiB
    of VMEM), the pools, the indexer's rows, the state and the snapshot store
    alias in and out, no program copies a whole pool, and a prefill program
    holds no ``while``."""
    from dynamo_tpu.ops.pallas.chip_check import whole_pool_copies

    compiled, (k, v, store, entries) = _sala_program(one_chip, program)
    text = compiled.as_text()
    assert entries == 176 and [a.shape for a in k[2:]] == [(11264, 4, 2, 128)] * 2
    assert ("tpu_custom_call" in text) == (program != "prefill_fresh")
    assert (" while(" in text) == (program == "decode_burst")
    assert whole_pool_copies(text, k[0]) == whole_pool_copies(text, k[2]) == 0
    resident = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in k + v)
    if program != "decode_burst":
        resident += sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in store["S"])
    assert compiled.memory_analysis().alias_size_in_bytes >= resident


def _gdn_config():
    from dynamo_tpu.models.config import qwen3_next_ep2_config

    return dataclasses_replace_layers(qwen3_next_ep2_config(), [0, 1, 6, 7])


@functools.cache
def _gdn_program(one_chip, program):
    """One served program of the Qwen3-Next stage at its published widths (a
    Gated DeltaNet layer and the full gated-attention layer, each with its
    experts: 256 of 512 held, top 10), as the runner builds it, over a
    33,792-token table of 128-token pages."""
    import types

    from dynamo_tpu.engines.tpu import block_pool
    from dynamo_tpu.engines.tpu.engine import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models import hybrid, llama

    cfg = _gdn_config()
    NB, S, P, bs = 6144, 64, 264, 128
    args = JaxEngineArgs(
        config=cfg, block_size=bs, num_kv_blocks=NB, max_num_seqs=S, max_model_len=P * bs,
        prefill_chunk=256, use_kernel=True,
    )
    runner = types.SimpleNamespace(config=cfg, args=args, use_kernel=True,
                                   _decode_sig_budget=None)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = lambda f: jax.tree.map(on_chip, jax.eval_shape(f))
    params = shapes(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    k, v = shapes(lambda: llama.init_kv_cache(cfg, NB, bs, layered=True))
    entries = block_pool.snapshot_entries(cfg, NB, bs, S)
    store = shapes(lambda: hybrid.init_ssm_state(cfg, entries))
    i32, f32 = jnp.int32, jnp.float32

    def rows(B):
        return [arr((B,), i32), arr((2,), jnp.uint32), arr((B,), f32),
                arr((B,), i32), arr((B,), f32)]

    if program == "decode_burst":
        state = shapes(lambda: hybrid.init_ssm_state(cfg, S))
        lowered = DeviceRunner._build_decode_fn_hybrid(runner, False, False).lower(
            params, k, v, state, arr((S,), i32), arr((S,), i32), arr((S,), i32),
            arr((S, P), i32), *rows(S),
        )
    else:
        fresh = program == "prefill_fresh"
        B, C, width = (8, 256, 2) if fresh else (8, 256, P)
        if program == "prefill_tail_one_row":  # a turn admitted alone
            B = 1
        state = shapes(lambda: hybrid.init_ssm_state(cfg, B))
        lowered = DeviceRunner._build_step_fn_hybrid(runner, False, 0, fresh).lower(
            params, k, v, store, state, arr((B, C), i32), arr((B,), i32),
            arr((B,), i32), arr((B, width), i32), arr((B, C // 64), i32), *rows(B),
        )
    return lowered.compile(), (params, k, v, store, state, entries)


@pytest.mark.parametrize("program", [
    "decode_burst", "prefill_fresh", "prefill_tail", "prefill_tail_one_row"])
def test_gdn_served_programs_compile_for_the_chip(one_chip, program):
    """The decode burst, a batch of fresh first chunks and eight turns' chunks
    over 33,792-token tables, of the Qwen3-Next stage at its published widths,
    compiled for the v5e as the runner builds them: both paged kernels lower
    at a head of 256 (two lane tiles), 8 queries a K/V head; the delta rule's
    chunk form (a static unroll over a chunk's four blocks) leaves a prefill
    program without a ``while``; the burst's recurrence is ``gdn_step_live``
    and no operation of it yields a whole ``f32[64, 32, 128, 128]`` state;
    pools, state and snapshot store alias in and out; the burst's 64 slots go
    through the hit-list expert kernel over 256 held experts, 8 x 256 tokens
    and (since PR 51) a lone turn's 256 through the grouped one."""
    from dynamo_tpu.ops.pallas.chip_check import whole_array_ops, whole_pool_copies

    compiled, (params, k, v, store, state, entries) = _gdn_program(one_chip, program)
    text = compiled.as_text()
    assert entries == 192 and k[0].shape == (6144, 128, 2, 256)
    assert ("tpu_custom_call" in text) and (" while(" in text) == (program == "decode_burst")
    assert whole_pool_copies(text, k[0]) == 0
    # (the kernel's call, not its name: a module's text lists every function
    # the process has traced)
    assert ("gdn_step_live/pallas_call" in text) == (program == "decode_burst")
    assert state["S"][0].shape[1:] == (32, 128, 128)
    if program == "decode_burst":
        assert whole_array_ops(text, state["S"][0]) == []
    resident = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in k + v + state["S"] + state["conv"])
    if program != "decode_burst":
        resident += sum(int(np.prod(a.shape)) * a.dtype.itemsize
                        for a in store["S"] + store["conv"])
    assert compiled.memory_analysis().alias_size_in_bytes >= resident
    assert ("expert_ffn_hit_list/pallas_call" in text) == (program == "decode_burst")
    assert ("expert_ffn_grouped/pallas_call" in text) == (program != "decode_burst")
    assert "ragged-dot" not in text
    experts = params["layers"][1]
    assert [whole_pool_copies(text, experts[m])
            for m in ("we_up", "we_gate", "we_down")] == [0, 0, 0]


GDN_STEP_SHAPES = {"64-slots": 64, "one-slot": 1}


@pytest.mark.parametrize("name", sorted(GDN_STEP_SHAPES))
def test_gdn_state_update_kernel_compiles_for_v5e(one_chip, name):
    """``gdn_step_live`` at the served widths (32 value heads of 128 x 128: a
    whole row of state a grid step) and with one slot (a list of two
    entries): Mosaic lowers the column and row selects, the sublane sums and
    the two float32 scalar prefetches, and the state aliases in and out."""
    from dynamo_tpu.ops.pallas.gdn_step import _gdn_step_live_impl

    B, H, D = GDN_STEP_SHAPES[name], 32, 128
    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    compiled = jax.jit(_gdn_step_live_impl, donate_argnums=(5,)).lower(
        sds((B, H, D)), sds((B, H, D)), sds((B, H, D)), sds((B, H)), sds((B, H)),
        sds((B, H, D, D)), sds((1,), jnp.int32), sds((B + 1,), jnp.int32),
        sds((B,), jnp.bool_),
    ).compile()
    assert "gdn_step_live/pallas_call" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes == B * H * D * D * 4


def _computations(hlo_text):
    """name -> text of every computation of an optimised HLO module."""
    import re

    return {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*?^\}$", hlo_text, re.M | re.S)}


def _reached(computations, name):
    """The text of a computation and of every one it calls, fusions' bodies
    and comparators included."""
    import re

    seen, todo = {}, [name]
    while todo:
        at = todo.pop()
        if at in seen:
            continue
        seen[at] = computations[at]
        for m in re.finditer(
                r"(?:calls|to_apply|body|condition|true_computation|false_computation)=%([\w.\-]+)"
                r"|(?:branch_computations|called_computations)=\{([^}]*)\}", seen[at]):
            todo += [m.group(1)] if m.group(1) else re.findall(r"%([\w.\-]+)", m.group(2))
    return "\n".join(seen.values())


def sampler_branches(hlo_text):
    """(arg-max branch, candidates' branch) of each ``conditional`` that
    ``ops/sampling.sample_tokens`` put into an optimised program (traced
    under the ``sample`` scope), each branch with everything it calls."""
    import re

    computations = _computations(hlo_text)
    found = []
    for line in hlo_text.splitlines():
        if " conditional(" not in line or "sample/cond" not in line:
            continue
        m = re.search(r"branch_computations=\{%([\w.\-]+), %([\w.\-]+)\}", line)
        if m is None:  # the predicated form names the true branch first
            m = re.search(r"true_computation=%([\w.\-]+), false_computation=%([\w.\-]+)", line)
            names = (m.group(2), m.group(1))
        else:
            names = m.groups()  # index 0 is ``lax.cond``'s false branch
        found.append(tuple(_reached(computations, n) for n in names))
    return found


SERVED_PROGRAMS = {
    "dense": lambda chip, program: _dense_program(chip, program, "qwen2.5-0.5b"),
    "hybrid": _hybrid_program,
    "latent": lambda chip, program: _mla_program(chip, program, depth=1),
    "window": _laguna_program,
    "sparse": _sala_program,
    "gdn": _gdn_program,
}


@pytest.mark.parametrize("program", ["decode_burst", "prefill_fresh", "prefill_tail"])
@pytest.mark.parametrize("cell", sorted(SERVED_PROGRAMS))
def test_served_programs_hold_one_conditional_for_the_sampler(one_chip, cell, program):
    """The decode burst and the prefill steps of the five served shapes, as
    the tests above compile them: ONE ``conditional`` from the sampler (in
    the burst inside the loop's body), whose arg-max branch holds no ``sort``
    and no top-k, exact or approximate, while the other holds both (traced
    here, where the default backend is the CPU, the candidates' search is
    ``lax.top_k``; on the chip ``approx_max_k``, a ``PartialReduce``); and a
    prefill program still holds no ``while``, by which benchmark/trace_names
    tells it from the burst."""
    text = SERVED_PROGRAMS[cell](one_chip, program)[0].as_text()
    ((greedy, full),) = sampler_branches(text)
    assert "PartialReduce" not in greedy
    for op in (" sort(", "top_k"):
        assert op not in greedy, op
        assert op in full, op
    assert " reduce(" in greedy  # the arg-max: one pass over the logits
    assert (" while(" in text) == (program == "decode_burst")
