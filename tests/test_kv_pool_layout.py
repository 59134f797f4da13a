"""The serving KV pool is resident at the width the kernels read
(ops/attention.pool_head_dim: a head narrower than a 128-lane tile is held
at the tile's width, lanes past the head zero and never read), and the
fresh-prompt prefill ladder is compiled before a worker serves
(admission.prefill_ladder / JaxEngine.compile_prefill_ladder;
docs/design_docs/engine.md).

What must hold, on any backend: the same logits bit for bit with the pool
held either way; blocks leave and enter the pool at the LOGICAL head size
(wire, tiers, checkpoint unchanged); after the ladder, simultaneous fresh
prompts compile nothing however the scheduler happens to split them."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.engines.tpu.admission import (
    PREFILL_CHUNK_FLOOR,
    prefill_chunk_bucket,
    prefill_table_bucket,
)
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import tiny_config
from dynamo_tpu.ops.attention import KV_LANE_TILE, pool_head_dim
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect

NB, BS = 32, 4


def _logical_pools(cfg):
    shape = (NB, BS, cfg.n_kv_heads, cfg.head_dim_)
    one = lambda: tuple(  # noqa: E731
        jnp.zeros(shape, cfg.dtype) for _ in range(cfg.n_layers)
    )
    return one(), one()


def test_pool_is_held_at_a_lane_tile_only_below_one():
    cfg = tiny_config()
    assert cfg.head_dim_ < KV_LANE_TILE  # tiny exercises the padded pool
    k, v = llama.init_kv_cache(cfg, NB, BS, layered=True)
    assert k[0].shape == (NB, BS, cfg.n_kv_heads, KV_LANE_TILE)
    assert v[0].shape == k[0].shape
    # a head of a lane tile or more is held as it is, byte for byte
    for hd in (128, 256):
        wide = tiny_config(d_model=2 * hd, n_heads=2, n_kv_heads=1, head_dim=hd)
        k, _ = llama.init_kv_cache(wide, NB, BS, layered=True)
        assert k[0].shape == (NB, BS, 1, hd)
        assert pool_head_dim(hd) == hd
    # int8 pools and the stacked layout keep the logical head size
    k8, _ = llama.init_kv_cache(cfg, NB, BS, layered=True, kv_dtype="int8")
    assert k8[0]["q8"].shape[-1] == cfg.head_dim_
    ks, _ = llama.init_kv_cache(cfg, NB, BS)
    assert ks.shape[-1] == cfg.head_dim_


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_logits_bit_identical_with_the_pool_held_either_way(dtype):
    """Prefill of two ragged rows, a prefix-tail chunk and eight greedy
    decode steps through forward_paged: every logit equal, and the padding
    lanes of the wide pool still zero afterwards."""
    cfg = tiny_config(dtype=dtype)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    params = dict(params, layers=llama.unstack_layer_params(
        params["layers"], cfg.n_layers
    ))
    rng = np.random.default_rng(3)
    B, C = 2, 16
    lens = np.array([16, 11], np.int32)
    toks = rng.integers(0, cfg.vocab_size, (B, C)).astype(np.int32)
    tables = np.arange(1, 1 + B * 8, dtype=np.int32).reshape(B, 8)

    def serve(k, v):
        out = []
        start = np.zeros(B, np.int32)
        logits, k, v = llama.forward_paged(
            params, cfg, toks, start, lens, tables, k, v, first_chunk=True
        )
        out.append(logits)
        # a later chunk reads the pages just written (the paged path)
        more = rng.integers(0, cfg.vocab_size, (B, 4)).astype(np.int32)
        logits, k, v = llama.forward_paged(
            params, cfg, more, lens, np.full(B, 4, np.int32), tables, k, v
        )
        out.append(logits)
        pos = lens + 4
        tok = np.asarray(jnp.argmax(logits, -1), np.int32)
        for _ in range(8):
            logits, k, v = llama.forward_paged(
                params, cfg, tok[:, None], pos, np.ones(B, np.int32),
                tables, k, v,
            )
            out.append(logits)
            tok = np.asarray(jnp.argmax(logits, -1), np.int32)
            pos = pos + 1
        return out, k

    rng_state = rng.bit_generator.state
    wide, k_wide = serve(*llama.init_kv_cache(cfg, NB, BS, layered=True))
    rng.bit_generator.state = rng_state
    logical, k_logical = serve(*_logical_pools(cfg))
    assert k_wide[0].shape[-1] == KV_LANE_TILE
    assert k_logical[0].shape[-1] == cfg.head_dim_
    for a, b in zip(wide, logical):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    hd = cfg.head_dim_
    for pw, pl_ in zip(k_wide, k_logical):
        assert np.array_equal(np.asarray(pw[..., :hd]), np.asarray(pl_))
        assert not np.asarray(pw[..., hd:]).any()


@pytest.mark.parametrize("kernel,C", [("decode", 1), ("decode", 4), ("chunk", 16)])
def test_kernels_bit_identical_on_a_wide_page(kernel, C):
    """Both Pallas kernels (interpreter) over a page held wider than the
    head, its padding lanes filled with garbage: the same bits as over the
    logical page, so the lanes past the head are provably never read."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        _paged_attention_decode_kernel_impl,
        _paged_attention_kernel_impl,
    )

    H, KH, D, B, P = 4, 2, 64, 3, 4
    rng = np.random.default_rng(C)
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((NB, BS, KH, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((NB, BS, KH, D)), jnp.bfloat16)
    junk = jnp.asarray(rng.standard_normal((NB, BS, KH, 64)) * 1e4, jnp.bfloat16)
    k_wide = jnp.concatenate([k, junk], -1)
    v_wide = jnp.concatenate([v, junk], -1)
    tables = jnp.asarray(rng.permutation(NB)[: B * P].reshape(B, P), jnp.int32)
    start = jnp.asarray([0, 5, P * BS - C], jnp.int32)
    lens = jnp.full((B,), C, jnp.int32)

    def run(kp, vp):
        if kernel == "decode":
            return _paged_attention_decode_kernel_impl(
                q, kp, vp, tables, start, 0, lens, interpret=True
            )
        return _paged_attention_kernel_impl(
            q, kp, vp, tables, start, lens, interpret=True
        )

    assert np.array_equal(
        np.asarray(run(k_wide, v_wide), np.float32),
        np.asarray(run(k, v), np.float32),
    )


def _engine(**over):
    defaults = dict(
        config=tiny_config(), block_size=4, num_kv_blocks=64, max_num_seqs=8,
        max_model_len=128, prefill_chunk=32,
    )
    defaults.update(over)
    return JaxEngine(JaxEngineArgs(**defaults))


def _req(tokens, max_tokens=4):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id="r",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens),
    )


@pytest.mark.parametrize("route", ["dense", "wire", "wire-int8", "kvbm", "checkpoint"])
async def test_blocks_leave_and_enter_at_the_logical_head_size(route, tmp_path):
    """Whatever moves blocks out of a wide pool hands them over at the
    logical head size, and what it hands over installs into another wide
    pool and serves the same tokens (the formats did not change)."""
    cfg = tiny_config()
    hd, KH, L = cfg.head_dim_, cfg.n_kv_heads, cfg.n_layers
    prompt = list(range(10, 42))  # 8 full blocks of 4
    kv = dict(kv_cache_dtype="int8") if route == "wire-int8" else {}
    a, b = _engine(**kv), _engine(**kv)
    kvbm = None
    try:
        if route == "kvbm":
            from dynamo_tpu.kvbm import HostTier, TieredKvManager

            kvbm = TieredKvManager(HostTier(64))
            kvbm.attach(a)
        out = await collect(a.generate(_req(prompt), Context()))
        want = [t for o in out for t in o.token_ids]
        if route != "wire-int8":
            assert a.runner.k_cache[0].shape[-1] == KV_LANE_TILE
        ids = list(range(1, 5))
        if route == "dense":
            k, v = a.runner.gather_blocks(ids)
            assert k.shape == v.shape == (len(ids), L, 4, KH, hd)
            b.runner.scatter_blocks(ids, k, v)
            k2, v2 = b.runner.gather_blocks(ids)
            assert np.array_equal(k, k2) and np.array_equal(v, v2)
            wide = np.asarray(b.runner.k_cache[0][jnp.asarray(ids)])
            assert not wide[..., hd:].any()
            return
        if route.startswith("wire"):
            wire = a.runner.gather_blocks_wire(ids)
            assert wire.k.shape == wire.v.shape == (len(ids), L, 4, KH, hd)
            assert wire.quantized == (route == "wire-int8")
            b.runner.scatter_blocks_wire(ids, wire)
            again = b.runner.gather_blocks_wire(ids)
            assert np.array_equal(wire.k, again.k)
            assert np.array_equal(wire.v, again.v)
            return
        if route == "kvbm":
            await asyncio.sleep(0.3)  # the write-through offload drains
            assert kvbm.offloaded > 0
            block = next(b for b in kvbm.tier._blocks.values() if b)
            assert block[0].shape == block[1].shape == (L, 4, KH, hd)
            # gone from the device, back from the host tier, same tokens
            a.clear_kv_blocks()
            before = a.prefill_tokens
            out = await collect(a.generate(_req(prompt), Context()))
            assert [t for o in out for t in o.token_ids] == want
            assert a.prefill_tokens - before < len(prompt)
            return
        ckpt = str(tmp_path / "ckpt")
        saved = await a.save_checkpoint(ckpt)
        assert saved["blocks"] > 0
        assert await b.load_checkpoint(ckpt) == saved["blocks"]
        out = await collect(b.generate(_req(prompt), Context()))
        assert [t for o in out for t in o.token_ids] == want
        assert b.stats()["prefill_tokens"] <= len(prompt) // 2
    finally:
        if kvbm is not None:
            await kvbm.close()
        await a.stop()
        await b.stop()


def test_chunk_floor_and_table_follow_the_chunk():
    args = JaxEngineArgs(block_size=16, max_model_len=2048, prefill_chunk=1024)
    assert PREFILL_CHUNK_FLOOR == KV_LANE_TILE
    # a short chunk pads up to the floor; the cell's lengths are untouched
    assert [prefill_chunk_bucket(n, 1024) for n in (1, 10, 65, 128, 129, 1024)] == [
        128, 128, 128, 128, 256, 1024]
    assert prefill_chunk_bucket(10, 32) == 32  # a chunk below the floor caps it
    # a fresh prompt's table is the chunk's own blocks ...
    for n in (1, 10, 65, 200, 512, 1000):
        c = prefill_chunk_bucket(n, 1024)
        assert prefill_table_bucket(-(-n // 16), c, args) == c // 16
    # ... a prefix-hit tail keeps the width of its whole context
    assert prefill_table_bucket(40, 128, args) == 64
    assert prefill_table_bucket(500, 128, args) == 128  # max_blocks_per_seq


SPLITS = [
    (4,), (2, 2), (1, 3), (3, 1), (1, 1, 2), (1, 1, 1, 1),
    (8,), (3, 5), (5, 3), (4, 4), (1, 7), (2, 6), (1, 2, 5), (2, 2, 2, 2),
]


@pytest.fixture(scope="module")
def laddered():
    """One engine with the ladder compiled, shared by the split cases (a
    module fixture cannot be async here: it owns its loop)."""
    loop = asyncio.new_event_loop()
    eng = _engine(
        block_size=16, num_kv_blocks=256, max_num_seqs=8, max_model_len=512,
        prefill_chunk=256, prefill_batch=8,
    )
    report = loop.run_until_complete(eng.compile_prefill_ladder())
    yield eng, report, loop
    loop.run_until_complete(eng.stop())
    loop.close()


def _compiled_step_programs(eng):
    """(procs, want_top, first_chunk) -> programs that callable holds."""
    return {
        key: fn._cache_size()
        for key, fn in eng.runner._step_fns.items() if fn._cache_size()
    }


def test_ladder_programs_exist_after_start(laddered):
    eng, report, _ = laddered
    ladder = eng._admitter.prefill_ladder()
    assert ladder == [
        (Bp, c, c // 16) for Bp in (1, 2, 4, 8) for c in (128, 256)
    ]
    assert report["prefill_ladder_programs"] == len(ladder)
    assert _compiled_step_programs(eng) == {(False, False, True): len(ladder)}
    stats = eng.stats()
    assert stats["prefill_ladder_programs"] == len(ladder)
    assert stats["startup_compiles"] >= len(ladder)
    assert stats["startup_compile_seconds"] > 0
    assert stats["prefill_tokens"] == 0 and eng.pool.free_blocks == 256


@pytest.mark.parametrize("split", SPLITS, ids=lambda s: "+".join(map(str, s)))
def test_simultaneous_fresh_prompts_compile_nothing_in_any_split(laddered, split):
    """Four or eight fresh prompts arriving together, admitted as the
    scheduler might split them: every batch meets a ladder program."""
    eng, _, loop = laddered
    before = _compiled_step_programs(eng)
    rng = np.random.default_rng(list(split))  # no case repeats a prompt

    admitted = []
    begin = eng._admitter._begin_prefill

    def spy(batch):
        admitted.append(len(batch))
        return begin(batch)

    async def run():
        for rows in split:
            outs = await asyncio.gather(*(
                collect(eng.generate(
                    # 1..256 tokens: below the floor and up to the chunk
                    _req(rng.integers(0, 500, int(rng.integers(1, 257))).tolist(),
                         max_tokens=1), Context()))
                for _ in range(rows)
            ))
            assert all(o[-1].finish_reason is not None for o in outs)

    eng._admitter._begin_prefill = spy
    try:
        loop.run_until_complete(run())
    finally:
        eng._admitter._begin_prefill = begin
    assert tuple(admitted) == split  # each group was one prefill batch
    assert _compiled_step_programs(eng) == before
