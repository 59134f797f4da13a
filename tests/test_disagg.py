"""Disaggregated prefill/decode tests: content-addressed KV export/import,
PrefillHandler bootstrap, full PrefillRouter flow — with the correctness
oracle that disaggregated greedy output equals aggregated greedy output
(the reference validates disagg through its serve suites; here we can
assert numerical equivalence directly)."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.disagg import (
    DecodeHandler,
    KvTransferHandler,
    PrefillHandler,
    PrefillRouter,
)
from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import tiny_config
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import as_engine, collect
from dynamo_tpu.runtime.pipeline import build_pipeline
from dynamo_tpu.tokens.blocks import compute_block_hashes


def make_engine(**over):
    defaults = dict(
        config=tiny_config(),
        block_size=4,
        num_kv_blocks=64,
        max_num_seqs=4,
        max_model_len=128,
        prefill_chunk=32,
        decode_steps=4,
    )
    defaults.update(over)
    return JaxEngine(JaxEngineArgs(**defaults))


def req(tokens, max_tokens=8):
    return PreprocessedRequest(
        token_ids=list(tokens),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens),
    )


async def serve_disagg(rt, namespace, prefill_engine, decode_engine, **exporter_kw):
    """A prefill worker (generate + kv endpoints, instance 1) and a decode
    worker (instance 2) on ``rt``, behind a PrefillRouter. Returns the
    served endpoints, the decode handler and the pipeline."""
    ns = rt.namespace(namespace)
    pc = ns.component("prefill")
    served = [
        await pc.endpoint("generate").serve_endpoint(
            PrefillHandler(prefill_engine, worker_id=1).generate, instance_id=1
        ),
        await pc.endpoint("kv").serve_endpoint(
            KvTransferHandler(prefill_engine, **exporter_kw).generate, instance_id=1
        ),
    ]

    async def kv_client():
        return await pc.endpoint("kv").client()

    dc = ns.component("backend")
    decode_handler = DecodeHandler(decode_engine, kv_client_factory=kv_client)
    served.append(
        await dc.endpoint("generate").serve_endpoint(
            decode_handler.generate, instance_id=2
        )
    )
    decode_client = await dc.endpoint("generate").client()

    async def prefill_client():
        return await pc.endpoint("generate").client()

    pipeline = build_pipeline(
        [PrefillRouter(prefill_client, threshold_tokens=8)], decode_client
    )
    return served, decode_handler, pipeline


async def test_export_import_roundtrip():
    """Blocks exported from one engine and imported into another must make
    the second engine's prefix cache hit (and produce identical logits —
    checked indirectly through identical greedy continuations)."""
    e1 = make_engine(seed=7)
    e2 = make_engine(seed=7)  # same weights (same init seed)
    try:
        prompt = list(range(40, 56))  # 4 full blocks
        out1 = await collect(e1.generate(req(prompt, max_tokens=6), Context()))
        toks1 = [t for o in out1 for t in o.token_ids]

        hashes = compute_block_hashes(prompt, 4)
        found, k, v = await e1.export_blocks_async(hashes)
        assert found == hashes
        assert k.shape[0] == len(hashes)

        installed = await e2.import_blocks_async(found, k, v)
        assert installed == len(hashes)
        assert e2.pool.match_prefix(hashes) == len(hashes)

        prefill_before = e2.prefill_tokens
        out2 = await collect(e2.generate(req(prompt, max_tokens=6), Context()))
        toks2 = [t for o in out2 for t in o.token_ids]
        # Imported blocks made the prompt a prefix hit: only the last token
        # (matched capped at prompt-1) is recomputed.
        assert e2.prefill_tokens - prefill_before < len(prompt)
        assert toks2 == toks1
    finally:
        await e1.stop()
        await e2.stop()


async def test_prefill_handler_bootstrap():
    engine = make_engine()
    try:
        handler = PrefillHandler(engine, worker_id=42)
        out = await collect(handler.generate(req(range(10, 26), max_tokens=50), Context()))
        assert len(out) == 1
        dp = out[0].disaggregated_params
        assert dp is not None and dp.worker_id == 42
        assert dp.kv_transfer["block_hashes"]
        assert out[0].token_ids and dp.kv_transfer["first_token"] == out[0].token_ids[0]
        # prefill engine released its sequence; blocks are cached for export
        assert engine.pool.active_blocks == 0
        assert engine.pool.cached_blocks > 0
    finally:
        await engine.stop()


async def test_disaggregated_equals_aggregated():
    """Full disagg flow over the process-local runtime: prefill worker +
    decode worker + PrefillRouter; greedy output must equal the aggregated
    single-engine output, and the decode engine must not re-prefill the
    full prompt."""
    rt = DistributedRuntime.detached()
    prefill_engine = make_engine(seed=3)
    decode_engine = make_engine(seed=3)
    oracle_engine = make_engine(seed=3)
    served = []
    try:
        served, _decode_handler, pipeline = await serve_disagg(
            rt, "t", prefill_engine, decode_engine
        )

        prompt = list(range(60, 78))  # 18 tokens: 4 full blocks + tail
        oracle = await collect(oracle_engine.generate(req(prompt, max_tokens=10), Context()))
        oracle_toks = [t for o in oracle for t in o.token_ids]

        out = await collect(pipeline.generate(req(prompt, max_tokens=10).to_dict(), Context()))
        toks = []
        for o in out:
            if hasattr(o, "token_ids"):
                toks.extend(o.token_ids or [])
            elif isinstance(o, dict):
                toks.extend(o.get("token_ids") or [])
        assert toks == oracle_toks, (toks, oracle_toks)
        # Decode engine skipped the transferred prefix: it prefilled at most
        # the tail block + first token, not the whole prompt.
        assert decode_engine.prefill_tokens < len(prompt)
        assert prefill_engine.prefill_tokens >= len(prompt) - 1
    finally:
        for s in served:
            await s.shutdown(grace_period=1)
        for e in (prefill_engine, decode_engine, oracle_engine):
            await e.stop()
        await rt.shutdown(grace_period=1)


async def test_prefill_router_falls_back_without_workers():
    """No prefill instances → aggregated path, stream unchanged."""
    rt = DistributedRuntime.detached()
    engine = make_engine(seed=5)
    ns = rt.namespace("t")
    try:
        dc = ns.component("backend")
        served = await dc.endpoint("generate").serve_endpoint(
            engine.generate, instance_id=2
        )
        decode_client = await dc.endpoint("generate").client()

        async def prefill_client():
            return await ns.component("prefill").endpoint("generate").client()

        pipeline = build_pipeline(
            [PrefillRouter(prefill_client, threshold_tokens=8)], decode_client
        )
        out = await collect(pipeline.generate(req(range(30, 46), max_tokens=5).to_dict(), Context()))
        toks = []
        for o in out:
            if hasattr(o, "token_ids"):
                toks.extend(o.token_ids or [])
            elif isinstance(o, dict):
                toks.extend(o.get("token_ids") or [])
        assert len(toks) == 5
        await served.shutdown(grace_period=1)
    finally:
        await engine.stop()
        await rt.shutdown(grace_period=1)


async def test_chunked_streamed_transfer():
    """With chunk_bytes forced tiny, the exporter streams MANY bounded
    messages and the importer chains chunks via anchor_parent — final
    decode output still equals the aggregated oracle, and the handler's
    transfer counters record the pull."""
    rt = DistributedRuntime.detached()
    prefill_engine = make_engine(seed=5)
    decode_engine = make_engine(seed=5)
    oracle_engine = make_engine(seed=5)
    ns = rt.namespace("tchunk")
    served = []
    try:
        pc = ns.component("prefill")
        exporter = KvTransferHandler(prefill_engine, chunk_bytes=1)  # 1 block/chunk
        assert exporter._blocks_per_chunk() == 1
        served.append(
            await pc.endpoint("generate").serve_endpoint(
                PrefillHandler(prefill_engine, worker_id=1).generate,
                instance_id=1,
            )
        )
        served.append(
            await pc.endpoint("kv").serve_endpoint(
                exporter.generate, instance_id=1
            )
        )

        async def kv_client():
            return await pc.endpoint("kv").client()

        dc = ns.component("backend")
        decode_handler = DecodeHandler(decode_engine, kv_client_factory=kv_client)
        served.append(
            await dc.endpoint("generate").serve_endpoint(
                decode_handler.generate, instance_id=2
            )
        )
        decode_client = await dc.endpoint("generate").client()

        async def prefill_client():
            return await pc.endpoint("generate").client()

        pipeline = build_pipeline(
            [PrefillRouter(prefill_client, threshold_tokens=8)], decode_client
        )

        prompt = list(range(30, 50))  # 20 tokens: 5 full blocks
        oracle = await collect(
            oracle_engine.generate(req(prompt, max_tokens=8), Context())
        )
        oracle_toks = [t for o in oracle for t in o.token_ids]
        out = await collect(
            pipeline.generate(req(prompt, max_tokens=8).to_dict(), Context())
        )
        toks = []
        for o in out:
            if hasattr(o, "token_ids"):
                toks.extend(o.token_ids or [])
            elif isinstance(o, dict):
                toks.extend(o.get("token_ids") or [])
        assert toks == oracle_toks, (toks, oracle_toks)
        # multi-chunk pull really happened and was fully imported
        assert decode_handler.transfers == 1
        assert decode_handler.transfer_failures == 0
        assert decode_handler.blocks_pulled >= 4, decode_handler.blocks_pulled
        assert decode_handler.bytes_pulled > 0
    finally:
        for s in served:
            await s.shutdown()
        await prefill_engine.stop()
        await decode_engine.stop()
        await oracle_engine.stop()


async def test_export_readback_overlaps_decode():
    """The export's HBM→host readback must run on the transfer lane, not
    the device thread: a generate() issued while a (artificially slow)
    export is draining must finish well before the export does."""
    import time as _time

    engine = make_engine()
    real_readback = engine.runner.gather_blocks_readback
    try:
        prompt = list(range(40, 56))
        await collect(engine.generate(req(prompt, max_tokens=2), Context()))
        # pre-warm the second request's program shapes so the timed leg
        # measures scheduling, not CPU compile time
        await collect(
            engine.generate(req(list(range(80, 90)), max_tokens=6), Context())
        )
        hashes = compute_block_hashes(prompt, 4)

        def slow_readback(k, v):
            _time.sleep(1.2)  # a slow wire/DCN drain
            return real_readback(k, v)

        engine.runner.gather_blocks_readback = slow_readback
        t0 = _time.monotonic()
        export_task = asyncio.ensure_future(
            engine.export_blocks_async(hashes)
        )
        await asyncio.sleep(0.05)  # let the dispatch land first
        out = await collect(
            engine.generate(req(list(range(60, 70)), max_tokens=6), Context())
        )
        t_decode_done = _time.monotonic() - t0
        found, _k, _v = await export_task
        t_export_done = _time.monotonic() - t0
        assert [t for o in out for t in o.token_ids], "decode produced nothing"
        assert found == hashes
        # decode finished while the transfer was still sleeping on the
        # wire. The RELATIVE ordering is the whole claim — an absolute
        # wall-clock bound here flaked on loaded hosts where compile/jit
        # stalls stretched the decode leg past any fixed budget while the
        # overlap itself held (ADVICE r5).
        assert t_decode_done < t_export_done, (t_decode_done, t_export_done)
    finally:
        engine.runner.gather_blocks_readback = real_readback
        await engine.stop()


async def test_concurrent_disagg_wave_is_token_exact_and_heals_nothing():
    """More streams than decode slots through the whole disagg path at
    once: every stream equals the aggregated engine's, every one pulled
    its prefix, the bytes are accounted per wire dtype, the link's
    bandwidth is learned, and with no fault armed the self-healing paths
    (pull retries, breaker, aggregated fallback, migration) never ran."""
    from dynamo_tpu.runtime import faults

    rt = DistributedRuntime.detached()
    prefill_engine = make_engine(seed=3)
    decode_engine = make_engine(seed=3, max_num_seqs=2)
    oracle_engine = make_engine(seed=3)
    served = []
    try:
        served, decode_handler, pipeline = await serve_disagg(
            rt, "twave", prefill_engine, decode_engine
        )

        prompts = [list(range(20 + 19 * i, 38 + 19 * i)) for i in range(5)]
        want = []
        for p in prompts:
            out = await collect(oracle_engine.generate(req(p, max_tokens=10), Context()))
            want.append([t for o in out for t in o.token_ids])

        activity0 = faults.activity_snapshot()

        async def one(p):
            toks = []
            async for o in pipeline.generate(req(p, max_tokens=10).to_dict(), Context()):
                toks.extend(
                    (o.token_ids if hasattr(o, "token_ids") else o.get("token_ids"))
                    or []
                )
            return toks

        got = await asyncio.gather(*(one(p) for p in prompts))
        assert got == want
        assert decode_handler.transfers == len(prompts)
        assert decode_handler.blocks_pulled >= 4 * len(prompts)
        assert sum(decode_handler.wire_bytes_by_dtype.values()) == \
            decode_handler.bytes_pulled > 0
        assert decode_handler.link_bandwidth()[1] > 0
        assert (
            decode_handler.transfer_failures, decode_handler.pull_retries,
            decode_handler.breaker_opens, decode_handler.pull_fallbacks,
        ) == (0, 0, 0, 0)
        assert faults.activity_snapshot() == activity0
    finally:
        for s in served:
            await s.shutdown(grace_period=1)
        for e in (prefill_engine, decode_engine, oracle_engine):
            await e.stop()
        await rt.shutdown(grace_period=1)
