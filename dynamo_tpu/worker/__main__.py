from __future__ import annotations

import argparse
import asyncio
import os
import random
import signal

import jax

from dynamo_tpu import config
from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.llm.discovery import register_llm
from dynamo_tpu.llm.model_card import ModelDeploymentCard, RuntimeConfig
from dynamo_tpu.models.config import (
    ModelConfig,
    gemma2_2b_config,
    gemma3_1b_config,
    laguna_xs2_pp8_config,
    llama3_3b_config,
    llama3_8b_config,
    llama3_70b_config,
    minicpm_sala_pp4_config,
    mixtral_8x7b_config,
    nemotron3_nano_ep2_config,
    openpangu_ultra_moe_ep16_config,
    qwen2_500m_config,
    qwen3_8b_config,
    qwen3_next_ep2_config,
    tiny_config,
    tiny_gdn_config,
    tiny_hybrid_config,
    tiny_mla_config,
    tiny_sala_config,
    tiny_swa_config,
)
from dynamo_tpu.parallel import MeshConfig, make_mesh
from dynamo_tpu.router import KvEventPublisher, LoadPublisher
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.utils.jax_env import (
    configure_compile_cache,
    require_serving_platform,
)
from dynamo_tpu.utils.logging import configure_logging, get_logger

logger = get_logger(__name__)

BUILTIN_CONFIGS = {
    "tiny": tiny_config,
    "qwen2.5-0.5b": qwen2_500m_config,
    "llama-3-8b": llama3_8b_config,
    "llama-3.2-3b": llama3_3b_config,
    "qwen3-8b": qwen3_8b_config,
    "llama-3-70b": llama3_70b_config,
    "gemma-2-2b": gemma2_2b_config,
    "gemma-3-1b": gemma3_1b_config,
    "mixtral-8x7b": mixtral_8x7b_config,
    "tiny-hybrid": tiny_hybrid_config,
    "nemotron-3-nano-30b-a3b-ep2": nemotron3_nano_ep2_config,
    "tiny-mla": tiny_mla_config,
    "openpangu-ultra-moe-718b-ep16": openpangu_ultra_moe_ep16_config,
    "tiny-swa": tiny_swa_config,
    "laguna-xs.2-pp8": laguna_xs2_pp8_config,
    "tiny-sala": tiny_sala_config,
    "minicpm-sala-pp4": minicpm_sala_pp4_config,
    "tiny-gdn": tiny_gdn_config,
    "qwen3-next-80b-a3b-ep2": qwen3_next_ep2_config,
}


def build_parser() -> argparse.ArgumentParser:
    """The worker's argument surface. Factored out so recipe validation
    (tests/test_recipes.py, tests/test_70b_fit.py) resolves the SAME
    defaults a deployed worker gets."""
    parser = argparse.ArgumentParser("dynamo-tpu worker (native JAX engine)")
    parser.add_argument(
        "--model",
        default="tiny",
        help="HF model directory, or a builtin config name "
        f"({', '.join(BUILTIN_CONFIGS)}) with random weights",
    )
    parser.add_argument("--served-model-name", default=None)
    parser.add_argument("--namespace", default=config.NAMESPACE.get())
    parser.add_argument("--component", default="backend")
    parser.add_argument("--endpoint", default="generate")
    parser.add_argument(
        "--block-size", type=int, default=config.KV_BLOCK_SIZE.get()
    )
    parser.add_argument("--num-kv-blocks", type=int, default=2048)
    parser.add_argument("--max-num-seqs", type=int, default=16)
    parser.add_argument("--max-model-len", type=int, default=2048)
    parser.add_argument("--prefill-chunk", type=int, default=512)
    parser.add_argument("--tensor-parallel-size", "--tp", type=int, default=1)
    parser.add_argument("--no-prefix-caching", action="store_true")
    parser.add_argument(
        "--is-prefill-worker", action="store_true",
        help="serve disaggregated prefill (ref: vllm/args.py --is-prefill-worker)",
    )
    parser.add_argument(
        "--prefill-component", default="prefill",
        help="component name prefill workers register under",
    )
    parser.add_argument(
        "--kv-offload-blocks", type=int, default=0,
        help="host-RAM KV tier capacity in blocks (0 = offload disabled; "
        "ref: KVBM G2 tier)",
    )
    parser.add_argument(
        "--kv-offload-dir", default=None,
        help="disk KV tier spool directory (KVBM G3; requires --kv-offload-blocks)",
    )
    parser.add_argument(
        "--kv-remote", default=None, metavar="NS/COMPONENT/ENDPOINT",
        help="shared KV store endpoint (KVBM G4; run python -m dynamo_tpu.kvbm)",
    )
    parser.add_argument(
        "--kv-host-arena-mb", type=int, default=0,
        help="back the host KV tier with a preallocated arena of this many "
        "MB (0 = plain numpy blocks)",
    )
    parser.add_argument("--decode-steps", type=int, default=8,
                        help="fused decode iterations per device dispatch")
    parser.add_argument("--pipeline-depth", type=int, default=2,
                        help="decode bursts in flight on the device (2 = "
                        "double-buffered dispatch/reap, 1 = synchronous; "
                        "docs/design_docs/decode_pipelining.md)")
    parser.add_argument("--tick-budget", action="store_true",
                        help="intra-chip prefill/decode disaggregation: cap "
                        "per-tick prefill chunk tokens with the closed-loop "
                        "TickBudgeter (docs/design_docs/disagg_serving.md, "
                        "intra-chip middle mode)")
    parser.add_argument("--tick-budget-floor", type=int, default=None,
                        help="starvation floor in prefill tokens per tick "
                        "(default: one prefill chunk)")
    parser.add_argument("--tick-budget-ceiling", type=int, default=None,
                        help="budget ceiling in prefill tokens per tick "
                        "(default: admit_batches_per_tick x prefill_chunk — "
                        "the unbudgeted per-tick admission cap)")
    parser.add_argument("--tick-budget-policy", type=float, default=0.5,
                        help="0 = strict-ITL (start at the floor), 1 = "
                        "max-throughput (start at the ceiling)")
    parser.add_argument("--tick-budget-itl-slo-ms", type=float, default=None,
                        help="per-token ITL SLO driving the budget's "
                        "shrink/grow control law (off: budget only moves "
                        "via the overload ladder's squeeze rung)")
    parser.add_argument("--lora-dir", default=None,
                        help="directory of PEFT LoRA adapters to serve "
                        "(ref: lib/llm/src/lora.rs)")
    parser.add_argument("--weight-cache-dir", default=None,
                        help="fast-restart weight cache (GMS-role, "
                        "models/weight_cache.py); default ~/.cache/dynamo_tpu")
    parser.add_argument("--system-port", type=int, default=None,
                        help="per-worker system HTTP server port "
                        "(health/metrics/engine admin/LoRAs; 0 = ephemeral; "
                        "ref: system_status_server.rs)")
    parser.add_argument("--model-type", choices=["chat", "completion", "multimodal"],
                        default="chat",
                        help="model card type; 'multimodal' makes the "
                        "frontend splice encode-worker embeddings (E/P/D)")
    parser.add_argument("--speculative", choices=["ngram"], default=None,
                        help="speculative decoding: ngram = prompt-lookup "
                        "proposals verified in one dispatch (greedy only)")
    parser.add_argument("--spec-k", type=int, default=4,
                        help="proposed tokens per speculative verify step")
    parser.add_argument("--spec-ngram", type=int, default=3,
                        help="match length for prompt-lookup proposals")
    parser.add_argument("--kv-checkpoint-dir", default=None,
                        help="warm-cache checkpoint directory (chrek/CRIU "
                        "role): restored at startup when present, saved on "
                        "graceful shutdown")
    parser.add_argument("--quantization", choices=["int8"], default=None,
                        help="weight-only quantization (int8: per-channel, "
                        "halves weight HBM — the FP8-checkpoint deployment "
                        "lever, TPU-style)")
    parser.add_argument("--kv-cache-dtype", choices=["int8", "auto"],
                        default=None,
                        help="KV-cache quantization (int8: per-token-head "
                        "dynamic scales — 2x KV capacity and half the "
                        "history-read bytes; the kv_cache_dtype=fp8 engine "
                        "lever, TPU-style). 'auto' applies the measured "
                        "break-even policy: int8 when max_model_len >= "
                        "DYN_TPU_KV_QUANT_AUTO_CTX or the pool cannot hold "
                        "the worst case at bf16")
    parser.add_argument("--coordinator", default=None,
                        help="multi-host: host:port of rank 0's "
                        "jax.distributed coordinator (or env "
                        "DYN_TPU_COORDINATOR); one process per host forms "
                        "ONE logical worker, rank 0 serves the endpoint")
    parser.add_argument("--num-processes", type=int, default=None,
                        help="multi-host world size (env DYN_TPU_NUM_PROCESSES)")
    parser.add_argument("--process-id", type=int, default=None,
                        help="multi-host rank of this process (env "
                        "DYN_TPU_PROCESS_ID)")
    return parser


async def main() -> None:
    parser = build_parser()
    args = parser.parse_args()
    if args.is_prefill_worker and args.component == "backend":
        args.component = args.prefill_component
    if args.kv_offload_blocks <= 0 and (
        args.kv_remote or args.kv_host_arena_mb or args.kv_offload_dir
    ):
        parser.error(
            "--kv-remote/--kv-host-arena-mb/--kv-offload-dir require "
            "--kv-offload-blocks > 0 (they configure the offload tier stack)"
        )
    if args.kv_remote:
        kv_remote_parts = args.kv_remote.split("/")
        if len(kv_remote_parts) != 3 or not all(kv_remote_parts):
            parser.error(
                f"--kv-remote must be NS/COMPONENT/ENDPOINT, got {args.kv_remote!r}"
            )

    configure_logging()
    configure_compile_cache()

    # Multi-host: join the jax.distributed runtime BEFORE any JAX use (the
    # backend must not exist yet). One process per host; rank 0 is the
    # leader and the only rank that serves/registers the endpoint (ref DP
    # leader pattern, components/src/dynamo/vllm/main.py:67-78).
    from dynamo_tpu.parallel.multihost import init_multihost

    topo = init_multihost(args.coordinator, args.num_processes, args.process_id)
    # Before the (possibly minutes-long) weight load: no chip and no
    # explicit JAX_PLATFORMS=cpu means this worker must not start.
    require_serving_platform()

    runtime = DistributedRuntime.from_settings() if topo.is_leader else None

    model_path = None
    if args.model in BUILTIN_CONFIGS:
        model_config = BUILTIN_CONFIGS[args.model]()
        params = None  # random init inside the engine
    else:
        model_path = args.model
        model_config = ModelConfig.from_model_dir(args.model)
        from dynamo_tpu.models.weight_cache import (
            DEFAULT_CACHE_DIR,
            load_checkpoint_cached,
        )

        params, cache_hit = load_checkpoint_cached(
            args.model, model_config,
            cache_dir=args.weight_cache_dir or DEFAULT_CACHE_DIR,
            quantization=args.quantization,
        )
        print(f"weights loaded (cache {'hit' if cache_hit else 'miss'})", flush=True)

    mesh = None
    if topo.is_multihost:
        # The global mesh spans every process's devices. Default tp = the
        # largest device-count divisor the model's kv heads can shard over
        # (a NamedSharding with more partitions than the axis size fails at
        # device_put); leftover devices become data parallelism.
        n_dev = len(jax.devices())
        if args.tensor_parallel_size > 1:
            tp = args.tensor_parallel_size
        else:
            tp = 1
            while (
                tp * 2 <= n_dev
                and n_dev % (tp * 2) == 0
                and model_config.n_kv_heads % (tp * 2) == 0
            ):
                tp *= 2
        mesh = make_mesh(MeshConfig(tp=tp, dp=n_dev // tp), jax.devices())
    elif args.tensor_parallel_size > 1:
        mesh = make_mesh(
            MeshConfig(tp=args.tensor_parallel_size), jax.devices()
        )

    engine_args = JaxEngineArgs(
        config=model_config,
        block_size=args.block_size,
        num_kv_blocks=args.num_kv_blocks,
        max_num_seqs=args.max_num_seqs,
        max_model_len=args.max_model_len,
        prefill_chunk=args.prefill_chunk,
        enable_prefix_caching=not args.no_prefix_caching,
        decode_steps=args.decode_steps,
        pipeline_depth=args.pipeline_depth,
        lora_dir=args.lora_dir,
        spec_mode=args.speculative,
        spec_k=args.spec_k,
        spec_ngram=args.spec_ngram,
        quantization=args.quantization,
        kv_cache_dtype=args.kv_cache_dtype,
        tick_budget_enabled=args.tick_budget,
        tick_budget_floor_tokens=args.tick_budget_floor,
        tick_budget_ceiling_tokens=args.tick_budget_ceiling,
        tick_budget_policy=args.tick_budget_policy,
        tick_budget_itl_slo_s=(
            args.tick_budget_itl_slo_ms / 1000.0
            if args.tick_budget_itl_slo_ms
            else None
        ),
    )

    if topo.is_multihost:
        from dynamo_tpu.engines.tpu import spmd
        from dynamo_tpu.engines.tpu.runner import DeviceRunner
        from dynamo_tpu.parallel.multihost import spmd_port

        runner = DeviceRunner(engine_args, params, mesh=mesh, topology=topo)
        port = spmd_port(topo.coordinator)
        if not topo.is_leader:
            # Follower rank: contribute devices to the collectives and
            # replay the leader's op stream until it closes the channel.
            host = topo.coordinator.rsplit(":", 1)[0]
            spmd.follow(runner, spmd.make_follower(host, port))
            return
        bcast = spmd.make_broadcaster(
            port, num_followers=topo.num_processes - 1
        )
        runner.set_broadcaster(bcast)
    else:
        runner = None

    name = args.served_model_name or model_config.name
    # Stable worker identity (crash plane): a restarted worker re-registers
    # under the SAME id with a fresh process incarnation, so the router's
    # rejoin purge and the fence line up; 0 keeps the old random-per-start
    # behavior for ad-hoc workers.
    instance_id = config.WORKER_ID.get() or random.getrandbits(63)
    # Trajectory plane: label this process's spans (clock-domain tag for
    # cross-worker stitching) and ship finished spans frontend-ward.
    from dynamo_tpu.runtime.trajectory import (
        TrajectoryShipper,
        set_global_shipper,
    )
    from dynamo_tpu.utils.tracing import global_tracer, set_service

    set_service(f"worker-{instance_id:#x}")
    trajectory_shipper = TrajectoryShipper(
        runtime.event_plane, args.namespace
    )
    trajectory_shipper.attach(global_tracer())
    set_global_shipper(trajectory_shipper)
    # Eagerly attach the local store too: the worker's own
    # /debug/trajectory must show ITS slice from the first request, not
    # from whenever the route is first scraped.
    from dynamo_tpu.runtime.trajectory import global_store

    global_store()
    kv_pub = KvEventPublisher(
        runtime.event_plane, args.namespace, args.component, instance_id
    )
    engine = JaxEngine(
        engine_args,
        params,
        mesh=mesh,
        on_kv_event=kv_pub.on_kv_event,
        runner=runner,
    )
    # Answer router re-sync requests with the pool's committed set (the
    # JetStream replay role) — a restarted router rebuilds its radix index
    # immediately instead of waiting for TTL churn.
    kv_pub.set_snapshot_fn(engine.pool.committed_view)
    kvbm = None
    if args.kv_offload_blocks > 0:
        from dynamo_tpu.kvbm import DiskTier, HostTier, RemoteTier, TieredKvManager

        disk = DiskTier(args.kv_offload_dir) if args.kv_offload_dir else None
        remote = None
        if args.kv_remote:
            ns, comp, ep_name = kv_remote_parts

            async def _kv_client():
                return await (
                    runtime.namespace(ns).component(comp).endpoint(ep_name).client()
                )

            remote = RemoteTier(_kv_client)
        kvbm = TieredKvManager(
            HostTier(
                args.kv_offload_blocks, next_tier=disk,
                arena_bytes=args.kv_host_arena_mb * (1 << 20) or None,
            ),
            remote=remote,
        )
        kvbm.attach(engine)
    load_pub = LoadPublisher(
        runtime.event_plane, args.namespace, args.component, instance_id,
        engine.stats, total_blocks=args.num_kv_blocks,
    )

    card = ModelDeploymentCard(
        name=name,
        model_type=args.model_type,
        model_path=model_path,
        context_length=args.max_model_len,
        kv_block_size=args.block_size,
        eos_token_ids=list(model_config.eos_token_ids),
        runtime_config=RuntimeConfig(
            total_kv_blocks=args.num_kv_blocks,
            kv_block_size=args.block_size,
            max_num_seqs=args.max_num_seqs,
            max_context_len=args.max_model_len,
        ),
    )
    from dynamo_tpu.disagg import DecodeHandler, KvTransferHandler, PrefillHandler
    from dynamo_tpu.runtime.liveness import process_incarnation

    component = runtime.namespace(args.namespace).component(args.component)
    endpoint = component.endpoint(args.endpoint)
    kv_endpoint = component.endpoint("kv")

    # Crash-plane startup order (docs/design_docs/fault_tolerance.md):
    # 1. system server UP first — /healthz (liveness: the process turns)
    #    answers during a long restore while /readyz stays 503, so the
    #    kubelet neither restarts the pod nor routes traffic at it;
    # 2. engine start + warm KV checkpoint restore (never-raise: any
    #    stamp mismatch or corruption is a logged, counted cold start);
    # 3. endpoints served + model registered under the FRESH incarnation —
    #    only now does the fleet see the worker at all;
    # 4. load reports begin (incarnation-stamped) and readiness flips —
    #    restored prefixes re-advertise via the router's kv-sync snapshot
    #    pull the moment the registration lands.
    ready_state: dict = {"ready": False, "detail": "starting"}
    system_server = None
    if args.system_port is not None:
        from dynamo_tpu.runtime.system_server import (
            SystemStatusServer,
            attach_engine,
        )

        system_server = SystemStatusServer(port=args.system_port)
        attach_engine(system_server, engine)

        def _worker_ready():
            # Drain-aware through EVERY trigger path (signal, POST /drain,
            # preStop GET): a draining worker is alive but not ready.
            dc = ready_state.get("drain_controller")
            if dc is not None and dc.state != 0:
                return False, "draining"
            return ready_state["ready"], ready_state["detail"]

        system_server.register_readiness("worker", _worker_ready)
        if kvbm is not None:
            kvbm.register_metrics(system_server)
        await system_server.start()
        print(f"system server on :{system_server.port}", flush=True)

    ready_state["detail"] = "starting engine"
    await engine.start()
    # Before anything registers: the prefill programs a fresh prompt can
    # reach compile here, where no load report is due and no request waits
    # (tracing and lowering hold the GIL: a registered worker that compiles
    # misses load reports, docs/design_docs/engine.md).
    ready_state["detail"] = "compiling the prefill ladder"
    warm = await engine.compile_prefill_ladder()
    print(
        f"prefill ladder: {warm['prefill_ladder_programs']} programs in "
        f"{warm['prefill_ladder_seconds']:.1f}s; compiled before serving: "
        f"{warm['startup_compiles']} programs in "
        f"{warm['startup_compile_seconds']:.1f}s",
        flush=True,
    )
    if args.kv_checkpoint_dir:
        # Restore BEFORE registering: the model card and the first load
        # report must describe a worker whose warm cache is already
        # installed, so a shared-prefix request routed here on the first
        # report serves without re-prefill. load_checkpoint never raises —
        # a bad checkpoint is a counted cold start, not a crash loop.
        ready_state["detail"] = "restoring KV checkpoint"
        n = await engine.load_checkpoint(args.kv_checkpoint_dir)
        if n:
            print(f"restored {n} warm KV blocks", flush=True)

    ready_state["detail"] = "registering endpoints"
    incarnation = process_incarnation()
    served_kv = await kv_endpoint.serve_endpoint(
        KvTransferHandler(engine).generate, instance_id=instance_id
    )

    async def control(request, context):
        """Admin ops (ref: clear_kv_blocks.rs; fanned out by the frontend)."""
        op = request.get("op") if isinstance(request, dict) else None
        if op == "clear_kv_blocks":
            yield {"cleared": engine.clear_kv_blocks()}
        elif op == "stats":
            yield engine.stats()
        else:
            yield {"error": f"unknown control op {op!r}"}

    served_ctl = await component.endpoint("control").serve_endpoint(
        control, instance_id=instance_id
    )
    served_handoff = None
    handoff_client_factory = None
    if args.is_prefill_worker:
        handler = PrefillHandler(engine, instance_id)
        served = await endpoint.serve_endpoint(
            handler.generate, instance_id=instance_id,
            metadata={"incarnation": incarnation},
        )
        # Prefill workers are found via their component endpoint, not the
        # model registry (ref: prefill_router.rs activate). Their in-flight
        # work is one bounded prefill each, so drain skips the handoff rung
        # (typed requeue re-dispatches whole requests).
    else:
        async def _kv_client():
            return await (
                runtime.namespace(args.namespace)
                .component(args.prefill_component)
                .endpoint("kv")
                .client()
            )

        handler = DecodeHandler(
            engine, kv_client_factory=_kv_client, worker_id=instance_id
        )
        # Load reports carry this worker's measured per-src pull bandwidth
        # (link-cost placement) and its open pull breakers (a FAILING link
        # is priced out of placement, not just a slow one).
        load_pub.link_bandwidth_fn = handler.link_bandwidth
        load_pub.link_faults_fn = handler.open_breaker_srcs
        served = await endpoint.serve_endpoint(
            handler.generate, instance_id=instance_id,
            metadata={"incarnation": incarnation},
        )
        await register_llm(
            runtime, card, endpoint, instance_id, incarnation=incarnation
        )
        # Live-handoff plane (rolling restarts): serve adoptions from
        # draining peers, and reach peers' handoff endpoints when WE drain.
        from dynamo_tpu.disagg import HANDOFF_ENDPOINT, HandoffHandler

        served_handoff = await component.endpoint(HANDOFF_ENDPOINT).serve_endpoint(
            HandoffHandler(engine).generate, instance_id=instance_id
        )

        async def handoff_client_factory():
            return await (
                runtime.namespace(args.namespace)
                .component(args.component)
                .endpoint(HANDOFF_ENDPOINT)
                .client()
            )
    load_pub.start()
    trajectory_shipper.start()
    # Worker-side overload plane: KV-pool-occupancy-driven brownout that
    # suspends speculative decode before admission backpressure turns
    # into a preemption storm (the engine's admit_kv_high_watermark does
    # the refusing; this re-arms spec when pressure clears). The
    # evaluate cadence rides the load-report task below.
    from dynamo_tpu.runtime.overload import OverloadController, config_from_env

    overload = OverloadController(
        config_from_env(),
        occupancy_source=lambda: engine.pool.usage,
    )
    overload.on_transition(
        lambda _old, new: engine.set_spec_suspended(new > 0)
    )
    if getattr(engine, "_budgeter", None) is not None:
        # Budget-squeeze rung: registering the lever makes the ladder
        # shrink the per-tick prefill budget one filled breach streak
        # BEFORE the max_tokens clamp, and release it last on recovery.
        # Unregistered (budgeter off), the ladder behaves exactly as
        # before.
        overload.on_budget_pressure(engine.set_budget_pressure)

    async def overload_eval_loop() -> None:
        while True:
            await asyncio.sleep(load_pub.interval_s)
            overload.evaluate()

    overload_task = asyncio.get_running_loop().create_task(
        overload_eval_loop(), name="overload-eval"
    )
    # Drain plane: SIGTERM (k8s pod deletion), POST /drain, or the preStop
    # hook triggers a live-handoff drain; the worker exits once drained.
    from dynamo_tpu.runtime.drain import DrainController

    shutdown = asyncio.Event()
    ready_state["drain_controller"] = drain_controller = DrainController(
        engine,
        worker_id=instance_id,
        handoff_client_factory=handoff_client_factory,
        load_publisher=load_pub,
        checkpoint_dir=args.kv_checkpoint_dir,
        on_drained=shutdown.set,
    )

    loop = asyncio.get_running_loop()

    def start_drain(sig_name: str) -> None:
        if drain_controller.state == 0:
            print(f"{sig_name}: draining (live handoff)...", flush=True)
        # A draining worker is alive but no longer ready: /readyz flips
        # 503 so the kubelet pulls it from service while streams hand off.
        ready_state["ready"] = False
        ready_state["detail"] = "draining"
        drain_controller.trigger()

    sigint_count = 0

    def on_sigint() -> None:
        nonlocal sigint_count
        sigint_count += 1
        if sigint_count >= 2:
            # Second ^C: the operator means NOW. Skip every drain step.
            print("second SIGINT: forcing exit", flush=True)
            os._exit(130)
        start_drain("SIGINT")

    # Loop signal handlers, NOT signal.signal: the previous bare
    # `asyncio.Event().wait()` meant SIGTERM killed the process without
    # ever running the finally block — no KV checkpoint, no graceful
    # endpoint shutdown, every live stream dropped.
    loop.add_signal_handler(signal.SIGTERM, start_drain, "SIGTERM")
    loop.add_signal_handler(signal.SIGINT, on_sigint)
    if system_server is not None:
        # Late source registration is fine: the server's routes consult
        # the registries per request (the server itself started before
        # the restore so /healthz was up the whole time).
        overload.register_metrics(system_server)
        drain_controller.register_metrics(system_server)
        system_server.register_drain(
            drain_controller.drain, drain_controller.status
        )
        if hasattr(handler, "register_metrics"):
            # DecodeHandler exposes the disagg transfer families; the
            # prefill handler has nothing to add.
            handler.register_metrics(system_server)
    ready_state["ready"] = True
    ready_state["detail"] = f"serving (incarnation {incarnation:#x})"
    print(
        f"worker serving {name} as {args.namespace}/{args.component}/"
        f"{args.endpoint} instance {instance_id:#x} "
        f"incarnation {incarnation:#x}",
        flush=True,
    )
    try:
        await shutdown.wait()
    finally:
        if (
            args.kv_checkpoint_dir
            and engine.pool.cached_blocks > 0
            and not drain_controller.checkpointed
        ):
            # Guarded: a drained/slept worker must not clobber a previous
            # warm checkpoint with an empty one.
            try:
                await engine.save_checkpoint(args.kv_checkpoint_dir)
            except Exception as exc:
                # Shutdown best-effort; next start just runs cold — but a
                # persistently failing checkpoint dir should be findable.
                logger.warning(
                    "KV checkpoint save failed on shutdown "
                    "(next start runs cold): %s", exc,
                )
        if system_server is not None:
            await system_server.stop()
        overload_task.cancel()
        from dynamo_tpu.runtime.tasks import reap_task

        await reap_task(overload_task, "overload eval loop", logger)
        if kvbm is not None:
            await kvbm.close()
        set_global_shipper(None)
        await trajectory_shipper.close()
        await load_pub.close()
        await kv_pub.close()
        await served.shutdown(grace_period=config.GRACE_PERIOD.get())
        await served_ctl.shutdown(grace_period=5)
        await served_kv.shutdown(grace_period=5)
        if served_handoff is not None:
            await served_handoff.shutdown(grace_period=5)
        await engine.stop()
        await runtime.shutdown(grace_period=config.GRACE_PERIOD.get())


if __name__ == "__main__":
    asyncio.run(main())
