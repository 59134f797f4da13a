"""Per-process system HTTP server: health, metrics, engine admin, LoRAs.

Reference parity: lib/runtime/src/system_status_server.rs — every worker
process exposes a small HTTP surface for orchestration:
  GET  /health             aggregated health (registered sources)
  GET  /live               liveness (the process event loop turns)
  GET  /metrics            Prometheus text (registered collectors)
  ANY  /engine/{path}      registered engine callbacks (sleep/wake/stats/…)
  GET  /v1/loras           list loaded adapters
  POST /v1/loras           {"name": ..., "path": ...} load an adapter
  DELETE /v1/loras/{name}  unload an adapter

Debug surface (serving-plane observability tentpole):
  GET  /debug/requests       recent + slow request-timeline summaries
  GET  /debug/requests/{id}  one ordered lifecycle timeline
  GET  /debug/traces         the process tracer's finished-span ring

KV-reuse plane (runtime/kv_reuse_observe.py):
  GET  /debug/kvcache          hit-rate/ROI rollup + sketch stats + top
                               prefixes (?top_k=)
  GET  /debug/kvcache/prefixes ranked prefix popularity, full depth (?k=)

Device-plane debug surface (runtime/device_observe.py):
  GET  /debug/memory         HBM ledger categories + pool byte split +
                             device.memory_stats() + host weight-cache tiers
  GET  /debug/compiles       per-program compile telemetry (watched_jit)
  GET  /debug/flight         merged flight-recorder rings (?limit=, ?kind=)
  POST /debug/profile        {"action": "start"|"stop"|"status", "dir"?,
                             "seconds"?} — on-demand jax.profiler capture

This is the TPU build's analog of the reference's axum system server; the
engine registers its callbacks via ``attach_engine`` (the reference's
engine-routes registry, system_status_server.rs /engine/{*path} handler).

``/metrics`` speaks OpenMetrics when the scraper asks for it (Accept:
application/openmetrics-text): metrics sources whose render callable takes
an ``openmetrics`` keyword (runtime/metrics_core.py registries) then emit
trace-id exemplars, linking histogram spikes to /debug timelines.
"""

from __future__ import annotations

import asyncio
import inspect
from typing import Any, Awaitable, Callable, Dict, List, Optional, Tuple

from aiohttp import web

from dynamo_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# handler(body: dict) -> (status, payload)
EngineRoute = Callable[[Dict[str, Any]], Awaitable[Tuple[int, Any]]]


def _takes_openmetrics(fn: Callable[..., str]) -> bool:
    """Does this metrics source accept an ``openmetrics`` keyword
    (metrics_core registries do; plain text lambdas don't)?"""
    try:
        return "openmetrics" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def _merge_expositions(parts: List[str]) -> str:
    """Concatenate metric sources, collapsing duplicate family metadata.

    Two same-kind subsystem objects on one server (metrics_core's per-object
    registries make this easy — e.g. two tiered managers both calling
    ``register_metrics``) each emit their own ``# HELP``/``# TYPE`` block
    for the same family, and Prometheus rejects an exposition whose
    metadata repeats or interleaves. Group every source's samples under one
    metadata block per family (first HELP/TYPE wins); sample lines pass
    through verbatim. Identical series from two sources therefore stay
    visible as duplicates (Prometheus flags them) instead of being
    silently collapsed or summed — objects whose series would collide
    should share one metrics instance instead.
    """
    order: List[str] = []
    meta: Dict[str, List[str]] = {}
    samples: Dict[str, List[str]] = {}

    def block(name: str) -> None:
        if name not in meta:
            meta[name] = []
            samples[name] = []
            order.append(name)

    for part in parts:
        current = ""  # bare samples before any metadata keep source order
        for line in part.splitlines():
            line = line.rstrip()
            if not line:
                continue
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                kind, name = line.split(None, 3)[1:3]
                block(name)
                current = name
                if not any(m.startswith(f"# {kind} ") for m in meta[name]):
                    meta[name].append(line)
            elif line.startswith("#"):
                continue  # stray comments / EOF markers from a source
            else:
                block(current)
                samples[current].append(line)
    lines: List[str] = []
    for name in order:
        lines.extend(meta[name])
        lines.extend(samples[name])
    return "\n".join(lines)


class SystemStatusServer:
    def __init__(
        self,
        *,
        host: str = "0.0.0.0",
        port: int = 0,
        lifecycle: Any = None,  # RequestLifecycle; None = process-global
        tracer: Any = None,  # utils/tracing.Tracer; None = process-global
        trajectory: Any = None,  # TrajectoryStore; None = process-global
    ) -> None:
        self.host = host
        self.port = port
        self._lifecycle = lifecycle
        self._tracer = tracer
        self._trajectory = trajectory
        self._engine_routes: Dict[str, EngineRoute] = {}
        self._health_sources: Dict[str, Callable[[], Tuple[bool, Any]]] = {}
        # Readiness sources (crash plane): /readyz is 200 only when EVERY
        # registered source reports ready. Liveness (/healthz, /live) is
        # process-up only — a restoring worker is alive but NOT ready, so
        # the kubelet keeps it out of service without restarting it.
        self._ready_sources: Dict[str, Callable[[], Tuple[bool, Any]]] = {}
        # (render fn, takes-openmetrics-kwarg) — classified once at
        # registration so the scrape path skips per-request reflection.
        self._metrics_sources: List[Tuple[Callable[[], str], bool]] = []
        self._lora_list: Optional[Callable[[], List[str]]] = None
        self._lora_load: Optional[Callable[[str, str], Awaitable[None]]] = None
        self._lora_unload: Optional[Callable[[str], Awaitable[None]]] = None
        # Device-plane debug sources: flight-recorder rings (name →
        # snapshot fn) and HBM-ledger samplers (name → category dict fn).
        self._flight_sources: List[Tuple[str, Callable[[], List[Any]]]] = []
        self._memory_sources: List[Tuple[str, Callable[[], Dict[str, int]]]] = []
        # Drain plane (runtime/drain.py): (start_fn(deadline_s) -> awaitable
        # status dict, status_fn() -> dict). Registered by register_drain.
        self._drain_start: Optional[Callable[..., Awaitable[Dict[str, Any]]]] = None
        self._drain_status: Optional[Callable[[], Dict[str, Any]]] = None
        self._profile_timers: set = set()  # strong refs to auto-stop tasks
        self._runtime_metrics_registered = False
        self._runner: Optional[web.AppRunner] = None

    # -- registration ------------------------------------------------------

    def register_engine_route(self, path: str, handler: EngineRoute) -> None:
        self._engine_routes[path.strip("/")] = handler

    def register_health(
        self, name: str, fn: Callable[[], Tuple[bool, Any]]
    ) -> None:
        self._health_sources[name] = fn

    def register_readiness(
        self, name: str, fn: Callable[[], Tuple[bool, Any]]
    ) -> None:
        """``fn() -> (ready, detail)``; /readyz is 503 until every source
        is ready. The worker registers its warm-restore + registration
        gate here (readiness split from liveness, ISSUE 10)."""
        self._ready_sources[name] = fn

    def register_metrics(self, fn: Callable[[], str]) -> None:
        """fn returns Prometheus exposition-format text."""
        self._metrics_sources.append((fn, _takes_openmetrics(fn)))

    def register_loras(self, list_fn, load_fn, unload_fn) -> None:
        self._lora_list = list_fn
        self._lora_load = load_fn
        self._lora_unload = unload_fn

    def register_drain(
        self,
        start_fn: Callable[..., Awaitable[Dict[str, Any]]],
        status_fn: Callable[[], Dict[str, Any]],
    ) -> None:
        """Wire the drain controller: ``POST /drain`` (and the preStop's
        ``GET /drain?start=1``) awaits ``start_fn(deadline_s=...)``;
        ``GET /drain`` returns ``status_fn()``."""
        self._drain_start = start_fn
        self._drain_status = status_fn

    def register_flight(
        self, name: str, fn: Callable[[], List[Any]]
    ) -> None:
        """fn returns a FlightRecorder snapshot (list of event dicts);
        /debug/flight merges every registered ring by timestamp."""
        self._flight_sources.append((name, fn))

    def register_memory(
        self, name: str, fn: Callable[[], Dict[str, int]]
    ) -> None:
        """fn returns {category: bytes}; /debug/memory groups by source.
        Sources named ``*_detail`` are informational breakdowns of bytes
        another source already accounts for — shown, but excluded from
        ``ledger_total_bytes`` (no double counting)."""
        self._memory_sources.append((name, fn))

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        # Device-plane runtime families (compile watcher + profiler) are
        # process-global like the lifecycle/tracer rings: every system
        # server exposes them. Guarded so a stop()/start() cycle doesn't
        # register the source twice.
        if not self._runtime_metrics_registered:
            from dynamo_tpu.runtime.device_observe import render_runtime_metrics
            from dynamo_tpu.runtime.kv_reuse_observe import render_kv_reuse_metrics
            from dynamo_tpu.runtime.liveness import render_fence_metrics
            from dynamo_tpu.runtime.trajectory import render_trajectory_metrics

            self.register_metrics(render_runtime_metrics)
            # Crash-plane process-global families (stale-incarnation drops
            # + restore duration/outcome): every process participates in
            # fencing, so every system server exposes them.
            self.register_metrics(render_fence_metrics)
            # SLO plane (ALL_SLO goodput/burn-rate/phase gauges): the
            # tracker is process-global like the lifecycle/tracer rings.
            self.register_metrics(render_trajectory_metrics)
            # KV-reuse plane (ALL_KVCACHE hit-rate/ROI/sketch gauges):
            # process-global, one sketch per process.
            self.register_metrics(render_kv_reuse_metrics)
            self._runtime_metrics_registered = True
        app = web.Application()
        app.router.add_get("/health", self._health)
        app.router.add_get("/live", self._live)
        # Probe split (deploy/pod_connector.py renders both): /healthz =
        # liveness (the event loop turns — restarting would not help a
        # slow restore), /readyz = readiness (restore done, endpoints
        # registered — route traffic here only past this gate).
        app.router.add_get("/healthz", self._live)
        app.router.add_get("/readyz", self._ready)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/debug/requests", self._debug_requests)
        app.router.add_get("/debug/requests/{id}", self._debug_request)
        app.router.add_get("/debug/traces", self._debug_traces)
        app.router.add_get("/debug/trajectory", self._debug_trajectories)
        app.router.add_get(
            "/debug/trajectory/{trace_id}", self._debug_trajectory
        )
        app.router.add_get("/debug/kvcache", self._debug_kvcache)
        app.router.add_get(
            "/debug/kvcache/prefixes", self._debug_kvcache_prefixes
        )
        app.router.add_get("/debug/memory", self._debug_memory)
        app.router.add_get("/debug/compiles", self._debug_compiles)
        app.router.add_get("/debug/flight", self._debug_flight)
        app.router.add_post("/debug/profile", self._debug_profile)
        app.router.add_get("/drain", self._drain_get)
        app.router.add_post("/drain", self._drain_post)
        app.router.add_route("*", "/engine/{path:.*}", self._engine)
        app.router.add_get("/v1/loras", self._loras_list)
        app.router.add_post("/v1/loras", self._loras_load)
        app.router.add_delete("/v1/loras/{name}", self._loras_unload)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        # Resolve the ephemeral port for port=0.
        server = site._server  # noqa: SLF001 - aiohttp exposes no accessor
        if server and server.sockets:
            self.port = server.sockets[0].getsockname()[1]
        logger.info("system status server on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    # -- handlers ----------------------------------------------------------

    async def _health(self, request: web.Request) -> web.Response:
        details: Dict[str, Any] = {}
        healthy = True
        for name, fn in self._health_sources.items():
            try:
                ok, detail = fn()
            except Exception as exc:  # a broken source is an unhealthy one
                ok, detail = False, f"health source error: {exc}"
            details[name] = detail
            healthy = healthy and ok
        status = 200 if healthy else 503
        return web.json_response(
            {"status": "healthy" if healthy else "unhealthy", "details": details},
            status=status,
        )

    async def _live(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _ready(self, request: web.Request) -> web.Response:
        details: Dict[str, Any] = {}
        ready = True
        for name, fn in self._ready_sources.items():
            try:
                ok, detail = fn()
            except Exception as exc:  # a broken source is a not-ready one
                ok, detail = False, f"readiness source error: {exc}"
            details[name] = detail
            ready = ready and ok
        return web.json_response(
            {"status": "ready" if ready else "not_ready", "details": details},
            status=200 if ready else 503,
        )

    async def _metrics(self, request: web.Request) -> web.Response:
        openmetrics = "application/openmetrics-text" in request.headers.get(
            "Accept", ""
        )
        parts = []
        for fn, takes_om in self._metrics_sources:
            try:
                if openmetrics and takes_om:
                    parts.append(fn(openmetrics=True))
                else:
                    parts.append(fn())
            except Exception:
                logger.exception("metrics source failed")
        text = _merge_expositions([p for p in parts if p])
        if openmetrics:
            return web.Response(
                text=text + "\n# EOF\n",
                content_type="application/openmetrics-text",
                charset="utf-8",
            )
        return web.Response(
            text=text + "\n",
            content_type="text/plain",
            charset="utf-8",
        )

    # -- debug surface (lifecycle timelines + trace ring) ------------------

    def _lifecycle_obj(self):
        if self._lifecycle is None:
            from dynamo_tpu.runtime.lifecycle import global_lifecycle

            self._lifecycle = global_lifecycle()
        return self._lifecycle

    def _tracer_obj(self):
        if self._tracer is None:
            from dynamo_tpu.utils.tracing import global_tracer

            self._tracer = global_tracer()
        return self._tracer

    async def _debug_requests(self, request: web.Request) -> web.Response:
        lc = self._lifecycle_obj()
        return web.json_response(
            {
                "slow_threshold_s": lc.slow_threshold_s,
                "requests": [tl.summary() for tl in lc.timelines()],
                "slow": [tl.request_id for tl in lc.slow_timelines()],
            }
        )

    async def _debug_request(self, request: web.Request) -> web.Response:
        rid = request.match_info["id"]
        tl = self._lifecycle_obj().get(rid)
        if tl is None:
            return web.json_response(
                {"error": f"no timeline for request {rid!r}"}, status=404
            )
        return web.json_response(tl.to_dict())

    async def _debug_traces(self, request: web.Request) -> web.Response:
        """Dump the span ring, optionally filtered: /debug/traces?trace_id=…
        returns only that trace (the exemplar-chasing path)."""
        want = request.query.get("trace_id")
        spans = self._tracer_obj().finished_spans()
        if want:
            spans = [s for s in spans if s.trace_id == want]
        return web.json_response({"spans": [s.to_dict() for s in spans]})

    # -- trajectory plane (runtime/trajectory.py) --------------------------

    def _trajectory_obj(self):
        if self._trajectory is None:
            from dynamo_tpu.runtime.trajectory import global_store

            self._trajectory = global_store()
        return self._trajectory

    async def _debug_trajectories(self, request: web.Request) -> web.Response:
        from dynamo_tpu.runtime.trajectory import trajectory_index

        return web.json_response(trajectory_index(self._trajectory_obj()))

    async def _debug_trajectory(self, request: web.Request) -> web.Response:
        from dynamo_tpu.runtime.trajectory import trajectory_view

        tid = request.match_info["trace_id"]
        stitched = trajectory_view(tid, self._trajectory_obj())
        if stitched is None:
            return web.json_response(
                {"error": f"no trajectory for trace {tid!r}"}, status=404
            )
        return web.json_response(stitched)

    # -- KV-reuse plane (runtime/kv_reuse_observe.py) ----------------------

    async def _debug_kvcache(self, request: web.Request) -> web.Response:
        from dynamo_tpu.runtime.kv_reuse_observe import kvcache_index

        try:
            top_k = int(request.query.get("top_k", "10"))
        except ValueError:
            top_k = 10
        return web.json_response(kvcache_index(top_k=top_k))

    async def _debug_kvcache_prefixes(
        self, request: web.Request
    ) -> web.Response:
        from dynamo_tpu.runtime.kv_reuse_observe import kvcache_prefixes

        try:
            k = int(request.query.get("k", "50"))
        except ValueError:
            k = 50
        return web.json_response(kvcache_prefixes(k=k))

    # -- device-plane debug surface (runtime/device_observe.py) ------------

    async def _debug_memory(self, request: web.Request) -> web.Response:
        from dynamo_tpu.runtime.device_observe import device_memory_stats

        sources: Dict[str, Dict[str, int]] = {}
        total = 0
        for name, fn in self._memory_sources:
            try:
                snap = fn()
            except Exception as exc:
                snap = {"error": f"{type(exc).__name__}: {exc}"}  # type: ignore[dict-item]
            sources[name] = snap
            if not name.endswith("_detail"):
                total += sum(
                    v for v in snap.values() if isinstance(v, int) and v > 0
                )
        body: Dict[str, Any] = {
            "sources": sources,
            "ledger_total_bytes": total,
            "devices": device_memory_stats(),
        }
        try:
            from dynamo_tpu.models.weight_cache import cache_usage

            # os.walk over the disk cache tiers off the event loop — this
            # loop also runs the engine tick; a cold/NFS cache walk here
            # would stall token streaming for the duration of the scrape.
            body["host_weight_cache"] = await asyncio.get_running_loop(
            ).run_in_executor(None, cache_usage)
        except Exception:  # keep the route alive without the models stack
            body["host_weight_cache"] = None
        # Cross-check where the backend reports real allocator numbers
        # (TPU does; CPU memory_stats is None): unaccounted = allocator
        # in-use minus everything the structural ledger can name. Only
        # computed for a SINGLE reporting device: the ledger counts each
        # logical array once, while N devices hold N physical copies of
        # replicated state — the naive multi-device difference would
        # report that replication as a phantom leak.
        reporting = [
            d for d in body["devices"]
            if isinstance(d, dict) and d.get("memory_stats")
        ]
        in_use = sum(
            d["memory_stats"].get("bytes_in_use", 0) for d in reporting
        )
        if in_use:
            body["device_bytes_in_use"] = in_use
            if len(reporting) == 1:
                body["unaccounted_bytes"] = in_use - total
            else:
                body["unaccounted_note"] = (
                    "multi-device: ledger bytes are logical (counted "
                    "once) while allocator bytes include per-device "
                    "replicas; no drift number computed"
                )
        return web.json_response(body)

    async def _debug_compiles(self, request: web.Request) -> web.Response:
        from dynamo_tpu.runtime.device_observe import global_compile_watcher

        return web.json_response(global_compile_watcher().snapshot())

    async def _debug_flight(self, request: web.Request) -> web.Response:
        """Merged flight-recorder rings, timestamp-ordered. Query params:
        ?limit=N (newest N after the merge), ?kind=dispatch (filter)."""
        events: List[Any] = []
        rings = []
        for name, fn in self._flight_sources:
            rings.append(name)
            try:
                events.extend(fn())
            except Exception:
                logger.exception("flight source %s failed", name)
        want_kind = request.query.get("kind")
        if want_kind:
            events = [e for e in events if e.get("kind") == want_kind]
        events.sort(key=lambda e: e.get("t_mono", 0.0))
        try:
            limit = int(request.query.get("limit", "0"))
        except ValueError:
            limit = 0
        if limit > 0:
            events = events[-limit:]
        return web.json_response({"rings": rings, "events": events})

    async def _debug_profile(self, request: web.Request) -> web.Response:
        from dynamo_tpu.runtime.device_observe import global_profiler

        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:
            body = {}
        if not isinstance(body, dict):
            body = {}
        action = str(body.get("action", "status"))
        profiler = global_profiler()
        if action == "start":
            # Validate BEFORE starting the trace: a bad 'seconds' after
            # start_trace would 500 while leaving an orphaned capture
            # active (and nothing to ever stop it).
            seconds: Optional[float] = None
            if body.get("seconds") is not None:
                try:
                    seconds = float(body["seconds"])
                except (TypeError, ValueError):
                    seconds = float("nan")
                # NaN fails the 0 < s check; inf would never fire.
                if not 0 < seconds < float("inf"):
                    return web.json_response(
                        {"error": f"bad seconds {body['seconds']!r} "
                                  "(need a positive finite number)"},
                        status=400,
                    )
            # start and stop run OFF the event loop: stop exports the trace
            # (seconds), and a worker whose loop is held that long misses
            # load reports and loses its lease.
            result = await asyncio.to_thread(profiler.start, body.get("dir"))
            if result.get("ok") and seconds:
                # Bounded capture: auto-stop keeps an operator's one-shot
                # POST from tracing forever when the stop call never comes.
                capture_gen = result.get("generation")

                async def _auto_stop() -> None:
                    await asyncio.sleep(seconds)
                    # Only stop OUR capture generation: a manual stop +
                    # fresh start during the sleep (even into the same
                    # dir) must not have ITS capture killed by this stale
                    # timer.
                    status = profiler.status()
                    if (
                        not status.get("active")
                        or status.get("generation") != capture_gen
                    ):
                        return
                    logger.info(
                        "auto-stopped profiler capture: %s",
                        await asyncio.to_thread(profiler.stop),
                    )

                # Hold a strong reference: the loop keeps only weak task
                # refs, and a GC'd timer would leave the capture unbounded.
                task = asyncio.get_running_loop().create_task(_auto_stop())
                self._profile_timers.add(task)
                task.add_done_callback(self._profile_timers.discard)
                result["auto_stop_s"] = seconds
            # A degraded (profiler-unavailable) start is the documented
            # graceful no-op — 200 with degraded:true, not an error; 409
            # is reserved for "a capture is already active".
            status = 200 if result.get("ok") or result.get("degraded") else 409
            return web.json_response(result, status=status)
        if action == "stop":
            result = await asyncio.to_thread(profiler.stop)
            status = 200 if result.get("ok") or result.get("degraded") else 409
            return web.json_response(result, status=status)
        if action == "status":
            return web.json_response(profiler.status())
        return web.json_response(
            {"error": f"unknown action {action!r} (start|stop|status)"},
            status=400,
        )

    # -- drain plane (runtime/drain.py) ------------------------------------

    async def _drain_get(self, request: web.Request) -> web.Response:
        """Drain status — or, with ``?start=1``, trigger-and-wait. The
        mutating GET exists for the k8s preStop hook, whose httpGet action
        only issues GETs; kubelet blocks on the response, which is exactly
        the preStop contract (pod deletion proceeds once drained)."""
        if self._drain_status is None:
            return web.json_response(
                {"error": "no drain controller registered"}, status=404
            )
        if request.query.get("start") in ("1", "true", "yes"):
            return await self._start_drain({})
        return web.json_response(self._drain_status())

    async def _drain_post(self, request: web.Request) -> web.Response:
        if self._drain_start is None:
            return web.json_response(
                {"error": "no drain controller registered"}, status=404
            )
        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:
            body = {}
        return await self._start_drain(body if isinstance(body, dict) else {})

    async def _start_drain(self, body: Dict[str, Any]) -> web.Response:
        deadline_s: Optional[float] = None
        if body.get("deadline_s") is not None:
            try:
                deadline_s = float(body["deadline_s"])
            except (TypeError, ValueError):
                return web.json_response(
                    {"error": f"bad deadline_s {body['deadline_s']!r}"},
                    status=400,
                )
        try:
            status = await self._drain_start(deadline_s=deadline_s)
        except Exception as exc:
            logger.exception("drain failed")
            return web.json_response({"error": repr(exc)}, status=500)
        return web.json_response(status)

    async def _engine(self, request: web.Request) -> web.Response:
        path = request.match_info["path"].strip("/")
        handler = self._engine_routes.get(path)
        if handler is None:
            return web.json_response(
                {"error": f"no engine route {path!r}",
                 "routes": sorted(self._engine_routes)},
                status=404,
            )
        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:
            body = {}
        try:
            status, payload = await handler(body if isinstance(body, dict) else {})
        except Exception as exc:
            logger.exception("engine route %s failed", path)
            return web.json_response({"error": str(exc)}, status=500)
        return web.json_response(payload, status=status)

    async def _loras_list(self, request: web.Request) -> web.Response:
        if self._lora_list is None:
            return web.json_response({"error": "LoRA not enabled"}, status=404)
        return web.json_response({"loras": self._lora_list()})

    async def _loras_load(self, request: web.Request) -> web.Response:
        if self._lora_load is None:
            return web.json_response({"error": "LoRA not enabled"}, status=404)
        try:
            body = await request.json()
            name, path = body["name"], body["path"]
        except Exception:
            return web.json_response(
                {"error": "body must be {'name': ..., 'path': ...}"}, status=400
            )
        try:
            await self._lora_load(name, path)
        except ValueError as exc:
            return web.json_response({"error": str(exc)}, status=409)
        except Exception as exc:
            logger.exception("LoRA load failed")
            return web.json_response({"error": str(exc)}, status=500)
        return web.json_response({"loaded": name}, status=201)

    async def _loras_unload(self, request: web.Request) -> web.Response:
        if self._lora_unload is None:
            return web.json_response({"error": "LoRA not enabled"}, status=404)
        name = request.match_info["name"]
        try:
            await self._lora_unload(name)
        except KeyError as exc:
            return web.json_response({"error": str(exc)}, status=404)
        return web.json_response({"unloaded": name})


def engine_stats_prometheus(stats: Dict[str, Any]) -> str:
    """Engine stats dict → Prometheus gauges with canonical names
    (ref: metrics/prometheus_names.rs — runtime/metric_names.py is the
    single place that defines them). Nested dict stats (the ``kvbm``
    sub-dict) flatten into ``<prefix>_<key>_<subkey>`` gauges instead of
    silently disappearing from the scrape."""
    from dynamo_tpu.runtime.metric_names import engine_gauge

    lines: List[str] = []

    def emit(name: str, value: float, source: str) -> None:
        lines.append(f"# HELP {name} Engine stat {source!r} (engine.stats())")
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {float(value)}")

    def numeric(value: Any) -> bool:
        return not isinstance(value, bool) and isinstance(value, (int, float))

    for key, value in stats.items():
        if isinstance(value, dict):
            for sub, sv in value.items():
                if numeric(sv):
                    emit(engine_gauge(f"{key}_{sub}"), sv, f"{key}.{sub}")
            continue
        if numeric(value):
            emit(engine_gauge(key), value, key)
    return "\n".join(lines)


def attach_engine(server: SystemStatusServer, engine: Any) -> None:
    """Register the native engine's admin surface on the system server
    (ref: the engine-routes registry in system_status_server.rs plus vllm
    handlers sleep/wake and LoRA load/unload). Tolerant of partial engines
    (the mocker, stubs): each route/metric source registers only when the
    engine exposes the matching surface, so a plain mock worker still gets
    /health, the /debug/* plane, and whatever stats it can report."""

    def has(name: str) -> bool:
        return callable(getattr(engine, name, None))

    async def _stats(body: Dict[str, Any]):
        return 200, engine.stats()

    async def _sleep(body: Dict[str, Any]):
        await engine.sleep(int(body.get("level", 1)))
        return 200, {"sleeping": True, "level": engine.sleep_level}

    async def _wake(body: Dict[str, Any]):
        await engine.wake()
        return 200, {"sleeping": False}

    async def _clear(body: Dict[str, Any]):
        return 200, {"cleared_blocks": engine.clear_kv_blocks()}

    async def _checkpoint(body: Dict[str, Any]):
        path = body.get("path")
        if not path:
            return 400, {"error": "body must include 'path'"}
        return 200, await engine.save_checkpoint(path)

    async def _restore(body: Dict[str, Any]):
        path = body.get("path")
        if not path:
            return 400, {"error": "body must include 'path'"}
        try:
            n = await engine.load_checkpoint(path)
        except (OSError, ValueError, KeyError, IndexError) as exc:
            # Malformed manifests surface as any of these (JSONDecodeError
            # is a ValueError; missing fields KeyError; short data arrays
            # IndexError) — all are bad-input 400s, not server faults.
            return 400, {"error": repr(exc)}
        return 200, {"restored_blocks": n}

    if has("stats"):
        server.register_engine_route("stats", _stats)
    if has("sleep"):
        server.register_engine_route("sleep", _sleep)
    if has("wake"):
        server.register_engine_route("wake", _wake)
    if has("clear_kv_blocks"):
        server.register_engine_route("clear_kv_blocks", _clear)
    if has("save_checkpoint"):
        server.register_engine_route("checkpoint", _checkpoint)
    if has("load_checkpoint"):
        server.register_engine_route("restore", _restore)

    def _engine_health():
        failure = getattr(engine, "_failure", None)
        if failure is not None:
            return False, f"engine failed: {failure}"
        level = getattr(engine, "sleep_level", 0)
        if level > 0:
            return True, f"asleep (level {level})"
        return True, "serving"

    server.register_health("engine", _engine_health)
    if has("stats"):
        server.register_metrics(
            lambda: engine_stats_prometheus(engine.stats())
        )
    if has("save_checkpoint") or has("load_checkpoint"):
        # Persisted-KV integrity counter (kvbm/integrity.py): process-
        # global, one registration per server — checkpoint CRC failures
        # and disk-tier spill failures land in the same family under
        # distinct source labels.
        from dynamo_tpu.kvbm.integrity import render_integrity_metrics

        server.register_metrics(render_integrity_metrics)
    step_metrics = getattr(engine, "step_metrics", None)
    if step_metrics is not None:
        step_metrics.register_metrics(server)

    # Device-plane sources (JaxEngine; mocks without them still attach):
    # flight rings → /debug/flight (+ per-kind event counters on /metrics),
    # HBM ledger → /debug/memory (+ per-category byte gauges).
    flight = getattr(engine, "flight", None)
    if flight is not None:
        server.register_flight(flight.name, flight.snapshot)
        server.register_metrics(flight.registry.render)
    runner_flight = getattr(getattr(engine, "runner", None), "flight", None)
    if runner_flight is not None:
        server.register_flight(runner_flight.name, runner_flight.snapshot)
        server.register_metrics(runner_flight.registry.render)
    hbm = getattr(engine, "hbm", None)
    if hbm is not None:
        server.register_memory("engine", hbm.snapshot)
        server.register_metrics(hbm.registry.render)
    pool_breakdown = getattr(engine, "kv_pool_bytes_breakdown", None)
    if pool_breakdown is not None:
        # Informational split of the ledger's kv_cache bytes (active vs
        # reusable-cached vs free) — "_detail" keeps it out of the total.
        server.register_memory("kv_pool_detail", pool_breakdown)

    async def _load(name: str, path: str) -> None:
        # Disk I/O + stacking + host→device transfer off the event loop —
        # a multi-second inline load would stall token streaming and the
        # discovery lease keep-alive.
        device = getattr(engine, "_device", None)
        if device is not None:
            await device(engine.load_lora, name, path)
        else:
            await asyncio.get_running_loop().run_in_executor(
                None, engine.load_lora, name, path
            )

    async def _unload(name: str) -> None:
        # Same device-thread routing as _load: under multihost the restack
        # op must serialize with in-flight decode mirroring.
        device = getattr(engine, "_device", None)
        if device is not None:
            await device(engine.unload_lora, name)
        else:
            engine.unload_lora(name)

    if has("lora_names") and has("load_lora") and has("unload_lora"):
        server.register_loras(engine.lora_names, _load, _unload)
