"""Canonical environment-variable registry.

Reference parity: lib/runtime/src/config/environment_names.rs (the DYN_*
namespace). All environment knobs used anywhere in dynamo_tpu are declared
here with defaults, parsers, owning subsystem, and documentation; modules
read through the registry constants' ``.get()`` so the name, default, and
parser live in exactly one place. ``python -m dynamo_tpu.cli env`` prints
the registry (``--markdown`` emits the docs/design_docs/config_knobs.md
reference table), and dynlint DYN008 enforces closure both directions:
no ad-hoc ``os.environ`` read of a DYN_TPU_* name anywhere else, no
declared knob without a reader.

This module is loaded BY FILE PATH by the linter and must stay
dependency-free (stdlib only).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

_REGISTRY: Dict[str, "EnvVar"] = {}


@dataclass(frozen=True)
class EnvVar:
    name: str
    default: Any
    parser: Callable[[str], Any]
    doc: str
    subsystem: str = ""

    def get(self) -> Any:
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        try:
            return self.parser(raw)
        except (ValueError, TypeError):
            return self.default


def _register(
    name: str, default: Any, parser: Callable[[str], Any], doc: str,
    subsystem: str,
) -> EnvVar:
    var = EnvVar(name, default, parser, doc, subsystem)
    _REGISTRY[name] = var
    return var


def _parse_bool(raw: str) -> bool:
    return raw.strip().lower() in ("1", "true", "yes", "on")


def env_str(name: str, default: str, doc: str = "", subsystem: str = "") -> EnvVar:
    return _REGISTRY.get(name) or _register(name, default, str, doc, subsystem)


def env_int(name: str, default: int, doc: str = "", subsystem: str = "") -> EnvVar:
    return _REGISTRY.get(name) or _register(name, default, int, doc, subsystem)


def env_float(name: str, default: float, doc: str = "", subsystem: str = "") -> EnvVar:
    return _REGISTRY.get(name) or _register(name, default, float, doc, subsystem)


def env_bool(name: str, default: bool, doc: str = "", subsystem: str = "") -> EnvVar:
    return _REGISTRY.get(name) or _register(name, default, _parse_bool, doc, subsystem)


def registry() -> Dict[str, EnvVar]:
    return dict(_REGISTRY)


def render_markdown() -> str:
    """The knob reference table (docs/design_docs/config_knobs.md body).

    Grouped by owning subsystem, sorted by name within; the checked-in
    doc is regenerated from this (``python -m dynamo_tpu.cli env
    --markdown``) and a tier-1 test pins doc == registry so they cannot
    drift.
    """
    lines = [
        "# Configuration knob reference",
        "",
        "Generated from `dynamo_tpu/config.py` — do not edit by hand.",
        "Regenerate with `python -m dynamo_tpu.cli env --markdown`.",
        "Every `DYN_TPU_*` environment read in the package goes through",
        "this registry (enforced by dynlint DYN008; see",
        "[static_analysis.md](static_analysis.md)).",
        "",
    ]
    by_subsystem: Dict[str, list] = {}
    for var in _REGISTRY.values():
        by_subsystem.setdefault(var.subsystem or "misc", []).append(var)
    for subsystem in sorted(by_subsystem):
        lines.append(f"## {subsystem}")
        lines.append("")
        lines.append("| Name | Default | Type | Description |")
        lines.append("|---|---|---|---|")
        for var in sorted(by_subsystem[subsystem], key=lambda v: v.name):
            ptype = getattr(var.parser, "__name__", "str")
            if ptype == "_parse_bool":
                ptype = "bool"
            default = repr(var.default)
            doc = " ".join(var.doc.split())
            lines.append(f"| `{var.name}` | `{default}` | {ptype} | {doc} |")
        lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Canonical knobs (ref: environment_names.rs). DYN_TPU_* namespace.
# ---------------------------------------------------------------------------

NAMESPACE = env_str(
    "DYN_TPU_NAMESPACE", "dynamo", "Default namespace for components",
    subsystem="runtime",
)
REQUEST_PLANE = env_str(
    "DYN_TPU_REQUEST_PLANE", "tcp",
    "Request plane for cross-process serving: tcp|http|local",
    subsystem="runtime",
)
DISCOVERY = env_str(
    "DYN_TPU_DISCOVERY", "memory",
    "Discovery backend: memory|file|discd (addr via DYN_TPU_DISCOVERY_ADDR)",
    subsystem="runtime",
)
DISCOVERY_ADDR = env_str(
    "DYN_TPU_DISCOVERY_ADDR", "127.0.0.1:6180",
    "discd service address or file-backend directory",
    subsystem="runtime",
)
EVENT_PLANE = env_str(
    "DYN_TPU_EVENT_PLANE", "zmq", "Event plane: memory|zmq",
    subsystem="runtime",
)
EVENT_PLANE_ADDR = env_str(
    "DYN_TPU_EVENT_PLANE_ADDR",
    "127.0.0.1:6181:6182",
    "ZMQ event broker address host:xsub_port:xpub_port",
    subsystem="runtime",
)
TCP_HOST = env_str(
    "DYN_TPU_TCP_HOST", "127.0.0.1",
    "Advertised host for the TCP request plane",
    subsystem="runtime",
)
LEASE_TTL = env_float(
    "DYN_TPU_LEASE_TTL", 10.0, "Discovery lease TTL seconds",
    subsystem="runtime",
)
KV_QUANT_AUTO_CTX = env_int(
    "DYN_TPU_KV_QUANT_AUTO_CTX", 512,
    "kv_cache_dtype=auto: quantize the KV cache to int8 when max_model_len "
    "reaches this (measured v5e break-even: int8 KV loses ~3.6 ms/step at "
    "ctx<=160 from scale DMAs, wins beyond a few hundred tokens and "
    "doubles pool capacity)",
    subsystem="engine",
)
FLIGHT_DUMP_DIR = env_str(
    "DYN_TPU_FLIGHT_DUMP_DIR", "",
    "Directory for engine flight-recorder JSON dumps on tick abort "
    "(empty = system temp dir)",
    subsystem="engine",
)
KV_BLOCK_SIZE = env_int(
    "DYN_TPU_KV_BLOCK_SIZE", 16,
    "KV cache block size in tokens (the worker/mocker --block-size "
    "default)",
    subsystem="engine",
)
LOG_LEVEL = env_str(
    "DYN_TPU_LOG", "info", "Log level (trace|debug|info|warn|error)",
    subsystem="logging",
)
LOG_JSON = env_bool(
    "DYN_TPU_LOG_JSON", False, "Emit JSONL structured logs",
    subsystem="logging",
)
HTTP_HOST = env_str(
    "DYN_TPU_HTTP_HOST", "0.0.0.0", "Frontend HTTP bind host",
    subsystem="frontend",
)
HTTP_PORT = env_int(
    "DYN_TPU_HTTP_PORT", 8000, "Frontend HTTP bind port",
    subsystem="frontend",
)
SYSTEM_PORT = env_int(
    "DYN_TPU_SYSTEM_PORT", 9090,
    "System status server port (/health /live /metrics)",
    subsystem="frontend",
)
ROUTER_TEMPERATURE = env_float(
    "DYN_TPU_ROUTER_TEMPERATURE", 0.0,
    "KV router softmax sampling temperature (0 = argmin)",
    subsystem="router",
)
ROUTER_OVERLAP_WEIGHT = env_float(
    "DYN_TPU_ROUTER_OVERLAP_WEIGHT", 1.0, "KV router overlap score weight",
    subsystem="router",
)
MIGRATION_LIMIT = env_int(
    "DYN_TPU_MIGRATION_LIMIT", 3,
    "Max per-request migrations to new workers on stream death",
    subsystem="llm",
)
MIGRATION_REPREFILL_CAP = env_int(
    "DYN_TPU_MIGRATION_REPREFILL_CAP", 131072,
    "Total re-prefill token budget across all migrations of one stream "
    "(caps the work a flapping worker set can burn per request)",
    subsystem="llm",
)
TOOL_JAIL_CAP_CHARS = env_int(
    "DYN_TPU_TOOL_JAIL_CAP_CHARS", 262144,
    "Tool-call jail unresolved-buffer cap (chars): generous for real "
    "calls, small enough that a marker bomb cannot balloon host RSS",
    subsystem="parsers",
)
# -- multi-host topology (parallel/multihost.py)
COORDINATOR = env_str(
    "DYN_TPU_COORDINATOR", "",
    "JAX multi-process coordinator address host:port (empty = single "
    "host; setting it opts the worker into the multihost env contract)",
    subsystem="parallel",
)
NUM_PROCESSES = env_int(
    "DYN_TPU_NUM_PROCESSES", 1,
    "Process count joining the multi-process JAX runtime",
    subsystem="parallel",
)
PROCESS_ID = env_int(
    "DYN_TPU_PROCESS_ID", 0,
    "This worker's process index in the multi-process JAX runtime",
    subsystem="parallel",
)
# -- disaggregated KV transfer (disagg/handlers.py)
PULL_ATTEMPTS = env_int(
    "DYN_TPU_PULL_ATTEMPTS", 3,
    "Bounded retry: attempts per decode-side KV pull (1 = single-shot)",
    subsystem="disagg",
)
PULL_BACKOFF_S = env_float(
    "DYN_TPU_PULL_BACKOFF_S", 0.05,
    "Exponential backoff base between pull attempts (base x 2^(n-1), "
    "capped)",
    subsystem="disagg",
)
PULL_TIMEOUT_S = env_float(
    "DYN_TPU_PULL_TIMEOUT_S", 30.0,
    "Per-attempt pull timeout when the request carries no deadline; with "
    "one, each attempt gets min(this, time remaining)",
    subsystem="disagg",
)
BREAKER_OPEN_AFTER = env_int(
    "DYN_TPU_BREAKER_OPEN_AFTER", 3,
    "Consecutive pull failures from one prefill source before the "
    "(src -> worker) circuit opens",
    subsystem="disagg",
)
BREAKER_COOLDOWN_S = env_float(
    "DYN_TPU_BREAKER_COOLDOWN_S", 30.0,
    "Open-circuit cooldown before the next pull is admitted as the "
    "half-open probe",
    subsystem="disagg",
)
KV_CHUNK_BYTES = env_int(
    "DYN_TPU_KV_CHUNK_BYTES", 8 << 20,
    "KV transfer chunk size: one message blocks the event loop and "
    "doubles peak host memory; ~8 MB chunks pipeline gather/wire/scatter",
    subsystem="disagg",
)
# -- overload armor (runtime/overload.py; docs/design_docs/overload_control.md)
OVERLOAD_MAX_CONCURRENCY = env_int(
    "DYN_TPU_OVERLOAD_MAX_CONCURRENCY", 256,
    "Frontend streams generating concurrently; excess queues (EDF)",
    subsystem="overload",
)
OVERLOAD_MAX_QUEUE = env_int(
    "DYN_TPU_OVERLOAD_MAX_QUEUE", 1024,
    "Bounded admission queue depth; beyond it requests shed 429",
    subsystem="overload",
)
OVERLOAD_MAX_QUEUE_DELAY_S = env_float(
    "DYN_TPU_OVERLOAD_MAX_QUEUE_DELAY_S", 30.0,
    "Shed when predicted queue delay exceeds this (429 + Retry-After)",
    subsystem="overload",
)
OVERLOAD_DEFAULT_DEADLINE_S = env_float(
    "DYN_TPU_OVERLOAD_DEFAULT_DEADLINE_S", 0.0,
    "Deadline stamped on requests that carry none (0 = unbounded)",
    subsystem="overload",
)
OVERLOAD_ITL_SLA_MS = env_float(
    "DYN_TPU_OVERLOAD_ITL_SLA_MS", 0.0,
    "p50 ITL SLA driving healthy->brownout->shed (0 = brownout disabled; "
    "admission caps still enforce)",
    subsystem="overload",
)
OVERLOAD_BROWNOUT_MAX_TOKENS = env_int(
    "DYN_TPU_OVERLOAD_BROWNOUT_MAX_TOKENS", 256,
    "max_tokens clamp applied while browned out",
    subsystem="overload",
)
# -- trajectory plane (runtime/trajectory.py; docs/design_docs/request_trajectory.md)
TRAJECTORY_RECENT = env_int(
    "DYN_TPU_TRAJECTORY_RECENT", 256,
    "Recent request trajectories retained for GET /debug/trajectory",
    subsystem="trajectory",
)
TRAJECTORY_SLOW = env_int(
    "DYN_TPU_TRAJECTORY_SLOW", 64,
    "Slow/errored trajectory summaries retained past recent-ring eviction",
    subsystem="trajectory",
)
TRAJECTORY_SHIP_INTERVAL_S = env_float(
    "DYN_TPU_TRAJECTORY_SHIP_S", 0.5,
    "Worker-side finished-span batch flush cadence onto the event plane",
    subsystem="trajectory",
)
SLO_TTFT_MS = env_float(
    "DYN_TPU_SLO_TTFT_MS", 0.0,
    "TTFT SLA for the goodput/burn-rate gauges (0 = SLO tracking off)",
    subsystem="trajectory",
)
SLO_ITL_MS = env_float(
    "DYN_TPU_SLO_ITL_MS", 0.0,
    "Mean-ITL SLA for the goodput/burn-rate gauges (0 = SLO tracking off)",
    subsystem="trajectory",
)
SLO_TARGET = env_float(
    "DYN_TPU_SLO_TARGET", 0.99,
    "SLO target the burn-rate denominates against (error budget = 1 - target)",
    subsystem="trajectory",
)
# -- crash plane (runtime/liveness.py; docs/design_docs/fault_tolerance.md)
LOAD_REPORT_INTERVAL_S = env_float(
    "DYN_TPU_LOAD_REPORT_INTERVAL_S", 1.0,
    "Worker load-report publish cadence (router/publisher.py "
    "LoadPublisher). The liveness detection budget is denominated in "
    "these intervals, so shrinking it tightens dead-worker detection",
    subsystem="liveness",
)
LIVENESS_INTERVAL_S = env_float(
    "DYN_TPU_LIVENESS_INTERVAL_S", 1.0,
    "Expected worker load-report cadence the frontend's liveness tracker "
    "judges missed intervals against (match the LoadPublisher interval)",
    subsystem="liveness",
)
LIVENESS_SUSPECT_AFTER = env_int(
    "DYN_TPU_LIVENESS_SUSPECT_AFTER", 2,
    "Missed load-report intervals before a worker is SUSPECT",
    subsystem="liveness",
)
LIVENESS_DEAD_AFTER = env_int(
    "DYN_TPU_LIVENESS_DEAD_AFTER", 5,
    "Missed load-report intervals before a worker is DEAD: drop_worker "
    "reconciliation runs and its in-flight streams abort into migration "
    "(detection-to-migration is bounded by dead_after x interval)",
    subsystem="liveness",
)
WORKER_ID = env_int(
    "DYN_TPU_WORKER_ID", 0,
    "Stable worker identity across restarts (0 = random per start). A "
    "restarted worker re-registers under the SAME id with a fresh "
    "incarnation so warm rejoin and incarnation fencing line up",
    subsystem="liveness",
)
GRACE_PERIOD = env_float(
    "DYN_TPU_GRACE_PERIOD", 30.0, "Graceful-shutdown drain seconds",
    subsystem="liveness",
)
DRAIN_DEADLINE_S = env_float(
    "DYN_TPU_DRAIN_DEADLINE_S", 30.0,
    "Live-handoff drain budget (SIGTERM / POST /drain / preStop): handoffs "
    "not completed by then fall back to re-prefill migration",
    subsystem="liveness",
)
DRAIN_HANDOFF_CONCURRENCY = env_int(
    "DYN_TPU_DRAIN_HANDOFF_CONCURRENCY", 4,
    "Concurrent handoff ships per drain: detach/export serialize at the "
    "engine's reconciled boundary, but the peer accept-ack round trips "
    "are independent — pipelining them keeps a full worker's drain "
    "inside the deadline on a slow link",
    subsystem="liveness",
)

# -- request lifecycle plane (runtime/lifecycle.py)
SLOW_REQUEST_S = env_float(
    "DYN_TPU_SLOW_REQUEST_S", 30.0,
    "Requests slower than this (seconds, received→done) are retained in "
    "the slow-request capture ring",
    subsystem="lifecycle",
)
LIFECYCLE_RECENT = env_int(
    "DYN_TPU_LIFECYCLE_RECENT", 256,
    "Recent-request timelines retained for GET /debug/requests",
    subsystem="lifecycle",
)
LIFECYCLE_SLOW = env_int(
    "DYN_TPU_LIFECYCLE_SLOW", 64,
    "Slow-request timelines retained past recent-ring eviction",
    subsystem="lifecycle",
)
# -- KV reuse observability (runtime/kv_reuse_observe.py)
KV_SKETCH_CAPACITY = env_int(
    "DYN_TPU_KV_SKETCH_CAPACITY", 4096,
    "Prefix-popularity sketch capacity (tracked prefixes; space-saving "
    "min-replacement keeps memory bounded regardless of distinct "
    "prefixes)",
    subsystem="kv-reuse",
)
KV_SKETCH_HALF_LIFE_S = env_float(
    "DYN_TPU_KV_SKETCH_HALF_LIFE_S", 600.0,
    "Popularity decay half-life in seconds (recency weighting of the "
    "prefix sketch; 0 disables decay)",
    subsystem="kv-reuse",
)
# -- auditing / tracing / native seams
AUDIT_POLICY = env_str(
    "DYN_TPU_AUDIT", "off",
    "Request auditing: off | stderr | file:<path> (JSONL records)",
    subsystem="frontend",
)
NATIVE = env_bool(
    "DYN_TPU_NATIVE", True,
    "Use C++ native components when buildable (0 = pure-Python fallbacks)",
    subsystem="native",
)
TRACE_FILE = env_str(
    "DYN_TPU_TRACE_FILE", "",
    "Append finished spans as JSONL to this path ('' disables file "
    "export)",
    subsystem="tracing",
)
OTLP_ENDPOINT = env_str(
    "DYN_TPU_OTLP_ENDPOINT", "",
    "OTLP/HTTP traces endpoint (e.g. http://collector:4318/v1/traces); "
    "'' disables the wire exporter",
    subsystem="tracing",
)
OTLP_SERVICE = env_str(
    "DYN_TPU_OTLP_SERVICE", "dynamo-tpu",
    "service.name resource attribute on exported spans",
    subsystem="tracing",
)

# The closed set dynlint DYN008 checks both directions: every DYN_TPU_*
# env read in the package resolves to one of these, every entry has a
# reader. Declarations above register in order, so this tuple is total
# by construction — subsystem modules alias these constants
# (``PERF_WINDOW = config.PERF_WINDOW``) instead of registering their
# own, so `dynamo-tpu env` and the generated reference table see the
# whole namespace without importing the serving stack.
ALL_KNOBS = tuple(_REGISTRY.values())
