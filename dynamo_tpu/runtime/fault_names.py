"""Canonical fault-point names: ONE place declares every injection seam.

Mirror of runtime/metric_names.py for the fault plane (runtime/faults.py):
``fault_point(...)`` call sites import these constants, and the dynlint
DYN006 pass closes the loop in both directions — a point name used at a
seam must be declared here, and a declared point must have at least one
seam (a dead point is chaos coverage that silently stopped existing).

This module is loaded BY FILE PATH by the linter (no package import) and
must stay dependency-free — constants and tuples only.

Naming scheme: ``<subsystem>.<operation>[.<phase>]``.
"""

from __future__ import annotations

# -- request/event planes (runtime/network/tcp.py, runtime/events/zmq_plane.py)
NET_TCP_SEND = "net.tcp.send"
NET_TCP_RECV = "net.tcp.recv"
NET_ZMQ_SEND = "net.zmq.send"
NET_ZMQ_RECV = "net.zmq.recv"

# -- disaggregated KV transfer (disagg/handlers.py) ---------------------------
# One pull-side hit per received chunk, BEFORE the chunk is imported: an
# injection here models the wire dying mid-transfer with N chunks landed.
DISAGG_PULL_CHUNK = "disagg.pull.chunk"
# Export side: one hit per chunk gathered by the KvTransferHandler.
DISAGG_KV_EXPORT = "disagg.kv.export"
# Import side: one hit per chunk handed to the engine's scatter path.
DISAGG_KV_IMPORT = "disagg.kv.import"

# -- engine decode tick (engines/tpu/engine.py) -------------------------------
# Dispatch: after the sync payloads are built, before the device call — the
# adversarial spot, because the dirty-slot sets were already cleared and
# recovery must resync them from the mirrors (_abort_inflight).
ENGINE_TICK_DISPATCH = "engine.tick.dispatch"
# Reap: before the oldest in-flight burst's readback.
ENGINE_TICK_REAP = "engine.tick.reap"
# Tick budgeter (engines/tpu/tick_budget.py): one hit per budget
# ADJUSTMENT the AIMD controller is about to commit (shrink or grow), not
# per evaluation — an injection models the control law dying and MUST skip
# that adjustment cleanly (budget unchanged, streaks reset, skip counted),
# never corrupt the budget or take the tick loop down with it.
ENGINE_BUDGET_APPLY = "engine.budget.apply"

# -- discovery / health (runtime/distributed.py, runtime/health.py) -----------
DISCOVERY_LEASE_RENEW = "discovery.lease.renew"
HEALTH_CANARY = "health.canary"

# -- KVBM storage tiers (kvbm/tiers.py) ---------------------------------------
KVBM_TIER_READ = "kvbm.tier.read"
KVBM_TIER_WRITE = "kvbm.tier.write"

# -- KVBM speculative prefetch (kvbm/manager.py) ------------------------------
# One hit per speculative onboard walk, at the top of the prefetch task
# BEFORE any tier read or device scatter: an injection models the prefetch
# machinery dying outright — the lease must settle as wasted (outcome
# "error"), the pool must stay balanced, and admission must fall back to
# the serial onboard path untouched.
KVBM_PREFETCH = "kvbm.prefetch"

# -- drain plane (runtime/drain.py, engines/tpu/engine.py) --------------------
# Export side of a live handoff: one hit per detached sequence, BEFORE the
# device gather — an injection models the draining worker failing to read
# its own pool (the ladder must fall through to re-prefill migration).
DRAIN_HANDOFF_EXPORT = "drain.handoff.export"
# Import side: one hit per adoption attempt on the PEER, before any pool
# mutation — an injection models the receiving worker refusing/dying, which
# the source must absorb by trying the next peer or falling down the ladder.
DRAIN_HANDOFF_IMPORT = "drain.handoff.import"

# -- crash plane (runtime/liveness.py, engines/tpu/kv_checkpoint.py) ----------
# One hit per load report admitted by the liveness tracker: an injected
# failure models report loss between the wire and the tracker — N
# consecutive injections must trip the same suspect/dead machinery a
# crashed worker does (the fake-clock detection tests replay this).
LIVENESS_REPORT = "liveness.report"
# One hit at the top of a warm-restart checkpoint restore, before anything
# is read: an injection models the restore machinery failing outright —
# which MUST resolve to a logged cold start (counted cold_error), never a
# crash loop.
RESTORE_LOAD = "restore.load"

# -- planner / elasticity plane (planner/planner_core.py) ---------------------
# One hit per adjustment-interval observation, BEFORE the metrics source is
# read: an injection models the scrape (or the metrics pipeline) dying —
# the control loop must skip the interval and keep converging, never crash
# or act on a half-read snapshot.
PLANNER_OBSERVE = "planner.observe"
# One hit per plan handed to the connector, BEFORE any actuation: an
# injection models the actuation plane (k8s API, process supervisor,
# drain endpoints) refusing the plan — the loop must retry on its own
# cadence and the fleet must never be left half-actuated by the raise
# (the elastic controller's per-action error handling owns partial fleets).
PLANNER_APPLY = "planner.apply"

# -- trajectory plane (runtime/trajectory.py) ---------------------------------
# One hit per shipped span/event batch, BEFORE the event-plane publish: an
# injection models the telemetry path dying — the batch is counted dropped
# and serving continues untouched (observability must never take down the
# data plane; the shipper tests replay this).
TRAJECTORY_SHIP = "trajectory.ship"

# -- parser plane (parsers/jail.py) -------------------------------------------
# One hit per jail operation (each content delta fed, plus the finish at
# stream end): an injection models the tool-call parser dying mid-stream
# — which MUST surface as a terminal typed SSE error frame
# (error_kind=tool_call_parse), never a dropped stream (the chunk-fuzz
# chaos suite replays this bit-identically).
PARSER_JAIL_FEED = "parser.jail.feed"

# -- overload plane (runtime/overload.py) -------------------------------------
# One hit per QUEUED admission attempt, before the EDF wait: an injected
# timeout here expires exactly that request's queue budget — the
# deterministic mid-queue-expiry schedule the saturation tests replay
# (wall-clock deadline races can't).
OVERLOAD_ADMIT = "overload.admit"

ALL_FAULT_POINTS = (
    NET_TCP_SEND,
    NET_TCP_RECV,
    NET_ZMQ_SEND,
    NET_ZMQ_RECV,
    DISAGG_PULL_CHUNK,
    DISAGG_KV_EXPORT,
    DISAGG_KV_IMPORT,
    ENGINE_TICK_DISPATCH,
    ENGINE_TICK_REAP,
    ENGINE_BUDGET_APPLY,
    DISCOVERY_LEASE_RENEW,
    HEALTH_CANARY,
    KVBM_TIER_READ,
    KVBM_TIER_WRITE,
    KVBM_PREFETCH,
    DRAIN_HANDOFF_EXPORT,
    DRAIN_HANDOFF_IMPORT,
    LIVENESS_REPORT,
    RESTORE_LOAD,
    PLANNER_OBSERVE,
    PLANNER_APPLY,
    TRAJECTORY_SHIP,
    OVERLOAD_ADMIT,
    PARSER_JAIL_FEED,
)
