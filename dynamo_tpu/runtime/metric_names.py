"""Canonical metric names: ONE place defines every Prometheus name.

Reference parity: lib/runtime/src/metrics/prometheus_names.rs — the
reference centralizes metric-name constants so dashboards, alerts, the
planner's scrape source, and the emitting components can never drift
apart. Same rule here: emitters (http/metrics.py, runtime/system_server.py)
and consumers (planner/metrics_source.py) import these constants instead
of repeating strings.

Naming scheme: ``dynamo_tpu_<subsystem>_<metric>[_unit][_total]``.
"""

from __future__ import annotations

# -- frontend (http/metrics.py) ---------------------------------------------
FRONTEND_PREFIX = "dynamo_tpu_frontend"
FRONTEND_REQUESTS_TOTAL = f"{FRONTEND_PREFIX}_requests_total"
FRONTEND_INFLIGHT = f"{FRONTEND_PREFIX}_inflight_requests"
FRONTEND_REQUEST_DURATION = f"{FRONTEND_PREFIX}_request_duration_seconds"
FRONTEND_TTFT = f"{FRONTEND_PREFIX}_time_to_first_token_seconds"
FRONTEND_ITL = f"{FRONTEND_PREFIX}_inter_token_latency_seconds"
FRONTEND_OUTPUT_TOKENS_TOTAL = f"{FRONTEND_PREFIX}_output_tokens_total"
FRONTEND_INPUT_TOKENS_TOTAL = f"{FRONTEND_PREFIX}_input_tokens_total"

# -- engine (runtime/system_server.py engine_stats_prometheus) ---------------
ENGINE_PREFIX = "dynamo_tpu_engine"


def engine_gauge(stat_key: str) -> str:
    """Engine stats-dict key → canonical gauge name (system server)."""
    return f"{ENGINE_PREFIX}_{stat_key}"


ENGINE_ACTIVE_SEQS = engine_gauge("active_seqs")
ENGINE_WAITING = engine_gauge("waiting")
ENGINE_KV_USAGE = engine_gauge("kv_usage")
ENGINE_FREE_BLOCKS = engine_gauge("free_blocks")
ENGINE_CACHED_BLOCKS = engine_gauge("cached_blocks")
ENGINE_TOTAL_BLOCKS = engine_gauge("total_blocks")
ENGINE_DECODE_STEPS = engine_gauge("decode_steps")
ENGINE_PREFILL_TOKENS = engine_gauge("prefill_tokens")
ENGINE_GENERATED_TOKENS = engine_gauge("generated_tokens")
ENGINE_SLEEP_LEVEL = engine_gauge("sleep_level")
ENGINE_PIPELINE_DEPTH = engine_gauge("pipeline_depth")
ENGINE_INFLIGHT_BURSTS = engine_gauge("inflight_bursts")
ENGINE_PREEMPTIONS = engine_gauge("preemptions")
# Overload plane inputs (engine admission backpressure): waiting-queue
# depth + the admission refusal watermark (ride load reports router-ward)
# and requests shed at dequeue with an expired deadline.
ENGINE_QUEUE_DEPTH = engine_gauge("queue_depth")
ENGINE_KV_HIGH_WATERMARK = engine_gauge("kv_high_watermark")
ENGINE_DEADLINE_SHEDS = engine_gauge("deadline_sheds")
# Drain plane input: 1 while the engine refuses new admissions because a
# live handoff drain is in progress (rides load reports router-ward so
# KvScheduler stops placing work here immediately).
ENGINE_DRAINING = engine_gauge("draining")
# Tick budgeter (engines/tpu/tick_budget.py): the EFFECTIVE per-tick
# prefill token budget (0 = budgeter off, unbounded admission), the
# budgeter state (0 off, 1 throughput/ceiling, 2 adaptive, 3 floor /
# brownout-squeezed), the compile-time chunk size the budget is consumed
# in, and watermark-hold rollovers (budget returned to decode, not
# idled). A silent budget collapse shows up HERE, not as a mystery TTFT
# regression.
ENGINE_PREFILL_BUDGET_TOKENS = engine_gauge("prefill_budget_tokens")
ENGINE_BUDGET_STATE = engine_gauge("budget_state")
ENGINE_PREFILL_CHUNK_TOKENS = engine_gauge("prefill_chunk_tokens")
ENGINE_BUDGET_ROLLOVERS = engine_gauge("budget_rollovers")

# -- engine step loop (engines/metrics.py EngineStepMetrics) -----------------
ENGINE_STEP_DURATION = f"{ENGINE_PREFIX}_step_duration_seconds"
ENGINE_BATCH_OCCUPANCY = f"{ENGINE_PREFIX}_batch_occupancy"
ENGINE_STEP_PREFILL_TOKENS = f"{ENGINE_PREFIX}_prefill_tokens_per_step"
ENGINE_STEP_DECODE_TOKENS = f"{ENGINE_PREFIX}_decode_tokens_per_step"
# Decode-tick pipelining (engines/tpu/engine.py dispatch/reap split):
# bursts in flight at each dispatch.
ENGINE_INFLIGHT_DEPTH = f"{ENGINE_PREFIX}_inflight_depth"
# The scheduler tick seen from inside (EngineStepMetrics.phase): wall time of
# the scheduler loop by phase — the phases are exclusive and partition the
# loop's wall time — and one observation per loop iteration that did not go
# idle. Each phase is also a jax.profiler.TraceAnnotation of the same name,
# so a profiler capture carries it on the device trace's clock.
ENGINE_TICK_PHASE = f"{ENGINE_PREFIX}_tick_phase_seconds"
ENGINE_TICK = f"{ENGINE_PREFIX}_tick_seconds"
# What the device waits for (label phase): wall time of the loop's segments
# that are not a device wait and began with no decode burst in flight, the
# device holding nothing the engine had not yet read back. A LOWER bound on
# device idleness: a burst that ended before the host came to read it, and
# gaps between operations inside a program, are invisible to the host.
ENGINE_DEVICE_STARVED_SECONDS_TOTAL = (
    f"{ENGINE_PREFIX}_device_starved_seconds_total"
)
# What a decoding row waits for. One observation per interval between two
# reaped bursts that each gave a frame to a live row (kind=prefill when the
# loop awaited a prefill step in between, else decode); rows that got the
# frame x the interval's seconds in each tick phase (label phase: over a
# window the series partition the time live rows spent waiting for
# frames); intervals over engines/metrics.py FRAME_STALL_SECONDS (label
# kind), each also a ``stall`` flight record and a WARNING line.
ENGINE_FRAME_INTERVAL = f"{ENGINE_PREFIX}_frame_interval_seconds"
ENGINE_FRAME_ROW_SECONDS_TOTAL = f"{ENGINE_PREFIX}_frame_row_seconds_total"
ENGINE_FRAME_STALLS_TOTAL = f"{ENGINE_PREFIX}_frame_stalls_total"
# Request phases at the stamps _export_phase_spans already reads
# (phase=queue|prefill|decode), one observation per finished stream, and
# the decode phase's tokens (generated - 1) beside them: deltas of sum and
# count over a window, which the rolling SLO gauges cannot give.
ENGINE_REQUEST_PHASE = f"{ENGINE_PREFIX}_request_phase_seconds"
ENGINE_REQUEST_DECODE_TOKENS_TOTAL = (
    f"{ENGINE_PREFIX}_request_decode_tokens_total"
)
# What the decode paged-attention kernel's grid is made of, per dispatched
# burst: the pages the active rows' contexts reach (live) and the slots of
# the dispatched table, max_num_seqs x width bucket (what a grid over the
# table would visit). live / slots = the share of the table that is work.
ENGINE_DECODE_LIVE_PAGES_TOTAL = f"{ENGINE_PREFIX}_decode_live_pages_total"
ENGINE_DECODE_TABLE_SLOTS_TOTAL = f"{ENGINE_PREFIX}_decode_table_slots_total"
# Expert load of a hybrid model's decode bursts, bumped once per reaped burst
# with sums over the burst's steps and expert layers (they come back with the
# burst's tokens): held experts that got a token / expert slots (layer-steps x
# experts held) = the share of the held experts' weights a step streams; most
# tokens on one held expert / mean tokens on a held expert = the imbalance.
ENGINE_MOE_EXPERTS_HIT_TOTAL = f"{ENGINE_PREFIX}_moe_experts_hit_total"
ENGINE_MOE_EXPERT_SLOTS_TOTAL = f"{ENGINE_PREFIX}_moe_expert_slots_total"
ENGINE_MOE_MAX_EXPERT_TOKENS_TOTAL = f"{ENGINE_PREFIX}_moe_max_expert_tokens_total"
ENGINE_MOE_MEAN_EXPERT_TOKENS_TOTAL = f"{ENGINE_PREFIX}_moe_mean_expert_tokens_total"
# Top-k choices the live rows of reaped decode bursts made, summed over steps
# and expert layers (label held=1|0): those that fell on experts held here
# (what ``moe_mean_expert_tokens`` sums, times the held count) and those on
# the absent share of the router's width; held / both = this chip's share of
# the deployment's routed work, 50% for a balanced half.
ENGINE_MOE_ASSIGNMENTS_TOTAL = f"{ENGINE_PREFIX}_moe_assignments_total"
# Live prompt tokens of reaped prefill steps that passed expert layers, by the
# form the step's STATIC shape [rows bucket, chunk bucket] gives
# (ops/moe.form_of: label form=hit_list|dense|grouped_kernel|grouped_xla; a
# chunk of up to 256 tokens is hit_list or, over many small experts,
# grouped_kernel); all four from start-up in an
# engine with expert layers: grouped_kernel / all four = how often a prefill
# step's experts run through the grouped Pallas kernel.
ENGINE_MOE_PREFILL_TOKENS_TOTAL = f"{ENGINE_PREFIX}_moe_prefill_tokens_total"
# Rows of recurrent state the steps of dispatched decode bursts pass (label
# state=updated|slots), a burst adding steps x slots to ``slots`` and to
# ``updated`` steps x its live rows where the step's recurrence runs through
# the live-row kernel (ops/pallas/ssd_step.py), steps x slots where it keeps
# the XLA form over every slot: updated / slots = the share of the slots'
# state a decode step reads and writes (100% says the kernel did not engage).
# Both series at 0 from start-up in an engine with recurrent layers.
ENGINE_SSM_DECODE_ROWS_TOTAL = f"{ENGINE_PREFIX}_ssm_decode_rows_total"
# Steps of dispatched decode bursts by the branch their sampler takes (label
# path=greedy|full): a burst adds --decode-steps to ``greedy`` when none of its
# live rows has temperature > 0 (ops/sampling.sample_tokens returns the arg-max
# of the logits and runs nothing else), else to ``full`` (candidate search,
# sort, filters and noise over every slot). The host applies the program's own
# predicate to the burst's rows: a dead slot's stale temperature counts in
# neither. greedy / both = how often the sampler's work is skipped. Both series
# at 0 from start-up.
ENGINE_SAMPLER_DECODE_STEPS_TOTAL = f"{ENGINE_PREFIX}_sampler_decode_steps_total"
# Positions of dispatched prefill steps (label kind=live|padded): a step of
# static shape [rows bucket, chunk bucket] computes every one of its rows x
# chunk positions; ``live`` are the prompt tokens among them (the rows' lens),
# ``padded`` the rest. live / both = the share of a prefill program's work
# that is prompt: what admission's grouping of unequal rows
# (admission.prefill_partition) raises. Both series at 0 from start-up. The
# dispatches themselves by shape (labels rows, chunk: the two buckets), a
# series from its first dispatch on.
ENGINE_PREFILL_POSITIONS_TOTAL = f"{ENGINE_PREFIX}_prefill_positions_total"
ENGINE_PREFILL_DISPATCHES_TOTAL = f"{ENGINE_PREFIX}_prefill_dispatches_total"
# Recurrent (state-space) state beside the paged K/V: slots are one per decode
# row, snapshots are the block-aligned state copies prefix reuse resumes from
# (label state=used|total).
ENGINE_SSM_STATE_SLOTS = f"{ENGINE_PREFIX}_ssm_state_slots"
ENGINE_SSM_SNAPSHOTS = f"{ENGINE_PREFIX}_ssm_snapshots"
ENGINE_SSM_SNAPSHOT_HITS_TOTAL = f"{ENGINE_PREFIX}_ssm_snapshot_hits_total"
ENGINE_SSM_SNAPSHOT_EVICTIONS_TOTAL = f"{ENGINE_PREFIX}_ssm_snapshot_evictions_total"
# Two page groups for one sequence (a model that mixes sliding-window and full
# attention layers; never touched otherwise). The gauge: blocks of each group
# (label group=full|window, state=used|cached|total). Released: window-group
# pages given back behind a running sequence's window. Dead / held, per
# dispatched decode burst: window-group pages a live row holds wholly behind
# its window / all it holds (dead / held = what the release leaves behind).
# Window live pages: the window-group pages the burst's rows attend over
# (``decode_live_pages_total`` counts the full group's). Cut: prefix hits
# shortened or lost because the window group no longer held the window in
# front of the resume position.
ENGINE_KV_GROUP_BLOCKS = f"{ENGINE_PREFIX}_kv_group_blocks"
ENGINE_WINDOW_PAGES_RELEASED_TOTAL = f"{ENGINE_PREFIX}_window_pages_released_total"
ENGINE_WINDOW_PAGES_DEAD_TOTAL = f"{ENGINE_PREFIX}_window_pages_dead_total"
ENGINE_WINDOW_PAGES_HELD_TOTAL = f"{ENGINE_PREFIX}_window_pages_held_total"
ENGINE_DECODE_WINDOW_LIVE_PAGES_TOTAL = f"{ENGINE_PREFIX}_decode_window_live_pages_total"
ENGINE_PREFIX_HITS_CUT_BY_WINDOW_TOTAL = f"{ENGINE_PREFIX}_prefix_hits_cut_by_window_total"
# Block-sparse attention chosen by an indexer (a model with sparse attention
# layers; never touched otherwise), per dispatched decode burst and per sparse
# layer: the pages the layer's kernel visits for the burst's rows (a row of
# ``dense_len`` tokens or more: the ``topk`` its indexer selects; a shorter
# one: all it holds) against the pages those rows hold, and the rows on each
# path (label path=sparse|dense). selected / live = what the selection leaves
# of the dense read.
ENGINE_SPARSE_PAGES_SELECTED_TOTAL = f"{ENGINE_PREFIX}_sparse_pages_selected_total"
ENGINE_SPARSE_PAGES_LIVE_TOTAL = f"{ENGINE_PREFIX}_sparse_pages_live_total"
ENGINE_SPARSE_ROWS_TOTAL = f"{ENGINE_PREFIX}_sparse_rows_total"

# The tick-phase vocabulary: every name EngineStepMetrics.phase accepts, in
# exactly one class. ``device_wait``: the loop awaits the device thread
# (a prefill step, a burst's readback, the pipeline drained ahead of
# admission); ``idle``: nothing to do (the wake wait, error backoff);
# ``host``: everything else the loop does on the CPU.
TICK_PHASES_HOST = (
    "tick.sched",  # loop bookkeeping between the phases below
    "tick.admit",  # queue pop, prefix match, pool allocation, KVBM onboard
    "tick.prefill_build",  # a chunk round's numpy arrays and its results
    "tick.install",  # commit blocks, slot state, the first token out
    "tick.decode_build",  # _prepare_decode, dirty-slot and table payloads
    "tick.decode_dispatch",  # sync + enqueue of one burst on the device thread
    "tick.emit",  # stop conditions, one output per row, stats, perf ledger
)
TICK_PHASES_DEVICE_WAIT = (
    "tick.drain",  # readback of in-flight bursts ahead of admission
    "tick.prefill_wait",  # await of one prefill step
    "tick.decode_wait",  # await of the oldest burst's readback
)
TICK_PHASES_IDLE = ("tick.idle",)
TICK_PHASES = TICK_PHASES_HOST + TICK_PHASES_DEVICE_WAIT + TICK_PHASES_IDLE
# Plain annotations (no counter) on the device thread, where the device
# calls really run: the spans a gap attribution reads.
DEVICE_SPANS = (
    "device.prefill_step", "device.decode_dispatch", "device.decode_read",
)
REQUEST_PHASES = ("queue", "prefill", "decode")
FRAME_KINDS = ("decode", "prefill")

# -- router (router/router.py KvRouter + router/scheduler.py) ----------------
ROUTER_PREFIX = "dynamo_tpu_router"
ROUTER_DECISIONS_TOTAL = f"{ROUTER_PREFIX}_decisions_total"
ROUTER_OVERLAP_BLOCKS = f"{ROUTER_PREFIX}_overlap_blocks"
ROUTER_WORKER_LOAD_BLOCKS = f"{ROUTER_PREFIX}_worker_load_blocks"
ROUTER_WORKER_KV_USAGE = f"{ROUTER_PREFIX}_worker_kv_usage"
ROUTER_KV_EVENTS_TOTAL = f"{ROUTER_PREFIX}_kv_events_total"
# Link-cost model input: EWMA transfer bandwidth per (src prefill worker,
# dst decode worker) pair, as the scheduler's select_worker sees it.
ROUTER_LINK_BANDWIDTH = f"{ROUTER_PREFIX}_link_bandwidth_bytes_per_s"

# -- KVBM (kvbm/manager.py TieredKvManager + kvbm/connector.py) --------------
KVBM_PREFIX = "dynamo_tpu_kvbm"
KVBM_OFFLOAD_BLOCKS_TOTAL = f"{KVBM_PREFIX}_offload_blocks_total"
KVBM_OFFLOAD_BYTES_TOTAL = f"{KVBM_PREFIX}_offload_bytes_total"
KVBM_ONBOARD_BLOCKS_TOTAL = f"{KVBM_PREFIX}_onboard_blocks_total"
KVBM_ONBOARD_BYTES_TOTAL = f"{KVBM_PREFIX}_onboard_bytes_total"
KVBM_LOOKUP_HITS_TOTAL = f"{KVBM_PREFIX}_lookup_hits_total"
KVBM_LOOKUP_MISSES_TOTAL = f"{KVBM_PREFIX}_lookup_misses_total"
KVBM_TIER_BLOCKS = f"{KVBM_PREFIX}_tier_blocks"
KVBM_TIER_EVICTIONS_TOTAL = f"{KVBM_PREFIX}_tier_evictions_total"
KVBM_POOL_PRESSURE_TRUNCATIONS_TOTAL = (
    f"{KVBM_PREFIX}_pool_pressure_truncations_total"
)
KVBM_FAILED_LOADS_TOTAL = f"{KVBM_PREFIX}_failed_loads_total"
# Integrity: persisted KV (checkpoint manifest arrays, disk-tier npz
# spills) whose CRC32 did not match on restore — counted as a miss, never
# installed, never a crash. Labeled by source (checkpoint | disk).
KVBM_RESTORE_CORRUPTION_TOTAL = f"{KVBM_PREFIX}_restore_corruption_total"
# Tier-flow latency (kv_reuse_observability.md): one offload burst /
# onboard walk, wall time. Direction is the family; the tier the blocks
# landed in / came from rides the {tier} label.
KVBM_OFFLOAD_DURATION = f"{KVBM_PREFIX}_offload_duration_seconds"
KVBM_ONBOARD_DURATION = f"{KVBM_PREFIX}_onboard_duration_seconds"
# Write-through losses: a committed block was evicted from the device pool
# before the offload worker could gather it ({reason}: device_evicted).
KVBM_OFFLOAD_MISSED_TOTAL = f"{KVBM_PREFIX}_offload_missed_total"
# Speculative onboarding (kv_prefetch.md): one prefetch lease per routed
# request with a tier-resident hint. {outcome}: claimed (admission joined
# the lease), revoked (abort/shed released it), skipped (nothing tier-
# resident / pool already warm), error (walk died). Blocks ride the same
# split as {outcome}: used | wasted — wasted is the bounded cost of
# speculation and the number the cold leg must hold at zero.
KVBM_PREFETCHES_TOTAL = f"{KVBM_PREFIX}_prefetches_total"
KVBM_PREFETCH_BLOCKS_TOTAL = f"{KVBM_PREFIX}_prefetch_blocks_total"
# Onboard wall time hidden behind queue wait + suffix prefill: walk wall
# time minus the stall admission actually observed joining the lease.
KVBM_PREFETCH_OVERLAP_SECONDS = f"{KVBM_PREFIX}_prefetch_overlap_seconds"

# -- KV-reuse plane (runtime/kv_reuse_observe.py KvReusePlane) ----------------
KVCACHE_PREFIX = "dynamo_tpu_kvcache"
# Prefix-cache hits by the tier the hit resolved from (device | host |
# disk | remote) and requests that found no cached prefix at all. The
# hit-rate gauge is the render-time ratio of these monotonic sources.
KVCACHE_HITS_TOTAL = f"{KVCACHE_PREFIX}_hits_total"
KVCACHE_MISSES_TOTAL = f"{KVCACHE_PREFIX}_misses_total"
KVCACHE_HIT_RATE = f"{KVCACHE_PREFIX}_hit_rate"
# Cache ROI: prefill tokens served from cache vs recomputed, and the
# estimated prefill seconds the cache saved (cached tokens x EWMA
# per-token prefill cost — the same number stamped per-request onto the
# trajectory rollup).
KVCACHE_REUSED_TOKENS_TOTAL = f"{KVCACHE_PREFIX}_reused_prefill_tokens_total"
KVCACHE_RECOMPUTED_TOKENS_TOTAL = (
    f"{KVCACHE_PREFIX}_recomputed_prefill_tokens_total"
)
KVCACHE_PREFILL_SECONDS_SAVED_TOTAL = (
    f"{KVCACHE_PREFIX}_prefill_seconds_saved_total"
)
KVCACHE_PREFILL_COST_PER_TOKEN = (
    f"{KVCACHE_PREFIX}_prefill_cost_per_token_seconds"
)
# Space-saving popularity sketch: live tracked-prefix count (bounded by
# capacity by construction), min-replacements (sketch churn under a
# heavy-tailed workload), and the p99 sketch lookup latency recorded by
# the scale harness (tests/test_kv_reuse_scale.py).
KVCACHE_SKETCH_TRACKED_PREFIXES = f"{KVCACHE_PREFIX}_sketch_tracked_prefixes"
KVCACHE_SKETCH_REPLACEMENTS_TOTAL = (
    f"{KVCACHE_PREFIX}_sketch_replacements_total"
)
KVCACHE_SKETCH_LOOKUP_P99_SECONDS = (
    f"{KVCACHE_PREFIX}_sketch_lookup_p99_seconds"
)
# Tier evictions by (tier, reason): arena_full (straight spill past a
# full pinned arena) | capacity (LRU overflow) | corrupt (CRC drop on
# read-back). Mirrors kvbm_tier_evictions_total with the reason split the
# popularity-eviction follow-on acts on.
KVCACHE_EVICTIONS_TOTAL = f"{KVCACHE_PREFIX}_evictions_total"

# -- device/runtime plane (runtime/device_observe.py) ------------------------
RUNTIME_PREFIX = "dynamo_tpu_runtime"
# Compile telemetry (watched_jit / CompileWatcher): every jax.jit site.
RUNTIME_COMPILES_TOTAL = f"{RUNTIME_PREFIX}_compiles_total"
RUNTIME_COMPILE_SIGNATURES = f"{RUNTIME_PREFIX}_compile_signatures"
RUNTIME_COMPILE_SECONDS = f"{RUNTIME_PREFIX}_compile_seconds"
RUNTIME_RECOMPILE_STORMS_TOTAL = f"{RUNTIME_PREFIX}_recompile_storms_total"
# HBM ledger (structural byte accounting + device.memory_stats mirror).
RUNTIME_HBM_BYTES = f"{RUNTIME_PREFIX}_hbm_bytes"
RUNTIME_HBM_DEVICE_BYTES = f"{RUNTIME_PREFIX}_hbm_device_bytes"
# Flight recorder rings (engine tick loop + device-thread runner).
RUNTIME_FLIGHT_EVENTS_TOTAL = f"{RUNTIME_PREFIX}_flight_events_total"
RUNTIME_FLIGHT_OVERWRITTEN_TOTAL = f"{RUNTIME_PREFIX}_flight_overwritten_total"
# On-demand jax.profiler captures (POST /debug/profile).
RUNTIME_PROFILER_CAPTURES_TOTAL = f"{RUNTIME_PREFIX}_profiler_captures_total"
# The garbage collector's pauses (one gc.callbacks hook, start to stop of
# each collection; label generation): every thread of the process stands
# still for them, the scheduler loop and the device thread included.
RUNTIME_GC_PAUSE_SECONDS_TOTAL = f"{RUNTIME_PREFIX}_gc_pause_seconds_total"
RUNTIME_GC_COLLECTIONS_TOTAL = f"{RUNTIME_PREFIX}_gc_collections_total"

# -- disagg (disagg/handlers.py DecodeHandler) -------------------------------
DISAGG_PREFIX = "dynamo_tpu_disagg"
DISAGG_TRANSFERS_TOTAL = f"{DISAGG_PREFIX}_transfers_total"
# One failed pull ATTEMPT, labeled by classified error_kind (timeout vs
# connection vs decode vs other). Attempts retry with anchor-resume; a
# pull that exhausts retries is the 2×-cost path (second full prefill).
DISAGG_TRANSFER_FAILURES_TOTAL = f"{DISAGG_PREFIX}_transfer_failures_total"
# Retried pull attempts (attempt 2+). Anchor-resume means a retry only
# moves the not-yet-imported tail, so retries are cheap but visible.
DISAGG_PULL_RETRIES_TOTAL = f"{DISAGG_PREFIX}_pull_retries_total"
# Per-src circuit breaker: state transitions {src, to∈open|half_open|
# closed} and a 0/1 open gauge per src. An open breaker is advertised in
# load reports and prices the (src, this worker) pair out of disagg
# placement (router/scheduler.py LinkCostModel.set_fault).
DISAGG_BREAKER_TRANSITIONS_TOTAL = f"{DISAGG_PREFIX}_breaker_transitions_total"
DISAGG_BREAKER_OPEN = f"{DISAGG_PREFIX}_breaker_open"
DISAGG_BLOCKS_PULLED_TOTAL = f"{DISAGG_PREFIX}_blocks_pulled_total"
DISAGG_BYTES_PULLED_TOTAL = f"{DISAGG_PREFIX}_bytes_pulled_total"
# Serialized KV payload bytes by wire dtype (disagg/wire.py schema v2):
# int8-on-the-wire vs densified is THE transfer-bound disagg lever.
DISAGG_KV_WIRE_BYTES_TOTAL = f"{DISAGG_PREFIX}_kv_wire_bytes_total"
DISAGG_TRANSFER_DURATION = f"{DISAGG_PREFIX}_transfer_duration_seconds"
# Observed per-(src, dst) transfer bandwidth EWMA, measured at the decode
# worker's pull path and folded into the router via load reports.
DISAGG_LINK_BANDWIDTH = f"{DISAGG_PREFIX}_link_bandwidth_bytes_per_s"

# -- migration (llm/migration.py Migration) ----------------------------------
MIGRATION_PREFIX = "dynamo_tpu_migration"
# Re-dispatches of a live stream to another worker, by failure reason
# (connection | timeout | no_instances | disagg | other).
MIGRATION_MIGRATIONS_TOTAL = f"{MIGRATION_PREFIX}_migrations_total"
# Streams that failed AFTER exhausting the migration budget (attempt
# limit or the re-prefill token cap) — each one reached the client.
MIGRATION_EXHAUSTED_TOTAL = f"{MIGRATION_PREFIX}_exhausted_total"
# Prompt+carried tokens re-prefilled by migrations (the cost the
# re-prefill cap bounds).
MIGRATION_REPREFILL_TOKENS_TOTAL = f"{MIGRATION_PREFIX}_reprefill_tokens_total"

# -- fault plane (runtime/faults.py FaultPlane) ------------------------------
FAULTS_PREFIX = "dynamo_tpu_faults"
FAULTS_ARMED = f"{FAULTS_PREFIX}_armed"
FAULTS_INJECTIONS_TOTAL = f"{FAULTS_PREFIX}_injections_total"

# -- drain plane (runtime/drain.py DrainController) ---------------------------
DRAIN_PREFIX = "dynamo_tpu_drain"
# State machine: 0 serving, 1 draining, 2 drained.
DRAIN_STATE = f"{DRAIN_PREFIX}_state"
# Completed drains (a worker usually drains once per life; a counter so
# aborted/retried drains are visible across restarts of the controller).
DRAIN_DRAINS_TOTAL = f"{DRAIN_PREFIX}_drains_total"
# In-flight streams resolved by the drain, by ladder rung: handoff (live
# KV moved, zero re-prefill), reprefill (fell back to PR 7 migration —
# the frontend re-prefills on another worker), requeue (never admitted;
# typed migratable refusal re-dispatches it whole).
DRAIN_STREAMS_TOTAL = f"{DRAIN_PREFIX}_streams_total"
# Serialized wire bytes of exported handoff KV (payload + scales).
DRAIN_HANDOFF_BYTES_TOTAL = f"{DRAIN_PREFIX}_handoff_bytes_total"
# Peer adoptions refused (capacity, shape/seed mismatch, peer draining) —
# each refusal walks the source further down the peer list / ladder.
DRAIN_PEER_REFUSALS_TOTAL = f"{DRAIN_PREFIX}_peer_refusals_total"
# Wall time of one full drain (trigger -> drained).
DRAIN_DURATION = f"{DRAIN_PREFIX}_duration_seconds"

# -- crash plane (runtime/liveness.py) ---------------------------------------
LIVENESS_PREFIX = "dynamo_tpu_liveness"
# Per-worker liveness state machine: 0 alive, 1 suspect (2 missed load
# reports), 2 dead (drop_worker reconciliation ran, streams aborted).
LIVENESS_WORKER_STATE = f"{LIVENESS_PREFIX}_worker_state"
# Last-report-to-declared-dead latency; bounded by dead_after x interval_s
# by construction (no TCP timeouts anywhere in the path).
LIVENESS_DETECTION_SECONDS = f"{LIVENESS_PREFIX}_detection_seconds"
# Packets from a prior worker incarnation dropped at a fencing seam
# (load_report | router_load | pull_reply | handoff_ack | tcp) — counted,
# never applied. load_report = the liveness tracker's fence, router_load =
# the scheduler's (separate subscriptions to one topic; distinct labels so
# one zombie packet is never double-counted).
LIVENESS_STALE_DROPS_TOTAL = (
    f"{LIVENESS_PREFIX}_stale_incarnation_drops_total"
)
# Warm-restart KV checkpoint restore: wall time and outcome (restored |
# partial | empty | cold_mismatch | cold_corrupt | cold_error). Every
# cold_* is a logged cold start, never a crash loop.
LIVENESS_RESTORE_SECONDS = f"{LIVENESS_PREFIX}_restore_seconds"
LIVENESS_RESTORE_OUTCOME_TOTAL = f"{LIVENESS_PREFIX}_restore_outcome_total"

# -- planner / elasticity plane (planner/planner_core.py, planner/elastic.py) -
PLANNER_PREFIX = "dynamo_tpu_planner"
# Correction-factor feedback (docs/design_docs/elasticity.md): decayed EWMA
# of observed/predicted SLA ratios folded into the interpolator outputs,
# labeled by stage (ttft | itl). 1.0 = the profile is honest; 2.0 = the
# fleet is twice as slow as profiled and sizing is being corrected up.
PLANNER_CORRECTION_FACTOR = f"{PLANNER_PREFIX}_correction_factor"
# The last computed plan, per pool (prefill | decode) — what the planner
# WANTS; the elastic controller's state gauge says what it is DOING.
PLANNER_DESIRED_REPLICAS = f"{PLANNER_PREFIX}_desired_replicas"
# Plan-transition state machine: 0 steady, 1 scaling_up, 2 scaling_down,
# 3 converged (an actuation just completed; cooldown running).
PLANNER_STATE = f"{PLANNER_PREFIX}_state"
PLANNER_TRANSITIONS_TOTAL = f"{PLANNER_PREFIX}_transitions_total"
# Plans the planner handed the connector (one per adjustment interval once
# predictors warm up).
PLANNER_APPLIES_TOTAL = f"{PLANNER_PREFIX}_applies_total"
# Plan changes suppressed by hysteresis/cooldown — oscillating load shows
# up here instead of as fleet churn.
PLANNER_HOLDS_TOTAL = f"{PLANNER_PREFIX}_holds_total"
# Workers retired through the drain plane (zero-re-prefill live handoff),
# by mode (planned = scale-down, preemption = spot reclaim).
PLANNER_SCALE_DOWN_DRAINS_TOTAL = f"{PLANNER_PREFIX}_scale_down_drains_total"
# Replicas launched but not yet counted: a scale-up replica only counts
# once its /readyz (warm restore included) goes green.
PLANNER_SCALE_UP_PENDING = f"{PLANNER_PREFIX}_scale_up_pending"

# -- overload plane (runtime/overload.py OverloadController) -----------------
OVERLOAD_PREFIX = "dynamo_tpu_overload"
# Brownout state machine: 0 healthy, 1 brownout (max_tokens clamped,
# speculative decode off), 2 shed (new admissions refused 503).
OVERLOAD_STATE = f"{OVERLOAD_PREFIX}_state"
OVERLOAD_TRANSITIONS_TOTAL = f"{OVERLOAD_PREFIX}_transitions_total"
# Admissions refused, by reason (queue_full | predicted_delay |
# deadline_expired | brownout_shed) — every shed reached a client as a
# typed 429/503/504 + Retry-After.
OVERLOAD_SHED_TOTAL = f"{OVERLOAD_PREFIX}_shed_total"
OVERLOAD_ADMITTED_TOTAL = f"{OVERLOAD_PREFIX}_admitted_total"
# Bounded EDF admission queue: live depth and the wait granted requests
# actually paid (the predicted-delay shed keeps the tail of this
# histogram inside max_queue_delay_s).
OVERLOAD_QUEUE_DEPTH = f"{OVERLOAD_PREFIX}_queue_depth"
OVERLOAD_QUEUE_DELAY = f"{OVERLOAD_PREFIX}_queue_delay_seconds"
# Requests whose deadline expired before admission (dead on arrival or
# expired mid-queue) — shed before any prefill work.
OVERLOAD_DEADLINE_EXPIRED_TOTAL = f"{OVERLOAD_PREFIX}_deadline_expired_total"

# -- parser plane (parsers/observe.py ParserPlane) ----------------------------
PARSER_PREFIX = "dynamo_tpu_parser"
# Tool calls fully streamed through the incremental jail, by dialect.
PARSER_TOOL_CALLS_TOTAL = f"{PARSER_PREFIX}_tool_calls_total"
# Argument-delta characters emitted while the call was still being
# generated — the incremental jail's reason to exist (the old jail held
# every argument byte until stream end).
PARSER_ARGS_DELTA_CHARS_TOTAL = f"{PARSER_PREFIX}_args_delta_chars_total"
# Degradation-ladder activations by dialect and reason (truncated |
# bad_nesting | drift | buffer_cap | ...) — a malformed call sealed or
# returned to content, never a dropped stream.
PARSER_DEGRADED_CALLS_TOTAL = f"{PARSER_PREFIX}_degraded_calls_total"
# Calls whose argument string was unparseable and shipped as a lossy
# {"__raw__": ...} wrap (tool_calling._normalize and its streaming twin);
# the emitted call carries degraded=true so clients and the SLO plane can
# see lossy parses.
PARSER_DEGRADED_ARGS_TOTAL = f"{PARSER_PREFIX}_degraded_args_total"
# Parser BUGS (not malformed model output): each surfaced as a terminal
# typed SSE error frame (error_kind=tool_call_parse).
PARSER_EXCEPTIONS_TOTAL = f"{PARSER_PREFIX}_exceptions_total"
# Tool-enabled streams through the jail by outcome (clean | degraded |
# error).
PARSER_STREAMS_TOTAL = f"{PARSER_PREFIX}_streams_total"
# Peak jailed-buffer size (chars) — bounded by the jail's buffer cap.
PARSER_JAIL_BUFFERED_PEAK_CHARS = (
    f"{PARSER_PREFIX}_jail_buffered_peak_chars"
)

# -- SLO plane (runtime/trajectory.py SloTracker) -----------------------------
SLO_PREFIX = "dynamo_tpu_slo"
# Rolling-window fraction of finished streams that met BOTH the TTFT and
# ITL SLAs, labeled by window (5m | 60m). 1.0 = every stream inside SLA.
SLO_GOODPUT = f"{SLO_PREFIX}_goodput_ratio"
# Finished streams by SLO verdict (good | breach) — the goodput ratio's
# monotonic source of truth across scrapes.
SLO_STREAMS_TOTAL = f"{SLO_PREFIX}_streams_total"
# Error-budget burn rate per window: breach fraction ÷ (1 − slo_target).
# 1.0 = burning exactly the budget; a multi-window alert fires when BOTH
# the fast and slow windows burn hot (the SRE-workbook shape).
SLO_BURN_RATE = f"{SLO_PREFIX}_burn_rate"
# p99 of each phase's per-request duration over the trajectory window —
# which phase (queue / prefill / kv_transfer / decode / handoff_stall /
# overhead) dominates the tail, as a number a dashboard can rank.
SLO_PHASE_P99_MS = f"{SLO_PREFIX}_phase_p99_contribution_ms"

ALL_FRONTEND = (
    FRONTEND_REQUESTS_TOTAL,
    FRONTEND_INFLIGHT,
    FRONTEND_REQUEST_DURATION,
    FRONTEND_TTFT,
    FRONTEND_ITL,
    FRONTEND_OUTPUT_TOKENS_TOTAL,
    FRONTEND_INPUT_TOKENS_TOTAL,
)

ALL_ROUTER = (
    ROUTER_DECISIONS_TOTAL,
    ROUTER_OVERLAP_BLOCKS,
    ROUTER_WORKER_LOAD_BLOCKS,
    ROUTER_WORKER_KV_USAGE,
    ROUTER_KV_EVENTS_TOTAL,
    ROUTER_LINK_BANDWIDTH,
)

ALL_KVBM = (
    KVBM_OFFLOAD_BLOCKS_TOTAL,
    KVBM_OFFLOAD_BYTES_TOTAL,
    KVBM_ONBOARD_BLOCKS_TOTAL,
    KVBM_ONBOARD_BYTES_TOTAL,
    KVBM_LOOKUP_HITS_TOTAL,
    KVBM_LOOKUP_MISSES_TOTAL,
    KVBM_TIER_BLOCKS,
    KVBM_TIER_EVICTIONS_TOTAL,
    KVBM_POOL_PRESSURE_TRUNCATIONS_TOTAL,
    KVBM_FAILED_LOADS_TOTAL,
    KVBM_RESTORE_CORRUPTION_TOTAL,
    KVBM_OFFLOAD_DURATION,
    KVBM_ONBOARD_DURATION,
    KVBM_OFFLOAD_MISSED_TOTAL,
    KVBM_PREFETCHES_TOTAL,
    KVBM_PREFETCH_BLOCKS_TOTAL,
    KVBM_PREFETCH_OVERLAP_SECONDS,
)

ALL_KVCACHE = (
    KVCACHE_HITS_TOTAL,
    KVCACHE_MISSES_TOTAL,
    KVCACHE_HIT_RATE,
    KVCACHE_REUSED_TOKENS_TOTAL,
    KVCACHE_RECOMPUTED_TOKENS_TOTAL,
    KVCACHE_PREFILL_SECONDS_SAVED_TOTAL,
    KVCACHE_PREFILL_COST_PER_TOKEN,
    KVCACHE_SKETCH_TRACKED_PREFIXES,
    KVCACHE_SKETCH_REPLACEMENTS_TOTAL,
    KVCACHE_SKETCH_LOOKUP_P99_SECONDS,
    KVCACHE_EVICTIONS_TOTAL,
)

ALL_DISAGG = (
    DISAGG_TRANSFERS_TOTAL,
    DISAGG_TRANSFER_FAILURES_TOTAL,
    DISAGG_PULL_RETRIES_TOTAL,
    DISAGG_BREAKER_TRANSITIONS_TOTAL,
    DISAGG_BREAKER_OPEN,
    DISAGG_BLOCKS_PULLED_TOTAL,
    DISAGG_BYTES_PULLED_TOTAL,
    DISAGG_KV_WIRE_BYTES_TOTAL,
    DISAGG_TRANSFER_DURATION,
    DISAGG_LINK_BANDWIDTH,
)

ALL_MIGRATION = (
    MIGRATION_MIGRATIONS_TOTAL,
    MIGRATION_EXHAUSTED_TOTAL,
    MIGRATION_REPREFILL_TOKENS_TOTAL,
)

ALL_FAULTS = (
    FAULTS_ARMED,
    FAULTS_INJECTIONS_TOTAL,
)

ALL_DRAIN = (
    DRAIN_STATE,
    DRAIN_DRAINS_TOTAL,
    DRAIN_STREAMS_TOTAL,
    DRAIN_HANDOFF_BYTES_TOTAL,
    DRAIN_PEER_REFUSALS_TOTAL,
    DRAIN_DURATION,
)

ALL_LIVENESS = (
    LIVENESS_WORKER_STATE,
    LIVENESS_DETECTION_SECONDS,
    LIVENESS_STALE_DROPS_TOTAL,
    LIVENESS_RESTORE_SECONDS,
    LIVENESS_RESTORE_OUTCOME_TOTAL,
)

ALL_PLANNER = (
    PLANNER_CORRECTION_FACTOR,
    PLANNER_DESIRED_REPLICAS,
    PLANNER_STATE,
    PLANNER_TRANSITIONS_TOTAL,
    PLANNER_APPLIES_TOTAL,
    PLANNER_HOLDS_TOTAL,
    PLANNER_SCALE_DOWN_DRAINS_TOTAL,
    PLANNER_SCALE_UP_PENDING,
)

ALL_SLO = (
    SLO_GOODPUT,
    SLO_STREAMS_TOTAL,
    SLO_BURN_RATE,
    SLO_PHASE_P99_MS,
)

ALL_PARSER = (
    PARSER_TOOL_CALLS_TOTAL,
    PARSER_ARGS_DELTA_CHARS_TOTAL,
    PARSER_DEGRADED_CALLS_TOTAL,
    PARSER_DEGRADED_ARGS_TOTAL,
    PARSER_EXCEPTIONS_TOTAL,
    PARSER_STREAMS_TOTAL,
    PARSER_JAIL_BUFFERED_PEAK_CHARS,
)

ALL_OVERLOAD = (
    OVERLOAD_STATE,
    OVERLOAD_TRANSITIONS_TOTAL,
    OVERLOAD_SHED_TOTAL,
    OVERLOAD_ADMITTED_TOTAL,
    OVERLOAD_QUEUE_DEPTH,
    OVERLOAD_QUEUE_DELAY,
    OVERLOAD_DEADLINE_EXPIRED_TOTAL,
)

ALL_RUNTIME = (
    RUNTIME_COMPILES_TOTAL,
    RUNTIME_COMPILE_SIGNATURES,
    RUNTIME_COMPILE_SECONDS,
    RUNTIME_RECOMPILE_STORMS_TOTAL,
    RUNTIME_HBM_BYTES,
    RUNTIME_HBM_DEVICE_BYTES,
    RUNTIME_FLIGHT_EVENTS_TOTAL,
    RUNTIME_FLIGHT_OVERWRITTEN_TOTAL,
    RUNTIME_PROFILER_CAPTURES_TOTAL,
    RUNTIME_GC_PAUSE_SECONDS_TOTAL,
    RUNTIME_GC_COLLECTIONS_TOTAL,
)

ALL_ENGINE = (
    ENGINE_ACTIVE_SEQS,
    ENGINE_WAITING,
    ENGINE_KV_USAGE,
    ENGINE_FREE_BLOCKS,
    ENGINE_CACHED_BLOCKS,
    ENGINE_TOTAL_BLOCKS,
    ENGINE_DECODE_STEPS,
    ENGINE_PREFILL_TOKENS,
    ENGINE_GENERATED_TOKENS,
    ENGINE_SLEEP_LEVEL,
    ENGINE_PIPELINE_DEPTH,
    ENGINE_INFLIGHT_BURSTS,
    ENGINE_PREEMPTIONS,
    ENGINE_QUEUE_DEPTH,
    ENGINE_KV_HIGH_WATERMARK,
    ENGINE_DEADLINE_SHEDS,
    ENGINE_DRAINING,
    ENGINE_PREFILL_BUDGET_TOKENS,
    ENGINE_BUDGET_STATE,
    ENGINE_PREFILL_CHUNK_TOKENS,
    ENGINE_BUDGET_ROLLOVERS,
    ENGINE_STEP_DURATION,
    ENGINE_BATCH_OCCUPANCY,
    ENGINE_STEP_PREFILL_TOKENS,
    ENGINE_STEP_DECODE_TOKENS,
    ENGINE_INFLIGHT_DEPTH,
    ENGINE_TICK_PHASE,
    ENGINE_TICK,
    ENGINE_DEVICE_STARVED_SECONDS_TOTAL,
    ENGINE_FRAME_INTERVAL,
    ENGINE_FRAME_ROW_SECONDS_TOTAL,
    ENGINE_FRAME_STALLS_TOTAL,
    ENGINE_REQUEST_PHASE,
    ENGINE_REQUEST_DECODE_TOKENS_TOTAL,
    ENGINE_DECODE_LIVE_PAGES_TOTAL,
    ENGINE_DECODE_TABLE_SLOTS_TOTAL,
    ENGINE_MOE_EXPERTS_HIT_TOTAL,
    ENGINE_MOE_EXPERT_SLOTS_TOTAL,
    ENGINE_MOE_MAX_EXPERT_TOKENS_TOTAL,
    ENGINE_MOE_MEAN_EXPERT_TOKENS_TOTAL,
    ENGINE_MOE_PREFILL_TOKENS_TOTAL,
    ENGINE_MOE_ASSIGNMENTS_TOTAL,
    ENGINE_SSM_DECODE_ROWS_TOTAL,
    ENGINE_SAMPLER_DECODE_STEPS_TOTAL,
    ENGINE_PREFILL_POSITIONS_TOTAL,
    ENGINE_PREFILL_DISPATCHES_TOTAL,
    ENGINE_SSM_STATE_SLOTS,
    ENGINE_SSM_SNAPSHOTS,
    ENGINE_SSM_SNAPSHOT_HITS_TOTAL,
    ENGINE_SSM_SNAPSHOT_EVICTIONS_TOTAL,
    ENGINE_KV_GROUP_BLOCKS,
    ENGINE_WINDOW_PAGES_RELEASED_TOTAL,
    ENGINE_WINDOW_PAGES_DEAD_TOTAL,
    ENGINE_WINDOW_PAGES_HELD_TOTAL,
    ENGINE_DECODE_WINDOW_LIVE_PAGES_TOTAL,
    ENGINE_PREFIX_HITS_CUT_BY_WINDOW_TOTAL,
    ENGINE_SPARSE_PAGES_SELECTED_TOTAL,
    ENGINE_SPARSE_PAGES_LIVE_TOTAL,
    ENGINE_SPARSE_ROWS_TOTAL,
)
