"""JaxEngine: continuous batching over a jit-compiled paged-KV model.

Reference parity: this is the framework's flagship backend, playing the role
vLLM plays behind components/src/dynamo/vllm (continuous batching, paged KV,
prefix caching, KV events, chunked prefill) — but TPU-native:

  - ONE jitted step function (model forward_paged + fused sampling) serves
    prefill (B=1, C=chunk) and decode (B=max_num_seqs, C=1). Shapes are
    bucketed (powers of two for chunk length and block-table width) so XLA
    compiles a handful of programs, then everything is cache hits.
  - KV cache = two [L, num_blocks, block_size, KH, D] arrays in HBM, donated
    through every step (XLA updates in place). Physical blocks are leased by
    block_pool.BlockPool with prefix reuse + LRU eviction and KV events.
  - All device work runs on a single executor thread so the asyncio serving
    loop never blocks on compiles or device sync.
  - Preemption-by-recompute when the pool is exhausted mid-decode (the
    youngest sequence releases its blocks and re-queues), like vLLM's
    recompute preemption.
"""

from __future__ import annotations

import asyncio
import collections
import functools
import math
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engines.mock.kv_manager import KvEvent
from dynamo_tpu.disagg.wire import check_config as wire_check_config
from dynamo_tpu.engines.tpu import block_pool
from dynamo_tpu.engines.tpu.block_pool import BlockPool, StateSnapshots, WindowPages
from dynamo_tpu.engines.tpu.runner import DeviceRunner, _next_pow2
from dynamo_tpu.engines.tpu.tick_budget import (
    BUDGET_STATE_OFF,
    TickBudgetConfig,
    TickBudgeter,
)
from dynamo_tpu.llm.protocols.common import (
    BackendOutput,
    FinishReason,
    PreprocessedRequest,
    TokenLogprob,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.moe import FORMS as MOE_FORMS
from dynamo_tpu.ops.sampling import compute_logprobs, sample_tokens
from dynamo_tpu.parallel.mesh import AxisNames
from dynamo_tpu.parallel.sharding import ShardingRules, param_shardings, shard_params
from dynamo_tpu.runtime import fault_names
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.faults import fault_point, note_activity
from dynamo_tpu.runtime.device_observe import (
    FlightRecorder,
    HbmLedger,
    dump_flight,
    global_compile_watcher,
    tree_device_bytes,
)
from dynamo_tpu.tokens.blocks import adapter_salt, compute_block_hashes
from dynamo_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclass
class JaxEngineArgs:
    """Engine knobs (ref: vllm EngineArgs surface used by
    components/src/dynamo/vllm/args.py — block size, gpu blocks, max seqs)."""

    config: ModelConfig = field(default_factory=ModelConfig)
    block_size: int = 16
    num_kv_blocks: int = 512
    max_num_seqs: int = 8
    max_model_len: int = 1024
    prefill_chunk: int = 512  # max tokens per prefill step (chunked prefill)
    watermark: float = 0.01
    # Admission backpressure (overload armor): refuse NEW admissions while
    # pool occupancy (active blocks only — reusable cached blocks don't
    # count) is at or past this fraction and sequences are running.
    # Admitting into a near-full pool doesn't serve the request faster —
    # it trades one queued request for a preemption storm that re-prefills
    # running ones. 1.0 disables (the pre-PR 8 behavior).
    admit_kv_high_watermark: float = 0.95
    # Batched prefill: pack up to this many admissions into ONE device
    # dispatch ([Bp, C] with per-row start/len). B=1 prefill wastes the MXU
    # and serial admission ramps a 64-slot engine a few rows a tick (what
    # was measured: PERF.md).
    prefill_batch: int = 8
    admit_batches_per_tick: int = 8  # bounds decode stall per scheduler tick
    enable_prefix_caching: bool = True
    use_kernel: Optional[bool] = None  # None = auto (pallas on TPU)
    seed: int = 0
    # Multi-LoRA: directory of PEFT adapters (lora/source.py layout). All
    # adapters are stacked and served from one compiled program
    # (ops/lora.py); requests select theirs via PreprocessedRequest.lora_name.
    lora_dir: Optional[str] = None
    # Fused decode iterations per dispatch (llama.decode_multi). Dispatch
    # latency dominates small-model decode on TPU; stop conditions are
    # evaluated host-side at this granularity (overshoot discarded).
    decode_steps: int = 8
    # Speculative decoding: "ngram" = prompt-lookup proposals (no draft
    # model) verified in ONE [B, spec_k+1]-token dispatch. Greedy-only — a
    # tick with sampling/logprobs/processor requests falls back to the
    # fused decode path. Wins latency on extractive/repetitive outputs.
    spec_mode: Optional[str] = None
    spec_ngram: int = 3  # match length for the prompt-lookup proposal
    spec_k: int = 4  # proposed tokens per verify dispatch
    # Weight quantization: "int8" = per-channel weight-only int8
    # (ops/quant.py) — halves weight HBM, 8B-class models fit one v5e chip
    # (the reference's FP8/NVFP4-checkpoint deployment lever, TPU-style).
    quantization: Optional[str] = None
    # Static top-N width compiled into the logprobs decode programs
    # (OpenAI caps top_logprobs at 20). Per-request counts trim at emit;
    # the logprob-free programs never pay for it.
    top_logprobs_cap: int = 20
    # KV cache layout: per-layer 4D pools (tuple of [NB, BS, KH, D]) instead
    # of one stacked 5D array. The layered form lets XLA update each pool in
    # place; the stacked form forces the layer-scan to rematerialize the FULL
    # cache as scan ys every step (~2× cache size of HBM traffic). Stacked
    # remains for pipeline-parallel stages that slice the layer axis.
    layered_cache: bool = True
    # KV-cache quantization: "int8" = per-token-per-head dynamic int8 pools
    # (ops/kv_quant.py) — halves the decode step's history-read bytes and
    # doubles the sequences a fixed HBM budget can hold. The reference's
    # kv_cache_dtype=fp8 engine lever, TPU-style. Requires layered_cache.
    kv_cache_dtype: Optional[str] = None
    # Decode-tick pipelining: how many fused decode bursts may be in flight
    # on the device at once. 2 (default) double-buffers — burst N+1 is
    # dispatched from the device-resident carry while the host reads back
    # and emits burst N, hiding readback RTT + emit/scheduler work behind
    # device compute. 1 = fully synchronous (dispatch, read, emit, repeat).
    # Token/logprob streams are bit-identical across depths for a fixed
    # seed: sampling noise is keyed on (seed, sequence salt, token index),
    # never dispatch order (docs/design_docs/decode_pipelining.md).
    # spec_mode caps the effective depth at 1 (prompt-lookup proposals
    # need reconciled host tokens at every burst boundary).
    pipeline_depth: int = 2
    # SLA-driven intra-chip prefill/decode split (tick_budget.py): when
    # enabled, the static admit_batches_per_tick cap is replaced by a
    # closed-loop per-tick prefill TOKEN budget that shrinks when decode
    # ITL burns the SLO error budget and grows back when it has headroom
    # (docs/design_docs/disagg_serving.md, "intra-chip middle mode").
    # Off by default: aggregated mode, today's behavior byte-for-byte.
    tick_budget_enabled: bool = False
    # Starvation floor / ceiling in prefill tokens per tick. None derives
    # floor = prefill_chunk (one chunk round always lands, bounding TTFT)
    # and ceiling = admit_batches_per_tick × prefill_chunk (the static
    # cap's worst-case single-tick prefill spend).
    tick_budget_floor_tokens: Optional[int] = None
    tick_budget_ceiling_tokens: Optional[int] = None
    # Policy knob: where between floor (0.0, strict ITL) and ceiling
    # (1.0, max throughput) the budget starts.
    tick_budget_policy: float = 0.5
    # Decode-phase ITL SLO driving the budgeter's internal burn estimate;
    # None = the budget only moves via an external burn source or the
    # overload ladder's squeeze.
    tick_budget_itl_slo_s: Optional[float] = None

    @property
    def max_blocks_per_seq(self) -> int:
        return math.ceil(self.max_model_len / self.block_size)

    @property
    def num_window_blocks(self) -> int:
        """Size of the window page group (a model that mixes sliding-window
        and full attention layers; 0 without one), derived: every row's most
        pages (window, prefill chunk, look-ahead) and as many cached chains'
        trailing windows again. ``num_kv_blocks`` sizes the full group."""
        win = self.config.window_group
        if win is None:
            return 0
        return WindowPages.blocks_needed(
            self.max_num_seqs, win.window, self.block_size, self.prefill_chunk,
            self.decode_steps * PIPELINE_LOOKAHEAD_BURSTS,
        )


@dataclass
class _Sequence:
    request: PreprocessedRequest
    context: Context
    queue: "asyncio.Queue[Optional[BackendOutput]]"
    prompt: List[int]
    all_tokens: List[int]  # prompt + generated
    generated: List[int] = field(default_factory=list)
    block_ids: List[int] = field(default_factory=list)
    block_hashes: List[int] = field(default_factory=list)  # committed prefix
    # Window page group (block_pool.WindowPages): pages by logical block, -1
    # where none is held; blocks below win_pinned came from the cache.
    win_ids: List[int] = field(default_factory=list)
    win_pinned: int = 0
    win_keep: Tuple[int, int] = (0, 0)  # the prompt's trailing window
    slot: int = -1
    next_token: int = 0  # decode input token
    logprob_pending: Optional[float] = None
    admission_failures: int = 0  # deterministic per-request errors (poisoned)
    hash_salt: int = 0  # adapter ⊕ multimodal content salt (prefix cache)
    # Sampling-RNG salt (arrival order): the sequence's noise stream is
    # keyed (engine seed, salt, token index) — survives preemption and is
    # independent of slot/batch/dispatch placement.
    salt: int = 0
    # Speculative prompt-lookup: n-gram → position AFTER its last occurrence
    # (incrementally indexed up to ngram_upto).
    ngram_index: Dict[tuple, int] = field(default_factory=dict)
    ngram_upto: int = 0
    # Live-handoff drain: position snapshot taken when the sequence is
    # detached from its slot (= len(all_tokens) - 1 at the reconciled
    # boundary); also the resume position an adopted sequence installs at.
    detach_pos: int = -1
    # Trajectory-plane phase boundaries (time.monotonic stamps; 0 = never
    # reached). Stamped OUTSIDE the decode tick — at enqueue, admission,
    # first streamed output, and detach — and folded into retrospective
    # engine.queue/prefill/decode spans when the stream ends, so the hot
    # loop itself never touches span machinery.
    t_enqueue: float = 0.0
    t_prefill_start: float = 0.0
    t_first_out: float = 0.0
    t_detached: float = 0.0
    # KV-reuse attribution (runtime/kv_reuse_observe.py): the tier this
    # request's prefix hit resolved from and the ROI dict stamped at
    # admission (cached/recomputed tokens, estimated seconds saved).
    kv_hit_tier: str = "device"
    kv_roi: Optional[Dict[str, Any]] = None
    # Speculative onboard lease (kvbm/manager.py KvPrefetch), started at
    # enqueue from the router's prefix hint. Admission joins and claims
    # it; abort/shed revokes it (the pinned blocks fall back to cache).
    kv_prefetch: Optional[Any] = None


@dataclass
class _InflightBurst:
    """One dispatched-but-unreaped decode burst (pipelined decode tick).
    ``seqs`` snapshots (slot, sequence) at dispatch time; at reap, a row is
    emitted only if its slot still holds the SAME sequence — rows whose
    sequence finished in an earlier burst while this one was in flight are
    dropped (their device-side writes landed in the 2-burst lookahead
    blocks that were reserved at dispatch, so they corrupt nothing)."""

    handles: Any  # runner._DecodeHandles
    seqs: List[Tuple[int, _Sequence]]
    t_dispatch: float
    occupancy: int
    nb_bucket: int  # table width the burst ran at (the reap's span names it)


# Block-table lookahead reserved by every decode dispatch, in bursts of
# ``decode_steps`` tokens. Constant 2 at EVERY pipeline depth — the
# speculative burst can never outrun its reservation, and depth 1 and
# depth 2 request pool blocks at identical points in the reap order, which
# is what makes preemption decisions (and therefore full token streams)
# depth-independent (docs/design_docs/decode_pipelining.md).
PIPELINE_LOOKAHEAD_BURSTS = 2


def table_width_bucket(max_blocks: int, cap: int) -> int:
    """Pow2 bucket for a dispatched block-table width, clamped to the
    engine's per-sequence table capacity. Every distinct width is a
    separate compiled decode program (XLA specializes on the operand
    shape), so bucketing bounds the program count to ~log2(cap) as
    contexts grow instead of one program per context length. Shared by
    the decode tick and the speculative-verify dispatch (spec.py)."""
    return min(_next_pow2(max(max_blocks, 1)), cap)


@dataclass
class _ProcPrep:
    """Per-request logits-processor parameters (ops/logits_process.py).
    Present only when the request actually uses a processor — absence keeps
    the engine on the processor-free compiled programs."""

    minp: float
    rep: float
    pres: float
    freq: float
    bias_ids: np.ndarray  # [MAX_BIAS_SLOTS] int32
    bias_vals: np.ndarray  # [MAX_BIAS_SLOTS] float32


@dataclass
class _Prep:
    """Admission bookkeeping produced by _prepare_admission."""

    ids: List[int]
    hashes: List[int]
    matched: int
    matched_tokens: int
    sp: Tuple[float, int, float]
    adapter_id: int
    mm_embeds: Optional[np.ndarray]
    mm_slot_of: Optional[np.ndarray]
    procs: Optional[_ProcPrep] = None
    # Hybrid models: the snapshot-store entry the recurrent state resumes
    # from at ``matched_tokens`` (-1 = from zeros, a fresh prompt).
    snap_src: int = -1


class JaxEngine:
    """AsyncEngine over the native JAX model."""

    def __init__(
        self,
        args: JaxEngineArgs,
        params: Optional[Any] = None,
        *,
        mesh: Optional[jax.sharding.Mesh] = None,
        rules: Optional[ShardingRules] = None,
        on_kv_event: Optional[Callable[[KvEvent], None]] = None,
        topology: Optional[Any] = None,  # parallel/multihost.HostTopology
        runner: Optional[DeviceRunner] = None,
    ) -> None:
        self.args = args
        self.config = args.config
        self.mesh = mesh
        self.rules = rules or ShardingRules()
        # Hybrid models keep two kinds of state in this one manager: the
        # paged K/V of their attention layers (the pool) and recurrent-state
        # snapshots (their index here, the arrays in the runner). The router
        # hears of a block only once a snapshot covers it.
        hybrid = self.config.is_hybrid
        recurrent = self.config.has_recurrent_state
        self.pool = BlockPool(
            args.num_kv_blocks, args.block_size, on_event=on_kv_event,
            announce_commits=not recurrent,
        )
        # A second page group for the sliding-window layers of a model that
        # also has full ones: its own pool and id space, one admission
        # decision over both, pages behind a row's window given back as it
        # advances (block_pool.WindowPages).
        self.window: Optional[WindowPages] = None
        win_group = self.config.window_group
        if win_group is not None:
            if recurrent:
                raise ValueError(
                    f"{self.config.name}: a window page group beside recurrent "
                    "state is not implemented"
                )
            self.window = WindowPages(
                args.num_window_blocks, args.block_size, win_group.window
            )
        sparse = self.config.sparse_index
        if sparse is not None and sparse.block != args.block_size:
            raise ValueError(
                f"{self.config.name} selects attention blocks of {sparse.block} "
                f"tokens and a page is the unit its kernels visit: serve it with "
                f"--block-size {sparse.block} (got {args.block_size})"
            )
        self.snapshots: Optional[StateSnapshots] = None
        # Tokens a scan block of the recurrent layers holds (the device
        # program can write the state at each), and between the boundaries
        # the engine keeps a snapshot at.
        self._ssm_stride = self._snap_every = 0
        if recurrent:
            self._ssm_stride, self._snap_every = self.config.snapshot_stride
            if args.prefill_chunk % self._ssm_stride:
                raise ValueError(
                    f"prefill_chunk {args.prefill_chunk} is not a multiple of "
                    f"the state-space scan block {self._ssm_stride}"
                )
            if args.enable_prefix_caching and self._snap_every % args.block_size == 0:
                self.snapshots = StateSnapshots(
                    block_pool.snapshot_entries(
                        self.config, args.num_kv_blocks, args.block_size,
                        args.max_num_seqs),
                    self._snap_every // args.block_size,
                    on_evict=self.pool.retract,
                )
        # All device state (params, LoRA stacks, KV caches, RNG, compiled
        # programs, sleep transitions) lives in the DeviceRunner; this class
        # owns scheduling policy only. A pre-built runner may be injected
        # (multihost leader shares construction with followers).
        self.runner = runner or DeviceRunner(
            args, params, mesh=mesh, rules=self.rules, topology=topology,
        )
        self._use_kernel = self.runner.use_kernel
        # Sleep/wake orchestration (ref: vllm handlers.py sleep :286 /
        # wake_up :317 — RL weight-sync workflows park the engine to free
        # accelerator memory). 0 = awake; 1 = KV freed; 2 = weights too.
        self._sleep_requested: Optional[int] = None
        self._sleep_inflight = False
        self._sleep_event = asyncio.Event()
        self.spec_proposed = 0
        self.spec_accepted = 0
        # Brownout lever (runtime/overload.py): under pressure speculative
        # decode burns decode ticks on rejected proposals — the overload
        # controller suspends it without tearing down the engine.
        self._spec_suspended = False
        # Requests shed at admission dequeue because their deadline had
        # already expired (observability; bench reads the activity
        # counter, tests read this).
        self.deadline_sheds = 0
        # Live-handoff drain plane (runtime/drain.py): while draining, new
        # generate() calls refuse with a typed migratable error, admission
        # holds, and the DrainController detaches/exports live decodes.
        # Detach requests and adoptions are serviced by the scheduler loop
        # behind its drain barrier (the only place slot state may mutate
        # with bursts reconciled).
        self._draining = False
        self._detach_requests: "collections.deque" = collections.deque()
        self._adoptions: "collections.deque[_Sequence]" = collections.deque()
        # Sequences in an in-flight admission batch (popped from _waiting,
        # slot not yet taken) — adopt_handoff counts them or it promises a
        # peer capacity the batch is about to install into.
        self._admitting = 0
        self.handoffs_exported = 0
        self.handoffs_adopted = 0
        # SLA-driven prefill/decode tick split (engines/tpu/tick_budget.py).
        # _pending_prefill: a budget-paused joint prefill parked at a chunk
        # boundary (blocks pinned, rows keep progress) — it resumes ahead
        # of any new admission. _tick_budget_left: this tick's remaining
        # prefill token grant (None = unbudgeted), decremented by the
        # admitter's chunk rounds.
        self._budgeter: Optional[TickBudgeter] = None
        if args.tick_budget_enabled:
            floor = args.tick_budget_floor_tokens
            if floor is None:
                floor = args.prefill_chunk
            ceiling = args.tick_budget_ceiling_tokens
            if ceiling is None:
                ceiling = max(
                    floor, args.admit_batches_per_tick * args.prefill_chunk
                )
            self._budgeter = TickBudgeter(
                TickBudgetConfig(
                    floor_tokens=int(floor),
                    ceiling_tokens=int(ceiling),
                    policy=args.tick_budget_policy,
                    itl_slo_s=args.tick_budget_itl_slo_s,
                ),
                on_event=self._record_budget_event,
            )
        self._pending_prefill: Optional[Any] = None
        self._tick_budget_left: Optional[int] = None

        S = args.max_num_seqs
        self._slots: List[Optional[_Sequence]] = [None] * S
        self._pos = np.zeros(S, dtype=np.int32)  # tokens resident in cache
        self._block_tables = np.zeros(
            self.tables_shape(S, args.max_blocks_per_seq), dtype=np.int32
        )
        self._temp = np.ones(S, dtype=np.float32)
        self._topk = np.zeros(S, dtype=np.int32)
        self._topp = np.ones(S, dtype=np.float32)
        self._adapter_ids = np.zeros(S, dtype=np.int32)
        self._tok_mirror = np.zeros(S, dtype=np.int32)  # decode input token
        self._salts = np.zeros(S, dtype=np.int32)  # per-slot sampling salt
        self._next_salt = 0  # arrival-order salt counter
        # Dirty-slot tracking for the device-resident decode state: the
        # numpy arrays above are the scheduler's VIEW; the device copies in
        # DeviceRunner.slot_state are reconciled incrementally at the next
        # dispatch for exactly the slots a mutating event touched
        # (admission, finish, preempt, spec emission → _dirty_state; block
        # append / table rewrite → _dirty_tables). Invariant: a slot with a
        # LIVE sequence is only ever state-dirty while no burst is in
        # flight (mutating events either happen at reap — where the dirty
        # row deactivates a finished slot — or behind a drain barrier).
        self._dirty_state: set = set(range(S))
        self._dirty_tables: set = set(range(S))
        # Pipelined decode: dispatched-but-unreaped bursts, oldest first.
        self._inflight: "collections.deque[_InflightBurst]" = (
            collections.deque()
        )
        self.preemptions = 0
        self._t_last_ready: Optional[float] = None  # last burst readback
        # Per-slot logits-processor params (neutral unless the occupant asks).
        from dynamo_tpu.ops.logits_process import MAX_BIAS_SLOTS

        self._minp = np.zeros(S, dtype=np.float32)
        self._rep = np.ones(S, dtype=np.float32)
        self._pres = np.zeros(S, dtype=np.float32)
        self._freq = np.zeros(S, dtype=np.float32)
        self._bias_ids = np.full((S, MAX_BIAS_SLOTS), -1, dtype=np.int32)
        self._bias_vals = np.zeros((S, MAX_BIAS_SLOTS), dtype=np.float32)
        self._uses_procs = np.zeros(S, dtype=bool)

        self.kvbm: Optional[Any] = None  # TieredKvManager (kvbm/manager.py)
        # Plain deque (+ wake event), NOT an asyncio.Queue: _requeue must
        # push preempted sequences to the FRONT, and the round-1 approach of
        # swapping in a fresh Queue raced concurrent generate() calls that
        # held the old object (requests lost forever).
        self._waiting: "collections.deque[_Sequence]" = collections.deque()
        self._loop_task: Optional[asyncio.Task] = None
        self._stopped = asyncio.Event()
        self._failure: Optional[str] = None  # terminal engine failure
        self._consecutive_tick_failures = 0
        # Consecutive failed admission attempts across ALL requests; resets
        # on any success. Catches systemic admission failure (e.g. a broken
        # prefill program) without letting a few poisoned requests brick the
        # engine.
        self._admission_failure_streak = 0
        self._wake = asyncio.Event()
        self._executor = ThreadPoolExecutor(1, thread_name_prefix="jax-engine")
        # Transfer lane: HBM→host readbacks for disagg/offload run here so
        # they never occupy the device-executor thread between decode ticks
        # (VERDICT r4 item 4 — transfers must overlap decode, the role of
        # the reference's async offload engine).
        self._transfer_executor = ThreadPoolExecutor(
            1, thread_name_prefix="jax-engine-transfer"
        )
        self.steps = 0  # decode iterations (observability)
        self.prefill_tokens = 0
        self.generated_tokens = 0
        # Prefill tokens through expert layers by the form their step took
        # (runner.prefill_expert_form); None for a model without experts.
        self.moe_prefill_tokens: Optional[Dict[str, int]] = (
            dict.fromkeys(MOE_FORMS, 0) if self.runner.expert_ffn is not None else None
        )
        # Step-loop metric families (registered on the system server by
        # attach_engine; dependency-free, so always on).
        from dynamo_tpu.engines.metrics import EngineStepMetrics

        self.step_metrics = EngineStepMetrics(inflight=self._inflight.__len__)
        if self.runner.ssd_step is not None:
            self.step_metrics.observe_ssm_decode(0, 0)  # both series from start-up
        if self.runner.expert_ffn is not None:
            self.step_metrics.observe_moe_assignments(0.0, 0.0)  # as those
        # What the sparse attention layers' decode kernel visits, summed over
        # dispatched bursts (the counters of the same names); None for a
        # model without such layers. The series exist from the first scrape.
        self.sparse_attention: Optional[Dict[str, Any]] = None
        if self.config.sparse_index is not None:
            self.sparse_attention = {
                "pages_selected": 0, "pages_live": 0, "rows": {"sparse": 0, "dense": 0}}
            self.step_metrics.observe_sparse(0, 0, self.sparse_attention["rows"])
        # Device-plane observability (runtime/device_observe.py):
        # - flight: the tick loop's single-writer event ring (admit,
        #   preempt, dispatch, reap, spec tick, KV transfers, abort). The
        #   runner owns a second ring for device-thread events; the system
        #   server merges both at GET /debug/flight.
        # - hbm: structural byte ledger over live device state, sampled at
        #   scrape/snapshot time only (never on the tick path).
        self.flight = FlightRecorder("engine")
        # Trajectory-plane clock-domain label for this engine's phase
        # spans; None = the process service label (worker mains set it,
        # multi-engine test harnesses give each engine its own).
        self.trace_proc: Optional[str] = None
        runner = self.runner
        self.hbm = HbmLedger()
        self.hbm.register(
            "kv_cache",
            lambda: tree_device_bytes((runner.k_cache, runner.v_cache)),
        )
        self.hbm.register("params", lambda: tree_device_bytes(runner.params))
        self.hbm.register(
            "slot_state", lambda: tree_device_bytes(runner.slot_state)
        )
        self.hbm.register(
            "slot_tables", lambda: tree_device_bytes(runner.slot_tables)
        )
        self.hbm.register("lora", lambda: tree_device_bytes(runner.lora))
        self.hbm.register(
            "proc_state", lambda: tree_device_bytes(runner.proc_state)
        )
        if hybrid:
            self.hbm.register(
                "ssm_state", lambda: tree_device_bytes(runner.ssm_state)
            )
            self.hbm.register(
                "ssm_snapshots", lambda: tree_device_bytes(runner.snap_store)
            )

        self._last_flight_dump = float("-inf")  # abort-dump rate limiter

        # stats() snapshot: the system-server thread scrapes stats while
        # the tick loop mutates _slots/_inflight/pool counters — a live
        # read can tear (kv_usage from before a reap, inflight_bursts from
        # after). The loop REPLACES this dict wholesale at reap/admission/
        # idle boundaries; readers get one consistent generation.
        self._stats_cache: Optional[Dict[str, Any]] = None
        self._startup_compile: Dict[str, Any] = {
            "prefill_ladder_programs": 0,
            "prefill_ladder_seconds": 0.0,
            "startup_compiles": 0,
            "startup_compile_seconds": 0.0,
        }

    # -- device-state delegates (DeviceRunner owns the mechanism) ---------

    @property
    def params(self):
        return self.runner.params

    @property
    def _k_cache(self):
        return self.runner.k_cache

    @property
    def _v_cache(self):
        return self.runner.v_cache

    @property
    def _host_params(self):
        return self.runner.host_params

    @property
    def _lora_index(self) -> Dict[str, int]:
        return self.runner.lora_index

    def load_lora(self, name: str, adapter_dir: str) -> None:
        """Load one adapter at runtime (ref: vllm handlers.py LoRA load
        :453). Changing the stack shape recompiles the decode program on the
        next step — acceptable for an administrative operation."""
        if name in self.runner.lora_index:
            raise ValueError(f"LoRA adapter {name!r} already loaded")
        from dynamo_tpu.engines.tpu.runner import _adapter_to_host
        from dynamo_tpu.lora import load_lora_adapter

        adapter = _adapter_to_host(
            load_lora_adapter(adapter_dir, self.config, name=name)
        )
        adapter.name = name
        self.runner.install_adapter(adapter)

    def unload_lora(self, name: str) -> None:
        """Unload by name. In-flight sequences using the adapter keep their
        (now zeroed) slot — they degrade to base-model output rather than
        crash; new requests naming it are rejected at admission."""
        if name not in self.runner.lora_index:
            # KeyError (not ValueError): the admin surface maps it to 404
            # while ValueError means conflict (409) on the load side.
            raise KeyError(f"LoRA adapter {name!r} is not loaded")
        self.runner.remove_adapter(name)

    def lora_names(self) -> List[str]:
        return sorted(self.runner.lora_index)

    def _pipeline_depth(self) -> int:
        # Speculative decoding caps the effective depth at 1: every spec
        # tick needs fully-reconciled host tokens to propose from, and a
        # pipelined fallback would advance 2 bursts between proposal
        # points — halving the lookup cadence and skipping right over
        # n-gram matches. Spec is itself a latency path; it keeps the
        # synchronous tick it was tuned for. A brownout-suspended spec
        # engine decodes on the fused path and gets its pipelining back.
        if self.args.spec_mode and not self._spec_suspended:
            return 1
        return max(1, int(getattr(self.args, "pipeline_depth", 1) or 1))

    def _dispatch_on_device(self, nb, want_logprobs, want_procs,
                            state_sync, table_sync):
        """Device-thread half of a burst dispatch: reconcile dirty slot
        rows into the device-resident state, then enqueue the burst."""
        with self.step_metrics.annotate("device.decode_dispatch", nb=nb):
            if state_sync is not None:
                self.runner.sync_slots(*state_sync)
            if table_sync is not None:
                self.runner.sync_tables(*table_sync)
            return self.runner.decode_dispatch(
                nb, want_logprobs=want_logprobs, use_procs=want_procs
            )

    def _read_on_device(self, handles, rows):
        """Device-thread half of a reap: block on the burst's readback."""
        with self.step_metrics.annotate("device.decode_read", rows=rows):
            return self.runner.decode_read(handles)

    def _run_step(
        self, tokens, start_pos, chunk_lens, block_tables, temp, topk, topp,
        adapter_ids, mm_embeds=None, mm_slot=None, procs=None, want_top=False,
        first_chunk=False, salts=None, ssm=None, snap_dst=None,
    ):
        """One prefill step on the device thread (blocking). See
        DeviceRunner.run_step; kept as an engine method so tests can inject
        faults by monkeypatching it."""
        hybrid = {} if ssm is None else {"ssm": ssm, "snap_dst": snap_dst}
        with self.step_metrics.annotate(
            "device.prefill_step", rows=len(tokens), chunk=len(tokens[0])
        ):
            return self.runner.run_step(
                tokens, start_pos, chunk_lens, block_tables, temp, topk, topp,
                adapter_ids, mm_embeds=mm_embeds, mm_slot=mm_slot, procs=procs,
                want_top=want_top, first_chunk=first_chunk, salts=salts,
                **hybrid,
            )

    async def _device(self, fn, *a):
        return await asyncio.get_running_loop().run_in_executor(
            self._executor, fn, *a
        )

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        if self._loop_task is None:
            self._loop_task = asyncio.get_running_loop().create_task(
                self._scheduler_loop(), name="jax-engine-scheduler"
            )

    async def compile_prefill_ladder(self) -> Dict[str, Any]:
        """Compile, before the worker says it serves, the prefill programs
        a batch of fresh prompts can reach (admission.prefill_ladder: rows
        bucket × chunk bucket), each run once through the callable the
        scheduler calls. A worker's start-up calls this between starting
        the engine and registering; an engine built directly (tests,
        tools) compiles at first use as before. Returns, and keeps for
        ``stats()``, what the process had compiled when it ended."""
        t0 = time.monotonic()
        programs = await self._admitter.compile_prefill_ladder()
        watcher = global_compile_watcher()
        watcher.start_up_ended()
        totals = watcher.totals()
        self._startup_compile = {
            "prefill_ladder_programs": programs,
            "prefill_ladder_seconds": round(time.monotonic() - t0, 3),
            "startup_compiles": totals["compiles"],
            "startup_compile_seconds": totals["compile_seconds"],
        }
        self._publish_stats()
        return dict(self._startup_compile)

    async def stop(self) -> None:
        self._stopped.set()
        self._wake.set()
        if self._loop_task is not None:
            await self._loop_task
            self._loop_task = None
        self._executor.shutdown(wait=False)
        self._transfer_executor.shutdown(wait=False)

    def stats(self) -> Dict[str, Any]:
        """Engine stats for /engine/stats and metric scrapes. While the
        scheduler loop is running, returns the snapshot it published at
        the last reap/admission boundary (see _publish_stats) — a cross-
        thread caller can never observe kv_usage and inflight_bursts from
        different tick generations. With no loop running (tests, stopped
        engine) the state is quiescent and computed live."""
        task = self._loop_task
        if task is not None and not task.done() and self._stats_cache is not None:
            return dict(self._stats_cache)
        return self._compute_stats()

    def _publish_stats(self) -> None:
        self._stats_cache = self._compute_stats()

    def _compute_stats(self) -> Dict[str, Any]:
        out = {
            "active_seqs": sum(1 for s in self._slots if s is not None),
            "waiting": len(self._waiting),
            "kv_usage": self.pool.usage if self.window is None else max(
                self.pool.usage, self.window.pool.usage),
            "free_blocks": self.pool.free_blocks,
            "cached_blocks": self.pool.cached_blocks,
            "total_blocks": self.args.num_kv_blocks,
            "decode_steps": self.steps,
            "prefill_tokens": self.prefill_tokens,
            "generated_tokens": self.generated_tokens,
            "sleep_level": self._sleep_level,
            "pipeline_depth": self._pipeline_depth(),
            "inflight_bursts": len(self._inflight),
            "preemptions": self.preemptions,
            # Drain plane: rides load reports so KvScheduler stops placing
            # new work here the moment the report lands.
            "draining": 1 if self._draining else 0,
            # Overload plane inputs: queue depth + the admission refusal
            # watermark ride load reports router-ward (LoadSnapshot), and
            # deadline sheds are the proof expired work never prefilled.
            "queue_depth": len(self._waiting),
            "kv_high_watermark": self.args.admit_kv_high_watermark,
            "deadline_sheds": self.deadline_sheds,
            # Tick-budget plane (engines/tpu/tick_budget.py): the
            # EFFECTIVE per-tick prefill budget and the chunk size ride
            # stats() into LoadSnapshot and the engine gauge family, so a
            # silent budget collapse shows as its own signal instead of
            # masquerading as an unexplained TTFT regression. Budgeter
            # off (aggregated mode) reports 0 / state OFF.
            "prefill_budget_tokens": (
                self._budgeter.budget_tokens
                if self._budgeter is not None else 0
            ),
            "budget_state": (
                self._budgeter.state
                if self._budgeter is not None else BUDGET_STATE_OFF
            ),
            "prefill_chunk_tokens": self.args.prefill_chunk,
            "budget_rollovers": (
                self._budgeter.rollovers
                if self._budgeter is not None else 0
            ),
            # One decode path serves (llama.decode_multi); the two literals
            # stay for benchmark/run.py's log line (ROADMAP D16). Which
            # attention implementation the runner chose at start and why.
            "decode_path": "xla",
            "decode_path_reason": "the XLA decode step is the only decode path",
            "attention_impl": self.runner.attention_impl,
            "attention_reason": self.runner.attention_reason,
            "expert_ffn": self.runner.expert_ffn,
            "ssd_step": self.runner.ssd_step,
            # What was compiled before this engine served a request
            # (compile_prefill_ladder; zeros where it was never called):
            # against /debug/compiles, what serving has compiled since.
            **self._startup_compile,
            # Sibling rows buckets of recurring prefix-hit prefill programs
            # (admission.run_pending_family): compiled so far, still to run.
            "prefill_family_programs": self._admitter.family_programs,
            "prefill_family_pending": len(self._admitter.family_pending),
        }
        if self.window is not None:
            out["kv_groups"] = {
                name: {
                    "used": pool.active_blocks, "cached": pool.cached_blocks,
                    "total": pool.num_blocks,
                }
                for name, pool in (("full", self.pool), ("window", self.window.pool))
            }
            out["window_pages_released"] = self.window.released
            out["prefix_hits_cut_by_window"] = self.window.cut_hits
            self.step_metrics.observe_kv_groups(
                out["kv_groups"], self.window.released, self.window.cut_hits
            )
        if self.moe_prefill_tokens is not None:
            out["moe_prefill_tokens"] = dict(self.moe_prefill_tokens)
            self.step_metrics.observe_moe_prefill(self.moe_prefill_tokens)
        if self.config.has_latent_cache:
            out["latent_pool"] = dict(self.runner.kv_pool)
            out["mla_attention"] = self.runner.mla_attention
        if self.sparse_attention is not None:
            out["sparse_attention"] = dict(
                self.sparse_attention, rows=dict(self.sparse_attention["rows"]),
                **self.runner.kv_pool.get("indexer", {}))
        if self.config.has_recurrent_state:
            snaps = self.snapshots
            out["ssm_snapshot_spacing"] = {
                "tokens": self._snap_every,
                "entry_bytes": self.runner.snap_entry_bytes,
            }
            out["ssm_state_slots"] = {
                "used": out["active_seqs"], "total": self.args.max_num_seqs,
            }
            out["ssm_snapshots"] = {
                "used": snaps.used if snaps else 0,
                "total": snaps.capacity if snaps else 0,
            }
            m = self.step_metrics
            m.observe_ssm(
                out["active_seqs"], self.args.max_num_seqs,
                out["ssm_snapshots"]["used"], out["ssm_snapshots"]["total"],
            )
            m.ssm_snapshot_hits.set_total(snaps.hits if snaps else 0)
            m.ssm_snapshot_evictions.set_total(snaps.evictions if snaps else 0)
        if self.args.spec_mode:
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
        if self.kvbm is not None:
            out["kvbm"] = self.kvbm.stats()
        return out

    @property
    def num_total_blocks(self) -> int:
        return self.args.num_kv_blocks

    def kv_pool_bytes_breakdown(self) -> Dict[str, int]:
        """Pool-state KV byte split (active/cached/free × per-block bytes)
        for GET /debug/memory — the HBM ledger's kv_cache category is the
        allocation's total footprint; this is how much of it holds live vs
        reusable vs dead content."""
        total = tree_device_bytes((self.runner.k_cache, self.runner.v_cache))
        per_block = total // max(self.args.num_kv_blocks, 1)
        return self.pool.bytes_breakdown(per_block)

    def tables_shape(self, rows: int, width: int) -> Tuple[int, ...]:
        """Shape of a block-table array of ``rows``: [rows, width], and with
        a window page group [rows, 2, width]: the full group's table, then
        the window group's (logically indexed, 0 where no page is held)."""
        return self.config.tables_shape(rows, width)

    def _release_blocks(self, seq: _Sequence) -> None:
        """Give back everything ``seq`` holds, in every page group (a
        sequence still in prefill holds ``prep.ids`` under the hashes it
        matched: ``_prepare_admission`` left both on it)."""
        self.pool.release(seq.block_ids, seq.block_hashes)
        if self.window is not None:
            self.window.release_all(seq.win_ids, seq.block_hashes)
            seq.win_pinned = 0
        seq.block_ids = []
        seq.block_hashes = []

    def _window_advance(self, seq: _Sequence, pos: int, upto: int) -> Optional[List[int]]:
        """Window group, before a step of ``seq`` whose first query is at
        ``pos`` and that writes up to ``upto``: pages behind the window go
        back, pages up to ``upto`` are taken. The logical blocks taken, or
        None where the group is dry."""
        return self.window.advance(
            seq.win_ids, seq.block_hashes, seq.win_pinned, seq.win_keep, pos, upto
        )

    def clear_kv_blocks(self) -> int:
        """Flush the reusable prefix cache (ref: clear_kv_blocks.rs route).
        In-flight sequences keep their pinned blocks."""
        n = self.pool.cached_blocks
        self.pool.clear()
        if self.window is not None:
            self.window.pool.clear()
        if self.snapshots is not None:
            self.snapshots.clear()
        return n

    # -- sleep / wake ------------------------------------------------------

    @property
    def sleep_level(self) -> int:
        return self.runner.sleep_level

    _sleep_level = property(lambda self: self.runner.sleep_level)

    async def sleep(self, level: int = 1) -> None:
        """Park the engine to free device memory (ref: vllm handlers.py
        sleep :286). Level 1 frees the KV cache; level 2 also offloads the
        weights to host RAM. Active sequences drain first; queued requests
        wait until wake()."""
        if self._sleep_level > 0:
            return
        if int(level) >= 2 and self.runner.multihost:
            # Validate HERE, not in the tick: a failure after the request is
            # queued would leave the sleep() caller awaiting an event that
            # never fires.
            raise RuntimeError(
                "sleep level 2 (weight offload) is unsupported in multihost "
                "mode; use level 1"
            )
        await self.start()
        if self._failure is not None or (
            self._loop_task is None or self._loop_task.done()
        ):
            raise RuntimeError(
                "engine scheduler is not running; cannot sleep "
                f"(failure: {self._failure})"
            )
        self._sleep_requested = max(1, min(2, int(level)))
        self._sleep_event.clear()
        self._wake.set()
        await self._sleep_event.wait()

    async def wake(self) -> None:
        """Restore device state after sleep (ref: vllm wake_up :317)."""
        if (
            self._sleep_level == 0
            and self._sleep_requested is None
            and not self._sleep_inflight
        ):
            return
        self._sleep_requested = None
        await self._device(self._do_wake)
        self.flight.record("wake")
        self._publish_stats()
        # Release a sleep() caller whose request we just cancelled.
        self._sleep_event.set()
        self._wake.set()

    def _do_sleep(self, level: int) -> None:
        # Device frees only — BlockPool (and its KV-event callback, which
        # touches asyncio state) is cleared on the event-loop thread in
        # _sleep_tick, per the engine's threading contract.
        self.runner.sleep_device(level)

    def _do_wake(self) -> None:
        self.runner.wake_device()

    # -- AsyncEngine -------------------------------------------------------

    async def generate(
        self, request: Any, context: Context
    ) -> AsyncIterator[BackendOutput]:
        await self.start()
        if self._draining:
            # Typed, MIGRATABLE refusal: the router stops placing work here
            # the moment the draining load report lands, but a request that
            # raced the report must bounce fast so the frontend's Migration
            # re-dispatches it to a serving worker (the "typed requeue"
            # rung of the drain ladder).
            from dynamo_tpu.runtime.drain import WorkerDrainingError

            raise WorkerDrainingError(
                "worker is draining; re-dispatch to another instance"
            )
        if isinstance(request, dict):
            request = PreprocessedRequest.from_dict(request)
        prompt = list(request.token_ids)
        if not prompt:
            yield BackendOutput(error="empty prompt", finish_reason=FinishReason.ERROR)
            return
        if len(prompt) >= self.args.max_model_len:
            yield BackendOutput(
                error=(
                    f"prompt length {len(prompt)} exceeds max_model_len "
                    f"{self.args.max_model_len}"
                ),
                finish_reason=FinishReason.ERROR,
            )
            return
        # Paged prefill needs every prompt block plus one decode block
        # resident at once: a prompt larger than the whole pool can never
        # be admitted, and admission would requeue it forever (pool-dry
        # looks transient from where it sits). Refuse it typed instead.
        n_prompt_blocks = math.ceil(len(prompt) / self.args.block_size)
        if n_prompt_blocks + 1 > self.args.num_kv_blocks:
            yield BackendOutput(
                error=(
                    f"prompt needs {n_prompt_blocks} KV blocks + 1 for "
                    f"decode, but the pool only has "
                    f"{self.args.num_kv_blocks}"
                ),
                finish_reason=FinishReason.ERROR,
            )
            return
        if self._failure is not None:
            yield BackendOutput(
                error=f"engine failed: {self._failure}",
                finish_reason=FinishReason.ERROR,
            )
            return
        if request.lora_name and request.lora_name not in self._lora_index:
            yield BackendOutput(
                error=(
                    f"unknown LoRA adapter {request.lora_name!r} "
                    f"(loaded: {self.lora_names()})"
                ),
                finish_reason=FinishReason.ERROR,
            )
            return
        seq = _Sequence(
            request=request,
            context=context,
            queue=asyncio.Queue(),
            prompt=prompt,
            all_tokens=list(prompt),
            # Arrival-order RNG salt: the sequence's sampling noise is
            # (seed, salt, token index), so its stream is identical no
            # matter which slot/burst/pipeline depth serves it.
            salt=self._next_salt,
        )
        self._next_salt = (self._next_salt + 1) & 0x7FFFFFFF
        seq.t_enqueue = time.monotonic()
        self._waiting.append(seq)
        self._maybe_prefetch(seq)
        self._wake.set()
        try:
            async for out in self._stream_outputs(seq):
                if seq.t_first_out == 0.0 and out.token_ids:
                    seq.t_first_out = time.monotonic()
                yield out
        finally:
            # A stream that ends before admission claimed its lease
            # (client abort, early error) must release the pinned blocks;
            # after a claim this is a no-op.
            self._revoke_prefetch(seq, "aborted")
            self._export_phase_spans(seq)

    def _maybe_prefetch(self, seq: _Sequence) -> None:
        """Speculative onboarding (docs/design_docs/kv_prefetch.md): the
        router ships its radix-match prediction as
        ``estimated_prefix_hit_blocks``; when the hint is positive, start
        the G2/G3→G1 onboard walk NOW so it overlaps this request's queue
        wait (and the batch ahead of it) instead of serializing inside
        admission. No hint — cold traffic, no router, or a multimodal
        salt we cannot compute before admission unpacks the embeds —
        means no walk: unrouted traffic never pays a speculation tax."""
        if self.kvbm is None or not self.args.enable_prefix_caching:
            return
        hint = int(getattr(seq.request, "estimated_prefix_hit_blocks", 0) or 0)
        if hint <= 0:
            return
        if (seq.request.extra or {}).get("mm_embeds"):
            return
        try:
            seq.hash_salt = adapter_salt(seq.request.lora_name)
            hashes = compute_block_hashes(
                seq.prompt, self.args.block_size, salt=seq.hash_salt
            )
            if not hashes or self.pool.match_prefix(hashes) >= len(hashes):
                return  # fully device-resident already: nothing to onboard
            seq.kv_prefetch = self.kvbm.prefetch(hashes)
        except Exception:
            # Speculation is optional: a prefetch-setup bug costs the
            # overlap, never the request (admission onboards serially).
            logger.debug("speculative prefetch setup failed", exc_info=True)

    def _revoke_prefetch(self, seq: _Sequence, reason: str) -> None:
        pf = seq.kv_prefetch
        if pf is not None:
            seq.kv_prefetch = None
            pf.revoke(reason)

    def _export_phase_spans(self, seq: _Sequence) -> None:
        """Retrospective engine.queue / engine.prefill / engine.decode
        spans for one finished stream (trajectory plane). Built once per
        request from the monotonic stamps the serving path already took —
        nothing here runs inside the decode tick, and requests outside any
        trace cost one dict lookup. The same stamps feed the request-phase
        histograms for EVERY finished stream, traced or not: what a window
        can difference (the SLO plane's rolling gauges cannot be)."""
        end = time.monotonic()
        t_admit = seq.t_prefill_start or seq.t_first_out or end
        # (start, end) of each phase on the monotonic clock; None = the
        # stream never reached it. A handed-off stream's decode ends at
        # detach — the relay gap is the drain plane's handoff_stall, and
        # the peer's own decode span covers the continuation.
        queue = (seq.t_enqueue or t_admit, t_admit)
        prefill = decode = None
        if seq.t_prefill_start:
            prefill = (seq.t_prefill_start, seq.t_first_out or end)
        if seq.t_first_out:
            decode = (seq.t_first_out, seq.t_detached or end)
        self.step_metrics.observe_request(
            queue, prefill, decode, decode_tokens=len(seq.generated) - 1
        )
        if not seq.context.baggage.get("traceparent"):
            return
        try:
            from dynamo_tpu.utils.tracing import export_span

            proc = getattr(self, "trace_proc", None)
            export_span(
                "engine.queue", seq.context,
                start_mono=queue[0], end_mono=queue[1], proc=proc,
            )
            if prefill:
                roi = seq.kv_roi or {}
                export_span(
                    "engine.prefill", seq.context,
                    start_mono=prefill[0], end_mono=prefill[1],
                    proc=proc, prompt_tokens=len(seq.prompt),
                    cached_tokens=roi.get("cached_tokens"),
                    prefill_seconds_saved=roi.get("seconds_saved"),
                )
            if decode:
                export_span(
                    "engine.decode", seq.context,
                    start_mono=decode[0], end_mono=decode[1],
                    proc=proc, generated=len(seq.generated),
                    handed_off=bool(seq.t_detached) or None,
                )
        except Exception:
            logger.debug("phase-span export failed", exc_info=True)

    async def _stream_outputs(
        self, seq: _Sequence
    ) -> AsyncIterator[BackendOutput]:
        """Drain a sequence's output queue to its consumer. An exception
        object on the queue RAISES out of the stream — the drain plane's
        fallback ladder uses this to surface a typed migratable error
        (handoff failed / worker draining) through the serving handler so
        the frontend's Migration re-dispatches the request."""
        while True:
            out = await seq.queue.get()
            if out is None:
                return
            if isinstance(out, BaseException):
                raise out
            yield out
            if out.finish_reason is not None:
                return

    # -- scheduler ---------------------------------------------------------

    async def _scheduler_loop(self) -> None:
        while not self._stopped.is_set():
            # One iteration = one tick scope: tick.sched underneath, the
            # phases below carve their own time out of it (EngineStepMetrics
            # .phase), and an iteration that never went idle is one
            # tick_seconds observation.
            with self.step_metrics.tick():
                try:
                    if self._sleep_requested is not None or self._sleep_level > 0:
                        if await self._sleep_tick():
                            continue
                    # Drain plane: detaches and adoptions mutate slot state, so
                    # they ride the same reconciled boundary admission does —
                    # every in-flight burst reaped first.
                    if self._detach_requests or self._adoptions:
                        await self._drain_inflight()
                        self._service_drain_queues()
                    # Admission installs into slots and allocates pool blocks —
                    # both must see fully-reconciled state, so drain the
                    # pipeline first. Gated on a free slot actually existing:
                    # under saturation (queue deep, every slot busy) the
                    # admission attempt is doomed and the pipeline keeps
                    # flowing instead of degrading to depth 1.
                    if self._inflight and (
                        self._pending_prefill is not None
                        or (
                            self._waiting
                            and any(s is None for s in self._slots)
                        )
                    ):
                        await self._drain_inflight()
                    # A prefix-hit prefill program that recurred brings the
                    # rows buckets beside it: at most one compile a tick
                    # (admission.run_pending_family), and no idling on it.
                    admitted = await self._admitter.run_pending_family()
                    with self.step_metrics.phase(
                        "tick.admit", waiting=len(self._waiting)
                    ):
                        if self._budgeter is not None:
                            # Budgeted admission (tick_budget.py): the
                            # closed-loop prefill token grant replaces the
                            # static batch cap.
                            admitted |= await self._admit_tick_budgeted()
                        else:
                            # Admit in batched prefill dispatches; a per-tick
                            # batch cap bounds how long running decodes stall
                            # behind prefill (chunked-prefill fairness, like
                            # the reference schedulers).
                            for _ in range(self.args.admit_batches_per_tick):
                                if await self._admit_batch() == 0:
                                    break
                                admitted = True
                    if admitted:
                        self._publish_stats()
                    active = (
                        any(s is not None for s in self._slots)
                        or bool(self._inflight)
                    )
                    if active:
                        if self.args.spec_mode == "ngram" and not self._spec_suspended:
                            if not await self._spec_tick():
                                await self._decode_tick()
                        else:
                            await self._decode_tick()
                    elif not admitted:
                        # Idle, no live row: the next reap's inter-reap gap
                        # would span the wait for a request — don't let it
                        # testify as ITL, nor as a frame interval.
                        self.step_metrics.forget_frame()
                        if self._budgeter is not None:
                            self._budgeter.note_idle()
                        self._publish_stats()
                        self._wake.clear()
                        with self.step_metrics.phase("tick.idle"):
                            try:
                                await asyncio.wait_for(
                                    self._wake.wait(), timeout=0.05
                                )
                            except asyncio.TimeoutError:
                                pass
                except asyncio.CancelledError:
                    raise
                except Exception as exc:
                    from dynamo_tpu.runtime.network.spmd_channel import (
                        SpmdChannelError,
                    )

                    if isinstance(exc, SpmdChannelError):
                        # A follower died: the SPMD worker group is beyond
                        # repair (the follower missed ops; every process must
                        # issue every global program). Fail FAST — no retries —
                        # so the supervisor restarts the whole group.
                        logger.error("SPMD channel broke: failing worker: %s", exc)
                        self._fail_terminally(exc)
                        break
                    # A failed tick may leave dispatched-but-unreaped bursts
                    # whose device carry ran ahead of what was emitted: drop
                    # them and resync from the host mirrors — the retried
                    # bursts regenerate identical tokens (position-keyed RNG).
                    self._abort_inflight()
                    # Retry with exponential backoff (transient device hiccups
                    # can span seconds), then treat the failure as terminal: fail
                    # every pending request and refuse new ones. Round 1 retried
                    # a missing-kernel ModuleNotFoundError forever and hung the
                    # bench for its whole timeout (VERDICT weak #1).
                    self._consecutive_tick_failures += 1
                    logger.exception(
                        "jax engine scheduler tick failed (%d consecutive)",
                        self._consecutive_tick_failures,
                    )
                    if self._consecutive_tick_failures >= 5:
                        self._fail_terminally(exc)
                        break
                    with self.step_metrics.phase("tick.idle"):
                        await asyncio.sleep(
                            min(0.05 * 2 ** self._consecutive_tick_failures, 2.0)
                        )
                else:
                    self._consecutive_tick_failures = 0
                    if self._failure is not None:  # systemic admission failure
                        break
        # Shutdown: in-flight results are dropped (every surviving sequence
        # is about to be finished with CANCELLED/ERROR anyway).
        self._inflight.clear()
        reason = (
            FinishReason.ERROR if self._failure is not None else FinishReason.CANCELLED
        )
        err = f"engine failed: {self._failure}" if self._failure else None
        # A budget-parked prefill never installed: release its pinned
        # blocks and route its rows through the same shutdown path as the
        # waiting queue below.
        self._unpark_pending()
        for seq in self._slots:
            if seq is not None:
                if err:
                    seq.queue.put_nowait(
                        BackendOutput(error=err, finish_reason=reason)
                    )
                    self._finish(seq, reason, emit=False)
                else:
                    self._finish(seq, reason)
        while self._waiting:
            seq = self._waiting.popleft()
            seq.queue.put_nowait(BackendOutput(error=err, finish_reason=reason))
        # Drain-plane stragglers: unresolved detach requests surface as an
        # error (the controller falls back down its ladder); adopted-but-
        # uninstalled sequences release their blocks and end their streams.
        while self._detach_requests:
            _rid, fut = self._detach_requests.popleft()
            if not fut.done():
                fut.set_exception(
                    RuntimeError("engine stopped during handoff detach")
                )
        while self._adoptions:
            seq = self._adoptions.popleft()
            self._release_blocks(seq)
            seq.queue.put_nowait(BackendOutput(error=err, finish_reason=reason))
        self._publish_stats()

    def _fail_terminally(self, exc: Exception) -> None:
        self._failure = f"{type(exc).__name__}: {exc}"
        logger.critical(
            "jax engine entering failed state: %s "
            "(tick strikes=%d, admission streak=%d)",
            self._failure,
            self._consecutive_tick_failures,
            self._admission_failure_streak,
        )

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    # -- admission (policy in engines/tpu/admission.py) --------------------
    # Thin delegates keep the engine surface stable (tests monkeypatch
    # these names for fault injection) while the pipeline lives in one
    # dedicated module.

    @property
    def _admitter(self):
        if self.__dict__.get("_admitter_obj") is None:
            from dynamo_tpu.engines.tpu.admission import Admitter

            self.__dict__["_admitter_obj"] = Admitter(self)
        return self.__dict__["_admitter_obj"]

    async def _admit_batch(self) -> int:
        if self._draining:
            # Draining: the controller sheds the waiting queue with typed
            # requeue errors; admitting one more prefill would just create
            # another live stream to hand off.
            return 0
        if self._pending_prefill is not None:
            # A budget-paused batch holds the admission pipeline: it must
            # resume (in FIFO order, at its chunk boundary) before
            # anything new dequeues.
            return 0
        return await self._admitter._admit_batch()

    async def _admit_tick_budgeted(self) -> bool:
        """Budgeted admission phase (engines/tpu/tick_budget.py): resume
        a parked prefill first, then admit new batches until this tick's
        prefill token grant is spent. Replaces the static
        admit_batches_per_tick cap; a tick with no decode work to protect
        gets an unbounded grant. Returns True when prefill ran."""
        budgeter = self._budgeter
        decode_active = (
            any(s is not None for s in self._slots) or bool(self._inflight)
        )
        self._tick_budget_left = budgeter.tick_grant(decode_active)
        admitted = False
        try:
            if self._pending_prefill is not None:
                if self._draining:
                    # The drain plane owns the queue now: the parked batch
                    # returns whole (typed-requeue rung, nothing half-
                    # installed).
                    self._unpark_pending()
                    return False
                await self._continue_pending()
                admitted = True  # the resume ran chunk rounds on-device
                if self._pending_prefill is not None:
                    return True  # grant spent; still parked
            while (
                self._tick_budget_left is None or self._tick_budget_left > 0
            ):
                n = await self._admit_batch()
                if n:
                    admitted = True
                if self._pending_prefill is not None or n == 0:
                    break
        finally:
            left = self._tick_budget_left
            self._tick_budget_left = None
            if left is not None:
                if left < 0:
                    # The last chunk round overdrew the grant (rounds are
                    # atomic): pay it back from the next tick's budget.
                    budgeter.add_debt(-left)
                elif (
                    left > 0
                    and decode_active
                    and self._waiting
                    and self._pending_prefill is None
                    and not self._draining
                ):
                    # Admission held with budget unspent (KV watermark,
                    # pool dry, slots full): the grant rolls into decode —
                    # the tick proceeds at full cadence instead of idling
                    # (the PR 8 + budgeter double-stall hazard).
                    budgeter.note_rollover(left)
        return admitted

    async def _continue_pending(self) -> int:
        """Resume the parked prefill's chunk rounds under the current
        grant; Admitter._run_prefill re-parks, installs, or containment-
        ejects. Returns rows installed."""
        pending = self._pending_prefill
        self._pending_prefill = None
        # While its rounds run the parked admission is no longer parked:
        # adopt_handoff counts its slots-to-be here, as it does a new
        # admission's (_run_prefill lets go of each group's as it installs).
        self._admitting = len(pending.held)
        try:
            return await self._admitter._run_prefill(pending)
        finally:
            self._admitting = 0

    def _unpark_pending(self) -> None:
        """Return a parked prefill batch to the waiting queue whole:
        release its pinned blocks, requeue rows in arrival order. Used by
        drain begin and engine shutdown — already-prefilled chunks are
        recomputed on re-admission (the same recompute contract as
        preemption, so streams stay bit-identical)."""
        pending = self._pending_prefill
        if pending is None:
            return
        self._pending_prefill = None
        # its group and the groups of the same admission not yet begun
        self._admitter._back_to_queue(pending.held)
        self.flight.record("prefill_unpark", rows=len(pending.held))

    def _record_budget_event(self, kind: str, **fields) -> None:
        """Flight-ring seam for the tick budgeter and the admission pause
        path: the engine stays the ring's single writer (DYN005)."""
        self.flight.record(kind, **fields)

    def set_budget_pressure(self, on: bool) -> None:
        """Overload-ladder first rung (runtime/overload.py): squeeze the
        per-tick prefill budget to the starvation floor / release it.
        Cheaper than clamping max_tokens or shedding, so the ladder fires
        it first and releases it last. No-op without a budgeter."""
        if self._budgeter is None:
            return
        self._budgeter.set_pressure(bool(on))
        self._wake.set()

    async def _finish_admission(self, batch) -> int:
        return await self._admitter._finish_admission(batch)

    def _contain_admission_failure(self, seqs, exc: Exception) -> None:
        self._admitter._contain_admission_failure(seqs, exc)

    async def _prepare_admission(self, seq: _Sequence):
        return await self._admitter._prepare_admission(seq)

    def _install(self, seq: _Sequence, prep, slot: int, first_token: int,
                 first_logprob: float, first_top=None) -> None:
        self._admitter._install(
            seq, prep, slot, first_token, first_logprob, first_top
        )
        self.flight.record(
            "admit", request_id=seq.request.request_id, slot=slot,
            prompt=len(seq.prompt), cached_blocks=prep.matched,
        )

    def _sampling_of(self, req: PreprocessedRequest) -> Tuple[float, int, float]:
        return self._admitter._sampling_of(req)

    def _procs_of(self, req: PreprocessedRequest):
        return self._admitter._procs_of(req)

    def _requeue(self, seq: _Sequence) -> None:
        seq.block_ids = []
        seq.block_hashes = []
        seq.win_ids = []
        seq.win_pinned = 0
        self._waiting.appendleft(seq)

    def set_spec_suspended(self, suspended: bool) -> None:
        """Brownout lever: park/restore speculative decode without
        touching the engine args (runtime/overload.py wires this to the
        healthy↔brownout transitions). Takes effect at the next tick;
        in-flight proposals finish normally."""
        suspended = bool(suspended)
        if suspended == self._spec_suspended:
            return
        self._spec_suspended = suspended
        if self.args.spec_mode:
            self.flight.record("spec_suspend", on=suspended)
        self._wake.set()

    def _shed_expired(self, seq: _Sequence) -> None:
        """Finish a waiting sequence that stopped BEFORE admission. A
        deadline expiry is a typed, client-visible error (the request's
        budget is gone — admitting it would burn prefill on work nobody
        is waiting for); a plain cancellation stays a quiet CANCELLED."""
        self._revoke_prefetch(seq, "shed")
        if seq.context.stop_reason == "deadline":
            self.deadline_sheds += 1
            note_activity("deadline_expired")
            self.flight.record(
                "deadline_shed", request_id=seq.request.request_id,
                queued_s=round(seq.context.elapsed, 3),
            )
            seq.queue.put_nowait(
                BackendOutput(
                    error="deadline expired before admission "
                    "(shed at dequeue, no prefill spent)",
                    error_kind="timeout",
                    finish_reason=FinishReason.ERROR,
                )
            )
        else:
            seq.queue.put_nowait(
                BackendOutput(finish_reason=FinishReason.CANCELLED)
            )

    async def _sleep_tick(self) -> bool:
        """Handle a pending sleep request / asleep state. Returns True when
        this tick is consumed (the main loop should ``continue``)."""
        if self._sleep_level > 0:  # asleep: idle until wake() or stop()
            self.step_metrics.forget_frame()
            self._publish_stats()
            self._wake.clear()
            with self.step_metrics.phase("tick.idle"):
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=0.05)
                except asyncio.TimeoutError:
                    pass
            return True
        # Sleep requested but not yet asleep: drain active sequences first
        # (no new admissions), then release device memory.
        if any(s is not None for s in self._slots):
            await self._decode_tick()
            return True
        if self._pending_prefill is not None:
            # A budget-parked prefill must resolve before sleeping —
            # pool.clear() below would free its pinned blocks in place.
            # Finish it unbudgeted (_tick_budget_left is None between
            # ticks); its sequences then drain via the decode branch
            # above on subsequent passes.
            await self._drain_inflight()
            await self._continue_pending()
            return True
        level = self._sleep_requested
        if level is None:  # wake() cancelled the request mid-drain
            return True
        # All sequences have finished; reap any zombie bursts so nothing
        # holds device buffers (or stale carry) across the sleep.
        await self._drain_inflight()
        self._sleep_requested = None
        self.pool.clear()  # on the loop thread: emits 'cleared' to routers
        # _sleep_inflight closes the window where a concurrent wake() sees
        # "not sleeping, nothing requested" while _do_sleep is in flight —
        # it must queue its _do_wake behind us on the device executor.
        self._sleep_inflight = True
        try:
            await self._device(self._do_sleep, level)
        finally:
            self._sleep_inflight = False
        self.flight.record("sleep", level=level)
        self._publish_stats()
        self._sleep_event.set()
        return True

    def _prepare_decode(self, lookahead: int) -> "List[_Sequence]":
        """Shared decode-tick preamble: finish cancelled/overlong sequences
        and ensure every survivor has blocks covering the next ``lookahead``
        positions (preempt-by-recompute when the pool is dry). Returns the
        active sequences."""
        args = self.args
        for slot in range(args.max_num_seqs - 1, -1, -1):
            seq = self._slots[slot]
            if seq is None:
                continue
            if seq.context.stopped:
                self._finish(seq, FinishReason.CANCELLED)
                continue
            pos = int(self._pos[slot])
            if pos >= args.max_model_len:
                self._finish(seq, FinishReason.LENGTH)
                continue
            last_pos = min(
                pos + lookahead - 1, args.max_blocks_per_seq * args.block_size - 1
            )
            need_blocks = last_pos // args.block_size + 1
            full_table = self._block_tables[slot] if self.window is None else (
                self._block_tables[slot, 0])
            while len(seq.block_ids) < need_blocks:
                b = self.pool.alloc()
                if b is None:
                    self._preempt(seq)
                    break
                full_table[len(seq.block_ids)] = b
                seq.block_ids.append(b)
                self._dirty_tables.add(slot)
            if self.window is not None and seq.slot >= 0:
                taken = self._window_advance(seq, pos, last_pos)
                if taken is None:
                    self._preempt(seq, "window page group exhausted")
                    continue
                for i in taken:
                    self._block_tables[slot, 1, i] = seq.win_ids[i]
                    self._dirty_tables.add(slot)
        return [s for s in self._slots if s is not None]

    # -- speculative decoding (prompt-lookup / n-gram) ---------------------
    # Policy lives in engines/tpu/spec.py (NgramSpecDecoder); the engine
    # keeps the device hook + a lazily built decoder.

    @property
    def _spec(self):
        if self.__dict__.get("_spec_decoder") is None:
            from dynamo_tpu.engines.tpu.spec import NgramSpecDecoder

            self.__dict__["_spec_decoder"] = NgramSpecDecoder(self)
        return self.__dict__["_spec_decoder"]

    def _run_spec(self, tokens, start_pos, chunk_lens, block_tables,
                  adapter_ids, temp=None, topk=None, topp=None):
        return self.runner.run_spec(
            tokens, start_pos, chunk_lens, block_tables, adapter_ids,
            temp=temp, topk=topk, topp=topp,
        )

    def _propose(self, seq: _Sequence) -> List[int]:
        return self._spec.propose(seq)

    def _spec_eligible(self, active: "List[_Sequence]") -> bool:
        return self._spec.eligible(active)

    async def _spec_tick(self) -> bool:
        handled = await self._spec.tick()
        if handled:
            self.flight.record(
                "spec_tick", proposed=self.spec_proposed,
                accepted=self.spec_accepted,
            )
        return handled

    async def _decode_tick(self) -> None:
        """Pipelined decode tick: top the in-flight window up to
        ``pipeline_depth`` bursts, then reap (read back + emit) the oldest.
        At depth 1 this degenerates to dispatch-then-reap — today's fully
        synchronous behavior. At depth 2 the device always has the next
        burst queued while the host overlaps readback, stop-condition
        reconciliation and emission of the previous one."""
        depth = self._pipeline_depth()
        while len(self._inflight) < depth:
            if not await self._dispatch_burst():
                break
        if self._inflight:
            await self._reap_burst()

    def _blocks_shortfall(self, lookahead: int) -> int:
        """How many blocks the next _prepare_decode would need beyond what
        the pool can serve (same per-seq arithmetic, so a non-positive
        shortfall guarantees allocation succeeds without preemption)."""
        args = self.args
        need = 0
        for slot, seq in enumerate(self._slots):
            if seq is None:
                continue
            pos = int(self._pos[slot])
            last_pos = min(
                pos + lookahead - 1,
                args.max_blocks_per_seq * args.block_size - 1,
            )
            need += max(0, last_pos // args.block_size + 1 - len(seq.block_ids))
        # (The window group cannot fall short: it is sized for every row's
        # most pages, and what a row holds behind its window goes back
        # before it takes more.)
        return need - self.pool.free_blocks

    async def _dispatch_burst(self) -> bool:
        """Prepare + enqueue one decode burst. Returns False when there is
        nothing to decode. H2D on the steady path is ZERO: slot state and
        tables upload only for dirty slots; tokens/pos ride the device
        carry of the previous burst."""
        args = self.args
        K = args.decode_steps
        lookahead = K * PIPELINE_LOOKAHEAD_BURSTS
        # Preemption drains the pipeline first: if growing the tables could
        # exhaust the pool, reap in-flight bursts (their finishes may free
        # blocks) before letting _prepare_decode preempt — so a preemption
        # decision is only ever taken against fully-reconciled state, at
        # the same reap boundary regardless of pipeline depth.
        while self._inflight and self._blocks_shortfall(lookahead) > 0:
            await self._reap_burst()
        with self.step_metrics.phase("tick.decode_build"):
            active = self._prepare_decode(lookahead)
            if not active:
                return False

            state_sync = self._build_state_sync()
            table_sync = self._build_table_sync()
            # Width bucket for THIS burst: host pos lags the device carry by
            # K per in-flight burst, so the burst being dispatched spans up
            # to host pos + (inflight + 1) * K — the same bucket a depth-1
            # engine computes for the same burst index.
            inflight_off = K * len(self._inflight)
            max_blocks = 1
            live_pages = 0
            win = self.window
            win_live = win_held = win_dead = 0
            sparse = self.config.sparse_index
            picked = {"pages_selected": 0, "pages_live": 0}
            on_path = {"sparse": 0, "dense": 0}
            for seq in active:
                ctx = int(self._pos[seq.slot]) + inflight_off + K
                blocks = (ctx - 1) // args.block_size + 1
                live_pages += blocks
                max_blocks = max(max_blocks, blocks)
                if sparse is not None:
                    on_sparse = ctx >= sparse.dense_len
                    on_path["sparse" if on_sparse else "dense"] += 1
                    picked["pages_live"] += blocks
                    picked["pages_selected"] += (
                        min(sparse.topk, blocks) if on_sparse else blocks)
                if win is not None:
                    win_live += blocks - win.first_live(ctx - K)
                    win_held += win.held(seq.win_ids)
                    win_dead += win.dead(seq.win_ids, ctx - K)
            nb_bucket = table_width_bucket(max_blocks, args.max_blocks_per_seq)
            want_logprobs = any(
                s.request.sampling.logprobs is not None for s in active
            )
            want_procs = any(self._uses_procs[s.slot] for s in active)
        t0 = time.monotonic()
        # Chaos seam, deliberately AFTER the sync payloads were built (the
        # dirty sets are already cleared): recovery must resync every slot
        # from the mirrors (_abort_inflight), and the position-keyed RNG
        # must regenerate identical tokens on the retried burst.
        fault_point(fault_names.ENGINE_TICK_DISPATCH)
        with self.step_metrics.phase(
            "tick.decode_dispatch", rows=len(active), nb=nb_bucket,
            inflight=len(self._inflight),
        ):
            handles = await self._device(
                self._dispatch_on_device, nb_bucket, want_logprobs,
                want_procs, state_sync, table_sync,
            )
        self.step_metrics.observe_inflight(len(self._inflight) + 1)
        self.step_metrics.observe_decode_pages(
            live_pages, args.max_num_seqs * nb_bucket
        )
        # The burst's own predicate (llama.decode_multi): live rows only.
        self.step_metrics.observe_sampler_steps(
            K, any(self._temp[s.slot] > 0.0 for s in active))
        if self.runner.ssd_step is not None:
            kernel = self.runner.ssd_step == self.runner.SSD_STEP_LIVE
            self.step_metrics.observe_ssm_decode(
                args.decode_steps * (len(active) if kernel else args.max_num_seqs),
                args.decode_steps * args.max_num_seqs,
            )
        if win is not None:
            self.step_metrics.observe_window_pages(win_live, win_held, win_dead)
        if sparse is not None:
            self.step_metrics.observe_sparse(
                picked["pages_selected"], picked["pages_live"], on_path)
            acc = self.sparse_attention
            for key, n in picked.items():
                acc[key] += n
            for path, n in on_path.items():
                acc["rows"][path] += n
        self._inflight.append(
            _InflightBurst(
                handles=handles,
                seqs=[(s.slot, s) for s in active],
                t_dispatch=t0,
                occupancy=len(active),
                nb_bucket=nb_bucket,
            )
        )
        self.flight.record(
            "dispatch", nb=nb_bucket, occupancy=len(active),
            inflight=len(self._inflight),
        )
        return True

    def _build_state_sync(self):
        """Payload for DeviceRunner.sync_slots covering the dirty slots
        (None when clean — the steady-state case)."""
        if not self._dirty_state:
            return None
        slots = sorted(self._dirty_state)
        self._dirty_state.clear()
        sl = np.asarray(slots, dtype=np.int64)
        rows = {
            "tokens": self._tok_mirror[sl],
            "pos": self._pos[sl],
            "active": np.asarray(
                [1 if self._slots[s] is not None else 0 for s in slots],
                np.int32,
            ),
            "temp": self._temp[sl],
            "topk": self._topk[sl],
            "topp": self._topp[sl],
            "adapter_ids": self._adapter_ids[sl],
            "salts": self._salts[sl],
            "minp": self._minp[sl],
            "rep": self._rep[sl],
            "pres": self._pres[sl],
            "freq": self._freq[sl],
            "bias_ids": self._bias_ids[sl],
            "bias_vals": self._bias_vals[sl],
        }
        return (slots, rows)

    def _build_table_sync(self):
        if not self._dirty_tables:
            return None
        slots = sorted(self._dirty_tables)
        self._dirty_tables.clear()
        return (slots, self._block_tables[np.asarray(slots, np.int64)].copy())

    async def _reap_burst(self, wait_phase: str = "tick.decode_wait") -> None:
        """Read back + emit the OLDEST in-flight burst. Stop conditions are
        reconciled here: a row whose sequence already finished (in a burst
        reaped while this one was in flight) is dropped — its slot was
        deactivated and its device pos reset by the dirty-slot sync, and
        its speculative KV writes landed in reserved lookahead blocks.
        ``wait_phase`` names the tick phase the readback wait belongs to
        (``tick.drain`` when the pipeline is drained ahead of admission)."""
        # Chaos seam: a reap failure drops an in-flight burst whose device
        # carry ran ahead of emission — the abort path must roll back.
        fault_point(fault_names.ENGINE_TICK_REAP)
        rec = self._inflight.popleft()
        with self.step_metrics.phase(
            wait_phase, rows=rec.occupancy, nb=rec.nb_bucket,
            inflight=len(self._inflight),
        ):
            toks, logps, topv, topi = await self._device(
                self._read_on_device, rec.handles, rec.occupancy
            )
        self._t_last_ready = time.monotonic()
        with self.step_metrics.phase("tick.emit", rows=rec.occupancy):
            self._emit_reaped(rec, toks, logps, topv, topi)

    def _emit_reaped(self, rec, toks, logps, topv, topi) -> None:
        """Host half of a reap: stop conditions and one output per row,
        then the step, budgeter and flight accounting."""
        self.steps += 1
        gen0 = self.generated_tokens
        moe = getattr(rec.handles, "moe_host", None)
        experts = self.config.specs_of("experts")
        if moe is not None and experts:
            held = experts[0].n_held
            self.step_metrics.observe_moe(
                float(moe[0]), self.args.decode_steps * len(experts) * held,
                float(moe[1]), float(moe[2]) / held,
            )
            # Every live row of the burst chose top_k experts a layer-step;
            # ``moe[2]`` counts the choices that fell on held ones.
            choices = (self.args.decode_steps * len(experts) * experts[0].top_k
                       * rec.occupancy)
            self.step_metrics.observe_moe_assignments(float(moe[2]), choices)
        rows = 0
        for slot, seq in rec.seqs:
            if self._slots[slot] is not seq or seq.slot != slot:
                continue  # finished/preempted while this burst was in flight
            rows += 1
            self._emit_burst(
                seq, toks[slot], logps[slot],
                None if topv is None else topv[slot],
                None if topi is None else topi[slot],
            )
        if rows:
            self._note_frame(rows)
        # Emitted (post-stop-condition) tokens, not dispatched K×B — the
        # honest throughput number the planner divides by step time. The
        # duration is dispatch→readback of THIS burst (queue-inclusive at
        # depth ≥ 2).
        self.step_metrics.observe_decode(
            time.monotonic() - rec.t_dispatch, rec.occupancy,
            self.generated_tokens - gen0,
        )
        if self._budgeter is not None:
            # ITL signal for the tick budgeter: same burst accounting the
            # step metrics use, with the reap's ready stamp as "now" so
            # the inter-reap gap is measured between readbacks.
            self._budgeter.observe_decode(
                self._t_last_ready - rec.t_dispatch, rec.occupancy,
                self.generated_tokens - gen0, now=self._t_last_ready,
            )
        self.flight.record(
            "reap", occupancy=rec.occupancy,
            tokens=self.generated_tokens - gen0,
            dur_ms=round(1000 * (self._t_last_ready - rec.t_dispatch), 3),
        )
        self._publish_stats()

    def _note_frame(self, rows: int) -> None:
        """``rows`` live rows just got a frame: the interval since the last
        one goes to the step metrics, and a stalled one on the record, with
        what lay in it (the worker's log is kept beside a benchmark run)."""
        stall = self.step_metrics.observe_frame(rows)
        if stall is None:
            return
        stall["waiting"] = len(self._waiting)
        self.flight.record("stall", **stall)
        # dynlint: disable=DYN002 -- a stall is an anomaly, not a steady tick: one line per frame interval over half a second, and the line is the record an operator and a benchmark run keep
        logger.warning(
            "stall: %.3f s between two frames (%s) with %d rows waiting and "
            "%d queued; most of it in %s; %d compiles and %.3f s of garbage "
            "collection inside it",
            stall["interval_s"], stall["frame_kind"], rows, stall["waiting"],
            ", ".join(f"{p} {s:.3f} s" for p, s in stall["phases"].items()),
            stall["compiles"], stall["gc_s"],
        )

    async def _drain_inflight(self) -> None:
        """Barrier: reap every in-flight burst. Required before any event
        that must see (or mutate) fully-reconciled slot/pool state —
        admission installs, speculative ticks, sleep, preemption."""
        while self._inflight:
            await self._reap_burst(wait_phase="tick.drain")

    def _abort_inflight(self) -> None:
        """Failure path: drop un-reaped bursts and resync EVERYTHING from
        the host mirrors. The device carry (pos/tokens) advanced past what
        was emitted; marking all slots dirty rolls the device state back to
        the scheduler's view, and the position-keyed sampling RNG makes the
        retried bursts regenerate the identical tokens."""
        aborted = len(self._inflight)
        self.flight.record("abort", inflight=aborted)
        # Post-mortem: persist both event rings (tick loop + device thread)
        # before the retry path overwrites the history that led here.
        # Rate-limited: a flapping device fails ticks repeatedly, and one
        # bounded dump per window captures the episode — an unbounded
        # stream of files (each a blocking write on this loop) would not.
        now = time.monotonic()
        path = None
        if now - self._last_flight_dump >= 30.0 and (
            self.flight.total or self.runner.flight.total
        ):
            path = dump_flight(
                {"engine": self.flight, "runner": self.runner.flight},
                reason="abort_inflight",
            )
            if path:
                # Stamp only on SUCCESS: a transiently unwritable dump dir
                # must not consume the rate-limit window for the episode.
                self._last_flight_dump = now
        logger.error(
            "aborted %d in-flight burst(s)%s", aborted,
            f"; flight recorder dumped to {path}" if path else "",
        )
        self._inflight.clear()
        self._dirty_state.update(range(self.args.max_num_seqs))
        self._dirty_tables.update(range(self.args.max_num_seqs))
        # Aborted proc-variant bursts already installed their advanced
        # out_counts into runner.proc_state at dispatch — rebuild every
        # live penalty-using slot's counts from the EMITTED history, or
        # the retry would apply penalties against double-counted tallies
        # (different logits → different tokens than the no-failure run).
        for slot, seq in enumerate(self._slots):
            if seq is not None and self._uses_procs[slot]:
                self.runner.proc_reset_slot(
                    slot, seq.request.token_ids, seq.generated
                )
        if self.config.has_recurrent_state:
            # The recurrent state advanced with the dropped bursts and has
            # no host mirror to roll back to: every live sequence recomputes.
            for seq in [s for s in self._slots if s is not None]:
                self._preempt(seq, why="in-flight bursts aborted")
        self._publish_stats()

    def _emit_burst(
        self, seq: _Sequence, toks: np.ndarray, logps: np.ndarray,
        topv: Optional[np.ndarray] = None, topi: Optional[np.ndarray] = None,
    ) -> None:
        """Consume one fused burst for a sequence: apply stop conditions and
        stream ONE BackendOutput for the whole burst. Vectorized: the
        per-token Python loop cost ~0.2 s of pure host time per 64×256
        wave (16k iterations)."""
        slot = seq.slot
        req = seq.request
        stop = req.stop
        K = len(toks)
        base = len(seq.generated)
        arr = np.asarray(toks)

        # Earliest stop position within the burst, per condition (K = none).
        def first_hit(token_ids, honor_min) -> int:
            if not token_ids:
                return K
            m = np.isin(arr, token_ids)
            if honor_min and stop.min_tokens is not None:
                # token k is the (base+k+1)-th generated token
                m &= (base + np.arange(K) + 1) >= stop.min_tokens
            idx = np.flatnonzero(m)
            return int(idx[0]) if idx.size else K

        eos_k = (
            K if stop.ignore_eos
            else first_hit(req.eos_token_ids or [], True)
        )
        stop_k = first_hit(stop.stop_token_ids or [], True)
        len_k = K
        if stop.max_tokens is not None:
            len_k = min(max(stop.max_tokens - base - 1, 0), K)
        model_k = min(
            max(self.args.max_model_len - len(seq.all_tokens) - 1, 0), K
        )
        cut = min(eos_k, stop_k, len_k, model_k)
        reason: Optional[FinishReason] = None
        if cut < K:
            # Precedence at the same position mirrors the per-token order:
            # EOS > STOP > LENGTH.
            if cut == eos_k:
                reason = FinishReason.EOS
            elif cut == stop_k:
                reason = FinishReason.STOP
            else:
                reason = FinishReason.LENGTH
        n_take = cut + 1 if cut < K else K
        emitted = arr[:n_take].tolist()
        emitted_logps = np.asarray(logps)[:n_take]
        seq.generated.extend(emitted)
        seq.all_tokens.extend(emitted)
        seq.next_token = emitted[-1]
        self._tok_mirror[slot] = emitted[-1]
        self.generated_tokens += n_take
        self._pos[slot] += n_take  # these tokens' KV is now resident
        self._commit_complete_blocks(seq, slot)

        logprobs = None
        if req.sampling.logprobs is not None:
            # Entry 0 is the SAMPLED token; entries 1.. are the request's
            # top-N alternatives (may repeat the sampled token, as OpenAI's
            # top_logprobs does when it ranks in the top N).
            n_top = min(int(req.sampling.logprobs), self.args.top_logprobs_cap)
            logprobs = []
            for k, (t, lp) in enumerate(zip(emitted, emitted_logps)):
                entry = [TokenLogprob(token_id=t, logprob=float(lp))]
                if topv is not None and n_top > 0:
                    entry.extend(
                        TokenLogprob(token_id=int(topi[k, j]), logprob=float(topv[k, j]))
                        for j in range(n_top)
                    )
                logprobs.append(entry)
        seq.queue.put_nowait(
            BackendOutput(
                token_ids=emitted,
                finish_reason=reason,
                cumulative_tokens=len(seq.generated),
                logprobs=logprobs,
            )
        )
        if reason is not None:
            self._finish(seq, reason, emit=False)

    def _commit_complete_blocks(self, seq: _Sequence, slot: int) -> None:
        """Commit every newly completed block (bulk form of the old
        per-token boundary check)."""
        args = self.args
        if not args.enable_prefix_caching:
            return
        pos = int(self._pos[slot])
        while True:
            bi = len(seq.block_hashes)
            if (bi + 1) * args.block_size > pos or bi >= len(seq.block_ids):
                return
            parent = seq.block_hashes[-1] if seq.block_hashes else None
            h = compute_block_hashes(
                seq.all_tokens[bi * args.block_size : (bi + 1) * args.block_size],
                args.block_size,
                parent_hash=parent,
                salt=seq.hash_salt,
            )[0]
            self.pool.commit(seq.block_ids[bi], h, parent)
            if self.window is not None:
                self.window.commit(seq.win_ids, bi, h, parent)
            seq.block_hashes.append(h)
            if self.kvbm is not None:
                self.kvbm.notify_commit(h, bi + 1, parent=parent)

    def _preempt(self, seq: _Sequence, why: str = "KV pool exhausted") -> None:
        """Release blocks and requeue for recompute (vLLM-style preemption).
        Only ever reached with an empty pipeline (_dispatch_burst drains
        before letting allocation fail), so the recompute — whose sampling
        keys are position-salted — regenerates the identical stream. A
        hybrid model's recurrent state goes with the slot: the recompute
        rebuilds it (from a snapshot as far as one reaches)."""
        # dynlint: disable=DYN002 -- preemption is a capacity event, not a steady-state tick: it fires at most once per pool exhaustion and operators page on it
        logger.warning("preempting request %s (%s)", seq.request.request_id, why)
        self.flight.record(
            "preempt", request_id=seq.request.request_id, slot=seq.slot,
            blocks=len(seq.block_ids),
        )
        self._release_blocks(seq)
        slot = seq.slot
        self._slots[slot] = None
        self._pos[slot] = 0
        self._tok_mirror[slot] = 0
        self._dirty_state.add(slot)
        self.preemptions += 1
        seq.slot = -1
        self._requeue(seq)

    def _emit_token(
        self, seq: _Sequence, token: int, logprob: float,
        top: Optional[list] = None,  # [(token_id, logprob)] top-N candidates
    ) -> None:
        """Append a generated token, evaluate stop conditions, stream output."""
        seq.generated.append(token)
        seq.all_tokens.append(token)
        seq.next_token = token
        self.generated_tokens += 1
        req = seq.request
        stop = req.stop
        n = len(seq.generated)
        min_ok = stop.min_tokens is None or n >= stop.min_tokens
        reason: Optional[FinishReason] = None
        if not stop.ignore_eos and min_ok and token in (req.eos_token_ids or []):
            reason = FinishReason.EOS
        elif min_ok and token in (stop.stop_token_ids or []):
            reason = FinishReason.STOP
        elif stop.max_tokens is not None and n >= stop.max_tokens:
            reason = FinishReason.LENGTH
        elif len(seq.all_tokens) >= self.args.max_model_len:
            reason = FinishReason.LENGTH

        logprobs = None
        if req.sampling.logprobs is not None:
            entry = [TokenLogprob(token_id=token, logprob=logprob)]
            if top:
                n_top = min(int(req.sampling.logprobs), self.args.top_logprobs_cap)
                entry.extend(
                    TokenLogprob(token_id=t, logprob=lp) for t, lp in top[:n_top]
                )
            logprobs = [entry]
        seq.queue.put_nowait(
            BackendOutput(
                token_ids=[token],
                finish_reason=reason,
                cumulative_tokens=n,
                logprobs=logprobs,
            )
        )
        if reason is not None:
            self._finish(seq, reason, emit=False)

    # -- KV block export/import (disaggregation + tiered offload) ----------
    #
    # Threading contract: BlockPool (and its KV-event callback, which touches
    # asyncio state) is only ever mutated on the event-loop thread; ONLY the
    # device array work runs on the executor thread, which also serializes it
    # with decode steps (the caches are donated through every step).

    async def export_blocks_wire_async(self, block_hashes: List[int]):
        """Copy committed blocks out of HBM in POOL-NATIVE wire form
        (disagg/wire.py KvWireBlocks): quantized pools ship {q8, scales}
        without ever materializing the dense form — half the readback and
        half the wire; dense pools ship their storage dtype. Returns
        (found_hashes, wire) — the prefill side of disaggregated P/D
        (ref: kv_router/prefill_router.rs bootstrap → NIXL read; here the
        transfer is host-staged DCN, SURVEY §2.5 TPU-equivalent note).
        Stops at the first miss: only a leading run of the chain is useful.
        Found blocks are pinned across the device copy so eviction can't
        recycle them mid-gather."""
        wire_check_config(self.config, "the disaggregation wire")
        matched, pinned_ids = self.pool.pin_prefix(block_hashes)
        try:
            ids = pinned_ids
            found = list(block_hashes[:matched])
            if not ids:
                return [], None

            # Two-phase: enqueue on the device thread (cheap), read back on
            # the transfer thread — decode ticks interleave with the copy.
            handles = await self._device(
                self.runner.gather_blocks_wire_dispatch, ids
            )
            wire = await asyncio.get_running_loop().run_in_executor(
                self._transfer_executor,
                self.runner.gather_blocks_wire_readback, handles,
            )
            # ``bytes`` is the ACTUAL serialized wire size (payload +
            # scales), not a post-dequant figure — the flight ring and the
            # bench read this as the transfer-plane cost.
            self.flight.record(
                "kv_export", blocks=len(found), bytes=int(wire.nbytes),
                dtype=wire.dtype,
            )
            return found, wire
        finally:
            if pinned_ids:
                self.pool.release(pinned_ids, block_hashes[: len(pinned_ids)])

    async def export_blocks_async(self, block_hashes: List[int]):
        """Dense-form export: (found_hashes, k_blocks, v_blocks) shaped
        [n, L, block_size, KH, D]. Kept for consumers that want dense
        arrays regardless of the pool encoding (checkpoint interop, the
        v1 transfer schema); quantized pools dequantize host-side to the
        v1 wire dtype. The transfer path proper should use
        export_blocks_wire_async."""
        wire_check_config(self.config, "the disaggregation wire")
        found, wire = await self.export_blocks_wire_async(block_hashes)
        if wire is None:
            return found, None, None
        k, v = wire.to_dense()
        return found, k, v

    async def import_blocks_wire_async(
        self, block_hashes: List[int], wire,
        *, anchor_parent: Optional[int] = None,
    ) -> int:
        """Insert transferred wire blocks (KvWireBlocks) into the pool as
        cached (committed) content, so normal prefix-cached admission
        reuses them. Returns how many were installed (stops when the pool
        is dry). All four interop cells land here: int8 wire installs
        verbatim into int8 pools and dequantizes on device into dense
        pools; dense wire requantizes on device into int8 pools.

        ``anchor_parent``: hash the FIRST block chains from when the caller
        knows the preceding block (mid-tree restore, suffix transfer whose
        parent is already resident)."""
        wire_check_config(self.config, "the disaggregation wire")
        ids: List[int] = []
        sel: List[int] = []
        parents: List[Optional[int]] = []
        parent: Optional[int] = anchor_parent
        for i, h in enumerate(block_hashes):
            if self.pool.contains(h):
                parent = h
                continue
            b = self.pool.alloc()
            if b is None:
                break
            # Allocated but NOT committed yet: private to us, so nobody can
            # pin the hash and attend over unwritten data.
            ids.append(b)
            sel.append(i)
            parents.append(parent)
            parent = h
        if not ids:
            return 0

        sub = wire.take(sel)
        try:
            await self._device(self.runner.scatter_blocks_wire, ids, sub)
        except Exception:
            for b in ids:
                self.pool.release([b], [])  # data never landed; just free
            raise
        for b, i, par in zip(ids, sel, parents):
            h = block_hashes[i]
            self.pool.commit(b, h, par)
            # imported blocks start unreferenced (cached): release our pin
            self.pool.release([b], [h])
        self.flight.record(
            "kv_import", blocks=len(ids), bytes=int(sub.nbytes),
            dtype=sub.dtype,
        )
        return len(ids)

    async def import_blocks_async(
        self, block_hashes: List[int], k_blocks, v_blocks,
        *, anchor_parent: Optional[int] = None,
    ) -> int:
        """Dense-form import (v1 surface): wraps the arrays as a dense
        wire payload and funnels through import_blocks_wire_async so the
        pin/scatter/commit/rollback invariants live in ONE place."""
        wire_check_config(self.config, "the disaggregation wire")
        from dynamo_tpu.disagg.wire import KvWireBlocks

        return await self.import_blocks_wire_async(
            block_hashes,
            KvWireBlocks.dense(np.asarray(k_blocks), np.asarray(v_blocks)),
            anchor_parent=anchor_parent,
        )

    # -- live-handoff drain (runtime/drain.py DrainController) -------------
    #
    # Threading contract: every method here runs on the event-loop thread.
    # Slot/pool mutation happens ONLY inside _service_drain_queues, which
    # the scheduler loop calls behind its drain barrier — so detach and
    # adoption observe the same fully-reconciled state admission does, and
    # the position-keyed sampling RNG makes the continuation bit-identical.

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Stop admitting new work: generate() refuses with a typed
        migratable error, the admission loop holds, and load reports carry
        ``draining`` so the router deflects placement immediately."""
        if not self._draining:
            self._draining = True
            # A budget-parked prefill returns to the queue whole, so the
            # controller's shed pass sees it immediately (it runs on this
            # same loop thread; the park state only exists between ticks).
            self._unpark_pending()
            self.flight.record("drain_begin")
            self._publish_stats()
            self._wake.set()

    def end_drain(self) -> None:
        """Abort a drain and return to serving (operator rollback)."""
        if self._draining:
            self._draining = False
            self.flight.record("drain_end")
            self._publish_stats()
            self._wake.set()

    def active_request_ids(self) -> List[str]:
        return [
            s.request.request_id for s in self._slots if s is not None
        ]

    def has_waiting(self) -> bool:
        return bool(self._waiting)

    def shed_waiting_for_drain(self, exc_factory) -> int:
        """Fail every not-yet-admitted request with a typed migratable
        error (``exc_factory(request_id) -> BaseException``) — the drain
        ladder's "typed requeue" rung: nothing was computed, so the
        frontend re-dispatches the request whole to a serving worker."""
        n = 0
        while self._waiting:
            seq = self._waiting.popleft()
            self.flight.record(
                "drain_requeue", request_id=seq.request.request_id
            )
            seq.queue.put_nowait(exc_factory(seq.request.request_id))
            n += 1
        if n:
            self._publish_stats()
        return n

    async def detach_for_handoff(self, request_id: str) -> Optional[_Sequence]:
        """Pull a live sequence out of its slot at the next reconciled
        burst boundary. Returns None when the stream already finished.
        The detached sequence keeps its pool blocks (and its output queue —
        the client is still attached to it); decode for it stops until a
        peer adopts it or the caller fails it down the ladder."""
        wire_check_config(self.config, "live handoff over the disaggregation wire")
        await self.start()
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._detach_requests.append((request_id, fut))
        self._wake.set()
        return await fut

    def _service_drain_queues(self) -> None:
        """Scheduler-loop half of detach/adopt (behind the drain barrier)."""
        while self._detach_requests:
            rid, fut = self._detach_requests.popleft()
            if fut.done():
                continue
            seq = next(
                (
                    s for s in self._slots
                    if s is not None and s.request.request_id == rid
                ),
                None,
            )
            if seq is None:
                fut.set_result(None)  # finished while the request queued
                continue
            slot = seq.slot
            seq.detach_pos = int(self._pos[slot])
            seq.t_detached = time.monotonic()
            self._slots[slot] = None
            self._pos[slot] = 0
            self._tok_mirror[slot] = 0
            self._dirty_state.add(slot)
            seq.slot = -1
            self.flight.record(
                "handoff_detach", request_id=rid, pos=seq.detach_pos,
                blocks=len(seq.block_ids),
            )
            fut.set_result(seq)
        while self._adoptions:
            slot = self._free_slot()
            if slot is None:
                break  # at capacity; retry once a finish frees a slot
            self._install_adopted(self._adoptions.popleft(), slot)
        self._publish_stats()

    async def export_detached(self, seq: _Sequence):
        """Gather a detached sequence's resident KV in pool-native wire
        form. Returns (HandoffTicket, KvWireBlocks): every committed block
        plus the partial tail rows covering ``detach_pos`` — the peer
        resumes with ZERO re-prefilled tokens."""
        from dynamo_tpu.disagg.handoff import HandoffTicket

        args = self.args
        pos = seq.detach_pos
        n_blocks = -(-pos // args.block_size)  # ceil; pos >= 1 always
        ids = seq.block_ids[:n_blocks]
        committed = seq.block_hashes[: min(len(seq.block_hashes), n_blocks)]
        # Chaos seam: the draining worker failing to read its own pool —
        # the ladder must absorb this as a re-prefill fallback.
        fault_point(fault_names.DRAIN_HANDOFF_EXPORT)
        handles = await self._device(
            self.runner.gather_blocks_wire_dispatch, ids
        )
        wire = await asyncio.get_running_loop().run_in_executor(
            self._transfer_executor,
            self.runner.gather_blocks_wire_readback, handles,
        )
        self.handoffs_exported += 1
        self.flight.record(
            "handoff_export", request_id=seq.request.request_id,
            blocks=len(ids), bytes=int(wire.nbytes), dtype=wire.dtype,
        )
        cfg = self.config
        ticket = HandoffTicket(
            request=seq.request.to_dict(),
            generated=list(seq.generated),
            salt=seq.salt,
            hash_salt=seq.hash_salt,
            pos=pos,
            committed_hashes=list(committed),
            n_blocks=n_blocks,
            model=cfg.name,
            block_size=args.block_size,
            n_layers=cfg.n_layers,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim_,
            seed=args.seed,
        )
        return ticket, wire

    def release_detached(self, seq: _Sequence) -> None:
        """Free a detached sequence's pool blocks (after the peer accepted
        the handoff, or before failing it down the ladder)."""
        self._release_blocks(seq)

    def fail_detached(self, seq: _Sequence, exc: BaseException) -> None:
        """Surface ``exc`` through the sequence's output stream (the
        serving handler raises it; a migratable type makes the frontend
        re-dispatch with the already-streamed tokens carried — the PR 7
        re-prefill rung of the drain ladder)."""
        if seq.block_ids:
            self.release_detached(seq)
        seq.queue.put_nowait(exc)

    async def adopt_handoff(self, ticket, wire, context: Context) -> _Sequence:
        """Peer side: install a HandoffTicket's blocks and queue the
        sequence for slot installation at the scheduler's next reconciled
        boundary. Raises HandoffRefused when this engine cannot take it
        (capacity, pool pressure, draining itself)."""
        wire_check_config(self.config, "live handoff over the disaggregation wire")
        from dynamo_tpu.disagg.handoff import HandoffRefused

        await self.start()
        if self._draining:
            raise HandoffRefused("peer is itself draining")
        if self._failure is not None:
            raise HandoffRefused(f"peer engine failed: {self._failure}")
        live = sum(1 for s in self._slots if s is not None)
        earmarked = len(self._adoptions) + self._admitting
        if self._pending_prefill is not None:
            # A budget-parked prefill batch holds slots-to-be exactly
            # like an in-flight admission does.
            earmarked += len(self._pending_prefill.held)
        if live + earmarked >= self.args.max_num_seqs:
            raise HandoffRefused(
                f"no free slot ({live} live + {len(self._adoptions)} "
                f"pending adoptions + {self._admitting} admitting of "
                f"{self.args.max_num_seqs})"
            )
        # Chaos seam: the receiving worker dying mid-adoption — the source
        # absorbs it by trying the next peer or falling down the ladder.
        fault_point(fault_names.DRAIN_HANDOFF_IMPORT)
        committed = list(ticket.committed_hashes)
        n_committed = len(committed)
        if n_committed:
            # Shared-cache rows install through the proven disagg path
            # (pin/scatter/commit/rollback in ONE place), then pin for the
            # adopted sequence exactly like prefix-cached admission.
            await self.import_blocks_wire_async(
                committed, wire.take(list(range(n_committed)))
            )
        matched, ids = (
            self.pool.pin_prefix(committed) if committed else (0, [])
        )
        tail_ids: List[int] = []
        try:
            if matched < n_committed:
                raise HandoffRefused(
                    f"pool pressure: only {matched}/{n_committed} committed "
                    "blocks resident after import"
                )
            tail_rows = list(range(n_committed, ticket.n_blocks))
            for _ in tail_rows:
                b = self.pool.alloc()
                if b is None:
                    raise HandoffRefused("pool dry for private tail blocks")
                tail_ids.append(b)
            if tail_ids:
                await self._device(
                    self.runner.scatter_blocks_wire, tail_ids,
                    wire.take(tail_rows),
                )
        except Exception:
            self.pool.release(ids + tail_ids, committed[:matched])
            raise
        req = PreprocessedRequest.from_dict(dict(ticket.request))
        prompt = list(req.token_ids)
        seq = _Sequence(
            request=req,
            context=context,
            queue=asyncio.Queue(),
            prompt=prompt,
            all_tokens=prompt + list(ticket.generated),
            generated=list(ticket.generated),
            # RNG continuity: the ORIGINAL arrival salt, not a fresh one —
            # fold_in(seed, salt, pos) then draws the identical noise the
            # source would have drawn for every remaining token.
            salt=int(ticket.salt),
            hash_salt=int(ticket.hash_salt),
            detach_pos=int(ticket.pos),
        )
        seq.block_ids = ids + tail_ids
        seq.block_hashes = committed[:matched]
        self._adoptions.append(seq)
        self._wake.set()
        self.handoffs_adopted += 1
        self.flight.record(
            "handoff_adopt", request_id=req.request_id, pos=seq.detach_pos,
            blocks=len(seq.block_ids), carried=len(seq.generated),
        )
        return seq

    def _set_slot_state(
        self, seq: _Sequence, slot: int, *, pos: int, block_ids: Any,
        sp: Tuple[float, int, float], adapter_id: int, procs: Any,
        tok_mirror: int,
    ) -> None:
        """Every per-slot field the device-resident decode state reads,
        set for a new occupant. ONE implementation shared by
        Admitter._install (fresh admission) and _install_adopted (live
        handoff) — the two MUST stay field-for-field identical, or an
        adopted sequence samples with stale state from the slot's
        previous occupant and the bit-identical-continuation guarantee
        breaks.

        Mutates every field the device-resident decode state reads —
        reconcile at the next dispatch (_dirty_state/_dirty_tables).
        Installs only ever happen behind the scheduler's drain barrier,
        so no in-flight burst can be holding this slot stale-active.
        """
        seq.slot = slot
        self._slots[slot] = seq
        self._pos[slot] = pos
        self._block_tables[slot] = 0
        if self.window is None:
            self._block_tables[slot, : len(block_ids)] = block_ids
        else:
            self._block_tables[slot, 0, : len(block_ids)] = block_ids
            self._block_tables[slot, 1, : len(seq.win_ids)] = np.maximum(seq.win_ids, 0)
        self._temp[slot], self._topk[slot], self._topp[slot] = sp
        self._adapter_ids[slot] = adapter_id
        self._salts[slot] = seq.salt
        self._tok_mirror[slot] = int(tok_mirror)
        self._dirty_state.add(slot)
        self._dirty_tables.add(slot)
        # Logits-processor slot state: neutral unless this occupant asks —
        # stale device bookkeeping from a previous occupant is harmless
        # under neutral params (identity transform).
        self._uses_procs[slot] = procs is not None
        if procs is None:
            self._minp[slot] = 0.0
            self._rep[slot] = 1.0
            self._pres[slot] = 0.0
            self._freq[slot] = 0.0
            self._bias_ids[slot, :] = -1
            self._bias_vals[slot, :] = 0.0
        else:
            self._minp[slot] = procs.minp
            self._rep[slot] = procs.rep
            self._pres[slot] = procs.pres
            self._freq[slot] = procs.freq
            self._bias_ids[slot] = procs.bias_ids
            self._bias_vals[slot] = procs.bias_vals
            # Exact penalty state: original prompt only in the mask;
            # generated tokens restore the output counts (re-admitted
            # preemption and adopted handoff both carry them).
            self.runner.proc_reset_slot(
                slot, seq.request.token_ids, seq.generated
            )

    def _install_adopted(self, seq: _Sequence, slot: int) -> None:
        """Slot installation for an adopted sequence — Admitter._install
        minus prefill and minus the first-token emit (everything up to the
        handoff point already reached the client through the source)."""
        req = seq.request
        self._set_slot_state(
            seq, slot, pos=seq.detach_pos, block_ids=seq.block_ids,
            sp=self._sampling_of(req),
            adapter_id=self._lora_index.get(req.lora_name or "", 0),
            procs=self._procs_of(req),
            # seq.generated already holds the handoff token: the source
            # counted it at emit, proc_reset_slot restores that count.
            tok_mirror=seq.all_tokens[-1],
        )
        seq.next_token = seq.all_tokens[-1]
        self.flight.record(
            "handoff_install", request_id=req.request_id, slot=slot,
            pos=seq.detach_pos,
        )

    async def stream_adopted(
        self, seq: _Sequence
    ) -> AsyncIterator[BackendOutput]:
        """Continuation outputs of an adopted sequence (handoff handler).
        The adopted portion gets its own engine.decode span (the peer's
        share of the trajectory; the source's decode span ended at
        detach)."""
        t0 = time.monotonic()
        try:
            async for out in self._stream_outputs(seq):
                yield out
        finally:
            if seq.context.baggage.get("traceparent"):
                try:
                    from dynamo_tpu.utils.tracing import export_span

                    export_span(
                        "engine.decode", seq.context, start_mono=t0,
                        proc=getattr(self, "trace_proc", None),
                        adopted=True, generated=len(seq.generated),
                    )
                except Exception:
                    logger.debug(
                        "adopted phase-span export failed", exc_info=True
                    )


    # -- checkpoint / restore (the chrek/CRIU fast-cold-start role) --------
    # Logic lives in engines/tpu/kv_checkpoint.py; these stay as the
    # engine's public surface (system server + worker shutdown use them).

    def record_ckpt_corruption(self, detail: str) -> None:
        """Flight-ring note for a CRC-failed checkpoint restore (called by
        kv_checkpoint.py; the append lives here so the engine stays the
        ring's single writer)."""
        self.flight.record("ckpt_corrupt", detail=detail)

    async def save_checkpoint(self, ckpt_dir: str) -> Dict[str, Any]:
        from dynamo_tpu.engines.tpu import kv_checkpoint

        return await kv_checkpoint.save_checkpoint(self, ckpt_dir)

    async def load_checkpoint(self, ckpt_dir: str) -> int:
        from dynamo_tpu.engines.tpu import kv_checkpoint

        return await kv_checkpoint.load_checkpoint(self, ckpt_dir)

    def _finish(self, seq: _Sequence, reason: FinishReason, emit: bool = True) -> None:
        self.flight.record(
            "finish", request_id=seq.request.request_id, reason=reason.value,
            generated=len(seq.generated),
        )
        self._release_blocks(seq)
        if seq.slot >= 0:
            self._slots[seq.slot] = None
            self._pos[seq.slot] = 0
            self._tok_mirror[seq.slot] = 0
            # Deactivate the device-side slot at the next dispatch: any
            # still-in-flight burst that has this row stale-active gets its
            # tokens dropped at reap, and the row stops advancing after.
            self._dirty_state.add(seq.slot)
            seq.slot = -1
        if emit:
            seq.queue.put_nowait(BackendOutput(finish_reason=reason))
