"""Admission pipeline: waiting queue -> prefilled, installed sequences.

Split from the engine monolith (the engine owns the scheduler loop; this
owns the admission policy): batched prefix-cache matching + block leasing,
chunked prefill of the rows admitted together as the [Bp, C] programs that
price least (PrefillPrice, prefill_partition), failure containment
(poisoned-request quarantine with a systemic-failure breaker), and slot
installation including logits-processor bookkeeping.

An admission is up to ``prefill_batch`` requests popped together. Where
every row is a fresh prompt, ``_finish_admission`` partitions them into
groups of like length and ``_run_prefill`` runs the groups back to back,
each its own PendingPrefill, rounds and install, each first round a program
of the start-up ladder; an admission any of whose rows resumes cached
context, or whose joint program prices least, is one group. One path: a
joint batch is the partition with one group. The price reads the parameter
tree's shapes and nothing at run time, so a given batch partitions the same
way in every run.

Reference parity: the role of vLLM's scheduler admission + prefix-cache
lookup behind components/src/dynamo/vllm (SURVEY §2.2), restructured
around ONE batched device dispatch per chunk round (B=1 prefill wastes the
MXU; measured 16× rows for 2.4× cost on the v5e).
"""

from __future__ import annotations

import asyncio
import collections
import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from dynamo_tpu.engines.tpu.runner import _next_pow2
from dynamo_tpu.models.llama import step_weights
from dynamo_tpu.ops.moe import RIDGE_TOKENS
from dynamo_tpu.runtime import lifecycle
from dynamo_tpu.runtime.device_observe import global_compile_watcher
from dynamo_tpu.runtime.kv_reuse_observe import global_plane as kv_reuse_plane
from dynamo_tpu.tokens.blocks import adapter_salt, compute_block_hashes

from dynamo_tpu.llm.protocols.common import (
    BackendOutput,
    FinishReason,
    PreprocessedRequest,
)
from dynamo_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# The shortest chunk a prefill program is compiled for: one lane tile of
# tokens. A shorter chunk pads up to it, so the programs a fresh prompt
# can reach are a finite ladder (rows bucket × chunk bucket, the table
# following the chunk) that the engine compiles before it serves
# (Admitter.compile_prefill_ladder).
PREFILL_CHUNK_FLOOR = 128


def prefill_chunk_bucket(n_tokens: int, prefill_chunk: int) -> int:
    """Chunk width a round of at most ``n_tokens`` a row dispatches at:
    its power of two, no shorter than the floor, no longer than the
    engine's ``prefill_chunk``."""
    return min(max(_next_pow2(n_tokens), PREFILL_CHUNK_FLOOR), prefill_chunk)


def prefill_table_bucket(nb_needed: int, c_bucket: int, args: Any) -> int:
    """Table width of a prefill batch whose longest row holds
    ``nb_needed`` blocks and whose first round runs at ``c_bucket``: the
    blocks' power of two, no narrower than the chunk's own blocks — so a
    fresh prompt that fits one chunk is a function of the chunk bucket
    alone (a 10-token prompt meets the (floor, floor / block) program)."""
    return min(
        max(_next_pow2(nb_needed), c_bucket // args.block_size),
        args.max_blocks_per_seq,
    )


# The price of a prefill program, held against the chip's own times of the
# start-up ladder's 16 programs (wall time of a step through
# ``engine._run_step`` with its readback, and for the hybrid configuration the
# state programs beside it; builders' chip runs, PRs 52 and 53, PERF.md §5 has
# the table): the dense 0.5B configuration reads 4.7 ms at [1, 128] and 119.7
# at [8, 1024], the hybrid one 11.8 and 155.7, whatever ``lens`` says. A
# program costs the SUM of what it streams and what its positions multiply
# by, not the longer of the two (no shape of either ladder sits on a flat:
# from 128 positions on each further one costs 7.2 us dense, 17.6 hybrid),
# and its positions' products run at ``PREFILL_PEAK_SHARE`` of the bf16 peak
# (3.6 and 3.9 us a position at the peak: thin matrices, the scan, the
# gathers round the grouped expert kernel). ``DISPATCH_BYTES`` is what a
# dispatch costs beside its program, in bytes of HBM time: the arrays built
# and enqueued, the first tokens read back, the rows installed, a hybrid
# model's state gathered and scattered (1.8 ms). With these the price reads
# the dense ladder's programs within -16 / +39% and the hybrid one's within
# -26 / -5% (PR 53's timing; what decides a partition is the price's ORDER
# of the ways to cut a batch, and it picks within 1-5% of the table's own
# optimum); ``engines/tpu/ladder_times.py`` times a configuration's ladder
# and prints the two constants its times give (dense 0.37 and 1.55e9,
# hybrid 0.23 and 1.31e9: the constants sit between the two). A split is taken only where it prices
# ``PARTITION_MARGIN`` under the joint program: a difference the price cannot
# resolve changes no dispatch. Raise either only against the ladder's table,
# never against a cell's tail.
PREFILL_PEAK_SHARE = 0.3
DISPATCH_BYTES = 1.5e9
PARTITION_MARGIN = 1.1


@dataclass(frozen=True)
class PrefillPrice:
    """What a prefill program of static shape ``[B, C]`` costs, in the
    currency of ``ops/moe.chunk_costs`` (bytes of HBM time; FLOPs through
    ``RIDGE_TOKENS``, two bytes a weight): what it streams, plus what its
    ``T = B x C`` positions multiply by, live or padded alike (``lens`` only
    masks), plus a dispatch. ``streamed`` is every matrix a step reads
    whatever its tokens (the head among them, the embedding table not: it
    is looked up) plus, an expert layer, the held experts expected hit;
    ``active`` is the weights one position multiplies by (the head not: it
    runs over ``B`` rows). The three numbers are the model's own account of
    its parameter tree's shapes (``models/llama.step_weights``)."""

    always_bytes: float
    active_weights: float
    # (bytes of a layer's held experts, the share of them one token's
    # choices are expected to hit), a layer
    experts: Tuple[Tuple[float, float], ...] = ()

    def __call__(self, B: int, C: int) -> float:
        T = B * C
        streamed = self.always_bytes + sum(
            held * min(1.0, T * hit) for held, hit in self.experts)
        products = T * 2.0 * self.active_weights / (RIDGE_TOKENS * PREFILL_PEAK_SHARE)
        return streamed + products + DISPATCH_BYTES


def prefill_partition(
    left: Sequence[int], prefill_chunk: int, price: Callable[[int, int], float]
) -> List[List[int]]:
    """The rows admitted together (``left[r]`` tokens still to prefill, in
    arrival order) as the groups whose programs price least: rows sorted by
    what they have left, each group contiguous in that order and run as its
    own ``[rows bucket, chunk bucket]`` rounds, its longest row deciding how
    many rounds and at which chunk; the split with the least total price,
    one group where none prices ``PARTITION_MARGIN`` under the joint
    program. Groups come oldest member first, a group's rows in arrival
    order."""
    order = sorted(range(len(left)), key=lambda r: (left[r], r))

    def rounds(lo: int, hi: int) -> float:  # rows order[lo:hi] as one program a round
        Bp, todo, total = _next_pow2(hi - lo), left[order[hi - 1]], 0.0
        while True:
            total += price(Bp, prefill_chunk_bucket(min(todo, prefill_chunk), prefill_chunk))
            todo -= prefill_chunk
            if todo <= 0:
                return total

    best: List[Tuple[float, int]] = [(0.0, 0)]  # (price of order[:hi], where its last group starts)
    for hi in range(1, len(order) + 1):
        # ties go to the larger last group (the lower ``lo``)
        best.append(min((best[lo][0] + rounds(lo, hi), lo) for lo in range(hi)))
    if rounds(0, len(order)) <= PARTITION_MARGIN * best[-1][0]:
        return [list(range(len(left)))]
    groups, hi = [], len(order)
    while hi:
        lo = best[hi][1]
        groups.append(sorted(order[lo:hi]))
        hi = lo
    return sorted(groups, key=lambda g: g[0])


@dataclass
class PendingPrefill:
    """The full loop state of ONE group of an admission (the rows of it
    that run as one ``[Bp, C]`` program a round; a joint batch is the
    admission's only group): the loop-invariant arrays built once per group
    (_begin_prefill) plus per-row progress, and what of the admission is
    still to come (``held``, ``later``). The budgeted tick
    (engine._admit_tick_budgeted) parks one of these when the prefill token
    grant runs out mid-group — a chunk boundary is
    a clean resume point (positions, tables and sampling arrays are
    exactly what the next round needs, and the position-keyed sampling
    RNG draws the identical first tokens on resume), which is what keeps
    budgeter-on and budgeter-off token streams bit-identical."""

    batch: List[Tuple[Any, Any]]
    prompts: List[List[int]]
    pos: List[int]
    first: List[Optional[Tuple[int, float, Optional[list]]]]
    want_top: bool
    tables: np.ndarray
    temp: np.ndarray
    topk: np.ndarray
    topp: np.ndarray
    adapter: np.ndarray
    salts: np.ndarray
    procs: Optional[tuple]
    mm_embeds: Optional[np.ndarray]
    mm_slot_of: Optional[np.ndarray]
    rows: int
    Bp: int
    # Hybrid models: the rows' recurrent state between chunk rounds (a
    # device tree; None until the first round starts it from snapshots or
    # zeros), and the snapshot keys this prefill reserved (forgotten again
    # if it fails before its programs ran).
    ssm: Any = None
    snap_keys: Optional[List[int]] = None
    # The rows of the same admission that still hold blocks and
    # slots-to-be, in the order they were admitted: this group's and those
    # of ``later``, the groups not yet begun (prefill_partition), each a
    # batch in that order too. They run after this one, ahead of any new
    # admission, and wait with it where a budget pause parks it.
    held: List[Tuple[Any, Any]] = field(default_factory=list)
    later: List[List[Tuple[Any, Any]]] = field(default_factory=list)

    def behind(self) -> List[Tuple[Any, Any]]:
        """The rows of the groups not yet begun, in the order they were
        admitted."""
        mine = {id(seq) for seq, _ in self.batch}
        return [row for row in self.held if id(row[0]) not in mine]


@dataclass
class _Family:
    """One prefix-hit prefill shape: rounds that met it, rows buckets run."""

    met: int = 0
    ran: Set[int] = field(default_factory=set)


class Admitter:
    """Engine-attached admission pipeline (state lives on the engine)."""

    def __init__(self, engine: Any) -> None:
        self.e = engine
        # Prefix-hit prefill programs by (chunk bucket, table width,
        # top-logprobs variant): how often a round met the shape and the
        # rows buckets that ran; the sibling rows buckets still to compile
        # (run_pending_family), and how many of them have run.
        self._families: Dict[Tuple[int, int, bool], "_Family"] = {}
        self.family_pending: Deque[Tuple[int, int, int, bool]] = collections.deque()
        self.family_programs = 0
        # What a prefill program [rows bucket, chunk bucket] of this
        # configuration costs: decides how an admission's rows are grouped.
        self.price = PrefillPrice(*step_weights(engine.runner.params, engine.config))

    async def _admit_batch(self) -> int:
        """Admit + prefill up to ``prefill_batch`` waiting sequences: one
        batched device dispatch per chunk round of each group they run as
        (``_finish_admission``). Returns how many were installed into the
        decode batch.

        Failure containment matches the round-2 breaker semantics: a
        poisoned batch is retried per-sequence (one retry then an error
        stream); the cross-request failure streak still detects systemic
        breakage and fails the engine terminally.
        """
        e = self.e

        free_slots = [i for i, s in enumerate(e._slots) if s is None]
        # Pending handoff adoptions (drain plane) hold a slot reservation:
        # adopt_handoff already promised the peer capacity, and the
        # scheduler loop installs adoptions before admission each tick —
        # local admission taking the last free slot would strand an
        # adopted LIVE stream (client mid-decode) behind the whole queue.
        for _ in e._adoptions:
            if free_slots:
                free_slots.pop()
        if not free_slots or not e._waiting:
            return 0
        batch: List[Tuple[Any, Any]] = []
        limit = min(len(free_slots), e.args.prefill_batch)
        # The dual of the reservation above: while this batch is being
        # prepared/prefilled (both await), adopt_handoff must count its
        # slots-to-be as taken (engine._admitting) or it accepts a
        # handoff into a slot this batch is about to install into.
        try:
            while e._waiting and len(batch) < limit:
                seq = e._waiting[0]
                # Expired/cancelled work sheds AT DEQUEUE, before any pool
                # or prefill spend — deadline expiries surface as a typed
                # error (overload armor: an already-dead request must
                # never reach the device).
                if seq.context.stopped:
                    e._waiting.popleft()
                    e._shed_expired(seq)
                    continue
                # Backpressure: past the high watermark, admitting trades
                # one queued request for a preemption storm against the
                # running ones — hold the queue and let decode drain
                # instead. Only with live occupants: an idle engine always
                # admits (the watermark measures contention, not fit).
                if (
                    e.pool.usage >= e.args.admit_kv_high_watermark
                    and any(s is not None for s in e._slots)
                ):
                    break
                has_mm = bool((seq.request.extra or {}).get("mm_embeds"))
                if has_mm and batch:
                    break  # multimodal rows carry their own embed arrays: solo batch
                e._waiting.popleft()
                e._admitting = len(batch) + 1
                try:
                    prep = await e._prepare_admission(seq)
                except asyncio.CancelledError:
                    e._waiting.appendleft(seq)
                    raise
                except Exception as exc:
                    e._contain_admission_failure([seq], exc)
                    return len(batch) if not batch else await e._finish_admission(batch)
                if prep is None:  # pool dry; seq was requeued to the front
                    break
                batch.append((seq, prep))
                e._admitting = len(batch)
                if has_mm:
                    break
            if not batch:
                return 0
            return await e._finish_admission(batch)
        finally:
            e._admitting = 0

    async def _finish_admission(self, batch: "List[Tuple[Any, Any]]") -> int:
        """Prefill and install the rows admitted together: as the groups
        that price least (``prefill_partition`` over the tokens each still
        has to prefill), one after another inside this admission, each its
        own ``[rows bucket, chunk bucket]`` rounds and install. A batch
        whose joint program prices least is one group, and so is a batch
        any of whose rows resumes cached context: its rounds run over a
        table as wide as that context, a program no start-up ladder holds
        and the family mechanism compiles as its ``(chunk, table)`` recurs,
        so a worker's prefix-hit traffic dispatches the shapes it did as
        one batch and meets none it has not compiled. Fresh rows' first
        round is the ladder's program at whatever ``[rows, chunk]``."""
        self._stamp_prefill_start(batch)
        groups = [batch]
        if not any(prep.matched_tokens for _, prep in batch):
            groups = [
                [batch[r] for r in rows]
                for rows in prefill_partition(
                    [len(seq.all_tokens) for seq, _ in batch],
                    self.e.args.prefill_chunk, self.price,
                )
            ]
        return await self._run_prefill(self._begin_group(groups, batch))

    def _begin_group(
        self, groups: "List[List[Tuple[Any, Any]]]", held: "List[Tuple[Any, Any]]"
    ) -> "PendingPrefill":
        """The first of an admission's remaining ``groups`` begun, the rest
        behind it; ``held`` the rows of them all, as admitted."""
        pending = self._begin_prefill(groups[0])
        pending.held, pending.later = held, groups[1:]
        return pending

    async def _run_prefill(self, pending: "PendingPrefill") -> int:
        """Run (or resume) an admission group by group: each group's chunk
        rounds to completion or to budget exhaustion, then its install,
        then the next group. Returns rows installed; 0 covers both
        containment (a failing group is ejected/requeued, its own rows
        only) and a budget park (the pending state is stashed on the
        engine, blocks still pinned, the groups not yet begun with it)."""
        e = self.e
        installed = 0
        while True:
            try:
                done = await self._prefill_rounds(pending)
            except asyncio.CancelledError:
                self._forget_snapshots(pending)
                self._back_to_queue(pending.held)
                raise
            except Exception as exc:
                self._forget_snapshots(pending)
                for seq, _ in pending.batch:
                    e._release_blocks(seq)
                e._contain_admission_failure([s for s, _ in pending.batch], exc)
                if e._failure is not None:
                    # Systemic: the groups behind go back to the queue, where
                    # the engine's shutdown ends their streams.
                    self._back_to_queue(pending.behind())
                    return installed
            else:
                if not done:
                    # Tick budget exhausted at a chunk boundary: park. Blocks
                    # stay pinned and per-row positions are kept — the engine
                    # resumes this exact state with the next tick's grant,
                    # ahead of any new admission (FIFO order is preserved).
                    e._pending_prefill = pending
                    e._record_budget_event(
                        "prefill_pause",
                        rows=pending.rows,
                        done=sum(pending.pos),
                        total=sum(len(p) for p in pending.prompts),
                    )
                    return installed
                await self._install_group(pending)
                installed += len(pending.batch)
            # Its rows hold slots-to-be no longer (adopt_handoff counts them).
            e._admitting = max(0, e._admitting - len(pending.batch))
            if not pending.later:
                return installed
            pending = self._begin_group(pending.later, pending.behind())

    def _back_to_queue(self, rows: "List[Tuple[Any, Any]]") -> None:
        """Rows in arrival order back to the front of the waiting queue in
        that order, their blocks released."""
        for seq, _ in reversed(rows):
            self.e._release_blocks(seq)
            self.e._requeue(seq)

    async def _install_group(self, pending: "PendingPrefill") -> None:
        e = self.e
        e._admission_failure_streak = 0
        free_iter = (i for i, s in enumerate(e._slots) if s is None)
        with e.step_metrics.phase("tick.install", rows=len(pending.batch)):
            slots = []
            for (seq, prep), f in zip(pending.batch, pending.first):
                tok, logp, top = f
                slots.append(next(free_iter))
                e._install(seq, prep, slots[-1], tok, logp, top)
            if pending.ssm is not None:
                # The rows' recurrent state joins the decode batch with
                # them: one scatter into the slots just taken.
                await e._device(
                    e.runner.ssm_install, slots, pending.ssm,
                    list(range(len(slots))),
                )

    def _forget_snapshots(self, pending: "PendingPrefill") -> None:
        if pending.snap_keys and self.e.snapshots is not None:
            self.e.snapshots.discard(pending.snap_keys)

    def _contain_admission_failure(self, seqs: "List[Any]", exc: Exception) -> None:
        """Per-request retry-once-then-eject; streak detects systemic failure."""
        e = self.e

        for seq in seqs:
            seq.admission_failures += 1
            if seq.admission_failures >= 2:
                logger.exception(
                    "ejecting request %s after %d admission failures",
                    seq.request.request_id, seq.admission_failures,
                )
                seq.queue.put_nowait(
                    BackendOutput(
                        error=f"admission failed: {type(exc).__name__}: {exc}",
                        finish_reason=FinishReason.ERROR,
                    )
                )
            else:
                logger.exception(
                    "admission of %s failed; will retry once",
                    seq.request.request_id,
                )
                e._waiting.appendleft(seq)
        e._admission_failure_streak += 1
        if e._admission_failure_streak >= 6:
            e._fail_terminally(exc)

    async def _prepare_admission(self, seq: Any) -> "Optional[Any]":
        """Pool work for one sequence: salting, prefix match, allocation.
        Returns None (after requeueing the sequence) when the pool is dry."""
        e = self.e

        args = e.args
        prompt = seq.all_tokens  # includes regenerated tokens after preemption
        n_blocks_prompt = math.ceil(len(prompt) / args.block_size)

        # Multimodal splice inputs (multimodal/handlers.py): packed patch
        # embeddings + a prompt-position → embedding-row map.
        mm_embeds: Optional[np.ndarray] = None
        mm_slot_of: Optional[np.ndarray] = None
        mm = seq.request.extra or {}
        if "mm_embeds" in mm:
            from dynamo_tpu.disagg.handlers import unpack_array

            mm_embeds = unpack_array(mm["mm_embeds"]).astype(np.float32)
            per_image = int(mm.get("mm_tokens_per_image", 0))
            mm_slot_of = np.full(len(prompt), -1, dtype=np.int32)
            row = 0
            for start in mm.get("mm_positions", []):
                for j in range(per_image):
                    if start + j < len(prompt):
                        mm_slot_of[start + j] = row
                    row += 1

        # Salted hashing: adapter ⊕ image content — neither LoRA K/V nor
        # image-conditioned K/V may cross-pollinate the base prefix cache.
        seq.hash_salt = adapter_salt(seq.request.lora_name)
        if mm_embeds is not None:
            import xxhash

            seq.hash_salt ^= xxhash.xxh3_64(mm_embeds.tobytes()).intdigest()

        hashes: List[int] = []
        matched = 0
        ids: List[int] = []
        snap_src = -1
        if args.enable_prefix_caching:
            hashes = compute_block_hashes(
                prompt, args.block_size, salt=seq.hash_salt
            )
            pf = getattr(seq, "kv_prefetch", None)
            stall = 0.0
            if e.kvbm is not None and hashes and pf is not None:
                # Speculative lease (docs/design_docs/kv_prefetch.md): the
                # onboard walk ran while this request sat in the queue, so
                # joining here stalls only for the un-overlapped remainder
                # — walk time minus this stall is the TTFT the speculation
                # bought, recorded by claim() below.
                t_wait = time.monotonic()
                await pf.wait()
                stall = time.monotonic() - t_wait
                if pf.settled:
                    # The walk died, was revoked, or found nothing — no
                    # lease is held: take the serial path below exactly
                    # like hintless traffic.
                    seq.kv_prefetch = None
                    pf = None
                elif pf.source:
                    seq.kv_hit_tier = pf.source
            if e.kvbm is not None and hashes and pf is None:
                # Serial fallback (unrouted/hintless traffic): onboard from
                # the lower tiers (G2/G3) anything that extends the device
                # prefix match (ref: KVBM onboard-before-prefill, §3.4).
                n_dev = e.pool.match_prefix(hashes)
                if n_dev < len(hashes):
                    try:
                        if await e.kvbm.onboard(hashes):
                            # Hit attribution for the KV-reuse plane: the
                            # match was extended from a lower tier.
                            seq.kv_hit_tier = (
                                getattr(e.kvbm, "last_onboard_source", None)
                                or "host"
                            )
                    except Exception:
                        logger.exception("KV onboard failed; prefilling locally")
            matched, ids = e.pool.pin_prefix(hashes)
            if e.config.has_recurrent_state:
                # A prefix is a hit only as far as K/V pages AND a snapshot
                # of the recurrent state both reach, and short of the last
                # token (whose logits the first sample needs).
                keep, snap_src = (0, -1) if e.snapshots is None else (
                    e.snapshots.lookup(
                        hashes, min(matched, (len(prompt) - 1) // args.block_size)
                    )
                )
                e.pool.release(ids[keep:], hashes[keep:matched])
                matched, ids = keep, ids[:keep]
            if e.window is not None and matched:
                # A prefix is a hit only as far as the full group holds the
                # blocks AND the window group still holds the window in
                # front of the resume position; otherwise the match is cut
                # back to where both hold (or to nothing).
                keep = e.window.cut_match(hashes, matched, len(prompt))
                e.pool.release(ids[keep:], hashes[keep:matched])
                matched, ids = keep, ids[:keep]
            if pf is not None:
                # Claim AFTER our own pin: the lease's pins release with
                # the blocks already re-held, so their refcounts never dip
                # to zero (and the pool can never evict them) in between.
                pf.claim(stall_s=stall)
                seq.kv_prefetch = None
        matched_tokens = min(matched * args.block_size, len(prompt) - 1)

        # Watermark headroom so running decodes can still grow.
        headroom = (
            int(args.num_kv_blocks * args.watermark)
            if any(s is not None for s in e._slots)
            else 0
        )
        need = n_blocks_prompt - len(ids) + 1 + headroom
        # One decision over both page groups: the window group must have a
        # row's most pages to give (it is sized so that it has).
        win_short = e.window is not None and e.window.pool.free_blocks < min(
            e.window.row_bound(e.window.window, args.block_size, args.prefill_chunk),
            n_blocks_prompt + 1,
        )
        if need > e.pool.free_blocks or win_short:
            e.pool.release(ids, hashes[:matched])
            e._requeue(seq)
            return None
        while len(ids) < n_blocks_prompt:
            b = e.pool.alloc()
            if b is None:  # raced below watermark; put everything back
                e.pool.release(ids, hashes[:matched])
                e._requeue(seq)
                return None
            ids.append(b)
        seq.block_ids = ids
        seq.block_hashes = hashes[:matched]
        if e.window is not None:
            seq.win_ids = e.window.pin_tail(hashes, matched, len(prompt))
            seq.win_pinned = matched
            seq.win_keep = e.window.prompt_tail(len(prompt))
        return _prep_cls()(
            ids=ids,
            hashes=hashes,
            matched=matched,
            matched_tokens=matched_tokens,
            sp=e._sampling_of(seq.request),
            adapter_id=e._lora_index.get(seq.request.lora_name or "", 0),
            mm_embeds=mm_embeds,
            mm_slot_of=mm_slot_of,
            procs=e._procs_of(seq.request),
            snap_src=snap_src,
        )

    def _row_buckets(self) -> List[int]:
        return sorted(
            {_next_pow2(r) for r in range(1, self.e.args.prefill_batch + 1)}
        )

    def prefill_ladder(self) -> List[Tuple[int, int, int]]:
        """Every (rows bucket, chunk bucket, table width) a batch of FRESH
        prompts that each fit one chunk can dispatch at — the shapes
        ``_begin_prefill`` / ``_prefill_rounds`` derive, enumerated."""
        args = self.e.args
        chunks = []
        c = prefill_chunk_bucket(1, args.prefill_chunk)
        while c < args.prefill_chunk:
            chunks.append(c)
            c *= 2
        chunks.append(args.prefill_chunk)
        return [
            (
                Bp, c,
                prefill_table_bucket(math.ceil(c / args.block_size), c, args),
            )
            for Bp in self._row_buckets()
            for c in chunks
        ]

    async def _run_empty_step(
        self, Bp: int, c: int, nb: int, *, first_chunk: bool,
        want_top: bool = False, step: Any = None,
    ):
        """One prefill program run THROUGH the callable the chunk rounds
        call (``engine._run_step``, or ``step`` around it, with the arrays
        a round builds: an ahead-of-time ``lower().compile()`` beside it
        would not fill that callable's cache, and its first real call would
        still trace and load), every row of length 0: nothing is written to
        the pool and nothing is emitted. Returns the step's outputs."""
        e = self.e
        zeros = np.zeros(Bp, dtype=np.int32)
        hybrid = ()
        if e.config.is_hybrid:
            # With the rows' recurrent state: started from zeros, no
            # snapshot kept.
            ssm = await e._device(e.runner.ssm_begin, zeros - 1)
            hybrid = (ssm, self._snap_dst(Bp, c))
        return await e._device(
            step or e._run_step,
            np.zeros((Bp, c), dtype=np.int32), zeros, zeros,
            np.zeros(e.tables_shape(Bp, nb), dtype=np.int32),
            np.ones(Bp, dtype=np.float32), zeros,
            np.ones(Bp, dtype=np.float32), zeros,
            None, None, None, want_top, first_chunk, zeros, *hybrid,
        )

    def _run_family_step(self, *step_args):
        """On the device thread: what this call compiles is the family's
        own warm-up, logged as that and not as a serving-path compile."""
        with global_compile_watcher().family_warm_up():
            return self.e._run_step(*step_args)

    async def compile_prefill_ladder(self) -> int:
        """Run every ladder program once (``_run_empty_step``). Returns
        how many programs ran.

        Not in the ladder: rounds after a prompt's first (prefix-hit
        tails, prompts longer than the chunk), whose rows-bucket family is
        compiled once such a shape recurs (``run_pending_family``); and,
        compiled at first use as before, top-logprobs and logits-processor
        variants, multimodal rows, and the decode and scatter programs."""
        e = self.e
        ladder = self.prefill_ladder()
        for Bp, c, nb in ladder:
            out = await self._run_empty_step(Bp, c, nb, first_chunk=True)
            if e.config.is_hybrid and c == ladder[0][1]:
                # The state programs beside the step (start, install) run
                # once per rows bucket too.
                rows = list(range(min(Bp, e.args.max_num_seqs)))
                await e._device(e.runner.ssm_install, rows, out[4], rows)
        return len(ladder)

    def _note_prefix_hit_round(self, Bp: int, c: int, nb: int, want_top: bool) -> None:
        """A chunk round of a batch that resumed from a prefix hit,
        dispatched at this shape (a long fresh prompt's own later chunks
        are no hit and are not counted: the 256 rounds of one 65 k prompt
        say nothing of what the next asks will run). Such
        programs are in no start-up ladder (most workers never meet one,
        and a ladder program costs seconds). One that RECURS says this
        worker's traffic hits prefixes: at its second meeting the sibling
        rows buckets, which the next burst of asks would each compile in
        front of live streams, are put down to be compiled one a tick."""
        family = self._families.setdefault((c, nb, want_top), _Family())
        family.met += 1
        family.ran.add(Bp)
        if family.met == 2:
            self.family_pending.extend(
                (rows, c, nb, want_top)
                for rows in self._row_buckets() if rows not in family.ran
            )

    async def run_pending_family(self) -> bool:
        """Compile AT MOST ONE pending sibling of a recurring prefix-hit
        prefill program: the scheduler calls this once a tick ahead of
        admission, so no await of its loop is longer than one compile and
        stats and load reports go out in between. True where one ran."""
        e = self.e
        while self.family_pending:
            Bp, c, nb, want_top = self.family_pending.popleft()
            family = self._families[(c, nb, want_top)]
            if Bp in family.ran:  # a batch of that many rows came first
                continue
            family.ran.add(Bp)
            try:
                with e.step_metrics.phase("tick.prefill_wait", rows=0, chunk=c, nb=nb):
                    await self._run_empty_step(
                        Bp, c, nb, first_chunk=False, want_top=want_top,
                        step=self._run_family_step,
                    )
            except Exception:
                logger.exception(
                    "family warm-up of the prefix-hit prefill program rows %d, "
                    "chunk %d, table %d failed; it compiles at first use",
                    Bp, c, nb,
                )
                continue
            self.family_programs += 1
            return True
        return False

    def _snap_dst(self, Bp: int, c_bucket: int) -> np.ndarray:
        """Snapshot destinations of one chunk round, nothing kept yet: every
        entry out of the store's range."""
        e = self.e
        stride = e._ssm_stride  # 0: no layer keeps recurrent state
        return np.full(
            (Bp, c_bucket // stride if stride else 0), e.runner.snap_entries,
            dtype=np.int32,
        )

    def _stamp_prefill_start(self, batch: "List[Tuple[Any, Any]]") -> None:
        """Lifecycle/ROI stamps of the rows admitted together, once an
        admission: the prefill phase of every row opens here, whichever
        group of the admission it runs in."""
        for seq, prep in batch:
            seq.t_prefill_start = time.monotonic()
            lifecycle.record(
                seq.request.request_id, "prefill_start",
                context=seq.context,
                prompt_tokens=len(seq.all_tokens),
                cached_tokens=prep.matched_tokens,
            )
            # Cache-ROI attribution: one feed per admitted request, on the
            # engine side only (the router feeds popularity, not ROI).
            seq.kv_roi = kv_reuse_plane().note_request(
                anchor=prep.hashes[prep.matched - 1] if prep.matched else None,
                cached_tokens=prep.matched_tokens,
                recomputed_tokens=len(seq.all_tokens) - prep.matched_tokens,
                tier=getattr(seq, "kv_hit_tier", "device"),
                trace_id=lifecycle.trace_id_of(seq.context),
            )

    def _begin_prefill(self, batch: "List[Tuple[Any, Any]]") -> PendingPrefill:
        """Per-group prefill preamble: every loop-invariant device array,
        captured as a PendingPrefill so the chunk rounds can pause and
        resume across ticks."""
        e = self.e
        args = e.args
        rows = len(batch)
        prompts = [seq.all_tokens for seq, _ in batch]
        pos = [prep.matched_tokens for _, prep in batch]
        first: List[Optional[Tuple[int, float, Optional[list]]]] = [None] * rows
        # Any row asking for top-N logprobs routes the batch through the
        # top-variant prefill program so the FIRST generated token carries
        # alternatives too (not just the fused-decode tokens).
        want_top = any(
            (seq.request.sampling.logprobs or 0) > 0 for seq, _ in batch
        )

        nb_needed = max(len(prep.ids) for _, prep in batch)
        first_round = max(
            min(len(p) - at, args.prefill_chunk) for p, at in zip(prompts, pos)
        )
        nb_bucket = prefill_table_bucket(
            nb_needed, prefill_chunk_bucket(first_round, args.prefill_chunk),
            args,
        )
        Bp = _next_pow2(rows)
        tables = np.zeros(e.tables_shape(Bp, nb_bucket), dtype=np.int32)
        full_tables = tables if e.window is None else tables[:, 0]
        temp = np.ones(Bp, dtype=np.float32)
        topk = np.zeros(Bp, dtype=np.int32)
        topp = np.ones(Bp, dtype=np.float32)
        adapter = np.zeros(Bp, dtype=np.int32)
        salts = np.zeros(Bp, dtype=np.int32)
        for r, (seq_r, prep) in enumerate(batch):
            full_tables[r, : len(prep.ids)] = prep.ids
            temp[r], topk[r], topp[r] = prep.sp
            adapter[r] = prep.adapter_id
            salts[r] = seq_r.salt
        procs = None
        if any(prep.procs is not None for _, prep in batch):
            from dynamo_tpu.ops.logits_process import MAX_BIAS_SLOTS, prompt_hot

            V = e.config.vocab_size
            minp = np.zeros(Bp, dtype=np.float32)
            rep = np.ones(Bp, dtype=np.float32)
            pres = np.zeros(Bp, dtype=np.float32)
            freq = np.zeros(Bp, dtype=np.float32)
            bias_ids = np.full((Bp, MAX_BIAS_SLOTS), -1, dtype=np.int32)
            bias_vals = np.zeros((Bp, MAX_BIAS_SLOTS), dtype=np.float32)
            pmask = np.zeros((Bp, V), dtype=np.bool_)
            for r, (seq_r, prep) in enumerate(batch):
                if prep.procs is None:
                    continue
                p = prep.procs
                minp[r], rep[r], pres[r], freq[r] = p.minp, p.rep, p.pres, p.freq
                bias_ids[r] = p.bias_ids
                bias_vals[r] = p.bias_vals
                # all_tokens (not just the prompt): for preempted re-prefills
                # the repetition penalty must keep covering already-generated
                # tokens. (pres/freq at this single re-sample are approximated
                # as zero; exact history is restored at _install.)
                pmask[r] = prompt_hot(seq_r.all_tokens, V)
            procs = (minp, rep, pres, freq, bias_ids, bias_vals, pmask)
        # Multimodal rows run solo (rows == 1), so row 0's arrays suffice.
        mm_embeds = batch[0][1].mm_embeds if rows == 1 else None
        mm_slot_of = batch[0][1].mm_slot_of if rows == 1 else None
        return PendingPrefill(
            batch=batch, prompts=prompts, pos=pos, first=first,
            want_top=want_top, tables=tables, temp=temp, topk=topk,
            topp=topp, adapter=adapter, salts=salts, procs=procs,
            mm_embeds=mm_embeds, mm_slot_of=mm_slot_of, rows=rows, Bp=Bp,
        )

    async def _prefill_rounds(self, pending: PendingPrefill) -> bool:
        """Chunk rounds for a (possibly resumed) joint prefill: one
        [Bp, C] dispatch per round with per-row start/len (forward_paged
        supports ragged rows natively). A round is the atomic budget
        unit: the tick-grant check happens BEFORE each round — so one
        round may overdraw, settled as debt by the budgeter — and a pause
        always lands on a chunk boundary. Returns True when every row has
        sampled its first token, False on a budget pause."""
        e = self.e
        args = e.args
        rows = pending.rows
        prompts = pending.prompts
        pos = pending.pos
        first = pending.first
        want_top = pending.want_top
        tables = pending.tables
        temp, topk, topp = pending.temp, pending.topk, pending.topp
        adapter, salts, procs = pending.adapter, pending.salts, pending.procs
        mm_embeds, mm_slot_of = pending.mm_embeds, pending.mm_slot_of
        Bp = pending.Bp

        phase = e.step_metrics.phase
        hybrid = e.config.is_hybrid
        with phase("tick.prefill_build", rows=rows):
            if hybrid and pending.ssm is None:
                src = np.full(Bp, -1, dtype=np.int32)
                for r, (_, prep) in enumerate(pending.batch):
                    src[r] = prep.snap_src
                pending.ssm = await e._device(e.runner.ssm_begin, src)
                pending.snap_keys = []
            while any(pos[r] < len(prompts[r]) for r in range(rows)):
                if e._tick_budget_left is not None and e._tick_budget_left <= 0:
                    return False
                chunks = [
                    prompts[r][pos[r] : pos[r] + args.prefill_chunk] for r in range(rows)
                ]
                c_bucket = prefill_chunk_bucket(
                    max(len(c) for c in chunks), args.prefill_chunk
                )
                tok_arr = np.zeros((Bp, c_bucket), dtype=np.int32)
                start = np.zeros(Bp, dtype=np.int32)
                lens = np.zeros(Bp, dtype=np.int32)
                for r in range(rows):
                    ch = chunks[r][:c_bucket]
                    tok_arr[r, : len(ch)] = ch
                    start[r] = pos[r]
                    lens[r] = len(ch)
                    if e.window is not None and ch:
                        # The window group turns over as the prefill runs:
                        # pages behind this round's first query go back,
                        # this chunk's are taken.
                        seq_r = pending.batch[r][0]
                        if e._window_advance(seq_r, pos[r], pos[r] + len(ch) - 1) is None:
                            raise RuntimeError(
                                "window page group exhausted in a prefill round"
                            )
                        tables[r, 1, :] = 0
                        held = np.maximum(seq_r.win_ids[: tables.shape[2]], 0)
                        tables[r, 1, : len(held)] = held
                mm_chunk = None
                if mm_slot_of is not None:
                    mm_chunk = np.full((Bp, c_bucket), -1, dtype=np.int32)
                    n0 = int(lens[0])
                    mm_chunk[0, :n0] = mm_slot_of[pos[0] : pos[0] + n0]
                # Fresh prefills (no prefix-cache hit, first chunk round) take
                # the dense in-chunk attention program — zero paged reads.
                first_chunk = bool(np.all(start[:rows] == 0))
                # Such a round reads no page and writes its chunk's own, the
                # table's first: it runs at the start-up ladder's width, so a
                # long fresh prompt's first round is the ladder's program
                # whatever table its later rounds need.
                step_tables = tables
                if first_chunk:
                    step_tables = np.ascontiguousarray(tables[..., : prefill_table_bucket(
                        math.ceil(c_bucket / args.block_size), c_bucket, args)])
                if (
                    not first_chunk and procs is None and mm_embeds is None
                    and any(prep.matched_tokens for _, prep in pending.batch)
                ):
                    self._note_prefix_hit_round(
                        Bp, c_bucket, tables.shape[-1], want_top
                    )
                hybrid_args = ()
                if hybrid:
                    snap_dst = self._snap_dst(Bp, c_bucket)
                    if e.snapshots is not None:
                        self._reserve_snapshots(pending, snap_dst, lens)
                    hybrid_args = (pending.ssm, snap_dst)
                t0 = time.monotonic()
                with phase(
                    "tick.prefill_wait", rows=rows, chunk=c_bucket,
                    nb=step_tables.shape[-1], tokens=int(lens.sum()),
                ):
                    toks, logps, topv, topi, *state = await e._device(
                        e._run_step,
                        tok_arr, start, lens, step_tables,
                        temp, topk, topp, adapter,
                        mm_embeds, mm_chunk, procs, want_top, first_chunk,
                        salts, *hybrid_args,
                    )
                    if hybrid:
                        pending.ssm = state[0]
                dt = time.monotonic() - t0
                e.step_metrics.observe_prefill(
                    # Occupancy counts rows still prefilling this round — short
                    # prompts finish earlier chunk rounds and ride along with
                    # lens == 0.
                    dt,
                    int(np.count_nonzero(lens[:rows])),
                    int(lens.sum()),
                    Bp, c_bucket,
                )
                # Per-token prefill cost EWMA — the basis for the plane's
                # prefill-seconds-saved estimate.
                kv_reuse_plane().note_prefill_cost(dt, int(lens.sum()))
                if e.moe_prefill_tokens is not None:
                    form = e.runner.prefill_expert_form(Bp, c_bucket)
                    e.moe_prefill_tokens[form] += int(lens.sum())
                if e._tick_budget_left is not None:
                    e._tick_budget_left -= int(lens.sum())
                for r in range(rows):
                    n = int(lens[r])
                    if n == 0:
                        continue
                    e.prefill_tokens += n
                    pos[r] += n
                    if pos[r] >= len(prompts[r]):
                        top = None
                        if topv is not None:
                            top = [
                                (int(topi[r, j]), float(topv[r, j]))
                                for j in range(topv.shape[1])
                            ]
                        first[r] = (int(toks[r]), float(logps[r]), top)
        assert all(f is not None for f in first)
        return True

    def _reserve_snapshots(
        self, pending: PendingPrefill, snap_dst: np.ndarray, lens: np.ndarray
    ) -> None:
        """Name, for this round, the snapshot-store entry of every snapshot
        boundary a row's chunk crosses and that is not kept yet (rows start
        their rounds on a scan-block boundary: a resume point is one, and a
        round advances by whole chunks). The program can write the state at
        every scan block's end; a boundary is every ``_snap_every`` tokens."""
        e = self.e
        stride, every, bs = e._ssm_stride, e._snap_every, e.args.block_size
        for r, (_, prep) in enumerate(pending.batch):
            for j in range(int(lens[r]) // stride):
                at = pending.pos[r] + (j + 1) * stride
                if at % every:
                    continue
                b = at // bs
                if b > len(prep.hashes):
                    break
                idx = e.snapshots.reserve(prep.hashes[b - 1])
                if idx >= 0:
                    snap_dst[r, j] = idx
                    pending.snap_keys.append(prep.hashes[b - 1])

    def _install(
        self, seq: Any, prep: "Any", slot: int, first_token: int,
        first_logprob: float, first_top: Optional[list] = None,
    ) -> None:
        """Commit fresh prompt blocks and join the decode batch."""
        e = self.e
        args = e.args
        prompt = seq.all_tokens
        if args.enable_prefix_caching:
            full = len(prompt) // args.block_size
            for i in range(prep.matched, full):
                parent = prep.hashes[i - 1] if i else None
                e.pool.commit(prep.ids[i], prep.hashes[i], parent)
                if e.window is not None:
                    e.window.commit(seq.win_ids, i, prep.hashes[i], parent)
                seq.block_hashes.append(prep.hashes[i])
                if e.kvbm is not None:
                    e.kvbm.notify_commit(prep.hashes[i], i + 1, parent=parent)
            if e.snapshots is not None:
                # The router hears of the blocks a snapshot now covers.
                k = e.snapshots.stride_blocks
                e.pool.announce(prep.hashes[: (full // k) * k])
        # Per-slot device state: ONE shared implementation with the
        # drain plane's _install_adopted (engine._set_slot_state) — any
        # new per-slot sampling field must land there, not here.
        e._set_slot_state(
            seq, slot, pos=len(prompt), block_ids=prep.ids, sp=prep.sp,
            adapter_id=prep.adapter_id, procs=prep.procs,
            tok_mirror=int(first_token),
        )
        if prep.procs is not None:
            # The freshly sampled first token is not in seq.generated yet
            # (emit below appends it): count it on the device now.
            e.runner.proc_count(slot, first_token)
        e._emit_token(seq, first_token, first_logprob, first_top)

    def _sampling_of(self, req: PreprocessedRequest) -> Tuple[float, int, float]:
        e = self.e
        s = req.sampling
        temp = s.temperature if s.temperature is not None else 1.0
        topk = s.top_k if s.top_k is not None and s.top_k > 0 else 0
        topp = s.top_p if s.top_p is not None else 1.0
        return float(temp), int(topk), float(topp)

    def _procs_of(self, req: PreprocessedRequest) -> Optional[Any]:
        """Logits-processor params, or None when the request uses none —
        None keeps the batch on the processor-free compiled programs."""
        e = self.e

        s = req.sampling
        rep = float(s.repetition_penalty) if s.repetition_penalty else 1.0
        pres = float(s.presence_penalty) if s.presence_penalty else 0.0
        freq = float(s.frequency_penalty) if s.frequency_penalty else 0.0
        minp = float(s.min_p) if s.min_p else 0.0
        bias = s.logit_bias
        if rep == 1.0 and pres == 0.0 and freq == 0.0 and minp <= 0.0 and not bias:
            return None
        from dynamo_tpu.ops.logits_process import pack_bias

        ids, vals = pack_bias(bias, e.config.vocab_size)
        return _procprep_cls()(
            minp=minp, rep=rep, pres=pres, freq=freq,
            bias_ids=ids, bias_vals=vals,
        )



def _prep_cls():
    from dynamo_tpu.engines.tpu.engine import _Prep

    return _Prep


def _procprep_cls():
    from dynamo_tpu.engines.tpu.engine import _ProcPrep

    return _ProcPrep
