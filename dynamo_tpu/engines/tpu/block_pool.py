"""Physical KV block pool: hash ↔ device-block-id, prefix reuse, LRU.

Reference parity: the G1 (device) pool of KVBM
(lib/llm/src/block_manager/pool/managed.rs — active/inactive sets, reuse &
eviction) fused with the mocker's KvManager semantics (kv_manager.rs:50).
Unlike the mock engine, blocks here name *physical slots* in the HBM cache
arrays, so the pool is the single source of truth for which device block
holds which content hash.

States: free (uninitialized/evicted) → active-private (being filled by one
sequence) → committed (full block, content-hashed, shareable) → inactive
(committed, refcount 0, LRU-evictable) → free.

Emits the same KvEvent stream as the mock engine for router indexing.

Two kinds of state in one manager (hybrid models): beside the paged K/V
blocks, ``StateSnapshots`` keeps the index of per-sequence RECURRENT state
copies (the device arrays live in the runner). A cached prefix is useless to
a state-space layer without the state at its end, so a prefix is a hit only
as far as K/V blocks AND a snapshot both reach, and a pool built with
``announce_commits=False`` tells the router of a committed block only once a
snapshot covers it (``announce``).

Two page groups for one sequence (a model that mixes sliding-window and full
attention layers, ``ModelConfig.cache_groups``): the full layers' pages are
this ``BlockPool``, the sliding layers' a second one inside ``WindowPages``
with an id space and a pool shape of its own. A sequence's window-group
pages behind its window are given back while it runs and are at once
another row's to take; what stays cached of a finished chain is its trailing
window. A prefix is then a hit only as far as the full group holds the
blocks AND the window group still holds the window in front of the resume
position (``WindowPages.cut_match``). KV events describe the full group.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

from dynamo_tpu.engines.mock.kv_manager import EventCallback, KvEvent


@dataclass
class _Committed:
    block_id: int
    parent_hash: Optional[int]
    ref_count: int = 0
    announced: bool = True  # a "stored" event went out for it


class BlockPool:
    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        *,
        on_event: Optional[EventCallback] = None,
        announce_commits: bool = True,
    ) -> None:
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._on_event = on_event
        self._announce_commits = announce_commits
        self._free: Deque[int] = deque(range(num_blocks))
        self._by_hash: Dict[int, _Committed] = {}
        self._lru: "OrderedDict[int, _Committed]" = OrderedDict()  # hash → entry

    # -- stats -------------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free) + len(self._lru)

    @property
    def cached_blocks(self) -> int:
        return len(self._lru)

    @property
    def active_blocks(self) -> int:
        return self.num_blocks - self.free_blocks

    @property
    def usage(self) -> float:
        return self.active_blocks / self.num_blocks if self.num_blocks else 0.0

    def bytes_breakdown(self, block_bytes: int) -> Dict[str, int]:
        """Structural byte accounting for the HBM ledger / GET
        /debug/memory: pool-state block counts × per-block KV bytes. The
        pool itself is the single source of truth for which physical
        blocks hold live vs reusable-cached vs free content, so this is
        the only place the split can be computed without tearing."""
        block_bytes = int(block_bytes)
        return {
            "active_bytes": self.active_blocks * block_bytes,
            "cached_bytes": self.cached_blocks * block_bytes,
            "free_bytes": len(self._free) * block_bytes,
            "total_bytes": self.num_blocks * block_bytes,
        }

    # -- prefix reuse ------------------------------------------------------

    def contains(self, block_hash: int) -> bool:
        """Whether a committed block with this content hash is resident."""
        return block_hash in self._by_hash

    def snapshot_committed(self):
        """Pin EVERY committed block and return
        [(hash, parent_hash, block_id)] — a stable view for checkpointing.
        The caller must release(ids, hashes) (aligned) when done."""
        out = []
        for h, entry in self._by_hash.items():
            if entry.ref_count == 0:
                self._lru.pop(h, None)
            entry.ref_count += 1
            out.append((h, entry.parent_hash, entry.block_id))
        return out

    def committed_view(self) -> List[Tuple[int, Optional[int]]]:
        """Read-only [(hash, parent_hash)] of every committed block, in
        insertion order (parents always commit before children, so replaying
        this list rebuilds a radix index). Used by KV-event re-sync."""
        return [
            (h, e.parent_hash) for h, e in self._by_hash.items() if e.announced
        ]

    def match_prefix(self, block_hashes: Sequence[int]) -> int:
        n = 0
        for h in block_hashes:
            if h in self._by_hash:
                n += 1
            else:
                break
        return n

    def pin_prefix(self, block_hashes: Sequence[int]) -> Tuple[int, List[int]]:
        """Pin the longest cached prefix; returns (matched_blocks, their ids)."""
        matched = self.match_prefix(block_hashes)
        ids: List[int] = []
        for h in block_hashes[:matched]:
            entry = self._by_hash[h]
            if entry.ref_count == 0:
                self._lru.pop(h, None)
            entry.ref_count += 1
            ids.append(entry.block_id)
        return matched, ids

    # -- allocation --------------------------------------------------------

    def alloc(self) -> Optional[int]:
        """Take one free physical block (evicting cold cache if needed)."""
        if self._free:
            return self._free.popleft()
        if self._lru:
            h, entry = self._lru.popitem(last=False)
            del self._by_hash[h]
            if entry.announced:
                self._emit(KvEvent(kind="removed", block_hashes=[h]))
            return entry.block_id
        return None

    def commit(
        self, block_id: int, block_hash: int, parent_hash: Optional[int]
    ) -> None:
        """A sequence finished filling `block_id`; register it shareable.

        If the hash is already cached (another sequence computed the same
        content), the physical block stays private to its owner — it is
        returned to the free list on release instead of double-registering.
        """
        if block_hash in self._by_hash:
            return
        self._by_hash[block_hash] = _Committed(
            block_id=block_id, parent_hash=parent_hash, ref_count=1,
            announced=self._announce_commits,
        )
        if self._announce_commits:
            self._emit(
                KvEvent(kind="stored", block_hashes=[block_hash], parent_hash=parent_hash)
            )

    def announce(self, block_hashes: Sequence[int]) -> None:
        """Tell the router of committed blocks it has not heard of (a pool
        with ``announce_commits=False``: a recurrent-state snapshot now
        covers them). In chain order, parents first."""
        for h in block_hashes:
            entry = self._by_hash.get(h)
            if entry is not None and not entry.announced:
                entry.announced = True
                self._emit(
                    KvEvent(kind="stored", block_hashes=[h], parent_hash=entry.parent_hash)
                )

    def retract(self, block_hash: int) -> None:
        """The snapshot at the end of this block went: the prefix through it
        is no longer a hit here, whatever K/V is resident."""
        entry = self._by_hash.get(block_hash)
        if entry is not None and entry.announced:
            entry.announced = False
            self._emit(KvEvent(kind="removed", block_hashes=[block_hash]))

    def discard(self, block_id: int, block_hash: Optional[int]) -> None:
        """A holder gives ``block_id`` up for good (a window-group page that
        fell behind its sequence's window): freed at once, and forgotten as a
        cached block, unless another sequence holds it too."""
        entry = None if block_hash is None else self._by_hash.get(block_hash)
        if entry is None or entry.block_id != block_id:
            self._free.append(block_id)
            return
        entry.ref_count -= 1
        if entry.ref_count <= 0:
            del self._by_hash[block_hash]
            self._lru.pop(block_hash, None)
            if entry.announced:
                self._emit(KvEvent(kind="removed", block_hashes=[block_hash]))
            self._free.append(block_id)

    def release(self, block_ids: Sequence[int], block_hashes: Sequence[int]) -> None:
        """Sequence done. `block_hashes[i]` pairs with `block_ids[i]` for the
        committed prefix; remaining ids are private/partial blocks → freed."""
        owned = set()
        for i, h in enumerate(block_hashes):
            entry = self._by_hash.get(h)
            if entry is not None and entry.block_id == block_ids[i]:
                owned.add(i)
                entry.ref_count -= 1
                if entry.ref_count <= 0:
                    entry.ref_count = 0
                    self._lru[h] = entry
                    self._lru.move_to_end(h)
        for i, bid in enumerate(block_ids):
            if i not in owned:
                self._free.append(bid)

    def clear(self) -> None:
        """Drop all reusable cached blocks (ref: clear_kv_blocks route)."""
        evicted = []
        for h in list(self._lru):
            entry = self._lru.pop(h)
            del self._by_hash[h]
            self._free.append(entry.block_id)
            if entry.announced:
                evicted.append(h)
        if evicted:
            self._emit(KvEvent(kind="removed", block_hashes=evicted))
        self._emit(KvEvent(kind="cleared"))

    def _emit(self, event: KvEvent) -> None:
        if self._on_event is not None:
            self._on_event(event)


class WindowPages:
    """The window page group: a pool of its own for the sliding-window
    layers, and the rules by which a sequence holds only the pages its
    window (and the chunk or look-ahead in front of it) can read.

    A sequence's pages are a list indexed by LOGICAL block, -1 where it
    holds none (behind the window: released, or never taken by a prefix
    hit). Which pages stay in the cache when they fall behind the window of
    a running sequence: those it pinned FROM the cache (a chain's trailing
    window, shared) and its own prompt's trailing window (``keep``: where
    the next request that shares this prompt resumes); every other page of
    its own is freed for good (``BlockPool.discard``), so that a long
    prefill does not push the chains' tails out of the cache. What a
    sequence still holds when it ends goes to the cache as the full
    group's blocks do: the chain's end is where its next turn resumes."""

    def __init__(self, num_blocks: int, block_size: int, window: int) -> None:
        self.pool = BlockPool(num_blocks, block_size)  # no events: see the module's head
        self.block_size = block_size
        self.window = window
        self.released = 0  # pages given back behind a running sequence's window
        self.cut_hits = 0  # prefix hits shortened (or lost) for want of a window tail

    @staticmethod
    def blocks_needed(
        max_num_seqs: int, window: int, block_size: int, prefill_chunk: int,
        lookahead: int,
    ) -> int:
        """The group's size: every row its most pages (``row_bound``) and as
        many cached chains' trailing windows again."""
        row = WindowPages.row_bound(window, block_size, max(prefill_chunk, lookahead))
        tail = (window - 1) // block_size + 2
        return max_num_seqs * (row + tail)

    @staticmethod
    def row_bound(window: int, block_size: int, ahead: int) -> int:
        """Most pages one sequence holds: the window behind its next query,
        the ``ahead`` tokens (a prefill chunk, the decode look-ahead) in
        front, and the page both may straddle."""
        return (window + ahead - 2) // block_size + 2

    def first_live(self, pos: int) -> int:
        """Logical block of the first key a query at ``pos`` sees."""
        return max(pos - self.window + 1, 0) // self.block_size

    def _resume_pos(self, blocks: int, n_tokens: int) -> int:
        return min(blocks * self.block_size, n_tokens - 1)

    def cut_match(self, hashes: Sequence[int], matched: int, n_tokens: int) -> int:
        """The longest prefix of at most ``matched`` blocks (what the full
        group holds) at whose end this group still holds the window: blocks
        [first_live(resume position), m) all cached here."""
        run, best = 0, 0
        runs = []
        for i in range(matched):
            run = run + 1 if self.pool.contains(hashes[i]) else 0
            runs.append(run)
        for m in range(matched, 0, -1):
            if runs[m - 1] >= m - self.first_live(self._resume_pos(m, n_tokens)):
                best = m
                break
        if best < matched:
            self.cut_hits += 1
        return best

    def pin_tail(self, hashes: Sequence[int], matched: int, n_tokens: int) -> List[int]:
        """Pin the window in front of the resume position of a ``matched``
        block prefix (``cut_match`` said it is here). Returns the sequence's
        page list: -1 up to the tail, then the pinned ids."""
        lo = self.first_live(self._resume_pos(matched, n_tokens)) if matched else 0
        got, ids = self.pool.pin_prefix(list(hashes[lo:matched]))
        assert got == matched - lo, "cut_match said the window tail is cached"
        return [-1] * lo + ids

    def prompt_tail(self, n_tokens: int) -> Tuple[int, int]:
        """Logical blocks [lo, hi) of a prompt's trailing window: the whole
        blocks a request resuming at the prompt's end would need."""
        hi = n_tokens // self.block_size
        return min(self.first_live(hi * self.block_size), hi), hi

    def advance(
        self, pages: List[int], hashes: Sequence[int], pinned: int,
        keep: Tuple[int, int], pos: int, upto: int,
    ) -> Optional[List[int]]:
        """Before a step whose first query is at ``pos`` and whose last
        written position is ``upto``: give back the pages wholly behind the
        window (to the cache where pinned from it or in ``keep``, else for
        good) and take pages up to ``upto``'s. Returns the logical blocks
        newly taken, None where the pool is dry (nothing taken then)."""
        # What a sequence holds is one run of slots up to the list's end, so
        # the pages behind the window are found from the window backwards:
        # the cost is what is released, not the context's length.
        i = min(self.first_live(pos), len(pages)) - 1
        while i >= 0 and pages[i] >= 0:
            h = hashes[i] if i < len(hashes) else None
            if i < pinned or keep[0] <= i < keep[1]:
                self.pool.release([pages[i]], [] if h is None else [h])
            else:
                self.pool.discard(pages[i], h)
            pages[i] = -1
            self.released += 1
            i -= 1
        need = upto // self.block_size + 1
        if need - len(pages) > self.pool.free_blocks:
            return None
        taken = []
        while len(pages) < need:
            taken.append(len(pages))
            pages.append(self.pool.alloc())
        return taken

    def commit(self, pages: Sequence[int], index: int, block_hash: int,
               parent: Optional[int]) -> None:
        if index < len(pages) and pages[index] >= 0:
            self.pool.commit(pages[index], block_hash, parent)

    def release_all(self, pages: List[int], hashes: Sequence[int]) -> None:
        """Sequence done (finished, preempted, aborted): committed pages go
        to the cache, where the chain's trailing window can be hit; the
        rest are freed."""
        for i, b in enumerate(pages):
            if b >= 0:
                self.pool.release([b], [hashes[i]] if i < len(hashes) else [])
        del pages[:]

    @staticmethod
    def _run_back(pages: Sequence[int], end: int) -> int:
        """Held slots in the run that ends just before ``end``."""
        i = end
        while i > 0 and pages[i - 1] >= 0:
            i -= 1
        return end - i

    def held(self, pages: Sequence[int]) -> int:
        return self._run_back(pages, len(pages))

    def dead(self, pages: Sequence[int], pos: int) -> int:
        """Pages held wholly behind the window of a query at ``pos``."""
        return self._run_back(pages, min(self.first_live(pos), len(pages)))


# Entries of the recurrent-state snapshot store of a hybrid model (one entry =
# every recurrent layer's state at one snapshot boundary): the runner
# allocates that many, the engine's StateSnapshots indexes them.
SSM_SNAPSHOT_ENTRIES = 256


def snapshot_entries(config, num_kv_blocks: int, block_size: int, max_num_seqs: int) -> int:
    """Entries of the snapshot store. A model whose recurrent spec names no
    spacing of its own (or that has no recurrent layer: the store is then an
    empty tree) snapshots every scan block into ``SSM_SNAPSHOT_ENTRIES``; one
    that does (an entry of lightning attention is megabytes a layer) gets one
    entry for every boundary the K/V pool's tokens can hold (a snapshot no
    page stands behind serves no hit), at least two a decode row, at most
    ``SSM_SNAPSHOT_ENTRIES``."""
    if not config.has_recurrent_state:
        return SSM_SNAPSHOT_ENTRIES
    scan, every = config.snapshot_stride
    if every == scan:
        return SSM_SNAPSHOT_ENTRIES
    return min(
        SSM_SNAPSHOT_ENTRIES, max(2 * max_num_seqs, num_kv_blocks * block_size // every)
    )


class StateSnapshots:
    """Index of recurrent-state snapshots: which entry of the runner's
    snapshot store holds the state at the END of which block (keyed by that
    block's chained content hash, so a key names the whole prefix). Bounded:
    ``capacity`` entries, least recently used evicted first. Snapshots sit
    at every ``stride_blocks``-th block boundary of a prompt (the recurrent
    spec's ``snapshot_every`` tokens)."""

    def __init__(
        self, capacity: int, stride_blocks: int,
        *, on_evict: Optional[Callable[[int], None]] = None,
    ) -> None:
        self.capacity = capacity
        self.stride_blocks = stride_blocks
        self._on_evict = on_evict
        self._entries: "OrderedDict[int, int]" = OrderedDict()  # hash -> index
        self._free: Deque[int] = deque(range(capacity))
        self.hits = 0
        self.evictions = 0

    @property
    def used(self) -> int:
        return len(self._entries)

    def lookup(self, block_hashes: Sequence[int], matched_blocks: int) -> Tuple[int, int]:
        """(blocks, store index) of the longest prefix of at most
        ``matched_blocks`` blocks that ends at a snapshot, (0, -1) if none."""
        stride = self.stride_blocks
        n = (min(matched_blocks, len(block_hashes)) // stride) * stride
        while n > 0:
            idx = self._entries.get(block_hashes[n - 1])
            if idx is not None:
                self._entries.move_to_end(block_hashes[n - 1])
                self.hits += 1
                return n, idx
            n -= stride
        return 0, -1

    def reserve(self, block_hash: int) -> int:
        """A store index for the snapshot at the end of ``block_hash``'s
        block, -1 when it is already kept (touched: it stays longer). The
        entry is visible at once: whoever reads it is dispatched after the
        program that writes it."""
        if block_hash in self._entries:
            self._entries.move_to_end(block_hash)
            return -1
        if not self._free:
            if not self._entries:
                return -1
            old, idx = self._entries.popitem(last=False)
            self.evictions += 1
            if self._on_evict is not None:
                self._on_evict(old)
            self._free.append(idx)
        idx = self._free.popleft()
        self._entries[block_hash] = idx
        return idx

    def discard(self, block_hashes: Sequence[int]) -> None:
        """Forget entries whose program never ran (a failed prefill)."""
        for h in block_hashes:
            idx = self._entries.pop(h, None)
            if idx is not None:
                self._free.append(idx)

    def clear(self) -> None:
        self.discard(list(self._entries))
