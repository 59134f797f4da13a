"""KV-reuse plane at enterprise scale (ISSUE 16 satellite): ~10^5 distinct
cached prefixes (10^6 @slow) through the REAL KvIndexer radix tree and the
popularity sketch together. The contracts:

  * sketch memory is bounded by capacity (entries AND lazy heap), no
    matter how many distinct prefixes stream past;
  * per-touch latency stays bounded — p99 recorded into the lint-pinned
    KVCACHE_SKETCH_LOOKUP_P99_SECONDS gauge;
  * on zipf traffic the sketch recovers the EXACT top-K vs a brute-force
    oracle (the space-saving guarantee the eviction policy will lean on);
  * the /debug/kvcache view stays coherent with what was fed;
  * departed workers leave zero residue in the sketch (the PR 10 audit
    extended to this plane).
"""

import time

import numpy as np
import pytest

from dynamo_tpu.router.indexer import KvIndexer
from dynamo_tpu.router.protocols import RouterEvent
from dynamo_tpu.runtime.kv_reuse_observe import (
    KvReusePlane,
    PrefixPopularitySketch,
    kvcache_index,
)

BLOCK = 16


def _scale_harness(n_prefixes: int, n_touches: int, capacity: int = 4096):
    """Store ``n_prefixes`` distinct single-block prefixes in a real
    indexer, then replay ``n_touches`` zipf-distributed lookups through
    indexer + plane. Returns (plane, indexer, oracle counts, p99_s)."""
    rng = np.random.default_rng(7)
    # Anchor hashes: distinct, deterministic, and NOT sequential (the
    # radix keys real traffic produces are 64-bit content hashes).
    anchors = rng.permutation(
        np.arange(1, n_prefixes + 1, dtype=np.uint64)
    )
    anchors = (
        (anchors * np.uint64(0x9E3779B97F4A7C15))
        & np.uint64(0x7FFFFFFFFFFFFFFF)
    ).astype(np.int64)

    indexer = KvIndexer(block_size=BLOCK)
    worker = 1
    for h in anchors:
        # One event per prefix: block_hashes is a parent->child CHAIN, so
        # distinct prefixes are distinct root blocks, not one long chain.
        indexer.apply(RouterEvent(
            worker_id=worker, kind="stored", block_hashes=[int(h)],
        ))

    # Zipf ranks -> anchor ids: heavy skew so true heavy hitters exist.
    ranks = rng.zipf(1.2, size=n_touches)
    ranks = np.minimum(ranks, n_prefixes) - 1

    plane = KvReusePlane(capacity=capacity)
    sketch = plane.sketch

    # Individually-timed subsample for the p99 bound; the rest in bulk.
    timed = min(20_000, n_touches)
    lat = np.empty(timed, dtype=np.float64)
    for j in range(timed):
        h = int(anchors[ranks[j]])
        t0 = time.perf_counter()
        sketch.touch(h, tokens=BLOCK, worker=(worker, 0))
        lat[j] = time.perf_counter() - t0
    for j in range(timed, n_touches):
        sketch.touch(
            int(anchors[ranks[j]]), tokens=BLOCK, worker=(worker, 0)
        )
    p99 = float(np.percentile(lat, 99))
    plane.metrics.sketch_lookup_p99.set(p99)

    # A real-indexer spot check: every sampled prefix must resolve.
    for j in range(0, n_touches, max(1, n_touches // 1000)):
        scores = indexer.find_matches([int(anchors[ranks[j]])])
        assert scores.scores.get((worker, 0)) == 1

    oracle = np.bincount(ranks, minlength=n_prefixes)
    return plane, indexer, anchors, oracle, p99


def _assert_scale_contracts(n_prefixes: int, n_touches: int) -> None:
    capacity = 4096
    plane, indexer, anchors, oracle, p99 = _scale_harness(
        n_prefixes, n_touches, capacity
    )
    sketch = plane.sketch

    # Memory bounded by capacity, not by distinct prefixes seen.
    assert len(sketch) <= capacity
    assert len(sketch._heap) <= 8 * capacity
    assert sketch.total_touches == n_touches
    assert sketch.replacements > 0  # the stream DID overflow capacity

    # Bounded p99 per-touch latency, recorded as the lint-pinned gauge.
    assert p99 < 5e-3, f"sketch touch p99 {p99 * 1e6:.1f}us"
    rendered = plane.metrics.render()
    assert "dynamo_tpu_kvcache_sketch_lookup_p99_seconds" in rendered

    # Exact top-K vs the brute-force oracle (zipf separates the heavy
    # hitters far past the space-saving error bound).
    K = 10
    want = {
        int(anchors[r]) for r in np.argsort(oracle)[::-1][:K]
    }
    got_rows = sketch.top(K)
    got = {int(row["anchor"], 16) for row in got_rows}
    assert got == want
    # Reported error bounds must not drown the scores for true heavies.
    for row in got_rows:
        assert row["score"] > row["score_error"]

    # Coherent /debug/kvcache view of the same plane.
    view = kvcache_index(plane=plane, top_k=K)
    assert view["sketch"]["tracked"] == len(sketch)
    assert view["sketch"]["capacity"] == capacity
    assert {int(r["anchor"], 16) for r in view["top_prefixes"]} == want
    top_tokens = {
        int(r["anchor"], 16): r["tokens_from_cache"]
        for r in view["top_prefixes"]
    }
    for r in np.argsort(oracle)[::-1][:K]:
        # Tracked-from-birth heavies count every token they served.
        assert top_tokens[int(anchors[r])] == int(oracle[r]) * BLOCK


def test_kv_reuse_scale_100k():
    _assert_scale_contracts(n_prefixes=100_000, n_touches=150_000)


@pytest.mark.slow
def test_kv_reuse_scale_1m():
    _assert_scale_contracts(n_prefixes=1_000_000, n_touches=1_500_000)


def _assert_tier_manager_scale(n_blocks: int) -> None:
    """Drive the TIER MANAGER itself at scale (ISSUE 17 satellite): with
    ~n distinct cached prefixes resident in the host tier,

      * onboard-lookup latency (match_chain) stays bounded — it is on the
        admission path for every hintless request;
      * /debug/kvcache stays coherent: live occupancy equals what was
        fed, capacity evictions mirrored exactly into the plane counters.
    """
    from dynamo_tpu.kvbm import HostTier, OffloadFilter, TieredKvManager

    rng = np.random.default_rng(11)
    hashes = rng.permutation(np.arange(1, n_blocks + 1, dtype=np.uint64))
    hashes = (
        (hashes * np.uint64(0x9E3779B97F4A7C15))
        & np.uint64(0x7FFFFFFFFFFFFFFF)
    ).astype(np.int64)

    plane = KvReusePlane(capacity=4096)
    host = HostTier(n_blocks)
    # min_frequency=∞: notify_commit never enqueues offload work, so the
    # manager runs engineless (no event loop in this test).
    kvbm = TieredKvManager(
        host, plane=plane, filter=OffloadFilter(min_frequency=10**9)
    )
    try:
        # ONE shared 1-byte payload: tier entries hold references, so the
        # footprint is the index, not n_blocks copies of KV data.
        payload = np.zeros(1, dtype=np.int8)
        for h in hashes:
            host.put(int(h), payload, payload)
        assert len(host) == n_blocks

        # Overflow past capacity: the oldest entries spill (dropped — no
        # next tier) and the deltas must mirror into the plane exactly.
        extra = 1000
        for h in range(n_blocks + 1, n_blocks + 1 + extra):
            host.put(h, payload, payload)
        kvbm._sync_plane()
        assert len(host) == n_blocks
        assert (
            plane.metrics.evictions.value(tier="host", reason="capacity")
            == extra
        )

        # Bounded onboard-lookup latency on a full tier: hits and misses.
        timed = min(20_000, n_blocks)
        lat = np.empty(timed, dtype=np.float64)
        probe = rng.integers(0, n_blocks, size=timed)
        for j in range(timed):
            h = int(hashes[probe[j]])
            t0 = time.perf_counter()
            n = kvbm.match_chain([h])
            lat[j] = time.perf_counter() - t0
            assert n == (1 if host.contains(h) else 0)
        p99 = float(np.percentile(lat, 99))
        assert p99 < 5e-3, f"match_chain p99 {p99 * 1e6:.1f}us"
        assert kvbm.match_chain([int(hashes[0]) ^ (1 << 60)]) == 0

        # Coherent /debug/kvcache: the manager's live occupancy source.
        view = kvcache_index(plane=plane, top_k=5)
        tier_view = view["tiers"]["kvbm"]["host"]
        assert tier_view["blocks"] == n_blocks
        assert tier_view["stored"] == n_blocks + extra
    finally:
        # Engineless manager: close() is async but nothing is in flight —
        # detach the plane sources directly (what close() would do).
        for name in list(kvbm.metrics._tier_sources):
            kvbm.metrics.unwatch_tier(name)
        plane.forget_tier_source(kvbm._plane_label)


def test_tier_manager_scale_100k():
    _assert_tier_manager_scale(100_000)


@pytest.mark.slow
def test_tier_manager_scale_1m():
    _assert_tier_manager_scale(1_000_000)


def test_drop_worker_zero_residue_through_scheduler():
    """The router wires plane.drop_worker as a KvScheduler drop callback:
    a departed worker's sketch contributions vanish with its radix/load
    state (zero-residue leak audit, PR 10)."""
    from dynamo_tpu.router.protocols import LoadSnapshot
    from dynamo_tpu.router.scheduler import KvScheduler

    plane = KvReusePlane(capacity=64)
    sched = KvScheduler(seed=3)
    sched.add_drop_callback(plane.drop_worker)
    w1, w2 = (1, 0), (2, 0)
    for w in (w1, w2):
        sched.update_load(LoadSnapshot(
            worker_id=w[0], active_blocks=1, total_blocks=64,
        ))
    # Anchor 100 is sustained by both workers, 200 only by the departing.
    plane.note_router_match(100, tokens=BLOCK, worker=w1)
    plane.note_router_match(100, tokens=BLOCK, worker=w2)
    plane.note_router_match(200, tokens=BLOCK, worker=w1)
    assert len(plane.sketch) == 2

    sched.drop_worker(w1)
    anchors = {int(r["anchor"], 16) for r in plane.sketch.top(10)}
    assert anchors == {100}  # w1-only entry fully purged
    [row] = plane.sketch.top(10)
    assert row["tokens_from_cache"] == BLOCK  # w1's tokens subtracted

    # Idempotent (monitor + deregistration can both fire).
    assert plane.drop_worker(w1) == 0


def test_sketch_decay_prefers_recent():
    """A once-hot prefix decays below a currently-hot one (recency
    weighting: the eviction-informing ranking must not canonize history)."""
    sketch = PrefixPopularitySketch(capacity=16, half_life_s=0.05)
    for _ in range(64):
        sketch.touch(1, tokens=BLOCK)
    time.sleep(0.25)  # 5 half-lives: old score / 32
    for _ in range(8):
        sketch.touch(2, tokens=BLOCK)
    top = sketch.top(2)
    assert int(top[0]["anchor"], 16) == 2
    # Raw lifetime hits are preserved un-decayed for display.
    by_anchor = {int(r["anchor"], 16): r for r in top}
    assert by_anchor[1]["hits"] == 64


def test_sketch_min_replacement_inherits_error():
    """Space-saving: at capacity, the newcomer replaces the minimum and
    inherits its count as the overestimation bound."""
    sketch = PrefixPopularitySketch(capacity=2, half_life_s=0.0)
    for _ in range(5):
        sketch.touch(1)
    sketch.touch(2)
    sketch.touch(3)  # replaces anchor 2 (count 1)
    assert sketch.replacements == 1
    assert len(sketch) == 2
    rows = {int(r["anchor"], 16): r for r in sketch.top(2)}
    assert set(rows) == {1, 3}
    assert rows[3]["score"] == pytest.approx(2.0)  # inherited 1 + own 1
    assert rows[3]["score_error"] == pytest.approx(1.0)
    assert rows[1]["score_error"] == 0.0


def test_popularity_eviction_keeps_heavy_hitters_that_lru_loses():
    """The sketch's ranking at work where it is spent: the same zipf
    stream of single-block prefixes, 256 distinct keys through 64 host
    slots, once against a plain-LRU host tier and once against one whose
    eviction the REAL manager bridge scores (sketch -> protected
    prefixes). At equal capacity the scored tier serves more of the
    stream: cold-key bursts evict its cold keys, LRU's hot ones."""
    from dynamo_tpu.kvbm import HostTier, OffloadFilter, TieredKvManager

    capacity, n_keys, draws = 64, 256, 4000
    rng = np.random.default_rng(24)
    ranks = np.minimum(rng.zipf(1.2, size=draws), n_keys) - 1
    keys = (
        (np.arange(1, n_keys + 1, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15))
        & np.uint64(0x7FFFFFFFFFFFFFFF)
    ).astype(np.int64)
    payload = np.zeros(1, dtype=np.int8)

    def hit_rate(scored: bool) -> float:
        host = HostTier(capacity)
        plane = KvReusePlane(capacity=n_keys)
        kvbm = None
        if scored:
            # min_frequency=inf: notify_commit never enqueues offload work,
            # so the manager runs engineless and only its scorer is live
            kvbm = TieredKvManager(
                host, plane=plane, filter=OffloadFilter(min_frequency=10**9)
            )
        hits = 0
        for j, r in enumerate(ranks):
            h = int(keys[r])
            if j == draws // 2:
                time.sleep(0.55)  # past the scorer's refresh interval
            if host.contains(h):
                hits += 1
                host.get(h)
                plane.sketch.touch(h, tokens=BLOCK)
            else:
                host.put(h, payload, payload)
                if kvbm is not None:
                    kvbm.notify_commit(h, 1)
        if kvbm is not None:
            for name in list(kvbm.metrics._tier_sources):  # noqa: SLF001
                kvbm.metrics.unwatch_tier(name)
            plane.forget_tier_source(kvbm._plane_label)  # noqa: SLF001
        return hits / draws

    lru, scored = hit_rate(False), hit_rate(True)
    assert scored > lru, (scored, lru)
