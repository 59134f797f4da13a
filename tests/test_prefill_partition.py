"""A prefill dispatch is as wide as its own rows (admission.py
``PrefillPrice`` / ``prefill_partition`` / ``_finish_admission``): the rows
admitted together run as the groups whose ``[rows bucket, chunk bucket]``
programs price least, each group its own rounds and install, a batch whose
joint program prices least as one group. On the CPU: the price from the
parameter trees' SHAPES at the published widths (nothing is allocated), the
partition as a pure function, and the tiny engines with a price that makes
them split (at their widths a dispatch costs more than any program, so they
never would)."""

import asyncio
import functools

import jax
import numpy as np
import pytest

from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
from dynamo_tpu.engines.tpu.admission import (
    DISPATCH_BYTES,
    PARTITION_MARGIN,
    PrefillPrice,
    _next_pow2,
    prefill_chunk_bucket,
    prefill_partition,
)
from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models.llama import step_weights
from dynamo_tpu.models.config import tiny_config, tiny_hybrid_config
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect
from dynamo_tpu.worker.__main__ import BUILTIN_CONFIGS

# -- the price -----------------------------------------------------------------

SERVED = {  # configuration -> its cell's --prefill-chunk
    "qwen2.5-0.5b": 1024,
    "nemotron-3-nano-30b-a3b-ep2": 1024,
    "openpangu-ultra-moe-718b-ep16": 256,
    "laguna-xs.2-pp8": 256,
    "minicpm-sala-pp4": 256,
    "qwen3-next-80b-a3b-ep2": 256,
}


@functools.lru_cache(maxsize=None)
def _price(name: str) -> PrefillPrice:
    config = BUILTIN_CONFIGS[name]()
    init = hybrid.init_params if config.is_hybrid else llama.init_params
    shapes = jax.eval_shape(functools.partial(init, config), jax.random.PRNGKey(0))
    return PrefillPrice(*step_weights(shapes, config))


@pytest.mark.parametrize("name", sorted(SERVED))
def test_price_never_falls_as_a_program_grows(name):
    price = _price(name)
    shapes = sorted(
        ((B, C) for B in (1, 2, 4, 8) for C in (128, 256, 512, 1024)),
        key=lambda s: s[0] * s[1])
    priced = [price(*s) for s in shapes]
    for (small, a), (large, b) in zip(zip(shapes, priced), zip(shapes[1:], priced[1:])):
        assert a <= b, (small, large)
    # the same positions price the same however they are laid out
    assert price(8, 128) == price(1, 1024) == price(2, 512)
    # nothing is free: a dispatch and the matrices every program streams
    assert price(1, 128) >= DISPATCH_BYTES + price.always_bytes


@pytest.mark.parametrize("name, always_gb, experts_gb", [
    ("qwen2.5-0.5b", 0.99, 0.0),  # 0.36 B outside the tied embedding + the head it is
    ("nemotron-3-nano-30b-a3b-ep2", 0.87, 5.11),  # 64 held experts of 19.96 MB x 4 layers
    ("laguna-xs.2-pp8", 0.89, 6.44),  # 256 experts of 6.29 MB x 4 layers
    ("qwen3-next-80b-a3b-ep2", 0.61, 6.44),
])
def test_price_reads_the_bytes_the_records_give(name, always_gb, experts_gb):
    price = _price(name)
    assert price.always_bytes / 1e9 == pytest.approx(always_gb, abs=0.01)
    assert sum(held for held, _ in price.experts) / 1e9 == pytest.approx(experts_gb, abs=0.01)


@pytest.mark.parametrize("left, groups", [
    ([120, 200, 90, 700], [[0, 2], [1], [3]]),  # one long prompt beside short ones
    ([200, 210], [[0, 1]]),  # one bucket: the joint program
    ([3000, 100, 120], [[0], [1, 2]]),  # short rows no longer ride the long row's rounds
])
def test_a_dense_half_billion_splits_unequal_rows(left, groups):
    assert prefill_partition(left, 1024, _price("qwen2.5-0.5b")) == groups


@pytest.mark.parametrize("name", ["nemotron-3-nano-30b-a3b-ep2", "laguna-xs.2-pp8",
                                  "qwen3-next-80b-a3b-ep2", "openpangu-ultra-moe-718b-ep16"])
@pytest.mark.parametrize("left", [[100, 250], [250, 60], [120, 128]])
def test_expert_widths_keep_a_two_row_turn_joint(name, left):
    """Two programs would each stream the held experts: a 128- and a
    256-token row stay one [2, 256] step."""
    assert prefill_partition(left, SERVED[name], _price(name)) == [[0, 1]]


def test_the_hybrid_cell_splits_a_burst_in_few_groups():
    """A program's floor (0.87 GB + 5.1 GB of held experts) caps the number
    of groups: not one program a row."""
    groups = prefill_partition(
        [70, 80, 90, 100, 200, 300, 500, 1000], 1024, _price("nemotron-3-nano-30b-a3b-ep2"))
    assert groups == [[0, 1, 2, 3], [4], [5, 6], [7]]  # [4, 128] [1, 256] [2, 512] [1, 1024]


# -- the partition -------------------------------------------------------------

PRICES = {
    "positions": lambda B, C: float(B * C),  # no floor at all: a group a bucket
    "floor": lambda B, C: 3000.0 + B * C,  # a stream every program pays
    "dispatch": lambda B, C: 1e6 + B * C,  # a dispatch dearer than any program
    "dense_0.5b": None,  # resolved in the test: the published widths
}
ROWS = [
    [65], [100, 100], [65, 1024], [120, 200, 90, 700], [300, 70, 300, 70, 900],
    [70, 80, 90, 100, 200, 300, 500, 1000], [1024] * 8, [5000, 100, 2000, 100],
    [129, 128, 127, 1, 257, 256, 255],
]


@pytest.mark.parametrize("price", sorted(PRICES))
@pytest.mark.parametrize("left", ROWS, ids=lambda r: "-".join(map(str, r)))
def test_partition_is_a_partition_of_sorted_runs(price, left):
    chunk = 1024
    fn = _price("qwen2.5-0.5b") if price == "dense_0.5b" else PRICES[price]
    groups = prefill_partition(left, chunk, fn)
    assert sorted(r for g in groups for r in g) == list(range(len(left)))  # each row once
    assert all(g == sorted(g) for g in groups)  # arrival order inside a group
    assert [g[0] for g in groups] == sorted(g[0] for g in groups)  # oldest member first
    assert max(len(g) for g in groups) <= 8
    # contiguous in the order of what the rows have left
    order = sorted(range(len(left)), key=lambda r: (left[r], r))
    rank = {r: i for i, r in enumerate(order)}
    for g in groups:
        ranks = sorted(rank[r] for r in g)
        assert ranks == list(range(ranks[0], ranks[0] + len(g)))

    def total(gs):
        out = 0.0
        for g in gs:
            todo = max(left[r] for r in g)
            while todo > 0:
                out += fn(_next_pow2(len(g)), prefill_chunk_bucket(min(todo, chunk), chunk))
                todo -= chunk
        return out

    # never dearer than the joint program, and a split only where it prices
    # the margin under it; never dearer than one program a row by more than
    # the margin either
    joint = total([list(range(len(left)))])
    assert total(groups) <= joint + 1e-6
    assert len(groups) == 1 or total(groups) * PARTITION_MARGIN < joint
    assert total(groups) <= PARTITION_MARGIN * total([[r] for r in range(len(left))]) + 1e-6
    if price == "dispatch":
        assert len(groups) == 1


@pytest.mark.parametrize("price", sorted(PRICES))
@pytest.mark.parametrize("left", [[130, 250], [600, 1024, 700, 513], [65] * 8, [128, 100, 90, 66]],
                         ids=lambda r: "-".join(map(str, r)))
def test_rows_of_one_bucket_stay_one_group(price, left):
    fn = _price("qwen2.5-0.5b") if price == "dense_0.5b" else PRICES[price]
    assert prefill_partition(left, 1024, fn) == [list(range(len(left)))]


# -- the engines ---------------------------------------------------------------

LENS = [20, 300, 150, 30, 400]  # chunk buckets 128, 512, 256, 128, 512 at chunk 512


def _engine(config, **kw):
    base = dict(config=config, block_size=16, num_kv_blocks=256, max_num_seqs=8,
                max_model_len=1024, prefill_chunk=512, decode_steps=4)
    base.update(kw)
    return JaxEngine(JaxEngineArgs(**base))


def _req(i, n, max_tokens=6):
    ids = np.random.default_rng([52, i]).integers(3, 500, n).tolist()
    return PreprocessedRequest(
        token_ids=ids, request_id=f"r{i}",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )


def _tokens(outs):
    return [t for o in outs for t in (o.token_ids or [])]


def _spy_groups(engine, monkeypatch):
    """(rows, rows bucket, chunk bucket) of every prefill step dispatched,
    and the rows of every install."""
    steps, installs, real_step = [], [], engine._run_step
    real_install = engine._admitter._install

    def step(tokens, start, lens, *a, **kw):
        steps.append((int(np.count_nonzero(lens)), len(tokens), len(tokens[0])))
        return real_step(tokens, start, lens, *a, **kw)

    def install(seq, *a, **kw):
        installs.append(seq.request.request_id)
        return real_install(seq, *a, **kw)

    monkeypatch.setattr(engine, "_run_step", step)
    monkeypatch.setattr(engine._admitter, "_install", install)
    return steps, installs


async def _alone(config, lens, **kw):
    engine = _engine(config, **kw)
    try:
        return [
            _tokens(await collect(engine.generate(_req(i, n), Context())))
            for i, n in enumerate(lens)
        ]
    finally:
        await engine.stop()


@pytest.mark.parametrize("make", [tiny_config, tiny_hybrid_config], ids=["dense", "hybrid"])
async def test_split_admission_streams_equal_each_request_alone(make, monkeypatch):
    """Five unequal prompts admitted together, under a price that groups
    them by bucket: three programs, each at its own width; every stream is
    the one the request gives alone; a slot and (hybrid) the recurrent state
    are installed once a row."""
    alone = await _alone(make(), LENS)
    engine = _engine(make())
    engine._admitter.price = PRICES["positions"]
    steps, installs = _spy_groups(engine, monkeypatch)
    state_rows = []
    if engine.config.is_hybrid:
        real = engine.runner.ssm_install
        monkeypatch.setattr(
            engine.runner, "ssm_install",
            lambda slots, state, rows: (state_rows.append(len(slots)), real(slots, state, rows))[1])
    try:
        outs = await asyncio.gather(*(
            collect(engine.generate(_req(i, n), Context())) for i, n in enumerate(LENS)))
        assert [_tokens(o) for o in outs] == alone
        assert steps == [(2, 2, 128), (2, 2, 512), (1, 1, 256)]  # oldest member first
        assert sorted(installs) == [f"r{i}" for i in range(len(LENS))]
        if engine.config.is_hybrid:
            assert state_rows == [2, 2, 1]
        m = engine.step_metrics
        assert m.prefill_positions.value(kind="live") == sum(LENS)
        assert m.prefill_positions.value(kind="padded") == 2 * 128 + 2 * 512 + 256 - sum(LENS)
        assert m.prefill_dispatches.value(rows="2", chunk="512") == 1
        assert engine.pool.active_blocks == 0
    finally:
        await engine.stop()


async def test_the_tiny_engines_keep_a_joint_batch_by_their_own_price(monkeypatch):
    """At toy widths a dispatch prices over any program: one [8, 512] step,
    as before the partition."""
    engine = _engine(tiny_config())
    steps, _ = _spy_groups(engine, monkeypatch)
    try:
        await asyncio.gather(*(
            collect(engine.generate(_req(i, n), Context())) for i, n in enumerate(LENS)))
        assert steps == [(5, 8, 512)]
    finally:
        await engine.stop()


@pytest.mark.parametrize("make", [tiny_config, tiny_hybrid_config], ids=["dense", "hybrid"])
async def test_an_admission_with_a_prefix_hit_row_stays_one_program(make, monkeypatch):
    """A row that resumes cached context runs over a table as wide as that
    context, a program of no ladder: the admission it is in dispatches the
    one shape it did before the partition, under any price; the next
    admission, all fresh, splits again."""
    engine = _engine(make())
    engine._admitter.price = PRICES["positions"]
    shared = _req(0, 300, 4).token_ids
    again = PreprocessedRequest(
        token_ids=shared + _req(9, 40).token_ids, request_id="again",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=4, ignore_eos=True))
    try:
        await collect(engine.generate(_req(0, 300, 4), Context()))  # its blocks stay cached
        steps, installs = _spy_groups(engine, monkeypatch)
        matched, begin = [], engine._admitter._begin_prefill
        monkeypatch.setattr(
            engine._admitter, "_begin_prefill",
            lambda batch: (matched.append([prep.matched_tokens for _, prep in batch]), begin(batch))[1])
        await asyncio.gather(
            collect(engine.generate(again, Context())),
            *(collect(engine.generate(_req(i, n, 4), Context())) for i, n in [(1, 20), (2, 400)]))
        assert [sorted(m) for m in matched] == [[0, 0, 288]]  # 18 of the 300 tokens' blocks
        assert steps == [(3, 4, 512)], steps
        del steps[:]
        await asyncio.gather(
            *(collect(engine.generate(_req(i, n, 4), Context())) for i, n in [(3, 30), (4, 410)]))
        assert steps == [(1, 1, 128), (1, 1, 512)], steps
        assert engine.pool.active_blocks == 0
    finally:
        await engine.stop()


@pytest.mark.parametrize("make", [tiny_config, tiny_hybrid_config], ids=["dense", "hybrid"])
async def test_a_failing_group_contains_to_its_own_rows(make, monkeypatch):
    """The [2, 512] group's program fails every time: its two rows are
    retried once and ejected with an error; the groups before and behind it
    install and stream as they do alone; no block stays pinned."""
    alone = await _alone(make(), LENS)
    engine = _engine(make())
    engine._admitter.price = PRICES["positions"]
    real = engine._run_step

    def step(tokens, *a, **kw):
        if len(tokens[0]) == 512:
            raise RuntimeError("injected: the 512-token program fails")
        return real(tokens, *a, **kw)

    monkeypatch.setattr(engine, "_run_step", step)
    try:
        outs = await asyncio.gather(*(
            collect(engine.generate(_req(i, n), Context())) for i, n in enumerate(LENS)))
        for i, (out, want) in enumerate(zip(outs, alone)):
            if LENS[i] > 256:
                assert out[-1].finish_reason == FinishReason.ERROR
                assert "injected" in out[-1].error
            else:
                assert _tokens(out) == want
        assert engine._failure is None
        assert engine.pool.active_blocks == 0
    finally:
        await engine.stop()


@pytest.mark.parametrize("make", [tiny_config, tiny_hybrid_config], ids=["dense", "hybrid"])
async def test_budgeted_tick_parks_with_groups_pending_and_resumes(make, monkeypatch):
    """Under a tick budget a pause lands on a chunk boundary of ONE group and
    the groups not yet begun wait with it (``PendingPrefill.later``), ahead
    of any new admission; every stream ends as it does alone."""
    lens = [20, 300, 150, 30, 400, 25]
    alone = await _alone(make(), lens, max_num_seqs=8)
    engine = _engine(
        make(), tick_budget_enabled=True, tick_budget_floor_tokens=64,
        tick_budget_ceiling_tokens=128, tick_budget_policy=0.0)
    engine._admitter.price = PRICES["positions"]
    parked = []
    real = engine._record_budget_event

    def record(kind, **fields):
        if kind == "prefill_pause":
            pending = engine._pending_prefill
            parked.append((len(pending.batch), [len(g) for g in pending.later], len(pending.held)))
            ids = [int(seq.request.request_id[1:]) for seq, _ in pending.held]
            assert ids == sorted(ids)  # as admitted, whatever the groups' order
        return real(kind, **fields)

    monkeypatch.setattr(engine, "_record_budget_event", record)
    try:
        first = asyncio.ensure_future(collect(engine.generate(_req(0, lens[0], 40), Context())))
        while engine.generated_tokens < 2:  # a decoding row: the budget binds
            await asyncio.sleep(0.002)
        outs = await asyncio.gather(*(
            collect(engine.generate(_req(i, n), Context()))
            for i, n in enumerate(lens) if i))
        await first
        assert [_tokens(o) for o in outs] == alone[1:]
        assert any(later for _, later, _ in parked), parked  # parked with groups not begun
        assert all(held == rows + sum(later) for rows, later, held in parked)
        assert engine._pending_prefill is None
        assert engine.pool.active_blocks == 0
    finally:
        await engine.stop()


@pytest.mark.parametrize("make", [tiny_config, tiny_hybrid_config], ids=["dense", "hybrid"])
async def test_a_cancelled_admission_returns_the_groups_not_yet_begun(make, monkeypatch):
    """Cancelled while its second group runs, an admission gives back that
    group AND the one behind it: to the front of the queue in the order
    they arrived, every block released, the first group's rows installed
    and counted no longer; admitted again they stream as they do alone."""
    alone = await _alone(make(), LENS)
    engine = _engine(make())
    adm = engine._admitter
    adm.price = PRICES["positions"]
    rounds, run, seen = adm._prefill_rounds, adm._run_prefill, []

    async def cancelled_once(pending):
        if not seen and len(pending.batch[0][0].all_tokens) > 256:  # the [2, 512] group
            seen.append("raised")
            raise asyncio.CancelledError()
        return await rounds(pending)

    async def caught(pending):
        try:
            return await run(pending)
        except asyncio.CancelledError:
            seen.append(([s.request.request_id for s in engine._waiting],
                         [len(s.block_ids) for s in engine._waiting],
                         sum(s is not None for s in engine._slots), engine._admitting))
            return 0

    monkeypatch.setattr(adm, "_prefill_rounds", cancelled_once)
    monkeypatch.setattr(adm, "_run_prefill", caught)
    try:
        outs = await asyncio.gather(*(
            collect(engine.generate(_req(i, n), Context())) for i, n in enumerate(LENS)))
        # groups [r0, r3] [r1, r4] [r2]: the first installed, the rest back as admitted
        assert seen == ["raised", (["r1", "r2", "r4"], [0, 0, 0], 2, 3)]
        assert [_tokens(o) for o in outs] == alone
        assert engine.pool.active_blocks == 0
    finally:
        await engine.stop()


@pytest.mark.parametrize("make", [tiny_config, tiny_hybrid_config], ids=["dense", "hybrid"])
async def test_shutdown_releases_a_parked_group_and_those_behind_it(make, monkeypatch):
    """The engine stops while a budget pause holds one group on a chunk
    boundary with groups not yet begun behind it: all of them go back to the
    queue in arrival order, their blocks released, and their streams end
    cancelled with the queue's."""
    lens = [20, 300, 150, 30, 400, 25]
    engine = _engine(
        make(), tick_budget_enabled=True, tick_budget_floor_tokens=64,
        tick_budget_ceiling_tokens=128, tick_budget_policy=0.0)
    engine._admitter.price = PRICES["positions"]
    parked, requeued = [], []
    real, requeue = engine._record_budget_event, engine._requeue

    def record(kind, **fields):
        pending = engine._pending_prefill
        if kind == "prefill_pause" and pending.later and not parked:
            parked.append([seq.request.request_id for seq, _ in pending.held])
            engine._stopped.set()  # the loop ends before it resumes the admission
        return real(kind, **fields)

    def spy(seq):
        requeued.append((seq.request.request_id, len(seq.block_ids)))
        return requeue(seq)

    monkeypatch.setattr(engine, "_record_budget_event", record)
    monkeypatch.setattr(engine, "_requeue", spy)
    try:
        first = asyncio.ensure_future(collect(engine.generate(_req(0, lens[0], 40), Context())))
        while engine.generated_tokens < 2:  # a decoding row: the budget binds
            await asyncio.sleep(0.002)
        outs = await asyncio.gather(*(
            collect(engine.generate(_req(i, n), Context()))
            for i, n in enumerate(lens) if i))
        await first
        assert parked and len(parked[0]) >= 2, parked
        # back at the queue's front in arrival order: requeued last to first
        assert [rid for rid, _ in requeued] == parked[0][::-1]
        assert all(blocks == 0 for _, blocks in requeued)
        for out, i in zip(outs, range(1, len(lens))):
            if f"r{i}" in parked[0]:
                assert out[-1].finish_reason == FinishReason.CANCELLED and not _tokens(out)
        assert engine._pending_prefill is None
        assert engine.pool.active_blocks == 0
    finally:
        await engine.stop()
