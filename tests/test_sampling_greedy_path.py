"""The sampler's two branches (ops/sampling.sample_tokens): a call none of
whose LIVE rows has temperature > 0 returns the arg-max of its logits and
runs nothing else; any other call runs what the sampler always ran, and a
row that samples gets the token it always got for the same key.
"""

import asyncio

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import tiny_config
from dynamo_tpu.ops import sampling
from dynamo_tpu.ops.sampling import NEG_INF, SAMPLE_WIDTH, fold_row_keys, sample_tokens


def parent_sample_tokens(logits, rng, temperature, top_k, top_p, min_p=None,
                         row_keys=None):
    """``sample_tokens`` as it stood before the arg-max branch (PR 47's tree,
    its CPU path: ``lax.top_k``), kept here as the oracle."""
    B, V = logits.shape
    W = min(SAMPLE_WIDTH, V)
    raw_top, top_idx = jax.lax.top_k(logits, W)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    top_logits = raw_top.astype(jnp.float32) / temp
    ranks = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
    k = jnp.where(top_k > 0, jnp.minimum(top_k, W), W)[:, None]
    keep_k = ranks < k
    probs = jax.nn.softmax(top_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (cum - probs) < jnp.clip(top_p, 0.0, 1.0)[:, None]
    keep = keep_k & keep_p
    if min_p is not None:
        keep_mp = probs >= jnp.clip(min_p, 0.0, 1.0)[:, None] * probs[:, :1]
        keep = keep & keep_mp
    masked = jnp.where(keep, top_logits, NEG_INF)
    if row_keys is not None:
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (W,), dtype=jnp.float32)
        )(row_keys)
    else:
        gumbel = jax.random.gumbel(rng, (B, W), dtype=jnp.float32)
    choice_rank = jnp.argmax(masked + gumbel, axis=-1)
    sampled = jnp.take_along_axis(top_idx, choice_rank[:, None], axis=-1)[:, 0]
    greedy = top_idx[:, 0]
    return jnp.where(temperature <= 0.0, greedy, sampled)


B, V = 6, 1000
KEY = jax.random.PRNGKey(11)


def _logits(dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, V), jnp.float32).astype(dtype)


def _params(temperature):
    return (jnp.asarray(temperature, jnp.float32), jnp.full((B,), 20, jnp.int32),
            jnp.full((B,), 0.9, jnp.float32))


# -- (a) every row greedy: the arg-max, ties to the lowest index ------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("masked", [False, True], ids=["every_row_live", "live_mask"])
def test_all_greedy_batch_is_the_argmax_with_ties_to_the_lowest_index(dtype, masked, monkeypatch):
    monkeypatch.setattr(  # the candidates' branch is traced, and must not run
        sampling, "sample_candidates", lambda logits, *a: jnp.full(logits.shape[:1], -1))
    logits = _logits(dtype)
    top = logits.max(axis=-1)
    # three logits tie for the largest in rows 1 and 4, far apart in the row
    for row, cols in ((1, (700, 33, 912)), (4, (999, 998, 5))):
        logits = logits.at[row, jnp.asarray(cols)].set(top[row] + 1)
    temperature = np.zeros(B, np.float32)
    live = None
    if masked:  # dead slots hold a stale temperature: start-up's 1.0
        live = jnp.asarray([True, True, False, True, True, False])
        temperature[[2, 5]] = 1.0
    toks = sample_tokens(logits, KEY, *_params(temperature), live=live)
    want = np.asarray(logits.astype(jnp.float32)).argmax(-1)
    assert want[1] == 33 and want[4] == 5
    np.testing.assert_array_equal(np.asarray(toks), want)
    assert toks.dtype == jnp.int32


# -- (b) a call with a sampling row: the parent's tokens, bit for bit -------


@pytest.mark.parametrize("mix", ["mixed", "all_sampled", "one_sampled"])
@pytest.mark.parametrize("keys", ["row_keys", "salts_positions", "one_key"])
@pytest.mark.parametrize("with_min_p", [False, True], ids=["no_min_p", "min_p"])
def test_a_sampling_call_gives_the_parents_tokens(mix, keys, with_min_p):
    temperature = {
        "mixed": [0.0, 0.7, 0.0, 1.3, 1.0, 0.0],
        "all_sampled": [0.5, 0.7, 2.0, 1.3, 1.0, 0.9],
        "one_sampled": [0.0, 0.0, 0.0, 0.0, 0.8, 0.0],
    }[mix]
    logits = _logits(jnp.bfloat16, seed=3)
    temp, top_k, top_p = _params(temperature)
    top_k = top_k.at[0].set(0).at[3].set(500)  # off, and clamped to the width
    min_p = jnp.full((B,), 0.05, jnp.float32) if with_min_p else None
    salts = jnp.arange(B, dtype=jnp.int32) * 7 + 1
    positions = jnp.arange(B, dtype=jnp.int32) + 40
    row_keys = fold_row_keys(KEY, salts, positions)
    if keys == "one_key":
        want = parent_sample_tokens(logits, KEY, temp, top_k, top_p, min_p)
        got = sample_tokens(logits, KEY, temp, top_k, top_p, min_p)
    else:
        want = parent_sample_tokens(logits, None, temp, top_k, top_p, min_p, row_keys)
        given = ({"row_keys": row_keys} if keys == "row_keys"
                 else {"salts": salts, "positions": positions})
        got = jax.jit(lambda lg: sample_tokens(
            lg, KEY, temp, top_k, top_p, min_p, live=jnp.ones((B,), bool), **given))(logits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # (not a vacuous comparison: some row left its arg-max)
    assert (np.asarray(want) != np.asarray(logits.astype(jnp.float32)).argmax(-1)).any()


# -- (c) the predicate reads live rows only ---------------------------------


def _flat_row_batch():
    """Row 2 has flat logits (one a hair above the rest: the arg-max is
    index 17) at temperature 1.0, and a key whose sampled token is another;
    the other rows are greedy."""
    logits = _logits(jnp.float32, seed=5)
    logits = logits.at[2].set(0.0).at[2, 17].set(1e-3)
    temperature = np.zeros(B, np.float32)
    temperature[2] = 1.0
    temp, _, _ = _params(temperature)
    off = (jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32))
    row_keys = fold_row_keys(KEY, jnp.arange(B), jnp.arange(B) + 1)
    sampled = int(parent_sample_tokens(logits, None, temp, *off, None, row_keys)[2])
    assert sampled != 17
    return logits, temp, off, row_keys, sampled


@pytest.mark.parametrize("row_is_live", [False, True], ids=["dead", "live"])
def test_a_dead_rows_stale_temperature_does_not_force_the_full_branch(row_is_live):
    logits, temp, off, row_keys, sampled = _flat_row_batch()
    live = jnp.ones((B,), bool).at[2].set(row_is_live)
    toks = np.asarray(sample_tokens(logits, None, temp, *off, row_keys=row_keys, live=live))
    # masked out, the row's token is its arg-max: the arg-max branch ran;
    # masked in, it is the one its key draws
    assert toks[2] == (sampled if row_is_live else 17)
    others = [0, 1, 3, 4, 5]
    np.testing.assert_array_equal(toks[others], np.asarray(logits).argmax(-1)[others])


def test_live_none_keeps_every_row_live():
    logits, temp, off, row_keys, sampled = _flat_row_batch()
    toks = sample_tokens(logits, None, temp, *off, row_keys=row_keys)
    assert int(toks[2]) == sampled
    assert bool(sampling.any_row_samples(temp))
    assert not bool(sampling.any_row_samples(temp, jnp.ones((B,), bool).at[2].set(False)))


# -- (d) a burst through decode_multi ---------------------------------------


def _burst(cfg, params, temperature, active, steps=4):
    S = len(active)
    bs, nb = 16, 16
    k, v = llama.init_kv_cache(cfg, nb, bs, layered=True)
    tables = jnp.asarray(np.arange(S * 2).reshape(S, 2) % nb, jnp.int32)
    out = jax.jit(lambda temp: llama.decode_multi(
        params, cfg, jnp.arange(S, dtype=jnp.int32) + 5,
        jnp.full((S,), 3, jnp.int32), jnp.asarray(active, jnp.int32), tables, k, v,
        jax.random.PRNGKey(1), temp, jnp.zeros((S,), jnp.int32),
        jnp.ones((S,), jnp.float32), num_steps=steps, want_logprobs=False,
        salts=jnp.arange(S, dtype=jnp.int32) + 100,
    ))(jnp.asarray(temperature, jnp.float32))
    return np.asarray(out[0])


def _parent_sampler(logits, rng, temperature, top_k, top_p, min_p=None, row_keys=None,
                    live=None, any_sampled=None, salts=None, positions=None):
    if salts is not None:
        row_keys = fold_row_keys(rng, salts, positions)
    return parent_sample_tokens(logits, rng, temperature, top_k, top_p, min_p, row_keys)


@pytest.mark.parametrize("burst", ["greedy_rows_stale_dead_slots", "a_live_row_samples"])
def test_decode_multi_returns_the_parents_tokens(burst, monkeypatch):
    cfg = tiny_config()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    active = [1, 0, 1, 1, 0, 0]
    temperature = [0.0, 1.0, 0.0, 0.0, 0.7, 1.0]  # the dead slots' are stale
    if burst == "a_live_row_samples":
        temperature[2] = 0.9
    with monkeypatch.context() as m:
        m.setattr(sampling, "sample_tokens", _parent_sampler)
        want = _burst(cfg, params, temperature, active)
    if burst == "greedy_rows_stale_dead_slots":
        # the steps take the arg-max branch: the candidates' never runs
        monkeypatch.setattr(
            sampling, "sample_candidates", lambda logits, *a: jnp.full(logits.shape[:1], -1))
    got = _burst(cfg, params, temperature, active)
    np.testing.assert_array_equal(got, want)
    live = np.asarray(active, bool)
    assert (got[live] >= 0).all()
    np.testing.assert_array_equal(got[~live], np.broadcast_to(
        (np.arange(6) + 5)[~live, None], got[~live].shape))  # a dead row keeps its token


def test_decode_multi_decides_once_outside_the_scan():
    """One ``cond`` in the scan's body, its predicate an operand computed
    before the loop (the temperatures and ``active`` hold over a burst)."""
    cfg = tiny_config()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    S = 4
    k, v = llama.init_kv_cache(cfg, 8, 16, layered=True)
    jaxpr = jax.make_jaxpr(lambda temp, active: llama.decode_multi(
        params, cfg, jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32), active,
        jnp.zeros((S, 2), jnp.int32), k, v, jax.random.PRNGKey(1), temp,
        jnp.zeros((S,), jnp.int32), jnp.ones((S,), jnp.float32), num_steps=4,
        want_logprobs=False, salts=jnp.arange(S, dtype=jnp.int32),
    ))(jnp.zeros((S,), jnp.float32), jnp.ones((S,), jnp.int32))
    top = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert "reduce_or" in top and "cond" not in top
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    body = [e.primitive.name for e in scan.params["jaxpr"].jaxpr.eqns]
    assert body.count("cond") == 1 and "reduce_or" not in body


# -- the counter ------------------------------------------------------------

FAMILY = "dynamo_tpu_engine_sampler_decode_steps_total"


def _steps(text, path):
    return int(float(text.split(f'{FAMILY}{{path="{path}"}} ')[1].split()[0]))


def test_observe_sampler_steps_adds_to_one_series():
    from dynamo_tpu.engines.metrics import EngineStepMetrics
    from dynamo_tpu.runtime import metric_names as mn

    assert mn.ENGINE_SAMPLER_DECODE_STEPS_TOTAL == FAMILY and FAMILY in mn.ALL_ENGINE
    m = EngineStepMetrics()
    assert (_steps(m.render(), "greedy"), _steps(m.render(), "full")) == (0, 0)
    m.observe_sampler_steps(8, False)
    m.observe_sampler_steps(8, False)
    m.observe_sampler_steps(8, True)
    assert (_steps(m.render(), "greedy"), _steps(m.render(), "full")) == (16, 8)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_engine_counts_a_dispatched_bursts_steps_under_its_path(temperature):
    """A burst of greedy rows adds ``--decode-steps`` to ``path="greedy"``
    and nothing to ``full``, whatever the free slots' temperatures (1.0
    since start-up); a burst with a sampling row the other way round."""
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.engine import collect

    steps = 4

    async def run():
        engine = JaxEngine(JaxEngineArgs(
            config=tiny_config(), block_size=16, num_kv_blocks=64, max_num_seqs=4,
            max_model_len=512, prefill_chunk=64, decode_steps=steps,
        ))
        try:
            at_start = engine.step_metrics.render()
            out = await collect(engine.generate(PreprocessedRequest(
                token_ids=list(range(3, 23)), request_id="a",
                sampling=SamplingOptions(temperature=temperature),
                stop=StopConditions(max_tokens=6, ignore_eos=True),
            ), Context()))
            return at_start, out, engine.step_metrics.render()
        finally:
            await engine.stop()

    at_start, out, text = asyncio.run(run())
    assert (_steps(at_start, "greedy"), _steps(at_start, "full")) == (0, 0)
    took, other = ("full", "greedy") if temperature > 0 else ("greedy", "full")
    assert _steps(text, took) > 0 and _steps(text, took) % steps == 0
    assert _steps(text, other) == 0
    assert sum(len(o.token_ids) for o in out) == 6
