"""Batched token sampling under jit.

Greedy / temperature / top-k / top-p / min-p with per-sequence parameters so
one compiled decode step serves a continuous batch of heterogeneous requests
(the reference delegates this to vLLM's sampler; here it is part of the
engine's fused decode step).

``sample_tokens`` has two branches, chosen on the device by a
``jax.lax.cond`` from what the call can observe (``any_row_samples``):

- no LIVE row has ``temperature > 0``: the arg-max of the logits, one
  reduction over ``[B, V]`` and nothing else. Ties go to the lowest index, as
  ``lax.top_k`` (the CPU path) and the plain references have always had it.
- otherwise ``sample_candidates``: the ``SAMPLE_WIDTH`` largest logits of a
  row (on the TPU ``approx_max_k`` with ``recall_target=0.99``, whose result
  is unsorted, so an ``argsort`` of the candidates follows; its first
  candidate is exact: the largest logit is the largest of its own bucket),
  top-k / top-p / min-p inside them (top-p truncates at SAMPLE_WIDTH
  candidates, the standard accelerator-side approximation; a request's
  top_k > SAMPLE_WIDTH is clamped), per-row Gumbel noise, and the first
  candidate for the rows at temperature 0.

Both name the same token for a greedy row except where two logits tie for the
largest: there the approximate top-k may name either, the arg-max the lowest.
A dead slot keeps its last request's temperature (a fresh one holds 1.0), so
the predicate reads live rows only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
SAMPLE_WIDTH = 64  # candidates considered by top-k/top-p filtering


def fold_row_keys(
    rng: jax.Array,  # single base PRNG key
    salts: jnp.ndarray,  # [B] int — per-sequence salt (admission order)
    positions: jnp.ndarray,  # [B] int — index of the token being sampled
) -> jax.Array:
    """Per-row sampling keys: fold (sequence salt, token index) into the
    engine's base key. This makes the sampling noise for a given token a
    pure function of (engine seed, sequence, position) — independent of
    dispatch count or batch composition — which is what lets the pipelined
    decode path (engines/tpu/engine.py) speculatively dispatch burst N+1
    before burst N's stop conditions are known, and lets preemption-by-
    recompute regenerate an identical continuation."""
    def one(s, p):
        return jax.random.fold_in(jax.random.fold_in(rng, s), p)

    return jax.vmap(one)(
        salts.astype(jnp.uint32), positions.astype(jnp.uint32)
    )


def any_row_samples(
    temperature: jnp.ndarray,  # [B] float
    live: jnp.ndarray = None,  # [B] bool; None: every row is live
) -> jnp.ndarray:
    """Scalar bool: some live row has ``temperature > 0``. False sends
    ``sample_tokens`` down its arg-max branch."""
    samples = temperature > 0.0
    return jnp.any(samples if live is None else samples & live)


def sample_tokens(
    logits: jnp.ndarray,  # [B, V] float
    rng: jax.Array,  # single PRNG key (ignored when row_keys given)
    temperature: jnp.ndarray,  # [B] float; <=0 means greedy
    top_k: jnp.ndarray,  # [B] int; <=0 means off
    top_p: jnp.ndarray,  # [B] float; >=1 means off
    min_p: jnp.ndarray = None,  # [B] float; <=0/None means off
    row_keys: jax.Array = None,  # [B] per-row keys (fold_row_keys)
    live: jnp.ndarray = None,  # [B] bool: the rows whose token is used
    any_sampled: jnp.ndarray = None,  # scalar bool, for a caller that has it
    salts: jnp.ndarray = None,  # [B] int: with positions, row_keys folded here
    positions: jnp.ndarray = None,  # [B] int
) -> jnp.ndarray:
    """Returns sampled token ids [B]. Fully vectorized, static shapes.

    When no live row samples (``any_row_samples(temperature, live)``; a
    caller whose temperatures hold over many calls passes the scalar as
    ``any_sampled``) the result is ``argmax(logits)``, ties to the lowest
    index, and the candidate search, the sort and the noise do not run.
    Otherwise every row goes through ``sample_candidates``: a row that
    samples, alone or beside greedy rows, gets the token it always got for
    the same key. ``live=None`` keeps every row live.

    With ``row_keys``, each row draws its gumbel noise from its own key so
    the sample depends only on that row's (key, logits, params) — batch
    layout and the other rows' state cannot perturb it. ``salts`` and
    ``positions`` give the same keys (``fold_row_keys(rng, salts,
    positions)``), folded inside the sampling branch."""
    if any_sampled is None:
        any_sampled = any_row_samples(temperature, live)

    def full():
        keys = row_keys if salts is None else fold_row_keys(rng, salts, positions)
        return sample_candidates(
            logits, rng, temperature, top_k, top_p, min_p, keys)

    def greedy():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return jax.lax.cond(any_sampled, full, greedy)


def sample_candidates(
    logits: jnp.ndarray,  # [B, V] float
    rng: jax.Array,  # single PRNG key (ignored when row_keys given)
    temperature: jnp.ndarray,  # [B] float; <=0 means greedy
    top_k: jnp.ndarray,  # [B] int; <=0 means off
    top_p: jnp.ndarray,  # [B] float; >=1 means off
    min_p: jnp.ndarray = None,  # [B] float; <=0/None means off
    row_keys: jax.Array = None,  # [B] per-row keys (fold_row_keys)
) -> jnp.ndarray:
    """``sample_tokens``' sampling branch: filtering and noise inside the
    top ``SAMPLE_WIDTH`` logits of every row."""
    B, V = logits.shape
    W = min(SAMPLE_WIDTH, V)

    # Top-k FIRST, on the raw (bf16) logits: per-row division by a positive
    # temperature preserves order, so the candidate set is identical — and
    # skipping the full-vocab f32 materialization saves two [B, V] HBM
    # passes per step (the sampler was ~35% of decode-step time at B=256).
    if jax.default_backend() == "tpu":
        # approx_max_k maps onto the TPU's segmented-reduce hardware path;
        # exact top_k lowers to a full sort network (measurably slower at
        # 150k vocab). recall_target keeps it effectively exact for the
        # head of the distribution that sampling actually uses.
        raw_top, top_idx = jax.lax.approx_max_k(logits, W, recall_target=0.99)
        order = jnp.argsort(-raw_top, axis=-1)  # approx op is unsorted
        raw_top = jnp.take_along_axis(raw_top, order, axis=-1)
        top_idx = jnp.take_along_axis(top_idx, order, axis=-1)
    else:
        raw_top, top_idx = jax.lax.top_k(logits, W)  # [B, W] descending

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    top_logits = raw_top.astype(jnp.float32) / temp  # [B, W] — cheap in W

    ranks = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
    k = jnp.where(top_k > 0, jnp.minimum(top_k, W), W)[:, None]
    keep_k = ranks < k

    probs = jax.nn.softmax(top_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep tokens while the cumulative mass *before* them is < top_p
    # (always keeps the first token).
    keep_p = (cum - probs) < jnp.clip(top_p, 0.0, 1.0)[:, None]

    keep = keep_k & keep_p
    if min_p is not None:
        # min-p: drop candidates with prob < min_p × max-prob. probs is
        # descending, so column 0 is the max. Neutral at min_p <= 0.
        keep_mp = probs >= jnp.clip(min_p, 0.0, 1.0)[:, None] * probs[:, :1]
        keep = keep & keep_mp
    masked = jnp.where(keep, top_logits, NEG_INF)
    if row_keys is not None:
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (W,), dtype=jnp.float32)
        )(row_keys)
    else:
        gumbel = jax.random.gumbel(rng, (B, W), dtype=jnp.float32)
    choice_rank = jnp.argmax(masked + gumbel, axis=-1)  # [B]
    sampled = jnp.take_along_axis(top_idx, choice_rank[:, None], axis=-1)[:, 0]

    greedy = top_idx[:, 0]  # top-1 of the scaled logits == argmax of logits
    return jnp.where(temperature <= 0.0, greedy, sampled)


def compute_logprobs(
    logits: jnp.ndarray,  # [B, V]
    token_ids: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """Log-probability of the chosen tokens (for logprobs=N support)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, token_ids[:, None], axis=-1)[:, 0]


def top_logprobs(
    logits: jnp.ndarray,  # [B, V]
    n: int,
) -> tuple:
    """Top-n (logprob, token_id) per row for OpenAI top_logprobs support.
    Returns ([B, n] float32 logprobs, [B, n] int32 ids), descending."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    vals, ids = jax.lax.top_k(logp, n)
    return vals, ids.astype(jnp.int32)


def spec_verify_sample(
    logits: jnp.ndarray,  # [B, C, V] — position i decides token i+1
    proposals: jnp.ndarray,  # [B, C-1] int32 draft tokens (one-hot draft q)
    prop_len: jnp.ndarray,  # [B] int32 — valid proposal count per row
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B]; <=0 greedy
    top_k: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
):
    """Speculative verify with REJECTION SAMPLING (Leviathan/Chen): exact
    target-distribution sampling for sampled requests, greedy verify as the
    temperature<=0 special case — one program serves mixed ticks.

    The prompt-lookup draft is deterministic (one-hot q), so acceptance of
    proposal x at a position with filtered target distribution p is
    u < p(x), and a rejection replaces it with a sample from p with x
    zeroed and renormalized — exactly max(p − q, 0) normalized. Filtering
    (temperature/top-k/top-p inside the top-W candidates) matches
    sample_tokens, so spec and non-spec paths draw from the same target.

    Returns (emitted [B, C] int32, counts [B] int32): row b's first
    counts[b] entries are the accepted prefix plus the final corrected (or
    bonus) token.
    """
    B, C, V = logits.shape
    W = min(SAMPLE_WIDTH, V)
    N = B * C
    flat = logits.reshape(N, V)

    if jax.default_backend() == "tpu":
        raw_top, top_idx = jax.lax.approx_max_k(flat, W, recall_target=0.99)
        order = jnp.argsort(-raw_top, axis=-1)
        raw_top = jnp.take_along_axis(raw_top, order, axis=-1)
        top_idx = jnp.take_along_axis(top_idx, order, axis=-1)
    else:
        raw_top, top_idx = jax.lax.top_k(flat, W)

    rep = lambda a: jnp.repeat(a, C, axis=0)  # noqa: E731 — [B] → [N]
    temp = jnp.maximum(rep(temperature), 1e-6)[:, None]
    top_logits = raw_top.astype(jnp.float32) / temp

    ranks = jax.lax.broadcasted_iota(jnp.int32, (N, W), 1)
    k = jnp.where(rep(top_k) > 0, jnp.minimum(rep(top_k), W), W)[:, None]
    keep_k = ranks < k
    probs = jax.nn.softmax(top_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (cum - probs) < jnp.clip(rep(top_p), 0.0, 1.0)[:, None]
    keep = keep_k & keep_p
    masked = jnp.where(keep, top_logits, NEG_INF)

    # draft token per position: proposals shifted onto logit positions
    prop_pos = jnp.concatenate(
        [proposals, jnp.zeros((B, 1), jnp.int32)], axis=1
    ).reshape(N)  # position i's draft (garbage past prop_len, masked later)
    match = top_idx == prop_pos[:, None]  # [N, W]
    pr = jax.nn.softmax(masked, axis=-1)  # renormalized filtered target
    p_prop = jnp.sum(jnp.where(match & keep, pr, 0.0), axis=-1)  # [N]

    rng_u, rng_g = jax.random.split(rng)
    u = jax.random.uniform(rng_u, (N,), dtype=jnp.float32)
    gumbel = jax.random.gumbel(rng_g, (N, W), dtype=jnp.float32)

    greedy = rep(temperature) <= 0.0
    argmax_tok = top_idx[:, 0]
    accept = jnp.where(greedy, prop_pos == argmax_tok, u < p_prop)

    # plain sample (bonus position) + rejection sample (proposal excluded)
    choice = jnp.argmax(masked + gumbel, axis=-1)
    sample = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
    masked_excl = jnp.where(match, NEG_INF, masked)
    choice_r = jnp.argmax(masked_excl + gumbel, axis=-1)
    resample = jnp.take_along_axis(top_idx, choice_r[:, None], axis=-1)[:, 0]
    sample = jnp.where(greedy, argmax_tok, sample)
    resample = jnp.where(greedy, argmax_tok, resample)

    accept = accept.reshape(B, C)
    sample = sample.reshape(B, C)
    resample = resample.reshape(B, C)

    pl_ = jnp.maximum(prop_len, 0)[:, None]  # [B, 1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, C), 1)
    acc_run = jnp.cumprod(
        jnp.where(pos < pl_, accept, False).astype(jnp.int32), axis=1
    )
    n_acc = jnp.sum(acc_run, axis=1)  # [B] accepted proposal count

    gather1 = lambda a, i: jnp.take_along_axis(  # noqa: E731
        a, i[:, None], axis=1
    )[:, 0]
    rejected = n_acc < pl_[:, 0]
    final = jnp.where(
        rejected, gather1(resample, n_acc), gather1(sample, n_acc)
    )

    props_padded = jnp.concatenate(
        [proposals, jnp.zeros((B, 1), jnp.int32)], axis=1
    )
    emitted = jnp.where(
        pos < n_acc[:, None],
        props_padded,
        jnp.where(pos == n_acc[:, None], final[:, None], 0),
    )
    counts = n_acc + 1
    return emitted, counts
