#!/usr/bin/env python3
"""Plain reference for ``minicpm-sala-pp4`` and the comparison that decides the
cell's ``correct``.

    python3 benchmark/references/minicpm-sala-pp4.py --config <file>

``run.py`` runs this as a child after the workers have gone (the chip is free
again), with the run's environment; a non-zero exit makes ``correct`` false.

**Where it runs.** A CPU rehearsal is the harness's to ask for
(``JAX_PLATFORMS=cpu`` in the environment, which ``run.py --rehearse-cpu``
sets): it compares the ``rehearse_cpu`` stand-in on contexts of 640 and 48
tokens. In every other case the first device must be the configuration's
``serving.platform`` and ``serving.device_kind``, or the child exits 2 and
compares nothing.

**What it drives.** A ``JaxEngine`` built from the cell's own worker flags, on
the worker's own seed-0 weights: admission, the block pool with the indexer's
rows under its ids, the state slots and the snapshot store, and the runner's
compiled programs (from a warm cache the worker's own executables;
``pipeline_depth`` 1 is the one departure: the same programs, less host
overlap). Ids and lengths come from the harness's ``--seed`` (read from its
command line; ``--seed`` here overrides), under the cell's traffic law
(``benchmark/traffic/long_ctx_mixed.json``): one context of each class, turns
by the turn law, greedy, all with ``logprobs``, ``N_OUT`` = 137 tokens each
(the first from the prefill, 17 bursts of 8 after it: compressed keys complete
and the local blocks move while decoding). The contexts are built one after
another, each ALONE, as the generator's warm-up asks them; the asks then
arrive TOGETHER, as the window's do:

* ``build_long``: the longest class's context (65,536 tokens): 256 chunks, the
  first dense over its own registers, the next 31 densely over the cache
  (under ``dense_len``), the rest over the blocks each query's indexer selects;
  16 snapshot boundaries; ``build_short``: the shortest class's (4,096: the
  dense path throughout);
* then AT ONCE (``asyncio.gather``) ``hit_long`` (the long context + a fresh
  turn: a prefix hit through K/V pages, indexer rows and the snapshot at
  65,536) and ``hit_short``: one prefill batch over tables of both widths, a
  decode burst with a 65 k row on the sparse path and a 4 k row on the dense
  one.

**What it compares with.** The float32 reference below. A context is computed
ONCE and continued (the same function on the suffix, given the prefix's
float32 keys, values and states: ``carry``), a continuation padded on the right
to ``PAD`` tokens (causal: no compared position sees the padding; the
lightning states are taken at the sequence's own length). What it holds when:
the engine's weights (bf16, 5.64 GB) stay for the whole run and are the
reference's source; of the engine's pools the rows C, D and E need are copied
out (the long context's K, V and compressed keys of the first sparse layer,
~0.14 GB) and pools, snapshot store and slots DROPPED before the first full
forward; the reference then holds the hidden state [T, 4096] float32 (1.07 GB
at 65,536), one sublayer's q, k, v and output (~4 GB in a lightning layer), or
scores of 16 queries a K/V head x 256 positions x T keys (1.07 GB).

The limits, each with its reason and its two readings (the builder's chip runs
of PR 46, PERF.md section 6):

A. ``logprob``: |served - reference| log-probability of the chosen token, per
   step, every row. The reference selects its own blocks: under random weights
   the scores over compressed keys are nearly flat (a relative spread of ~5%
   over ~1,000 blocks), so bfloat16 and float32 rank the marginal block
   differently at some queries (C counts them), and such a query's output
   moves by about what one block of 64 weighs: statistics are therefore
   medians, per row and over all steps. Judged: the median over all steps (the
   precision of the whole path: a bfloat16 state, a dropped muP factor, a
   per-head gate move every step) and EVERY row's median (a wrong page table, a
   stale snapshot, a missing forced block or a wrong position garbles that row
   and nobody else's).
B. ``kv rows``: relative L2 error, per token, of the first sparse layer's K
   rows (q/k-normed, not rotated) and V rows as they lie in the pool after
   ``build_long``, over the context's first and last 2,048 tokens, and of its
   compressed keys over the whole context, against the reference's (that
   layer's input is the embedding: nothing upstream reaches it).
C. ``selection`` (the rule of ISSUE 46, section 2 (a)): the program's
   ``select_blocks`` in the pool's dtype on the POOL'S OWN compressed keys, for
   the context's last ``--prefill-chunk`` positions, against the REFERENCE's
   block scores: every forced block is present, the count is ``topk``, and
   every other selected block's reference score is no lower than the
   reference's k-th best among the blocks that are not forced, less
   ``LIMIT_SCORE_SHORTFALL`` of it.
D. ``attention``: the program's sparse attention (on the chip the live-span
   decode kernel over each (query, K/V head)'s selected pages) over THE POOL'S
   OWN ROWS, copied out page by page, GIVEN THE PROGRAM'S selection (C's), for
   ``D_QUERIES`` decode rows at the context's last position and as ONE CHUNK of
   ``--prefill-chunk`` queries (the call a turn's prefill makes), against the
   reference's attention over the same rows and the same selection: relative
   L2 per (query, head).
E. ``state``: the FIRST lightning layer's state as it lies in ``hit_short``'s
   slot after its last burst (through the snapshot at 4,096, a turn's chunk
   and 17 bursts), against the reference's after the same tokens: relative
   L2 per head, the largest judged (the slow heads keep hundreds of tokens: a
   state rounded to bfloat16 after every token drifts most there).
   ``hit_long``'s is printed, not judged: at position 65,700 the rotary
   angle of the fastest lanes is a float32 product of ~65,700 rad, whose last
   bit is 0.008 rad, and the program's frequencies (folded by the compiler)
   and the reference's (computed on the device) differ in their last bit,
   which is 0.01-0.07 rad there: the two states lie in frames rotated against
   each other by that (2.1e-2 relative on every head of every layer, my chip
   runs, PR 46), while attention within either frame depends on DIFFERENCES
   of positions and agrees (A reads 2e-4 on that row).

``--readings`` (the builder's) also prints what A and E read when the
reference is degraded (``state_bf16``, ``no_residual_scale``,
``gate_per_head``) and what D reads under a bfloat16 softmax: each fault must
fail at least one limit (a missing forced block fails C by count).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.realpath(__file__))))
HERE = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
sys.path.insert(0, ROOT)

# Each limit between its two readings: what the program read over the builder's
# seeds, and what a fault or the nearest lower precision reads (PERF.md section
# 6 has the runs; "program" = the worker's flags as served, bfloat16 pools,
# float32 state).
LIMIT_LOGPROB = 0.0004  # A, median over all steps: program 0.00020-0.00021 (548 steps a seed; the logits are small, the final hidden state is divided by 16); a gate per head 0.00063-0.00071, no residual scale 0.0050-0.0059; a bfloat16 state (0.00019-0.00023), a bfloat16 softmax and a missing forced block do NOT move A: E, D and C part those
LIMIT_LOGPROB_ROW = 0.00045  # A, every row's median (137 steps): program's worst row 0.00022-0.00025 (three seeds); a gate per head 0.00063 and more on every row
LIMIT_KV = 5.5e-3  # B, median over the sampled tokens, K, V and compressed keys each: program 2.77e-3-2.78e-3 (K: the normed input, the projection, the head norm), 2.32e-3 (V), 3.25e-3 (compressed keys: the mean of 32 bfloat16 rows, rounded again); 8-bit rows read 6.4e-3-8.0e-3 on the same statistic (PR 44's readings, benchmark/references/laguna-xs.2-pp8.py)
LIMIT_KV_ROW = 2.5e-2  # B, every sampled token: a guard against a misplaced row (reads about 1), not a precision limit: program's largest 3.3e-3-4.2e-3
LIMIT_SCORE_SHORTFALL = 5e-3  # C, relative to the k-th best reference score: program 6.3e-4-6.6e-4 (bfloat16 scores over float32 ones, three seeds; 78-102 of 512 sets then differ from the reference's own by a marginal block); a selection blind to the scores could read 0.20
LIMIT_ATTENTION = 3.0e-3  # D, median over (query, head), each call: program 2.12e-3 (chunk and decode rows, two seeds); the program's roundings with a bfloat16 softmax on top 4.2e-3
LIMIT_ATTENTION_ROW = 6.0e-3  # D, every (query, head): program's worst of 8,192 2.78e-3-2.84e-3; with a bfloat16 softmax 1.2e-2
LIMIT_STATE = 1.0e-2  # E, every head of the first lightning layer, hit_short's slot (the largest of 32): program 4.9e-3 (median 4.7e-3: the bfloat16 keys and values of ~4,380 tokens and the rotary frame at that position); a state rounded to bfloat16 after every token 2.0e-2 (median 6.7e-3: the slow heads drift most), no residual scale 5.5e-2; a gate per head does not move the first layer's state (4.9e-3): A parts that one
N_OUT = 137
PAD = 512  # continuations are padded to this many tokens: one compiled length
D_QUERIES = 8
B_SAMPLE = 2048
BLOCKING = dict(query_block=256, token_block=4096)

# --- reference: begin ---------------------------------------------------------
# The forward pass of a cut MiniCPM-SALA model in straightforward jax.numpy:
# float32, matmuls at "highest" precision, ONE sequence at a time, no paged
# cache, no chunks of the program's, no kernels, every mask built from
# positions, the lightning recurrence token by token (a ``lax.scan`` over the
# tokens), the block selection by a full sort. Attention runs in blocks of
# query positions and one K/V head at a time (``query_block``: the result does
# not depend on it), so the published widths fit beside the program under
# test.
#
# Layer equations (x = rmsnorm(h) of the sublayer's input, r = scale_depth /
# sqrt(mup_denominator), the PUBLISHED depth's):
#   h <- h + r * mixer(x); h <- h + r * W_down(silu(W_gate x) * W_up x);
#   h_0 = scale_emb * embed[token]; logits = W_head(rmsnorm(h) / (d / d_base)).
#   lightning: q, k, v = W x as [H, D]; q, k <- rmsnorm_head; rotary (theta,
#     all D lanes, lane i paired with lane i + D/2) on q and k;
#     S_t = lambda_h S_{t-1} + k_t^T v_t (float32), o_t = q_t S_t / sqrt(D);
#     o <- rmsnorm_head(o); y = W_o(o * sigmoid(W_g x)), W_g [d, H D].
#   sparse (minicpm4): q [H, D], k, v [KH, D]; q, k <- rmsnorm_head; no rotary.
#     Compressed key j of K/V head g: mean(k[stride j : stride j + kernel]).
#     A query at position t with t + 1 >= dense_len: p_{h,j} = softmax_j(q_h .
#     Kc_j / sqrt(D)) over the j whose window ends at or before t; a_j = sum
#     of p over the heads of the group; block score b_m = max of a_j over the
#     j whose window meets block m; selected = the first ``init_blocks``
#     blocks, the blocks that hold tokens (t - window, t], and the highest
#     b_m among the rest until ``topk`` blocks are selected (all blocks when
#     the sequence has ``topk`` or fewer); o_h = softmax over the selected
#     blocks' tokens <= t. With t + 1 < dense_len: plain causal attention.
#     y = W_o(o * sigmoid(W_g x)), W_g [d, H D].
#
# Assumed, where the published config names a switch and not its shape (the
# configuration's file lists the same under ``assumed``):
#   1. lambda_h = exp(-2^(-8 (h + 1) / H)), the ALiBi slopes lightning
#      attention is published with, the same in every layer;
#   2. both output gates are per lane (W_g [d, H D]; the full model then
#      counts 9.47 B parameters, per head 8.93 B; the card says "9B");
#   3. the norms' placement: q/k norms before the rotation, the output norm
#      before the gate;
#   4. the sparse sizes (kernel 32, stride 16, block 64, topk 64, init 1,
#      window 2048, dense_len 8192) are the MiniCPM4 family's published
#      ``sparse_config``; the block score is the MAX over the windows that
#      meet the block.
# Departures from the published implementation: (i) the score's softmax is
# exact over the compressed keys (the published CUDA kernels approximate its
# normaliser from coarser keys); (ii) dense or sparse is decided per QUERY, by
# the length of the sequence up to and including it (t + 1 >= dense_len), not
# once per call by the whole prompt's length, so that a token's output does
# not depend on how the prompt was cut into calls (decode agrees with both).
#   * ``degrade``: None is the reference. "state_bf16" rounds the lightning
#     state to bfloat16 after every token; "no_residual_scale", "no_embed_scale"
#     and "no_logit_scale" drop one muP factor; "no_init_block" leaves the
#     forced first block out of the selection; "gate_per_head" gates every
#     lane of a head by the head's first gate lane; "softmax_bf16" rounds the
#     attention's scores, probabilities and sums to bfloat16: each exists to
#     show what a lower precision or a wrong law reads against each limit.
# ``selection`` replaces a sparse layer's own choice (a boolean [T, KH, blocks]
# per sparse sublayer index): logits GIVEN THE PROGRAM'S selection. ``carry``
# continues a prefix the same function computed: per mixer sublayer the
# prefix's float32 keys and values, or the state after it, and its length.
import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16_round(a):  # (a cast pair would be optimised away)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def ref_rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def ref_rope(x, theta, first=0):
    """x [T, H, D] at positions first..first+T-1, all D lanes rotate."""
    T, D = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = ((first + jnp.arange(T, dtype=jnp.float32))[:, None] * inv)[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], -1)
    return x * cos + rot * sin


def ref_gate(x, w_g, o, degrade):
    """o [T, H, D] * sigmoid(x W_g) per lane."""
    g = jax.nn.sigmoid(x @ _f32(w_g)).reshape(o.shape)
    if degrade == "gate_per_head":
        g = jnp.broadcast_to(g[..., :1], o.shape)
    return o * g


def ref_lightning(x, w, L, eps, degrade=None, carry=None, length=None):
    """x [T, d] -> (y [T, d], the state [H, D, D] after the last token, or
    after the first ``length`` where the rest is padding)."""
    T = x.shape[0]
    H, D = L["heads"], L["head_dim"]
    first = 0 if carry is None else carry["length"]
    q = ref_rmsnorm((x @ _f32(w["wq"])).reshape(T, H, D), w["q_norm"], eps)
    k = ref_rmsnorm((x @ _f32(w["wk"])).reshape(T, H, D), w["k_norm"], eps)
    v = (x @ _f32(w["wv"])).reshape(T, H, D)
    q, k = ref_rope(q, L["theta"], first), ref_rope(k, L["theta"], first)
    lam = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(H, dtype=jnp.float32) + 1) / H)))

    def token(S, qkv):  # the recurrence, one token at a time
        q_t, k_t, v_t, real = qkv
        new = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]  # [H, Dk, Dv]
        if degrade == "state_bf16":
            new = _bf16_round(new)
        return jnp.where(real, new, S), jnp.einsum("hk,hkv->hv", q_t, new) * D**-0.5

    S0 = jnp.zeros((H, D, D), jnp.float32) if carry is None else carry["S"]
    real = jnp.arange(T) < (T if length is None else length)
    S, o = jax.lax.scan(token, S0, (q, k, v, real))
    o = ref_gate(x, w["w_gate_attn"], ref_rmsnorm(o, w["o_norm"], eps), degrade)
    return o.reshape(T, H * D) @ _f32(w["wo"]), S


def ref_compressed_keys(k, L):
    """k [T, KH, D] -> Kc [J, KH, D], J = the complete windows:
    Kc_j = mean(k[stride j : stride j + kernel])."""
    T = k.shape[0]
    J = max((T - L["kernel"]) // L["stride"] + 1, 0)
    at = (jnp.arange(J) * L["stride"])[:, None] + jnp.arange(L["kernel"])[None]
    return k[at].mean(1) if J else jnp.zeros((0,) + k.shape[1:], jnp.float32)


def ref_block_scores(q, kc, q_pos, L, n_blocks):
    """q [Q, H, D] at positions q_pos [Q], kc [J, KH, D] -> b [Q, KH, blocks]:
    the block scores (-inf for a block no complete window at or before the
    query meets)."""
    Q, H, D = q.shape
    J, KH = kc.shape[0], kc.shape[1]
    if J == 0:
        return jnp.full((Q, KH, n_blocks), -jnp.inf)
    st, kn, bl = L["stride"], L["kernel"], L["block"]
    qg = q.reshape(Q, KH, H // KH, D)
    s = jnp.einsum("qgnd,jgd->qgnj", qg, kc) * D**-0.5
    j = jnp.arange(J)
    ended = ((j * st + kn - 1)[None, :] <= jnp.asarray(q_pos)[:, None])[:, None, None]  # [Q,1,1,J]
    top = jnp.max(jnp.where(ended, s, -1e30), -1, keepdims=True)
    p = jnp.where(ended, jnp.exp(jnp.minimum(s - top, 0.0)), 0.0)
    a = (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(2)  # [Q, KH, J]
    a = jnp.where(ended[:, :, 0], a, -jnp.inf)
    # Window j covers tokens [st j, st j + kn), block m [bl m, bl m + bl):
    # they meet for j from floor((bl m - kn) / st) + 1 to ceil(bl (m + 1) / st) - 1.
    m = jnp.arange(n_blocks)
    j_lo = (bl * m - kn) // st + 1
    width = -(-bl // st) + -(-kn // st)
    at = j_lo[:, None] + jnp.arange(width)[None]  # [blocks, width]
    meets = (at >= 0) & (at < J) & (at * st < (m[:, None] + 1) * bl) & (at * st + kn > m[:, None] * bl)
    vals = jnp.where(meets[None, None], a[:, :, jnp.clip(at, 0, J - 1)], -jnp.inf)
    return vals.max(-1)


def ref_forced(q_pos, L, n_blocks, degrade=None):
    """[Q, blocks]: the first blocks and those of the last ``window`` tokens."""
    t = jnp.asarray(q_pos)[:, None]
    m = jnp.arange(n_blocks)[None, :]
    local = (m >= jnp.maximum(t - L["window"] + 1, 0) // L["block"]) & (m <= t // L["block"])
    first = m < (0 if degrade == "no_init_block" else L["init_blocks"])
    return (first & (m <= t // L["block"])) | local


def ref_select(b, q_pos, L, degrade=None):
    """b [Q, KH, blocks] -> the selected set, boolean [Q, KH, blocks], by a
    full sort: forced blocks first, then the best scores, ``topk`` in all."""
    n_blocks = b.shape[-1]
    forced = ref_forced(q_pos, L, n_blocks, degrade)[:, None]
    seen = (jnp.arange(n_blocks)[None, :] <= jnp.asarray(q_pos)[:, None] // L["block"])[:, None]
    key = jnp.where(forced, jnp.inf, jnp.where(seen, jnp.nan_to_num(b, neginf=-1e30), -jnp.inf))
    order = jnp.argsort(-key, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < L["topk"]) & seen


def ref_sparse_attention(x, w, L, eps, degrade=None, carry=None, selection=None,
                         query_block=None, queries=None, want=False):
    """x [T, d] -> (y [T, d], k [T0 + T, KH, D], v). ``queries`` (indices into
    x): only those rows of y are computed (the others are zeros). ``want``:
    also return {"scores" b, "selected", "heads" o [T, H, D] after the gate}
    at the computed queries."""
    T = x.shape[0]
    H, KH, D = L["heads"], L["kv_heads"], L["head_dim"]
    first = 0 if carry is None else carry["length"]
    low = _bf16_round if degrade == "softmax_bf16" else (lambda a: a)
    q = ref_rmsnorm((x @ _f32(w["wq"])).reshape(T, H, D), w["q_norm"], eps)
    k = ref_rmsnorm((x @ _f32(w["wk"])).reshape(T, KH, D), w["k_norm"], eps)
    v = (x @ _f32(w["wv"])).reshape(T, KH, D)
    if carry is not None:
        k, v = jnp.concatenate([carry["k"], k], 0), jnp.concatenate([carry["v"], v], 0)
    Tk = k.shape[0]
    n_blocks = -(-Tk // L["block"])
    pad = n_blocks * L["block"] - Tk
    k_pad = jnp.concatenate([k, jnp.zeros((pad, KH, D), jnp.float32)], 0)
    v_pad = jnp.concatenate([v, jnp.zeros((pad, KH, D), jnp.float32)], 0)
    kc = ref_compressed_keys(k, L)
    rows = jnp.arange(T) if queries is None else jnp.asarray(queries)
    n = rows.shape[0]
    QB = min(query_block or n, n) or 1
    n_pad = -(-n // QB) * QB
    rows_p = jnp.concatenate([rows, jnp.full((n_pad - n,), rows[-1] if n else 0)])
    t_key = jnp.arange(n_blocks * L["block"])

    def block(r):  # QB queries against every key, masked from positions
        idx = jax.lax.dynamic_slice_in_dim(rows_p, r, QB)
        q_pos = first + idx
        qb = q[idx]
        b = ref_block_scores(qb, kc, q_pos, L, n_blocks)
        chosen = ref_select(b, q_pos, L, degrade)
        if selection is not None:
            chosen = selection[idx]
        dense = (q_pos + 1 < L["dense_len"])[:, None, None]
        per_key = jnp.repeat(chosen | dense, L["block"], axis=-1)  # [QB, KH, keys]
        seen = per_key & (t_key[None, None, :] <= q_pos[:, None, None])
        out = []
        for g in range(KH):  # one K/V head at a time
            s = low(jnp.einsum("qnd,td->qnt", qb.reshape(QB, KH, H // KH, D)[:, g], k_pad[:, g]) * D**-0.5)
            s = jnp.where(seen[:, g, None, :], s, -jnp.inf)
            p = low(jnp.exp(s - s.max(-1, keepdims=True)))
            p = low(p / low(p.sum(-1, keepdims=True)))
            out.append(low(jnp.einsum("qnt,td->qnd", p, v_pad[:, g])))
        return jnp.stack(out, 1).reshape(QB, H, D), b, chosen

    o, b, chosen = jax.lax.map(block, jnp.arange(0, n_pad, QB))
    o = o.reshape(n_pad, H, D)[:n]
    o = ref_gate(x[rows], w["w_gate_attn"], o, degrade)
    y = jnp.zeros((T, H * D), jnp.float32).at[rows].set(o.reshape(n, H * D)) @ _f32(w["wo"])
    extra = None
    if want:
        extra = {"scores": b.reshape(n_pad, KH, n_blocks)[:n],
                 "selected": chosen.reshape(n_pad, KH, n_blocks)[:n], "heads": o}
    return y, k, v, extra


def ref_dense_ffn(x, w, token_block=None):
    """Gated-silu FFN, in blocks of tokens. x [T, d] -> [T, d]."""
    f = lambda xb: (jax.nn.silu(xb @ _f32(w["w_gate"])) * (xb @ _f32(w["w_up"]))) @ _f32(w["w_down"])
    T = x.shape[0]
    tb = token_block or T
    if T <= tb or T % tb:
        return f(x)
    return jax.lax.map(f, x.reshape(T // tb, tb, -1)).reshape(T, -1)


class _Static(dict):
    """A description as a static (hashable) argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _mixer(h, w, L, model, degrade, carry, selection, query_block, queries, want, length=None):
    with jax.default_matmul_precision("highest"):
        x = ref_rmsnorm(h, w["norm"], model["eps"])
        if L["kind"] == "lightning":
            out, S = ref_lightning(x, w, L, model["eps"], degrade, carry, length)
            new, extra = {"S": S}, None
        else:
            out, k, v, extra = ref_sparse_attention(
                x, w, L, model["eps"], degrade, carry, selection, query_block, queries, want)
            new = {"k": k, "v": v}
        r = 1.0 if degrade == "no_residual_scale" else model["residual"]
        return h + r * out, new, extra


def _ffn(h, w, model, degrade, token_block):
    with jax.default_matmul_precision("highest"):
        r = 1.0 if degrade == "no_residual_scale" else model["residual"]
        return h + r * ref_dense_ffn(ref_rmsnorm(h, w["norm"], model["eps"]), w, token_block)


# dynlint: disable=DYN001 -- the reference is not the serving path: no compile telemetry wanted, and one program per sublayer kind and length is the point
_MIXER = jax.jit(_mixer, static_argnums=(2, 3, 4, 7, 9))
# dynlint: disable=DYN001 -- as above
_FFN = jax.jit(_ffn, static_argnums=(2, 3, 4))


def reference_forward(weights, layers, tokens, model, positions=None, degrade=None,
                      carry=None, selection=None, query_block=None, token_block=None,
                      last_queries_only=False, want=(), length=None):
    """tokens [T] (after ``carry``'s prefix, if any) -> {"logits" [n, V] at
    ``positions`` (indices into ``tokens``; default all), "carry": for every
    mixer sublayer the keys and values or the state after the last token, and
    the length, "extra": for each sparse sublayer index in ``want`` its block
    scores, selected set and gated per-head output at ``positions``}.
    ``last_queries_only``: the LAST sparse sublayer computes only the queries
    at ``positions`` (nothing after it mixes positions; the others' rows are
    not needed). ``length``: tokens from there on are padding (nothing compared
    sees them: causal), and the carry's lightning states are those after the
    first ``length``. ``model`` = {"eps", "embed", "residual", "logit_divisor"}."""
    T = len(tokens)
    keep = jnp.arange(T) if positions is None else jnp.asarray(positions)
    model = _Static(model)
    first = 0 if carry is None else carry["length"]
    mixers = [i for i, L in enumerate(layers) if L["kind"] != "ffn"]
    with jax.default_matmul_precision("highest"):
        h = _f32(weights["embed"][jnp.asarray(tokens)])
        if degrade != "no_embed_scale":
            h = h * model["embed"]
        new_carry, extra = {"length": first + T}, {}
        for i, (w, L) in enumerate(zip(weights["layers"], layers)):
            if L["kind"] == "ffn":
                h = _FFN(h, w, model, degrade, token_block)
                continue
            only = keep if (
                last_queries_only and i == mixers[-1] and L["kind"] == "sparse") else None
            prev = None if carry is None else dict(carry[i], length=first)
            h, new_carry[i], ex = _MIXER(
                h, w, _Static(L), model, degrade, prev,
                None if selection is None else selection.get(i), query_block,
                only, i in want, length)
            if ex is not None:
                extra[i] = ex if only is not None else jax.tree.map(lambda a: a[keep], ex)
        h = ref_rmsnorm(h[keep], weights["final_norm"], model["eps"])
        if degrade != "no_logit_scale":
            h = h / model["logit_divisor"]
        return {"logits": h @ _f32(weights["lm_head"]), "carry": new_carry, "extra": extra}

# --- reference: end -----------------------------------------------------------


def describe(config):
    """The reference's sublayer descriptions of a ModelConfig."""
    out = []
    for s in config.layer_specs:
        if s.kind == "dense_ffn":
            out.append(dict(kind="ffn"))
        elif s.kind == "lightning":
            out.append(dict(kind="lightning", heads=s.n_heads, head_dim=s.head_dim,
                            theta=float(s.rope_theta)))
        else:
            sp = s.sparse
            out.append(dict(
                kind="sparse", heads=s.n_heads, kv_heads=s.n_kv_heads, head_dim=s.head_dim,
                kernel=sp.kernel, stride=sp.stride, block=sp.block, topk=sp.topk,
                init_blocks=sp.init_blocks, window=sp.window, dense_len=sp.dense_len))
    return out


def describe_model(config):
    return dict(eps=float(config.rms_norm_eps), embed=float(config.embed_multiplier),
                residual=float(config.residual_multiplier),
                logit_divisor=float(config.logit_divisor))


def say(msg):
    print(f"[reference +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def harness_seed():
    """``run.py`` gives its reference child no ``--seed``: read the harness's
    own from its command line (this process's parent)."""
    try:
        with open(f"/proc/{os.getppid()}/cmdline", "rb") as f:
            argv = f.read().decode("utf-8", "replace").split("\0")
    except OSError:
        return None
    for i, a in enumerate(argv):
        if a == "--seed" and i + 1 < len(argv) and argv[i + 1].lstrip("-").isdigit():
            return int(argv[i + 1])
        if a.startswith("--seed=") and a[7:].lstrip("-").isdigit():
            return int(a[7:])
    return None


async def serve(engine, rid, prompt, n):
    """One request through the engine, greedy, with logprobs. Returns
    (rid, (tokens, log-probabilities of the chosen tokens))."""
    from dynamo_tpu.llm.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.context import Context

    request = PreprocessedRequest(
        token_ids=[int(t) for t in prompt], request_id=rid,
        sampling=SamplingOptions(temperature=0.0, logprobs=0),
        stop=StopConditions(max_tokens=n, ignore_eos=True))
    toks, lps = [], []
    async for out in engine.generate(request, Context()):
        if out.error:
            raise RuntimeError(f"{rid}: {out.error}")
        toks += list(out.token_ids)
        lps += [step[0].logprob for step in (out.logprobs or [])]
    return rid, (toks, lps)


def rows_rel_l2(got, want):
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return jnp.linalg.norm(got - want, axis=-1) / (jnp.linalg.norm(want, axis=-1) + 1e-30)


def _ref_selected_attention(q, k, v, q_pos, chosen, block, bf16_softmax=False):
    """q [Q, H, D] float32 at positions q_pos [Q] over key rows k, v [T, KH, D]
    at positions 0..T-1, each query over the blocks ``chosen`` [Q, KH, blocks]
    names: [Q, H, D]. The mask from positions and the set. ``bf16_softmax``
    rounds scores, probabilities and sums to bfloat16 (a second reading)."""
    Q, H, D = q.shape
    KH = k.shape[1]
    low = _bf16_round if bf16_softmax else (lambda a: a)
    per_key = jnp.repeat(chosen, block, axis=-1)[..., : k.shape[0]]
    seen = per_key & (jnp.arange(k.shape[0])[None, None, :] <= jnp.asarray(q_pos)[:, None, None])
    qg = q.reshape(Q, KH, H // KH, D)
    heads = []
    with jax.default_matmul_precision("highest"):
        for g in range(KH):
            s = low(jnp.einsum("qnd,td->qnt", qg[:, g], k[:, g]) * D**-0.5)
            s = jnp.where(seen[:, g, None, :], s, -jnp.inf)
            p = low(jnp.exp(s - s.max(-1, keepdims=True)))
            p = low(p / low(p.sum(-1, keepdims=True)))
            heads.append(low(jnp.einsum("qnt,td->qnd", p, v[:, g])))
    return jnp.stack(heads, 1).reshape(Q, H, D)


# dynlint: disable=DYN001 -- the reference is not the serving path
ref_selected_attention = jax.jit(_ref_selected_attention, static_argnums=(5, 6))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=None, help="default: the harness's own --seed")
    ap.add_argument("--readings", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        cfg_file = json.load(f)
    with open(os.path.join(HERE, "traffic", "long_ctx_mixed.json")) as f:
        traffic = json.load(f)

    import numpy as np

    from dynamo_tpu.engines.tpu.engine import JaxEngine, JaxEngineArgs
    from dynamo_tpu.models import hybrid
    from dynamo_tpu.models.llama import _rms_norm
    from dynamo_tpu.ops import sparse_attention as sa
    from dynamo_tpu.tokens.blocks import compute_block_hashes
    from dynamo_tpu.utils.jax_env import configure_compile_cache
    from dynamo_tpu.worker.__main__ import BUILTIN_CONFIGS

    configure_compile_cache()
    rehearse = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    device = jax.devices()[0]
    wanted = (cfg_file["serving"]["platform"], cfg_file["serving"]["device_kind"])
    if not rehearse and (device.platform, device.device_kind) != wanted:
        say(f"NOTHING COMPARED: the first device is {device.platform}/{device.device_kind}, the "
            f"configuration is served on {wanted[0]}/{wanted[1]}, and no rehearsal was asked for")
        return 2
    serving = cfg_file["rehearse_cpu"] if rehearse else cfg_file["serving"]
    wargs = serving["workers"][0]["args"]
    flag = lambda name: int(
        wargs[wargs.index(name) + 1] if name in wargs
        else cfg_file["serving"]["worker_flag_defaults"][name])
    config = BUILTIN_CONFIGS[wargs[wargs.index("--model") + 1]]()
    layers, model = describe(config), describe_model(config)
    if not rehearse:
        sp = cfg_file["assumed"]["sparse_config"]
        first = next(L for L in layers if L["kind"] == "sparse")
        assert {k: first[k] for k in sp} == sp, "the preset and the configuration file disagree"
        assert [L["kind"] for L in layers[::2]] == [
            "sparse" if m == "minicpm4" else "lightning" for m in cfg_file["mixer_types"]]
        assert config.vocab_size == cfg_file["vocab_size"] and config.d_model == cfg_file["hidden_size"]
        assert model["embed"] == cfg_file["scale_emb"] and model["logit_divisor"] == (
            cfg_file["hidden_size"] / cfg_file["dim_model_base"])
    blocking = dict(query_block=16, token_block=64) if rehearse else BLOCKING
    steps, block = flag("--decode-steps"), flag("--block-size")
    chunk = flag("--prefill-chunk")
    engine = JaxEngine(JaxEngineArgs(
        config=config, block_size=block, num_kv_blocks=flag("--num-kv-blocks"),
        max_num_seqs=flag("--max-num-seqs"), max_model_len=flag("--max-model-len"),
        prefill_chunk=chunk, decode_steps=steps, pipeline_depth=1))
    weights = engine.runner.params
    jax.block_until_ready(weights)
    seed = args.seed if args.seed is not None else harness_seed()
    if seed is None:
        seed = time.time_ns() % (1 << 32)
    say(f"{device.platform}/{device.device_kind}: {config.name}, {len(layers)} sublayers, engine up on its "
        f"seed-0 weights; snapshots every {engine._snap_every} tokens in {engine.snapshots.capacity} "
        f"entries; ids and lengths from seed {seed}")

    # -- the requests ----------------------------------------------------------
    rng = np.random.default_rng([int(seed) % (1 << 32), 46])
    sizes = sorted(int(c["tokens"]) for c in traffic["contexts"])
    n_long, n_short = (640, 48) if rehearse else (sizes[-1], sizes[0])
    law = traffic["turn_tokens"]
    if rehearse:
        law = dict(law, median=24, min=8, max=48)
    ids = lambda n: rng.integers(16, config.vocab_size, int(n)).astype(np.int32)
    turn = lambda: ids(int(np.clip(np.exp(rng.normal(np.log(law["median"]), law["sigma"])), law["min"], law["max"])))
    ctx = {"long": ids(n_long), "short": ids(n_short)}
    turns = {"long": turn(), "short": turn()}
    n_out = 1 + steps * (4 if rehearse else (N_OUT - 1) // steps)
    pad = 96 if rehearse else PAD
    prompts = {f"build_{c}": ctx[c] for c in ctx}
    prompts.update({f"hit_{c}": np.concatenate([ctx[c], turns[c]]) for c in ctx})
    builds, hits = ("build_long", "build_short"), ("hit_long", "hit_short")

    # -- the program -----------------------------------------------------------
    async def drive():
        served, reuse, slots = {}, {}, {}
        install = engine._admitter._install

        def watched(seq, prep, slot, *a, **kw):  # which slot a row decodes in
            slots[seq.request.request_id] = slot
            return install(seq, prep, slot, *a, **kw)

        engine._admitter._install = watched
        for wave in tuple((rid,) for rid in builds) + (hits,):  # the asks at once
            computed = engine.prefill_tokens
            served.update(await asyncio.gather(*(
                serve(engine, rid, prompts[rid], n_out) for rid in wave)))
            reuse[wave[0] if len(wave) == 1 else "hits"] = (
                sum(len(prompts[rid]) for rid in wave) - (engine.prefill_tokens - computed))
        hashes = compute_block_hashes([int(t) for t in ctx["long"]], block, salt=0)
        matched, page_ids = engine.pool.pin_prefix(hashes)
        engine.pool.release(page_ids, hashes[:matched])
        facts = dict(preemptions=engine.preemptions, snapshot_hits=engine.snapshots.hits,
                     snapshots=engine.snapshots.used, sparse=engine.stats().get("sparse_attention"))
        state = jax.tree.map(lambda a: a, engine.runner.ssm_state)
        await engine.stop()
        return served, reuse, matched, page_ids, slots, facts, state

    served, reuse, matched, page_ids, slots, facts, state = asyncio.run(drive())
    p_long = n_long // block
    say(f"served {[(r, len(prompts[r]), n_out) for r in builds + hits]}; reused {reuse}; the long context's "
        f"{matched} of {p_long} pages are resident; snapshots used {facts['snapshots']}, hits "
        f"{facts['snapshot_hits']}; preemptions {facts['preemptions']}; decode rows and pages {facts['sparse']}")
    failures = []
    if matched < p_long:
        say("DISAGREES: the long context's pages are not resident")
        return 1

    # -- copy out of the pools what B, C, D and E need, then drop the device state -----------------
    attn_specs = config.specs_of("attention")
    spec, sparse = attn_specs[0], attn_specs[0].sparse
    hd = spec.head_dim
    at_pages = jnp.asarray(np.asarray(page_ids[:p_long]))
    kc_pool, k_pool, v_pool = engine.runner.k_cache[len(attn_specs)], engine.runner.k_cache[0], engine.runner.v_cache[0]
    rows = {"k": k_pool[at_pages], "v": v_pool[at_pages], "kc": kc_pool[at_pages]}
    i_lights = [i for i, L in enumerate(layers) if L["kind"] == "lightning"]
    S_slot = {rid: [jnp.asarray(S[slots[rid]], jnp.float32) for S in state["S"]]  # each [H, value, key]
              for rid in hits}
    jax.block_until_ready((rows, S_slot))
    use_kernel = engine.runner.use_kernel
    del kc_pool, k_pool, v_pool, state
    engine.runner.k_cache = engine.runner.v_cache = engine.runner.ssm_state = engine.runner.snap_store = None
    flat = lambda a: jnp.asarray(a, jnp.float32)[..., :hd].reshape(-1, a.shape[2], hd)

    # -- A and E: the reference, a context once and its continuations ---------------------------------
    hp = jax.default_matmul_precision("highest")
    i_sparse0 = next(i for i, L in enumerate(layers) if L["kind"] == "sparse")
    L0 = layers[i_sparse0]
    last = np.arange(n_long - chunk, n_long)  # C's and D's queries: the context's last chunk

    def continuation(carry, prompt_tail, toks, degrade=None):
        """The reference over a row's tokens after its context: log-probabilities
        of the chosen tokens, and the carry at the row's own length."""
        seq = np.concatenate([prompt_tail, np.asarray(toks[:-1], np.int32)]).astype(np.int32)
        n, real = len(toks), len(seq)
        assert real <= pad, (real, pad)
        seq = np.concatenate([seq, ids(pad - real)])
        at = len(prompt_tail) - 1 + np.arange(n)
        out = reference_forward(weights, layers, seq, model, positions=at, degrade=degrade,
                                carry=carry, last_queries_only=True, length=real, **blocking)
        with hp:
            logp = jax.nn.log_softmax(out["logits"], axis=-1)
            chosen = np.asarray(jnp.take_along_axis(logp, jnp.asarray(toks)[:, None], axis=-1)[:, 0])
        return chosen, out["carry"]

    def read_class(c, degrade=None, keep=None):
        """Both rows of a class: the context once (its last position is the
        build row's first step, its carry both rows' start)."""
        head = reference_forward(
            weights, layers, ctx[c], model, positions=[len(ctx[c]) - 1], degrade=degrade,
            last_queries_only=True, want=(i_sparse0,) if keep is not None else (), **blocking)
        out = {}
        for rid, tail in ((f"build_{c}", ctx[c][:0]), (f"hit_{c}", turns[c])):
            toks = served[rid][0]
            if len(tail) == 0:  # the first step's logits are the context's last
                with hp:
                    lp0 = jax.nn.log_softmax(head["logits"], axis=-1)[0, toks[0]]
                rest, carry = continuation(head["carry"], np.asarray(toks[:1], np.int32), toks[1:], degrade)
                out[rid] = (np.concatenate([[float(lp0)], rest]), carry)
            else:
                out[rid] = continuation(head["carry"], tail, toks, degrade)
        if keep is not None:
            keep["carry"] = head["carry"]
        return out

    per_row, all_steps, kept, carries = {}, [], {}, {}
    for c in ("long", "short"):
        for rid, (chosen, carry) in read_class(c, keep=kept if c == "long" else None).items():
            err = np.abs(np.asarray(served[rid][1]) - chosen)
            assert len(err) == n_out, (rid, len(err))
            all_steps += list(err)
            carries[rid] = carry
            per_row[rid] = dict(median=float(np.median(err)), largest=float(err.max()), chosen=chosen)
            say(f"A {rid}: {len(err)} steps at {len(prompts[rid])} tokens of prompt, median "
                f"{per_row[rid]['median']:.5f}, largest {per_row[rid]['largest']:.4f}")
    a_all = float(np.median(all_steps))
    a_row = max(per_row.items(), key=lambda kv: kv[1]["median"])
    say(f"A logprob of the chosen token, {len(all_steps)} steps of {len(per_row)} rows: median {a_all:.5f} "
        f"against {LIMIT_LOGPROB}; the worst row's median {a_row[1]['median']:.5f} ({a_row[0]}) against "
        f"{LIMIT_LOGPROB_ROW}")

    def state_error(rid, carry):
        """Relative L2 per head of every lightning layer's state in ``rid``'s
        slot against ``carry``'s: [layers, heads]."""
        out = []
        for n, i in enumerate(i_lights):
            want = jnp.swapaxes(carry[i]["S"], -1, -2)  # [H, value, key], as the slot holds it
            flat2 = lambda a: a.reshape(a.shape[0], -1)
            out.append(np.asarray(jnp.linalg.norm(flat2(S_slot[rid][n] - want), axis=-1)
                                  / jnp.linalg.norm(flat2(want), axis=-1)))
        return np.stack(out)

    e_layers = {rid: state_error(rid, carries[rid]) for rid in hits}
    e_heads = e_layers["hit_short"][0]
    say(f"E the FIRST lightning layer's state in hit_short's slot after {len(prompts['hit_short']) + n_out - 1} "
        f"tokens, {len(e_heads)} heads: median {np.median(e_heads):.3e}, largest {e_heads.max():.3e} "
        f"against {LIMIT_STATE:.1e}; every lightning layer's median "
        f"{[f'{np.median(e):.2e}' for e in e_layers['hit_short']]}; in hit_long's slot after "
        f"{len(prompts['hit_long']) + n_out - 1} tokens {[f'{np.median(e):.2e}' for e in e_layers['hit_long']]} "
        f"(printed, not judged: the rotary frame's float32 precision at that position)")

    # -- B: the first sparse layer's K, V and compressed keys in the pool ------------------------------
    w0 = weights["layers"][i_sparse0]
    k_ref, v_ref = kept["carry"][i_sparse0]["k"], kept["carry"][i_sparse0]["v"]  # [n_long, KH, D]
    take = min(B_SAMPLE, n_long // 2)
    at = np.concatenate([np.arange(take), np.arange(n_long - take, n_long)])
    k_in, v_in = flat(rows["k"])[: n_long], flat(rows["v"])[: n_long]
    b_k = np.asarray(rows_rel_l2(k_in[at].reshape(len(at), -1), k_ref[at].reshape(len(at), -1)))
    b_v = np.asarray(rows_rel_l2(v_in[at].reshape(len(at), -1), v_ref[at].reshape(len(at), -1)))
    with hp:
        kc_ref = ref_compressed_keys(k_ref, L0)  # window j -> the pool's slot j + 1
    kc_in = flat(rows["kc"])[1: 1 + kc_ref.shape[0]]
    b_c = np.asarray(rows_rel_l2(kc_in.reshape(kc_in.shape[0], -1), kc_ref.reshape(kc_ref.shape[0], -1)))
    b_med = max(np.median(b_k), np.median(b_v), np.median(b_c))
    b_max = max(b_k.max(), b_v.max(), b_c.max())
    say(f"B the first sparse layer's rows in the pool: K and V at {len(at)} of {n_long} tokens, all "
        f"{len(b_c)} compressed keys: medians {np.median(b_k):.3e} {np.median(b_v):.3e} {np.median(b_c):.3e} "
        f"against {LIMIT_KV:.1e}, largest {b_k.max():.3e} {b_v.max():.3e} {b_c.max():.3e} against "
        f"{LIMIT_KV_ROW:.1e}")

    # -- C and D: the program's selection and attention over the pool's own rows ---------------------
    with hp:
        x = ref_rmsnorm(_f32(weights["embed"][jnp.asarray(ctx["long"][last])]) * model["embed"],
                        w0["norm"], model["eps"])
        q_ref = ref_rmsnorm((x @ _f32(w0["wq"])).reshape(chunk, spec.n_heads, hd), w0["q_norm"], model["eps"])
    # the program's own queries: its dtype, its operations
    xe = weights["embed"][jnp.asarray(ctx["long"][last])].astype(config.dtype) * jnp.asarray(
        config.embed_multiplier, config.dtype)
    xp = _rms_norm(xe, w0["norm"], config.rms_norm_eps)
    q_prog = _rms_norm(jnp.einsum("cd,dh->ch", xp, w0["wq"]).reshape(chunk, spec.n_heads, hd),
                       w0["q_norm"], config.rms_norm_eps)
    table = jnp.arange(p_long, dtype=jnp.int32)[None]
    scale = hd**-0.5
    n_blocks = p_long

    # dynlint: disable=DYN001 -- the reference child's own call of the program's function: no compile telemetry wanted
    call = jax.jit(lambda q, k, v, kc, tables, start, lens: sa.sparse_paged_attention(
        q, k, v, kc, tables, start, lens, sparse, sm_scale=scale, use_kernel=use_kernel,
        want_selection=True))

    def program(q, start, C):
        """The engine's own call over the copied pages, in order: B rows of C
        queries, a row's first at position ``start``."""
        B = q.shape[0]
        out, (sel, count, is_sparse) = call(
            q, rows["k"], rows["v"], rows["kc"], jnp.tile(table, (B, 1)),
            jnp.full((B,), start, jnp.int32), jnp.full((B,), C, jnp.int32))
        sel, count = np.asarray(sel), np.asarray(count)
        chosen = np.zeros((B * C, sel.shape[2], n_blocks), bool)
        for b in range(B):
            for c in range(C):
                for g in range(sel.shape[2]):
                    chosen[b * C + c, g, sel[b, c, g, : count[b, c]]] = True
        return jnp.asarray(out, jnp.float32).reshape(B * C, *q.shape[2:]), chosen, np.asarray(is_sparse).ravel()

    got_chunk, chosen, is_sparse = program(q_prog[None], n_long - chunk, chunk)
    with hp:
        b_ref = np.asarray(ref_block_scores(q_ref, ref_compressed_keys(k_ref, L0), last, L0, n_blocks))
    forced = np.asarray(ref_forced(last, L0, n_blocks))
    shortfall, missing, differs, blind = 0.0, 0, 0, 0.0
    own = np.asarray(ref_select(jnp.asarray(b_ref), last, L0))
    for qi, t in enumerate(last):
        if not is_sparse[qi]:
            continue
        for g in range(chosen.shape[1]):
            mine = chosen[qi, g]
            missing += int((forced[qi] & ~mine).sum()) + int(mine.sum() != min(L0["topk"], t // block + 1))
            differs += int((mine != own[qi, g]).any())
            free = mine & ~forced[qi]
            if free.any():
                rest = np.sort(b_ref[qi, g][~forced[qi] & (np.arange(n_blocks) <= t // block)])
                kth = rest[-int(free.sum())]
                shortfall = max(shortfall, float((kth - b_ref[qi, g][free].min()) / kth))
                blind = max(blind, float((kth - rest[0]) / kth))  # had it taken the worst candidate
    say(f"C selection at the context's last {chunk} positions x {chosen.shape[1]} K/V heads "
        f"({int(is_sparse.sum())} on the sparse path): forced blocks missing or a wrong count {missing}; the "
        f"largest shortfall of a selected block's reference score under the reference's k-th best "
        f"{shortfall:.3e} of it against {LIMIT_SCORE_SHORTFALL} (a selection blind to the scores could read "
        f"{blind:.3e}); sets that differ from the reference's own {differs} of "
        f"{int(is_sparse.sum()) * chosen.shape[1]}")

    d_reads = {}
    rounded = jnp.asarray(q_prog, jnp.float32)
    want_chunk = ref_selected_attention(rounded, k_in, v_in, last, jnp.asarray(chosen), L0["block"])
    d_reads["one chunk"] = np.asarray(rows_rel_l2(got_chunk, want_chunk)).ravel()
    got_rows, chosen_rows, _ = program(q_prog[-D_QUERIES:, None], n_long - 1, 1)
    want_rows = ref_selected_attention(rounded[-D_QUERIES:], k_in, v_in, np.full(D_QUERIES, n_long - 1),
                                       jnp.asarray(chosen_rows), L0["block"])
    d_reads["decode rows"] = np.asarray(rows_rel_l2(got_rows, want_rows)).ravel()
    for name, d in d_reads.items():
        say(f"D the first sparse layer's attention as {name} over {p_long} pages of the pool's rows "
            f"({'kernel' if use_kernel else 'xla'}), {len(d)} (query, head) pairs: median {np.median(d):.3e} "
            f"against {LIMIT_ATTENTION:.1e}, largest {d.max():.3e} against {LIMIT_ATTENTION_ROW:.1e}")

    if args.readings:
        say("second readings, each fault against the limit it must fail:")
        soft = ref_selected_attention(rounded, k_in, v_in, last, jnp.asarray(chosen), L0["block"], True)
        d_soft = np.asarray(rows_rel_l2(_bf16_round(soft), want_chunk)).ravel()
        say(f"  the program's roundings + a bfloat16 softmax (the reference's chunk, queries and output "
            f"rounded, scores, probabilities and sums too, against the reference): D median "
            f"{np.median(d_soft):.3e} largest {d_soft.max():.3e}")
        for degrade in ("state_bf16", "no_residual_scale", "gate_per_head"):
            low = read_class("long", degrade)
            for rid in ("build_long", "hit_long"):
                err = np.abs(np.asarray(served[rid][1]) - low[rid][0])
                say(f"  {degrade}: A of {rid} against the degraded reference: median {np.median(err):.5f} "
                    f"(largest {err.max():.4f})")
            e_low = state_error("hit_short", read_class("short", degrade)["hit_short"][1])
            say(f"  {degrade}: E (hit_short) against the degraded reference: the first layer's median "
                f"{np.median(e_low[0]):.3e} largest {e_low[0].max():.3e}; every layer's median "
                f"{[f'{np.median(e):.2e}' for e in e_low]}")

    def hold(what, value, limit):
        if not value <= limit:  # (a NaN fails)
            failures.append(f"{what} {value:.5g} > {limit}")

    hold("A logprob, median over all steps", a_all, LIMIT_LOGPROB)
    hold(f"A logprob, row {a_row[0]}'s median", a_row[1]["median"], LIMIT_LOGPROB_ROW)
    hold("B K, V and compressed keys, median", float(b_med), LIMIT_KV)
    hold("B K, V and compressed keys, largest", float(b_max), LIMIT_KV_ROW)
    hold("C a selected block's shortfall under the k-th best reference score", shortfall, LIMIT_SCORE_SHORTFALL)
    if missing:
        failures.append(f"C {missing} forced blocks missing or counts wrong in the program's selection")
    for name, d in d_reads.items():
        hold(f"D {name}, median", float(np.median(d)), LIMIT_ATTENTION)
        hold(f"D {name}, largest", float(d.max()), LIMIT_ATTENTION_ROW)
    hold("E lightning state, largest over the heads", float(e_heads.max()), LIMIT_STATE)
    if reuse["build_long"] != 0 or reuse["build_short"] != 0:
        failures.append(f"a fresh context reused tokens (reused {reuse}): it was not fresh")
    every = engine._snap_every
    if reuse["hits"] < n_long // every * every + n_short // every * every:
        failures.append(f"a resident context was not served as a prefix hit through a snapshot (reused {reuse})")
    if facts["snapshot_hits"] < 1 + (n_short >= every):
        failures.append(f"snapshot hits {facts['snapshot_hits']}")
    if facts["preemptions"]:
        failures.append(f"{facts['preemptions']} preemptions")
    for why in failures:
        say(f"DISAGREES: {why}")
    say("agrees" if not failures else "does not agree")
    return 1 if failures else 0


T0 = time.monotonic()
if __name__ == "__main__":
    sys.exit(main())
