#!/usr/bin/env python3
"""Plain reference for ``qwen3-next-80b-a3b-ep2`` and the comparison that
decides the cell's ``correct``.

    python3 benchmark/references/qwen3-next-80b-a3b-ep2.py --config <file>

``run.py`` runs this as a child after the workers have gone (the chip is free
again), with the run's environment; a non-zero exit makes ``correct`` false.

**Where it runs.** A CPU rehearsal is the harness's to ask for
(``JAX_PLATFORMS=cpu`` in the environment, which ``run.py --rehearse-cpu``
sets): it compares the ``rehearse_cpu`` stand-in on contexts of 640 and 160
tokens. In every other case the first device must be the configuration's
``serving.platform`` and ``serving.device_kind``, or the child exits 2 and
compares nothing.

**What it drives.** A ``JaxEngine`` built from the cell's own worker flags, on
the worker's own seed-0 weights: admission, the block pool, the state slots
and the snapshot store, and the runner's compiled programs (from a warm cache
the worker's own executables; ``pipeline_depth`` 1 is the one departure: the
same programs, less host overlap). Ids and lengths come from the harness's
``--seed`` (read from its command line; ``--seed`` here overrides), under the
cell's traffic law (``benchmark/traffic/long_session_mixed.json``): one context
of each class, turns by the turn law, greedy, all with ``logprobs``, ``N_OUT``
= 65 tokens each (the first from the prefill, 8 bursts of 8 after it). The
contexts are built one after another, each ALONE, as the generator's warm-up
asks them; the asks then arrive TOGETHER, as the window's do:

* ``build_long``: the longest class's context (32,768 tokens): 128 chunks of
  256 through the delta rule's chunk form (512 blocks of 64, sequential in the
  state) and the chunk kernel at head 256, 8 snapshot boundaries;
  ``build_short``: the shortest class's (4,096);
* then AT ONCE (``asyncio.gather``) ``hit_long`` (the long context + a fresh
  turn: a prefix hit through the full layer's K/V pages and the snapshot at
  32,768, matrix + conv tail of three layers) and ``hit_short``: one prefill
  batch over tables of both widths, then decode bursts with a 33 k row and a
  4 k row side by side through the live-row kernel.

**What it compares with.** The float32 reference below, whose delta rule runs
TOKEN BY TOKEN (nothing of the chunk form). A context is computed ONCE and
continued (the same function on the suffix, given the prefix's float32 keys,
values, states and conv tails: ``carry``), a continuation padded on the right
to ``PAD`` tokens (causal: no compared position sees the padding; the states
are taken at the sequence's own length). The engine's weights (bf16, 7.36 GB)
stay for the whole run and are the reference's source; of the engine's pools
the rows B needs are copied out and pools, snapshot store and slots DROPPED
before the first full forward.

The limits, each with its reason and its two readings (the builder's chip runs
of PR 50, PERF.md section 6):

A. ``logprob``: |served - reference| log-probability of the chosen token, per
   step, every row. Judged: the median over all steps (the precision of the
   whole path) and EVERY row's median (a wrong page table, a stale snapshot or
   a wrong position garbles that row and nobody else's). Medians, not a
   largest-of-n: under random weights a step's routing can turn on a rounded
   score (ten of 512 near-equal experts), and that step then reads far off.
B. ``kv rows``: relative L2 error, per token, of the full layer's K rows
   (q/k-normed and rotated) and V rows as they lie in the pool after
   ``build_long``, over the context's first and last ``B_SAMPLE`` tokens,
   against the reference's (three Gated DeltaNet layers and their experts lie
   upstream: this reads the whole stack below the one cache).
E. ``state``: EVERY Gated DeltaNet layer's matrices as they lie in
   ``hit_short``'s and ``hit_long``'s slots after their last burst (through
   the snapshot, a turn's chunk and 8 bursts of the live-row kernel), against
   the reference's after the same tokens: relative Frobenius error a layer
   (and per head, printed). No rotation enters this state, so the long row is
   judged too. Two limits: a tight one on the FIRST layer, whose input is the
   embedding (what its state reads is the delta rule's own precision: this is
   the limit a bfloat16 state fails), and a looser one on every layer (the
   second and third read the bfloat16 layers above them as well).

``--readings`` (the builder's) also prints what A and E read on the short class
when the reference is degraded (``state_bf16``, ``no_beta``, ``no_decay``,
``no_l2norm``): each fault must fail at least one limit.
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
LIMIT_LOGPROB = 0.03  # A, median over all steps (260): program 0.0079-0.0105 (seventeen seeds; bfloat16 weights and activations through eight sublayers, ten of 512 near-equal experts a token); a dropped beta 0.46-0.52, a dropped decay 0.97-1.00, un-normalised q and k NaN; a bfloat16 state (0.012-0.020) does NOT move A beyond it: E parts that one
LIMIT_LOGPROB_ROW = 0.04  # A, every row's median (65 steps): program's worst row 0.0088-0.0139 (seventeen seeds); a dropped beta 0.46 and more on every row
LIMIT_KV = 2.5e-2  # B, median over the sampled tokens, K and V each: program 1.02e-2-1.10e-2 (K), 1.01e-2-1.09e-2 (V), seventeen seeds, the same over the context's last 2,048 tokens (three bfloat16 Gated DeltaNet layers and their experts lie upstream: a token whose tenth expert flips under bfloat16 scores moves its row by what one expert of ten weighs); a zero-centred k norm read as plain reads 1.0 (the drawn weight is 0: K vanishes)
LIMIT_KV_ROW = 0.3  # B, every sampled token: a guard against a misplaced or unrotated row (reads about 1.4), not a precision limit: program's largest 0.084-0.104 (seventeen seeds)
LIMIT_STATE_FIRST = 7e-3  # E, the FIRST Gated DeltaNet layer (its input is the embedding: nothing upstream reaches it), both hit rows: program 3.79e-3-4.02e-3 (seventeen seeds, both rows: the bfloat16 q, k, v and conv tail of 4,300 and 32,900 tokens); a state rounded to bfloat16 after every token 1.27e-2, a dropped beta 0.57, a dropped decay 0.82, un-normalised q and k NaN
LIMIT_STATE = 6e-2  # E, every Gated DeltaNet layer, both hit rows: program 8.4e-3-3.08e-2 on the second and third layers (seventeen seeds) (the second and third layers' inputs carry the layers' above them: bfloat16 experts); a dropped beta 0.57-0.70
N_OUT = 65
PAD = 512  # continuations are padded to this many tokens: one compiled length
B_SAMPLE = 2048
BLOCKING = dict(query_block=256, token_block=4096)

# --- reference: begin ---------------------------------------------------------
# The forward pass of a cut Qwen3-Next model in straightforward jax.numpy:
# float32, matmuls at "highest" precision, ONE sequence at a time, no paged
# cache, no chunks of the program's, no kernels, every mask built from
# positions, the gated delta rule TOKEN BY TOKEN (a ``lax.scan`` over the
# tokens: nothing of the chunked form), full causal softmax attention, every
# held expert densely. Attention runs in blocks of query positions and the
# experts in blocks of tokens (``query_block``, ``token_block``: the result
# does not depend on them), so the published widths fit beside the program
# under test.
#
# Layer equations (x = rmsnorm(h) of the sublayer's input; every RMS-norm
# weight is zero-centred, y = x^ (1 + w), but the Gated DeltaNet output
# norm's, which is plain):
#   h <- h + mixer(x); h <- h + experts(x); h_0 = embed[token];
#   logits = W_head rmsnorm(h).
#   gated delta (GDN): [q|k|v|z] = x W_qkvz, [b|a] = x W_ba; [q|k|v] through a
#     causal depth-wise conv of K taps (no bias; the K-1 inputs before a
#     continuation are carried), then silu; beta = sigmoid(b); g = -exp(A_log)
#     softplus(a + dt_bias); q, k L2-normalised per head (x / sqrt(sum x^2 +
#     1e-6)), key head j serving value heads j R .. j R + R - 1, q / sqrt(Dk);
#     per value head, S [Dk, Dv] float32:
#       S <- exp(g_t) S; u_t = beta_t (v_t - k_t S); S <- S + k_t^T u_t;
#       o_t = q_t S
#     o <- rmsnorm_head(o) w (plain) * silu(z); y = o W_out.
#   gated attention: q [H, D] = x W_q, gate [H, D] = x W_g (the published
#     q_proj holds both, a head's 2 D outputs as D query and D gate lanes:
#     here they are two matrices side by side), k, v [KH, D]; q, k <-
#     rmsnorm_head (zero-centred); rotary on the first ``rotary`` lanes (lane
#     i paired with lane i + rotary / 2) at ``theta``; causal softmax attention
#     / sqrt(D); y = (o * sigmoid(gate)) W_o.
#   experts: p = softmax(x W_r) over ALL ``n_experts``; the top k, weights
#     renormalised over the k; the sum over the chosen experts THAT ARE HELD
#     (``held`` = [lo, hi): one chip's share) of w_e * W_down_e(silu(W_gate_e x)
#     * W_up_e x), plus ``shared_share`` x sigmoid(x w_sg) * the shared expert
#     (a scalar gate a token). The shares of an expert-parallel group, the
#     shared expert counted once, add up to the uncut layer.
#
# Departures from the published description, each noted: (i) q_proj as two
# matrices (above) and in_proj_qkvz as [q | k | v | z] blocks, not interleaved
# by key head: a permutation of columns of randomly drawn matrices; (ii) the
# multi-token-prediction module is not built; (iii) the cut itself (four
# layers, the held half of the experts, the first half of the vocabulary).
#   * ``degrade``: None is the reference. "state_bf16" rounds the GDN state to
#     bfloat16 after every token; "no_beta" sets beta = 1; "no_decay" sets g =
#     0; "no_l2norm" leaves q and k as the conv gave them; "norm_plain" reads
#     the zero-centred norm weights as plain ones; "no_shared_gate" drops the
#     shared expert's sigmoid gate; "no_attn_gate" the attention's: each exists
#     to show what a lower precision or a wrong law reads against each limit.
# ``carry`` continues a prefix the same function computed: per mixer sublayer
# the prefix's float32 keys and values, or the GDN state and conv tail after
# it, and its length.
import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16_round(a):  # (a cast pair would be optimised away)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def ref_rmsnorm(x, w, eps, zero_centred=True):
    w = _f32(w)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w if zero_centred else w)


def ref_rope(x, theta, rotary, first=0):
    """x [T, H, D] at positions first..first+T-1: the first ``rotary`` lanes
    rotate, the rest pass."""
    T = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    ang = ((first + jnp.arange(T, dtype=jnp.float32))[:, None] * inv)[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    head = x[..., :rotary]
    rot = jnp.concatenate([-head[..., rotary // 2:], head[..., : rotary // 2]], -1)
    return jnp.concatenate([head * cos + rot * sin, x[..., rotary:]], -1)


def ref_gated_delta(x, w, L, eps, degrade=None, carry=None, length=None):
    """x [T, d] -> (y [T, d], {"S" [H, Dk, Dv], "conv" [K-1, ch]}: the state
    and the conv tail after the last token, or after the first ``length``
    where the rest is padding)."""
    T = x.shape[0]
    H, HK, Dk, Dv, K = L["heads"], L["k_heads"], L["k_dim"], L["head_dim"], L["conv_kernel"]
    kw, ch = HK * Dk, 2 * HK * Dk + H * Dv
    qkvz = x @ _f32(w["w_qkvz"])
    qkv, z = qkvz[:, :ch], qkvz[:, ch:].reshape(T, H, Dv)
    ba = x @ _f32(w["w_ba"])
    beta = jax.nn.sigmoid(ba[:, :H])
    g = -jnp.exp(_f32(w["A_log"])) * jax.nn.softplus(ba[:, H:] + _f32(w["dt_bias"]))
    if degrade == "no_beta":
        beta = jnp.ones_like(beta)
    if degrade == "no_decay":
        g = jnp.zeros_like(g)
    tail = jnp.zeros((K - 1, ch), jnp.float32) if carry is None else carry["conv"]
    padded = jnp.concatenate([tail, qkv], 0)  # [K-1+T, ch]
    taps = _f32(w["conv_w"])  # [K, ch]; taps[K-1] multiplies the newest input
    act = jax.nn.silu(sum(padded[j : j + T] * taps[j] for j in range(K)))
    n = T if length is None else length
    new_tail = jax.lax.dynamic_slice_in_dim(padded, n, K - 1, 0)

    def unit(a):
        if degrade == "no_l2norm":
            return a
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(act[:, :kw].reshape(T, HK, Dk)) * Dk**-0.5, H // HK, axis=1)
    k = jnp.repeat(unit(act[:, kw : 2 * kw].reshape(T, HK, Dk)), H // HK, axis=1)
    v = act[:, 2 * kw :].reshape(T, H, Dv)

    def token(S, t):  # the rule, one token at a time
        q_t, k_t, v_t, g_t, b_t, real = t
        new = S * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, new))
        new = new + k_t[:, :, None] * u[:, None, :]
        if degrade == "state_bf16":
            new = _bf16_round(new)
        return jnp.where(real, new, S), jnp.einsum("hk,hkv->hv", q_t, new)

    S0 = jnp.zeros((H, Dk, Dv), jnp.float32) if carry is None else carry["S"]
    S, o = jax.lax.scan(token, S0, (q, k, v, g, beta, jnp.arange(T) < n))
    o = ref_rmsnorm(o, w["o_norm"], eps, zero_centred=False) * jax.nn.silu(z)
    return o.reshape(T, H * Dv) @ _f32(w["w_out"]), {"S": S, "conv": new_tail}


def ref_attention(x, w, L, eps, degrade=None, carry=None, query_block=None, queries=None):
    """x [T, d] -> (y [n, d] at ``queries`` (indices into the T; default all),
    k, v [first + T, KH, D] float32 after the q/k norm and the rotation)."""
    T = x.shape[0]
    H, KH, D = L["heads"], L["kv_heads"], L["head_dim"]
    first = 0 if carry is None else carry["length"]
    zc = degrade != "norm_plain"
    q = ref_rmsnorm((x @ _f32(w["wq"])).reshape(T, H, D), w["q_norm"], eps, zc)
    k = ref_rmsnorm((x @ _f32(w["wk"])).reshape(T, KH, D), w["k_norm"], eps, zc)
    v = (x @ _f32(w["wv"])).reshape(T, KH, D)
    q = ref_rope(q, L["theta"], L["rotary"], first)
    k = ref_rope(k, L["theta"], L["rotary"], first)
    gate = jax.nn.sigmoid(x @ _f32(w["w_gate_attn"])).reshape(T, H, D)
    if degrade == "no_attn_gate":
        gate = jnp.ones_like(gate)
    if carry is not None:
        k, v = jnp.concatenate([carry["k"], k], 0), jnp.concatenate([carry["v"], v], 0)
    at = jnp.arange(T) if queries is None else jnp.asarray(queries)
    q, gate = q[at], gate[at]
    n = q.shape[0]
    pos = first + at
    kg = jnp.repeat(k, H // KH, axis=1)
    vg = jnp.repeat(v, H // KH, axis=1)

    def block(qp):  # a block of queries against every key, masked from positions
        qb, pb = qp
        s = jnp.einsum("qhd,thd->hqt", qb, kg) * D**-0.5
        s = jnp.where(jnp.arange(k.shape[0])[None, None, :] <= pb[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqt,thd->qhd", jax.nn.softmax(s, axis=-1), vg)

    qb = query_block or n
    if n <= qb or n % qb:
        o = block((q, pos))
    else:
        o = jax.lax.map(block, (q.reshape(n // qb, qb, H, D), pos.reshape(n // qb, qb)))
        o = o.reshape(n, H, D)
    return (o * gate).reshape(n, H * D) @ _f32(w["wo"]), k, v


def ref_route(x, w, L):
    """(chosen expert ids [T, k], their renormalised weights [T, k])."""
    p = jax.nn.softmax(x @ _f32(w["router_w"]), axis=-1)
    top, idx = jax.lax.top_k(p, L["top_k"])
    return idx, top / (top.sum(-1, keepdims=True) + 1e-20)


def ref_experts(x, w, L, degrade=None, token_block=None):
    """x [T, d] -> [T, d]: the held chosen experts' weighted outputs + the
    gated shared expert's, ``shared_share`` of it."""
    lo = L["held"][0]

    def tokens(xb):
        idx, wt = ref_route(xb, w, L)
        ffn = lambda gate, up, down: (jax.nn.silu(xb @ _f32(gate)) * (xb @ _f32(up))) @ _f32(down)

        def expert(out, e):  # the loop over the experts held, one at a time
            e_id, gate, up, down = e
            share = jnp.where(idx == e_id + lo, wt, 0.0).sum(-1)  # [T], 0 where not chosen
            return out + share[:, None] * ffn(gate, up, down), None

        shared = ffn(w["ws_gate"], w["ws_up"], w["ws_down"])
        if degrade != "no_shared_gate":
            shared = shared * jax.nn.sigmoid(xb @ _f32(w["ws_gate_scalar"]))
        out, _ = jax.lax.scan(
            expert, L.get("shared_share", 1.0) * shared,
            (jnp.arange(w["we_up"].shape[0]), w["we_gate"], w["we_up"], w["we_down"]))
        return out

    T = x.shape[0]
    tb = token_block or T
    if T <= tb or T % tb:
        return tokens(x)
    return jax.lax.map(tokens, x.reshape(T // tb, tb, -1)).reshape(T, -1)


class _Static(dict):
    """A description as a static (hashable) argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _sublayer(h, w, L, eps, degrade, carry, query_block, token_block, queries, length):
    with jax.default_matmul_precision("highest"):
        x = ref_rmsnorm(h, w["norm"], eps, degrade != "norm_plain")
        if L["kind"] == "experts":
            return h + ref_experts(x, w, L, degrade, token_block), None
        if L["kind"] == "gated_delta":
            out, new = ref_gated_delta(x, w, L, eps, degrade, carry, length)
            return h + out, new
        out, k, v = ref_attention(x, w, L, eps, degrade, carry, query_block, queries)
        return (h if queries is None else h[jnp.asarray(queries)]) + out, {"k": k, "v": v}


# dynlint: disable=DYN001 -- the reference is not the serving path: no compile telemetry wanted, and one program per sublayer kind and length is the point
_SUBLAYER = jax.jit(_sublayer, static_argnums=(2, 3, 4, 6, 7))


def reference_forward(weights, layers, tokens, eps, positions=None, degrade=None,
                      carry=None, query_block=None, token_block=None,
                      last_queries_only=False, length=None):
    """tokens [T] (after ``carry``'s prefix, if any) -> {"logits" [n, V] at
    ``positions`` (indices into ``tokens``; default all), "carry": for every
    mixer sublayer the keys and values or the state and conv tail after the
    last token, and the length}. ``last_queries_only``: the LAST attention
    sublayer computes only the queries at ``positions``, and the sublayers
    after it only those rows (valid where no recurrent sublayer follows it:
    nothing after it mixes positions). ``length``: tokens from there on are
    padding (nothing compared sees them: causal), and the carry's GDN states
    are those after the first ``length``."""
    T = len(tokens)
    keep = jnp.arange(T) if positions is None else jnp.asarray(positions)
    first = 0 if carry is None else carry["length"]
    kinds = [L["kind"] for L in layers]
    mixers = [i for i, kind in enumerate(kinds) if kind != "experts"]
    cut = mixers[-1] if last_queries_only and kinds[mixers[-1]] == "attention" else None
    with jax.default_matmul_precision("highest"):
        h = _f32(weights["embed"][jnp.asarray(tokens)])
        new_carry = {"length": first + T}
        for i, (w, L) in enumerate(zip(weights["layers"], layers)):
            prev = None if carry is None or L["kind"] == "experts" else dict(carry[i], length=first)
            h, new = _SUBLAYER(h, w, _Static(L), eps, degrade, prev, query_block, token_block,
                               keep if i == cut else None, length)
            if new is not None:
                new_carry[i] = new
        if cut is None:
            h = h[keep]
        zc = degrade != "norm_plain"
        h = ref_rmsnorm(h, weights["final_norm"], eps, zc)
        return {"logits": h @ _f32(weights["lm_head"]), "carry": new_carry}

# --- reference: end -----------------------------------------------------------


def describe(config):
    """The reference's sublayer descriptions of a ModelConfig."""
    out = []
    for s in config.layer_specs:
        if s.kind == "experts":
            out.append(dict(kind="experts", top_k=s.top_k, held=tuple(s.held_), shared_share=1.0))
        elif s.kind == "gated_delta":
            out.append(dict(kind="gated_delta", heads=s.n_heads, k_heads=s.n_k_heads,
                            k_dim=s.k_dim, head_dim=s.head_dim, conv_kernel=s.conv_kernel))
        else:
            out.append(dict(kind="attention", heads=s.n_heads, kv_heads=s.n_kv_heads,
                            head_dim=s.head_dim, theta=float(s.rope.theta),
                            rotary=int(s.rope.rotary_dim)))
    return out


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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=None, help="default: the harness's own --seed")
    ap.add_argument("--readings", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        cfg_file = json.load(f)
    with open(os.path.join(HERE, "traffic", "long_session_mixed.json")) as f:
        traffic = json.load(f)

    import numpy as np

    from dynamo_tpu.engines.tpu.engine import JaxEngine, JaxEngineArgs
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
    layers, eps = describe(config), float(config.rms_norm_eps)
    if not rehearse:
        gdn = next(L for L in layers if L["kind"] == "gated_delta")
        attn = next(L for L in layers if L["kind"] == "attention")
        experts = next(L for L in layers if L["kind"] == "experts")
        assert [L["kind"] for L in layers[::2]] == [
            "attention" if (i + 1) % cfg_file["full_attention_interval"] == 0 else "gated_delta"
            for i in range(cfg_file["num_hidden_layers"])], "the preset and the configuration file disagree"
        assert (gdn["heads"], gdn["k_heads"], gdn["k_dim"], gdn["head_dim"], gdn["conv_kernel"]) == tuple(
            cfg_file[k] for k in ("linear_num_value_heads", "linear_num_key_heads", "linear_key_head_dim",
                                  "linear_value_head_dim", "linear_conv_kernel_dim"))
        assert (attn["heads"], attn["kv_heads"], attn["head_dim"], attn["theta"]) == tuple(
            cfg_file[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta"))
        assert attn["rotary"] == int(cfg_file["head_dim"] * cfg_file["partial_rotary_factor"])
        assert experts["top_k"] == cfg_file["num_experts_per_tok"] and experts["held"] == (0, cfg_file["num_experts"])
        assert config.vocab_size == cfg_file["vocab_size"] and config.d_model == cfg_file["hidden_size"]
    blocking = dict(query_block=16, token_block=64) if rehearse else BLOCKING
    steps, block = flag("--decode-steps"), flag("--block-size")
    engine = JaxEngine(JaxEngineArgs(
        config=config, block_size=block, num_kv_blocks=flag("--num-kv-blocks"),
        max_num_seqs=flag("--max-num-seqs"), max_model_len=flag("--max-model-len"),
        prefill_chunk=flag("--prefill-chunk"), decode_steps=steps, pipeline_depth=1))
    weights = engine.runner.params
    jax.block_until_ready(weights)
    seed = args.seed if args.seed is not None else harness_seed()
    if seed is None:
        seed = time.time_ns() % (1 << 32)
    say(f"{device.platform}/{device.device_kind}: {config.name}, {len(layers)} sublayers, engine up on its "
        f"seed-0 weights; ssd_step: {engine.runner.ssd_step}; snapshots every {engine._snap_every} tokens in "
        f"{engine.snapshots.capacity} entries; ids and lengths from seed {seed}")

    # -- the requests ----------------------------------------------------------
    rng = np.random.default_rng([int(seed) % (1 << 32), 50])
    sizes = sorted(int(c["tokens"]) for c in traffic["contexts"])
    n_long, n_short = (640, 160) if rehearse else (sizes[-1], sizes[0])
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
                     snapshots=engine.snapshots.used)
        state = jax.tree.map(lambda a: a, engine.runner.ssm_state)
        await engine.stop()
        return served, reuse, matched, page_ids, slots, facts, state

    served, reuse, matched, page_ids, slots, facts, state = asyncio.run(drive())
    p_long = n_long // block
    say(f"served {[(r, len(prompts[r]), n_out) for r in builds + hits]}; reused {reuse}; the long context's "
        f"{matched} of {p_long} pages are resident; snapshots used {facts['snapshots']}, hits "
        f"{facts['snapshot_hits']}; preemptions {facts['preemptions']}")
    failures = []
    if matched < p_long:
        say("DISAGREES: the long context's pages are not resident")
        return 1

    # -- copy out of the pools what B and E need, then drop the device state ------------------------
    spec = config.specs_of("attention")[0]
    hd = spec.head_dim
    at_pages = jnp.asarray(np.asarray(page_ids[:p_long]))
    rows = {"k": engine.runner.k_cache[0][at_pages], "v": engine.runner.v_cache[0][at_pages]}
    i_gdn = [i for i, L in enumerate(layers) if L["kind"] == "gated_delta"]
    i_attn = next(i for i, L in enumerate(layers) if L["kind"] == "attention")
    S_slot = {rid: [jnp.asarray(S[slots[rid]], jnp.float32) for S in state["S"]]  # each [H, key, value]
              for rid in hits}
    jax.block_until_ready((rows, S_slot))
    del state
    engine.runner.k_cache = engine.runner.v_cache = engine.runner.ssm_state = engine.runner.snap_store = None
    flat = lambda a: jnp.asarray(a, jnp.float32)[..., :hd].reshape(-1, a.shape[2], hd)

    # -- A and E: the reference, a context once and its continuations ---------------------------------
    hp = jax.default_matmul_precision("highest")

    def continuation(carry, prompt_tail, toks, degrade=None):
        """The reference over a row's tokens after its context: log-probabilities
        of the chosen tokens, and the carry at the row's own length."""
        seq = np.concatenate([prompt_tail, np.asarray(toks[:-1], np.int32)]).astype(np.int32)
        n, real = len(toks), len(seq)
        assert real <= pad, (real, pad)
        seq = np.concatenate([seq, ids(pad - real)])
        at = len(prompt_tail) - 1 + np.arange(n)
        out = reference_forward(weights, layers, seq, eps, positions=at, degrade=degrade,
                                carry=carry, last_queries_only=True, length=real, **blocking)
        with hp:
            logp = jax.nn.log_softmax(out["logits"], axis=-1)
            chosen = np.asarray(jnp.take_along_axis(logp, jnp.asarray(toks)[:, None], axis=-1)[:, 0])
        return chosen, out["carry"]

    def read_class(c, degrade=None, keep=None):
        """Both rows of a class: the context once (its last position is the
        build row's first step, its carry both rows' start)."""
        head = reference_forward(
            weights, layers, ctx[c], eps, positions=[len(ctx[c]) - 1], degrade=degrade,
            last_queries_only=True, **blocking)
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

    def state_error(rid, carry):
        """Relative Frobenius error of every Gated DeltaNet layer's state in
        ``rid``'s slot against ``carry``'s: ([layers], [layers, heads])."""
        whole, heads = [], []
        for n, i in enumerate(i_gdn):
            got, want = S_slot[rid][n], carry[i]["S"]
            whole.append(float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)))
            flat2 = lambda a: a.reshape(a.shape[0], -1)
            heads.append(np.asarray(jnp.linalg.norm(flat2(got - want), axis=-1)
                                    / jnp.linalg.norm(flat2(want), axis=-1)))
        return np.asarray(whole), np.stack(heads)

    per_row, all_steps, kept, carries = {}, [], {}, {}
    for c in ("long", "short"):
        for rid, (chosen, carry) in read_class(c, keep=kept if c == "long" else None).items():
            err = np.abs(np.asarray(served[rid][1]) - chosen)
            assert len(err) == n_out, (rid, len(err))
            all_steps += list(err)
            carries[rid] = carry
            per_row[rid] = dict(median=float(np.median(err)), largest=float(err.max()))
            say(f"A {rid}: {len(err)} steps at {len(prompts[rid])} tokens of prompt, median "
                f"{per_row[rid]['median']:.5f}, largest {per_row[rid]['largest']:.4f}")
    a_all = float(np.median(all_steps))
    a_row = max(per_row.items(), key=lambda kv: kv[1]["median"])
    say(f"A logprob of the chosen token, {len(all_steps)} steps of {len(per_row)} rows: median {a_all:.5f} "
        f"against {LIMIT_LOGPROB}; the worst row's median {a_row[1]['median']:.5f} ({a_row[0]}) against "
        f"{LIMIT_LOGPROB_ROW}")

    e_reads = {rid: state_error(rid, carries[rid]) for rid in hits}
    for rid in hits:
        whole, heads = e_reads[rid]
        say(f"E every Gated DeltaNet layer's state in {rid}'s slot after {len(prompts[rid]) + n_out - 1} "
            f"tokens: relative Frobenius error a layer {[f'{e:.3e}' for e in whole]} against "
            f"{LIMIT_STATE_FIRST:.1e} (the first) and {LIMIT_STATE:.1e}; the worst head of each "
            f"{[f'{h.max():.2e}' for h in heads]}")
    e_first = max(float(e_reads[rid][0][0]) for rid in hits)
    e_max = max(float(e_reads[rid][0].max()) for rid in hits)

    # -- B: the full layer's K and V rows in the pool ---------------------------------------------------
    k_ref, v_ref = kept["carry"][i_attn]["k"], kept["carry"][i_attn]["v"]  # [n_long, KH, D]
    take = min(B_SAMPLE, n_long // 2)
    at = np.concatenate([np.arange(take), np.arange(n_long - take, n_long)])
    k_in, v_in = flat(rows["k"])[: n_long], flat(rows["v"])[: n_long]
    b_k = np.asarray(rows_rel_l2(k_in[at].reshape(len(at), -1), k_ref[at].reshape(len(at), -1)))
    b_v = np.asarray(rows_rel_l2(v_in[at].reshape(len(at), -1), v_ref[at].reshape(len(at), -1)))
    b_med, b_max = max(np.median(b_k), np.median(b_v)), max(b_k.max(), b_v.max())
    say(f"B the full layer's rows in the pool: K and V at {len(at)} of {n_long} tokens: medians "
        f"{np.median(b_k):.3e} {np.median(b_v):.3e} against {LIMIT_KV:.1e}, largest {b_k.max():.3e} "
        f"{b_v.max():.3e} against {LIMIT_KV_ROW:.1e}; the last {take} tokens' medians "
        f"{np.median(b_k[take:]):.3e} {np.median(b_v[take:]):.3e}")

    if args.readings:
        say("second readings (the short class), each fault against the limit it must fail:")
        for degrade in ("state_bf16", "no_beta", "no_decay", "no_l2norm"):
            low = read_class("short", degrade)
            for rid in ("build_short", "hit_short"):
                err = np.abs(np.asarray(served[rid][1]) - low[rid][0])
                say(f"  {degrade}: A of {rid} against the degraded reference: median {np.median(err):.5f} "
                    f"(largest {err.max():.4f})")
            e_low, _ = state_error("hit_short", low["hit_short"][1])
            say(f"  {degrade}: E (hit_short) against the degraded reference: a layer "
                f"{[f'{e:.3e}' for e in e_low]}")

    def hold(what, value, limit):
        if not value <= limit:  # (a NaN fails)
            failures.append(f"{what} {value:.5g} > {limit}")

    hold("A logprob, median over all steps", a_all, LIMIT_LOGPROB)
    hold(f"A logprob, row {a_row[0]}'s median", a_row[1]["median"], LIMIT_LOGPROB_ROW)
    hold("B K and V, median", float(b_med), LIMIT_KV)
    hold("B K and V, largest", float(b_max), LIMIT_KV_ROW)
    hold("E the first Gated DeltaNet layer's state, the worse of both hit rows", e_first, LIMIT_STATE_FIRST)
    hold("E Gated DeltaNet state, the worst layer of both hit rows", e_max, LIMIT_STATE)
    if reuse["build_long"] != 0 or reuse["build_short"] != 0:
        failures.append(f"a fresh context reused tokens (reused {reuse}): it was not fresh")
    every = engine._snap_every
    if reuse["hits"] < n_long // every * every + n_short // every * every:
        failures.append(f"a resident context was not served as a prefix hit through a snapshot (reused {reuse})")
    if facts["snapshot_hits"] < 2:
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
