#!/usr/bin/env python3
"""Plain reference for ``laguna-xs.2-pp8`` and the comparison that decides the
cell's ``correct``.

    python3 benchmark/references/laguna-xs.2-pp8.py --config <file>

``run.py`` runs this as a child after the workers have gone (the chip is free
again), with the run's environment; a non-zero exit makes ``correct`` false.

**Where it runs.** A CPU rehearsal is the harness's to ask for
(``JAX_PLATFORMS=cpu`` in the environment, which ``run.py --rehearse-cpu``
sets): it compares the ``rehearse_cpu`` stand-in on contexts of 1,280 and 256
tokens. In every other case the first device must be the configuration's
``serving.platform`` and ``serving.device_kind``, or the child exits 2 and
compares nothing.

**What it drives.** A ``JaxEngine`` built from the cell's own worker flags, on
the worker's own seed-0 weights: admission, BOTH page groups (the full layers'
block pool and the sliding layers' window group, whose pages behind the window
are given back while a sequence runs), and the runner's compiled programs
(from a warm cache the worker's own executables; ``pipeline_depth`` 1 is the
one departure: the same programs, less host overlap). Ids and lengths come
from the harness's ``--seed`` (read from its command line; ``--seed`` here
overrides), under the cell's traffic law
(``benchmark/traffic/agent_mixed_ctx.json``): one context of each class, turns
by the turn law, greedy, all with ``logprobs``. The contexts are built one
after another, each ALONE, as the generator's warm-up asks them; the asks
then arrive TOGETHER, as the window's do:

* ``build_long``: the longest class's context (32,000 tokens): chunked
  prefill, the first chunk dense, every later one over both pools; the window
  group turns over ~62 times; ``build_short``: the shortest class's (2,048);
* then AT ONCE (``asyncio.gather``) ``hit_long`` (the long context + a fresh
  turn), ``hit_short`` and ``hit_short_b`` (the short context + a fresh turn
  each, two sessions that fork from one history): PREFIX HITS over both
  groups (the engine's counters must say so: every token of the three
  contexts reused, no hit cut by the window), the three turns in ONE prefill
  program over tables that differ 12-fold in width (``[rows, 2, width]``, a
  sliding layer's view cut per row), then decoded side by side in the
  wide-table program: rows of ~32 k and ~2.2 k tokens in one burst, two of
  them over the same cached pages. ``hit_short`` decodes ``LONG_DECODE``
  (641) tokens, so that decode crosses the window five times and gives pages
  back (the counter must have moved) while the others come and go beside it.

**What it compares with.** The float32 reference below, one sequence at a
time: each served sequence (prompt + the tokens the engine chose) padded on the
right to a multiple of the query block (causal: no compared position sees the
padding). What it holds when: the engine's weights (bf16, 7.74 GB) stay for the
whole run and are the reference's source; of the engine's pools (4.7 GB) the
rows B and D need are copied out (a context's K and V of three layers, ~0.3 GB)
and the pools DROPPED before the first full forward; the reference then holds
the hidden state [T, 2048] float32 (0.27 GB), one sublayer's q, k, v and
output (~3 GB at 64 heads), and scores of 2 K/V heads x 8 queries a head x 256
positions x T keys (0.54 GB).

The limits, each with its reason and its two readings (the builder's chip runs
of PR 44, PERF.md section 6). Bfloat16 activations choose a different eighth
expert than the float32 reference where the eighth and ninth scores of 256 lie
closer than the rounding; such a step reads tenths where the others read
hundredths, so statistics are medians per row and over all steps:

A. ``logprob``: |served - reference| log-probability of the chosen token, per
   step, every row. Judged: the median over all steps (the precision of the
   whole path: rotary on the wrong lanes, a missing gate, a wrong norm or
   weight moves every step) and EVERY row's median (a wrong page table, a
   released page read as data, a stale window page or a wrong position
   garbles that row and nobody else's: ``hit_short`` decodes five windows past
   its prompt, over pages that were given back and taken again).
B. ``kv rows``: relative L2 error, per token, of layer 0's K rows (rotated) and
   V rows as they lie in the FULL group's pool after ``build_long``, over the
   context's first and last 2,048 tokens, against the reference's. That
   layer's input is the embedding: no routing flip reaches it, so it is
   tight: bfloat16 rows against an 8-bit pool's. Judged: the median (the
   precision) and the largest (a guard against a misplaced row only).
D. ``attention``: the program's paged attention (the decode kernel on the
   chip) over THE POOLS' OWN ROWS, copied out page by page, for 8 query rows
   at the long context's last position, on the REFERENCE's queries of the
   context's last 8 positions, against the reference's attention, its own
   float32 queries, over the same rows: layer 1 (sliding, 64 heads: the window
   group's pages, which after ``build_long`` are exactly the context's trailing
   window) and layer 4 (full, 48 heads: the full group's 250 pages). Relative
   L2 per (query, head). What D reads is then the kernel's: queries and output
   rounded to bfloat16; a bfloat16 softmax on top reads higher and fails.
   The cause of the largest is bounded as in the latent cell's child (PERF.md
   section 6, PR 39): the rows are the pool's on both sides.
   And the same as ONE CHUNK of ``--prefill-chunk`` (256) queries, the call a turn's
   prefill makes (the chunk kernel; at 64 heads in blocks of 64 query
   positions, ``chunk_query_block``): layer 4's queries of the context's last
   256 positions over the full group's 250 pages, and layer 1's (64 heads,
   window 512) over a sliding layer's VIEW as ``hybrid._window_view`` cuts it
   for such a chunk: 7 pages, positions rebased to the first. The window
   group keeps only a context's trailing 512 tokens once it is built, which a
   chunk of 256 behind a window of 512 does not fit, so those 7 pages are the
   full group's layer-4 rows of the context's end (as E's are): the pool's
   rows on both sides, real magnitudes, not layer 1's own keys.
E. ``window edges``: the sliding layer's call (64 heads, window 512) over 6
   pages of the pool's rows, queries aimed with a sharp score at the OLDEST key
   inside the window (t - 511) and at the first key outside it (t - 512):
   relative L2 against the reference, which masks from positions. A window of
   511 or of 513 moves one of the two probes' outputs wholesale (reads about
   1); agreement reads as D.

``--readings`` (the builder's) also prints what each limit reads when the
reference is degraded (``softmax_bf16``, ``kv_int8``, ``window_511``,
``window_513``, ``rope_all_lanes``, ``no_gate``): each fault must fail at
least one limit.
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
# float32 softmax state).
LIMIT_LOGPROB = 0.02  # A, median over all steps: program 0.0083-0.0096 (11 seeds, the asks one after another), 0.0081-0.0094 (8 seeds, the asks at once); rotary on all lanes of a full layer 0.39-0.42, no output gate 0.30-0.33; a bfloat16 softmax 0.0088-0.0092: A does NOT part that one, D does
LIMIT_LOGPROB_ROW = 0.05  # A, every row's median: program's worst row 0.0085-0.0206 (11 seeds; the build rows then had 9 steps, since 25), 0.0094-0.0113 (8 seeds, the asks at once); a row on another row's pages or positions has every step wrong (reasoned: no such fault was injected on the chip)
LIMIT_KV = 5.5e-3  # B, median over the sampled tokens, K and V each: program K 3.94e-3-4.15e-3 (three bfloat16 roundings: the normed input, the projection, the rotation), V 2.33e-3 (two); 8-bit rows alone 6.9e-3 / 6.4e-3, on top of the program's roundings 8.0e-3
LIMIT_KV_ROW = 2.0e-2  # B, every sampled token: a guard against a row written to another slot or rotated for another position (reads about 1), NOT a precision limit: the program's largest of 4,096 8.8e-3-1.01e-2 and an 8-bit pool's 8.8e-3 do not part (a largest of n needs its cause bounded: the median does the precision)
LIMIT_ATTENTION = 3.5e-3  # D, median over (query, head), each call: program 2.70e-3-2.80e-3 (sliding) / 2.52e-3-2.59e-3 (full) as decode rows, 2.77e-3-2.80e-3 / 2.55e-3-2.57e-3 as a chunk (8 seeds) (the program's roundings emulated on the reference 2.32e-3-2.46e-3 / 2.45e-3-2.48e-3); with a bfloat16 softmax too 4.77e-3-5.11e-3 / 4.81e-3-4.90e-3
LIMIT_CHUNK_ROW = 1.2e-2  # D as a chunk, every (query, head) of 256 x 64 / 256 x 48: program's worst 5.65e-3-7.54e-3 (sliding) / 6.05e-3-7.15e-3 (full) over 8 seeds (the limit was set from the first two); its cause is bounded: the same rows on both sides, and the program's roundings emulated read 5.62e-3 / 7.10e-3 on the seed that read 5.65e-3 / 7.15e-3; with a bfloat16 softmax 1.76e-2 / 2.51e-2 (32 times the decode call's samples read the same largest: the tail is the rounding's, not a sample's)
LIMIT_ATTENTION_ROW = 1.0e-2  # D as decode rows, every (query, head): program's worst 3.79e-3-5.46e-3 (sliding) / 3.48e-3-7.14e-3 (full) (emulated 3.42e-3-4.39e-3 / 4.40e-3-7.06e-3); with a bfloat16 softmax 1.07e-2-1.39e-2 / 1.28e-2-2.33e-2
LIMIT_EDGE = 0.05  # E, every probe: program 1.9e-3-2.6e-3; a window of 511 reads 5.3, of 513 1.1
LONG_DECODE = 641
D_QUERIES = 8
EDGE_PAGES = 6
EDGE_SHARPNESS = 30.0  # the aimed key's score, in units of sm_scale * |k|^2
B_SAMPLE = 2048  # B reads the context's first and last so many tokens
# How the reference is blocked at the published widths (the result does not
# depend on it): K/V heads a group, query positions a block.
BLOCKING = dict(kv_group=2, query_block=256)
PAD_BLOCKS = 4  # sequences are padded to a multiple of so many query blocks: few distinct lengths, few compiles

# --- reference: begin ---------------------------------------------------------
# The forward pass of a cut laguna model in straightforward jax.numpy: float32,
# matmuls at "highest" precision, ONE sequence at a time, no cache, no kernels,
# no batching, every mask built from positions. The experts are a loop over
# the experts. Weights are converted to float32 one sublayer (one expert) at a
# time, and attention runs in groups of K/V heads and blocks of query
# positions (``kv_group``, ``query_block``: the result does not depend on
# them), so the published widths fit beside the program under test at 32 k
# tokens.
#
# Assumed, where the published config names a switch and not its shape (the
# configuration's file lists the same under ``assumed``):
#   1. ``gating: true`` is a sigmoid gate PER HEAD, computed from the
#      sublayer's normed input: o_h <- sigmoid(N1 x . w_g)_h * o_h, w_g
#      [d, heads of that layer], before W_o (a per-lane gate would add 0.63 B
#      parameters to the published 33.4 B; per head the count comes out);
#   2. the router scores with a sigmoid (no scoring key in the config): the
#      ``top_k`` largest scores choose, and, normalised over the chosen,
#      times ``moe_routed_scaling_factor``, weigh the experts' outputs; no
#      correction bias, no groups, float32 router;
#   3. no query/key norm (no key for one), and the dense layer 0 has no
#      shared expert.
# Also: the rotary lanes pair lane i with lane i + rotary/2 (the repo's
# ``rotate_half`` layout; with seeded random weights an interleaved pairing is
# a relabelling); a full layer rotates its first ``rotary`` lanes only, with
# YaRN's blended frequencies and cos and sin times the attention factor.
#   * ``degrade``: None is the reference. "softmax_bf16" rounds scores,
#     probabilities and their sums to bfloat16; "kv_int8" rounds each token's
#     K and V rows to 8 bits with one scale a head; "window_511" / "window_513"
#     move the sliding window by one; "rope_all_lanes" rotates all lanes of a
#     full layer (its frequencies over the whole head); "no_gate" leaves the
#     output gate out: each exists to show what a lower precision or a wrong
#     law reads against each limit.
import math

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16_round(a):  # (a cast pair would be optimised away)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def ref_rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def ref_rope_freqs(rotary, theta, yarn):
    """[rotary // 2] frequencies; ``yarn`` = (factor, original positions,
    beta_fast, beta_slow) or None."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    if yarn is None:
        return inv
    factor, original, beta_fast, beta_slow = yarn
    dim = lambda rot: rotary * math.log(original / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), rotary - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotary // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def ref_rope(x, L, degrade=None):
    """x [T, H, D] at positions 0..T-1: the first ``rotary`` lanes rotate."""
    T, D = x.shape[0], x.shape[-1]
    rotary = D if degrade == "rope_all_lanes" else L["rotary"]
    freqs = ref_rope_freqs(rotary, L["theta"], L["yarn"])
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * freqs)[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * L["attention_factor"]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * L["attention_factor"]
    head, rest = x[..., :rotary], x[..., rotary:]
    rot = jnp.concatenate([-head[..., rotary // 2:], head[..., : rotary // 2]], -1)
    return jnp.concatenate([head * cos + rot * sin, rest], -1)


def _int8_rows(a):  # [T, KH, D]: one scale a token a head
    scale = jnp.max(jnp.abs(a), -1, keepdims=True) / 127.0 + 1e-30
    return jnp.round(a / scale) * scale


def ref_attention(x, w, L, degrade=None, kv_group=None, query_block=None, want_heads=False):
    """Causal (and, where ``window``, sliding) attention with the per-head
    output gate. x [T, d] -> [T, d] (or, ``want_heads``, the gated per-head
    output [T, H, D] before W_o)."""
    T = x.shape[0]
    H, KH, D, W = L["heads"], L["kv_heads"], L["head_dim"], L["window"]
    if W and degrade in ("window_511", "window_513"):
        W = W + (1 if degrade == "window_513" else -1)
    G, QB, Q = kv_group or KH, query_block or T, H // KH
    assert T % QB == 0 and KH % G == 0, (T, QB, KH, G)
    low = _bf16_round if degrade == "softmax_bf16" else (lambda a: a)
    q = ref_rope((x @ _f32(w["wq"])).reshape(T, H, D), L, degrade if not L["window"] else None)
    k = ref_rope((x @ _f32(w["wk"])).reshape(T, KH, D), L, degrade if not L["window"] else None)
    v = (x @ _f32(w["wv"])).reshape(T, KH, D)
    if degrade == "kv_int8":
        k, v = _int8_rows(k), _int8_rows(v)
    # A query block sees keys from its first query's window on: K and V are
    # cut to that span (``span`` keys ending at the block's last query).
    span = min(T, QB + W) if W else T
    pad = span - QB
    k_pad = jnp.concatenate([jnp.zeros((pad, KH, D), jnp.float32), k], 0)
    v_pad = jnp.concatenate([jnp.zeros((pad, KH, D), jnp.float32), v], 0)
    heads = []
    for g0 in range(0, KH, G):  # a group of K/V heads at a time
        qg = q.reshape(T, KH, Q, D)[:, g0 : g0 + G]

        def block(r0):  # QB query positions from r0 against ``span`` keys
            qb = jax.lax.dynamic_slice_in_dim(qg, r0, QB)
            kb = jax.lax.dynamic_slice_in_dim(k_pad, r0, span)[:, g0 : g0 + G]
            vb = jax.lax.dynamic_slice_in_dim(v_pad, r0, span)[:, g0 : g0 + G]
            s = low(jnp.einsum("qgnd,tgd->gnqt", qb, kb) * D**-0.5)
            t_pos = r0 - pad + jnp.arange(span)[None, :]  # key positions
            q_pos = r0 + jnp.arange(QB)[:, None]
            seen = (t_pos >= 0) & (t_pos <= q_pos)
            if W:
                seen = seen & (t_pos > q_pos - W)
            s = jnp.where(seen[None, None], s, -jnp.inf)
            p = low(jnp.exp(s - s.max(-1, keepdims=True)))
            p = low(p / low(p.sum(-1, keepdims=True)))
            return low(jnp.einsum("gnqt,tgd->qgnd", p, vb))

        heads.append(jax.lax.map(block, jnp.arange(0, T, QB)).reshape(T, G * Q, D))
    o = jnp.concatenate(heads, 1)  # [T, H, D]
    if L["gate"] and degrade != "no_gate":
        o = o * jax.nn.sigmoid(x @ _f32(w["w_gate_attn"]))[..., None]
    if want_heads:
        return o
    return o.reshape(T, H * D) @ _f32(w["wo"])


def ref_dense_ffn(x, w, L):
    """Gated-silu FFN. x [T, d] -> [T, d]."""
    return (jax.nn.silu(x @ _f32(w["w_gate"])) * (x @ _f32(w["w_up"]))) @ _f32(w["w_down"])


def ref_route(x, w, L):
    """(chosen expert ids [T, k], their weights [T, k], the margin [T]
    between the last chosen and the first not chosen score)."""
    s = jax.nn.sigmoid(x @ _f32(w["router_w"]))
    top, idx = jax.lax.top_k(s, L["top_k"] + 1)
    wt = top[:, : L["top_k"]]
    wt = wt / (wt.sum(-1, keepdims=True) + 1e-20) * L["scale"]
    return idx[:, : L["top_k"]], wt, top[:, L["top_k"] - 1] - top[:, L["top_k"]]


def ref_experts(x, w, L):
    """x [T, d] -> out [T, d]: the routed experts' weighted outputs + the
    shared expert's."""
    idx, wt, _ = ref_route(x, w, L)
    ffn = lambda gate, up, down: (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)

    def expert(out, e):  # the loop over the experts, one at a time
        e_id, gate, up, down = e
        share = jnp.where(idx == e_id, wt, 0.0).sum(-1)  # [T], 0 where not chosen
        return out + share[:, None] * ffn(gate, up, down), None

    shared = ffn(w["ws_gate"], w["ws_up"], w["ws_down"])
    out, _ = jax.lax.scan(
        expert, shared,
        (jnp.arange(w["we_up"].shape[0]), w["we_gate"], w["we_up"], w["we_down"]))
    return out


def reference_forward(weights, layers, tokens, eps, positions=None, degrade=None,
                      kv_group=None, query_block=None, attention_of=()):
    """tokens [T] -> {"logits" [n, V] at ``positions`` (default: all),
    "hidden": the input of every sublayer at ``positions`` [n, d], "final":
    the residual stream after the last sublayer at ``positions``,
    "attention": for each sublayer index in ``attention_of`` its gated
    per-head attention output [n, H, D]}."""
    keep = jnp.arange(len(tokens)) if positions is None else jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        h = _f32(weights["embed"][jnp.asarray(tokens)])
        hidden, attention = [], {}
        for i, (w, L) in enumerate(zip(weights["layers"], layers)):
            hidden.append(h[keep])
            if i in attention_of:
                attention[i] = _ATTENTION_HEADS(
                    h, w, _Static(L), eps, degrade, kv_group, query_block)[keep]
            h = _SUBLAYER(h, w, _Static(L), eps, degrade, kv_group, query_block)
        return {"logits": _head(h[keep], weights["final_norm"], weights["lm_head"], eps),
                "hidden": hidden, "final": h[keep], "attention": attention}


class _Static(dict):
    """A sublayer description as a static (hashable) argument: one compiled
    function per sublayer kind and sequence length, not one per call."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _sublayer(h, w, L, eps, degrade, kv_group, query_block):
    """h <- h + F(N(h))."""
    with jax.default_matmul_precision("highest"):
        x = ref_rmsnorm(h, w["norm"], eps)
        if L["kind"] == "attention":
            out = ref_attention(x, w, L, degrade, kv_group, query_block)
        elif L["kind"] == "dense_ffn":
            out = ref_dense_ffn(x, w, L)
        else:
            out = ref_experts(x, w, L)
        return h + out


def _attention_heads(h, w, L, eps, degrade, kv_group, query_block):
    with jax.default_matmul_precision("highest"):
        return ref_attention(
            ref_rmsnorm(h, w["norm"], eps), w, L, degrade, kv_group, query_block,
            want_heads=True)


# dynlint: disable=DYN001 -- the reference is not the serving path: no compile telemetry wanted, and one program per sublayer kind and length is the point
_SUBLAYER = jax.jit(_sublayer, static_argnums=(2, 3, 4, 5, 6))
# dynlint: disable=DYN001 -- as above
_ATTENTION_HEADS = jax.jit(_attention_heads, static_argnums=(2, 3, 4, 5, 6))


# dynlint: disable=DYN001 -- as above
@jax.jit
def _head(h, norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return ref_rmsnorm(h, norm, eps) @ _f32(head)

# --- reference: end -----------------------------------------------------------


def describe(cfg):
    """The reference's sublayer descriptions from the configuration FILE."""
    out = []
    for i in range(int(cfg["num_hidden_layers"])):
        kind = cfg["layer_types"][i]
        rp = cfg["rope_parameters"][kind]
        yarn = None
        if rp.get("rope_type", "default") == "yarn":
            yarn = (float(rp["factor"]), int(rp["original_max_position_embeddings"]),
                    float(rp["beta_fast"]), float(rp["beta_slow"]))
        out.append(dict(
            kind="attention", heads=int(cfg["num_attention_heads_per_layer"][i]),
            kv_heads=int(cfg["num_key_value_heads"]), head_dim=int(cfg["head_dim"]),
            window=int(cfg["sliding_window"]) if kind == "sliding_attention" else 0,
            gate=bool(cfg["gating"]),
            rotary=int(cfg["head_dim"] * float(rp.get("partial_rotary_factor", 1.0))),
            theta=float(rp["rope_theta"]), yarn=yarn,
            attention_factor=float(rp.get("attention_factor", 1.0))))
        if cfg["mlp_layer_types"][i] == "dense":
            out.append(dict(kind="dense_ffn"))
        else:
            out.append(dict(kind="experts", top_k=int(cfg["num_experts_per_tok"]),
                            scale=float(cfg["moe_routed_scaling_factor"])))
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


def rope_at(x, L, positions, degrade=None):
    """``ref_rope`` for rows x [n, H, D] at the given positions."""
    D = x.shape[-1]
    rotary = D if degrade == "rope_all_lanes" else L["rotary"]
    ang = (jnp.asarray(positions, jnp.float32)[:, None]
           * ref_rope_freqs(rotary, L["theta"], L["yarn"]))[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * L["attention_factor"]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * L["attention_factor"]
    head, rest = x[..., :rotary], x[..., rotary:]
    rot = jnp.concatenate([-head[..., rotary // 2:], head[..., : rotary // 2]], -1)
    return jnp.concatenate([head * cos + rot * sin, rest], -1)


def ref_rows_attention(q, k, v, q_pos, window, low=lambda a: a):
    """q [Q, H, D] float32, the queries at position(s) ``q_pos`` (one for
    all, or [Q]), over key rows k, v [T, KH, D] at positions 0..T-1:
    [Q, H, D]. The mask from positions; one K/V head at a time (a chunk's
    scores over 32 k keys are 0.2 GB a head)."""
    Q, H, D = q.shape
    KH = k.shape[1]
    q_pos = jnp.asarray(q_pos).reshape(-1, 1)
    t_pos = jnp.arange(k.shape[0])[None, :]
    seen = t_pos <= q_pos
    if window:
        seen = seen & (t_pos > q_pos - window)
    qg = q.reshape(Q, KH, H // KH, D)
    heads = []
    with jax.default_matmul_precision("highest"):
        for g in range(KH):
            s = low(jnp.einsum("qnd,td->nqt", qg[:, g], k[:, g]) * D**-0.5)
            s = jnp.where(seen[None], s, -jnp.inf)
            p = low(jnp.exp(s - s.max(-1, keepdims=True)))
            p = low(p / low(p.sum(-1, keepdims=True)))
            heads.append(low(jnp.einsum("nqt,td->qnd", p, v[:, g])))
    return jnp.stack(heads, 1).reshape(Q, H, D)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=None, help="default: the harness's own --seed")
    ap.add_argument("--readings", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        cfg_file = json.load(f)
    with open(os.path.join(HERE, "traffic", "agent_mixed_ctx.json")) as f:
        traffic = json.load(f)

    import numpy as np

    from dynamo_tpu.engines.tpu.engine import JaxEngine, JaxEngineArgs
    from dynamo_tpu.models.laguna_reference import describe_layers
    from dynamo_tpu.ops.attention import paged_attention
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
    if rehearse:
        layers, blocking = describe_layers(config), dict(kv_group=1, query_block=64)
    else:
        layers, blocking = describe(cfg_file), BLOCKING
        assert layers == describe_layers(config), "the preset and the configuration file disagree"
        assert config.vocab_size == cfg_file["vocab_size"] and config.d_model == cfg_file["hidden_size"]
    steps, block = flag("--decode-steps"), flag("--block-size")
    engine = JaxEngine(JaxEngineArgs(
        config=config, block_size=block, num_kv_blocks=flag("--num-kv-blocks"),
        max_num_seqs=flag("--max-num-seqs"), max_model_len=flag("--max-model-len"),
        prefill_chunk=flag("--prefill-chunk"), decode_steps=steps, pipeline_depth=1))
    weights, eps = engine.runner.params, config.rms_norm_eps
    jax.block_until_ready(weights)
    seed = args.seed if args.seed is not None else harness_seed()
    if seed is None:
        seed = time.time_ns() % (1 << 32)
    window = engine.window.window
    say(f"{device.platform}/{device.device_kind}: {config.name}, {len(layers)} sublayers, engine up on its "
        f"seed-0 weights; page groups {engine.stats().get('kv_groups')}, window {window}; ids and lengths "
        f"from seed {seed}")

    # -- the requests ----------------------------------------------------------
    rng = np.random.default_rng([int(seed) % (1 << 32), 44])
    sizes = sorted(int(c["tokens"]) for c in traffic["contexts"])
    n_long, n_short = (1280, 256) if rehearse else (sizes[-1], sizes[0])
    law = traffic["turn_tokens"]
    ids = lambda n: rng.integers(16, config.vocab_size, int(n)).astype(np.int32)
    turn = lambda: ids(int(np.clip(np.exp(rng.normal(np.log(law["median"]), law["sigma"])), law["min"], law["max"])))
    ctx_long, ctx_short = ids(n_long), ids(n_short)
    prompts = {
        "build_long": ctx_long, "hit_long": np.concatenate([ctx_long, turn()]),
        "build_short": ctx_short, "hit_short": np.concatenate([ctx_short, turn()]),
        "hit_short_b": np.concatenate([ctx_short, turn()]),
    }
    long_decode = 1 + steps * 5 if rehearse else LONG_DECODE
    n_out = {"build_long": 1 + steps * 3, "hit_long": 1 + steps * int(rng.choice((2, 3, 4))),
             "build_short": 1 + steps * 3, "hit_short": long_decode,
             "hit_short_b": 1 + steps * int(rng.choice((8, 12, 16)))}
    builds, hits = ("build_long", "build_short"), ("hit_long", "hit_short", "hit_short_b")
    order = builds + hits
    p_long = n_long // block  # whole pages of the long context

    # -- the program -----------------------------------------------------------
    async def drive():
        served, reuse, batches = {}, {}, []
        rounds = engine._admitter._prefill_rounds

        def watched(pending):  # (rows, table shape) of every prefill batch
            batches.append((pending.rows, tuple(pending.tables.shape)))
            return rounds(pending)

        engine._admitter._prefill_rounds = watched
        for wave in tuple((rid,) for rid in builds) + (hits,):  # the asks at once
            computed, first = engine.prefill_tokens, len(batches)
            served.update(await asyncio.gather(*(
                serve(engine, rid, prompts[rid], n_out[rid]) for rid in wave)))
            reuse[wave[0] if len(wave) == 1 else "hits"] = (
                sum(len(prompts[rid]) for rid in wave) - (engine.prefill_tokens - computed))
        hashes = compute_block_hashes([int(t) for t in ctx_long], block, salt=0)
        matched, full_ids = engine.pool.pin_prefix(hashes)
        engine.pool.release(full_ids, hashes[:matched])
        lo = engine.window.first_live(p_long * block)
        got, win_ids = engine.window.pool.pin_prefix(hashes[lo:p_long])
        engine.window.pool.release(win_ids, hashes[lo : lo + got])
        facts = dict(preemptions=engine.preemptions, released=engine.window.released,
                     cut=engine.window.cut_hits, groups=engine.stats().get("kv_groups"),
                     hit_batches=batches[first:])
        await engine.stop()
        return served, reuse, matched, full_ids, lo, win_ids, facts

    served, reuse, matched, full_ids, win_lo, win_ids, facts = asyncio.run(drive())
    say(f"served {[(r, len(prompts[r]), n_out[r]) for r in order]}; reused {reuse}; the long context's "
        f"{matched} of {p_long} pages are resident in the full group and {len(win_ids)} of "
        f"{p_long - win_lo} of its trailing window in the window group; window pages released "
        f"{facts['released']}, prefix hits cut {facts['cut']}, preemptions {facts['preemptions']}; "
        f"the hits' prefill batches (rows, tables) {facts['hit_batches']}; page groups {facts['groups']}")
    failures = []
    if matched < p_long or len(win_ids) < p_long - win_lo:
        failures.append("the long context's pages are not resident in both page groups")
        for why in failures:
            say(f"DISAGREES: {why}")
        return 1

    # -- copy out of the pools what B, D and E need, then drop the pools -------------------------
    attn_at = [i for i, L in enumerate(layers) if L["kind"] == "attention"]
    i_full0, i_slide, i_full4 = attn_at[0], attn_at[1], attn_at[-1]
    assert not layers[i_full0]["window"] and layers[i_slide]["window"] and not layers[i_full4]["window"]
    kc, vc = engine.runner.k_cache, engine.runner.v_cache
    hd = layers[0]["head_dim"]
    pages = lambda pool, at: jnp.asarray(pool[jnp.asarray(at)])  # [n, block, KH, >= D], the pool's dtype
    full_at, win_at = np.asarray(full_ids[:p_long]), np.asarray(win_ids)
    pool_rows = {
        "k0": pages(kc[0], full_at), "v0": pages(vc[0], full_at),
        "k1": pages(kc[1], win_at), "v1": pages(vc[1], win_at),
        "k4": pages(kc[len(attn_at) - 1], full_at), "v4": pages(vc[len(attn_at) - 1], full_at),
    }
    jax.block_until_ready(pool_rows)
    use_kernel = engine.runner.use_kernel
    del kc, vc
    engine.runner.k_cache = engine.runner.v_cache = None
    flat = lambda a: jnp.asarray(a, jnp.float32)[..., :hd].reshape(-1, a.shape[2], hd)  # [T, KH, D]

    # -- A: the reference, row by row ----------------------------------------------------------
    hp = jax.default_matmul_precision("highest")
    qb = blocking["query_block"]
    n_ctx = p_long * block
    chunk = flag("--prefill-chunk")
    last = np.arange(n_ctx - chunk, n_ctx)  # D's chunk; its last D_QUERIES are D's decode rows

    def read_row(rid, degrade=None, extra=()):
        toks, prompt = served[rid][0], prompts[rid]
        n, P = len(toks), len(prompt)
        length = P + n - 1  # the tokens the program consumed: all but the last it chose
        T = -(-length // (qb * PAD_BLOCKS)) * qb * PAD_BLOCKS
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32), ids(T - length)])
        pos = np.concatenate([P - 1 + np.arange(n), np.asarray(extra, np.int64)]).astype(np.int64)
        ref = reference_forward(weights, layers, seq, eps, positions=pos, degrade=degrade, **blocking)
        with hp:
            logp = jax.nn.log_softmax(ref["logits"][:n], axis=-1)
            chosen = np.asarray(jnp.take_along_axis(logp, jnp.asarray(toks)[:, None], axis=-1)[:, 0])
        return chosen, ref

    per_row, all_steps, ref_long = {}, [], None
    for rid in order:
        chosen, ref = read_row(rid, extra=last if rid == "build_long" else ())
        if rid == "build_long":
            ref_long = {i: ref["hidden"][i][-chunk:] for i in (i_slide, i_full4)}
        del ref
        err = np.abs(np.asarray(served[rid][1]) - chosen)
        assert len(err) == n_out[rid], (rid, len(err))
        all_steps += list(err)
        per_row[rid] = dict(median=float(np.median(err)), largest=float(err.max()), chosen=chosen)
        say(f"A {rid}: {len(err)} steps at {len(prompts[rid])} tokens of prompt, median "
            f"{per_row[rid]['median']:.5f}, largest {per_row[rid]['largest']:.4f}")
    a_all = float(np.median(all_steps))
    a_row = max(per_row.items(), key=lambda kv: kv[1]["median"])
    say(f"A logprob of the chosen token, {len(all_steps)} steps of {len(per_row)} rows: median {a_all:.5f} "
        f"against {LIMIT_LOGPROB}; the worst row's median {a_row[1]['median']:.5f} ({a_row[0]}) against "
        f"{LIMIT_LOGPROB_ROW}")

    # -- B: layer 0's K and V rows in the full group's pool ------------------------------------------
    w0, L0 = weights["layers"][i_full0], _Static(layers[i_full0])
    take = min(B_SAMPLE, n_ctx // 2)
    at = np.concatenate([np.arange(take), np.arange(n_ctx - take, n_ctx)])

    def kv_rows(degrade=None):
        with hp:
            x0 = ref_rmsnorm(_f32(weights["embed"][jnp.asarray(ctx_long[at])]), w0["norm"], eps)
            k = rope_at((x0 @ _f32(w0["wk"])).reshape(len(at), -1, hd), L0, at, degrade)
            v = (x0 @ _f32(w0["wv"])).reshape(len(at), -1, hd)
        if degrade == "kv_int8":
            k, v = _int8_rows(k), _int8_rows(v)
        return k.reshape(len(at), -1), v.reshape(len(at), -1)

    k_ref, v_ref = kv_rows()
    in_pool = lambda name: flat(pool_rows[name]).reshape(n_ctx, -1)[jnp.asarray(at)]
    b_k = np.asarray(rows_rel_l2(in_pool("k0"), k_ref))
    b_v = np.asarray(rows_rel_l2(in_pool("v0"), v_ref))
    b_med, b_max = max(np.median(b_k), np.median(b_v)), max(b_k.max(), b_v.max())
    say(f"B layer 0's K and V rows, {len(at)} of {n_ctx} tokens in the full group's pool: medians {np.median(b_k):.3e} "
        f"{np.median(b_v):.3e} against {LIMIT_KV:.1e}, largest {b_k.max():.3e} {b_v.max():.3e} against "
        f"{LIMIT_KV_ROW:.1e}")

    # -- D and E: the program's paged attention over the pools' own rows ------------------------------
    def queries(i):
        """The reference's float32 queries [chunk, H, D] of sublayer ``i`` at
        the long context's last positions (rotated as there)."""
        w, L = weights["layers"][i], _Static(layers[i])
        with hp:
            x = ref_rmsnorm(ref_long[i], w["norm"], eps)
            q = (x @ _f32(w["wq"])).reshape(chunk, L["heads"], hd)
            return rope_at(q, L, last)

    def program(q, k_pages, v_pages, start, window):
        """The engine's own call over the pages given, in order. q [B, C, H,
        D]: B rows of C queries, a row's first at position ``start`` (C = 1:
        decode rows; one row of C = the chunk: a turn's prefill)."""
        B, C = q.shape[:2]
        n = k_pages.shape[0]
        table = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (B, n))
        out = paged_attention(
            q.astype(config.dtype), k_pages, v_pages, table,
            jnp.full((B,), start, jnp.int32), jnp.full((B,), C, jnp.int32),
            sm_scale=hd**-0.5, use_kernel=use_kernel, window=window)
        return jnp.asarray(out, jnp.float32).reshape(B * C, *q.shape[2:])

    degraded = (
        ("softmax_bf16 (the reference, nothing else rounded)", lambda a: a, _bf16_round),
        ("the program's roundings (queries and output to bfloat16)", _bf16_round, lambda a: a),
        ("the program's roundings + a bfloat16 softmax", _bf16_round, _bf16_round))
    view = (window + chunk - 2) // block + 2  # pages of a sliding layer's view for a chunk
    view_of = lambda name: pool_rows[name][max(p_long - view, 0):]
    view_lo = max(p_long - view, 0) * block  # the view's first position
    d_reads = {}
    for name, i, k_pages, v_pages, lo, W, as_chunk in (
        ("layer 1 (sliding)", i_slide, pool_rows["k1"], pool_rows["v1"], win_lo * block, window, False),
        ("layer 4 (full)", i_full4, pool_rows["k4"], pool_rows["v4"], 0, 0, False),
        ("layer 1's chunk (sliding, over the view's pages of layer 4's rows)", i_slide,
         view_of("k4"), view_of("v4"), view_lo, window, True),
        ("layer 4's chunk (full)", i_full4, pool_rows["k4"], pool_rows["v4"], 0, 0, True),
    ):
        q = queries(i) if as_chunk else queries(i)[-D_QUERIES:]
        # decode rows all stand at the context's last position; a chunk's at their own
        q_pos = (last if as_chunk else n_ctx - 1) - lo
        got = program(q[None] if as_chunk else q[:, None], k_pages, v_pages,
                      n_ctx - chunk - lo if as_chunk else q_pos, W)
        want = ref_rows_attention(q, flat(k_pages), flat(v_pages), q_pos, W)
        d = np.asarray(rows_rel_l2(got, want)).ravel()
        d_reads[name] = (d, LIMIT_CHUNK_ROW if as_chunk else LIMIT_ATTENTION_ROW)
        say(f"D {name}, {q.shape[0]} queries x {q.shape[1]} heads over {k_pages.shape[0]} pages "
            f"({'kernel' if use_kernel else 'xla'}): median {np.median(d):.3e} against {LIMIT_ATTENTION:.1e}, "
            f"largest {d.max():.3e} against {d_reads[name][1]:.1e}")
        if args.readings:
            for what, ends, low in degraded:
                dl = np.asarray(rows_rel_l2(ends(ref_rows_attention(
                    ends(q), flat(k_pages), flat(v_pages), q_pos, W, low)), want)).ravel()
                say(f"  {what}: D median {np.median(dl):.3e} largest {dl.max():.3e} "
                    f"99.9th {np.quantile(dl, 0.999):.3e}")

    # E: probes at both edges of the window, over EDGE_PAGES pages of the pool's rows
    e_pages = min(EDGE_PAGES, p_long)
    ek, ev = pool_rows["k4"][:e_pages], pool_rows["v4"][:e_pages]
    kf = flat(ek)
    heads = layers[i_slide]["heads"]
    t_q = e_pages * block - 1 - block // 2  # the probes' position: every key up to it exists
    if t_q - window < 0:  # (a rehearsal whose pages hold less than a window cannot probe its edge)
        t_q = e_pages * block - 1
    aims = [t_q - window + 1, max(t_q - window, 0)]  # the oldest key inside, the first outside
    per_kv = heads // kf.shape[1]

    def probes():
        rows = []
        for r in range(D_QUERIES):
            aim = aims[r % 2]
            key = kf[aim]  # [KH, D]
            sharp = EDGE_SHARPNESS / (hd**-0.5 * jnp.sum(key * key, -1, keepdims=True))
            rows.append(jnp.repeat(key * sharp, per_kv, axis=0))  # every head of a group aims alike
        return _bf16_round(jnp.stack(rows))  # [Q, H, D], what the program is given exactly

    qe = probes()
    got_e = program(qe[:, None], ek, ev, t_q, window)

    def edge(win):
        return np.asarray(rows_rel_l2(got_e, ref_rows_attention(qe, kf, flat(ev), t_q, win))).ravel()

    e_reads = edge(window)
    say(f"E probes at the window's edges (keys {aims} from position {t_q}, window {window}), "
        f"{D_QUERIES} rows x {heads} heads: largest {e_reads.max():.3e} against {LIMIT_EDGE}")

    if args.readings:
        say("second readings, each fault against the limit it must fail:")
        for w_off in (window - 1, window + 1):
            say(f"  a window of {w_off}: E largest {edge(w_off).max():.3e}")
        k8, v8 = kv_rows("kv_int8")
        say(f"  kv_int8 (the reference's own rows at 8 bits against its own): B medians "
            f"{np.median(np.asarray(rows_rel_l2(k8, k_ref))):.3e} {np.median(np.asarray(rows_rel_l2(v8, v_ref))):.3e} "
            f"largest {np.asarray(rows_rel_l2(k8, k_ref)).max():.3e} {np.asarray(rows_rel_l2(v8, v_ref)).max():.3e}")
        kr, _ = kv_rows("rope_all_lanes")
        say(f"  rope_all_lanes: B (K rows of the pool against the reference rotated on all lanes) median "
            f"{np.median(np.asarray(rows_rel_l2(in_pool('k0'), kr))):.3e}")
        for degrade in ("softmax_bf16", "kv_int8", "window_511", "window_513", "rope_all_lanes", "no_gate"):
            low, _ = read_row("hit_short", degrade)
            err = np.abs(np.asarray(served["hit_short"][1]) - low)
            moved = np.abs(low - per_row["hit_short"]["chosen"])
            say(f"  {degrade}: A of hit_short against the degraded reference: median {np.median(err):.5f} "
                f"(largest {err.max():.4f}); the reference's own log-probabilities move by a median of "
                f"{np.median(moved):.5f}")

    def hold(what, value, limit):
        if not value <= limit:  # (a NaN fails)
            failures.append(f"{what} {value:.5g} > {limit}")

    hold("A logprob, median over all steps", a_all, LIMIT_LOGPROB)
    hold(f"A logprob, row {a_row[0]}'s median", a_row[1]["median"], LIMIT_LOGPROB_ROW)
    hold("B K and V rows, median", float(b_med), LIMIT_KV)
    hold("B K and V rows, largest", float(b_max), LIMIT_KV_ROW)
    for name, (d, row_limit) in d_reads.items():
        hold(f"D {name}, median", float(np.median(d)), LIMIT_ATTENTION)
        hold(f"D {name}, largest", float(d.max()), row_limit)
    hold("E window edges, largest", float(e_reads.max()), LIMIT_EDGE)
    if reuse["build_long"] != 0 or reuse["build_short"] != 0:
        failures.append(f"a fresh context reused tokens (reused {reuse}): it was not fresh")
    if reuse["hits"] < n_long // block * block + 2 * (n_short // block * block):
        failures.append(f"a resident context was not served as a prefix hit over both page groups (reused {reuse})")
    if max(rows for rows, _ in facts["hit_batches"]) < len(hits):
        failures.append(f"the asks were not prefilled as one batch over their mixed tables ({facts['hit_batches']})")
    if facts["cut"]:
        failures.append(f"{facts['cut']} prefix hits were cut by the window group")
    if not facts["released"]:
        failures.append("no window-group page was given back while a sequence ran")
    if facts["preemptions"]:
        failures.append(f"{facts['preemptions']} preemptions")
    for why in failures:
        say(f"DISAGREES: {why}")
    say("agrees" if not failures else "does not agree")
    return 1 if failures else 0


T0 = time.monotonic()
if __name__ == "__main__":
    sys.exit(main())
