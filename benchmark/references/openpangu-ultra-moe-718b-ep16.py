#!/usr/bin/env python3
"""Plain reference for ``openpangu-ultra-moe-718b-ep16`` and the comparison
that decides the cell's ``correct``.

    python3 benchmark/references/openpangu-ultra-moe-718b-ep16.py --config <file>

``run.py`` runs this as a child after the workers have gone (the chip is free
again), with the run's environment; a non-zero exit makes ``correct`` false.

**Where it runs.** A CPU rehearsal is the harness's to ask for
(``JAX_PLATFORMS=cpu`` in the environment, which ``run.py --rehearse-cpu``
sets): it compares the ``rehearse_cpu`` stand-in on a document of 640 tokens.
In every other case the first device must be the configuration's
``serving.platform`` and ``serving.device_kind``, or the child exits 2 and
compares nothing.

**What it drives.** A ``JaxEngine`` built from the cell's own worker flags, on
the worker's own seed-0 weights: admission, the block pool, the latent pool
and the runner's compiled programs (from a warm cache the worker's own
executables; ``pipeline_depth`` 1 is the one departure: the same programs,
less host overlap). Ids and lengths come from the harness's ``--seed`` (read
from its command line; ``--seed`` here overrides), under the cell's traffic
law (``benchmark/traffic/doc_qa_shared.json``): ONE document of
``documents.tokens`` (16,384) tokens, questions by the question law, answers
of 1 + 8k tokens (whole bursts), greedy, all with ``logprobs``:

* ``fresh``: document + question 1, alone: chunked prefill of the whole
  document through the latent pool (the first chunk expanded, every later one
  absorbed over the pool), then decode;
* ``again``: the same prompt: a PREFIX HIT (the engine's counters must say
  so), the question's tail as a chunk over cached latents, then decode;
* ``hit0``, ``hit1``: document + question 2 and + question 3, AT ONCE: a
  batch of question chunks over one resident document, decoded side by side.

**What it compares with.** The float32 reference below, one sequence at a
time: each served sequence (prompt + the tokens the engine chose) padded on
the right to a multiple of the query block (causal: no compared position sees
the padding). What it holds when: the engine's weights (bf16, 9.84 GB) stay
for the whole run and are the reference's source; the engine's latent pool
(2.1 GB) is read for B and D and then DROPPED, before the first full forward;
the reference then holds the hidden state [T, 7680] float32 (0.5 GB), one
sublayer's intermediate at a time, weights converted to float32 one group of
8 heads / one 2,304-wide slice of the dense FFN / one expert at a time, and
scores of 8 heads x 256 queries x T keys (0.14 GB): under 3 GB in all.

The limits, each with its reason and its two readings (the builder's chip
runs of PR 39, PERF.md section 6). Bfloat16 activations choose a different
eighth expert than the float32 reference where the eighth and ninth scores of
256 lie closer than the rounding; such a step reads tenths where the others
read hundredths. So a step is DECIDED where the reference's margin is above
``DECIDED_MARGIN`` in every expert layer, and statistics are per row:

A. ``logprob``: |served - reference| log-probability of the chosen token,
   per step, every row. Judged: the median over all steps (the precision of
   the whole path: a dropped rotary key, a wrong norm or weight moves every
   step) and EVERY row's median (a wrong page table, a stale latent page or
   a wrong position garbles that row and nobody else's).
A2. ``again``: the prefix-hit row's log-probabilities against the fresh
   row's, program against program, the median over the steps up to and at
   the one where their tokens part (until then both have consumed the same
   tokens): same weights, same latents (the hit reads the pages the fresh
   prefill wrote), only the question's tail is recomputed as a chunk over
   the pool. Judged where they share ``AGAIN_STEPS`` steps or more; the hit
   row is one of A's rows whatever they share.
B. ``latents``: relative L2 error, per token, of the FIRST layer's cache row
   (c_kv | rotary key) as it lies in the engine's pool after ``fresh``,
   against the reference's. That layer's input is the embedding: no routing
   flip reaches it, so it is tight: bfloat16 rows read 2.9e-3; an 8-bit pool
   (one scale a token) reads 7.3e-3 and fails. Judged: the median over the
   document's tokens and the largest.
D. ``attention``: the program's absorbed attention (the kernel on the chip)
   over that pool, for the document's last 8 positions and all 128 heads, on
   the REFERENCE's own absorbed queries, against the reference's expanded
   attention, its own float32 queries, over THE SAME cache rows (the pool's,
   which B has just held to the reference's own); relative L2 per (query,
   head). What D reads is then the kernel's: queries, probabilities and
   output rounded to bfloat16, and it does not move with the ids (median
   2.380e-3-2.397e-3, largest 2.71e-3-2.86e-3 over 13 seeds). A bfloat16
   softmax (scores, probabilities and their sums rounded too) on top of those
   roundings reads 3.03e-3 and 4.65e-3 and fails both limits; a dropped
   rotary key reads 0.22.
   First D was taken against the reference over ITS OWN rows: B's rounding
   then came in a second time, through each pair's softmax, and the largest
   of 1,024 pairs moved with the document's ids (3.52e-3-3.86e-3 over 18
   seeds, 4.94e-3 on seed 1230714264, against 4.6e-3: one pair whose
   softmax is the sharpest of the 1,024 read 3.10e-3 from the pool's rows
   alone, where the kernel's own share was 2.65e-3 as everywhere; PERF.md
   section 6).

``--readings`` (the builder's) also prints what the reference itself reads
against B and D when degraded (``latent_int8``, ``softmax_bf16``,
``no_rope_key``) and what A reads for ``fresh`` under each.
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
# 6 has the runs: 29 seeds on the chip for A and B, 13 for A2 and D as they are
# now, PR 39; "program" = the worker's flags as served, bfloat16 latents,
# float32 softmax state).
LIMIT_LOGPROB = 0.02  # A, median over all steps: program 0.0037-0.0070; a dropped rotary key 0.18-0.19
LIMIT_LOGPROB_ROW = 0.05  # A, every row's median: program's worst row 0.0041-0.0085; a row on another row's pages or positions has every step wrong (reasoned: no such fault was injected on the chip)
LIMIT_AGAIN = 0.02  # A2, median over the steps both rows share: program 0.0000-0.0046; reading other pages than the fresh prefill wrote would read as A's row fault
AGAIN_STEPS = 4  # A2 is judged where the two rows share this many steps (they shared 8-33 in 29 runs); under it one undecided step would be the median
LIMIT_LATENT = 5.0e-3  # B, median over the document's tokens: program 2.897e-3-2.912e-3; an 8-bit pool 7.34e-3-7.35e-3
LIMIT_LATENT_ROW = 9.0e-3  # B, every token: program's worst 5.32e-3-6.42e-3; an 8-bit pool's worst 1.24e-2; a dropped or unrotated rotary key about 0.3 (reasoned)
LIMIT_ATTENTION = 2.7e-3  # D, median over (query, head): program 2.380e-3-2.397e-3; the program's roundings emulated on the reference 2.391e-3 (the program read 2.391e-3 on that seed), with a bfloat16 softmax too 3.028e-3; the reference with a bfloat16 softmax and nothing else rounded 2.548e-3; a dropped rotary key 0.22
LIMIT_ATTENTION_ROW = 3.6e-3  # D, every (query, head): program's worst 2.711e-3-2.862e-3; the program's roundings with a bfloat16 softmax 4.649e-3 (the reference with one, nothing else rounded, 4.383e-3)
DECIDED_MARGIN = 1.0e-3
D_QUERIES = 8
# How the reference is blocked at the published widths (the result does not
# depend on it): heads a group, query positions a block, dense-FFN width a slice.
BLOCKING = dict(head_group=8, query_block=256, ffn_block=2304)

# --- reference: begin ---------------------------------------------------------
# The forward pass of a cut pangu_ultra_moe model in straightforward jax.numpy:
# float32, matmuls at "highest" precision, ONE sequence at a time, EXPANDED
# attention only (per-head keys and values made from the latents), no cache,
# no kernels, no batching. The experts are a loop over the experts held.
# Weights are converted to float32 one sublayer (one expert, one slice of the
# dense FFN's width) at a time, and attention runs in groups of heads and
# blocks of query positions (``head_group``, ``query_block``: the result does
# not depend on them), so the published widths fit beside the program under
# test at 16 k tokens.
#
# Departures from the published description, each because of the cut this
# configuration states (benchmark/configs/<name>.json) or of what its config
# leaves to the family's convention (the file's ``assumed``):
#   * only ``held`` = [lo, hi) of the routed experts exist; the router keeps
#     its full width and a token's weights are normalised over all its chosen
#     experts, absent ones included; what an absent expert would add is left
#     out, and that partial sum goes on to the next layer;
#   * the vocabulary is the first ``V`` rows of the embedding and of the head;
#   * one leading dense layer and the expert layers that follow it, as many
#     as ``layers`` describes; the multi-token-prediction module is not built;
#   * ``kv_b_proj`` is held as its key half ``w_kb`` and its value half
#     ``w_vb`` ([kv_rank, heads, width] each), and the rotary lanes pair lane
#     i with lane i + rope/2 (the repo's ``rotate_half`` layout): with seeded
#     random weights either is a relabelling;
#   * ``degrade``: None is the reference. "latent_int8" rounds the cached row
#     (c_kv | k_r) to 8 bits with one scale a token, "softmax_bf16" rounds
#     scores, probabilities and their sums to bfloat16, "no_rope_key" leaves
#     the shared rotary key out of the scores: each exists to show what a
#     lower precision or a dropped term reads against each limit.
import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16_round(a):  # (a cast pair would be optimised away)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def ref_rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def ref_rope(x, theta):
    """x [T, ..., D] at positions 0..T-1: lane i pairs with lane i + D/2."""
    T, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2)))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], -1)
    return x * cos + rot * sin


def ref_latents(x, w, L, eps, theta, degrade=None):
    """x [T, d] -> (c_kv [T, R] normed, k_r [T, rope] rotated): the row a
    cache would hold."""
    R = L["kv_rank"]
    ckr = x @ _f32(w["w_kva"])
    c_kv, k_r = ref_rmsnorm(ckr[:, :R], w["kv_norm"], eps), ref_rope(ckr[:, R:], theta)
    if degrade == "latent_int8":
        row = jnp.concatenate([c_kv, k_r], -1)
        scale = jnp.max(jnp.abs(row), -1, keepdims=True) / 127.0
        row = jnp.round(row / scale) * scale
        c_kv, k_r = row[:, :R], row[:, R:]
    return c_kv, k_r


def ref_mla(x, w, L, eps, theta, degrade=None, head_group=None, query_block=None):
    """Causal latent attention, expanded. x [T, d] -> [T, d]."""
    T = x.shape[0]
    H, dn, dr, dv = L["heads"], L["nope"], L["rope"], L["v"]
    G, QB = head_group or H, query_block or T
    assert T % QB == 0 and H % G == 0, (T, QB, H, G)
    c_q = ref_rmsnorm(x @ _f32(w["w_qa"]), w["q_norm"], eps)
    c_kv, k_r = ref_latents(x, w, L, eps, theta, degrade)
    if degrade == "no_rope_key":
        k_r = jnp.zeros_like(k_r)
    low = _bf16_round if degrade == "softmax_bf16" else (lambda a: a)
    out = jnp.zeros((T, x.shape[1]), jnp.float32)
    for h0 in range(0, H, G):  # a group of heads at a time
        q = jnp.einsum("tr,rhk->thk", c_q, _f32(w["w_qb"][:, h0 : h0 + G]))
        q_n, q_r = q[..., :dn], ref_rope(q[..., dn:], theta)
        k_n = jnp.einsum("tr,rhk->thk", c_kv, _f32(w["w_kb"][:, h0 : h0 + G]))
        v = jnp.einsum("tr,rhk->thk", c_kv, _f32(w["w_vb"][:, h0 : h0 + G]))

        def block(r0):  # QB query positions from r0 against every key
            qn = jax.lax.dynamic_slice_in_dim(q_n, r0, QB)
            qr = jax.lax.dynamic_slice_in_dim(q_r, r0, QB)
            s = jnp.einsum("qhk,thk->hqt", qn, k_n) + jnp.einsum("qhk,tk->hqt", qr, k_r)
            s = low(s * (dn + dr) ** -0.5)
            seen = jnp.arange(T)[None, :] <= (r0 + jnp.arange(QB))[:, None]
            s = jnp.where(seen[None], s, -jnp.inf)
            p = low(jnp.exp(s - s.max(-1, keepdims=True)))
            p = low(p / low(p.sum(-1, keepdims=True)))
            return low(jnp.einsum("hqt,thk->qhk", p, v))

        o = jax.lax.map(block, jnp.arange(0, T, QB)).reshape(T, -1)
        out = out + o @ _f32(w["wo"][h0 * dv : (h0 + G) * dv])
    return out


def ref_dense_ffn(x, w, L, ffn_block=None):
    """Gated-silu FFN, a slice of its width at a time. x [T, d] -> [T, d]."""
    F = w["w_up"].shape[1]
    fb = ffn_block or F
    out = jnp.zeros_like(x)
    for f0 in range(0, F, fb):
        gate = jax.nn.silu(x @ _f32(w["w_gate"][:, f0 : f0 + fb]))
        out = out + (gate * (x @ _f32(w["w_up"][:, f0 : f0 + fb]))) @ _f32(w["w_down"][f0 : f0 + fb])
    return out


def ref_route(x, w, L):
    """(chosen expert ids [T, k], their weights [T, k], the margin [T]
    between the last chosen and the first not chosen score): sigmoid scores
    choose and weigh, no correction bias, no groups."""
    s = jax.nn.sigmoid(x @ _f32(w["router_w"]))
    top, idx = jax.lax.top_k(s, L["top_k"] + 1)
    wt = top[:, : L["top_k"]]
    wt = wt / (wt.sum(-1, keepdims=True) + 1e-20) * L["scale"]
    return idx[:, : L["top_k"]], wt, top[:, L["top_k"] - 1] - top[:, L["top_k"]]


def ref_experts(x, w, L):
    """x [T, d] -> out [T, d]: the held experts' part + the shared expert."""
    idx, wt, _ = ref_route(x, w, L)
    lo, hi = L["held"]
    ffn = lambda gate, up, down: (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)

    def expert(out, e):  # the loop over the experts held, one at a time
        e_id, gate, up, down = e
        share = jnp.where(idx == e_id, wt, 0.0).sum(-1)  # [T], 0 where not chosen
        return out + share[:, None] * ffn(gate, up, down), None

    shared = ffn(w["ws_gate"], w["ws_up"], w["ws_down"])
    out, _ = jax.lax.scan(
        expert, shared, (jnp.arange(lo, hi), w["we_gate"], w["we_up"], w["we_down"]))
    return out


def reference_forward(weights, layers, tokens, eps, theta, positions=None, degrade=None,
                      head_group=None, query_block=None, ffn_block=None):
    """tokens [T] -> {"logits" [n, V] at ``positions`` (default: all),
    "hidden": the input of every sublayer at ``positions`` [n, d]}."""
    keep = jnp.arange(len(tokens)) if positions is None else jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        h = _f32(weights["embed"][jnp.asarray(tokens)])
        hidden = []
        for w, L in zip(weights["layers"], layers):
            hidden.append(h[keep])
            h = _SUBLAYER(h, w, _Static(L), eps, theta, degrade, head_group, query_block, ffn_block)
        return {"logits": _head(h[keep], weights["final_norm"], weights["lm_head"], eps),
                "hidden": hidden}


class _Static(dict):
    """A sublayer description as a static (hashable) argument: one compiled
    function per sublayer kind and sequence length, not one per call."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _sublayer(h, w, L, eps, theta, degrade, head_group, query_block, ffn_block):
    """h <- h + N_post(F(N_pre(h))): sandwich norms where ``post_norm``."""
    with jax.default_matmul_precision("highest"):
        x = ref_rmsnorm(h, w["norm"], eps)
        if L["kind"] == "mla":
            out = ref_mla(x, w, L, eps, theta, degrade, head_group, query_block)
        elif L["kind"] == "dense_ffn":
            out = ref_dense_ffn(x, w, L, ffn_block)
        else:
            out = ref_experts(x, w, L)
        if L["post_norm"]:
            out = ref_rmsnorm(out, w["post_norm"], eps)
        return h + out


# dynlint: disable=DYN001 -- the reference is not the serving path: no compile telemetry wanted, and one program per sublayer kind and length is the point
_SUBLAYER = jax.jit(_sublayer, static_argnums=(2, 3, 4, 5, 6, 7, 8))


# dynlint: disable=DYN001 -- as above
@jax.jit
def _head(h, norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return ref_rmsnorm(h, norm, eps) @ _f32(head)

# --- reference: end -----------------------------------------------------------


def describe(cfg):
    """The reference's sublayer descriptions from the configuration FILE."""
    mla = dict(kind="mla", heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
               kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
               v=cfg["v_head_dim"], post_norm=bool(cfg["sandwich_norm"]))
    out = []
    for i in range(int(cfg["num_hidden_layers"])):
        out.append(dict(mla))
        if i < int(cfg["first_k_dense_replace"]):
            out.append(dict(kind="dense_ffn", post_norm=bool(cfg["sandwich_norm"])))
        else:
            out.append(dict(kind="experts", top_k=cfg["num_experts_per_tok"],
                            scale=float(cfg["routed_scaling_factor"]), held=tuple(cfg["experts_held"]),
                            post_norm=bool(cfg["sandwich_norm"])))
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


async def serve(engine, wave):
    """``wave`` [(id, prompt, n)] through the engine at once, greedy, with
    logprobs. Returns {id: (tokens, log-probabilities of the chosen tokens)}."""
    from dynamo_tpu.llm.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.context import Context

    async def one(rid, prompt, n):
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

    return dict(await asyncio.gather(*(one(*w) for w in wave)))


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
    with open(os.path.join(HERE, "traffic", "doc_qa_shared.json")) as f:
        traffic = json.load(f)

    import numpy as np

    from dynamo_tpu.engines.tpu.engine import JaxEngine, JaxEngineArgs
    from dynamo_tpu.models.pangu_ultra_moe_reference import describe_layers
    from dynamo_tpu.ops.attention import mla_paged_attention, pad_head
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
        layers, blocking = describe_layers(config), dict(head_group=2, query_block=64, ffn_block=64)
    else:
        layers, blocking = describe(cfg_file), BLOCKING
        assert layers == describe_layers(config), "the preset and the configuration file disagree"
        assert config.vocab_size == cfg_file["vocab_size"] and config.d_model == cfg_file["hidden_size"]
    steps, block = flag("--decode-steps"), flag("--block-size")
    engine = JaxEngine(JaxEngineArgs(
        config=config, block_size=block, num_kv_blocks=flag("--num-kv-blocks"),
        max_num_seqs=flag("--max-num-seqs"), max_model_len=flag("--max-model-len"),
        prefill_chunk=flag("--prefill-chunk"), decode_steps=steps, pipeline_depth=1))
    weights, eps, theta = engine.runner.params, config.rms_norm_eps, config.rope_theta
    jax.block_until_ready(weights)
    seed = args.seed if args.seed is not None else harness_seed()
    if seed is None:
        seed = time.time_ns() % (1 << 32)
    say(f"{device.platform}/{device.device_kind}: {config.name}, {len(layers)} sublayers, engine up on its "
        f"seed-0 weights ({engine.stats().get('mla_attention')}); ids and lengths from seed {seed}")

    # -- the requests ----------------------------------------------------------
    rng = np.random.default_rng([int(seed) % (1 << 32), 39])
    n_doc = 640 if rehearse else int(traffic["documents"]["tokens"])
    law = traffic["question_tokens"]
    ids = lambda n: rng.integers(16, config.vocab_size, int(n)).astype(np.int32)
    q_len = lambda: int(np.clip(np.exp(rng.normal(np.log(law["median"]), law["sigma"])), law["min"], law["max"]))
    doc = ids(n_doc)
    bursts = (1, 2) if rehearse else (2, 3, 4)
    prompts = {rid: np.concatenate([doc, ids(q_len())]) for rid in ("fresh", "hit0", "hit1")}
    prompts["again"] = prompts["fresh"]
    n_out = {rid: 1 + steps * int(rng.choice(bursts)) for rid in prompts}
    n_out["again"] = n_out["fresh"]

    # -- the program -----------------------------------------------------------
    async def drive():
        served, reuse = {}, {}
        for wave in (["fresh"], ["again"], ["hit0", "hit1"]):
            computed = engine.prefill_tokens
            served.update(await serve(engine, [(rid, prompts[rid], n_out[rid]) for rid in wave]))
            asked = sum(len(prompts[rid]) for rid in wave)
            reuse[wave[0]] = asked - (engine.prefill_tokens - computed)
        preemptions = engine.preemptions
        hashes = compute_block_hashes([int(t) for t in doc], block, salt=0)
        matched, block_ids = engine.pool.pin_prefix(hashes)
        engine.pool.release(block_ids, hashes[:matched])
        await engine.stop()
        return served, reuse, preemptions, matched, block_ids

    served, reuse, preemptions, matched, block_ids = asyncio.run(drive())
    say(f"served fresh ({len(prompts['fresh'])} tokens, reused {reuse['fresh']}), asked again: reused "
        f"{reuse['again']}, two hits at once: reused {reuse['hit0']} of {len(prompts['hit0']) + len(prompts['hit1'])}; "
        f"outputs {[n_out[r] for r in ('fresh', 'hit0', 'hit1')]}; the document's {matched} pages are resident; "
        f"preemptions {preemptions}")

    # -- B and D: the first layer's pool against the reference's latents ----------------------
    hp = jax.default_matmul_precision("highest")
    w0, L0 = weights["layers"][0], _Static(layers[0])
    pool0 = engine.runner.k_cache[0]
    R, dr, dn, H = L0["kv_rank"], L0["rope"], L0["nope"], L0["heads"]
    n_pages = n_doc // block
    assert matched >= n_pages, f"only {matched} of the document's {n_pages} pages are resident"
    table = np.zeros((1, flag("--max-model-len") // block), np.int32)
    table[0, :n_pages] = block_ids[:n_pages]
    in_pool = jnp.asarray(pool0[jnp.asarray(block_ids[:n_pages])], jnp.float32).reshape(n_doc, -1)[:, : R + dr]
    seen = jnp.arange(n_doc)[None] <= (n_doc - D_QUERIES + jnp.arange(D_QUERIES))[:, None]
    with hp:
        x0 = ref_rmsnorm(_f32(weights["embed"][jnp.asarray(doc)]), w0["norm"], eps)
        c_q = ref_rmsnorm(x0 @ _f32(w0["w_qa"]), w0["q_norm"], eps)

    def cache_rows(degrade=None):
        """The reference's own cache rows [n_doc, R + rope] of the first layer."""
        with hp:
            return jnp.concatenate(ref_latents(x0, w0, L0, eps, theta, degrade), -1)

    def attention(rows, degrade=None):
        """(o_lat [Q, H, R], absorbed queries [Q, H, R + rope]): the
        reference's first layer, EXPANDED, its own float32 queries for the
        document's last ``D_QUERIES`` positions over the cache ``rows``."""
        with hp:
            c_kv, k_r = rows[:, :R], rows[:, R:]
            k_rs = jnp.zeros_like(k_r) if degrade == "no_rope_key" else k_r
            low = _bf16_round if degrade == "softmax_bf16" else (lambda a: a)
            outs, q_abs = [], []
            for h0 in range(0, H, blocking["head_group"]):
                sl = slice(h0, h0 + blocking["head_group"])
                q = jnp.einsum("tr,rhk->thk", c_q, _f32(w0["w_qb"][:, sl]))
                q_n, q_r = q[-D_QUERIES:, :, :dn], ref_rope(q[..., dn:], theta)[-D_QUERIES:]
                w_kb = _f32(w0["w_kb"][:, sl])
                k_n = jnp.einsum("tr,rhk->thk", c_kv, w_kb)
                s = jnp.einsum("qhk,thk->hqt", q_n, k_n) + jnp.einsum("qhk,tk->hqt", q_r, k_rs)
                s = low(s * (dn + dr) ** -0.5)
                s = jnp.where(seen[None], s, -jnp.inf)
                p = low(jnp.exp(s - s.max(-1, keepdims=True)))
                p = low(p / low(p.sum(-1, keepdims=True)))
                outs.append(low(jnp.einsum("hqt,tr->qhr", p, c_kv)))
                q_abs.append(jnp.concatenate([jnp.einsum("qhk,rhk->qhr", q_n, w_kb), q_r], -1))
            return jnp.concatenate(outs, 1), jnp.concatenate(q_abs, 1)

    rows_ref = cache_rows()
    b_rows = np.asarray(rows_rel_l2(in_pool, rows_ref))
    o_ref, q_abs = attention(in_pool)
    got = mla_paged_attention(
        pad_head(q_abs.astype(config.dtype)[None], pool0.shape[-1]), pool0, jnp.asarray(table),
        jnp.asarray([n_doc - D_QUERIES], jnp.int32), jnp.asarray([D_QUERIES], jnp.int32),
        v_width=R, sm_scale=(dn + dr) ** -0.5, use_kernel=engine.runner.use_kernel)[0]
    d_rows = np.asarray(rows_rel_l2(got, o_ref)).ravel()
    say(f"B latent rows of the first layer, {n_doc} tokens in the pool: median {np.median(b_rows):.3e} "
        f"against {LIMIT_LATENT:.1e}, largest {b_rows.max():.3e} against {LIMIT_LATENT_ROW:.1e}")
    say(f"D absorbed attention over the pool ({'kernel' if engine.runner.use_kernel else 'xla'}), "
        f"{D_QUERIES} queries x {H} heads: median {np.median(d_rows):.3e} against {LIMIT_ATTENTION:.1e}, "
        f"largest {d_rows.max():.3e} against {LIMIT_ATTENTION_ROW:.1e}")
    if args.readings:
        say("second readings (B: the reference's own rows, degraded, against its own; D: over the pool's rows, "
            "against the reference over the pool's rows):")
        b_low = np.asarray(rows_rel_l2(cache_rows("latent_int8"), rows_ref))
        say(f"  latent_int8: B median {np.median(b_low):.3e} largest {b_low.max():.3e}")
        for degrade in ("softmax_bf16", "no_rope_key"):
            d_low = np.asarray(rows_rel_l2(attention(in_pool, degrade)[0], o_ref))
            say(f"  {degrade} (the reference, nothing else rounded): D median {np.median(d_low):.3e} "
                f"largest {d_low.max():.3e}")
        # What a PROGRAM with a bfloat16 softmax would read: the program's own
        # roundings (absorbed queries, probabilities and the output to
        # bfloat16, float32 sums), with and without scores, probabilities and
        # their sums rounded too. The first must land on the program's D.
        def program_like(softmax_low):
            low = _bf16_round if softmax_low else (lambda a: a)
            with hp:
                s = low(jnp.einsum("qhw,tw->hqt", _bf16_round(q_abs), in_pool) * (dn + dr) ** -0.5)
                s = jnp.where(seen[None], s, -jnp.inf)
                p = low(jnp.exp(s - s.max(-1, keepdims=True)))
                p = low(p / low(p.sum(-1, keepdims=True)))
                return _bf16_round(jnp.einsum("hqt,tr->qhr", _bf16_round(p), in_pool[:, :R]))

        for name, softmax_low in (("the program's roundings", False),
                                  ("the program's roundings + a bfloat16 softmax", True)):
            d_like = np.asarray(rows_rel_l2(program_like(softmax_low), o_ref))
            say(f"  {name}: D median {np.median(d_like):.3e} largest {d_like.max():.3e}")
        d_was = np.asarray(rows_rel_l2(got, attention(rows_ref)[0]))
        say(f"  B's roundings and D's together (the kernel over the pool against the reference over ITS OWN "
            f"rows, as D was first judged): median {np.median(d_was):.3e} largest {d_was.max():.3e}")
    # The pool has been read: drop it before the full forwards.
    del pool0
    engine.runner.k_cache = engine.runner.v_cache = None

    # -- A: the reference, row by row ----------------------------------------------------------
    experts_at = [i for i, L in enumerate(layers) if L["kind"] == "experts"]
    qb = blocking["query_block"]

    def read_row(rid, degrade=None):
        toks, prompt = served[rid][0], prompts[rid]
        n, P = len(toks), len(prompt)
        length = P + n - 1  # the tokens the program consumed: all but the last it chose
        T = -(-length // qb) * qb
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32), ids(T - length)])
        pos = P - 1 + np.arange(n)  # step t is predicted at P - 1 + t
        ref = reference_forward(weights, layers, seq, eps, theta, positions=pos, degrade=degrade,
                                **blocking)
        with hp:
            logp = jax.nn.log_softmax(ref["logits"], axis=-1)
            chosen = np.asarray(jnp.take_along_axis(logp, jnp.asarray(toks)[:, None], axis=-1)[:, 0])
            decided = np.ones(n, bool)
            for i in experts_at:
                w = weights["layers"][i]
                x = ref_rmsnorm(ref["hidden"][i], w["norm"], eps)
                decided &= np.asarray(ref_route(x, w, _Static(layers[i]))[2]) > DECIDED_MARGIN
        return chosen, decided

    per_row, all_steps = {}, []
    for rid in ("fresh", "again", "hit0", "hit1"):
        chosen, decided = read_row(rid)
        err = np.abs(np.asarray(served[rid][1]) - chosen)
        assert len(err) == n_out[rid], (rid, len(err))
        all_steps += list(err)
        per_row[rid] = dict(median=float(np.median(err)), largest=float(err.max()),
                            decided=float(decided.mean()),
                            decided_max=float(np.where(decided, err, 0).max()), chosen=chosen)
        say(f"A {rid}: {len(err)} steps at {len(prompts[rid])} tokens of prompt, median "
            f"{per_row[rid]['median']:.5f}, largest {per_row[rid]['largest']:.4f} (decided "
            f"{per_row[rid]['decided_max']:.4f}), decided share {per_row[rid]['decided']:.2f}")
    a_all = float(np.median(all_steps))
    a_row = max(per_row.items(), key=lambda kv: kv[1]["median"])
    (tok_a, lp_a), (tok_f, lp_f) = served["again"], served["fresh"]
    # Up to AND AT the step where their tokens part, both rows have consumed
    # the same tokens; there, each chose its own largest log-probability, and
    # two largest differ by no more than the rows do anywhere.
    shared = min(len(tok_a), 1 + next((t for t, (x, y) in enumerate(zip(tok_a, tok_f)) if x != y), len(tok_a)))
    a2 = float(np.median(np.abs(np.subtract(lp_a[:shared], lp_f[:shared]))))
    say(f"A logprob of the chosen token, {len(all_steps)} steps of {len(per_row)} rows: median {a_all:.5f} "
        f"against {LIMIT_LOGPROB}; the worst row's median {a_row[1]['median']:.5f} ({a_row[0]}) against "
        f"{LIMIT_LOGPROB_ROW}")
    say(f"A2 the prefix hit against the fresh serving of one prompt, median over the {shared} steps they "
        f"share: {a2:.5f} against {LIMIT_AGAIN}"
        + ("" if shared >= AGAIN_STEPS else f": NOT judged under {AGAIN_STEPS} steps (A holds the row)"))
    if args.readings:
        for degrade in ("latent_int8", "softmax_bf16", "no_rope_key"):
            low, _ = read_row("fresh", degrade)
            say(f"  {degrade}: the reference's own log-probabilities of fresh's tokens move by a median of "
                f"{float(np.median(np.abs(low - per_row['fresh']['chosen']))):.5f} "
                f"(largest {float(np.abs(low - per_row['fresh']['chosen']).max()):.4f})")

    failures = []

    def hold(what, value, limit):
        if not value <= limit:  # (a NaN fails)
            failures.append(f"{what} {value:.5g} > {limit}")

    hold("A logprob, median over all steps", a_all, LIMIT_LOGPROB)
    hold(f"A logprob, row {a_row[0]}'s median", a_row[1]["median"], LIMIT_LOGPROB_ROW)
    if shared >= AGAIN_STEPS:
        hold("A2 the prefix hit against the fresh serving", a2, LIMIT_AGAIN)
    hold("B latent rows, median", float(np.median(b_rows)), LIMIT_LATENT)
    hold("B latent rows, largest", float(b_rows.max()), LIMIT_LATENT_ROW)
    hold("D absorbed attention, median", float(np.median(d_rows)), LIMIT_ATTENTION)
    hold("D absorbed attention, largest", float(d_rows.max()), LIMIT_ATTENTION_ROW)
    if reuse["fresh"] != 0:
        failures.append(f"the fresh document reused {reuse['fresh']} tokens: it was not fresh")
    if reuse["again"] < n_pages * block or reuse["hit0"] < 2 * n_pages * block:
        failures.append(f"a repeated document was not served as a prefix hit (reused {reuse})")
    if preemptions:
        failures.append(f"{preemptions} preemptions")
    for why in failures:
        say(f"DISAGREES: {why}")
    say("agrees" if not failures else "does not agree")
    return 1 if failures else 0


T0 = time.monotonic()
if __name__ == "__main__":
    sys.exit(main())
