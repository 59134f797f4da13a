"""Speculative-decoding break-even measurement.

Real-checkpoint acceptance cannot be measured in this environment (zero
egress: no real weights exist, and random weights drive prompt-lookup
acceptance to ~0 by construction). What
CAN be measured on hardware is the COST side, which fixes the break-even
acceptance rate any real deployment needs:

  plain:  one fused decode step emits 1 token/seq in t_decode
  spec:   one verify step over [B, k+1] emits (1 + accepted) tokens/seq
          in t_verify (+ host proposal overhead, measured separately)

  spec wins  ⇔  E[accepted] > t_verify / t_decode - 1

Usage (real chip):
  python -m dynamo_tpu.bench.spec_breakeven --model llama3-8b --quant int8
  → JSON {t_decode_ms, t_verify_ms, k, break_even_acceptance, ...}

Ref: the reference's engines expose spec decode as a config lever
(docs per-engine spec-decode guidance); engines/tpu/spec.py is the
local implementation this prices.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _time_readback(arr) -> float:
    t0 = time.perf_counter()
    _ = np.asarray(arr)
    return time.perf_counter() - t0


def measure(model: str = "llama3-8b", quant: str | None = "int8",
            batch: int = 64, ctx: int = 160, spec_k: int = 4,
            block_size: int = 128, iters: int = 16) -> dict:
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.utils.jax_env import configure_compile_cache

    configure_compile_cache()

    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.engines.tpu.engine import JaxEngineArgs
    from dynamo_tpu.models.config import (
        llama3_8b_config,
        qwen2_500m_config,
        tiny_config,
    )

    cfg = {
        "llama3-8b": llama3_8b_config,
        "qwen2.5-0.5b": qwen2_500m_config,
        "tiny": tiny_config,
    }[model]()
    P = (ctx + spec_k + block_size) // block_size + 1
    args = JaxEngineArgs(
        config=cfg, block_size=block_size, num_kv_blocks=batch * P + 8,
        max_num_seqs=batch, max_model_len=P * block_size,
        decode_steps=iters, quantization=quant,
    )
    runner = DeviceRunner(args)
    rng = np.random.default_rng(0)
    NB = args.num_kv_blocks
    tables = rng.permutation(NB - 1)[: batch * P].reshape(batch, P).astype(
        np.int32
    )
    pos = np.full((batch,), ctx, np.int32)
    toks = np.ones((batch,), np.int32)
    ones = np.ones((batch,), np.int32)
    temp = np.zeros((batch,), np.float32)
    topk = np.zeros((batch,), np.int32)
    topp = np.ones((batch,), np.float32)

    # Time at the jit level with ONE readback per timed loop. Measure
    # what the closing readback costs and subtract it, so the per-sample
    # cost does not depend on the loop count (it otherwise inflates the
    # short verify loop far more than the long decode loop).
    probe = jnp.zeros((8,), jnp.int32)
    _ = np.asarray(probe)
    t_rtt = min(
        _time_readback(probe) for _ in range(3)
    )

    def time_loop(fn, n, read):
        out = fn()  # compile
        _ = np.asarray(read(out))  # drain the compile+warmup dispatch
        out = fn()  # warm steady-state
        _ = np.asarray(read(out))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        _ = np.asarray(read(out))
        return max(time.perf_counter() - t0 - t_rtt, 1e-9) / n

    d = jnp.asarray
    salts = np.zeros((batch,), np.int32)
    # Cache key matches the runner's dispatcher: (want_logprobs,
    # use_procs) — the decode program the serving path actually
    # dispatches for plain greedy bursts.
    dec_key = (False, False)
    dec_fn = runner._decode_state_fns.get(dec_key)
    if dec_fn is None:
        dec_fn = runner._build_decode_fn()
        runner._decode_state_fns[dec_key] = dec_fn

    # The state-path decode program donates tokens/pos (the carry), so
    # hand it FRESH device copies each call — pos stays constant across
    # timed iterations (constant attention work), unlike threading the
    # advancing carry.
    def dec_call():
        out = dec_fn(
            runner.params, runner.lora, runner.k_cache, runner.v_cache,
            d(toks), d(pos), d(ones), d(tables),
            d(salts), runner.rng, d(temp), d(topk), d(topp),
            d(np.zeros((batch,), np.int32)),
        )
        # out = (toks, logp, k, v, carry_tok, carry_pos)
        runner.k_cache, runner.v_cache = out[2], out[3]
        return out

    t_decode = time_loop(dec_call, 3, lambda o: o[0]) / iters

    # spec verify: ONE [B, k+1] forward + rejection-sampling acceptance at
    # every position (greedy rows degrade to argmax verify inside the same
    # program)
    ver_toks = np.ones((batch, spec_k + 1), np.int32)
    lens = np.full((batch,), spec_k + 1, np.int32)
    if runner._spec_fn is None:
        runner._spec_fn = runner._build_spec_fn()

    def mk_ver_call(vtemp):
        vt = d(np.full((batch,), vtemp, np.float32))

        def ver_call():
            out = runner._spec_fn(
                runner.params, runner.lora, runner.k_cache, runner.v_cache,
                d(ver_toks), d(pos), d(lens), d(tables), None,
                runner.rng, np.int32(2), vt, d(topk), d(topp),
            )
            runner.k_cache, runner.v_cache = out[-2], out[-1]
            return out

        return ver_call

    t_verify = time_loop(mk_ver_call(0.0), 8, lambda o: o[0])

    # sampled-mode probe: the same verify program with temperature>0 rows.
    # Proposals here are the model's own greedy continuations, so the
    # accepted-token count shows how much of the greedy acceptance a
    # sampled deployment retains at this temperature (rejection sampling
    # accepts proposal x with prob p(x) — r5, VERDICT item 7).
    t_verify_sampled = time_loop(mk_ver_call(0.8), 8, lambda o: o[0])
    greedy_emit, greedy_counts = runner.run_spec(
        ver_toks, pos, lens, tables, None,
    )
    # Proposals = each row's VALID greedy-verify emissions; positions past
    # counts[i] are zero padding, not model tokens, so pad by repeating the
    # last valid token (repeats depress tail acceptance — the column is a
    # lower bound on sampled acceptance of greedy-quality proposals).
    sampled_props = np.zeros((batch, spec_k), np.int32)
    for i in range(batch):
        n = max(int(greedy_counts[i]), 1)
        row = greedy_emit[i, :n]
        sampled_props[i, :min(n, spec_k)] = row[:spec_k]
        if n < spec_k:
            sampled_props[i, n:] = row[n - 1]
    sp_toks = np.concatenate(
        [ver_toks[:, :1], sampled_props], axis=1
    ).astype(np.int32)
    _em, sp_counts = runner.run_spec(
        sp_toks, pos, lens, tables, None,
        temp=np.full((batch,), 0.8, np.float32),
        topk=topk, topp=topp,
    )
    sampled_accepted = float(np.mean(sp_counts - 1))

    # host proposal cost: the same index+lookup NgramSpecDecoder.propose
    # runs per sequence per tick (engines/tpu/spec.py:41), standalone
    hist = rng.integers(0, 1000, size=512).tolist()
    n = 3

    def propose_once():
        index = {}
        for p in range(n - 1, len(hist) - 1):
            index[tuple(hist[p - n + 1 : p + 1])] = p + 1
        cont = index.get(tuple(hist[-n:]))
        return hist[cont : cont + spec_k] if cont is not None else []

    t0 = time.perf_counter()
    for _ in range(200):
        propose_once()
    t_proposal = (time.perf_counter() - t0) / 200

    be = t_verify / t_decode - 1.0
    return {
        "metric": "speculative-decode break-even",
        "model": cfg.name,
        "quant": quant,
        "batch": batch,
        "ctx": ctx,
        "spec_k": spec_k,
        "t_decode_ms_per_token_step": round(t_decode * 1000, 3),
        "t_verify_ms": round(t_verify * 1000, 3),
        "t_proposal_us": round(t_proposal * 1e6, 1),
        "verify_over_decode": round(t_verify / t_decode, 3),
        # spec emits (1 + accepted) tokens per verify; plain emits
        # t_verify/t_decode tokens in the same wall time
        "break_even_accepted_tokens": round(be, 3),
        "break_even_acceptance_rate": round(max(be, 0.0) / spec_k, 3),
        "t_verify_sampled_ms": round(t_verify_sampled * 1000, 3),
        "sampled_accepted_of_greedy_props": round(sampled_accepted, 3),
        "backend": jax.default_backend(),
    }


def main() -> None:
    ap = argparse.ArgumentParser("spec break-even")
    ap.add_argument("--model", default="llama3-8b")
    ap.add_argument("--quant", default="int8")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ctx", type=int, default=160)
    ap.add_argument("--spec-k", type=int, default=4)
    args = ap.parse_args()
    print(
        json.dumps(
            measure(
                args.model, args.quant or None, args.batch, args.ctx,
                args.spec_k,
            )
        )
    )


if __name__ == "__main__":
    main()
