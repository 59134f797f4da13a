"""The start-up ladder's prefill programs timed on the chip, beside their price.

What ``admission.PrefillPrice``'s two constants were fitted with (PERF.md §5
has the table, PRs 52-53). For an engine built from a worker's own flags, every
fresh-prompt ladder program ``[rows bucket, chunk bucket]`` is run through
``engine._run_step`` with EVERY ROW FULL of random tokens over pages of its
own (an empty step reads low: the expert kernels skip dead rows, and equal
tokens all choose the same experts), the median of ``--reps`` wall times of
the step with its readback, which is what ``tick.prefill_wait`` awaits; a
hybrid model's state programs (``ssm_begin``, ``ssm_install``) beside it.
Then the line through the times over the positions ``T``, every program's
relative error counting alike: its slope against a position's products at
the bf16 peak is ``PREFILL_PEAK_SHARE``, what its intercept leaves over what
the smallest program streams is ``DISPATCH_BYTES`` (PR 53: dense 0.5B 0.37
and 1.55 GB, hybrid 0.23 and 1.31 GB: the constants sit between the two).
Run it through ``chiprun`` with a cell's worker flags:

    python -m dynamo_tpu.engines.tpu.ladder_times --model qwen2.5-0.5b \\
        --num-kv-blocks 16384 --max-num-seqs 64 --max-model-len 2048 --prefill-chunk 1024
"""

import argparse
import json
import os
import statistics
import time

import jax
import numpy as np

from dynamo_tpu.engines.tpu.engine import JaxEngine, JaxEngineArgs
from dynamo_tpu.ops.moe import RIDGE_TOKENS
from dynamo_tpu.utils.jax_env import configure_compile_cache
from dynamo_tpu.worker.__main__ import BUILTIN_CONFIGS

HBM_BYTES_PER_S = 819e9  # v5e; RIDGE_TOKENS is this chip's too


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", required=True, choices=sorted(BUILTIN_CONFIGS))
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-kv-blocks", type=int, required=True)
    ap.add_argument("--max-num-seqs", type=int, required=True)
    ap.add_argument("--max-model-len", type=int, required=True)
    ap.add_argument("--prefill-chunk", type=int, required=True)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/ladder_times")
    a = ap.parse_args()

    configure_compile_cache()
    config = BUILTIN_CONFIGS[a.model]()
    engine = JaxEngine(JaxEngineArgs(
        config=config, block_size=a.block_size, num_kv_blocks=a.num_kv_blocks,
        max_num_seqs=a.max_num_seqs, max_model_len=a.max_model_len,
        prefill_chunk=a.prefill_chunk, decode_steps=a.decode_steps))
    jax.block_until_ready(engine.runner.params)
    dev = jax.devices()[0]
    print(f"DEVICE {dev.platform} {dev.device_kind}; {config.name}", flush=True)
    adm, rng = engine._admitter, np.random.default_rng(52)

    def once(Bp: int, c: int, nb: int) -> float:
        tables = np.zeros(engine.tables_shape(Bp, nb), dtype=np.int32)
        full = tables if engine.window is None else tables[:, 0]
        for r in range(Bp):
            full[r, :] = 1 + r * nb + np.arange(nb)
        zeros = np.zeros(Bp, dtype=np.int32)
        toks = rng.integers(0, config.vocab_size, (Bp, c)).astype(np.int32)
        t0 = time.perf_counter()
        state = ()
        if config.is_hybrid:
            state = (engine.runner.ssm_begin(zeros - 1), adm._snap_dst(Bp, c))
        out = engine._run_step(
            toks, zeros, np.full(Bp, c, dtype=np.int32), tables,
            np.zeros(Bp, np.float32), zeros, np.ones(Bp, np.float32), zeros,
            None, None, None, False, True, zeros, *state)
        if config.is_hybrid:
            rows = list(range(min(Bp, a.max_num_seqs)))
            engine.runner.ssm_install(rows, out[4], rows)
            jax.block_until_ready(engine.runner.ssm_state)
        return time.perf_counter() - t0

    rows = []
    for Bp, c, nb in adm.prefill_ladder():
        once(Bp, c, nb)  # compiles
        ms = 1e3 * statistics.median(once(Bp, c, nb) for _ in range(a.reps))
        priced = 1e3 * adm.price(Bp, c) / HBM_BYTES_PER_S
        rows.append({"rows": Bp, "chunk": c, "ms": round(ms, 3), "priced_ms": round(priced, 3)})
        print("ROW " + json.dumps(rows[-1]), flush=True)

    T = np.array([r["rows"] * r["chunk"] for r in rows], dtype=float)
    ms = np.array([r["ms"] for r in rows])
    slope, intercept = np.polyfit(T, ms * 1e-3, 1, w=1.0 / ms)
    price = adm.price
    at_peak = 2.0 * price.active_weights / RIDGE_TOKENS / HBM_BYTES_PER_S  # s a position
    streamed = price.always_bytes + sum(  # by the smallest program
        held * min(1.0, T.min() * hit) for held, hit in price.experts)
    fit = {
        "us_a_position": round(slope * 1e6, 3),
        "us_a_position_at_peak": round(at_peak * 1e6, 3),
        "peak_share": round(at_peak / slope, 3),
        "dispatch_bytes": round(intercept * HBM_BYTES_PER_S - streamed),
    }
    print("FIT " + json.dumps(fit), flush=True)
    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, f"{a.model}.json"), "w") as f:
        json.dump({"device": dev.device_kind, "rows": rows, "fit": fit}, f, indent=1)
    os._exit(0)  # the engine's threads hold the process otherwise


if __name__ == "__main__":
    main()
