"""Restart benchmark: SIGKILL a serving worker → replacement's first token.

The chrek role (ref: deploy/chrek/pkg/checkpoint/criu.go:1 — process-image
checkpoint so a worker restart skips cold init). A TPU worker's process
image cannot be CRIU'd meaningfully (HBM state dies with the process), so
warm restart here is the sum of the framework's durable tiers, and this
bench puts ONE NUMBER on it:

  cold  = fresh spawn: HF safetensors ingest + every jit compile
  warm  = replacement spawn after SIGKILL: weights mmap'd from the tmpfs
          tier (models/weight_cache.py — the GMS role), jit compiles served
          from the persistent XLA compilation cache, KV restored from the
          checkpoint when one exists (engines/tpu/kv_checkpoint.py)

Usage:
  python -m dynamo_tpu.bench.restart --model-dir /path/to/hf-model
  → one JSON line {"cold_s", "warm_s", "speedup", ...}

The measured interval is spawn→first-token: it includes process start,
jax init, weight load, engine build, prefill+decode compile, and the
first generated token — the full kill→recovery a supervisor sees.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time
from typing import Optional

from dynamo_tpu.utils.jax_env import (
    COMPILE_CACHE_ENV,
    compile_cache_dir,
    configure_compile_cache,
)


def _worker_body(model_dir: str, workdir: str) -> None:
    """Subprocess: load via the tiered cache, serve one token, report,
    then hold (the parent SIGKILLs us — crash, not graceful exit)."""
    import dataclasses

    import jax

    configure_compile_cache()  # the parent placed it (run())

    import asyncio

    import jax.numpy as jnp

    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.weight_cache import load_checkpoint_cached
    from dynamo_tpu.runtime.context import Context

    config = ModelConfig.from_model_dir(model_dir)
    if jax.default_backend() == "cpu":
        config = dataclasses.replace(config, dtype=jnp.float32)
    t_load0 = time.perf_counter()
    params, hit = load_checkpoint_cached(
        model_dir, config,
        cache_dir=os.path.join(workdir, "disk"),
        shm_dir=os.path.join(workdir, "shm"),
    )
    load_s = time.perf_counter() - t_load0
    engine = JaxEngine(
        JaxEngineArgs(
            config=config, block_size=16, num_kv_blocks=64, max_num_seqs=2,
            max_model_len=256, decode_steps=4,
        ),
        params,
    )

    async def first_token() -> float:
        req = PreprocessedRequest(
            token_ids=[5, 6, 7, 8, 9], request_id="restart-bench",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=2, ignore_eos=True),
        )
        async for out in engine.generate(req, Context()):
            if out.token_ids:
                return time.perf_counter()
        raise RuntimeError("no token produced")

    t_tok = asyncio.run(first_token())
    print(
        "READY "
        + json.dumps(
            {"weights_hit": hit, "load_s": round(load_s, 3),
             "token_at": t_tok}
        ),
        flush=True,
    )
    signal.pause()  # hold until the parent SIGKILLs us


def _spawn_and_time(model_dir: str, workdir: str, compile_cache: str) -> dict:
    """Spawn one worker, wait for its first token, return timings. The
    returned process is already SIGKILLed (crash semantics)."""
    env = {
        **os.environ,
        "PYTHONPATH": os.pathsep.join(sys.path),
        COMPILE_CACHE_ENV: compile_cache,
    }
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.bench.restart",
         "--worker", model_dir, workdir],
        stdout=subprocess.PIPE, env=env, text=True, bufsize=1,
    )
    info: Optional[dict] = None
    assert proc.stdout is not None
    # readline() blocks forever on a silent hung worker — read from a
    # thread so the 600s bound is real.
    import queue as _queue
    import threading

    lines: _queue.Queue = _queue.Queue()

    def _reader():
        for line in proc.stdout:
            lines.put(line)
        lines.put(None)

    threading.Thread(target=_reader, daemon=True).start()
    deadline = time.time() + 600
    while time.time() < deadline:
        try:
            line = lines.get(timeout=5)
        except _queue.Empty:
            continue
        if line is None:
            break
        if line.startswith("READY "):
            info = json.loads(line[len("READY "):])
            break
    elapsed = time.perf_counter() - t0
    proc.kill()  # SIGKILL: the crash the warm path must recover from
    proc.wait(timeout=30)
    if info is None:
        raise RuntimeError("worker never produced a token")
    return {
        "spawn_to_first_token_s": round(elapsed, 3),
        "weights_hit": info["weights_hit"],
        "weight_load_s": info["load_s"],
    }


def run(model_dir: str, workdir: str) -> dict:
    os.makedirs(workdir, exist_ok=True)
    # The cold leg needs an EMPTY compile cache and the warm leg the cold
    # leg's: a fixed sub-directory of the one cache location, emptied
    # here, handed to both workers through the variable.
    compile_cache = os.path.join(compile_cache_dir(), "restart_bench")
    shutil.rmtree(compile_cache, ignore_errors=True)
    cold = _spawn_and_time(model_dir, workdir, compile_cache)
    warm = _spawn_and_time(model_dir, workdir, compile_cache)
    assert not cold["weights_hit"] and warm["weights_hit"], (cold, warm)
    return {
        "metric": "kill-to-first-token recovery",
        "cold_s": cold["spawn_to_first_token_s"],
        "warm_s": warm["spawn_to_first_token_s"],
        "speedup": round(
            cold["spawn_to_first_token_s"]
            / max(warm["spawn_to_first_token_s"], 1e-9),
            2,
        ),
        "cold_weight_load_s": cold["weight_load_s"],
        "warm_weight_load_s": warm["weight_load_s"],
    }


def main() -> None:
    if len(sys.argv) >= 4 and sys.argv[1] == "--worker":
        _worker_body(sys.argv[2], sys.argv[3])
        return
    import argparse
    import tempfile

    ap = argparse.ArgumentParser("restart bench")
    ap.add_argument("--model-dir", required=True)
    ap.add_argument(
        "--workdir", default=None,
        help="weight cache root (shm/disk tiers); a warm workdir from a "
        "previous run makes even the 'cold' leg's weight load warm",
    )
    args = ap.parse_args()
    workdir = args.workdir or tempfile.mkdtemp(prefix="restart-bench-")
    print(json.dumps(run(args.model_dir, workdir)))


if __name__ == "__main__":
    main()
