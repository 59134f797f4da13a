"""GMS-role weight survival: a SIGKILLed worker's replacement remaps
RAM-resident weights instead of re-ingesting the checkpoint.

Reference parity: lib/gpu_memory_service/README.md:1-60 — weights owned
outside the worker process so a crash costs a remap, not a reload. The
TPU-native form (models/weight_cache.py SHM tier): the engine-ready pytree
lives in tmpfs pages owned by the kernel, mmapped by whichever worker
process is alive.
"""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model_dir(tmp_path):
    import torch
    import transformers

    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64,
    )
    model = transformers.LlamaForCausalLM(cfg).eval().to(torch.float32)
    d = tmp_path / "model"
    model.save_pretrained(str(d), safe_serialization=True)
    return str(d)


def test_shm_tier_hit_without_disk(tmp_path):
    """SHM tier alone satisfies a reload (disk tier removed in between)."""
    pytest.importorskip("transformers")
    import shutil

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.weight_cache import load_checkpoint_cached

    model_dir = _model_dir(tmp_path)
    config = dataclasses.replace(
        ModelConfig.from_model_dir(model_dir), dtype=jnp.float32
    )
    disk, shm = str(tmp_path / "disk"), str(tmp_path / "shm")
    p1, hit1 = load_checkpoint_cached(
        model_dir, config, cache_dir=disk, shm_dir=shm
    )
    assert not hit1
    shutil.rmtree(disk)  # only the RAM tier remains
    p2, hit2 = load_checkpoint_cached(
        model_dir, config, cache_dir=disk, shm_dir=shm
    )
    assert hit2
    import jax

    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_killed_worker_recovers_without_reingest(tmp_path):
    """SIGKILL a serving worker; its replacement must (a) hit the RAM tier,
    (b) produce identical greedy output, (c) skip the HF ingest entirely —
    measured as a bounded load time relative to the cold path."""
    pytest.importorskip("transformers")
    model_dir = _model_dir(tmp_path)
    disk, shm = str(tmp_path / "disk"), str(tmp_path / "shm")
    script = os.path.join(REPO, "tests", "_gms_proc.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}

    # Worker 1: cold load, serves, then hangs "mid-serve" until SIGKILL.
    p1 = subprocess.Popen(
        [sys.executable, script, model_dir, disk, shm, "serve"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True,
    )
    served1 = None
    deadline = time.time() + 240
    assert p1.stdout is not None
    while time.time() < deadline:
        line = p1.stdout.readline()
        if line.startswith("SERVED "):
            served1 = json.loads(line[len("SERVED "):])
            break
    assert served1 is not None, p1.stderr.read() if p1.stderr else ""
    assert served1["hit"] is False
    os.kill(p1.pid, signal.SIGKILL)  # crash, not graceful shutdown
    p1.wait(timeout=30)

    # Worker 2: must recover from the RAM tier the dead worker left behind.
    t0 = time.perf_counter()
    out2 = subprocess.run(
        [sys.executable, script, model_dir, disk, shm, "once"],
        capture_output=True, env=env, text=True, timeout=240,
    )
    recovery_s = time.perf_counter() - t0
    assert out2.returncode == 0, out2.stderr[-4000:]
    line = [l for l in out2.stdout.splitlines() if l.startswith("SERVED ")]
    served2 = json.loads(line[0][len("SERVED "):])
    assert served2["hit"] is True, served2
    assert served2["tokens"] == served1["tokens"]
    # The ingest is the expensive part; the warm load must be well under it
    # (the bound is generous — CI noise — but a full re-ingest would blow it).
    assert served2["load_ms"] < max(served1["load_ms"], 200.0), (
        served1, served2,
    )
    # Document the measured recovery in the test log (restart-to-first-token).
    print(
        f"recovery: process restart → first token "
        f"{recovery_s:.1f}s (load {served2['load_ms']:.0f}ms, "
        f"ttft {served2['ttft_ms']:.0f}ms; cold load was "
        f"{served1['load_ms']:.0f}ms)"
    )
