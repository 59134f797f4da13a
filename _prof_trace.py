"""Capture a REAL device trace of the llama-3-8B int8 decode step and print
the per-op time breakdown (r5 VERDICT item 2: resolve where the missing HBM
bandwidth goes; don't design the megakernel blind).

Usage: python _prof_trace.py [outdir]   (env PB/PBS/PCTX/PSTEPS as _prof_8b)
"""
import glob
import gzip
import json
import os
import sys
import time
import collections

import numpy as np
import jax
import jax.numpy as jnp

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import llama3_8b_config
from dynamo_tpu.models.quantize import init_quantized_params, quantize_params

cfg = llama3_8b_config()
print("backend", jax.default_backend(), flush=True)

B = int(os.environ.get("PB", 64))
BS = int(os.environ.get("PBS", 128))
CTX = int(os.environ.get("PCTX", 160))
P = (CTX + 1 + BS - 1) // BS
NB = max(B * P + 8, 192 * 128 // BS)
STEPS = int(os.environ.get("PSTEPS", 16))
OUT = sys.argv[1] if len(sys.argv) > 1 else "/tmp/trace_8b"

params = init_quantized_params(cfg, 0)
axes = llama.param_logical_axes(cfg)
params, _ = quantize_params(params, axes)
k, v = llama.init_kv_cache(cfg, NB, BS, layered=True, kv_dtype=None)
rng0 = np.random.default_rng(0)
tables = jnp.asarray(rng0.permutation(NB)[: B * P].reshape(B, P).astype(np.int32))
tok = jnp.ones((B,), jnp.int32)
pos = jnp.full((B,), CTX, jnp.int32)
act = jnp.ones((B,), jnp.int32)
rng = jax.random.PRNGKey(1)
temp = jnp.ones((B,), jnp.float32)
topk = jnp.zeros((B,), jnp.int32)
topp = jnp.full((B,), 0.95, jnp.float32)


def f(p_, k_, v_):
    return llama.decode_multi(
        p_, cfg, tok, pos, act, tables, k_, v_, rng, temp, topk, topp,
        num_steps=STEPS, use_kernel=True, want_logprobs=False,
    )


fn = jax.jit(f, donate_argnums=(1, 2))

# Warm (compile + first dispatch), then trace one timed call.
out = fn(params, k, v)
k, v = out[-2], out[-1]
_ = np.asarray(out[0])
out = fn(params, k, v)
k, v = out[-2], out[-1]
_ = np.asarray(out[0])

t0 = time.perf_counter()
with jax.profiler.trace(OUT):
    out = fn(params, k, v)
    k, v = out[-2], out[-1]
    _ = np.asarray(out[0])
wall = time.perf_counter() - t0
print(f"traced call: {wall*1000:.1f} ms wall, {wall/STEPS*1000:.2f} ms/step", flush=True)

# ---- parse ----
paths = sorted(glob.glob(os.path.join(OUT, "plugins/profile/*/*.trace.json.gz")))
path = paths[-1]
d = json.load(gzip.open(path))
ev = d["traceEvents"]

# Find the TPU device pid.
pid_name = {}
for e in ev:
    if e.get("ph") == "M" and e.get("name") == "process_name":
        pid_name[e["pid"]] = e["args"]["name"]
tpu_pids = {p for p, n in pid_name.items() if "TPU" in n}
print("device tracks:", {p: n for p, n in pid_name.items()}, flush=True)

dev = [e for e in ev if e.get("ph") == "X" and e.get("pid") in tpu_pids]
total = sum(e.get("dur", 0) for e in dev)
by_name = collections.Counter()
counts = collections.Counter()
for e in dev:
    by_name[e["name"]] += e.get("dur", 0)
    counts[e["name"]] += 1
print(f"\ndevice events: {len(dev)}, total device-op time {total/1e3:.2f} ms "
      f"({total/1e3/STEPS:.3f} ms/step)\n")
print(f"{'us total':>10} {'us/step':>9} {'n':>5}  name")
for n, us in by_name.most_common(40):
    print(f"{us:>10} {us/STEPS:>9.1f} {counts[n]:>5}  {n}")

# Span of device activity vs sum of op durations => gaps (scheduling bubbles).
if dev:
    t_start = min(e["ts"] for e in dev)
    t_end = max(e["ts"] + e.get("dur", 0) for e in dev)
    span = t_end - t_start
    print(f"\ndevice busy {total/1e3:.2f} ms over span {span/1e3:.2f} ms "
          f"-> occupancy {total/max(span,1):.2%}")
