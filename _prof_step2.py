"""Decode-step breakdown with the layered cache (r3 layout).

Ablates the fused step: full / no-attention-kernel (XLA paged) / no-cache-
write / matmuls-only, at the bench shape, to find where the 15.2 ms/step
now lives.
"""
import os
import time
import numpy as np
import jax, jax.numpy as jnp

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config
from dynamo_tpu.ops.sampling import sample_tokens

cfg = qwen2_500m_config()
BS = 128
NB = 65536 // BS
B = 256
STEPS = 64
L = cfg.n_layers
params = llama.init_params(cfg, jax.random.PRNGKey(0))

tokens = jnp.ones((B,), jnp.int32)
start_pos = jnp.full((B,), 160, jnp.int32)
active = jnp.ones((B,), jnp.int32)
tables = jnp.asarray((np.arange(B * 4, dtype=np.int32) % NB).reshape(B, 4))
rng = jax.random.PRNGKey(1)
temp = jnp.ones((B,), jnp.float32)
topk = jnp.zeros((B,), jnp.int32)
topp = jnp.full((B,), 0.95, jnp.float32)


def timeit(name, fn, *args):
    out = fn(*args)
    state = out[-2], out[-1]
    np.asarray(jax.tree.leaves(out[0])[0])
    n = 6
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args[:-2], *state)
        state = out[-2], out[-1]
        np.asarray(jax.tree.leaves(out[0])[0])
    dt = (time.perf_counter() - t0) / n
    print(f"{name}: {dt/STEPS*1000:6.2f} ms/step ({B*STEPS/dt:7.0f} tok/s)",
          flush=True)


def make(use_kernel):
    def run(params, k, v):
        return llama.decode_multi(
            params, cfg, tokens, start_pos, active, tables, k, v,
            rng, temp, topk, topp, num_steps=STEPS, use_kernel=use_kernel,
            want_logprobs=False,
        )
    return jax.jit(run, donate_argnums=(1, 2))


for name, kernel in (("kernel", True), ("xla-paged", False)):
    k, v = llama.init_kv_cache(cfg, NB, BS, layered=True)
    timeit(f"full {name}", make(kernel), params, k, v)


# Ablation: replace attention with zeros (keeps QKV/wo matmuls + cache
# writes + MLP + sampling) — isolates the attention read cost.
import dynamo_tpu.models.llama as L

real_paged = L.paged_attention
L.paged_attention = lambda q, *a, **k: jnp.zeros_like(q)
k, v = llama.init_kv_cache(cfg, NB, BS, layered=True)
timeit("no-attention", make(True), params, k, v)
L.paged_attention = real_paged

# Ablation: no cache write (attention reads stale zeros — same traffic).
real_write = L.write_chunk_to_cache
L.write_chunk_to_cache = lambda c, *a, **kw: c
k, v = llama.init_kv_cache(cfg, NB, BS, layered=True)
timeit("no-cache-write", make(True), params, k, v)
L.write_chunk_to_cache = real_write
