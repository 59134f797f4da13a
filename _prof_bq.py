"""Re-sweep decode-kernel batch_block with the LAYERED cache program."""
import os, sys, time
import numpy as np
import jax, jax.numpy as jnp
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config
import dynamo_tpu.ops.attention as A

cfg = qwen2_500m_config()
BS = 128; NB = 65536 // BS; B = 256; STEPS = 64
params = llama.init_params(cfg, jax.random.PRNGKey(0))
tokens = jnp.ones((B,), jnp.int32)
start_pos = jnp.full((B,), 160, jnp.int32)
active = jnp.ones((B,), jnp.int32)
tables = jnp.asarray((np.arange(B * 2, dtype=np.int32) % NB).reshape(B, 2))
rng = jax.random.PRNGKey(1)
temp = jnp.ones((B,), jnp.float32); topk = jnp.zeros((B,), jnp.int32); topp = jnp.full((B,), 0.95, jnp.float32)

BQ = int(sys.argv[1])
import functools
A.paged_attention_decode_kernel = functools.partial(
    A.paged_attention_decode_kernel, batch_block=BQ
)

def run(params, k, v):
    return llama.decode_multi(params, cfg, tokens, start_pos, active, tables, k, v,
        rng, temp, topk, topp, num_steps=STEPS, use_kernel=True, want_logprobs=False)
f = jax.jit(run, donate_argnums=(1, 2))
k, v = llama.init_kv_cache(cfg, NB, BS, layered=True)
out = f(params, k, v); k, v = out[-2], out[-1]; np.asarray(out[0])
n = 6; t0 = time.perf_counter()
for _ in range(n):
    out = f(params, k, v); k, v = out[-2], out[-1]; np.asarray(out[0])
dt = (time.perf_counter() - t0) / n
print(f"BQ={BQ}: {dt/STEPS*1000:.2f} ms/step ({B*STEPS/dt:.0f} tok/s)", flush=True)
