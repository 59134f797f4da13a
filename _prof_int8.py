"""Int8 vs bf16 decode at the 3B shape (fits both on one 16GB chip).

In the bandwidth-bound decode regime weight-only int8 must WIN (half the
weight bytes) — if it doesn't, the dequant isn't fusing into the dot.
"""
import sys, time
import numpy as np
import jax, jax.numpy as jnp
import os
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import llama3_3b_config
from dynamo_tpu.models.quantize import init_quantized_params
from dynamo_tpu.ops.sampling import sample_tokens

cfg = llama3_3b_config()
BS = 64
NB = 16384 // BS  # 256 blocks * 64 = 16k positions; KV = 28L*16k*8KH*128D*2*2B = 1.9GB
B = 64
STEPS = 32
L = cfg.n_layers
MAXB = 4

which = sys.argv[1] if len(sys.argv) > 1 else "both"

tokens = jnp.ones((B,), jnp.int32)
start_pos = jnp.full((B,), 128, jnp.int32)
active = jnp.ones((B,), jnp.int32)
tables = jnp.asarray((np.arange(B * MAXB, dtype=np.int32) % NB).reshape(B, MAXB))
rng = jax.random.PRNGKey(1)
temp = jnp.ones((B,), jnp.float32)
topk = jnp.zeros((B,), jnp.int32)
topp = jnp.full((B,), 0.95, jnp.float32)


def bench(name, params):
    k, v = llama.init_kv_cache(cfg, NB, BS, layered=True)

    def run(params, k, v):
        return llama.decode_multi(
            params, cfg, tokens, start_pos, active, tables, k, v,
            rng, temp, topk, topp, num_steps=STEPS, use_kernel=True,
            want_logprobs=False,
        )

    f = jax.jit(run, donate_argnums=(1, 2))
    out = f(params, k, v); k, v = out[-2], out[-1]; np.asarray(out[0])
    n = 5
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(params, k, v); k, v = out[-2], out[-1]; np.asarray(out[0])
    dt = (time.perf_counter() - t0) / n
    print(f"{name}: {dt/STEPS*1000:.2f} ms/step ({B*STEPS/dt:.0f} tok/s)", flush=True)


if which in ("both", "int8"):
    qp = init_quantized_params(cfg, 0)
    bench("3B int8", qp)
    del qp
if which in ("both", "bf16"):
    fp = llama.init_params(cfg, jax.random.PRNGKey(0))
    bench("3B bf16", fp)
