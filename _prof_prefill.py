import time
import numpy as np
import jax, jax.numpy as jnp
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config

cfg = qwen2_500m_config()
NB, BS = 2048, 16
params = llama.init_params(cfg, jax.random.PRNGKey(0))
k, v = llama.init_kv_cache(cfg, NB, BS)

def bench(fn, *args, n=10, label=""):
    out = fn(*args); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n): out = fn(*args)
    jax.block_until_ready(out)
    print(f"{label}: {(time.perf_counter()-t0)/n*1000:.2f} ms")

for B in (1, 8, 16):
    C = 128
    toks = jnp.ones((B, C), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), C, jnp.int32)
    tables = jnp.asarray(np.arange(B*8, dtype=np.int32).reshape(B, 8))
    for uk in (True, False):
        f = jax.jit(lambda p_,k_,v_,t_: llama.forward_paged(p_, cfg, t_, pos, lens, tables, k_, v_, use_kernel=uk)[0])
        bench(f, params, k, v, toks, n=5, label=f"prefill B={B} C=128 kernel={uk}")
