import time, functools
import numpy as np
import jax, jax.numpy as jnp
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config
import dynamo_tpu.ops.attention as att
import dynamo_tpu.ops.pallas.paged_attention as pk

cfg = qwen2_500m_config()
B, BS, P = 128, 32, 16
NB = 65536 // BS
params = llama.init_params(cfg, jax.random.PRNGKey(0))
tables = jnp.asarray(np.random.default_rng(0).permutation(NB)[:B*P].reshape(B, P).astype(np.int32))
tok = jnp.ones((B,), jnp.int32); pos = jnp.full((B,), 200, jnp.int32); act = jnp.ones((B,), jnp.int32)
rng = jax.random.PRNGKey(1)
t = jnp.ones((B,), jnp.float32); tk = jnp.zeros((B,), jnp.int32); tp = jnp.ones((B,), jnp.float32)

def run(label, use_kernel, S=None):
    att.paged_attention_kernel = (
        pk.paged_attention_kernel if S is None
        else functools.partial(pk.paged_attention_kernel, pages_per_step=S)
    )
    def step(p_, k_, v_):
        return llama.decode_multi(p_, cfg, tok, pos, act, tables, k_, v_, rng, t, tk, tp,
                                  num_steps=32, use_kernel=use_kernel, want_logprobs=False)
    f = jax.jit(step, donate_argnums=(1,2))
    k, v = llama.init_kv_cache(cfg, NB, BS)
    out = f(params, k, v); jax.block_until_ready(out); k, v = out[2], out[3]
    n = 3; t0 = time.perf_counter()
    for _ in range(n):
        out = f(params, k, v); k, v = out[2], out[3]
    jax.block_until_ready(out)
    dt = (time.perf_counter()-t0)/n
    print(f"{label}: {dt*1000:.0f} ms -> {B*32/dt:.0f} tok/s")

run("xla attention", False)
run("kernel S=1", True, 1)
run("kernel S=2", True, 2)
run("kernel S=4", True, 4)
run("kernel S=8", True, 8)
run("kernel S=16", True, 16)
