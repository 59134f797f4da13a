import time
import numpy as np
import jax, jax.numpy as jnp
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config
from dynamo_tpu.ops.sampling import sample_tokens, compute_logprobs

cfg = qwen2_500m_config()
BS = 32; NB = 65536 // BS
params = llama.init_params(cfg, jax.random.PRNGKey(0))

def mkcache():
    return llama.init_kv_cache(cfg, NB, BS)

B, C = 8, 128
toks = jnp.ones((B, C), jnp.int32)
pos = jnp.zeros((B,), jnp.int32)
lens = jnp.full((B,), C, jnp.int32)
tables = jnp.asarray(np.arange(B*16, dtype=np.int32).reshape(B, 16))
rng = jax.random.PRNGKey(1)
t = jnp.ones((B,), jnp.float32); tk = jnp.zeros((B,), jnp.int32); tp = jnp.ones((B,), jnp.float32)

def variant(name, donate, with_sampling, kernel):
    def step(p_, k_, v_):
        logits, k_, v_ = llama.forward_paged(p_, cfg, toks, pos, lens, tables, k_, v_, use_kernel=kernel)
        if with_sampling:
            s = sample_tokens(logits, rng, t, tk, tp)
            lp = compute_logprobs(logits, s)
            return s, lp, k_, v_
        return logits, k_, v_
    f = jax.jit(step, donate_argnums=(1,2)) if donate else jax.jit(step)
    k, v = mkcache()
    out = f(params, k, v); jax.block_until_ready(out)
    if donate: k, v = out[-2], out[-1]
    n = 5; t0 = time.perf_counter()
    for _ in range(n):
        out = f(params, k, v)
        if donate: k, v = out[-2], out[-1]
    jax.block_until_ready(out)
    print(f"{name}: {(time.perf_counter()-t0)/n*1000:.1f} ms")

variant("prefill donate+sample kernel=T", True, True, True)
variant("prefill donate+sample kernel=F", True, True, False)
variant("prefill donate no-sample kernel=T", True, False, True)
variant("prefill NO-donate+sample kernel=T", False, True, True)
