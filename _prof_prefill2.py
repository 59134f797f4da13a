"""Prefill dispatch timing at the bench shape (layered cache).

What does one [Bp, C] prefill step cost on the chip, kernel vs no-kernel,
and how does it scale with Bp? TTFT at concurrency 256 is queueing on these
dispatches.
"""
import time
import numpy as np
import jax, jax.numpy as jnp

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config
from dynamo_tpu.ops.sampling import sample_tokens, compute_logprobs

cfg = qwen2_500m_config()
BS = 128
NB = 65536 // BS
L = cfg.n_layers
params = llama.init_params(cfg, jax.random.PRNGKey(0))


def run(Bp, C, use_kernel):
    k5, v5 = llama.init_kv_cache(cfg, NB, BS, layered=True)

    def step(params, k, v, toks, start, lens, tables, rng):
        logits, k, v = llama.forward_paged(
            params, cfg, toks, start, lens, tables, k, v, use_kernel=use_kernel
        )
        s = sample_tokens(logits, rng, jnp.ones((Bp,), jnp.float32),
                          jnp.zeros((Bp,), jnp.int32), jnp.ones((Bp,), jnp.float32))
        lp = compute_logprobs(logits, s)
        return s, lp, k, v

    f = jax.jit(step, donate_argnums=(1, 2))
    toks = jnp.ones((Bp, C), jnp.int32)
    start = jnp.zeros((Bp,), jnp.int32)
    lens = jnp.full((Bp,), C, jnp.int32)
    tables = jnp.asarray((np.arange(Bp * 4, dtype=np.int32) % NB).reshape(Bp, 4))
    rng = jax.random.PRNGKey(1)
    out = f(params, k5, v5, toks, start, lens, tables, rng)
    k5, v5 = out[-2], out[-1]
    np.asarray(out[0])
    n = 6
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(params, k5, v5, toks, start, lens, tables, rng)
        k5, v5 = out[-2], out[-1]
        np.asarray(out[0])
    dt = (time.perf_counter() - t0) / n
    print(f"prefill Bp={Bp:4d} C={C} kernel={use_kernel}: {dt*1000:7.1f} ms "
          f"({Bp*C/dt/1e3:.0f}k tok/s)", flush=True)


for Bp in (8, 32, 128):
    run(Bp, 128, True)
run(128, 128, False)
run(64, 256, True)
