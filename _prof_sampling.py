import time
import numpy as np
import jax, jax.numpy as jnp
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

B, V = 64, 151936
logits = jnp.asarray(np.random.default_rng(0).standard_normal((B, V)), jnp.float32)

def bench(f, *a, n=20, label=""):
    f(*a)[0].block_until_ready() if isinstance(f(*a), tuple) else jax.block_until_ready(f(*a))
    t0 = time.perf_counter()
    for _ in range(n): r = f(*a)
    jax.block_until_ready(r)
    print(f"{label}: {(time.perf_counter()-t0)/n*1000:.2f} ms")

bench(jax.jit(lambda l: jax.lax.approx_max_k(l, 64, recall_target=0.99)), logits, label="approx_max_k W=64 r=.99")
bench(jax.jit(lambda l: jax.lax.approx_max_k(l, 64, recall_target=0.95)), logits, label="approx_max_k W=64 r=.95")
bench(jax.jit(lambda l: jax.lax.approx_max_k(l, 32, recall_target=0.95)), logits, label="approx_max_k W=32 r=.95")
bench(jax.jit(lambda l: jax.lax.top_k(l, 64)), logits, label="lax.top_k W=64")
bench(jax.jit(lambda l: jnp.argmax(l, -1)), logits, label="argmax")
from dynamo_tpu.ops.sampling import sample_tokens
rng = jax.random.PRNGKey(0)
t = jnp.ones((B,), jnp.float32); tk = jnp.zeros((B,), jnp.int32); tp = jnp.full((B,), 0.95, jnp.float32)
bench(jax.jit(lambda l: sample_tokens(l, rng, t, tk, tp)), logits, label="sample_tokens full")
# gumbel-trick full-vocab: filterless temperature sampling
def gumbel_sample(l):
    g = jax.random.gumbel(rng, l.shape, dtype=jnp.float32)
    return jnp.argmax(l / t[:, None] + g, axis=-1)
bench(jax.jit(gumbel_sample), logits, label="gumbel argmax (no topk/topp)")
