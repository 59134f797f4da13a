import time
import numpy as np
import jax, jax.numpy as jnp
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

def bench(label, fn, n=30):
    fn()
    t0 = time.perf_counter()
    for _ in range(n): r = fn()
    jax.block_until_ready(r) if r is not None else None
    print(f"{label}: {(time.perf_counter()-t0)/n*1000:.2f} ms")

a = np.zeros((128, 16), np.int32)
bench("jnp.asarray [128,16] (async)", lambda: jnp.asarray(a))
bench("jnp.asarray + block", lambda: jax.block_until_ready(jnp.asarray(a)))
key = jax.random.PRNGKey(0)
def split():
    k1, k2 = jax.random.split(key)
    return k2
bench("jax.random.split (async)", split)
bench("jax.random.split + block", lambda: jax.block_until_ready(split()))
x = jnp.ones((128, 32), jnp.int32)
bench("device_get [128,32]", lambda: jax.device_get(x))
f = jax.jit(lambda v: v + 1)
f(x)
bench("tiny jit dispatch + get", lambda: jax.device_get(f(x)))
