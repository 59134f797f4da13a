import time
import numpy as np
import jax, jax.numpy as jnp
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
print("backend", jax.default_backend(), jax.devices())

# HBM read roofline: reduce a big bf16 array
for gb in (0.5, 1.0):
    n = int(gb * (1<<30) / 2)
    a = jnp.ones((n,), jnp.bfloat16)
    f = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))
    f(a).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5): r = f(a)
    r.block_until_ready()
    dt = (time.perf_counter()-t0)/5
    print(f"HBM read {gb}GB: {dt*1000:.2f} ms -> {gb/dt:.0f} GB/s")

# MXU roofline: big matmul
for m,k,nn in ((4096,4096,4096), (8192,8192,8192)):
    a = jnp.ones((m,k), jnp.bfloat16); b = jnp.ones((k,nn), jnp.bfloat16)
    f = jax.jit(lambda x,y: x@y)
    f(a,b).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10): r = f(a,b)
    r.block_until_ready()
    dt = (time.perf_counter()-t0)/10
    print(f"matmul {m}: {dt*1000:.2f} ms -> {2*m*k*nn/dt/1e12:.1f} TFLOP/s")

# batch scaling of a layer-stack weight-stream: x[B,d] through 24 layers
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config
cfg = qwen2_500m_config()
params = llama.init_params(cfg, jax.random.PRNGKey(0))
def stream(p_, x):
    def layer(x, lp):
        q = x @ lp["wq"]
        a = q @ lp["wo"]
        g = jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])
        return x + a + g @ lp["w_down"], None
    x, _ = jax.lax.scan(layer, x, p_["layers"])
    return x @ p_["embed"].T
f = jax.jit(stream)
for B in (32, 64, 128, 256):
    x = jnp.ones((B, cfg.d_model), jnp.bfloat16)
    f(params, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10): r = f(params, x)
    r.block_until_ready()
    dt = (time.perf_counter()-t0)/10
    print(f"layer-stream B={B}: {dt*1000:.2f} ms -> {B/dt:.0f} tok/s")
