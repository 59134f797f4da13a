import os, time, json
import numpy as np
import jax, jax.numpy as jnp
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config
from dynamo_tpu.ops.attention import paged_attention

cfg = qwen2_500m_config()
print("backend", jax.default_backend())
B, BS, NB, P = 64, 16, 2048, 32  # 32 pages = 512 ctx
params = llama.init_params(cfg, jax.random.PRNGKey(0))
k, v = llama.init_kv_cache(cfg, NB, BS)
tables = jnp.asarray(np.random.default_rng(0).permutation(NB)[:B*P].reshape(B, P).astype(np.int32))
tok = jnp.ones((B,), jnp.int32)
pos = jnp.full((B,), 200, jnp.int32)
act = jnp.ones((B,), jnp.int32)
rng = jax.random.PRNGKey(1)
temp = jnp.ones((B,), jnp.float32); topk = jnp.zeros((B,), jnp.int32); topp = jnp.ones((B,), jnp.float32)

def bench(fn, *args, n=20, label=""):
    out = fn(*args); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter()-t0)/n
    print(f"{label}: {dt*1000:.2f} ms")
    return dt

# 1) full fused decode (32 steps)
dec = jax.jit(lambda p_,k_,v_: llama.decode_multi(p_, cfg, tok, pos, act, tables, k_, v_, rng, temp, topk, topp, num_steps=32, use_kernel=True))
d = bench(dec, params, k, v, n=3, label="decode_multi(32 steps, B=64, kernel)")
print(f"  per-token-step: {d/32*1000:.2f} ms -> {B*32/d:.0f} tok/s")

# 2) single forward (C=1) with kernel vs without
f1 = jax.jit(lambda p_,k_,v_: llama.forward_paged(p_, cfg, tok[:,None], pos, act, tables, k_, v_, use_kernel=True)[0])
bench(f1, params, k, v, n=10, label="forward C=1 kernel")
f2 = jax.jit(lambda p_,k_,v_: llama.forward_paged(p_, cfg, tok[:,None], pos, act, tables, k_, v_, use_kernel=False)[0])
bench(f2, params, k, v, n=10, label="forward C=1 xla-attn")

# 3) attention alone (kernel), 24 layers worth approximated by 1 call
q = jnp.ones((B,1,cfg.n_heads,cfg.head_dim_), jnp.bfloat16)
kc1 = k[0]; vc1 = v[0]
att = jax.jit(lambda q_,k_,v_: paged_attention(q_, k_, v_, tables, pos, act, use_kernel=True))
bench(att, q, kc1, vc1, n=20, label="paged_attention kernel single layer")

# 4) matmul-only model step reference (no attention): rough floor
def mm_only(p_, x):
    def layer(carry, lp):
        x = carry
        h = x @ lp["wq"]; h2 = x @ lp["wk"]; h3 = x @ lp["wv"]
        x = x + (h @ lp["wo"].T[:cfg.n_heads*cfg.head_dim_,:].T if False else h @ jnp.zeros_like(lp["wo"]))
        g = jax.nn.silu(x @ lp["w_gate"]); u = x @ lp["w_up"]
        x = x + (g*u) @ lp["w_down"]
        return x, None
    x, _ = jax.lax.scan(layer, x, p_["layers"])
    return x @ p_["embed"].T
mm = jax.jit(mm_only)
x0 = jnp.ones((B, cfg.d_model), jnp.bfloat16)
bench(mm, params, x0, n=10, label="matmul-only step (B=64)")

# 5) sampling
from dynamo_tpu.ops.sampling import sample_tokens
logits = jnp.ones((B, cfg.vocab_size), jnp.float32)
smp = jax.jit(lambda l: sample_tokens(l, rng, temp, topk, topp))
bench(smp, logits, n=20, label="sample_tokens (B=64, V=152k)")
