"""Compare decode-step cache strategies on the real chip at the bench shape.

A: current — layer-scan with cache as xs/ys (full stacked cache rematerialized
   per step).
B: unrolled — Python loop over layers, cache as L-tuples of 4D arrays carried
   through the step scan (in-place scatter, no stacked copy).

Run: python _prof_unroll.py [steps]
"""
import sys
import time
import numpy as np
import jax, jax.numpy as jnp

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import qwen2_500m_config
from dynamo_tpu.ops.sampling import sample_tokens

cfg = qwen2_500m_config()
BS = 128
NB = 65536 // BS  # 512 blocks
B = 256
STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 64
MAX_BLOCKS = 4  # per-seq table: 4*128 = 512 positions, enough for ISL+OSL

params = llama.init_params(cfg, jax.random.PRNGKey(0))
L = cfg.n_layers

tokens = jnp.ones((B,), jnp.int32)
start_pos = jnp.full((B,), 128, jnp.int32)
active = jnp.ones((B,), jnp.int32)
tables = jnp.asarray((np.arange(B * MAX_BLOCKS, dtype=np.int32) % NB).reshape(B, MAX_BLOCKS))
rng = jax.random.PRNGKey(1)
temp = jnp.ones((B,), jnp.float32)
topk = jnp.zeros((B,), jnp.int32)
topp = jnp.full((B,), 0.95, jnp.float32)


def timeit(name, f, k, v):
    # Donated caches: thread the returned cache arrays into the next call.
    out = f(params, k, v)
    k, v = out[-2], out[-1]
    np.asarray(jax.tree.leaves(out[0])[0])  # force completion
    n = 8
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(params, k, v)
        k, v = out[-2], out[-1]
        np.asarray(jax.tree.leaves(out[0])[0])
    dt = (time.perf_counter() - t0) / n
    print(f"{name}: {dt*1000:.1f} ms/dispatch = {dt/STEPS*1000:.2f} ms/step "
          f"({B*STEPS/dt:.0f} tok/s)", flush=True)
    return out


# ---------------- A: current scan form ----------------
def run_scan(params, k_cache, v_cache):
    return llama.decode_multi(
        params, cfg, tokens, start_pos, active, tables, k_cache, v_cache,
        rng, temp, topk, topp, num_steps=STEPS, use_kernel=True,
        want_logprobs=False,
    )

k_cache, v_cache = llama.init_kv_cache(cfg, NB, BS)
f_scan = jax.jit(run_scan, donate_argnums=(1, 2))
print("compiling A (scan xs/ys)...", flush=True)
out = timeit("A scan-xs/ys", f_scan, k_cache, v_cache)
del out, k_cache, v_cache


# ---------------- B: unrolled per-layer tuples ----------------
from dynamo_tpu.models.llama import decoder_layer, embed_tokens, lm_head_logits, rope_table


def forward_unrolled(params, toks, pos, lens, block_tables, k_layers, v_layers):
    c = cfg
    Bb, C = toks.shape
    hd = c.head_dim_
    x = embed_tokens(params, c, toks)
    p = pos[:, None] + jax.lax.broadcasted_iota(jnp.int32, (Bb, C), 1)
    cos, sin = rope_table(p, hd, c.rope_theta)
    windows = c.layer_windows()
    k_out, v_out = [], []
    for l in range(L):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        win = jnp.asarray(windows[l], jnp.int32)
        x, k_l, v_l = decoder_layer(
            c, lp, {}, win, x, cos, sin, k_layers[l], v_layers[l],
            block_tables, pos, lens, use_kernel=True, adapter_ids=None,
        )
        k_out.append(k_l)
        v_out.append(v_l)
    last = jnp.clip(lens - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return lm_head_logits(params, c, x_last), tuple(k_out), tuple(v_out)


def run_unrolled(params, k_layers, v_layers):
    def one(carry, step_rng):
        toks, pos, k_t, v_t = carry
        logits, k_t, v_t = forward_unrolled(
            params, toks[:, None], pos, active, tables, k_t, v_t
        )
        nxt = sample_tokens(logits, step_rng, temp, topk, topp)
        nxt = jnp.where(active > 0, nxt, toks)
        return (nxt, pos + active, k_t, v_t), nxt

    rngs = jax.random.split(rng, STEPS)
    (_, _, k_t, v_t), toks_out = jax.lax.scan(
        one, (tokens, start_pos, k_layers, v_layers), rngs
    )
    return toks_out.T, k_t, v_t


k5, v5 = llama.init_kv_cache(cfg, NB, BS)
k_layers = tuple(k5[l] for l in range(L))
v_layers = tuple(v5[l] for l in range(L))
del k5, v5
f_unroll = jax.jit(run_unrolled, donate_argnums=(1, 2))
print("compiling B (unrolled per-layer)...", flush=True)
t0 = time.perf_counter()
out = timeit("B unrolled", f_unroll, k_layers, v_layers)
print(f"(B total incl first compile+run: {time.perf_counter()-t0:.0f}s)")
