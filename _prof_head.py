"""Prototype v2: fused pallas int8 lm-head kernel + two-stage exact top-k.

Weight pre-chunked [NC, D, BN] so every grid step DMAs one contiguous
chunk; logits computed directly in [B, BN] layout; stage-2 top-k in XLA.
"""
import functools, time, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
from dynamo_tpu.ops.quant import quantize_q8

V, D, B = 128256, 4096, 64
BN = int(sys.argv[1]) if len(sys.argv) > 1 else 768
NC = V // BN
assert NC * BN == V, (V, BN)
NG = V // 128
W = 64


def _head_kernel(wc_ref, s_ref, x_ref, out_ref):
    w = wc_ref[0].astype(jnp.bfloat16)  # [D, BN]
    y = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, BN]
    out_ref[...] = y * s_ref[0]


@jax.jit
def head_fused(wc, ws, x):
    return pl.pallas_call(
        _head_kernel,
        grid=(NC,),
        in_specs=[
            pl.BlockSpec((1, D, BN), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, BN), lambda i: (i, 0, 0)),
            pl.BlockSpec((B, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B, BN), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, V), jnp.float32),
    )(wc, ws, x)


@jax.jit
def topk2(logits):
    g = logits.reshape(B, NG, 128)
    gmax = g.max(-1)  # [B, NG]
    gv, gi = jax.lax.top_k(gmax, W)
    cand = jnp.take_along_axis(g, gi[:, :, None], axis=1)  # [B, W, 128]
    cv, ci = jax.lax.top_k(cand.reshape(B, W * 128), W)
    tok = jnp.take_along_axis(gi, ci // 128, axis=1) * 128 + ci % 128
    return cv, tok


def bench(label, f, *a, n=20):
    r = f(*a)
    _ = jax.tree.map(np.asarray, r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*a)
    _ = jax.tree.map(np.asarray, r)
    print(f"{label}: {(time.perf_counter()-t0)/n*1000:.2f} ms", flush=True)


rng = np.random.default_rng(0)
w0 = rng.standard_normal((D, V), dtype=np.float32)
qt = quantize_q8(w0, [0])  # q8 [D, V], s [1, V]
wc = jnp.asarray(
    np.ascontiguousarray(qt["q8"].reshape(D, NC, BN).transpose(1, 0, 2))
)
ws = jnp.asarray(np.ascontiguousarray(qt["s"].reshape(1, NC, BN).transpose(1, 0, 2)))
x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32)).astype(jnp.bfloat16)

bench(f"fused head kernel BN={BN} [B,V]", head_fused, wc, ws, x)
lg = head_fused(wc, ws, x)
bench("topk2 (XLA two-stage)", topk2, lg)
full = jax.jit(lambda wc_, ws_, x_: topk2(head_fused(wc_, ws_, x_)))
bench("fused head + topk2", full, wc, ws, x)

cv, tok = full(wc, ws, x)
ref = x.astype(jnp.float32) @ (qt["q8"].astype(np.float32) * qt["s"])
ev, ei = jax.lax.top_k(ref, W)
print("values close:", bool(jnp.allclose(cv, ev, rtol=1e-3, atol=1e-3)))
print("ids match:", float((tok == ei).mean()))
