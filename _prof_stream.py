"""Diagnose pallas weight-streaming rate vs XLA: a CHAIN of 16 matmuls
(distinct weights, one jit) so device time ≫ the host's enqueue floor.
Decides the r5 fused-layer plan."""
import functools, time, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

D, FF, B, NW = 4096, 14336, 128, 8
CHAIN = 16  # matmuls per dispatch (weights cycled)
GB = CHAIN * D * FF / 1e9

rng = np.random.default_rng(0)
ws = [
    np.ascontiguousarray(rng.integers(-127, 127, size=(D, FF)).astype(np.int8))
    for _ in range(NW)
]
x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32)).astype(jnp.bfloat16)


def bench(label, f, *a, n=4):
    r = f(*a)
    _ = np.asarray(jax.tree.leaves(r)[0]).ravel()[:4]
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*a)
    _ = np.asarray(jax.tree.leaves(r)[0]).ravel()[:4]
    dt = (time.perf_counter() - t0) / n
    print(f"{label}: {dt*1000:.2f} ms/chain -> {GB/dt:.0f} GB/s", flush=True)


# 0) XLA chain (the model's current path shape)
wj = [jnp.asarray(w) for w in ws]


@jax.jit
def xla_chain(x_, *w_):
    acc = jnp.zeros((B,), jnp.float32)
    for i in range(CHAIN):
        w = w_[i % NW]
        y = jax.lax.dot_general(
            x_, w.astype(x_.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = acc + y[:, 0] + y[:, -1]
    return acc


bench("XLA int8 chain", xla_chain, x, *wj)


# 1) pallas chain: pre-tiled weights, contiguous DMA per grid step
def mk_pallas(BN):
    NT = FF // BN

    def _k(wt_ref, x_ref, o_ref):
        w = wt_ref[0].astype(jnp.bfloat16)
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    wt = [
        jnp.asarray(
            np.ascontiguousarray(w.reshape(D, NT, BN).transpose(1, 0, 2))
        )
        for w in ws
    ]

    def one(x_, w_):
        return pl.pallas_call(
            _k,
            grid=(NT,),
            in_specs=[
                pl.BlockSpec((1, D, BN), lambda i: (i, 0, 0)),
                pl.BlockSpec((B, D), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((B, BN), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((B, FF), jnp.float32),
        )(w_, x_)

    @jax.jit
    def chain(x_, *w_):
        acc = jnp.zeros((B,), jnp.float32)
        for i in range(CHAIN):
            y = one(x_, w_[i % NW])
            acc = acc + y[:, 0] + y[:, -1]
        return acc

    def run(x_):
        return chain(x_, *wt)

    return run


for BN in (512, 1024):
    bench(f"pallas int8 chain BN={BN}", mk_pallas(BN), x)
