"""Prototype: manual double-buffered int8 weight-streaming FFN in pallas.

Validates the megakernel premise (VERDICT r5 item 1): can a pallas kernel
stream int8 weights from HBM at >= XLA's measured ~88% of roofline while
fusing norm+gate+up+silu+mul+down+residual in one program? Measured
IN-PROGRAM (16-iter scan) because isolated kernel timings don't transfer
on this chip.
"""
import functools, time, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

B, D, F = 64, 4096, 14336
TF = 512           # ffn-dim tile for gate/up (cols) and down (rows)
NT = F // TF       # 28 tiles
GB = (2 * D * F + F * D) / 1e9  # int8 bytes streamed per call

rng = np.random.default_rng(0)
wg = jnp.asarray(rng.integers(-127, 127, (D, F), dtype=np.int64).astype(np.int8))
wu = jnp.asarray(rng.integers(-127, 127, (D, F), dtype=np.int64).astype(np.int8))
wd = jnp.asarray(rng.integers(-127, 127, (F, D), dtype=np.int64).astype(np.int8))
sg = jnp.asarray(rng.standard_normal((1, F)).astype(np.float32) * 0.01)
su = jnp.asarray(rng.standard_normal((1, F)).astype(np.float32) * 0.01)
sd = jnp.asarray(rng.standard_normal((1, D)).astype(np.float32) * 0.01)
x0 = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32)).astype(jnp.bfloat16)


def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, sg_ref, su_ref, sd_ref, o_ref):
    def body(gu_ref, acc_ref, sem):
        x = x_ref[...]

        # phase 1: gate/up tiles — wbuf slots: [2 buffers][2 mats][D, TF]
        def phase_gu(wbuf):
            def gu_dma(slot, t, which, ref):
                return pltpu.make_async_copy(
                    ref.at[:, pl.ds(t * TF, TF)],
                    wbuf.at[slot, which],
                    sem.at[slot * 2 + which],
                )

            gu_dma(0, 0, 0, wg_ref).start()
            gu_dma(0, 0, 1, wu_ref).start()

            def gu_loop(t, _):
                slot = jax.lax.rem(t, 2)
                nxt = jax.lax.rem(t + 1, 2)

                @pl.when(t + 1 < NT)
                def _():
                    gu_dma(nxt, t + 1, 0, wg_ref).start()
                    gu_dma(nxt, t + 1, 1, wu_ref).start()

                gu_dma(slot, t, 0, wg_ref).wait()
                gu_dma(slot, t, 1, wu_ref).wait()
                g = jax.lax.dot_general(
                    x, wbuf[slot, 0].astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
                ) * sg_ref[0, pl.ds(t * TF, TF)][None, :]
                u = jax.lax.dot_general(
                    x, wbuf[slot, 1].astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
                ) * su_ref[0, pl.ds(t * TF, TF)][None, :]
                gu = (g * jax.lax.logistic(g) * u).astype(jnp.bfloat16)
                gu_ref[:, pl.ds(t * TF, TF)] = gu
                return ()

            jax.lax.fori_loop(0, NT, gu_loop, (), unroll=False)

        pl.run_scoped(phase_gu, wbuf=pltpu.VMEM((2, 2, D, TF), jnp.int8))

        # phase 2: down tiles — accumulate partial sums in f32
        def phase_down(dbuf):
            def d_dma(slot, t):
                return pltpu.make_async_copy(
                    wd_ref.at[pl.ds(t * TF, TF), :], dbuf.at[slot],
                    sem.at[4 + slot],
                )

            d_dma(0, 0).start()
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def d_loop(t, _):
                slot = jax.lax.rem(t, 2)
                nxt = jax.lax.rem(t + 1, 2)

                @pl.when(t + 1 < NT)
                def _():
                    d_dma(nxt, t + 1).start()

                d_dma(slot, t).wait()
                gu_t = gu_ref[:, pl.ds(t * TF, TF)]
                acc_ref[...] += jax.lax.dot_general(
                    gu_t, dbuf[slot].astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
                )
                return ()

            jax.lax.fori_loop(0, NT, d_loop, (), unroll=False)

        pl.run_scoped(phase_down, dbuf=pltpu.VMEM((2, TF, D), jnp.int8))
        o_ref[...] = (acc_ref[...] * sd_ref[0][None, :]).astype(o_ref.dtype)

    pl.run_scoped(
        body,
        gu_ref=pltpu.VMEM((B, F), jnp.bfloat16),
        acc_ref=pltpu.VMEM((B, D), jnp.float32),
        sem=pltpu.SemaphoreType.DMA((6,)),
    )


@jax.jit
def ffn_pallas(x, wg, wu, wd, sg, su, sd):
    return pl.pallas_call(
        _ffn_kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # x: small, live in VMEM
            pl.BlockSpec(memory_space=pltpu.ANY),   # weights: HBM, manual DMA
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.VMEM),  # scales: small
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.bfloat16),
    )(x, wg, wu, wd, sg, su, sd)


def ffn_xla(x, wg, wu, wd, sg, su, sd):
    g = jax.lax.dot_general(
        x, wg.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sg
    u = jax.lax.dot_general(
        x, wu.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * su
    gu = (g * jax.lax.logistic(g) * u).astype(jnp.bfloat16)
    y = jax.lax.dot_general(
        gu, wd.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sd
    return y.astype(jnp.bfloat16)


def in_program(f):
    # 16 chained iterations in one dispatch, feeding output back into input
    # (forces sequential execution; mimics the decode scan environment).
    @jax.jit
    def run(x):
        def one(c, _):
            y = f(c, wg, wu, wd, sg, su, sd)
            return (c + 0.001 * y).astype(jnp.bfloat16), ()
        y, _ = jax.lax.scan(one, x, None, length=16)
        return y
    return run


if __name__ == "__main__":
    # correctness first
    if True:  # correctness gate always runs (cheap vs the bench)
        a = np.asarray(ffn_pallas(x0, wg, wu, wd, sg, su, sd), dtype=np.float32)
        b = np.asarray(ffn_xla(x0, wg, wu, wd, sg, su, sd), dtype=np.float32)
        err = np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)
        print(f"rel err: {err:.2e}", flush=True)
        assert err < 3e-2, "mismatch"

    for name, f in [("pallas", ffn_pallas), ("xla", ffn_xla)]:
        run = in_program(f)
        y = run(x0); _ = np.asarray(y)[:2, :2]
        ts = []
        for _i in range(5):
            t0 = time.perf_counter()
            y = run(x0); _ = np.asarray(y)[:2, :2]
            ts.append(time.perf_counter() - t0)
        dt = min(ts) / 16
        print(f"{name}: {dt*1e6:.1f} us/ffn -> {GB/dt:.0f} GB/s", flush=True)
