"""Prototype: packed block-diagonal decode attention kernel (v2) vs v1.

v2 design: cache viewed as [NB, bs, KH*D] (free bitcast); per sequence the
whole-page QK product is ONE MXU dot  k[bs, KD] @ qd[KD, R]  where qd is the
block-diagonal packing of the R = KH*G query rows (built in-kernel from a
[D, R] query slice with an iota mask — ~3 vector ops); scores live in a
single [R, bs] lane-major tile so the online softmax is ~10 dense VPU ops
instead of KH*G tiny ones; PV is one [R, bs] @ [bs, KD] dot; the per-head
output blocks are sliced out of the accumulator only at finalize.
"""
import functools, time, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

NEG_INF = -1e30


def _decode_kernel_packed(
    block_tables_ref, start_pos_ref, window_ref,
    qdr_ref,  # [BQ, D, R]  (rows d, cols (h, g) h-major)
    *refs,  # k_0, v_0, ..., k_{BQ-1}, v_{BQ-1}, o_ref, mask, qd, m, l, acc
    sm_scale, block_size, batch_block, n_kv_heads, logit_cap=0.0,
):
    BQ = batch_block
    kv_refs = refs[: 2 * BQ]
    o_ref = refs[2 * BQ]
    mask_ref, qd_ref, m_ref, l_ref, acc_ref = refs[2 * BQ + 1 :]

    bb = pl.program_id(0)
    p = pl.program_id(1)
    num_steps = pl.num_programs(1)
    KH = n_kv_heads
    D = qdr_ref.shape[1]
    R = qdr_ref.shape[2]
    G = R // KH
    KD = KH * D
    bs = block_size

    @pl.when((bb == 0) & (p == 0))
    def _init_mask():
        # Block-diag selector: mask[(h', d), (h, g)] = 1 iff h' == h.
        row_h = jax.lax.broadcasted_iota(jnp.int32, (KD, R), 0) // D
        col_h = jax.lax.broadcasted_iota(jnp.int32, (KD, R), 1) // G
        mask_ref[...] = (row_h == col_h).astype(mask_ref.dtype)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # qd[(h', d), (h, g)] = q[d, (h,g)] iff h' == h (block-diagonal).
        for j in range(BQ):
            tiled = jnp.concatenate([qdr_ref[j]] * KH, axis=0)  # [KD, R]
            qd_ref[j] = tiled * mask_ref[...]

    win = window_ref[0]
    for j in range(BQ):
        start = start_pos_ref[bb * BQ + j]
        last_needed = start // bs
        first_needed = jnp.where(
            win > 0, jnp.maximum(start - win + 1, 0) // bs, 0
        )

        @pl.when((p >= first_needed) & (p <= last_needed))
        def _compute(j=j, start=start):
            k = kv_refs[2 * j][0]  # [bs, KD] bf16
            s = jax.lax.dot_general(
                k, qd_ref[j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # [bs, R] f32 — t on sublanes, (h,g) on lanes
            if logit_cap > 0.0:
                s = logit_cap * jnp.tanh(s / logit_cap)
            t_idx = p * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
            visible = (t_idx <= start) & ((win <= 0) | (t_idx > start - win))
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[j]  # [1, R]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(s - m_new).astype(jnp.bfloat16)  # [bs, R]
            l_ref[j] = l_ref[j] * alpha + jnp.sum(
                probs.astype(jnp.float32), 0, keepdims=True
            )
            v = kv_refs[2 * j + 1][0]  # [bs, KD] bf16
            for h in range(KH):
                pv = jax.lax.dot_general(
                    probs[:, h * G : (h + 1) * G],
                    v[:, h * D : (h + 1) * D],
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [G, D]
                acc_ref[j, h] = acc_ref[j, h] * alpha[0, h * G : (h + 1) * G][
                    :, None
                ] + pv
            m_ref[j] = m_new

    @pl.when(p == num_steps - 1)
    def _finalize():
        for j in range(BQ):
            for h in range(KH):
                l = l_ref[j, :, h * G : (h + 1) * G]  # [1, G]
                o_ref[j, h] = (
                    acc_ref[j, h] / jnp.maximum(l[0][:, None], 1e-30)
                ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "batch_block", "logit_cap")
)
def decode_packed(
    q,  # [B, 1, H, D]
    k_cache,  # [NB, bs, KH, D]
    v_cache,
    block_tables,  # [B, P]
    start_pos,  # [B]
    window=0,
    *,
    sm_scale=None,
    batch_block: int = 8,
    logit_cap: float = 0.0,
):
    B, C, H, D = q.shape
    NB, bs, KH, _ = k_cache.shape
    G = H // KH
    R = KH * G
    KD = KH * D
    scale = sm_scale if sm_scale is not None else D**-0.5
    BQ = max(min(batch_block, B), 1)
    B_pad = ((B + BQ - 1) // BQ) * BQ
    if B_pad != B:
        q = jnp.pad(q, ((0, B_pad - B), (0, 0), (0, 0), (0, 0)))
        block_tables = jnp.pad(block_tables, ((0, B_pad - B), (0, 0)))
        start_pos = jnp.pad(start_pos, (0, B_pad - B))
    P = block_tables.shape[1]
    win = jnp.asarray(window, jnp.int32).reshape(1)

    # [B, 1, H, D] -> [B, D, R(h-major,g)]
    qdr = (
        q.reshape(B_pad, KH, G, D).transpose(0, 3, 1, 2).reshape(B_pad, D, R)
    ).astype(k_cache.dtype)
    k2 = k_cache.reshape(NB, bs, KD)
    v2 = v_cache.reshape(NB, bs, KD)

    def q_map(bb, p, bt, sp, w):
        return (bb, 0, 0)

    def kv_map_for(j):
        def kv_map(bb, p, bt, sp, w):
            return (bt[bb * BQ + j, p], 0, 0)
        return kv_map

    in_specs = [pl.BlockSpec((BQ, D, R), q_map)]
    kv_args = []
    for j in range(BQ):
        spec = pl.BlockSpec((1, bs, KD), kv_map_for(j))
        in_specs.extend([spec, spec])
        kv_args.extend([k2, v2])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B_pad // BQ, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (BQ, KH, G, D), lambda bb, p, bt, sp, w: (bb, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((KD, R), k2.dtype),
            pltpu.VMEM((BQ, KD, R), k2.dtype),
            pltpu.VMEM((BQ, 1, R), jnp.float32),
            pltpu.VMEM((BQ, 1, R), jnp.float32),
            pltpu.VMEM((BQ, KH, G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_packed, sm_scale=scale, block_size=bs,
        batch_block=BQ, n_kv_heads=KH, logit_cap=logit_cap,
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B_pad, KH, G, D), q.dtype),
    )(
        block_tables.astype(jnp.int32), start_pos.astype(jnp.int32), win,
        qdr, *kv_args,
    )
    out = out[:B].reshape(B, KH, 1, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, 1, H, D)


if __name__ == "__main__":
    from dynamo_tpu.ops.attention import _paged_attention_xla
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel,
    )

    B = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    KH, G, D, bs, P = 8, 4, 128, 128, 2
    H = KH * G
    NB = B * P + 8
    CTX = 160
    L = 32

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)).astype(np.float32)).astype(jnp.bfloat16)
    k_c = jnp.asarray(rng.standard_normal((NB, bs, KH, D)).astype(np.float32)).astype(jnp.bfloat16)
    v_c = jnp.asarray(rng.standard_normal((NB, bs, KH, D)).astype(np.float32)).astype(jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32))
    pos = jnp.full((B,), CTX, jnp.int32)
    ones = jnp.ones((B,), jnp.int32)

    # parity
    ref = _paged_attention_xla(q, k_c, v_c, tables, pos, ones)
    out2 = decode_packed(q, k_c, v_c, tables, pos)
    err = jnp.abs(out2.astype(jnp.float32) - ref.astype(jnp.float32)).max()
    print("packed vs oracle max err:", float(err), flush=True)
    out1 = paged_attention_decode_kernel(q, k_c, v_c, tables, pos)
    err1 = jnp.abs(out1.astype(jnp.float32) - ref.astype(jnp.float32)).max()
    print("v1 vs oracle max err:", float(err1), flush=True)

    # timing: scan over 32 layer-calls in one dispatch
    def bench(label, fn, n=5):
        def outer(q_, k_, v_):
            def one(c, _):
                o = fn(q_ + (c * 0.001).astype(q_.dtype), k_, v_, tables, pos)
                return c + o.astype(jnp.float32).mean() * 0.0, ()
            y, _ = jax.lax.scan(one, jnp.float32(0), None, length=L)
            return y
        f = jax.jit(outer)
        _ = np.asarray(f(q, k_c, v_c))
        t0 = time.perf_counter()
        for _ in range(n):
            r = f(q, k_c, v_c)
        _ = np.asarray(r)
        dt = (time.perf_counter() - t0) / n
        print(f"{label}: {dt*1000:.2f} ms for {L} layers", flush=True)

    bench("v1 kernel", lambda q_, k_, v_, t_, p_: paged_attention_decode_kernel(q_, k_, v_, t_, p_))
    bench("v2 packed", lambda q_, k_, v_, t_, p_: decode_packed(q_, k_, v_, t_, p_))


# --- v1 variant: bf16 operands (no f32 casts) ---
def _decode_kernel_bf16(
    block_tables_ref, start_pos_ref, window_ref,
    q_ref, *refs, sm_scale, block_size, batch_block, logit_cap=0.0,
):
    BQ = batch_block
    kv_refs = refs[: 2 * BQ]
    o_ref = refs[2 * BQ]
    m_ref, l_ref, acc_ref = refs[2 * BQ + 1 :]
    bb = pl.program_id(0)
    p = pl.program_id(1)
    num_steps = pl.num_programs(1)
    KH = q_ref.shape[1]
    G = q_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    win = window_ref[0]
    for j in range(BQ):
        start = start_pos_ref[bb * BQ + j]
        last_needed_page = start // block_size
        first_needed_page = jnp.where(
            win > 0, jnp.maximum(start - win + 1, 0) // block_size, 0
        )

        @pl.when((p >= first_needed_page) & (p <= last_needed_page))
        def _compute(j=j, start=start):
            t_idx = p * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_size), 1
            )
            visible = t_idx <= start
            visible = visible & ((win <= 0) | (t_idx > start - win))
            for h in range(KH):
                q = q_ref[j, h]  # [G, D] bf16
                k = kv_refs[2 * j][0, :, h, :]  # [bs, D] bf16
                v = kv_refs[2 * j + 1][0, :, h, :]
                s_mat = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale
                if logit_cap > 0.0:
                    s_mat = logit_cap * jnp.tanh(s_mat / logit_cap)
                s_mat = jnp.where(visible, s_mat, NEG_INF)
                m_prev = m_ref[j, h]
                m_new = jnp.maximum(m_prev, jnp.max(s_mat, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                probs = jnp.exp(s_mat - m_new).astype(jnp.bfloat16)
                l_ref[j, h] = l_ref[j, h] * alpha + jnp.sum(
                    probs.astype(jnp.float32), axis=-1, keepdims=True
                )
                acc_ref[j, h] = acc_ref[j, h] * alpha + jax.lax.dot_general(
                    probs, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                m_ref[j, h] = m_new

    @pl.when(p == num_steps - 1)
    def _finalize():
        for j in range(BQ):
            for h in range(KH):
                out = acc_ref[j, h] / jnp.maximum(l_ref[j, h], 1e-30)
                o_ref[j, h] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "batch_block", "logit_cap"))
def decode_bf16(q, k_cache, v_cache, block_tables, start_pos, window=0, *,
                sm_scale=None, batch_block=8, logit_cap=0.0):
    B, C, n_heads, head_dim = q.shape
    _, block_size, n_kv_heads, _ = k_cache.shape
    G = n_heads // n_kv_heads
    scale = sm_scale if sm_scale is not None else head_dim**-0.5
    BQ = max(min(batch_block, B), 1)
    B_pad = ((B + BQ - 1) // BQ) * BQ
    if B_pad != B:
        q = jnp.pad(q, ((0, B_pad - B), (0, 0), (0, 0), (0, 0)))
        block_tables = jnp.pad(block_tables, ((0, B_pad - B), (0, 0)))
        start_pos = jnp.pad(start_pos, (0, B_pad - B))
    q4 = q.reshape(B_pad, n_kv_heads, G, head_dim)
    P = block_tables.shape[1]
    win = jnp.asarray(window, jnp.int32).reshape(1)
    def q_map(bb, p, bt, sp, w):
        return (bb, 0, 0, 0)
    def kv_map_for(j):
        def kv_map(bb, p, bt, sp, w):
            return (bt[bb * BQ + j, p], 0, 0, 0)
        return kv_map
    in_specs = [pl.BlockSpec((BQ, n_kv_heads, G, head_dim), q_map)]
    kv_args = []
    for j in range(BQ):
        spec = pl.BlockSpec((1, block_size, n_kv_heads, head_dim), kv_map_for(j))
        in_specs.extend([spec, spec])
        kv_args.extend([k_cache, v_cache])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B_pad // BQ, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BQ, n_kv_heads, G, head_dim), q_map),
        scratch_shapes=[
            pltpu.VMEM((BQ, n_kv_heads, G, 1), jnp.float32),
            pltpu.VMEM((BQ, n_kv_heads, G, 1), jnp.float32),
            pltpu.VMEM((BQ, n_kv_heads, G, head_dim), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_bf16, sm_scale=scale, block_size=block_size,
        batch_block=BQ, logit_cap=logit_cap,
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B_pad, n_kv_heads, G, head_dim), q.dtype),
    )(block_tables.astype(jnp.int32), start_pos.astype(jnp.int32), win, q4, *kv_args)
    out = out[:B].reshape(B, n_kv_heads, 1, G, head_dim).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, 1, n_heads, head_dim)
