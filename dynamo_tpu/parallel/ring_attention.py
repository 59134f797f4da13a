"""Ring attention: sequence-parallel exact attention for long context.

The long-context strategy the SURVEY calls first-class: shard the sequence
over the ``sp`` mesh axis, keep each device's Q resident, and rotate K/V
shards around the ring with ``ppermute`` while accumulating flash-style
online softmax — exact attention over sequences far beyond one device's
memory, with communication overlapped against compute by XLA.

This is the TPU-native counterpart of the reference's long-context serving
(context parallelism in its engines): collectives over ICI neighbors
(ppermute = ring), no all-gather of the full sequence, O(T/n) activation
memory per device.

Public pattern: ring attention (Liu et al.) / the scaling-book sharding
recipe; implementation here is original, built on shard_map + ppermute.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _flash_block(q, k, v, mask, m, l, acc, scale):
    """One online-softmax accumulation step.

    q [B,H,Tq,D], k/v [B,H,Tk,D], mask [Tq,Tk] bool, carries m/l [B,H,Tq,1],
    acc [B,H,Tq,D] (all float32)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, acc_new


def ring_attention(
    q: jnp.ndarray,  # [B, T, H, D] — T sharded over `axis` under shard_map
    k: jnp.ndarray,  # [B, T, KH, D]
    v: jnp.ndarray,  # [B, T, KH, D]
    *,
    axis: str,
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Per-shard body (call under shard_map; see make_ring_attention).

    Each rank holds a T/n slice; K/V slices rotate n times around the ring.
    GQA: KH may divide H; K/V heads are broadcast over the query groups.
    """
    B, T_blk, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = sm_scale if sm_scale is not None else D**-0.5
    idx = jax.lax.axis_index(axis)
    n = jax.lax.psum(1, axis)

    # [B, H, T, D] layout for the inner compute
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)
    if G > 1:
        expand = lambda x: jnp.repeat(  # noqa: E731
            x.astype(jnp.float32).transpose(0, 2, 1, 3), G, axis=1
        )
    else:
        expand = lambda x: x.astype(jnp.float32).transpose(0, 2, 1, 3)  # noqa: E731

    q_pos = idx * T_blk + jax.lax.broadcasted_iota(jnp.int32, (T_blk, T_blk), 0)

    perm = [(j, (j + 1) % n) for j in range(n)]  # ring: j → j+1

    def body(i, carry):
        k_c, v_c, m, l, acc = carry
        # The K/V block currently held started at rank (idx - i) mod n.
        src = jax.lax.rem(idx - i + n, n)
        k_pos = src * T_blk + jax.lax.broadcasted_iota(jnp.int32, (T_blk, T_blk), 1)
        mask = (q_pos >= k_pos) if causal else jnp.ones_like(q_pos, dtype=bool)
        m, l, acc = _flash_block(qf, expand(k_c), expand(v_c), mask, m, l, acc, scale)
        # Rotate for the next step (the final rotation is harmless and keeps
        # the loop body uniform; XLA overlaps it with the epilogue).
        k_c = jax.lax.ppermute(k_c, axis, perm)
        v_c = jax.lax.ppermute(v_c, axis, perm)
        return k_c, v_c, m, l, acc

    m0 = jnp.full((B, H, T_blk, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T_blk, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, T_blk, D), jnp.float32)
    _, _, m, l, acc = jax.lax.fori_loop(0, n, body, (k, v, m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)  # causal ⇒ every query sees itself
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, T_blk, H, D]


def make_ring_attention(mesh: Mesh, axis: str = "sp", *, causal: bool = True):
    """Jitted [B, T, H, D] ring attention with T sharded over ``axis``."""
    spec = P(None, axis, None, None)
    from dynamo_tpu.runtime.device_observe import watched_jit

    fn = jax.shard_map(
        functools.partial(ring_attention, axis=axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return watched_jit("parallel.ring_attention", jax.jit(fn))
