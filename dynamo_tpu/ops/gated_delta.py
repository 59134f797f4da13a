"""Gated DeltaNet (the gated delta rule, arXiv:2412.06464) mixer core, in
plain XLA.

Two forms of one recurrence, per value head with a float32 state S [Dk key,
Dv value]:

    S <- exp(g_t) S;  u_t = beta_t (v_t - k_t S);  S <- S + k_t^T u_t
    o_t = q_t S

The state is multiplied by ``exp(g)(I - beta k^T k)``, a MATRIX: no choice of
A, B, C, x makes ops/mamba2's scalar-decay recurrence say it. ``gdn_step`` is
the one-token form over every slot (the CPU path and the tests' oracle of
ops/pallas/gdn_step.py). ``gdn_chunk_scan`` runs a prefill chunk in blocks
of ``chunk`` tokens from a carried state. Inside a block, with ``gamma`` the
running sum of g:

    T  = (I + strict_tril(diag(beta) (K K^T * e^(gamma_i - gamma_j))))^-1
    V~ = T diag(beta) V;   K~ = T diag(beta) (K * e^gamma)

and then, block after block (sequential in S, a STATIC unroll: a prefill
program holds no ``while``, which is how benchmark/trace_names/ tells it from
a decode burst):

    V' = V~ - K~ S
    O  = (Q * e^gamma) S + tril(Q K^T * e^(gamma_i - gamma_j)) V'
    S <- e^(gamma_C) S + (K * e^(gamma_C - gamma))^T V'

``T`` is the inverse of a unit lower-triangular matrix: forward substitution
inside diagonal blocks of ``INV_BASE`` rows (a static unroll, every block of
every head at once) and the block formula ``[[A, 0], [C, B]]^-1 = [[A^-1, 0],
[-B^-1 C A^-1, B^-1]]`` up from there. (The product form ``(I - A)(I +
A^2)(I + A^4)...`` is a dozen matmuls and no substitution, but its terms grow
like binomials where neighbouring keys are alike, and cancel in float32.)

It returns the state at the END OF EVERY BLOCK, which is what the engine
snapshots for prefix reuse. A padded position is given g = 0 and beta = 0 by
the caller: no decay, ``u`` = 0, so padding leaves the state untouched.
Everything is float32 and every contraction runs at ``highest`` precision
(on the TPU a float32 contraction otherwise rounds its inputs to bfloat16,
and the substitution feeds on its own results).

q and k come in L2-normalised per head, q scaled by ``Dk ** -0.5``, both
repeated to the value heads (``prepare_qk``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
INV_BASE = 8
L2_EPS = 1e-6


def prepare_qk(q: jnp.ndarray, k: jnp.ndarray, repeat: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q, k [..., Hk, Dk] after the conv and silu -> float32 [..., Hk *
    repeat, Dk]: each L2-normalised over its head's lanes, q over
    ``sqrt(Dk)``, value head i reading key head ``i // repeat``."""
    def unit(x):
        xf = x.astype(_F32)
        return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + L2_EPS)

    q = unit(q) * q.shape[-1] ** -0.5
    return jnp.repeat(q, repeat, axis=-2), jnp.repeat(unit(k), repeat, axis=-2)


def gdn_step(
    q: jnp.ndarray,  # [B, H, Dk] (prepare_qk)
    k: jnp.ndarray,  # [B, H, Dk]
    v: jnp.ndarray,  # [B, H, Dv]
    g: jnp.ndarray,  # [B, H] log decay; 0 for a row that must not move
    beta: jnp.ndarray,  # [B, H]; 0 for a row that must not move
    state: jnp.ndarray,  # [B, H, Dk, Dv]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token: (o [B, H, Dv] float32, new state in the dtype it came in;
    the arithmetic is float32)."""
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    S = state.astype(_F32) * jnp.exp(g.astype(_F32))[..., None, None]
    u = beta.astype(_F32)[..., None] * (vf - (kf[..., None] * S).sum(-2))
    new = (S + kf[..., None] * u[..., None, :]).astype(state.dtype)
    return (qf[..., None] * new.astype(_F32)).sum(-2), new


def _inv_unit_lower(A: jnp.ndarray) -> jnp.ndarray:
    """(I + A)^-1 for A [..., C, C] strictly lower triangular."""
    C = A.shape[-1]
    n = C // INV_BASE
    base = INV_BASE if C % INV_BASE == 0 and n & (n - 1) == 0 else C
    n = C // base
    lead = A.shape[:-2]
    # The diagonal blocks, [..., n, base, base], by forward substitution:
    # row i of the inverse is e_i - A[i, :i] (rows < i of the inverse).
    Ab = A.reshape(lead + (n, base, n, base))
    D = jnp.stack([Ab[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(base, dtype=_F32)
    T = jnp.broadcast_to(eye, D.shape)
    for i in range(1, base):
        row = eye[i] - jnp.einsum(
            "...j,...jk->...k", D[..., i, :i], T[..., :i, :], precision=_HI)
        T = T.at[..., i, :].set(row)
    # Pairs of neighbouring inverted blocks merge until one is left.
    size = base
    while n > 1:
        Ab = A.reshape(lead + (n, size, n, size))
        low = jnp.stack(  # the block under each pair's first diagonal block
            [Ab[..., 2 * p + 1, :, 2 * p, :] for p in range(n // 2)], axis=-3)
        Tp = T.reshape(lead + (n // 2, 2, size, size))
        a, b = Tp[..., 0, :, :], Tp[..., 1, :, :]
        c = -jnp.einsum("...ij,...jk,...kl->...il", b, low, a, precision=_HI)
        top = jnp.concatenate([a, jnp.zeros_like(a)], axis=-1)
        T = jnp.concatenate([top, jnp.concatenate([c, b], axis=-1)], axis=-2)
        n, size = n // 2, size * 2
    return T.reshape(lead + (C, C))


def gdn_chunk_scan(
    q: jnp.ndarray,  # [B, T, H, Dk] (prepare_qk)
    k: jnp.ndarray,  # [B, T, H, Dk]
    v: jnp.ndarray,  # [B, T, H, Dv]
    g: jnp.ndarray,  # [B, T, H] log decay; 0 at padded positions
    beta: jnp.ndarray,  # [B, T, H]; 0 at padded positions
    state: jnp.ndarray,  # [B, H, Dk, Dv] float32, before the chunk
    *,
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (o [B, T, H, Dv] float32, block-end states [B, T // chunk, H,
    Dk, Dv] float32). T is a multiple of ``chunk``."""
    Bsz, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = chunk
    nc = T // C
    assert nc * C == T, (T, C)

    def blocks(x):  # [B, T, H, ...] -> [B, nc, H, C, ...]
        x = x.astype(_F32).reshape((Bsz, nc, C, H) + x.shape[3:])
        return jnp.moveaxis(x, 3, 2)

    qf, kf, vf, gf, bf = blocks(q), blocks(k), blocks(v), blocks(g), blocks(beta)
    gamma = jnp.cumsum(gf, axis=-1)  # [B, nc, H, C], inclusive
    seg = gamma[..., :, None] - gamma[..., None, :]  # gamma_i - gamma_j
    tril = jnp.tril(jnp.ones((C, C), dtype=bool))
    decay = jnp.exp(jnp.where(tril, seg, -jnp.inf))  # 0 above the diagonal
    kk = jnp.einsum("bnhik,bnhjk->bnhij", kf, kf, precision=_HI)
    strict = jnp.tril(jnp.ones((C, C), dtype=bool), -1)
    A = jnp.where(strict, bf[..., :, None] * kk * decay, 0.0)
    Tm = _inv_unit_lower(A) * bf[..., None, :]  # T diag(beta)
    eg = jnp.exp(gamma)[..., None]
    v_t = jnp.einsum("bnhij,bnhjv->bnhiv", Tm, vf, precision=_HI)
    k_t = jnp.einsum("bnhij,bnhjk->bnhik", Tm, kf * eg, precision=_HI)
    qk = jnp.einsum("bnhik,bnhjk->bnhij", qf, kf, precision=_HI) * decay
    q_in = qf * eg
    to_end = kf * jnp.exp(gamma[..., -1:] - gamma)[..., None]  # K * e^(gamma_C - gamma)
    g_end = jnp.exp(gamma[..., -1])[..., None, None]  # [B, nc, H, 1, 1]

    S = state.astype(_F32)
    outs, ends = [], []
    for n in range(nc):  # sequential in S: a static unroll, no ``while``
        v_new = v_t[:, n] - jnp.einsum("bhik,bhkv->bhiv", k_t[:, n], S, precision=_HI)
        o = jnp.einsum("bhik,bhkv->bhiv", q_in[:, n], S, precision=_HI)
        o = o + jnp.einsum("bhij,bhjv->bhiv", qk[:, n], v_new, precision=_HI)
        S = g_end[:, n] * S + jnp.einsum(
            "bhik,bhiv->bhkv", to_end[:, n], v_new, precision=_HI)
        outs.append(o)
        ends.append(S)
    o = jnp.moveaxis(jnp.stack(outs, axis=1), 2, 3)  # [B, nc, C, H, Dv]
    return o.reshape(Bsz, T, H, Dv), jnp.stack(ends, axis=1)
