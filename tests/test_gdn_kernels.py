"""The gated delta rule's two forms (ops/gated_delta.py) and its live-row
Pallas kernel (ops/pallas/gdn_step.py), on the CPU at small sizes.

The chunk form against the token-by-token recurrence (blocks, ragged
``chunk_lens`` as g = beta = 0 on padding, a start state, every block-end
state); ``gdn_step_live`` under the interpreter against the XLA step over
every slot (dead slots untouched bit for bit, a list of one live row, none
live).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import gated_delta as gd
from dynamo_tpu.ops.pallas.gdn_step import gdn_step_live, gdn_step_reason
from dynamo_tpu.ops.pallas.ssd_step import live_row_list

# float32 on both sides, every contraction at ``highest``: what is left is the
# order of float32 sums (a block's inverse and three products against 64
# rank-one updates): 3e-7 measured at outputs of magnitude 0.7.
TOL = 5e-6


def _inputs(seed, B, T, H, HK, Dk, Dv, lens=None, alike=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, HK, Dk))
    k = jax.random.normal(ks[1], (B, T, HK, Dk))
    if alike:  # neighbouring keys nearly parallel: the inverse's hard case
        k = k[:, :1] + 0.05 * k
    v = jax.random.normal(ks[2], (B, T, H, Dv))
    g = -0.1 * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = jax.random.normal(ks[5], (B, H, Dk, Dv))
    if lens is not None:
        real = (jnp.arange(T)[None] < jnp.asarray(lens)[:, None])[..., None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    q, k = gd.prepare_qk(q, k, H // HK)
    return q, k, v, g, beta, S0


def _token_by_token(q, k, v, g, beta, S):
    outs, states = [], []
    for t in range(q.shape[1]):
        o, S = gd.gdn_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        outs.append(o)
        states.append(S)
    return jnp.stack(outs, 1), states


@pytest.mark.parametrize("case", [
    dict(name="blocks of 64", T=128, chunk=64, Dk=32, Dv=32),
    dict(name="blocks of 16, a start state", T=64, chunk=16, Dk=16, Dv=16),
    dict(name="ragged chunk_lens", T=64, chunk=16, Dk=16, Dv=24, lens=(37, 0, 64)),
    dict(name="one block", T=16, chunk=16, Dk=8, Dv=16),
    dict(name="a block no multiple of the inverse's base", T=24, chunk=12, Dk=8, Dv=8),
    dict(name="keys nearly parallel", T=128, chunk=64, Dk=32, Dv=32, alike=True),
], ids=lambda c: c["name"])
def test_chunk_form_is_the_token_by_token_rule(case):
    lens = case.get("lens")
    B = len(lens) if lens else 2
    q, k, v, g, beta, S0 = _inputs(
        3, B, case["T"], 4, 2, case["Dk"], case["Dv"], lens, case.get("alike", False))
    want_o, want_S = _token_by_token(q, k, v, g, beta, S0)
    got_o, ends = gd.gdn_chunk_scan(q, k, v, g, beta, S0, chunk=case["chunk"])
    scale = max(1.0, float(jnp.abs(want_o).max()))
    if lens:  # outputs at padded positions are nobody's
        real = np.arange(case["T"])[None] < np.asarray(lens)[:, None]
        got_o, want_o = got_o[real], want_o[real]
    assert float(jnp.abs(got_o - want_o).max()) <= TOL * scale
    for n in range(case["T"] // case["chunk"]):  # every block-end state
        want = want_S[(n + 1) * case["chunk"] - 1]
        assert float(jnp.abs(ends[:, n] - want).max()) <= TOL * max(1.0, float(jnp.abs(want).max()))
    if lens:  # a row of length 0 keeps its state bit for bit
        assert (np.asarray(ends[1, -1]) == np.asarray(S0[1])).all()


def test_a_chunk_cut_anywhere_is_one_call():
    q, k, v, g, beta, S0 = _inputs(5, 1, 96, 4, 2, 16, 16)
    whole, ends = gd.gdn_chunk_scan(q, k, v, g, beta, S0, chunk=16)
    cut = lambda a: (a[:, :32], a[:, 32:])
    (q1, q2), (k1, k2), (v1, v2), (g1, g2), (b1, b2) = map(cut, (q, k, v, g, beta))
    o1, e1 = gd.gdn_chunk_scan(q1, k1, v1, g1, b1, S0, chunk=16)
    o2, e2 = gd.gdn_chunk_scan(q2, k2, v2, g2, b2, e1[:, -1], chunk=16)
    assert float(jnp.abs(jnp.concatenate([o1, o2], 1) - whole).max()) <= TOL
    assert float(jnp.abs(e2[:, -1] - ends[:, -1]).max()) <= TOL


@pytest.mark.parametrize("live", [
    (1, 0, 1, 1, 0, 0, 1, 0), (0, 0, 0, 1, 0, 0, 0, 0), (0,) * 8, (1,) * 8,
], ids=["some live", "one live row", "none live", "every row live"])
@pytest.mark.parametrize("heads_a_step", [None, 2])
def test_live_row_kernel_is_the_xla_step_on_live_rows_and_touches_no_other(live, heads_a_step):
    B, H, Dk, Dv = len(live), 4, 16, 128
    q, k, v, g, beta, S0 = _inputs(7, B, 1, H, 2, Dk, Dv)
    q, k, v, g, beta = q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
    active = jnp.asarray(live, jnp.int32)
    want_o, want_S = gd.gdn_step(q, k, v, g, beta, S0)
    got_o, got_S = gdn_step_live(q, k, v, g, beta, S0 + 0, *live_row_list(active),
                                 heads_a_step=heads_a_step, interpret=True)
    for b, on in enumerate(live):
        if on:
            assert float(jnp.abs(got_o[b] - want_o[b]).max()) <= 1e-6
            assert float(jnp.abs(got_S[b] - want_S[b]).max()) <= 1e-6
        else:  # a dead slot: its state bit for bit, its output zero
            assert (np.asarray(got_S[b]) == np.asarray(S0[b])).all()
            assert not np.asarray(got_o[b]).any()


@pytest.mark.parametrize("shape,dtype,why", [
    ((64, 32, 128, 128), jnp.float32, None),
    ((64, 32, 128, 128), jnp.bfloat16, "not float32"),
    ((2, 4, 16, 16), jnp.float32, "128 lanes"),
    ((2, 4, 12, 128), jnp.float32, "8 sublanes"),
])
def test_the_kernel_is_chosen_by_the_state_it_is_given(shape, dtype, why):
    got = gdn_step_reason(True, shape, dtype)
    assert (got is None) if why is None else (why in got)
    assert "use_kernel is false" in gdn_step_reason(False, shape, dtype)
