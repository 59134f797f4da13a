"""The live-row state-update kernel (ops/pallas/ssd_step.py) under the Pallas
interpreter against ``ops/mamba2.ssd_step``, the XLA form it replaces in a
decode burst: live rows agree, dead rows are neither read nor written.
Mosaic's verdict on the same shapes is tests/test_mosaic_compile.py's, the
chip's ops/pallas/chip_check.py's."""

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models.config import (
    DenseFFNSpec, LightningSpec, Mamba2Spec, tiny_hybrid_config, tiny_sala_config,
)
from dynamo_tpu.ops import mamba2 as m2
from dynamo_tpu.ops.pallas import ssd_step as sk

SLOTS = 6
SHAPES = {
    # name: (H, P, N, G): the two served shapes
    "mamba2_grouped": (64, 64, 128, 8),
    "lightning_per_head": (32, 128, 128, 32),
}
LIVE_SETS = {
    "none": [], "one": [2], "first": [0], "last": [SLOTS - 1],
    "scattered": [1, 3, 4], "all": list(range(SLOTS)),
}


def _inputs(H, P, N, G, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (SLOTS, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (SLOTS, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (SLOTS, G, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (SLOTS, G, N), jnp.float32)
    state = jax.random.normal(ks[5], (SLOTS, H, P, N), jnp.float32)
    return x, dt, A, Bm, Cm, state


def _active(live):
    active = np.zeros(SLOTS, np.int32)
    active[live] = 1
    return jnp.asarray(active)


@pytest.mark.parametrize("live", sorted(LIVE_SETS))
def test_live_row_list_keeps_the_work_list_invariant(live):
    """Length slots + 1 (never a list of one entry), the live slots first
    and in order, every entry at or past ``total`` repeating the last."""
    want = LIVE_SETS[live]
    total, step_row, mask = sk.live_row_list(_active(want))
    step_row = np.asarray(step_row)
    assert total.shape == (1,) and int(total[0]) == len(want)
    assert step_row.shape == (SLOTS + 1,) and step_row.dtype == np.int32
    assert list(step_row[: len(want)]) == want
    assert (step_row[len(want):] == (want[-1] if want else 0)).all()
    assert list(np.flatnonzero(np.asarray(mask))) == want


@pytest.mark.parametrize("live", sorted(LIVE_SETS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_updates_the_live_rows_and_no_other(shape, live):
    H, P, N, G = SHAPES[shape]
    x, dt, A, Bm, Cm, state = _inputs(H, P, N, G)
    rows = sk.live_row_list(_active(LIVE_SETS[live]))
    y, new = sk._ssd_step_live_impl(x, dt, A, Bm, Cm, state, *rows, interpret=True)
    y_ref, new_ref = m2.ssd_step(
        x, jnp.where(rows.mask[:, None], dt, 0.0), A, Bm, Cm, state)
    dead = ~np.asarray(rows.mask)
    assert y.shape == (SLOTS, H, P) and y.dtype == jnp.float32
    # a dead row: its state bit for bit, its y zero (not the buffer's)
    np.testing.assert_array_equal(np.asarray(new)[dead], np.asarray(state)[dead])
    assert not np.asarray(y)[dead].any()
    np.testing.assert_allclose(
        np.asarray(new)[~dead], np.asarray(new_ref)[~dead], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(y)[~dead], np.asarray(y_ref)[~dead], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("heads_a_step", [1, 2, 4])
def test_kernel_in_head_tiles(heads_a_step):
    """Several grid steps a row: a tile of heads reads its own groups."""
    H, P, N, G = 8, 16, 128, 4
    x, dt, A, Bm, Cm, state = _inputs(H, P, N, G, seed=1)
    rows = sk.live_row_list(_active([1, 4, 5]))
    y, new = sk._ssd_step_live_impl(
        x, dt, A, Bm, Cm, state, *rows, heads_a_step=heads_a_step, interpret=True)
    y_ref, new_ref = m2.ssd_step(
        x, jnp.where(rows.mask[:, None], dt, 0.0), A, Bm, Cm, state)
    live = np.asarray(rows.mask)
    np.testing.assert_array_equal(np.asarray(new)[~live], np.asarray(state)[~live])
    np.testing.assert_allclose(np.asarray(new), np.asarray(new_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(y)[live], np.asarray(y_ref)[live], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("case,shape,dtype,want", [
    ("served_mamba2", (64, 64, 64, 128), jnp.float32, None),
    ("served_lightning", (32, 32, 128, 128), jnp.float32, None),
    ("bfloat16_state", (64, 64, 64, 128), jnp.bfloat16, "not float32"),
    ("narrow_state", (8, 8, 16, 16), jnp.float32, "128 lanes"),
    ("odd_head", (8, 8, 12, 128), jnp.float32, "8 sublanes"),
])
def test_reason_reads_the_shapes(case, shape, dtype, want):
    assert sk.ssd_step_reason(False, shape, dtype) == sk.NO_KERNELS
    why = sk.ssd_step_reason(True, shape, dtype)
    assert (why is None) if want is None else (want in why)
    if want is None:  # a whole row a step at both served shapes
        assert sk.head_tile(*shape[1:]) == shape[1]


def _recurrent_only(kind):
    """A model of recurrent layers and dense FFNs alone, its state at the
    kernel's tiling (N = 128): on the CPU ``use_kernel`` then reaches no
    kernel but this one."""
    ffn = DenseFFNSpec(d_ff=64)
    if kind == "mamba2":
        mixer = Mamba2Spec(n_heads=4, head_dim=8, state_size=128, n_groups=2, scan_block=16)
        return tiny_hybrid_config(layer_specs=(mixer, ffn, mixer), n_layers=3)
    mixer = LightningSpec(n_heads=2, head_dim=128, scan_block=16, snapshot_every=64)
    return dataclasses.replace(
        tiny_sala_config(), layer_specs=(mixer, ffn, mixer, ffn), n_layers=4)


@pytest.mark.parametrize("kind", ["mamba2", "lightning"])
def test_bursts_equal_the_xla_path_token_for_token(kind, monkeypatch):
    """Three bursts of 8 steps through ``decode_multi``, rows going in and
    out of ``active`` between them: the kernel path (interpreted) gives the
    XLA path's tokens, and a row that sat a burst out kept its state bit
    for bit."""
    calls = []

    def interpreted(*args):
        calls.append(args[5].shape)
        return sk._ssd_step_live_impl(*args, interpret=True)

    monkeypatch.setattr(hybrid, "ssd_step_live", interpreted)
    cfg = _recurrent_only(kind)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B, steps = 5, 8
    zeros = jnp.zeros((B,), jnp.float32)

    def burst(use_kernel, tokens, pos, active, ssm):
        out = llama.decode_multi(
            params, cfg, tokens, pos, active, jnp.zeros((B, 1), jnp.int32), (), (),
            jax.random.PRNGKey(1), zeros, jnp.zeros((B,), jnp.int32), zeros + 1.0,
            num_steps=steps, use_kernel=use_kernel, want_logprobs=False,
            want_carry=True, ssm=ssm,
        )
        return out[0], out[-4], out[-3], out[-2]  # tokens, carry, positions, ssm

    state = hybrid.init_ssm_state(cfg, B)
    state = jax.tree.map(  # every slot holds something: a dead one must keep it
        lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape, a.dtype) * 0.1, state)
    start = jnp.asarray(np.arange(B) + 3, jnp.int32)
    paths = {}
    for use_kernel in (False, True):
        toks, pos, ssm, seen = start, jnp.full((B,), 10, jnp.int32), state, []
        for live in ([0, 2, 3], [2], [1, 2, 4], []):
            active = jnp.asarray(np.isin(np.arange(B), live).astype(np.int32))
            before = ssm
            out, toks, pos, ssm = burst(use_kernel, toks, pos, active, ssm)
            seen.append(np.asarray(out))
            for old, new in zip(before["S"], ssm["S"]):
                dead = ~np.isin(np.arange(B), live)
                np.testing.assert_array_equal(np.asarray(new)[dead], np.asarray(old)[dead])
        paths[use_kernel] = (seen, ssm)
    n_rec = len(cfg.recurrent_specs)
    assert len(calls) == n_rec * 4  # traced once a layer a burst, inside the scan
    for a, b in zip(*(paths[k][0] for k in (False, True))):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(paths[False][1]["S"], paths[True][1]["S"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_a_step_outside_a_burst_keeps_the_xla_form(monkeypatch):
    """No live-row list (a prefill step, a test's single forward): the XLA
    recurrence over every row, whatever ``use_kernel``."""
    monkeypatch.setattr(hybrid, "ssd_step_live", lambda *a: pytest.fail("kernel called"))
    cfg = _recurrent_only("mamba2")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B = 3
    out = hybrid.forward(
        params, cfg, jnp.ones((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.int32), jnp.zeros((B, 1), jnp.int32), (), (),
        hybrid.init_ssm_state(cfg, B), use_kernel=True,
    )
    assert out[0].shape == (B, cfg.vocab_size)


FAMILY = "dynamo_tpu_engine_ssm_decode_rows_total"


def test_observe_ssm_decode_adds_both_series():
    from dynamo_tpu.engines.metrics import EngineStepMetrics
    from dynamo_tpu.runtime import metric_names as mn

    assert mn.ENGINE_SSM_DECODE_ROWS_TOTAL == FAMILY and FAMILY in mn.ALL_ENGINE
    m = EngineStepMetrics()
    assert FAMILY + "{" not in m.render()  # never touched without recurrent layers
    m.observe_ssm_decode(0, 0)
    assert f'{FAMILY}{{state="updated"}} 0' in m.render()
    m.observe_ssm_decode(8 * 3, 8 * 64)
    m.observe_ssm_decode(8 * 64, 8 * 64)  # a burst on the XLA form: every slot
    text = m.render()
    assert f'{FAMILY}{{state="updated"}} {8 * 67}' in text
    assert f'{FAMILY}{{state="slots"}} {8 * 128}' in text


@pytest.mark.parametrize("model", ["tiny-hybrid", "tiny-sala", "tiny"])
def test_engine_counts_the_state_rows_a_burst_updates(model):
    """Both series at 0 from start-up in an engine with recurrent layers
    (none in one without); on the CPU the recurrence keeps the XLA form, so
    a dispatched burst adds steps x slots to both (the share reads 100%:
    the mechanism did not engage) and ``/engine/stats`` says why."""
    import asyncio

    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.models.config import tiny_config
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.engine import collect

    config = {"tiny-hybrid": tiny_hybrid_config, "tiny-sala": tiny_sala_config,
              "tiny": tiny_config}[model]()
    slots, steps = 4, 4

    async def run():
        engine = JaxEngine(JaxEngineArgs(
            config=config, block_size=16, num_kv_blocks=64, max_num_seqs=slots,
            max_model_len=512, prefill_chunk=64, decode_steps=steps,
        ))
        try:
            at_start = engine.step_metrics.render()
            await collect(engine.generate(PreprocessedRequest(
                token_ids=list(range(3, 23)), request_id="a",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=6, ignore_eos=True),
            ), Context()))
            return at_start, engine.stats(), engine.step_metrics.render()
        finally:
            await engine.stop()

    at_start, stats, text = asyncio.run(run())
    if model == "tiny":
        assert stats["ssd_step"] is None
        assert FAMILY + "{" not in at_start and FAMILY + "{" not in text
        return
    assert stats["ssd_step"].startswith("xla every slot, ")
    for state in ("updated", "slots"):
        assert f'{FAMILY}{{state="{state}"}} 0' in at_start
    rows = {
        state: int(float(text.split(f'{FAMILY}{{state="{state}"}} ')[1].split()[0]))
        for state in ("updated", "slots")
    }
    assert rows["slots"] > 0 and rows["slots"] % (steps * slots) == 0
    assert rows["updated"] == rows["slots"]
