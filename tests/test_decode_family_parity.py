"""One decode path: the dense families decode through the Pallas kernels as
they do through the XLA gather form, with bf16 and with int8 weights.

``llama.decode_multi`` over per-layer pools is the one program a dense
deployment decodes with (``--quantization int8`` included). Here the
kernels run in interpret mode (CPU CI) inside it, family by family as
miniatures of the presets, against the same burst with ``use_kernel=False``;
then the engine, greedy, with int8 weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dynamo_tpu.ops.attention as attn
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quantize import quantize_params


def _cfg(**overrides):
    base = dict(
        name="family-test", d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=512, vocab_size=128, head_dim=128, rope_theta=10000.0,
        dtype=jnp.bfloat16,
    )
    base.update(overrides)
    return ModelConfig(**base)


_GEMMA = dict(act_fn="gelu_tanh", rmsnorm_unit_offset=True, post_norms=True,
              query_scale=128.0, rms_norm_eps=1e-6)

FAMILIES = {
    "gqa-head128": _cfg(),
    # the pool of a 64-lane head is held at 128 lanes (pool_head_dim)
    "qwen2.5": _cfg(qkv_bias=True, tie_word_embeddings=True, head_dim=64),
    "qwen3": _cfg(qk_norm=True, rms_norm_eps=1e-6),
    "gemma2": _cfg(**_GEMMA, attn_logit_softcap=30.0, sliding_window=24,
                   sliding_window_pattern=2),
    "gemma3": _cfg(**_GEMMA, qk_norm=True, sliding_window=24,
                   sliding_window_pattern=2, rope_local_theta=1000.0),
    "head256": _cfg(n_heads=2, n_kv_heads=1, head_dim=256),
    # positions 33..44 less 17: the first visible key is mid-page
    "window-straddles-a-page": _cfg(sliding_window=17),
    "rope-scaling": _cfg(rope_scaling_factor=8.0),
}


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """Both paged-attention kernels under the Pallas interpreter."""
    for name in ("paged_attention_decode_kernel", "paged_attention_kernel"):
        monkeypatch.setattr(
            attn, name, functools.partial(getattr(attn, name), interpret=True)
        )


def _scramble(params, seed):
    """Norm weights and biases away from their neutral initial values,
    which would hide an epilogue the two paths disagree on."""
    r = np.random.default_rng(seed)

    def draw(name, a):
        if name.endswith("norm"):
            return jnp.asarray(r.uniform(0.5, 1.5, a.shape), a.dtype)
        if name in ("bq", "bk", "bv"):
            return jnp.asarray(r.standard_normal(a.shape) * 0.1, a.dtype)
        return a

    layers = {k: draw(k, v) for k, v in params["layers"].items()}
    return dict(params, layers=layers)


@pytest.mark.parametrize("weights", ["bf16", "int8"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_decode_burst_through_the_kernels_matches_the_gather_form(
    interpreted_kernels, family, weights
):
    """One burst of 8 steps over ragged rows and a dead slot (stale position,
    a table of blocks it does not own): the tokens of the live rows are the
    XLA path's and the log-probabilities agree within bf16 tolerance."""
    c = FAMILIES[family]
    B, bs, P, NB, steps = 4, 16, 4, 20, 8
    rng = np.random.default_rng(len(family))
    params = _scramble(llama.init_params(c, jax.random.PRNGKey(3)), seed=11)
    if weights == "int8":
        params, _ = quantize_params(params, llama.param_logical_axes(c))
    tables = jnp.asarray(rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32))
    tables = tables.at[3].set(tables[0])  # row 3, the dead slot, owns no block
    hist = jnp.asarray([44, 33, 17, 0], jnp.int32)
    prompt = jnp.asarray(rng.integers(0, c.vocab_size, (B, 48)).astype(np.int32))
    kc, vc = llama.init_kv_cache(c, NB, bs, layered=True)
    assert kc[0].shape[-1] % 128 == 0
    _, kc, vc = llama.forward_paged(
        params, c, prompt, jnp.zeros((B,), jnp.int32), hist, tables, kc, vc,
        first_chunk=True,
    )
    live = np.asarray([True, True, True, False])
    tok = jnp.asarray(rng.integers(0, c.vocab_size, (B,)).astype(np.int32))
    pos = jnp.where(live, hist, 10**5)  # the dead slot's position is stale
    zeros, ones = jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32)

    def burst(use_kernel):
        out = jax.jit(lambda p, *rest: llama.decode_multi(
            p, c, *rest, num_steps=steps, use_kernel=use_kernel
        ))(
            params, tok, pos, jnp.asarray(live, jnp.int32), tables, kc, vc,
            jax.random.PRNGKey(0), ones * 0.0, zeros, ones,
        )
        return np.asarray(out[0])[live], np.asarray(out[1])[live]

    toks_x, logp_x = burst(False)
    toks_k, logp_k = burst(True)
    assert toks_k.shape == (3, steps)
    np.testing.assert_array_equal(toks_k, toks_x)
    np.testing.assert_allclose(logp_k, logp_x, atol=4e-2)


ENGINE_CASES = {
    # name: (family, prompt tokens, max_model_len)
    "gqa-head128": ("gqa-head128", 8, 96),
    "qwen3": ("qwen3", 8, 96),
    "gemma3": ("gemma3", 8, 96),
    # a decode table bucket of 32 pages: past every other case's table
    "prompt-of-300-tokens": ("gqa-head128", 300, 4096),
}


@pytest.mark.parametrize("case", sorted(ENGINE_CASES))
async def test_engine_int8_kernels_match_the_gather_form(interpreted_kernels, case):
    """The engine, greedy, ``quantization="int8"``: the stream through the
    kernels is token for token the ``use_kernel=False`` stream."""
    from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs
    from dynamo_tpu.engines.tpu.engine import table_width_bucket
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.engine import collect

    family, n_prompt, max_len = ENGINE_CASES[case]
    prompt = [(i % 90) + 3 for i in range(n_prompt)]

    async def run(use_kernel):
        e = JaxEngine(JaxEngineArgs(
            config=FAMILIES[family], block_size=16, num_kv_blocks=128,
            max_num_seqs=4, max_model_len=max_len, quantization="int8",
            use_kernel=use_kernel,
        ))
        assert e.runner.attention_impl == ("pallas" if use_kernel else "xla")
        widths = []
        dispatch = e.runner.decode_dispatch
        e.runner.decode_dispatch = lambda nb, *a, **k: (
            widths.append(int(nb)), dispatch(nb, *a, **k))[1]
        try:
            req = PreprocessedRequest(
                token_ids=prompt, request_id=f"{case}-{use_kernel}",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=10),
            )
            outs = await collect(e.generate(req, Context()))
            assert not [o.error for o in outs if o.error]
            return [t for o in outs for t in o.token_ids], widths
        finally:
            await e.stop()

    base, _ = await run(False)
    served, widths = await run(True)
    assert len(base) == 10 and served == base
    assert max(widths) >= table_width_bucket(-(-n_prompt // 16), max_len // 16)
