"""MiniCPM-SALA (``minicpm_sala``): block-sparse attention chosen by a cached
indexer beside lightning (linear) attention as recurrent state. Small sizes
(``tiny-sala``: float32, blocks of 16, top 4, dense under 64 tokens), CPU,
seeded; every comparison is of LOGITS (or log-probabilities), never tokens.

(a) the served forward through the cache against the plain reference, with
the reference's own selection and given the program's; (b) lightning =
``ssd_chunk_scan`` / ``ssd_step`` against the token-by-token recurrence; (c)
the indexer's cache; (d) the selection; (e) what each tolerance catches; (f)
``from_hf_config``; (g) snapshot spacing; (h) the engine: fresh, chunked, a
prefix hit; (i) the kernel form under the interpreter; (j) the benchmark's
copy of the reference; (k) refusals.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engines.tpu import JaxEngine, JaxEngineArgs, block_pool
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models import minicpm_sala_reference as ref
from dynamo_tpu.models.config import (
    MINICPM_SALA_HF,
    ModelConfig,
    minicpm_sala_pp4_config,
    tiny_hybrid_config,
    tiny_sala_config,
)
from dynamo_tpu.ops import sparse_attention as sa
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import collect

ROOT = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
BENCH_REF = os.path.join(ROOT, "benchmark", "references", "minicpm-sala-pp4.py")
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "minicpm-sala-pp4.json")
BLOCK, T, CHUNK = 16, 320, 64
# float32 on both sides: what is left is the order of float32 sums (the
# chunked scan against the token-by-token recurrence, the paged gather against
# the dense mask): 3e-7 measured at logits of magnitude 1.2. A bfloat16 state
# reads 2e-3, a dropped muP factor or a per-head gate 1e-2 and more (e).
TOL = 2e-5


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), np.abs(got - want).max()


@pytest.fixture(scope="module")
def tiny():
    """One sequence of 320 tokens through the system's forward in five chunks
    of 64 (4 pages each; the first dense over its own registers, every later
    one over the cache; queries from position 63 on take the sparse path),
    with what every sparse layer's indexer selected; and the reference."""
    c = tiny_sala_config()
    p = llama.init_params(c, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, 512, T).astype(np.int32)
    k, v = hybrid.init_kv_cache(c, 64, BLOCK)
    ssm = hybrid.init_ssm_state(c, 1)
    table = jnp.arange(1, T // BLOCK + 5, dtype=jnp.int32)[None]
    logits, picked = [], []
    for at in range(0, T, CHUNK):
        lg, k, v, ssm, _, sel = hybrid.forward(
            p, c, jnp.asarray(toks[None, at:at + CHUNK]), jnp.array([at]), jnp.array([CHUNK]),
            table, k, v, ssm, first_chunk=(at == 0), all_logits=True, want_selection=True)
        logits.append(np.asarray(lg[0]))
        picked.append([[np.asarray(a) for a in layer] for layer in sel])
    layers, model = ref.describe_layers(c), ref.describe_model(c)
    sparse_at = [i for i, L in enumerate(layers) if L["kind"] == "sparse"]
    want = ref.reference_forward(p, layers, toks, model, want=tuple(sparse_at))
    # the program's selection as the reference takes it: boolean [T, KH, blocks]
    n_blocks = T // BLOCK
    chosen = {}
    for n, i in enumerate(sparse_at):
        mask = np.zeros((T, 2, n_blocks), bool)
        for ci, per_layer in enumerate(picked):
            sel, count, _ = per_layer[n]
            for q in range(CHUNK):
                for g in range(2):
                    mask[ci * CHUNK + q, g, sel[0, q, g, : count[0, q]]] = True
        chosen[i] = mask
    return dict(c=c, p=p, toks=toks, logits=np.concatenate(logits), picked=picked, k=k, v=v,
                ssm=ssm, table=table, layers=layers, model=model, ref=want, chosen=chosen,
                sparse_at=sparse_at)


# -- (a) the system against the reference -----------------------------------------------------


def test_served_forward_matches_the_reference_where_the_selections_agree(tiny):
    """Rule (c): float32 on both sides select the same blocks (checked), and
    the plain comparison holds at every position."""
    for i in tiny["sparse_at"]:
        sparse_q = np.arange(T) + 1 >= tiny["layers"][i]["dense_len"]
        assert (np.asarray(tiny["ref"]["extra"][i]["selected"])[sparse_q]
                == tiny["chosen"][i][sparse_q]).all()
    _close(tiny["logits"], tiny["ref"]["logits"])


def test_served_forward_matches_the_reference_given_its_selection(tiny):
    """Rule (b): the reference's logits GIVEN THE PROGRAM'S selection."""
    given = ref.reference_forward(
        tiny["p"], tiny["layers"], tiny["toks"], tiny["model"],
        selection={i: jnp.asarray(m) for i, m in tiny["chosen"].items()})
    _close(tiny["logits"], given["logits"])


def test_selected_blocks_obey_the_reference_scores(tiny):
    """Rule (a): forced blocks are all present, and every other selected
    block's REFERENCE score is no lower than the reference's k-th best among
    the blocks that are not forced, less an epsilon (scores are shares of 1
    summed over 2 heads; float32 sums differ by 1e-6)."""
    for i in tiny["sparse_at"]:
        L = tiny["layers"][i]
        scores = np.asarray(tiny["ref"]["extra"][i]["scores"])  # [T, KH, blocks]
        forced = np.asarray(ref.ref_forced(np.arange(T), L, scores.shape[-1]))
        for t in range(L["dense_len"] - 1, T):
            for g in range(2):
                mine = tiny["chosen"][i][t, g]
                assert (mine & forced[t]).sum() == forced[t].sum()
                assert mine.sum() == min(L["topk"], t // BLOCK + 1)
                free = mine & ~forced[t]
                rest = np.sort(scores[t, g][~forced[t] & (np.arange(scores.shape[-1]) <= t // BLOCK)])
                if free.any():
                    kth = rest[-free.sum()]
                    assert scores[t, g][free].min() >= kth - 1e-5


def test_dense_len_switches_per_query(tiny):
    """Position 62 (a sequence of 63) attends densely, 63 (of 64) sparsely:
    the reference with EVERY block selected equals the plain one up to 62 and
    differs from 63 on."""
    sel = next(iter(tiny["chosen"].values()))
    everything = {i: jnp.ones_like(jnp.asarray(sel)) for i in tiny["sparse_at"]}
    dense = ref.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][:96], tiny["model"],
                                  selection={i: m[:96, :, :6] for i, m in everything.items()})
    plain = np.asarray(tiny["ref"]["logits"][:96])
    _close(dense["logits"][:63], plain[:63])
    assert np.abs(np.asarray(dense["logits"][80:]) - plain[80:]).max() > 1e-3
    is_sparse = np.concatenate([chunk[0][2][0] for chunk in tiny["picked"]])
    assert not is_sparse[:63].any() and is_sparse[63:].all()


# -- (b) lightning attention is the Mamba-2 recurrence ---------------------------------------------


@pytest.mark.parametrize("length", [64, 50, 1])
def test_lightning_scan_and_step_equal_the_token_recurrence(tiny, length):
    c = tiny["c"]
    spec = c.specs_of("lightning")[0]
    i = [n for n, s in enumerate(c.layer_specs) if s.kind == "lightning"][0]
    lp, L = tiny["p"]["layers"][i], tiny["layers"][i]
    x = jax.random.normal(jax.random.PRNGKey(length), (64, c.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, S_ref = ref.ref_lightning(x[:length], lp, L, c.rms_norm_eps)
        S0 = jnp.zeros((1, spec.n_heads, spec.head_dim, spec.head_dim), jnp.float32)
        pos = jnp.arange(64)[None]
        rope = hybrid.rope_table(pos, spec.head_dim, spec.rope_theta)
        got, S, _ = hybrid._lightning_mixer(
            c, spec, lp, x[None], jnp.array([length]), rope, S0,
            jnp.full((1, 4), 99, jnp.int32), jnp.zeros((4,) + S0.shape[1:], jnp.float32))
    _close(got[0, :length], want)
    _close(jnp.swapaxes(S[0], -1, -2), S_ref)  # the state is held [value, key]
    if length == 1:  # the decode step from that state
        with jax.default_matmul_precision("highest"):
            step, S1, _ = hybrid._lightning_mixer(
                c, spec, lp, x[None, 1:2], jnp.array([1]),
                hybrid.rope_table(pos[:, 1:2], spec.head_dim, spec.rope_theta), S, None, None)
            two, S2 = ref.ref_lightning(x[:2], lp, L, c.rms_norm_eps)
        _close(step[0, 0], two[1])
        _close(jnp.swapaxes(S1[0], -1, -2), S2)


# -- (c) the indexer's cache ------------------------------------------------------------------------


def test_compressed_keys_in_the_cache_are_the_windows_means(tiny):
    """After five chunks the cache's compressed keys are the means of the
    cache's own K rows, window by window, across chunk and page boundaries,
    each filed with the page of its window's LAST token."""
    c, k = tiny["c"], tiny["k"]
    n_attn = len(c.specs_of("attention"))
    sp = c.sparse_index
    hd = c.specs_of("attention")[0].head_dim
    ids = np.asarray(tiny["table"][0])
    for layer in range(n_attn):
        rows = np.asarray(k[layer])[ids][..., :hd].reshape(-1, 2, hd)[:T]
        kc = np.asarray(k[n_attn + layer])[ids][..., :hd]  # [pages, 4, KH, D]
        for e in range(1, T // sp.stride):
            end = e * sp.stride + sp.stride - 1
            want = rows[end - sp.kernel + 1: end + 1].mean(0)
            got = kc[end // BLOCK, (end % BLOCK) // sp.stride]
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        assert not kc[0, 0].any()  # no window ends in a sequence's first ``stride`` tokens


def test_a_shared_pages_compressed_keys_do_not_depend_on_what_follows(tiny):
    """Two continuations of one prefix write pages of their own; the prefix's
    pages (K, V and compressed keys) read the same afterwards, so a prefix
    hit reads what its builder left."""
    c, p = tiny["c"], tiny["p"]
    n_attn = len(c.specs_of("attention"))
    shared = np.asarray(tiny["table"][0][: 128 // BLOCK])
    before = [np.asarray(a)[shared] for a in tiny["k"]]
    k, v = tiny["k"], tiny["v"]
    for seed, own in ((1, 40), (2, 48)):
        table = jnp.concatenate([jnp.asarray(shared), jnp.arange(own, own + 8)])[None]
        toks = np.random.default_rng(seed).integers(3, 512, CHUNK).astype(np.int32)
        # the state does not matter to what the SPARSE layer 0 writes, but keep it honest
        out = hybrid.forward(p, c, jnp.asarray(toks[None]), jnp.array([128]), jnp.array([CHUNK]),
                             table, k, v, hybrid.init_ssm_state(c, 1))
        k, v = out[1], out[2]
        for a, b in zip(before, (np.asarray(x)[shared] for x in k)):
            np.testing.assert_array_equal(a, b)
    assert len(k) == n_attn * 2


# -- (e) what the tolerance catches ---------------------------------------------------------------


@pytest.mark.parametrize("fault", ["state_bf16", "no_residual_scale", "no_embed_scale",
                                   "no_logit_scale", "no_init_block", "gate_per_head"])
def test_each_fault_breaks_the_tolerance(tiny, fault):
    """A bfloat16 state, a dropped muP factor, a missing forced block and a
    gate per head each move the logits past TOL (so the comparison above
    would fail on the program that had the fault)."""
    bad = ref.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][:160], tiny["model"],
                                degrade=fault)
    err = np.abs(np.asarray(bad["logits"]) - np.asarray(tiny["ref"]["logits"][:160])).max()
    assert err > 20 * TOL * max(1.0, np.abs(np.asarray(tiny["ref"]["logits"])).max()), err


# -- (f) from_hf_config ---------------------------------------------------------------------------


def test_from_hf_config_yields_the_published_model():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "MiniCPM-SALA")["config"]
    assert {k: MINICPM_SALA_HF[k] for k in row} == row
    cfg = ModelConfig.from_hf_config(row)
    kinds = [s.kind for s in cfg.layer_specs[::2]]
    sparse_at = [i for i, s in enumerate(cfg.layer_specs[::2]) if s.kind == "attention"]
    assert sparse_at == [0, 9, 16, 17, 22, 29, 30, 31] and kinds.count("lightning") == 24
    assert all(s.kind == "dense_ffn" and s.d_ff == 16384 for s in cfg.layer_specs[1::2])
    attn, lightning = cfg.layer_specs[0], cfg.layer_specs[2]
    assert (attn.n_heads, attn.n_kv_heads, attn.head_dim, attn.positions) == (32, 2, 128, "none")
    assert attn.qk_norm and attn.gate and attn.gate_lanes and attn.sparse.topk == 64
    assert (lightning.n_heads, lightning.head_dim, lightning.snapshot_every) == (32, 128, 4096)
    assert cfg.embed_multiplier == 12 and cfg.logit_divisor == 16
    assert abs(cfg.residual_multiplier - 1.4 / 32**0.5) < 1e-12
    count = lambda c: sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(
            jax.eval_shape(lambda: hybrid.init_params(c, jax.random.PRNGKey(0)))))
    assert count(cfg) == 9_477_110_784  # 8 x 253.8 M + 24 x 285.2 M + 601.7 M: the card's "9B"
    cut = minicpm_sala_pp4_config()
    assert [s.kind for s in cut.layer_specs[::2]] == ["attention"] + ["lightning"] * 6 + ["attention"]
    assert round(count(cut) / 1e6) == 2821 and cut.residual_multiplier == cfg.residual_multiplier
    assert hybrid.ssm_state_bytes(cut) == 6 * 32 * 128 * 128 * 4  # 12.58 MB a snapshot entry


def test_benchmark_configuration_file_agrees_with_the_preset():
    with open(CONFIG_FILE) as f:
        file = json.load(f)
    hf = {k: file[k] for k in MINICPM_SALA_HF}
    published = dict(hf, num_hidden_layers=file["published"]["num_hidden_layers"],
                     mixer_types=file["published"]["mixer_types"])
    assert published == MINICPM_SALA_HF
    built = dataclasses.replace(ModelConfig.from_hf_config(hf), name="minicpm-sala-pp4",
                                max_position_embeddings=524288)
    assert built == minicpm_sala_pp4_config()
    assert file["reduced"] == ["num_hidden_layers"]
    sp = minicpm_sala_pp4_config().sparse_index
    assert {k: getattr(sp, k) for k in file["assumed"]["sparse_config"]} == file["assumed"]["sparse_config"]


# -- (g) snapshot spacing -------------------------------------------------------------------------


def test_snapshot_spacing_is_data_of_the_recurrent_spec():
    assert tiny_hybrid_config().snapshot_stride == (16, 16)  # every scan block, as before
    assert block_pool.snapshot_entries(tiny_hybrid_config(), 64, 16, 4) == block_pool.SSM_SNAPSHOT_ENTRIES
    assert tiny_sala_config().snapshot_stride == (16, 64)
    assert minicpm_sala_pp4_config().snapshot_stride == (64, 4096)
    # one entry for every boundary the pool's tokens can hold: 11264 x 64 / 4096
    assert block_pool.snapshot_entries(minicpm_sala_pp4_config(), 11264, 64, 32) == 176
    assert block_pool.snapshot_entries(tiny_sala_config(), 8, 16, 4) == 8  # two a decode row


def test_a_spaced_store_serves_the_last_boundary_both_reach_and_evicts_lru():
    store = block_pool.StateSnapshots(3, stride_blocks=4)
    hashes = list(range(100, 120))
    assert store.reserve(hashes[3]) == 0 and store.reserve(hashes[7]) == 1
    assert store.lookup(hashes, 11) == (8, 1)  # pages reach 11 blocks, a snapshot 8
    assert store.lookup(hashes, 7) == (4, 0)
    assert store.lookup(hashes, 3) == (0, -1)
    assert store.reserve(hashes[11]) == 2
    assert store.lookup(hashes, 8) == (8, 1)  # touched: block 3's entry is now the oldest
    assert store.reserve(hashes[15]) == 0 and store.evictions == 1
    assert store.lookup(hashes, 7) == (0, -1)


# -- (h) the engine ---------------------------------------------------------------------------------


def _req(tokens, rid, n):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        sampling=SamplingOptions(temperature=0.0, logprobs=1),
        stop=StopConditions(max_tokens=n, ignore_eos=True))


def _sig(outs):
    assert not [o.error for o in outs if o.error]
    return ([t for o in outs for t in o.token_ids],
            [lp[0].logprob for o in outs for lp in (o.logprobs or [])])


async def test_engine_serves_fresh_chunked_and_as_a_prefix_hit():
    """300 tokens of context in chunks of 64 across page and snapshot
    boundaries, 20 tokens decoded past it (compressed keys complete and the
    local blocks move while decoding); then the context + a fresh turn: K/V,
    the indexer's rows and a snapshot serve the first 256 tokens. Every
    served log-probability against the reference's full forward."""
    c = tiny_sala_config()
    engine = JaxEngine(JaxEngineArgs(config=c, block_size=BLOCK, num_kv_blocks=128, max_num_seqs=4,
                                     max_model_len=1024, prefill_chunk=CHUNK))
    rng = np.random.default_rng(1)
    ctx, turn = rng.integers(3, 500, 300).tolist(), rng.integers(3, 500, 40).tolist()
    try:
        assert (engine.snapshots.capacity, engine.snapshots.stride_blocks) == (32, 4)
        fresh = _sig(await collect(engine.generate(_req(ctx, "a", 20), Context())))
        assert engine.snapshots.used == 4  # 64, 128, 192, 256: not one a scan block (18)
        before = engine.prefill_tokens
        hit = _sig(await collect(engine.generate(_req(ctx + turn, "b", 20), Context())))
        assert engine.prefill_tokens - before == 340 - 256 and engine.snapshots.hits == 1
        stats = engine.stats()
        assert stats["sparse_attention"]["rows"]["sparse"] > 0
        assert stats["sparse_attention"]["pages_selected"] < stats["sparse_attention"]["pages_live"]
        text = engine.step_metrics.render()
        assert 'dynamo_tpu_engine_sparse_rows_total{path="sparse"}' in text
        assert "dynamo_tpu_engine_ssm_snapshot_hits_total 1" in text
        params = engine.runner.params
    finally:
        await engine.stop()
    layers, model = ref.describe_layers(c), ref.describe_model(c)
    for prompt, (toks, lps) in ((ctx, fresh), (ctx + turn, hit)):
        seq = np.asarray(prompt + toks[:-1], np.int32)
        at = len(prompt) - 1 + np.arange(len(toks))
        want = jax.nn.log_softmax(
            ref.reference_forward(params, layers, seq, model, positions=at)["logits"], -1)
        chosen = np.asarray(jnp.take_along_axis(want, jnp.asarray(toks)[:, None], -1)[:, 0])
        assert len(lps) == 20
        np.testing.assert_allclose(lps, chosen, atol=2e-5, rtol=0)


async def test_the_hybrid_preset_still_snapshots_every_scan_block():
    engine = JaxEngine(JaxEngineArgs(config=tiny_hybrid_config(), block_size=16, num_kv_blocks=64,
                                     max_num_seqs=4, max_model_len=512, prefill_chunk=128))
    try:
        assert (engine.snapshots.capacity, engine.snapshots.stride_blocks) == (256, 1)
        await collect(engine.generate(
            _req(np.random.default_rng(5).integers(3, 500, 100).tolist(), "a", 4), Context()))
        assert engine.snapshots.used == 6
    finally:
        await engine.stop()


def test_a_sparse_model_is_served_at_its_own_block_size():
    with pytest.raises(ValueError, match="--block-size 16"):
        JaxEngine(JaxEngineArgs(config=tiny_sala_config(), block_size=8, num_kv_blocks=64,
                                max_num_seqs=2, max_model_len=256, prefill_chunk=64))


# -- (i) the kernel form ------------------------------------------------------------------------------


@pytest.mark.parametrize("C", [1, 8])
def test_selected_pages_kernel_is_the_masked_form(tiny, C):
    """The live-span decode kernel over each (query, K/V head)'s selected
    pages, under the interpreter, against the XLA form that masks from
    positions and the selected set: decode rows at different lengths (one of
    them an empty slot) and a short chunk."""
    c = tiny["c"]
    spec = c.specs_of("attention")[0]
    k, v, kc = tiny["k"][0], tiny["v"][0], tiny["k"][2]
    B = 3
    tables = jnp.tile(tiny["table"], (B, 1))
    start = jnp.array([T - C, 200, 130], jnp.int32)
    lens = jnp.array([C, C, 0], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(C), (B, C, spec.n_heads, spec.head_dim), jnp.float32)
    scale = spec.head_dim**-0.5
    sel, count, is_sparse = sa.select_blocks(q, kc, tables, start, spec.sparse, sm_scale=scale)
    assert is_sparse.all()
    want = sa._xla_form(q, k, v, tables, start, sel, count, is_sparse, sm_scale=scale)
    got = sa._selected_rows_kernel(q, k, v, tables, start, lens, sel, count, is_sparse,
                                   sm_scale=scale, interpret=True)
    _close(got[:2], want[:2], 1e-5)
    assert not np.asarray(got[2]).any()  # an empty slot is no grid step


# -- (j) the benchmark's copy of the reference --------------------------------------------------------


def _marked(path):
    with open(path) as f:
        text = f.read()
    return text[text.index("# --- reference: begin"): text.index("# --- reference: end")]


def test_benchmark_copy_of_the_reference_agrees(tiny):
    assert _marked(BENCH_REF) == _marked(ref.__file__)
    spec = importlib.util.spec_from_file_location("bench_reference_sala", BENCH_REF)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    got = copy.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][:96], tiny["model"])
    _close(got["logits"], tiny["ref"]["logits"][:96], 1e-5)


def test_reference_continues_a_prefix_it_computed(tiny):
    """The benchmark's child computes a long context once and continues it
    twice: a prefix's float32 keys, values and states, then the suffix, is
    the full forward; and the last sparse sublayer may compute only the
    compared queries, in blocks."""
    head = ref.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][:200], tiny["model"])
    at = np.array([0, 57, 119])
    tail = ref.reference_forward(tiny["p"], tiny["layers"], tiny["toks"][200:], tiny["model"],
                                 carry=head["carry"], positions=at, query_block=2,
                                 token_block=40, last_queries_only=True)
    _close(tail["logits"], tiny["ref"]["logits"][200 + at], 1e-5)
    assert tail["carry"]["length"] == T


def test_reference_child_agrees_in_a_rehearsal(monkeypatch, capsys):
    """The comparison that decides the cell's ``correct``, as the harness's
    CPU rehearsal runs it: the engine's own rows (two contexts built alone,
    two asks at once) against the reference, A to E."""
    import sys

    spec = importlib.util.spec_from_file_location("bench_reference_child_sala", BENCH_REF)
    child = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(child)
    monkeypatch.setattr(sys, "argv", [BENCH_REF, "--config", CONFIG_FILE, "--seed", "4600000011"])
    assert child.main() == 0, capsys.readouterr().out
    out = capsys.readouterr().out
    assert "hits 1" in out and "'hits': 640" in out and "agrees" in out
    assert "forced blocks missing or a wrong count 0" in out


def test_reference_child_compares_nothing_off_its_device():
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run([sys.executable, BENCH_REF, "--config", CONFIG_FILE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 2, done.stdout[-2000:] + done.stderr[-2000:]
    assert "NOTHING COMPARED" in done.stdout


# -- (k) refusals -------------------------------------------------------------------------------------


@pytest.mark.parametrize("mechanism", ["the disaggregation wire", "the KVBM tiers",
                                       "the KV checkpoint", "a device mesh", "int8 KV"])
def test_mechanisms_that_carry_only_kv_refuse_the_configuration(mechanism):
    from dynamo_tpu.models.config import refuse_hybrid

    c = tiny_sala_config()
    with pytest.raises(ValueError, match="compressed keys") as err:
        if mechanism == "int8 KV":  # the runner's own refusal, through the same words
            JaxEngine(JaxEngineArgs(config=c, block_size=16, num_kv_blocks=64, max_num_seqs=2,
                                    max_model_len=256, prefill_chunk=64, kv_cache_dtype="int8"))
        else:
            refuse_hybrid(c, mechanism)
    assert "lightning-attention" in str(err.value)
