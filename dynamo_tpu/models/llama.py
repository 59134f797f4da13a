"""Llama-family decoder: pure-JAX, scan-over-layers, paged KV cache.

TPU-first design notes (vs the reference's torch engines):
  - functional params pytree; layers stacked on a leading axis and consumed
    by `lax.scan` — one traced layer body regardless of depth (fast compile,
    XLA pipelines the per-layer HBM traffic).
  - one `forward_paged` serves prefill, chunked prefill and decode: a chunk
    of C tokens per sequence starting at `start_pos`, K/V written into the
    block pool first, then attention over the pages (ops/attention.py).
  - logical-axis annotations (parallel/sharding.py) drive tp/dp/sp layout;
    XLA inserts the collectives.

Covers Llama-2/3, Qwen2/2.5 (qkv_bias, tied embeddings), Mistral via
ModelConfig knobs.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import (
    dense_chunk_attention,
    paged_attention,
    paged_attention_plan,
    pool_head_dim,
    write_chunk_to_cache,
)
from dynamo_tpu.ops.lora import lora_delta
from dynamo_tpu.ops.moe import moe_ffn
from dynamo_tpu.ops.quant import embed_lookup, lm_head as q_lm_head, qeinsum
from dynamo_tpu.ops.rope import apply_rope, rope_table

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter initialization / logical sharding axes
# ---------------------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random-init params (He-style scaled normal), layers stacked on axis 0."""
    c = config
    if c.is_hybrid:
        from dynamo_tpu.models import hybrid

        return hybrid.init_params(c, key)
    hd = c.head_dim_
    L = c.n_layers
    keys = jax.random.split(key, 12)

    def norm(k, shape, scale):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(c.dtype)

    d, ff, H, KH = c.d_model, c.d_ff, c.n_heads, c.n_kv_heads
    s_d = d**-0.5
    s_ff = ff**-0.5
    # Unit-offset norms (Gemma) store w-1 → effective weight 1+w; ones()
    # here means effective 2.0 for them, fine for random init.
    norm_fill = 0.0 if c.rmsnorm_unit_offset else 1.0
    layers: Params = {
        "attn_norm": jnp.full((L, d), norm_fill, dtype=c.dtype),
        "wq": norm(keys[0], (L, d, H * hd), s_d),
        "wk": norm(keys[1], (L, d, KH * hd), s_d),
        "wv": norm(keys[2], (L, d, KH * hd), s_d),
        "wo": norm(keys[3], (L, H * hd, d), (H * hd) ** -0.5),
        "mlp_norm": jnp.full((L, d), norm_fill, dtype=c.dtype),
    }
    if c.post_norms:
        layers["attn_post_norm"] = jnp.full((L, d), norm_fill, dtype=c.dtype)
        layers["mlp_post_norm"] = jnp.full((L, d), norm_fill, dtype=c.dtype)
    if c.is_moe:
        E, eff = c.n_experts, c.moe_d_ff_
        s_eff = eff**-0.5
        layers["router_w"] = norm(keys[9], (L, d, E), s_d)
        layers["we_gate"] = norm(keys[4], (L, E, d, eff), s_d)
        layers["we_up"] = norm(keys[5], (L, E, d, eff), s_d)
        layers["we_down"] = norm(keys[6], (L, E, eff, d), s_eff)
    else:
        layers["w_gate"] = norm(keys[4], (L, d, ff), s_d)
        layers["w_up"] = norm(keys[5], (L, d, ff), s_d)
        layers["w_down"] = norm(keys[6], (L, ff, d), s_ff)
    if c.qkv_bias:
        layers["bq"] = jnp.zeros((L, H * hd), dtype=c.dtype)
        layers["bk"] = jnp.zeros((L, KH * hd), dtype=c.dtype)
        layers["bv"] = jnp.zeros((L, KH * hd), dtype=c.dtype)
    if c.qk_norm:
        layers["q_norm"] = jnp.ones((L, hd), dtype=c.dtype)
        layers["k_norm"] = jnp.ones((L, hd), dtype=c.dtype)
    params: Params = {
        "embed": norm(keys[7], (c.vocab_size, d), 1.0),
        "layers": layers,
        "final_norm": jnp.full((d,), norm_fill, dtype=c.dtype),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = norm(keys[8], (d, c.vocab_size), s_d)
    return params


def param_logical_axes(config: ModelConfig) -> Params:
    """Logical axis names per param (see parallel/sharding.py rules)."""
    if config.is_hybrid:
        from dynamo_tpu.models import hybrid

        return hybrid.param_logical_axes(config)
    layers = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if config.post_norms:
        layers["attn_post_norm"] = ("layers", "embed")
        layers["mlp_post_norm"] = ("layers", "embed")
    if config.is_moe:
        layers["router_w"] = ("layers", "embed", None)
        layers["we_gate"] = ("layers", "experts", "embed", "ffn")
        layers["we_up"] = ("layers", "experts", "embed", "ffn")
        layers["we_down"] = ("layers", "experts", "ffn", "embed")
    else:
        layers["w_gate"] = ("layers", "embed", "ffn")
        layers["w_up"] = ("layers", "embed", "ffn")
        layers["w_down"] = ("layers", "ffn", "embed")
    if config.qkv_bias:
        layers["bq"] = ("layers", "heads")
        layers["bk"] = ("layers", "kv_heads")
        layers["bv"] = ("layers", "kv_heads")
    if config.qk_norm:
        layers["q_norm"] = ("layers", "head_dim")
        layers["k_norm"] = ("layers", "head_dim")
    axes: Params = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
    }
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def kv_cache_shape(
    config: ModelConfig, num_blocks: int, block_size: int
) -> Tuple[int, ...]:
    return (config.n_layers, num_blocks, block_size, config.n_kv_heads, config.head_dim_)


def init_kv_cache(
    config: ModelConfig, num_blocks: int, block_size: int, *,
    layered: bool = False, kv_dtype: Optional[str] = None,
    window_blocks: int = 0,
):
    """Zeroed K/V pools. ``layered=False``: one stacked [L, NB, BS, KH, D]
    array each (checkpoint/transfer-friendly). ``layered=True``: L-tuples of
    4D arrays [NB, BS, KH, pool_head_dim(D)] — the serving layout, resident
    as the kernels read it. The layered form is what the hot path
    wants: the stacked form forces the layer-scan to rematerialize the FULL
    cache as scan ys every step (~2× cache size of HBM traffic per decode
    step), while per-layer carries update in place.

    ``kv_dtype="int8"`` (layered only): each layer's pool is a quantized
    {"q8", "s"} dict (ops/kv_quant.py) — half the history-read bytes and
    half the decode kernel's page VMEM.

    A hybrid model (``config.layer_specs``) has pools for its attention
    layers only, always layered (models/hybrid.py)."""
    if config.is_hybrid:
        from dynamo_tpu.models import hybrid

        if kv_dtype or not layered:
            raise ValueError(
                f"{config.name}: a hybrid model serves from layered, "
                "unquantized K/V pools only"
            )
        # window_blocks: the blocks of a window page group's pools (hybrid
        # models that have one; 0 = as many as the full group's).
        return hybrid.init_kv_cache(
            config, num_blocks, block_size, window_blocks or num_blocks
        )
    if kv_dtype == "int8":
        if not layered:
            raise ValueError("int8 KV cache requires the layered layout")
        shape = kv_cache_shape(config, num_blocks, block_size)[1:]
        s_shape = (num_blocks, config.n_kv_heads, block_size)

        def one():
            return {
                "q8": jnp.zeros(shape, dtype=jnp.int8),
                # zero scales: zero pages dequantize to exact zeros
                "s": jnp.zeros(s_shape, dtype=jnp.float32),
            }

        k = tuple(one() for _ in range(config.n_layers))
        v = tuple(one() for _ in range(config.n_layers))
        return k, v
    if layered:
        # The serving pools are resident in the layout the kernels and the
        # decode burst's ``while`` carry read: a head narrower than a lane
        # tile is held at the tile's width (ops/attention.pool_head_dim),
        # lanes past the head zero and never read. Blocks leave and enter
        # at the logical head size (runner gather/scatter).
        shape = kv_cache_shape(config, num_blocks, block_size)[1:-1] + (
            pool_head_dim(config.head_dim_),
        )
        k = tuple(jnp.zeros(shape, dtype=config.dtype) for _ in range(config.n_layers))
        v = tuple(jnp.zeros(shape, dtype=config.dtype) for _ in range(config.n_layers))
        return k, v
    shape = kv_cache_shape(config, num_blocks, block_size)
    return jnp.zeros(shape, dtype=config.dtype), jnp.zeros(shape, dtype=config.dtype)


def kv_cache_logical_axes() -> Tuple[str, ...]:
    return ("layers", "kv_blocks", None, "kv_heads", "head_dim")


def kv_cache_layered_axes() -> Tuple[str, ...]:
    """Logical axes of ONE layer's pool in the layered layout."""
    return ("kv_blocks", None, "kv_heads", "head_dim")


def is_layered_cache(cache) -> bool:
    return isinstance(cache, (tuple, list))


def unstack_layer_params(layers, n_layers: int):
    """Stacked [L, ...] per-leaf layer params → list of per-layer trees:
    the serving layout, paired with the layered KV cache. With stacked
    params the per-layer ``a[l]`` slices inside the unrolled decode loop
    force XLA to re-lay-out the kv-projection weights EVERY STEP (the
    stacked array's layout puts the layer dim minor; a device trace at the
    8B shape showed 4 s8-relayout fusions costing ~0.7 ms/step). Separate
    per-layer buffers are born in their matmul-preferred layout, so the
    loop body references them directly. A list (not tuple) so the axes
    tree mirrors it without tripping param_shardings' tuple is_leaf.

    Conversion runs leaf-by-leaf as a jit split: one dispatch per leaf
    rather than n_layers × n_leaves eager slices. The split asks to donate
    its input, but on the chip XLA reports the donation unusable (one
    stacked buffer cannot alias 36 outputs), so the stacked and per-layer
    copies coexist until the caller drops the stacked tree: loading
    Qwen3-8B int8 (8.2 GB of weights) peaks at 15.15 GB in use on a
    16.9 GB v5e (my chip run, PR 21; PERF.md open questions)."""
    splits: Dict[Tuple[Any, ...], Any] = {}

    def split_leaf(a):
        from dynamo_tpu.runtime.device_observe import watched_jit

        a = jnp.asarray(a)
        key = (a.shape, a.dtype)
        if key not in splits:
            # One watch name for every leaf-shaped split program: the
            # signature count legitimately tracks distinct leaf shapes, so
            # the site is unbudgeted (load-time only, never a hot path).
            splits[key] = watched_jit(
                "llama.unstack_layer_split",
                jax.jit(
                    lambda x: tuple(x[l] for l in range(n_layers)),
                    donate_argnums=(0,),
                ),
            )
        return splits[key](a)

    per_leaf = jax.tree.map(split_leaf, layers)
    return [
        jax.tree.map(
            lambda t: t[l], per_leaf,
            is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, dict),
        )
        for l in range(n_layers)
    ]


def unstack_layer_axes(layer_axes, n_layers: int):
    """Logical-axes tree matching unstack_layer_params: the leading
    "layers" axis is stripped from every leaf tuple."""
    one = jax.tree.map(
        lambda t: t[1:], layer_axes, is_leaf=lambda x: isinstance(x, tuple)
    )
    return [one for _ in range(n_layers)]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _rms_norm(
    x: jnp.ndarray, w: jnp.ndarray, eps: float, unit_offset: bool = False
) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    # Gemma stores norm weights as (w - 1); effective scale is 1 + w.
    return normed * (1.0 + w) if unit_offset else normed * w


def _act(x: jnp.ndarray, act_fn: str) -> jnp.ndarray:
    if act_fn == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def decoder_layer(
    c: ModelConfig,
    lp: Params,  # one layer's params (axis 0 stripped)
    ll: Dict[str, Any],  # one layer's stacked LoRA arrays ({} = none)
    win: jnp.ndarray,  # scalar int32 sliding window (0 = full)
    x: jnp.ndarray,  # [B, C, d]
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    k_c: jnp.ndarray,  # [num_blocks, block_size, KH, D] — this layer's pool
    v_c: jnp.ndarray,
    block_tables: jnp.ndarray,
    start_pos: jnp.ndarray,
    chunk_lens: jnp.ndarray,
    *,
    use_kernel: bool,
    adapter_ids: Optional[jnp.ndarray],
    first_chunk: bool = False,
    cos_loc: Optional[jnp.ndarray] = None,  # Gemma-3 local-rope table
    sin_loc: Optional[jnp.ndarray] = None,
    attn_plan=None,  # ops.attention.paged_attention_plan() for this window
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decoder layer (attention + FFN, all family knobs). Shared by the
    scan-over-layers forward and the pipeline-parallel stage executor
    (parallel/pipeline.py), so every architecture behavior lives in exactly
    one place.

    ``first_chunk`` (static): every row's history is the in-flight chunk
    itself (start_pos == 0, fresh prefill) — attend densely over the
    registers (ops/attention.dense_chunk_attention) instead of reading the
    pages just written; the cache is still written for the decode that
    follows. Removes ALL per-layer page DMA from fresh-prefill programs."""
    B, C = x.shape[:2]
    hd = c.head_dim_
    uo = c.rmsnorm_unit_offset
    sm_scale = c.query_scale**-0.5 if c.query_scale is not None else hd**-0.5
    cap = float(c.attn_logit_softcap or 0.0)

    # named_scope: metadata on the operations, so a device trace says which
    # block an operation belongs to; the computation is unchanged.
    with jax.named_scope("qkv"):
        h = _rms_norm(x, lp["attn_norm"], c.rms_norm_eps, uo)
        q = qeinsum("bcd,dh->bch", h, lp["wq"]) + lora_delta(ll, "wq", h, adapter_ids)
        k = qeinsum("bcd,dh->bch", h, lp["wk"]) + lora_delta(ll, "wk", h, adapter_ids)
        v = qeinsum("bcd,dh->bch", h, lp["wv"]) + lora_delta(ll, "wv", h, adapter_ids)
        if c.qkv_bias:
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        q = q.reshape(B, C, c.n_heads, hd)
        k = k.reshape(B, C, c.n_kv_heads, hd)
        v = v.reshape(B, C, c.n_kv_heads, hd)
        if c.qk_norm:
            # Qwen3/Gemma-3: per-head RMSNorm over head_dim on q and k, BEFORE
            # RoPE (HF attention order: norm → rope). Gemma-family norms store
            # (w - 1), hence the unit offset.
            q = _rms_norm(q, lp["q_norm"], c.rms_norm_eps, uo)
            k = _rms_norm(k, lp["k_norm"], c.rms_norm_eps, uo)
        if cos_loc is not None:
            # Gemma-3 dual-frequency RoPE: windowed (local) layers rotate with
            # the local-base table; global layers with the (possibly
            # position-scaled) global table. ``win`` is a traced scalar.
            sel = (win > 0)
            cos = jnp.where(sel, cos_loc, cos)
            sin = jnp.where(sel, sin_loc, sin)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    with jax.named_scope("kv_write"):
        k_c = write_chunk_to_cache(k_c, k, block_tables, start_pos, chunk_lens)
        v_c = write_chunk_to_cache(v_c, v, block_tables, start_pos, chunk_lens)

    with jax.named_scope("attn"):
        if first_chunk:
            attn = dense_chunk_attention(
                q, k, v, chunk_lens, sm_scale=sm_scale, window=win,
                logit_cap=cap,
            ).reshape(B, C, -1)
        else:
            attn = paged_attention(
                q, k_c, v_c, block_tables, start_pos, chunk_lens,
                use_kernel=use_kernel, sm_scale=sm_scale, window=win,
                logit_cap=cap, plan=attn_plan,
            ).reshape(B, C, -1)
        attn_out = qeinsum("bch,hd->bcd", attn, lp["wo"]) + lora_delta(
            ll, "wo", attn, adapter_ids
        )
        if c.post_norms:
            attn_out = _rms_norm(attn_out, lp["attn_post_norm"], c.rms_norm_eps, uo)
        x = x + attn_out

    with jax.named_scope("mlp"):
        h = _rms_norm(x, lp["mlp_norm"], c.rms_norm_eps, uo)
        if c.is_moe:
            mlp_out = moe_ffn(h, lp, c.experts_spec())
        else:
            gate = _act(
                qeinsum("bcd,df->bcf", h, lp["w_gate"])
                + lora_delta(ll, "w_gate", h, adapter_ids),
                c.act_fn,
            )
            up = qeinsum("bcd,df->bcf", h, lp["w_up"]) + lora_delta(
                ll, "w_up", h, adapter_ids
            )
            gu = gate * up
            mlp_out = qeinsum("bcf,fd->bcd", gu, lp["w_down"]) + lora_delta(
                ll, "w_down", gu, adapter_ids
            )
        if c.post_norms:
            mlp_out = _rms_norm(mlp_out, lp["mlp_post_norm"], c.rms_norm_eps, uo)
        x = x + mlp_out
    return x, k_c, v_c


def embed_tokens(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,
    mm_embeds: Optional[jnp.ndarray] = None,
    mm_slot: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Token (+ multimodal splice) embeddings with family scaling."""
    c = config
    x = embed_lookup(params["embed"], tokens, c.dtype)
    if c.embed_scale:  # Gemma: embeddings scaled by sqrt(d_model)
        x = x * jnp.asarray(c.d_model**0.5, dtype=c.dtype)
    if mm_embeds is not None and mm_slot is not None:
        rows = mm_embeds[jnp.clip(mm_slot, 0, mm_embeds.shape[0] - 1)]
        x = jnp.where((mm_slot >= 0)[..., None], rows.astype(x.dtype), x)
    return x


def lm_head_logits(
    params: Params, config: ModelConfig, x: jnp.ndarray
) -> jnp.ndarray:
    """Final norm → vocab projection → final softcap. x: [..., d]."""
    c = config
    with jax.named_scope("lm_head"):
        x = _rms_norm(
            x, params["final_norm"], c.rms_norm_eps, c.rmsnorm_unit_offset
        )
        head = params["embed"] if c.tie_word_embeddings else params["lm_head"]
        logits = q_lm_head(x, head, tied=c.tie_word_embeddings)
        if c.final_logit_softcap:
            fcap = float(c.final_logit_softcap)
            logits = fcap * jnp.tanh(logits / fcap)
    return logits


def forward_paged(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, C] int32
    start_pos: jnp.ndarray,  # [B] int32
    chunk_lens: jnp.ndarray,  # [B] int32
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    k_cache: jnp.ndarray,  # [L, num_blocks, block_size, KH, D]
    v_cache: jnp.ndarray,
    *,
    use_kernel: bool = False,
    lora: Optional[Dict[str, Any]] = None,  # target → (A [L,N,d,r], B [L,N,r,h])
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] int32, 0 = no adapter
    mm_embeds: Optional[jnp.ndarray] = None,  # [M, d] image patch embeddings
    mm_slot: Optional[jnp.ndarray] = None,  # [B, C] int32 row into mm_embeds, -1=text
    all_logits: bool = False,  # True → logits for EVERY position [B, C, V]
    first_chunk: bool = False,  # static: fresh prefill, dense in-chunk attention
    ssm: Optional[Dict[str, Any]] = None,  # hybrid: recurrent state (models/hybrid.py)
    snap: Optional[Dict[str, Any]] = None,  # hybrid: snapshot store + destinations
    want_moe_stats: bool = False,
    live_rows: Optional[Any] = None,  # hybrid decode burst: ssd_step.live_row_list
) -> Tuple[jnp.ndarray, ...]:
    """One forward step over a chunk. Returns (last_logits [B, V], k_cache,
    v_cache). K/V for the chunk are scattered into the pools before attending,
    so the same function implements prefill (large C), chunked prefill
    (start_pos > 0), and decode (C = 1).

    Multi-LoRA: ``lora`` carries layer-major stacked adapters (ops/lora.py);
    each sequence's ``adapter_ids`` entry selects its adapter per einsum —
    one compiled program for any adapter mix (punica-role, TPU-style)."""
    c = config
    if c.is_hybrid:
        # One mixer per layer and two kinds of state: returns (logits, k, v,
        # ssm', snapshot store' | None, expert-load stats | None).
        from dynamo_tpu.models import hybrid

        if lora or mm_embeds is not None:
            raise ValueError(
                f"{c.name}: LoRA and multimodal splices are not implemented "
                "for hybrid models"
            )
        return hybrid.forward(
            params, c, tokens, start_pos, chunk_lens, block_tables, k_cache,
            v_cache, ssm, use_kernel=use_kernel, first_chunk=first_chunk,
            all_logits=all_logits, snap=snap, want_moe_stats=want_moe_stats,
            live_rows=live_rows,
        )
    B, C = tokens.shape
    hd = c.head_dim_

    x = embed_tokens(params, c, tokens, mm_embeds, mm_slot)  # [B, C, d]

    pos = start_pos[:, None] + jax.lax.broadcasted_iota(jnp.int32, (B, C), 1)
    cos, sin = rope_table(
        pos, hd, c.rope_theta, scale=c.rope_scaling_factor or 1.0
    )  # [B, C, hd]
    cos_loc = sin_loc = None
    if c.rope_local_theta is not None:
        # Gemma-3: local (windowed) layers rotate at the local base freq,
        # UNscaled (HF applies rope_scaling only to the global rope).
        cos_loc, sin_loc = rope_table(pos, hd, c.rope_local_theta)

    if is_layered_cache(k_cache):
        # Serving layout: Python-unrolled layers over per-layer 4D pools.
        # Static layer indices let XLA update every pool in place (step-scan
        # carry / donated buffer). The stacked form below rematerializes the
        # FULL cache as scan ys every call (~2× cache size of HBM traffic).
        # HLO grows ~L× but is traced once; compile stays cached.
        win_list = c.layer_windows()
        layered_params = isinstance(params["layers"], (tuple, list))

        # The paged-attention kernel's grid follows from positions, table
        # and window alone: one derivation per STEP and distinct window,
        # shared by the layers.
        attn_plans = {} if first_chunk else {
            w: paged_attention_plan(
                C, c.n_heads, k_cache[0], block_tables, start_pos,
                chunk_lens, use_kernel=use_kernel, window=w,
            )
            for w in sorted({int(w) for w in win_list})
        }
        k_out, v_out = [], []
        for l in range(c.n_layers):
            if layered_params:
                lp_l = params["layers"][l]
            else:
                lp_l = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            ll_l = jax.tree.map(lambda a, _l=l: a[_l], lora) if lora else {}
            x, k_l, v_l = decoder_layer(
                c, lp_l, ll_l, jnp.asarray(win_list[l], jnp.int32), x, cos, sin,
                k_cache[l], v_cache[l], block_tables, start_pos, chunk_lens,
                use_kernel=use_kernel, adapter_ids=adapter_ids,
                first_chunk=first_chunk, cos_loc=cos_loc, sin_loc=sin_loc,
                attn_plan=attn_plans.get(int(win_list[l])),
            )
            k_out.append(k_l)
            v_out.append(v_l)
        k_cache, v_cache = tuple(k_out), tuple(v_out)
    else:
        # Per-layer sliding windows (0 = full) ride the scan xs so one traced
        # body serves Gemma-2's alternating local/global layers.
        windows = jnp.asarray(c.layer_windows(), dtype=jnp.int32)

        def layer_fn(carry, xs):
            x = carry
            lp, k_c, v_c, ll, win = xs
            x, k_c, v_c = decoder_layer(
                c, lp, ll, win, x, cos, sin, k_c, v_c,
                block_tables, start_pos, chunk_lens,
                use_kernel=use_kernel, adapter_ids=adapter_ids,
                first_chunk=first_chunk, cos_loc=cos_loc, sin_loc=sin_loc,
            )
            return x, (k_c, v_c)

        x, (k_cache, v_cache) = jax.lax.scan(
            layer_fn, x, (params["layers"], k_cache, v_cache, lora or {}, windows)
        )

    if all_logits:
        # Every position's logits (speculative verify reads them all).
        return lm_head_logits(params, c, x), k_cache, v_cache
    # Only the last valid position's logits are needed (sampling).
    last_idx = jnp.clip(chunk_lens - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]  # [B, d]
    return lm_head_logits(params, c, x_last), k_cache, v_cache


def encode(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32 (right-padded)
    lengths: jnp.ndarray,  # [B] int32 valid lengths
) -> jnp.ndarray:
    """Mean-pooled final hidden states [B, d] — the embedding-model forward
    (bidirectional is unnecessary for decoder-embedding models; pooling over
    the causal states matches the common last/mean-pool recipes)."""
    c = config
    B, T = tokens.shape
    hd = c.head_dim_
    uo = c.rmsnorm_unit_offset
    sm_scale = c.query_scale**-0.5 if c.query_scale is not None else hd**-0.5
    cap = float(c.attn_logit_softcap or 0.0)
    windows = jnp.asarray(c.layer_windows(), dtype=jnp.int32)
    x = embed_lookup(params["embed"], tokens, c.dtype)
    if c.embed_scale:
        x = x * jnp.asarray(c.d_model**0.5, dtype=c.dtype)
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, T), 1)
    cos, sin = rope_table(
        pos, hd, c.rope_theta, scale=c.rope_scaling_factor or 1.0
    )
    cos_loc = sin_loc = None
    if c.rope_local_theta is not None:
        cos_loc, sin_loc = rope_table(pos, hd, c.rope_local_theta)

    def layer_fn(carry, xs):
        x = carry
        lp, win = xs
        h = _rms_norm(x, lp["attn_norm"], c.rms_norm_eps, uo)
        q = qeinsum("btd,dh->bth", h, lp["wq"])
        k = qeinsum("btd,dh->bth", h, lp["wk"])
        v = qeinsum("btd,dh->bth", h, lp["wv"])
        if c.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(B, T, c.n_heads, hd)
        k = k.reshape(B, T, c.n_kv_heads, hd)
        if c.qk_norm:  # Qwen3/Gemma-3: per-head RMSNorm before RoPE
            q = _rms_norm(q, lp["q_norm"], c.rms_norm_eps, uo)
            k = _rms_norm(k, lp["k_norm"], c.rms_norm_eps, uo)
        lcos, lsin = cos, sin
        if cos_loc is not None:  # Gemma-3 dual-frequency rope
            sel = (win > 0)
            lcos = jnp.where(sel, cos_loc, cos)
            lsin = jnp.where(sel, sin_loc, sin)
        q = apply_rope(q, lcos, lsin)
        k = apply_rope(k, lcos, lsin)
        v = v.reshape(B, T, c.n_kv_heads, hd)
        G = c.q_per_kv
        qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)
        kf = jnp.repeat(k.astype(jnp.float32).transpose(0, 2, 1, 3), G, axis=1)
        vf = jnp.repeat(v.astype(jnp.float32).transpose(0, 2, 1, 3), G, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * sm_scale
        if cap > 0.0:
            s = cap * jnp.tanh(s / cap)
        t_q = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        t_k = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        causal = (t_q >= t_k) & ((win <= 0) | (t_k > t_q - win))
        valid = t_k[None] < lengths[:, None, None]  # padded keys masked
        s = jnp.where(causal[None, None] & valid[:, None], s, -1e30)
        attn = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vf)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, T, -1).astype(x.dtype)
        attn_out = qeinsum("bth,hd->btd", attn, lp["wo"])
        if c.post_norms:
            attn_out = _rms_norm(attn_out, lp["attn_post_norm"], c.rms_norm_eps, uo)
        x = x + attn_out
        h = _rms_norm(x, lp["mlp_norm"], c.rms_norm_eps, uo)
        if c.is_moe:
            mlp_out = moe_ffn(h, lp, c.experts_spec())
        else:
            gate = _act(qeinsum("btd,df->btf", h, lp["w_gate"]), c.act_fn)
            up = qeinsum("btd,df->btf", h, lp["w_up"])
            mlp_out = qeinsum("btf,fd->btd", gate * up, lp["w_down"])
        if c.post_norms:
            mlp_out = _rms_norm(mlp_out, lp["mlp_post_norm"], c.rms_norm_eps, uo)
        x = x + mlp_out
        return x, None

    x, _ = jax.lax.scan(layer_fn, x, (params["layers"], windows))
    x = _rms_norm(x, params["final_norm"], c.rms_norm_eps, uo).astype(jnp.float32)
    mask = (jax.lax.broadcasted_iota(jnp.int32, (B, T), 1) < lengths[:, None])
    pooled = (x * mask[..., None]).sum(1) / jnp.maximum(
        lengths[:, None].astype(jnp.float32), 1.0
    )
    return pooled


def decode_multi(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B] int32 — current input token per slot
    start_pos: jnp.ndarray,  # [B] int32
    active: jnp.ndarray,  # [B] int32 0/1
    block_tables: jnp.ndarray,  # [B, max_blocks]
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    *,
    num_steps: int,
    use_kernel: bool = False,
    lora: Optional[Dict[str, Any]] = None,
    adapter_ids: Optional[jnp.ndarray] = None,
    want_logprobs: bool = True,
    min_p: Optional[jnp.ndarray] = None,  # [B]
    proc_params: Optional[Any] = None,  # logits_process.ProcParams
    proc_state: Optional[Any] = None,  # logits_process.ProcState
    num_top_logprobs: int = 0,  # >0 → also return top-N alternatives/step
    salts: Optional[jnp.ndarray] = None,  # [B] per-sequence sampling salt
    want_carry: bool = False,  # also return the device-resident carry
    ssm: Optional[Dict[str, Any]] = None,  # hybrid: per-slot recurrent state
) -> Tuple[jnp.ndarray, ...]:
    """``num_steps`` fused decode iterations in ONE dispatch (lax.scan over
    single-token forward+sample steps). Minimizes host↔device round trips —
    the decisive factor on TPU where dispatch latency dwarfs a small model's
    step compute. Host-side stop conditions are applied afterwards at
    num_steps granularity (overshoot tokens are discarded; their KV writes
    beyond the table capacity are dropped by write_chunk_to_cache).

    When ``proc_params``/``proc_state`` are given (ops/logits_process.py),
    penalties/bias are applied before sampling and generated-token counts
    are carried through the scan.

    RNG: with ``salts`` the per-step sampling key for row b is derived from
    (rng, salts[b], position-of-sampled-token) — see
    ops/sampling.fold_row_keys. Noise then depends only on (seed, sequence,
    token index), never on dispatch order, which is the determinism
    contract the pipelined decode scheduler relies on. Without salts the
    legacy per-dispatch split keys are used (profiling scripts).

    Returns (tokens [B, num_steps], logprobs [B, num_steps], k_cache,
    v_cache[, proc_state][, carry_tokens [B], carry_pos [B]]). With
    ``num_top_logprobs`` = N > 0 the tuple gains (top_vals
    [B, num_steps, N], top_ids [B, num_steps, N]) right after the logprobs
    entry — the per-step top-N alternatives that back the OpenAI
    ``top_logprobs`` surface. With ``want_carry`` the final carry (last
    sampled token and advanced position per row) comes last — device
    arrays the runner feeds straight into the next burst without a host
    round trip.

    A hybrid model (``ssm`` given) carries its recurrent state through the
    steps beside the pools, and the tuple ENDS with (ssm', expert-load
    stats float32 [3] summed over the burst's steps and expert layers).
    """
    from dynamo_tpu.ops import logits_process as lp
    from dynamo_tpu.ops.sampling import (
        any_row_samples,
        compute_logprobs,
        sample_tokens,
        top_logprobs as top_logprobs_op,
    )

    hybrid_state = ssm is not None
    # ``active`` and the temperatures are constant over the burst: whether a
    # live row samples (a dead slot keeps a stale temperature) is decided
    # once, outside the scan; the steps' sampler takes its arg-max branch
    # when none does.
    any_sampled = any_row_samples(temperature, active > 0)
    # ``active`` is constant over the burst: the list of rows whose recurrent
    # state the steps update is derived once, outside the scan.
    live_rows = None
    if hybrid_state and use_kernel and config.has_recurrent_state:
        from dynamo_tpu.ops.pallas.ssd_step import live_row_list

        live_rows = live_row_list(active)

    def one(carry, step_rng):
        if hybrid_state:
            carry, (ssm_c, moe_acc) = carry[:-1], carry[-1]
        if proc_state is not None:
            toks, pos, k_c, v_c, st = carry
        else:
            toks, pos, k_c, v_c = carry
            st = None
        if hybrid_state:
            logits, k_c, v_c, ssm_c, _, moe_st = forward_paged(
                params, config, toks[:, None], pos, active, block_tables,
                k_c, v_c, use_kernel=use_kernel, ssm=ssm_c, want_moe_stats=True,
                live_rows=live_rows,
            )
            moe_acc = moe_acc + moe_st
        else:
            logits, k_c, v_c = forward_paged(
                params, config, toks[:, None], pos, active, block_tables, k_c, v_c,
                use_kernel=use_kernel, lora=lora, adapter_ids=adapter_ids,
            )
        with jax.named_scope("sample"):
            if proc_params is not None:
                logits = lp.apply(logits, proc_params, st)
            if salts is not None:
                # The sampled token's index is pos + 1 (pos counts the tokens
                # before the current input token; the input occupies index
                # pos) — the same index the prefill program folds for the
                # first generated token, so preemption-by-recompute redraws
                # identical noise.
                nxt = sample_tokens(
                    logits, rng, temperature, top_k, top_p, min_p,
                    salts=salts, positions=pos + 1, any_sampled=any_sampled,
                )
            else:
                nxt = sample_tokens(
                    logits, step_rng, temperature, top_k, top_p, min_p,
                    any_sampled=any_sampled,
                )
            nxt = jnp.where(active > 0, nxt, toks)
        if want_logprobs:
            logp = compute_logprobs(logits, nxt)
        else:
            # Full-vocab log-softmax each step is pure waste when no active
            # request asked for logprobs (the common case).
            logp = jnp.zeros_like(nxt, dtype=jnp.float32)
        ys = (nxt, logp)
        if num_top_logprobs > 0:
            tv, ti = top_logprobs_op(logits, num_top_logprobs)
            ys = ys + (tv, ti)
        if st is not None:
            st = lp.record_tokens(st, nxt, active)
        pos = pos + active
        out = (nxt, pos, k_c, v_c) + ((st,) if st is not None else ())
        if hybrid_state:
            out = out + ((ssm_c, moe_acc),)
        return out, ys

    xs = None if salts is not None else jax.random.split(rng, num_steps)
    init = (tokens, start_pos, k_cache, v_cache)
    if proc_state is not None:
        init = init + (proc_state,)
    if hybrid_state:
        init = init + ((ssm, jnp.zeros((3,), jnp.float32)),)
    fin, ys = jax.lax.scan(one, init, xs, length=num_steps)
    if hybrid_state:
        fin, (ssm, moe_acc) = fin[:-1], fin[-1]
    if proc_state is not None:
        fin_toks, fin_pos, k_cache, v_cache, proc_state = fin
    else:
        fin_toks, fin_pos, k_cache, v_cache = fin
    toks, logps = ys[0], ys[1]
    out: Tuple[jnp.ndarray, ...] = (toks.T, logps.T)
    if num_top_logprobs > 0:
        # scan stacks on axis 0 (steps) → [B, S, N]
        out = out + (ys[2].swapaxes(0, 1), ys[3].swapaxes(0, 1))
    out = out + (k_cache, v_cache)
    if proc_state is not None:
        out = out + (proc_state,)
    if want_carry:
        out = out + (fin_toks, fin_pos)
    if hybrid_state:
        out = out + (ssm, moe_acc)
    return out


_EXPERT_MATRICES = ("we_up", "we_gate", "we_down")


def step_weights(
    params: Params, config: ModelConfig
) -> Tuple[float, float, Tuple[Tuple[float, float], ...]]:
    """What a step over this tree reads and multiplies by, from its leaves'
    shapes alone (arrays or ``jax.eval_shape`` structs; ``layers`` stacked
    or one tree a layer; a quantized leaf by its own item size):

    - the bytes every step streams whatever its tokens: every matrix outside
      the expert layers, the head among them, the embedding table not (it is
      looked up) unless it is the head too;
    - the weights one position multiplies by: the same matrices without the
      head (it runs over a step's rows, not its positions) plus the experts
      one token's choices hit;
    - an expert layer: (the bytes of its held experts, the share of them one
      token's choices are expected to hit).

    What ``engines/tpu/admission.PrefillPrice`` prices a prefill program by."""
    always = active = 0.0
    held: Dict[int, list] = {}  # expert layer (0: stacked layers) -> [bytes, weights]
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        nbytes = float(leaf.size) * leaf.dtype.itemsize
        if any(k in _EXPERT_MATRICES for k in keys):
            of = held.setdefault(keys[1] if isinstance(keys[1], int) else 0, [0.0, 0.0])
            of[0] += nbytes
            of[1] += leaf.size
        elif keys[0] == "embed":
            always += nbytes if config.tie_word_embeddings else 0.0
        else:
            always += nbytes
            active += 0.0 if keys[0] == "lm_head" else leaf.size
    experts = []
    for layer, (nbytes, weights) in sorted(held.items()):
        spec = config.layer_specs[layer] if config.is_hybrid else config.experts_spec()
        hit = spec.top_k / spec.n_experts
        experts.append((nbytes, hit))
        active += weights * hit
    return always, active, tuple(experts)
