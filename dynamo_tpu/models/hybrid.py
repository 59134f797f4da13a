"""Hybrid decoder: one mixer per layer, of the kind its spec names.

``h <- h + mixer(rmsnorm(h))`` for every layer, a final RMSNorm and an untied
head (the ``nemotron_h`` family). The mixer is attention over the paged K/V
pool (the pools, tables and kernels of the llama-family path), a Mamba-2
state-space mixer over per-sequence recurrent state, or a dropless
mixture of experts (``ops/moe.py``). ``ModelConfig.layer_specs`` says which;
``llama.forward_paged`` / ``llama.decode_multi`` hand over to this module
when it is set, so the dense decoder layer stays as it was.

A family whose published layer is two sublayers (``pangu_ultra_moe``: latent
attention, then a dense FFN or the experts) is two entries a layer; a spec
with ``post_norm`` norms the sublayer's output too, before the residual is
added (``h <- h + rmsnorm(mixer(rmsnorm(h)))``). Latent attention (MLA)
caches ONE row a token a layer, ``c_kv`` beside the rotary key all heads
share: ``k_cache[i]`` is then the i-th latent layer's pool
``[blocks, block, width]`` and ``v_cache`` is empty.

A sparse-attention layer (``AttentionSpec.sparse``: ``minicpm_sala``) caches
a third array, its indexer's compressed keys, under the K/V pool's own block
ids: those pools follow the attention layers' in ``k_cache``
(ops/sparse_attention.py). MiniCPM's scalings (``embed_multiplier``,
``residual_multiplier``, ``logit_divisor``) apply where the config sets them.

Two kinds of state travel with a sequence. K/V pages exist only for the
attention layers: ``k_cache[i]`` belongs to the i-th ATTENTION layer. The
recurrent state of the Mamba-2, lightning-attention and Gated DeltaNet layers
is ``{"conv": (...), "S": (...)}``, batch-major: ``S`` one entry per recurrent
layer, the SSM state ``[B, H, P, N]`` (lightning: ``[B, H, D value, D key]``;
Gated DeltaNet: ``[B, H value, D key, D value]``) in the spec's
``state_dtype`` (float32); ``conv`` one entry per Mamba-2 or Gated DeltaNet
layer, the conv tail ``[B, K-1, channels]`` in the model's dtype. A prefill chunk starts from the state it is
given and returns the state after each row's last real token; positions
past ``chunk_lens`` leave it untouched. It can also write the state at the
end of every ``scan_block`` tokens into a snapshot store
(``snap={"store": ..., "dst": [B, C // scan_block]}``, an out-of-range
destination writes nothing), which is what prefix reuse resumes from.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import _rms_norm as _rms
from dynamo_tpu.ops import gated_delta as gd
from dynamo_tpu.ops import mamba2 as m2
from dynamo_tpu.ops.attention import (
    dense_chunk_attention,
    latent_pool_width,
    pad_head,
    mla_attention_plan,
    mla_chunk_attention,
    mla_paged_attention,
    paged_attention,
    paged_attention_plan,
    pool_head_dim,
    write_chunk_to_cache,
)
from dynamo_tpu.ops.moe import moe_ffn
from dynamo_tpu.ops.pallas.gdn_step import gdn_step_live, gdn_step_reason
from dynamo_tpu.ops.pallas.ssd_step import ssd_step_live, ssd_step_reason
from dynamo_tpu.ops.rope import apply_rope, rope_table, rope_table_for
from dynamo_tpu.ops.sparse_attention import (
    compressed_pool,
    sparse_paged_attention,
    write_compressed_keys,
)

Params = Dict[str, Any]
_F32 = jnp.float32


# -- parameters ----------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters, one dict per layer (born per layer: the
    kinds differ, so nothing stacks). Scaled-normal matrices; the Mamba-2
    scalars as the family initialises them (dt in [1e-3, 1e-1] log-uniform
    through the inverse softplus, A in [1, 16], D = 1); a correction bias
    wide enough that leaving it out changes which experts are chosen."""
    c = config
    d = c.d_model

    def norm(k, shape, scale, dtype=None):
        # One matrix at a time: dispatched ahead, the float32 draws of a whole
        # layer's expert stacks are alive at once, beside the weights already
        # held (15.70 of 15.75 GB at 4.9 B parameters: my chip run, PR 39).
        return jax.block_until_ready(
            (jax.random.normal(k, shape, dtype=_F32) * scale).astype(dtype or c.dtype)
        )

    def unit(shape):  # a norm weight of effective scale 1
        return (jnp.zeros if c.rmsnorm_unit_offset else jnp.ones)(shape, c.dtype)

    layers = []
    for i, spec in enumerate(c.layer_specs):
        k = jax.random.split(jax.random.fold_in(key, i), 8)
        lp: Params = {"norm": unit((d,))}
        if getattr(spec, "post_norm", False):
            lp["post_norm"] = unit((d,))
        if spec.kind == "mla":
            H, qr, kr = spec.n_heads, spec.q_rank, spec.kv_rank
            # The published ``kv_b_proj`` is held as its key half and its
            # value half: the absorbed form uses them apart.
            lp.update(
                w_qa=norm(k[0], (d, qr), d**-0.5),
                q_norm=jnp.ones((qr,), c.dtype),
                w_qb=norm(k[1], (qr, H, spec.qk_dim), qr**-0.5),
                w_kva=norm(k[2], (d, spec.cache_width), d**-0.5),
                kv_norm=jnp.ones((kr,), c.dtype),
                w_kb=norm(k[3], (kr, H, spec.nope_dim), kr**-0.5),
                w_vb=norm(k[4], (kr, H, spec.v_dim), kr**-0.5),
                wo=norm(k[5], (H * spec.v_dim, d), (H * spec.v_dim) ** -0.5),
            )
        elif spec.kind == "dense_ffn":
            f = spec.d_ff
            lp.update(
                w_gate=norm(k[0], (d, f), d**-0.5), w_up=norm(k[1], (d, f), d**-0.5),
                w_down=norm(k[2], (f, d), f**-0.5),
            )
        elif spec.kind == "attention":
            hq, hk = spec.n_heads * spec.head_dim, spec.n_kv_heads * spec.head_dim
            lp.update(
                wq=norm(k[0], (d, hq), d**-0.5), wk=norm(k[1], (d, hk), d**-0.5),
                wv=norm(k[2], (d, hk), d**-0.5), wo=norm(k[3], (hq, d), hq**-0.5),
            )
            if spec.gate:
                lp["w_gate_attn"] = norm(
                    k[4], (d, hq if spec.gate_lanes else spec.n_heads), d**-0.5)
            if spec.qk_norm:
                lp.update(q_norm=unit((spec.head_dim,)), k_norm=unit((spec.head_dim,)))
        elif spec.kind == "gated_delta":
            # The decay's scalars: exp(A_log) in [1, 4] and a softplus bias
            # whose rate is log-uniform in [1e-3, 2.5e-2], so that exp(g)
            # spans roughly 0.9 to 0.999 a token over the heads (the
            # projection's own part moves it a token): a state that forgets
            # in ten tokens would hide its own errors.
            H = spec.n_heads
            dt = jnp.exp(
                jax.random.uniform(k[2], (H,), _F32) * (jnp.log(2.5e-2) - jnp.log(1e-3))
                + jnp.log(1e-3)
            )
            lp.update(
                w_qkvz=norm(k[0], (d, spec.conv_channels + spec.v_width), d**-0.5),
                w_ba=norm(k[4], (d, 2 * H), d**-0.5),
                conv_w=norm(k[1], (spec.conv_kernel, spec.conv_channels),
                            spec.conv_kernel**-0.5),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # inverse softplus
                A_log=jnp.log(jax.random.uniform(k[3], (H,), _F32, 1.0, 4.0)),
                o_norm=jnp.ones((spec.head_dim,), c.dtype),  # plain, not zero-centred
                w_out=norm(k[5], (spec.v_width, d), spec.v_width**-0.5),
            )
        elif spec.kind == "lightning":
            hq = spec.n_heads * spec.head_dim
            lp.update(
                wq=norm(k[0], (d, hq), d**-0.5), wk=norm(k[1], (d, hq), d**-0.5),
                wv=norm(k[2], (d, hq), d**-0.5), wo=norm(k[3], (hq, d), hq**-0.5),
                w_gate_attn=norm(k[4], (d, hq), d**-0.5),
                q_norm=jnp.ones((spec.head_dim,), c.dtype),
                k_norm=jnp.ones((spec.head_dim,), c.dtype),
                o_norm=jnp.ones((spec.head_dim,), c.dtype),
            )
        elif spec.kind == "mamba2":
            H, di = spec.n_heads, spec.d_inner
            dt = jnp.exp(
                jax.random.uniform(k[2], (H,), _F32) * (jnp.log(0.1) - jnp.log(1e-3))
                + jnp.log(1e-3)
            )
            lp.update(
                w_in=norm(k[0], (d, spec.in_width), d**-0.5),
                conv_w=norm(k[1], (spec.conv_kernel, spec.conv_channels),
                            spec.conv_kernel**-0.5),
                conv_b=jnp.zeros((spec.conv_channels,), c.dtype),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # inverse softplus
                A_log=jnp.log(jax.random.uniform(k[3], (H,), _F32, 1.0, 16.0)),
                D=jnp.ones((H,), _F32),
                gnorm=jnp.ones((di,), c.dtype),
                w_out=norm(k[4], (di, d), di**-0.5),
            )
        elif spec.kind == "experts":
            E, Eh, f, fs = spec.n_experts, spec.n_held, spec.d_ff, spec.shared_d_ff
            lp.update(
                router_w=norm(k[0], (d, E), d**-0.5, _F32),
                we_up=norm(k[1], (Eh, d, f), d**-0.5),
                we_down=norm(k[2], (Eh, f, d), f**-0.5),
            )
            if spec.routing == "sigmoid_bias":
                lp["router_bias"] = norm(k[3], (E,), 0.1, _F32)
            if spec.activation == "silu_gated":
                lp["we_gate"] = norm(k[4], (Eh, d, f), d**-0.5)
            if fs:
                lp.update(ws_up=norm(k[5], (d, fs), d**-0.5),
                          ws_down=norm(k[6], (fs, d), fs**-0.5))
                if spec.activation == "silu_gated":
                    lp["ws_gate"] = norm(k[7], (d, fs), d**-0.5)
                if spec.shared_gate:
                    lp["ws_gate_scalar"] = norm(
                        jax.random.fold_in(k[7], 1), (d, 1), d**-0.5)
        else:
            raise ValueError(f"unknown layer kind {spec.kind!r}")
        layers.append(lp)
    ke, kh = jax.random.split(jax.random.fold_in(key, 10_000))
    return {
        "embed": norm(ke, (c.vocab_size, d), 1.0),
        "layers": layers,
        "final_norm": unit((d,)),
        "lm_head": norm(kh, (d, c.vocab_size), d**-0.5),
    }


def param_logical_axes(config: ModelConfig) -> Params:
    """Nothing of a hybrid model is sharded yet (the runner refuses a mesh):
    every axis of every leaf is replicated."""
    shapes = jax.eval_shape(lambda: init_params(config, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a: (None,) * a.ndim, shapes)


def init_kv_cache(config: ModelConfig, num_blocks: int, block_size: int,
                  window_blocks: int = 0):
    """One layered pool per ATTENTION layer, in the layout the kernels read
    (llama.init_kv_cache's layered form). A latent-attention model has one
    LATENT pool per such layer and no V pool: logically
    [blocks, block, 1, cache_width], held as [blocks, block, width] with the
    row at whole lane tiles (ops/attention.latent_pool_width). Where the
    model has a window page group (``config.window_group``) its layers'
    pools hold ``window_blocks`` blocks, the other layers' ``num_blocks``:
    two pool shapes, two id spaces. A sparse-attention layer's compressed
    keys (ops/sparse_attention.py: the indexer's cache, under the K/V pool's
    own block ids) follow the attention layers' K pools in the first tuple,
    one a sparse layer in layer order."""
    latent = config.specs_of("mla")
    if latent:
        if config.specs_of("attention"):
            raise ValueError(
                f"{config.name}: latent and K/V attention layers in one model "
                "are not implemented (one block table, two pool shapes)"
            )
        return tuple(
            jnp.zeros(
                (num_blocks, block_size, latent_pool_width(s.cache_width)), config.dtype
            )
            for s in latent
        ), ()
    k, v = [], []
    win = config.window_group
    for i, spec in enumerate(config.specs_of("attention")):
        blocks = window_blocks if win is not None and i in win.layers else num_blocks
        shape = (blocks, block_size, spec.n_kv_heads, pool_head_dim(spec.head_dim))
        k.append(jnp.zeros(shape, config.dtype))
        v.append(jnp.zeros(shape, config.dtype))
    for spec in config.specs_of("attention"):
        if spec.sparse is not None:
            k.append(compressed_pool(
                num_blocks, spec.sparse, spec.n_kv_heads, spec.head_dim, config.dtype))
    return tuple(k), tuple(v)


def init_ssm_state(config: ModelConfig, rows: int) -> Dict[str, Tuple[jnp.ndarray, ...]]:
    """Zeroed recurrent state for ``rows`` sequences (or snapshot entries):
    ``S`` one matrix stack per recurrent layer in layer order (Mamba-2
    [rows, H, P, N]; lightning attention [rows, H, D value, D key]; Gated
    DeltaNet [rows, H value, D key, D value]), ``conv`` one tail per Mamba-2
    or Gated DeltaNet layer (lightning attention has none)."""
    conv, S = [], []
    for spec in config.recurrent_specs:
        if spec.kind != "lightning":
            conv.append(
                jnp.zeros((rows, spec.conv_kernel - 1, spec.conv_channels), config.dtype)
            )
        if spec.kind == "gated_delta":
            matrix = (spec.k_dim, spec.head_dim)
        else:
            matrix = (spec.head_dim,
                      spec.state_size if spec.kind == "mamba2" else spec.head_dim)
        S.append(
            jnp.zeros((rows, spec.n_heads) + matrix, jnp.dtype(spec.state_dtype))
        )
    return {"conv": tuple(conv), "S": tuple(S)}


def ssm_state_bytes(config: ModelConfig) -> int:
    """Bytes of one sequence's recurrent state over all recurrent layers."""
    return sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(jax.eval_shape(lambda: init_ssm_state(config, 1)))
    )


# -- mixers --------------------------------------------------------------------


def _decode_recurrence(x, dt, A, Bm, Cm, S, live_rows):
    """The one-token recurrence of a decode step, (y, S'): given a burst's
    live-row list (``llama.decode_multi`` derives one where ``use_kernel``)
    and a state the kernel takes (``ssd_step_reason``), over the rows that
    decode and no others, in place; otherwise ``m2.ssd_step`` over every
    slot (a dead slot's dt is 0). One recurrence for both callers: they
    differ in A, B, C and the shapes."""
    if live_rows is not None and ssd_step_reason(True, S.shape, S.dtype) is None:
        return ssd_step_live(x, dt, A, Bm, Cm, S, *live_rows)
    return m2.ssd_step(x, dt, A, Bm, Cm, S)


def decode_recurrence_reason(spec, use_kernel: bool, S) -> Optional[str]:
    """None where a decode step of a recurrent layer of ``spec``'s kind, over
    the slots' state ``S``, runs its live-row Pallas kernel; otherwise why it
    keeps the XLA step over every slot. What the mixers below branch on and
    the runner logs."""
    reason = gdn_step_reason if spec.kind == "gated_delta" else ssd_step_reason
    return reason(use_kernel, S.shape, S.dtype)


def _snap_blocks(snap_store, snap_dst, padded, ends, scan_block: int, K: int):
    """The snapshot store with the conv tail and the state at each block end
    of the chunk written to ``snap_dst`` [B, C // scan_block] (out of range:
    nothing is written)."""
    B, nb = ends.shape[:2]
    at = (jnp.arange(nb, dtype=jnp.int32) + 1) * scan_block
    idx = at[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    tails = padded[:, idx]  # [B, nb, K-1, ch]: the tail at each block end
    dst = snap_dst.reshape(B * nb)
    return {
        "conv": snap_store["conv"].at[dst].set(
            tails.reshape((B * nb,) + tails.shape[2:]), mode="drop"),
        "S": snap_store["S"].at[dst].set(
            ends.reshape((B * nb,) + ends.shape[2:]), mode="drop"),
    }


def _gated_delta_mixer(c, spec, lp, h, chunk_lens, conv, S, snap_dst, snap_store,
                       live_rows=None):
    """Gated DeltaNet (config.GatedDeltaSpec; ops/gated_delta.py). h [B, C, d]
    -> (out [B, C, d], conv', S', snap_store'). C == 1 with ``snap_dst`` None
    is the decode step's one-token rule: over the burst's live rows, in
    place, where ``gdn_step_reason`` finds nothing against the kernel."""
    B, C, _ = h.shape
    H, Dv, K = spec.n_heads, spec.head_dim, spec.conv_kernel
    kw, ch = spec.k_width, spec.conv_channels
    qkvz = jnp.einsum("bcd,dw->bcw", h, lp["w_qkvz"], preferred_element_type=_F32)
    qkv, z = qkvz[..., :ch].astype(c.dtype), qkvz[..., ch:]
    ba = jnp.einsum("bcd,dw->bcw", h, lp["w_ba"], preferred_element_type=_F32)
    real = (jax.lax.broadcasted_iota(jnp.int32, (B, C), 1) < chunk_lens[:, None])[..., None]
    beta = jnp.where(real, jax.nn.sigmoid(ba[..., :H]), 0.0)
    g = jnp.where(
        real,
        -jnp.exp(lp["A_log"].astype(_F32)) * jax.nn.softplus(ba[..., H:] + lp["dt_bias"]),
        0.0)
    no_bias = jnp.zeros((ch,), _F32)

    def heads(act):  # [..., ch] after the conv -> q, k [..., H, Dk], v [..., H, Dv]
        act = jax.nn.silu(act)
        lead = act.shape[:-1]
        q, k = gd.prepare_qk(
            act[..., :kw].reshape(lead + (spec.n_k_heads, spec.k_dim)),
            act[..., kw : 2 * kw].reshape(lead + (spec.n_k_heads, spec.k_dim)),
            H // spec.n_k_heads)
        return q, k, act[..., 2 * kw :].reshape(lead + (H, Dv))

    if C == 1 and snap_dst is None:
        act, tail = m2.conv_step(qkv[:, 0], conv, lp["conv_w"], no_bias)
        conv_new = jnp.where(real[:, :1], tail, conv)
        q, k, v = heads(act)
        if live_rows is not None and decode_recurrence_reason(spec, True, S) is None:
            o, S_new = gdn_step_live(q, k, v, g[:, 0], beta[:, 0], S, *live_rows)
        else:
            o, S_new = gd.gdn_step(q, k, v, g[:, 0], beta[:, 0], S)
        o = o[:, None]  # [B, 1, H, Dv]
    else:
        act, padded = m2.conv_chunk(qkv, conv, lp["conv_w"], no_bias)
        conv_new = m2.conv_tail_at(padded, chunk_lens, K)
        q, k, v = heads(act)
        o, ends = gd.gdn_chunk_scan(q, k, v, g, beta, S, chunk=spec.scan_block)
        ends = ends.astype(S.dtype)
        S_new = ends[:, -1]
        if snap_dst is not None:
            snap_store = _snap_blocks(snap_store, snap_dst, padded, ends, spec.scan_block, K)
    # A plain-weight RMS norm per head, times silu(z), then out.
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + c.rms_norm_eps)
    o = o * lp["o_norm"].astype(_F32) * jax.nn.silu(z.reshape(o.shape))
    out = jnp.einsum("bci,id->bcd", o.reshape(B, C, H * Dv).astype(c.dtype), lp["w_out"])
    return out, conv_new, S_new, snap_store


def _mamba_mixer(c, spec, lp, h, chunk_lens, conv, S, snap_dst, snap_store,
                 live_rows=None):
    """h [B, C, d] -> (out [B, C, d], conv', S', snap_store'). C == 1 with
    ``snap_dst`` None is the decode step's one-token recurrence."""
    B, C, _ = h.shape
    H, P, G, N = spec.n_heads, spec.head_dim, spec.n_groups, spec.state_size
    di, ch, K = spec.d_inner, spec.conv_channels, spec.conv_kernel
    zxd = jnp.einsum("bcd,dw->bcw", h, lp["w_in"], preferred_element_type=_F32)
    z, xbc, dt = zxd[..., :di], zxd[..., di : di + ch].astype(c.dtype), zxd[..., di + ch :]
    real = jax.lax.broadcasted_iota(jnp.int32, (B, C), 1) < chunk_lens[:, None]
    dt = jnp.where(real[..., None], jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
    A = -jnp.exp(lp["A_log"].astype(_F32))
    if C == 1 and snap_dst is None:
        act, tail = m2.conv_step(xbc[:, 0], conv, lp["conv_w"], lp["conv_b"])
        conv_new = jnp.where(real[:, :1, None], tail, conv)
        act = jax.nn.silu(act)
        x = act[:, :di].reshape(B, H, P)
        Bm = act[:, di : di + G * N].reshape(B, G, N)
        Cm = act[:, di + G * N :].reshape(B, G, N)
        y, S_new = _decode_recurrence(x, dt[:, 0], A, Bm, Cm, S, live_rows)
        y = (y + lp["D"][None, :, None] * x)[:, None]  # [B, 1, H, P]
    else:
        act, padded = m2.conv_chunk(xbc, conv, lp["conv_w"], lp["conv_b"])
        conv_new = m2.conv_tail_at(padded, chunk_lens, K)
        act = jax.nn.silu(act)
        x = act[..., :di].reshape(B, C, H, P)
        Bm = act[..., di : di + G * N].reshape(B, C, G, N)
        Cm = act[..., di + G * N :].reshape(B, C, G, N)
        y, ends = m2.ssd_chunk_scan(x, dt, A, Bm, Cm, S, chunk=spec.scan_block)
        ends = ends.astype(S.dtype)
        S_new = ends[:, -1]
        y = y + lp["D"][None, None, :, None] * x
        if snap_dst is not None:
            snap_store = _snap_blocks(snap_store, snap_dst, padded, ends, spec.scan_block, K)
    # Gated, grouped RMSNorm over groups of d_inner / n_groups, then out.
    y = y.reshape(B, C, di) * jax.nn.silu(z)
    yg = y.reshape(B, C, G, di // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + c.rms_norm_eps)
    y = (yg.reshape(B, C, di) * lp["gnorm"].astype(_F32)).astype(c.dtype)
    return jnp.einsum("bci,id->bcd", y, lp["w_out"]), conv_new, S_new, snap_store


def _window_view(block_tables, start_pos, window: int, C: int, block_size: int,
                 num_blocks: int):
    """A sliding layer's view of its (logically indexed) table: the slots
    from the page of the first key the chunk's first query sees on, as many
    as a chunk of ``C`` can span, and the positions rebased to that page.
    Attention and the cache write depend on positions only through their
    differences, so every path (both kernels, the XLA form) runs on the view
    as it is: a slot behind the window, whose page the engine has released,
    is in no view, and a chunk over a long context gathers the window's
    pages only. Returns (table for reads, table for writes, start_pos'):
    a slot past the table's end reads block 0 (positions past every query)
    and writes nowhere."""
    P = block_tables.shape[1]
    width = (window + C - 2) // block_size + 2
    if width >= P:  # the table is no wider than a view: nothing to cut
        return block_tables, block_tables, start_pos
    poff = jnp.maximum(start_pos.astype(jnp.int32) - window + 1, 0) // block_size
    slot = poff[:, None] + jnp.arange(width, dtype=jnp.int32)[None]
    ids = jnp.take_along_axis(block_tables, jnp.minimum(slot, P - 1), axis=1)
    inside = slot < P
    return (
        jnp.where(inside, ids, 0), jnp.where(inside, ids, num_blocks),
        start_pos - poff * block_size,
    )


def _gated(lp, h, attn, per_lane):
    """The sigmoid output gate from the sublayer's normed input, per head or
    per lane (``w_gate_attn`` [d, H] or [d, H D]). attn [B, C, H, D]."""
    g = jax.nn.sigmoid(
        jnp.einsum("bcd,dh->bch", h, lp["w_gate_attn"], preferred_element_type=_F32)
    )
    out = attn.astype(_F32)
    return (out * (g.reshape(attn.shape) if per_lane else g[..., None])).astype(attn.dtype)


def _attention_mixer(c, spec, lp, h, k_c, v_c, block_tables, start_pos, chunk_lens,
                     rope, *, use_kernel, first_chunk, plan, write_tables=None,
                     kc_c=None, want_selection=False):
    """Returns (out, k pool, v pool) and, for a sparse layer (``kc_c`` its
    compressed keys), also (compressed pool, selection or None)."""
    B, C, _ = h.shape
    hd = spec.head_dim
    q = jnp.einsum("bcd,dh->bch", h, lp["wq"]).reshape(B, C, spec.n_heads, hd)
    k = jnp.einsum("bcd,dh->bch", h, lp["wk"]).reshape(B, C, spec.n_kv_heads, hd)
    v = jnp.einsum("bcd,dh->bch", h, lp["wv"]).reshape(B, C, spec.n_kv_heads, hd)
    if spec.qk_norm:
        q = _rms(q, lp["q_norm"], c.rms_norm_eps, c.rmsnorm_unit_offset)
        k = _rms(k, lp["k_norm"], c.rms_norm_eps, c.rmsnorm_unit_offset)
    if spec.positions == "rope":
        q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    wt = block_tables if write_tables is None else write_tables
    k_c = write_chunk_to_cache(k_c, k, wt, start_pos, chunk_lens)
    v_c = write_chunk_to_cache(v_c, v, wt, start_pos, chunk_lens)
    win = jnp.asarray(spec.window, jnp.int32)
    picked = None
    if spec.sparse is not None:
        with jax.named_scope("sparse_index"):
            kc_c = write_compressed_keys(
                kc_c, k_c, block_tables, start_pos, chunk_lens, C, spec.sparse)
    # A fresh chunk attends over its own registers; a sparse layer's only
    # where no query of the chunk reaches ``dense_len``.
    if first_chunk and (spec.sparse is None or C < spec.sparse.dense_len) and not want_selection:
        attn = dense_chunk_attention(q, k, v, chunk_lens, sm_scale=hd**-0.5, window=win)
    elif spec.sparse is not None:
        attn = sparse_paged_attention(
            q, k_c, v_c, kc_c, block_tables, start_pos, chunk_lens, spec.sparse,
            sm_scale=hd**-0.5, use_kernel=use_kernel, want_selection=want_selection,
        )
        if want_selection:
            attn, picked = attn
    else:
        attn = paged_attention(
            q, k_c, v_c, block_tables, start_pos, chunk_lens, use_kernel=use_kernel,
            sm_scale=hd**-0.5, window=win, plan=plan,
        )
    if spec.gate:
        attn = _gated(lp, h, attn, spec.gate_lanes)
    out = jnp.einsum("bch,hd->bcd", attn.reshape(B, C, -1), lp["wo"])
    if spec.sparse is not None:
        return out, k_c, v_c, kc_c, picked
    return out, k_c, v_c


def _lightning_mixer(c, spec, lp, h, chunk_lens, rope, S, snap_dst, snap_S,
                     live_rows=None):
    """Lightning attention (config.LightningSpec). h [B, C, d] -> (out, S',
    snapshot stack'). The recurrence is ops/mamba2's with dt = 1 on real
    positions (0 on padding: the state stays), A = -slope, B = k, C = q,
    x = v; C == 1 with ``snap_dst`` None is the decode step."""
    B, C, _ = h.shape
    H, D = spec.n_heads, spec.head_dim
    q = jnp.einsum("bcd,dh->bch", h, lp["wq"]).reshape(B, C, H, D)
    k = jnp.einsum("bcd,dh->bch", h, lp["wk"]).reshape(B, C, H, D)
    v = jnp.einsum("bcd,dh->bch", h, lp["wv"]).reshape(B, C, H, D)
    q = _rms(q, lp["q_norm"], c.rms_norm_eps)
    k = _rms(k, lp["k_norm"], c.rms_norm_eps)
    q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    real = jax.lax.broadcasted_iota(jnp.int32, (B, C), 1) < chunk_lens[:, None]
    dt = jnp.broadcast_to(real[..., None].astype(_F32), (B, C, H))
    A = -jnp.asarray(spec.slopes, _F32)
    qs = q.astype(_F32) * D**-0.5
    if C == 1 and snap_dst is None:
        y, S_new = _decode_recurrence(
            v[:, 0], dt[:, 0], A, k[:, 0], qs[:, 0], S, live_rows)
        y = y[:, None]
    else:
        y, ends = m2.ssd_chunk_scan(v, dt, A, k, qs, S, chunk=spec.scan_block)
        ends = ends.astype(S.dtype)
        S_new = ends[:, -1]
        if snap_dst is not None:
            nb = C // spec.scan_block
            snap_S = snap_S.at[snap_dst.reshape(B * nb)].set(
                ends.reshape((B * nb,) + ends.shape[2:]), mode="drop")
    y = _rms(y, lp["o_norm"], c.rms_norm_eps).astype(c.dtype)  # [B, C, H, D]
    y = _gated(lp, h, y, True)
    return jnp.einsum("bch,hd->bcd", y.reshape(B, C, H * D), lp["wo"]), S_new, snap_S


def _mla_mixer(c, spec, lp, h, pool, block_tables, start_pos, chunk_lens, rope,
               *, use_kernel, first_chunk, plan):
    """Latent attention. The cache row ``c_kv | k_r`` is written first;
    a fresh chunk then attends in the expanded form over its own latents,
    every other step in the absorbed form over the pool's rows."""
    B, C, _ = h.shape
    H, R, dn = spec.n_heads, spec.kv_rank, spec.nope_dim
    scale = spec.qk_dim**-0.5
    cq = _rms(jnp.einsum("bcd,dr->bcr", h, lp["w_qa"]), lp["q_norm"], c.rms_norm_eps)
    q = jnp.einsum("bcr,rhk->bchk", cq, lp["w_qb"])  # [B, C, H, nope + rope]
    q_n, q_r = q[..., :dn], apply_rope(q[..., dn:], *rope)
    ckr = jnp.einsum("bcd,dr->bcr", h, lp["w_kva"])
    c_kv = _rms(ckr[..., :R], lp["kv_norm"], c.rms_norm_eps)
    k_r = apply_rope(ckr[..., None, R:], *rope)[:, :, 0]  # one key, every head's
    pool = write_chunk_to_cache(
        pool, jnp.concatenate([c_kv, k_r], axis=-1), block_tables, start_pos, chunk_lens
    )
    if first_chunk:
        k_n = jnp.einsum("bcr,rhk->bchk", c_kv, lp["w_kb"])
        v = jnp.einsum("bcr,rhk->bchk", c_kv, lp["w_vb"])
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, :, None], (B, C, H, spec.rope_dim))], axis=-1
        )
        attn = mla_chunk_attention(
            jnp.concatenate([q_n, q_r], axis=-1), k, v, chunk_lens, sm_scale=scale
        )
    else:
        q_abs = jnp.concatenate(
            [jnp.einsum("bchk,rhk->bchr", q_n, lp["w_kb"]), q_r], axis=-1
        )
        o_lat = mla_paged_attention(
            pad_head(q_abs, pool.shape[-1]), pool, block_tables, start_pos, chunk_lens,
            v_width=R, sm_scale=scale, use_kernel=use_kernel, plan=plan,
        )
        attn = jnp.einsum("bchr,rhk->bchk", o_lat, lp["w_vb"])
    return jnp.einsum("bch,hd->bcd", attn.reshape(B, C, -1), lp["wo"]), pool


def _dense_ffn(lp, h):
    gate = jnp.einsum("bcd,df->bcf", h, lp["w_gate"])
    up = jnp.einsum("bcd,df->bcf", h, lp["w_up"])
    return jnp.einsum("bcf,fd->bcd", jax.nn.silu(gate) * up, lp["w_down"])


# A sublayer's scope in a device trace: ``mixer_<kind>`` but for these.
_SCOPES = {"mla": "mixer_mla", "dense_ffn": "ffn_dense"}


def _scope(c, spec) -> str:
    if spec.kind == "attention" and c.window_group is not None:
        return "mixer_attention_window" if spec.window else "mixer_attention_full"
    if spec.kind == "attention" and spec.sparse is not None:
        return "mixer_sparse_attention"
    return _SCOPES.get(spec.kind, f"mixer_{spec.kind}")


# -- forward -------------------------------------------------------------------


def forward(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, C]
    start_pos: jnp.ndarray,  # [B]
    chunk_lens: jnp.ndarray,  # [B]
    block_tables: jnp.ndarray,
    k_cache,
    v_cache,
    ssm: Dict[str, Tuple[jnp.ndarray, ...]],
    *,
    use_kernel: bool = False,
    first_chunk: bool = False,
    all_logits: bool = False,
    snap: Optional[Dict[str, Any]] = None,
    want_moe_stats: bool = False,
    want_selection: bool = False,
    live_rows=None,
):
    """One step over a chunk: (logits, k_cache, v_cache, ssm', snap store' or
    None, expert-load stats float32 [3] summed over the expert layers or
    None). ``chunk_lens`` marks the real tokens of each row; a row of
    length 0 changes nothing of its own. ``want_selection`` (tests, the
    benchmark's reference) replaces the last entry by what every sparse
    layer's indexer selected, ``ops/sparse_attention.select_blocks``'s
    triple a layer. ``live_rows`` (ops/pallas/ssd_step.live_row_list of a
    decode burst's ``active``, derived once a burst): the rows whose
    recurrent state a decode step updates; every recurrent layer shares it."""
    c = config
    B, C = tokens.shape
    x = params["embed"][tokens].astype(c.dtype)
    if c.embed_multiplier != 1.0:
        x = x * jnp.asarray(c.embed_multiplier, c.dtype)
    rope = None
    latent = c.specs_of("mla")
    attn_specs = c.specs_of("attention")
    pos = start_pos[:, None] + jax.lax.broadcasted_iota(jnp.int32, (B, C), 1)
    if latent or any(s.positions == "rope" and s.rope is None for s in attn_specs):
        rope = rope_table(
            pos, latent[0].rope_dim if latent else c.head_dim_, c.rope_theta
        )
    # One rope table per distinct law and one view + plan per distinct
    # (page group, window, queries a K/V head), not per layer.
    ropes = {law: rope_table_for(pos, law) for law in {s.rope for s in attn_specs if s.rope}}
    for s in c.specs_of("lightning"):  # one table per (head, theta), not per layer
        if ("lightning", s.head_dim, s.rope_theta) not in ropes:
            ropes["lightning", s.head_dim, s.rope_theta] = rope_table(
                pos, s.head_dim, s.rope_theta)
    n_attn = len(attn_specs)
    selections = []
    group_of = {}  # attention layer -> its page group's index
    for g, group in enumerate(c.cache_groups):
        group_of.update(dict.fromkeys(group.layers, g))
    views, plans = {}, {}
    plan = None
    if not first_chunk and latent:
        plan = mla_attention_plan(
            C, k_cache[0], block_tables, start_pos, chunk_lens, use_kernel=use_kernel
        )
    for i, s in enumerate(attn_specs):
        g = group_of.get(i, 0)
        if (g, s.window) not in views:
            table = block_tables if block_tables.ndim == 2 else block_tables[:, g]
            views[g, s.window] = (table, None, start_pos) if not s.window else (
                _window_view(table, start_pos, s.window, C, k_cache[i].shape[1],
                             k_cache[i].shape[0]))
        key = (g, s.window, s.n_heads)
        if s.sparse is not None:
            continue  # a sparse layer's grids follow its own selection
        if not first_chunk and key not in plans:
            table, _, start = views[g, s.window]
            plans[key] = paged_attention_plan(
                C, s.n_heads, k_cache[i], table, start, chunk_lens,
                use_kernel=use_kernel, window=s.window,
            )
    real = jax.lax.broadcasted_iota(jnp.int32, (B, C), 1) < chunk_lens[:, None]
    k_out, v_out, conv_out, s_out = list(k_cache), list(v_cache), [], []
    store = None if snap is None else snap["store"]
    store_conv = None if store is None else list(store["conv"])
    store_S = None if store is None else list(store["S"])
    stats = jnp.zeros((3,), _F32)
    ia = im = ir = isp = 0
    for spec, lp in zip(c.layer_specs, params["layers"]):
        h = _rms(x, lp["norm"], c.rms_norm_eps, c.rmsnorm_unit_offset)
        with jax.named_scope(_scope(c, spec)):
            if spec.kind == "mla":
                out, k_out[ia] = _mla_mixer(
                    c, spec, lp, h, k_cache[ia], block_tables, start_pos, chunk_lens,
                    rope, use_kernel=use_kernel, first_chunk=first_chunk, plan=plan,
                )
                ia += 1
            elif spec.kind == "dense_ffn":
                out = _dense_ffn(lp, h)
            elif spec.kind == "attention":
                g = group_of.get(ia, 0)
                table, wtable, start = views[g, spec.window]
                sparse = {} if spec.sparse is None else dict(
                    kc_c=k_cache[n_attn + isp], want_selection=want_selection)
                out, k_out[ia], v_out[ia], *more = _attention_mixer(
                    c, spec, lp, h, k_cache[ia], v_cache[ia], table, start,
                    chunk_lens, ropes.get(spec.rope, rope), use_kernel=use_kernel,
                    first_chunk=first_chunk,
                    plan=plans.get((g, spec.window, spec.n_heads)), write_tables=wtable,
                    **sparse,
                )
                if more:
                    k_out[n_attn + isp] = more[0]
                    selections.append(more[1])
                    isp += 1
                ia += 1
            elif spec.kind in ("mamba2", "gated_delta"):
                one = None if store is None else {
                    "conv": store_conv[im], "S": store_S[ir]}
                mixer = _mamba_mixer if spec.kind == "mamba2" else _gated_delta_mixer
                out, cv, S, one = mixer(
                    c, spec, lp, h, chunk_lens, ssm["conv"][im], ssm["S"][ir],
                    None if snap is None else snap["dst"], one,
                    live_rows=live_rows,
                )
                conv_out.append(cv)
                s_out.append(S)
                if one is not None:
                    store_conv[im], store_S[ir] = one["conv"], one["S"]
                im += 1
                ir += 1
            elif spec.kind == "lightning":
                out, S, one = _lightning_mixer(
                    c, spec, lp, h, chunk_lens,
                    ropes["lightning", spec.head_dim, spec.rope_theta], ssm["S"][ir],
                    None if snap is None else snap["dst"],
                    None if store is None else store_S[ir],
                    live_rows=live_rows,
                )
                s_out.append(S)
                if store is not None:
                    store_S[ir] = one
                ir += 1
            else:
                if want_moe_stats:
                    out, st = moe_ffn(
                        h, lp, spec, row_mask=real, want_stats=True,
                        use_kernel=use_kernel,
                    )
                    stats = stats + st
                else:
                    out = moe_ffn(h, lp, spec, row_mask=real, use_kernel=use_kernel)
            if "post_norm" in lp:
                out = _rms(out.astype(x.dtype), lp["post_norm"], c.rms_norm_eps,
                           c.rmsnorm_unit_offset)
        if c.residual_multiplier != 1.0:
            out = out * jnp.asarray(c.residual_multiplier, out.dtype)
        x = x + out.astype(x.dtype)
    ssm_new = {"conv": tuple(conv_out), "S": tuple(s_out)}
    store_new = None if store is None else {
        "conv": tuple(store_conv), "S": tuple(store_S)}
    if not all_logits:
        last = jnp.clip(chunk_lens - 1, 0, C - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    with jax.named_scope("lm_head"):
        x = _rms(x, params["final_norm"], c.rms_norm_eps, c.rmsnorm_unit_offset)
        if c.logit_divisor != 1.0:
            x = x * jnp.asarray(1.0 / c.logit_divisor, x.dtype)
        logits = jnp.einsum(
            "...d,dv->...v", x, params["lm_head"], preferred_element_type=_F32
        )
    if want_selection:
        return logits, tuple(k_out), tuple(v_out), ssm_new, store_new, selections
    return (
        logits, tuple(k_out), tuple(v_out), ssm_new, store_new,
        stats if want_moe_stats else None,
    )
