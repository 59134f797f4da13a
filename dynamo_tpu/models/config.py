"""Model architecture config.

One dataclass describes the dense decoder family; `from_hf_config` ingests a
HuggingFace `config.json` (llama / qwen2 / mistral architectures), which is
what the reference's ModelDeploymentCard resolves from the hub
(ref: lib/llm/src/model_card.rs:178, local_model/).
"""

from __future__ import annotations

import json
import os
import dataclasses
from dataclasses import dataclass, field
from functools import cached_property
from typing import Any, Dict, List, Optional, Tuple, Union

import jax.numpy as jnp


# -- per-layer specs ---------------------------------------------------------
# A hybrid model's layers are one mixer each, of different kinds
# (``ModelConfig.layer_specs``); the layer loop (models/hybrid.py) dispatches
# on the spec's ``kind``. The llama-family decoder layer (attention + FFN)
# builds an ExpertsSpec from its MoE knobs, so one expert op serves both.
# A published layer of two sublayers (a mixer, then an FFN or experts) is two
# entries; where the family norms a sublayer's OUTPUT too, before the residual
# is added (``sandwich_norm``), the entry says so: ``post_norm``.


@dataclass(frozen=True)
class RopeLaw:
    """A rotary law as data of an attention spec: the first ``rotary_dim``
    lanes of a head rotate (the rest carry no position), at ``theta``;
    ``yarn`` = (factor, original positions, beta_fast, beta_slow) blends,
    per frequency, ``inv_freq`` and ``inv_freq / factor`` by the linear ramp
    between the two correction dims, and cos and sin are multiplied by
    ``attention_factor`` (ops/rope.rope_freqs)."""

    theta: float
    rotary_dim: int
    yarn: Optional[Tuple[float, int, float, float]] = None
    attention_factor: float = 1.0


@dataclass(frozen=True)
class SparseIndex:
    """Trainable block-sparse attention (InfLLM-v2, the MiniCPM4 family) as
    data of an attention spec. Keys are mean-pooled into COMPRESSED keys
    (windows of ``kernel`` tokens every ``stride``: the indexer's cache); a
    query at position t whose sequence is at least ``dense_len`` long scores
    the compressed keys whose window ends at or before t (an exact softmax),
    sums the scores over the query heads of a K/V head, takes for each
    ``block`` of tokens the largest score of a window that meets it, and
    attends over ``topk`` blocks only: the first ``init_blocks``, the blocks
    that hold the last ``window`` tokens, and the best-scored others. A
    shorter sequence attends densely. ``kernel`` is twice ``stride`` and
    ``block`` a multiple of it; the pool's page is one ``block``."""

    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192

    @property
    def keys_per_block(self) -> int:
        """Compressed keys filed with a page: those whose window's LAST
        token lies in it."""
        return self.block // self.stride


@dataclass(frozen=True)
class AttentionSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    positions: str = "rope"  # "rope" | "none" (no positional rotation)
    # Keys a query at position t sees: (t - window, t]; 0 = every position.
    # A windowed layer's pages live in a page group of their own
    # (``ModelConfig.cache_groups``): the pool keeps the window, not the
    # context.
    window: int = 0
    # None with positions "rope": the model's ``rope_theta`` over the head.
    rope: Optional[RopeLaw] = None
    # A sigmoid gate per head on the attention output, from the sublayer's
    # normed input (``w_g`` [d, heads]), before the output projection;
    # ``gate_lanes``: per lane instead (``w_g`` [d, heads * head_dim]).
    gate: bool = False
    gate_lanes: bool = False
    # An RMS norm (a weight of ``head_dim``) on every query and key head,
    # before any rotation.
    qk_norm: bool = False
    # Block-sparse attention chosen by an indexer over compressed keys, which
    # the layer caches beside K and V under the pool's own block ids.
    sparse: Optional[SparseIndex] = None
    kind: str = "attention"


@dataclass(frozen=True)
class LatentAttentionSpec:
    """Multi-head latent attention (MLA): queries through a low-rank
    bottleneck, keys and values expanded from one cached latent a token,
    ``c_kv`` [kv_rank] beside one rotary key ``k_r`` [rope_dim] that all
    heads share. The cache row is ``cache_width`` values, whatever the
    number of heads."""

    n_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int  # a head's query/key lanes without positions
    rope_dim: int  # its rotary lanes; the key's are shared by every head
    v_dim: int
    post_norm: bool = False
    kind: str = "mla"

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def cache_width(self) -> int:
        return self.kv_rank + self.rope_dim


@dataclass(frozen=True)
class DenseFFNSpec:
    """A gated-silu FFN as a sublayer of its own."""

    d_ff: int
    post_norm: bool = False
    kind: str = "dense_ffn"


@dataclass(frozen=True)
class Mamba2Spec:
    n_heads: int
    head_dim: int
    state_size: int
    n_groups: int
    conv_kernel: int = 4
    # Tokens per block of the prefill scan = the stride at which the engine
    # may snapshot the state for prefix reuse. The result does not depend on
    # it (the source's ``chunk_size`` 128 is its own kernel's block).
    scan_block: int = 64
    state_dtype: str = "float32"
    # Tokens between the snapshot boundaries of a prompt (a multiple of
    # ``scan_block``); None = every ``scan_block``, in a store of
    # block_pool.SSM_SNAPSHOT_ENTRIES entries.
    snapshot_every: Optional[int] = None
    kind: str = "mamba2"

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    @property
    def in_width(self) -> int:  # [z | xBC | dt]
        return self.d_inner + self.conv_channels + self.n_heads


@dataclass(frozen=True)
class LightningSpec:
    """Lightning (linear) attention as recurrent state: per head h a matrix
    ``S_t = lambda_h S_{t-1} + k_t^T v_t`` [head_dim, head_dim] in float32,
    ``o_t = q_t S_t / sqrt(head_dim)``, with q/k RMS norms, rotary over all
    lanes at ``rope_theta``, an RMS norm on ``o`` and a per-lane sigmoid gate.
    ``lambda_h = exp(-2^(-8 (h + 1) / n_heads))`` (``slopes``). It is
    ops/mamba2's recurrence with dt = 1, A = -slope, B = k, C = q, x = v and
    one group a head, so prefill and decode run ``ssd_chunk_scan`` and
    ``ssd_step``; there is no conv tail."""

    n_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    scan_block: int = 64
    state_dtype: str = "float32"
    # Tokens between snapshot boundaries: an entry is n_heads * head_dim^2
    # float32 a layer (2.1 MB at 32 heads of 128), so a long prompt keeps
    # one every so many tokens, not one a scan block.
    snapshot_every: Optional[int] = 4096
    kind: str = "lightning"

    @property
    def slopes(self) -> Tuple[float, ...]:
        n = self.n_heads
        return tuple(2.0 ** (-8.0 * (h + 1) / n) for h in range(n))


@dataclass(frozen=True)
class GatedDeltaSpec:
    """Gated DeltaNet (arXiv:2412.06464; ``qwen3_next``'s linear-attention
    layer) as recurrent state: per value head a float32 matrix S [key,
    value] with a MATRIX transition,

        S <- exp(g_t) S;  u_t = beta_t (v_t - k_t S);  S <- S + k_t^T u_t;
        o_t = q_t S

    (``g_t = -exp(A_log) softplus(a_t + dt_bias)``, ``beta_t = sigmoid(b_t)``,
    q and k L2-normalised per head, q / sqrt(k_dim), a key head serving
    ``n_heads / n_k_heads`` value heads), behind a causal depth-wise conv of
    ``conv_kernel`` taps over ``[q | k | v]`` whose tail is state too, and in
    front of a per-head RMS norm times ``silu(z)``. The state is multiplied
    by ``exp(g)(I - beta k^T k)``, not by a scalar: ops/mamba2's recurrence
    cannot express it (ops/gated_delta.py)."""

    n_heads: int  # value heads
    n_k_heads: int
    head_dim: int  # a value head's lanes
    k_dim: int  # a key head's lanes
    conv_kernel: int = 4
    scan_block: int = 64
    state_dtype: str = "float32"
    # An entry is n_heads * k_dim * head_dim float32 + the conv tail a layer
    # (2.15 MB at 32 heads of 128 x 128): one every so many tokens.
    snapshot_every: Optional[int] = 4096
    kind: str = "gated_delta"

    @property
    def k_width(self) -> int:
        return self.n_k_heads * self.k_dim

    @property
    def v_width(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_channels(self) -> int:  # [q | k | v]
        return 2 * self.k_width + self.v_width


RECURRENT_KINDS = ("mamba2", "lightning", "gated_delta")


@dataclass(frozen=True)
class ExpertsSpec:
    n_experts: int  # the router's width: every expert of the model
    top_k: int
    d_ff: int
    # "softmax" | "sigmoid" | "sigmoid_bias" (a correction bias chooses)
    routing: str = "softmax"
    norm_topk: bool = True
    scale: float = 1.0
    activation: str = "silu_gated"  # "silu_gated" | "relu2" (no gate matrix)
    shared_d_ff: int = 0  # one shared expert of this width (0 = none)
    # The shared expert's output times ``sigmoid(x w)``, a scalar a token
    # (``ws_gate_scalar`` [d, 1]).
    shared_gate: bool = False
    held: Optional[Tuple[int, int]] = None  # [lo, hi) experts held here; None = all
    post_norm: bool = False
    kind: str = "experts"

    @property
    def held_(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held_
        return hi - lo

    def holding(self, lo: int, hi: int) -> "ExpertsSpec":
        return dataclasses.replace(self, held=(lo, hi))


@dataclass(frozen=True)
class CacheGroup:
    """Attention layers (indices among the attention layers) that share a
    block table, and how much of a sequence their pools keep: ``window`` 0 =
    all of it, else the last ``window`` tokens (pages wholly behind it are
    released while the sequence runs)."""

    name: str  # "full" | "window"
    layers: Tuple[int, ...]
    window: int


LayerSpec = Union[
    AttentionSpec, LatentAttentionSpec, Mamba2Spec, LightningSpec, GatedDeltaSpec,
    ExpertsSpec, DenseFFNSpec,
]


def refuse_hybrid(config: Any, mechanism: str) -> None:
    """Raise, naming ``mechanism``, for a configuration whose sequences carry
    recurrent state or whose cache is one latent pool a layer: the mechanisms
    that call this move a (K, V) pair of paged blocks per layer and nothing
    else."""
    why = getattr(config, "hybrid_refusal", lambda m: None)(mechanism)
    if why:
        raise ValueError(why)


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: Optional[int] = None  # defaults to d_model // n_heads
    d_ff: int = 14336
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    max_position_embeddings: int = 8192
    qkv_bias: bool = False  # Qwen2-style
    # Qwen3-style per-head RMSNorm on q and k (over head_dim, before RoPE).
    qk_norm: bool = False
    tie_word_embeddings: bool = False
    # MoE knobs (0 experts = dense). Covers Mixtral/Qwen-MoE/DeepSeek-lite
    # shapes: every layer's FFN becomes top-k routed experts (ops/moe.py).
    n_experts: int = 0
    n_experts_per_tok: int = 2
    moe_d_ff: Optional[int] = None  # expert hidden dim (default: d_ff)
    norm_topk_prob: bool = True
    # Hybrid models: one mixer per layer, each of its own kind (see the
    # specs above). None = the llama-family layer (attention + FFN) in
    # every layer, described by the knobs of this class.
    layer_specs: Optional[Tuple[LayerSpec, ...]] = None
    eos_token_ids: List[int] = field(default_factory=list)
    bos_token_id: Optional[int] = None
    dtype: Any = jnp.bfloat16
    name: str = "llama"
    # Gemma-family knobs (defaults = llama semantics):
    act_fn: str = "silu"  # "silu" | "gelu_tanh"
    rmsnorm_unit_offset: bool = False  # weight stored as (w - 1), apply 1+w
    post_norms: bool = False  # extra norms AFTER attention and FFN blocks
    embed_scale: bool = False  # multiply embeddings by sqrt(d_model)
    attn_logit_softcap: Optional[float] = None  # cap·tanh(s/cap) on scores
    final_logit_softcap: Optional[float] = None  # same on lm_head logits
    query_scale: Optional[float] = None  # q·scale⁻⁰·⁵ (query_pre_attn_scalar)
    # Sliding-window attention: window size in tokens (None = full) applied
    # to layers where ``layer_idx % sliding_window_every == 0`` (1 = all
    # layers, Mistral-style; 2 = alternating, Gemma-2-style).
    sliding_window: Optional[int] = None
    sliding_window_every: int = 1
    # HF-style pattern (Gemma-3): layer i is WINDOWED unless
    # (i + 1) % sliding_window_pattern == 0 (i.e. every pattern-th layer is
    # global — the 5:1 local/global layout). Takes precedence over
    # sliding_window_every when set.
    sliding_window_pattern: Optional[int] = None
    # Authoritative per-layer window list (overrides every pattern knob):
    # ingested verbatim from an HF ``layer_types`` list, so aperiodic
    # layouts are honored exactly.
    layer_window_overrides: Optional[List[int]] = None
    # Gemma-3 dual-frequency RoPE: LOCAL (windowed) layers use this theta;
    # global layers use rope_theta (optionally linearly position-scaled by
    # rope_scaling_factor, the HF rope_scaling={linear, factor} dialect).
    rope_local_theta: Optional[float] = None
    rope_scaling_factor: Optional[float] = None
    # MiniCPM's scalings (hybrid models only; 1.0 = none): the embedding is
    # multiplied by ``embed_multiplier``, every sublayer's output by
    # ``residual_multiplier`` before the residual add, and the final hidden
    # state divided by ``logit_divisor`` before the head.
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logit_divisor: float = 1.0

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def moe_d_ff_(self) -> int:
        return self.moe_d_ff if self.moe_d_ff is not None else self.d_ff

    def experts_spec(self) -> ExpertsSpec:
        """The llama-family MoE FFN as data of the one expert op: softmax
        routing, gated-silu experts, every expert held."""
        return ExpertsSpec(
            n_experts=self.n_experts, top_k=self.n_experts_per_tok,
            d_ff=self.moe_d_ff_, norm_topk=self.norm_topk_prob,
        )

    @property
    def is_hybrid(self) -> bool:
        return self.layer_specs is not None

    def specs_of(self, kind: str) -> List[LayerSpec]:
        return [s for s in (self.layer_specs or ()) if s.kind == kind]

    @cached_property
    def recurrent_specs(self) -> List[LayerSpec]:
        """The layers whose sequences carry state beside the paged pools
        (Mamba-2, lightning attention, Gated DeltaNet), in layer order."""
        return [s for s in (self.layer_specs or ()) if s.kind in RECURRENT_KINDS]

    @property
    def has_recurrent_state(self) -> bool:
        return bool(self.recurrent_specs)

    @property
    def snapshot_stride(self) -> Tuple[int, int]:
        """(scan block, tokens between snapshot boundaries) of the recurrent
        layers; one of each a model."""
        specs = self.recurrent_specs
        strides = {(s.scan_block, s.snapshot_every or s.scan_block) for s in specs}
        if len(strides) != 1:
            raise ValueError(
                f"{self.name}: recurrent layers with scan blocks and snapshot "
                f"spacings {sorted(strides)}: one of each a model is implemented"
            )
        scan, every = next(iter(strides))
        if every % scan:
            raise ValueError(
                f"{self.name}: snapshot spacing {every} is not a multiple of "
                f"the scan block {scan}"
            )
        return scan, every

    @cached_property
    def sparse_index(self) -> Optional[SparseIndex]:
        """The indexer sizes of the model's sparse attention layers (one set
        a model), or None."""
        found = {s.sparse for s in self.specs_of("attention") if s.sparse}
        if len(found) > 1:
            raise ValueError(f"{self.name}: one set of sparse sizes a model")
        return next(iter(found)) if found else None

    @property
    def has_latent_cache(self) -> bool:
        """The paged pools hold one latent row a token a layer, not K and V."""
        return bool(self.specs_of("mla"))

    @cached_property
    def cache_groups(self) -> Tuple[CacheGroup, ...]:
        """The cache spec: which attention layers share a page group and how
        much of a sequence each group keeps. One entry ("full", every
        attention layer, keep all) but for a hybrid model that mixes
        windowed and full attention layers: then the full layers' group
        first and one "window" group (one window a model) after it."""
        attn = self.specs_of("attention")
        windows = sorted({s.window for s in attn if s.window})
        if not windows:
            n = len(attn) or len(self.specs_of("mla")) or self.n_layers
            return (CacheGroup("full", tuple(range(n)), 0),)
        if len(windows) > 1:
            raise ValueError(
                f"{self.name}: windows {windows}: one window page group a "
                "model is implemented"
            )
        full = tuple(i for i, s in enumerate(attn) if not s.window)
        win = tuple(i for i, s in enumerate(attn) if s.window)
        if not full:  # nothing to tell apart: one table, every page kept
            return (CacheGroup("full", win, 0),)
        return (CacheGroup("full", full, 0), CacheGroup("window", win, windows[0]))

    def tables_shape(self, rows: int, width: int) -> Tuple[int, ...]:
        """Shape of a block-table array: one table a row, or one per page
        group where the model has two."""
        groups = len(self.cache_groups)
        return (rows, width) if groups == 1 else (rows, groups, width)

    @property
    def window_group(self) -> Optional[CacheGroup]:
        """The page group that keeps a window of each sequence, where the
        model has one BESIDE a full group (two tables a row)."""
        groups = self.cache_groups
        return groups[1] if len(groups) > 1 else None

    def hybrid_refusal(self, mechanism: str) -> Optional[str]:
        """Why ``mechanism`` cannot serve this configuration, or None. The
        mechanisms that spell out the per-layer K/V tuple (disaggregation
        wire, KVBM tiers, KV checkpoints, int8 KV) know nothing of
        per-sequence recurrent state, nor of a pool that is one latent
        tile a layer."""
        mla = self.specs_of("mla")
        win = self.window_group
        sparse = [s for s in self.specs_of("attention") if s.sparse]
        if sparse:
            return (
                f"{mechanism} moves a (K, V) pair of paged blocks per layer, "
                f"and {self.name} keeps a third array under the same block "
                f"ids in {len(sparse)} sparse-attention layers (the indexer's "
                f"compressed keys, {sparse[0].sparse.keys_per_block} a page, "
                "which the selection of every later query reads)"
                + (
                    f" beside recurrent state in {len(self.recurrent_specs)} "
                    "lightning-attention layers" if self.recurrent_specs else ""
                )
                + f": {mechanism} carries neither"
            )
        if win is not None:
            return (
                f"{mechanism} moves one list of paged K/V blocks per sequence, "
                f"the same ids in every layer, and {self.name} keeps two page "
                f"groups: {len(win.layers)} sliding-window layers whose pages "
                f"behind the last {win.window} tokens are released while the "
                f"sequence runs, beside {len(self.cache_groups[0].layers)} "
                f"full layers; {mechanism} knows nothing of the second table "
                "nor of a page that is gone"
            )
        if mla:
            return (
                f"{mechanism} carries a (K, V) pair of paged blocks per layer, "
                f"and {self.name} keeps ONE latent pool per layer (c_kv beside "
                f"the shared rotary key, {mla[0].cache_width} values a token) "
                f"in {len(mla)} latent-attention layers: there is no K and no "
                f"V block for {mechanism} to move"
            )
        if not self.recurrent_specs:
            return None
        gdn = self.specs_of("gated_delta")
        if gdn:
            return (
                f"{mechanism} moves paged K/V blocks only, and {self.name} "
                f"keeps per-sequence recurrent state (a conv tail and a "
                f"float32 matrix a head under the delta rule's matrix "
                f"transition) in {len(gdn)} Gated DeltaNet layers that "
                f"{mechanism} does not carry"
            )
        if not self.specs_of("mamba2"):
            return (
                f"{mechanism} moves paged K/V blocks only, and {self.name} "
                f"keeps per-sequence recurrent state (a float32 matrix a head) "
                f"in {len(self.recurrent_specs)} lightning-attention layers "
                f"that {mechanism} does not carry"
            )
        return (
            f"{mechanism} moves paged K/V blocks only, and {self.name} keeps "
            f"per-sequence recurrent state (conv tail + SSM state) in "
            f"{len(self.specs_of('mamba2'))} Mamba-2 layers that {mechanism} "
            "does not carry"
        )

    def layer_windows(self) -> List[int]:
        """Per-layer attention window (0 = unlimited)."""
        if self.layer_window_overrides is not None:
            assert len(self.layer_window_overrides) == self.n_layers
            return list(self.layer_window_overrides)
        if not self.sliding_window:
            return [0] * self.n_layers
        if self.sliding_window_pattern:
            p = self.sliding_window_pattern
            return [
                self.sliding_window if (i + 1) % p != 0 else 0
                for i in range(self.n_layers)
            ]
        return [
            self.sliding_window if i % max(self.sliding_window_every, 1) == 0 else 0
            for i in range(self.n_layers)
        ]

    @classmethod
    def from_hf_config(cls, cfg: Dict[str, Any], name: str = "") -> "ModelConfig":
        if str(cfg.get("model_type", "")) == "nemotron_h":
            return _nemotron_h_from_hf(cfg, name)
        if str(cfg.get("model_type", "")) == "pangu_ultra_moe":
            return _pangu_ultra_moe_from_hf(cfg, name)
        if str(cfg.get("model_type", "")) == "laguna":
            return _laguna_from_hf(cfg, name)
        if str(cfg.get("model_type", "")) == "minicpm_sala":
            return _minicpm_sala_from_hf(cfg, name)
        if str(cfg.get("model_type", "")) == "qwen3_next":
            return _qwen3_next_from_hf(cfg, name)
        archs = cfg.get("architectures") or [""]
        arch = archs[0].lower()
        eos = cfg.get("eos_token_id")
        if eos is None:
            eos_ids: List[int] = []
        elif isinstance(eos, list):
            eos_ids = [int(e) for e in eos]
        else:
            eos_ids = [int(eos)]
        # MoE fields across HF dialects: Mixtral (num_local_experts),
        # Qwen-MoE (num_experts + moe_intermediate_size + norm_topk_prob)
        n_experts = cfg.get("num_local_experts") or cfg.get("num_experts") or 0
        model_type = str(cfg.get("model_type", ""))
        # Gemma-family: unit-offset norms, GeGLU, scaled/tied embeddings.
        # Gemma-2 ADDS post-norms, softcaps and 1:1 local/global layers;
        # Gemma-3 swaps softcaps for qk-norm, 5:1 local/global layers and
        # dual-frequency RoPE (implemented since r5).
        gemma = "gemma" in arch or "gemma" in model_type
        gemma2 = "gemma2" in arch or model_type == "gemma2"
        # Gemma-3 (text): gemma-2 layout + qk-norm, 5:1 local/global layers
        # (sliding_window_pattern / layer_types), dual-frequency RoPE
        # (rope_local_base_freq on windowed layers), softcaps removed.
        gemma3 = "gemma3" in arch or "gemma3" in model_type
        swp = cfg.get("sliding_window_pattern") or cfg.get(
            "_sliding_window_pattern"
        )
        # (gated: a vestigial sliding_window behind use_sliding_window=false
        # must not re-enter through the layer_types path either)
        _gated_window = (
            cfg.get("sliding_window")
            if cfg.get("use_sliding_window", True)
            else None
        )
        window_overrides = None
        if cfg.get("layer_types") and _gated_window:
            # layer_types is the authoritative per-layer layout — honor it
            # VERBATIM (aperiodic lists included) instead of inferring a
            # period from it.
            window_overrides = [
                int(_gated_window) if t == "sliding_attention" else 0
                for t in cfg["layer_types"]
            ]
        if gemma3 and not swp and window_overrides is None:
            # A gemma-3 config carrying neither field would silently fall
            # through to every-layer-windowed — the garbage-logits mode the
            # old refusal existed to prevent.
            raise ValueError(
                "gemma-3 config carries neither sliding_window_pattern nor "
                "layer_types; cannot determine the local/global layer layout"
            )
        rope_scaling = cfg.get("rope_scaling") or {}
        rope_factor = (
            float(rope_scaling.get("factor"))
            if rope_scaling.get("rope_type", rope_scaling.get("type")) == "linear"
            and rope_scaling.get("factor")
            else None
        )
        # Some configs (Qwen2 dialect) carry a vestigial sliding_window with
        # an explicit use_sliding_window=false gate — honor the gate.
        sliding = (
            cfg.get("sliding_window")
            if cfg.get("use_sliding_window", True)
            else None
        )
        return cls(
            vocab_size=cfg["vocab_size"],
            d_model=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            d_ff=cfg["intermediate_size"],
            n_experts=int(n_experts),
            n_experts_per_tok=int(cfg.get("num_experts_per_tok", 2)),
            moe_d_ff=cfg.get("moe_intermediate_size"),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            rope_theta=cfg.get("rope_theta", 10000.0),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            qkv_bias="qwen2" in arch and "qwen3" not in arch,
            qk_norm="qwen3" in arch or model_type == "qwen3" or gemma3,
            tie_word_embeddings=cfg.get("tie_word_embeddings", gemma),
            eos_token_ids=eos_ids,
            bos_token_id=cfg.get("bos_token_id"),
            name=name or cfg.get("model_type", "llama"),
            # Gemma-2 (ref: the HF Gemma2 config dialect)
            # Prefer the modern 'hidden_activation' key ('or', not a dict
            # default: real Gemma-1 hub configs carry an explicit
            # hidden_activation: null beside hidden_act). HF forces tanh-gelu
            # for the gemma family regardless of hidden_act, so plain 'gelu'
            # and an unset gemma config both resolve to gelu_tanh.
            act_fn=(
                "gelu_tanh"
                if (
                    (cfg.get("hidden_activation") or cfg.get("hidden_act"))
                    in ("gelu_pytorch_tanh", "gelu_tanh", "gelu")
                    or (
                        gemma
                        and not cfg.get("hidden_activation")
                        and not cfg.get("hidden_act")
                    )
                )
                else "silu"
            ),
            rmsnorm_unit_offset=gemma,
            post_norms=gemma2 or gemma3,
            embed_scale=gemma,
            attn_logit_softcap=cfg.get("attn_logit_softcapping"),
            final_logit_softcap=cfg.get("final_logit_softcapping"),
            query_scale=cfg.get("query_pre_attn_scalar"),
            sliding_window=int(sliding) if sliding else None,
            sliding_window_every=2 if gemma2 else 1,
            sliding_window_pattern=(
                int(swp) if (gemma3 and swp and window_overrides is None)
                else None
            ),
            layer_window_overrides=window_overrides,
            rope_local_theta=(
                float(cfg.get("rope_local_base_freq", 10000.0))
                if gemma3 else None
            ),
            rope_scaling_factor=rope_factor,
        )

    @classmethod
    def from_model_dir(cls, path: str) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf_config(json.load(f), name=os.path.basename(path.rstrip("/")))


def _nemotron_h_from_hf(cfg: Dict[str, Any], name: str = "") -> ModelConfig:
    """``nemotron_h``: ``hybrid_override_pattern`` names each layer's one
    mixer: ``M`` Mamba-2, ``E`` experts (``-`` a dense MLP, which no
    supported model of the family has), ``*`` attention. The family's
    published modelling code rotates no positions in its attention layers
    (``rope_theta`` in the config is unused): ``positions="none"``."""
    pattern = str(cfg["hybrid_override_pattern"])[: int(cfg["num_hidden_layers"])]
    attn = AttentionSpec(
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]),
        positions="none",
    )
    mamba = Mamba2Spec(
        n_heads=int(cfg["mamba_num_heads"]), head_dim=int(cfg["mamba_head_dim"]),
        state_size=int(cfg["ssm_state_size"]), n_groups=int(cfg["n_groups"]),
        conv_kernel=int(cfg["conv_kernel"]),
    )
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("nemotron_h: grouped routing (n_group > 1) is not implemented")
    experts = ExpertsSpec(
        n_experts=int(cfg["n_routed_experts"]), top_k=int(cfg["num_experts_per_tok"]),
        d_ff=int(cfg["moe_intermediate_size"]), routing="sigmoid_bias",
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        scale=float(cfg.get("routed_scaling_factor", 1.0)),
        activation={"relu2": "relu2"}[cfg.get("mlp_hidden_act", "relu2")],
        shared_d_ff=int(cfg.get("moe_shared_expert_intermediate_size", 0))
        * int(cfg.get("n_shared_experts", 1)),
    )
    kinds = {"M": mamba, "E": experts, "*": attn}
    unknown = sorted(set(pattern) - set(kinds))
    if unknown:
        raise ValueError(f"nemotron_h: layer kinds {unknown} are not implemented")
    eos = cfg.get("eos_token_id")
    return ModelConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=len(pattern), n_heads=attn.n_heads, n_kv_heads=attn.n_kv_heads,
        head_dim=attn.head_dim, d_ff=int(cfg["intermediate_size"]),
        rms_norm_eps=float(cfg.get("norm_eps", cfg.get("layer_norm_epsilon", 1e-5))),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        max_position_embeddings=int(cfg.get("max_position_embeddings", 8192)),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        eos_token_ids=[] if eos is None else [int(e) for e in (eos if isinstance(eos, list) else [eos])],
        bos_token_id=cfg.get("bos_token_id"),
        name=name or "nemotron_h",
        layer_specs=tuple(kinds[ch] for ch in pattern),
    )


# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, the keys of its public config.json
# that say something about its shape
# (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json).
NEMOTRON_3_NANO_30B_A3B_HF: Dict[str, Any] = {
    "model_type": "nemotron_h", "hidden_size": 2688, "num_hidden_layers": 52,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "attention_bias": False, "intermediate_size": 1856, "vocab_size": 131072,
    "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128,
    "n_groups": 8, "conv_kernel": 4, "chunk_size": 128, "expand": 2,
    "use_conv_bias": True, "mamba_proj_bias": False, "mamba_hidden_act": "silu",
    "n_routed_experts": 128, "num_experts_per_tok": 6, "n_group": 1,
    "topk_group": 1, "moe_intermediate_size": 1856, "n_shared_experts": 1,
    "moe_shared_expert_intermediate_size": 3712, "mlp_hidden_act": "relu2",
    "mlp_bias": False, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "norm_eps": 1e-05, "layer_norm_epsilon": 1e-05, "rope_theta": 10000,
    "max_position_embeddings": 262144, "tie_word_embeddings": False,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
}


def cut_hybrid(
    cfg: ModelConfig, *, n_layers: int, experts_held: Tuple[int, int],
    vocab_rows: int, name: str,
) -> ModelConfig:
    """One chip's share of an expert-parallel group, cut in depth: the
    pattern's first ``n_layers`` layers, ``experts_held`` of every expert
    layer (the router keeps its width), the first ``vocab_rows`` rows of
    the embedding and the head. No width changes."""
    specs = tuple(
        s.holding(*experts_held) if s.kind == "experts" else s
        for s in cfg.layer_specs[:n_layers]
    )
    return dataclasses.replace(
        cfg, n_layers=n_layers, layer_specs=specs, vocab_size=vocab_rows, name=name,
    )


def _pangu_ultra_moe_from_hf(cfg: Dict[str, Any], name: str = "") -> ModelConfig:
    """``pangu_ultra_moe``: every published layer is latent attention, then
    a dense FFN (the first ``first_k_dense_replace`` layers) or the experts;
    two entries of ``layer_specs`` a layer. ``sandwich_norm`` puts a norm on
    each sublayer's output too. Sigmoid scores choose and weigh the experts
    (no ``scoring_func``, ``n_group`` or ``topk_method`` key: no correction
    bias, no groups). The multi-token-prediction module
    (``num_nextn_predict_layers``) is a draft head and is not built."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"pangu_ultra_moe: hidden_act {cfg['hidden_act']!r} is not implemented")
    if cfg.get("rope_scaling"):
        raise ValueError("pangu_ultra_moe: rope_scaling is not implemented")
    if cfg.get("attention_bias"):
        raise ValueError("pangu_ultra_moe: attention_bias is not implemented")
    post = bool(cfg.get("sandwich_norm", False))
    mla = LatentAttentionSpec(
        n_heads=int(cfg["num_attention_heads"]), q_rank=int(cfg["q_lora_rank"]),
        kv_rank=int(cfg["kv_lora_rank"]), nope_dim=int(cfg["qk_nope_head_dim"]),
        rope_dim=int(cfg["qk_rope_head_dim"]), v_dim=int(cfg["v_head_dim"]),
        post_norm=post,
    )
    dense = DenseFFNSpec(d_ff=int(cfg["intermediate_size"]), post_norm=post)
    experts = ExpertsSpec(
        n_experts=int(cfg["n_routed_experts"]), top_k=int(cfg["num_experts_per_tok"]),
        d_ff=int(cfg["moe_intermediate_size"]), routing="sigmoid",
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        scale=float(cfg.get("routed_scaling_factor", 1.0)),
        shared_d_ff=int(cfg["moe_intermediate_size"]) * int(cfg.get("n_shared_experts", 0)),
        post_norm=post,
    )
    n, k = int(cfg["num_hidden_layers"]), int(cfg.get("first_k_dense_replace", 0))
    specs: List[LayerSpec] = []
    for i in range(n):
        specs += [mla, dense if i < k else experts]
    eos = cfg.get("eos_token_id")
    return ModelConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=len(specs), n_heads=mla.n_heads,
        n_kv_heads=int(cfg.get("num_key_value_heads", mla.n_heads)),
        head_dim=mla.qk_dim, d_ff=dense.d_ff,
        rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        max_position_embeddings=int(cfg.get("max_position_embeddings", 8192)),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        eos_token_ids=[] if eos is None else [int(e) for e in (eos if isinstance(eos, list) else [eos])],
        bos_token_id=cfg.get("bos_token_id"),
        name=name or "pangu_ultra_moe",
        layer_specs=tuple(specs),
    )


# openPangu-Ultra-MoE-718B, the keys of its public config.json that say
# something about its shape
# (https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json).
OPENPANGU_ULTRA_MOE_718B_HF: Dict[str, Any] = {
    "model_type": "pangu_ultra_moe", "hidden_size": 7680, "num_hidden_layers": 61,
    "first_k_dense_replace": 3, "intermediate_size": 18432, "hidden_act": "silu",
    "num_attention_heads": 128, "num_key_value_heads": 128, "attention_bias": False,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "sandwich_norm": True,
    "n_routed_experts": 256, "num_experts_per_tok": 8, "moe_intermediate_size": 2048,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-05, "rope_theta": 25600000,
    "max_position_embeddings": 131072, "tie_word_embeddings": False,
    "vocab_size": 153600,
}


def openpangu_ultra_moe_ep16_config() -> ModelConfig:
    """openPangu-Ultra-MoE-718B at its published widths, as share 0 of the
    first stage of its served deployment: sixteen chips share each layer by
    expert parallelism (16 of the 256 routed experts each; latent attention,
    dense FFN, router and shared expert whole on every chip, each attending
    over its own batch), the vocabulary sliced eight ways, the 61 layers on
    thirteen such groups as pipeline stages. This chip: one leading dense
    layer and four expert layers, ten sublayers."""
    hf = dict(OPENPANGU_ULTRA_MOE_718B_HF, num_hidden_layers=5, first_k_dense_replace=1)
    return cut_hybrid(
        ModelConfig.from_hf_config(hf), n_layers=10, experts_held=(0, 16),
        vocab_rows=19200, name="openpangu-ultra-moe-718b-ep16",
    )


def _rope_law_from_hf(rp: Dict[str, Any], head_dim: int) -> RopeLaw:
    rotary = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return RopeLaw(float(rp["rope_theta"]), rotary)
    if kind != "yarn":
        raise ValueError(f"laguna: rope_type {kind!r} is not implemented")
    return RopeLaw(
        float(rp["rope_theta"]), rotary,
        yarn=(float(rp["factor"]), int(rp["original_max_position_embeddings"]),
              float(rp["beta_fast"]), float(rp["beta_slow"])),
        attention_factor=float(rp["attention_factor"]),
    )


def _laguna_from_hf(cfg: Dict[str, Any], name: str = "") -> ModelConfig:
    """``laguna``: every published layer is attention, then a dense FFN
    (``mlp_layer_types`` "dense") or the experts; two entries of
    ``layer_specs`` a layer. ``layer_types`` says full or sliding-window
    attention, ``num_attention_heads_per_layer`` the query heads of each,
    ``rope_parameters`` the rotary law of each kind, ``gating`` the sigmoid
    gate on the attention output. Assumed, where the config names a switch
    and not its shape: the gate is per head; sigmoid scores choose and weigh
    the experts, normalised over the chosen, times
    ``moe_routed_scaling_factor``; no query/key norm; the dense layer has no
    shared expert (models/laguna_reference.py states the same)."""
    if cfg.get("attention_bias"):
        raise ValueError("laguna: attention_bias is not implemented")
    if cfg.get("moe_apply_router_weight_on_input"):
        raise ValueError("laguna: router weights on the experts' input are not implemented")
    hd, kv = int(cfg["head_dim"]), int(cfg["num_key_value_heads"])
    n = int(cfg["num_hidden_layers"])
    laws = {
        k: _rope_law_from_hf(rp, hd)
        for k, rp in cfg["rope_parameters"].items() if isinstance(rp, dict)
    }
    heads = cfg.get("num_attention_heads_per_layer") or [int(cfg["num_attention_heads"])] * n
    dense = DenseFFNSpec(d_ff=int(cfg["intermediate_size"]))
    experts = ExpertsSpec(
        n_experts=int(cfg["num_experts"]), top_k=int(cfg["num_experts_per_tok"]),
        d_ff=int(cfg["moe_intermediate_size"]), routing="sigmoid", norm_topk=True,
        scale=float(cfg.get("moe_routed_scaling_factor", 1.0)),
        shared_d_ff=int(cfg.get("shared_expert_intermediate_size", 0)),
    )
    specs: List[LayerSpec] = []
    for i in range(n):
        kind = cfg["layer_types"][i]
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"laguna: layer type {kind!r}")
        specs.append(AttentionSpec(
            n_heads=int(heads[i]), n_kv_heads=kv, head_dim=hd,
            window=int(cfg["sliding_window"]) if kind == "sliding_attention" else 0,
            rope=laws[kind], gate=bool(cfg.get("gating", False)),
        ))
        specs.append(dense if cfg["mlp_layer_types"][i] == "dense" else experts)
    eos = cfg.get("eos_token_id")
    return ModelConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=len(specs), n_heads=int(cfg["num_attention_heads"]), n_kv_heads=kv,
        head_dim=hd, d_ff=dense.d_ff, rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        rope_theta=laws["full_attention"].theta,
        max_position_embeddings=int(cfg.get("max_position_embeddings", 8192)),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        eos_token_ids=[] if eos is None else [int(e) for e in (eos if isinstance(eos, list) else [eos])],
        bos_token_id=cfg.get("bos_token_id"),
        name=name or "laguna", layer_specs=tuple(specs),
    )


# Laguna-XS.2, the keys of its public config.json that say something about its
# shape (https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json).
_LAGUNA_PERIOD = ["full_attention"] + ["sliding_attention"] * 3
LAGUNA_XS2_HF: Dict[str, Any] = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
    "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
            "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": _LAGUNA_PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}


def laguna_xs2_pp8_config() -> ModelConfig:
    """Laguna-XS.2 at its published widths, as stage 0 of its served
    deployment: eight chips as eight pipeline stages of five layers, every
    layer held whole by its stage's chip (every expert, the whole
    vocabulary). This chip: layers 0-4, ``full+dense, sliding, sliding,
    sliding, full``, ten sublayers; the head sits here so that the stage
    yields logits (in the deployment it is stage 7's)."""
    hf = dict(LAGUNA_XS2_HF, num_hidden_layers=5)
    return dataclasses.replace(
        ModelConfig.from_hf_config(hf), name="laguna-xs.2-pp8"
    )


# What the MiniCPM4 family publishes as ``sparse_config`` and the MiniCPM-SALA
# config.json does not carry (benchmark/configs/minicpm-sala-pp4.json lists
# the same under ``assumed``).
MINICPM4_SPARSE = SparseIndex(
    kernel=32, stride=16, block=64, topk=64, init_blocks=1, window=2048,
    dense_len=8192,
)


def _minicpm_sala_from_hf(
    cfg: Dict[str, Any], name: str = "", *, sparse: SparseIndex = MINICPM4_SPARSE,
) -> ModelConfig:
    """``minicpm_sala``: every published layer is a mixer (``mixer_types``:
    ``minicpm4`` block-sparse GQA without rotary, or ``lightning-attn``
    linear attention), then a gated-silu FFN; two entries of ``layer_specs``
    a layer. MiniCPM's scalings: embeddings x ``scale_emb``, every sublayer's
    output x ``scale_depth / sqrt(mup_denominator)`` (the PUBLISHED depth,
    whatever ``num_hidden_layers`` is cut to), the final hidden state /
    (``hidden_size / dim_model_base``). Assumed, where the config names a
    switch and not its shape: the output gates are per lane; the lightning
    slopes are ALiBi's (``LightningSpec.slopes``), the same in every layer;
    the sparse sizes are the MiniCPM4 family's
    (models/minicpm_sala_reference.py states the same)."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"minicpm_sala: hidden_act {cfg['hidden_act']!r} is not implemented")
    if cfg.get("attention_bias"):
        raise ValueError("minicpm_sala: attention_bias is not implemented")
    n = int(cfg["num_hidden_layers"])
    kinds = list(cfg["mixer_types"])[:n]
    hd = int(cfg["head_dim"])
    attn = AttentionSpec(
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=hd,
        positions="rope" if cfg.get("attn_use_rope", False) else "none",
        gate=bool(cfg.get("attn_use_output_gate", False)), gate_lanes=True,
        qk_norm=bool(cfg.get("qk_norm", False)), sparse=sparse,
    )
    if attn.positions != "none":
        raise ValueError("minicpm_sala: rotary sparse-attention layers are not implemented")
    if int(cfg.get("lightning_nkv", cfg["lightning_nh"])) != int(cfg["lightning_nh"]):
        raise ValueError("minicpm_sala: grouped lightning K/V heads are not implemented")
    for key in ("qk_norm", "use_output_gate", "use_output_norm", "lightning_use_rope"):
        if not cfg.get(key, True):
            raise ValueError(f"minicpm_sala: {key} false is not implemented")
    lightning = LightningSpec(
        n_heads=int(cfg["lightning_nh"]), head_dim=int(cfg["lightning_head_dim"]),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
    )
    ffn = DenseFFNSpec(d_ff=int(cfg["intermediate_size"]))
    mixers = {"minicpm4": attn, "lightning-attn": lightning}
    unknown = sorted(set(kinds) - set(mixers))
    if unknown:
        raise ValueError(f"minicpm_sala: mixer types {unknown} are not implemented")
    specs: List[LayerSpec] = []
    for kind in kinds:
        specs += [mixers[kind], ffn]
    d = int(cfg["hidden_size"])
    eos = cfg.get("eos_token_id")
    return ModelConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=d, n_layers=len(specs),
        n_heads=attn.n_heads, n_kv_heads=attn.n_kv_heads, head_dim=hd, d_ff=ffn.d_ff,
        rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        max_position_embeddings=int(cfg.get("max_position_embeddings", 8192)),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        eos_token_ids=[] if eos is None else [int(e) for e in (eos if isinstance(eos, list) else [eos])],
        bos_token_id=cfg.get("bos_token_id"),
        name=name or "minicpm_sala", layer_specs=tuple(specs),
        embed_multiplier=float(cfg.get("scale_emb", 1.0)),
        residual_multiplier=float(cfg.get("scale_depth", 1.0))
        / float(cfg.get("mup_denominator", 1.0)) ** 0.5,
        logit_divisor=d / float(cfg.get("dim_model_base", d)),
    )


# MiniCPM-SALA, the keys of its public config.json that say something about
# its shape (https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json).
_SALA_MIXERS = "SLLLLLLLLSLLLLLLSSLLLLSLLLLLLSSS"
MINICPM_SALA_HF: Dict[str, Any] = {
    "model_type": "minicpm_sala", "attention_bias": False, "attn_use_rope": False,
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "lightning_head_dim": 128, "lightning_nh": 32,
    "lightning_nkv": 32, "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288,
    "mixer_types": [
        "minicpm4" if ch == "S" else "lightning-attn" for ch in _SALA_MIXERS],
    "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2,
    "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
}
# The served stage: the published layers 9..16, two whole periods at 1 : 3.
SALA_PP4_LAYERS = (9, 17)


def minicpm_sala_pp4_config() -> ModelConfig:
    """MiniCPM-SALA at its published widths, as one pipeline stage of eight
    layers of a v5e-4 host (four stages, every layer whole on its chip): the
    published layers 9..16, ``minicpm4, lightning x 6, minicpm4``, two whole
    periods in the published 1 : 3 ratio (no stage-aligned span of eight has
    it: the stages hold 1 : 7, 1 : 7, 3 : 5, 3 : 5). Sixteen sublayers;
    embedding and head both sit here so that the stage takes ids and yields
    logits. The residual scale keeps the PUBLISHED depth's 1.4 / sqrt(32)."""
    lo, hi = SALA_PP4_LAYERS
    hf = dict(
        MINICPM_SALA_HF, num_hidden_layers=hi - lo,
        mixer_types=MINICPM_SALA_HF["mixer_types"][lo:hi],
    )
    return dataclasses.replace(
        ModelConfig.from_hf_config(hf), name="minicpm-sala-pp4",
        max_position_embeddings=524288,
    )


def tiny_sala_config(**overrides) -> ModelConfig:
    """The served MiniCPM-SALA stage at toy widths (tests, the CPU
    rehearsal): 2 sparse + 6 lightning layers in the cell's order, 4 query
    over 2 K/V heads of 32, indexer window 8 / stride 4, blocks of 16, top 4
    of which 1 leading and the last 32 tokens' are forced, dense under 64
    tokens, so that the sparse path is taken at a few hundred tokens;
    lightning heads 4 x 32, snapshots every 64 tokens."""
    sparse = SparseIndex(
        kernel=8, stride=4, block=16, topk=4, init_blocks=1, window=32, dense_len=64,
    )
    attn = AttentionSpec(
        n_heads=4, n_kv_heads=2, head_dim=32, positions="none", gate=True,
        gate_lanes=True, qk_norm=True, sparse=sparse,
    )
    lightning = LightningSpec(n_heads=4, head_dim=32, scan_block=16, snapshot_every=64)
    ffn = DenseFFNSpec(d_ff=128)
    specs: List[LayerSpec] = []
    for mixer in (attn,) + (lightning,) * 6 + (attn,):
        specs += [mixer, ffn]
    base = dict(
        vocab_size=512, d_model=64, n_layers=len(specs), n_heads=4, n_kv_heads=2,
        head_dim=32, d_ff=128, max_position_embeddings=4096, eos_token_ids=[2],
        rms_norm_eps=1e-6, rope_theta=10000.0, dtype=jnp.float32, name="tiny-sala",
        layer_specs=tuple(specs), embed_multiplier=12.0,
        residual_multiplier=1.4 / 32**0.5, logit_divisor=64 / 16,
    )
    base.update(overrides)
    return ModelConfig(**base)


def _qwen3_next_from_hf(cfg: Dict[str, Any], name: str = "") -> ModelConfig:
    """``qwen3_next``: every published layer is a mixer, then the experts;
    two entries of ``layer_specs`` a layer. Layer i is gated softmax
    attention when ``(i + 1) % full_attention_interval == 0`` (the doubled
    ``q_proj`` is a query matrix and a per-lane gate matrix side by side;
    zero-centred per-head q/k norms; rotary on the first
    ``partial_rotary_factor`` of the lanes), a Gated DeltaNet layer
    otherwise. Every RMS-norm weight of the family is zero-centred
    (``rmsnorm_unit_offset``) but the Gated DeltaNet output norm's. Experts:
    softmax over the whole router, top-k renormalised, one shared expert
    under a scalar sigmoid gate. The multi-token-prediction module is a
    draft head and is not built."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"qwen3_next: hidden_act {cfg['hidden_act']!r} is not implemented")
    if cfg.get("rope_scaling"):
        raise ValueError("qwen3_next: rope_scaling is not implemented")
    if cfg.get("attention_bias"):
        raise ValueError("qwen3_next: attention_bias is not implemented")
    if cfg.get("mlp_only_layers") or int(cfg.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("qwen3_next: dense-MLP layers are not implemented")
    if cfg.get("use_sliding_window"):
        raise ValueError("qwen3_next: use_sliding_window is not implemented")
    hd = int(cfg["head_dim"])
    n = int(cfg["num_hidden_layers"])
    every = int(cfg.get("full_attention_interval", 4))
    attn = AttentionSpec(
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=hd,
        rope=RopeLaw(float(cfg.get("rope_theta", 10000.0)),
                     int(hd * float(cfg.get("partial_rotary_factor", 1.0)))),
        gate=True, gate_lanes=True, qk_norm=True,
    )
    gdn = GatedDeltaSpec(
        n_heads=int(cfg["linear_num_value_heads"]),
        n_k_heads=int(cfg["linear_num_key_heads"]),
        head_dim=int(cfg["linear_value_head_dim"]), k_dim=int(cfg["linear_key_head_dim"]),
        conv_kernel=int(cfg.get("linear_conv_kernel_dim", 4)),
    )
    experts = ExpertsSpec(
        n_experts=int(cfg["num_experts"]), top_k=int(cfg["num_experts_per_tok"]),
        d_ff=int(cfg["moe_intermediate_size"]), routing="softmax",
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        shared_d_ff=int(cfg.get("shared_expert_intermediate_size", 0)),
        shared_gate=bool(cfg.get("shared_expert_intermediate_size", 0)),
    )
    specs: List[LayerSpec] = []
    for i in range(n):
        specs += [attn if (i + 1) % every == 0 else gdn, experts]
    eos = cfg.get("eos_token_id")
    return ModelConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=len(specs), n_heads=attn.n_heads, n_kv_heads=attn.n_kv_heads,
        head_dim=hd, d_ff=int(cfg.get("intermediate_size", 0)),
        rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        rope_theta=attn.rope.theta,
        max_position_embeddings=int(cfg.get("max_position_embeddings", 8192)),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        eos_token_ids=[] if eos is None else [int(e) for e in (eos if isinstance(eos, list) else [eos])],
        bos_token_id=cfg.get("bos_token_id"),
        name=name or "qwen3_next", layer_specs=tuple(specs),
        rmsnorm_unit_offset=True,
    )


# Qwen3-Next-80B-A3B-Instruct, the keys of its public config.json that say
# something about its shape
# (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json).
QWEN3_NEXT_80B_A3B_HF: Dict[str, Any] = {
    "model_type": "qwen3_next", "decoder_sparse_step": 1, "full_attention_interval": 4,
    "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def qwen3_next_ep2_config() -> ModelConfig:
    """Qwen3-Next-80B-A3B-Instruct at its published widths, as share 0 of the
    first stage of its served deployment: two chips share each layer by
    expert parallelism over one batch (256 of the 512 routed experts and half
    the vocabulary each; mixers, router and shared expert whole), the 48
    layers on twelve such pairs, one period a pair. This chip: the published
    layers 0..3, ``GDN, GDN, GDN, full``, eight sublayers; embedding and head
    both sit here so that the stage takes ids and yields logits."""
    hf = dict(QWEN3_NEXT_80B_A3B_HF, num_hidden_layers=4)
    return cut_hybrid(
        ModelConfig.from_hf_config(hf), n_layers=8, experts_held=(0, 256),
        vocab_rows=75968, name="qwen3-next-80b-a3b-ep2",
    )


def tiny_gdn_config(**overrides) -> ModelConfig:
    """The served Qwen3-Next stage at toy widths (tests, the CPU rehearsal):
    ``GDN, GDN, GDN, full`` with the experts after each; Gated DeltaNet 2 key
    heads serving 4 value heads of 16 x 16, conv 4, blocks of 16, snapshots
    every 64 tokens; gated attention 4 queries over 2 K/V heads of 32, rotary
    on 8 lanes; 16 experts routed over of which the first 8 are held, softmax
    top 4, a shared expert under its scalar gate; zero-centred norms."""
    attn = AttentionSpec(
        n_heads=4, n_kv_heads=2, head_dim=32, rope=RopeLaw(1e7, 8), gate=True,
        gate_lanes=True, qk_norm=True,
    )
    gdn = GatedDeltaSpec(
        n_heads=4, n_k_heads=2, head_dim=16, k_dim=16, scan_block=16, snapshot_every=64)
    experts = ExpertsSpec(
        n_experts=16, top_k=4, d_ff=32, routing="softmax", shared_d_ff=32,
        shared_gate=True, held=(0, 8),
    )
    specs: List[LayerSpec] = []
    for mixer in (gdn, gdn, gdn, attn):
        specs += [mixer, experts]
    base = dict(
        vocab_size=512, d_model=64, n_layers=len(specs), n_heads=4, n_kv_heads=2,
        head_dim=32, d_ff=32, max_position_embeddings=4096, eos_token_ids=[2],
        rms_norm_eps=1e-6, rope_theta=1e7, dtype=jnp.float32, name="tiny-gdn",
        layer_specs=tuple(specs), rmsnorm_unit_offset=True,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_swa_config(n_layers: int = 5, **overrides) -> ModelConfig:
    """The Laguna layer at toy widths (tests, the CPU rehearsal): the period
    F S S S from a dense layer 0 on (``n_layers`` 5 ends on a full layer, as
    the served stage does), window 8, 6 and 8 queries over 2 K/V heads,
    partial + YaRN rotary in the full layers and plain rotary in the
    sliding ones, the per-head output gate, 16 experts top 4 + a shared
    one."""
    full = AttentionSpec(
        n_heads=12, n_kv_heads=2, head_dim=16, gate=True,
        rope=RopeLaw(500000.0, 8, yarn=(8.0, 32, 8.0, 1.0), attention_factor=1.2079),
    )
    slide = AttentionSpec(
        n_heads=16, n_kv_heads=2, head_dim=16, gate=True, window=8,
        rope=RopeLaw(10000.0, 16),
    )
    dense = DenseFFNSpec(d_ff=192)
    experts = ExpertsSpec(
        n_experts=16, top_k=4, d_ff=64, routing="sigmoid", scale=2.5, shared_d_ff=64,
    )
    specs: List[LayerSpec] = []
    for i in range(n_layers):
        specs += [slide if i % 4 else full, experts if i else dense]
    base = dict(
        vocab_size=512, d_model=128, n_layers=len(specs), n_heads=12, n_kv_heads=2,
        head_dim=16, d_ff=192, max_position_embeddings=2048, eos_token_ids=[2],
        rms_norm_eps=1e-6, dtype=jnp.float32, name="tiny-swa",
        layer_specs=tuple(specs),
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_mla_config(**overrides) -> ModelConfig:
    """The openPangu layer at toy widths (tests, the CPU rehearsal): one
    leading dense layer and two expert layers, sandwich norms, latent
    attention with a cache row of 32 + 16 values, 16 experts routed over of
    which the first 4 are held, plain sigmoid routing, gated-silu experts
    and a shared one."""
    mla = LatentAttentionSpec(
        n_heads=4, q_rank=48, kv_rank=32, nope_dim=16, rope_dim=16, v_dim=16,
        post_norm=True,
    )
    dense = DenseFFNSpec(d_ff=192, post_norm=True)
    experts = ExpertsSpec(
        n_experts=16, top_k=4, d_ff=64, routing="sigmoid", scale=2.5,
        shared_d_ff=64, held=(0, 4), post_norm=True,
    )
    base = dict(
        vocab_size=512, d_model=128, n_layers=6, n_heads=4, n_kv_heads=4,
        head_dim=32, d_ff=192, max_position_embeddings=2048, eos_token_ids=[2],
        rope_theta=25600000.0, dtype=jnp.float32, name="tiny-mla",
        layer_specs=(mla, dense, mla, experts, mla, experts),
    )
    base.update(overrides)
    return ModelConfig(**base)


def nemotron3_nano_ep2_config() -> ModelConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths, as share 0 of
    the first stage of its served deployment: two chips share each layer by
    expert parallelism over one batch (64 of the 128 routed experts and half
    the vocabulary each; mixers, attention, router and shared expert whole),
    the 52 layers on six such pairs. This chip: the pattern's first nine
    layers ``MEMEM*EME``."""
    return cut_hybrid(
        ModelConfig.from_hf_config(NEMOTRON_3_NANO_30B_A3B_HF),
        n_layers=9, experts_held=(0, 64), vocab_rows=65536,
        name="nemotron-3-nano-30b-a3b-ep2",
    )


def tiny_hybrid_config(**overrides) -> ModelConfig:
    """All three mixer kinds at toy widths (tests, the CPU rehearsal): the
    pattern ``ME*ME``, 8 experts routed over of which the first 4 are held,
    sigmoid routing with the correction bias, relu2 experts and a shared
    one, attention without rotary positions."""
    attn = AttentionSpec(n_heads=4, n_kv_heads=2, head_dim=32, positions="none")
    mamba = Mamba2Spec(n_heads=8, head_dim=16, state_size=16, n_groups=2, scan_block=16)
    experts = ExpertsSpec(
        n_experts=8, top_k=3, d_ff=64, routing="sigmoid_bias", scale=2.5,
        activation="relu2", shared_d_ff=96, held=(0, 4),
    )
    base = dict(
        vocab_size=512, d_model=128, n_layers=5, n_heads=4, n_kv_heads=2,
        head_dim=32, d_ff=64, max_position_embeddings=2048, eos_token_ids=[2],
        dtype=jnp.float32, name="tiny-hybrid",
        layer_specs=(mamba, experts, attn, mamba, experts),
    )
    base.update(overrides)
    return ModelConfig(**base)


# Handy known shapes for tests/benchmarks (no downloads in this environment).
def tiny_config(**overrides) -> ModelConfig:
    base = dict(
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_position_embeddings=512,
        eos_token_ids=[2],
        dtype=jnp.float32,
        name="tiny-llama",
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_moe_config(**overrides) -> ModelConfig:
    base = dict(
        n_experts=4,
        n_experts_per_tok=2,
        moe_d_ff=128,
        name="tiny-moe",
    )
    base.update(overrides)
    return tiny_config(**base)


def mixtral_8x7b_config() -> ModelConfig:
    """Mixtral-8x7B shape (BASELINE MoE class; ref: recipes/ MoE configs)."""
    return ModelConfig(
        vocab_size=32000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        n_experts=8,
        n_experts_per_tok=2,
        rope_theta=1000000.0,
        max_position_embeddings=32768,
        eos_token_ids=[2],
        name="mixtral-8x7b",
    )


def qwen2_500m_config() -> ModelConfig:
    """Qwen2.5-0.5B shape (SURVEY §7 stage 5 first real model)."""
    return ModelConfig(
        vocab_size=151936,
        d_model=896,
        n_layers=24,
        n_heads=14,
        n_kv_heads=2,
        d_ff=4864,
        rope_theta=1000000.0,
        max_position_embeddings=32768,
        qkv_bias=True,
        tie_word_embeddings=True,
        eos_token_ids=[151645],
        name="qwen2.5-0.5b",
    )


def llama3_8b_config() -> ModelConfig:
    return ModelConfig(
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=500000.0,
        max_position_embeddings=8192,
        eos_token_ids=[128001, 128009],
        name="llama-3-8b",
    )


def qwen3_8b_config() -> ModelConfig:
    """Qwen3-8B shape (HF Qwen/Qwen3-8B config.json values): qk-norm,
    no qkv bias, head_dim 128 — the architecture family of the reference's
    only hard in-tree perf anchor (aiconfigurator Qwen3-32B,
    docs/performance/aiconfigurator.md:55-59)."""
    return ModelConfig(
        vocab_size=151936,
        d_model=4096,
        n_layers=36,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=12288,
        rms_norm_eps=1e-6,
        rope_theta=1000000.0,
        max_position_embeddings=40960,
        qk_norm=True,
        eos_token_ids=[151645],
        name="qwen3-8b",
    )


def llama3_3b_config() -> ModelConfig:
    """Llama-3.2-3B shape (HF meta-llama/Llama-3.2-3B config.json values).
    The largest dense shape whose bf16 AND int8 forms both fit one 16 GB
    chip — the apples-to-apples proof shape for weight-only quantization."""
    return ModelConfig(
        vocab_size=128256,
        d_model=3072,
        n_layers=28,
        n_heads=24,
        n_kv_heads=8,
        head_dim=128,
        d_ff=8192,
        rope_theta=500000.0,
        max_position_embeddings=8192,
        tie_word_embeddings=True,
        eos_token_ids=[128001, 128009],
        name="llama-3.2-3b",
    )


def llama3_70b_config() -> ModelConfig:
    return ModelConfig(
        vocab_size=128256,
        d_model=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        d_ff=28672,
        rope_theta=500000.0,
        max_position_embeddings=8192,
        eos_token_ids=[128001, 128009],
        name="llama-3-70b",
    )


def gemma3_1b_config() -> ModelConfig:
    """Gemma-3-1B text shape (HF google/gemma-3-1b-it config.json values):
    5:1 local/global layers, dual-frequency RoPE, qk-norm."""
    return ModelConfig(
        vocab_size=262144,
        d_model=1152,
        n_layers=26,
        n_heads=4,
        n_kv_heads=1,
        head_dim=256,
        d_ff=6912,
        rms_norm_eps=1e-6,
        rope_theta=1000000.0,
        rope_local_theta=10000.0,
        max_position_embeddings=32768,
        qk_norm=True,
        tie_word_embeddings=True,
        act_fn="gelu_tanh",
        rmsnorm_unit_offset=True,
        post_norms=True,
        embed_scale=True,
        query_scale=256,
        sliding_window=512,
        sliding_window_pattern=6,
        eos_token_ids=[1, 106],
        name="gemma-3-1b",
    )


def gemma2_2b_config() -> ModelConfig:
    """Gemma-2-2B shape (HF google/gemma-2-2b config.json values)."""
    return ModelConfig(
        vocab_size=256000,
        d_model=2304,
        n_layers=26,
        n_heads=8,
        n_kv_heads=4,
        head_dim=256,
        d_ff=9216,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        max_position_embeddings=8192,
        tie_word_embeddings=True,
        eos_token_ids=[1, 107],
        name="gemma-2-2b",
        act_fn="gelu_tanh",
        rmsnorm_unit_offset=True,
        post_norms=True,
        embed_scale=True,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        query_scale=256.0,
        sliding_window=4096,
        sliding_window_every=2,
    )
