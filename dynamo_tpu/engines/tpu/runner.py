"""DeviceRunner: sole owner of the engine's device state and programs.

Split out of the engine monolith so the scheduler (engines/tpu/engine.py)
owns *policy* — admission, slots, stop conditions — while this owns
*mechanism*: params, LoRA stacks, KV cache arrays, RNG, the compiled step /
fused-decode / speculative-verify programs, sleep/wake device transitions,
and block gather/scatter. The reference keeps the same boundary between its
scheduler components and engine runtimes (SURVEY §2.2 native-engine role).

Multi-host SPMD: when constructed with a multi-process topology
(parallel/multihost.py), the runner on the leader mirrors every device
invocation over the op channel (runtime/network/spmd_channel.py) and the
runner on each follower replays it (engines/tpu/spmd.follow) — every
process issues identical global-mesh programs, the JAX-native form of the
reference's DP leader / non-leader ranks
(components/src/dynamo/vllm/main.py:67-78).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.engines.tpu import block_pool
from dynamo_tpu.models import llama
from dynamo_tpu.ops.sampling import compute_logprobs, sample_tokens
from dynamo_tpu.parallel.sharding import (
    ShardingRules,
    param_shardings,
    shard_params,
)
from dynamo_tpu.runtime.device_observe import (
    FlightRecorder,
    global_compile_watcher,
    tree_device_bytes,
    watched_jit,
)
from dynamo_tpu.utils.jax_env import require_serving_platform
from dynamo_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _scatter_state_rows_impl(state, idx, rows):
    """Write ``rows[k][i]`` into ``state[k][idx[i]]`` for every slot-state
    field — ONE device program per row-count bucket, so a dirty-slot sync
    costs a single small H2D + dispatch regardless of how many per-slot
    arrays the decode state carries.

    Deliberately NOT donated: donating these dict-of-small-array operands
    through a shared module-level jit trips a native double-free in
    jaxlib 0.4.37's CPU client when the persistent compilation cache
    serves the executable (segfault at the next engine's buffer GC,
    reproduced under tests/). The copies are a few KB on rare mutating
    events — not a hot path."""
    return {k: state[k].at[idx].set(rows[k]) for k in state}


_scatter_state_rows = watched_jit(
    "runner.scatter_state_rows", jax.jit(_scatter_state_rows_impl)
)


def _scatter_table_rows_impl(tables, idx, rows):
    return tables.at[idx].set(rows)


_scatter_table_rows = watched_jit(
    "runner.scatter_table_rows", jax.jit(_scatter_table_rows_impl)
)


@dataclass
class _DecodeHandles:
    """Un-materialized device results of one dispatched decode burst.
    Returned by decode_dispatch; decode_read blocks on them."""

    toks: Any
    logp: Any
    topv: Optional[Any] = None
    topi: Optional[Any] = None
    # Hybrid models: the burst's expert-load sums (device float32 [3]), and,
    # once decode_read has run, the same on the host: they come back with
    # the tokens, in the one readback.
    moe: Optional[Any] = None
    moe_host: Optional[np.ndarray] = None


def _scatter_blocks_impl(cache, idx, blocks):
    """cache ← blocks [L, n, BS, KH, D] at idx [n]. Works on all layouts:
    stacked [L, NB, BS, KH, D], per-layer tuple of [NB, BS, KH, >= D]
    (blocks arrive at the logical head size and are widened to the pool's,
    ops/attention.pool_head_dim), or per-layer int8 {"q8", "s"} pools
    (blocks arrive in the dequantized wire format and are re-quantized
    here — so bf16 and int8 engines interoperate over disagg/checkpoint
    transfers)."""
    from dynamo_tpu.ops.attention import pad_head
    from dynamo_tpu.ops.kv_quant import quantize_kv_chunk

    def one(c, blk):
        if isinstance(c, dict):
            q8, s = quantize_kv_chunk(blk)  # [n, BS, KH, D], [n, BS, KH]
            return {
                "q8": c["q8"].at[idx].set(q8),
                "s": c["s"].at[idx].set(s.transpose(0, 2, 1)),
            }
        return c.at[idx].set(pad_head(blk.astype(c.dtype), c.shape[-1]))

    if isinstance(cache, (tuple, list)):
        return tuple(one(c, blocks[l]) for l, c in enumerate(cache))
    return cache.at[:, idx].set(blocks.astype(cache.dtype))


_scatter_blocks = watched_jit(
    "runner.scatter_blocks",
    functools.partial(jax.jit, donate_argnums=(0,))(_scatter_blocks_impl),
)


# The DENSE wire/checkpoint dtype: int8 pools are dequantized to this by
# _gather_blocks when a dense export is requested (v1 importers, the
# checkpoint path); non-quantized pools ship in their storage dtype
# (casting would perturb fp32 test configs). The TRANSFER path prefers the
# pool-native wire form (gather_blocks_wire_* below + disagg/wire.py
# schema v2) — quantized pools then ship {q8, scales} without ever
# materializing the dense form. Chunk sizing on the transfer path must use
# disagg/wire.py::wire_block_bytes(), not a dtype literal.
KV_QUANT_WIRE_DTYPE = jnp.bfloat16


def _gather_blocks_impl(cache, idx, head_dim=None):
    """[L, n, BS, KH, D] of blocks idx [n], from any cache layout, as ONE
    device program (a per-layer host gather would pay L dispatch RTTs).
    Int8 pools are dequantized to KV_QUANT_WIRE_DTYPE — the wire/checkpoint
    format is always dense [L, n, BS, KH, D], D the LOGICAL head size:
    ``head_dim`` (static) cuts a pool held wider (pool_head_dim) back to
    it, so a block leaves as it would from a pool of any width."""
    from dynamo_tpu.ops.kv_quant import dequantize_pages

    def one(c):
        if isinstance(c, dict):
            return dequantize_pages(
                c["q8"][idx], c["s"][idx], KV_QUANT_WIRE_DTYPE
            )
        return c[idx][..., :head_dim]

    if isinstance(cache, (tuple, list)):
        return jnp.stack([one(c) for c in cache])
    return cache[:, idx]


_gather_blocks = watched_jit(
    "runner.gather_blocks",
    functools.partial(jax.jit, static_argnames=("head_dim",))(
        _gather_blocks_impl
    ),
)


def _gather_blocks_q8_impl(cache, idx):
    """Pool-native gather of a QUANTIZED cache: (q8 [L, n, BS, KH, D] int8,
    s [L, n, KH, BS] f32) of blocks idx, with NO dequantization — half the
    HBM readback and half the wire of the dense form. One device program
    (same dispatch-RTT argument as _gather_blocks)."""
    q8 = jnp.stack([c["q8"][idx] for c in cache])
    s = jnp.stack([c["s"][idx] for c in cache])
    return q8, s


_gather_blocks_q8 = watched_jit(
    "runner.gather_blocks_q8", jax.jit(_gather_blocks_q8_impl)
)


def _scatter_blocks_q8_impl(cache, idx, q8, s):
    """cache ← quantized wire blocks (q8 [L, n, BS, KH, D], s [L, n, KH, BS])
    at idx. Quantized pools take them VERBATIM (an int8→int8 transfer is
    bit-exact); dense pools dequantize on device — either way the int8
    payload rides H2D at half the dense width."""
    from dynamo_tpu.ops.attention import pad_head
    from dynamo_tpu.ops.kv_quant import dequantize_pages

    def one(c, q8_l, s_l):
        if isinstance(c, dict):
            return {"q8": c["q8"].at[idx].set(q8_l), "s": c["s"].at[idx].set(s_l)}
        return c.at[idx].set(
            pad_head(dequantize_pages(q8_l, s_l, c.dtype), c.shape[-1])
        )

    if isinstance(cache, (tuple, list)):
        return tuple(one(c, q8[l], s[l]) for l, c in enumerate(cache))
    return cache.at[:, idx].set(dequantize_pages(q8, s, cache.dtype))


_scatter_blocks_q8 = watched_jit(
    "runner.scatter_blocks_q8",
    functools.partial(jax.jit, donate_argnums=(0,))(_scatter_blocks_q8_impl),
)


def _ssm_gather_impl(store, src):
    """Rows ``src`` [B] of the snapshot store as a prefill's starting state;
    a negative index starts from zeros (a fresh prompt)."""
    def one(a):
        rows = a[jnp.clip(src, 0, a.shape[0] - 1)]
        keep = (src >= 0).reshape((-1,) + (1,) * (rows.ndim - 1))
        return jnp.where(keep, rows, jnp.zeros_like(rows))

    return jax.tree.map(one, store)


_ssm_gather = watched_jit("runner.ssm_gather", jax.jit(_ssm_gather_impl))


def _ssm_install_impl(state, rows_state, rows, slots):
    """state[slots[i]] <- rows_state[rows[i]]: a finished prefill's
    recurrent state joins the decode batch."""
    return jax.tree.map(lambda a, r: a.at[slots].set(r[rows]), state, rows_state)


_ssm_install = watched_jit(
    "runner.ssm_install",
    functools.partial(jax.jit, donate_argnums=(0,))(_ssm_install_impl),
)


def _adapter_to_host(adapter):
    """Keep retained adapters as host numpy: only the STACKED arrays belong
    in HBM — retaining per-adapter device copies for restacking would
    double LoRA device memory."""
    adapter.weights = {
        t: (np.asarray(A), np.asarray(B)) for t, (A, B) in adapter.weights.items()
    }
    return adapter


class DeviceRunner:
    """Device-state owner + program cache for one (possibly multi-process)
    logical worker. All ``run_*``/device methods are synchronous and meant
    to execute on the engine's single device thread (or the follower's main
    thread)."""

    def __init__(
        self,
        args: Any,  # JaxEngineArgs
        params: Optional[Any] = None,
        *,
        mesh=None,
        rules: Optional[ShardingRules] = None,
        topology=None,  # parallel/multihost.HostTopology
    ) -> None:
        self.args = args
        self.config = args.config
        self.mesh = mesh
        self.rules = rules or ShardingRules()
        self.topology = topology
        self.multihost = bool(topology is not None and topology.is_multihost)
        # Fail before any device allocation when JAX quietly took the CPU
        # (no chip found, JAX_PLATFORMS unset): every process that builds
        # an engine passes through here.
        backend = require_serving_platform()
        if getattr(args, "kv_cache_dtype", None) == "auto":
            # Policy: int8 KV costs two extra scale DMAs per page, which
            # dominate at short context, and pays on long context and on
            # pool capacity. Quantize when the model length crosses
            # DYN_TPU_KV_QUANT_AUTO_CTX OR the pool cannot hold the worst
            # case at bf16 (capacity pressure -> halving bytes beats
            # preemption-by-recompute thrash).
            from dynamo_tpu import config as _cfg

            pool_tokens = args.num_kv_blocks * args.block_size
            pressure = pool_tokens < args.max_num_seqs * args.max_model_len
            args.kv_cache_dtype = (
                "int8"
                if args.layered_cache
                and (
                    args.max_model_len >= _cfg.KV_QUANT_AUTO_CTX.get()
                    or pressure
                )
                else None
            )
            logger.info(
                "kv_cache_dtype=auto resolved to %s (max_model_len=%d, "
                "pool_tokens=%d, pressure=%s)",
                args.kv_cache_dtype, args.max_model_len, pool_tokens,
                pressure,
            )
        self._spmd_tx = None  # SpmdBroadcaster on the leader
        if self.hybrid:
            unsupported = [
                what for what, on in (
                    ("a device mesh (expert parallelism with its exchange)", mesh is not None),
                    ("weight quantization", bool(args.quantization)),
                    ("a quantized KV pool", bool(getattr(args, "kv_cache_dtype", None))),
                    ("the stacked KV layout", not args.layered_cache),
                    ("LoRA adapters", bool(args.lora_dir)),
                    ("speculative decoding", bool(args.spec_mode)),
                ) if on
            ]
            if unsupported:
                what = ", ".join(unsupported)
                raise ValueError(
                    self.config.hybrid_refusal(what)
                    or f"{self.config.name} is served by the per-layer-spec "
                    f"loop (models/hybrid.py); not implemented for it: {what}"
                )
        self.use_kernel, self.attention_reason = self._choose_attention(
            args, backend, mesh
        )
        if self.multihost and mesh is None:
            raise ValueError("multihost topology requires a device mesh")
        self._repl = (
            NamedSharding(mesh, P()) if (self.multihost and mesh is not None) else None
        )

        self._param_axes = llama.param_logical_axes(self.config)
        if args.quantization and args.quantization != "int8":
            raise ValueError(
                f"unsupported quantization {args.quantization!r} (int8 only)"
            )
        if params is None:
            if args.quantization:
                # Random-init directly in int8 — a full-precision tree
                # would fill HBM (8B fp ≈ a whole 16 GB chip) and fp init
                # on the single host core takes minutes at 8B scale.
                from dynamo_tpu.models.quantize import init_quantized_params

                params = init_quantized_params(self.config, args.seed)
            elif mesh is not None:
                # Born sharded: a full-precision 8B tree does not fit the
                # one device an unsharded init would land on.
                params = watched_jit(
                    "runner.init_params_sharded",
                    jax.jit(
                        functools.partial(llama.init_params, self.config),
                        out_shardings=param_shardings(
                            self._param_axes, self.rules, mesh
                        ),
                    ),
                )(jax.random.PRNGKey(args.seed))
            else:
                params = llama.init_params(
                    self.config, jax.random.PRNGKey(args.seed)
                )
        if args.quantization:
            from dynamo_tpu.models.quantize import quantize_params

            # Idempotent for pre-quantized checkpoints (hf_loader/weight
            # cache quantize host-side); rebuilds the axes tree either way.
            params, self._param_axes = quantize_params(params, self._param_axes)
        if mesh is not None:
            params = shard_params(params, self._param_axes, self.rules, mesh)
        if self.args.layered_cache and not isinstance(
            params.get("layers"), (tuple, list)
        ):
            # Serving layout: per-layer weight buffers next to the per-layer
            # KV pools (see llama.unstack_layer_params — removes the
            # per-step weight relayout fusions the stacked form costs).
            params = dict(
                params,
                layers=llama.unstack_layer_params(
                    params["layers"], self.config.n_layers
                ),
            )
            self._param_axes = dict(
                self._param_axes,
                layers=llama.unstack_layer_axes(
                    self._param_axes["layers"], self.config.n_layers
                ),
            )
        self.params = params
        self.k_cache, self.v_cache = self.alloc_kv_cache()
        # Hybrid models: the second kind of state. ``ssm_state`` holds one
        # row per decode slot (not paged: a sequence's state is one conv
        # tail and one SSM matrix per Mamba-2 layer, whatever its length),
        # threaded through each burst as a donated carry like tokens/pos.
        # ``snap_store`` holds the snapshots prefix reuse resumes from; the
        # engine's StateSnapshots is its index.
        self.ssm_state: Optional[Any] = None
        self.snap_store: Optional[Any] = None
        if self.hybrid:
            from dynamo_tpu.models import hybrid

            self.ssm_state = hybrid.init_ssm_state(self.config, args.max_num_seqs)
            self.snap_entries = block_pool.snapshot_entries(
                self.config, args.num_kv_blocks, args.block_size, args.max_num_seqs)
            self.snap_store = hybrid.init_ssm_state(self.config, self.snap_entries)
            self.snap_entry_bytes = hybrid.ssm_state_bytes(self.config)

        # Multi-LoRA state: adapter name → index into the stacked arrays
        # (index 0 is the zero "no adapter" slot).
        self.lora: Optional[Dict[str, Any]] = None
        self.lora_index: Dict[str, int] = {}
        self._adapter_list: List[Optional[Any]] = []  # slot i ↔ stacked index i+1
        if args.lora_dir:
            self._load_loras(args.lora_dir)

        # RNG: ONE fixed base key. Decode/prefill sampling keys are derived
        # on device from (base key, sequence salt, token index) —
        # ops/sampling.fold_row_keys — so noise never depends on dispatch
        # order (the pipelined scheduler's determinism contract). The
        # host-side rng_step counter remains only for the speculative
        # verify program, which has no per-token position structure.
        self.rng = jax.random.PRNGKey(args.seed ^ 0x5EED)
        if self._repl is not None:
            self.rng = jax.device_put(self.rng, self._repl)
        self.rng_step = 0

        # Device-resident decode slot state: everything the fused decode
        # program reads per slot lives in HBM and is updated INCREMENTALLY
        # on the rare mutating events (admission, finish, preempt, block
        # append) via sync_slots/sync_tables — never re-uploaded from host
        # numpy on steady-state ticks. The engine keeps numpy mirrors as
        # the scheduler's view only. tokens/pos are additionally threaded
        # through each burst as a donated carry (decode_dispatch).
        from dynamo_tpu.ops.logits_process import MAX_BIAS_SLOTS

        S = args.max_num_seqs
        state0 = {
            "tokens": np.zeros(S, np.int32),
            "pos": np.zeros(S, np.int32),
            "active": np.zeros(S, np.int32),
            "temp": np.ones(S, np.float32),
            "topk": np.zeros(S, np.int32),
            "topp": np.ones(S, np.float32),
            "adapter_ids": np.zeros(S, np.int32),
            "salts": np.zeros(S, np.int32),
            "minp": np.zeros(S, np.float32),
            "rep": np.ones(S, np.float32),
            "pres": np.zeros(S, np.float32),
            "freq": np.zeros(S, np.float32),
            "bias_ids": np.full((S, MAX_BIAS_SLOTS), -1, np.int32),
            "bias_vals": np.zeros((S, MAX_BIAS_SLOTS), np.float32),
        }
        self.slot_state = {
            k: self._dev_persistent(v) for k, v in state0.items()
        }
        self.slot_tables = self._dev_persistent(
            np.zeros(self.config.tables_shape(S, args.max_blocks_per_seq), np.int32)
        )
        # H2D accounting for the hot path: every slot-state upload and
        # decode dispatch appends ("slot_sync"|"table_sync", rows) /
        # ("decode", nb). Tests assert steady-state ticks are pure
        # dispatches (no re-upload of pos/temp/topk/topp/adapter_ids/
        # block_tables); bounded ring so serving never grows it unbounded.
        self.transfer_log: List[Tuple[str, int]] = []
        self._transfer_log_cap = 4096
        # Device-thread flight ring: transfer syncs and decode dispatches.
        # Separate ring from the
        # engine's (single-writer contract — this one is written from the
        # device-executor thread); /debug/flight merges them by timestamp.
        self.flight = FlightRecorder("runner")

        # Expected distinct-signature budget for the width-bucketed decode
        # and spec-verify programs: pow2 table widths give ~log2(cap)+1
        # buckets per program object; 2× + margin tolerates legitimate
        # re-specialization (LoRA stack restacks change operand shapes on
        # the same jit object). Crossing it means dispatch widths stopped
        # bucketing — the recompile-storm signal.
        width_buckets = max(int(args.max_blocks_per_seq), 1).bit_length() + 1
        self._decode_sig_budget = 2 * width_buckets + 4
        watcher = global_compile_watcher()
        for prog in ("runner.decode_state", "runner.spec_verify"):
            watcher.set_budget(prog, self._decode_sig_budget)

        # State-path decode programs, keyed (want_logprobs, use_procs).
        # The logprob-free variant skips a full-vocab log-softmax per fused
        # step (the common case); processor variants compile lazily on the
        # first request that uses one.
        self._decode_state_fns: Dict[Tuple[bool, bool], Any] = {}
        self._step_fn = self._build_step_fn()
        # (want_procs, want_top, first_chunk) → lazily compiled prefill
        # program variants. first_chunk (fresh prefill, start_pos all 0)
        # uses dense in-chunk attention — zero paged reads.
        self._step_fns: Dict[Tuple[bool, bool, bool], Any] = {
            (False, False, False): self._step_fn
        }
        self.proc_state: Optional[Any] = None  # logits_process.ProcState
        self._spec_fn: Optional[Any] = None  # speculative verify program
        self.sleep_level = 0
        self.host_params: Optional[Any] = None
        self.expert_ffn = self._describe_expert_ffn()
        self.ssd_step = self._describe_ssd_step()
        self._prefill_expert_forms: Dict[Tuple[int, int], Optional[str]] = {}
        logger.info(
            "device runner: platform=%s device_kind=%s devices=%d mesh=%s | "
            "attention: %s (%s) | expert_ffn: %s | ssd_step: %s",
            backend, jax.devices()[0].device_kind, len(jax.devices()),
            dict(mesh.shape) if mesh is not None else None,
            self.attention_impl, self.attention_reason, self.expert_ffn,
            self.ssd_step,
        )
        values = jax.tree.leaves(self.k_cache)[0]  # int8 pools: "q8" sorts first
        self.kv_pool = {
            "gb": round(tree_device_bytes((self.k_cache, self.v_cache)) / 1e9, 3),
            "dtype": values.dtype.name, "shape": list(values.shape),
        }
        if self.config.has_latent_cache:
            spec = self.config.specs_of("mla")[0]
            self.mla_attention = (
                "kernel" if self.use_kernel else f"xla: {self.attention_reason}"
            )
            logger.info(
                "latent pool: %.2f GB resident | %s%s per layer, %d layers: a row "
                "is c_kv %d + rotary key %d of %d lanes, no V pool | "
                "mla_attention: %s",
                self.kv_pool["gb"], values.dtype.name, list(values.shape),
                len(self.k_cache), spec.kv_rank, spec.rope_dim, values.shape[-1],
                self.mla_attention,
            )
        elif self.config.window_group is not None:
            # Two page groups: each group's bytes, pool shape and layers,
            # and what the same tenants would take on one block id for all
            # layers (every layer's pool as large as the full group's).
            groups = {}
            for group in self.config.cache_groups:
                pools = [self.k_cache[i] for i in group.layers]
                pools += [self.v_cache[i] for i in group.layers]
                groups[group.name] = {
                    "gb": round(tree_device_bytes(pools) / 1e9, 3),
                    "bytes": tree_device_bytes(pools),
                    "dtype": pools[0].dtype.name, "shape": list(pools[0].shape),
                    "layers": len(group.layers), "window": group.window,
                }
            self.kv_pool["groups"] = groups
            full, win = groups["full"], groups["window"]
            one_id = full["bytes"] // full["layers"] * (full["layers"] + win["layers"])
            one_id_gb = one_id / 1e9
            self.kv_pool["one_block_id_bytes"] = one_id
            self.kv_pool["one_block_id_gb"] = round(one_id_gb, 3)
            logger.info(
                "kv pool: full %.2f GB %s%s x %d | window %.2f GB %s%s x %d "
                "(the last %d tokens of a row) | %.2f GB resident; one block id "
                "for all %d layers would hold %.2f GB for the same tenants",
                full["gb"], full["dtype"], full["shape"], full["layers"],
                win["gb"], win["dtype"], win["shape"], win["layers"],
                win["window"], self.kv_pool["gb"],
                full["layers"] + win["layers"], one_id_gb,
            )
        else:
            logger.info(
                "kv pool: %.2f GB resident | %s%s per layer for a head of %d",
                self.kv_pool["gb"], values.dtype.name, list(values.shape),
                self.config.head_dim_,
            )
        sparse = self.config.sparse_index
        if sparse is not None:
            n_attn = len(self.config.specs_of("attention"))
            rows = self.k_cache[n_attn:]
            self.kv_pool["indexer"] = {
                "indexer_gb": round(tree_device_bytes(rows) / 1e9, 3),
                "indexer_shape": list(rows[0].shape), "indexer_layers": len(rows),
            }
            logger.info(
                "indexer rows: %.3f GB | %s%s per sparse layer x %d under the "
                "K/V pool's block ids: %d compressed keys a page (the mean of "
                "%d keys every %d), top %d blocks of %d tokens from %d tokens on",
                self.kv_pool["indexer"]["indexer_gb"], rows[0].dtype.name,
                list(rows[0].shape), len(rows), sparse.keys_per_block, sparse.kernel,
                sparse.stride, sparse.topk, sparse.block, sparse.dense_len,
            )
        if self.config.has_recurrent_state:
            logger.info(
                "recurrent state: %.2f GB in %d slots, %.2f GB in %d snapshots "
                "(one every %d tokens of a prompt, %.2f MB each) "
                "| %d attention, %d mamba2, %d lightning, %d gated_delta, "
                "%d expert layers",
                tree_device_bytes(self.ssm_state) / 1e9, args.max_num_seqs,
                tree_device_bytes(self.snap_store) / 1e9,
                jax.tree.leaves(self.snap_store)[0].shape[0],
                self.config.snapshot_stride[1], self.snap_entry_bytes / 1e6,
                len(self.config.specs_of("attention")),
                len(self.config.specs_of("mamba2")),
                len(self.config.specs_of("lightning")),
                len(self.config.specs_of("gated_delta")),
                len(self.config.specs_of("experts")),
            )

    @property
    def hybrid(self) -> bool:
        """The per-layer-spec loop serves (models/hybrid.py): its programs
        carry the recurrent state beside the pools, empty where no layer
        has any."""
        return self.config.is_hybrid

    @property
    def attention_impl(self) -> str:
        return "pallas" if self.use_kernel else "xla"

    def _describe_expert_ffn(self) -> Optional[str]:
        """The form a decode step's expert layers take and why
        (ops/moe.form_in_use at ``[max_num_seqs, 1]`` tokens); None for a model
        without expert layers. Not a choice: ``moe_ffn`` makes it, from the
        same arguments, every time it is traced."""
        from dynamo_tpu.ops.moe import form_in_use

        c = self.config
        if not self.hybrid:
            return "xla, the stacked layer loop takes no kernel" if c.is_moe else None
        forms = {
            form_in_use(self.use_kernel, (self.args.max_num_seqs, 1), lp, spec)
            for spec, lp in zip(c.layer_specs, self.params["layers"])
            if spec.kind == "experts"
        }
        return "; ".join(sorted(forms)) or None

    SSD_STEP_LIVE = "pallas live rows"

    def _describe_ssd_step(self) -> Optional[str]:
        """The form a decode step's recurrence takes and why
        (hybrid.decode_recurrence_reason over the slots' states: a kind's
        own ``*_step_reason``); None for a model without recurrent layers.
        Not a choice: the mixers make it, from the same arguments, every
        time they are traced."""
        from dynamo_tpu.models.hybrid import decode_recurrence_reason

        if not self.ssm_state or not self.ssm_state["S"]:
            return None
        whys = {decode_recurrence_reason(spec, self.use_kernel, S)
                for spec, S in zip(self.config.recurrent_specs, self.ssm_state["S"])}
        return "; ".join(sorted(
            self.SSD_STEP_LIVE if why is None else f"xla every slot, {why}"
            for why in whys))

    def prefill_expert_form(self, rows: int, chunk: int) -> Optional[str]:
        """ops/moe.form_of for a prefill step of ``[rows, chunk]`` static
        tokens (rows bucket, chunk bucket): what ``moe_ffn`` branches on
        when that program is traced, at the first expert layer; None for a
        model without expert layers."""
        from dynamo_tpu.ops.moe import form_of

        c = self.config
        step = (rows, chunk)
        if step not in self._prefill_expert_forms:
            layers = self.params["layers"]
            if self.hybrid:
                given = next(
                    ((self.use_kernel, lp, spec) for spec, lp in zip(c.layer_specs, layers)
                     if spec.kind == "experts"), None)
            elif c.is_moe:  # the stacked layer loop takes no kernel
                lp = layers if isinstance(layers, dict) else layers[0]
                given = (False, lp, c.experts_spec())
            else:
                given = None
            self._prefill_expert_forms[step] = (
                given and form_of(given[0], step, *given[1:])[0])
        return self._prefill_expert_forms[step]

    # -- path selection ----------------------------------------------------

    @staticmethod
    def _choose_attention(args, backend: str, mesh) -> Tuple[bool, str]:
        """(use the Pallas paged-attention kernels?, why). Decided once at
        start from what the process can observe; a kernel chosen here that
        Mosaic then refuses is a bug and stops the worker — nothing demotes
        to the XLA gather path at run time."""
        if args.use_kernel:
            if mesh is not None:
                raise ValueError(
                    "use_kernel=True under a device mesh: the Pallas "
                    "paged-attention call is not wrapped in shard_map and "
                    "XLA cannot partition a Mosaic call"
                )
            return True, "use_kernel=True set by the caller"
        if args.use_kernel is not None:
            return False, "use_kernel=False set by the caller"
        if backend != "tpu":
            return False, f"platform is {backend} (Mosaic lowers on TPU only)"
        if mesh is not None:
            return False, (
                "device mesh present: the Pallas call is not wrapped in "
                "shard_map over the tp axis, so XLA attention serves "
                "sharded layouts"
            )
        # Every preset's attention shape lowers on the installed Mosaic
        # (chip_smoke.py's kernel table, CHANGES.md PR 21). A shape a later
        # table shows refused gets its reason returned here, so its worker
        # serves from XLA visibly instead of dying at the first request.
        return True, "platform is tpu, single device"

    # -- SPMD --------------------------------------------------------------

    def set_broadcaster(self, broadcaster) -> None:
        """Leader only: mirror every device op to the followers."""
        self._spmd_tx = broadcaster

    def _mirror(self, op: str, **kwargs: Any) -> None:
        if self._spmd_tx is not None:
            self._spmd_tx.send(op, **kwargs)

    def _dev_persistent(self, x):
        """Place a PERSISTENT array on device (LoRA stacks, anything that
        lives across dispatches). Unlike _dev, never returns host numpy —
        a persistent host array passed into every jit call would re-pay
        its full H2D transfer per dispatch."""
        if x is None:
            return None
        if self._repl is not None:
            return jax.device_put(np.ascontiguousarray(x), self._repl)
        return jnp.asarray(np.ascontiguousarray(x))

    def _dev(self, x):
        """Host → device conversion for replicated jit inputs. Multihost:
        every process supplies the identical full array, device_put builds
        the replicated global array. Single-process: hand numpy straight to
        jit — it folds the transfer into the dispatch instead of paying a
        separate device_put per argument."""
        if x is None:
            return None
        if self._repl is not None:
            return jax.device_put(np.asarray(x), self._repl)
        return x

    def _constrain_out(self, *arrays):
        """Force small sampled outputs fully-replicated under multihost so
        every process (and the leader's numpy readback) can see them."""
        if not self.multihost:
            return arrays if len(arrays) > 1 else arrays[0]
        out = tuple(
            jax.lax.with_sharding_constraint(a, self._repl) for a in arrays
        )
        return out if len(out) > 1 else out[0]

    # -- allocation --------------------------------------------------------

    def alloc_kv_cache(self):
        k_cache, v_cache = llama.init_kv_cache(
            self.config, self.args.num_kv_blocks, self.args.block_size,
            layered=self.args.layered_cache,
            kv_dtype=getattr(self.args, "kv_cache_dtype", None),
            window_blocks=getattr(self.args, "num_window_blocks", 0),
        )
        if self.mesh is not None:
            if self.args.layered_cache:
                cache_sharding = self.rules.sharding(
                    self.mesh, *llama.kv_cache_layered_axes()
                )
                # int8 pools are {"q8": [NB, BS, KH, D], "s": [NB, KH, BS]}
                # dicts — the scale's kv_heads axis shards with the values.
                s_sharding = self.rules.sharding(
                    self.mesh, "kv_blocks", "kv_heads", None
                )

                def place(pool):
                    if isinstance(pool, dict):
                        return {
                            "q8": jax.device_put(pool["q8"], cache_sharding),
                            "s": jax.device_put(pool["s"], s_sharding),
                        }
                    return jax.device_put(pool, cache_sharding)

                k_cache = tuple(place(k) for k in k_cache)
                v_cache = tuple(place(v) for v in v_cache)
            else:
                cache_sharding = self.rules.sharding(
                    self.mesh, *llama.kv_cache_logical_axes()
                )
                k_cache = jax.device_put(k_cache, cache_sharding)
                v_cache = jax.device_put(v_cache, cache_sharding)
        return k_cache, v_cache

    # -- LoRA --------------------------------------------------------------

    def _load_loras(self, lora_dir: str) -> None:
        """Load every adapter under ``lora_dir`` and stack them layer-major
        for the layer-loop forward (lora/loader.py)."""
        from dynamo_tpu.lora import LocalLoRASource, load_lora_adapter

        source = LocalLoRASource(lora_dir)
        names = source.list_adapters()
        if not names:
            logger.warning("lora_dir %s contains no adapters", lora_dir)
            return
        self._adapter_list = [
            _adapter_to_host(
                load_lora_adapter(source.fetch(n, lora_dir), self.config, name=n)
            )
            for n in names
        ]
        self._restack_loras()

    def _restack_loras(self) -> None:
        """Rebuild the stacked LoRA arrays from ``_adapter_list`` (None
        entries are freed slots that keep later indices stable — in-flight
        sequences hold adapter ids by position)."""
        from dynamo_tpu.lora.loader import LoRAAdapter, stack_adapters

        real = [a for a in self._adapter_list if a is not None]
        if not real:
            self.lora = None
            self.lora_index = {}
            return
        padded = [
            a if a is not None
            else LoRAAdapter(name=f"__free_{i}", rank=1, scaling=0.0)
            for i, a in enumerate(self._adapter_list)
        ]
        targets = sorted({t for a in real for t in a.targets})
        stacked = stack_adapters(padded, self.config, targets)
        # [N+1, L, ...] → layer-major [L, N+1, ...] for the layer loop.
        self.lora = {
            t: (
                self._dev_persistent(A.swapaxes(0, 1)),
                self._dev_persistent(B.swapaxes(0, 1)),
            )
            for t, (A, B) in stacked.items()
        }
        self.lora_index = {
            a.name: i
            for i, a in enumerate(self._adapter_list, start=1)
            if a is not None
        }
        logger.info(
            "LoRA stack: %d slot(s), adapters %s (targets: %s)",
            len(self._adapter_list), sorted(self.lora_index), targets,
        )

    def install_adapter(self, adapter) -> None:
        """Add one host-resident adapter into a free slot and restack.
        Mirrored by value (not path) so followers need no shared FS."""
        self._mirror(
            "lora_install",
            name=adapter.name, rank=adapter.rank, scaling=adapter.scaling,
            weights={t: [A, B] for t, (A, B) in adapter.weights.items()},
        )
        for i, slot in enumerate(self._adapter_list):
            if slot is None:
                self._adapter_list[i] = adapter
                break
        else:
            self._adapter_list.append(adapter)
        self._restack_loras()

    def remove_adapter(self, name: str) -> int:
        """Free an adapter slot by name; returns its (stable) index."""
        self._mirror("lora_remove", name=name)
        idx = self.lora_index[name]
        self._adapter_list[idx - 1] = None
        self._restack_loras()
        return idx

    # -- jitted programs ---------------------------------------------------

    def _build_step_fn(self, want_procs: bool = False, want_top: bool = False,
                       first_chunk: bool = False):
        cfg = self.config
        use_kernel = self.use_kernel
        num_top = self.args.top_logprobs_cap if want_top else 0
        if cfg.is_hybrid:  # (not self.hybrid: the compile tests build on a stand-in)
            return self._build_step_fn_hybrid(want_procs, num_top, first_chunk)

        # The function's name is the program's name in a device trace
        # (``jit_prefill_step``): benchmark/trace_names/ tells the steps
        # apart by it.
        def prefill_step(params, lora, k_cache, v_cache, tokens, start_pos,
                         chunk_lens, block_tables, salts, rng, temp, topk,
                         topp, adapter_ids, mm_embeds, mm_slot,
                         minp=None, rep=None, pres=None, freq=None,
                         bias_ids=None, bias_vals=None, pmask=None):
            logits, k_cache, v_cache = llama.forward_paged(
                params, cfg, tokens, start_pos, chunk_lens, block_tables,
                k_cache, v_cache, use_kernel=use_kernel,
                lora=lora, adapter_ids=adapter_ids,
                mm_embeds=mm_embeds, mm_slot=mm_slot,
                first_chunk=first_chunk,
            )
            # Sampling key per row = (base key, sequence salt, index of the
            # sampled token) — start_pos + chunk_lens is exactly the index
            # the sampled token will occupy, matching decode_multi's
            # per-step fold so a preempted sequence's recompute redraws
            # identical noise for the same position. A padding row of the rows
            # bucket (chunk_lens 0) is no reason to sample: a step whose live
            # rows are all greedy takes the arg-max (ops/sampling.py).
            with jax.named_scope("sample"):
                if want_procs:
                    from dynamo_tpu.ops import logits_process as lp

                    # At the first sampled token only the prompt has been
                    # seen.
                    pp = lp.ProcParams(rep=rep, pres=pres, freq=freq,
                                       bias_ids=bias_ids, bias_vals=bias_vals)
                    logits = lp.apply_prompt_only(logits, pmask, pp)
                toks = sample_tokens(  # (minp is None without the processors)
                    logits, rng, temp, topk, topp, minp, salts=salts,
                    positions=start_pos + chunk_lens, live=chunk_lens > 0)
                logp = compute_logprobs(logits, toks)
            if num_top > 0:
                from dynamo_tpu.ops.sampling import top_logprobs as top_op

                tv, ti = top_op(logits, num_top)
                toks, logp, tv, ti = self._constrain_out(toks, logp, tv, ti)
                return toks, logp, tv, ti, k_cache, v_cache
            toks, logp = self._constrain_out(toks, logp)
            return toks, logp, k_cache, v_cache

        return watched_jit(
            "runner.prefill_step", jax.jit(prefill_step, donate_argnums=(2, 3))
        )

    def _build_step_fn_hybrid(self, want_procs: bool, num_top: int,
                              first_chunk: bool):
        """The prefill program of a hybrid model: the same step with the
        rows' recurrent state in and out, and the snapshot store written at
        the block ends the engine named (``snap_dst``). Same name in a
        device trace (``jit_prefill_step``)."""
        cfg = self.config
        use_kernel = self.use_kernel

        def prefill_step(params, k_cache, v_cache, snap_store, ssm, tokens,
                         start_pos, chunk_lens, block_tables, snap_dst, salts,
                         rng, temp, topk, topp, minp=None, rep=None, pres=None,
                         freq=None, bias_ids=None, bias_vals=None, pmask=None):
            logits, k_cache, v_cache, ssm, snap_store, _ = llama.forward_paged(
                params, cfg, tokens, start_pos, chunk_lens, block_tables,
                k_cache, v_cache, use_kernel=use_kernel,
                first_chunk=first_chunk, ssm=ssm,
                snap={"store": snap_store, "dst": snap_dst},
            )
            with jax.named_scope("sample"):
                if want_procs:
                    from dynamo_tpu.ops import logits_process as lp

                    pp = lp.ProcParams(rep=rep, pres=pres, freq=freq,
                                       bias_ids=bias_ids, bias_vals=bias_vals)
                    logits = lp.apply_prompt_only(logits, pmask, pp)
                toks = sample_tokens(
                    logits, rng, temp, topk, topp, minp, salts=salts,
                    positions=start_pos + chunk_lens, live=chunk_lens > 0)
                logp = compute_logprobs(logits, toks)
            small = (toks, logp)
            if num_top > 0:
                from dynamo_tpu.ops.sampling import top_logprobs as top_op

                small = small + top_op(logits, num_top)
            return small + (k_cache, v_cache, snap_store, ssm)

        return watched_jit(
            "runner.prefill_step",
            jax.jit(prefill_step, donate_argnums=(1, 2, 3, 4)),
        )

    def _build_decode_fn_hybrid(self, want_logprobs: bool, want_procs: bool):
        """The decode burst of a hybrid model: the slots' recurrent state is
        a donated carry beside the pools, and the burst's expert-load sums
        come back with its tokens. Same name in a device trace
        (``jit_decode_burst``). Output: (toks, logps[, top_vals, top_ids],
        k, v[, proc_counts], carry_tokens, carry_pos, ssm, moe)."""
        from dynamo_tpu.ops import logits_process as lp

        cfg = self.config
        use_kernel = self.use_kernel
        num_steps = self.args.decode_steps
        num_top = self.args.top_logprobs_cap if want_logprobs else 0

        def decode_burst(params, k_cache, v_cache, ssm, tokens, pos, active,
                         block_tables, salts, rng, temp, topk, topp, minp=None,
                         rep=None, pres=None, freq=None, bias_ids=None,
                         bias_vals=None, counts=None, pmask=None):
            procs = {}
            if want_procs:
                procs = dict(
                    min_p=minp,
                    proc_params=lp.ProcParams(
                        rep=rep, pres=pres, freq=freq, bias_ids=bias_ids,
                        bias_vals=bias_vals),
                    proc_state=lp.ProcState(out_counts=counts, prompt_mask=pmask),
                )
            out = llama.decode_multi(
                params, cfg, tokens, pos, active, block_tables, k_cache,
                v_cache, rng, temp, topk, topp, num_steps=num_steps,
                use_kernel=use_kernel, want_logprobs=want_logprobs,
                num_top_logprobs=num_top, salts=salts, want_carry=True,
                ssm=ssm, **procs,
            )
            if want_procs:  # (*small, k, v, proc_state, tok, pos, ssm, moe)
                return out[:-5] + (out[-5].out_counts,) + out[-4:]
            return out

        donate = (1, 2, 3, 4, 5) + ((19,) if want_procs else ())
        return watched_jit(
            "runner.decode_state",
            jax.jit(decode_burst, donate_argnums=donate),
            budget=self._decode_sig_budget,
        )

    def _build_decode_fn(self, want_logprobs: bool = False,
                         want_procs: bool = False):
        """Fused-decode program over the DEVICE-RESIDENT slot state.

        Inputs beyond params/caches are the slot-state arrays (tokens, pos,
        active, table slice, salts, sampling/processor params) — all device
        arrays, so a steady-state dispatch moves zero host bytes. tokens
        and pos are donated and come back as the carry (last sampled token
        + advanced position per slot), which the runner installs as the
        next burst's inputs without any host round trip.

        Output layout: (toks [S,K], logps [S,K][, top_vals, top_ids],
        k_cache, v_cache[, proc_counts], carry_tokens [S], carry_pos [S]).
        """
        if self.config.is_hybrid:
            return self._build_decode_fn_hybrid(want_logprobs, want_procs)
        cfg = self.config
        use_kernel = self.use_kernel
        num_steps = self.args.decode_steps

        # The logprobs program variants also surface the per-step top-N
        # alternatives (OpenAI top_logprobs); the common variants skip it.
        num_top = self.args.top_logprobs_cap if want_logprobs else 0

        if not want_procs:
            def decode_burst(params, lora, k_cache, v_cache, tokens, pos,
                             active, block_tables, salts, rng, temp, topk,
                             topp, adapter_ids):
                out = llama.decode_multi(
                    params, cfg, tokens, pos, active, block_tables,
                    k_cache, v_cache, rng, temp, topk, topp,
                    num_steps=num_steps, use_kernel=use_kernel,
                    lora=lora, adapter_ids=adapter_ids,
                    want_logprobs=want_logprobs,
                    num_top_logprobs=num_top,
                    salts=salts, want_carry=True,
                )
                # out = (*small, k, v, carry_tok, carry_pos)
                small = self._constrain_out(*out[:-4])
                if not isinstance(small, tuple):
                    small = (small,)
                carry = self._constrain_out(*out[-2:])
                return small + out[-4:-2] + carry

            return watched_jit(
                "runner.decode_state",
                jax.jit(decode_burst, donate_argnums=(2, 3, 4, 5)),
                budget=self._decode_sig_budget,
            )

        from dynamo_tpu.ops import logits_process as lp

        def decode_burst_procs(params, lora, k_cache, v_cache, tokens, pos,
                               active, block_tables, salts, rng, temp, topk,
                               topp, adapter_ids, minp, rep, pres, freq,
                               bias_ids, bias_vals, counts, pmask):
            pp = lp.ProcParams(rep=rep, pres=pres, freq=freq,
                               bias_ids=bias_ids, bias_vals=bias_vals)
            st = lp.ProcState(out_counts=counts, prompt_mask=pmask)
            out = llama.decode_multi(
                params, cfg, tokens, pos, active, block_tables,
                k_cache, v_cache, rng, temp, topk, topp,
                num_steps=num_steps, use_kernel=use_kernel,
                lora=lora, adapter_ids=adapter_ids,
                want_logprobs=want_logprobs,
                min_p=minp, proc_params=pp, proc_state=st,
                num_top_logprobs=num_top,
                salts=salts, want_carry=True,
            )
            # out = (*small, k, v, proc_state, carry_tok, carry_pos)
            st = out[-3]
            small = self._constrain_out(*out[:-5])
            if not isinstance(small, tuple):
                small = (small,)
            carry = self._constrain_out(*out[-2:])
            return small + (out[-5], out[-4], st.out_counts) + carry

        # donate caches + tokens/pos carry + the token-count array.
        return watched_jit(
            "runner.decode_state",
            jax.jit(decode_burst_procs, donate_argnums=(2, 3, 4, 5, 20)),
            budget=self._decode_sig_budget,
        )

    def _build_spec_fn(self):
        cfg = self.config
        use_kernel = self.use_kernel

        def spec_verify_step(params, lora, k_cache, v_cache, tokens, start_pos,
                             chunk_lens, block_tables, adapter_ids, rng,
                             rng_step, temp, topk, topp):
            from dynamo_tpu.ops.sampling import spec_verify_sample

            rng = jax.random.fold_in(rng, rng_step)
            logits, k_cache, v_cache = llama.forward_paged(
                params, cfg, tokens, start_pos, chunk_lens, block_tables,
                k_cache, v_cache, use_kernel=use_kernel,
                lora=lora, adapter_ids=adapter_ids, all_logits=True,
            )
            # Rejection-sampling verify: exact target-distribution sampling
            # for temperature>0 rows, greedy verify for temperature<=0 rows
            # — ONE program serves mixed ticks (r4's greedy-only gate made
            # spec ~never engage on production traffic).
            emitted, counts = spec_verify_sample(
                logits, tokens[:, 1:], jnp.maximum(chunk_lens - 1, 0),
                rng, temp, topk, topp,
            )
            emitted, counts = self._constrain_out(emitted, counts)
            return emitted, counts, k_cache, v_cache

        return watched_jit(
            "runner.spec_verify",
            jax.jit(spec_verify_step, donate_argnums=(2, 3)),
            budget=self._decode_sig_budget,
        )

    # -- logits-processor device state ------------------------------------

    def ensure_proc_state(self):
        if self.proc_state is None:
            from dynamo_tpu.ops import logits_process as lp

            self.proc_state = lp.init_state(
                self.args.max_num_seqs, self.config.vocab_size
            )
        return self.proc_state

    def proc_reset_slot(self, slot: int, prompt_ids, generated) -> None:
        """(Re)initialize one slot's processor bookkeeping; mirrored so
        follower proc_state stays bit-identical."""
        from dynamo_tpu.ops import logits_process as lp

        self._mirror(
            "proc_reset", slot=slot,
            prompt_ids=np.asarray(prompt_ids, dtype=np.int32),
            generated=np.asarray(generated, dtype=np.int32),
        )
        st = self.ensure_proc_state()
        self.proc_state = lp.reset_slot(st, slot, list(prompt_ids), list(generated))

    def proc_count(self, slot: int, token: int) -> None:
        from dynamo_tpu.ops import logits_process as lp

        self._mirror("proc_count", slot=slot, token=int(token))
        st = self.ensure_proc_state()
        self.proc_state = lp.count_token(st, slot, int(token))

    # -- device invocations ------------------------------------------------

    @staticmethod
    def _get_all(*arrays):
        """Readback that pipelines the host transfers: start every copy
        async, then materialize, so N readbacks overlap instead of
        running back to back."""
        for a in arrays:
            if a is not None and hasattr(a, "copy_to_host_async"):
                try:
                    a.copy_to_host_async()
                # dynlint: disable=DYN003 -- best-effort prefetch: device_get below is the real (reported) readback, and a per-array log here would spam every reap on backends without async copies
                except Exception:
                    pass
        return tuple(
            None if a is None else np.asarray(jax.device_get(a))
            for a in arrays
        )

    def run_step(
        self, tokens, start_pos, chunk_lens, block_tables, temp, topk, topp,
        adapter_ids, mm_embeds=None, mm_slot=None, procs=None, want_top=False,
        first_chunk=False, salts=None, ssm=None, snap_dst=None,
    ):
        """One prefill/verify forward + sample. Returns (tokens, logprobs,
        top_vals | None, top_ids | None) as numpy.

        Hybrid models: ``ssm`` is the rows' recurrent state before the chunk
        (``ssm_begin``, or the previous round's), ``snap_dst`` [rows, chunk //
        scan block] the snapshot-store index each block end is written to
        (out of range = not kept); a fifth value is returned, the state
        after the chunk, a device tree for the next round or ``ssm_install``.

        ``procs``: optional (minp, rep, pres, freq, bias_ids, bias_vals,
        prompt_mask) per-row arrays — routes through the logits-processor
        program. ``want_top``: also return the top-N alternatives.
        ``first_chunk``: every row is a fresh prefill (start_pos == 0) —
        selects the dense in-chunk attention program (no paged reads).
        ``salts``: per-row sequence salts for the position-keyed sampling
        RNG. Defaults to arange(rows) so rows keep independent noise for
        direct callers (the engine always passes real sequence salts)."""
        if salts is None:
            salts = np.arange(len(np.asarray(tokens)), dtype=np.int32)
        self._mirror(
            "step", tokens=tokens, start_pos=start_pos, chunk_lens=chunk_lens,
            block_tables=block_tables, temp=temp, topk=topk, topp=topp,
            adapter_ids=adapter_ids, mm_embeds=mm_embeds, mm_slot=mm_slot,
            procs=None if procs is None else list(procs), want_top=want_top,
            first_chunk=first_chunk, salts=salts,
        )
        key = (procs is not None, bool(want_top), bool(first_chunk))
        fn = self._step_fns.get(key)
        if fn is None:
            fn = self._build_step_fn(
                want_procs=key[0], want_top=key[1], first_chunk=key[2]
            )
            self._step_fns[key] = fn
        d = self._dev
        if self.hybrid:
            if mm_embeds is not None:
                raise ValueError("multimodal rows are not implemented for hybrid models")
            args = [
                self.params, self.k_cache, self.v_cache, self.snap_store, ssm,
                tokens, start_pos, chunk_lens, block_tables,
                np.asarray(snap_dst, dtype=np.int32),
                np.asarray(salts, dtype=np.int32), self.rng, temp, topk, topp,
            ]
            if procs is not None:
                args += list(procs)
            out = fn(*args)
            self.k_cache, self.v_cache, self.snap_store, ssm_out = out[-4:]
            toks, logp = out[0], out[1]
            topv, topi = (out[2], out[3]) if want_top else (None, None)
            return self._get_all(toks, logp, topv, topi) + (ssm_out,)
        args = [
            self.params, self.lora, self.k_cache, self.v_cache,
            d(tokens), d(start_pos), d(chunk_lens), d(block_tables),
            d(np.asarray(salts, dtype=np.int32)), self.rng,
            d(temp), d(topk), d(topp), d(adapter_ids),
            d(mm_embeds), d(mm_slot),
        ]
        if procs is not None:
            minp, rep, pres, freq, bias_ids, bias_vals, pmask = procs
            args += [
                d(minp), d(rep), d(pres), d(freq),
                d(bias_ids), d(bias_vals), d(pmask),
            ]
        out = fn(*args)
        topv = topi = None
        if want_top:
            toks, logp, topv, topi, self.k_cache, self.v_cache = out
        else:
            toks, logp, self.k_cache, self.v_cache = out
        return self._get_all(toks, logp, topv, topi)

    # -- recurrent state (hybrid models) -----------------------------------

    def ssm_begin(self, src) -> Any:
        """The starting state of a prefill batch: row i resumes from entry
        ``src[i]`` of the snapshot store, or from zeros where it is -1."""
        return _ssm_gather(self.snap_store, np.asarray(src, dtype=np.int32))

    def ssm_install(self, slots, state, rows) -> None:
        """Rows ``rows`` of a finished prefill's state into decode slots
        ``slots``. Counts are padded to a power of two by repeating the
        first pair (idempotent), one program per bucket."""
        slots, rows = [int(s) for s in slots], [int(r) for r in rows]
        if not slots:
            return
        pad = _next_pow2(len(slots)) - len(slots)
        self.ssm_state = _ssm_install(
            self.ssm_state, state,
            np.asarray(rows + rows[:1] * pad, np.int32),
            np.asarray(slots + slots[:1] * pad, np.int32),
        )
        self._log_transfer("ssm_install", len(slots))

    # -- device-resident decode slot state ---------------------------------

    def _log_transfer(self, kind: str, n: int) -> None:
        if len(self.transfer_log) >= self._transfer_log_cap:
            del self.transfer_log[: self._transfer_log_cap // 2]
        self.transfer_log.append((kind, n))
        # Same events, typed + timestamped, in the device-thread flight
        # ring (transfer_log stays as the tests' raw H2D count assertion).
        self.flight.record(kind, n=n)

    def sync_slots(self, slots, rows: Dict[str, Any]) -> None:
        """Scatter dirty slot rows into the device-resident decode state —
        the ONLY H2D path for pos/active/sampling/processor params after
        engine start. ``rows[k][i]`` lands at ``slot_state[k][slots[i]]``.
        Row counts are pow2-padded (repeating row 0 — idempotent) so the
        scatter compiles per bucket, not per count."""
        slots = [int(s) for s in slots]
        if not slots:
            return
        rows = {k: np.asarray(v) for k, v in rows.items()}
        if set(rows) != set(self.slot_state):
            raise ValueError(
                f"slot sync rows {sorted(rows)} != state fields "
                f"{sorted(self.slot_state)}"
            )
        self._mirror("slot_sync", slots=np.asarray(slots, np.int32),
                     rows=rows)
        R = _next_pow2(len(slots))
        idx = np.asarray(slots + [slots[0]] * (R - len(slots)), np.int32)
        padded = {
            k: np.concatenate([v, np.repeat(v[:1], R - len(slots), axis=0)])
            if R > len(slots) else v
            for k, v in rows.items()
        }
        d = self._dev
        self.slot_state = _scatter_state_rows(
            self.slot_state, d(idx), {k: d(v) for k, v in padded.items()}
        )
        self._log_transfer("slot_sync", len(slots))

    def sync_tables(self, slots, rows) -> None:
        """Scatter dirty block-table rows (full table width) into the
        device-resident table. Called only when a slot's table actually
        changed (admission, block append, preempt) — steady-state decode
        ticks never re-upload tables."""
        slots = [int(s) for s in slots]
        if not slots:
            return
        rows = np.asarray(rows, np.int32)
        self._mirror("table_sync", slots=np.asarray(slots, np.int32),
                     rows=rows)
        R = _next_pow2(len(slots))
        idx = np.asarray(slots + [slots[0]] * (R - len(slots)), np.int32)
        if R > len(slots):
            rows = np.concatenate(
                [rows, np.repeat(rows[:1], R - len(slots), axis=0)]
            )
        d = self._dev
        self.slot_tables = _scatter_table_rows(
            self.slot_tables, d(idx), d(rows)
        )
        self._log_transfer("table_sync", len(slots))

    def decode_dispatch(self, nb: int, want_logprobs: bool = False,
                        use_procs: bool = False) -> "_DecodeHandles":
        """ENQUEUE one fused decode burst over the device-resident slot
        state and return un-materialized result handles. No host arrays
        are read or written: the block table is sliced on device to the
        ``nb`` width bucket, tokens/pos come from the previous burst's
        donated carry, and the outputs start their D2H copies
        asynchronously. Pair with :meth:`decode_read` (leader) — followers
        dispatch and drop the handles.

        Each (width bucket, program variant) compiles at its first
        dispatch. A compile error from the path chosen at start
        propagates: on the one installation there is, a kernel the runner
        selected either lowers or is a bug."""
        nb = int(nb)
        want_logprobs, use_procs = bool(want_logprobs), bool(use_procs)
        self._mirror(
            "decode_state", nb=nb, want_logprobs=want_logprobs,
            use_procs=use_procs,
        )
        variant = (want_logprobs, use_procs)
        fn = self._decode_state_fns.get(variant)
        if fn is None:
            fn = self._build_decode_fn(
                want_logprobs=want_logprobs, want_procs=use_procs
            )
            self._decode_state_fns[variant] = fn
        st = self.slot_state
        tables_nb = self.slot_tables[..., :nb]
        topv = topi = None
        if self.hybrid:
            args = [
                self.params, self.k_cache, self.v_cache, self.ssm_state,
                st["tokens"], st["pos"], st["active"], tables_nb, st["salts"],
                self.rng, st["temp"], st["topk"], st["topp"],
            ]
            if use_procs:
                ps = self.ensure_proc_state()
                args += [st["minp"], st["rep"], st["pres"], st["freq"],
                         st["bias_ids"], st["bias_vals"], ps.out_counts,
                         ps.prompt_mask]
            out = fn(*args)
            carry_tok, carry_pos, self.ssm_state, moe = out[-4:]
            out = out[:-4]
            if use_procs:
                from dynamo_tpu.ops import logits_process as lp

                self.proc_state = lp.ProcState(
                    out_counts=out[-1], prompt_mask=ps.prompt_mask
                )
                out = out[:-1]
            self.k_cache, self.v_cache = out[-2:]
            toks, logp = out[0], out[1]
            if want_logprobs:
                topv, topi = out[2], out[3]
            self.slot_state = dict(
                self.slot_state, tokens=carry_tok, pos=carry_pos
            )
            self._log_transfer("decode", nb)
            return _DecodeHandles(
                toks=toks, logp=logp, topv=topv, topi=topi, moe=moe
            )
        base = (
            self.params, self.lora, self.k_cache, self.v_cache,
            st["tokens"], st["pos"], st["active"], tables_nb, st["salts"],
            self.rng, st["temp"], st["topk"], st["topp"], st["adapter_ids"],
        )
        if use_procs:
            ps = self.ensure_proc_state()
            out = fn(
                *base, st["minp"], st["rep"], st["pres"], st["freq"],
                st["bias_ids"], st["bias_vals"],
                ps.out_counts, ps.prompt_mask,
            )
            from dynamo_tpu.ops import logits_process as lp

            if want_logprobs:
                (toks, logp, topv, topi, self.k_cache, self.v_cache,
                 counts, carry_tok, carry_pos) = out
            else:
                (toks, logp, self.k_cache, self.v_cache, counts,
                 carry_tok, carry_pos) = out
            self.proc_state = lp.ProcState(
                out_counts=counts, prompt_mask=ps.prompt_mask
            )
        else:
            out = fn(*base)
            if want_logprobs:
                (toks, logp, topv, topi, self.k_cache, self.v_cache,
                 carry_tok, carry_pos) = out
            else:
                (toks, logp, self.k_cache, self.v_cache,
                 carry_tok, carry_pos) = out
        # Install the carry as the next burst's input — tokens/pos never
        # travel through the host on the decode hot loop.
        self.slot_state = dict(
            self.slot_state, tokens=carry_tok, pos=carry_pos
        )
        self._log_transfer("decode", nb)
        return _DecodeHandles(toks=toks, logp=logp, topv=topv, topi=topi)

    def decode_read(self, handles: "_DecodeHandles"):
        """Blocking readback half of decode_dispatch. Returns ([S, K]
        tokens, [S, K] logprobs, top_vals | None, top_ids | None) numpy."""
        out = self._get_all(
            handles.toks, handles.logp, handles.topv, handles.topi, handles.moe
        )
        handles.moe_host = out[4]
        return out[:4]

    def run_decode(
        self, tokens, start_pos, active, block_tables, temp, topk, topp,
        adapter_ids, want_logprobs=False, procs=None, salts=None,
    ):
        """Synchronous convenience form (tests, tools): seed the slot state
        from host arrays, dispatch one burst, read it back. The serving
        engine drives sync_slots/decode_dispatch/decode_read directly.
        ``procs``: optional (minp, rep, pres, freq, bias_ids, bias_vals)
        slot arrays → the processor program. Returns ([B, K] tokens,
        [B, K] logprobs, top_vals | None, top_ids | None) as numpy."""
        S = len(np.asarray(tokens))
        if procs is not None:
            minp, rep, pres, freq, bias_ids, bias_vals = procs
        else:
            from dynamo_tpu.ops.logits_process import MAX_BIAS_SLOTS

            minp = np.zeros(S, np.float32)
            rep = np.ones(S, np.float32)
            pres = np.zeros(S, np.float32)
            freq = np.zeros(S, np.float32)
            bias_ids = np.full((S, MAX_BIAS_SLOTS), -1, np.int32)
            bias_vals = np.zeros((S, MAX_BIAS_SLOTS), np.float32)
        self.sync_slots(
            list(range(S)),
            {
                "tokens": np.asarray(tokens, np.int32),
                "pos": np.asarray(start_pos, np.int32),
                "active": np.asarray(active, np.int32),
                "temp": np.asarray(temp, np.float32),
                "topk": np.asarray(topk, np.int32),
                "topp": np.asarray(topp, np.float32),
                "adapter_ids": np.asarray(adapter_ids, np.int32),
                # arange default keeps rows' noise independent for direct
                # callers (the engine supplies real sequence salts).
                "salts": (
                    np.arange(S, dtype=np.int32) if salts is None
                    else np.asarray(salts, np.int32)
                ),
                "minp": np.asarray(minp, np.float32),
                "rep": np.asarray(rep, np.float32),
                "pres": np.asarray(pres, np.float32),
                "freq": np.asarray(freq, np.float32),
                "bias_ids": np.asarray(bias_ids, np.int32),
                "bias_vals": np.asarray(bias_vals, np.float32),
            },
        )
        tables = np.asarray(block_tables, np.int32)
        nb = tables.shape[-1]
        full = np.zeros(self.slot_tables.shape, np.int32)
        full[..., : min(nb, full.shape[-1])] = tables[..., : full.shape[-1]]
        self.sync_tables(list(range(S)), full)
        handles = self.decode_dispatch(
            nb, want_logprobs=want_logprobs, use_procs=procs is not None
        )
        return self.decode_read(handles)

    def run_spec(self, tokens, start_pos, chunk_lens, block_tables,
                 adapter_ids, temp=None, topk=None, topp=None):
        """Speculative verify with rejection sampling: returns
        (emitted [S, C] tokens, counts [S]) — row i's first counts[i]
        entries are the accepted prefix + the corrected/bonus token."""
        S = tokens.shape[0]
        if temp is None:
            temp = np.zeros(S, dtype=np.float32)  # greedy
        if topk is None:
            topk = np.zeros(S, dtype=np.int32)
        if topp is None:
            topp = np.ones(S, dtype=np.float32)
        self._mirror(
            "spec", tokens=tokens, start_pos=start_pos, chunk_lens=chunk_lens,
            block_tables=block_tables, adapter_ids=adapter_ids,
            temp=temp, topk=topk, topp=topp,
        )
        if self._spec_fn is None:
            self._spec_fn = self._build_spec_fn()
        step_id = np.int32(self.rng_step & 0x7FFFFFFF)
        self.rng_step += 1
        d = self._dev
        emitted, counts, self.k_cache, self.v_cache = self._spec_fn(
            self.params, self.lora, self.k_cache, self.v_cache,
            d(tokens), d(start_pos), d(chunk_lens), d(block_tables),
            d(adapter_ids), self.rng, step_id, d(temp), d(topk), d(topp),
        )
        return (
            np.asarray(jax.device_get(emitted)),
            np.asarray(jax.device_get(counts)),
        )

    # -- block transfer (disagg / checkpoint) ------------------------------

    def pool_quantized(self) -> bool:
        """Is the KV pool stored quantized ({q8, s} per layer)?"""
        from dynamo_tpu.ops.kv_quant import is_quantized_pool

        kc = self.k_cache
        if isinstance(kc, (tuple, list)):
            kc = kc[0]
        return is_quantized_pool(kc)

    def kv_wire_dtype(self) -> str:
        """Pool-native wire dtype tag (disagg/wire.py schema): "int8" for
        quantized pools, the storage dtype name otherwise."""
        if self.pool_quantized():
            return "int8"
        return str(jnp.dtype(self.config.dtype).name)

    def gather_blocks_dispatch(self, ids: List[int]):
        """ENQUEUE the block gather and return the (not-yet-read) device
        arrays. Runs on the device-executor thread but only pays dispatch
        cost — the synchronous HBM→host readback happens in
        gather_blocks_readback on a transfer thread, so decode ticks keep
        flowing while a disagg/offload transfer drains (the overlap the
        reference gets from its async offload engine + stream-based copies,
        lib/llm/src/block_manager/offload.rs:1, block/transfer/cuda.rs:1).
        Device-side ordering is safe: the gather program is enqueued before
        any later decode step, so donated cache updates cannot outrun it."""
        self._mirror("gather", ids=np.asarray(ids, dtype=np.int32))
        idx = self._dev(np.asarray(ids, dtype=np.int32))
        hd = self.config.head_dim_
        k = _gather_blocks(self.k_cache, idx, head_dim=hd)
        v = _gather_blocks(self.v_cache, idx, head_dim=hd)
        if self.multihost:
            # Followers also compute the gather (they must join the
            # collective); only the leader reads it back, replicated.
            k, v = self._constrain_out(k, v)
        return k.swapaxes(0, 1), v.swapaxes(0, 1)

    @staticmethod
    def gather_blocks_readback(k, v) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking readback half of gather_blocks_dispatch — call from a
        transfer executor, never the device thread."""
        return (
            np.asarray(jax.device_get(k)), np.asarray(jax.device_get(v))
        )

    def gather_blocks(self, ids: List[int]) -> Tuple[np.ndarray, np.ndarray]:
        """Copy blocks out of HBM → ([n, L, BS, KH, D] k, v) numpy.
        Synchronous convenience form (SPMD followers, tests)."""
        return self.gather_blocks_readback(*self.gather_blocks_dispatch(ids))

    def scatter_blocks(self, ids: List[int], k_blocks, v_blocks) -> None:
        """Insert [n, L, BS, KH, D] host blocks into HBM at ``ids``."""
        self._mirror(
            "scatter", ids=np.asarray(ids, dtype=np.int32),
            k_blocks=np.asarray(k_blocks), v_blocks=np.asarray(v_blocks),
        )
        idx = self._dev(np.asarray(ids, dtype=np.int32))
        k_sel = self._dev(
            np.asarray(k_blocks).swapaxes(0, 1).astype(self.config.dtype)
        )
        v_sel = self._dev(
            np.asarray(v_blocks).swapaxes(0, 1).astype(self.config.dtype)
        )
        self.k_cache = _scatter_blocks(self.k_cache, idx, k_sel)
        self.v_cache = _scatter_blocks(self.v_cache, idx, v_sel)

    # -- pool-native wire transfer (disagg/wire.py schema v2) --------------

    def gather_blocks_wire_dispatch(self, ids: List[int]):
        """ENQUEUE a pool-native gather and return un-read device handles.
        Quantized pools ship {q8, scales} WITHOUT dequantizing — half the
        readback and half the wire; dense pools reuse the dense dispatch.
        Same two-phase contract as gather_blocks_dispatch (readback on the
        transfer thread keeps decode ticks flowing)."""
        if not self.pool_quantized():
            k, v = self.gather_blocks_dispatch(ids)  # mirrors "gather"
            return ("dense", self.kv_wire_dtype(), k, v)
        self._mirror("gather_wire", ids=np.asarray(ids, dtype=np.int32))
        idx = self._dev(np.asarray(ids, dtype=np.int32))
        kq, ks = _gather_blocks_q8(self.k_cache, idx)
        vq, vs = _gather_blocks_q8(self.v_cache, idx)
        if self.multihost:
            kq, ks, vq, vs = self._constrain_out(kq, ks, vq, vs)
        return (
            "q8", "int8",
            kq.swapaxes(0, 1), ks.swapaxes(0, 1),
            vq.swapaxes(0, 1), vs.swapaxes(0, 1),
        )

    @staticmethod
    def gather_blocks_wire_readback(handles):
        """Blocking readback half of gather_blocks_wire_dispatch — call
        from a transfer executor, never the device thread. Returns
        disagg/wire.py KvWireBlocks."""
        from dynamo_tpu.disagg.wire import KvWireBlocks

        if handles[0] == "dense":
            _, dtype, k, v = handles
            return KvWireBlocks(
                dtype=dtype,
                k=np.asarray(jax.device_get(k)),
                v=np.asarray(jax.device_get(v)),
            )
        _, dtype, kq, ks, vq, vs = handles
        return KvWireBlocks(
            dtype=dtype,
            k=np.asarray(jax.device_get(kq)),
            v=np.asarray(jax.device_get(vq)),
            k_scale=np.asarray(jax.device_get(ks)),
            v_scale=np.asarray(jax.device_get(vs)),
        )

    def gather_blocks_wire(self, ids: List[int]):
        """Synchronous convenience form (SPMD followers, tests)."""
        return self.gather_blocks_wire_readback(
            self.gather_blocks_wire_dispatch(ids)
        )

    def scatter_blocks_wire(self, ids: List[int], wire) -> None:
        """Install wire blocks (KvWireBlocks) into HBM at ``ids``. Dense
        payloads reuse scatter_blocks (which requantizes into int8 pools on
        device); quantized payloads ship int8 over H2D and install verbatim
        (int8 pool) or dequantize on device (dense pool)."""
        if not wire.quantized:
            self.scatter_blocks(ids, wire.k, wire.v)
            return
        self._mirror(
            "scatter_wire", ids=np.asarray(ids, dtype=np.int32),
            k_q8=np.asarray(wire.k), k_s=np.asarray(wire.k_scale),
            v_q8=np.asarray(wire.v), v_s=np.asarray(wire.v_scale),
        )
        idx = self._dev(np.asarray(ids, dtype=np.int32))
        kq = self._dev(np.asarray(wire.k).swapaxes(0, 1))
        ks = self._dev(np.asarray(wire.k_scale).swapaxes(0, 1))
        vq = self._dev(np.asarray(wire.v).swapaxes(0, 1))
        vs = self._dev(np.asarray(wire.v_scale).swapaxes(0, 1))
        self.k_cache = _scatter_blocks_q8(self.k_cache, idx, kq, ks)
        self.v_cache = _scatter_blocks_q8(self.v_cache, idx, vq, vs)

    # -- sleep / wake device transitions -----------------------------------

    def sleep_device(self, level: int) -> None:
        """Free device memory. Level 1: KV cache; level 2: weights → host.
        Level 2 is single-host only (a tp-sharded global param tree is not
        addressable from one process)."""
        if self.hybrid:
            raise RuntimeError(
                f"{self.config.name}: sleep frees and re-allocates the K/V "
                "pools only; a hybrid model's recurrent state and snapshots "
                "are not taught to it"
            )
        if level >= 2 and self.multihost:
            raise RuntimeError(
                "sleep level 2 (weight offload) is unsupported in multihost "
                "mode; use level 1"
            )
        self._mirror("sleep", level=level)
        self.k_cache = None
        self.v_cache = None
        if level >= 2:
            self.host_params = jax.device_get(self.params)
            self.params = None
        self.sleep_level = level
        logger.info("engine asleep at level %d", level)

    def wake_device(self) -> None:
        self._mirror("wake")
        if self.sleep_level >= 2 and self.host_params is not None:
            params = self.host_params
            self.host_params = None
            if self.mesh is not None:
                params = shard_params(
                    params, self._param_axes, self.rules, self.mesh
                )
            else:
                params = jax.tree_util.tree_map(jnp.asarray, params)
            self.params = params
        if self.k_cache is None:
            self.k_cache, self.v_cache = self.alloc_kv_cache()
        self.sleep_level = 0
        logger.info("engine awake")
