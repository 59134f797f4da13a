"""Pipeline-parallel (pp) stage execution for the paged decoder.

Reference parity: the reference treats PP as engine passthrough
(SURVEY §2.4 — vLLM/TRT-LLM run pipeline stages over NCCL). TPU-first
design: the layer stack (and each layer's KV pool) shards over the ``pp``
mesh axis; a GPipe-style schedule runs under ``shard_map`` with
``lax.ppermute`` moving activations stage→stage over ICI. The batch splits
into PP microbatches so stages overlap once the pipeline fills
(T = M + PP - 1 ticks, M = PP microbatches).

Bubble math: utilization = M / (M + PP - 1) = 50%+ at M = PP; serving fills
the pipe continuously so steady-state decode approaches 100%. Fill/drain
ticks compute on garbage activations whose cache writes are suppressed by
zeroed chunk_lens (write_chunk_to_cache drops everything) and whose
outputs are never collected.

Every architecture behavior comes from models/llama.py::decoder_layer —
the same body the single-stage scan uses — so tp×pp composition and all
family knobs (windows, softcaps, post-norms, int8 weights) hold here too.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig


def forward_paged_pp(
    params: Dict[str, Any],
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, C] int32
    start_pos: jnp.ndarray,  # [B]
    chunk_lens: jnp.ndarray,  # [B]
    block_tables: jnp.ndarray,  # [B, P]
    k_cache: jnp.ndarray,  # [L, NB, BS, KH, D] (sharded on L over pp)
    v_cache: jnp.ndarray,
    mesh: Mesh,
    *,
    axis: str = "pp",
    use_kernel: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipeline-parallel forward over the ``axis`` mesh dimension.

    Same contract as models/llama.py::forward_paged (last-position logits +
    updated caches); B must divide by the pp degree (the microbatch count).
    """
    from dynamo_tpu.models import llama

    c = config
    PP = mesh.shape[axis]
    B, C = tokens.shape
    if isinstance(params.get("layers"), (tuple, list)):
        raise ValueError(
            "forward_paged_pp requires STACKED layer params ([L, ...] per "
            "leaf, sliced over the pp axis); got the layered serving layout "
            "— construct the runner with layered_cache=False for pipeline "
            "parallelism"
        )
    assert c.n_layers % PP == 0, "n_layers must divide by pp degree"
    assert B % PP == 0, "batch must divide into pp microbatches"
    M = PP  # microbatch count = stages (the classic GPipe fill)
    mb = B // M
    T = M + PP - 1

    x = llama.embed_tokens(params, c, tokens)  # [B, C, d] (replicated)
    x_mb = x.reshape(M, mb, C, -1)
    sp_mb = start_pos.reshape(M, mb)
    cl_mb = chunk_lens.reshape(M, mb)
    bt_mb = block_tables.reshape(M, mb, -1)
    windows = jnp.asarray(c.layer_windows(), dtype=jnp.int32)

    layer_specs = jax.tree.map(lambda _: P(axis), params["layers"])

    def stage_fn(local_layers, local_windows, k_c, v_c, x_mb, sp_mb, cl_mb, bt_mb):
        r = jax.lax.axis_index(axis)

        def run_local_stack(x_in, sp, cl, bt, k_c, v_c):
            pos = sp[:, None] + jax.lax.broadcasted_iota(
                jnp.int32, (mb, C), 1
            )
            from dynamo_tpu.ops.rope import rope_table

            cos, sin = rope_table(
                pos, c.head_dim_, c.rope_theta,
                scale=c.rope_scaling_factor or 1.0,
            )
            cos_loc = sin_loc = None
            if c.rope_local_theta is not None:
                cos_loc, sin_loc = rope_table(
                    pos, c.head_dim_, c.rope_local_theta
                )

            def layer_fn(carry, xs):
                x = carry
                lp, k_l, v_l, win = xs
                x, k_l, v_l = llama.decoder_layer(
                    c, lp, {}, win, x, cos, sin, k_l, v_l, bt, sp, cl,
                    use_kernel=use_kernel, adapter_ids=None,
                    cos_loc=cos_loc, sin_loc=sin_loc,
                )
                return x, (k_l, v_l)

            x_out, (k_c, v_c) = jax.lax.scan(
                layer_fn, x_in, (local_layers, k_c, v_c, local_windows)
            )
            return x_out, k_c, v_c

        def tick(carry, t):
            act, k_c, v_c, out = carry
            m = t - r  # the microbatch this stage works on at tick t
            valid = (m >= 0) & (m < M)
            mc = jnp.clip(m, 0, M - 1)
            # Stage 0 ingests a fresh microbatch; later stages consume what
            # the previous stage permuted over last tick.
            x_in = jnp.where(r == 0, x_mb[mc], act)
            sp = sp_mb[mc]
            cl = jnp.where(valid, cl_mb[mc], 0)  # garbage ticks write nothing
            bt = bt_mb[mc]
            x_out, k_c, v_c = run_local_stack(x_in, sp, cl, bt, k_c, v_c)
            # Last stage owns the finished microbatch.
            out = jnp.where(
                valid & (r == PP - 1), out.at[mc].set(x_out), out
            )
            act = jax.lax.ppermute(
                x_out, axis, [(i, (i + 1) % PP) for i in range(PP)]
            )
            return (act, k_c, v_c, out), None

        init = (
            jnp.zeros((mb, C, x_mb.shape[-1]), x_mb.dtype),
            k_c,
            v_c,
            jnp.zeros_like(x_mb),
        )
        (_, k_c, v_c, out), _ = jax.lax.scan(
            tick, init, jnp.arange(T, dtype=jnp.int32)
        )
        # Replicate the collected activations (only the last stage holds
        # real values).
        out = jax.lax.psum(
            jnp.where(r == PP - 1, out, jnp.zeros_like(out)), axis
        )
        return out, k_c, v_c

    replicated = P()
    out, k_cache, v_cache = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(
            layer_specs,  # layer stack sharded over pp
            P(axis),  # per-layer windows
            P(axis),  # k_cache on layers
            P(axis),  # v_cache
            replicated, replicated, replicated, replicated,
        ),
        out_specs=(replicated, P(axis), P(axis)),
        check_vma=False,
    )(params["layers"], windows, k_cache, v_cache, x_mb, sp_mb, cl_mb, bt_mb)

    x = out.reshape(B, C, -1)
    last_idx = jnp.clip(chunk_lens - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    logits = llama.lm_head_logits(params, c, x_last)
    return logits, k_cache, v_cache
