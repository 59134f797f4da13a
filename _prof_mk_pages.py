"""Profile the fused megakernel's dynamic page streaming across table widths.

Sweeps block-table widths (pow2 buckets) at a fixed shape and reports, per
width: trace+compile wall time and steady-state per-layer step time. The
r5 static unroll made BOTH scale with width (and refused widths > 16); the
r6 dynamic page loop must hold trace/compile ~flat while step time tracks
the ACTUAL history length, not the table capacity — this script is the
measurement for docs/design_docs/megakernel_paged_streaming.md.

Run: python _prof_mk_pages.py [widths...]   (default: 16 64 256)
On CPU the kernel runs in interpret mode (timings are relative only); on
the real chip it exercises Mosaic lowering at every width.
"""
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quantize import quantize_params
from dynamo_tpu.ops.pallas.fused_layer import fused_decoder_layer, supports
from dynamo_tpu.ops.rope import rope_table

ON_TPU = jax.default_backend() == "tpu"
# On the chip, the 8B serving shape; on CPU a 1-layer miniature (interpret
# mode pays python-per-op, the sweep's SHAPE of the curve is what matters).
if ON_TPU:
    cfg = ModelConfig(
        name="prof-8b", d_model=4096, n_layers=1, n_heads=32, n_kv_heads=8,
        d_ff=14336, vocab_size=128256, head_dim=128, rope_theta=500000.0,
        dtype=jnp.bfloat16,
    )
    B, BS = 64, 16
else:
    cfg = ModelConfig(
        name="prof-mini", d_model=256, n_layers=1, n_heads=4, n_kv_heads=2,
        d_ff=512, vocab_size=128, head_dim=128, rope_theta=10000.0,
        dtype=jnp.bfloat16,
    )
    B, BS = 8, 16

assert supports(cfg, lora=False, quantized_weights=True)
widths = [int(w) for w in sys.argv[1:]] or [16, 64, 256]

params = llama.init_params(cfg, jax.random.PRNGKey(0))
qparams, _ = quantize_params(params, llama.param_logical_axes(cfg))
lp = jax.tree.map(lambda a: a[0], qparams["layers"])

rng = np.random.default_rng(0)
x = jnp.asarray(
    rng.standard_normal((B, cfg.d_model)).astype(np.float32) * 0.3
).astype(jnp.bfloat16)

# Sliding window for the windowed-row columns: a fixed 4-page window, so
# as the table grows the LIVE span per row stays constant — the r11
# windowed page loop (per-row start at floor((pos−W)/BS)) must hold
# windowed step time ~flat across widths while the full-attention column
# keeps tracking the table-filling history.
WINDOW_TOKENS = 4 * 16

rows = []
for P in widths:
    NB = B * P + 8
    KH, D = cfg.n_kv_heads, cfg.head_dim_
    k_pool = jnp.zeros((NB, BS, KH, D), jnp.bfloat16)
    v_pool = jnp.zeros((NB, BS, KH, D), jnp.bfloat16)
    tables = jnp.asarray(
        (np.arange(B * P, dtype=np.int32) % NB).reshape(B, P)
    )
    # history fills the table: step time at width P measures P real pages
    start_pos = jnp.full((B,), P * BS - 1, jnp.int32)
    cos, sin = rope_table(start_pos[:, None], D, cfg.rope_theta)

    def run(window=None):
        return fused_decoder_layer(
            x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables, start_pos,
            eps=cfg.rms_norm_eps, sm_scale=D**-0.5, batch_block=4,
            window=window,
        )

    t0 = time.perf_counter()
    out = run()
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0

    n = 20 if ON_TPU else 3
    t0 = time.perf_counter()
    for _ in range(n):
        out = run()
    jax.block_until_ready(out)
    step_ms = (time.perf_counter() - t0) / n * 1000

    # Windowed-row column: same full table, but every row's live span is
    # the fixed window — pages before floor((pos−W)/BS) are never
    # streamed, so this column should stay ~flat as P grows.
    win = jnp.asarray(WINDOW_TOKENS, jnp.int32)
    jax.block_until_ready(run(win))  # compile the windowed variant
    t0 = time.perf_counter()
    for _ in range(n):
        out = run(win)
    jax.block_until_ready(out)
    win_step_ms = (time.perf_counter() - t0) / n * 1000
    rows.append(
        {"table_pages": P, "ctx_tokens": P * BS,
         "trace_compile_s": round(compile_s, 3),
         "step_ms_per_layer": round(step_ms, 3),
         "window_tokens": WINDOW_TOKENS,
         "windowed_step_ms_per_layer": round(win_step_ms, 3),
         "windowed_vs_full": round(win_step_ms / max(step_ms, 1e-9), 3)}
    )
    print(json.dumps(rows[-1]), flush=True)

print(json.dumps({"backend": jax.default_backend(), "B": B, "sweep": rows}))
