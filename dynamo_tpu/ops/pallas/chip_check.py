"""Compile every Pallas entry point on the chip and check it against XLA.

``python -m dynamo_tpu.ops.pallas.chip_check`` is chip_smoke.py's kernel
stage (its own process: the smoke's parent never touches JAX). For each
attention shape the worker's builtin presets produce it lowers
``paged_attention_decode_kernel`` and ``paged_attention_kernel`` with
``interpret=False`` over bf16 and int8-KV pools, and ``fused_decoder_layer``
at the Qwen3-8B layer shape for every pow2 table width up to the worker's
default model length; each compiled call is compared with
``_paged_attention_xla`` / ``decoder_layer`` under
``jax.default_matmul_precision("highest")``.

A refusal is RECORDED here (kernel, shape, first line of the compiler's
message), never served around: the table goes to CHANGES.md, a refused
preset gets its reason into the runner's start-up choice
(``DeviceRunner._choose_attention`` / ``_choose_decode_path``) so its
worker is not routed to the kernel, and a refused or disagreeing kernel on
the smoke model's own path (``required`` rows) fails the stage.

Tolerance, one for every row: outputs are bf16 (8 significand bits, ulp
2^-8 relative), values are O(1), and the kernels accumulate in f32 in a
different order than the reference, so |kernel − reference| ≤ 4 bf16 ulps
of the largest magnitude in the output: ``atol = 4 · 2^-8 · max|ref|``.
The fused layer adds the int8-weight matmuls, whose f32 partial sums are
rounded to bf16 at each phase boundary in both implementations but tile by
tile in the kernel; it gets 4× that bound.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import qwen3_8b_config, tiny_config
from dynamo_tpu.utils.jax_env import (
    configure_compile_cache,
    require_serving_platform,
)

BLOCK_SIZE = 16
ULP_BF16 = 2.0**-8


def _first_line(exc: BaseException) -> str:
    text = f"{type(exc).__name__}: {exc}".strip()
    for line in text.splitlines():
        if line.strip():
            return line.strip()[:300]
    return type(exc).__name__


def _agrees(out, ref, ulps: float) -> Optional[str]:
    """None when ``out`` is finite, shaped like ``ref`` and within the
    bound; otherwise what is wrong."""
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if out.shape != ref.shape:
        return f"shape {out.shape} != reference {ref.shape}"
    if not np.isfinite(out).all():
        return "non-finite values"
    atol = ulps * ULP_BF16 * max(float(np.abs(ref).max()), 1.0)
    err = float(np.abs(out - ref).max())
    if err > atol:
        return f"max |kernel-reference| {err:.4g} > {atol:.4g}"
    return None


def _attention_shapes() -> List[Dict[str, Any]]:
    """Distinct attention shapes of the worker's builtin presets. A preset
    with a sliding window or a softcap contributes a row that exercises
    them."""
    from dynamo_tpu.worker.__main__ import BUILTIN_CONFIGS

    seen: Dict[tuple, Dict[str, Any]] = {}
    for name, make in BUILTIN_CONFIGS.items():
        if name == "tiny":
            continue  # float32 test shape, never a TPU worker
        c = make()
        windows = [w for w in c.layer_windows() if w]
        key = (
            c.n_heads, c.n_kv_heads, c.head_dim_,
            bool(windows), float(c.attn_logit_softcap or 0.0),
        )
        row = seen.setdefault(key, {
            "presets": [], "H": c.n_heads, "KH": c.n_kv_heads,
            "D": c.head_dim_,
            # A window shorter than the test history so it masks keys.
            "window": min(windows[0], 100) if windows else 0,
            "softcap": float(c.attn_logit_softcap or 0.0),
        })
        row["presets"].append(name)
    return list(seen.values())


def _attention_inputs(shape, C: int, quantized: bool, B: int, P: int):
    from dynamo_tpu.ops.kv_quant import quantize_kv_chunk

    H, KH, D = shape["H"], shape["KH"], shape["D"]
    rng = np.random.default_rng(H * 1000 + D + C)
    NB = B * P + 1
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.bfloat16)
    pools = []
    for _ in range(2):
        dense = jnp.asarray(
            rng.standard_normal((NB, BLOCK_SIZE, KH, D)), jnp.bfloat16
        )
        if quantized:
            q8, s = quantize_kv_chunk(dense)
            dense = {"q8": q8, "s": s.transpose(0, 2, 1)}
        pools.append(dense)
    tables = jnp.asarray(
        rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32)
    )
    start = jnp.asarray(
        rng.integers(0, P * BLOCK_SIZE - C, B).astype(np.int32)
    )
    lens = jnp.full((B,), C, jnp.int32)
    return q, pools[0], pools[1], tables, start, lens


def _timed(row: Dict[str, Any], run, reference, ulps: float) -> Dict[str, Any]:
    """Compile+run one kernel call, then its reference; fill the row."""
    t0 = time.monotonic()
    try:
        out = jax.block_until_ready(run())
    except Exception as exc:  # the table's purpose: record the refusal
        row.update(status="refused", message=_first_line(exc))
    else:
        with jax.default_matmul_precision("highest"):
            bad = _agrees(out, reference(), ulps)
        row.update(
            status="compiled" if bad is None else "disagrees",
            message=bad or "",
        )
    row["seconds"] = round(time.monotonic() - t0, 1)
    return row


def attention_jobs(interpret: bool, B: int, P: int):
    from dynamo_tpu.ops.attention import _paged_attention_xla
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel,
        paged_attention_kernel,
    )

    def job(shape, kernel_name, C, quantized):
        q, k, v, tables, start, lens = _attention_inputs(
            shape, C, quantized, B, P
        )
        row = {
            "kernel": kernel_name,
            "shape": (
                f"H{shape['H']} KH{shape['KH']} D{shape['D']} C{C} "
                f"B{B} P{P} {'int8' if quantized else 'bf16'}-KV"
                + (f" window{shape['window']}" if shape["window"] else "")
                + (f" softcap{shape['softcap']:g}" if shape["softcap"] else "")
            ),
            "presets": shape["presets"],
            # The smoke worker (qwen3-8b) serves a bf16 pool.
            "required": "qwen3-8b" in shape["presets"] and not quantized,
        }
        kw = dict(
            window=shape["window"], logit_cap=shape["softcap"],
            interpret=interpret,
        )

        def run():
            if C == 1:
                return paged_attention_decode_kernel(
                    q, k, v, tables, start, **kw
                )
            return paged_attention_kernel(q, k, v, tables, start, lens, **kw)

        def reference():
            return _paged_attention_xla(
                q, k, v, tables, start, lens, shape["window"],
                logit_cap=shape["softcap"],
            )

        return _timed(row, run, reference, ulps=4)

    return [
        functools.partial(job, shape, kernel_name, C, quantized)
        for shape in _attention_shapes()
        for kernel_name, C in (("paged_attention_decode", 1),
                               ("paged_attention", 16))
        for quantized in (False, True)
    ]


def fused_layer_jobs(interpret: bool, config, B: int, widths: List[int]):
    """The megakernel at one model's layer shape, one job per table width,
    against models/llama.decoder_layer on the same int8 weights. The
    logprobs / logits-processor program variants wrap the SAME layer kernel
    (they differ after the lm_head), so a width that compiles here compiles
    for every variant."""
    import dataclasses

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.quantize import init_quantized_params
    from dynamo_tpu.ops.pallas.fused_layer import fused_decoder_layer
    from dynamo_tpu.ops.rope import rope_table

    c = dataclasses.replace(config, n_layers=1, vocab_size=256)
    params = init_quantized_params(c, seed=0)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    D, KH = c.head_dim_, c.n_kv_heads

    def job(P):
        rng = np.random.default_rng(P)
        NB = B * P + 1
        k_pool = jnp.asarray(
            rng.standard_normal((NB, BLOCK_SIZE, KH, D)), jnp.bfloat16
        )
        v_pool = jnp.asarray(
            rng.standard_normal((NB, BLOCK_SIZE, KH, D)), jnp.bfloat16
        )
        tables = jnp.asarray(
            rng.permutation(NB)[: B * P].reshape(B, P).astype(np.int32)
        )
        # Leave the last slot free: the reference writes the new token.
        start = jnp.asarray(
            rng.integers(0, P * BLOCK_SIZE - 1, B).astype(np.int32)
        )
        x = jnp.asarray(rng.standard_normal((B, c.d_model)), jnp.bfloat16)
        cos, sin = rope_table(start[:, None], D, c.rope_theta)
        row = {
            "kernel": "fused_decoder_layer",
            "shape": (
                f"{config.name} layer d{c.d_model} H{c.n_heads} KH{KH} "
                f"D{D} F{c.d_ff} B{B} P{P} bf16-KV"
            ),
            "presets": [config.name],
            # The runner selects it for the smoke worker (int8 qwen3-8b).
            "required": True,
        }

        def run():
            return fused_decoder_layer(
                x, cos[:, 0], sin[:, 0], lp, k_pool, v_pool, tables, start,
                eps=c.rms_norm_eps, sm_scale=D**-0.5, interpret=interpret,
            )[0]

        def reference():
            return llama.decoder_layer(
                c, lp, {}, jnp.asarray(0, jnp.int32), x[:, None], cos, sin,
                k_pool, v_pool, tables, start, jnp.ones((B,), jnp.int32),
                use_kernel=False, adapter_ids=None,
            )[0][:, 0]

        return _timed(row, run, reference, ulps=16)

    return [functools.partial(job, P) for P in widths]


def main() -> int:
    ap = argparse.ArgumentParser("pallas kernels: compile on the chip, check vs XLA")
    ap.add_argument(
        "--interpret", action="store_true",
        help="CPU rehearsal of this script's control flow: Pallas "
        "interpreter, small batch and widths. Says nothing about Mosaic.",
    )
    ap.add_argument("--out", default=None, help="also write the table as JSON here")
    args = ap.parse_args()

    configure_compile_cache()
    platform = require_serving_platform()
    if (platform == "tpu") == args.interpret:
        print(
            f"platform is {platform}: run with --interpret on the CPU and "
            "without it on the chip", file=sys.stderr,
        )
        return 2
    if args.interpret:
        fused_cfg = tiny_config(
            d_model=256, head_dim=128, n_heads=4, n_kv_heads=2, d_ff=512,
            qk_norm=True, dtype=jnp.bfloat16, name="tiny-fused",
        )
        jobs = attention_jobs(True, B=4, P=4)
        jobs += fused_layer_jobs(True, fused_cfg, B=4, widths=[1, 4])
    else:
        from dynamo_tpu.worker.__main__ import build_parser

        worker = build_parser().parse_args([])
        top = worker.max_model_len // BLOCK_SIZE
        widths = [1 << i for i in range(top.bit_length())]
        jobs = attention_jobs(False, B=worker.max_num_seqs, P=16)
        jobs += fused_layer_jobs(
            False, qwen3_8b_config(), B=worker.max_num_seqs, widths=widths
        )
    # Mosaic and XLA compile on the host with the GIL released: rows
    # compile side by side instead of one after another (32 rows took
    # 673 s in sequence on the chip machine, my chip run, PR 21).
    t0 = time.monotonic()
    with ThreadPoolExecutor(max_workers=max(1, (os.cpu_count() or 2) - 1)) as pool:
        rows = list(pool.map(lambda job: job(), jobs))
    wall = time.monotonic() - t0

    dev = jax.devices()[0]
    print(f"kernel table on {dev.platform} / {dev.device_kind}"
          + (" (Pallas INTERPRETER — not Mosaic)" if args.interpret else ""))
    print("| kernel | shape | presets | result | compile+check s |")
    print("|---|---|---|---|---|")
    for r in rows:
        result = r["status"] + (f": {r['message']}" if r["message"] else "")
        print(f"| {r['kernel']} | {r['shape']} | {', '.join(r['presets'])} "
              f"| {result} | {r['seconds']} |")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({
                "platform": dev.platform, "device_kind": dev.device_kind,
                "interpret": args.interpret, "rows": rows,
            }, f, indent=1)
    broken = [
        r for r in rows
        if r["status"] == "disagrees"
        or (r["required"] and r["status"] != "compiled")
    ]
    for r in broken:
        print(f"FAILED: {r['kernel']} {r['shape']}: {r['status']} "
              f"{r['message']}", file=sys.stderr)
    print(json.dumps({
        "kernel_rows": len(rows),
        "compiled": sum(r["status"] == "compiled" for r in rows),
        "refused": sum(r["status"] == "refused" for r in rows),
        "disagrees": sum(r["status"] == "disagrees" for r in rows),
        "wall_seconds": round(wall, 1),
    }))
    return 1 if broken else 0


if __name__ == "__main__":
    sys.exit(main())
