"""``kernel.mla_decode_roofline`` and ``kernel.mla_attention_roofline``: a
latent-attention configuration's decode step, and its absorbed attention
kernel alone, against their least times (``roofline_mla.py``).

Both are worked at what the program DISPATCHED in the capture, from counters
it bumps once per burst, read between ``capture_start`` and ``window_end``
with the same fall-back to ``drained`` as ``decode_roofline_share``
(``trace.py``): rows of a burst and context of a row exactly as there (every
row's last page counted empty, so page rounding never raises a share);
experts hit per expert layer-step and (token, held expert) pairs per
layer-step as ``hybrid_roofline`` reads them.

``what``:
  ``decode_step``       least time of the whole step over the median device
                        time of one decode program / ``--decode-steps``
  ``attention_kernel``  least time of the kernel over ONE layer (both bounds:
                        it sits at the ridge) over the device time of ONE call
                        of ``kernel`` in the decode programs: the step's
                        device time x the kernel's share of the decode
                        programs' operation time (the reducer's self times,
                        clipped to the same window on both sides) / layers

Left out (None, with the reason in ``ctx.why_nothing``) where the capture
holds no decode program, where no burst was counted, where the configuration
has no latent cache, where the kernel is not among the decode programs'
operations, or on a program that does not export the counters: it then raises
nothing.
"""

import roofline_mla
from readers import trace


def read(p, ctx):
    if not ctx.trace:
        return trace._nothing(ctx, "no trace summary")
    cfg = ctx.config
    if "kv_lora_rank" not in cfg:
        return trace._nothing(ctx, "not a latent-attention configuration")
    step_s = trace._program_median_s(p, ctx)
    if not step_s:
        return trace._nothing(ctx, f"no {p['program']} program in the capture")
    rows = bursts = pages = hit = slots = mean_tokens = None
    for to in ("window_end", "drained"):
        rows = trace._capture_increase(ctx, to, p["rows_metric"] + "_sum", p.get("rows_labels"))
        bursts = trace._capture_increase(ctx, to, p["rows_metric"] + "_count", p.get("rows_labels"))
        pages = trace._capture_increase(ctx, to, p["live_pages_metric"])
        hit = trace._capture_increase(ctx, to, p["hit_metric"])
        slots = trace._capture_increase(ctx, to, p["slots_metric"])
        mean_tokens = trace._capture_increase(ctx, to, p["mean_tokens_metric"])
        if bursts and rows and slots:
            break
    if None in (rows, bursts, pages, hit, slots, mean_tokens):
        return trace._nothing(ctx, "the workers do not export the burst and expert-load counters")
    if not bursts or not rows or not slots:
        return trace._nothing(ctx, "no decode burst was counted between capture_start and drained")
    rows_per_burst = rows / bursts
    mean_ctx = max(0.0, pages - rows) * float(ctx.worker_flag("--block-size")) / rows
    if p["what"] == "attention_kernel":
        ops = dict((ctx.trace.get("program_top_ops") or {}).get(p["program"]) or [])
        kernel_s = sum(v for n, v in ops.items() if p["kernel"] in n)
        ops_s = ctx.trace["programs"][p["program"]].get("ops_s")
        if not kernel_s or not ops_s:
            return trace._nothing(ctx, f"no {p['kernel']} among the {p['program']} programs' operations")
        layers = float(cfg["num_hidden_layers"])
        call_s = step_s * kernel_s / ops_s / layers
        least, bound, nbytes, flops = roofline_mla.attention_least_seconds(
            cfg, rows_per_burst, mean_ctx, ctx.device_kind)
        ctx.notes.append(
            f"mla attention roofline: {rows_per_burst:.1f} rows x {mean_ctx:.0f} tokens, one layer: "
            f"{nbytes / 1e6:.1f} MB, {flops / 1e9:.1f} GFLOP, least {least * 1e6:.1f} us ({bound}-bound); "
            f"{p['kernel']} is {100 * kernel_s / ops_s:.1f}% of the decode programs' operation time: "
            f"{call_s * 1e6:.1f} us a call")
        return 100.0 * least / call_s
    held = float(cfg["n_routed_experts"])
    layer_steps = slots / held
    experts_hit = hit / layer_steps
    expert_tokens = mean_tokens * held / layer_steps
    least, bound, terms = roofline_mla.decode_step_least_seconds(
        cfg, rows_per_burst, mean_ctx, experts_hit, expert_tokens, ctx.device_kind)
    ctx.notes.append(
        f"mla decode roofline: {rows_per_burst:.1f} rows x {mean_ctx:.0f} tokens per dispatched burst "
        f"({bursts:.0f} bursts to {to}), {experts_hit:.1f} of {held:.0f} held experts hit and "
        f"{expert_tokens:.1f} routed pairs per expert layer-step; least {least * 1e3:.3f} ms ({bound}-bound: "
        + ", ".join(f"{k} {v / 1e9:.3f} GB" for k, v in terms.items())
        + f"), measured {step_s * 1e3:.3f} ms/step")
    return 100.0 * least / step_s
