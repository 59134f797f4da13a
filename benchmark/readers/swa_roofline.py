"""``kernel.swa_decode_roofline`` and ``kernel.paged_attention_roofline``: the
decode step of a configuration that mixes sliding-window and full attention
layers over two page groups, and its paged-attention decode kernel over the
step's attention layers, against their least times (``roofline_swa.py``).

Both are worked at what the program DISPATCHED in the capture, from counters
it bumps once per burst, read between ``capture_start`` and ``window_end``
with the same fall-back to ``drained`` as ``decode_roofline_share``
(``trace.py``): rows of a burst as there; the full group's live pages
(``live_pages_metric``) and the window group's (``window_live_pages_metric``)
per burst as the program counted them, each row's last page counted empty (so
page rounding never raises a share); experts hit per expert layer-step and
(token, expert) pairs per layer-step as ``hybrid_roofline`` reads them.

``what``:
  ``decode_step``       least time of the whole step over the median device
                        time of one decode program / ``--decode-steps``
  ``attention_kernel``  least time of the kernel over ALL the step's attention
                        layers (the full layers' and the sliding layers' live
                        K/V bytes and their FLOPs) over the step's device time
                        x the kernel's share of the decode programs' operation
                        time (the reducer's self times). One custom-call name
                        serves both layer kinds: the trace cannot split them.

Left out (None, with the reason in ``ctx.why_nothing``) where the capture
holds no decode program, where no burst was counted, where the configuration
has no sliding layer, where the kernel is not among the decode programs'
operations, or on a program that does not export the counters: it then raises
nothing.
"""

import roofline_swa
from readers import trace


def read(p, ctx):
    if not ctx.trace:
        return trace._nothing(ctx, "no trace summary")
    cfg = ctx.config
    if "sliding_attention" not in (cfg.get("layer_types") or []):
        return trace._nothing(ctx, "not a configuration with sliding-window layers")
    step_s = trace._program_median_s(p, ctx)
    if not step_s:
        return trace._nothing(ctx, f"no {p['program']} program in the capture")
    names = ("live_pages_metric", "window_live_pages_metric", "hit_metric", "slots_metric",
             "mean_tokens_metric")
    rows = bursts = None
    seen = {}
    for to in ("window_end", "drained"):
        rows = trace._capture_increase(ctx, to, p["rows_metric"] + "_sum", p.get("rows_labels"))
        bursts = trace._capture_increase(ctx, to, p["rows_metric"] + "_count", p.get("rows_labels"))
        seen = {n: trace._capture_increase(ctx, to, p[n]) for n in names}
        if bursts and rows and seen["slots_metric"]:
            break
    if rows is None or bursts is None or None in seen.values():
        return trace._nothing(ctx, "the workers do not export the burst, page-group and expert-load counters")
    if not bursts or not rows or not seen["slots_metric"]:
        return trace._nothing(ctx, "no decode burst was counted between capture_start and drained")
    rows_per_burst = rows / bursts
    block = int(ctx.worker_flag("--block-size"))
    # Per burst, each row's last page counted empty (it holds 1..block tokens).
    full_pages = max(0.0, seen["live_pages_metric"] - rows) / bursts
    window_pages = max(0.0, seen["window_live_pages_metric"] - rows) / bursts
    if p["what"] == "attention_kernel":
        ops = dict((ctx.trace.get("program_top_ops") or {}).get(p["program"]) or [])
        kernel_s = sum(v for n, v in ops.items() if p["kernel"] in n)
        ops_s = ctx.trace["programs"][p["program"]].get("ops_s")
        if not kernel_s or not ops_s:
            return trace._nothing(ctx, f"no {p['kernel']} among the {p['program']} programs' operations")
        calls_s = step_s * kernel_s / ops_s
        least, bound, nbytes, flops = roofline_swa.attention_least_seconds(
            cfg, full_pages, window_pages, block, ctx.device_kind)
        ctx.notes.append(
            f"paged attention roofline: {rows_per_burst:.1f} rows, {full_pages:.1f} full-group and "
            f"{window_pages:.1f} window-group live pages a step: {nbytes / 1e6:.1f} MB, {flops / 1e9:.2f} GFLOP "
            f"over {len(roofline_swa.layers_of(cfg))} layers, least {least * 1e6:.1f} us ({bound}-bound); "
            f"{p['kernel']} is {100 * kernel_s / ops_s:.1f}% of the decode programs' operation time: "
            f"{calls_s * 1e6:.1f} us a step")
        return 100.0 * least / calls_s
    held = float(cfg["num_experts"])
    layer_steps = seen["slots_metric"] / held
    experts_hit = seen["hit_metric"] / layer_steps
    expert_tokens = seen["mean_tokens_metric"] * held / layer_steps
    least, bound, terms = roofline_swa.decode_step_least_seconds(
        cfg, rows_per_burst, full_pages, window_pages, block, experts_hit, expert_tokens,
        ctx.device_kind)
    ctx.notes.append(
        f"swa decode roofline: {rows_per_burst:.1f} rows per dispatched burst ({bursts:.0f} bursts to {to}), "
        f"{full_pages:.1f} full-group and {window_pages:.1f} window-group live pages, {experts_hit:.1f} of "
        f"{held:.0f} experts hit and {expert_tokens:.1f} routed pairs per expert layer-step; least "
        f"{least * 1e3:.3f} ms ({bound}-bound: "
        + ", ".join(f"{k} {v / 1e9:.3f} GB" for k, v in terms.items())
        + f"), measured {step_s * 1e3:.3f} ms/step")
    return 100.0 * least / step_s
