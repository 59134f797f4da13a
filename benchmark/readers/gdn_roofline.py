"""``kernel.gdn_decode_roofline`` and ``kernel.gdn_state_update_roofline``: the
decode step of a configuration with Gated DeltaNet layers beside gated
attention and experts in every layer, and its live-row state-update kernel,
against their least times (``roofline_gdn.py``).

Both are worked at what the program DISPATCHED in the capture, from counters
it bumps once per burst, read between ``capture_start`` and ``window_end``
with the same fall-back to ``drained`` as ``decode_roofline_share``
(``trace.py``): rows of a burst and context of a row exactly as there (each
row's last page counted empty, so page rounding never raises a share);
experts hit per expert layer-step and (token, held expert) pairs per
layer-step as ``hybrid_roofline`` works them.

``what``:
  ``decode_step``   least time of the whole step over the median device time
                    of one decode program / ``--decode-steps``
  ``state_kernel``  least time of the state-update kernel over the step's
                    recurrent layers (every live row's float32 matrix read and
                    written, ``roofline_gdn.state_update_least_seconds``) over
                    the step's device time x the kernel's share of the decode
                    programs' operation time (the reducer's self times; the
                    kernel is named by ``kernel``, the layers' count comes
                    from the configuration)

Left out (None, with the reason in ``ctx.why_nothing``) where the capture
holds no decode program, where no burst was counted, where the configuration
has no Gated DeltaNet layer, where the kernel is not among the decode
programs' operations (a program that kept the XLA step over every slot, or one
from before the kernel), or on a program that does not export the counters: it
then raises nothing.
"""

import roofline_gdn
from readers import trace


def read(p, ctx):
    if not ctx.trace:
        return trace._nothing(ctx, "no trace summary")
    cfg = ctx.config
    if "linear_num_value_heads" not in cfg:
        return trace._nothing(ctx, "not a configuration with Gated DeltaNet layers")
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
    n_gdn = roofline_gdn.layers_of(cfg).count("gdn")
    if p["what"] == "state_kernel":
        ops = dict((ctx.trace.get("program_top_ops") or {}).get(p["program"]) or [])
        kernel_s = sum(v for n, v in ops.items() if p["kernel"] in n)
        ops_s = ctx.trace["programs"][p["program"]].get("ops_s")
        if not kernel_s or not ops_s:
            return trace._nothing(ctx, f"no {p['kernel']} among the {p['program']} programs' operations")
        calls_s = step_s * kernel_s / ops_s
        least, nbytes = roofline_gdn.state_update_least_seconds(
            roofline_gdn.state_matrix_bytes(cfg), rows_per_burst, n_gdn, ctx.device_kind)
        ctx.notes.append(
            f"gdn state update roofline: {rows_per_burst:.1f} live rows x {n_gdn} layers x "
            f"{roofline_gdn.state_matrix_bytes(cfg) / 1e6:.2f} MB read and written a step: "
            f"{nbytes / 1e6:.1f} MB, least {least * 1e6:.1f} us; {p['kernel']} is "
            f"{100 * kernel_s / ops_s:.1f}% of the decode programs' operation time: "
            f"{calls_s * 1e6:.1f} us a step")
        return 100.0 * least / calls_s
    held = float(cfg["num_experts"])
    layer_steps = slots / held
    mean_ctx = max(0.0, pages - rows) * float(ctx.worker_flag("--block-size")) / rows
    experts_hit = hit / layer_steps
    expert_tokens = mean_tokens * held / layer_steps
    least, bound, terms = roofline_gdn.decode_step_least_seconds(
        cfg, rows_per_burst, mean_ctx, experts_hit, expert_tokens, ctx.device_kind)
    ctx.notes.append(
        f"gdn decode roofline: {rows_per_burst:.1f} rows x {mean_ctx:.0f} tokens per dispatched burst "
        f"({bursts:.0f} bursts to {to}), {experts_hit:.1f} of {held:.0f} held experts hit and "
        f"{expert_tokens:.1f} routed pairs per expert layer-step; least {least * 1e3:.3f} ms ({bound}-bound: "
        + ", ".join(f"{k} {v / 1e9:.3f} GB" for k, v in terms.items())
        + f"), measured {step_s * 1e3:.3f} ms/step")
    return 100.0 * least / step_s
