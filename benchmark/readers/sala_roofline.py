"""``kernel.sala_decode_roofline`` and ``kernel.sparse_attention_roofline``: the
decode step of a configuration with block-sparse attention layers beside
lightning-attention layers, and its paged-attention decode kernel over the
sparse layers, against their least times (``roofline_sala.py``).

Both are worked at what the program DISPATCHED in the capture, from counters
it bumps once per burst, read between ``capture_start`` and ``window_end``
with the same fall-back to ``drained`` as ``decode_roofline_share``
(``trace.py``): rows of a burst as there; the pages the sparse layers' kernel
visits for the burst's rows (``selected_metric``: what the rows' indexers
selected, all a row holds where it is under ``dense_len``) and the pages those
rows hold (``live_metric``), each row's last page counted empty (so page
rounding never raises a share); the rows on the sparse path
(``rows_path_metric`` with ``path="sparse"``), each of which selects ``topk``
pages, which tells the sparse-path rows' live pages (whose compressed keys the
indexer scores) from the dense rows'.

``what``:
  ``decode_step``       least time of the whole step over the median device
                        time of one decode program / ``--decode-steps``
  ``attention_kernel``  least time of the kernel over the step's sparse
                        layers (the visited pages' K/V bytes and their FLOPs)
                        over the step's device time x the kernel's share of
                        the decode programs' operation time (the reducer's
                        self times). One custom-call name serves the selected
                        and the dense rows: the trace cannot split them.

Left out (None, with the reason in ``ctx.why_nothing``) where the capture
holds no decode program, where no burst was counted, where the configuration
has no sparse layer, where the kernel is not among the decode programs'
operations, or on a program that does not export the counters: it then raises
nothing.
"""

import roofline_sala
from readers import trace


def read(p, ctx):
    if not ctx.trace:
        return trace._nothing(ctx, "no trace summary")
    cfg = ctx.config
    if "minicpm4" not in (cfg.get("mixer_types") or []):
        return trace._nothing(ctx, "not a configuration with sparse attention layers")
    step_s = trace._program_median_s(p, ctx)
    if not step_s:
        return trace._nothing(ctx, f"no {p['program']} program in the capture")
    rows = bursts = selected = live = on_sparse = None
    for to in ("window_end", "drained"):
        rows = trace._capture_increase(ctx, to, p["rows_metric"] + "_sum", p.get("rows_labels"))
        bursts = trace._capture_increase(ctx, to, p["rows_metric"] + "_count", p.get("rows_labels"))
        selected = trace._capture_increase(ctx, to, p["selected_metric"])
        live = trace._capture_increase(ctx, to, p["live_metric"])
        on_sparse = trace._capture_increase(ctx, to, p["rows_path_metric"], {"path": "sparse"})
        if bursts and rows and selected:
            break
    if None in (rows, bursts, selected, live, on_sparse):
        return trace._nothing(ctx, "the workers do not export the burst and selected-page counters")
    if not bursts or not rows or not selected:
        return trace._nothing(ctx, "no decode burst was counted between capture_start and drained")
    rows_per_burst = rows / bursts
    block = int(ctx.worker_flag("--block-size"))
    topk = int(cfg["assumed"]["sparse_config"]["topk"])
    # Per burst, each row's last page counted empty (it holds 1..block tokens).
    visited = max(0.0, selected - rows) / bursts
    # A sparse-path row selects ``topk`` pages; what else was selected is the
    # dense rows' own pages, and the rest of the live pages the sparse rows'.
    scored = max(0.0, live - (selected - on_sparse * topk) - on_sparse) / bursts
    if p["what"] == "attention_kernel":
        ops = dict((ctx.trace.get("program_top_ops") or {}).get(p["program"]) or [])
        kernel_s = sum(v for n, v in ops.items() if p["kernel"] in n)
        ops_s = ctx.trace["programs"][p["program"]].get("ops_s")
        if not kernel_s or not ops_s:
            return trace._nothing(ctx, f"no {p['kernel']} among the {p['program']} programs' operations")
        calls_s = step_s * kernel_s / ops_s
        least, bound, nbytes, flops = roofline_sala.attention_least_seconds(
            cfg, visited, block, ctx.device_kind)
        ctx.notes.append(
            f"sparse attention roofline: {rows_per_burst:.1f} rows ({on_sparse / bursts:.1f} on the sparse "
            f"path), {visited:.1f} pages visited of {max(0.0, live - rows) / bursts:.1f} held a step: "
            f"{nbytes / 1e6:.1f} MB, {flops / 1e9:.2f} GFLOP over "
            f"{roofline_sala.layers_of(cfg).count('sparse')} layers, least {least * 1e6:.1f} us "
            f"({bound}-bound); {p['kernel']} is {100 * kernel_s / ops_s:.1f}% of the decode programs' "
            f"operation time: {calls_s * 1e6:.1f} us a step")
        return 100.0 * least / calls_s
    least, bound, terms = roofline_sala.decode_step_least_seconds(
        cfg, rows_per_burst, visited, scored, block, ctx.device_kind)
    ctx.notes.append(
        f"sala decode roofline: {rows_per_burst:.1f} rows per dispatched burst ({bursts:.0f} bursts to {to}, "
        f"{on_sparse / bursts:.1f} rows on the sparse path), {visited:.1f} pages visited and {scored:.1f} "
        f"pages' compressed keys scored a sparse layer; least {least * 1e3:.3f} ms ({bound}-bound: "
        + ", ".join(f"{k} {v / 1e9:.3f} GB" for k, v in terms.items())
        + f"), measured {step_s * 1e3:.3f} ms/step")
    return 100.0 * least / step_s
