"""A per-layer metric from the reduced device trace (``trace_reduce.py``
writes the summary; this picks from it). With ``--trace 0`` there is no
summary and every metric here is left out.

``what``:
  ``program_median_ms``  median device duration of one execution of
                         ``program``, divided by the worker flag
                         ``per_flag`` when given (a decode burst runs
                         ``--decode-steps`` steps)
  ``program_us_per_token`` device seconds of ``program`` in the capture over
                         the increase of the counter ``tokens_metric`` between
                         ``capture_start`` and ``window_end``
  ``custom_call_share``  share of ``program``'s device-op time inside custom
                         calls (Mosaic kernels)
  ``idle_share``         100 * (1 - busy / window)
  ``decode_roofline_share`` least time of one decode step (roofline.py, at
                         the rows and mean context polled during the capture)
                         over the measured device time of one step
"""

import roofline
from readers import prometheus_delta, prometheus_poll


def _program_median_s(p, ctx):
    """Median device seconds of one execution of ``program``, divided by the
    worker flag ``per_flag`` when given."""
    prog = ctx.trace["programs"].get(p["program"])
    if not prog or not prog["count"]:
        return None
    per = float(ctx.worker_flag(p["per_flag"])) if p.get("per_flag") else 1.0
    return prog["median_s"] / per


def read(p, ctx):
    if not ctx.trace:
        return None
    what = p["what"]
    if what == "program_median_ms":
        median_s = _program_median_s(p, ctx)
        return None if median_s is None else median_s * 1e3
    if what == "program_us_per_token":
        prog = ctx.trace["programs"].get(p["program"])
        tokens = prometheus_delta.read(
            {"target": "workers", "metric": p["tokens_metric"],
             "from": "capture_start", "to": "window_end"}, ctx)
        if not prog or not tokens:
            return None
        return prog["total_s"] * 1e6 / tokens
    if what == "custom_call_share":
        prog = ctx.trace["programs"].get(p["program"])
        if not prog or not prog.get("ops_s"):
            return None
        return 100.0 * prog.get("custom_call_s", 0.0) / prog["ops_s"]
    if what == "idle_share":
        return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
    if what == "decode_roofline_share":
        step_s = _program_median_s(p, ctx)
        rows = prometheus_poll.series(
            {"target": "workers", "metric": p["rows_metric"]}, ctx, ctx.capture_t0, ctx.w1)
        used = prometheus_poll.series(
            {"target": "workers", "metric": p["total_blocks_metric"]}, ctx, ctx.capture_t0, ctx.w1)
        free = prometheus_poll.series(
            {"target": "workers", "metric": p["free_blocks_metric"]}, ctx, ctx.capture_t0, ctx.w1)
        if not step_s or not rows or not used or len(used) != len(free):
            return None
        n_workers = len(ctx.targets("workers"))
        mean_rows = sum(rows) / len(rows) / n_workers
        if mean_rows <= 0:
            return None
        live_tokens = sum(u - f for u, f in zip(used, free)) / len(used) / n_workers \
            * float(ctx.worker_flag("--block-size"))
        least, bound = roofline.decode_step_least_seconds(
            ctx.config, mean_rows, live_tokens / mean_rows, ctx.device_kind)
        ctx.notes.append(
            f"decode roofline: {mean_rows:.1f} rows x {live_tokens / mean_rows:.0f} tokens, "
            f"least {least * 1e3:.3f} ms ({bound}-bound), measured {step_s * 1e3:.3f} ms/step")
        return 100.0 * least / step_s
    raise ValueError(f"trace reader: unknown {what!r}")
