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
  ``decode_roofline_share`` least time of one decode step (roofline.py) over
                         the measured device time of one step, at the rows
                         and the context of the decode bursts the program
                         DISPATCHED during the capture: the increase, between
                         ``capture_start`` and ``window_end`` (or ``drained``,
                         where no burst was reaped before the window's end:
                         the device was idle till then, and the reducer's
                         window, which opens at the first device operation,
                         lies in the drain), of counters the
                         engine bumps once per burst (``rows_metric`` with
                         ``rows_labels``: a histogram of the rows of a burst;
                         ``live_pages_metric``: the sum over those rows of
                         ``ceil(context / --block-size)``). Rows of a burst
                         = increase of _sum / increase of _count; context of
                         a row = (pages - rows) x block size / rows, every
                         row's last page counted EMPTY: it under-reads the
                         true mean by a few tokens, so page rounding can
                         never flatter the share. No gauge poll is read: a
                         request that lives 0.3 s is seen by a counter and
                         missed by a 2 Hz poll (PR 31). Over several workers
                         the increases are added before dividing, so rows
                         and context are those of one burst whatever the
                         number of workers.

A reader that leaves its metric out says why in ``ctx.why_nothing``; run.py
prints it.
"""

import roofline
from readers import prometheus_delta


def _nothing(ctx, why):
    ctx.why_nothing = why
    return None


def _capture_increase(ctx, to, metric, labels=None):
    """Increase of a worker counter from ``capture_start`` to the snapshot
    ``to``, added over the workers; None when none exports it."""
    return prometheus_delta.read(
        {"target": "workers", "metric": metric, "labels": labels,
         "from": "capture_start", "to": to}, ctx)


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
        return _nothing(ctx, "no trace summary")
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
        if not step_s:
            return _nothing(ctx, f"no {p['program']} program in the capture")
        # The reducer's window opens at the first device operation of the
        # capture. Where the device sat idle from ``capture_start`` to the
        # window's end, that window lies in the drain, and so do its bursts.
        for to in ("window_end", "drained"):
            rows = _capture_increase(ctx, to, p["rows_metric"] + "_sum", p.get("rows_labels"))
            bursts = _capture_increase(ctx, to, p["rows_metric"] + "_count", p.get("rows_labels"))
            pages = _capture_increase(ctx, to, p["live_pages_metric"])
            if bursts and rows:
                break
        if rows is None or bursts is None or pages is None:
            return _nothing(ctx, "the workers do not export "
                            f"{p['rows_metric']} and {p['live_pages_metric']}")
        if not bursts or not rows:
            return _nothing(ctx, "no decode burst was counted between capture_start and drained")
        block = float(ctx.worker_flag("--block-size"))
        rows_per_burst = rows / bursts
        mean_ctx = max(0.0, pages - rows) * block / rows
        least, bound = roofline.decode_step_least_seconds(
            ctx.config, rows_per_burst, mean_ctx, ctx.device_kind)
        ctx.notes.append(
            f"decode roofline: {rows_per_burst:.1f} rows x {mean_ctx:.0f} tokens per dispatched "
            f"burst ({bursts:.0f} bursts counted from capture_start to {to}), least {least * 1e3:.3f} ms "
            f"({bound}-bound), measured {step_s * 1e3:.3f} ms/step")
        return 100.0 * least / step_s
    raise ValueError(f"trace reader: unknown {what!r}")
