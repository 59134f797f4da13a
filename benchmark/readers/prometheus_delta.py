"""A per-layer metric from the change of Prometheus counters between two
instants of the run (``window_start``, ``capture_start``, ``drained``).

Parameters: ``target`` (``workers`` or ``frontend``), ``from`` / ``to``
(default window_start / drained), ``labels``, ``scale``, and one of
  ``mode: delta``  ``metric``              -> its increase
  ``mode: mean``   ``metric``              -> increase of _sum / increase of _count
  ``mode: share``  ``num``, ``den`` (list) -> 100 * increase(num) / sum of increase(den)
Over several workers the increases are added before dividing.
``percent_of_worker_flag`` divides by the value of that worker flag and
multiplies by 100 (rows of a batch -> share of ``--max-num-seqs``).
"""

import prom


def _increase(ctx, p, name):
    a, b = p.get("from", "window_start"), p.get("to", "drained")
    if a not in ctx.snapshots or b not in ctx.snapshots:
        return None
    total, seen = 0.0, False
    for target in ctx.targets(p["target"]):
        after = prom.total(ctx.snapshots[b][target], name, p.get("labels"))
        if after is None:
            continue
        before = prom.total(ctx.snapshots[a][target], name, p.get("labels")) or 0.0
        total += after - before
        seen = True
    return total if seen else None


def read(p, ctx):
    mode = p.get("mode", "delta")
    if mode == "delta":
        value = _increase(ctx, p, p["metric"])
    elif mode == "mean":
        s, n = _increase(ctx, p, p["metric"] + "_sum"), _increase(ctx, p, p["metric"] + "_count")
        value = s / n if s is not None and n else None
    elif mode == "share":
        num = _increase(ctx, p, p["num"]) or 0.0
        dens = [_increase(ctx, p, d) for d in p["den"]]
        den = sum(d for d in dens if d is not None)
        value = 100.0 * num / den if den else None
    else:
        raise ValueError(f"prometheus_delta: unknown mode {mode!r}")
    if value is None:
        return None
    if p.get("percent_of_worker_flag"):
        value = 100.0 * value / float(ctx.worker_flag(p["percent_of_worker_flag"]))
    return value * p.get("scale", 1.0)
