"""A per-layer metric from a gauge polled at 2 Hz during the window.

Parameters: ``target`` (``workers`` or ``frontend``), ``metric``,
``labels``, ``reduce`` (``max`` or ``mean``), ``scale``. Each poll adds the
gauge over label sets and over workers; the reduction is over the polls that
fell inside the measured window.
"""

import prom


def series(p, ctx, t0, t1):
    out = []
    for t, by_target in ctx.polls:
        if not t0 <= t < t1:
            continue
        vals = [prom.total(by_target[target], p["metric"], p.get("labels"))
                for target in ctx.targets(p["target"]) if target in by_target]
        vals = [v for v in vals if v is not None]
        if vals:
            out.append(sum(vals))
    return out


def read(p, ctx):
    vals = series(p, ctx, ctx.w0, ctx.w1)
    if not vals:
        return None
    value = max(vals) if p.get("reduce", "max") == "max" else sum(vals) / len(vals)
    return value * p.get("scale", 1.0)
