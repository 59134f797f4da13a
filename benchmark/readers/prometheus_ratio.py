"""A per-layer metric that is a ratio of sums of Prometheus increases:

    scale * (sum of increases of the ``num`` terms) / (sum of those of ``den``)

between two instants of the run (``from`` / ``to``, default window_start /
drained). Each term is ``{"metric": name, "labels": {...}}`` with a label set
of its own, which ``prometheus_delta``'s ``share`` (one ``labels`` for both
sides) cannot say. Parameters: ``target``, ``num``, ``den``, ``scale``
(default 1), ``from``, ``to``. A term the program does not export counts for
nothing; when no numerator term, or no denominator term, is exported (a
program from before the metric's counters) the metric is left out.
"""

from readers import prometheus_delta


def _sum(ctx, p, terms):
    seen = [
        prometheus_delta._increase(ctx, {**p, "labels": t.get("labels")}, t["metric"])
        for t in terms
    ]
    seen = [v for v in seen if v is not None]
    return sum(seen) if seen else None


def read(p, ctx):
    num, den = _sum(ctx, p, p["num"]), _sum(ctx, p, p["den"])
    if num is None or not den:
        return None
    return p.get("scale", 1.0) * num / den
