"""A per-layer metric of the load generator itself, from its own records.

``what: late_ms`` with ``q``: the q-th percentile of (sent - due) over the
requests due in the window. A starved generator must not be read as a fast
server. ``what: ttft_ms`` with ``q``: a percentile of the time to first token,
and ``what: out_tok_per_s``: output tokens received inside the window per
second and chip, where a cell records them without judging by them.
"""

import stats


def read(p, ctx):
    if p["what"] == "late_ms":
        return stats.lateness_ms(ctx.records, ctx.w0, ctx.w1, p.get("q", 95))
    if p["what"] == "ttft_ms":
        return stats.ttft_ms(ctx.records, ctx.w0, ctx.w1, p.get("q", 95))
    if p["what"] == "out_tok_per_s":
        return stats.out_tok_per_s(ctx.records, ctx.w0, ctx.w1, ctx.chips) if ctx.records else None
    raise ValueError(f"client reader: unknown {p['what']!r}")
