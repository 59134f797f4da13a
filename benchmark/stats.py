"""The arithmetic from recorded requests to end-to-end metrics.

Kept with the benchmark so that no PR that claims a gain can change it.
Checked on made-up frames by ``selfcheck.py``. Nothing here reads a clock.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default), in
    plain Python so that this file needs nothing but the standard library.
    ``math.inf`` entries are legal and sort last: a failed request counts as
    the largest value, and a percentile that lands on one is reported as the
    largest finite value seen (a metric is a number as measured, and JSON has
    no infinity); the failure itself shows in ``failed``."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    finite = [v for v in ordered if math.isfinite(v)]
    if not finite:
        raise ValueError("no finite value: every request failed")
    pos = (len(ordered) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    a, b = ordered[lo], ordered[hi]
    if not math.isfinite(a) or not math.isfinite(b):
        return finite[-1]
    return a + (b - a) * (pos - lo)


@dataclass
class Record:
    """One request as the client saw it. Times are seconds on the load
    generator's monotonic clock, measured from the start of traffic."""

    rid: int
    due: float
    prompt_len: int
    max_tokens: int
    sent: Optional[float] = None
    frame_times: List[float] = field(default_factory=list)  # frames that carry tokens
    done: Optional[float] = None  # time of "[DONE]"
    finish_reason: Optional[str] = None
    usage: Optional[Dict[str, int]] = None
    error: Optional[str] = None
    tag: str = ""

    @property
    def ok(self) -> bool:
        return self.error is None and self.done is not None and bool(self.frame_times)

    @property
    def output_tokens(self) -> int:
        return int((self.usage or {}).get("completion_tokens", 0))

    def ttft_s(self) -> float:
        """Due -> first frame that carries a token; a failed or refused
        request counts as the largest value."""
        if not self.ok:
            return math.inf
        return self.frame_times[0] - self.due

    def tpot_s(self) -> Optional[float]:
        """(last frame - first frame) / (output tokens - tokens in the first
        frame). Per token, not per frame: one frame carries a burst of
        ``--decode-steps`` tokens. The stream does not say how many tokens a
        frame holds; the prefill emits one token in a frame of its own, so
        the first frame is counted as ONE token. None when the request has a
        single frame (nothing to divide)."""
        if not self.ok or len(self.frame_times) < 2 or self.output_tokens < 2:
            return None
        return (self.frame_times[-1] - self.frame_times[0]) / (self.output_tokens - 1)

    def tokens_between(self, t0: float, t1: float) -> float:
        """Output tokens received in [t0, t1): the first frame is one token,
        the rest of ``completion_tokens`` is spread evenly over the other
        frames (every burst but the last carries the same count)."""
        if not self.frame_times:
            return 0.0
        n = self.output_tokens or len(self.frame_times)
        rest = len(self.frame_times) - 1
        per = (n - 1) / rest if rest else 0.0
        total = 0.0
        for i, t in enumerate(self.frame_times):
            if t0 <= t < t1:
                total += 1.0 if i == 0 else per
        return total


def end_to_end(records: List[Record], w0: float, w1: float, chips: int,
               setup_s: float) -> Dict[str, Dict[str, float]]:
    """Every end-to-end metric a cell may list in BENCHMARK.json, over
    requests DUE in [w0, w1); a cell reports the ones listed for it."""
    due = [r for r in records if w0 <= r.due < w1]
    ttft = [r.ttft_s() for r in due]
    tpot = [t for t in (r.tpot_s() for r in due) if t is not None]
    out = {
        "ttft_p50_ms": {"value": percentile(ttft, 50) * 1e3, "unit": "ms"},
        "ttft_p95_ms": {"value": percentile(ttft, 95) * 1e3, "unit": "ms"},
        "out_tok_per_s": {"value": out_tok_per_s(records, w0, w1, chips), "unit": "tokens/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    if tpot:
        out["tpot_p95_ms"] = {"value": percentile(tpot, 95) * 1e3, "unit": "ms"}
    return out


def out_tok_per_s(records: List[Record], w0: float, w1: float, chips: int) -> float:
    """Every output token received inside the window, whichever request it
    belongs to (all the work and all the time of the window), per chip."""
    return sum(r.tokens_between(w0, w1) for r in records) / (w1 - w0) / chips


def ttft_ms(records: List[Record], w0: float, w1: float, q: float) -> Optional[float]:
    """A percentile of the time to first token of the requests due in the
    window, for cells where it is recorded and not judged."""
    ttft = [r.ttft_s() for r in records if w0 <= r.due < w1]
    if not any(math.isfinite(t) for t in ttft):
        return None
    return percentile(ttft, q) * 1e3


def lateness_ms(records: List[Record], w0: float, w1: float, q: float) -> Optional[float]:
    late = [r.sent - r.due for r in records if w0 <= r.due < w1 and r.sent is not None]
    return percentile(late, q) * 1e3 if late else None
