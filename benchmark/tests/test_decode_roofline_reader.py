"""The ``decode_roofline_share`` branch of ``readers/trace.py`` on made-up
captures: no chip, no trace file, no JAX.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

Kept beside the reader because tier-1 (``tests/``) is not the benchmark's to
edit; ``selfcheck.py`` part 3 runs case (a) by hand.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.realpath(__file__)), ".."))

from readers import trace  # noqa: E402
from selfcheck import (  # noqa: E402
    PR31_AFTER, PR31_BEFORE, PR31_POLLS, PR31_SHARE, MadeUpCapture, roofline_params, scrape_text,
)

WEIGHTS_ONLY = 100.0 * 987_922_432 / 819e9 / (0.0544 / 8)
OLDER_PROGRAM = "dynamo_tpu_engine_active_seqs 3\n"  # a scrape without the two families

CASES = {
    # (a) PR 31: every poll in the capture reads 0 rows, the counters rose by
    # 12 bursts x 4 rows and their pages
    "polls_all_idle": (dict(before=[PR31_BEFORE], after=[PR31_AFTER], polls=PR31_POLLS),
                       PR31_SHARE, "to window_end"),
    # (b) no decode burst between capture_start and window_end, none traced
    "no_decode_burst": (dict(before=[PR31_AFTER], after=[PR31_AFTER], burst_s=0.0),
                        None, "no decode program in the capture"),
    # the device idle from the capture's start to the window's end, the traced
    # bursts all in the drain: counted to ``drained``
    "bursts_in_the_drain": (dict(before=[PR31_BEFORE], after=[PR31_BEFORE], drained=[PR31_AFTER]),
                            PR31_SHARE, "to drained"),
    # a decode program in the trace that no counter saw
    "burst_not_counted": (dict(before=[PR31_AFTER], after=[PR31_AFTER]),
                          None, "no decode burst was counted"),
    # (c) a program from before the counters (older than PR 25)
    "no_counters": (dict(before=[OLDER_PROGRAM], after=[OLDER_PROGRAM]),
                    None, "do not export"),
    # (d) two workers: the same per-burst rows and context as one
    "two_workers": (dict(before=[PR31_BEFORE] * 2, after=[PR31_AFTER] * 2),
                    PR31_SHARE, "4.0 rows x 320 tokens"),
    # two workers of which one sat idle: the bursts of the other, undiluted
    "one_of_two_idle": (dict(before=[PR31_BEFORE, PR31_BEFORE], after=[PR31_AFTER, PR31_BEFORE]),
                        PR31_SHARE, "4.0 rows x 320 tokens"),
    # bursts reaped in the capture whose dispatch (and pages) fell before it:
    # the context reads 0 and the share its floor, the weights alone
    "pages_before_capture": (
        dict(before=[PR31_BEFORE], after=[scrape_text(bursts=1012, rows=11548, pages=250000)]),
        WEIGHTS_ONLY, "4.0 rows x 0 tokens"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_decode_roofline_share(name):
    made_up, want, says = CASES[name]
    ctx = MadeUpCapture(**made_up)
    got = trace.read(roofline_params(), ctx)
    step = trace.read({"what": "program_median_ms", "program": "decode",
                       "per_flag": "--decode-steps"}, ctx)
    if want is None:
        assert got is None and says in ctx.why_nothing
        assert (step is None) == (name == "no_decode_burst")
    else:
        assert got == pytest.approx(want, rel=1e-12) and step == pytest.approx(6.8)
        assert says in ctx.notes[0] and "4.0 rows x" in ctx.notes[0]
        assert "per dispatched burst" in ctx.notes[0]
        assert 17.5 < got < 18.1  # weights are 1.206 of the least 1.206-1.226 ms


def test_the_share_is_a_lower_estimate():
    """Page rounding cannot flatter the share: over rows that end their
    bursts at every context from 200 to 455 tokens, the reader's context is
    under the mean the 8 steps of a burst attend over (end - 3.5), by the 5
    tokens that an empty last page (8.5 on average) leaves of it."""
    import roofline

    ends = list(range(200, 456))
    pages = sum(-(-c // 16) for c in ends)
    ctx = MadeUpCapture([scrape_text(0, 0, 0)], [scrape_text(len(ends) // 4, len(ends), pages)])
    got = trace.read(roofline_params(), ctx)
    burst_mean = sum(ends) / len(ends) - 3.5
    true_least, _ = roofline.decode_step_least_seconds(ctx.config, 4, burst_mean, ctx.device_kind)
    assert got < 100.0 * true_least / (0.0544 / 8)
    assert f"x {burst_mean - 5:.0f} tokens" in ctx.notes[0], ctx.notes
