"""Open loop: requests are sent on a schedule whether or not earlier ones
have finished. Parameters (traffic file): ``arrivals`` (law ``poisson`` or
``gamma`` with ``cv``), ``prompt_tokens`` and ``output_tokens`` (length
laws), ``blocks``, and optionally ``schedule_seed``. The cell file gives
``rate`` in requests/s.

A segment (the window, or a warm-up lap) of ``rate x duration`` requests holds
the same multiset of prompt lengths, of output lengths and of gaps whatever
draws its order: each is taken at evenly spaced quantiles of its law. The
order of each of the three is dealt independently (``laws.dealt``, in
``blocks`` hands so that every stretch of the window carries the same work).

Who deals: ``schedule_seed`` when the mix gives one, and then every run of the
cell offers ONE FIXED SCHEDULE and ``--seed`` draws only the prompts' token
ids; otherwise ``--seed`` deals, and the schedule differs from seed to seed.
Measured on the chip with ``--seed`` dealing (PR 23, four seeds of
``chat_burst``): ``tpot_p95_ms`` and ``ttft_p50_ms`` spread by 10-11% from
seed to seed where two runs of one seed agreed within 2% (tpot): which
requests meet which burst decides a tail of 384 requests, and no bound of at
most 10% carries that. A mix whose judged metric is steadier than a tail
(tokens per second above the knee) can leave ``schedule_seed`` out.
"""

from __future__ import annotations

import numpy as np

import laws


def probe_lengths(params):
    """Prompt lengths at the quartiles of the law (the probe set)."""
    return [laws.quantile(params["prompt_tokens"], q) for q in (0.125, 0.375, 0.625, 0.875)]


def plan(params, rate, duration, rng):
    """[(offset, prompt length, output length)] of one segment."""
    n = max(int(round(rate * duration)), 1)
    blocks = max(min(int(params.get("blocks", 1)), n), 1)
    prompts = laws.dealt(laws.stratified(params["prompt_tokens"], n), blocks, rng)
    outputs = laws.dealt(laws.stratified(params["output_tokens"], n), blocks, rng)
    gap = laws.dealt(laws.gaps(params["arrivals"], n, duration), blocks, rng)
    t, out = 0.0, []
    for i in range(n):
        out.append((t, prompts[i], outputs[i]))
        t += gap[i]  # the gap AFTER request i; the first is due at 0
    return out


async def run(api, params, cell):
    rate = float(cell["rate"])
    while True:
        seg = await api.next_segment()
        if seg is None:
            return
        dealer = int(params.get("schedule_seed", api.seed))
        order = np.random.default_rng([dealer, 104729, seg.index])
        ids = np.random.default_rng([api.seed, 7919, seg.index])
        for offset, n_prompt, n_out in plan(params, rate, seg.duration, order):
            due = seg.t0 + offset
            await api.sleep_until(due)
            api.fire(due, laws.token_ids(ids, n_prompt, api.vocab), n_out, seg.kind)
        await api.sleep_until(seg.t0 + seg.duration)
