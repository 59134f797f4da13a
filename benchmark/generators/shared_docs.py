"""Open loop over a few long documents that many short questions share.

Parameters (traffic file): ``documents`` (``count``, ``tokens``),
``question_tokens`` and ``output_tokens`` (length laws), ``arrivals``,
``blocks``, optionally ``schedule_seed``, and under ``warmup`` the
``hit_bursts`` ([rows, question tokens, output tokens]). The cell file gives
``rate`` in requests/s.

A request is one document followed by a fresh question. The documents' ids are
drawn from the first ``--seed`` this process sees, once, and each document is
asked once, ALONE, before the first segment is taken (the generator's own
warm-up, inside set-up): from then on every ask is a prefix hit on a resident
document, and the question is a chunk over cached state. Then the
``hit_bursts``: ``rows`` simultaneous asks with fresh questions of one length,
so that every batch shape of a question chunk over a full table is compiled
(the harness's own ``warmup.bursts`` are fresh prompts: other programs).

A segment (a warm-up lap, the window) of ``rate x duration`` requests holds the
same multiset of question lengths, output lengths and gaps whoever orders it
(``laws.stratified`` / ``laws.gaps``), dealt in ``blocks`` hands
(``laws.dealt``); the documents are dealt round the requests, so each is asked
the same number of times to within one, in an order of its own. Who deals:
``schedule_seed`` when the mix gives one (ONE FIXED SCHEDULE, ``--seed`` draws
ids only: see ``open_loop``), else ``--seed``.
"""

from __future__ import annotations

import asyncio

import numpy as np

import laws

# The process's documents: one set for all the segments it serves (a sweep
# calls ``run`` once a rate, with another seed each time: the questions
# change, the documents that are resident do not).
_DOCS: list = []


def probe_lengths(params):
    """Document + question at the quartiles of the question law: a probe is
    a whole fresh document, and its repeat a prefix hit over it."""
    d = int(params["documents"]["tokens"])
    return [d + laws.quantile(params["question_tokens"], q) for q in (0.125, 0.375, 0.625, 0.875)]


async def _listed(api):
    """Up to a minute for the frontend to list the model again (a first
    compile can hold the worker past the frontend's liveness budget; the
    request then fails within milliseconds, its program not yet compiled)."""
    import aiohttp

    async with aiohttp.ClientSession() as http:
        for _ in range(120):
            try:
                async with http.get(api.client.base + "/v1/models") as resp:
                    if api.client.model in [m["id"] for m in (await resp.json())["data"]]:
                        return
            except (aiohttp.ClientError, asyncio.TimeoutError, KeyError, ValueError):
                pass
            await asyncio.sleep(0.5)


async def _ask(api, make_prompt, n_out, tag):
    """One set-up request; asked again with a fresh prompt, up to three
    times, where it failed (as the harness's probes and bursts are)."""
    for _ in range(3):
        prompt = make_prompt()
        rec = await api.client.request(api.now(), prompt, n_out, tag)
        if rec.ok:
            return rec
        await _listed(api)
    raise RuntimeError(f"set-up request ({tag}, prompt {len(prompt)}) failed three times: {rec.error}")


async def _resident_documents(api, params):
    if _DOCS:
        return _DOCS
    spec = params["documents"]
    ids = np.random.default_rng([api.seed, 6151])
    docs = [laws.token_ids(ids, int(spec["tokens"]), api.vocab) for _ in range(int(spec["count"]))]
    for doc in docs:  # each asked once, alone: a fresh chunked prefill into the pool
        await _ask(api, lambda: doc + laws.token_ids(ids, 32, api.vocab), 8, "document")
    for rows, n_question, n_out in params.get("warmup", {}).get("hit_bursts", []):
        await asyncio.gather(*(
            _ask(api, lambda r=r: docs[r % len(docs)] + laws.token_ids(ids, n_question, api.vocab),
                 n_out, "hit_burst")
            for r in range(rows)))
    _DOCS.extend(docs)
    return _DOCS


def plan(params, rate, duration, rng):
    """[(offset, document index, question length, output length)]."""
    n = max(int(round(rate * duration)), 1)
    blocks = max(min(int(params.get("blocks", 1)), n), 1)
    count = int(params["documents"]["count"])
    docs = laws.dealt([i % count for i in range(n)], blocks, rng)
    questions = laws.dealt(laws.stratified(params["question_tokens"], n), blocks, rng)
    outputs = laws.dealt(laws.stratified(params["output_tokens"], n), blocks, rng)
    gap = laws.dealt(laws.gaps(params["arrivals"], n, duration), blocks, rng)
    t, out = 0.0, []
    for i in range(n):
        out.append((t, docs[i], questions[i], outputs[i]))
        t += gap[i]  # the gap AFTER request i; the first is due at 0
    return out


async def run(api, params, cell):
    rate = float(cell["rate"])
    docs = await _resident_documents(api, params)
    while True:
        seg = await api.next_segment()
        if seg is None:
            return
        dealer = int(params.get("schedule_seed", api.seed))
        order = np.random.default_rng([dealer, 104729, seg.index])
        ids = np.random.default_rng([api.seed, 7919, seg.index])
        for offset, d, n_question, n_out in plan(params, rate, seg.duration, order):
            due = seg.t0 + offset
            await api.sleep_until(due)
            api.fire(due, docs[d] + laws.token_ids(ids, n_question, api.vocab), n_out, seg.kind)
        await api.sleep_until(seg.t0 + seg.duration)
