"""Open loop over resident contexts of several CLASSES that short turns share.

``shared_docs`` with document classes: the same laws, the same dealing, the
same ``hit_bursts`` convention. Parameters (traffic file): ``contexts``, a list
of ``{name, count, tokens, share}`` (``share``: the part of the requests that
go to the class, dealt evenly inside it), ``turn_tokens`` and
``output_tokens`` (length laws), ``arrivals``, ``blocks``, optionally
``schedule_seed``, and under ``warmup`` the ``hit_bursts`` ([rows, turn
tokens, output tokens, class name or "mixed"]). The cell file gives ``rate``
in requests/s.

A request is one resident context followed by a fresh turn. The contexts' ids
are drawn from the first ``--seed`` this process sees, once, and each context
is asked once, ALONE and with nothing after it, before the first segment is
taken (the generator's own warm-up, inside set-up): its prompt ends where
every later request resumes, so the window page group of a model that has one
keeps the context's trailing window in its cache, and from then on every ask
is a prefix hit on a resident context with the turn as a chunk over cached
state. Then the ``hit_bursts``: ``rows`` simultaneous asks with fresh turns of
one length, over one class or over the classes in turn ("mixed"): a prefill
program is one per (rows, chunk, table-width) bucket and the classes' tables
differ in width, so every program the window can meet exists before it.

A segment (a warm-up lap, the window) of ``rate x duration`` requests holds the
same multiset of classes, turn lengths, output lengths and gaps whoever orders
it (``laws.stratified`` / ``laws.gaps``), dealt in ``blocks`` hands
(``laws.dealt``). Who deals: ``schedule_seed`` when the mix gives one (ONE
FIXED SCHEDULE, ``--seed`` draws ids only: see ``open_loop``), else ``--seed``.
"""

from __future__ import annotations

import asyncio

import numpy as np

import laws
from generators.shared_docs import _ask

# The process's contexts by class: one set for all the segments it serves.
_CONTEXTS: dict = {}


def probe_lengths(params):
    """Fresh contexts as the harness's probes: three of the shortest class and
    one of the longest, each with a turn at a quartile of the turn law; the
    first (a short one) is asked again and must be a prefix hit."""
    tokens = sorted(int(c["tokens"]) for c in params["contexts"])
    turn = [laws.quantile(params["turn_tokens"], q) for q in (0.125, 0.375, 0.625, 0.875)]
    return [tokens[0] + turn[0], tokens[-1] + turn[1], tokens[0] + turn[2], tokens[0] + turn[3]]


async def _resident_contexts(api, params):
    if _CONTEXTS:
        return _CONTEXTS
    ids = np.random.default_rng([api.seed, 6151])
    classes = {
        c["name"]: [laws.token_ids(ids, int(c["tokens"]), api.vocab) for _ in range(int(c["count"]))]
        for c in params["contexts"]
    }
    for contexts in classes.values():
        for context in contexts:  # each asked once, alone: a fresh chunked prefill into the pools
            await _ask(api, lambda: context, 8, "context")
    most = max(len(cs) for cs in classes.values())
    everyone = [cs[i % len(cs)] for i in range(most) for cs in classes.values()]
    for rows, n_turn, n_out, which in params.get("warmup", {}).get("hit_bursts", []):
        pool = everyone if which == "mixed" else classes[which]
        await asyncio.gather(*(
            _ask(api, lambda r=r: pool[r % len(pool)] + laws.token_ids(ids, n_turn, api.vocab),
                 n_out, "hit_burst")
            for r in range(rows)))
    _CONTEXTS.update(classes)
    return _CONTEXTS


def plan(params, rate, duration, rng):
    """[(offset, class name, context index, turn length, output length)]."""
    n = max(int(round(rate * duration)), 1)
    blocks = max(min(int(params.get("blocks", 1)), n), 1)
    who = []
    left = n
    for k, c in enumerate(params["contexts"]):
        last = k == len(params["contexts"]) - 1
        m = left if last else min(int(round(n * float(c["share"]))), left)
        left -= m
        who += [((i + 0.5) / max(m, 1), c["name"], i % int(c["count"])) for i in range(m)]
    # The classes interleaved evenly before dealing, so that every hand
    # holds each class in its share.
    who = laws.dealt([w[1:] for w in sorted(who)], blocks, rng)
    turns = laws.dealt(laws.stratified(params["turn_tokens"], n), blocks, rng)
    outputs = laws.dealt(laws.stratified(params["output_tokens"], n), blocks, rng)
    gap = laws.dealt(laws.gaps(params["arrivals"], n, duration), blocks, rng)
    t, out = 0.0, []
    for i in range(n):
        out.append((t, who[i][0], who[i][1], turns[i], outputs[i]))
        t += gap[i]  # the gap AFTER request i; the first is due at 0
    return out


async def run(api, params, cell):
    rate = float(cell["rate"])
    contexts = await _resident_contexts(api, params)
    while True:
        seg = await api.next_segment()
        if seg is None:
            return
        dealer = int(params.get("schedule_seed", api.seed))
        order = np.random.default_rng([dealer, 104729, seg.index])
        ids = np.random.default_rng([api.seed, 7919, seg.index])
        for offset, name, c, n_turn, n_out in plan(params, rate, seg.duration, order):
            due = seg.t0 + offset
            await api.sleep_until(due)
            api.fire(due, contexts[name][c] + laws.token_ids(ids, n_turn, api.vocab), n_out, seg.kind)
        await api.sleep_until(seg.t0 + seg.duration)
