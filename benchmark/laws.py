"""Length and arrival laws shared by the traffic generators.

A law is a small JSON object in a traffic file. Lengths and gaps are taken at
evenly spaced quantiles of the law (``stratified``), so every run of a cell
offers the same multiset of sizes and of gaps whatever the seed: a seed
changes the order (``dealt``) and the token ids, never the amount of work.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def quantile(law: Dict[str, Any], q: float) -> int:
    """The q-quantile of a length law as a whole number of tokens.
    ``lognormal`` is the law of ``median`` and ``sigma`` TRUNCATED to
    [min, max]: the quantile of the law given that it falls inside, so no
    mass piles up at either end."""
    kind = law["law"]
    if kind == "fixed":
        return int(law["value"])
    if kind == "lognormal":
        z = NormalDist()
        lo = z.cdf(math.log(law["min"] / law["median"]) / law["sigma"])
        hi = z.cdf(math.log(law["max"] / law["median"]) / law["sigma"])
        x = law["median"] * math.exp(law["sigma"] * z.inv_cdf(lo + q * (hi - lo)))
        return int(min(max(round(x), law["min"]), law["max"]))
    raise ValueError(f"unknown length law {kind!r}")


def stratified(law: Dict[str, Any], n: int) -> List[int]:
    """n lengths at the quantiles (i + 0.5) / n, in ascending order."""
    return [quantile(law, (i + 0.5) / n) for i in range(n)]


def gaps(law: Dict[str, Any], n: int, duration: float) -> List[float]:
    """n inter-arrival gaps at the quantiles (i + 0.5) / n of the arrival
    law, ascending, scaled to sum to ``duration`` exactly: a window of a given
    length always holds the same arrivals' worth of gaps. ``poisson``:
    exponential gaps. ``gamma``: gamma gaps with coefficient of variation
    ``cv`` (cv 1 is Poisson; above 1 arrivals come in bursts)."""
    q = (np.arange(n) + 0.5) / n
    kind = law["law"]
    if kind == "poisson":
        g = -np.log1p(-q)
    elif kind == "gamma":
        from scipy.special import gammaincinv

        g = gammaincinv(1.0 / float(law["cv"]) ** 2, q)
    else:
        raise ValueError(f"unknown arrival law {kind!r}")
    return (g * (duration / g.sum())).tolist()


def dealt(ascending: List, blocks: int, rng: np.random.Generator) -> List:
    """The seed's order of a stratified sample. The values are dealt round
    the table into ``blocks`` hands, so that every hand holds every
    ``blocks``-th quantile; the seed shuffles each hand; the hands follow one
    another. Every stretch of a window then carries the same work whatever
    the seed, in an order the seed draws (``blocks`` 1 is a plain shuffle)."""
    out: List = []
    for b in range(blocks):
        hand = list(ascending[b::blocks])
        rng.shuffle(hand)
        out += hand
    return out


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    """n token ids drawn from the seed: no natural text, so no accidental
    sharing. Ids stay clear of the lowest ones (specials)."""
    return rng.integers(16, vocab, size=n).tolist()
