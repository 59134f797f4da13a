"""Prometheus text exposition -> {family: [(labels, value), ...]}."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

Samples = Dict[str, List[Tuple[Dict[str, str], float]]]
_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(\{[^}]*\})?\s+(\S+)")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> Samples:
    out: Samples = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, value))
    return out


def total(samples: Samples, name: str,
          labels: Optional[Dict[str, str]] = None) -> Optional[float]:
    """Sum of the family's samples whose labels include ``labels``; None
    when there is no such sample (a counter nothing incremented)."""
    rows = [
        v for lab, v in samples.get(name, [])
        if all(lab.get(k) == want for k, want in (labels or {}).items())
    ]
    return sum(rows) if rows else None
