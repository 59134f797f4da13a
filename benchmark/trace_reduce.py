"""Reduce the profiler's ``.xplane.pb`` of the served worker(s) to a small
summary: device busy time, per-program and per-operation device time, the
share inside custom calls, and the longest idle gaps.

Run as a CHILD process with ``JAX_PLATFORMS=cpu`` after the workers have
gone: it needs ``jax.profiler.ProfileData`` to read the file and must not
hold the chip. Promoted from ``_prof_trace.py`` (which read the capped
``trace.json.gz`` of a bare ``decode_multi`` call).

    python trace_reduce.py --window-s 4.0 --out summary.json DIR [DIR ...]

The map from the names in the trace to ``prefill`` / ``decode`` / ``custom
call`` is data: every ``trace_names/*.json`` is merged, so a later PR that
renames a step adds a file.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import statistics
import sys
from collections import defaultdict
from typing import Any, Dict, List, Tuple

HERE = os.path.dirname(os.path.realpath(__file__))


def load_names() -> Dict[str, Any]:
    merged: Dict[str, Any] = {"device_plane": [], "module_line": [], "op_line": [],
                              "programs": {}, "custom_call": []}
    for path in sorted(glob.glob(os.path.join(HERE, "trace_names", "*.json"))):
        with open(path) as f:
            part = json.load(f)
        for key in ("device_plane", "module_line", "op_line", "custom_call"):
            merged[key] += part.get(key, [])
        merged["programs"].update(part.get("programs", {}))  # a later file overrides a kind
    return merged


def classify(name: str, op_names, programs: Dict[str, Any]) -> str:
    """The kind of one program execution. A rule is a list of substrings of
    the module's name, or an object: ``module`` (substrings of the name),
    ``has_op`` / ``lacks_op`` (substrings that some / no operation inside the
    execution carries). Both jitted steps are called ``step`` in the program
    today, so their names alone do not tell them apart."""
    for kind, rule in programs.items():
        if isinstance(rule, list):
            rule = {"module": rule}
        if rule.get("module") and not any(p in name for p in rule["module"]):
            continue
        if rule.get("has_op") and not any(p in n for p in rule["has_op"] for n in op_names):
            continue
        if rule.get("lacks_op") and any(p in n for p in rule["lacks_op"] for n in op_names):
            continue
        return kind
    return "other"


_INDEX = re.compile(r"\.\d+$")


def short(name: str) -> str:
    """An operation's name in the trace is its whole HLO line. Keep the
    result's name without its running number (24 layers call one kernel under
    24 numbers), and say when the operation is a custom call (a Mosaic kernel)."""
    head, _, rest = name.partition(" = ")
    head = _INDEX.sub("", head.lstrip("%"))
    return head + " custom-call" if " custom-call(" in rest else head


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Per-name SELF time of possibly nested events on one line: an event's
    duration minus that of the events directly inside it (a ``while`` holds
    its body's operations; counting both would count the time twice)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List[Any]] = []  # [end, name, self]
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            done = stack.pop()
            out[done[1]] += done[2]
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    for done in stack:
        out[done[1]] += done[2]
    return out


def reduce_plane(plane, names, window_ns: float) -> Dict[str, Any]:
    lines = {ln.name: ln for ln in plane.lines}
    op_line = next((lines[n] for n in names["op_line"] if n in lines), None)
    mod_line = next((lines[n] for n in names["module_line"] if n in lines), None)
    if op_line is None:
        return {}
    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in op_line.events]
    if not ops:
        return {}
    mods = []
    if mod_line is not None:
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name) for e in mod_line.events)
    mod_starts = [m[0] for m in mods]

    def module_index(t: float) -> int:
        """The execution that holds instant t. A few tiny programs (a slice,
        a scatter) can be stamped just after the start of the long one that
        follows them: step back over those that ended before t."""
        i = bisect.bisect_right(mod_starts, t) - 1
        for _ in range(8):
            if i < 0 or mods[i][1] >= t:
                break
            i -= 1
        return i if i >= 0 and mods[i][1] >= t else -1

    # Classify every execution by its name and by ALL the operations inside
    # it, before anything is clipped to the window.
    # A module's name ends in the program's fingerprint, so all executions
    # of one name are one program: classify the NAME by the operations seen in
    # any of its executions (the first one of a capture may have begun before
    # the trace did and lack its opening operations).
    names_in: Dict[str, set] = defaultdict(set)
    for s, _, n in ops:
        i = module_index(s)
        if i >= 0:
            names_in[mods[i][2]].add(short(n))
    kind_by_name = {n: classify(n, ops_in, names["programs"]) for n, ops_in in names_in.items()}
    kind_of = [kind_by_name.get(m[2], "other") for m in mods]

    def kind_at(t: float) -> str:
        i = module_index(t)
        return kind_of[i] if i >= 0 else "other"

    t0 = min(s for s, _, _ in ops)
    t1 = t0 + window_ns
    ops = [(max(s, t0), min(e, t1), short(n)) for s, e, n in ops if s < t1 and e > t0]
    busy = union([(s, e) for s, e, _ in ops])
    busy_ns = sum(e - s for s, e in busy)

    programs: Dict[str, Dict[str, Any]] = {}
    by_kind_durs: Dict[str, List[float]] = defaultdict(list)
    module_table: Dict[str, Dict[str, Any]] = {}
    for i, (s, e, n) in enumerate(mods):
        if s >= t0 and e <= t1:  # whole executions only: a clipped one has no duration
            by_kind_durs[kind_of[i]].append(e - s)
            row = module_table.setdefault(n, {"kind": kind_of[i], "durs": []})
            row["durs"].append(e - s)
    ops_by_kind: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    for s, e, n in ops:
        ops_by_kind[kind_at(s)].append((s, e, n))
    op_self = self_times(ops)
    for kind in set(by_kind_durs) | set(ops_by_kind):
        durs = by_kind_durs.get(kind, [])
        selfs = self_times(ops_by_kind.get(kind, []))
        programs[kind] = {
            "count": len(durs),
            "total_s": sum(durs) / 1e9,
            "median_s": statistics.median(durs) / 1e9 if durs else 0.0,
            "ops_s": sum(selfs.values()) / 1e9,
            "custom_call_s": sum(
                v for n, v in selfs.items() if any(p in n for p in names["custom_call"])
            ) / 1e9,
            "top_ops": [[n, v / 1e9] for n, v in sorted(selfs.items(), key=lambda kv: -kv[1])[:8]],
        }

    gaps: Dict[str, float] = defaultdict(float)
    longest = 0.0
    edges = [(t0, t0)] + busy + [(t1, t1)]
    for (_, a_end), (b_start, _) in zip(edges, edges[1:]):
        gap = b_start - a_end
        if gap <= 0:
            continue
        before = kind_at(a_end - 1) if a_end > t0 else "capture start"
        after = kind_at(b_start + 1) if b_start < t1 else "capture end"
        gaps[f"host:unattributed after {before} before {after}"] += gap
        longest = max(longest, gap)
    return {
        "busy_s": busy_ns / 1e9,
        "programs": programs,
        "op_self_s": {n: v / 1e9 for n, v in op_self.items()},
        "gaps_s": {n: v / 1e9 for n, v in gaps.items()},
        "longest_gap_s": longest / 1e9,
        "modules": {
            n: {"kind": r["kind"], "count": len(r["durs"]), "median_s": statistics.median(r["durs"]) / 1e9,
                "total_s": sum(r["durs"]) / 1e9}
            for n, r in sorted(module_table.items(), key=lambda kv: -sum(kv[1]["durs"]))[:40]
        },
    }


def inventory(pd) -> List[Dict[str, Any]]:
    """What the file holds, for reading one trace by hand."""
    out = []
    for plane in pd.planes:
        rows = []
        for ln in plane.lines:
            n, first = 0, None
            for e in ln.events:
                n += 1
                if first is None:
                    first = e.name
            rows.append({"line": ln.name, "events": n, "first": first})
        out.append({"plane": plane.name, "lines": rows[:40]})
    return out


def reduce_dirs(dirs: List[str], window_s: float) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    names = load_names()
    plane_res = [re.compile(p) for p in names["device_plane"]]
    per_plane, inv = [], []
    for d in dirs:
        paths = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            continue
        pd = ProfileData.from_file(paths[-1])
        inv.append({"file": os.path.relpath(paths[-1], d), "bytes": os.path.getsize(paths[-1]),
                    "planes": inventory(pd)})
        for plane in pd.planes:
            if any(r.search(plane.name) for r in plane_res):
                got = reduce_plane(plane, names, window_s * 1e9)
                if got:
                    per_plane.append(got)
    if not per_plane:
        return {"error": "no device plane with operations in the trace", "inventory": inv}
    n = len(per_plane)
    programs: Dict[str, Dict[str, Any]] = {}
    for kind in {k for p in per_plane for k in p["programs"]}:
        rows = [p["programs"][kind] for p in per_plane if kind in p["programs"]]
        programs[kind] = {
            "count": sum(r["count"] for r in rows),
            "total_s": sum(r["total_s"] for r in rows),
            "median_s": statistics.median([r["median_s"] for r in rows if r["count"]] or [0.0]),
            "ops_s": sum(r["ops_s"] for r in rows),
            "custom_call_s": sum(r["custom_call_s"] for r in rows),
        }
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for p in per_plane:
        for k, v in p["op_self_s"].items():
            ops[k] += v / n
        for k, v in p["gaps_s"].items():
            gaps[k] += v / n
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "device_planes": n,
        "window_s": window_s,
        "busy_s": sum(p["busy_s"] for p in per_plane) / n,
        "programs": programs,
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
        "longest_gap_s": max(p["longest_gap_s"] for p in per_plane),
        "modules": per_plane[0]["modules"],
        "program_top_ops": {k: v.get("top_ops") for k, v in per_plane[0]["programs"].items()},
        "inventory": inv,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--window-s", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("dirs", nargs="+")
    args = ap.parse_args()
    summary = reduce_dirs(args.dirs, args.window_s)
    with open(args.out, "w") as f:
        json.dump(summary, f)
    return 1 if "error" in summary else 0


if __name__ == "__main__":
    sys.exit(main())
