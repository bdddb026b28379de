"""Reducers: from spans, counters and device events to numbers. Pure
Python over plain lists, so that tests can check them on a small recorded
event list and every PR computes the same number in the same way.

An event is `[name, start_ns, duration_ns]`; a span is
`{"name", "t0", "dur", "tid", "attrs"}` with seconds on the host's clock
(`tid` and `attrs` may be missing: a span recorded before PR 38 kept
neither).
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Sequence  # [name, start_ns, duration_ns]


def span_sum_s(spans: Iterable[Dict], name: str) -> float:
    """Seconds inside spans called `name`."""
    return sum(s["dur"] for s in spans if s["name"] == name)


def matching(events: Iterable[Event], patterns: Sequence[str]) -> List[Event]:
    """Events whose name matches one of the regular expressions."""
    regs = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e[0]) for r in regs)]


def total_ns(events: Iterable[Event]) -> float:
    return float(sum(e[2] for e in events))


def merge_intervals(events: Iterable[Event], lo: float, hi: float
                    ) -> List[Tuple[float, float]]:
    """The union of the events' intervals, clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    spans = sorted((max(e[1], lo), min(e[1] + e[2], hi)) for e in events)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some event ran."""
    return float(sum(e - s for s, e in merge_intervals(events, lo, hi)))


def idle_gaps(events: Iterable[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] in which no event ran, as (start, end)."""
    gaps, at = [], lo
    for s, e in merge_intervals(events, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    return gaps


def short_name(name: str) -> str:
    """An operation's own name out of the HLO text the profiler gives it:
    `%copy.1 = f32[..] copy(..)` reads `copy.1`."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:80]


def top_by_name(events: Iterable[Event], n: int = 10
                ) -> List[List]:
    """[[name, seconds], ...] of the n names with most time."""
    acc: Dict[str, float] = {}
    for e in events:
        name = short_name(e[0])
        acc[name] = acc.get(name, 0.0) + e[2]
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def neighbour_gaps(events: Sequence[Event], lo: float, hi: float
                   ) -> Dict[str, float]:
    """{name: idle ns}: each idle gap of [lo, hi] named by the device
    operation that ended before it and the one that began after it
    (`job start` and `job end` at the window's ends), the gaps of one name
    added up."""
    ops = sorted((e for e in events if e[1] + e[2] > lo and e[1] < hi),
                 key=lambda e: e[1])
    acc: Dict[str, float] = {}
    at, left = lo, "job start"          # the busy front and who set it
    for name, start, dur in ops:
        if start > at:
            key = f"{left} -> {short_name(name)}"
            acc[key] = acc.get(key, 0.0) + (start - at)
        if start + dur > at:
            at, left = start + dur, short_name(name)
    if hi > at:
        key = f"{left} -> job end"
        acc[key] = acc.get(key, 0.0) + (hi - at)
    return acc


def idle_by_neighbours(events: Sequence[Event], lo: float, hi: float,
                       n: int = 10) -> List[List]:
    """[[name, idle seconds], ...] of the n names of `neighbour_gaps` with
    most idle time, most first. It says which program ends a gap, not what
    the host was doing: `innermost_on_profiler_clock` says that."""
    rows = sorted(neighbour_gaps(events, lo, hi).items(),
                  key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


# ------------------------------------------- the host's clock on the trace's
#: the clock join is refused where its slack is above this share of the
#: window, or below 0
MAX_SLACK_SHARE = 0.01
NO_SPAN = "no span"
REST = "rest"


def clock_join(spans: Sequence[Dict], root: str, lo: float, hi: float):
    """(root span, slack ns) of the join of the host's clock to the
    profiler's through the one span called `root`, or None where there is
    not exactly one. The root lies inside the window [lo, hi], so a span
    that starts at `t0` stands at `lo + (t0 - root's t0)`, too early by at
    most `slack = window length - root's duration`."""
    roots = [s for s in spans if s["name"] == root]
    if len(roots) != 1:
        return None
    return roots[0], (hi - lo) - roots[0]["dur"] * 1e9


def join_holds(slack: float, lo: float, hi: float) -> bool:
    return 0.0 <= slack <= MAX_SLACK_SHARE * (hi - lo)


def on_profiler_clock(span: Dict, root: Dict, lo: float
                      ) -> Tuple[float, float]:
    """(start ns, end ns) of a span by the join through `root`."""
    start = lo + (span["t0"] - root["t0"]) * 1e9
    return start, start + span["dur"] * 1e9


def leaves_on_profiler_clock(spans, names, root, lo):
    """[(start_ns, end_ns, name), ...] of the spans called one of `names`,
    sorted and made disjoint: of two that overlap, the one that began
    first keeps the time."""
    out, front = [], lo
    for s in sorted((s for s in spans if s["name"] in names),
                    key=lambda s: s["t0"]):
        start, end = on_profiler_clock(s, root, lo)
        start = max(start, front)
        if end > start:
            out.append((start, end, s["name"]))
            front = end
    return out


def job_thread(spans: Sequence[Dict], root: Dict) -> List[Dict]:
    """The spans of the root's thread. A span without `tid` counts as the
    job thread's."""
    tid = root.get("tid")
    return [s for s in spans if s.get("tid", tid) == tid]


def innermost_on_profiler_clock(spans, root, lo, hi):
    """[(start_ns, end_ns, name), ...] that cut [lo, hi] without a gap,
    each piece named by the innermost span of the job's thread that covers
    it, or `NO_SPAN`. The innermost is the shortest: of spans that nest it
    is the one inside the others. Of two alike it is the one begun later,
    and then the name that sorts last (`a.b.c` before `a.b`)."""
    placed = []
    for s in job_thread(spans, root):
        start, end = on_profiler_clock(s, root, lo)
        start, end = max(start, lo), min(end, hi)
        if end > start:
            placed.append((start, end, s["name"]))
    placed.sort()
    cuts = sorted({lo, hi} | {p[0] for p in placed} | {p[1] for p in placed})
    out, active, nxt = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(placed) and placed[nxt][0] <= a:
            active.append(placed[nxt])
            nxt += 1
        active = [p for p in active if p[1] > a]
        inner = max(active, key=lambda p: (p[0] - p[1], p[0], p[2]))[2] \
            if active else NO_SPAN
        out.append((a, b, inner))
    return out


def idle_inside(pieces, gaps) -> Dict[str, float]:
    """{name: ns} of the idle gaps that fall inside each of the sorted,
    disjoint (start, end, name) pieces; over the pieces of
    `innermost_on_profiler_clock`, which leave no gap, the values add up to
    the gaps' length."""
    acc: Dict[str, float] = {}
    at = 0                              # both lists are sorted: one walk
    for g0, g1 in gaps:
        while at < len(pieces) and pieces[at][1] <= g0:
            at += 1
        for p0, p1, name in pieces[at:]:
            if p0 >= g1:
                break
            acc[name] = acc.get(name, 0.0) + min(p1, g1) - max(p0, g0)
    return acc


def top_with_rest(acc: Dict[str, float], keep: int) -> List[List]:
    """[[name, seconds], ...], most first: all of `acc` where it has at most
    `keep + 1` names, else the `keep` largest and `REST` with the others'
    sum, so that the rows always add up to the whole."""
    rows = sorted(acc.items(), key=lambda kv: -kv[1])
    if len(rows) > keep + 1:
        rows = rows[:keep] + [(REST, sum(ns for _n, ns in rows[keep:]))]
    return [[name, ns / 1e9] for name, ns in rows]


def hbm_peak(readings: Sequence[Dict]) -> Dict[str, int]:
    """What one chip held at its fullest, from its `memory_stats()` read
    at the start of each job of the window and at the window's close.

    The runtime counts two things apart, and both are HBM that nothing
    else can have: live buffers (`bytes_in_use`, with its own
    `peak_bytes_in_use`), and the scratch it sets aside for the
    temporaries of the programs it has loaded (`bytes_reserved`), which
    stays set aside between calls. Of the first the highest peak read
    counts; of the second the *least* reading, which was therefore held
    all through the window, beside the first's peak whenever that came.
    A backend that says nothing reads 0."""
    in_use = max((int(r.get("peak_bytes_in_use", 0)) for r in readings),
                 default=0)
    reserved = min((int(r.get("bytes_reserved", 0)) for r in readings),
                   default=0)
    return {"in_use_peak": in_use, "reserved": reserved,
            "peak": in_use + reserved}


def load_peaks(device_kind: str, path: Optional[str] = None) -> Dict:
    """The chip's published peaks, by `device_kind`. A device the table
    does not hold is an error, never a default."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}: add the "
            "device with its published numbers and their source")
    return table[device_kind]


def roofline(ops: float, nbytes: float, seconds: float, peaks: Dict
             ) -> Optional[Dict]:
    """The least time the chip could take for `ops` operations and
    `nbytes` bytes over the time taken, in percent, with the bound that
    applied. Nothing when no time was measured."""
    if seconds <= 0:
        return None
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return {"share_pct": 100.0 * max(t_ops, t_mem) / seconds,
            "bound": "compute" if t_ops >= t_mem else "memory",
            "least_s": max(t_ops, t_mem)}


def knn_manhattan_work(nq: int, n: int, d: int, k: int, calls: int = 1
                       ) -> Tuple[float, float]:
    """(operations, bytes) the exact manhattan top-k needs for `nq` valid
    queries against `n` valid train rows of `d` attributes, from semantic
    sizes alone: a subtract, an absolute value and an add per pair and
    attribute; the index read once per kernel call at 4 d bytes a row,
    the queries read and k distances and k indices written once. Block
    sizes, padding and the operands' layout are the kernel's business
    and do not enter."""
    ops = 3.0 * d * nq * n
    nbytes = calls * 4.0 * d * n + 4.0 * d * nq + 8.0 * k * nq
    return ops, nbytes
