"""Reducers: from spans, counters and device events to numbers. Pure
Python over plain lists, so that tests can check them on a small recorded
event list and every PR computes the same number in the same way.

An event is `[name, start_ns, duration_ns]`; a span is
`{"name", "t0", "dur"}` with seconds on the host's clock.
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


def idle_by_neighbours(events: Sequence[Event], lo: float, hi: float,
                       n: int = 10) -> List[List]:
    """[[name, idle seconds], ...], most first: each idle gap of [lo, hi]
    named by the device operation that ended before it and the one that
    began after it (`job start` and `job end` at the window's ends), the
    gaps of one name added up. The program has no spans on the profiler's
    clock yet, so this is as far as a gap can be named."""
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
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
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
