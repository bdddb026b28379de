"""Roll an avenir-trace Chrome-trace file into per-phase tables.

The span flight recorder (avenir_tpu.obs.trace) exports ``traceEvents``
JSON that Perfetto / chrome://tracing render on a timeline; this tool is
the terminal view of the same file: a per-phase rollup (count, total,
self, mean, p95, max per span name; self is a span's time less what the
spans it encloses on its thread cover, the column that adds up), a per-chunk breakdown of the streaming
phases (read / parse / fold), and a stall-attribution section that ranks
the producer/consumer stall sources by total blocked time — the first
question profiling-guided tuning asks ("where does the time go per
chunk, and who is waiting on whom").

Usage:
    python tools/trace_report.py TRACE.json [--top N] [--json]

The rollup quantiles come from the same log-bucketed accumulator the
job server's latency surface uses (avenir_tpu.obs.histogram), so a number
printed here and one printed by ``python -m avenir_tpu stats`` mean the
same thing.
"""

import argparse
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from avenir_tpu.obs.histogram import LatencyHistogram  # noqa: E402

#: span names whose duration is time BLOCKED, not time working — ranked
#: separately so a stall can never hide inside a work phase's mean
STALL_PREFIX = "stream.stall."


def load_events(path):
    """The complete-event spans of a Chrome-trace file as dicts with
    millisecond durations (other event types are skipped)."""
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, list):            # the bare JSON-array trace form
        events, meta = doc, {}
    else:
        events, meta = doc.get("traceEvents", []), doc.get("metadata", {})
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        out.append({"name": ev.get("name", "?"),
                    "dur_ms": float(ev.get("dur", 0.0)) / 1000.0,
                    "ts": float(ev.get("ts", 0.0)),
                    "tid": ev.get("tid"),
                    "args": ev.get("args") or {}})
    return out, meta


def add_self_times(events):
    """Give every event its `self_ms`: its duration less what the spans it
    encloses on its own thread cover. Spans nest (a parent only encloses),
    so a total over nested spans would count the same time once per level;
    self times add up to the outermost span's duration."""
    by_tid = defaultdict(list)
    for ev in events:
        ev["self_ms"] = ev["dur_ms"]
        by_tid[ev["tid"]].append(ev)
    for evs in by_tid.values():
        evs.sort(key=lambda ev: (ev["ts"], -ev["dur_ms"]))
        open_spans = []                  # enclosing spans, outermost first
        for ev in evs:
            while open_spans and _end_us(open_spans[-1]) <= ev["ts"]:
                open_spans.pop()
            if open_spans:
                parent = open_spans[-1]
                inside = min(_end_us(ev), _end_us(parent)) - ev["ts"]
                parent["self_ms"] -= inside / 1000.0
            open_spans.append(ev)
    return events


def _end_us(ev):
    return ev["ts"] + ev["dur_ms"] * 1000.0


def self_totals(events):
    """{name: summed self_ms} of events `add_self_times` has seen."""
    totals = defaultdict(float)
    for ev in events:
        totals[ev["name"]] += ev["self_ms"]
    return dict(totals)


def rollup(events):
    """{name: LatencyHistogram-of-ms} across all spans."""
    hists = defaultdict(LatencyHistogram)
    for ev in events:
        hists[ev["name"]].add(ev["dur_ms"])
    return dict(hists)


def phase_table(hists, wall_ms, self_ms):
    """The per-phase rows, widest total first. `wall_ms` (trace extent)
    scales the %-of-wall column; phases overlap across threads and
    parents enclose their children, so the percentages legitimately sum
    past 100. `self_ms` ({name: ms}, `self_totals`) is the column that
    adds up."""
    rows = []
    for name, h in hists.items():
        rows.append({"phase": name, "count": h.count,
                     "total_ms": round(h.total, 3),
                     "self_ms": round(self_ms.get(name, 0.0), 3),
                     "mean_ms": round(h.mean, 3),
                     "p95_ms": round(h.quantile(95), 3),
                     "max_ms": round(h.max_val, 3),
                     "pct_wall": round(100.0 * h.total / wall_ms, 1)
                     if wall_ms else 0.0})
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def chunk_table(events):
    """Per-sink fold totals: the ``stream.fold`` spans carry their sink
    label, so this is the 'which fold owns the chunk time' answer."""
    per_sink = defaultdict(LatencyHistogram)
    for ev in events:
        if ev["name"] == "stream.fold":
            per_sink[str(ev["args"].get("sink", "?"))].add(ev["dur_ms"])
    rows = [{"sink": sink, "chunks": h.count,
             "total_ms": round(h.total, 3),
             "mean_ms": round(h.mean, 3),
             "p95_ms": round(h.quantile(95), 3)}
            for sink, h in per_sink.items()]
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def stall_table(events):
    """Stall sources ranked by total blocked time. ``producer`` stalls
    mean the consumer (fold/parse downstream) is the bottleneck;
    ``consumer`` stalls mean the producer (read/parse upstream) is."""
    per_name = defaultdict(LatencyHistogram)
    for ev in events:
        if ev["name"].startswith(STALL_PREFIX):
            per_name[ev["name"]].add(ev["dur_ms"])
    rows = [{"stall": name, "count": h.count,
             "total_ms": round(h.total, 3),
             "mean_ms": round(h.mean, 3),
             "max_ms": round(h.max_val, 3)}
            for name, h in per_name.items()]
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def build_report(path, top=20):
    events, meta = load_events(path)
    if not events:
        return {"trace": path, "spans": 0, "error": "no complete events"}
    t_lo = min(ev["ts"] for ev in events)
    t_hi = max(ev["ts"] + ev["dur_ms"] * 1000.0 for ev in events)
    wall_ms = (t_hi - t_lo) / 1000.0
    add_self_times(events)
    work = [ev for ev in events
            if not ev["name"].startswith(STALL_PREFIX)]
    return {"trace": path,
            "spans": len(events),
            "dropped_spans": int(meta.get("dropped_spans", 0)),
            "wall_ms": round(wall_ms, 3),
            "threads": len({ev["tid"] for ev in events}),
            "phases": phase_table(rollup(work), wall_ms,
                                  self_totals(work))[:top],
            "folds": chunk_table(events)[:top],
            "stalls": stall_table(events)[:top]}


def _print_rows(rows, cols, title):
    if not rows:
        return
    print(f"\n{title}")
    widths = {c: max(len(c), *(len(str(r[c])) for r in rows))
              for c in cols}
    print("  " + "  ".join(c.rjust(widths[c]) for c in cols))
    for r in rows:
        print("  " + "  ".join(str(r[c]).rjust(widths[c]) for c in cols))


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="trace_report",
        description="per-phase/per-chunk rollup of an avenir-trace file")
    ap.add_argument("trace", help="Chrome-trace JSON (obs export, or a "
                                  "directory containing trace.json)")
    ap.add_argument("--top", type=int, default=20,
                    help="rows per table (default 20)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object")
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = os.path.join(path, "trace.json")
    try:
        report = build_report(path, top=args.top)
    except (OSError, ValueError) as e:
        print(f"cannot read trace {path!r}: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(report, indent=1))
        return 0 if "error" not in report else 1
    if "error" in report:
        print(f"{path}: {report['error']}")
        return 1
    print(f"trace {path}: {report['spans']} spans "
          f"({report['dropped_spans']} dropped) across "
          f"{report['threads']} thread(s), {report['wall_ms']:.1f}ms wall")
    _print_rows(report["phases"],
                ["phase", "count", "total_ms", "self_ms", "mean_ms",
                 "p95_ms", "max_ms", "pct_wall"], "per-phase rollup (ms):")
    _print_rows(report["folds"],
                ["sink", "chunks", "total_ms", "mean_ms", "p95_ms"],
                "per-sink fold time (ms):")
    _print_rows(report["stalls"],
                ["stall", "count", "total_ms", "mean_ms", "max_ms"],
                "stall attribution (ms, top sources first):")
    if report["stalls"]:
        top = report["stalls"][0]
        side = ("consumer is the bottleneck (folds can't keep up)"
                if top["stall"].endswith("producer")
                else "producer is the bottleneck (read/parse can't keep up)")
        print(f"\ntop stall: {top['stall']} "
              f"({top['total_ms']:.1f}ms total) -> {side}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
