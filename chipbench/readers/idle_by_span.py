"""The share of the first chip's idle time inside the traced job that
falls inside one of the program's spans the metric's file lists under
`leaves`, in percent; the table behind it goes to `ctx["notes"]`.

The spans are on the host's clock and the device's operations on the
profiler's. The join goes through the root span (`root` in the file), by
`reduce.clock_join`, the join `run.py`'s `breakdown.idle_gaps` uses too.
The reader writes the slack to `ctx["notes"]["clock_join_slack_ms"]`, and
above `reduce.MAX_SLACK_SHARE` of the window it returns nothing.
`ctx["notes"]["idle_by_span"]` is `[[span, idle seconds], ...]`, most
first, `unnamed` among the rows: each idle gap named by the listed leaf
the host was in. The rows add up to the idle time. Leaves that overlap
share no time twice: the one that began first keeps it. The list is the
file's, whatever thread a span was recorded on."""
from chipbench import reduce

UNNAMED = "unnamed"


def read(ctx, params):
    lo, hi = ctx["window_ns"]
    joined = reduce.clock_join(ctx["spans"], params["root"], lo, hi)
    if joined is None or not ctx["devices"]:
        return None
    root, slack = joined
    ctx["notes"]["clock_join_slack_ms"] = slack / 1e6
    if not reduce.join_holds(slack, lo, hi):
        return None
    leaves = reduce.leaves_on_profiler_clock(ctx["spans"], set(params["leaves"]),
                                             root, lo)
    first_chip = next(iter(ctx["devices"].values()))["ops"]
    gaps = reduce.idle_gaps(first_chip, lo, hi)
    idle = sum(e - s for s, e in gaps)
    if idle <= 0:
        return None
    acc = reduce.idle_inside(leaves, gaps)
    named = sum(acc.values())
    acc[UNNAMED] = idle - named
    ctx["notes"]["idle_by_span"] = [
        [name, ns / 1e9] for name, ns in sorted(acc.items(),
                                                key=lambda kv: -kv[1])]
    return 100.0 * named / idle
