"""The share of the first chip's idle time inside the traced job that
falls inside one of the program's spans the metric's file lists under
`leaves`, in percent; the table behind it goes to `ctx["notes"]`.

The spans are on the host's clock and the device's operations on the
profiler's. The join goes through the root span (`root` in the file): it
lies inside the job's annotation, so a span that starts at `t0` stands
at `window start + (t0 - root's t0)`, too early by at most
`slack = window length - root's duration`. The reader writes the slack to
`ctx["notes"]["clock_join_slack_ms"]`, and above `MAX_SLACK_SHARE` of the
window it returns nothing. `ctx["notes"]["idle_by_span"]` is
`[[span, idle seconds], ...]`, most first, `unnamed` among the rows: each
idle gap named by what the host was doing. The rows add up to the idle
time. Leaves that overlap (one of another thread: `ctx["spans"]` carries
no thread) share no time twice: the one that began first keeps it."""
from chipbench import reduce

MAX_SLACK_SHARE = 0.01
UNNAMED = "unnamed"


def leaves_on_profiler_clock(spans, names, root, lo):
    """[(start_ns, end_ns, name), ...] of the spans called one of `names`,
    sorted and made disjoint."""
    out, front = [], lo
    for s in sorted((s for s in spans if s["name"] in names),
                    key=lambda s: s["t0"]):
        start = lo + (s["t0"] - root["t0"]) * 1e9
        end = start + s["dur"] * 1e9
        start = max(start, front)
        if end > start:
            out.append((start, end, s["name"]))
            front = end
    return out


def read(ctx, params):
    roots = [s for s in ctx["spans"] if s["name"] == params["root"]]
    if len(roots) != 1 or not ctx["devices"]:
        return None
    lo, hi = ctx["window_ns"]
    slack = (hi - lo) - roots[0]["dur"] * 1e9
    ctx["notes"]["clock_join_slack_ms"] = slack / 1e6
    if not 0.0 <= slack <= MAX_SLACK_SHARE * (hi - lo):
        return None
    leaves = leaves_on_profiler_clock(ctx["spans"], set(params["leaves"]),
                                      roots[0], lo)
    first_chip = next(iter(ctx["devices"].values()))["ops"]
    gaps = reduce.idle_gaps(first_chip, lo, hi)
    idle = sum(e - s for s, e in gaps)
    if idle <= 0:
        return None
    acc, at = {}, 0                       # both lists are sorted: one walk
    for g0, g1 in gaps:
        while at < len(leaves) and leaves[at][1] <= g0:
            at += 1
        for l0, l1, name in leaves[at:]:
            if l0 >= g1:
                break
            acc[name] = acc.get(name, 0.0) + min(l1, g1) - max(l0, g0)
    named = sum(acc.values())
    acc[UNNAMED] = idle - named
    ctx["notes"]["idle_by_span"] = [
        [name, ns / 1e9] for name, ns in sorted(acc.items(),
                                                key=lambda kv: -kv[1])]
    return 100.0 * named / idle
