"""Milliseconds of the program's span of one name that none of the spans
the metric's file lists under `children` covers, per job: the span's
duration less the union of the children's intervals, clipped to it.
The file names the job thread's leaves, whatever thread a span of that
name was recorded on (the spans carry their thread since PR 38; the list
stays until a PR shows that the two agree); a program without the span
leaves the metric out."""
from chipbench import reduce


def read(ctx, params):
    roots = [s for s in ctx["spans"] if s["name"] == params["span"]]
    if not roots:
        return None
    kids = [[s["name"], s["t0"], s["dur"]] for s in ctx["spans"]
            if s["name"] in params["children"]]
    bare = sum(r["dur"] - reduce.busy_ns(kids, r["t0"], r["t0"] + r["dur"])
               for r in roots)
    return 1000.0 * bare / ctx["jobs"]
