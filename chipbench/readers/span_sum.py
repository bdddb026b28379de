"""Milliseconds inside the program's spans of one name, per job."""
from chipbench import reduce


def read(ctx, params):
    if not ctx["spans"]:
        return None
    return 1000.0 * reduce.span_sum_s(ctx["spans"], params["span"]) / ctx["jobs"]
