"""Milliseconds the device spent in the operations whose names match the
metric's patterns (regular expressions over the name the trace gives an
operation: `%all-reduce.1 = s32[..] all-reduce(..)`), per job, averaged
over the chips used. The names found go to
`ctx["notes"]["<metric>_ops"]`, so that a pattern that has begun to match
something else shows. Where no chip ran such an operation nothing is
returned, never 0."""
from chipbench import reduce


def read(ctx, params):
    if not ctx["devices"]:
        return None
    per_chip, names = [], set()
    for dev in ctx["devices"].values():
        found = reduce.matching(dev["ops"], params["patterns"])
        per_chip.append(reduce.total_ns(found))
        names.update(reduce.short_name(e[0]) for e in found)
    total = sum(per_chip) / len(per_chip)
    if total <= 0:
        return None
    ctx["notes"][params.get("note", "op_ms") + "_ops"] = sorted(names)
    return total / 1e6 / ctx["jobs"]
