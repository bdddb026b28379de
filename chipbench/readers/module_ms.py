"""Milliseconds the device spent in the jitted programs whose names match
the metric's patterns, per job, averaged over the chips used."""
from chipbench import reduce


def device_ms(ctx, patterns):
    if not ctx["devices"]:
        return None
    per_chip = [reduce.total_ns(reduce.matching(dev["modules"], patterns))
                for dev in ctx["devices"].values()]
    total = sum(per_chip) / len(per_chip)
    return total / 1e6 / ctx["jobs"] if total > 0 else None


def read(ctx, params):
    return device_ms(ctx, params["patterns"])
