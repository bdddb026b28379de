"""The share of the traced job in which no operation ran on the device,
in percent, averaged over the chips used."""
from chipbench import reduce


def read(ctx, params):
    if not ctx["devices"]:
        return None
    lo, hi = ctx["window_ns"]
    busy = [reduce.busy_ns(dev["ops"], lo, hi)
            for dev in ctx["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
