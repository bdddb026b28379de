"""A support round's share of its roofline where several chips shared the
work: the least time the chips of the trace could take together for the
round's semantic work over the whole file (`fia_roofline.pairs_work`,
`sets_work`: one chip's published peaks times the number of chips) over
the device time of the slowest chip in the programs that counted it,
since the job waits for that one. `params.rounds` is `pairs` or `sets`.
The bound that applied and the number of chips go to `ctx["notes"]`.
Over a one-chip trace it is `fia_roofline`'s number. Where no such
program ran nothing is returned, never 0."""
from chipbench import reduce

from chipbench.readers import fia_roofline


def slowest_chip_ms(ctx, patterns):
    """The most device time any one chip spent in the programs whose names
    match, a job; nothing where none ran."""
    per_chip = [reduce.total_ns(reduce.matching(dev["modules"], patterns))
                for dev in ctx["devices"].values()]
    slowest = max(per_chip, default=0.0)
    return slowest / 1e6 / ctx["jobs"] if slowest > 0 else None


def read(ctx, params):
    ms = slowest_chip_ms(ctx, params["patterns"])
    z = ctx["sizes"]
    if ms is None or not z.get("frequent"):
        return None
    if params["rounds"] == "pairs":
        ops, nbytes = fia_roofline.pairs_work(z["n"], z["frequent"])
    else:
        ops, nbytes = fia_roofline.sets_work(z["n"], z["frequent"],
                                             z["candidates"])
    if ops <= 0:
        return None
    chips = len(ctx["devices"])
    together = {key: chips * ctx["peaks"][key]
                for key in ("flops_per_s", "hbm_bytes_per_s")}
    roof = reduce.roofline(ops, nbytes, ms / 1e3, together)
    ctx["notes"][f"mesh_{params['rounds']}_roofline_bound"] = roof["bound"]
    ctx["notes"]["mesh_roofline_chips"] = chips
    return roof["share_pct"]
