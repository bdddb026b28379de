"""The forest's level passes' share of their roofline: the least time the
chip could take for the passes' semantic work (`level_work`) over the
device time of the level programs. The bound that applied goes to
`ctx["notes"]`. Where no level program ran nothing is returned, never 0."""
from chipbench import reduce

from chipbench.readers import module_ms


def level_work(n, trees, splits, levels):
    """(operations, bytes) the level histograms of a forest need, from
    semantic sizes alone: a pass adds every row into one cell of every
    tree's histogram for every candidate split, `n x trees x splits`
    accumulations, and reads a segment id a split and a label a row, a
    leaf id and a bootstrap count a tree and row, one byte each: the
    fewest any form can read. `levels` passes: one a level the trees
    grew, one for the final counts. The leaf advances between the levels
    are in the device time and not in the work, so the share errs low.
    Row blocks, padding, dtypes and layout are the program's business and
    do not enter."""
    ops = float(levels) * n * trees * splits
    nbytes = float(levels) * (n * (splits + 1) + 2.0 * n * trees)
    return ops, nbytes


def read(ctx, params):
    ms = module_ms.device_ms(ctx, params["patterns"])
    if ms is None:
        return None
    z = ctx["sizes"]
    ops, nbytes = level_work(z["n"], z["trees"], z["splits"], z["levels"])
    roof = reduce.roofline(ops, nbytes, ms / 1e3, ctx["peaks"])
    ctx["notes"]["forest_level_roofline_bound"] = roof["bound"]
    return roof["share_pct"]
