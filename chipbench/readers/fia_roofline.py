"""A support round's share of its roofline: the least time the chip could
take for the round's semantic work (`pairs_work`, `sets_work`) over the
device time of the programs that counted it. `params.rounds` is `pairs`
(length 2) or `sets` (every length from 3). The bound that applied goes to
`ctx["notes"]`. Where no such program ran nothing is returned, never 0."""
from chipbench import reduce

from chipbench.readers import module_ms


def pairs_work(n, frequent):
    """(operations, bytes) the supports of all pairs of `frequent` items
    over `n` baskets need, from semantic sizes alone: a multiply and an
    add a basket and pair, `2 n C2` with `C2 = V'(V'-1)/2`; the baskets'
    bits read once, `n V' / 8` bytes, and a 4-byte count a pair written.
    A full Gram matrix does twice the pairs' count, so a program that
    forms one cannot pass 50% by operations. Blocks, padding, dtypes and
    layout are the program's business and do not enter."""
    c2 = frequent * (frequent - 1) / 2.0
    return 2.0 * n * c2, n * frequent / 8.0 + 4.0 * c2


def sets_work(n, frequent, candidates):
    """(operations, bytes) the supports of the candidates of every length
    from 3 need: `candidates` is {k: Ck}; a round tests k items of each
    candidate in each basket, `2 n Ck k`, reads the baskets' bits once and
    writes a 4-byte count a candidate. A matmul against candidate masks
    does V'/k times that count, so its share says how much room another
    form has."""
    ops = nbytes = 0.0
    for k, ck in candidates.items():
        if int(k) >= 3:
            ops += 2.0 * n * ck * int(k)
            nbytes += n * frequent / 8.0 + 4.0 * ck
    return ops, nbytes


def read(ctx, params):
    ms = module_ms.device_ms(ctx, params["patterns"])
    z = ctx["sizes"]
    if ms is None or not z.get("frequent"):
        return None
    if params["rounds"] == "pairs":
        ops, nbytes = pairs_work(z["n"], z["frequent"])
    else:
        ops, nbytes = sets_work(z["n"], z["frequent"], z["candidates"])
    if ops <= 0:
        return None
    roof = reduce.roofline(ops, nbytes, ms / 1e3, ctx["peaks"])
    ctx["notes"][f"fia_{params['rounds']}_roofline_bound"] = roof["bound"]
    return roof["share_pct"]
