"""The kNN kernel's share of its roofline: the least time the chip could
take for the job's semantic work (`reduce.knn_manhattan_work`) over the
device time of the kernel's programs. The bound that applied goes to
`ctx["notes"]`."""
from chipbench import reduce

from chipbench.readers import module_ms


def read(ctx, params):
    ms = module_ms.device_ms(ctx, params["patterns"])
    if ms is None:
        return None
    z = ctx["sizes"]
    ops, nbytes = reduce.knn_manhattan_work(z["nq"], z["n"], z["d"], z["k"],
                                            calls=z["kernel_calls"])
    roof = reduce.roofline(ops, nbytes, ms / 1e3, ctx["peaks"])
    ctx["notes"]["knn_kernel_roofline_bound"] = roof["bound"]
    return roof["share_pct"]
