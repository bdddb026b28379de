"""Scaling-efficiency measurement over device-mesh subsets.

BASELINE.md's north-star metric includes "scaling efficiency 8->256 chips";
the reference itself scaled by adding Hadoop nodes, with the shuffle as the
scaling bottleneck. Here the equivalent measurement is weak scaling of the
mesh kernels (`parallel/distributed.py`): fix the per-device workload, grow
the device count, and report how close total throughput stays to linear.
XLA's psum/all_gather over the mesh replace the shuffle, so the efficiency
loss is exactly the collective cost.

On a host with fewer real chips than requested the harness runs on virtual
CPU devices (`--xla_force_host_platform_device_count`). Virtual devices
share the host's cores, so absolute rates are meaningless and even relative
efficiency mixes collective overhead with core contention — the numbers are
a smoke-level proxy until real multi-chip hardware is attached; the shape of
the harness (and the sharding programs it runs) is identical either way.
"""

from __future__ import annotations

import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from avenir_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, data_mesh

# NB weak-scaling workload dims, shared by _nb_rate and the analytic
# per-device traffic fields in measure_scaling
_NB_CLASSES, _NB_FEAT, _NB_BMAX = 2, 8, 10


def collective_payload_model(family: str, mesh_shape: Dict[str, int],
                             **dims: int) -> int:
    """Analytic collective payload (bytes) of ONE step of a distributed
    family from `parallel/distributed.py` on a mesh of shape `mesh_shape`.

    This is the single source of truth the IR-level auditor
    (`analysis/ir.py`) asserts compiled HLO against, per family. "Payload"
    means the summed byte size of every collective instruction's result
    shapes — exactly what :func:`hlo_collective_payloads` extracts — so
    model and measurement count the same thing regardless of how XLA's
    combiner fuses or splits the ops.

    Family keys match ``distributed.FAMILIES``; `dims` are the family's
    workload dimensions (the manifest pins concrete values):

    - ``nb_train``:     psum of [F, K, B] f32 counts + [K] f32 class counts
    - ``knn_topk``:     two tiled all-gathers over 'model' of the per-query
                        candidate merge: [nq/data, model*k] f32 + i32
                        (0 when the mesh has no model axis — no collective)
    - ``tree_level``:   psum of the [L, NS, S, K] f32 level histogram
    - ``lr_step``:      psum of the [D] f32 gradient + f32 weight total
    - ``markov_counts``: psum of [C, S, S] f32 bigram counts
    - ``apriori_support``: psum of [C] s32 candidate supports
    - ``bandit_select``: 0 — the map-only per-group job has no collective
    - ``crosscount``:   psum of the [A, B] f32 contingency table
    """
    data_n = mesh_shape.get(DATA_AXIS, 1)
    model_n = mesh_shape.get(MODEL_AXIS, 1)
    if family == "nb_train":
        return (dims["n_feat"] * dims["num_classes"] * dims["bmax"]
                + dims["num_classes"]) * 4
    if family == "knn_topk":
        if model_n <= 1:
            return 0
        return (dims["nq"] // data_n) * model_n * dims["k"] * (4 + 4)
    if family == "tree_level":
        return (dims["n_leaves"] * dims["n_splits"] * dims["smax"]
                * dims["num_classes"]) * 4
    if family == "lr_step":
        return (dims["d"] + 1) * 4
    if family == "markov_counts":
        return dims["n_classes"] * dims["n_states"] * dims["n_states"] * 4
    if family == "apriori_support":
        return dims["n_cand"] * 4
    if family == "bandit_select":
        return 0
    if family == "crosscount":
        return dims["bins_a"] * dims["bins_b"] * 4
    raise KeyError(f"no analytic payload model for family {family!r}")


def nb_payload_bytes() -> int:
    """All-reduce payload of the weak-scaling NB step: the [F, K, B] count
    tensor + [K] class counts in f32. The single source of the number the
    compiled-HLO check validates and the projections consume."""
    return collective_payload_model(
        "nb_train", {}, n_feat=_NB_FEAT, num_classes=_NB_CLASSES,
        bmax=_NB_BMAX)


def _timed_scalar(many_fn, *args) -> float:
    """Best-of-2 wall clock of the jitted scalar-reducing many_fn, warmup
    excluded, result forced to host with float(): every measurement runs
    its iterations inside one program and fetches the scalar, so the clock
    stops when the device has finished."""
    import jax.numpy as jnp

    _ = float(many_fn(*args))
    best = np.inf
    for s in (1, 2):
        shifted = (jnp.roll(args[0], s, axis=0),) + args[1:]
        t0 = time.perf_counter()
        _ = float(many_fn(*shifted))
        best = min(best, time.perf_counter() - t0)
    return best


_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1}
# matches sync collectives AND the async '-start' form (the XLA:TPU
# default in compiled HLO); '-done' halves are skipped so an async pair
# counts its payload once
_COLLECTIVE_LINE = re.compile(
    r"(?<!%)\b(all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|collective-permute)(-start)?\s*\(")
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def hlo_collective_payloads(compiled_text: str) -> List[Dict]:
    """Collective ops in a compiled HLO module with their payload bytes.

    This is the VALIDATION side of the scaling story: the analytic
    per-device traffic model (ring all-reduce moves 2(P-1)/P x payload)
    is only as good as its payload numbers, and those can silently grow
    when XLA reduces more than the model assumes. Parsing the compiled
    module pins them to what actually ships over the interconnect.
    Returns [{op, payload_bytes}] for each collective instruction (the
    payload is the summed byte size of the op's result shapes; for a
    tuple all-reduce that is the full reduced state)."""
    out = []
    for ln in compiled_text.splitlines():
        eq = ln.find("=")
        if eq < 0:
            continue
        # the result shapes sit between '=' and the op name; search only
        # the right-hand side, and reject %references to collective
        # instructions appearing as operands of other ops
        rhs = ln[eq + 1:]
        m = _COLLECTIVE_LINE.search(rhs)
        if not m:
            continue
        size = 0
        for dt, dims in _SHAPE.findall(rhs[: m.start()]):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _DTYPE_BYTES[dt]
        out.append({"op": m.group(1), "payload_bytes": size})
    return out


def project_efficiency(
    per_device_step_seconds: float,
    allreduce_payload_bytes: float,
    counts: Sequence[int] = (8, 64, 256),
    ici_bytes_per_sec: float = 9.0e10,
    ici_hop_latency_s: float = 1.0e-6,
) -> List[Dict]:
    """Weak-scaling efficiency projection for P chips on one ICI domain.

    efficiency(P) = t_compute / (t_compute + t_comm(P)). The collective
    model is a dimension-wise all-reduce on a (near-)square 2D torus —
    the v5e pod topology: bandwidth term 2(P-1)/P x payload / bw, latency
    term 2 x sum(2(dim-1)) hops. Bandwidth/latency defaults are public
    v5e ICI ballparks (O(100) GB/s per chip, ~1us per hop).

    What the model says for this workload family: payloads are
    sub-kilobyte, so the bandwidth term is always noise and the knee is
    pure hop latency — ~60us at 256 chips. Against the bench's measured
    ~440us NB step (65k rows/device) that costs ~12%; the chunked
    streaming fold (accumulate(defer=True), multi-million-row chunks per
    device between flushes) pushes steps to multi-millisecond and the
    projection back to ~1.0. Scale-out is therefore an amortization knob
    the framework already exposes, not a redesign."""
    rows = []
    for p in counts:
        # near-square 2D torus factorization of p
        d1 = int(np.sqrt(p))
        while p % d1:
            d1 -= 1
        d2 = p // d1
        hops = 2 * ((d1 - 1) + (d2 - 1)) if p > 1 else 0
        t_comm = (2.0 * (p - 1) / p * allreduce_payload_bytes
                  / ici_bytes_per_sec + hops * ici_hop_latency_s)
        eff = per_device_step_seconds / (per_device_step_seconds + t_comm)
        rows.append({"devices": int(p), "projected_efficiency": round(eff, 4),
                     "torus": [d1, d2],
                     "t_compute_us": round(per_device_step_seconds * 1e6, 1),
                     "t_collective_us": round(t_comm * 1e6, 2)})
    return rows


def _nb_rate(mesh, rows: int, iters: int) -> float:
    """Weak-scaling NB sufficient-stat rate (rows/sec) on the given mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from avenir_tpu.parallel.distributed import distributed_nb_train_fn

    k_classes, n_feat, bmax = _NB_CLASSES, _NB_FEAT, _NB_BMAX
    rng = np.random.default_rng(0)
    codes = rng.integers(0, bmax, (rows, n_feat)).astype(np.int32)
    labels = rng.integers(0, k_classes, rows).astype(np.int32)
    w = np.ones((rows,), np.float32)
    shard = NamedSharding(mesh, P(mesh.axis_names))
    step = distributed_nb_train_fn(mesh, k_classes, bmax)

    codes_d = jax.device_put(codes, shard)
    labels_d = jax.device_put(labels, shard)
    w_d = jax.device_put(w, shard)

    # the step index rides as an operand, not a closure: a closure-captured
    # `iters` would bake the shape into the trace and recompile per value
    steps = jnp.arange(1, iters + 1)

    @jax.jit
    def many(codes_d, labels_d, w_d, steps):
        def body(i):
            # distinct data per step: on-device roll along the feature axis
            # keeps the row sharding intact (no cross-shard traffic)
            out = step(jnp.roll(codes_d, i, axis=1), labels_d, w_d)
            return sum(jnp.sum(o) for o in jax.tree.leaves(out))
        return jax.lax.map(body, steps).sum()

    return rows * iters / _timed_scalar(many, codes_d, labels_d, w_d, steps)


def _nb_compiled_collectives(mesh) -> List[Dict]:
    """Compile the sharded NB train step on `mesh` and return its
    collective instructions (hlo_collective_payloads)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from avenir_tpu.parallel.distributed import distributed_nb_train_fn

    rows = 8 * len(mesh.devices.flat)
    shard = NamedSharding(mesh, P(mesh.axis_names))
    step = distributed_nb_train_fn(mesh, _NB_CLASSES, _NB_BMAX)
    args = [
        jax.device_put(np.zeros((rows, _NB_FEAT), np.int32), shard),
        jax.device_put(np.zeros((rows,), np.int32), shard),
        jax.device_put(np.ones((rows,), np.float32), shard),
    ]
    compiled = jax.jit(step).lower(*args).compile()
    return hlo_collective_payloads(compiled.as_text())


def _knn_compiled_collectives(mesh, k: int = 5) -> Tuple[List[Dict], int]:
    """Compile the MODEL-parallel KNN candidate-merge step on `mesh` and
    return (collective instructions, analytic all-gather bytes): each
    device gathers [nq_local, P_model*k] distances (f32) + labels (i32) —
    the k*P candidate merge, NOT the n_train rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from avenir_tpu.parallel.distributed import distributed_topk_fn

    data_n = mesh.shape[DATA_AXIS]
    model_n = mesh.shape.get(MODEL_AXIS, 1)
    nq, train, d = 8 * data_n, 16 * model_n, 8
    step = distributed_topk_fn(mesh, k=k, metric="euclidean")
    args = [
        jax.device_put(np.zeros((nq, d), np.float32),
                       NamedSharding(mesh, P(DATA_AXIS, None))),
        jax.device_put(np.zeros((train, d), np.float32),
                       NamedSharding(mesh, P(MODEL_AXIS, None))),
        jax.device_put(np.zeros((train,), np.int32),
                       NamedSharding(mesh, P(MODEL_AXIS))),
    ]
    compiled = step.lower(*args).compile()
    analytic = collective_payload_model(
        "knn_topk", dict(mesh.shape), nq=nq, k=k)
    return hlo_collective_payloads(compiled.as_text()), analytic


def _knn_rate(mesh, queries: int, train: int, iters: int, k: int = 5) -> float:
    """Weak-scaling data-parallel KNN top-k rate (queries/sec)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from avenir_tpu.parallel.distributed import distributed_topk_fn

    d = 8
    rng = np.random.default_rng(1)
    q = rng.normal(size=(queries, d)).astype(np.float32)
    t = rng.normal(size=(train, d)).astype(np.float32)
    t_labels = rng.integers(0, 2, train).astype(np.int32)
    q_spec = NamedSharding(mesh, P(DATA_AXIS, None))
    rep = NamedSharding(mesh, P())
    step = distributed_topk_fn(mesh, k=k, metric="euclidean")

    q_d = jax.device_put(q, q_spec)
    t_d = jax.device_put(t, rep)
    l_d = jax.device_put(t_labels, rep)

    # step indices as an operand for the same no-recompile reason as _nb_rate
    steps = jnp.arange(1, iters + 1)

    @jax.jit
    def many(q_d, t_d, l_d, steps):
        def body(i):
            dist, labs = step(jnp.roll(q_d, i, axis=1), t_d, l_d)
            return jnp.sum(dist) + jnp.sum(labs).astype(jnp.float32)
        return jax.lax.map(body, steps).sum()

    return queries * iters / _timed_scalar(many, q_d, t_d, l_d, steps)


def measure_scaling(
    devices: Optional[Sequence] = None,
    counts: Sequence[int] = (1, 2, 4, 8),
    nb_rows_per_device: int = 65_536,
    knn_queries_per_device: int = 256,
    knn_train: int = 8_192,
    iters: int = 4,
) -> dict:
    """Run the distributed NB + KNN steps on mesh subsets of `counts`
    devices and report weak-scaling rates + efficiency vs linear.

    Returns {"table": [{devices, nb_rows_per_sec, nb_efficiency,
    knn_queries_per_sec, knn_efficiency}, ...], "efficiency_at_max": {...}}
    where efficiency = rate(P) / (P * rate(1)).
    """
    import jax

    devs = list(devices if devices is not None else jax.devices())
    counts = [c for c in counts if c <= len(devs)]
    if not counts:
        raise ValueError(
            f"no requested device count fits the {len(devs)} available "
            f"devices; include a count <= {len(devs)} (e.g. 1)"
        )
    # analytic per-device work/traffic per step — constant per-device work
    # is the weak-scaling invariant, and the ring-all-reduce bytes
    # (2(P-1)/P x tensor bytes) are the collective cost the efficiency
    # number prices in; unlike the wall clock these hold on real chips and
    # let a contended virtual run still validate the harness math
    nb_tensor_bytes = nb_payload_bytes()
    table = []
    for n in counts:
        mesh = data_mesh(devs[:n], model_parallel=1)
        nb = _nb_rate(mesh, nb_rows_per_device * n, iters)
        knn = _knn_rate(mesh, knn_queries_per_device * n, knn_train, iters)
        table.append({
            "devices": n,
            "nb_rows_per_sec": round(nb, 1),
            "knn_queries_per_sec": round(knn, 1),
            "nb_rows_per_device_per_step": nb_rows_per_device,
            "nb_allreduce_bytes_per_device": round(
                2 * (n - 1) / n * nb_tensor_bytes),
            "knn_queries_per_device_per_step": knn_queries_per_device,
        })
    base = table[0]
    for row in table:
        # efficiency vs linear relative to the smallest measured mesh
        scale = row["devices"] / base["devices"]
        row["nb_efficiency"] = round(
            row["nb_rows_per_sec"] / (scale * base["nb_rows_per_sec"]), 3)
        row["knn_efficiency"] = round(
            row["knn_queries_per_sec"] / (scale * base["knn_queries_per_sec"]),
            3)
    last = table[-1]
    virtual = devs[0].platform == "cpu"
    # HLO-validated traffic: parse the compiled sharded program's
    # collectives and check the analytic payload against what XLA emits
    hlo = _nb_compiled_collectives(data_mesh(devs[: last["devices"]],
                                            model_parallel=1))
    hlo_payload = sum(o["payload_bytes"] for o in hlo
                      if o["op"] == "all-reduce")
    # second family: the model-parallel KNN candidate merge (all-gather)
    knn_hlo: List[Dict] = []
    knn_analytic = 0
    if last["devices"] >= 2 and last["devices"] % 2 == 0:
        knn_hlo, knn_analytic = _knn_compiled_collectives(
            data_mesh(devs[: last["devices"]], model_parallel=2))
    knn_gather = sum(o["payload_bytes"] for o in knn_hlo
                     if o["op"] == "all-gather")
    # projection to pod scale from the measured per-device step time; on
    # virtual devices the compute side is contention-distorted, flagged
    step_s = nb_rows_per_device / (base["nb_rows_per_sec"]
                                   / base["devices"])
    out = {
        "table": table,
        "efficiency_at_max": {
            "devices": last["devices"],
            "nb": last["nb_efficiency"],
            "knn": last["knn_efficiency"],
        },
        "nb_hlo_collectives": hlo,
        "nb_hlo_allreduce_payload_bytes": hlo_payload,
        "nb_analytic_payload_bytes": nb_tensor_bytes,
        "payload_model_validated": hlo_payload == nb_tensor_bytes,
        "knn_hlo_collectives": knn_hlo,
        "knn_hlo_allgather_payload_bytes": knn_gather,
        "knn_analytic_allgather_payload_bytes": knn_analytic,
        "knn_payload_model_validated": bool(knn_hlo)
        and knn_gather == knn_analytic,
        "projection_8_to_256": project_efficiency(step_s, hlo_payload),
        "projection_note": (
            "projection_8_to_256 is a MODEL, not a measurement: payload "
            "bytes are HLO-validated and the single-chip step time is "
            "measured, but ICI bandwidth/latency are datasheet "
            "assumptions (project_efficiency) — no multi-chip hardware "
            "exists in this environment to measure against"),
        "virtual_devices": virtual,
    }
    if virtual:
        out["note"] = (
            "virtual CPU devices share one host's cores (the 1-device XLA "
            "run already uses the full host threadpool), so efficiency-vs-"
            "linear is core-contention-bound here; on real chips the same "
            "harness measures true ICI scaling"
        )
    return out
