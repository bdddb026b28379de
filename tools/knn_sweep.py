"""On-chip block/dtype sweep for the pallas KNN kernels.

Usage: python tools/knn_sweep.py [d]
Prints qps + TF/s per config; the timing is memoization-safe (lax.map over
rolled inputs, scalar-forced). It times private kernels and is no benchmark:
the benchmark is chipbench/run.py.
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

KNN_QUERIES = 8_192
KNN_TRAIN = 131_072
STEPS = 8
K = 5


def timed(many_fn, *args, repeats=3):
    import jax.numpy as jnp

    _ = float(many_fn(*args))
    best = np.inf
    for s in range(1, repeats + 1):
        shifted = (jnp.roll(args[0], s, axis=-1),) + args[1:]
        t0 = time.perf_counter()
        _ = float(many_fn(*shifted))
        best = min(best, time.perf_counter() - t0)
    return best


def run(dim):
    import jax
    import jax.numpy as jnp
    from avenir_tpu.models.knn import _vote
    from avenir_tpu.ops.pallas_knn import (knn_classify_lanes,
                                           knn_topk_lanes, knn_topk_pallas)

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(KNN_QUERIES, dim)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(KNN_TRAIN, dim)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 2, KNN_TRAIN).astype(np.int32))

    configs = [
        ("old_packed", knn_topk_pallas, 512, 4096, "float32", {"packed": True}),
        ("old_packed", knn_topk_pallas, 512, 4096, "bfloat16", {"packed": True}),
        ("lanes", knn_topk_lanes, 512, 4096, "float32", {}),
        ("lanes", knn_topk_lanes, 512, 4096, "bfloat16", {}),
        ("lanes", knn_topk_lanes, 256, 4096, "bfloat16", {}),
        ("lanes", knn_topk_lanes, 256, 8192, "bfloat16", {}),
        ("lanes", knn_topk_lanes, 512, 2048, "bfloat16", {}),
        ("lanes", knn_topk_lanes, 1024, 4096, "bfloat16", {}),
    ]
    for name, fn, bq, bt, cdt, extra in configs:
        @jax.jit
        def many(q, t):
            def step(i):
                qi = jnp.roll(q, i, axis=0)
                # the exact kernel returns its count of extracted slices too
                dist, idx = fn(qi, t, k=K, block_q=bq, block_t=bt,
                               metric="euclidean", compute_dtype=cdt,
                               **extra)[:2]
                return jnp.sum(dist) + jnp.sum(idx).astype(jnp.float32)
            return jax.lax.map(step, jnp.arange(1, STEPS + 1)).sum()

        try:
            dt = timed(many, q, t)
        except Exception as exc:
            print(f"{name} bq={bq} bt={bt} {cdt}: FAILED {type(exc).__name__}: "
                  f"{str(exc)[:200]}")
            continue
        qps = KNN_QUERIES * STEPS / dt
        tfs = 2.0 * KNN_QUERIES * KNN_TRAIN * dim * STEPS / dt / 1e12
        print(f"{name} bq={bq} bt={bt} {cdt}: {qps:.3e} q/s  {tfs:.1f} TF/s")

    # fused-vs-composed A/B at the same block configs (VERDICT item: the
    # fused in-kernel vote must beat topk+XLA-vote on hardware, or its
    # bench default stays off). Same timing methodology.
    ab_configs = [(1024, 4096), (512, 4096), (1024, 2048), (512, 8192)]
    for bq, bt in ab_configs:
        @jax.jit
        def composed(q, t, labels):
            def step(i):
                qi = jnp.roll(q, i, axis=0)
                dist, idx = knn_topk_lanes(
                    qi, t, k=K, block_q=bq, block_t=bt,
                    metric="euclidean", compute_dtype="bfloat16")
                scores = _vote(dist, labels[idx], jnp.ones_like(dist),
                               "gaussian", 30.0, 2, False, False)
                return jnp.sum(scores).astype(jnp.float32)
            return jax.lax.map(step, jnp.arange(1, STEPS + 1)).sum()

        @jax.jit
        def fused(q, t, labels):
            def step(i):
                scores = knn_classify_lanes(
                    jnp.roll(q, i, axis=0), t, labels, k=K, n_classes=2,
                    kernel_fn="gaussian", kernel_param=30.0, block_q=bq,
                    block_t=bt, metric="euclidean",
                    compute_dtype="bfloat16")
                return jnp.sum(scores)
            return jax.lax.map(step, jnp.arange(1, STEPS + 1)).sum()

        for label, fn2 in (("composed", composed), ("fused", fused)):
            try:
                dt = timed(fn2, q, t, labels)
                print(f"{label} bq={bq} bt={bt}: "
                      f"{KNN_QUERIES * STEPS / dt:.3e} classify q/s")
            except Exception as exc:
                print(f"{label} bq={bq} bt={bt}: FAILED "
                      f"{type(exc).__name__}: {str(exc)[:200]}")


if __name__ == "__main__":
    run(int(sys.argv[1]) if len(sys.argv) > 1 else 128)
