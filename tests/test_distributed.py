"""Distributed mesh kernels on the virtual 8-device CPU mesh."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from avenir_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, data_mesh
from avenir_tpu.parallel.distributed import (
    distributed_nb_train_fn,
    distributed_topk_fn,
)


@pytest.fixture(scope="module")
def mesh2d():
    return data_mesh(jax.devices(), model_parallel=2)   # 4 x 2


class TestDistributedNB:
    def test_counts_match_host_oracle(self, mesh2d):
        rng = np.random.default_rng(0)
        rows, k, nf, bmax = 128, 3, 4, 6
        codes = rng.integers(0, bmax, (rows, nf)).astype(np.int32)
        labels = rng.integers(0, k, rows).astype(np.int32)
        w = np.ones(rows, np.float32)
        axes = (DATA_AXIS, MODEL_AXIS)
        shard = NamedSharding(mesh2d, P(axes))
        fn = distributed_nb_train_fn(mesh2d, k, bmax)
        post, cls = fn(
            jax.device_put(codes, shard),
            jax.device_put(labels, shard),
            jax.device_put(w, shard),
        )
        oracle = np.zeros((nf, k, bmax))
        for i in range(rows):
            for f in range(nf):
                oracle[f, labels[i], codes[i, f]] += 1
        np.testing.assert_allclose(np.asarray(post), oracle, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(cls), np.bincount(labels, minlength=k), rtol=1e-6
        )


class TestDistributedTopk:
    def test_matches_single_device(self, mesh2d):
        rng = np.random.default_rng(1)
        nq, nt, d, k = 16, 64, 4, 3
        q = rng.normal(size=(nq, d)).astype(np.float32)
        t = rng.normal(size=(nt, d)).astype(np.float32)
        t_labels = rng.integers(0, 2, nt).astype(np.int32)

        fn = distributed_topk_fn(mesh2d, k=k)
        dist, labs = fn(
            jax.device_put(q, NamedSharding(mesh2d, P(DATA_AXIS, None))),
            jax.device_put(t, NamedSharding(mesh2d, P(MODEL_AXIS, None))),
            jax.device_put(t_labels, NamedSharding(mesh2d, P(MODEL_AXIS))),
        )
        dist, labs = np.asarray(dist), np.asarray(labs)

        # host oracle
        full = np.abs(q[:, None, :] - t[None, :, :]).sum(-1) / d
        oidx = np.argsort(full, axis=1, kind="stable")[:, :k]
        od = np.take_along_axis(full, oidx, axis=1)
        np.testing.assert_allclose(np.sort(dist, axis=1), od, atol=1e-5)
        # labels of selected neighbors match oracle label multiset
        for r in range(nq):
            assert sorted(labs[r]) == sorted(t_labels[oidx[r]])

    def test_1d_mesh_replicated_train(self):
        mesh = data_mesh(jax.devices())                 # pure data-parallel
        rng = np.random.default_rng(2)
        q = rng.normal(size=(16, 3)).astype(np.float32)
        t = rng.normal(size=(32, 3)).astype(np.float32)
        t_labels = rng.integers(0, 2, 32).astype(np.int32)
        fn = distributed_topk_fn(mesh, k=2)
        dist, labs = fn(
            jax.device_put(q, NamedSharding(mesh, P(DATA_AXIS, None))),
            jax.device_put(t, NamedSharding(mesh, P())),
            jax.device_put(t_labels, NamedSharding(mesh, P())),
        )
        assert np.asarray(dist).shape == (16, 2)
        assert np.isfinite(np.asarray(dist)).all()


def test_distributed_tree_level_matches_single_device(mesh8, rng):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from avenir_tpu.models.tree import LANES, _level_histogram, to_lines
    from avenir_tpu.parallel import DATA_AXIS, distributed_tree_level_fn

    n, L, NS, S, K = 8 * LANES * 3, 3, 4, 2, 2
    leaf = to_lines(rng.integers(0, L, n).astype(np.int32))
    seg = to_lines(rng.integers(0, S, (NS, n)).astype(np.int8))
    labels = to_lines(rng.integers(0, K, n).astype(np.int32))
    w = to_lines(rng.integers(0, 4, n).astype(np.int32))

    single = np.asarray(_level_histogram(
        jnp.asarray(leaf), jnp.asarray(seg), jnp.asarray(labels),
        jnp.asarray(w), L, S, K))
    shard = NamedSharding(mesh8, P(DATA_AXIS))
    step = distributed_tree_level_fn(mesh8, L, NS, S, K)
    dist = np.asarray(step(
        jax.device_put(leaf, shard),
        jax.device_put(seg, NamedSharding(mesh8, P(None, DATA_AXIS))),
        jax.device_put(labels, shard), jax.device_put(w, shard)))
    assert dist.dtype == np.int32
    np.testing.assert_array_equal(dist, single)


def test_distributed_lr_step_matches_single_device(mesh8, rng):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from avenir_tpu.parallel import DATA_AXIS, distributed_lr_step_fn

    n, d = 512, 5
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    coeff0 = np.zeros(d, np.float32)

    # single-device oracle: full-batch sigmoid gradient step
    p = 1.0 / (1.0 + np.exp(-(x @ coeff0)))
    expected = coeff0 + 0.7 * (x.T @ ((y - p) * w)) / n

    shard = NamedSharding(mesh8, P(DATA_AXIS))
    step = distributed_lr_step_fn(mesh8, learning_rate=0.7)
    got = np.asarray(step(jnp.asarray(coeff0), jax.device_put(x, shard),
                          jax.device_put(y, shard), jax.device_put(w, shard)))
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_distributed_crosscount_matches_numpy(mesh8, rng):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from avenir_tpu.parallel import DATA_AXIS, distributed_crosscount_fn

    n, A, B = 1024, 6, 3
    a = rng.integers(0, A, n).astype(np.int32)
    b = rng.integers(0, B, n).astype(np.int32)
    w = np.ones(n, np.float32)
    expected = np.zeros((A, B))
    np.add.at(expected, (a, b), 1.0)

    shard = NamedSharding(mesh8, P(DATA_AXIS))
    cc = distributed_crosscount_fn(mesh8, A, B)
    got = np.asarray(cc(jax.device_put(a, shard), jax.device_put(b, shard),
                        jax.device_put(w, shard)))
    np.testing.assert_allclose(got, expected, atol=1e-4)


def test_tree_builder_mesh_equals_single_device(mesh8):
    from avenir_tpu.data import generate_churn
    from avenir_tpu.models.tree import DecisionTreeBuilder

    ds = generate_churn(300, seed=21)
    single = DecisionTreeBuilder(ds.schema, max_depth=2).fit(ds)
    sharded = DecisionTreeBuilder(ds.schema, max_depth=2).fit(ds, mesh=mesh8)
    cls_vals = ds.schema.class_values()
    np.testing.assert_array_equal(single.predict(ds, cls_vals),
                                  sharded.predict(ds, cls_vals))
    assert len(single.paths) == len(sharded.paths)


def test_lr_mesh_equals_single_device(mesh8):
    from avenir_tpu.data import generate_elearn
    from avenir_tpu.models.regress import LogisticRegression

    ds = generate_elearn(333, seed=22)   # deliberately not shard-divisible
    single = LogisticRegression(iteration_limit=5).fit(ds)
    sharded = LogisticRegression(iteration_limit=5).fit(ds, mesh=mesh8)
    np.testing.assert_allclose(sharded.coeff, single.coeff,
                               rtol=1e-4, atol=1e-5)


def test_multihost_helpers_single_process(mesh8, rng):
    import jax
    from avenir_tpu.parallel import multihost

    assert multihost.initialize() == 1
    lo, hi = multihost.host_shard_bounds(1000)
    assert (lo, hi) == (0, 1000)     # single process owns everything
    rows = rng.normal(size=(64, 4)).astype(np.float32)
    arr = multihost.global_rows(mesh8, rows)
    assert arr.shape == (64, 4)
    np.testing.assert_allclose(np.asarray(arr), rows)
    # the array is actually row-sharded over the mesh
    assert len(arr.sharding.device_set) == 8


def test_distributed_bandit_select_matches_single():
    """Group-sharded UCB1 picks equal the single-device kernel exactly
    (selection reads only each group's own stats; no collective)."""
    from avenir_tpu.models.bandits import _ucb1_kernel
    from avenir_tpu.parallel.distributed import distributed_bandit_select_fn
    from avenir_tpu.parallel.mesh import data_mesh

    mesh = data_mesh(jax.devices()[:4], model_parallel=1)
    rng = np.random.default_rng(8)
    g, a = 64, 5
    counts = rng.integers(0, 40, (g, a)).astype(np.int32)
    rewards = (rng.random((g, a)) * 100).astype(np.float32)
    mask = np.ones((g, a), bool)
    mask[:, -1] = False                      # padded arm slots
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(mesh.axis_names))
    sel = distributed_bandit_select_fn(mesh, batch_size=3)
    got = np.asarray(sel(jax.device_put(counts, shard),
                         jax.device_put(rewards, shard),
                         jax.device_put(mask, shard), 7.0))
    ref = np.asarray(_ucb1_kernel(jnp.asarray(counts), jnp.asarray(rewards),
                                  jnp.asarray(mask), 7.0, 100.0, 3))
    np.testing.assert_array_equal(got, ref)
    assert (got < a - 1).all()               # padded arm never picked
