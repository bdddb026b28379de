"""Distributed algorithm kernels over 2-D (data x model) meshes.

The reference distributes KNN by materializing all-pairs distances through a
MapReduce shuffle (sifarish + knn.sh pipeline). The TPU-native form shards
the *query* rows over the 'data' mesh axis and the *train* rows over the
'model' axis: each device computes a local streaming top-k against its train
shard, then an all_gather over 'model' merges the per-shard candidate sets —
k*P candidates per query instead of n_train, so the ICI traffic is tiny.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from avenir_tpu.ops.distance import pairwise_distance
from avenir_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, shard_map


def distributed_topk_fn(
    mesh: Mesh,
    k: int,
    metric: str = "manhattan",
):
    """Build a jitted distributed top-k: queries sharded over 'data', train
    rows sharded over 'model' (replicated if the mesh has no model axis).

    Returned fn(q_num, t_num, t_labels) -> (dist [nq, k], labels [nq, k])
    with q row-sharded and outputs row-sharded the same way. Numeric
    features only for now; route mixed categorical data through
    NeighborIndex on a single chip or encode categoricals numerically.
    """
    has_model = MODEL_AXIS in mesh.axis_names

    def kernel(q_num, t_num, t_labels):
        # local block: all queries in my data shard vs my train shard
        d = pairwise_distance(q_num, t_num, metric=metric)
        loc_d, loc_i = lax.top_k(-d, k)
        loc_d = -loc_d
        loc_lab = jnp.take(t_labels, loc_i)                     # [nq_loc, k]
        if has_model:
            # merge candidate sets across train shards: [P*k] per query
            all_d = lax.all_gather(loc_d, MODEL_AXIS, axis=1, tiled=True)
            all_lab = lax.all_gather(loc_lab, MODEL_AXIS, axis=1, tiled=True)
            neg, pos = lax.top_k(-all_d, k)
            return -neg, jnp.take_along_axis(all_lab, pos, axis=1)
        return loc_d, loc_lab

    in_specs = (
        P(DATA_AXIS, None),
        P(MODEL_AXIS, None) if has_model else P(),
        P(MODEL_AXIS) if has_model else P(),
    )
    out_specs = (P(DATA_AXIS, None), P(DATA_AXIS, None))
    return jax.jit(
        shard_map(kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


def distributed_nb_train_fn(mesh: Mesh, num_classes: int, bmax: int):
    """Build a jitted mesh-wide Naive Bayes sufficient-stat step: row shards
    count locally (one-hot einsum on the MXU), psum over 'data' (and 'model'
    if present, so every device holds the global counts)."""
    axes = tuple(a for a in (DATA_AXIS, MODEL_AXIS) if a in mesh.axis_names)

    def kernel(codes, labels, w):
        oh_k = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32) * w[:, None]
        oh_b = jax.nn.one_hot(codes, bmax, dtype=jnp.float32)
        post = jnp.einsum("nk,nfb->fkb", oh_k, oh_b)
        cls = oh_k.sum(axis=0)
        return (
            lax.psum(post, axes),
            lax.psum(cls, axes),
        )

    row_spec = P(axes)  # rows sharded over all mesh axes jointly
    return jax.jit(
        shard_map(
            kernel,
            mesh=mesh,
            in_specs=(row_spec, row_spec, row_spec),
            out_specs=(P(), P()),
        )
    )


@functools.lru_cache(maxsize=None)
def distributed_tree_level_fn(mesh: Mesh, n_leaves: int, n_splits: int,
                              smax: int, num_classes: int, digits: int = 1):
    """Build a jitted mesh-wide tree-level histogram step: every row shard
    computes its [L, NS, S, K] class-histogram block locally (the one
    level pass of models.tree, which replaces one whole MR tree level,
    SURVEY §3.4), then a psum over the mesh replicates the global
    histogram — the host picks splits from a tensor that is tiny
    regardless of row count. Arguments as the pass takes them, in lines
    (`models.tree.to_lines`): leaf_id [R, LANES] int32, seg_matrix
    [n_splits, R, LANES] int8, labels [R, LANES] int32, weights [R, LANES]
    int32 whole numbers under 128**digits; the lines shard over the mesh.
    Built once per mesh and shape."""
    from avenir_tpu.models.tree import _level_histogram

    axes = tuple(a for a in (DATA_AXIS, MODEL_AXIS) if a in mesh.axis_names)

    def kernel(leaf_id, seg_matrix, labels, weights):
        h = _level_histogram(leaf_id, seg_matrix, labels, weights,
                             n_leaves, smax, num_classes, digits)
        return lax.psum(h, axes)

    row = P(axes)
    return jax.jit(
        shard_map(kernel, mesh=mesh,
                      in_specs=(row, P(None, axes), row, row), out_specs=P())
    )


def distributed_lr_step_fn(mesh: Mesh, learning_rate: float = 1.0):
    """Build a jitted data-parallel logistic-regression step: per-shard
    gradient halves (regress._lr_grad, the same core as the single-device
    step), psum'd so every device applies the identical update (the
    reference's mapper-aggregate + single reducer, SURVEY §3.6, as one
    collective). Unlike _lr_step, rows carry weights and the normalizer is
    the weight total — zero-weight padding rows drop out exactly."""
    from avenir_tpu.models.regress import _lr_grad

    axes = tuple(a for a in (DATA_AXIS, MODEL_AXIS) if a in mesh.axis_names)

    def kernel(coeff, x, y, w):
        grad = lax.psum(_lr_grad(coeff, x, y, w), axes)
        n = jnp.maximum(lax.psum(jnp.sum(w), axes), 1.0)
        return coeff + learning_rate * grad / n

    row = P(axes)
    return jax.jit(
        shard_map(kernel, mesh=mesh,
                      in_specs=(P(), row, row, row), out_specs=P())
    )


def distributed_markov_counts_fn(mesh: Mesh, n_states: int,
                                 n_classes: int = 1):
    """Build a jitted mesh-wide Markov bigram counter: padded sequences
    shard over the mesh rows, each shard runs the keyed segment_sum
    (models.markov._bigram_counts — the Hadoop/Spark shuffle of
    MarkovStateTransitionModel as one reduction), psum merges the
    [C, S, S] count tensors so every device holds the global matrix."""
    from avenir_tpu.models.markov import _bigram_counts

    axes = tuple(a for a in (DATA_AXIS, MODEL_AXIS) if a in mesh.axis_names)

    def kernel(padded, labels):
        c = _bigram_counts(padded, labels, n_states, n_classes)
        return lax.psum(c, axes)

    row = P(axes)
    return jax.jit(
        shard_map(kernel, mesh=mesh, in_specs=(row, row), out_specs=P())
    )


def distributed_apriori_support_fn(mesh: Mesh, k: int):
    """Build a jitted mesh-wide Apriori support counter: the multi-hot
    transaction tile shards over the mesh rows, candidates replicate, each
    shard counts containment via the MXU matmul
    (models.association._contain_counts), and a psum yields global
    supports — the per-k MR job (FrequentItemsApriori.java:51) as one
    collective."""
    from avenir_tpu.models.association import _contain_counts

    axes = tuple(a for a in (DATA_AXIS, MODEL_AXIS) if a in mesh.axis_names)

    def kernel(trans, cand):
        return lax.psum(_contain_counts(trans, cand, k), axes)

    return jax.jit(
        shard_map(kernel, mesh=mesh, in_specs=(P(axes), P()),
                      out_specs=P())
    )


def distributed_bandit_select_fn(mesh: Mesh, batch_size: int,
                                 max_reward: float = 100.0):
    """Build a jitted mesh-wide UCB1 bandit round: groups shard over the
    mesh rows (the map-only per-group MR job GreedyRandomBandit.java:148 /
    AuerDeterministic.java:130 is embarrassingly parallel — selection
    reads only the group's own arm stats, so the only collective cost is
    zero), each shard scores and ranks its groups, and the output stays
    group-sharded like the job's per-mapper output files."""
    from avenir_tpu.models.bandits import _ucb1_kernel

    axes = tuple(a for a in (DATA_AXIS, MODEL_AXIS) if a in mesh.axis_names)

    def kernel(counts, rewards, mask, round_num):
        # the shared single-device kernel, per shard (nested jit inlines)
        return _ucb1_kernel(counts, rewards, mask, round_num, max_reward,
                            batch_size)

    row = P(axes)
    return jax.jit(
        shard_map(kernel, mesh=mesh,
                      in_specs=(row, row, row, P()),
                      out_specs=row)
    )


def distributed_crosscount_fn(mesh: Mesh, bins_a: int, bins_b: int):
    """Build a jitted mesh-wide contingency counter: the primitive behind
    mutual information / correlations (SURVEY §2.4) — per-shard one-hot
    einsum, psum-merged [A, B] joint counts."""
    axes = tuple(a for a in (DATA_AXIS, MODEL_AXIS) if a in mesh.axis_names)

    def kernel(a, b, w):
        oa = jax.nn.one_hot(a, bins_a, dtype=jnp.float32) * w[:, None]
        ob = jax.nn.one_hot(b, bins_b, dtype=jnp.float32)
        return lax.psum(jnp.einsum("na,nb->ab", oa, ob), axes)

    row = P(axes)
    return jax.jit(
        shard_map(kernel, mesh=mesh, in_specs=(row, row, row),
                      out_specs=P())
    )


#: every distributed family this module exports, keyed by the short name
#: the collective-payload auditor and scaling harness use. Adding a family
#: here without a manifest entry + analytic payload model fails
#: tests/test_graftlint_ir.py — the auditor's coverage is this dict.
FAMILIES = {
    "knn_topk": distributed_topk_fn,
    "nb_train": distributed_nb_train_fn,
    "tree_level": distributed_tree_level_fn,
    "lr_step": distributed_lr_step_fn,
    "markov_counts": distributed_markov_counts_fn,
    "apriori_support": distributed_apriori_support_fn,
    "bandit_select": distributed_bandit_select_fn,
    "crosscount": distributed_crosscount_fn,
}
