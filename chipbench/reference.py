"""The plain reference of the kNN classification job: what
`nearestNeighbor` has to compute, written down once and straightforwardly.

It imports nothing of `avenir_tpu` and takes nothing the program made: its
inputs are the rows the benchmark's generator drew. Distances are float32
`jax.numpy` (elementwise, so "highest precision" is simply float32), a
block of train rows at a time; the Naive Bayes moments and posteriors of
the class-conditional weighting are float64 numpy on the host.

Semantics (the upstream job's, `knn/Neighborhood.java`, as the tutorial
runs it):

  distance   mean over attributes of |q - t| / (max - min)    (manhattan)
  neighbours the k train rows of least distance
  score      kernel none: 1;  gaussian: floor(100 exp(-0.5 (d/param)^2))
             with d = floor(100 distance)
  weighting  class-conditional: score * P(features of the neighbour | its
             class) under a per-class gaussian Naive Bayes fitted on the
             train rows (sample variance); a posterior of 0 leaves the
             score unweighted
  class      the class of largest summed score; the line carries every
             class's share of the total to three decimals

`dtype` is the control's handle: bfloat16 runs the same distance
arithmetic one precision below the configuration's.
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Dict, List, Sequence, Tuple

import numpy as np

KERNEL_SCALE = 100
BLOCK_ROWS = 1 << 17


GROUP = 128


def _least(cand_d, keep):
    """(distance [S, keep] ascending, column [S, keep]): `keep` rounds of
    take-the-least over the columns of `cand_d`."""
    import jax.numpy as jnp

    rows = jnp.arange(cand_d.shape[0])
    out_d, out_j = [], []
    for _ in range(keep):
        j = jnp.argmin(cand_d, axis=1)
        out_d.append(cand_d[rows, j])
        out_j.append(j)
        cand_d = cand_d.at[rows, j].set(jnp.inf)
    return jnp.stack(out_d, axis=1), jnp.stack(out_j, axis=1)


def _topk_block(q, t_blk, base, n_valid, best_d, best_i, keep):
    """Fold one feature-major train block [d, B] into the running best
    [S, keep]. The block's distances are taken once; its `keep` least lie
    in the `keep` groups of 128 columns with the least group minimum, so
    only those groups are searched, together with the carried best."""
    import jax.numpy as jnp

    s, b = q.shape[0], t_blk.shape[1]
    dist = jnp.zeros((s, b), q.dtype)
    for f in range(q.shape[1]):
        dist = dist + jnp.abs(q[:, f][:, None] - t_blk[f][None, :])
    dist = (dist / q.shape[1]).astype(jnp.float32)
    col = base + jnp.arange(b, dtype=jnp.int32)
    dist = jnp.where(col[None, :] < n_valid, dist, jnp.inf)
    groups = dist.reshape(s, b // GROUP, GROUP)
    _, grp = _least(groups.min(axis=2), keep)                    # [S, keep]
    near = jnp.take_along_axis(groups, grp[:, :, None], axis=1)  # [S,keep,128]
    near_col = (base + grp[:, :, None] * GROUP
                + jnp.arange(GROUP, dtype=jnp.int32)[None, None, :])
    cand_d = jnp.concatenate([best_d, near.reshape(s, -1)], axis=1)
    cand_i = jnp.concatenate([best_i, near_col.reshape(s, -1)], axis=1)
    out_d, j = _least(cand_d, keep)
    return out_d, jnp.take_along_axis(cand_i, j, axis=1)


def topk_manhattan(queries: np.ndarray, train: np.ndarray,
                   ranges: np.ndarray, keep: int, dtype: str = "float32",
                   block_rows: int = BLOCK_ROWS
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(distance [S, keep] ascending float32, train row [S, keep]) of the
    `keep` nearest train rows of each query, ties in any order. `queries`
    [S, d] and `train` [n, d] are raw float32 values."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n, d = train.shape
    block_rows = min(block_rows, -(-max(n, 1) // GROUP) * GROUP)
    r = np.asarray(ranges, np.float32)
    q = jnp.asarray(np.asarray(queries, np.float32) / r).astype(dt)
    fold = jax.jit(partial(_topk_block, keep=keep))
    best_d = jnp.full((q.shape[0], keep), jnp.inf, jnp.float32)
    best_i = jnp.full((q.shape[0], keep), -1, jnp.int32)
    for lo in range(0, n, block_rows):
        blk = np.zeros((d, block_rows), np.float32)
        hi = min(lo + block_rows, n)
        blk[:, :hi - lo] = (train[lo:hi] / r).T
        best_d, best_i = fold(q, jnp.asarray(blk).astype(dt),
                              jnp.int32(lo), jnp.int32(n), best_d, best_i)
    return np.asarray(best_d), np.asarray(best_i)


def nb_fit(train: np.ndarray, y: np.ndarray, n_classes: int
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-class gaussian of each attribute: (mean, std) [d, K] float64,
    sample variance (n - 1), std floored at 1e-6."""
    d = train.shape[1]
    y = y.astype(np.int64)
    cnt = np.maximum(np.bincount(y, minlength=n_classes), 1).astype(np.float64)
    mean = np.zeros((d, n_classes))
    std = np.zeros((d, n_classes))
    for f in range(d):
        x = train[:, f].astype(np.float64)
        m = np.bincount(y, weights=x, minlength=n_classes) / cnt
        dev = x - m[y]
        var = (np.bincount(y, weights=dev * dev, minlength=n_classes)
               / np.maximum(cnt - 1, 1))
        mean[f] = m
        std[f] = np.maximum(np.sqrt(np.maximum(var, 1e-30)), 1e-6)
    return mean, std


def feature_prob(x: np.ndarray, y: np.ndarray, mean: np.ndarray,
                 std: np.ndarray) -> np.ndarray:
    """P(features | own class) of rows `x` [m, d] with classes `y` [m]."""
    m, s = mean[:, y].T, std[:, y].T                     # [m, d]
    z = (x.astype(np.float64) - m) / s
    logp = (-0.5 * np.log(2 * np.pi) - np.log(s) - 0.5 * z * z).sum(axis=1)
    return np.exp(logp)


def class_scores(dist: np.ndarray, labels: np.ndarray, post: np.ndarray,
                 ref: Dict, n_classes: int) -> np.ndarray:
    """Summed scores [.., K] of neighbour sets: `dist`, `labels`, `post`
    are [.., k]."""
    d = np.floor(dist.astype(np.float64) * KERNEL_SCALE)
    if ref["kernel"] == "none":
        score = np.ones_like(d)
    elif ref["kernel"] == "gaussian":
        t = d / float(ref["kernel_param"])
        score = np.floor(KERNEL_SCALE * np.exp(-0.5 * t * t))
    else:
        raise ValueError(f"reference has no kernel {ref['kernel']!r}")
    if ref["class_cond_weighted"]:
        p = post.astype(np.float32).astype(np.float64)
        score = np.where(p > 0, score * p, score)
    onehot = labels[..., None] == np.arange(n_classes)
    return (score[..., None] * onehot).sum(axis=-2)


def shares_of(scores: np.ndarray) -> np.ndarray:
    tot = scores.sum(axis=-1, keepdims=True)
    return scores / np.where(tot == 0, 1.0, tot)


def format_line(rid: str, scores: np.ndarray, classes: Sequence[str]) -> str:
    """The job's output line with `nen.output.class.distr=true`."""
    sh = shares_of(scores)
    fields = [rid, classes[int(np.argmax(scores))]]
    fields += [f"{c}:{s:.3f}" for c, s in zip(classes, sh)]
    return ",".join(fields)


def neighbour_sets(dist: np.ndarray, k: int, tie_tol: float
                   ) -> Tuple[List[Tuple[int, ...]], bool]:
    """Every set of k candidates that is a right answer for one query,
    given its `keep` nearest by ascending distance: those surely inside
    (closer than the k-th by more than `tie_tol`) and each choice among
    those level with the k-th. The flag says the tie ran past the
    candidates kept, so the sets may be incomplete."""
    keep = len(dist)
    k = min(k, keep)
    dk = dist[k - 1]
    sure = [i for i in range(keep) if dist[i] < dk - tie_tol]
    level = [i for i in range(keep)
             if i not in sure and dist[i] <= dk + tie_tol]
    open_end = keep > k and dist[keep - 1] <= dk + tie_tol
    need = k - len(sure)
    sets = [tuple(sorted(sure + list(c)))
            for c in itertools.combinations(level, need)]
    return sets, open_end
