"""Pallas TPU kernel: fused distance tile + streaming top-k for KNN.

The KNN hot loop (SURVEY §7 "hard parts": blocked streaming top-k is the
main genuinely new kernel) spends its time producing an [nq, nt] distance
surface and reducing each row to its k smallest entries. The jnp path
(ops/distance.blocked_topk_neighbors) materializes each [nq, block] tile
through HBM and pays for a full sort-based lax.top_k per block. The exact
kernel walks each [BQ, BT] block in slices of slice_rows(BT) train rows
(a rolled loop), computes a slice's distances in VMEM and first asks one
thing of them: does any lie strictly under its query's k-th best so far?
Only then does it run k iterative extractions of the least (distance,
column) pair on the slice (k is small — 5-ish — so k VPU passes beat a
sort) and merge them into the running [BQ, k] best buffer, which lives in
the revisited output block across the train-block grid axis. A train row
at position p of a corpus in no particular order enters a query's best k
with probability k/p, so most slices are passed over; the answer is the
same bits either way, the k least (distance, index) pairs in
lexicographic order (a tie goes to the row already held, then to the
lower column), and a third output counts the slices extracted.

Memory: the train block is BT x D f32 in VMEM, row-major (default 8192
rows), a slice's distances BQ x slice_rows(BT) (256 x 2048 = 2 MB), the
manhattan form's query columns broadcast along the lanes D x BQ x 128;
distances never touch HBM; output is [nq, k] + [nq, k] and the count.

Numeric-feature metrics only (euclidean via one MXU matmul, manhattan via a
D-pass VPU loop); the mixed categorical path stays on the jnp route.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_INF = float("inf")


_PACK_BITS = 12                      # low mantissa bits carrying the column
_PACK_MASK = (1 << _PACK_BITS) - 1
# sentinel for masked/empty packed slots: a huge FINITE float (~3.19e38) with
# zero pack bits, so bit-pattern ordering stays monotonic (NaN/inf patterns
# would break int comparisons after bitcast) and decode stays comparable
_SENTINEL = np.int32(0x7F700000)


def _dot_precision(compute_dtype):
    """TPU dot_general defaults to bf16 MXU passes even for f32 operands;
    request HIGHEST so compute_dtype=float32 is genuinely f32 (measured
    ~4e-3 relative distance error otherwise). bfloat16 keeps the native
    single-pass rate."""
    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(compute_dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _tile_distance(q, t, metric, compute_dtype):
    """[BQ, BT] distance tile (squared sums for euclidean)."""
    if metric == "euclidean":
        # squared distances via one MXU matmul; sqrt deferred to the end.
        # compute_dtype=bfloat16 runs the matmul at the MXU's native rate
        # (f32 accumulate); norms stay f32 so the loss is only in the cross
        # term's 8 mantissa bits.
        qs = jnp.sum(q * q, axis=1)[:, None]
        ts = jnp.sum(t * t, axis=1)[None, :]
        return jnp.maximum(
            qs + ts - 2.0 * jax.lax.dot_general(
                q.astype(compute_dtype), t.astype(compute_dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_dot_precision(compute_dtype)),
            0.0,
        )
    # manhattan: D broadcast passes on the VPU
    tile = jnp.zeros((q.shape[0], t.shape[0]), jnp.float32)
    for f in range(q.shape[1]):
        tile = tile + jnp.abs(q[:, f][:, None] - t[:, f][None, :])
    return tile


def _least(x, col):
    """Row minimum of x [BQ, S] and the lowest column that holds it (col is
    the column iota). Two min reductions, not jnp.argmin: the compiled
    index reduction gives a tie to the highest lane (shown on a v5e,
    PERF.md section 6, PR 37), so with it the order among equal distances
    would depend on where a row happens to lie in a vector register."""
    m = jnp.min(x, axis=1)
    return m, jnp.min(jnp.where(x == m[:, None], col, x.shape[1]), axis=1)


def _merge_into_best(best_d_ref, best_i_ref, cand_d, cand_i, k):
    """Fold [BQ, m] candidates into the carried [BQ, k] best buffers via k
    extraction rounds on the (small) concatenated array; among equal
    distances the rows already held come first, then the lower column."""
    all_d = jnp.concatenate([best_d_ref[...], cand_d], axis=1)
    all_i = jnp.concatenate([best_i_ref[...], cand_i], axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, all_d.shape, 1)
    new_d = []
    new_i = []
    for _ in range(k):
        m, am = _least(all_d, pos)
        sel = pos == am[:, None]
        # gather the index at the argmin lane via a masked reduction
        picked_i = jnp.sum(jnp.where(sel, all_i, 0), axis=1)
        new_d.append(m)
        new_i.append(picked_i)
        all_d = jnp.where(sel, _INF, all_d)
    best_d_ref[...] = jnp.stack(new_d, axis=1)
    best_i_ref[...] = jnp.stack(new_i, axis=1)


#: widest slice of train rows the exact kernel tests at once (the widths
#: tried on the chip are in PERF.md section 6, PR 37)
_SLICE_ROWS = 2048
#: most VMEM the query columns' lane broadcasts may take, made once a
#: query block; above it they are made again for every slice
_QUERY_BROADCAST_BYTES = 4 << 20


def slice_rows(block_t: int) -> int:
    """Train rows of one slice of the exact kernel's tile: the widest run
    of whole 128-row groups that divides `block_t`, at most _SLICE_ROWS;
    a block that has no such run is one slice."""
    s = math.gcd(block_t, _SLICE_ROWS)
    return s if s % _LANES == 0 else block_t


def _group_rows(block_t: int) -> int:
    """Train rows whose distances the exact kernel accumulates at once over
    all features: one lane group of a slice (the whole of an odd slice)."""
    width = slice_rows(block_t)
    return _LANES if width % _LANES == 0 else width


def _hoists_queries(d: int, block_q: int, block_t: int) -> bool:
    return d * block_q * _group_rows(block_t) * 4 <= _QUERY_BROADCAST_BYTES


def _manhattan_parts(q_ref, qb_ref, ts, group):
    """The [BQ, group] distance tiles of a train slice ts [S, D], one a
    lane group: each accumulated over all D features before the next is
    begun, so it stays in vector registers (the sums are the whole-tile
    form's, feature by feature in float32). The slice's rows go to the
    lanes by an identity matmul at HIGHEST precision, which moves every
    finite float32 exactly: three bfloat16 pieces, each times one."""
    bq, d = q_ref.shape
    dp = -(-d // 8) * 8
    ident = (jax.lax.broadcasted_iota(jnp.int32, (dp, d), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (dp, d), 1))
    t_rows = jax.lax.dot_general(
        ident.astype(jnp.float32), ts, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)                 # [dp, S]
    parts = []
    for g in range(ts.shape[0] // group):
        acc = jnp.zeros((bq, group), jnp.float32)
        for f in range(d):
            q_col = (qb_ref[f] if qb_ref is not None else
                     jnp.broadcast_to(q_ref[:, f:f + 1], (bq, group)))
            acc = acc + jnp.abs(
                q_col - t_rows[f:f + 1, g * group:(g + 1) * group])
        parts.append(acc)
    return parts


def _knn_kernel(q_ref, t_ref, best_d_ref, best_i_ref, n_ext_ref, *qb_ref,
                k: int, metric: str, block_t: int, n_valid: int, nt: int,
                compute_dtype=jnp.float32):
    """Exact path. The tile is walked in slices of slice_rows(block_t)
    train rows; a slice is extracted (k rounds of the least pair left,
    then the merge) only where some row of it lies strictly under its
    query's k-th best. A slice with no such row would leave the best
    buffers as they are (a tie goes to the row already held, then to the
    lower column: _least), so skipping it changes no bit, and the result
    is the k least (distance, index) pairs in lexicographic order
    whatever the slice width. n_ext_ref counts the slices this query block
    extracted; qb_ref, where the wrapper made room for it, holds the
    manhattan form's query columns broadcast along the lanes."""
    tb = pl.program_id(1)
    width = slice_rows(block_t)
    group = _group_rows(block_t)
    bq, d = q_ref.shape
    qb_ref = qb_ref[0] if qb_ref else None

    @pl.when(tb == 0)
    def _init():
        best_d_ref[...] = jnp.full_like(best_d_ref, _INF)
        best_i_ref[...] = jnp.full_like(best_i_ref, -1)
        n_ext_ref[...] = jnp.zeros_like(n_ext_ref)
        if qb_ref is not None:          # the query block is every tb's
            for f in range(d):
                qb_ref[f] = jnp.broadcast_to(q_ref[:, f:f + 1], (bq, group))

    col = jax.lax.broadcasted_iota(jnp.int32, (bq, width), 1)

    def one_slice(c, carry):
        start = pl.multiple_of(c * width, width)
        ts = t_ref[pl.ds(start, width), :]
        if metric == "manhattan":
            parts = _manhattan_parts(q_ref, qb_ref, ts, group)
        else:
            tile = _tile_distance(q_ref[...], ts, metric, compute_dtype)
            parts = [tile[:, g:g + group] for g in range(0, width, group)]
        base = tb * block_t + start
        if n_valid < nt:                    # static: skip mask when unpadded
            lane = jax.lax.broadcasted_iota(jnp.int32, (bq, group), 1)
            parts = [jnp.where(base + g * group + lane < n_valid, part, _INF)
                     for g, part in enumerate(parts)]
        # the test, one minimum a pair: +inf until k rows are held, so the
        # first slices always pass; a masked pad row is +inf and never does
        least = functools.reduce(jnp.minimum, parts)

        @pl.when(jnp.any(least < best_d_ref[:, k - 1:k]))
        def _extract():
            # k extractions of the least pair left: top-k without a sort
            rest = jnp.concatenate(parts, axis=1)
            cand_d = []
            cand_i = []
            for _ in range(k):
                m, am = _least(rest, col)                    # [BQ] each
                cand_d.append(m[:, None])
                cand_i.append(base + am[:, None])
                rest = jnp.where(col == am[:, None], _INF, rest)
            _merge_into_best(best_d_ref, best_i_ref,
                             jnp.concatenate(cand_d, axis=1),
                             jnp.concatenate(cand_i, axis=1), k)
            n_ext_ref[...] += 1

        return carry

    jax.lax.fori_loop(0, block_t // width, one_slice, 0)


def _knn_kernel_packed(q_ref, t_ref, best_d_ref, best_i_ref, *, k: int,
                       metric: str, block_t: int, n_valid: int, nt: int,
                       compute_dtype=jnp.float32):
    """Packed-key path: distances are non-negative f32, so their int32 bit
    patterns order identically; the low _PACK_BITS mantissa bits are
    repurposed to carry the in-tile column. A k-deep compare-exchange
    insertion network then keeps the k smallest keys PER LANE in one pass
    over the tile (2 VPU ops per element per depth, indices ride free),
    and the row top-k — provably a subset of the per-lane top-k union —
    is extracted from the [BQ, k*128] remainder. Cost: ~2k cheap passes
    instead of k (min + argmin + mask) lane-reduction passes.

    Quantization: zeroing _PACK_BITS mantissa bits shifts distances by
    <= 2^-12 relative (~2.4e-4) and can reorder genuinely tied-to-that-
    precision neighbors; exact path is the default."""
    lanes = 128
    chunks = block_t // lanes
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        best_d_ref[...] = jnp.full_like(best_d_ref, _INF)
        best_i_ref[...] = jnp.full_like(best_i_ref, -1)

    tile = _tile_distance(q_ref[...], t_ref[...], metric, compute_dtype)
    base = tb * block_t
    bits = jax.lax.bitcast_convert_type(tile, jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    key = jnp.bitwise_or(jnp.bitwise_and(bits, ~jnp.int32(_PACK_MASK)), col)
    if n_valid < nt:                        # static: skip mask when unpadded
        key = jnp.where(base + col < n_valid, key, _SENTINEL)

    # insertion network: carries[j] holds the (j+1)-th smallest key per lane
    bq = key.shape[0]
    carries = [jnp.full((bq, lanes), _SENTINEL, jnp.int32) for _ in range(k)]
    for c in range(chunks):
        x = key[:, c * lanes:(c + 1) * lanes]
        for j in range(k):
            lo = jnp.minimum(carries[j], x)
            x = jnp.maximum(carries[j], x)
            carries[j] = lo

    # extract the row top-k from the k*128 survivors: the packed row-min IS
    # (distance, column) — no argmin or gather needed, and masking by key
    # equality is exact because packed keys are unique per tile (distinct
    # column bits; sentinels only equal the min once everything is consumed)
    cand = jnp.concatenate(carries, axis=1)           # [BQ, k*128] packed
    out_d = []
    out_i = []
    out_e = []
    for _ in range(k):
        m = jnp.min(cand, axis=1)
        # int32 (not bool) empty flags: Mosaic rejects bool concat
        out_e.append(jnp.where(m == _SENTINEL, 1, 0)[:, None])
        out_d.append(jax.lax.bitcast_convert_type(
            jnp.bitwise_and(m, ~jnp.int32(_PACK_MASK)), jnp.float32)[:, None])
        out_i.append(
            (base + jnp.bitwise_and(m, jnp.int32(_PACK_MASK)))[:, None])
        cand = jnp.where(cand == m[:, None], _SENTINEL, cand)
    # empty slots are exactly the sentinel bit pattern (checked before
    # decode, so a genuine quantized distance that happens to be huge is
    # still reported rather than laundered away); launder empties to +inf
    # so the final isinf -> -1 index masking applies
    dmat = jnp.where(jnp.concatenate(out_e, axis=1) == 1, _INF,
                     jnp.concatenate(out_d, axis=1))
    _merge_into_best(best_d_ref, best_i_ref, dmat,
                     jnp.concatenate(out_i, axis=1), k)


_LANES = 128
# lane-kernel corpus cap: 12 chunk-id bits (keeps distance quantization
# <= 2^-11); callers route bigger corpora to the exact kernel
LANE_CORPUS_CAP = _LANES * (1 << 12)


def _lane_pack_bits(nt: int) -> int:
    """Mantissa bits needed to carry a global 128-column chunk id."""
    n_chunks = (nt + _LANES - 1) // _LANES
    return max(1, (n_chunks - 1).bit_length())


def _hi_depth(k: int) -> int:
    """Carry depth needed for the hi (pair-loser) stream.

    A hi-stream element e in the row top-k has, for each smaller hi-stream
    element h in its lane, TWO distinct row elements below e (h and h's
    pair partner), plus e's own partner: 2H + 1 <= k - 1, so
    H <= floor((k-2)/2) and depth H+1 suffices. k=1: a pair loser can
    never be the row minimum, so the hi stream needs no carries at all."""
    return 0 if k < 2 else (k - 2) // 2 + 1


def _knn_kernel_lanes(q_ref, t_ref, keys_ref, *, k: int, metric: str,
                      block_t: int, n_valid: int, nt: int, pack_bits: int,
                      compute_dtype=jnp.float32):
    """Lane-resident packed top-k (the round-3 fast path).

    Differences from _knn_kernel_packed:
    - the low mantissa bits carry the *global 128-column chunk id*
      (column // 128); the lane index is implicit in the vector position,
      so pack_bits = log2(nt/128) instead of log2(block_t) — finer
      quantization (2^-13 at nt=128k vs 2^-12) and no block_t cap.
    - the per-lane carries live in the revisited output block across the
      whole train-block grid axis; there is NO per-tile extraction or
      merge. The row top-k is recovered from the final packed buffer by
      one tiny XLA pass (_extract_lane_topk), amortized over all tiles.
    - a pair-fold front end: adjacent 128-column chunks are compare-
      exchanged once, then the winners (lo) feed a k-deep insertion
      network and the losers (hi) a _hi_depth(k)-deep one. The kernel is
      VMEM-bandwidth-bound, and the fold halves the elements entering the
      deep network: ~(2 + 3*(2k-1)/2 + 3*(2h-1)/2) streamed passes per
      element instead of 3*(2k-1).

    Correctness of the per-lane carry: a row element with global rank r
    has at most r-1 smaller elements anywhere, hence fewer than k smaller
    elements in its own lane, so every row-top-k lo-element survives the
    k-deep lo carry; the hi bound is proven at _hi_depth."""
    chunks = block_t // _LANES
    assert chunks % 2 == 0, "block_t must be a multiple of 256 (pair fold)"
    tb = pl.program_id(1)
    mask = jnp.int32((1 << pack_bits) - 1)
    khi = _hi_depth(k)

    @pl.when(tb == 0)
    def _init():
        keys_ref[...] = jnp.full_like(keys_ref, _SENTINEL)

    if metric == "euclidean":
        # the wrapper pre-scales q by -2, so dist^2 = qs + ts + (-2q)@t
        # with qs recovered as sum((-2q)^2)/4 — one fewer full-tile pass
        # than computing qs + ts - 2*(q@t)
        qv = q_ref[...]
        tv = t_ref[...]
        qs = 0.25 * jnp.sum(qv * qv, axis=1)[:, None]
        ts = jnp.sum(tv * tv, axis=1)[None, :]
        dot = jax.lax.dot_general(
            qv.astype(compute_dtype), tv.astype(compute_dtype),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_dot_precision(compute_dtype))
        tile = jnp.maximum(qs + ts + dot, 0.0)
    else:
        tile = _tile_distance(q_ref[...], t_ref[...], metric, compute_dtype)
    bits = jax.lax.bitcast_convert_type(tile, jnp.int32)
    base_chunk = tb * chunks

    carr_lo = [keys_ref[:, j * _LANES:(j + 1) * _LANES] for j in range(k)]
    carr_hi = [keys_ref[:, (k + j) * _LANES:(k + j + 1) * _LANES]
               for j in range(khi)]
    if n_valid < nt:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def packed_chunk(c):
        x = jnp.bitwise_or(
            jnp.bitwise_and(bits[:, c * _LANES:(c + 1) * _LANES], ~mask),
            base_chunk + c,
        )
        if n_valid < nt:                    # static: only padded corpora
            col = (base_chunk + c) * _LANES + lane
            x = jnp.where(col < n_valid, x, _SENTINEL)
        return x

    def insert(carries, x):
        depth = len(carries)
        for j in range(depth):
            lo = jnp.minimum(carries[j], x)
            if j < depth - 1:
                x = jnp.maximum(carries[j], x)
            carries[j] = lo

    for c in range(0, chunks, 2):
        x0 = packed_chunk(c)
        x1 = packed_chunk(c + 1)
        insert(carr_lo, jnp.minimum(x0, x1))
        if khi:
            insert(carr_hi, jnp.maximum(x0, x1))
    keys_ref[...] = jnp.concatenate(carr_lo + carr_hi, axis=1)


def _extract_lane_topk(keys: jnp.ndarray, k: int, pack_bits: int):
    """[nq, k*128] packed per-lane carries -> (dist_sq [nq,k], col [nq,k]).

    Packed keys order identically to the (non-negative) distances they
    encode, so the k algebraically-smallest keys ARE the row top-k. They
    are recovered with k min+argmin extraction rounds — NOT lax.top_k,
    whose sort-based TPU lowering measured ~70x slower than the pallas
    kernel it post-processes. The position's low 7 bits are the lane.
    Empty slots hold _SENTINEL (a huge finite float with zero pack bits)
    and decode to (+inf, -1); a genuine distance whose bit pattern reaches
    the sentinel (>= ~3.19e38) is indistinguishable from empty by
    construction — unreachable for normalized features."""
    mask = jnp.int32((1 << pack_bits) - 1)
    pos_iota = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    cand = keys
    ks, ps = [], []
    imax = jnp.int32(np.iinfo(np.int32).max)
    for _ in range(k):
        m = jnp.min(cand, axis=1)
        am = jnp.argmin(cand, axis=1).astype(jnp.int32)
        ks.append(m[:, None])
        ps.append(am[:, None])
        cand = jnp.where(pos_iota == am[:, None], imax, cand)
    key = jnp.concatenate(ks, axis=1)
    pos = jnp.concatenate(ps, axis=1)
    lane = pos % _LANES
    chunk = jnp.bitwise_and(key, mask)
    dbits = jnp.bitwise_and(key, ~mask)
    empty = key >= _SENTINEL
    dist = jnp.where(
        empty, _INF, jax.lax.bitcast_convert_type(dbits, jnp.float32))
    col = jnp.where(empty, -1, chunk * _LANES + lane)
    return dist, col


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_t", "metric", "n_valid",
                     "interpret", "compute_dtype", "n_attrs"),
)
def knn_topk_lanes(
    q: jnp.ndarray,                 # [nq, D] f32, nq % block_q == 0
    t: jnp.ndarray,                 # [nt, D] f32, nt % block_t == 0
    k: int = 8,
    block_q: int = 512,
    block_t: int = 4096,
    metric: str = "euclidean",
    n_valid: Optional[int] = None,
    interpret: bool = False,
    compute_dtype: str = "float32",
    n_attrs: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(dist [nq, k] ascending, index [nq, k]) via the lane-resident packed
    kernel — the fastest path. Distances are quantized to 2^-(23-pack_bits)
    relative (pack_bits = log2(nt/128); 2^-13 at nt=128k, never coarser
    than 2^-11 under the nt cap below), which can reorder near-ties.
    Semantics otherwise match knn_topk_pallas."""
    nq, d = q.shape
    nt = t.shape[0]
    assert nq % block_q == 0, f"pad queries to a multiple of {block_q}"
    assert nt % block_t == 0, f"pad train rows to a multiple of {block_t}"
    assert block_t % (2 * _LANES) == 0, "pair fold needs block_t % 256 == 0"
    assert k <= block_t
    pack_bits = _lane_pack_bits(nt)
    assert pack_bits <= 12, (
        f"corpus {nt} needs {pack_bits} chunk-id bits; cap is 12 "
        f"(<= {LANE_CORPUS_CAP} rows) to keep quantization <= 2^-11")
    nv = nt if n_valid is None else n_valid
    if metric == "euclidean":
        q = q * jnp.float32(-2.0)       # see _knn_kernel_lanes epilogue

    kernel = functools.partial(
        _knn_kernel_lanes, k=k, metric=metric, block_t=block_t, n_valid=nv,
        nt=nt, pack_bits=pack_bits,
        compute_dtype=jnp.dtype(compute_dtype).type)
    grid = (nq // block_q, nt // block_t)
    width = (k + _hi_depth(k)) * _LANES
    keys = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, width), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, width), jnp.int32),
        interpret=interpret,
    )(q, t)
    best_d, best_i = _extract_lane_topk(keys, k, pack_bits)
    # n_attrs: semantic attribute count when columns one-hot-expand fewer
    # mixed attributes (ops.distance mixed semantics); defaults to columns
    na = d if n_attrs is None else n_attrs
    if metric == "euclidean":
        best_d = jnp.sqrt(jnp.maximum(best_d, 0.0) / max(na, 1))
    else:
        best_d = best_d / max(na, 1)
    best_i = jnp.where(jnp.isinf(best_d), -1, best_i)
    return best_d, best_i


def _kernel_score(dist, kernel: str, kernel_param: float):
    """Reference vote scores (Neighborhood.java:150-218, KERNEL_SCALE=100)
    on [BQ] final attribute-averaged distances — the same formulas as
    models.knn._vote, evaluated in-kernel."""
    d = jnp.floor(dist * 100.0)
    if kernel == "none":
        return jnp.ones_like(d)
    if kernel == "linearMultiplicative":
        return jnp.where(d == 0, 200.0, jnp.floor(100.0 / jnp.maximum(d, 1.0)))
    if kernel == "linearAdditive":
        return jnp.maximum(100.0 - d, 0.0)
    if kernel == "gaussian":
        t = d / kernel_param
        return jnp.floor(100.0 * jnp.exp(-0.5 * t * t))
    raise ValueError(f"unknown kernel {kernel}")


def _knn_kernel_lanes_vote(q_ref, t_ref, lab_ref, keys_ref, scores_ref, *,
                           k: int, metric: str, block_t: int, n_valid: int,
                           nt: int, label_bits: int, n_classes: int,
                           n_attrs: int, kernel_fn: str, kernel_param: float,
                           n_tb: int, compute_dtype=jnp.float32):
    """Lane-resident top-k with a FUSED class vote epilogue.

    Same carry structure as _knn_kernel_lanes, but the key's low mantissa
    bits carry the train row's CLASS LABEL instead of its chunk id — the
    fused classify job needs votes, not neighbor identities, and
    label_bits (1-3) is far finer quantization than the 10-12 chunk-id
    bits (2^-20ish vs 2^-12). On the final train block the kernel
    extracts the row top-k from the carries and accumulates the
    kernel-weighted one-hot vote into scores [BQ, C] — the only HBM
    output that scales with k is gone (C columns instead of
    (k + khi) * 128 packed lanes), attacking the measured output-rate
    ceiling of the top-k kernel directly."""
    chunks = block_t // _LANES
    assert chunks % 2 == 0, "block_t must be a multiple of 256 (pair fold)"
    tb = pl.program_id(1)
    mask = jnp.int32((1 << label_bits) - 1)
    khi = _hi_depth(k)

    @pl.when(tb == 0)
    def _init():
        keys_ref[...] = jnp.full_like(keys_ref, _SENTINEL)
        scores_ref[...] = jnp.zeros_like(scores_ref)

    if metric == "euclidean":
        qv = q_ref[...]
        tv = t_ref[...]
        qs = 0.25 * jnp.sum(qv * qv, axis=1)[:, None]
        ts = jnp.sum(tv * tv, axis=1)[None, :]
        dot = jax.lax.dot_general(
            qv.astype(compute_dtype), tv.astype(compute_dtype),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_dot_precision(compute_dtype))
        tile = jnp.maximum(qs + ts + dot, 0.0)
    else:
        tile = _tile_distance(q_ref[...], t_ref[...], metric, compute_dtype)
    bits = jax.lax.bitcast_convert_type(tile, jnp.int32)
    # full-tile label OR + validity mask: Mosaic rejects 128-lane chunk
    # slices of the [1, block_t] labels block ("Invalid input layout"),
    # but lowers the whole-tile broadcast fine — chunk AFTER packing,
    # exactly like the topk kernel chunks its column-packed keys
    key_full = jnp.bitwise_or(jnp.bitwise_and(bits, ~mask), lab_ref[...])
    if n_valid < nt:
        col = jax.lax.broadcasted_iota(jnp.int32, key_full.shape, 1)
        key_full = jnp.where(tb * block_t + col < n_valid, key_full,
                             _SENTINEL)

    carr_lo = [keys_ref[:, j * _LANES:(j + 1) * _LANES] for j in range(k)]
    carr_hi = [keys_ref[:, (k + j) * _LANES:(k + j + 1) * _LANES]
               for j in range(khi)]

    def packed_chunk(c):
        return key_full[:, c * _LANES:(c + 1) * _LANES]

    def insert(carries, x):
        depth = len(carries)
        for j in range(depth):
            lo = jnp.minimum(carries[j], x)
            if j < depth - 1:
                x = jnp.maximum(carries[j], x)
            carries[j] = lo

    for c in range(0, chunks, 2):
        x0 = packed_chunk(c)
        x1 = packed_chunk(c + 1)
        insert(carr_lo, jnp.minimum(x0, x1))
        if khi:
            insert(carr_hi, jnp.maximum(x0, x1))
    keys_ref[...] = jnp.concatenate(carr_lo + carr_hi, axis=1)

    @pl.when(tb == n_tb - 1)
    def _vote_epilogue():
        # k min-extraction rounds with NO argmin: Mosaic only lowers
        # index-reductions for f32 and the packed keys are int32, so each
        # round consumes ALL lanes equal to the row minimum at once and
        # weights the vote by the duplicate count (clipped to the k-budget
        # left). Identical semantics to one-at-a-time extraction —
        # duplicate packed keys carry the same (distance, label) and so
        # the same vote — and fewer reduction passes when ties exist.
        cand = keys_ref[...]
        bq = cand.shape[0]
        cols = [jnp.zeros((bq,), jnp.float32) for _ in range(n_classes)]
        imax = jnp.int32(np.iinfo(np.int32).max)
        remaining = jnp.full((bq,), k, jnp.int32)
        for _ in range(k):
            m = jnp.min(cand, axis=1)                       # [BQ] packed
            eq = cand == m[:, None]
            cnt = jnp.sum(eq.astype(jnp.int32), axis=1)
            cand = jnp.where(eq, imax, cand)
            empty = m >= _SENTINEL
            take = jnp.where(empty, 0, jnp.minimum(cnt, remaining))
            remaining = remaining - take
            d2 = jax.lax.bitcast_convert_type(
                jnp.bitwise_and(m, ~mask), jnp.float32)
            if metric == "euclidean":
                dist = jnp.sqrt(jnp.maximum(d2, 0.0) / max(n_attrs, 1))
            else:
                dist = d2 / max(n_attrs, 1)
            # select, don't multiply: once every lane is consumed m is
            # int32 max, whose label-masked bits BITCAST TO NaN — and
            # NaN * 0 is NaN, which would poison the class columns
            s = jnp.where(take > 0,
                          _kernel_score(dist, kernel_fn, kernel_param)
                          * take.astype(jnp.float32), 0.0)
            lab = jnp.bitwise_and(m, mask)
            for c in range(n_classes):
                cols[c] = cols[c] + jnp.where(lab == c, s, 0.0)
        scores_ref[...] = jnp.stack(cols, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_classes", "n_attrs", "kernel_fn",
                     "kernel_param", "block_q", "block_t", "metric",
                     "n_valid", "interpret", "compute_dtype"),
)
def knn_classify_lanes(
    q: jnp.ndarray,                 # [nq, D] f32, nq % block_q == 0
    t: jnp.ndarray,                 # [nt, D] f32, nt % block_t == 0
    t_labels: jnp.ndarray,          # [nt] int32 class codes
    k: int = 8,
    n_classes: int = 2,
    n_attrs: Optional[int] = None,
    kernel_fn: str = "none",
    kernel_param: float = 1.0,
    block_q: int = 512,
    block_t: int = 4096,
    metric: str = "euclidean",
    n_valid: Optional[int] = None,
    interpret: bool = False,
    compute_dtype: str = "float32",
) -> jnp.ndarray:
    """Fully fused KNN classification: class scores [nq, n_classes] of the
    kernel-weighted top-k vote (Neighborhood semantics, non-class-cond
    modes), computed without the top-k results ever leaving the kernel.
    `n_attrs` overrides the distance-normalization divisor when columns
    are a one-hot expansion of fewer semantic attributes (mixed data)."""
    nq, d = q.shape
    nt = t.shape[0]
    assert nq % block_q == 0, f"pad queries to a multiple of {block_q}"
    assert nt % block_t == 0, f"pad train rows to a multiple of {block_t}"
    assert block_t % (2 * _LANES) == 0, "pair fold needs block_t % 256 == 0"
    assert k <= block_t
    label_bits = max(1, (n_classes - 1).bit_length())
    assert label_bits <= 6, f"{n_classes} classes need > 6 label bits"
    nv = nt if n_valid is None else n_valid
    na = d if n_attrs is None else n_attrs
    if metric == "euclidean":
        q = q * jnp.float32(-2.0)
    n_tb = nt // block_t

    kernel = functools.partial(
        _knn_kernel_lanes_vote, k=k, metric=metric, block_t=block_t,
        n_valid=nv, nt=nt, label_bits=label_bits, n_classes=n_classes,
        n_attrs=na, kernel_fn=kernel_fn, kernel_param=float(kernel_param),
        n_tb=n_tb, compute_dtype=jnp.dtype(compute_dtype).type)
    grid = (nq // block_q, n_tb)
    width = (k + _hi_depth(k)) * _LANES
    _, scores = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_t), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, width), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, n_classes), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, width), jnp.int32),
            jax.ShapeDtypeStruct((nq, n_classes), jnp.float32),
        ],
        # the full-tile packed-key intermediate (block_q x block_t i32, on
        # top of the f32 distance tile) overflows the 16M default scoped-
        # vmem stack at the bench shapes (1024x4096) by ~2M; raise the cap
        # modestly (a 96M cap sent the mosaic allocator into a search that
        # did not terminate within 20 minutes)
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=24 * 1024 * 1024),
        interpret=interpret,
    )(q, t, t_labels.astype(jnp.int32)[None, :])
    return scores


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_t", "metric", "n_valid",
                     "interpret", "compute_dtype", "packed", "n_attrs"),
)
def knn_topk_pallas(
    q: jnp.ndarray,                 # [nq, D] f32, nq % block_q == 0
    t: jnp.ndarray,                 # [nt, D] f32, nt % block_t == 0
    k: int = 8,
    block_q: int = 256,
    block_t: int = 8192,
    metric: str = "euclidean",
    n_valid: Optional[int] = None,
    interpret: bool = False,
    compute_dtype: str = "float32",
    packed: bool = False,
    n_attrs: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(dist [nq, k] ascending, index [nq, k]) of the k nearest train rows,
    and per query block the slices of slice_rows(block_t) train rows
    that the kernel extracted [nq // block_q] (packed: every one).

    Distances match ops.distance.pairwise_distance semantics (attribute-
    averaged; euclidean = sqrt of mean squared per-attribute distance) for
    pre-normalized numeric features. Pad rows (pad_train / query padding)
    to the block sizes; `n_valid` masks train padding.

    compute_dtype="bfloat16" runs the euclidean cross-term matmul in bf16
    (f32 accumulate) at the MXU's native rate — ~8 relative decimal digits
    become ~2-3, which can reorder near-tied neighbors but moves reported
    distances by <1e-2 relative; exact f32 is the default.

    packed=True uses the packed-key insertion-network kernel
    (_knn_kernel_packed): ~2-3x faster tile reduction in exchange for
    quantizing distances to ~2^-12 relative (and the tie-reordering that
    implies). Exact bit-level distances stay the default."""
    nq, d = q.shape
    nt = t.shape[0]
    assert nq % block_q == 0, f"pad queries to a multiple of {block_q}"
    assert nt % block_t == 0, f"pad train rows to a multiple of {block_t}"
    assert k <= block_t
    if packed:
        assert block_t % 128 == 0 and block_t <= (1 << _PACK_BITS), (
            f"packed kernel needs block_t % 128 == 0 and <= {1 << _PACK_BITS}")
    nv = nt if n_valid is None else n_valid

    kernel = functools.partial(
        _knn_kernel_packed if packed else _knn_kernel,
        k=k, metric=metric, block_t=block_t, n_valid=nv, nt=nt,
        compute_dtype=jnp.dtype(compute_dtype).type)
    grid = (nq // block_q, nt // block_t)
    out_specs = [
        # revisited across the train axis: the running best buffer
        pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((nq, k), jnp.float32),
        jax.ShapeDtypeStruct((nq, k), jnp.int32),
    ]
    scratch = []
    if not packed:
        # the exact kernel's count of extracted slices, one (8, 128) block
        # a query block, every element of it the count
        out_specs.append(pl.BlockSpec((8, _LANES), lambda i, j: (i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((grid[0] * 8, _LANES), jnp.int32))
        if metric == "manhattan" and _hoists_queries(d, block_q, block_t):
            scratch.append(pltpu.VMEM(
                (d, block_q, _group_rows(block_t)), jnp.float32))
    best_d, best_i, *n_ext = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i, j: (j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, t)
    # the packed kernel extracts every tile whole: every slice of it
    extracted = (n_ext[0][::8, 0] if n_ext else jnp.full(
        (grid[0],), nt // slice_rows(block_t), jnp.int32))
    na = d if n_attrs is None else n_attrs
    if metric == "euclidean":
        # kernel carries squared sums; finish to attribute-averaged sqrt
        best_d = jnp.sqrt(jnp.maximum(best_d, 0.0) / max(na, 1))
    else:
        best_d = best_d / max(na, 1)
    best_i = jnp.where(jnp.isinf(best_d), -1, best_i)
    return best_d, best_i, extracted


def pallas_available() -> bool:
    """The compiled kernel needs a real TPU backend; everywhere else the
    interpret path (tests) or the jnp route serves. A backend that fails
    to initialise raises here: it must not read as "no TPU"."""
    return jax.default_backend() == "tpu"
