"""Bit-packed set containment: the streamed miners' counting kernel.

The Apriori / GSP streaming path is N-proportional in exactly one place:
"does transaction t contain candidate c" evaluated for every (row,
candidate) pair of every chunk. The dense formulation — uint8 multi-hot
rows against a float32 candidate matrix, `(T @ C.T) == k` — pays 8x the
memory it needs per block (one byte per vocabulary bit) and recompiles
per candidate length because k is a static argument.

Here transaction rows are packed 32 vocabulary bits per uint32 word
(`pack_rows_u32`), and containment runs as a popcount fold over the words:

    overlap[b, c] = sum_w popcount(trans[b, w] & cand[c, w])
    contained     = overlap == popcount-weight(cand[c])

The candidate weight is computed in-kernel, so ONE compiled executable
counts candidates of every itemset length — a whole mining round (and the
final transaction-id pass over kept sets of ALL lengths) batches into a
single fused [C_total, W] candidate matrix per chunk. Blocks shrink ~8x
(uint32 bitset vs uint8 multi-hot), which is what keeps the 100M-row
streamed Apriori inside its RSS budget. `jnp`-portable: population_count
lowers to the VPU on TPU and to vectorized code on CPU.

The second half of the module is the miners' resident route: the same
baskets item-major, as bit columns that stay on the chip across the
rounds (`place_columns`), the pairs' Gram matrix on the MXU
(`_pair_gram`) and the longer sets' supports as popcounts of ANDed
columns (`_set_supports`). Where a job runs on several chips the basket
axis is sharded over a mesh and each chip counts its own words by the
same two programs; the int32 counts are added across the chips
(`_pair_gram_mesh`, `_set_supports_mesh`).
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from avenir_tpu.parallel.mesh import DATA_AXIS, shard_map

WORD_BITS = 32


def words_for(n_bits: int) -> int:
    """uint32 words needed for n_bits vocabulary bits (>= 1: zero-width
    arrays would force a separate compiled shape for the empty edge)."""
    return max((max(n_bits, 0) + WORD_BITS - 1) // WORD_BITS, 1)


def pack_rows_u32(multihot: np.ndarray) -> np.ndarray:
    """uint8/bool multi-hot [N, V] -> uint32 bitset [N, words_for(V)].

    Bit b of word w holds vocabulary column w*32 + b (little-endian bit
    order); packer and candidate encoder must agree, nothing else reads
    the layout."""
    mh = np.ascontiguousarray(multihot, dtype=np.uint8)
    n, v = mh.shape
    w = words_for(v)
    pad_cols = w * WORD_BITS - v
    if pad_cols:
        mh = np.pad(mh, ((0, 0), (0, pad_cols)))
    packed = np.packbits(mh, axis=1, bitorder="little")
    return packed.view(np.uint32).reshape(n, w)


def pack_index_rows_u32(item_rows: Sequence[Sequence[int]], n_bits: int,
                        n_rows: int = 0) -> np.ndarray:
    """Candidate index tuples -> uint32 bitset [max(n_rows, len), W].

    Rows past len(item_rows) stay all-zero (shape-bucket padding); the
    kernel counts zero-weight rows as 0, so padding never counts."""
    rows = max(n_rows, len(item_rows))
    out = np.zeros((rows, words_for(n_bits)), np.uint32)
    for r, items in enumerate(item_rows):
        for i in items:
            out[r, i // WORD_BITS] |= np.uint32(1) << np.uint32(i % WORD_BITS)
    return out


@jax.jit
def _overlap_fold(trans: jnp.ndarray, cand: jnp.ndarray) -> jnp.ndarray:
    """popcount(t & c) summed over words: int32 [B, C].

    A lax.scan over the word axis keeps the live intermediate at [B, C]
    instead of materializing the [B, C, W] AND product."""
    def step(acc, w):
        t_w, c_w = w                                     # [B], [C]
        hit = jax.lax.population_count(t_w[:, None] & c_w[None, :])
        return acc + hit.astype(jnp.int32), None

    init = jnp.zeros((trans.shape[0], cand.shape[0]), jnp.int32)
    acc, _ = jax.lax.scan(step, init, (trans.T, cand.T))
    return acc


@jax.jit
def bitset_contain_counts(trans: jnp.ndarray, cand: jnp.ndarray
                          ) -> jnp.ndarray:
    """counts[c] = #rows of `trans` whose bitset is a superset of cand[c].

    trans uint32 [B, W], cand uint32 [C, W] — candidates of MIXED itemset
    lengths share one call (the weight is computed per candidate, not
    passed statically). All-zero candidate rows (shape padding) count 0."""
    weight = jnp.sum(
        jax.lax.population_count(cand).astype(jnp.int32), axis=1)   # [C]
    contained = _overlap_fold(trans, cand) == weight[None, :]       # [B, C]
    return jnp.sum(contained & (weight > 0)[None, :], axis=0,
                   dtype=jnp.int32)


@partial(jax.jit, donate_argnums=(0,))
def bitset_fold_counts(acc: jnp.ndarray, trans: jnp.ndarray,
                       cand: jnp.ndarray) -> jnp.ndarray:
    """acc + bitset_contain_counts(trans, cand) with the accumulator
    DONATED: the per-chunk fold carry of the streamed miners. A chunk
    loop re-dispatching this keeps exactly one [C] int32 buffer alive on
    device (the donated input aliases the output) and never round-trips
    the host — counts are exact int32 (bounded by the transaction count,
    < 2^31 at any measured scale), so the fold is chunk-layout-invariant
    by integer associativity."""
    return acc + bitset_contain_counts(trans, cand)


@jax.jit
def bitset_contain_mask(trans: jnp.ndarray, cand: jnp.ndarray
                        ) -> jnp.ndarray:
    """bool [B, C]: row b contains candidate c (zero-weight rows False) —
    the exact-transaction-id pass over kept sets of every length."""
    weight = jnp.sum(
        jax.lax.population_count(cand).astype(jnp.int32), axis=1)
    return (_overlap_fold(trans, cand) == weight[None, :]) & \
        (weight > 0)[None, :]


def packed_block_nbytes(block_rows: int, n_bits: int) -> Tuple[int, int]:
    """(packed, dense) block byte sizes — the ~8x RSS headroom the packed
    path buys; surfaced so benches can report it without re-deriving."""
    return (block_rows * words_for(n_bits) * 4, block_rows * max(n_bits, 1))


# --------------------------------------------------------------------------
# Resident bit columns: the baskets kept on the chip across the rounds
# --------------------------------------------------------------------------
# The miners' resident route holds the baskets item-major: uint32
# [words_for(V) * 32, n_pad / 32], bit t % 32 of word t // 32 of row i says
# whether basket t holds item i. A basket costs words_for(V) * 4 bytes
# and nothing else: both axes are whole tiles (the item axis a multiple of
# 32, the word axis of SLAB_ALIGN), so the chip pads neither. Rows past V
# and baskets past n are all zero and count nowhere.
SLAB_ALIGN = 128                 # words: a slab is whole lanes
GRAM_BLOCK_WORDS = 8192          # 262,144 baskets a block of the Gram
_F32_EXACT = 1 << 24             # a float32 sum of ones is exact up to here


def column_rows(n_bits: int) -> int:
    """Rows of the resident column array for n_bits items."""
    return words_for(n_bits) * WORD_BITS


def slab_words_for(n_rows: int, most_rows: int = 1 << 17) -> int:
    """Words (of 32 baskets) in one slab of the resident columns, which
    is what one put carries to the chip: whole lanes, at most `most_rows`
    baskets, no more than the file needs."""
    words = -(-max(n_rows, 1) // WORD_BITS)
    words = -(-words // SLAB_ALIGN) * SLAB_ALIGN
    return min(words, most_rows // WORD_BITS)


def columns_from_multihot(multihot: np.ndarray, words: int) -> np.ndarray:
    """uint8 multi-hot [N, V] -> resident columns uint32
    [column_rows(V), words] (words * 32 >= N)."""
    n, v = multihot.shape
    out = np.zeros((column_rows(v), words * 4), np.uint8)
    packed = np.packbits(np.ascontiguousarray(multihot.T, dtype=np.uint8),
                         axis=1, bitorder="little")
    out[:v, :packed.shape[1]] = packed
    return out.view(np.uint32)


@partial(jax.jit, donate_argnums=(0,))
def place_columns(cols: jnp.ndarray, slab: jnp.ndarray,
                  word_at: jnp.ndarray) -> jnp.ndarray:
    """`cols` with `slab` written at word `word_at`; `cols` is DONATED, so
    the resident array is built in place, a slab at a time."""
    return jax.lax.dynamic_update_slice_in_dim(cols, slab, word_at, axis=1)


@partial(jax.jit, static_argnames=("block_words",))
def _pair_gram(cols: jnp.ndarray, block_words: int) -> jnp.ndarray:
    """G[a, b] = #baskets that hold items a and b, int32 [V_rows, V_rows],
    over the resident columns: the sum over baskets of x x^T as matmuls on
    the MXU. A block of `block_words` words is unpacked one bit plane at a
    time (32 baskets a word, in any order: a sum over baskets) to 0/1 in
    bfloat16; the planes of a block add up in float32, exact while the
    block holds at most 2^24 baskets, and the blocks add up in int32. The
    diagonal is every item's own count."""
    v_rows, n_words = cols.shape
    assert n_words % block_words == 0
    assert block_words * WORD_BITS <= _F32_EXACT

    def block(i, total):
        words = jax.lax.dynamic_slice_in_dim(
            cols, i * block_words, block_words, axis=1)

        def plane(j, acc):
            x = ((words >> j.astype(jnp.uint32)) & 1).astype(jnp.bfloat16)
            return acc + jax.lax.dot_general(
                x, x, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        part = jax.lax.fori_loop(
            0, WORD_BITS, plane, jnp.zeros((v_rows, v_rows), jnp.float32))
        return total + part.astype(jnp.int32)

    return jax.lax.fori_loop(0, n_words // block_words, block,
                             jnp.zeros((v_rows, v_rows), jnp.int32))


@jax.jit
def _set_supports(cols: jnp.ndarray, cands: jnp.ndarray) -> jnp.ndarray:
    """counts[c] = #baskets that hold every item of cands[c], int32 [C]:
    the popcount of the AND of the candidate's columns, a candidate at a
    time over the resident columns. cands int32 [C, k]; a padding row
    names item 0 k times and its count is dropped by the caller."""
    def one(items):
        both = cols[items[0]]
        for j in range(1, cands.shape[1]):
            both = both & cols[items[j]]
        return jnp.sum(jax.lax.population_count(both).astype(jnp.int32))

    return jax.lax.map(one, cands)


# The same two programs over columns whose basket axis is sharded over a
# mesh's data axis: under `shard_map` each chip slices and counts its own
# words (partitioning by annotation alone would all-gather the columns
# for the Gram's dynamic slices), and `psum` adds the int32 counts, so
# float32 never sums past one block of one chip.
BASKETS_SHARDED = P(None, DATA_AXIS)     # the resident columns on a mesh


@partial(jax.jit, static_argnames=("mesh", "block_words"))
def _pair_gram_mesh(cols: jnp.ndarray, mesh: Mesh, block_words: int
                    ) -> jnp.ndarray:
    """`_pair_gram` of every chip's words, added across the chips: int32
    [V_rows, V_rows], replicated. `block_words` divides a chip's words."""
    def shard(words):
        return jax.lax.psum(_pair_gram(words, block_words), DATA_AXIS)

    return shard_map(shard, mesh, in_specs=BASKETS_SHARDED,
                     out_specs=P())(cols)


@partial(jax.jit, static_argnames=("mesh",))
def _set_supports_mesh(cols: jnp.ndarray, cands: jnp.ndarray, mesh: Mesh
                       ) -> jnp.ndarray:
    """`_set_supports` of every chip's words for the same candidates,
    added across the chips: int32 [C], replicated."""
    def shard(words, rows):
        return jax.lax.psum(_set_supports(words, rows), DATA_AXIS)

    return shard_map(shard, mesh, in_specs=(BASKETS_SHARDED, P()),
                     out_specs=P())(cols, cands)
