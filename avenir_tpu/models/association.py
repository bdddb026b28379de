"""Association mining: Apriori frequent itemsets + rule generation.

Reference (SURVEY §2.5): org/avenir/association/ — FrequentItemsApriori runs
one MR job per itemset length k (driver loops over k): k=1 emits each item
(FrequentItemsApriori.java:138-150); k>1 loads the frequent (k-1)-itemset
file and extends each itemset with co-occurring items, sorted-key dedup
(:151-195); values are transaction ids (exact support, fia.emit.trans.id) or
counts; the reducer thresholds support = count / fia.total.tans.count
against fia.support.threshold. InfrequentItemMarker.java:41-46 replaces
infrequent items with a marker token after k=1 to shrink later scans.
AssociationRuleMiner.java:44-190 generates antecedent sublists (up to
arm.max.ante.size) of each frequent itemset and keeps rules whose
confidence = support(itemset) / support(antecedent) exceeds
arm.conf.threshold.

TPU-native design: items are dictionary-encoded at ingest, like every other
categorical in this framework, and after the k=1 round only the frequent
ones are kept (InfrequentItemMarker applied at ingest). The support
counting is the N-proportional work and runs on device, by one of two
routes that write the same bytes:

- resident (FrequentItemsApriori.mine_whole, and mine() for rows already in
  memory): the file is read whole and tokenised by two native passes
  (native.ingest: the vocabulary and every item's count, then the baskets
  packed over the frequent items into bit columns, one row an item, one
  bit a basket); the columns are put on the chip once and stay there for
  every round. Pair supports are one Gram matrix, G = sum_t x_t x_t^T,
  blocked matmuls on the MXU that replace the Hadoop shuffle; a longer
  candidate's support is the popcount of the AND of its items' columns.
  A job that sees several chips shards the basket axis over a mesh of
  them: each chip holds a run of the slabs and counts its own, and one
  all-reduce a round adds the counts (the bytes written are the same).
- re-scan (FrequentItemsApriori.mine_stream: what does not fit the device
  or the host, a stated block size, exact transaction ids): one streamed
  scan per itemset length over bit-packed row blocks, counted by a
  popcount containment fold.

Candidate *generation* stays on the host (classical Apriori join + subset
prune over the frequent (k-1) sets): it is tiny, irregular, and
data-dependent — the wrong shape for XLA. The per-k loop of the
reference's driver survives as a host loop; the frequent-itemset state
between rounds stays as a plain file via save/load
(the reference's "model = file between steps" property, SURVEY §5).
"""

from __future__ import annotations

import os

from dataclasses import dataclass, field
from functools import partial
from itertools import combinations
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from avenir_tpu import obs as _obs
from avenir_tpu.native.ingest import SpillScanMixin


# --------------------------------------------------------------------------
# Transaction ingest
# --------------------------------------------------------------------------
def merge_support_counts(*states: "Dict") -> "Dict":
    """The miners' support-merge rule (the ROADMAP open question): sum
    per-candidate support counts keyed by candidate identity across
    shard states. Candidates are keyed canonically (token or
    sorted-token tuples), NOT by per-shard masked ids — shard sources
    discover vocabularies in data order, so only token-space keys align
    across shards. int32-safe by construction: per-shard device folds
    carry int32 counts, and this merge accumulates them as unbounded
    Python ints, so P shards each near the int32 ceiling can never wrap
    the merged total. A candidate absent from a shard simply contributes
    nothing (support 0 there). This is the reducer half of the
    MapReduce combiner/reducer contract (arXiv:1801.09802) the sharded
    mining drivers — and the straggler/redundant-work designs of
    arXiv:1802.03049 — are built on."""
    out: Dict = {}
    for state in states:
        for cand, cnt in state.items():
            out[cand] = out.get(cand, 0) + int(cnt)
    return out


def frequent_tokens(support1: Dict, min_count: float) -> List[str]:
    """The canonical frequent-token frontier after the merged k=1
    round: tokens whose merged support beats the threshold, SORTED —
    the one ordering every merged/sharded driver derives candidates
    (and the per-shard masks) from."""
    return sorted(t for t, cnt in support1.items() if cnt > min_count)


def stream_candidate_support(src: "StreamingTransactionSource",
                             cand_ids: List[Tuple[int, ...]],
                             c_pad: int, block: int = 8192) -> np.ndarray:
    """One streamed support pass over ONE source: candidates (masked
    item-id tuples in `src`'s id space) packed into a [c_pad, words]
    bitset matrix, blocks double-buffered against the donated int32
    device fold. The SINGLE implementation of the N-proportional
    counting — mine_stream, the sharded mine_stream_merged driver and
    the distributed per-k block workers all fold through it, which is
    what makes their counts (and therefore their outputs) identical by
    construction."""
    from avenir_tpu.core.stream import double_buffered
    from avenir_tpu.ops.bitset import (bitset_fold_counts,
                                       pack_index_rows_u32)

    cand_d = jnp.asarray(pack_index_rows_u32(
        cand_ids, src.masked_width, c_pad))
    counts_d = jnp.zeros(c_pad, jnp.int32)
    for packed in double_buffered(src.packed_chunks(block)):
        # host-side span: the donated fold dispatches async, so the
        # duration is dispatch+transfer time, not device occupancy;
        # recorded, as every per-block fold is, so it reads no counters
        t0 = _obs.now()
        counts_d = bitset_fold_counts(counts_d, jnp.asarray(packed), cand_d)
        _obs.record("stream.fold", t0, sink="apriori_support")
    return np.asarray(counts_d, np.int64)


def count_token_supports(src: "StreamingTransactionSource",
                         cands: List[Tuple[str, ...]], c_pad: int,
                         block: int = 8192) -> np.ndarray:
    """Support counts of canonical TOKEN-space candidates over ONE
    source, aligned to ``cands``: translate per source via token_code
    (a candidate holding a token this source never saw — or masked out
    — counts 0 without a scan), count the present ones through the one
    :func:`stream_candidate_support` fold. The per-shard body of
    mine_stream_merged AND the sharded per-k worker's block fold."""
    ids = [tuple(src.token_code(t) for t in cd) for cd in cands]
    present = [ci for ci, m in enumerate(ids)
               if all(i >= 0 for i in m)]
    counts = np.zeros(len(cands), np.int64)
    if present:
        shard = stream_candidate_support(
            src, [ids[ci] for ci in present], c_pad, block)
        counts[present] = shard[:len(present)]
    return counts


def collect_token_trans_ids(src: "StreamingTransactionSource",
                            all_sets: List[Tuple[str, ...]], c_pad: int,
                            block: int = 8192) -> List[List[str]]:
    """Per-set exact transaction-id lists over ONE source for the fused
    all-lengths id pass (fia.emit.trans.id): token-space sets translate
    via token_code, row ids come back in THIS source's row order — the
    per-shard body of _collect_trans_ids_merged and the sharded tids
    level's block fold. NOTE: rows come from ``src.chunks`` (the
    id-bearing python feed), so a per-block caller must hand a source
    whose paths ARE its block (a byte slice) — the cache stores no
    ids."""
    from avenir_tpu.ops.bitset import (bitset_contain_mask,
                                       pack_index_rows_u32, pack_rows_u32)

    tids: List[List[str]] = [[] for _ in all_sets]
    ids = [tuple(src.token_code(t) for t in cd) for cd in all_sets]
    present = [ci for ci, m in enumerate(ids)
               if all(i >= 0 for i in m)]
    if not present:
        return tids
    cand_d = jnp.asarray(pack_index_rows_u32(
        [ids[ci] for ci in present], src.masked_width, c_pad))
    for mh, row_ids in src.chunks(block, with_ids=True):
        m = np.asarray(bitset_contain_mask(
            jnp.asarray(pack_rows_u32(mh)), cand_d))
        for pi, ci in enumerate(present):
            for r in np.flatnonzero(m[:len(row_ids), pi]):
                tids[ci].append(str(row_ids[r]))
    return tids


class TransactionSet:
    """Dictionary-encoded transactions: multi-hot uint8 [N, V] + id column.

    Input rows follow the reference's layout (FrequentItemsApriori.java:
    134-150): a transaction id at `trans_id_ord`, `skip_field_count` leading
    non-item fields, every remaining field an item token. A `marker` token
    (InfrequentItemMarker output) is dropped at ingest.
    """

    def __init__(self, multihot: np.ndarray, vocab: List[str],
                 trans_ids: np.ndarray):
        self.multihot = multihot            # uint8 [N, V]
        self.vocab = vocab                  # item id -> token
        self.index = {t: i for i, t in enumerate(vocab)}
        self.trans_ids = trans_ids          # object [N]

    @classmethod
    def from_rows(cls, rows: Sequence[Sequence[str]], trans_id_ord: int = 0,
                  skip_field_count: int = 1,
                  marker: Optional[str] = None) -> "TransactionSet":
        vocab: List[str] = []
        index: Dict[str, int] = {}
        encoded: List[List[int]] = []
        ids: List[str] = []
        for row in rows:
            ids.append(row[trans_id_ord])
            items = []
            for tok in row[skip_field_count:]:
                if tok == "" or (marker is not None and tok == marker):
                    continue
                if tok not in index:
                    index[tok] = len(vocab)
                    vocab.append(tok)
                items.append(index[tok])
            encoded.append(items)
        mh = np.zeros((len(rows), max(len(vocab), 1)), dtype=np.uint8)
        for i, items in enumerate(encoded):
            mh[i, items] = 1
        return cls(mh, vocab, np.array(ids, dtype=object))

    @classmethod
    def from_csv(cls, source: Union[str, Iterable[str]], delim: str = ",",
                 trans_id_ord: int = 0, skip_field_count: int = 1,
                 marker: Optional[str] = None) -> "TransactionSet":
        import io, os
        if isinstance(source, str):
            if os.path.exists(source):
                lines: Iterable[str] = open(source, "r")
            elif "\n" in source or delim in source or source == "":
                lines = io.StringIO(source)
            else:
                raise FileNotFoundError(f"no such transactions file: {source!r}")
        else:
            lines = source
        rows = [
            # trim set matches the native seq_encode / streaming source
            [t.strip(" \t\r") for t in ln.rstrip("\n").split(delim)]
            for ln in lines if ln.strip()
        ]
        if hasattr(lines, "close") and lines is not source:
            lines.close()
        return cls.from_rows(rows, trans_id_ord, skip_field_count, marker)

    def __len__(self) -> int:
        return self.multihot.shape[0]


class StreamingTransactionSource(SpillScanMixin):
    """Re-iterable chunked transaction reader for unbounded-size mining.

    Apriori is inherently multi-pass — the reference runs one MR job per
    itemset length k over the same HDFS input
    (FrequentItemsApriori.java:123-126) — so streaming means each k-pass
    re-scans the file at O(block) host RSS instead of holding the [N, V]
    multi-hot matrix. Pass 1 (scan_items) freezes the item vocabulary and
    per-item supports — natively when the C encoder is built, so no
    per-row Python runs even on the discovery pass. After the k=1 round
    the miner installs the frequent-item mask (mask_items — the ingest
    form of the reference's InfrequentItemMarker), and packed_chunks()
    then yields uint32 BITSET blocks over the frequent vocabulary only:
    V shrinks to the surviving items and each block is ~8x smaller than
    the uint8 multi-hot it replaces."""

    def __init__(self, paths: Sequence[str], delim: str = ",",
                 trans_id_ord: int = 0, skip_field_count: int = 1,
                 marker: Optional[str] = None,
                 block_bytes: int = 64 << 20,
                 spill_cache: bool = True,
                 cache_budget_bytes: Optional[int] = None):
        self.paths = list(paths)
        self.delim = delim
        self.trans_id_ord = trans_id_ord
        self.skip = skip_field_count
        self.marker = marker
        self.block_bytes = block_bytes
        self.spill_cache = spill_cache
        self.cache_budget_bytes = cache_budget_bytes
        self.vocab: List[str] = []
        self.index: Dict[str, int] = {}
        self.n_trans = 0
        self._item_counts: Optional[np.ndarray] = None
        self._kept_ids: Optional[np.ndarray] = None   # orig ids, ascending
        self._remap: Optional[np.ndarray] = None      # orig id -> masked|-1
        self._cache = None            # EncodedBlockCache once pass 1 ran
        self._scan_counts: Optional[np.ndarray] = None
        self._scan_encoder = None

    def _row_blocks(self):
        from avenir_tpu.core.stream import iter_line_blocks, prefetched

        for path in self.paths:
            for lines in prefetched(
                    iter_line_blocks(path, self.block_bytes), depth=1):
                # trim set matches the native seq_encode trim exactly
                # (space/tab/CR): the vocab pass and the native counting
                # pass must agree on token identity
                yield [[t.strip(" \t\r") for t in ln.split(self.delim)]
                       for ln in lines]

    # ------------------------------------------------------------ pass 1
    # (scan lifecycle, SharedScan sink adapter and cache ownership live
    # in native.ingest.SpillScanMixin — one copy for both miner sources)
    @property
    def _scan_marker(self) -> Optional[str]:
        return self.marker

    def _reset_scan_state(self) -> None:
        self.n_trans = 0

    def _scan_result(self) -> Tuple[List[str], np.ndarray, int]:
        return self.vocab, self._item_counts, self.n_trans

    def _note_encoded_rows(self, per_row: np.ndarray, n: int) -> None:
        self.n_trans += n

    def scan_items(self) -> Tuple[List[str], np.ndarray, int]:
        """Pass 1: (vocab, per-item transaction counts, n_trans). An item
        repeated within one transaction counts once (multi-hot algebra).
        The pass also spills each block's region-compacted codes to the
        encoded-block cache (when enabled), so every later per-k scan
        replays encoded blocks instead of re-parsing CSV."""
        if self._item_counts is not None:
            return self.vocab, self._item_counts, self.n_trans
        return self._scan_all()

    def _scan_block(self, data: bytes) -> None:
        """Fold one raw byte block into the pass-1 state (native encoder
        when built, python tokenizer otherwise) and spill its encoded
        form to the cache."""
        from avenir_tpu.native.ingest import (csr_rows,
                                              distinct_row_code_counts)

        if self._scan_encoder is not None:
            out = self._scan_encoder.encode(data)
            if out is None:
                return
            codes, offsets, region, n = out
            self._grow_counts()
            row_of, _ = csr_rows(offsets)
            self._scan_counts += distinct_row_code_counts(
                row_of, codes, region, len(self.vocab))
            if self._cache is not None:
                blk_counts = np.bincount(row_of[region].astype(np.intp),
                                         minlength=n)
                self._cache.add_block(blk_counts, codes[region])
            self.n_trans += n
            return
        rows = [[t.strip(" \t\r") for t in ln.split(self.delim)]
                for ln in data.decode("utf-8", "replace").split("\n")
                if ln.strip()]
        if not rows:
            return
        blk_counts = np.zeros(len(rows), np.int64)
        blk_codes: List[int] = []
        for r, row in enumerate(rows):
            k0 = len(blk_codes)
            for tok in row[self.skip:]:
                if tok == "" or tok == self.marker:
                    continue
                i = self.index.get(tok)
                if i is None:
                    i = len(self.vocab)
                    self.index[tok] = i
                    self.vocab.append(tok)
                blk_codes.append(i)
            blk_counts[r] = len(blk_codes) - k0
        codes = np.asarray(blk_codes, np.int32)
        self._grow_counts()
        row_of = np.repeat(np.arange(len(rows), dtype=np.int32), blk_counts)
        region = np.ones(codes.shape[0], bool)
        self._scan_counts += distinct_row_code_counts(
            row_of, codes, region, len(self.vocab))
        if self._cache is not None:
            self._cache.add_block(blk_counts, codes)
        self.n_trans += len(rows)

    # ----------------------------------------------------- frequent mask
    def mask_items(self, keep_ids: Sequence[int]) -> int:
        """Install the frequent-item vocabulary mask (the ingest analog of
        InfrequentItemMarker.java:41-46): packed_chunks() thereafter
        encodes over ONLY these items, in masked id space 0..len(keep)-1
        (ascending original order, so sorted tuples stay sorted). Returns
        the masked vocabulary width."""
        kept = np.asarray(sorted(keep_ids), np.int32)
        remap = np.full(max(len(self.vocab), 1), -1, np.int32)
        remap[kept] = np.arange(kept.shape[0], dtype=np.int32)
        self._kept_ids, self._remap = kept, remap
        return int(kept.shape[0])

    @property
    def masked_width(self) -> int:
        return (len(self.vocab) if self._kept_ids is None
                else int(self._kept_ids.shape[0]))

    def masked_token(self, masked_id: int) -> str:
        """Token for a masked item id (identity when no mask installed)."""
        if self._kept_ids is None:
            return self.vocab[masked_id]
        return self.vocab[int(self._kept_ids[masked_id])]

    def token_code(self, tok: str) -> int:
        """Candidate-encoding lookup in the packed_chunks() id space
        (masked when a mask is installed); -2 marks a token this source
        never saw / masked out — its candidates count 0 here. Mirrors
        StreamingSequenceSource.token_code so the sharded mining driver
        translates canonical token-space candidates per shard."""
        i = self.index.get(tok)
        if i is None:
            return -2
        if self._remap is not None:
            i = int(self._remap[i])
            if i < 0:
                return -2
        return i

    def _apply_mask(self, r: np.ndarray, c: np.ndarray):
        if self._remap is None:
            return r, c
        m = self._remap[c]
        ok = m >= 0
        return r[ok], m[ok]

    # ------------------------------------------------------- chunk feeds
    def packed_chunks(self, block_rows: int = 8192):
        """Yield uint32 bitset blocks [block_rows, words(V_masked)] over
        the (masked) vocabulary; row tails zero-pad (an all-zero row
        contains no nonempty candidate, so it never counts). Rides the
        native ragged encoder when built — no per-row Python on the
        N-proportional path; the Python fallback packs the same blocks
        from split rows."""
        from avenir_tpu.ops.bitset import pack_rows_u32

        for mh in self._dense_chunks(block_rows):
            yield pack_rows_u32(mh)

    def _dense_chunks(self, block_rows: int):
        """uint8 [block_rows, V_masked] multi-hot blocks (mask applied).
        Replays the encoded-block cache when pass 1 spilled one and the
        sources are unchanged — no CSV read, no re-tokenize; sources
        whose segment the cache's byte budget evicted re-parse natively
        while the survivors keep replaying; otherwise the native (or
        python) re-parse path runs as before."""
        from avenir_tpu.core.stream import prefetched
        from avenir_tpu.native.ingest import (csr_region_mask, csr_rows,
                                              native_seq_ready,
                                              seq_encode_native)

        vm = max(self.masked_width, 1)

        def pages(r, c, n):
            # r is sorted (row_of nondecreasing): each page is a
            # searchsorted slice, not a full-array rescan
            bounds = np.searchsorted(
                r, np.arange(0, n + block_rows, block_rows,
                             dtype=np.int32))
            for page, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
                mh = np.zeros((block_rows, vm), np.uint8)
                mh[r[lo:hi] - page * block_rows, c[lo:hi]] = 1
                yield mh

        def replay_pages(blk_iter):
            for counts, codes in prefetched(blk_iter, depth=1):
                n = counts.shape[0]
                if n <= 0:
                    continue
                row_of = np.repeat(np.arange(n, dtype=np.int32), counts)
                r, c = self._apply_mask(row_of, codes)
                yield from pages(r, c, n)

        def parse_pages(path, byte_range=None):
            from avenir_tpu.core.stream import iter_byte_blocks

            for data in prefetched(
                    iter_byte_blocks(path, self.block_bytes, byte_range),
                    depth=1):
                # cannot be None: availability + 1-byte delim checked
                codes, offsets = seq_encode_native(
                    data, self.delim, self.vocab)
                n = offsets.shape[0] - 1
                if n <= 0:
                    continue
                # item region only; unknown tokens (-1: ids, marker,
                # empties) drop exactly like the python path
                valid = csr_region_mask(offsets, self.skip,
                                        codes.shape[0])
                np.logical_and(valid, codes >= 0, out=valid)
                row_of, _ = csr_rows(offsets)
                r, c = self._apply_mask(row_of[valid], codes[valid])
                yield from pages(r, c, n)

        if self._cache is not None and self._cache.valid:
            yield from replay_pages(self._cache.blocks())
            return
        if native_seq_ready(self.delim):
            for si, path in enumerate(self.paths):
                if self._cache is None:
                    yield from parse_pages(path)
                    continue
                if self._cache.source_valid(si):
                    yield from replay_pages(self._cache.blocks(si))
                    continue
                delta = self._cache.source_delta(si)
                if delta is not None:
                    # appended source: the committed blocks still
                    # content-match the file's prefix (per-block
                    # fingerprints) — replay them and re-parse only the
                    # appended tail instead of the whole file
                    yield from replay_pages(
                        self._cache.blocks(si, prefix=True))
                    yield from parse_pages(
                        path, (delta, os.path.getsize(path)))
                else:
                    yield from parse_pages(path)
            return

        for mh, _ids in self.chunks(block_rows):
            yield mh

    def chunks(self, block_rows: int = 8192, with_ids: bool = False):
        """Yield (multihot uint8 [block_rows, V_masked], ids) blocks from
        the Python row path — the id-bearing feed (the exact-trans-id
        pass needs per-row ids, which the native CSR encode drops) and
        the no-compiler fallback behind _dense_chunks."""
        vm = max(self.masked_width, 1)

        def emit(rows):
            mh = np.zeros((block_rows, vm), np.uint8)
            ids = []
            for r, row in enumerate(rows):
                if with_ids:
                    ids.append(row[self.trans_id_ord])
                for tok in row[self.skip:]:
                    i = self.index.get(tok)
                    if i is None:
                        continue
                    if self._remap is not None:
                        i = int(self._remap[i])
                        if i < 0:
                            continue
                    mh[r, i] = 1
            return mh, ids

        buf: List[List[str]] = []
        for rows in self._row_blocks():
            buf.extend(rows)
            while len(buf) >= block_rows:
                yield emit(buf[:block_rows])
                buf = buf[block_rows:]
        if buf:
            yield emit(buf)

# --------------------------------------------------------------------------
# Itemset containers (the between-rounds file state)
# --------------------------------------------------------------------------
@dataclass
class ItemSet:
    items: Tuple[str, ...]          # sorted item tokens
    support: float                  # fraction of transactions
    count: int
    trans_ids: Optional[List[str]] = None

    def line(self, delim: str = ",") -> str:
        parts = list(self.items) + [f"{self.support:.6f}"]
        if self.trans_ids is not None:
            parts += list(self.trans_ids)
        return delim.join(parts)


@dataclass
class ItemSetList:
    """Frequent itemsets of one length k (association/ItemSetList.java:34):
    the file handed from round k to round k+1."""
    length: int
    item_sets: List[ItemSet] = field(default_factory=list)

    def save(self, path: str, delim: str = ",") -> None:
        with open(path, "w") as fh:
            for s in self.item_sets:
                fh.write(s.line(delim) + "\n")

    @classmethod
    def load(cls, path: str, length: int, with_trans_ids: bool = False,
             delim: str = ",") -> "ItemSetList":
        sets = []
        with open(path) as fh:
            for ln in fh:
                toks = [t.strip() for t in ln.rstrip("\n").split(delim)]
                if not toks or toks == [""]:
                    continue
                items = tuple(toks[:length])
                support = float(toks[length])
                tids = toks[length + 1:] if with_trans_ids else None
                sets.append(ItemSet(items, support, 0, tids))
        return cls(length, sets)

    def supports(self) -> Dict[Tuple[str, ...], float]:
        return {s.items: s.support for s in self.item_sets}

    def __len__(self) -> int:
        return len(self.item_sets)


# --------------------------------------------------------------------------
# Device support counting
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("k",))
def _contain_counts(trans: jnp.ndarray, cand: jnp.ndarray, k: int):
    """counts[c] = #transactions containing all k items of candidate c.

    trans float32 [B, V] multi-hot tile, cand float32 [C, V] multi-hot.
    The matmul rides the MXU; equality against the static k recovers exact
    set containment."""
    overlap = trans @ cand.T                       # [B, C]
    return jnp.sum(overlap >= k, axis=0, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("k",))
def _contain_mask(trans: jnp.ndarray, cand: jnp.ndarray, k: int):
    return (trans @ cand.T) >= k                   # [B, C] bool


def _count_support(multihot: np.ndarray, cand_rows: np.ndarray, k: int,
                   block: int = 8192,
                   want_mask: bool = False):
    """Blocked streaming support count over transaction tiles."""
    n, v = multihot.shape
    c = cand_rows.shape[0]
    counts = np.zeros((c,), dtype=np.int64)
    masks = [] if want_mask else None
    cand_f = jnp.asarray(cand_rows, dtype=jnp.float32)
    for s in range(0, n, block):
        tile = jnp.asarray(multihot[s:s + block], dtype=jnp.float32)
        if want_mask:
            m = np.asarray(_contain_mask(tile, cand_f, k))
            masks.append(m)
            counts += m.sum(axis=0)
        else:
            counts += np.asarray(_contain_counts(tile, cand_f, k), dtype=np.int64)
    if want_mask:
        return counts, np.concatenate(masks, axis=0)
    return counts, None


# --------------------------------------------------------------------------
# Apriori driver
# --------------------------------------------------------------------------
def _generate_candidates(freq_prev: List[Tuple[int, ...]], k: int
                         ) -> List[Tuple[int, ...]]:
    """Classical Apriori join + prune on item-id tuples (host side).

    Equivalent to the reference's extend-with-co-occurring-item + sorted-key
    dedup (FrequentItemsApriori.java:151-195), minus the candidates the
    subset prune can reject early."""
    prev_set = set(freq_prev)
    freq_sorted = sorted(freq_prev)
    cands = []
    for i, a in enumerate(freq_sorted):
        for b in freq_sorted[i + 1:]:
            if a[:-1] != b[:-1]:
                break               # sorted: no more shared (k-2)-prefix
            cand = a + (b[-1],)
            # prune: all (k-1)-subsets must be frequent
            if all(cand[:j] + cand[j + 1:] in prev_set for j in range(k)):
                cands.append(cand)
    return cands


class FrequentItemsApriori:
    """Frequent itemset miner: host per-k loop + device support matmuls.

    Parameters mirror the reference's fia.* keys: support_threshold
    (fia.support.threshold, fraction), max_length (driver loop bound),
    emit_trans_id (fia.emit.trans.id → exact transaction id lists in the
    output, FrequentItemsApriori.java:143-149)."""

    def __init__(self, support_threshold: float, max_length: int = 3,
                 emit_trans_id: bool = False, block: int = 8192):
        self.support_threshold = support_threshold
        self.max_length = max_length
        self.emit_trans_id = emit_trans_id
        self.block = block

    def mine(self, tx: TransactionSet) -> List[ItemSetList]:
        """The in-memory form of the resident route: the multi-hot rows
        are packed over the frequent items into the same bit columns
        (`ops.bitset`) and counted by the same programs as a file's."""
        from avenir_tpu.ops.bitset import (columns_from_multihot,
                                           slab_words_for)

        n = len(tx)
        min_count = self.support_threshold * n
        col_counts = self.multihot_item_counts(tx)
        freq1 = [i for i in range(len(tx.vocab)) if col_counts[i] > min_count]
        cols_d = jnp.asarray(columns_from_multihot(
            tx.multihot[:, freq1], slab_words_for(n)))
        rounds = [(1, [(i,) for i in freq1],
                   [int(col_counts[i]) for i in freq1])]
        rounds += [(k, [tuple(freq1[m] for m in ids_t) for ids_t in ids_k],
                    counts_k)
                   for k, ids_k, counts_k in self._resident_rounds(
                       cols_d, len(freq1), min_count)]
        return [self._pack(tx, ids_k, k, counts_k)
                for k, ids_k, counts_k in rounds]

    # ------------------------------------------------- the resident route
    #: the share of the device's memory the packed baskets may take: the
    #: rest is head-room for the Gram's unpacked block, its [V, V] sums
    #: and whatever else the process holds on the chip
    RESIDENT_SHARE = 0.6
    #: what a backend that states no limit (the CPU) is held to
    UNSTATED_LIMIT_BYTES = 2 << 30
    #: the share of the host's memory the file may take, read whole: the
    #: packed columns stand beside it until the file is let go
    HOST_SHARE = 0.25

    @staticmethod
    def device_bytes_limit() -> int:
        """The least `bytes_limit` over the devices a job's mesh is made
        of (`utils.devices.job_mesh`): every one holds an equal share."""
        return min(int((d.memory_stats() or {}).get(
            "bytes_limit", FrequentItemsApriori.UNSTATED_LIMIT_BYTES))
            for d in jax.local_devices())

    @staticmethod
    def host_bytes() -> int:
        return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")

    def resident_words(self, n: int, frequent: int, devices: int = 1
                       ) -> Optional[Tuple[int, int]]:
        """(slab words, slabs a device) of the resident bit columns of `n`
        baskets over `frequent` items, or nothing where they do not fit.
        The slabs are dealt to the `devices` in contiguous runs of equal
        length, the last run padded with empty slabs (a basket of no bits
        adds to no count); the test is one run's packed bytes (4 a word
        and item row, `column_rows(frequent)` rows) against
        `RESIDENT_SHARE` of one device's `bytes_limit`."""
        from avenir_tpu.ops.bitset import column_rows, slab_words_for

        slab = slab_words_for(n)
        slabs = -(-max(n, 1) // (slab * 32))
        run = -(-slabs // devices)
        fits = (4 * slab * run * column_rows(frequent)
                <= self.RESIDENT_SHARE * self.device_bytes_limit())
        return (slab, run) if fits else None

    def mine_whole(self, paths: Sequence[str], delim: str = ",",
                   skip_field_count: int = 1, marker: Optional[str] = None,
                   mesh=None) -> Optional[Tuple[List[ItemSetList], int]]:
        """The resident route over a file: read whole, tokenised by two
        native passes that are each one call (`native.ingest.
        basket_scan_native`: the vocabulary in order of first appearance
        and every item's count; `basket_pack_native`: the baskets as bit
        columns over the frequent items), the columns put on the chip
        once, every later round one program over them
        (`_resident_rounds`). No block loop, no sort, no cache. Returns
        the itemset lists and the number of baskets. With a `mesh`
        (`utils.devices.job_mesh`: the job sees several chips) the
        columns' basket axis is sharded over it, a run of slabs a chip,
        and the rounds' counts are added across the chips; the read, the
        two passes and the bytes written are the same.

        Returns nothing where the route is not to be taken, and the
        caller mines the stream instead (`mine_stream` writes the same
        bytes). Of the five refusals (a stated block size, which the job
        sees before it calls; exact transaction ids; no native parser for
        this delimiter; a file over `HOST_SHARE` of the host's memory;
        packed baskets that do not fit the device) further chips lift the
        last alone: `resident_words` holds a chip to its share of the
        slabs (known only after pass 1, whose work is lost where even
        that does not fit). The file is still one buffer on one host."""
        from avenir_tpu.native.ingest import (basket_pack_native,
                                              basket_scan_native,
                                              native_seq_ready,
                                              read_files_native)
        from avenir_tpu.ops.bitset import column_rows

        if (self.emit_trans_id or not native_seq_ready(delim)
                or sum(os.path.getsize(p) for p in paths)
                > self.HOST_SHARE * self.host_bytes()):
            return None
        devices = mesh.size if mesh is not None else 1
        with _obs.span("fia.mine", resident=False, devices=devices) as note:
            with _obs.span("fia.read", files=len(paths)) as read:
                data = read_files_native(paths)
                read["nbytes"] = int(data.shape[0])
            with _obs.span("fia.scan", nth=1) as scanned:
                scan = basket_scan_native(data, delim, skip_field_count,
                                          marker)
                scanned.update(threads=scan.threads, tokens=scan.tokens)
            n = scan.rows
            min_count = self.support_threshold * n
            freq1 = np.flatnonzero(scan.counts > min_count)
            vm = int(freq1.shape[0])
            note.update(rows=n, vocab=len(scan.vocab), frequent=vm,
                        words=-(-vm // 32))
            fits = self.resident_words(n, vm, devices)
            if fits is None:
                return None
            rounds: List[Tuple[int, List[Tuple[int, ...]], List[int]]] = [
                (1, [(m,) for m in range(vm)], scan.counts[freq1].tolist())]
            if self.max_length > 1 and vm:
                # masked ids are ranks of the ascending original ids, as
                # the stream's mask_items numbers them
                item_row = np.full(len(scan.vocab), -1, np.int32)
                item_row[freq1] = np.arange(vm, dtype=np.int32)
                with _obs.span("fia.scan", nth=2, threads=scan.threads,
                               tokens=scan.tokens):
                    slabs = basket_pack_native(
                        data, delim, skip_field_count, marker, scan,
                        item_row, column_rows(vm), fits[0])
                del data
                cols_d = self._put_resident(slabs, mesh)
                del slabs
                rounds += self._resident_rounds(cols_d, vm, min_count, mesh)
                del cols_d
            note.update(rounds=len(rounds), resident=True)
            kept = [scan.vocab[i] for i in freq1.tolist()]
            return [self._item_sets(n, kept.__getitem__, ids_k, k, counts_k)
                    for k, ids_k, counts_k in rounds], n

    @staticmethod
    def _put_resident(slabs: np.ndarray, mesh=None) -> jnp.ndarray:
        """The packed slabs [slabs, rows, words] put on the chip once, a
        slab at a time into one array that is built in place; it stays
        there for every later round. With a `mesh` every chip gets a
        contiguous run of the slabs (`resident_words`) in an array of its
        own, the chips' puts issued in turn and none waited for, and the
        arrays are joined into one whose basket axis is sharded over the
        mesh: nothing is staged whole on one chip."""
        from jax.sharding import NamedSharding

        from avenir_tpu.ops.bitset import BASKETS_SHARDED, place_columns
        from avenir_tpu.utils.devices import note_devices_used

        n_slabs, v_rows, words = slabs.shape
        chips = [None] if mesh is None else list(mesh.devices.flat)
        run = -(-n_slabs // len(chips))
        attrs = {"nbytes": slabs.nbytes, "slabs": n_slabs}
        if mesh is not None:
            attrs.update(devices=len(chips),
                         nbytes_per_device=4 * v_rows * run * words)
        with _obs.span("fia.put", **attrs):
            parts = []
            for chip in chips:
                # made on its own chip: `jnp.zeros(device=chip)` makes the
                # array on the first chip and copies it over
                with jax.default_device(chip):
                    parts.append(jnp.zeros((v_rows, run * words), jnp.uint32))
            for j in range(run):
                for d, chip in enumerate(chips):
                    at = d * run + j
                    if at >= n_slabs:
                        continue          # padding: the slab stays empty
                    # the put is this route's fold of the stream: a slab
                    # of it goes into the resident state (the coverage
                    # auditor's name); recorded, as every per-block fold
                    # is, so it reads no counters
                    t0 = _obs.now()
                    # one chip: `jnp.int32` is a device operation that
                    # paces this loop; without it 24 more slabs are in
                    # flight at 50M baskets (340 MB)
                    if chip is None:
                        slab, word_at = (jnp.asarray(slabs[at]),
                                         jnp.int32(j * words))
                    else:
                        slab, word_at = (jax.device_put(slabs[at], chip),
                                         np.int32(j * words))
                    parts[d] = place_columns(parts[d], slab, word_at)
                    _obs.record("stream.fold", t0, sink="apriori_resident")
            if mesh is None:
                return jax.block_until_ready(parts[0])
            note_devices_used(len(chips))
            return jax.block_until_ready(
                jax.make_array_from_single_device_arrays(
                    (v_rows, len(chips) * run * words),
                    NamedSharding(mesh, BASKETS_SHARDED), parts))

    def _resident_rounds(self, cols_d: jnp.ndarray, frequent: int,
                         min_count: float, mesh=None):
        """Rounds 2 and up over the resident bit columns: [(k, sets as
        tuples of masked item ids, counts)]. Round 2 is one Gram matrix
        (`_pair_gram`): support({a, b}) = G[a, b], and no candidate list
        exists. A later round's candidates are the host's join and prune;
        their supports are one program (`_set_supports`), the candidate
        axis padded to a bucket size so that a recurring round compiles
        nothing. Over columns sharded on a `mesh` the two programs run on
        every chip's own words and one all-reduce a round adds the int32
        counts (`_pair_gram_mesh`, `_set_supports_mesh`); the host fetches
        one replica."""
        from avenir_tpu.ops.bitset import (GRAM_BLOCK_WORDS, _pair_gram,
                                           _pair_gram_mesh, _set_supports,
                                           _set_supports_mesh)

        devices = mesh.size if mesh is not None else 1
        rounds = []
        freq_ids: List[Tuple[int, ...]] = [(m,) for m in range(frequent)]
        for k in range(2, self.max_length + 1):
            with _obs.span("fia.round.candidates", k=k) as note:
                if k == 2:
                    n_cands = frequent * (frequent - 1) // 2
                else:
                    cands = _generate_candidates(freq_ids, k)
                    n_cands = len(cands)
                    c_pad = max(64, 1 << max(n_cands - 1, 0).bit_length())
                    cand_rows = np.zeros((c_pad, k), np.int32)
                    cand_rows[:n_cands] = np.asarray(
                        cands, np.int32).reshape(n_cands, k)
                note["candidates"] = n_cands
            if not n_cands:
                break
            with _obs.span("fia.round.dispatch", k=k, candidates=n_cands,
                           devices=devices):
                if k == 2:
                    words = cols_d.shape[1] // devices       # a chip's own
                    block = min(GRAM_BLOCK_WORDS, words)
                    while words % block:
                        block //= 2
                    out_d = (_pair_gram(cols_d, block) if mesh is None else
                             _pair_gram_mesh(cols_d, mesh, block))
                elif mesh is None:
                    out_d = _set_supports(cols_d, jnp.asarray(cand_rows))
                else:
                    out_d = _set_supports_mesh(cols_d, cand_rows, mesh)
            with _obs.span("fia.round.fetch", k=k, candidates=n_cands) as note:
                out = np.asarray(out_d)
                if k == 2:
                    a, b = np.nonzero(
                        np.triu(out[:frequent, :frequent], 1) > min_count)
                    freq_ids = list(zip(a.tolist(), b.tolist()))
                    counts = out[a, b].tolist()
                else:
                    keep = np.flatnonzero(out[:n_cands] > min_count)
                    freq_ids = [cands[i] for i in keep]
                    counts = out[keep].tolist()
                note["kept"] = len(freq_ids)
            if not freq_ids:
                break
            rounds.append((k, freq_ids, counts))
        return rounds

    def mine_stream(self, src: StreamingTransactionSource
                    ) -> List[ItemSetList]:
        """mine() at unbounded input size: one streamed scan per itemset
        length k (the reference's one-MR-job-per-k driver loop,
        FrequentItemsApriori.java:123-126).

        The N-proportional counting is a blocked BIT-PACKED device fold:
        after the k=1 pass the frequent-item mask shrinks the vocabulary
        (InfrequentItemMarker at ingest), chunks arrive as uint32 bitsets
        (~8x less block RSS than uint8 multi-hot), and the popcount
        containment kernel takes candidates of any length — one compiled
        executable serves every round, and the exact-transaction-id pass
        runs ONCE over the kept sets of ALL lengths fused into a single
        candidate matrix instead of one streamed scan per k. Chunk
        encode/pack double-buffers against the device fold, whose int32
        carry is DONATED (ops.bitset.bitset_fold_counts) — per-k rounds
        dispatch asynchronously with one host pull at the end. Per-k
        re-scans replay the pass-1 encoded-block cache when the sources
        are unchanged (see EncodedBlockCache) instead of re-parsing."""
        with _obs.span("fia.mine", resident=False) as note:
            with _obs.span("fia.scan"):
                vocab, col_counts, n = src.scan_items()
            min_count = self.support_threshold * n

            # k = 1 from the scan; install the frequent-item mask so every
            # later block encodes over the surviving vocabulary only.
            # Masked ids are ranks of the ascending original ids, so sorted
            # candidate tuples stay sorted under the remap.
            freq1 = [i for i in range(len(vocab))
                     if col_counts[i] > min_count]
            vm = src.mask_items(freq1)
            rounds: List[Tuple[int, List[Tuple[int, ...]], List[int]]] = [
                (1, [(m,) for m in range(vm)],
                 [int(col_counts[i]) for i in freq1])]

            freq_ids: List[Tuple[int, ...]] = rounds[0][1]
            for k in range(2, self.max_length + 1):
                cands = _generate_candidates(freq_ids, k)
                if not cands:
                    break
                # pad the candidate axis to a bucket size so recurring
                # rounds reuse the compiled executable; zero candidate
                # rows count 0
                c_pad = max(64, 1 << (len(cands) - 1).bit_length())
                counts = self._stream_support(src, cands, c_pad)
                kept = [(c, int(cnt))
                        for c, cnt in zip(cands, counts[:len(cands)])
                        if cnt > min_count]
                if not kept:
                    break
                freq_ids = [c for c, _ in kept]
                rounds.append((k, freq_ids, [cnt for _, cnt in kept]))
            note.update(rows=n, vocab=len(vocab), frequent=vm,
                        words=-(-vm // 32), rounds=len(rounds))

            tids = self._collect_trans_ids(src, rounds) \
                if self.emit_trans_id else None
            out: List[ItemSetList] = []
            at = 0
            for k, ids_k, counts_k in rounds:
                out.append(self._item_sets(
                    src.n_trans, src.masked_token, ids_k, k, counts_k,
                    tids[at:at + len(ids_k)] if tids is not None else None))
                at += len(ids_k)
            return out

    def _stream_support(self, src: StreamingTransactionSource,
                        cand_ids: List[Tuple[int, ...]], c_pad: int
                        ) -> np.ndarray:
        """One streamed support pass over ONE source — the module-level
        :func:`stream_candidate_support` at this miner's block size."""
        return stream_candidate_support(src, cand_ids, c_pad, self.block)

    def _merged_rounds(self, support1: Dict, n: int, count_fn):
        """The per-k control loop of the MERGED mining drivers over
        canonical token-space candidates: threshold the merged k=1
        supports, generate each level's candidates, count them through
        ``count_fn(k, cands, c_pad) -> int64 [len(cands)]``, prune, and
        stop on an empty frontier. Shared by mine_stream_merged (counts
        per shard source in-process) and the sharded per-k driver
        (counts per ledger block across worker processes) — ONE loop,
        so their kept sets and counts agree by construction."""
        min_count = self.support_threshold * n
        freq_toks = frequent_tokens(support1, min_count)
        rounds: List[Tuple[int, List[Tuple[str, ...]], List[int]]] = [
            (1, [(t,) for t in freq_toks],
             [int(support1[t]) for t in freq_toks])]

        freq_sets: List[Tuple[str, ...]] = rounds[0][1]
        for k in range(2, self.max_length + 1):
            cands = _generate_candidates(freq_sets, k)
            if not cands:
                break
            c_pad = max(64, 1 << (len(cands) - 1).bit_length())
            counts = count_fn(k, cands, c_pad)
            kept = [(cd, int(cnt)) for cd, cnt in zip(cands, counts)
                    if cnt > min_count]
            if not kept:
                break
            freq_sets = [cd for cd, _ in kept]
            rounds.append((k, freq_sets, [cnt for _, cnt in kept]))
        return rounds

    def _pack_merged_rounds(self, rounds, n: int,
                            tids: Optional[List[List[str]]] = None
                            ) -> List[ItemSetList]:
        """Merged rounds -> per-length ItemSetLists (sorted sets, global
        support fractions) — the artifact-shaping tail shared by
        mine_stream_merged and the sharded per-k driver."""
        out: List[ItemSetList] = []
        at = 0
        for k, sets_k, counts_k in rounds:
            sets = []
            for ci, cd in enumerate(sets_k):
                sets.append(ItemSet(
                    tuple(sorted(cd)), counts_k[ci] / n, int(counts_k[ci]),
                    tids[at + ci] if tids is not None else None))
            sets.sort(key=lambda s: s.items)
            out.append(ItemSetList(k, sets))
            at += len(sets_k)
        return out

    def mine_stream_merged(self, sources: Sequence[StreamingTransactionSource]
                           ) -> List[ItemSetList]:
        """mine_stream() over P shard sources with the support-merge
        algebra: each per-k round counts every candidate independently
        per shard (the SAME _stream_support fold mine_stream drives) and
        merges the counts via merge_support_counts, thresholding against
        the GLOBAL transaction count — so the mined output is
        byte-identical to a single mine_stream over the concatenated
        shards (integer counts partition exactly across row-aligned
        shards; the shard-merge auditor re-proves this every round).

        Candidates live in canonical token space here — per-shard masked
        ids don't align across shards (vocab discovery order is data
        order) — and translate per shard via token_code; a candidate
        with a token some shard never saw counts 0 there without a scan.
        fia.emit.trans.id concatenates per-shard id lists in shard
        order, which IS corpus order for byte-range shards."""
        srcs = list(sources)
        if len(srcs) == 1:
            return self.mine_stream(srcs[0])
        scans = [src.scan_items() for src in srcs]
        n = sum(s[2] for s in scans)
        min_count = self.support_threshold * n
        support1 = merge_support_counts(
            *[{vocab[i]: int(counts[i]) for i in range(len(vocab))}
              for vocab, counts, _n in scans])
        freq_toks = frequent_tokens(support1, min_count)
        for src in srcs:
            src.mask_items([src.index[t] for t in freq_toks
                            if t in src.index])

        def count_level(k, cands, c_pad):
            counts = np.zeros(len(cands), np.int64)
            for src in srcs:
                counts += count_token_supports(src, cands, c_pad,
                                               self.block)
            return counts

        rounds = self._merged_rounds(support1, n, count_level)
        tids = self._collect_trans_ids_merged(srcs, rounds) \
            if self.emit_trans_id else None
        return self._pack_merged_rounds(rounds, n, tids)

    def _collect_trans_ids_merged(self, srcs, rounds) -> List[List[str]]:
        """The exact-trans-id pass of the sharded driver: one fused
        all-lengths scan PER SHARD (collect_token_trans_ids),
        per-candidate id lists concatenated in shard order (= corpus
        order for byte-range shards)."""
        all_sets = [cd for _k, sets_k, _c in rounds for cd in sets_k]
        tids: List[List[str]] = [[] for _ in all_sets]
        if not all_sets:
            return tids
        c_pad = max(64, 1 << (len(all_sets) - 1).bit_length())
        for src in srcs:
            shard = collect_token_trans_ids(src, all_sets, c_pad,
                                            self.block)
            for ci in range(len(all_sets)):
                tids[ci].extend(shard[ci])
        return tids

    def _collect_trans_ids(self, src: StreamingTransactionSource,
                           rounds) -> List[List[str]]:
        """ONE extra streamed pass for fia.emit.trans.id: the kept sets of
        every length fuse into a single packed candidate matrix (the
        popcount kernel needs no per-length dispatch), so exact per-set
        transaction id lists cost one scan total, not one per k."""
        from avenir_tpu.ops.bitset import (bitset_contain_mask,
                                           pack_index_rows_u32, pack_rows_u32)

        all_sets = [ids_t for _k, ids_k, _c in rounds for ids_t in ids_k]
        if not all_sets:
            return []
        vm = src.masked_width
        c_pad = max(64, 1 << (len(all_sets) - 1).bit_length())
        cand_d = jnp.asarray(pack_index_rows_u32(all_sets, vm, c_pad))
        tids: List[List[str]] = [[] for _ in all_sets]
        for mh, ids in src.chunks(self.block, with_ids=True):
            m = np.asarray(bitset_contain_mask(
                jnp.asarray(pack_rows_u32(mh)), cand_d))
            for ci in range(len(all_sets)):
                for r in np.flatnonzero(m[:len(ids), ci]):
                    tids[ci].append(str(ids[r]))
        return tids

    @staticmethod
    def _item_sets(n: int, token_of, freq_ids: List[Tuple[int, ...]], k: int,
                   counts: List[int],
                   tids: Optional[List[List[str]]] = None) -> ItemSetList:
        """One length's kept sets as the file holds them: `token_of` turns
        a masked item id into its token; tokens ascending in a set, sets
        ascending in the list, support the count over `n`."""
        sets = []
        for ci, ids_t in enumerate(freq_ids):
            tokens = tuple(sorted(token_of(i) for i in ids_t))
            sets.append(ItemSet(tokens, counts[ci] / n, int(counts[ci]),
                                tids[ci] if tids is not None else None))
        sets.sort(key=lambda s: s.items)
        return ItemSetList(k, sets)

    def _pack(self, tx: TransactionSet, freq_ids: List[Tuple[int, ...]],
              k: int, counts: List[int]) -> ItemSetList:
        if not freq_ids:
            return ItemSetList(k, [])
        n = len(tx)
        mask = None
        if self.emit_trans_id:
            # the only case needing a second device pass: per-transaction
            # membership masks for the surviving frequent sets
            cand_rows = np.zeros((len(freq_ids), tx.multihot.shape[1]),
                                 np.uint8)
            for ci, items in enumerate(freq_ids):
                cand_rows[ci, list(items)] = 1
            _, mask = _count_support(
                tx.multihot, cand_rows, k, self.block, want_mask=True)
        sets = []
        for ci, ids in enumerate(freq_ids):
            tokens = tuple(sorted(tx.vocab[i] for i in ids))
            tids = (
                [str(t) for t in tx.trans_ids[mask[:, ci]]]
                if self.emit_trans_id else None
            )
            sets.append(ItemSet(tokens, counts[ci] / n, int(counts[ci]), tids))
        sets.sort(key=lambda s: s.items)
        return ItemSetList(k, sets)

    @staticmethod
    def multihot_item_counts(tx: TransactionSet) -> np.ndarray:
        return tx.multihot.astype(np.int64).sum(axis=0)


# --------------------------------------------------------------------------
# Infrequent item marker
# --------------------------------------------------------------------------
class InfrequentItemMarker:
    """Replace infrequent items with a marker token after the k=1 round
    (InfrequentItemMarker.java:41-46) so later scans shrink."""

    def __init__(self, frequent_items: Iterable[str], marker: str = "*",
                 skip_field_count: int = 1):
        self.frequent = set(frequent_items)
        self.marker = marker
        self.skip = skip_field_count

    def mark_row(self, row: Sequence[str]) -> List[str]:
        out = list(row[:self.skip])
        for tok in row[self.skip:]:
            out.append(tok if tok in self.frequent else self.marker)
        return out

    def mark(self, rows: Iterable[Sequence[str]]) -> List[List[str]]:
        return [self.mark_row(r) for r in rows]


# --------------------------------------------------------------------------
# Rule mining
# --------------------------------------------------------------------------
@dataclass
class AssociationRule:
    antecedent: Tuple[str, ...]
    consequent: Tuple[str, ...]
    confidence: float
    support: float                  # support of the full itemset
    lift: float = float("nan")

    def line(self) -> str:
        return (",".join(self.antecedent) + " -> " + ",".join(self.consequent)
                + f" ({self.confidence:.4f})")


class AssociationRuleMiner:
    """Rules from frequent itemsets (AssociationRuleMiner.java:94-190):
    antecedent = each sublist up to max_ante_size, confidence =
    support(itemset) / support(antecedent), kept when above the threshold
    (arm.conf.threshold). Lift (vs the consequent's marginal support) is
    added when the consequent's support is known."""

    def __init__(self, conf_threshold: float, max_ante_size: int = 3):
        self.conf_threshold = conf_threshold
        self.max_ante_size = max_ante_size

    def mine(self, item_set_lists: Sequence[ItemSetList]
             ) -> List[AssociationRule]:
        supports: Dict[Tuple[str, ...], float] = {}
        for isl in item_set_lists:
            supports.update(isl.supports())
        rules: List[AssociationRule] = []
        for isl in item_set_lists:
            if isl.length < 2:
                continue
            for s in isl.item_sets:
                items = s.items
                for size in range(1, min(self.max_ante_size, len(items) - 1) + 1):
                    for ante in combinations(items, size):
                        ante_sup = supports.get(tuple(sorted(ante)))
                        if ante_sup is None or ante_sup <= 0:
                            continue
                        conf = s.support / ante_sup
                        if conf > self.conf_threshold:
                            cons = tuple(t for t in items if t not in ante)
                            cons_sup = supports.get(tuple(sorted(cons)))
                            lift = (conf / cons_sup) if cons_sup else float("nan")
                            rules.append(AssociationRule(
                                ante, cons, conf, s.support, lift))
        rules.sort(key=lambda r: (-r.confidence, r.antecedent, r.consequent))
        return rules
