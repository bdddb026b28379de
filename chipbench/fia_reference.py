"""The plain reference of a frequent-itemset job (`frequentItemsApriori`):
the baskets as one bit column an item, a set's support as the popcount of
the AND of its columns, Apriori's join and prune, and the line a set is
written as, in plain numpy. Imports nothing of the program and touches no
device.

**The baskets** are made by the generator the configuration names
(`generators/<kind>.py`): its patterns once, from the constant the
configuration states (`generator.pattern_seed`: one table for every run,
as `T10I4D100K` is one table, so the frequent items, the word count and
the candidate counts do not move with the seed), then from the seed the
baskets a chunk of `CHUNK_ROWS` at a time, each chunk from a generator of
its own, as distinct (basket, item) pairs. A chunk is
small on purpose: its arrays stay under the size from which the C library
hands memory back to the system at once, so the threads that draw reuse
theirs, and the machine's count of touched memory stays near what is live.
`Columns.bits` is [items, ceil(n / 8)] uint8: bit `t % 8` of byte `t // 8`
of row `i` says whether basket `t` holds item `i`.

**A set is frequent** when its count is *over* `fia.support.threshold` x n
(upstream's reducer compares with greater-than). **Candidates** of length
k are the joins of two frequent (k-1)-sets that agree in all but their
last item, kept where every (k-1)-subset is frequent. **A line** is the
set's item tokens in ascending order, then `count / n` to six decimals,
comma-separated; a file holds one length's sets in ascending order.
"""

from __future__ import annotations

import itertools
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from chipbench import generate

CHUNK_ROWS = 1 << 17                 # baskets drawn, packed and written at once
PATTERN_STREAM = 1 << 18             # seed stream of the patterns
ID_STREAM = 1 << 19                  # seed stream of the transaction ids
COUNT_THREADS = 8
SETS_A_TASK = 128                    # sets counted by one task of a thread
GATHER_BYTES = 8 << 20               # columns gathered in one step, at most
ItemSet = Tuple[int, ...]


# ------------------------------------------------------------- the job
def job_semantics(properties: Dict) -> Dict:
    """What the job's `fia.*` properties ask for."""
    return {"threshold": float(properties["fia.support.threshold"]),
            "max_length": int(properties.get("fia.item.set.length", 3)),
            "skip": int(properties.get("fia.skip.field.count", 1))}


def tokens(gen: Dict) -> List[str]:
    """Item number -> the token the file holds."""
    return [f"{gen['item_prefix']}{i:0{int(gen['item_digits'])}d}"
            for i in range(int(gen["items"]))]


def over(count: int, threshold: float, n: int) -> bool:
    return count > threshold * n


def line(items: Sequence[str], support: float) -> str:
    return ",".join(list(items) + [f"{support:.6f}"])


# --------------------------------------------------------- the baskets
def chunks(n: int) -> List[Tuple[int, int, int]]:
    """(chunk number, first basket, basket after the last)."""
    return [(c, lo, min(lo + CHUNK_ROWS, n))
            for c, lo in enumerate(range(0, n, CHUNK_ROWS))]


def draw_patterns(module, gen: Dict) -> Dict:
    """The pattern table: from `generator.pattern_seed`, not the run's."""
    return module.patterns(
        generate.seed_for(int(gen["pattern_seed"]), PATTERN_STREAM), gen)


def draw_chunk(module, seed: int, chunk: int, m: int, gen: Dict, pats: Dict):
    """(basket, item) of chunk `chunk`'s `m` baskets, numbered from 0."""
    return module.draw(generate.seed_for(seed, 0, chunk), m, gen, pats)


def chunk_bits(basket: np.ndarray, item: np.ndarray, m: int, n_items: int
               ) -> np.ndarray:
    """[n_items, ceil(m / 8)] uint8 of one chunk's distinct pairs."""
    width = (m + 7) // 8
    order = np.argsort(item, kind="stable")      # by item, then basket
    b = basket[order].astype(np.int64)
    cell = item[order].astype(np.int64) * width + (b >> 3)
    bit = (1 << (b & 7)).astype(np.uint8)
    out = np.zeros(n_items * width, np.uint8)
    if len(cell):
        first = np.flatnonzero(np.concatenate([[True], cell[1:] != cell[:-1]]))
        out[cell[first]] = np.add.reduceat(bit, first)   # distinct bits: OR
    return out.reshape(n_items, width)


class Columns:
    """The baskets of one file, a bit column an item."""

    def __init__(self, n: int, n_items: int):
        self.n, self.n_items = n, n_items
        self.bits = np.zeros((n_items, (n + 7) // 8), np.uint8)

    def put(self, lo: int, part: np.ndarray) -> None:
        """One chunk's columns, its first basket `lo` (a multiple of 8)."""
        self.bits[:, lo // 8: lo // 8 + part.shape[1]] = part

    def item_counts(self) -> np.ndarray:
        """[n_items] int64: the baskets that hold each item."""
        return np.array([np.bitwise_count(col).sum(dtype=np.int64)
                         for col in self.bits], np.int64)

    def count(self, items: Iterable[int]) -> int:
        """Baskets that hold every item of `items`."""
        return self._counts([tuple(items)])[0]

    def _counts(self, sets: Sequence[ItemSet]) -> List[int]:
        """The popcount of the AND of each set's columns (sets of one
        length). Short columns are gathered and ANDed all sets at once;
        long ones go a set at a time through two buffers of one column's
        size, so that nothing is allocated a set."""
        width = self.bits.shape[1]
        if width * len(sets) <= GATHER_BYTES:
            at = np.asarray(sets, np.int64)
            both = self.bits[at[:, 0]]
            for j in range(1, at.shape[1]):
                both &= self.bits[at[:, j]]
            return np.bitwise_count(both).sum(axis=1, dtype=np.int64).tolist()
        both, ones, out = np.empty(width, np.uint8), np.empty(width, np.uint8), []
        for items in sets:
            col = self.bits[items[0]]
            for i in items[1:]:
                col = np.bitwise_and(col, self.bits[i], out=both)
            out.append(int(np.bitwise_count(col, out=ones).sum(dtype=np.int64)))
        return out

    def counts(self, sets: Sequence[ItemSet]) -> List[int]:
        """`count` of each set, `SETS_A_TASK` at a time on a few threads
        (numpy drops the lock)."""
        if not len(sets):
            return []
        parts = [sets[lo:lo + SETS_A_TASK]
                 for lo in range(0, len(sets), SETS_A_TASK)]
        if len(parts) < 2:
            return self._counts(sets)
        with ThreadPoolExecutor(COUNT_THREADS) as pool:
            return [c for part in pool.map(self._counts, parts) for c in part]


def draw_columns(bench_dir: str, seed: int, n: int, gen: Dict
                 ) -> Tuple[Columns, Dict]:
    """The file's baskets and the patterns they were drawn from, without
    writing it: what the control compares against."""
    module = generate.load_module(bench_dir, "generators", gen["kind"])
    pats = draw_patterns(module, gen)
    cols = Columns(n, int(gen["items"]))
    for c, lo, hi in chunks(n):
        basket, item = draw_chunk(module, seed, c, hi - lo, gen, pats)
        cols.put(lo, chunk_bits(basket, item, hi - lo, cols.n_items))
    return cols, pats


# -------------------------------------------------------------- Apriori
def join_and_prune(frequent: Iterable[ItemSet], k: int) -> List[ItemSet]:
    """The candidates of length k from the frequent sets of length k - 1
    (sorted tuples), in ascending order."""
    have = set(frequent)
    by_head: Dict[ItemSet, List[int]] = {}
    for s in sorted(have):
        by_head.setdefault(s[:-1], []).append(s[-1])
    out = []
    for head, tails in sorted(by_head.items()):
        for a, b in itertools.combinations(tails, 2):
            cand = head + (a, b)
            if all(cand[:j] + cand[j + 1:] in have for j in range(k - 2)):
                out.append(cand)        # the two that were joined are there
    return out


def mine(cols: Columns, threshold: float, max_length: int
         ) -> List[List[Tuple[ItemSet, int]]]:
    """[[(set, count), ...] of length 1, of length 2, ...]: every set of
    up to `max_length` items whose count is over the threshold, a length's
    sets in ascending order; stops at the first length with none."""
    singles = [(i,) for i in range(cols.n_items)]
    levels, cands = [], singles
    for k in range(1, max_length + 1):
        if k > 1:
            cands = join_and_prune([s for s, _c in levels[-1]], k)
        kept = [(s, c) for s, c in zip(cands, cols.counts(cands))
                if over(c, threshold, cols.n)]
        if not kept:
            break
        levels.append(kept)
    return levels
