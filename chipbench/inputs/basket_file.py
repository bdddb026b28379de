"""The inputs of a job that reads one file of baskets and writes a
directory of itemset files, such as `frequentItemsApriori`: one file made
from the seed (the baskets; the generator's pattern table is the
configuration's own, `generator.pattern_seed`) and the properties; no
schema and no test files. Found by
`inputs_kind` in the configuration's file.

A row is the transaction id (`generator.id_digits` digits), then the
basket's items, each as its token (`generator.item_prefix` and
`generator.item_digits` digits), in ascending order and each once: rows
are of unequal length, which `one_csv_bulk`'s sheet of columns cannot
write. The bytes are laid out by whole columns: a row's place from the
running sum of the rows' lengths, an item's from its rank in its basket;
a chunk of `fia_reference.CHUNK_ROWS` baskets at a time on a few threads,
each chunk from a generator of its own (`fia_reference.draw_chunk`).

What is kept for the check: the baskets as one bit column an item
(`columns`), the generator's patterns, and the tokens. Every job of the
window reads the same file (`n_files` is 1), so every output has to equal
the warm-up job's byte for byte.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import fia_reference as ref
from chipbench import generate

DRAW_THREADS = 8


def _digits(out, at, values, width):
    """`values` as `width` decimal digits into `out`, the most
    significant at `at`."""
    v = values.astype(np.int64)
    for j in range(width - 1, -1, -1):
        v, r = np.divmod(v, 10)
        out[at + j] = r + 48


def format_rows(ids, basket, item, m, gen):
    """The chunk's `m` rows as CSV bytes, from its sorted distinct pairs."""
    id_digits, digits = int(gen["id_digits"]), int(gen["item_digits"])
    prefix = gen["item_prefix"].encode()
    token = 1 + len(prefix) + digits                 # ",I042"
    held = np.bincount(basket, minlength=m)
    length = id_digits + token * held + 1
    row_at = np.cumsum(length) - length
    out = np.empty(int(length.sum()), np.uint8)
    _digits(out, row_at, ids, id_digits)
    nth = np.arange(len(basket)) - (np.cumsum(held) - held)[basket]
    at = row_at[basket] + id_digits + token * nth
    out[at] = ord(",")
    for j, ch in enumerate(prefix):
        out[at + 1 + j] = ch
    _digits(out, at + 1 + len(prefix), item, digits)
    out[row_at + length - 1] = ord("\n")
    return out.tobytes()


def write_properties(cfg, work):
    path = os.path.join(work, "job.properties")
    with open(path, "w") as fh:
        for key, val in cfg["properties"].items():
            fh.write(f"{key}={val}\n")
    return path


class Inputs:
    out_suffix = ""                 # the output is a directory of files
    n_files = 1

    def __init__(self, cell, seed, work):
        cfg = cell.config
        gen = cfg["generator"]
        n = self.n = int(cfg["train_rows"])
        module = generate.load_module(cell.bench_dir, "generators", gen["kind"])
        self.tokens = ref.tokens(gen)
        self.patterns = ref.draw_patterns(module, gen)
        self.columns = ref.Columns(n, int(gen["items"]))
        lo_id = 10 ** (int(gen["id_digits"]) - 1)

        def one(span):
            c, lo, hi = span
            basket, item = ref.draw_chunk(module, seed, c, hi - lo, gen,
                                          self.patterns)
            ids = generate.seed_for(seed, ref.ID_STREAM, c).integers(
                lo_id, 10 * lo_id, hi - lo)
            self.columns.put(lo, ref.chunk_bits(basket, item, hi - lo,
                                                self.columns.n_items))
            return format_rows(ids, basket, item, hi - lo, gen)

        self.train_path = os.path.join(work, "baskets.csv")
        with open(self.train_path + ".part", "wb") as fh, \
                ThreadPoolExecutor(DRAW_THREADS) as pool:
            for blob in pool.map(one, ref.chunks(n)):
                fh.write(blob)
        os.replace(self.train_path + ".part", self.train_path)
        self.props_path = write_properties(cfg, work)
        self.job = cfg["job"]

    def argv(self, file_no, out):
        return [self.job, "--conf", self.props_path, self.train_path, out]

    def warmup_argv(self, out):
        return self.argv(0, out)
