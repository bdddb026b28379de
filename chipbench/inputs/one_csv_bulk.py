"""The inputs of a job that reads one file and writes a model, such as
`randomForest`: one CSV made from the seed, the schema and the properties;
no test files. Found by `inputs_kind` in the configuration's file.

A row follows the schema's ordinals: the id (`generator.id_digits`
digits), each feature field (a categorical field as its value's name, an
`int` field as the whole number), the class as its name, and at the
ordinals the schema does not declare a column of `generator.unread`, which
the job skips as upstream does. The general writer `generate.make_csv`
writes numeric feature columns only, so this module has its own: whole
columns into a fixed-width sheet, a million rows at a time on a few
threads, each chunk drawn from a generator of its own, the places a
shorter value leaves empty dropped at the end.

Every job of the window reads the same file (`n_files` is 1) and writes a
directory of `tree-NNN.json`, so every output has to equal the warm-up
job's byte for byte.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import generate, jobfiles
from chipbench.forest_reference import feature_fields  # flagged `feature`

UNREAD_STREAM = 1 << 19              # seed stream of the unread columns


def chunks(n):
    """(chunk number, first row, row after the last) of the chunks n rows
    are drawn and written in, each from a generator of its own."""
    return [(c, lo, min(lo + generate.CHUNK_ROWS, n))
            for c, lo in enumerate(range(0, n, generate.CHUNK_ROWS))]


def draw_features(module, seed, n, gen, fields):
    """(codes [n, d] int16, y [n] int8) of a whole file, chunk by chunk,
    without writing it: what the check's control compares against."""
    codes = np.empty((n, len(fields)), np.int16)
    y = np.empty(n, np.int8)
    for c, lo, hi in chunks(n):
        codes[lo:hi], y[lo:hi] = module.draw(
            generate.seed_for(seed, 0, c), hi - lo, gen, fields)
    return codes, y


def _names(sheet, col, codes, names):
    """The names `names[codes]` into the sheet from `col`; returns the
    column after the longest."""
    longest = max(len(v) for v in names)
    table = np.zeros((len(names), longest), np.uint8)
    for i, v in enumerate(names):
        table[i, :len(v)] = np.frombuffer(str(v).encode(), np.uint8)
    sheet[:, col:col + longest] = table[codes]
    return col + longest


def _number(sheet, col, values, width):
    """`values` (not negative) as up to `width` digits with no leading
    zeros; returns the column after them."""
    generate._digits(sheet, col, values.astype(np.int64), width)
    for j in range(width - 1):
        sheet[values < 10 ** (width - 1 - j), col + j] = 0
    return col + width


def format_rows(ids, unread, codes, y, gen, fields, class_at):
    """The rows as CSV bytes: columns by ordinal, one sheet."""
    m = len(y)
    by_ordinal = {0: ("number", ids, int(gen["id_digits"]))}
    for o, idx in unread.items():
        by_ordinal[o] = ("names", idx, [str(v) for v in gen["unread"][str(o)]])
    for j, f in enumerate(fields):
        if f["dataType"] == "categorical":
            by_ordinal[f["ordinal"]] = ("names", codes[:, j], f["cardinality"])
        else:
            by_ordinal[f["ordinal"]] = ("number", codes[:, j],
                                        len(str(int(f["max"]))))
    by_ordinal[class_at] = ("names", y, list(gen["classes"]))
    cols = [by_ordinal[o] for o in sorted(by_ordinal)]
    width = sum(c[2] if c[0] == "number" else max(len(v) for v in c[2])
                for c in cols) + len(cols)
    sheet = np.zeros((m, width), np.uint8)
    at = 0
    for kind, values, how in cols:
        at = (_number if kind == "number" else _names)(sheet, at, values, how)
        sheet[:, at] = ord(",")
        at += 1
    sheet[:, at - 1] = ord("\n")
    flat = sheet.ravel()
    return flat[flat != 0].tobytes()


class Inputs:
    out_suffix = ""                 # the output is a directory of trees
    n_files = 1

    def __init__(self, cell, seed, work):
        cfg = cell.config
        gen, schema = cfg["generator"], cfg["schema"]
        self.fields = feature_fields(schema)
        self.classes = list(gen["classes"])
        n = int(cfg["train_rows"])
        module = generate.load_module(cell.bench_dir, "generators", gen["kind"])
        declared = {f["ordinal"] for f in schema["fields"]}
        class_at = max(declared)
        assert {int(o) for o in gen["unread"]}.isdisjoint(declared)
        # codes [n, d]: a categorical field's index into its cardinality,
        # an int field's number; y [n]: index into classes
        self.codes = np.empty((n, len(self.fields)), np.int16)
        self.y = np.empty(n, np.int8)

        def one(span):
            c, lo, hi = span
            codes, y = module.draw(generate.seed_for(seed, 0, c), hi - lo,
                                   gen, self.fields)
            ids, unread = module.draw_unread(
                generate.seed_for(seed, UNREAD_STREAM, c), hi - lo, gen)
            self.codes[lo:hi], self.y[lo:hi] = codes, y
            return format_rows(ids, unread, codes, y, gen, self.fields,
                               class_at)

        self.train_path = os.path.join(work, "train.csv")
        with open(self.train_path + ".part", "wb") as fh, \
                ThreadPoolExecutor(generate.WRITER_THREADS) as pool:
            for blob in pool.map(one, chunks(n)):
                fh.write(blob)
        os.replace(self.train_path + ".part", self.train_path)
        self.schema_path, self.props_path = \
            jobfiles.write_schema_and_properties(cfg, work)
        self.job = cfg["job"]

    def argv(self, file_no, out):
        return [self.job, "--conf", self.props_path, self.train_path, out]

    def warmup_argv(self, out):
        return self.argv(0, out)
