"""The benchmark's one general data writer: labelled numeric rows, drawn
by the generator the configuration names and written as CSV in bulk.

What is drawn is a file of its own, `generators/<kind>.py`, found by
`generator.kind` in the configuration's file: its `draw(rng, n, gen,
fields)` returns whole numbers `q` [n, d] and class codes `y` [n]. What
is written follows the schema's feature fields: a field of `dataType`
`int` is written as the whole number itself, a `double` as thousandths
with three decimals (`%.3f`). A row is `<prefix>%08d,` + the d values +
`,<class name>`.

The values are turned into text by integer arithmetic over whole
columns, a million rows at a time, on a few threads. The number a parser
reads back is exactly `q / 10**decimals`, so the reference can work from
`q` without reading the file.
"""

from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHUNK_ROWS = 1 << 20
WRITER_THREADS = 4
_ID_DIGITS = 8
_DECIMALS = {"int": 0, "long": 0, "double": 3, "float": 3}


def feature_fields(schema: Dict) -> List[Dict]:
    """The schema's feature fields in the order of their ordinals: those
    flagged `feature`, or where none is, as the program reads a rich
    schema, every field that is neither the id nor categorical."""
    fields = schema["entity"]["fields"] if "entity" in schema \
        else schema["fields"]
    fields = sorted(fields, key=lambda f: f["ordinal"])
    flagged = [f for f in fields if f.get("feature")]
    return flagged or [f for f in fields if not f.get("id")
                       and f["dataType"] != "categorical"]


def decimals_of(fields: Sequence[Dict]) -> List[int]:
    return [_DECIMALS[f["dataType"]] for f in fields]


@dataclass
class Rows:
    """One generated file's content: values as whole numbers of
    10**-decimals, class codes and the number its first row's id carries."""

    q: np.ndarray                   # [n, d] int32
    y: np.ndarray                   # [n] int8, index into classes
    id_start: int
    decimals: Sequence[int]         # per column

    def __len__(self) -> int:
        return self.q.shape[0]

    def values(self, pick=None) -> np.ndarray:
        """[n, d] float32, what a parser of the file reads (of the rows
        `pick` alone, where given)."""
        q = self.q if pick is None else self.q[pick]
        scale = np.float32(10.0) ** np.asarray(self.decimals, np.float32)
        return q.astype(np.float32) / scale

    def ids(self, prefix: str = "S") -> List[str]:
        return [f"{prefix}{self.id_start + i:0{_ID_DIGITS}d}"
                for i in range(len(self))]


def seed_for(seed: int, stream: int, chunk: int = 0) -> np.random.Generator:
    """The generator of one chunk of stream `stream` under `--seed`: any
    whole number of any size, streams and chunks independent of each
    other."""
    if seed < 0:
        raise ValueError(f"--seed must not be negative, got {seed}")
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), int(stream), int(chunk)]))


def load_module(bench_dir: str, folder: str, name: str):
    """The module `<bench_dir>/<folder>/<name>.py`: how the harness finds
    a generator, a loop, a check or a reader by the name a data file
    gives."""
    path = os.path.join(bench_dir, folder, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {folder}/{name}.py under {bench_dir}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def file_rows(mix: Dict) -> List[int]:
    """The rows of each of a mix's test files: `rows_per_file` is one
    number for all `files_per_seed` files, or a list with one for each."""
    n, rows = int(mix["files_per_seed"]), mix["rows_per_file"]
    if isinstance(rows, list):
        if len(rows) != n:
            raise ValueError("rows_per_file lists one number for each of "
                             f"the {n} files, got {len(rows)}")
        return [int(r) for r in rows]
    return [int(rows)] * n


def draw(rng: np.random.Generator, n: int, gen: Dict, fields: Sequence[Dict],
         id_start: int, bench_dir: str = HERE) -> Rows:
    """`n` rows of the distribution `gen` describes over `fields`, by the
    `draw(rng, n, gen, fields)` of `generators/<gen.kind>.py`."""
    if id_start + n > 10 ** _ID_DIGITS:
        raise ValueError("ids would not fit eight digits")
    q, y = load_module(bench_dir, "generators", gen["kind"]).draw(
        rng, n, gen, fields)
    return Rows(np.asarray(q, np.int32), np.asarray(y, np.int8), id_start,
                decimals_of(fields))


def _digits(out: np.ndarray, col: int, v: np.ndarray, width: int) -> None:
    """`v` as `width` decimal digits, most significant at `col`."""
    v = v.copy()
    for j in range(width - 1, -1, -1):
        v, r = np.divmod(v, 10)
        out[:, col + j] = r + 48


def format_rows(rows: Rows, lo: int, hi: int, classes: Sequence[str],
                id_prefix: str = "S") -> bytes:
    """Rows lo..hi as CSV bytes. A fixed-width sheet is filled column by
    column; the places a shorter number leaves empty hold 0 and are
    dropped in one pass at the end."""
    q, y = rows.q[lo:hi], rows.y[lo:hi]
    m, d = q.shape
    if q.size and q.min() < 0:
        raise ValueError("the writer wants values that are not negative")
    pre = id_prefix.encode()
    scales = [10 ** k for k in rows.decimals]
    wholes = [max(1, len(str(int(q[:, f].max() // scales[f]) if m else 0)))
              for f in range(d)]
    widths = [w + (1 + k if k else 0) for w, k in zip(wholes, rows.decimals)]
    longest = max(len(c) for c in classes)
    width = len(pre) + _ID_DIGITS + 1 + sum(widths) + d + longest + 1
    sheet = np.zeros((m, width), np.uint8)
    col = 0
    for b in pre:
        sheet[:, col] = b
        col += 1
    _digits(sheet, col, np.arange(rows.id_start + lo, rows.id_start + hi,
                                  dtype=np.int64), _ID_DIGITS)
    col += _ID_DIGITS
    sheet[:, col] = ord(",")
    col += 1
    for f in range(d):
        whole, frac = np.divmod(q[:, f], scales[f])
        _digits(sheet, col, whole, wholes[f])
        # leading zeros of the whole part are not written ("5.250", "7")
        for j in range(wholes[f] - 1):
            sheet[whole < 10 ** (wholes[f] - 1 - j), col + j] = 0
        col += wholes[f]
        if rows.decimals[f]:
            sheet[:, col] = ord(".")
            _digits(sheet, col + 1, frac, rows.decimals[f])
            col += 1 + rows.decimals[f]
        sheet[:, col] = ord(",")
        col += 1
    names = np.zeros((len(classes), longest), np.uint8)
    for i, c in enumerate(classes):
        names[i, :len(c)] = np.frombuffer(c.encode(), np.uint8)
    sheet[:, col:col + longest] = names[y]
    sheet[:, -1] = ord("\n")
    flat = sheet.ravel()
    return flat[flat != 0].tobytes()


def make_csv(path: Optional[str], seed: int, stream: int, n: int, gen: Dict,
             fields: Sequence[Dict], id_start: int = 0,
             bench_dir: str = HERE) -> Rows:
    """Draw `n` rows from (`seed`, `stream`) and write them to `path`, a
    chunk at a time: each chunk has a generator of its own, so a few
    threads draw and format (numpy releases the lock) while this one
    writes in order. Returns all the rows; with no `path` it only draws."""
    d = len(fields)
    classes, prefix = list(gen["classes"]), gen["id_prefix"]
    q = np.empty((n, d), np.int32)
    y = np.empty((n,), np.int8)
    spans = [(c, lo, min(lo + CHUNK_ROWS, n))
             for c, lo in enumerate(range(0, n, CHUNK_ROWS))]

    def one(span):
        c, lo, hi = span
        rows = draw(seed_for(seed, stream, c), hi - lo, gen, fields,
                    id_start + lo, bench_dir)
        blob = (format_rows(rows, 0, hi - lo, classes, prefix)
                if path else b"")
        return lo, hi, rows, blob

    tmp = (path + ".part") if path else os.devnull
    with open(tmp, "wb") as fh, ThreadPoolExecutor(WRITER_THREADS) as pool:
        for lo, hi, rows, blob in pool.map(one, spans):
            q[lo:hi], y[lo:hi] = rows.q, rows.y
            fh.write(blob)
    if path:
        os.replace(tmp, path)
    return Rows(q, y, id_start, decimals_of(fields))
