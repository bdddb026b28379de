"""ctypes binding for the native CSV ingest (csv_ingest.cpp).

The shared library builds lazily with g++ on first use (no pybind11 in the
image; plain `extern "C"` + ctypes per the environment constraints) and is
cached next to the source under a name keyed on what it was built from:
the source's content, the compiler flags and this host's CPU (the flags
include ``-march=native``). A library built from other source, or on
another machine, has another name and is never loaded. On the CPU the
package degrades to the Python parser when the build fails —
`native_available()` gates the fast path; a process on an accelerator
calls `require_native()` and stops instead.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from avenir_tpu import obs as _obs

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "csv_ingest.cpp")
_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-pthread")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
#: why the library is unavailable (compiler output), once a build failed
_build_error: Optional[str] = None


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: this CPU's feature list."""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    return line.strip()
    except OSError:
        pass
    return platform.machine()


def _lib_path() -> str:
    key = hashlib.sha256()
    with open(_SRC, "rb") as fh:
        key.update(fh.read())
    key.update(" ".join(_FLAGS).encode())
    key.update(_host_cpu().encode())
    return os.path.join(_DIR, f"libcsv_ingest.{key.hexdigest()[:16]}.so")


def _compile(lib_path: str) -> None:
    """Build to a private name and rename into place, so a concurrent
    process never loads a half-written library."""
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(["g++", *_FLAGS, "-o", tmp, _SRC],
                              capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as exc:
        raise RuntimeError(f"g++ did not run: {exc!r}") from exc
    if proc.returncode != 0:
        raise RuntimeError(
            f"g++ exited {proc.returncode}: {proc.stderr.strip()[-800:]}")
    os.replace(tmp, lib_path)


def _build() -> Optional[ctypes.CDLL]:
    global _build_error
    lib_path = _lib_path()
    try:
        if not os.path.exists(lib_path):
            _compile(lib_path)
        lib = ctypes.CDLL(lib_path)
    except (OSError, RuntimeError) as exc:
        _build_error = str(exc)
        return None
    c_char_p = ctypes.c_char_p
    i64, i32 = ctypes.c_int64, ctypes.c_int32
    p_i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    p_f32 = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    p_i64 = ctypes.POINTER(i64)

    lib.csv_count_rows.restype = i64
    lib.csv_count_rows.argtypes = [c_char_p, i64]
    lib.csv_count_rows_mt.restype = i64
    lib.csv_count_rows_mt.argtypes = [c_char_p, i64, i32]
    lib.csv_parse.restype = i64
    lib.csv_parse.argtypes = [
        c_char_p, i64, ctypes.c_char, i32,
        p_i32, i32, p_f32,
        p_i32, i32, c_char_p, p_i32, p_i32, i64,
        p_i64, ctypes.POINTER(i32),
    ]
    lib.csv_parse_mt.restype = i64
    lib.csv_parse_mt.argtypes = lib.csv_parse.argtypes + [i32]
    lib.csv_column_bytes.restype = i64
    lib.csv_column_bytes.argtypes = [c_char_p, i64, ctypes.c_char, i32]
    lib.csv_extract_column.restype = i64
    lib.csv_extract_column.argtypes = [c_char_p, i64, ctypes.c_char, i32,
                                       ctypes.c_char_p, i64]
    lib.csv_distinct_column.restype = i64
    lib.csv_distinct_column.argtypes = [c_char_p, i64, ctypes.c_char, i32, i32,
                                        ctypes.POINTER(ctypes.c_void_p), p_i64]
    lib.csv_free.restype = None
    lib.csv_free.argtypes = [ctypes.c_void_p]
    p_i64_arr = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.seq_token_count.restype = i64
    lib.seq_token_count.argtypes = [c_char_p, i64, ctypes.c_char, p_i64]
    lib.seq_encode.restype = i64
    lib.seq_encode.argtypes = [c_char_p, i64, ctypes.c_char,
                               c_char_p, i32, p_i32, i64, p_i64_arr, i64]
    lib.file_read_mt.restype = i64
    lib.file_read_mt.argtypes = [c_char_p, np.ctypeslib.ndpointer(
        np.uint8, flags="C_CONTIGUOUS"), i64, i32]
    lib.fia_scan.restype = i64
    lib.fia_scan.argtypes = [c_char_p, i64, ctypes.c_char, i32, c_char_p, i64,
                             i32, ctypes.POINTER(ctypes.c_void_p), p_i64,
                             p_i64, p_i64, ctypes.POINTER(i32)]
    lib.fia_pack.restype = i64
    lib.fia_pack.argtypes = [
        c_char_p, i64, ctypes.c_char, i32, c_char_p, i64, c_char_p, i64, i32,
        p_i32, np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
        i64, i64, i64, i64, i32]
    u64 = ctypes.c_uint64
    lib.pcg64_bootstrap_counts.restype = i64
    lib.pcg64_bootstrap_counts.argtypes = [
        u64, u64, u64, u64, i64, i32, i64, p_i32, i32,
        ctypes.POINTER(i32), ctypes.POINTER(i32)]
    p_ptr = np.ctypeslib.ndpointer(np.uintp, flags="C_CONTIGUOUS")
    lib.knn_index_matrix.restype = i64
    lib.knn_index_matrix.argtypes = [
        p_ptr, p_f32, i32, p_ptr, p_i32, i32, ctypes.c_float, i64, i64,
        np.ctypeslib.ndpointer(np.float32, ndim=2, flags="C_CONTIGUOUS"),
        i32, ctypes.POINTER(i32), p_i64]
    return lib


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is None and _build_error is None:
        with _lock:
            if _lib is None and _build_error is None:
                _lib = _build()
    return _lib


def native_available() -> bool:
    return _get_lib() is not None


def require_native() -> None:
    """The accelerator path's rule (utils.devices.require_backend): the
    Python parser is several times slower, so a chip fed by it is a
    degrade nobody asked for — stop with the compiler's message."""
    if _get_lib() is None:
        raise RuntimeError(
            "native CSV parser unavailable on an accelerator host "
            f"({_build_error}); the Python parser is not a fallback here")


def parse_csv_native(
    data: bytes,
    delim: str,
    numeric_ordinals: List[int],
    categorical: List[Tuple[int, List[str]]],   # (ordinal, cardinality)
    string_ordinals: List[int],
    lazy_strings: bool = False,
    threads: int = 0,
    span=_obs.no_span,
) -> Tuple[int, Dict[int, np.ndarray], Dict[int, object]]:
    """One native pass: (n_rows, {ordinal: column array}, {ordinal: thunk}).

    Numeric columns come back float32 (missing -> NaN), categorical int32
    codes against the given cardinalities (unknown value raises ValueError,
    matching the Python parser's contract; a short row is the empty token,
    so it raises too unless the cardinality holds ``""``). A categorical
    whose vocabulary is discovered from the data comes through here like a
    declared one, once `distinct_column_native` has found its values.
    String/id columns come back as numpy object arrays — or, with
    lazy_strings=True, as zero-arg thunks in the third return value
    (materializing millions of python strings costs more than the whole
    numeric/categorical parse; algorithms that never read ids skip it
    entirely).

    `span` is the caller's span factory (`obs.span`, or `obs.no_span` on
    a route that names no phases); each step runs under a leaf of it, one
    after the other: `dataset.parse.count`, `.prefill` (the outputs'
    sentinels: their pages' first touch), `.fields` (the threaded pass),
    `.check` (short rows of the categoricals), `.ids` (the string
    columns' extraction)."""
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native CSV ingest unavailable (no g++?)")
    d = delim.encode()[0:1]
    with span("dataset.parse.count") as note:
        n = int(lib.csv_count_rows_mt(data, len(data), np.int32(threads)))
        note["rows"] = n
    columns: Dict[int, np.ndarray] = {}

    num_ords = np.asarray(numeric_ordinals, np.int32)
    cat_ords = np.asarray([o for o, _ in categorical], np.int32)
    vocab_blob = b"".join(
        v.encode() + b"\0" for _, card in categorical for v in card
    )
    vocab_counts = np.asarray([len(card) for _, card in categorical], np.int32)
    if vocab_blob.count(b"\0") != int(vocab_counts.sum()):
        raise ValueError("a categorical value holds a NUL byte, which the "
                         "native parser's vocabulary cannot carry")
    all_ords = list(numeric_ordinals) + [o for o, _ in categorical] + list(
        string_ordinals)
    max_ord = max(all_ords) if all_ords else 0

    # prefill sentinels: rows shorter than the schema leave numeric NaN
    # and categorical the empty token's code (both matching the Python
    # parser), or -1 where the vocabulary has no such value (checked below)
    with span("dataset.parse.prefill") as note:
        num_out = np.full((len(num_ords), n), np.nan, np.float32)
        cat_out = np.full((len(cat_ords), n), -1, np.int32)
        for i, (_, card) in enumerate(categorical):
            if "" in card:
                cat_out[i] = card.index("")
        note["nbytes"] = num_out.nbytes + cat_out.nbytes
    err_row = ctypes.c_int64(-1)
    err_ord = ctypes.c_int32(-1)
    # threads=0 lets the library pick hardware_concurrency; stripes are
    # capped so small buffers stay on the sequential path (identical
    # semantics either way — the MT entry splits at newline boundaries
    # into disjoint global row ranges)
    with span("dataset.parse.fields", threads=threads):
        got = int(lib.csv_parse_mt(
            data, len(data), d, np.int32(max_ord),
            num_ords, len(num_ords), num_out,
            cat_ords, len(cat_ords), vocab_blob, vocab_counts, cat_out,
            np.int64(n), ctypes.byref(err_row), ctypes.byref(err_ord),
            np.int32(threads),
        ))
    if got < 0:
        # recover the offending token for the standard error message
        bad = _extract_column(lib, data, d, int(err_ord.value))
        tok = bad[err_row.value] if err_row.value < len(bad) else "?"
        if got == -2:
            raise ValueError(
                f"could not convert string to float: {tok!r} at ordinal "
                f"{err_ord.value}")
        raise ValueError(
            f"value {tok!r} not in declared cardinality of ordinal "
            f"{err_ord.value}")
    for i, o in enumerate(numeric_ordinals):
        columns[o] = num_out[i]
    with span("dataset.parse.check"):
        for i, (o, _) in enumerate(categorical):
            if (cat_out[i] < 0).any():
                row = int(np.argmax(cat_out[i] < 0))
                raise ValueError(
                    f"value '' not in declared cardinality of ordinal {o} "
                    f"(row {row} is short)")
            columns[o] = cat_out[i]
    lazy: Dict[int, object] = {}
    with span("dataset.parse.ids", columns=len(string_ordinals),
              nbytes=0) as note:
        for o in string_ordinals:
            raw = _extract_column_bytes(lib, data, d, o)
            note["nbytes"] += len(raw)
            if lazy_strings:
                # the native extraction runs now into a COMPACT per-column
                # buffer (so the thunk does not pin the whole CSV block);
                # only the python-string materialization — the expensive
                # part — is deferred
                lazy[o] = (lambda r=raw: np.array(_lines(r), dtype=object))
            else:
                columns[o] = np.array(_lines(raw), dtype=object)
    return got, columns, lazy


def _extract_column_bytes(lib, data: bytes, d: bytes, ordinal: int) -> bytes:
    cap = int(lib.csv_column_bytes(data, len(data), d, np.int32(ordinal)))
    buf = ctypes.create_string_buffer(max(cap, 1))
    w = int(lib.csv_extract_column(data, len(data), d, np.int32(ordinal),
                                   buf, np.int64(cap)))
    return buf.raw[:w] if w > 0 else b""


def _lines(raw: bytes) -> List[str]:
    """The tokens of a newline-joined column buffer (trailing newline
    included), as `_extract_column_bytes` gives it."""
    return raw.decode().split("\n")[:-1]


def _extract_column(lib, data: bytes, d: bytes, ordinal: int) -> List[str]:
    return _lines(_extract_column_bytes(lib, data, d, ordinal))


def distinct_column_native(data: bytes, delim: str, ordinal: int,
                           threads: int = 0) -> Tuple[List[str], int]:
    """(the distinct trimmed tokens of one column in no order, the rows
    scanned): what vocabulary discovery needs of a column, found by a
    striped native scan that makes no Python string per row. A short row
    counts as the empty token, as in `extract_column_raw`."""
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native CSV ingest unavailable (no g++?)")
    out = ctypes.c_void_p()
    n_rows = ctypes.c_int64(0)
    size = int(lib.csv_distinct_column(
        data, len(data), delim.encode()[0:1], np.int32(ordinal),
        np.int32(threads), ctypes.byref(out), ctypes.byref(n_rows)))
    if size < 0:
        raise MemoryError("csv_distinct_column could not allocate its result")
    try:
        blob = ctypes.string_at(out, size)
    finally:
        lib.csv_free(out)
    return blob.decode().split("\n")[:-1], n_rows.value


def extract_column_raw(data: bytes, delim: str, ordinal: int
                       ) -> Optional[bytes]:
    """One column's trimmed tokens as the native parser's compact
    newline-joined buffer (trailing newline included) — the exact bytes
    the lazy-string thunks of parse_csv_native defer over, which is
    also what the columnar sidecar stores for open-vocabulary columns.
    None when the native library or a single-byte delimiter is not
    available."""
    lib = _get_lib()
    if lib is None:
        return None
    d = delim.encode()
    if len(d) != 1:
        return None
    return _extract_column_bytes(lib, data, d, ordinal)


def seq_encode_native(data: bytes, delim: str, vocab: List[str]
                      ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Ragged tokenize + dictionary-encode a text block against one
    vocabulary (the sequence-job ingest). Returns (codes int32
    [total_tokens], offsets int64 [rows+1]) in CSR form — token t of row
    r is codes[offsets[r] + t]; unknown tokens are -1. None when the
    native library is unavailable (callers fall back to Python split)."""
    lib = _get_lib()
    if lib is None:
        return None
    d = delim.encode()
    if len(d) != 1:
        return None
    n_tokens = ctypes.c_int64(0)
    n_rows = int(lib.seq_token_count(data, len(data), d,
                                     ctypes.byref(n_tokens)))
    codes = np.empty(max(n_tokens.value, 1), np.int32)
    offsets = np.empty(n_rows + 1, np.int64)
    blob = b"".join(v.encode() + b"\0" for v in vocab)
    got = int(lib.seq_encode(data, len(data), d, blob, len(vocab),
                             codes, codes.shape[0], offsets, n_rows + 1))
    if got != n_rows:
        raise RuntimeError(f"seq_encode row mismatch: {got} != {n_rows}")
    return codes[: int(offsets[n_rows])], offsets


class BasketScan(NamedTuple):
    """What `basket_scan_native` found in one file of baskets."""
    vocab: List[str]        # item tokens, in order of first appearance
    blob: bytes             # the same tokens as read, each followed by \n
    counts: np.ndarray      # int64 [V]: the baskets that hold each item
    rows: int
    tokens: int             # item tokens read (repeats included)
    threads: int            # stripes the library cut


def read_files_native(paths: Sequence[str], threads: int = 0) -> np.ndarray:
    """The files read whole into one uint8 buffer, one after the other, a
    file that ends inside a line ended with a newline: each by one native
    call whose stripes pread their own byte ranges (`file_read_mt`), so
    the copy and the first touch of the buffer's pages are spread over
    the library's threads."""
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native CSV ingest unavailable (no g++?)")
    sizes = [os.path.getsize(p) for p in paths]
    data = np.empty(sum(sizes) + len(sizes), np.uint8)
    at = 0
    for path, size in zip(paths, sizes):
        if size and int(lib.file_read_mt(os.fsencode(path), data[at:at + size],
                                         size, np.int32(threads))) != size:
            raise OSError(f"could not read {size} bytes of {path!r}")
        at += size
        if size and data[at - 1] != 10:
            data[at] = 10
            at += 1
    return data[:at]


def _buffer_args(data) -> Tuple[object, int]:
    """(pointer, length) of a text buffer held as bytes or as a uint8
    array (what `read_files_native` returns)."""
    if isinstance(data, np.ndarray):
        return data.ctypes.data_as(ctypes.c_char_p), int(data.shape[0])
    return data, len(data)


def _marker_args(marker: Optional[str]) -> Tuple[Optional[bytes], int]:
    if marker is None:
        return None, -1
    m = marker.encode()
    return m, len(m)


def basket_scan_native(data, delim: str, skip: int,
                       marker: Optional[str] = None,
                       threads: int = 0) -> BasketScan:
    """Pass 1 of the itemset miner's resident route over a whole file in
    memory (bytes, or the uint8 buffer of `read_files_native`): one native
    call, striped over lines inside (`fia_scan`). The
    fields from `skip` on are items; token identity is `seq_encode`'s
    (space, tab and CR trimmed, the empty token and `marker` dropped). An
    item's code is its rank by first appearance, whatever `threads` is."""
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native CSV ingest unavailable (no g++?)")
    out = ctypes.c_void_p()
    tok_bytes, rows, tokens = (ctypes.c_int64(0) for _ in range(3))
    used = ctypes.c_int32(0)
    v = int(lib.fia_scan(
        *_buffer_args(data), delim.encode()[0:1], np.int32(skip),
        *_marker_args(marker), np.int32(threads), ctypes.byref(out),
        ctypes.byref(tok_bytes), ctypes.byref(rows), ctypes.byref(tokens),
        ctypes.byref(used)))
    if v < 0:
        raise MemoryError("fia_scan could not allocate its result")
    try:
        raw = ctypes.string_at(out, 8 * v + tok_bytes.value)
    finally:
        lib.csv_free(out)
    blob = raw[8 * v:]
    return BasketScan(blob.decode("utf-8", "replace").split("\n")[:-1], blob,
                      np.frombuffer(raw, np.int64, v).copy(), rows.value,
                      tokens.value, used.value)


def basket_pack_native(data, delim: str, skip: int,
                       marker: Optional[str], scan: BasketScan,
                       item_row: np.ndarray, v_rows: int, slab_words: int,
                       threads: int = 0) -> np.ndarray:
    """Pass 2 of the resident route: the file's baskets as packed bit
    columns, uint32 [slabs, v_rows, slab_words], made by one native call
    (`fia_pack`) into one array. Basket t is bit t % 32 of word
    (t // 32) % slab_words of row `item_row[code]` of slab
    t // 32 // slab_words; an item whose `item_row` is -1 is left out and
    the baskets past the file's last are all zero. The slabs are
    contiguous, so each goes to the device with no copy."""
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native CSV ingest unavailable (no g++?)")
    n_slabs = max(-(-scan.rows // (slab_words * 32)), 1)
    cols = np.zeros((n_slabs, v_rows, slab_words), np.uint32)
    got = int(lib.fia_pack(
        *_buffer_args(data), delim.encode()[0:1], np.int32(skip),
        *_marker_args(marker), scan.blob, len(scan.blob), len(scan.vocab),
        np.ascontiguousarray(item_row, np.int32), cols, v_rows, slab_words,
        n_slabs, scan.rows, np.int32(threads)))
    if got != scan.rows:
        raise RuntimeError(f"fia_pack row mismatch: {got} != {scan.rows}")
    return cols


class BootstrapCounts(NamedTuple):
    """What `bootstrap_counts_native` says of the walk it made."""
    rejected: int           # 32-bit values thrown away before the last draw
    max_weight: int         # the largest count written
    threads: int            # stripes the library cut


#: `Generator.integers` takes 32-bit values while its largest draw is
#: under this; from here on it takes 64-bit ones, another rule
UINT32_DRAWS = (1 << 32) - 1


def bootstrap_counts_native(rng: np.random.Generator, n: int,
                            ws: np.ndarray, threads: int = 0
                            ) -> Optional[BootstrapCounts]:
    """`ws[t, :n] += np.bincount(rng.integers(0, n, n), minlength=n)` for
    every row t of the zeroed int32 `ws`, in row order, as one native call
    that walks the generator's stream by position on every core
    (`pcg64_bootstrap_counts`). None, and nothing written, where that walk
    is not numpy's: a generator that is not PCG64 or holds a buffered
    32-bit value, or an `n` whose draws numpy does not take from 32-bit
    values. `rng` itself is not advanced."""
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native CSV ingest unavailable (no g++?)")
    state = rng.bit_generator.state
    if (state["bit_generator"] != "PCG64" or state["has_uint32"]
            or not 0 <= n - 1 < UINT32_DRAWS):
        return None
    if (ws.dtype != np.int32 or ws.ndim != 2 or ws.shape[1] < n
            or not ws.flags.c_contiguous):
        raise ValueError("ws wants a C-contiguous int32 [trees, >= n] array")
    mask = (1 << 64) - 1
    lcg = state["state"]
    most, used = ctypes.c_int32(0), ctypes.c_int32(0)
    rejected = int(lib.pcg64_bootstrap_counts(
        lcg["state"] >> 64, lcg["state"] & mask, lcg["inc"] >> 64,
        lcg["inc"] & mask, n, ws.shape[0], ws.shape[1], ws,
        np.int32(threads), ctypes.byref(most), ctypes.byref(used)))
    return BootstrapCounts(rejected, most.value, used.value)


def knn_index_matrix_native(num: Sequence[np.ndarray], ranges: np.ndarray,
                            cats: Sequence[np.ndarray], bins: Sequence[int],
                            scale: np.float32, out: np.ndarray,
                            threads: int = 0,
                            cat_names: Sequence[str] = ()) -> int:
    """Write the kNN kernels' input matrix into `out`, float32
    [n_padded, len(num) + sum(bins)], in one pass striped over the rows
    (`knn_index_matrix`): each numeric column divided by its range
    floored at 1e-9, each categorical one-hot at `scale`, the rows past
    the columns' length zero. `num` are float32 and `cats` int32 columns
    of one length; `threads=0` lets the library choose. Returns the
    stripes cut. A code outside [0, bins) raises ValueError, naming the
    column by `cat_names` where given."""
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native CSV ingest unavailable (no g++?)")
    num = [np.ascontiguousarray(c) for c in num]
    cats = [np.ascontiguousarray(c) for c in cats]
    n = len(num[0]) if num else len(cats[0]) if cats else 0
    width = len(num) + int(sum(bins))
    ranges = np.ascontiguousarray(ranges)
    if (any(c.dtype != np.float32 or c.shape != (n,) for c in num)
            or any(c.dtype != np.int32 or c.shape != (n,) for c in cats)
            or ranges.dtype != np.float32 or ranges.shape != (len(num),)
            or len(bins) != len(cats)):
        raise ValueError("knn_index_matrix wants float32 numeric and int32 "
                         "code columns of one length, a float32 range each")
    if (out.dtype != np.float32 or out.ndim != 2 or out.shape[0] < n
            or out.shape[1] != width or not out.flags.c_contiguous):
        raise ValueError(f"out wants a C-contiguous float32 [>= {n}, "
                         f"{width}] array")
    num_at = np.asarray([c.ctypes.data for c in num], np.uintp)
    cat_at = np.asarray([c.ctypes.data for c in cats], np.uintp)
    err_field, err_row = ctypes.c_int32(-1), ctypes.c_int64(-1)
    stripes = int(lib.knn_index_matrix(
        num_at, ranges, len(num), cat_at, np.asarray(bins, np.int32),
        len(cats), scale, n, out.shape[0], out, np.int32(threads),
        ctypes.byref(err_field), ctypes.byref(err_row)))
    if stripes < 0:
        f, row = err_field.value, err_row.value
        name = cat_names[f] if f < len(cat_names) else f"#{f}"
        raise ValueError(
            f"categorical field {name!r} holds code {int(cats[f][row])} at "
            f"row {row}, outside its {bins[f]} values")
    return stripes


def native_seq_ready(delim: str) -> bool:
    """True when the native sequence encoder handles this delimiter
    (single byte) and the library is built — the gate every CSR
    consumer checks before taking the byte-block path."""
    return len(delim.encode()) == 1 and native_available()


def csr_rows(offsets: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(row_of [total_tokens], starts [n_rows]) for a CSR offsets array —
    the shared row-decode of every seq_encode consumer (markov fit_csr,
    HMM add_csr, apriori counting chunks). row_of is int32: a block
    never holds 2^31 rows (blocks are tens of MB), and the token-
    proportional arrays dominate a streaming pass's transient RSS, so
    halving them matters at scale."""
    return (np.repeat(np.arange(offsets.shape[0] - 1, dtype=np.int32),
                      np.diff(offsets)),
            offsets[:-1])


def csr_region_mask(offsets: np.ndarray, skip: int, n_tokens: int
                    ) -> np.ndarray:
    """bool [n_tokens]: True where a token sits at within-row position
    >= skip (the item/sequence region past the meta fields). Built by
    unmarking the first `skip` positions of each row — O(rows * skip)
    small arrays instead of the arange(n_tokens) + starts[row_of]
    int64 temporaries the naive position compare materializes (those
    were the largest transients of the miners' streaming passes)."""
    region = np.ones(n_tokens, bool)
    starts, ends = offsets[:-1], offsets[1:]
    for j in range(skip):
        pos = starts + j
        region[pos[pos < ends]] = False
    return region


class BlockScanEncoder:
    """Per-block body of the vocabulary-DISCOVERING native scan — the
    shared pass-1 engine of the streaming miners (association
    scan_items, sequence scan), factored so an external SharedScan can
    drive it one byte block at a time (core.stream.SharedScan fans one
    disk read out to N sinks; this is the miner-side sink body).

    Each block encodes against the CURRENT vocab plus two drop
    sentinels (the infrequent-item marker and the empty token, which
    would otherwise read as unknown and force the slow path on every
    block of a trailing-delimiter CSV). A block with genuinely unknown
    tokens takes one Python pass to extend `vocab`/`index` in place,
    then re-encodes — but only if that pass actually added something;
    steady-state blocks of a vocabulary-stable stream never touch
    per-row Python. `region` is True exactly at item positions holding
    a REAL vocab code (sentinels, ids and short rows excluded), so
    callers can fold counts straight off (codes[region], row_of[region]).
    Vocab codes are append-only, so codes encoded against an EARLIER
    vocab prefix stay valid against the final vocabulary — the property
    the encoded-block spill cache (EncodedBlockCache) is built on."""

    def __init__(self, delim: str, skip: int, vocab: List[str],
                 index: Dict[str, int], marker: Optional[str] = None):
        self.delim = delim
        self.skip = skip
        self.vocab = vocab
        self.index = index
        self.marker = marker
        self._sentinels = ([marker] if marker is not None else []) + [""]

    def encode(self, data: bytes):
        """(codes, offsets, region, n_rows) for one raw byte block, or
        None for a block with no rows."""
        codes, offsets = seq_encode_native(data, self.delim,
                                           self.vocab + self._sentinels)
        n = offsets.shape[0] - 1
        if n <= 0:
            return None
        region = csr_region_mask(offsets, self.skip, codes.shape[0])
        if (codes[region] < 0).any():
            added = False
            for ln in data.decode("utf-8", "replace").split("\n"):
                if not ln.strip():
                    continue
                for tok in [t.strip(" \t\r")
                            for t in ln.split(self.delim)][self.skip:]:
                    if tok and tok != self.marker and tok not in self.index:
                        self.index[tok] = len(self.vocab)
                        self.vocab.append(tok)
                        added = True
            if added:
                codes, offsets = seq_encode_native(
                    data, self.delim, self.vocab + self._sentinels)
        v = len(self.vocab)
        np.logical_and(region, codes >= 0, out=region)
        np.logical_and(region, codes < v, out=region)     # sentinels drop
        return codes, offsets, region, n


def scan_encode_blocks(paths, delim: str, skip: int, vocab: List[str],
                       index: Dict[str, int], block_bytes: int,
                       marker: Optional[str] = None):
    """Vocabulary-DISCOVERING native scan: yield (codes, offsets, region,
    n_rows) per byte block (see BlockScanEncoder for the per-block
    contract; this generator owns the prefetched disk read)."""
    from avenir_tpu.core.stream import iter_byte_blocks, prefetched

    enc = BlockScanEncoder(delim, skip, vocab, index, marker)
    for path in paths:
        for data in prefetched(iter_byte_blocks(path, block_bytes),
                               depth=1):
            out = enc.encode(data)
            if out is not None:
                yield out


# --------------------------------------------------------------------------
# Encoded-block spill cache
# --------------------------------------------------------------------------
_ENC_MAGIC = b"AVNRENC1"
_ENC_DTYPES = {0: np.uint8, 1: np.uint16, 2: np.uint32}

#: the cache's default on-disk byte budget — generous (the 100M-row
#: anchors spill ~1.5GB of CSV into ~600MB of codes), but FINITE: an
#: unbudgeted spill is exactly the `mem-cache-spill-unbudgeted` hazard
#: graftlint --mem flags, and the resident job server needs every spill
#: evictable
DEFAULT_CACHE_BUDGET_BYTES = 1 << 30


def _enc_dtype_code(max_value: int) -> int:
    if max_value < (1 << 8):
        return 0
    if max_value < (1 << 16):
        return 1
    return 2


class EncodedBlockCache:
    """Compact on-disk spill cache of region-compacted encoded blocks.

    The multi-pass miners (Apriori / GSP) re-scan their CSV once per
    itemset length k; after PR 1 the scan cost — disk read + native
    tokenize/encode — dominates each pass, not the device fold. The
    discovery scan (pass 1) already produces every later pass's inputs:
    the region-masked vocab codes of each block, in row order. This
    cache spills exactly that, per block:

        header  <q n_rows> <q n_tokens> <B counts_dtype> <B codes_dtype>
        counts  n_rows  elements — region token count per row
        codes   n_tokens elements — vocab codes of region tokens, row-major

    with the narrowest dtype that fits (1-byte codes for vocabularies
    under 256 items), so the cache is a fraction of the raw CSV bytes —
    replay passes read it instead of re-parsing CSV, and the raw-block /
    full-codes transients of the scan never materialize again (this is
    also what buys back Apriori's thin RSS headroom at 100M rows).

    Byte budget: the spill is bounded by `byte_budget` (default
    :data:`DEFAULT_CACHE_BUDGET_BYTES`; a config surface sits at the
    jobs' ``stream.encoded.cache.budget.mb`` key). Blocks land in one
    SEGMENT per source (``set_source``; writers that cannot attribute
    blocks — the shared-scan external feed — use one combined segment).
    Exceeding the budget evicts whole least-recently-replayed source
    segments atomically (never-replayed segments first, in write
    order), accumulating ``evicted_bytes``; consumers re-parse evicted
    sources and keep replaying the survivors (``source_valid(i)`` /
    ``blocks(i)``), so a tight budget degrades throughput, never
    correctness.

    Invalidation contract: validity is PER BLOCK, not per file. The
    own-read scan records a content fingerprint (offset + length +
    blake2b hash, ``note_block``) for every raw block it encodes; at
    replay time a source whose quick (path, size, mtime_ns) snapshot
    moved is re-proven by re-hashing the recorded ranges (memoized per
    file snapshot). An APPENDED source therefore stays replayable —
    its committed blocks still content-match the file's prefix
    (``source_delta`` hands consumers the byte offset where coverage
    ends, and only the tail re-parses) — while an in-place edit, or a
    writer that never saw raw blocks (the shared-scan external feed
    records no fingerprints), falls back to the whole-file snapshot
    gate and the full re-parse path. commit() still refuses a source
    that changed at all while the scan ran: a torn cache never commits.
    The cache directory is owned by this object (a tempdir unless
    `cache_dir` is given) and is removed on close()/GC; it is a
    within-job spill, not a cross-run artifact store."""

    #: segment key of the combined (source-unattributed) write stream
    _COMBINED = None

    #: sentinel: no segment can serve the requested source
    _NO_SEGMENT = object()

    def __init__(self, sources: Sequence[str],
                 cache_dir: Optional[str] = None,
                 byte_budget: Optional[int] = None):
        import tempfile

        self.sources = list(sources)
        self.byte_budget = (DEFAULT_CACHE_BUDGET_BYTES
                            if byte_budget is None else int(byte_budget))
        self._own_dir = cache_dir is None
        self._dir = cache_dir or tempfile.mkdtemp(prefix="avenir_encblk_")
        os.makedirs(self._dir, exist_ok=True)
        self._fh = None
        self._cur = self._COMBINED        # segment being written
        self._seg_order: list = []        # segment keys in write order
        self._seg_bytes: dict = {}        # segment key -> bytes written
        self._evicted: set = set()
        self._last_replay: dict = {}      # segment key -> replay clock
        self._replay_clock = 0
        self._fingerprint = None
        self._block_fps: dict = {}        # segment key -> [(off, len, hash)]
        self._delta_memo: dict = {}       # (src, size, mtime) -> end | None
        self._committed = False
        self.n_blocks = 0
        self.evicted_bytes = 0
        self.replays = 0          # completed replay passes

    def _seg_path(self, key) -> str:
        name = ("encoded_blocks.bin" if key is self._COMBINED
                else f"encoded_blocks_s{key}.bin")
        return os.path.join(self._dir, name)

    # ------------------------------------------------------------- write
    def _current_fingerprint(self):
        """Cheap stat identity of the source set — the begin/commit
        torn-write GATE only (a scan that mutated its own sources can
        never commit); REPLAY validity is the per-block content
        re-proof (``_content_coverage``), never this stat tuple.

        key-covered: all — replay identity is the content fingerprints.
        """
        from avenir_tpu.core.keys import key_site

        key_site("cache.fingerprint")
        out = []
        for p in self.sources:
            try:
                st = os.stat(p)
                out.append((p, st.st_size, st.st_mtime_ns))
            except OSError:
                out.append((p, -1, -1))
        return tuple(out)

    def begin(self) -> None:
        """Start (or restart) a write pass; any prior content is gone."""
        self.abort()
        for key in self._seg_order:
            try:
                os.remove(self._seg_path(key))
            except OSError:
                pass
        self._fingerprint = self._current_fingerprint()
        self._seg_order = []
        self._seg_bytes = {}
        self._evicted = set()
        self._last_replay = {}
        self._block_fps = {}
        self._delta_memo = {}
        self._cur = self._COMBINED
        self.n_blocks = 0
        self.evicted_bytes = 0

    def note_block(self, offset: int, data: bytes) -> None:
        """Record the CONTENT fingerprint (offset + length + hash) of one
        raw byte block of the currently-attributed source, whether or
        not the block spills any payload (blank blocks cover bytes but
        add no rows). Per-block fingerprints are what turn an appended
        source from a total invalidation into a delta: the committed
        blocks still content-match the file's prefix, so replay serves
        them and only the appended tail re-parses (source_delta).
        Writers that cannot see raw blocks — the shared-scan external
        feed — simply never call this and keep the whole-file gate."""
        from avenir_tpu.core.incremental import block_hash

        self.note_fingerprint(offset, len(data), block_hash(data))

    def note_fingerprint(self, offset: int, length: int,
                         hash_: str) -> None:
        """note_block for a writer that already holds the block's content
        hash (the sidecar-aware scan computes one fingerprint per block
        for its own manifest) — same contract, no second hash pass."""
        if self._fingerprint is None:
            raise RuntimeError("note_block() before begin()")
        if self._committed:
            raise RuntimeError("note_block() after commit()")
        self._block_fps.setdefault(self._cur, []).append(
            (int(offset), int(length), hash_))

    def set_source(self, index: int) -> None:
        """Attribute subsequent add_block() calls to source `index` —
        per-source segments are what make partial eviction (and partial
        replay) possible. Writers that cannot attribute blocks simply
        never call this and get one combined segment."""
        if self._cur == index:
            return
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        self._cur = index

    def _open_segment(self) -> None:
        path = self._seg_path(self._cur)
        if self._cur in self._seg_order and os.path.exists(path):
            # a writer returning to an earlier source (interleaved
            # set_source calls) must EXTEND its segment — "wb" here
            # would silently truncate committed blocks and replay a
            # partial segment as if it were whole
            self._fh = open(path, "ab")
            return
        self._fh = open(path, "wb")
        self._fh.write(_ENC_MAGIC)
        self._seg_bytes[self._cur] = len(_ENC_MAGIC)
        if self._cur not in self._seg_order:
            self._seg_order.append(self._cur)

    def _spilled_bytes(self) -> int:
        """Live spill size from the per-segment byte counters — O(live
        segments) arithmetic, no flush/stat per call (add_block calls
        this once per block)."""
        return sum(n for k, n in self._seg_bytes.items()
                   if k not in self._evicted)

    def _evict_segment(self, key) -> None:
        if key == self._cur and self._fh is not None:
            self._fh.close()
            self._fh = None
        try:
            os.remove(self._seg_path(key))
        except OSError:
            pass
        self.evicted_bytes += self._seg_bytes.get(key, 0)
        self._evicted.add(key)

    def evict_to(self, byte_budget: int) -> int:
        """Evict whole segments, least-recently-replayed first (never-
        replayed segments in write order before any replayed one), until
        the spill fits `byte_budget`. The currently-written segment goes
        last — but it too is evicted when it alone exceeds the budget
        (the cache then quietly disables itself for that source and the
        consumer re-parses). Returns the bytes evicted by this call."""
        before = self.evicted_bytes
        order = {k: i for i, k in enumerate(self._seg_order)}
        live = [k for k in self._seg_order if k not in self._evicted]
        live.sort(key=lambda k: (k == self._cur,
                                 self._last_replay.get(k, -1), order[k]))
        spilled = self._spilled_bytes()
        for key in live:
            if spilled <= byte_budget:
                break
            spilled -= self._seg_bytes.get(key, 0)
            self._evict_segment(key)
        return self.evicted_bytes - before

    def add_block(self, counts: np.ndarray, codes: np.ndarray) -> None:
        """Append one block: per-row region token counts + the region
        token codes (row-major). Narrowest-dtype encoding per block; a
        write that pushes the spill past the byte budget triggers
        whole-segment eviction. Blocks for an already-evicted segment
        are dropped (and counted) — the budget is a hard bound."""
        import struct

        if self._fingerprint is None:
            raise RuntimeError("add_block() before begin()")
        if self._committed:
            raise RuntimeError(
                "add_block() after commit(): a sealed cache never grows "
                "— call begin() to rewrite it")
        counts = np.ascontiguousarray(counts)
        codes = np.ascontiguousarray(codes)
        cd = _enc_dtype_code(int(counts.max(initial=0)))
        kd = _enc_dtype_code(int(codes.max(initial=0)))
        size = (18 + counts.shape[0] * _ENC_DTYPES[cd]().itemsize
                + codes.shape[0] * _ENC_DTYPES[kd]().itemsize)
        if self._cur in self._evicted:
            self.evicted_bytes += size
            return
        if self._fh is None:
            self._open_segment()
        self._fh.write(struct.pack("<qqBB", counts.shape[0],
                                   codes.shape[0], cd, kd))
        counts.astype(_ENC_DTYPES[cd]).tofile(self._fh)
        codes.astype(_ENC_DTYPES[kd]).tofile(self._fh)
        self.n_blocks += 1
        self._seg_bytes[self._cur] = self._seg_bytes.get(self._cur, 0) + size
        if self._spilled_bytes() > self.byte_budget:
            self.evict_to(self.byte_budget)

    def commit(self) -> bool:
        """Seal the write pass. Returns False (and stays invalid) when a
        source changed while the scan ran — a torn cache must never be
        replayed. Segments evicted by the budget stay evicted; the
        surviving ones replay."""
        if self._fingerprint is None:
            return False
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        self._committed = self._fingerprint == self._current_fingerprint()
        return self._committed

    def abort(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        self._fingerprint = None     # a new begin() must precede writes
        self._committed = False

    # ------------------------------------------------------------ replay
    def _segment_key(self, index: int):
        """Segment key serving source `index` (its own segment, or the
        combined one when it is the only source), else _NO_SEGMENT."""
        if index in self._seg_order:
            return index
        if self._COMBINED in self._seg_order and len(self.sources) == 1 \
                and index == 0:
            return self._COMBINED
        return self._NO_SEGMENT

    def _content_coverage(self, index: int) -> Optional[int]:
        """Byte offset up to which source `index`'s recorded per-block
        fingerprints still content-match the file, re-proven by hashing
        the recorded ranges (memoized per (size, mtime_ns) snapshot so
        per-k replay passes verify once, not once per pass). None when
        no fingerprints were recorded, the serving segment is evicted
        or absent, or ANY recorded block mismatches — coverage is
        all-or-nothing: the cache replays every committed block of a
        source or none of them."""
        if not self._committed:
            return None
        key = self._segment_key(index)
        if key is self._NO_SEGMENT or key in self._evicted \
                or not os.path.exists(self._seg_path(key)):
            return None
        fps = self._block_fps.get(key)
        if not fps:
            return None
        path = self.sources[index]
        try:
            st = os.stat(path)
        except OSError:
            return None
        memo = (index, st.st_size, st.st_mtime_ns)
        if memo not in self._delta_memo:
            from avenir_tpu.core.incremental import verified_prefix

            n, covered = verified_prefix(
                path, [{"offset": o, "length": ln, "hash": h}
                       for o, ln, h in fps])
            self._delta_memo[memo] = covered if n == len(fps) else None
        return self._delta_memo[memo]

    def source_delta(self, index: int) -> Optional[int]:
        """Byte offset at which source `index`'s cached coverage ends,
        when its committed blocks are still a verified content PREFIX of
        the current file — the appended-source replay gate: consumers
        replay ``blocks(index, prefix=True)`` and re-parse only
        ``[delta, size)``. None when the prefix itself no longer matches
        (an in-place edit), the segment was evicted, the writer recorded
        no fingerprints (external shared-scan feeds), or the coverage
        ends MID-LINE on a grown file (the scanned corpus' last line had
        no terminator, so the appended bytes extend an already-encoded
        row — splicing a tail re-parse there would split one line into
        two)."""
        cov = self._content_coverage(index)
        if cov is None:
            return None
        path = self.sources[index]
        try:
            size = os.path.getsize(path)
        except OSError:
            return None
        if cov < size:
            from avenir_tpu.core.incremental import ends_at_newline

            if not ends_at_newline(path, cov):
                return None
        return cov

    def _source_unchanged(self, index: int) -> bool:
        rec = self._fingerprint[index]
        path = self.sources[index]
        try:
            st = os.stat(path)
            cur = (path, st.st_size, st.st_mtime_ns)
        except OSError:
            cur = (path, -1, -1)
        if cur == rec:
            return True
        # mtime-only churn (touch, copy-back) must not torch the cache:
        # the per-block content fingerprints re-prove the bytes; full
        # validity needs them to cover the file END TO END
        cov = self._content_coverage(index)
        return cov is not None and cov == cur[1]

    def _fingerprint_ok(self) -> bool:
        if not self._committed or self._fingerprint is None:
            return False
        if self._fingerprint == self._current_fingerprint():
            return True
        return all(self._source_unchanged(i)
                   for i in range(len(self.sources)))

    @property
    def valid(self) -> bool:
        """True when a committed cache exists, the sources are
        byte-for-byte the ones it encoded (size+mtime fingerprint), AND
        no segment was evicted — the all-or-nothing replay gate. With
        evictions, consumers use the per-source gate below."""
        return (self._fingerprint_ok() and not self._evicted
                and all(os.path.exists(self._seg_path(k))
                        for k in self._seg_order))

    def source_valid(self, index: int) -> bool:
        """True when source `index`'s blocks can replay IN FULL (the
        file is covered end to end): its own segment survives, or the
        cache wrote one combined segment for a single source. A multi-
        source combined segment cannot split, so it replays only through
        the all-or-nothing `valid` gate. An appended source fails this
        gate but keeps the prefix gate: see source_delta()."""
        if not self._fingerprint_ok():
            return False
        key = self._segment_key(index)
        if key is self._NO_SEGMENT:
            return False
        return (key not in self._evicted
                and os.path.exists(self._seg_path(key)))

    def _read_segment(self, key):
        import struct

        path = self._seg_path(key)
        with open(path, "rb") as fh:
            if fh.read(len(_ENC_MAGIC)) != _ENC_MAGIC:
                raise RuntimeError("encoded-block cache is corrupt")
            while True:
                head = fh.read(18)
                if not head:
                    break
                n_rows, n_tok, cd, kd = struct.unpack("<qqBB", head)
                counts = np.fromfile(fh, _ENC_DTYPES[cd], n_rows)
                codes = np.fromfile(fh, _ENC_DTYPES[kd], n_tok)
                if counts.shape[0] != n_rows or codes.shape[0] != n_tok:
                    raise RuntimeError("encoded-block cache is truncated")
                # int32 both ways: per-row region counts are bounded by
                # tokens-per-row and codes by the vocab — widening the
                # block-proportional arrays to int64 here was exactly the
                # mem-dtype-expansion-at-parse shape this tier flags
                yield counts.astype(np.int32), codes.astype(np.int32)
        self._replay_clock += 1
        self._last_replay[key] = self._replay_clock

    def blocks(self, source: Optional[int] = None, prefix: bool = False):
        """Yield (counts int32 [n_rows], codes int32 [n_tokens]) per
        cached block — all segments in write order by default, one
        source's segment with `source=i`. With ``prefix=True`` the
        per-source gate relaxes from full coverage to the verified-
        content-prefix gate (source_delta): the appended-source replay,
        where the caller re-parses the tail itself. Raises RuntimeError
        when the requested scope is not replayable — callers check
        `valid` / `source_valid(i)` / `source_delta(i)` and fall back
        to the re-parse path."""
        if source is not None:
            ok = self.source_valid(source) or (
                prefix and self.source_delta(source) is not None)
            if not ok:
                raise RuntimeError(
                    f"encoded-block segment for source {source} is "
                    f"stale, evicted or absent")
            key = source if source in self._seg_order else self._COMBINED
            yield from self._read_segment(key)
            live = [k for k in self._seg_order if k not in self._evicted]
            if live and key == live[-1]:
                self.replays += 1
            return
        if not self.valid:
            raise RuntimeError("encoded-block cache is stale or absent")
        for key in self._seg_order:
            yield from self._read_segment(key)
        self.replays += 1

    def nbytes(self) -> int:
        try:
            return self._spilled_bytes()
        except OSError:
            return 0

    # ----------------------------------------------------------- cleanup
    def close(self) -> None:
        import shutil

        self.abort()
        if self._own_dir:
            shutil.rmtree(self._dir, ignore_errors=True)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def distinct_row_code_counts(row_of: np.ndarray, codes: np.ndarray,
                             region: np.ndarray, v: int) -> np.ndarray:
    """counts[c] = #rows whose region tokens include code c, each row
    counted once (the multi-hot k=1 support algebra): in-place sort +
    consecutive-diff dedup, so the int64 key array is the only
    token-sized temporary — no np.unique copy."""
    keys = row_of[region].astype(np.int64) * v + codes[region]
    keys.sort()
    if not keys.shape[0]:
        return np.zeros(v, np.int64)
    uniq = np.empty(keys.shape[0], bool)
    uniq[0] = True
    np.not_equal(keys[1:], keys[:-1], out=uniq[1:])
    return np.bincount((keys[uniq] % v).astype(np.intp), minlength=v)


class SpillScanMixin:
    """Shared pass-1 machinery of the streaming miner sources
    (association.StreamingTransactionSource, sequence.
    StreamingSequenceSource): the scan lifecycle (begin -> per-block
    -> finish/commit), the SharedScan sink adapter, and the encoded-
    block cache's ownership. ONE copy, so a cache-lifecycle fix can
    never land in one miner and silently miss the other.

    Subclass contract — attributes: ``paths``, ``delim``, ``skip``,
    ``block_bytes``, ``spill_cache``, ``vocab``, ``index``, ``_cache``,
    ``_item_counts``, ``_scan_counts``, ``_scan_encoder``; methods:
    ``_scan_block(data)`` (fold one raw byte block, updating
    ``_scan_counts`` via ``_grow_counts`` and spilling to ``_cache``),
    ``_reset_scan_state()`` (zero the per-scan row counters) and
    ``_scan_result()`` (the (vocab, counts, n) tuple scan()/scan_items()
    return). ``_scan_marker`` is the infrequent-item sentinel forwarded
    to the encoder (None when the format has none); an optional
    ``cache_budget_bytes`` attribute bounds the encoded-block spill
    (None -> the cache's generous default)."""

    _scan_marker: Optional[str] = None

    def _scan_begin(self) -> None:
        self._reset_scan_state()
        self._scan_counts = np.zeros(0, np.int64)
        self._sidecar_vocab_src = None
        self._sidecar_vocab_done = 0
        self._scan_encoder = (
            BlockScanEncoder(self.delim, self.skip, self.vocab, self.index,
                             marker=self._scan_marker)
            if native_seq_ready(self.delim) else None)
        if self.spill_cache:
            if self._cache is not None:
                self._cache.close()
            self._cache = EncodedBlockCache(
                self.paths,
                byte_budget=getattr(self, "cache_budget_bytes", None))
            self._cache.begin()

    def _grow_counts(self) -> None:
        v = len(self.vocab)
        if self._scan_counts.shape[0] < v:
            self._scan_counts = np.concatenate(
                [self._scan_counts,
                 np.zeros(v - self._scan_counts.shape[0], np.int64)])

    def _scan_all(self):
        """Own-read scan driver: prefetched byte blocks of every path
        through _scan_block, then seal. Blocks attribute to per-source
        cache segments so a budget eviction drops whole sources, not the
        whole cache (the SharedScan feed below cannot attribute and
        writes one combined segment), and every block's content
        fingerprint is recorded (note_block) so an appended source later
        replays its committed prefix and re-parses only the tail.

        A runner that attached ``sidecar_opts`` (runner._build_miner_
        source) routes each path through the cross-run columnar sidecar
        first: verified blocks replay as SidecarBytesBlock (no tokenize,
        no parse — _scan_encoded_block), cold blocks arrive raw and both
        fold AND pack, so the NEXT run's pass 1 is parse-free too. The
        per-k spill cache sits on top either way — replayed blocks feed
        it their re-mapped codes, cold blocks their scanned ones."""
        from avenir_tpu.core.stream import iter_byte_blocks, prefetched

        self._scan_begin()
        label = type(self).__name__
        opts = getattr(self, "sidecar_opts", None)
        for si, path in enumerate(self.paths):
            feed = None
            if opts is not None:
                from avenir_tpu.native import sidecar as _sidecar

                feed = _sidecar.byte_blocks(opts, path, self.delim,
                                            self.skip, self.block_bytes)
            if feed is not None:
                if self._cache is not None:
                    self._cache.set_source(si)
                for off, length, hsh, payload in feed:
                    if self._cache is not None:
                        self._cache.note_fingerprint(off, length, hsh)
                    if payload is None:
                        continue
                    if isinstance(payload, (bytes, bytearray)):
                        t0 = _obs.now()
                        self._scan_block(payload)
                        _obs.record("stream.parse", t0, sink=label,
                                    nbytes=length)
                    else:
                        self._scan_encoded_block(payload)
            elif self._cache is not None:
                self._cache.set_source(si)
                for off, data in prefetched(
                        iter_byte_blocks(path, self.block_bytes,
                                         with_offsets=True), depth=1):
                    self._cache.note_block(off, data)
                    t0 = _obs.now()
                    self._scan_block(data)
                    _obs.record("stream.parse", t0, sink=label,
                                nbytes=len(data))
            else:
                for data in prefetched(
                        iter_byte_blocks(path, self.block_bytes), depth=1):
                    t0 = _obs.now()
                    self._scan_block(data)
                    _obs.record("stream.parse", t0, sink=label,
                                nbytes=len(data))
        return self._scan_finish()

    def _scan_encoded_block(self, blk) -> None:
        """Fold one replayed sidecar block (native.sidecar.
        SidecarBytesBlock) — the parse-free twin of _scan_block. The
        sidecar's vocabulary extends this source's in FIRST-SEEN order
        (minus the infrequent-item marker, which the sidecar keeps but
        miners drop), which is exactly the order the cold discovery scan
        would have assigned — so codes, counts and the per-k spill cache
        come out identical to a cold pass over the same bytes."""
        if blk.skip != self.skip:
            raise ValueError(
                f"sidecar block packed at skip={blk.skip} fed to a "
                f"skip={self.skip} scan")
        # the merge watermark is PER SIDECAR: each source's manifest has
        # its own vocabulary (one shared list per feed), so key the
        # watermark on that list's identity — a scan crossing inputs
        # (own-read multi-path or a shared feed) restarts at 0 for the
        # next source instead of skipping its unseen tokens
        if getattr(self, "_sidecar_vocab_src", None) is not blk.vocab:
            self._sidecar_vocab_src = blk.vocab
            self._sidecar_vocab_done = 0
        done = self._sidecar_vocab_done
        for tok in blk.vocab[done:blk.vocab_end]:
            if tok != self._scan_marker and tok not in self.index:
                self.index[tok] = len(self.vocab)
                self.vocab.append(tok)
        self._sidecar_vocab_done = max(done, blk.vocab_end)
        self._grow_counts()
        # stored sidecar codes are vocab code + 1 with 0 = the empty
        # token; map through a LUT onto THIS source's codes, -1 dropping
        # empties and the marker exactly as the cold region mask does
        lut = np.full(blk.vocab_end + 1, -1, np.int32)
        for k in range(blk.vocab_end):
            tok = blk.vocab[k]
            if tok != self._scan_marker:
                lut[k + 1] = self.index[tok]
        mapped = lut[blk.codes]
        region = mapped >= 0
        row_of = np.repeat(np.arange(blk.n, dtype=np.int32), blk.counts)
        self._scan_counts += distinct_row_code_counts(
            row_of, mapped, region, len(self.vocab))
        per_row = np.bincount(row_of[region].astype(np.intp),
                              minlength=blk.n)
        if self._cache is not None:
            self._cache.add_block(per_row, mapped[region])
        self._note_encoded_rows(per_row, blk.n)

    def _note_encoded_rows(self, per_row: np.ndarray, n: int) -> None:
        """Subclass hook: update the per-scan row counters for one
        replayed block (association: transaction count; sequence: row
        count and max length) — the only part of the block fold the
        mixin cannot name for both miners."""
        raise NotImplementedError

    def scan_consumer(self):
        """Shared-scan sink: pass 1 driven by EXTERNAL raw byte blocks
        (core.stream.SharedScan fans one disk read to N such sinks).
        consume() per block; finish() seals the scan and returns what
        the source's own scan entry point would."""
        self._scan_begin()
        src = self
        label = type(self).__name__

        class _ScanSink:
            def consume(self, data) -> None:
                if not isinstance(data, (bytes, bytearray)):
                    # a sidecar-replayed block from a sidecar-aware
                    # shared feed: parse-free fold, no stream.parse span
                    src._scan_encoded_block(data)
                    return
                # pass-1 parse/encode of an externally-read block: the
                # same stream.parse span the own-read scan records
                t0 = _obs.now()
                src._scan_block(data)
                _obs.record("stream.parse", t0, sink=label,
                            nbytes=len(data))

            def finish(self):
                return src._scan_finish()

        return _ScanSink()

    def _scan_finish(self):
        self._item_counts = self._scan_counts
        self._scan_encoder = None
        if self._cache is not None and not self._cache.commit():
            # a source changed under the scan: never replay a torn cache
            self._cache.close()
            self._cache = None
        return self._scan_result()

    def restore_scan_state(self, vocab, counts) -> None:
        """Restore a mid-scan checkpoint into a freshly-BEGUN scan (the
        fold-state resume contract, graftlint --merge): reinstall the
        checkpointed discovery vocabulary and partial per-item counts in
        place (the encoder holds references to `vocab`/`index`, so they
        mutate, never rebind), rebuild the native encoder over them, and
        DROP the spill cache — a cache begun after the restore would
        hold only post-restore blocks yet commit as complete, and a
        later per-k pass would replay a truncated corpus. Restored scans
        therefore re-parse their sources per-k: correctness over
        throughput, documented in docs/DESIGN.md. Callers restore their
        own row counters (n_trans / n_rows / t_max) — the mixin does not
        know their names."""
        self.vocab[:] = list(vocab)
        self.index.clear()
        self.index.update({t: i for i, t in enumerate(self.vocab)})
        self._scan_counts = np.asarray(counts, np.int64).copy()
        if self._scan_encoder is not None:
            self._scan_encoder = BlockScanEncoder(
                self.delim, self.skip, self.vocab, self.index,
                marker=self._scan_marker)
        if self._cache is not None:
            self._cache.close()
            self._cache = None
        self.spill_cache = False

    @property
    def cache_replays(self) -> int:
        """Completed encoded-block replay passes."""
        return self._cache.replays if self._cache is not None else 0

    def cache_ready(self) -> bool:
        """True when the pass-1 spill cache is committed and EVERY
        source's segment can still replay in full (the cache's own
        content gates) — the warm-replay precondition the resident job
        server checks before serving a repeat mining request from this
        source with zero CSV parses. Any corpus change fails the gate:
        a warm hit can never serve stale discovery counts."""
        c = self._cache
        if c is None or self._item_counts is None:
            return False
        return all(c.source_valid(i) for i in range(len(self.paths)))

    def cache_evict_to(self, byte_budget: int) -> int:
        """Trim the spill toward `byte_budget` through the cache's own
        segment eviction (``EncodedBlockCache.evict_to``); returns the
        bytes evicted, 0 when the cache is off — the handle the job
        server's warm-state budget enforcement consumes."""
        return (self._cache.evict_to(byte_budget)
                if self._cache is not None else 0)

    @property
    def cache_nbytes(self) -> int:
        """On-disk size of the encoded-block spill cache (0 when off)."""
        return self._cache.nbytes() if self._cache is not None else 0

    @property
    def cache_evicted_bytes(self) -> int:
        """Bytes the spill cache evicted (or dropped) to hold its byte
        budget — surfaced as the Cache:EvictedBytes job counter."""
        return (self._cache.evicted_bytes
                if self._cache is not None else 0)

    def close(self) -> None:
        if self._cache is not None:
            self._cache.close()
            self._cache = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def extract_column_native(data: bytes, delim: str, ordinal: int
                          ) -> Optional[np.ndarray]:
    """One column's trimmed tokens for every non-blank line of a raw text
    block (short rows yield ''), as a numpy unicode array — the open-
    vocabulary companion to seq_encode_native (entity ids cannot
    dictionary-encode). None when the native library is unavailable."""
    lib = _get_lib()
    if lib is None:
        return None
    d = delim.encode()
    if len(d) != 1:
        return None
    raw = _extract_column_bytes(lib, data, d, ordinal)
    return np.array(raw.decode("utf-8", "replace").split("\n")[:-1])
