"""Columnar dataset: CSV rows -> device-friendly arrays.

The reference's unit of data is a delimited text line on HDFS whose fields
get meaning from the FeatureSchema JSON (every mapper re-splits the line,
e.g. bayesian/BayesianDistribution.java:137-178). The TPU-native equivalent
is columnar: parse once on the host, dictionary-encode categoricals against
the schema's declared cardinality, bucketize binned numerics, and hand the
algorithms dense int32/float32 matrices that vmap/segment_sum can chew on.

Three views cover every algorithm family:
- `feature_codes()`  int32 [n, F]: dense per-feature states (categorical code
  or numeric bucket) — count-based algorithms (NB, MI, correlations, tree
  categorical splits, Apriori-style contingency work).
- `feature_matrix()` float32 [n, D]: numeric values (raw numerics; categorical
  columns excluded) — distance/gradient algorithms (KNN, LR, Fisher).
- `labels()`         int32 [n]: encoded class attribute.

Row identity (the `id` field) stays host-side as numpy object/str arrays —
ids never need to touch the device.
"""

from __future__ import annotations

import io
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from avenir_tpu import obs as _obs
from avenir_tpu.core.schema import FeatureField, FeatureSchema


class Dataset:
    """Columnar view of one CSV input split against a FeatureSchema."""

    def __init__(
        self,
        schema: FeatureSchema,
        columns: Dict[int, np.ndarray],
        n_rows: int,
        raw_rows: Optional[List[List[str]]] = None,
        lazy: Optional[Dict[int, object]] = None,
    ):
        self.schema = schema
        self.columns = columns          # ordinal -> np array (codes / floats / object)
        self.n_rows = n_rows
        self.raw_rows = raw_rows        # kept when passthrough output is needed
        # string/id columns parse lazily (thunks): most algorithms never
        # touch ids, and materializing millions of python strings halves
        # the native ingest rate (the 1B-row streaming path skips it)
        self._lazy = dict(lazy) if lazy else {}
        # feature_codes memo: a shared scan hands one chunk to several
        # consumers, each stacking the same [n, F] code matrix
        self._codes_cache: Dict[tuple, tuple] = {}

    # ------------------------------------------------------------------ load
    @classmethod
    def from_csv(
        cls,
        source: Union[str, bytes, Iterable[str]],
        schema: FeatureSchema,
        delim: str = ",",
        keep_raw: bool = False,
        engine: str = "auto",
    ) -> "Dataset":
        """Parse CSV lines (a path, a text blob, raw bytes, or an iterable
        of lines) into columns. Unknown categorical values raise — the
        schema declares the full cardinality, same contract as the
        reference. A string is treated as a file path if such a file
        exists, otherwise as content (content must contain a newline or the
        delimiter). Bytes are always content — the block-streaming reader
        (core/stream.py) hands file blocks here without a decode copy.

        engine: 'auto' uses the native C++ parser (avenir_tpu/native) when
        built and applicable (path/blob/bytes source, single-char delimiter,
        no keep_raw), 'native' requires it, 'python' forces the row parser.

        A path source parses under a `dataset.parse` span whose children
        (`dataset.read`, `.parse.native`, `.encode`, `.range`, and inside
        `.parse.native` its steps `.parse.count`, `.prefill`, `.fields`,
        `.check`, `.ids`) stay on the caller's thread; the block route
        (bytes, on the prefetcher's thread, which `stream.parse` already
        spans) emits none of them."""
        if isinstance(source, str) and os.path.exists(source):
            with _obs.span("dataset.parse", path=source,
                           nbytes=os.path.getsize(source)) as note:
                ds = cls._from_source(source, schema, delim, keep_raw, engine)
                note["rows"] = len(ds)
            return ds
        return cls._from_source(source, schema, delim, keep_raw, engine)

    @classmethod
    def _from_source(cls, source, schema: FeatureSchema, delim: str,
                     keep_raw: bool, engine: str) -> "Dataset":
        if engine not in ("auto", "native", "python"):
            raise ValueError(f"unknown CSV engine {engine!r} "
                             "(want auto, native, or python)")
        if isinstance(source, (bytes, bytearray)):
            native_ok = not keep_raw and len(delim.encode()) == 1
            if engine in ("auto", "native") and native_ok:
                ds = cls._from_native_data(bytes(source), schema, delim,
                                           required=engine == "native")
                if ds is not None:
                    return ds
            if engine == "native":
                raise ValueError(
                    "engine='native' requires a single-byte delimiter and "
                    "keep_raw=False")
            source = io.StringIO(bytes(source).decode())
        native_ok = (not keep_raw and isinstance(source, str)
                     and len(delim.encode()) == 1)
        if engine == "native" and not native_ok:
            raise ValueError(
                "engine='native' requires a path/blob source, a single-byte "
                "delimiter, and keep_raw=False")
        if engine in ("auto", "native") and native_ok:
            ds = cls._from_csv_native(source, schema, delim,
                                      required=engine == "native")
            if ds is not None:
                return ds
        if isinstance(source, str):
            if os.path.exists(source):
                lines: Iterable[str] = open(source, "r")
            elif "\n" in source or delim in source:
                lines = io.StringIO(source)
            elif source == "":
                lines = io.StringIO("")
            else:
                raise FileNotFoundError(f"no such CSV file: {source!r}")
        else:
            lines = source

        rows: List[List[str]] = []
        for line in lines:
            line = line.rstrip("\n").rstrip("\r")
            if not line.strip():
                continue
            rows.append([tok.strip() for tok in line.split(delim)])
        if hasattr(lines, "close") and lines is not source:
            lines.close()
        return cls.from_rows(rows, schema, keep_raw=keep_raw)

    @classmethod
    def _from_csv_native(cls, source: str, schema: FeatureSchema,
                         delim: str, required: bool) -> Optional["Dataset"]:
        """Native one-pass columnar parse of a path/blob source; None when
        unavailable (caller falls through to the Python parser)."""
        is_path = os.path.exists(source)
        if is_path:
            with _obs.span("dataset.read") as note:
                with open(source, "rb") as fh:
                    data = fh.read()
                note["nbytes"] = len(data)
        elif "\n" in source or delim in source or source == "":
            data = source.encode()
        else:
            raise FileNotFoundError(f"no such CSV file: {source!r}")
        return cls._from_native_data(data, schema, delim, required,
                                     spanned=is_path)

    @classmethod
    def _from_native_data(cls, data: bytes, schema: FeatureSchema,
                          delim: str, required: bool,
                          spanned: bool = False) -> Optional["Dataset"]:
        from avenir_tpu.native.ingest import (distinct_column_native,
                                              native_available,
                                              parse_csv_native)

        # only the path route names its phases: a block's parse runs on
        # the prefetcher's thread, inside that route's own stream.parse
        span = _obs.span if spanned else _obs.no_span

        if not native_available():
            if required:
                raise RuntimeError("native CSV ingest unavailable")
            return None
        numeric = [f.ordinal for f in schema.fields if f.is_numeric]
        cats = [f for f in schema.fields if f.is_categorical]
        # a vocabulary the schema does not declare (data-discovered,
        # growable) is settled first, from the column's distinct tokens;
        # the parse then encodes every categorical in C alike
        discovered = [f for f in cats
                      if not f.cardinality or f.discovered_cardinality]
        strings = [f.ordinal for f in schema.fields
                   if not f.is_numeric and not f.is_categorical]
        try:
            with span("dataset.encode", fields=len(discovered), rows=0,
                      native=0, vocab=0) as note:
                for fld in discovered:
                    distinct, note["rows"] = distinct_column_native(
                        data, delim, fld.ordinal)
                    _discover_cardinality(fld, distinct)
                    note["native"] += 1
                    note["vocab"] += len(distinct)
            with span("dataset.parse.native", columns=len(schema.fields)) as note:
                n, columns, lazy = parse_csv_native(
                    data, delim, numeric,
                    [(f.ordinal, f.cardinality) for f in cats], strings,
                    lazy_strings=True, span=span)
                note["rows"] = n
        except ValueError as e:
            # align cardinality errors with the Python parser (field name);
            # other ValueErrors (e.g. invalid numerics) pass through as-is
            msg = str(e)
            if " not in declared cardinality" in msg:
                for fld in schema.fields:
                    if msg.endswith(f"ordinal {fld.ordinal}") or \
                            f"ordinal {fld.ordinal} " in msg:
                        raise ValueError(
                            msg.split(" not in ")[0]
                            + f" not in declared cardinality of field "
                            f"{fld.name!r}") from None
            raise
        with span("dataset.range", fields=len(numeric)):
            for fld in schema.fields:
                if fld.is_numeric and fld.ordinal in columns:
                    _discover_numeric_range(fld, columns[fld.ordinal])
        return cls(schema, columns, n, lazy=lazy)

    @classmethod
    def from_rows(
        cls,
        rows: List[List[str]],
        schema: FeatureSchema,
        keep_raw: bool = False,
    ) -> "Dataset":
        n = len(rows)
        columns: Dict[int, np.ndarray] = {}
        for fld in schema.fields:
            o = fld.ordinal
            toks = [r[o] if o < len(r) else "" for r in rows]
            if fld.is_categorical:
                _discover_cardinality(fld, toks)
                index = fld.cardinality_index()
                try:
                    columns[o] = np.array([index[t] for t in toks], dtype=np.int32)
                except KeyError as e:
                    raise ValueError(
                        f"value {e.args[0]!r} not in declared cardinality of "
                        f"field {fld.name!r}"
                    ) from None
            elif fld.is_numeric:
                dt = np.float32
                columns[o] = np.array(
                    [float(t) if t != "" else np.nan for t in toks], dtype=dt
                )
                _discover_numeric_range(fld, columns[o])
            else:  # string / text / id: host-side object column
                columns[o] = np.array(toks, dtype=object)
        return cls(schema, columns, n, raw_rows=rows if keep_raw else None)

    # ----------------------------------------------------------------- views
    def column(self, ordinal: int) -> np.ndarray:
        if ordinal not in self.columns and ordinal in self._lazy:
            self.columns[ordinal] = self._lazy.pop(ordinal)()
        return self.columns[ordinal]

    def ids(self) -> np.ndarray:
        idf = self.schema.id_field
        if idf is None:
            return np.array([str(i) for i in range(self.n_rows)], dtype=object)
        return self.column(idf.ordinal)

    def labels(self) -> np.ndarray:
        """Encoded class attribute codes, int32 [n]."""
        cf = self.schema.class_field
        if cf is None:
            raise ValueError("schema has no class attribute")
        col = self.column(cf.ordinal)
        if col.dtype == object:  # class field declared as plain string
            index = cf.cardinality_index()
            return np.array([index[v] for v in col], dtype=np.int32)
        return col.astype(np.int32)

    def feature_codes(
        self, fields: Optional[Sequence[FeatureField]] = None
    ) -> Tuple[np.ndarray, List[int]]:
        """Dense per-feature states.

        Returns (codes int32 [n, F], bins list[F]) over the dense-encodable
        feature fields (categoricals + bucketized numerics), in ordinal order.
        Numeric features without bucketWidth are skipped (they have no dense
        state; the Gaussian path of NB handles them from feature_matrix()).
        """
        if fields is None:
            fields = [f for f in self.schema.feature_fields if f.num_bins() > 0]
        # keyed on (ordinal, bins) so a vocabulary discovered AFTER a
        # cached call (growing num_bins) misses instead of serving codes
        # stacked against the stale bin count
        memo_key = tuple((f.ordinal, f.num_bins()) for f in fields)
        hit = self._codes_cache.get(memo_key)
        if hit is not None:
            return hit[0], list(hit[1])
        cols = []
        bins = []
        for fld in fields:
            nb = fld.num_bins()
            if nb <= 0:
                continue
            col = self.column(fld.ordinal)
            if fld.is_categorical:
                # copy=False: the stack below copies; an int32 column
                # (the native parse and replay norm) need not copy twice
                cols.append(col.astype(np.int32, copy=False))
            else:
                if np.isnan(col).any():
                    raise ValueError(
                        f"missing value in bucketized numeric field {fld.name!r} "
                        "(empty tokens cannot be dense-encoded)"
                    )
                lo = fld.min if fld.min is not None else 0.0
                code = np.floor((col - lo) / fld.bucket_width).astype(np.int32)
                cols.append(np.clip(code, 0, nb - 1))
            bins.append(nb)
        codes = (np.stack(cols, axis=1) if cols
                 else np.zeros((self.n_rows, 0), dtype=np.int32))
        # the cached matrix is SHARED across callers (a SharedScan chunk
        # feeds several consumers): freeze it so an in-place write in
        # one fused job raises instead of corrupting every other's codes
        codes.setflags(write=False)
        self._codes_cache[memo_key] = (codes, tuple(bins))
        return codes, bins

    def feature_matrix(
        self, fields: Optional[Sequence[FeatureField]] = None
    ) -> np.ndarray:
        """float32 [n, D] of numeric feature values (raw, unbinned)."""
        if fields is None:
            fields = [f for f in self.schema.feature_fields if f.is_numeric]
        cols = [self.column(f.ordinal).astype(np.float32, copy=False)
                for f in fields]
        if not cols:
            return np.zeros((self.n_rows, 0), dtype=np.float32)
        return np.stack(cols, axis=1)

    def numeric_feature_fields(self) -> List[FeatureField]:
        return [f for f in self.schema.feature_fields if f.is_numeric]

    def encodable_feature_fields(self) -> List[FeatureField]:
        return [f for f in self.schema.feature_fields if f.num_bins() > 0]

    # ------------------------------------------------------------- utilities
    def to_csv(self, delim: str = ",") -> str:
        """Render rows back to reference-style CSV text (categorical codes
        decoded to their cardinality values). Uses raw rows when kept."""
        if self.raw_rows is not None:
            return "\n".join(delim.join(r) for r in self.raw_rows) + "\n"
        # tokens land at their declared ordinals; gaps (fields present in
        # the file but undeclared in the schema, e.g. call_hangup's area
        # code) become empty tokens so the row re-parses against the schema
        width = max(f.ordinal for f in self.schema.fields) + 1
        lines = []
        for i in range(self.n_rows):
            toks = [""] * width
            for fld in self.schema.fields:
                col = self.column(fld.ordinal)
                if fld.is_categorical:
                    tok = fld.decode_value(int(col[i]))
                elif fld.is_numeric:
                    v = float(col[i])
                    # NaN is the documented missing-value sentinel from both
                    # parsers; render it (and inf) back as an empty token
                    tok = ("" if not np.isfinite(v)
                           else str(int(v)) if v == int(v) else f"{v:.6g}")
                else:
                    tok = str(col[i])
                toks[fld.ordinal] = tok
            lines.append(delim.join(toks))
        return "\n".join(lines) + "\n"

    def take(self, idx: np.ndarray) -> "Dataset":
        """Row subset (numpy fancy index) — used by samplers and CV splits."""
        # lazy columns stay lazy: compose the subset onto the thunk so a
        # sampler over an id-bearing dataset still never materializes ids
        # unless someone reads them
        sub_idx = np.asarray(idx)
        lazy = {o: (lambda o=o: self.column(o)[sub_idx])
                for o in self._lazy}
        cols = {o: c[idx] for o, c in self.columns.items()}
        raw = [self.raw_rows[i] for i in idx] if self.raw_rows is not None else None
        return Dataset(self.schema, cols, int(sub_idx.shape[0]), raw,
                       lazy=lazy)

    def __len__(self) -> int:
        return self.n_rows

    def __repr__(self) -> str:
        return f"Dataset(n={self.n_rows}, fields={len(self.schema)})"


def _discover_cardinality(fld, tokens) -> None:
    """Categorical fields may ship without a declared cardinality (e.g.
    `status` in the reference's elearnActivity.json rich schema) — the
    value set is then discovered from the data, sorted for determinism,
    and recorded on the (shared) schema field so later splits parsed
    against the same schema object encode consistently; unseen values in
    later splits extend the vocabulary instead of raising."""
    if fld.cardinality:
        if fld.discovered_cardinality:
            known = set(fld.cardinality)
            new = sorted({t for t in tokens} - known)
            if new:
                fld.cardinality.extend(new)
        return
    fld.cardinality = sorted({t for t in tokens})
    fld.discovered_cardinality = True


def _discover_numeric_range(fld, col: np.ndarray) -> None:
    """Numeric fields with bucketWidth but no declared max (the
    reference's hosp_readmit.json style — the Java jobs bin by
    floor(value/width) with data-determined extent): record the observed
    max on the (shared) schema field so num_bins() covers every seen
    code. The max only grows across chunks/splits, so earlier codes stay
    valid and streaming count accumulators just pad the bin axis."""
    if not fld.bucket_width or (fld.max is not None
                                and not fld.discovered_range):
        return
    finite = col[np.isfinite(col)]
    if finite.size == 0:
        return
    hi = float(finite.max())
    fld.max = hi if fld.max is None else max(fld.max, hi)
    fld.discovered_range = True


def pad_rows(n: int, multiple: int) -> int:
    """Rows padded up to a multiple (device shard divisibility)."""
    return ((n + multiple - 1) // multiple) * multiple


def _feature_ranges(num_fields) -> np.ndarray:
    """float32 [Dn]: each numeric field's declared max - min, 1.0 where the
    schema declares no extent."""
    return np.array(
        [
            (f.max - f.min) if (f.max is not None and f.min is not None) else 1.0
            for f in num_fields
        ],
        dtype=np.float32,
    )


def extract_mixed_features(ds: "Dataset"):
    """Split a dataset into distance-ready arrays: (x_num float32 [n, Dn],
    ranges float32 [Dn], x_cat int32 [n, Dc] | None, cat_bins tuple | None).

    Ranges come from the schema's declared min/max (1.0 fallback) — the
    normalization the mixed-attribute distance metric uses. Shared by KNN
    and clustering. (Relief normalizes per-feature diffs itself with a
    data-derived range fallback — explore.relief_relevance.)"""
    num_fields = [f for f in ds.schema.feature_fields if f.is_numeric]
    cat_fields = [f for f in ds.schema.feature_fields if f.is_categorical]
    x_num = ds.feature_matrix(num_fields)
    ranges = _feature_ranges(num_fields)
    if cat_fields:
        x_cat = np.stack(
            [ds.column(f.ordinal).astype(np.int32) for f in cat_fields], axis=1
        )
        bins = tuple(len(f.cardinality) for f in cat_fields)
    else:
        x_cat, bins = None, None
    return x_num, ranges, x_cat, bins


def mixed_feature_columns(ds: "Dataset"):
    """`extract_mixed_features`' parts before they are stacked: (numeric
    columns float32 [n] each, ranges float32 [Dn], categorical code columns
    int32 [n] each, cat_bins tuple | None). A column already of its type
    is the dataset's own array, not a copy; another is converted as
    `feature_matrix` converts it."""
    num_fields = [f for f in ds.schema.feature_fields if f.is_numeric]
    cat_fields = [f for f in ds.schema.feature_fields if f.is_categorical]
    num = [ds.column(f.ordinal).astype(np.float32, copy=False)
           for f in num_fields]
    cats = [ds.column(f.ordinal).astype(np.int32, copy=False)
            for f in cat_fields]
    bins = tuple(len(f.cardinality) for f in cat_fields) if cat_fields else None
    return num, _feature_ranges(num_fields), cats, bins
