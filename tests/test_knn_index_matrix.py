"""The kernel route's index and query matrix, written once from the
dataset's columns by the native pass (`models.knn.kernel_matrix`): the
same bytes as `pad_train(_expand_mixed(...))` over every schema kind,
metric, row count and thread request; a code outside its field's values
refused; `NeighborIndex.queries` and the `knn.index.*` spans of the
kernel route, held on the CPU with the kernel's availability pretended."""

import os

import numpy as np
import pytest

from avenir_tpu import obs
from avenir_tpu.core.dataset import (Dataset, extract_mixed_features,
                                     mixed_feature_columns)
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.models.knn import (_BLOCK_Q, NeighborIndex, _expand_mixed,
                                   kernel_matrix)
from avenir_tpu.native import ingest
from avenir_tpu.ops.distance import pad_train

pytestmark = pytest.mark.skipif(not ingest.native_available(),
                                reason="native library not built")

#: numeric fields: a declared range, a zero range (the 1e-9 floor), a
#: negative span, and one with no declared extent (range 1.0)
NUMERIC = [
    {"name": "a", "dataType": "double", "feature": True, "min": 0, "max": 600},
    {"name": "flat", "dataType": "double", "feature": True, "min": 5, "max": 5},
    {"name": "neg", "dataType": "double", "feature": True,
     "min": -40, "max": -2.5},
    {"name": "free", "dataType": "double", "feature": True},
]
CATEGORICAL = [
    {"name": "kind", "dataType": "categorical", "feature": True,
     "cardinality": ["x", "y", "z"]},
    {"name": "tier", "dataType": "categorical", "feature": True,
     "cardinality": ["p", "q", "r", "s", "t"]},
]
SCHEMAS = {"numeric": NUMERIC, "categorical": CATEGORICAL,
           "mixed": NUMERIC[:2] + CATEGORICAL[:1] + NUMERIC[2:]
           + CATEGORICAL[1:]}
#: (rows, pad multiple): one row, around one query block, a count whose
#: matrix crosses stripes (pad rows in the last), and one padded across
#: several stripes that hold no row at all
SIZES = [(1, 256), (255, 256), (256, 256), (257, 256), (300_001, 256),
         (100_003, 1 << 19)]


def _schema(kind):
    return FeatureSchema.from_json({"fields": [
        {**f, "ordinal": i} for i, f in enumerate(SCHEMAS[kind])]})


def _dataset(kind, n, seed=41, dtype=np.float32):
    """Columns as a parser leaves them: numerics with negatives and NaN,
    codes int32."""
    schema = _schema(kind)
    rng = np.random.default_rng(seed)
    cols = {}
    for f in schema.fields:
        if f.is_categorical:
            cols[f.ordinal] = rng.integers(0, len(f.cardinality), n,
                                           dtype=np.int32)
        else:
            x = rng.normal(0.0, 300.0, n)
            x[rng.random(n) < 0.01] = np.nan
            cols[f.ordinal] = x.astype(dtype)
    return Dataset(schema, cols, n)


def _oracle(ds, metric, multiple):
    """The parent's form: stack, divide, one-hot, concatenate, pad."""
    x_num, ranges, x_cat, bins = extract_mixed_features(ds)
    x, _ = _expand_mixed(x_num, ranges, x_cat, bins, metric)
    return pad_train(x, None, multiple)[0]


def _same_bytes(got, want):
    assert got.dtype == want.dtype == np.float32
    assert got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("threads", [1, 0])
@pytest.mark.parametrize("metric", ["manhattan", "euclidean"])
@pytest.mark.parametrize("kind", sorted(SCHEMAS))
@pytest.mark.parametrize("n, multiple", SIZES)
def test_the_native_matrix_is_the_expanded_and_padded_one(n, multiple, kind,
                                                          metric, threads):
    ds = _dataset(kind, n)
    num, ranges, cats, bins = mixed_feature_columns(ds)
    got, native = kernel_matrix(num, ranges, cats, bins, metric, multiple,
                                threads=threads)
    assert native
    _same_bytes(got, _oracle(ds, metric, multiple))
    # the columns handed over are the dataset's own arrays, not copies
    assert all(c is ds.column(f.ordinal) for c, f in zip(
        num + cats, [f for f in ds.schema.feature_fields if f.is_numeric]
        + [f for f in ds.schema.feature_fields if f.is_categorical]))


@pytest.mark.parametrize("threads", [1, 3, 0])
def test_a_large_matrix_is_cut_into_stripes_as_asked(threads):
    ds = _dataset("mixed", 300_001)
    num, ranges, cats, bins = mixed_feature_columns(ds)
    out = np.empty((300_032, len(num) + sum(bins)), np.float32)
    stripes = ingest.knn_index_matrix_native(num, ranges, cats, bins,
                                             np.float32(0.5), out,
                                             threads=threads)
    # 300,032 rows x 12 columns is 14.4 MB: three stripes of 4 MB at most
    assert stripes == (threads or min(os.cpu_count() or 1, 3))
    _same_bytes(out, _oracle(ds, "manhattan", 32))


@pytest.mark.parametrize("kind", sorted(SCHEMAS))
def test_float64_columns_are_converted_as_the_stacked_form_converts_them(kind):
    ds = _dataset(kind, 1_000, dtype=np.float64)
    num, ranges, cats, bins = mixed_feature_columns(ds)
    assert all(c.dtype == np.float32 for c in num)
    got, native = kernel_matrix(num, ranges, cats, bins, "euclidean", 256)
    assert native
    _same_bytes(got, _oracle(ds, "euclidean", 256))


@pytest.mark.parametrize("metric", ["manhattan", "euclidean"])
def test_without_the_native_library_the_stacked_form_makes_the_same_bytes(
        metric, monkeypatch):
    ds = _dataset("mixed", 5_000)
    parts = mixed_feature_columns(ds)
    native_out, native = kernel_matrix(*parts, metric, 1_024)
    monkeypatch.setattr(ingest, "native_available", lambda: False)
    numpy_out, fell_back = kernel_matrix(*parts, metric, 1_024)
    assert native and not fell_back
    _same_bytes(native_out, numpy_out)


@pytest.mark.parametrize("code", [3, -1, 1_000_000])
def test_a_code_outside_its_fields_values_is_refused_by_name(code):
    ds = _dataset("mixed", 2_000)
    kind = next(f.ordinal for f in ds.schema.fields if f.name == "kind")
    ds.columns[kind] = ds.columns[kind].copy()
    ds.columns[kind][1_234] = code
    num, ranges, cats, bins = mixed_feature_columns(ds)
    with pytest.raises(ValueError, match=r"'kind' holds code .* at row 1234"):
        kernel_matrix(num, ranges, cats, bins, "manhattan", 256,
                      cat_names=("kind", "tier"))


def test_the_binding_refuses_columns_it_cannot_read():
    ds = _dataset("numeric", 100)
    num, ranges, cats, bins = mixed_feature_columns(ds)
    out = np.empty((256, len(num)), np.float32)
    wide = num[0].astype(np.float64)
    with pytest.raises(ValueError, match="float32 numeric"):
        ingest.knn_index_matrix_native([wide, *num[1:]], ranges, cats, (),
                                       np.float32(0.5), out)
    with pytest.raises(ValueError, match="out wants"):
        ingest.knn_index_matrix_native(num, ranges, cats, (), np.float32(0.5),
                                       out[:, :2])


@pytest.fixture
def kernel_route(monkeypatch):
    """The kernel route on the CPU: only the index is built and the
    queries prepared, no kernel runs."""
    import avenir_tpu.ops.pallas_knn as pk

    monkeypatch.setattr(pk, "pallas_available", lambda: True)


@pytest.mark.parametrize("kind, nq", [("numeric", 300), ("mixed", 256),
                                      ("categorical", 1)])
def test_queries_are_the_parents_form(kind, nq, kernel_route):
    train, test = _dataset(kind, 3_000, seed=1), _dataset(kind, nq, seed=2)
    index = NeighborIndex(train, metric="euclidean")
    assert index.kernel == "exact"
    q, q_cat, got_nq = index.queries(test)
    x_num, ranges, x_cat, bins = extract_mixed_features(test)
    want, _ = _expand_mixed(x_num, ranges, x_cat, bins, "euclidean")
    pad = (-nq) % _BLOCK_Q
    want = np.concatenate([want, np.zeros((pad, want.shape[1]), want.dtype)])
    assert q_cat is None and got_nq == nq
    _same_bytes(q, want)


@pytest.mark.parametrize("kind", sorted(SCHEMAS))
def test_the_kernel_routes_build_spans_say_what_the_pass_wrote(kind,
                                                               kernel_route):
    train = _dataset(kind, 20_000)
    with obs.capture() as rec:
        index = NeighborIndex(train)
    spans = rec.spans()
    # no `knn.index.pad`: the pass writes the pad rows
    assert sorted(s.name for s in spans if s.name.startswith("knn.index.")) \
        == ["knn.index.build", "knn.index.expand", "knn.index.extract",
            "knn.index.put", "knn.index.put.landed"]
    expand, = [s for s in spans if s.name == "knn.index.expand"]
    attrs = {k: v for k, v in expand.attrs.items()
             if k not in obs.USAGE_ATTRS}
    assert attrs == {"native": True, "threads": 0,
                     "nbytes": index.n_padded * index.t_num.shape[1] * 4}
    assert index.n_padded == 24_576 and index.n_valid == 20_000
    _same_bytes(np.asarray(index.t_num), _oracle(train, "manhattan",
                                                 index.block))
