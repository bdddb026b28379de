"""The native parse split into its steps: a path parse's
`dataset.parse.native` encloses exactly its five leaves, one after the
other on the caller's thread, and they cover it; the block route names
none of them; tracing changes no byte of the columns. And the jobs whose
large puts are now followed to their landing: the kNN job's index and
labels, the forest's labels and weights."""

import threading

import numpy as np
import pytest

from avenir_tpu import obs
from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.native import ingest

needs_native = pytest.mark.skipif(not ingest.native_available(),
                                  reason="native CSV parser not built")

PARSE_LEAVES = ("dataset.parse.count", "dataset.parse.prefill",
                "dataset.parse.fields", "dataset.parse.check",
                "dataset.parse.ids")
#: an id, numerics, a declared categorical with the empty token, an
#: undeclared one, and a second string column
SCHEMA = {"fields": [
    {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
    {"name": "x", "ordinal": 1, "dataType": "double", "feature": True},
    {"name": "kind", "ordinal": 2, "dataType": "categorical", "feature": True,
     "cardinality": ["", "a", "b"]},
    {"name": "n", "ordinal": 3, "dataType": "int", "feature": True,
     "min": 0, "max": 99},
    {"name": "note", "ordinal": 4, "dataType": "string"},
    {"name": "status", "ordinal": 5, "dataType": "categorical"}]}
ROWS = 120_000


@pytest.fixture(scope="module")
def csv_path(tmp_path_factory):
    rng = np.random.default_rng(39)
    kinds = np.array(["", "a", "b"])[rng.integers(0, 3, ROWS)]
    path = tmp_path_factory.mktemp("parse_spans") / "rows.csv"
    with open(path, "w") as fh:
        for i in range(ROWS):
            fh.write(f"R{i:07d},{rng.random():.5f},{kinds[i]},{i % 100},"
                     f"n{i % 7},{'yes' if i % 3 else 'no'}\n")
    return str(path)


def _parse(path):
    schema = FeatureSchema.from_json(SCHEMA)
    with obs.capture() as rec:
        ds = Dataset.from_csv(path, schema)
    return ds, rec.spans()


@needs_native
def test_the_native_parse_encloses_exactly_its_five_steps(csv_path):
    _ds, spans = _parse(csv_path)
    (native,) = [s for s in spans if s.name == "dataset.parse.native"]
    end = native.t0 + native.dur
    inside = [s for s in spans if native.t0 <= s.t0 and s.t0 + s.dur <= end
              and s is not native]
    assert [s.name for s in inside] == list(PARSE_LEAVES)
    me = threading.get_ident()
    assert {s.tid for s in inside} == {native.tid} == {me}
    for a, b in zip(inside, inside[1:]):
        assert a.t0 + a.dur <= b.t0, (a.name, b.name)
    # what no leaf covers is the small glue between the steps
    assert sum(s.dur for s in inside) >= 0.8 * native.dur
    by_name = {s.name: s.attrs for s in spans}
    assert by_name["dataset.parse.count"]["rows"] == ROWS
    # float32 x, int n and the two categoricals' int32 codes
    assert by_name["dataset.parse.prefill"]["nbytes"] == 4 * 4 * ROWS
    # the threads asked of the library: 0, as many as the host has cores
    assert by_name["dataset.parse.fields"]["threads"] == 0
    ids = by_name["dataset.parse.ids"]
    assert ids["columns"] == 2
    # each token and its newline: "R0000000" and "nK"
    assert ids["nbytes"] == ROWS * (9 + 3)
    assert "columns" in by_name["dataset.parse.native"]
    for name in PARSE_LEAVES:
        assert set(obs.USAGE_ATTRS) <= set(by_name[name]), name


@needs_native
def test_a_block_parse_names_no_step(csv_path):
    schema = FeatureSchema.from_json(SCHEMA)
    with open(csv_path, "rb") as fh:
        data = fh.read()
    with obs.capture() as rec:
        ds = Dataset.from_csv(data, schema)
    assert len(ds) == ROWS and len(rec) == 0


@needs_native
def test_tracing_changes_no_byte_of_the_columns(csv_path):
    on, spans = _parse(csv_path)
    assert spans
    was = obs.set_enabled(False)
    try:
        off = Dataset.from_csv(csv_path, FeatureSchema.from_json(SCHEMA))
    finally:
        obs.set_enabled(was)
    assert len(on) == len(off) == ROWS
    for fld in SCHEMA["fields"]:
        a, b = on.column(fld["ordinal"]), off.column(fld["ordinal"])
        assert a.dtype == b.dtype, fld["name"]
        if a.dtype == object:
            assert a.tolist() == b.tolist(), fld["name"]
        else:
            assert a.tobytes() == b.tobytes(), fld["name"]


@needs_native
def test_the_parse_still_refuses_a_short_row_under_its_check():
    schema = FeatureSchema.from_json({"fields": [
        {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
        {"name": "kind", "ordinal": 1, "dataType": "categorical",
         "feature": True, "cardinality": ["a", "b"]}]})
    with obs.capture() as rec:
        with pytest.raises(ValueError, match="'' not in declared "
                           "cardinality of field 'kind'"):
            Dataset._from_native_data(b"r0,a\nr1\n", schema, ",", True,
                                      spanned=True)
    assert [s.name for s in rec.spans()][-2:] == ["dataset.parse.check",
                                                 "dataset.parse.native"]


def _landings(rec, name):
    return [s for s in rec.spans() if s.name == name]


@needs_native
def test_the_forest_job_follows_both_puts_to_their_landing(tmp_path):
    from test_tree import call_hangup_rows, forest_properties

    from avenir_tpu.runner import run_job

    train, schema_path, _codes, _y = call_hangup_rows(8_192, 5, tmp_path)
    with obs.capture() as rec:
        run_job("randomForest", forest_properties(schema_path), [train],
                str(tmp_path / "out"))
    # the capture's end waited for both landings
    landed = _landings(rec, "tree.put.landed")
    puts = [s for s in rec.spans() if s.name == "tree.put"]
    assert len(landed) == len(puts) == 2
    me = threading.get_ident()
    for put, got in zip(puts, sorted(landed, key=lambda s: s.t0)):
        assert got.tid != me and got.attrs["landed"] is True
        assert put.t0 <= got.t0 <= put.t0 + put.dur <= got.t0 + got.dur
    # labels and ten trees' leaf ids; then ten trees' weights
    assert sorted(s.attrs["nbytes"] for s in landed)[1] >= 10 * 8_192 * 4


@needs_native
def test_the_knn_index_and_labels_puts_are_followed_to_their_landing():
    from avenir_tpu.models.knn import NearestNeighborClassifier

    schema = FeatureSchema.from_json({"fields": [
        {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
        {"name": "a", "ordinal": 1, "dataType": "int", "feature": True,
         "min": 0, "max": 99},
        {"name": "c", "ordinal": 2, "dataType": "categorical",
         "cardinality": ["n", "y"]}]})
    text = "".join(f"r{i},{i % 100},{'y' if i % 2 else 'n'}\n"
                   for i in range(3_000))
    train = Dataset.from_csv(text.encode(), schema)
    with obs.capture() as rec:
        knn = NearestNeighborClassifier(train)
    landed = sorted(_landings(rec, "knn.index.put.landed"),
                    key=lambda s: s.t0)
    assert [s.attrs["landed"] for s in landed] == [True, True]
    assert landed[1].attrs["nbytes"] == knn.index.n_padded * 4
