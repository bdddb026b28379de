"""Native C++ CSV ingest vs the Python parser (parity + error contract)."""

import numpy as np
import pytest

from avenir_tpu.core.dataset import Dataset
from avenir_tpu.data import churn_schema, generate_churn
from avenir_tpu.native import native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="g++ unavailable; native ingest not built")


def parse_both(csv_text, schema, **kw):
    py = Dataset.from_csv(csv_text, schema, engine="python", **kw)
    nat = Dataset.from_csv(csv_text, schema, engine="native", **kw)
    return py, nat


def test_columns_match_python_parser():
    schema = churn_schema()
    csv_text = generate_churn(500, seed=9, as_csv=True)
    py, nat = parse_both(csv_text, schema)
    assert len(py) == len(nat) == 500
    for fld in schema.fields:
        a, b = py.column(fld.ordinal), nat.column(fld.ordinal)
        if fld.is_numeric:
            np.testing.assert_allclose(a, b, rtol=1e-6)
        else:
            assert list(a) == list(b), fld.name
    np.testing.assert_array_equal(py.labels(), nat.labels())
    codes_p, bins_p = py.feature_codes()
    codes_n, bins_n = nat.feature_codes()
    assert bins_p == bins_n
    np.testing.assert_array_equal(codes_p, codes_n)


def test_file_path_source(tmp_path):
    schema = churn_schema()
    p = str(tmp_path / "churn.csv")
    with open(p, "w") as fh:
        fh.write(generate_churn(100, seed=10, as_csv=True))
    nat = Dataset.from_csv(p, schema, engine="native")
    py = Dataset.from_csv(p, schema, engine="python")
    assert len(nat) == len(py) == 100
    assert list(nat.ids()) == list(py.ids())


def test_unknown_categorical_raises_with_field_name():
    schema = churn_schema()
    bad = "C1,low,med,low,good,50,open\nC2,BOGUS,med,low,good,50,open\n"
    with pytest.raises(ValueError, match="minUsed"):
        Dataset.from_csv(bad, schema, engine="native")
    with pytest.raises(ValueError, match="minUsed"):
        Dataset.from_csv(bad, schema, engine="python")


def test_short_row_raises():
    schema = churn_schema()
    bad = "C1,low,med\n"
    with pytest.raises(ValueError):
        Dataset.from_csv(bad, schema, engine="native")


def test_missing_numeric_is_nan():
    schema = churn_schema()
    csv_text = "C1,low,med,low,good,,open\n"
    nat = Dataset.from_csv(csv_text, schema, engine="native")
    assert np.isnan(nat.column(5)[0])


def test_blank_lines_and_crlf():
    schema = churn_schema()
    csv_text = "C1,low,med,low,good,50,open\r\n\n  \nC2,high,low,med,poor,10,closed\r\n"
    py, nat = parse_both(csv_text, schema)
    assert len(py) == len(nat) == 2
    assert list(nat.ids()) == ["C1", "C2"]


def test_gapped_ordinals():
    from avenir_tpu.data import call_hangup_schema, generate_call_hangup

    schema = call_hangup_schema()
    csv_text = generate_call_hangup(200, seed=11, as_csv=True)
    py, nat = parse_both(csv_text, schema)
    for fld in schema.fields:
        a, b = py.column(fld.ordinal), nat.column(fld.ordinal)
        if fld.is_numeric:
            np.testing.assert_allclose(a, b)
        else:
            assert list(a) == list(b)


def test_short_row_keeps_string_column_alignment():
    """A row shorter than a string ordinal must yield an empty token, not
    shift later rows' ids."""
    from avenir_tpu.core.schema import FeatureSchema
    schema = FeatureSchema.from_json({"fields": [
        {"name": "a", "ordinal": 0, "dataType": "double", "feature": True},
        {"name": "id", "ordinal": 2, "id": True, "dataType": "string"},
    ]})
    csv_text = "1,x,id1\n2,y\n3,z,id3\n"
    nat = Dataset.from_csv(csv_text, schema, engine="native")
    assert list(nat.ids()) == ["id1", "", "id3"]
    py = Dataset.from_csv(csv_text, schema, engine="python")
    assert list(py.ids()) == list(nat.ids())


def test_invalid_numeric_raises_like_python():
    from avenir_tpu.core.schema import FeatureSchema
    schema = FeatureSchema.from_json({"fields": [
        {"name": "x", "ordinal": 0, "dataType": "double", "feature": True},
        {"name": "y", "ordinal": 1, "dataType": "string"},
    ]})
    bad = "1.5,ok\nabc,ok\n"
    with pytest.raises(ValueError, match="float"):
        Dataset.from_csv(bad, schema, engine="native")
    with pytest.raises(ValueError):
        Dataset.from_csv(bad, schema, engine="python")


def test_native_required_contract_errors():
    schema = churn_schema()
    csv_text = generate_churn(5, seed=1, as_csv=True)
    with pytest.raises(ValueError, match="native"):
        Dataset.from_csv(csv_text, schema, engine="native", keep_raw=True)
    with pytest.raises(ValueError, match="native"):
        Dataset.from_csv(csv_text.splitlines(), schema, engine="native")


def test_auto_engine_used_by_default(tmp_path):
    """auto engine gives identical datasets to python on a normal file."""
    schema = churn_schema()
    csv_text = generate_churn(50, seed=12, as_csv=True)
    auto = Dataset.from_csv(csv_text, schema)
    py = Dataset.from_csv(csv_text, schema, engine="python")
    np.testing.assert_array_equal(auto.labels(), py.labels())


def test_multithreaded_parse_matches_sequential():
    """csv_parse_mt stripes the buffer at newline boundaries into disjoint
    global row ranges; outputs must be byte-identical to the sequential
    path on a buffer big enough to actually split (> 2 x 4MB stripes)."""
    from avenir_tpu.native.ingest import native_available, parse_csv_native

    if not native_available():
        pytest.skip("no native lib")
    rng = np.random.default_rng(3)
    n = 360_000                     # ~9MB with these fields
    cats = ["red", "green", "blue", "violet"]
    rows = []
    for i in range(n):
        rows.append(f"id{i},{rng.random()*100:.4f},{cats[i % 4]},"
                    f"{rng.integers(0, 1000)}")
    blob = ("\n".join(rows) + "\n").encode()
    assert len(blob) > 8 * (1 << 20)
    args = (",", [1, 3], [(2, cats)], [0])
    got_seq, cols_seq, _ = parse_csv_native(blob, *args, threads=1)
    got_mt, cols_mt, _ = parse_csv_native(blob, *args, threads=2)
    assert got_seq == got_mt == n
    for o in (1, 3):
        np.testing.assert_array_equal(cols_seq[o], cols_mt[o])
    np.testing.assert_array_equal(cols_seq[2], cols_mt[2])

    # an error deep in the second stripe reports the same global row
    bad_rows = rows[:]
    bad_rows[300_000] = "idX,not_a_number,red,7"
    bad_blob = ("\n".join(bad_rows) + "\n").encode()
    with pytest.raises(ValueError, match="not_a_number"):
        parse_csv_native(bad_blob, *args, threads=2)
    # unknown categorical in stripe 2
    bad_rows[300_000] = "idX,1.0,chartreuse,7"
    with pytest.raises(ValueError, match="chartreuse"):
        parse_csv_native(("\n".join(bad_rows) + "\n").encode(), *args,
                         threads=2)


def test_fuzz_native_matches_python_parser():
    """Differential fuzz: random CSVs with whitespace, blank lines, short
    rows, negatives, exponent floats, empty numeric fields and two
    categoricals of undeclared vocabulary must parse identically through
    the native and python engines."""
    from avenir_tpu.core.dataset import Dataset
    from avenir_tpu.core.schema import FeatureSchema

    schema = {"fields": [
        {"name": "id", "ordinal": 0, "dataType": "string", "id": True},
        {"name": "a", "ordinal": 1, "dataType": "double", "feature": True,
         "min": -100, "max": 100},
        {"name": "c", "ordinal": 2, "dataType": "categorical",
         "feature": True, "cardinality": ["x", "y", "z"]},
        {"name": "b", "ordinal": 3, "dataType": "double", "feature": True,
         "min": -100, "max": 100},
        {"name": "cls", "ordinal": 4, "dataType": "categorical",
         "class": True, "cardinality": ["neg", "pos"]},
        # no cardinality: discovered from the data, and grown by every
        # later trial parsed against the same schema object
        {"name": "u", "ordinal": 5, "dataType": "categorical",
         "feature": True},
        {"name": "v", "ordinal": 6, "dataType": "categorical",
         "feature": True},
    ]}
    nat_schema = FeatureSchema.from_json(schema)
    py_schema = FeatureSchema.from_json(schema)
    rng = np.random.default_rng(99)
    cats, classes = ["x", "y", "z"], ["neg", "pos"]
    pool = ["", "a", "b", "ab", "B", "\u00e9t\u00e9", "z z", "0", "-"]
    for trial in range(10):
        lines = []
        for i in range(rng.integers(5, 60)):
            kind = rng.random()
            a = f"{rng.normal()*50:.4f}"
            if kind < 0.1:
                a = f"{rng.normal():.3e}"           # exponent float
            elif kind < 0.2:
                a = ""                              # empty numeric -> NaN
            b = f"{int(rng.integers(-99, 99))}"
            pad = " " * int(rng.integers(0, 3))
            line = (f"{pad}r{i},{a},{pad}{cats[rng.integers(0,3)]}"
                    f"{pad},{b},{classes[rng.integers(0,2)]}")
            # the undeclared pair: drawn from a pool that widens with the
            # trials, padded, and cut off in some rows (a short row is
            # the empty token there, never an error)
            width = 2 + trial
            cut = rng.random()
            if cut > 0.1:
                line += f",{pad}{pool[rng.integers(0, width) % len(pool)]}"
            if cut > 0.2:
                line += f",{pool[rng.integers(0, width) % len(pool)]}{pad}"
            lines.append(line)
            if rng.random() < 0.15:
                lines.append("")                    # blank line
        text = "\n".join(lines) + "\n"
        nat = Dataset.from_csv(text, nat_schema, engine="native")
        py = Dataset.from_csv(text, py_schema, engine="python")
        assert len(nat) == len(py)
        for name in ("u", "v"):
            assert (nat_schema.field_by_name(name).cardinality
                    == py_schema.field_by_name(name).cardinality)
        for o in (1, 3):
            np.testing.assert_array_equal(np.isnan(nat.column(o)),
                                          np.isnan(py.column(o)))
            m = ~np.isnan(py.column(o))
            np.testing.assert_allclose(nat.column(o)[m], py.column(o)[m],
                                       rtol=1e-6)
        for o in (2, 4, 5, 6):
            assert nat.column(o).dtype == np.int32
            np.testing.assert_array_equal(nat.column(o), py.column(o))


# --------------------------------------------------------------------------
# categoricals whose vocabulary is discovered from the data
# --------------------------------------------------------------------------
def _discovering_schema(extra=()):
    from avenir_tpu.core.schema import FeatureSchema

    return FeatureSchema.from_json({"fields": [
        {"name": "x", "ordinal": 0, "dataType": "double", "feature": True},
        {"name": "status", "ordinal": 1, "dataType": "categorical"},
        *extra]})


_SECOND = ({"name": "grade", "ordinal": 3, "dataType": "categorical",
            "feature": True},)

#: name -> (further schema fields, the splits parsed one after another
#: against one schema object, the vocabulary of `status` after the last)
DISCOVERY_CASES = {
    "first_discovery": ((), ["1,pass\n2,fail\n3,pass\n"], ["fail", "pass"]),
    "later_split_extends": (
        (), ["1,pass\n2,fail\n", "3,pass\n", "4,zeta\n5,fail\n6,hold\n7,alpha\n"],
        ["fail", "pass", "alpha", "hold", "zeta"]),
    "short_rows": ((), ["1,pass\n2\n3,fail\n", "4\n"], ["", "fail", "pass"]),
    "short_row_in_a_later_split": (
        (), ["1,pass\n", "2\n3,fail\n"], ["pass", "", "fail"]),
    "crlf_and_padding": (
        (), ["1, pass \r\n2,\tfail\r\n\r\n  \n3,pass\t \r\n4,fail"],
        ["fail", "pass"]),
    "non_ascii": (
        (), ["1,r\u00e9ussi\n2,\u00e9chec\n3,z\n4,\u65e5\u672c\n5,r\u00e9ussi\n"],
        ["r\u00e9ussi", "z", "\u00e9chec", "\u65e5\u672c"]),
    "two_undeclared_fields": (
        _SECOND, ["1,pass,-,b\n2,fail,-,a\n3,pass,-\n", "4,hold,-,c\n"],
        ["fail", "pass", "hold"]),
    "zero_rows": ((), ["", "\n \n", "1,pass\n"], ["pass"]),
    "many_values": (
        (), ["".join(f"{i},v{(i * 7919) % 200_000}\n" for i in range(200_000)),
             "1,v7\n2,w\n"],
        sorted(f"v{i}" for i in range(200_000)) + ["w"]),
}


def _as_source(route, text, tmp_path, i):
    if route == "bytes":                # the block route (core/stream.py)
        return text.encode()
    if route == "path" and text:
        p = tmp_path / f"split{i}.csv"
        p.write_bytes(text.encode())
        return str(p)
    return text


@pytest.mark.parametrize("route", ["text", "bytes", "path"])
@pytest.mark.parametrize("case", sorted(DISCOVERY_CASES))
def test_discovered_categorical_matches_python_engine(case, route, tmp_path):
    """The contract of a discovered vocabulary, stated by the python engine
    and kept by the native one on the same bytes: int32 codes, sorted on
    first discovery, known codes stable and new values appended sorted in
    a later split, a short row the empty token, whatever the route."""
    extra, splits, want = DISCOVERY_CASES[case]
    nat_schema, py_schema = _discovering_schema(extra), _discovering_schema(extra)
    cat_fields = ["status"] + [f["name"] for f in extra]
    for i, text in enumerate(splits):
        nat = Dataset.from_csv(_as_source(route, text, tmp_path, i),
                               nat_schema, engine="native")
        py = Dataset.from_csv(text, py_schema, engine="python")
        assert len(nat) == len(py)
        for name in cat_fields:
            nf, pf = nat_schema.field_by_name(name), py_schema.field_by_name(name)
            assert nf.cardinality == pf.cardinality
            assert nf.discovered_cardinality and pf.discovered_cardinality
            col = nat.column(nf.ordinal)
            assert col.dtype == np.int32 and col.shape == (len(py),)
            np.testing.assert_array_equal(col, py.column(pf.ordinal))
    assert nat_schema.field_by_name("status").cardinality == want


@pytest.fixture(scope="module")
def striped_blob():
    """17 MB, so that up to four stripes of 4 MB are cut: an undeclared
    column of 23 values at the last ordinal, every 1,000th row short."""
    rows = []
    for i in range(800_000):
        rows.append(f"id{i:07d},{i % 977}.5" if i % 1000 == 999
                    else f"id{i:07d},{i % 977}.5,tok{(i * 31) % 23}")
    blob = ("\n".join(rows) + "\n").encode()
    assert len(blob) > 16 * (1 << 20)
    return blob


@pytest.mark.parametrize("threads", [1, 2, 3, 4, 0])
def test_distinct_column_is_the_same_from_one_thread_or_many_stripes(
        striped_blob, threads):
    from avenir_tpu.native.ingest import distinct_column_native

    tokens, rows = distinct_column_native(striped_blob, ",", 2, threads=threads)
    assert rows == 800_000
    assert len(tokens) == 24            # no value twice, whatever the stripe
    assert sorted(tokens) == sorted([""] + [f"tok{i}" for i in range(23)])


@pytest.mark.parametrize("threads", [1, 4])
def test_discovered_column_parses_the_same_striped(striped_blob, threads):
    from avenir_tpu.native.ingest import parse_csv_native

    vocab = sorted([""] + [f"tok{i}" for i in range(23)])
    n, cols, _ = parse_csv_native(striped_blob, ",", [1], [(2, vocab)], [],
                                  threads=threads)
    assert n == 800_000
    i = np.arange(n)
    want = np.array([vocab.index(f"tok{k}") for k in range(23)])[(i * 31) % 23]
    want[i % 1000 == 999] = vocab.index("")
    np.testing.assert_array_equal(cols[2], want)


def test_no_python_string_per_row_for_a_discovered_field(monkeypatch):
    """The discovered field goes to the parser as a categorical with its
    vocabulary complete, never as a string column: no thunk over its
    tokens comes back, and the dataset holds int32 codes only."""
    from avenir_tpu.native import ingest

    seen = {}
    real = ingest.parse_csv_native

    def spy(data, delim, numeric, categorical, strings, **kw):
        out = real(data, delim, numeric, categorical, strings, **kw)
        seen.update(categorical=categorical, strings=strings, lazy=out[2])
        return out

    monkeypatch.setattr(ingest, "parse_csv_native", spy)
    from avenir_tpu.core.schema import FeatureSchema
    schema = FeatureSchema.from_json({"fields": [
        {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
        {"name": "x", "ordinal": 1, "dataType": "double", "feature": True},
        {"name": "status", "ordinal": 2, "dataType": "categorical"},
    ]})
    for text in (b"a,1,pass\nb,2,fail\n", b"c,3,hold\n"):
        ds = Dataset.from_csv(text, schema, engine="native")
        assert seen["strings"] == [0]
        assert seen["categorical"] == [(2, schema.field_by_name("status").cardinality)]
        assert set(seen["lazy"]) == {0} and set(ds._lazy) == {0}
        assert ds.columns[2].dtype == np.int32
    assert schema.field_by_name("status").cardinality == ["fail", "pass", "hold"]


@pytest.mark.parametrize("engine", ["native", "python"])
@pytest.mark.parametrize("text,message", [
    ("C1,low,med,low,good,50,open\nC2,BOGUS,med,low,good,50,open\n",
     "value 'BOGUS' not in declared cardinality of field 'minUsed'"),
    ("C1,low,med\n",
     "value '' not in declared cardinality of field 'CSCalls'"),
], ids=["unknown_value", "short_row"])
def test_declared_categorical_still_raises(text, message, engine):
    """The declared route keeps its contract and its words: a short row is
    the empty token, which a declared vocabulary as a rule does not hold."""
    with pytest.raises(ValueError) as err:
        Dataset.from_csv(text, churn_schema(), engine=engine)
    assert str(err.value) == message


@pytest.mark.parametrize("engine", ["native", "python"])
def test_short_row_is_the_empty_token_where_the_vocabulary_declares_it(engine):
    from avenir_tpu.core.schema import FeatureSchema

    schema = FeatureSchema.from_json({"fields": [
        {"name": "x", "ordinal": 0, "dataType": "double", "feature": True},
        {"name": "c", "ordinal": 1, "dataType": "categorical",
         "feature": True, "cardinality": ["a", "", "b"]}]})
    ds = Dataset.from_csv("1,b\n2\n3,\n4,a\n", schema, engine=engine)
    np.testing.assert_array_equal(ds.column(1), [2, 1, 1, 0])
    assert schema.field_by_name("c").cardinality == ["a", "", "b"]


def test_vocabulary_value_with_a_nul_byte_is_refused():
    with pytest.raises(ValueError, match="NUL"):
        Dataset.from_csv(b"1,a\x00b\n2,c\n", _discovering_schema(),
                         engine="native")
