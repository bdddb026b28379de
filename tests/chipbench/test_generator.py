"""The vectorised e-learning writer: the bytes the schema's format and
the generator's distribution define, from any seed, in files disjoint
from each other."""

import json
import os

import numpy as np
import pytest

from bench_fixtures import ROOT

from chipbench import generate

with open(os.path.join(ROOT, "chipbench", "configs", "knn-elearn.json")) as _fh:
    CONFIG = json.load(_fh)
FIELDS = generate.feature_fields(CONFIG["schema"])
BENCH = CONFIG["generator"]
#: the tutorial's own generator as the repo records it
ELEARN = dict(BENCH, levels=[0.3, 0.7], sigma=0.12)
MAXES = [600, 200, 100, 28, 100, 100, 280, 180, 26]
CLASSES = ["fail", "pass"]


def elearn_rows(n, seed):
    """`tests/test_reference_configs.py::_elearn_rows` in the writer's
    order of draws (all classes first, then all levels) and with its ids:
    the row loop the vectorised writer is held to."""
    rng = np.random.default_rng(seed)
    passed = rng.random(n) < 0.5
    noise = rng.normal(0.0, 0.12, (n, 9))
    rows = []
    for i in range(n):
        frac = (0.7 if passed[i] else 0.3) + noise[i]
        vals = [int(np.clip(f * m, 0, m)) for f, m in zip(frac, MAXES)]
        rows.append(f"S{i:08d}," + ",".join(map(str, vals))
                    + "," + ("pass" if passed[i] else "fail"))
    return "\n".join(rows) + "\n"


def test_the_schema_is_the_sources_record():
    assert [f["max"] for f in FIELDS] == MAXES
    assert all(f["dataType"] == "int" and f["min"] == 0 for f in FIELDS)
    assert generate.decimals_of(FIELDS) == [0] * 9


@pytest.mark.parametrize("seed", [11, 5])
def test_bytes_equal_the_row_loop(seed):
    rows = generate.draw(np.random.default_rng(seed), 4096, ELEARN, FIELDS, 0)
    blob = generate.format_rows(rows, 0, 4096, CLASSES)
    assert blob == elearn_rows(4096, seed).encode()


def test_short_and_long_numbers_keep_their_format():
    rows = generate.Rows(np.array([[0, 5, 250, 9999, 10000, 100000],
                                   [600, 7, 26, 0, 1, 12]], np.int32),
                         np.array([1, 0], np.int8), 7, [0, 0, 0, 3, 3, 3])
    assert generate.format_rows(rows, 0, 2, ["fail", "passed"]) == \
        b"S00000007,0,5,250,9.999,10.000,100.000,passed\n" \
        b"S00000008,600,7,26,0.000,0.001,0.012,fail\n"
    assert np.array_equal(rows.values()[0], np.float32(
        [0.0, 5.0, 250.0, 9.999, 10.0, 100.0]))
    with pytest.raises(ValueError):
        generate.format_rows(generate.Rows(np.array([[-1]], np.int32),
                                           np.array([0], np.int8), 0, [0]),
                             0, 1, CLASSES)


def test_file_is_what_it_returns_and_chunks_join(tmp_path, monkeypatch):
    monkeypatch.setattr(generate, "CHUNK_ROWS", 1000)
    path = str(tmp_path / "t.csv")
    rows = generate.make_csv(path, 2**31 + 12345, 0, 4096, BENCH, FIELDS)
    with open(path) as fh:
        lines = fh.read().splitlines()
    assert len(lines) == 4096 and not os.path.exists(path + ".part")
    assert [ln.split(",")[0] for ln in lines] == rows.ids()
    assert len(set(rows.ids())) == 4096
    got = np.array([[float(v) for v in ln.split(",")[1:10]] for ln in lines],
                   np.float32)
    assert np.array_equal(got, rows.values())
    assert (got >= 0).all() and (got <= np.float32(MAXES)).all()
    assert [ln.split(",")[10] for ln in lines] == [CLASSES[c] for c in rows.y]
    # drawing without writing gives the same rows
    again = generate.make_csv(None, 2**31 + 12345, 0, 4096, BENCH, FIELDS)
    assert np.array_equal(again.q, rows.q) and np.array_equal(again.y, rows.y)


def test_two_seeds_differ_and_one_seed_repeats():
    a = generate.make_csv(None, 1, 0, 4096, BENCH, FIELDS)
    b = generate.make_csv(None, 2, 0, 4096, BENCH, FIELDS)
    c = generate.make_csv(None, 1, 0, 4096, BENCH, FIELDS)
    assert not np.array_equal(a.q, b.q)
    assert np.array_equal(a.q, c.q) and np.array_equal(a.y, c.y)


def test_a_cells_test_files_are_disjoint():
    start, stride = BENCH["test_id_start"], BENCH["test_id_stride"]
    assert start >= CONFIG["train_rows"]
    files = [generate.make_csv(None, 99, 1 + j, 1024, BENCH, FIELDS,
                               start + j * stride) for j in range(4)]
    ids = [i for f in files for i in f.ids()]
    assert len(set(ids)) == len(ids)
    train_ids = set(generate.make_csv(None, 99, 0, 2048, BENCH, FIELDS).ids())
    assert not train_ids & set(ids)
    rows = {tuple(r) for f in files for r in f.q}
    assert len(rows) == 4 * 1024            # no query appears twice


def test_classes_overlap_as_the_configuration_assumes():
    """At the configuration's levels a good share of 5-neighbourhoods is
    mixed, so a wrong neighbour shows in the output; at the tutorial's
    own, none is."""
    ranges = np.float32(MAXES)

    def mixed_share(gen):
        r = generate.make_csv(None, 3, 0, 4096, gen, FIELDS)
        x = r.values() / ranges
        d = np.abs(x[:256, None, :] - x[None, :, :]).sum(axis=2)
        near = np.argsort(d, axis=1)[:, 1:6]
        votes = r.y[near].sum(axis=1)
        return np.mean((votes > 0) & (votes < 5))
    assert mixed_share(BENCH) > 0.1
    assert mixed_share(ELEARN) < 0.02
    assert BENCH["sigma"] == ELEARN["sigma"]        # the one departure


@pytest.mark.parametrize("mix, want", [
    ({"files_per_seed": 3, "rows_per_file": 256}, [256, 256, 256]),
    ({"files_per_seed": 3, "rows_per_file": [256, 1024, 512]}, [256, 1024, 512]),
])
def test_a_mix_gives_each_file_its_rows(mix, want):
    assert generate.file_rows(mix) == want


def test_a_mix_that_lists_too_few_sizes_is_refused():
    with pytest.raises(ValueError):
        generate.file_rows({"files_per_seed": 3, "rows_per_file": [1, 2]})


def test_a_generator_is_found_by_its_kind(tmp_path):
    with pytest.raises(FileNotFoundError, match="generators/no_such"):
        generate.draw(np.random.default_rng(0), 4, dict(BENCH, kind="no_such"),
                      FIELDS, 0)
    os.makedirs(tmp_path / "generators")
    (tmp_path / "generators" / "ones.py").write_text(
        "import numpy as np\n"
        "def draw(rng, n, gen, fields):\n"
        "    return np.ones((n, len(fields)), np.int32), np.zeros(n, np.int8)\n")
    rows = generate.draw(np.random.default_rng(0), 4, dict(BENCH, kind="ones"),
                         FIELDS, 0, str(tmp_path))
    assert rows.q.shape == (4, 9) and rows.q.min() == 1


def test_a_negative_seed_is_refused():
    with pytest.raises(ValueError):
        generate.seed_for(-1, 0)
