"""The random-forest deployment `rf-hangup` and its cell `rf-hangup.rebuild`
on the CPU at 16,384 rows: the program's job through `run_from_cli`
against the plain reference (`forest_reference.py`) through the check a
chip run uses (`checks/forest_paths.py`); the control, which has to fail
it; the harness driven over a broken timed path, which has to fail it too;
the generator's and the input module's bytes; every pin of `pins.py`; and
the new readers over a recorded forest job (data/events_forest_job.json)."""

import hashlib
import json
import os
import shutil
from unittest import mock

import numpy as np
import pytest

import pins
from bench_fixtures import CPU_DEVICE, ROOT, compile_cache_off, small_copy

from chipbench import compare, generate, manifest, run
from chipbench import forest_reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "rf-hangup.rebuild"
ROWS = 16_384
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    DOC = json.load(_fh)
FOREST_METRICS = [m["name"] for m in DOC["per_layer"]
                  if m["name"].startswith("forest_")]
EXACT = ("model_bad", "unstable_bytes", "rows_unrouted", "population_gap",
         "split_not_best")


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The small copy at 16,384 train rows, the persistent compile cache
    off while these tests run."""
    tmp = tmp_path_factory.mktemp("forest")
    with compile_cache_off():
        yield small_copy(str(tmp), train_rows=ROWS), str(tmp)


def drive(bench, seed, entry=run.default_entry, name="work"):
    man, tmp = bench
    return run.run_cell(man.cell(CELL), man, seed, 0.0, False,
                        dict(CPU_DEVICE), entry=entry,
                        work_root=os.path.join(tmp, name))


def checked(result):
    return {k: v["value"] for k, v in result["checked"].items() if k != "_seen"}


# ------------------------------------------------------------ the reference
@pytest.mark.parametrize("name", ["forest_reference.py",
                                  "checks/forest_paths.py"])
def test_reference_and_check_import_nothing_of_the_program(name):
    with open(os.path.join(ROOT, "chipbench", name)) as fh:
        text = fh.read()
    assert "import avenir" not in text and "from avenir" not in text


def test_candidate_splits_are_the_eighteen_of_the_source():
    """call_hangup.json: 1 + 7 + 1 + 9 candidate splits of two segments,
    the same the program enumerates from the same schema, in its order."""
    from avenir_tpu.core.schema import FeatureSchema
    from avenir_tpu.models.tree import enumerate_splits

    schema = manifest.Manifest().cell(CELL).config["schema"]
    splits = ref.candidate_splits(schema)
    by_attr = {}
    for s in splits:
        by_attr[s["attribute"]] = by_attr.get(s["attribute"], 0) + 1
    assert by_attr == {1: 1, 3: 7, 4: 1, 5: 9} and len(splits) == 18
    assert all(s["segments"] == 2 for s in splits)
    full = json.loads(json.dumps(schema))
    full["fields"][-1]["cardinality"] = ["F", "T"]
    theirs = enumerate_splits(FeatureSchema.from_json(full))
    assert [[ref.predicate_key(p.to_json()) for p in s.predicates]
            for s in theirs] == [s["predicates"] for s in splits]


def test_compacted_rows_give_the_forest_all_rows_give():
    """Every number of the reference is a sum over rows: 2 x 4 x 2 x 601
    x 2 weighted rows stand for all of them, exactly."""
    from chipbench.checks import forest_paths

    man = manifest.Manifest()
    cfg = man.cell(CELL).config
    fields = ref.feature_fields(cfg["schema"])
    codes, y = man.module("generators", "call_hangup").draw(
        generate.seed_for(3, 0), 4096, cfg["generator"], fields)
    sem = dict(ref.job_semantics(cfg["properties"]), trees=3)
    weights = ref.bootstrap_weights(0, len(y), 3, "withReplace")
    table = forest_paths.Table(cfg["schema"], codes, y, 2, sem, weights)
    assert table.codes.shape == (19_232, 4) and table.rows.sum() == 4096
    np.testing.assert_array_equal(table.weights.sum(axis=1), weights.sum(axis=1))
    plain = ref.grow_forest(codes, y, weights, cfg["schema"], 2, sem)
    small = ref.grow_forest(table.codes, table.y, table.weights, cfg["schema"],
                            2, sem)
    assert json.dumps(plain, default=str) == json.dumps(small, default=str)


# --------------------------------------------------- the cell, end to end
@pytest.mark.parametrize("seed", [2**31 + 28, 7])
def test_program_agrees_with_the_reference(bench, seed):
    res = drive(bench, seed)
    assert res["correct"] is True, res["checked"]
    assert res["attempted"] == 1 and res["failed"] == 0
    got = checked(res)
    assert all(got[k] == 0 for k in EXACT)
    assert got["class_share_gap_max"] < 1e-12      # the file prints float64
    seen = res["checked"]["_seen"]
    assert seen["forests_compared"] == 1 and seen["paths"] >= 30
    assert set(res["metrics"]) == {"job_s", "setup_s"}
    work = os.path.join(bench[1], "work", CELL)
    assert sorted(os.listdir(work)) == ["job.properties", "out_000",
                                        "out_warmup", "schema.json",
                                        "train.csv"]
    assert sorted(os.listdir(os.path.join(work, "out_000"))) == [
        f"tree-{t:03d}.json" for t in range(10)]
    with open(os.path.join(work, "train.csv")) as fh:
        row = fh.readline().rstrip("\n").split(",")
    assert len(row) == 7 and len(row[0]) == 10 and row[0].isdigit()
    assert row[1] in ("business", "residence") and row[2] in (
        "408", "607", "336", "646", "206") and row[4] in ("AM", "PM")
    assert row[5].isdigit() and row[6] in "FT"


def test_sizes_are_semantic_and_count_the_passes(bench):
    man, _tmp = bench
    cell = man.cell(CELL)
    check = man.module("checks", "forest_paths")

    class Seen:
        y, classes, levels_seen = np.zeros(ROWS), ["F", "T"], 2

    assert check.sizes(cell, Seen) == {
        "n": ROWS, "trees": 10, "splits": 18, "segments": 2, "classes": 2,
        "levels": 3}


# ------------------------------------------------------------- the control
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(bench, seed):
    """Counts accumulated in float32 in row order and shares kept in
    bfloat16: at a test's size every cell stays far under 2^24, so the
    counts are still right and the shares' eight bits fail
    `class_share_gap_max`; with float32 shares the same control passes."""
    man, _tmp = bench
    cell = man.cell(CELL)
    check = man.module("checks", "forest_paths")
    limits = cell.config["check"]["limits"]
    low = check.control_numbers(cell, seed, 1, "bfloat16")
    good, _ = compare.verdict(low, limits)
    assert not good
    assert low["class_share_gap_max"] > 100 * limits["class_share_gap_max"]
    assert all(low[k] == 0 for k in EXACT)
    assert set(check.CONTROL_FAILS) == {"population_gap", "class_share_gap_max"}
    same, _ = compare.verdict(check.control_numbers(cell, seed, 1, "float32"),
                              limits)
    assert same


def test_a_float32_counter_stops_at_two_to_the_24th():
    """What fails `population_gap` at the cell's size, where a root cell
    holds some 25M: 16,777,216 + 1 = 16,777,216."""
    from chipbench.checks import forest_paths

    ones = np.ones(2**24 + 1000, np.int64)
    assert forest_paths.float32_in_row_order(ones) == 2.0**24
    assert forest_paths.float32_in_row_order(ones[:1000]) == 1000.0
    assert forest_paths.float32_in_row_order(ones[:0]) == 0.0


# ------------------------------------------------- a broken timed path
def a_tree_file_missing(argv):
    run.default_entry(argv)
    os.remove(os.path.join(argv[-1], "tree-007.json"))


def half_the_rows(argv):
    with open(argv[3]) as fh:
        lines = fh.readlines()
    with open(argv[3] + ".half", "w") as fh:
        fh.writelines(lines[:len(lines) // 2])
    run.default_entry(argv[:3] + [argv[3] + ".half"] + argv[4:])


def another_seeds_sample(argv):
    from avenir_tpu.models.tree import RandomForestBuilder

    sample = RandomForestBuilder._sample

    def shifted(self, n):
        self.seed += 1
        try:
            return sample(self, n)
        finally:
            self.seed -= 1

    with mock.patch.object(RandomForestBuilder, "_sample", shifted):
        run.default_entry(argv)


def first_split_of_each_attribute(argv):
    """Each drawn attribute offers only its first candidate split: the hold
    time then splits at 60, which is not its best."""
    from avenir_tpu.models.tree import DecisionTreeBuilder

    allowed = DecisionTreeBuilder._allowed_splits

    def first_only(self, leaf):
        seen, out = set(), []
        for i in allowed(self, leaf):
            if self.splits[i].attribute not in seen:
                seen.add(self.splits[i].attribute)
                out.append(i)
        return out

    with mock.patch.object(DecisionTreeBuilder, "_allowed_splits", first_only):
        run.default_entry(argv)


@pytest.mark.parametrize("fault, fails, holds", [
    (a_tree_file_missing, ["model_bad"],
     ["rows_unrouted", "population_gap", "split_not_best", "unstable_bytes"]),
    (half_the_rows, ["population_gap"],
     ["model_bad", "rows_unrouted", "unstable_bytes"]),
    (another_seeds_sample, ["population_gap"],
     ["model_bad", "rows_unrouted", "unstable_bytes"]),
    (first_split_of_each_attribute, ["split_not_best"],
     ["model_bad", "rows_unrouted", "population_gap", "unstable_bytes"])],
    ids=lambda v: getattr(v, "__name__", None))
def test_a_broken_timed_path_comes_out_not_correct(bench, fault, fails, holds):
    res = drive(bench, 11, entry=fault, name="work_" + fault.__name__)
    got = checked(res)
    assert res["correct"] is False
    assert all(got[k] > 0 for k in fails), got
    assert all(got[k] == 0 for k in holds), got


def test_unstable_output_is_seen(bench):
    calls = []

    def entry(argv):
        run.default_entry(argv)
        calls.append(argv[-1])
        if len(calls) == 2:
            with open(os.path.join(argv[-1], "tree-000.json"), "a") as fh:
                fh.write(" ")

    res = drive(bench, 12, entry=entry, name="work_unstable")
    assert res["correct"] is False and checked(res)["unstable_bytes"] >= 1


# ------------------------------------------------------ bytes, by hash
DRAW_SHA256 = "fd6f4be1c93a353c6d3918e0936f71223635cb3b825371e4be7b04b8f6ca67ba"
FILES_SHA256 = {
    "train.csv": "642210fe5b8bd115e5727777b6b8f5e3915eae50b35ea1d916374788723ecc2a",
    "schema.json": "a5887b60ac4c416936823c45d03b48131b498810e09315ddac1f05eb1ba0a95a",
    "job.properties": "23472f9c0713d4426119da50320bd75fae105d7fa7732c5da0d3090c0246a777",
}


def test_the_generator_and_the_input_module_write_these_bytes(bench, tmp_path):
    """For a fixed seed at 16,384 rows: what the generator draws, and the
    train file, the schema and the properties the input module writes
    (the work directory's path in job.properties read as `{work}`)."""
    man, _tmp = bench
    cell = man.cell(CELL)
    fields = ref.feature_fields(cell.config["schema"])
    codes, y = man.module("generators", "call_hangup").draw(
        generate.seed_for(2**31 + 28, 0, 0), ROWS, cell.config["generator"],
        fields)
    assert codes.dtype == np.int16 and y.dtype == np.int8
    assert hashlib.sha256(codes.tobytes() + y.tobytes()).hexdigest() == DRAW_SHA256
    work = str(tmp_path)
    inputs = man.inputs(cell.config).Inputs(cell, 2**31 + 28, work)
    np.testing.assert_array_equal(inputs.codes, codes)
    np.testing.assert_array_equal(inputs.y, y)
    got = {}
    for f in os.listdir(work):
        with open(os.path.join(work, f), "rb") as fh:
            got[f] = hashlib.sha256(
                fh.read().replace(work.encode(), b"{work}")).hexdigest()
    assert got == FILES_SHA256
    at = lambda name: os.path.join(work, name)  # noqa: E731
    assert inputs.n_files == 1 and inputs.out_suffix == ""
    assert inputs.argv(0, "OUT") == inputs.warmup_argv("OUT") == [
        "randomForest", "--conf", at("job.properties"), at("train.csv"), "OUT"]


def test_chunks_join_and_threads_keep_the_order(bench, tmp_path, monkeypatch):
    """The file is the same whatever the chunk size's threads did: rows in
    chunk order, each chunk a generator of its own."""
    man, _tmp = bench
    cell = man.cell(CELL)
    monkeypatch.setattr(generate, "CHUNK_ROWS", 5000)
    inputs = man.inputs(cell.config).Inputs(cell, 5, str(tmp_path))
    with open(inputs.train_path) as fh:
        rows = [ln.rstrip("\n").split(",") for ln in fh]
    assert len(rows) == ROWS
    fields = ref.feature_fields(cell.config["schema"])
    names = [f.get("cardinality") for f in fields]
    for at in (0, 4999, 5000, ROWS - 1):
        want = [names[j][c] if names[j] else str(c)
                for j, c in enumerate(inputs.codes[at])]
        assert [rows[at][1]] + rows[at][3:6] == want
        assert rows[at][6] == "FT"[inputs.y[at]]


# --------------------------------------------------------------- the pins
def test_the_deployment_through_every_pin():
    man = manifest.Manifest()
    cfg = next(c for c in DOC["configs"] if c["name"] == "rf-hangup")
    entry = next(w for w in DOC["workloads"] if w["name"] == CELL)
    pins.hold_configuration(man, cfg)
    pins.hold_environment(man, cfg, ROOT)
    pins.hold_cell(man, entry)
    pins.hold_four_chip_share(DOC)
    for m in DOC["per_layer"]:
        if CELL in m.get("workloads", []):
            pins.hold_per_layer_metric(man, m)
    pins.hold_the_first_sixteen(DOC)
    assert cfg["reduced"] == ["train_rows"] and entry["chips"] == 1
    doc = pins.config_doc(man, cfg)
    assert "environment" not in doc and doc["inputs_kind"] == "one_csv_bulk"
    assert doc["job"] == "randomForest"
    assert set(doc["assumed"]) >= {"train_rows", "generator", "schema",
                                   "num_trees", "max_depth", "sampling_rule"}
    assert doc["properties"] == {
        "dtb.feature.schema.file.path": "{schema}",
        "dtb.split.algorithm": "giniIndex",
        "dtb.path.stopping.strategy": "maxDepth",
        "dtb.max.depth.limit": "2",
        "dtb.sub.sampling.strategy": "withReplace",
        "dtb.split.attribute.selection.strategy": "randomNotUsedYet",
        "dtb.num.trees": "10"}
    assert doc["check"]["limits"] == {
        "model_bad": 0, "unstable_bytes": 0, "rows_unrouted": 0,
        "population_gap": 0, "class_share_gap_max": 1e-06, "split_not_best": 0}
    # upstream call_hangup.json: seven columns a row, the area code at
    # ordinal 2 not declared, the class without a cardinality
    fields = doc["schema"]["fields"]
    assert [f["ordinal"] for f in fields] == [0, 1, 3, 4, 5, 6]
    assert list(doc["generator"]["unread"]) == ["2"]
    assert "cardinality" not in fields[-1] and not fields[-1].get("feature")
    assert doc["train_rows"] % 2**20 == 0
    mix = man.cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["files_per_seed"]) == ("closed", 1, 1)
    assert mix["rows_per_file"] == doc["train_rows"]
    # a row on the device: 18 B of segment ids, 4 B of label, and a tree's
    # leaf id and bootstrap count at 4 B each: a quarter of 16 GiB or more
    live = doc["train_rows"] * (18 + 4 + 8 * 10)
    assert live >= 0.27 * 2**34


def test_the_cells_per_layer_metrics_hold_these_thirteen_in_order():
    """The five it shares and its own eight (PR 38 added the eighth,
    `forest_segments_device_ms_per_job`), in their order among whatever a
    later PR adds, and no kNN cell reports one (`pins.hold_forest_names`)."""
    pins.hold_forest_names(manifest.Manifest())
    assert FOREST_METRICS[:8] == pins.FOREST_NAMES


# ------------------------------------------- the readers, a recorded job
@pytest.fixture()
def ctx():
    with open(os.path.join(HERE, "data", "events_forest_job.json")) as fh:
        recorded = json.load(fh)
    ann = recorded["annotations"][0]
    return {"spans": recorded["spans"], "devices": recorded["devices"],
            "window_ns": (ann[1], ann[1] + ann[2]), "jobs": 1, "notes": {},
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "sizes": {"n": 1_000_000, "trees": 10, "splits": 18,
                      "segments": 2, "classes": 2, "levels": 3}}


def read(ctx, name):
    man = manifest.Manifest()
    spec = man.metric(name)
    return man.reader(spec["reader"])(ctx, spec["params"])


#: least time: 3 passes x (1e6 x 19 + 2 x 1e6 x 10) B at 819 GB/s, over 14.5 ms
ROOFLINE = 100.0 * (3 * 39e6 / 819e9) / 14.5e-3
EXPECTED = {
    "forest_level_ms_per_job": 14.5,           # 4 + 4.5 + 5 and two of 0.5
    "forest_level_roofline": ROOFLINE,
    "forest_sample_ms_per_job": 30.0,
    "forest_segments_ms_per_job": 8.0,
    "forest_select_ms_per_job": 3.0,
    "forest_unspanned_ms_per_job": 10.6,       # 0.8 + 5 + 4.8 ms of the job
    "forest_idle_named_share": 100.0 * (85.5 - 10.6 - 0.4) / 85.5,
    "forest_segments_device_ms_per_job": 1.3,  # jit__segment_lines (PR 38)
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_forest_metric_reads_the_recorded_job(ctx, name):
    assert sorted(EXPECTED) == sorted(pins.FOREST_NAMES)
    assert read(ctx, name) == pytest.approx(EXPECTED[name], rel=1e-6)


def test_the_roofline_is_memory_bound_and_knows_only_semantic_sizes(ctx):
    roofline = manifest.Manifest().module("readers", "forest_roofline")
    ops, nbytes = roofline.level_work(1_000_000, 10, 18, 3)
    assert (ops, nbytes) == (3 * 1e6 * 10 * 18, 3 * (1e6 * 19 + 2e7))
    assert read(ctx, "forest_level_roofline") == pytest.approx(ROOFLINE)
    assert ctx["notes"]["forest_level_roofline_bound"] == "memory"
    ctx["sizes"].update(row_block=131072, dtype="int8", lanes=128)
    assert read(ctx, "forest_level_roofline") == pytest.approx(ROOFLINE)


@pytest.mark.parametrize("name", ["forest_level_roofline",
                                  "forest_level_ms_per_job"])
def test_where_no_level_program_ran_nothing_is_returned_never_zero(ctx, name):
    dev = ctx["devices"]["/device:TPU:0"]
    dev["modules"] = [m for m in dev["modules"]
                      if "_level_histogram" not in m[0]
                      and "_advance_leaves" not in m[0]]
    assert read(ctx, name) is None
    ctx["devices"] = {}
    assert read(ctx, name) is None


@pytest.mark.parametrize("name", ["forest_unspanned_ms_per_job",
                                  "forest_idle_named_share"])
def test_a_program_without_the_spans_leaves_the_metric_out(ctx, name):
    """The parent commit has no `tree.*` span; its `job.cli` alone still
    joins the clocks, and a program with no `job.cli` returns nothing."""
    ctx["spans"] = [s for s in ctx["spans"]
                    if not s["name"].startswith(("tree.", "forest."))]
    assert read(ctx, name) is not None
    ctx["spans"] = []
    assert read(ctx, name) is None


def test_the_segment_program_is_read_apart_from_the_level_programs(ctx):
    """`forest_segments_device_ms_per_job` (PR 38) reads `_segment_lines`
    alone; the level programs' metrics read what they read without it;
    where no segment program ran nothing is returned, never 0."""
    assert read(ctx, "forest_segments_device_ms_per_job") == \
        pytest.approx(1.3, rel=1e-9)
    dev = ctx["devices"]["/device:TPU:0"]
    dev["modules"] = [m for m in dev["modules"] if "_segment_lines" not in m[0]]
    assert read(ctx, "forest_segments_device_ms_per_job") is None
    assert read(ctx, "forest_level_ms_per_job") == pytest.approx(14.5)
    entry = next(m for m in DOC["per_layer"]
                 if m["name"] == "forest_segments_device_ms_per_job")
    assert DOC["per_layer"].index(entry) == 43   # after PR 37's 43 entries
    assert (entry["source"], entry["layer"], entry["moves"]) == (
        "device_trace", "Device kernels", "job_s")


def test_both_forest_lists_name_the_same_leaves_and_no_parent():
    man = manifest.Manifest()
    kids = man.metric("forest_unspanned_ms_per_job")["params"]["children"]
    assert kids == man.metric("forest_idle_named_share")["params"]["leaves"]
    assert len(set(kids)) == len(kids)
    for parent in ("job.cli", "job.run", "dataset.parse", "tree.fit"):
        assert parent not in kids
    assert {"forest.sample", "tree.segments", "tree.put", "tree.level.fetch",
            "tree.write"} <= set(kids)


def test_the_schema_and_properties_writer_is_one_function(tmp_path):
    from chipbench import jobfiles

    cfg = {"schema": {"fields": []}, "properties": {"a.path": "{schema}", "b": "1"}}
    schema, props = jobfiles.write_schema_and_properties(cfg, str(tmp_path))
    with open(props) as fh:
        assert fh.read() == f"a.path={schema}\nb=1\n"
    with open(schema) as fh:
        assert json.load(fh) == cfg["schema"]
    shutil.rmtree(str(tmp_path), ignore_errors=True)
