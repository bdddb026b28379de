"""BENCHMARK.json against the driver's rules a test can check here, and
the harness's promise that a cell, a traffic mix and a per-layer metric
are added as files plus one entry."""

import json
import os
import shutil

import pytest

import pins
from bench_fixtures import (CPU_DEVICE, ROOT, compile_cache_off, file_stamps,
                            small_copy)
from pins import NAME, PATH, UNIT, VMEM, UNTIL, one_line

from chipbench import compare, generate, manifest, run

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    DOC = json.load(_fh)
CELLS = [w["name"] for w in DOC["workloads"]]
LAYER_METRICS = [m["name"] for m in DOC["per_layer"]]
E2E = {m["name"]: m for m in DOC["end_to_end"]}


def test_top_level_keys_and_command():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"][-1] == "chipbench/run.py"
    assert all(one_line(w) for w in DOC["command"]) and len(DOC["command"]) <= 32
    assert DOC["paths"] == ["chipbench", "tests/chipbench"]
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in DOC["paths"])
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    total = runs * (DOC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("cfg", DOC["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(cfg):
    """What every configuration of any job family is held to, and under
    the e-learning deployment's names the shapes it fixes (`pins.py`)."""
    pins.hold_configuration(manifest.Manifest(), cfg)


def test_configurations_do_not_share_a_file_or_a_source():
    assert len({c["file"] for c in DOC["configs"]}) == len(DOC["configs"])
    assert len({c["source"] for c in DOC["configs"]}) == len(DOC["configs"])
    assert len({c["name"] for c in DOC["configs"]}) == len(DOC["configs"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    entry = next(w for w in DOC["workloads"] if w["name"] == cell)
    pins.hold_cell(manifest.Manifest(), entry)
    assert entry["chips"] in (1, 4)  # since PR 36 a path of the job does cross chips


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips():
    pins.hold_four_chip_share(DOC)
    four = dict(DOC["workloads"][0], chips=4)
    pins.hold_four_chip_share({"workloads": [four]})            # one always may
    pins.hold_four_chip_share({"workloads": [four] * 2 + DOC["workloads"] * 3})
    with pytest.raises(AssertionError):
        pins.hold_four_chip_share({"workloads": [four] * 2 + DOC["workloads"]})


def test_the_closed_loop_drives_one_client_and_says_so():
    """`clients` is the loop's to hold, not every cell's: another loop may
    drive several."""
    closed = manifest.Manifest().module("loops", "closed")
    jobs = []
    assert closed.drive(jobs.append, 0.0, {"clients": 1}) == 1 and jobs == [0]
    with pytest.raises(ValueError, match="one client"):
        closed.drive(jobs.append, 0.0, {"clients": 2})
    assert jobs == [0]


def test_no_pair_of_configuration_and_traffic_twice():
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(set(CELLS)) == len(CELLS)
    assert 1 <= len(CELLS) <= 24


@pytest.mark.parametrize("name", sorted(E2E))
def test_end_to_end_metric(name):
    m = E2E[name]
    assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert set(E2E) == {"job_s", "setup_s"}


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_per_layer_metric_has_its_file_reader_and_cells(name):
    m = next(x for x in DOC["per_layer"] if x["name"] == name)
    pins.hold_per_layer_metric(manifest.Manifest(), m)


def test_layers_are_spelled_one_way():
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        perf = fh.read()
    for layer in {m["layer"] for m in DOC["per_layer"]}:
        assert f"| {layer} |" in perf, f"PERF.md section 3 lacks layer {layer!r}"


def test_files_under_paths_are_named_from_a_names_characters():
    for base in DOC["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel


# ------------------------------------------------- the runtime's one setting
@pytest.mark.parametrize("cfg", DOC["configs"], ids=lambda c: c["name"])
def test_a_configuration_states_only_the_one_runtime_flag(cfg):
    """None, or what `run.ALLOWED_ENVIRONMENT` allows; the e-learning
    deployment its one flag (`pins.hold_environment`)."""
    pins.hold_environment(manifest.Manifest(), cfg, ROOT)


@pytest.mark.parametrize("key, val", [
    ("XLA_FLAGS", "--xla_force_host_platform_device_count=4"),
    ("LIBTPU_INIT_ARGS", "--xla_tpu_enable_something=true"),
    ("LIBTPU_INIT_ARGS", VMEM + " --xla_more=1"),
    ("JAX_PLATFORMS", "cpu")])
def test_no_other_setting_of_the_runtime_may_be_stated(key, val):
    env = {}
    with pytest.raises(ValueError, match="may not set"):
        run.apply_environment({"environment": {key: val}}, ROOT, env)
    assert env == {}


def test_the_flag_goes_once_the_kernel_states_its_own_limit(tmp_path):
    """The flag stands in for a fault of the program (PERF.md, Open
    questions): once `knn_topk_pallas` passes `vmem_limit_bytes` the
    harness withholds it, so it cannot outlive the fault."""
    cfg = {"environment": {"LIBTPU_INIT_ARGS": VMEM}, "environment_until": UNTIL}
    ops = tmp_path / "avenir_tpu" / "ops"
    ops.mkdir(parents=True)
    src = ops / "pallas_knn.py"
    src.write_text(
        "def knn_classify_lanes():\n"
        "    return call(params(vmem_limit_bytes=24))\n"
        "def knn_topk_pallas():\n    return call(interpret=False)\n")
    env = {}
    assert run.apply_environment(cfg, str(tmp_path), env) == cfg["environment"]
    src.write_text(
        "def knn_topk_pallas():\n"
        "    return call(compiler_params=P(vmem_limit_bytes=32 << 20))\n")
    env = {}
    assert run.apply_environment(cfg, str(tmp_path), env) == {} and env == {}
    assert not run.source_has_keyword(str(tmp_path / "none.py"), "f", "k")


# ------------------------------------------------------- adding without edits
def test_a_cell_a_mix_and_a_metric_are_added_as_files_plus_one_entry(tmp_path):
    """What a later PR does: new files, one new entry each, no file that
    was there edited. The harness finds them by name and runs the new
    cell: another k, test files of three sizes, a loop, a generator and a
    check of its own."""
    man = small_copy(str(tmp_path))
    bench = man.bench_dir
    before = file_stamps(bench)

    def add(folder, name, content):
        with open(os.path.join(bench, folder, name), "w") as fh:
            fh.write(content if isinstance(content, str) else json.dumps(content))

    with open(man.config_file("knn-elearn")) as fh:
        cfg = json.load(fh)
    cfg.update(name="knn-elearn-k9", source="a throw-away deployment")
    cfg["properties"]["nen.top.match.count"] = "9"
    cfg["generator"]["kind"] = "two_level_again"
    cfg["reference"]["kind"] = "knn_again"
    add("configs", "knn-elearn-k9.json", cfg)
    add("traffic", "trickle.json", {"loop": "twice", "clients": 1,
                                    "files_per_seed": 3,
                                    "rows_per_file": [256, 512, 256]})
    add("generators", "two_level_again.py",
        "from chipbench.generators.two_level import draw  # noqa: F401\n")
    add("checks", "knn_again.py",
        "from chipbench.checks.knn_classify import *  # noqa: F401,F403\n")
    add("loops", "twice.py",
        "def drive(one_job, seconds, mix):\n"
        "    one_job(0)\n    one_job(1)\n    return 2\n")
    add("metrics", "read_ms_per_job.json",
        {"name": "read_ms_per_job", "unit": "ms", "reader": "span_sum",
         "params": {"span": "stream.read"}})
    add("metrics", "jobs_traced.json",
        {"name": "jobs_traced", "unit": "count", "reader": "jobs_traced",
         "params": {}})
    add("readers", "jobs_traced.py",
        "def read(ctx, params):\n    return float(ctx['jobs'])\n")
    doc = dict(man.doc)
    doc["configs"] = doc["configs"] + [{
        "name": "knn-elearn-k9", "source": cfg["source"],
        "file": "chipbench/configs/knn-elearn-k9.json", "reduced": [],
        "why": "throw-away"}]
    doc["workloads"] = doc["workloads"] + [{
        "name": "knn-elearn-k9.trickle", "config": "knn-elearn-k9",
        "traffic": "trickle", "chips": 1, "why": "throw-away"}]
    layer = doc["per_layer"][0]["layer"]
    doc["per_layer"] = doc["per_layer"] + [
        {"name": "read_ms_per_job", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": layer, "moves": "job_s",
         "workloads": ["knn-elearn-k9.trickle"]},
        {"name": "jobs_traced", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": layer, "moves": "job_s",
         "workloads": ["knn-elearn-k9.trickle"]}]
    with open(os.path.join(man.root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)

    again = manifest.Manifest(man.root, bench)
    cell = again.cell("knn-elearn-k9.trickle")
    assert cell.config["properties"]["nen.top.match.count"] == "9"
    assert generate.file_rows(cell.traffic) == [256, 512, 256]
    assert [m["name"] for m in cell.per_layer] == ["read_ms_per_job", "jobs_traced"]
    ctx = {"jobs": 1, "spans": [{"name": "stream.read", "t0": 0.0, "dur": 0.25}]}
    values = {m["name"]: again.reader(again.metric(m["name"])["reader"])(
        ctx, again.metric(m["name"])["params"]) for m in cell.per_layer}
    assert values == {"read_ms_per_job": 250.0, "jobs_traced": 1.0}
    # the cells that were there neither see the new metrics nor changed
    assert "jobs_traced" not in [m["name"] for m in again.cell(CELLS[0]).per_layer]

    # and the harness runs it, on the CPU: two jobs of 256 and 512 rows
    # with nine neighbours each, held to the reference for k = 9
    with compile_cache_off():
        res = run.run_cell(cell, again, 41, 0.0, False, dict(CPU_DEVICE),
                           work_root=os.path.join(str(tmp_path), "work"))
    assert res["correct"] is True, res["checked"]
    assert res["attempted"] == 2 and res["failed"] == 0
    assert res["checked"]["_seen"]["sampled"] == 256
    # ninths, to the three decimals a line prints
    assert res["checked"]["share_gap_max"]["value"] <= 0.0005
    with open(os.path.join(str(tmp_path), "work", cell.name, "out_001.csv")) as fh:
        lines = fh.read().splitlines()
    assert len(lines) == 512
    assert {round(float(ln.split(":")[-1]) * 9, 2) % 1 for ln in lines} <= {0.0, 0.01, 0.99}

    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p
    shutil.rmtree(str(tmp_path), ignore_errors=True)


FAMILY = os.path.join(HERE, "data", "family_dectree")


def test_a_deployment_of_another_job_family_is_added_as_files_and_entries(tmp_path):
    """What the next `model_config` PR does, with the files kept under
    `data/family_dectree/`: a `decTree` job on a schema of three
    categorical features and one numeric (the shape of call_hangup.json),
    one input and a model file for output. It brings its input module (one
    CSV, no test files), generator, check, loop, mix and span metric, adds
    its entries, joins a metric that was there, and edits nothing. The
    harness runs it, and every pin on configurations, cells and metrics
    holds for what was added as for what was there."""
    from avenir_tpu import obs
    from avenir_tpu.models.tree import DecisionPathList

    # 8,192 train rows: the e-learning pins want whole kernel blocks
    man = small_copy(str(tmp_path), train_rows=8192)
    bench = man.bench_dir
    before = file_stamps(bench)

    added = []
    for folder in sorted(os.listdir(FAMILY)):
        if os.path.isdir(os.path.join(FAMILY, folder)):
            os.makedirs(os.path.join(bench, folder), exist_ok=True)
            for f in os.listdir(os.path.join(FAMILY, folder)):
                dst = os.path.join(bench, folder, f)
                assert dst not in before, dst
                shutil.copy(os.path.join(FAMILY, folder, f), dst)
                added.append(os.path.join(folder, f))
    assert {os.path.dirname(a) for a in added} == {
        "configs", "inputs", "generators", "checks", "loops", "traffic",
        "metrics"}
    with open(os.path.join(FAMILY, "entries.json")) as fh:
        entries = json.load(fh)
    doc = json.loads(json.dumps(man.doc))
    for key in ("configs", "workloads", "per_layer"):
        doc[key] += entries[key]
    for metric, cell_name in entries["joins"].items():
        next(m for m in doc["per_layer"]
             if m["name"] == metric)["workloads"].append(cell_name)
    with open(os.path.join(man.root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)

    again = manifest.Manifest(man.root, bench)
    cell = again.cell("dectree-hangup-tiny.rebuild")
    kinds = [f["dataType"] for f in cell.config["schema"]["fields"]
             if f.get("feature")]
    assert kinds.count("categorical") >= 2 and "int" in kinds
    assert [m["name"] for m in cell.per_layer] == [
        "train_parse_ms_per_job", "tree_parse_ms_per_job"]

    # every pin, over the copy's entries: the new ones and the old
    for cfg in doc["configs"]:
        pins.hold_configuration(again, cfg)
        pins.hold_environment(again, cfg, ROOT)
    for entry in doc["workloads"]:
        pins.hold_cell(again, entry)
    pins.hold_four_chip_share(doc)
    for m in doc["per_layer"]:
        pins.hold_per_layer_metric(again, m)
    for name in pins.SPAN_EIGHT:
        pins.hold_span_metric_entry(doc, name)
    pins.hold_the_first_sixteen(doc)
    assert len({c["source"] for c in doc["configs"]}) == len(doc["configs"])

    # the harness runs it on the CPU: two tree builds from one input
    spans = []

    def entry_with_spans(argv):
        with obs.capture() as rec:
            run.default_entry(argv)
        spans.append([{"name": sp.name, "t0": sp.t0, "dur": sp.dur}
                      for sp in rec.spans()])

    work_root = os.path.join(str(tmp_path), "work")
    with compile_cache_off():
        res = run.run_cell(cell, again, 2**31 + 27, 0.0, False,
                           dict(CPU_DEVICE), entry=entry_with_spans,
                           work_root=work_root)
        # a tree built from half the rows fails the exact number
        cut = run.run_cell(cell, again, 7, 0.0, False, dict(CPU_DEVICE),
                           entry=half_the_rows, work_root=work_root + "_cut")
    assert cut["correct"] is False and cut["checked"]["population_gap"]["value"] > 0
    assert res["correct"] is True, res["checked"]
    assert res["attempted"] == 2 and res["failed"] == 0
    assert set(res["checked"]) == {"model_bad", "unstable_bytes",
                                   "population_gap", "class_share_gap_max",
                                   "_seen"}
    assert res["checked"]["_seen"]["paths"] > 1
    work = os.path.join(str(tmp_path), "work", cell.name)
    assert sorted(os.listdir(work)) == [
        "job.properties", "out_000.json", "out_001.json", "out_warmup.json",
        "schema.json", "train.csv"]
    model = DecisionPathList.load(os.path.join(work, "out_001.json"))
    assert len(model.paths) == res["checked"]["_seen"]["paths"]
    with open(os.path.join(work, "out_000.json"), "rb") as a, \
            open(os.path.join(work, "out_001.json"), "rb") as b:
        assert a.read() == b.read()
    with open(os.path.join(work, "train.csv")) as fh:
        row = fh.readline().rstrip("\n").split(",")
    assert len(row) == 6 and row[1] in ("business", "residence") \
        and row[3] in ("AM", "PM") and row[4].isdigit() and row[5] in "FT"

    # its control, one precision lower, comes out as not correct
    check = again.module("checks", "dectree_paths")
    limits = cell.config["check"]["limits"]
    good, _ = compare.verdict(check.control_numbers(cell, 5, 2, "float32"), limits)
    assert good
    low = check.control_numbers(cell, 5, 2, "bfloat16")
    good, _ = compare.verdict(low, limits)
    assert not good and low["class_share_gap_max"] > 100 * limits["class_share_gap_max"]

    # both metrics read the job's own spans: the one it brought, and the
    # one that was there, whose list the new cell joined
    ctx = {"jobs": 1, "spans": spans[-1]}
    for m in cell.per_layer:
        spec = again.metric(m["name"])
        assert again.reader(spec["reader"])(ctx, spec["params"]) > 0.0
    assert "tree_parse_ms_per_job" not in [
        m["name"] for m in again.cell(CELLS[0]).per_layer]

    assert not set(added) & {os.path.relpath(p, bench) for p in before}
    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p
    shutil.rmtree(str(tmp_path), ignore_errors=True)


def test_a_per_layer_entry_appended_at_the_end_passes_every_pin(tmp_path):
    """What a later PR of any kind may do: one more metric at the end of
    `per_layer`, listing every cell, as a file and an entry. Every pin of
    `pins.py` holds over the copy, each cell's accepted names in their
    order among them (PR 38 made the pins that held names to their places
    containment), and every cell reports the new metric last."""
    man = small_copy(str(tmp_path), train_rows=8192)
    bench = man.bench_dir
    before = file_stamps(bench)
    with open(os.path.join(bench, "metrics", "throwaway_ms_per_job.json"),
              "w") as fh:
        json.dump({"name": "throwaway_ms_per_job", "unit": "ms",
                   "reader": "span_sum", "params": {"span": "job.cli"}}, fh)
    doc = json.loads(json.dumps(man.doc))
    doc["per_layer"].append({
        "name": "throwaway_ms_per_job", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "Entry and device rule",
        "moves": "job_s", "workloads": [w["name"] for w in doc["workloads"]]})
    with open(os.path.join(man.root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)

    again = manifest.Manifest(man.root, bench)
    for cfg in doc["configs"]:
        pins.hold_configuration(again, cfg)
        pins.hold_environment(again, cfg, ROOT)
    for entry in doc["workloads"]:
        pins.hold_cell(again, entry)
        assert pins.cell_names(again, entry["name"])[-1] == "throwaway_ms_per_job"
    pins.hold_four_chip_share(doc)
    for m in doc["per_layer"]:
        pins.hold_per_layer_metric(again, m)
    for name in pins.SPAN_EIGHT:
        pins.hold_span_metric_entry(doc, name)
    pins.hold_the_first_sixteen(doc)
    pins.hold_every_cells_names(again)
    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p
    shutil.rmtree(str(tmp_path), ignore_errors=True)


def half_the_rows(argv):
    """Half of the batch left out: the tree is built from the first half
    of the train file."""
    with open(argv[3]) as fh:
        lines = fh.readlines()
    half = argv[3] + ".half"
    with open(half, "w") as fh:
        fh.writelines(lines[:len(lines) // 2])
    run.default_entry(argv[:3] + [half] + argv[4:])


# ------------------------------------------------- the inputs, byte for byte
#: sha256 of what PR 26's `run.Inputs` wrote for seed 2**31 + 27 in the
#: small copy (the work directory's path in job.properties replaced by
#: `{work}`); the test files are the same in both cells, as every mix is
#: cut alike there
PARENT_SHA256 = {
    "schema.json": "3e377c499e2f4ae290454f564644b1afa6d578c0d16714944bc50b43bfc391d2",
    "test_00.csv": "32691dc46a34f96ea124dfdecef52815a33f4aadcee28b005e9ef82b0da21fd5",
    "test_01.csv": "48adeb6f1ceb1a25144eaa3160d554e70f6a358cffa9bdbfc169a742e88906a2",
    "test_warmup.csv": "32691dc46a34f96ea124dfdecef52815a33f4aadcee28b005e9ef82b0da21fd5",
    "train.csv": "f77610e5cc6094e9ba130c374a6236a3e5706afc9e39a9f8585545ba20ee2a47",
}
PARENT_PROPERTIES_SHA256 = {
    "knn-elearn.bulk": "5f28cc751ffdf1acf48afbb9f2d0b7df2b24c2ba56677ce89a224a9870cc29eb",
    "knn-elearn-ccw.adhoc": "b0e3158e0faaf2d2b48bac97ca953d1872fbc1b08bacd5197bcdec44feaaee73",
    "knn-elearn-ccw.bulk": "b0e3158e0faaf2d2b48bac97ca953d1872fbc1b08bacd5197bcdec44feaaee73",
}


@pytest.mark.parametrize("cell_name", sorted(PARENT_PROPERTIES_SHA256))
def test_the_input_module_writes_the_bytes_the_harness_wrote(tmp_path, cell_name):
    """`Inputs` moved from `run.py` to `inputs/train_test_csv.py`: for a
    fixed seed the train file, the test files, the schema and the
    properties are the parent's, byte for byte, and so are the arguments."""
    import hashlib

    man = small_copy(str(tmp_path))
    cell = man.cell(cell_name)
    assert "inputs_kind" not in cell.config
    work = os.path.join(str(tmp_path), "work")
    os.makedirs(work)
    inputs = man.inputs(cell.config).Inputs(cell, 2**31 + 27, work)
    warm = inputs.warmup_argv("OUT")
    got = {}
    for f in os.listdir(work):
        with open(os.path.join(work, f), "rb") as fh:
            got[f] = hashlib.sha256(
                fh.read().replace(work.encode(), b"{work}")).hexdigest()
    assert got == dict(PARENT_SHA256,
                       **{"job.properties": PARENT_PROPERTIES_SHA256[cell_name]})
    at = lambda name: os.path.join(work, name)  # noqa: E731
    assert inputs.n_files == 2 and inputs.out_suffix == ".csv"
    assert inputs.argv(1, "OUT") == [
        "nearestNeighbor", "--conf", at("job.properties"), at("train.csv"),
        at("test_01.csv"), "OUT"]
    assert warm == ["nearestNeighbor", "--conf", at("job.properties"),
                    at("train.csv"), at("test_warmup.csv"), "OUT"]
