"""BENCHMARK.json against the driver's rules a test can check here, and
the harness's promise that a cell, a traffic mix and a per-layer metric
are added as files plus one entry."""

import json
import os
import re
import shutil

import pytest

from bench_fixtures import CPU_DEVICE, ROOT, small_copy

from chipbench import generate, manifest, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    DOC = json.load(_fh)
CELLS = [w["name"] for w in DOC["workloads"]]
LAYER_METRICS = [m["name"] for m in DOC["per_layer"]]
E2E = {m["name"]: m for m in DOC["end_to_end"]}


def one_line(text, most=200):
    return 1 <= len(text) <= most and "\n" not in text and "\t" not in text


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
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and one_line(cfg["source"]) and one_line(cfg["why"])
    assert cfg["file"].startswith("chipbench/") and PATH.match(cfg["file"])
    assert len(cfg["reduced"]) <= 16 and all(NAME.match(k) for k in cfg["reduced"])
    assert any(w["config"] == cfg["name"] for w in DOC["workloads"])
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        doc = json.load(fh)
    assert doc["name"] == cfg["name"] and doc["source"] == cfg["source"]
    # the shapes the deployment fixes, and the guarantees it states
    # upstream elearnActivity.json as the repo records it
    # (tests/test_reference_configs.py): studentID, nine whole-number
    # activity fields with these maxima, the status class
    fields = doc["schema"]["entity"]["fields"]
    feats = generate.feature_fields(doc["schema"])
    assert [f["max"] for f in feats] == [600, 200, 100, 28, 100, 100, 280, 180, 26]
    assert all(f["min"] == 0 and f["dataType"] == "int" for f in feats)
    assert fields[0]["id"] and fields[-1]["dataType"] == "categorical"
    assert len(fields) == 11
    assert doc["properties"]["nen.top.match.count"] == "5"
    assert doc["reference"] == {"kind": "knn_classify"}
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "generators", doc["generator"]["kind"] + ".py"))
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "checks", doc["reference"]["kind"] + ".py"))
    assert set(doc["assumed"]) >= {"train_rows", "generator", "schema"}
    assert doc["precision"] == "float32" and len(doc["guarantees"]) >= 3
    assert doc["train_rows"] % 8192 == 0
    assert set(doc["check"]["limits"]) >= {"lines_bad", "share_gap_max", "class_flips"}


def test_configurations_do_not_share_a_file_or_a_source():
    assert len({c["file"] for c in DOC["configs"]}) == len(DOC["configs"])
    assert len({c["source"] for c in DOC["configs"]}) == len(DOC["configs"])
    assert len({c["name"] for c in DOC["configs"]}) == len(DOC["configs"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    man = manifest.Manifest()
    entry = next(w for w in DOC["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(entry["traffic"]) and one_line(entry["why"])
    assert entry["chips"] == 1
    got = man.cell(cell)
    assert got.config["name"] == entry["config"]
    assert os.path.exists(man.path("traffic", entry["traffic"]))
    assert callable(man.module("loops", got.traffic["loop"]).drive)
    assert got.traffic["clients"] == 1
    assert all(r % 256 == 0 for r in generate.file_rows(got.traffic))
    # every cell reports setup_s, one more end-to-end metric, one per-layer
    names = [m["name"] for m in got.end_to_end]
    assert "setup_s" in names and len(names) >= 2 and got.per_layer


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
    man = manifest.Manifest()
    m = next(x for x in DOC["per_layer"] if x["name"] == name)
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(name) and UNIT.match(m["unit"]) and one_line(m["layer"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    spec = man.metric(name)
    assert spec["name"] == name and spec["unit"] == m["unit"]
    assert callable(man.reader(spec["reader"]))
    # the one end-to-end metric it moves is reported in each of its cells
    assert m["moves"] in E2E
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        moved = E2E[m["moves"]]
        assert cell in moved.get("workloads", CELLS)
        assert name in [x["name"] for x in man.cell(cell).per_layer]
    if name.endswith("_roofline") or "mfu" in name:
        assert m["unit"] == "%" and m["better"] == "higher"


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
VMEM = "--xla_tpu_scoped_vmem_limit_kib=32768"
UNTIL = {"file": "avenir_tpu/ops/pallas_knn.py", "function": "knn_topk_pallas",
         "keyword": "vmem_limit_bytes"}


@pytest.mark.parametrize("cfg", DOC["configs"], ids=lambda c: c["name"])
def test_a_configuration_states_only_the_one_runtime_flag(cfg):
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        doc = json.load(fh)
    assert doc["environment"] == {"LIBTPU_INIT_ARGS": VMEM}
    assert doc["environment_until"] == UNTIL and doc["environment_why"]
    env = {}
    # today the program's exact kernel states no limit of its own, so the
    # flag is applied, and echoed for the result line
    assert run.apply_environment(doc, ROOT, env) == {"LIBTPU_INIT_ARGS": VMEM}
    assert env == {"LIBTPU_INIT_ARGS": VMEM}
    env = {"LIBTPU_INIT_ARGS": "--other=1"}
    run.apply_environment(doc, ROOT, env)
    assert env["LIBTPU_INIT_ARGS"] == "--other=1 " + VMEM


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
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    man = small_copy(str(tmp_path))
    bench = man.bench_dir
    before = {}
    for folder, _d, files in os.walk(bench):
        for f in files:
            p = os.path.join(folder, f)
            before[p] = os.path.getmtime(p), os.path.getsize(p)

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
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        res = run.run_cell(cell, again, 41, 0.0, False, dict(CPU_DEVICE),
                           work_root=os.path.join(str(tmp_path), "work"))
    finally:
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_compilation_cache_dir", was[1])
        compilation_cache.reset_cache()
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
