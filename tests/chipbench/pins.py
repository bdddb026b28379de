"""What the entries of a `BENCHMARK.json` are held to, as functions of a
manifest: the tests run them over the repo's own entries, and the test
that adds a deployment of another job family to a copy runs them over the
copy's. What the driver and the guides ask of every configuration, cell
and metric stands here once; what is the kNN e-learning deployment's alone
is pinned under its configurations' names.
"""

import json
import os
import re

from chipbench import generate, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

#: the upstream kNN deployment's two configurations (PR 24)
E_LEARNING = ("knn-elearn", "knn-elearn-ccw")
VMEM = "--xla_tpu_scoped_vmem_limit_kib=32768"
UNTIL = {"file": "avenir_tpu/ops/pallas_knn.py", "function": "knn_topk_pallas",
         "keyword": "vmem_limit_bytes"}

#: PR 24's eight per-layer metrics, in their order, then PR 25's eight
FIRST_EIGHT = ["compiles_in_window", "parse_ms_per_job",
               "stall_consumer_ms_per_job", "nb_fold_device_ms_per_job",
               "knn_kernel_ms_per_job", "knn_kernel_roofline",
               "device_idle_share", "peak_hbm_gb"]
SPAN_EIGHT = ["train_parse_ms_per_job", "index_build_ms_per_job",
              "nb_fit_ms_per_job", "nb_posterior_ms_per_job",
              "device_wait_ms_per_job", "output_write_ms_per_job",
              "job_unspanned_ms_per_job", "idle_named_share"]
#: the cells each of PR 25's eight has had since it was added
WEIGHTED_CELLS = ["knn-elearn-ccw.adhoc"]
BOTH_CELLS = ["knn-elearn.bulk", "knn-elearn-ccw.adhoc"]


def one_line(text, most=200):
    return 1 <= len(text) <= most and "\n" not in text and "\t" not in text


def config_doc(man, cfg):
    with open(os.path.join(man.root, cfg["file"])) as fh:
        return json.load(fh)


# ------------------------------------------------------------ configurations
def hold_configuration(man, cfg):
    """Any configuration, of any job family."""
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and one_line(cfg["source"]) and one_line(cfg["why"])
    assert cfg["file"].startswith("chipbench/") and PATH.match(cfg["file"])
    assert len(cfg["reduced"]) <= 16 and all(NAME.match(k) for k in cfg["reduced"])
    assert any(w["config"] == cfg["name"] for w in man.doc["workloads"])
    doc = config_doc(man, cfg)
    assert doc["name"] == cfg["name"] and doc["source"] == cfg["source"]
    # every cut is a key of the file, and the file says how it was cut
    assert all(k in doc for k in cfg["reduced"])
    assert set(doc["assumed"]) >= set(cfg["reduced"])
    assert doc["job"] and doc["precision"] and doc["device_bytes"]
    assert len(doc["guarantees"]) >= 3 and all(doc["guarantees"])
    # an exact number (limit 0), and one that a lower precision fails:
    # the check says which its control has to fail
    limits = doc["check"]["limits"]
    assert any(limit == 0 for limit in limits.values())
    check = man.module("checks", doc["reference"]["kind"])
    assert callable(check.numbers) and callable(check.sizes)
    assert callable(check.control_numbers)
    assert set(check.CONTROL_FAILS) & set(limits)
    assert callable(man.module("generators", doc["generator"]["kind"]).draw)
    assert callable(man.inputs(doc).Inputs)
    if cfg["name"] in E_LEARNING:
        hold_e_learning(man, doc)


def hold_e_learning(man, doc):
    """The shapes the kNN e-learning deployment fixes: upstream
    elearnActivity.json as the repo records it
    (tests/test_reference_configs.py): studentID, nine whole-number
    activity fields with these maxima, the status class."""
    fields = doc["schema"]["entity"]["fields"]
    feats = generate.feature_fields(doc["schema"])
    assert [f["max"] for f in feats] == [600, 200, 100, 28, 100, 100, 280, 180, 26]
    assert all(f["min"] == 0 and f["dataType"] == "int" for f in feats)
    assert fields[0]["id"] and fields[-1]["dataType"] == "categorical"
    assert len(fields) == 11
    assert doc["properties"]["nen.top.match.count"] == "5"
    assert doc["reference"] == {"kind": "knn_classify"}
    assert os.path.exists(os.path.join(
        man.bench_dir, "generators", doc["generator"]["kind"] + ".py"))
    assert os.path.exists(os.path.join(
        man.bench_dir, "checks", doc["reference"]["kind"] + ".py"))
    assert "inputs_kind" not in doc            # the default: train_test_csv
    assert set(doc["assumed"]) >= {"train_rows", "generator", "schema"}
    assert doc["precision"] == "float32" and len(doc["guarantees"]) >= 3
    assert doc["train_rows"] % 8192 == 0
    assert set(doc["check"]["limits"]) >= {"lines_bad", "share_gap_max", "class_flips"}


def hold_environment(man, cfg, root):
    """The runtime's settings a configuration states: none, or what
    `run.ALLOWED_ENVIRONMENT` allows, with the mend that ends it named.
    The e-learning deployment states its one flag."""
    doc = config_doc(man, cfg)
    wanted = doc.get("environment", {})
    if wanted:
        assert doc["environment_why"]
        assert set(doc["environment_until"]) == {"file", "function", "keyword"}
    env = {}
    # while the program lacks the mend a flag is applied, and echoed for
    # the result line; a configuration that states none changes nothing
    applied = run.apply_environment(doc, root, env)
    assert applied in ({}, wanted) and env == applied
    if cfg["name"] in E_LEARNING:
        assert doc["environment"] == {"LIBTPU_INIT_ARGS": VMEM}
        assert doc["environment_until"] == UNTIL and doc["environment_why"]
        # today the program's exact kernel states no limit of its own, so
        # the flag is applied; one the caller's environment holds is kept
        assert applied == {"LIBTPU_INIT_ARGS": VMEM}
        env = {"LIBTPU_INIT_ARGS": "--other=1"}
        run.apply_environment(doc, root, env)
        assert env["LIBTPU_INIT_ARGS"] == "--other=1 " + VMEM


# ------------------------------------------------------------------- cells
def hold_cell(man, entry):
    """Any cell: its files are found by name, and it reports `setup_s`,
    one more end-to-end metric and one per-layer metric."""
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
    assert one_line(entry["why"])
    assert entry["chips"] in (1, 4)
    got = man.cell(entry["name"])
    assert got.config["name"] == entry["config"]
    assert os.path.exists(man.path("traffic", entry["traffic"]))
    assert callable(man.module("loops", got.traffic["loop"]).drive)
    if got.config["reference"]["kind"] == "knn_classify":
        # the kNN kernel's query block: a test file is whole blocks
        assert all(r % 256 == 0 for r in generate.file_rows(got.traffic))
    names = [m["name"] for m in got.end_to_end]
    assert "setup_s" in names and len(names) >= 2 and got.per_layer


def hold_four_chip_share(doc):
    """At most a quarter of the cells, rounded down, may ask for four
    chips, and one always may."""
    fours = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    assert fours <= max(1, len(doc["workloads"]) // 4)


# ----------------------------------------------------------------- metrics
def hold_per_layer_metric(man, m):
    doc = man.doc
    cells = [w["name"] for w in doc["workloads"]]
    e2e = {x["name"]: x for x in doc["end_to_end"]}
    name = m["name"]
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(name) and UNIT.match(m["unit"]) and one_line(m["layer"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    spec = man.metric(name)
    assert spec["name"] == name and spec["unit"] == m["unit"]
    assert callable(man.reader(spec["reader"]))
    # the one end-to-end metric it moves is reported in each of its cells
    assert m["moves"] in e2e
    for cell in m.get("workloads", cells):
        assert cell in cells
        assert cell in e2e[m["moves"]].get("workloads", cells)
        assert name in [x["name"] for x in man.cell(cell).per_layer]
    if name.endswith("_roofline") or "mfu" in name:
        assert m["unit"] == "%" and m["better"] == "higher"


def hold_span_metric_entry(doc, name):
    """One of PR 25's eight: its entry as it was, its cells at least those
    it has had (later cells may join the list)."""
    entry = next(m for m in doc["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span" and entry["moves"] == "job_s"
    had = WEIGHTED_CELLS if name.startswith("nb_") else BOTH_CELLS
    assert set(entry["workloads"]) >= set(had)
    assert (entry["unit"], entry["better"]) == (
        ("%", "higher") if name == "idle_named_share" else ("ms", "lower"))


def hold_the_first_sixteen(doc):
    """The sixteen entries PRs 24 and 25 added stand first, as they
    were; what follows the sixteenth is free."""
    names = [m["name"] for m in doc["per_layer"]]
    assert names[:8] == FIRST_EIGHT
    assert sorted(names[8:16]) == sorted(SPAN_EIGHT)
    assert len(set(names)) == len(names)


# ------------------------------------------- each cell's names, in order
#: the per-layer names of three cells as the benchmark had them when PR 38
#: made these pins containment, in their order. A later PR may add names
#: to a cell's list (new entries go at the end of `per_layer`), never take
#: one away or reorder them
SHARED_FOUR = ["compiles_in_window", "device_idle_share", "peak_hbm_gb",
               "train_encode_ms_per_job"]
FOREST_CELL, FIA_CELL, MESH_CELL = ("rf-hangup.rebuild", "fia-t10i4.remine",
                                    "fia-t10i4-mesh4.remine")
FOREST_NAMES = [
    "forest_level_ms_per_job", "forest_level_roofline",
    "forest_sample_ms_per_job", "forest_segments_ms_per_job",
    "forest_select_ms_per_job", "forest_unspanned_ms_per_job",
    "forest_idle_named_share", "forest_segments_device_ms_per_job"]
FIA_NAMES = [
    "fia_read_ms_per_job", "fia_scan_ms_per_job", "fia_put_ms_per_job",
    "fia_candidates_ms_per_job", "fia_support_ms_per_job",
    "fia_pairs_roofline", "fia_sets_roofline", "fia_unspanned_ms_per_job",
    "fia_idle_named_share"]
MESH_NAMES = [
    "mesh_read_ms_per_job", "mesh_scan_ms_per_job", "mesh_put_ms_per_job",
    "mesh_support_ms_per_job", "mesh_allreduce_ms_per_job",
    "mesh_pairs_roofline", "mesh_sets_roofline", "mesh_unspanned_ms_per_job",
    "mesh_idle_named_share"]
#: the layer of each of the miner's nine, and of the four-chip cell's nine
FIA_LAYERS = ["Parse / replay", "Parse / replay", "Job registry and executors",
              "Job registry and executors", "Device kernels", "Device kernels",
              "Device kernels", "Entry and device rule", "Device"]
MESH_LAYERS = ["Parse / replay", "Parse / replay", "Job registry and executors",
               "Mesh", "Mesh", "Mesh", "Mesh", "Entry and device rule",
               "Device"]


def hold_in_order(names, accepted):
    """Every accepted name stands in `names`, in the accepted order; other
    names may stand among and after them."""
    rest = iter(names)
    assert all(name in rest for name in accepted), (accepted, names)


def cell_names(man, cell, key="per_layer"):
    return [m["name"] for m in getattr(man.cell(cell), key)]


def own_entries(man, names, cell):
    """The entries of `names`: each is `cell`'s and moves `job_s`."""
    entries = {m["name"]: m for m in man.doc["per_layer"]}
    for name in names:
        assert cell in entries[name]["workloads"], name
        assert entries[name]["moves"] == "job_s", name
    return [entries[name] for name in names]


def hold_forest_names(man):
    """`rf-hangup.rebuild`: the five metrics it shares, then its own eight,
    in order; `job_s` and `setup_s`; no kNN cell reports a `forest_*`."""
    hold_in_order(cell_names(man, FOREST_CELL),
                  SHARED_FOUR[:3] + ["train_parse_ms_per_job",
                                     "train_encode_ms_per_job"] + FOREST_NAMES)
    hold_in_order(cell_names(man, FOREST_CELL, "end_to_end"), ["job_s", "setup_s"])
    own_entries(man, FOREST_NAMES, FOREST_CELL)
    assert not set(FOREST_NAMES) & set(cell_names(man, "knn-elearn.bulk"))


def hold_fia_names(man):
    """`fia-t10i4.remine`: the four metrics it shares, then the miner's
    nine in their order and with their layers, which stand in `per_layer`
    in that order too; the forest cell and the four-chip cell report none
    of the nine."""
    hold_in_order(cell_names(man, FIA_CELL), SHARED_FOUR + FIA_NAMES)
    hold_in_order(cell_names(man, FIA_CELL, "end_to_end"), ["job_s", "setup_s"])
    hold_in_order([m["name"] for m in man.doc["per_layer"]], FIA_NAMES)
    entries = own_entries(man, FIA_NAMES, FIA_CELL)
    assert [m["layer"] for m in entries] == FIA_LAYERS
    for other in (FOREST_CELL, MESH_CELL):
        assert not set(FIA_NAMES) & set(cell_names(man, other)), other


def hold_mesh_names(man):
    """`fia-t10i4-mesh4.remine`: the four metrics it shares (it stands on
    their lists), then its own nine in their order, with their layers and
    sources, all of them after the miner's nine in `per_layer`; the
    one-chip cell reports none of them, and this cell no `fia_*`, least of
    all `fia_*_roofline`, which would divide the whole file's work by one
    chip's peak."""
    hold_in_order(cell_names(man, MESH_CELL), SHARED_FOUR + MESH_NAMES)
    hold_in_order(cell_names(man, MESH_CELL, "end_to_end"), ["job_s", "setup_s"])
    names = [m["name"] for m in man.doc["per_layer"]]
    hold_in_order(names, FIA_NAMES + MESH_NAMES)
    entries = own_entries(man, MESH_NAMES, MESH_CELL)
    assert [m["layer"] for m in entries] == MESH_LAYERS
    assert [m["source"] for m in entries] == (
        ["program_span"] * 3 + ["device_trace"] * 4 + ["program_span"] * 2)
    own_entries(man, SHARED_FOUR, MESH_CELL)
    assert not set(MESH_NAMES) & set(cell_names(man, FIA_CELL))
    assert not any(n.startswith("fia_") for n in cell_names(man, MESH_CELL))


def hold_every_cells_names(man):
    hold_forest_names(man)
    hold_fia_names(man)
    hold_mesh_names(man)
