"""The four-chip deployment `fia-t10i4-mesh4` and its cell
`fia-t10i4-mesh4.remine`: the entry, the configuration (`fia-t10i4`'s but
for its size) and the rule of that size as arithmetic, the cell's thirteen
per-layer names and their order (after the one-chip cell's nine; since
PR 38 not their places), every pin of `pins.py`, the two new readers over
a recorded four-device job (data/events_mesh4_job.json) and over the
recorded one-device job, and the cell's job on the CPU: a process that sees four
devices, at 45,056 baskets with slabs of 4,096, against the plain reference
through the check a chip run uses."""

import json
import os

import pytest

import pins
from bench_fixtures import CPU_DEVICE, ROOT, compile_cache_off, small_copy

from chipbench import manifest, run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL, CONFIG, ONE_CHIP = ("fia-t10i4-mesh4.remine", "fia-t10i4-mesh4",
                          "fia-t10i4")
ROWS = 11 * 4096                     # eleven slabs: runs of 3, 3, 3 and 2
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    DOC = json.load(_fh)
NAMES = [m["name"] for m in DOC["per_layer"]]
MESH_METRICS = [n for n in NAMES if n.startswith("mesh_")]
SHARED = pins.SHARED_FOUR
EXACT = ("sets_bad", "unstable_bytes", "support_wrong", "sets_surplus",
         "closure_broken", "sets_missing")
GIB16 = 1 << 34


# ------------------------------------------------- the entry and the pins
def test_the_deployment_through_every_pin():
    man = manifest.Manifest()
    cfg = next(c for c in DOC["configs"] if c["name"] == CONFIG)
    entry = next(w for w in DOC["workloads"] if w["name"] == CELL)
    pins.hold_configuration(man, cfg)
    pins.hold_environment(man, cfg, ROOT)
    pins.hold_cell(man, entry)
    pins.hold_four_chip_share(DOC)
    for m in DOC["per_layer"]:
        if CELL in m.get("workloads", []):
            pins.hold_per_layer_metric(man, m)
    pins.hold_the_first_sixteen(DOC)
    # added after the five one-chip cells and four configurations; entries
    # that are there keep their places, later ones go after them
    assert DOC["workloads"].index(entry) == 5 and DOC["configs"].index(cfg) == 4
    assert cfg["reduced"] == ["train_rows"] and entry["chips"] == 4
    assert (entry["config"], entry["traffic"]) == (CONFIG,
                                                   "remine-nightly-mesh4")
    # the first cell that asks for four chips
    assert [w["name"] for w in DOC["workloads"] if w["chips"] == 4][0] == CELL
    assert len(cfg["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("host path", "mesh", "GB of bit columns a chip"):
        assert word in entry["why"]
    mix = man.cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["files_per_seed"]) == ("closed", 1, 1)
    assert mix["rows_per_file"] == pins.config_doc(man, cfg)["train_rows"]


def test_the_configuration_is_the_one_chip_deployments_but_for_its_size():
    man = manifest.Manifest()
    docs = {c["name"]: pins.config_doc(man, c) for c in DOC["configs"]}
    four, one = docs[CONFIG], docs[ONE_CHIP]
    for key in ("job", "inputs_kind", "inputs", "properties", "generator",
                "reference", "precision", "guarantees", "check"):
        assert four[key] == one[key], key
    assert four["generator"]["pattern_seed"] == 19940912
    assert "environment" not in four and "schema" not in four
    assert set(four["assumed"]) == set(one["assumed"]) | {"mesh"}
    for key in set(one["assumed"]) - {"train_rows"}:
        assert four["assumed"][key] == one["assumed"][key]
    for said in ("four chips share the basket axis", "a quarter of the baskets",
                 "all of the items", "some 90m"):
        assert said in four["assumed"]["train_rows"].lower(), said
    assert four["source"] != one["source"] and "4 chips" in four["source"]
    assert "PLACEHOLDER" not in four["device_bytes"]


def test_the_size_moves_only_by_the_issues_rule():
    """Steps of 4 x 2 x 2^20 baskets from 4 x 40 x 2^20, no further than
    4 x 48 x 2^20; at 27 words a chip's quarter is 26% of 16 GiB or more
    by its columns alone, and the whole is more than `RESIDENT_SHARE` of
    one chip, so that no one chip takes it."""
    from avenir_tpu.models.association import FrequentItemsApriori

    rows = manifest.Manifest().cell(CELL).config["train_rows"]
    step, least, most = 4 * 2 << 20, 4 * 40 << 20, 4 * 48 << 20
    assert least <= rows <= most and (rows - least) % step == 0
    assert rows == 167_772_160       # the floor's size: PERF.md section 4
    a_chip = rows // 4 * 27 * 4
    assert a_chip == 4_529_848_320 and a_chip >= 0.26 * GIB16
    assert rows * 27 * 4 > FrequentItemsApriori.RESIDENT_SHARE * GIB16
    assert rows * 27 * 4 == 18_119_393_280 > GIB16
    assert rows < 1 << 31            # an int32 count holds every basket
    # where a 51 s window holds another number of jobs
    assert [DOC["run_seconds"] / k for k in (4, 3, 2)] == [12.75, 17.0, 25.5]
    # 61 B a row of CSV under HOST_SHARE of a 140 GiB host
    assert rows * 61 < FrequentItemsApriori.HOST_SHARE * (140 << 30)


def test_the_rule_of_the_program_takes_the_cell_on_four_chips_alone(
        monkeypatch):
    from avenir_tpu.models.association import FrequentItemsApriori

    monkeypatch.setattr(FrequentItemsApriori, "device_bytes_limit",
                        staticmethod(lambda: 15_750_000_000))
    rows = manifest.Manifest().cell(CELL).config["train_rows"]
    miner = FrequentItemsApriori(0.0033)
    assert miner.resident_words(rows, 857) is None
    assert miner.resident_words(rows, 857, 4) == (4096, 320)


# ------------------------------------------------------ the per-layer names
def test_the_cells_per_layer_metrics_are_these_thirteen():
    """The four it shares and its nine, in their order among whatever a
    later PR adds, with their layers and sources (`pins.hold_mesh_names`);
    the cell stands on the four shared lists after the cells that were on
    them before it."""
    pins.hold_mesh_names(manifest.Manifest())
    assert MESH_METRICS[:9] == pins.MESH_NAMES
    layers = {m["name"]: m for m in DOC["per_layer"]}
    for name in SHARED:
        assert layers[name]["workloads"].index(CELL) == 5


def test_the_nine_stand_after_the_one_chip_cells_nine_in_their_order():
    """A PR adds entries at the end of a list of `BENCHMARK.json` and
    nowhere else: the nine `mesh_*` stand after the nine `fia_*`, each nine
    in its order, wherever later entries put the end of the list (PR 36
    appended them last; PR 38 appended one more after them). The `fia_*`
    are the one-chip cell's; this cell joins no `fia_*` list, least of all
    `fia_*_roofline`, which divide the whole file's work by one chip's
    peak."""
    pins.hold_in_order(NAMES, pins.FIA_NAMES + pins.MESH_NAMES)
    assert NAMES.index("fia_read_ms_per_job") == 25
    assert NAMES.index("mesh_read_ms_per_job") == 34
    for m in DOC["per_layer"]:
        if m["name"].startswith("fia_"):
            assert ONE_CHIP + ".remine" in m["workloads"]
            assert CELL not in m["workloads"]
    one = [m["name"] for m in manifest.Manifest().cell(
        ONE_CHIP + ".remine").per_layer]
    assert not set(one) & set(MESH_METRICS)


def test_the_one_chip_cells_thirteen_are_as_they_were_but_for_their_place():
    """What `test_itemsets.py` holds of the one-chip cell's names
    (`pins.hold_fia_names`: the names, their order, their cell, what they
    move and their layers), held from here too, since this cell's nine
    must stand after them and none of them may list this cell."""
    pins.hold_fia_names(manifest.Manifest())


def test_the_span_metrics_read_what_the_one_chip_cells_read():
    man = manifest.Manifest()
    for mesh, fia in (("mesh_read_ms_per_job", "fia_read_ms_per_job"),
                      ("mesh_scan_ms_per_job", "fia_scan_ms_per_job"),
                      ("mesh_put_ms_per_job", "fia_put_ms_per_job"),
                      ("mesh_support_ms_per_job", "fia_support_ms_per_job"),
                      ("mesh_unspanned_ms_per_job", "fia_unspanned_ms_per_job"),
                      ("mesh_idle_named_share", "fia_idle_named_share")):
        a, b = man.metric(mesh), man.metric(fia)
        assert (a["reader"], a["params"], a["unit"]) == (
            b["reader"], b["params"], b["unit"])
    assert "FIRST chip" in man.metric("mesh_idle_named_share")["what"]
    for name in ("mesh_pairs_roofline", "mesh_sets_roofline"):
        spec = man.metric(name)
        assert spec["reader"] == "mesh_roofline"
        assert spec["params"] == man.metric(
            name.replace("mesh_", "fia_"))["params"]


@pytest.mark.parametrize("name", ["readers/op_ms.py",
                                  "readers/mesh_roofline.py"])
def test_the_new_readers_import_nothing_of_the_program(name):
    with open(os.path.join(ROOT, "chipbench", name)) as fh:
        text = fh.read()
    assert "import avenir" not in text and "from avenir" not in text
    assert "import jax" not in text
    if name.endswith("mesh_roofline.py"):      # imported, not copied
        assert "from chipbench.readers import fia_roofline" in text
        assert "def pairs_work" not in text and "def sets_work" not in text


def test_the_four_chip_files_are_listed_beside_the_families():
    """`README.md` and `README-families.md` are the benchmark's own and not
    this PR's to edit: the cell's files stand in a file of their own."""
    with open(os.path.join(ROOT, "chipbench", "README-mesh4.md")) as fh:
        text = fh.read()
    for said in (CONFIG, "remine-nightly-mesh4", "mesh_*", "op_ms",
                 "mesh_roofline"):
        assert said in text, said


# ------------------------------------------- the readers, recorded jobs
def recorded(name, n):
    with open(os.path.join(HERE, "data", name)) as fh:
        rec = json.load(fh)
    ann = rec["annotations"][0]
    return {"spans": rec["spans"], "devices": rec["devices"],
            "window_ns": (ann[1], ann[1] + ann[2]), "jobs": 1, "notes": {},
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "sizes": {"n": n, "items": 1000, "frequent": 800,
                      "max_length": 3, "candidates": {2: 319_600, 3: 2_000}}}


@pytest.fixture()
def ctx():
    """Four chips, four times the baskets of the one-chip recording."""
    return recorded("events_mesh4_job.json", 4_000_000)


@pytest.fixture()
def one_chip_ctx():
    return recorded("events_fia_job.json", 1_000_000)


def read(ctx, name):
    man = manifest.Manifest()
    spec = man.metric(name)
    return man.reader(spec["reader"])(ctx, spec["params"])


#: pairs: 2 x 4e6 x 319,600 operations at 4 x 197 TFLOP/s over the slowest
#: chip's 10 ms; sets: 4e6 x 100 + 4 x 2,000 bytes at 4 x 819 GB/s over 4 ms
PAIRS = 100.0 * (2 * 4e6 * 319_600 / (4 * 197e12)) / 10e-3
SETS = 100.0 * ((4e8 + 8_000) / (4 * 819e9)) / 4e-3
EXPECTED = {
    "mesh_read_ms_per_job": 12.0,
    "mesh_scan_ms_per_job": 40.0,
    "mesh_put_ms_per_job": 6.0,
    # a chip: (8 + 2, 8.5 + 2, 9 + 2, 10 + 4) / 4
    "mesh_support_ms_per_job": 45.5 / 4,
    # a chip: (2 + 0.4, 1.5 + 0.4, 1 + 0.4, 0.2 + 0.4) / 4
    "mesh_allreduce_ms_per_job": 6.3 / 4,
    "mesh_pairs_roofline": PAIRS,
    "mesh_sets_roofline": SETS,
    "mesh_unspanned_ms_per_job": 17.1,
    # the first chip is busy for 12 of the 82.5 ms the leaves cover
    "mesh_idle_named_share": 100.0 * (82.5 - 12.0) / 88.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_mesh_metric_reads_the_recorded_four_chip_job(ctx, name):
    assert sorted(EXPECTED) == sorted(pins.MESH_NAMES)
    assert read(ctx, name) == pytest.approx(EXPECTED[name], rel=1e-6)


def test_the_accepted_metrics_the_cell_joins_read_the_four_chips(ctx):
    assert read(ctx, "train_encode_ms_per_job") == 0.0
    # busy 12, 12.5, 13 and 16 ms of 100: the mean over the chips used
    assert read(ctx, "device_idle_share") == pytest.approx(100 - 53.5 / 4)


def test_the_rooflines_take_the_slowest_chip_and_every_chips_peak(ctx):
    assert 30 < read(ctx, "mesh_pairs_roofline") < 35
    assert 3 < read(ctx, "mesh_sets_roofline") < 3.2
    assert ctx["notes"] == {"mesh_pairs_roofline_bound": "compute",
                            "mesh_sets_roofline_bound": "memory",
                            "mesh_roofline_chips": 4}
    # a faster first chip changes nothing: the job waits for the slowest
    ctx["devices"]["/device:TPU:0"]["modules"][2][2] = 1e6
    assert read(ctx, "mesh_pairs_roofline") == pytest.approx(PAIRS)
    # a full Gram does twice the pairs' count on every chip: at the MXUs'
    # peak the share reads half, and cannot pass 100%
    roofline = manifest.Manifest().module("readers", "fia_roofline")
    ops, _bytes = roofline.pairs_work(4_000_000, 800)
    full_gram_s = 2.0 * 1e6 * 800 * 800 / 197e12      # a chip's quarter
    assert ops / (4 * 197e12) / full_gram_s < 0.5
    # the one-chip reader over this trace would divide the whole work by
    # one chip's peak and a mean time: over 100%
    spec = manifest.Manifest().metric("fia_pairs_roofline")
    assert manifest.Manifest().reader("fia_roofline")(
        ctx, spec["params"]) > 4 * PAIRS


@pytest.mark.parametrize("name, want", [
    ("mesh_pairs_roofline", 100.0 * (2e6 * 319_600 / 197e12) / 8e-3),
    ("mesh_sets_roofline", 100.0 * ((1e8 + 8_000) / 819e9) / 2e-3)])
def test_over_a_one_chip_job_it_is_the_one_chip_roofline(one_chip_ctx, name,
                                                         want):
    assert read(one_chip_ctx, name) == pytest.approx(want, rel=1e-9)
    assert read(one_chip_ctx, name) == pytest.approx(
        read(one_chip_ctx, name.replace("mesh_", "fia_")), rel=1e-9)
    assert one_chip_ctx["notes"]["mesh_roofline_chips"] == 1


def test_the_all_reduce_is_found_by_its_own_name_alone(ctx, one_chip_ctx):
    read(ctx, "mesh_allreduce_ms_per_job")
    # by the opcode: the chip's trace names a psum's all-reduce `psum.7`
    assert ctx["notes"]["mesh_allreduce_ms_per_job_ops"] == [
        "all-reduce-done.2", "all-reduce-start.2", "psum.7"]
    # the fusion that takes the all-reduce as its operand is not one
    for dev in ctx["devices"].values():
        dev["ops"] = [e for e in dev["ops"] if e[0].startswith("%fusion")]
    assert any("%psum.7)" in e[0] for e in dev["ops"])
    assert read(ctx, "mesh_allreduce_ms_per_job") is None
    # a trace that gives bare names
    dev["ops"].append(["all-reduce.3", 1e6, 2e6])
    assert read(ctx, "mesh_allreduce_ms_per_job") == pytest.approx(2.0 / 4)
    # a job on one chip has none: nothing, never 0
    assert read(one_chip_ctx, "mesh_allreduce_ms_per_job") is None
    one_chip_ctx["devices"] = {}
    assert read(one_chip_ctx, "mesh_allreduce_ms_per_job") is None


@pytest.mark.parametrize("name", ["mesh_pairs_roofline", "mesh_sets_roofline",
                                  "mesh_support_ms_per_job"])
def test_where_no_support_program_ran_nothing_is_returned_never_zero(ctx, name):
    for dev in ctx["devices"].values():
        dev["modules"] = [m for m in dev["modules"]
                          if "_pair_gram" not in m[0]
                          and "_set_supports" not in m[0]]
    assert read(ctx, name) is None
    ctx["devices"] = {}
    assert read(ctx, name) is None


def test_a_round_that_did_not_run_has_no_roofline(ctx):
    ctx["sizes"]["candidates"] = {2: 319_600}
    assert read(ctx, "mesh_sets_roofline") is None
    ctx["sizes"]["frequent"] = 0               # no output was compared
    assert read(ctx, "mesh_pairs_roofline") is None


@pytest.mark.parametrize("name", ["mesh_unspanned_ms_per_job",
                                  "mesh_idle_named_share",
                                  "mesh_read_ms_per_job"])
def test_a_program_without_the_spans_leaves_the_metric_out(ctx, name):
    ctx["spans"] = []
    assert read(ctx, name) is None


# ------------------------------------- the cell's job, four CPU devices
@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh4")
    with compile_cache_off():
        yield small_copy(str(tmp), train_rows=ROWS), str(tmp)


@pytest.mark.parametrize("seed", [2**31 + 36, 5, 23])
def test_four_devices_agree_with_the_reference(bench, seed, monkeypatch):
    """The cell through the harness as a chip run drives it, in a process
    that sees four devices: every exact number of the check 0."""
    import jax

    from avenir_tpu import obs
    from avenir_tpu.ops import bitset

    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: four)
    whole = bitset.slab_words_for
    monkeypatch.setattr(bitset, "slab_words_for",
                        lambda n, most_rows=4096: whole(n, most_rows))
    man, tmp = bench
    with obs.capture() as rec:
        res = run.run_cell(man.cell(CELL), man, seed, 0.0, False,
                           dict(CPU_DEVICE, count=4),
                           work_root=os.path.join(tmp, "work"))
    assert res["correct"] is True, res["checked"]
    assert res["attempted"] == 1 and res["failed"] == 0
    got = {k: v["value"] for k, v in res["checked"].items() if k != "_seen"}
    assert all(got[k] == 0 for k in EXACT) and got["support_gap_max"] == 0.0
    seen = res["checked"]["_seen"]
    assert seen["outputs_compared"] == 1 and seen["sets"] >= 2500
    assert set(res["metrics"]) == {"job_s", "setup_s"}
    puts = [s for s in rec.spans() if s.name == "fia.put"]
    assert len(puts) == 2                      # the warm-up job and the one
    assert all(p.attrs["devices"] == 4 and p.attrs["slabs"] == 11
               for p in puts)
    mines = [s for s in rec.spans() if s.name == "fia.mine"]
    assert all(m.attrs["resident"] and m.attrs["rows"] == ROWS for m in mines)
