"""The six metrics that read what is under the host's largest rows: the
native parse's steps, the process's CPU time on `job.cli` (reader
`span_attr`), and the large puts' landings. On a
recorded kNN job (data/events_parse_job.json: the train file's parse with
its five steps, the index's puts and their landings, the root), and on the
recorded jobs the accepted readers are held to, with the steps added
inside `dataset.parse.native`: those readers read what they read, and the
traced line's idle rows name the steps."""

import copy
import json
import os

import pytest

import pins
from bench_fixtures import ROOT

from chipbench import manifest, reduce, run

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    DOC = json.load(_fh)

STEPS = ("dataset.parse.count", "dataset.parse.prefill",
         "dataset.parse.fields", "dataset.parse.check", "dataset.parse.ids")
KNN = ["knn-elearn.bulk", "knn-elearn-ccw.adhoc", "knn-elearn-ccw.bulk"]
PARSING = KNN + ["rf-hangup.rebuild"]
ALL = [w["name"] for w in DOC["workloads"]]
#: name: (unit, source, layer, cells), in the order they were appended
SIX = {
    "parse_prefill_ms_per_job": ("ms", "program_span", "Parse / replay", PARSING),
    "parse_fields_ms_per_job": ("ms", "program_span", "Parse / replay", PARSING),
    "parse_ids_ms_per_job": ("ms", "program_span", "Parse / replay", PARSING),
    "job_cpu_s_per_job": ("s", "program_counter", "Entry and device rule", ALL),
    "index_put_landed_ms_per_job": ("ms", "program_span", "Device", KNN),
    "forest_put_landed_ms_per_job": ("ms", "program_span", "Device",
                                     ["rf-hangup.rebuild"]),
}
#: what each reads over the recorded job (its spans' own numbers)
EXPECTED = {
    "parse_prefill_ms_per_job": 17.077,
    "parse_fields_ms_per_job": 55.708,
    "parse_ids_ms_per_job": 102.511,
    "job_cpu_s_per_job": 8.65917,
    "index_put_landed_ms_per_job": 0.623 + 56.737,
}
#: the counters no metric reads (the benchmark's host counts no faults or
#: switches), read by `span_attr` over the recorded job all the same:
#: (span, attribute): its sum
COUNTED = {
    ("dataset.parse", "minflt"): 33_541,
    ("job.cli", "minflt"): 54_108,
    ("job.cli", "nivcsw"): 57,
    ("job.cli", "cpu_ms"): 8_659.17,
}


def parse_job():
    with open(os.path.join(HERE, "data", "events_parse_job.json")) as fh:
        return {"spans": json.load(fh)["spans"], "jobs": 1, "notes": {}}


def read(ctx, name, params=None):
    man = manifest.Manifest()
    spec = man.metric(name)
    return man.reader(spec["reader"])(ctx, params or spec["params"])


def span(ctx, name):
    return next(s for s in ctx["spans"] if s["name"] == name)


# -------------------------------------------------------- the six, read
@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_new_metric_reads_the_recorded_job(name):
    assert read(parse_job(), name) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("span_name, attr", sorted(COUNTED))
def test_span_attr_reads_every_counter_of_the_recorded_job(span_name, attr):
    params = {"span": span_name, "attr": attr}
    assert read(parse_job(), "job_cpu_s_per_job", params) == pytest.approx(
        COUNTED[span_name, attr], rel=1e-9)


def test_the_forest_landings_add_up_per_job():
    ctx = parse_job()
    ctx["jobs"] = 2
    ctx["spans"] += [
        {"name": "tree.put.landed", "t0": 50.5, "dur": 0.04, "tid": 2,
         "attrs": {"nbytes": 3, "landed": True}},
        {"name": "tree.put.landed", "t0": 51.0, "dur": 0.30, "tid": 3,
         "attrs": {"nbytes": 9, "landed": True}}]
    assert read(ctx, "forest_put_landed_ms_per_job") == pytest.approx(170.0)


def test_span_attr_sums_the_attribute_of_one_name_times_its_scale():
    ctx = {"jobs": 2, "spans": [
        {"name": "job.cli", "t0": 0.0, "dur": 1.0, "attrs": {"cpu_ms": 1500.0}},
        {"name": "job.cli", "t0": 2.0, "dur": 1.0, "attrs": {"cpu_ms": 500.0}},
        # one without it adds nothing, and no other name counts
        {"name": "job.cli", "t0": 4.0, "dur": 1.0, "attrs": {"job": "x"}},
        {"name": "job.run", "t0": 0.0, "dur": 1.0, "attrs": {"cpu_ms": 7.0}}]}
    params = {"span": "job.cli", "attr": "cpu_ms", "scale": 0.001}
    assert read(ctx, "job_cpu_s_per_job", params) == pytest.approx(1.0)
    params.pop("scale")
    assert read(ctx, "job_cpu_s_per_job", params) == pytest.approx(1000.0)


@pytest.mark.parametrize("span_name, attr", sorted(COUNTED))
def test_span_attr_leaves_the_metric_out_where_no_span_carries_it(span_name,
                                                                  attr):
    """A program whose spans record no counters, as the parent's: no
    value rather than a false 0; and none where the span is not there."""
    def got(ctx):
        return read(ctx, "job_cpu_s_per_job",
                    {"span": span_name, "attr": attr, "scale": 0.001})

    ctx = parse_job()
    for s in ctx["spans"]:
        s["attrs"] = {k: v for k, v in s.get("attrs", {}).items()
                      if k not in ("cpu_ms", "minflt", "nivcsw")}
    assert got(ctx) is None
    ctx["spans"] = [s for s in parse_job()["spans"]
                    if s["name"] not in ("job.cli", "dataset.parse")]
    assert got(ctx) is None
    assert got({"spans": [], "jobs": 1}) is None
    # a span recorded without attributes, as in the older recorded jobs
    assert got({"jobs": 1, "spans": [{"name": span_name, "t0": 0.0,
                                      "dur": 1.0}]}) is None


# ---------------------------------- the native parse is its five steps
def test_the_native_parse_less_its_five_steps_is_under_two_percent():
    ctx = parse_job()
    native = span(ctx, "dataset.parse.native")
    bare_ms = read(ctx, "job_unspanned_ms_per_job",
                   {"span": "dataset.parse.native", "children": list(STEPS)})
    assert 0.0 <= bare_ms < 0.02 * native["dur"] * 1000.0
    steps = sorted((s for s in ctx["spans"] if s["name"] in STEPS),
                   key=lambda s: s["t0"])
    assert [s["name"] for s in steps] == list(STEPS)
    for a, b in zip(steps, steps[1:]):
        assert a["t0"] + a["dur"] <= b["t0"]
    assert steps[0]["t0"] >= native["t0"]
    assert steps[-1]["t0"] + steps[-1]["dur"] <= native["t0"] + native["dur"]
    # the steps' attributes
    assert span(ctx, "dataset.parse.count")["attrs"]["rows"] == \
        span(ctx, "dataset.parse.native")["attrs"]["rows"]
    assert span(ctx, "dataset.parse.ids")["attrs"]["columns"] == 1
    # the threads asked of the library: 0, the host's cores
    assert span(ctx, "dataset.parse.fields")["attrs"]["threads"] == 0


def with_steps(rec):
    """`rec` with the five steps laid inside its `dataset.parse.native`:
    1, 5, 13, 0.5 and 10.3 ms of its 30, in order, 0.2 ms left over."""
    out = copy.deepcopy(rec)
    native = span(out, "dataset.parse.native")
    at = native["t0"]
    for name, ms in zip(STEPS, (1.0, 5.0, 13.0, 0.5, 10.3)):
        out["spans"].append({"name": name, "t0": at, "dur": ms / 1000.0})
        at += ms / 1000.0
    return out


def knn_job():
    with open(os.path.join(HERE, "data", "events_knn_job.json")) as fh:
        rec = json.load(fh)
    ann = rec["annotations"][0]
    return {"spans": rec["spans"], "devices": rec["devices"],
            "window_ns": (ann[1], ann[1] + ann[2]), "jobs": 1, "notes": {}}


@pytest.mark.parametrize("name", ["train_parse_ms_per_job",
                                  "job_unspanned_ms_per_job",
                                  "idle_named_share", "device_wait_ms_per_job"])
def test_the_readers_that_list_the_native_parse_read_what_they_read(name):
    """Their lists name `dataset.parse.native`, whose interval is what it
    was: the steps inside it change no number."""
    before, after = knn_job(), with_steps(knn_job())
    assert read(after, name) == pytest.approx(read(before, name), rel=1e-12)


def test_the_forest_readers_read_what_they_read_with_the_steps():
    with open(os.path.join(HERE, "data", "events_forest_job.json")) as fh:
        rec = json.load(fh)
    ann = rec["annotations"][0]
    before = {"spans": rec["spans"], "devices": rec["devices"],
              "window_ns": (ann[1], ann[1] + ann[2]), "jobs": 1, "notes": {}}
    after = with_steps(before)
    for name in ("forest_unspanned_ms_per_job", "forest_idle_named_share",
                 "train_parse_ms_per_job"):
        assert read(after, name) == pytest.approx(read(before, name),
                                                  rel=1e-12), name


def test_the_idle_rows_name_the_steps_where_they_named_the_native_parse():
    """`breakdown.idle_gaps` names a gap by the innermost span of the
    job's thread: the 30 ms the chip idles in the parse go to its five
    steps, and what none covers to `dataset.parse.native`; the rows still
    add up to the idle time."""
    before, after = knn_job(), with_steps(knn_job())
    lo, hi = after["window_ns"]
    ops = next(iter(after["devices"].values()))["ops"]
    was = run.idle_namer(before, lo, hi)(ops)
    got = run.idle_namer(after, lo, hi)(ops)
    assert after["notes"]["idle_gaps_named_by"] == "span"
    assert was["dataset.parse.native"] == pytest.approx(30e6)
    assert got["dataset.parse.native"] == pytest.approx(0.2e6, abs=1.0)
    for name, ms in zip(STEPS, (1.0, 5.0, 13.0, 0.5, 10.3)):
        assert got[name] == pytest.approx(ms * 1e6, abs=1.0), name
    assert sum(got.values()) == pytest.approx(sum(was.values()), rel=1e-12)
    assert {k: v for k, v in got.items() if k not in STEPS
            and k != "dataset.parse.native"} == pytest.approx(
        {k: v for k, v in was.items() if k != "dataset.parse.native"})


def test_a_landing_names_no_gap_of_the_jobs_thread():
    """The waiter's span is another thread's: the rows stay as they were."""
    ctx = knn_job()
    for s in ctx["spans"]:
        s["tid"] = 1
    lo, hi = ctx["window_ns"]
    ops = next(iter(ctx["devices"].values()))["ops"]
    was = run.idle_namer(ctx, lo, hi)(ops)
    put = span(ctx, "knn.index.put")
    ctx["spans"].append({"name": "knn.index.put.landed", "t0": put["t0"],
                         "dur": 0.05, "tid": 2, "attrs": {"landed": True}})
    assert run.idle_namer(ctx, lo, hi)(ops) == was
    root, _slack = reduce.clock_join(ctx["spans"], "job.cli", lo, hi)
    landed = span(ctx, "knn.index.put.landed")
    # its end on the device's clock, by the same join: 31 + 50 ms
    assert reduce.on_profiler_clock(landed, root, lo)[1] == pytest.approx(
        lo + 80e6, abs=1.0)


# -------------------------------------------------- the six entries
def test_the_six_entries_stand_after_the_accepted_ones_in_order():
    names = [m["name"] for m in DOC["per_layer"]]
    pins.hold_in_order(names, ["forest_segments_device_ms_per_job", *SIX])
    entries = {m["name"]: m for m in DOC["per_layer"]}
    for name, (unit, source, layer, cells) in SIX.items():
        m = entries[name]
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
        assert (m["better"], m["moves"]) == ("lower", "job_s")
        assert m["workloads"] == cells, name
        spec = manifest.Manifest().metric(name)
        assert spec["unit"] == unit


def test_no_cell_reports_one_of_the_six_it_is_not_listed_for():
    man = manifest.Manifest()
    for cell in ALL:
        have = set(pins.cell_names(man, cell)) & set(SIX)
        assert have == {n for n, row in SIX.items() if cell in row[3]}, cell
