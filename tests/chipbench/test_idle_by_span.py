"""The traced line's account of idle time (PR 38): `breakdown.idle_gaps`
puts every part of the first chip's idle time down to the innermost span
of the job's thread the host was in, or `no span`, through the clock join
the span readers use (`reduce.clock_join`); `notes.by_chip` gives each chip
of a cell of several its busy time, idle share and rows; the device
operations' names for the gaps go to `notes.idle_by_neighbours`. And the
per-layer readers read what they read before: every metric PR 37 had, over
the four recorded jobs, against the values PR 37's tree read
(data/accepted_metric_values.json), with and without threads on the
spans."""

import json
import os

import pytest

from bench_fixtures import ROOT  # noqa: F401

from chipbench import manifest, reduce, run

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "accepted_metric_values.json")) as _fh:
    ACCEPTED = json.load(_fh)["values"]
#: each recorded job, its cell and the semantic size `n` of its test
RECORDED = {"events_knn_job.json": ("knn-elearn.bulk", 10_485_760),
            "events_forest_job.json": ("rf-hangup.rebuild", 1_000_000),
            "events_fia_job.json": ("fia-t10i4.remine", 1_000_000),
            "events_mesh4_job.json": ("fia-t10i4-mesh4.remine", 4_000_000)}
SIZES = {"nq": 1024, "d": 9, "k": 5, "kernel_calls": 1, "trees": 10,
         "splits": 18, "segments": 2, "classes": 2, "levels": 3,
         "items": 1000, "frequent": 800, "max_length": 3,
         "candidates": {2: 319_600, 3: 2_000}}
HBM = {"peak": 5_500_000_000, "in_use_peak": 500_000_000,
       "reserved": 5_000_000_000}
JOB_THREAD, PREFETCHER = 11, 12


def recorded(name, threads=False):
    """A recorded job as `run.traced_job` hands it on; with `threads`, its
    spans carry a thread and attributes as a chip run's do: the
    prefetcher's `stream.parse` its own, every other the job's."""
    with open(os.path.join(HERE, "data", name)) as fh:
        rec = json.load(fh)
    if threads:
        for s in rec["spans"]:
            s["tid"] = PREFETCHER if s["name"] == "stream.parse" else JOB_THREAD
            s["attrs"] = {"rows": 7}
    ann = rec["annotations"][0]
    return {"spans": rec["spans"], "devices": rec["devices"],
            "window_ns": (ann[1], ann[1] + ann[2]), "jobs": 1, "notes": {},
            "compiles": ({"xla_compiles": 30, "compile_cache_hits": 12},
                         {"xla_compiles": 33, "compile_cache_hits": 14}),
            "memory_peak_bytes": HBM["peak"],
            "memory_live_peak_bytes": HBM["in_use_peak"],
            "memory_reserved_bytes": HBM["reserved"],
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "sizes": dict(SIZES, n=RECORDED[name][1]), "lines": rec["lines"]}


# ------------------------------------------- the readers read as they read
@pytest.mark.parametrize("metric", sorted(ACCEPTED["events_knn_job.json"]))
def test_every_accepted_metric_reads_what_it_read_at_pr_37(metric):
    """No reader changed what it returns: over each recorded job, with the
    spans as recorded (no thread) and with the threads a chip run now
    keeps, the value PR 37's tree read, to 1e-9; nothing where it found
    nothing."""
    man = manifest.Manifest()
    spec = man.metric(metric)
    for name in RECORDED:
        want = ACCEPTED[name][metric]
        for threads in (False, True):
            got = man.reader(spec["reader"])(recorded(name, threads),
                                             spec.get("params", {}))
            if want is None:
                assert got is None, (name, threads)
            else:
                assert got == pytest.approx(want, rel=1e-9, abs=1e-9), (
                    name, threads)


def test_the_table_holds_every_metric_pr_37_had():
    names = [m["name"] for m in manifest.Manifest().doc["per_layer"]]
    for values in ACCEPTED.values():
        assert list(values) == sorted(names[:43])


# ------------------------------------------------ the line's idle rows
def traced(name, cell=None, threads=True, edit=None):
    """`run.per_layer` over a recorded job, as a chip run's `--trace 1`
    makes it: (metrics, the line's extra keys, the ctx)."""
    man = manifest.Manifest()
    ctx = recorded(name, threads)
    if edit:
        edit(ctx)
    sizes = ctx.pop("sizes")
    metrics, extra = run.per_layer(man.cell(cell or RECORDED[name][0]), man,
                                   ctx, sizes, {"kind": "TPU v5 lite"}, HBM)
    return metrics, extra, ctx


def first_chip_idle_s(ctx, chip=0):
    lo, hi = ctx["window_ns"]
    ops = list(ctx["devices"].values())[chip]["ops"]
    return sum(e - s for s, e in reduce.idle_gaps(ops, lo, hi)) / 1e9


def test_each_part_of_a_gap_goes_to_the_innermost_span_of_the_jobs_thread():
    """The recorded kNN job (its note gives the spans on the profiler's
    clock): the idle gaps 1-38, 39-44, 44.5-78 and 94-101 ms. 46-51 ms lies
    in `job.run` alone; 100.6-101 after `job.cli` has ended, in no span;
    the prefetcher's `stream.parse`, on its own thread, names nothing."""
    _m, extra, ctx = traced("events_knn_job.json")
    rows = extra["breakdown"]["idle_gaps"]
    assert [r[0] for r in rows] == [
        "dataset.parse.native", "nb.feature_prob.continuous", "knn.index.put",
        "job.run", "knn.output.write", "nb.fit", "knn.query.fetch",
        "nb.feature_prob.binned", reduce.NO_SPAN, reduce.REST]
    want = [0.030, 0.024, 0.009, 0.005, 0.0046, 0.0045, 0.004, 0.001, 0.0004]
    for (_name, got), w in zip(rows, want):
        assert got == pytest.approx(w, abs=1e-12)
    # the rest is rounding: spans that meet at one instant on the host's
    # clock overlap by a few femtoseconds on the profiler's
    assert rows[-1][1] == pytest.approx(0.0, abs=1e-12)
    assert sum(s for _n, s in rows) == pytest.approx(0.0825, rel=1e-9)
    notes = extra["notes"]
    assert notes["idle_gaps_named_by"] == "span"
    assert notes["clock_join_slack_ms"] == pytest.approx(0.4, rel=1e-6)
    assert "by_chip" not in notes                      # one chip


def test_the_rows_add_up_to_the_first_chips_idle_time_in_every_recorded_job():
    for name in RECORDED:
        _m, extra, ctx = traced(name)
        rows = extra["breakdown"]["idle_gaps"]
        assert len(rows) <= 10
        assert sum(s for _n, s in rows) == pytest.approx(
            first_chip_idle_s(ctx), rel=1e-9, abs=1e-12), name
        names = {s["name"] for s in ctx["spans"]} | {reduce.NO_SPAN,
                                                     reduce.REST}
        assert {r[0] for r in rows} <= names, name


def test_the_gaps_named_by_operations_move_to_the_notes():
    """What `breakdown.idle_gaps` held until PR 37: it still says which
    program ends a gap."""
    _m, extra, ctx = traced("events_knn_job.json")
    lo, hi = ctx["window_ns"]
    ops = ctx["devices"]["/device:TPU:0"]["ops"]
    assert extra["notes"]["idle_by_neighbours"] == \
        reduce.idle_by_neighbours(ops, lo, hi)
    assert extra["notes"]["idle_by_neighbours"][0][0] == \
        "job start -> copy.1"


def test_a_span_recorded_without_a_thread_is_the_jobs():
    """The files recorded before PR 38 carry no thread: every span counts
    as the job thread's, the prefetcher's `stream.parse` too (76.2-76.8 ms,
    inside the fetch, which keeps 1.4 of its 2 ms there)."""
    _m, extra, _ctx = traced("events_knn_job.json", threads=False)
    got = dict(extra["breakdown"]["idle_gaps"])
    assert got["stream.parse"] == pytest.approx(0.0006, abs=1e-12)
    assert got["knn.query.fetch"] == pytest.approx(0.0034, abs=1e-12)


def test_another_threads_span_names_no_gap_though_a_list_names_it():
    """A wait recorded on the prefetcher's thread under a leaf's name is
    not the job's: the line's rows do not see it, and `idle_named_share`,
    whose reader keeps its list (PR 38 changes no reader), still does."""
    def add(ctx):
        fetch = next(s for s in ctx["spans"] if s["name"] == "knn.query.fetch")
        ctx["spans"].append({"name": "stream.stall.consumer", "tid": PREFETCHER,
                             "t0": fetch["t0"] - 0.030, "dur": 0.020})
    metrics, extra, _ctx = traced("events_knn_job.json", edit=add)
    assert "stream.stall.consumer" not in dict(extra["breakdown"]["idle_gaps"])
    assert "stream.stall.consumer" in dict(extra["notes"]["idle_by_span"])
    _m, plain, _ctx = traced("events_knn_job.json")
    assert extra["breakdown"] == plain["breakdown"]


def test_a_parent_keeps_the_time_no_leaf_of_it_covers():
    def drop(ctx):
        ctx["spans"] = [s for s in ctx["spans"]
                        if s["name"] != "nb.feature_prob.continuous"]
    _m, extra, _ctx = traced("events_knn_job.json", edit=drop)
    got = dict(extra["breakdown"]["idle_gaps"])
    assert got["nb.feature_prob"] == pytest.approx(0.024, abs=1e-12)
    assert got[reduce.NO_SPAN] == pytest.approx(0.0004, abs=1e-12)


def test_nine_names_and_the_rest_where_more_spans_share_the_idle_time():
    """The driver keeps ten rows a list: nine named, the tenth `rest`,
    which holds what the others leave, so the rows still add up."""
    def many(ctx):
        parse = next(s for s in ctx["spans"]
                     if s["name"] == "dataset.parse.native")
        for i in range(12):           # 2 ms each, inside the parse
            ctx["spans"].append({"name": f"dataset.part{i:02d}",
                                 "tid": JOB_THREAD,
                                 "t0": parse["t0"] + 0.002 * i,
                                 "dur": 0.002 - 0.0001 * i})
    _m, extra, ctx = traced("events_knn_job.json", edit=many)
    rows = extra["breakdown"]["idle_gaps"]
    assert len(rows) == 10 and rows[-1][0] == reduce.REST
    assert rows[0] == ["nb.feature_prob.continuous", pytest.approx(0.024)]
    assert sum(s for _n, s in rows) == pytest.approx(0.0825, rel=1e-9)
    assert reduce.top_with_rest({"a": 3.0, "b": 2.0}, 1) == [
        ["a", 3e-9], ["b", 2e-9]]                   # no rest of one name
    assert reduce.top_with_rest({"a": 3.0, "b": 2.0, "c": 1.0}, 1) == [
        ["a", 3e-9], [reduce.REST, 3e-9]]


@pytest.mark.parametrize("edit, why", [
    (lambda ctx: next(s for s in ctx["spans"]
                      if s["name"] == "job.cli").update(dur=0.0985),
     "slack"),
    (lambda ctx: ctx.update(spans=[s for s in ctx["spans"]
                                   if s["name"] != "job.cli"]),
     "not one job.cli")],
    ids=["join_refused", "no_root_span"])
def test_where_the_join_fails_the_gaps_are_named_by_operations(edit, why):
    _m, extra, ctx = traced("events_knn_job.json", edit=edit)
    lo, hi = ctx["window_ns"]
    ops = ctx["devices"]["/device:TPU:0"]["ops"]
    assert extra["breakdown"]["idle_gaps"] == reduce.top_with_rest(
        reduce.neighbour_gaps(ops, lo, hi), run.IDLE_ROWS)
    said = extra["notes"]["idle_gaps_named_by"]
    assert said.startswith("operations") and why in said
    assert sum(s for _n, s in extra["breakdown"]["idle_gaps"]) == \
        pytest.approx(0.0825, rel=1e-9)


def test_a_row_a_chip_on_four_chips():
    """The recorded four-chip job: every chip's busy time, idle share and
    five rows by span with the rest, adding up to that chip's idle time;
    `breakdown` stays the first chip's."""
    _m, extra, ctx = traced("events_mesh4_job.json")
    lo, hi = ctx["window_ns"]
    chips = extra["notes"]["by_chip"]
    assert [c["chip"] for c in chips] == [f"/device:TPU:{i}" for i in range(4)]
    for i, (row, dev) in enumerate(zip(chips, ctx["devices"].values())):
        busy = reduce.busy_ns(dev["ops"], lo, hi) / 1e9
        assert row["busy_s"] == pytest.approx(busy, rel=1e-12)
        assert row["idle_pct"] == pytest.approx(100 * (1 - busy / 0.1))
        assert len(row["idle_gaps"]) <= 6
        assert sum(s for _n, s in row["idle_gaps"]) == pytest.approx(
            first_chip_idle_s(ctx, i), rel=1e-9)
    # busy 12, 12.5, 13 and 16 ms (test_mesh4.py): the mean is device.busy_s
    assert [c["busy_s"] for c in chips] == pytest.approx(
        [0.012, 0.0125, 0.013, 0.016])
    assert extra["device"]["busy_s"] == pytest.approx(0.053500 / 4)
    first = chips[0]["idle_gaps"]
    assert extra["breakdown"]["idle_gaps"][:5] == first[:5]
    # the slowest chip's Gram ends 1.8 ms after the first's: the first
    # chip waits in the fetch, inside `fia.round.fetch`, longer
    fetch = [dict(c["idle_gaps"]).get("fia.round.fetch", 0.0) for c in chips]
    assert fetch[0] > fetch[3]
