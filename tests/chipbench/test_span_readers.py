"""The two readers that name the host's time by the program's own spans,
`span_self` and `idle_by_span`, and every metric file that reads those
spans, on a small recorded kNN job (data/events_knn_job.json): a root,
seven leaves, one stretch no span covers, and idle gaps that straddle the
leaves' edges."""

import copy
import json
import os

import pytest

import pins
from bench_fixtures import ROOT

from chipbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    DOC = json.load(_fh)


@pytest.fixture()
def ctx():
    with open(os.path.join(HERE, "data", "events_knn_job.json")) as fh:
        recorded = json.load(fh)
    ann = recorded["annotations"][0]
    return {"spans": recorded["spans"], "devices": recorded["devices"],
            "window_ns": (ann[1], ann[1] + ann[2]), "jobs": 1, "notes": {}}


def read(ctx, name):
    man = manifest.Manifest()
    spec = man.metric(name)
    return man.reader(spec["reader"])(ctx, spec["params"])


def span(ctx, name):
    return next(s for s in ctx["spans"] if s["name"] == name)


EXPECTED = {
    "train_parse_ms_per_job": 30.0,
    "index_build_ms_per_job": 10.0,
    "nb_fit_ms_per_job": 5.0,
    "nb_posterior_ms_per_job": 25.0,
    "device_wait_ms_per_job": 20.0,
    "output_write_ms_per_job": 4.6,
    "job_unspanned_ms_per_job": 5.0,             # 45-50 ms of the job
    "idle_named_share": 100.0 * 77.1 / 82.5,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_new_metric_file_reads_the_recorded_job(ctx, name):
    assert read(ctx, name) == pytest.approx(EXPECTED[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_new_metric_has_its_entry_cells_and_layer(name):
    """Its entry as PR 25 wrote it; its cells at least those it has had
    (the three `nb_*` the weighted cell, the rest both), and more may be
    listed."""
    pins.hold_span_metric_entry(DOC, name)


def test_the_new_entries_come_last_and_the_old_stand_as_they_were():
    """PR 24's eight, then PR 25's eight (the ones this file reads the
    recorded job with); what follows the sixteenth is free."""
    pins.hold_the_first_sixteen(DOC)
    assert sorted(EXPECTED) == sorted(pins.SPAN_EIGHT)


ENCODE = "train_encode_ms_per_job"


def test_the_seventeenth_metric_reads_the_encode_leaf(ctx):
    """`dataset.encode` is a leaf of `dataset.parse`: the recorded job
    spends 6 of the parse's 30 ms there."""
    entry = DOC["per_layer"][16]
    assert entry["name"] == ENCODE and entry["layer"] == "Parse / replay"
    assert (entry["source"], entry["moves"]) == ("program_span", "job_s")
    assert set(entry["workloads"]) == {w["name"] for w in DOC["workloads"]}
    parse = span(ctx, "dataset.parse")
    ctx["spans"].append({"name": "dataset.encode", "t0": parse["t0"] + 0.002,
                         "dur": 0.006})
    assert read(ctx, ENCODE) == pytest.approx(6.0, rel=1e-6)
    assert read(ctx, "train_parse_ms_per_job") == pytest.approx(30.0, rel=1e-6)


def test_idle_is_named_by_the_leaf_the_host_was_in(ctx):
    read(ctx, "idle_named_share")
    rows = ctx["notes"]["idle_by_span"]
    assert [r[0] for r in rows] == [
        "dataset.parse.native", "nb.feature_prob.continuous", "knn.index.put",
        "unnamed", "knn.output.write", "nb.fit", "knn.query.fetch",
        "nb.feature_prob.binned"]
    got = dict(rows)
    # gap 1-38 ms: 30 in the parse, 7 in the put; gap 39-44: 2 more in the
    # put, 3 in the fit; gap 44.5-78: 1.5 in the fit, 5 in no span, 1 and
    # 24 in the posterior's two leaves, 2 in the fetch; gap 94-101: 2 in the fetch, 4.6 in
    # the write, 0.4 after job.cli has ended
    assert got == pytest.approx({
        "dataset.parse.native": 0.030, "knn.index.put": 0.009,
        "nb.fit": 0.0045, "nb.feature_prob.binned": 0.001,
        "nb.feature_prob.continuous": 0.024, "knn.query.fetch": 0.004,
        "knn.output.write": 0.0046, "unnamed": 0.0054}, rel=1e-6)


def test_the_rows_add_up_to_the_idle_time_of_device_idle_share(ctx):
    read(ctx, "idle_named_share")
    idle_s = read(ctx, "device_idle_share") / 100.0 * 0.100
    assert idle_s == pytest.approx(0.0825)
    assert sum(s for _n, s in ctx["notes"]["idle_by_span"]) == \
        pytest.approx(idle_s, rel=1e-9)


def test_the_clock_joins_slack_is_noted(ctx):
    assert read(ctx, "idle_named_share") is not None
    assert ctx["notes"]["clock_join_slack_ms"] == pytest.approx(0.4, rel=1e-6)


@pytest.mark.parametrize("dur_s, slack_ms", [(0.0985, 1.5), (0.1002, -0.2)],
                         ids=["root_far_shorter_than_the_window",
                              "root_longer_than_the_window"])
def test_a_join_no_better_than_a_hundredth_of_the_window_is_refused(
        ctx, dur_s, slack_ms):
    span(ctx, "job.cli")["dur"] = dur_s
    assert read(ctx, "idle_named_share") is None
    assert ctx["notes"]["clock_join_slack_ms"] == pytest.approx(slack_ms, rel=1e-6)
    assert "idle_by_span" not in ctx["notes"]


def test_leaves_that_overlap_share_no_idle_time_twice(ctx):
    """The reader goes by its list of names, not by thread: the
    prefetcher's wait for its block has the name of the job thread's, and
    may overlap a leaf."""
    fetch = span(ctx, "knn.query.fetch")
    ctx["spans"].append({"name": "stream.stall.consumer",
                         "t0": fetch["t0"] + 0.001, "dur": 0.030})
    read(ctx, "idle_named_share")
    got = dict(ctx["notes"]["idle_by_span"])
    assert sum(got.values()) == pytest.approx(0.0825, rel=1e-9)
    # the fetch began first and keeps its 4 ms; of the wait what is left
    # counts, 95-106 ms of the job, and the write, which began inside
    # that, gets none
    assert got["knn.query.fetch"] == pytest.approx(0.004, rel=1e-6)
    assert got["stream.stall.consumer"] == pytest.approx(0.005, rel=1e-6)
    assert "knn.output.write" not in got


def test_unspanned_time_is_clipped_to_the_root_and_counts_overlap_once(ctx):
    ctx["spans"] += [
        # a leaf that begins before the root and one that ends after it
        {"name": "dataset.read", "t0": span(ctx, "job.cli")["t0"] - 0.010,
         "dur": 0.012},
        {"name": "knn.output.write", "t0": 50.0992, "dur": 0.010},
        # two leaves over the same 2 ms of the uncovered stretch
        {"name": "knn.query.prepare", "t0": 50.0462, "dur": 0.002},
        {"name": "knn.query.dispatch", "t0": 50.0462, "dur": 0.002}]
    assert read(ctx, "job_unspanned_ms_per_job") == pytest.approx(3.0, rel=1e-6)


def test_a_span_no_list_names_changes_nothing(ctx):
    before = copy.deepcopy(ctx)
    ctx["spans"] = [s for s in ctx["spans"] if s["name"] != "stream.parse"]
    for name in ("job_unspanned_ms_per_job", "idle_named_share"):
        assert read(ctx, name) == pytest.approx(read(before, name))


@pytest.mark.parametrize("name", ["job_unspanned_ms_per_job",
                                  "idle_named_share"])
def test_a_program_without_the_spans_leaves_the_metric_out(ctx, name):
    """The parent commit has no `job.cli`: nothing is returned, nothing
    raised, and the traced line leaves the metric out."""
    ctx["spans"] = [s for s in ctx["spans"]
                    if s["name"] in ("job.run", "stream.parse")]
    assert read(ctx, name) is None
    ctx["spans"] = []
    assert read(ctx, name) is None


def test_idle_by_span_needs_a_device_and_some_idle_time(ctx):
    ctx["devices"]["/device:TPU:0"]["ops"] = [["busy", 0.0, 200e6]]
    assert read(ctx, "idle_named_share") is None
    ctx["devices"] = {}
    assert read(ctx, "idle_named_share") is None


def test_both_lists_name_the_same_leaves_and_no_parent():
    man = manifest.Manifest()
    kids = man.metric("job_unspanned_ms_per_job")["params"]["children"]
    assert kids == man.metric("idle_named_share")["params"]["leaves"]
    assert len(set(kids)) == len(kids)
    for parent in ("job.cli", "job.run", "dataset.parse", "knn.index.build",
                   "nb.feature_prob"):
        assert parent not in kids
    # the prefetcher's spans are another thread's
    assert "stream.read" not in kids and "stream.parse" not in kids
    assert "stream.stall.consumer" in kids
