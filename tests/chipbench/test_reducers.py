"""The reducers from spans, counters and device events to numbers, on a
small recorded event list (data/events_small.json): one chip, a traced
job of 10 ms that starts at 1 ms on the profiler's clock."""

import inspect
import json
import os

import pytest

from bench_fixtures import ROOT  # noqa: F401

from chipbench import manifest, reduce

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "events_small.json")) as fh:
        return json.load(fh)


@pytest.fixture()
def ctx(recorded):
    ann = recorded["annotations"][0]
    return {"spans": recorded["spans"], "devices": recorded["devices"],
            "window_ns": (ann[1], ann[1] + ann[2]), "jobs": 1,
            "compiles": ({"xla_compiles": 30, "compile_cache_hits": 12},
                         {"xla_compiles": 33, "compile_cache_hits": 14}),
            "memory_peak_bytes": 5_500_000_000,
            "memory_live_peak_bytes": 500_000_000,
            "memory_reserved_bytes": 5_000_000_000, "notes": {}, "peaks": V5E,
            "sizes": {"nq": 1024, "n": 10_485_760, "d": 9, "k": 5,
                      "kernel_calls": 1}}


def test_busy_is_the_union_clipped_to_the_window(recorded):
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    # in [1 ms, 11 ms]: copy 0.5, fusions 2.0-3.0 (overlapping), kernel
    # 4-8, fusion 8.5-9, convert 9.5-9.6; the second kernel is outside
    assert reduce.busy_ns(ops, 1e6, 11e6) == pytest.approx(6.1e6)
    gaps = reduce.idle_gaps(ops, 1e6, 11e6)
    assert sum(e - s for s, e in gaps) == pytest.approx(3.9e6)
    assert gaps[0] == (1.5e6, 2.0e6) and gaps[-1] == (9.6e6, 11e6)
    assert reduce.merge_intervals([], 0, 10) == []
    assert reduce.idle_gaps([], 0, 10) == [(0, 10)]


def test_kernel_time_by_name_pattern(recorded):
    mods = recorded["devices"]["/device:TPU:0"]["modules"]
    knn = reduce.matching(mods, ["knn_topk", "_vote"])
    assert reduce.total_ns(knn) == pytest.approx(8.5e6)
    assert reduce.total_ns(reduce.matching(mods, ["_fold_batch_kernel"])) == 1e6
    assert reduce.matching(mods, ["no_such_program"]) == []


READINGS = [   # one chip: before job 0, before job 1, at the window's close
    {"bytes_in_use": 30_000, "peak_bytes_in_use": 900, "bytes_reserved": 10_700},
    {"bytes_in_use": 30_000, "peak_bytes_in_use": 1_600, "bytes_reserved": 10_700},
    {"bytes_in_use": 40_000, "peak_bytes_in_use": 1_600, "bytes_reserved": 10_700}]


@pytest.mark.parametrize("readings, want", [
    (READINGS, {"in_use_peak": 1_600, "reserved": 10_700, "peak": 12_300}),
    # scratch let go between two jobs was not held beside the peak
    (READINGS[:1] + [dict(READINGS[1], bytes_reserved=0)] + READINGS[2:],
     {"in_use_peak": 1_600, "reserved": 0, "peak": 1_600}),
    # a backend that says nothing (the CPU)
    ([{}, {}], {"in_use_peak": 0, "reserved": 0, "peak": 0}),
], ids=["held_throughout", "let_go_between_jobs", "backend_says_nothing"])
def test_hbm_peak_adds_what_was_held_throughout(readings, want):
    assert reduce.hbm_peak(readings) == want


def test_top_by_name(recorded):
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    top = reduce.top_by_name(ops, 2)
    assert top[0] == ["knn_topk_pallas.1", pytest.approx(0.008)]
    assert len(top) == 2
    assert reduce.short_name(
        "%copy.1 = f32[8,6]{1,0:T(8,128)} copy(f32[8,6]{0,1} %t.1)") == "copy.1"


def test_idle_gaps_are_named_by_the_operations_around_them(recorded):
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    rows = reduce.idle_by_neighbours(ops, 1e6, 11e6)
    got = dict((n, s) for n, s in rows)
    # idle: 1.5-2.0, 3.0-4.0, 8.0-8.5, 9.0-9.5, 9.6-11.0 ms
    assert sum(got.values()) == pytest.approx(0.0039)
    assert rows[0][0].endswith("-> job end") and rows[0][1] == pytest.approx(0.0014)
    assert all(" -> " in n for n in got)
    assert any(n.endswith("-> knn_topk_pallas.1") and s == pytest.approx(0.001)
               for n, s in rows)
    # a gap of one name met twice is added up, and the list is cut to n
    twice = [["a", 0.0, 1.0], ["b", 2.0, 1.0], ["a", 3.0, 1.0], ["b", 5.0, 1.0]]
    assert reduce.idle_by_neighbours(twice, 0.0, 6.0) == [["a -> b", 2e-9]]
    assert reduce.idle_by_neighbours([], 0.0, 5.0) == [["job start -> job end", 5e-9]]
    assert len(reduce.idle_by_neighbours(ops, 1e6, 11e6, n=2)) == 2


def test_roofline_from_semantic_sizes_names_its_bound():
    ops, nbytes = reduce.knn_manhattan_work(32768, 10_485_760, 6, 5)
    assert ops == 3 * 6 * 32768 * 10_485_760
    assert nbytes == 4 * 6 * 10_485_760 + 4 * 6 * 32768 + 8 * 5 * 32768
    roof = reduce.roofline(ops, nbytes, 10.0, V5E)
    assert roof["bound"] == "compute"
    assert roof["share_pct"] == pytest.approx(100 * ops / 197e12 / 10.0)
    few = reduce.roofline(*reduce.knn_manhattan_work(1, 10_485_760, 6, 5), 1.0, V5E)
    assert few["bound"] == "memory"
    assert reduce.roofline(1.0, 1.0, 0.0, V5E) is None


def test_roofline_work_knows_no_block_padding_or_dtype():
    """The count is a function of the job's semantic sizes alone, so a
    later kernel with other blocks reads against the same work."""
    assert list(inspect.signature(reduce.knn_manhattan_work).parameters) == \
        ["nq", "n", "d", "k", "calls"]


@pytest.mark.parametrize("extra", [{}, {"block_q": 512, "block_t": 4096},
                                   {"padded_rows": 8192, "dtype": "bfloat16"}])
def test_roofline_reader_ignores_kernel_details(ctx, extra):
    man = manifest.Manifest()
    spec = man.metric("knn_kernel_roofline")
    ctx["sizes"].update(extra)
    got = man.reader(spec["reader"])(ctx, spec["params"])
    ops = 3 * 9 * 1024 * 10_485_760
    assert got == pytest.approx(100 * (ops / 197e12) / 8.5e-3)
    assert ctx["notes"]["knn_kernel_roofline_bound"] == "compute"
    assert got <= 100


def test_unknown_device_kind_raises(tmp_path):
    assert reduce.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "source" in reduce.load_peaks("TPU v5 lite")
    with pytest.raises(KeyError, match="no peaks for device kind"):
        reduce.load_peaks("TPU v99")
    with pytest.raises(KeyError):
        reduce.load_peaks("cpu")


EXPECTED = {
    "compiles_in_window": 1.0,                 # 3 asked, 2 from the cache
    "parse_ms_per_job": 0.75,
    "stall_consumer_ms_per_job": 0.0,          # captured, none of 1 ms
    "nb_fold_device_ms_per_job": 1.0,
    "knn_kernel_ms_per_job": 8.5,
    "device_idle_share": 39.0,
    "peak_hbm_gb": 0.5,                        # the live buffers alone
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_metric_file_reads_the_recorded_job(ctx, name):
    man = manifest.Manifest()
    spec = man.metric(name)
    got = man.reader(spec["reader"])(ctx, spec.get("params", {}))
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ["parse_ms_per_job", "knn_kernel_ms_per_job",
                                  "nb_fold_device_ms_per_job",
                                  "knn_kernel_roofline", "device_idle_share",
                                  "peak_hbm_gb"])
def test_a_reader_that_finds_nothing_returns_nothing(ctx, name):
    man = manifest.Manifest()
    spec = man.metric(name)
    ctx.update(spans=[], devices={}, memory_peak_bytes=0,
               memory_live_peak_bytes=0, memory_reserved_bytes=0)
    assert man.reader(spec["reader"])(ctx, spec.get("params", {})) is None


def test_a_program_off_the_path_leaves_its_metric_out(ctx):
    man = manifest.Manifest()
    dev = ctx["devices"]["/device:TPU:0"]
    dev["modules"] = [m for m in dev["modules"] if "fold" not in m[0]]
    spec = man.metric("nb_fold_device_ms_per_job")
    assert man.reader(spec["reader"])(ctx, spec["params"]) is None
