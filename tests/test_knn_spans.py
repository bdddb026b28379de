"""The spans inside the kNN job (ISSUE 25): which are emitted and how
often, that the job thread's leaves are disjoint and cover the root, that
under a profiler session they stand on the host's plane of the
`.xplane.pb`, what `--trace DIR` leaves behind, and that switching
tracing off changes no output byte. On the CPU the `jnp` route serves,
through the same call sites as the kernels' (it has no `_expand_mixed`,
so no `knn.index.expand`)."""

import glob
import json
import os
import threading

import numpy as np
import pytest

from avenir_tpu import obs
from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.runner import run_from_cli

#: the source's shape in small: an id, int activity fields, and a class
#: whose values the schema does not declare
SCHEMA = {"fields": [
    {"name": "studentID", "ordinal": 0, "id": True, "dataType": "string"},
    {"name": "a1", "ordinal": 1, "dataType": "int", "feature": True,
     "min": 0, "max": 600},
    {"name": "a2", "ordinal": 2, "dataType": "int", "feature": True,
     "min": 0, "max": 200},
    {"name": "a3", "ordinal": 3, "dataType": "int", "feature": True,
     "min": 0, "max": 28},
    {"name": "status", "ordinal": 4, "dataType": "categorical"}]}
TRAIN_ROWS, TEST_ROWS = 200_000, 600

PARENTS = ("job.cli", "job.run", "dataset.parse", "knn.index.build",
           "nb.feature_prob")
#: the job thread's leaves, as the benchmark's two span readers list them
LEAVES = ("dataset.read", "dataset.parse.native", "dataset.encode",
          "dataset.range", "knn.index.extract", "knn.index.expand",
          "knn.index.pad", "knn.index.put", "nb.fit",
          "nb.feature_prob.binned", "nb.feature_prob.continuous",
          "stream.stall.consumer", "knn.query.prepare", "knn.query.dispatch",
          "knn.query.fetch", "knn.output.write")
#: the native parse's steps, inside `dataset.parse.native`
PARSE_LEAVES = ("dataset.parse.count", "dataset.parse.prefill",
                "dataset.parse.fields", "dataset.parse.check",
                "dataset.parse.ids")
#: the landing of the index's and the labels' puts, on a waiter thread
LANDED = "knn.index.put.landed"


def _given(attrs):
    """A span's attributes less the resource counters every span adds."""
    return {k: v for k, v in (attrs or {}).items()
            if k not in obs.USAGE_ATTRS}


def _write_csv(path, rows, seed, first_id):
    rng = np.random.default_rng(seed)
    y = rng.random(rows) < 0.5
    hi = np.array([600, 200, 28])
    x = np.clip(rng.normal(np.where(y, 0.56, 0.44)[:, None], 0.12,
                           (rows, 3)) * hi, 0, hi).astype(int)
    with open(path, "w") as fh:
        for i in range(rows):
            fh.write(f"S{first_id + i:07d},{x[i, 0]},{x[i, 1]},{x[i, 2]},"
                     f"{'pass' if y[i] else 'fail'}\n")


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("knn_spans")
    paths = {k: str(d / v) for k, v in {
        "schema": "schema.json", "train": "train.csv", "test": "test.csv",
        "weighted": "weighted.properties", "plain": "plain.properties"}.items()}
    with open(paths["schema"], "w") as fh:
        json.dump(SCHEMA, fh)
    _write_csv(paths["train"], TRAIN_ROWS, 1, 0)
    _write_csv(paths["test"], TEST_ROWS, 2, 5_000_000)
    common = {"nen.feature.schema.file.path": paths["schema"],
              "nen.top.match.count": "5", "nen.validation.mode": "true",
              "nen.output.class.distr": "true",
              # the test file streams in several blocks of some 4 KB
              "nen.stream.block.size.mb": "0.004", "nen.stream.sidecar": "false"}
    for name, extra in (
            ("weighted", {"nen.kernel.function": "gaussian",
                          "nen.kernel.param": "30",
                          "nen.class.condtion.weighted": "true"}),
            ("plain", {"nen.kernel.function": "none"})):
        with open(paths[name], "w") as fh:
            for key, val in {**common, **extra}.items():
                fh.write(f"{key}={val}\n")
    paths["dir"] = str(d)
    return paths


def _argv(files, flow, out, *flags):
    return ["nearestNeighbor", *flags, "--conf", files[flow],
            files["train"], files["test"], out]


def _run_captured(files, flow, out):
    """The job's spans, after one untimed run that compiles its shapes."""
    run_from_cli(_argv(files, flow, out))
    with obs.capture() as rec:
        run_from_cli(_argv(files, flow, out))
    return rec.spans()


def _blocks(spans):
    return sum(1 for s in spans if s.name == "stream.parse")


@pytest.mark.parametrize("flow", ["weighted", "plain"])
def test_the_job_emits_each_span_as_often_as_it_should(files, flow, tmp_path):
    spans = _run_captured(files, flow, str(tmp_path / "out.csv"))
    count = {}
    for s in spans:
        count[s.name] = count.get(s.name, 0) + 1
    blocks = _blocks(spans)
    assert blocks > 1
    weighted = flow == "weighted"
    want = {"job.cli": 1, "job.run": 1,
            # the train file alone: the test file comes by the block route
            "dataset.parse": 1, "dataset.read": 1, "dataset.parse.native": 1,
            "dataset.encode": 1, "dataset.range": 1,
            **dict.fromkeys(PARSE_LEAVES, 1),
            "knn.index.build": 1, "knn.index.extract": 1, "knn.index.pad": 1,
            # the index, the labels, the posterior
            "knn.index.put": 3,
            "nb.fit": int(weighted), "nb.feature_prob": int(weighted),
            "nb.feature_prob.binned": int(weighted),
            "nb.feature_prob.continuous": int(weighted),
            "knn.query.prepare": blocks, "knn.query.dispatch": blocks,
            "knn.query.fetch": blocks, "knn.output.write": blocks,
            # the jnp route expands nothing
            "knn.index.expand": 0}
    assert {name: count.get(name, 0) for name in want} == want
    known = set(PARENTS) | set(LEAVES) | set(PARSE_LEAVES) | {
        "stream.read", "stream.parse", "stream.stall.producer", LANDED}
    assert set(count) <= known, set(count) - known
    by_name = {s.name: s for s in spans}
    assert _given(by_name["job.cli"].attrs) == {"job": "nearestNeighbor"}
    parse = _given(by_name["dataset.parse"].attrs)
    assert parse == {"path": files["train"], "rows": TRAIN_ROWS,
                     "nbytes": os.path.getsize(files["train"])}
    assert _given(by_name["dataset.read"].attrs) == {"nbytes": parse["nbytes"]}
    assert _given(by_name["dataset.parse.native"].attrs) == {
        "rows": TRAIN_ROWS, "columns": 5}
    assert _given(by_name["dataset.encode"].attrs) == {
        "fields": 1, "rows": TRAIN_ROWS, "native": 1, "vocab": 2}
    assert _given(by_name["dataset.range"].attrs) == {"fields": 3}
    build = by_name["knn.index.build"].attrs
    assert build["rows"] == TRAIN_ROWS and build["attrs"] == 3
    assert build["padded_rows"] >= TRAIN_ROWS
    assert build["nbytes"] == build["padded_rows"] * 3 * 4
    puts = [s.attrs["nbytes"] for s in spans if s.name == "knn.index.put"]
    assert puts == [build["nbytes"], build["padded_rows"] * 4,
                    build["padded_rows"] * 4]
    if weighted:
        assert _given(by_name["nb.fit"].attrs) == {"rows": TRAIN_ROWS}
        assert _given(by_name["nb.feature_prob"].attrs) == {
            "rows": TRAIN_ROWS, "binned": 0, "continuous": 3}
    rows = [s.attrs["rows"] for s in spans if s.name == "knn.query.fetch"]
    assert sum(rows) == TEST_ROWS
    for name in ("knn.query.prepare", "knn.query.dispatch", "knn.output.write"):
        assert [s.attrs["rows"] for s in spans if s.name == name] == rows
    assert {s.attrs["kernel"] for s in spans
            if s.name == "knn.query.dispatch"} == {"jnp"}


def test_leaves_are_disjoint_lie_inside_the_root_and_cover_it(files, tmp_path):
    spans = _run_captured(files, "weighted", str(tmp_path / "out.csv"))
    me = threading.get_ident()
    root = next(s for s in spans if s.name == "job.cli")
    assert root.tid == me
    leaves = sorted((s for s in spans if s.name in LEAVES and s.tid == me),
                    key=lambda s: s.t0)
    # everything but the prefetcher's read and parse is the job thread's
    assert {s.name for s in spans if s.tid != me} <= {
        "stream.read", "stream.parse", "stream.stall.consumer",
        "stream.stall.producer", LANDED}
    for a, b in zip(leaves, leaves[1:]):
        assert a.t0 + a.dur <= b.t0, (a.name, b.name)
    assert leaves[0].t0 >= root.t0
    assert leaves[-1].t0 + leaves[-1].dur <= root.t0 + root.dur
    assert sum(s.dur for s in leaves) >= 0.95 * root.dur
    # parents only enclose
    for parent, kids in (("dataset.parse", "dataset."),
                         ("knn.index.build", "knn.index."),
                         ("nb.feature_prob", "nb.feature_prob.")):
        p = next(s for s in spans if s.name == parent)
        inside = [s for s in leaves if s.name.startswith(kids)
                  and s.t0 < p.t0 + p.dur]
        assert inside and all(p.t0 <= s.t0 and s.t0 + s.dur <= p.t0 + p.dur
                              for s in inside)


def _host_events(trace_dir):
    """{name: [duration_s, ...]} of every event on the host's planes of the
    one `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1, found
    out = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(ev.duration_ns / 1e9)
    return out


@pytest.fixture(scope="module")
def traced(files):
    """One weighted job under `--trace DIR`; (DIR, the output file)."""
    out = os.path.join(files["dir"], "out_traced.csv")
    run_from_cli(_argv(files, "weighted", out))
    trace_dir = os.path.join(files["dir"], "trace")
    obs.recorder().clear()      # a process of its own starts with an empty ring
    run_from_cli(_argv(files, "weighted", out, "--trace", trace_dir))
    return trace_dir, out


def test_under_a_profiler_session_the_spans_stand_on_the_host_plane(traced):
    trace_dir, _out = traced
    on_plane = _host_events(trace_dir)
    with open(os.path.join(trace_dir, "trace.json")) as fh:
        ring = [e for e in json.load(fh)["traceEvents"]]
    by_name = {}
    for ev in ring:
        by_name.setdefault(ev["name"], []).append(ev["dur"] / 1e6)
    through_span = (set(PARENTS) | set(LEAVES) | set(PARSE_LEAVES)) - {
        "job.run", "stream.stall.consumer", "knn.index.expand"}
    assert through_span <= set(by_name)
    for name in sorted(through_span):
        assert name in on_plane, f"{name} is not on the host's plane"
        assert len(on_plane[name]) == len(by_name[name])
        for plane_s, ring_s in zip(sorted(on_plane[name]),
                                   sorted(by_name[name])):
            assert plane_s == pytest.approx(ring_s, abs=1e-3), name
    # retroactive record() sites stay in the ring alone
    assert "job.run" in by_name and "job.run" not in on_plane


def test_trace_flag_leaves_a_device_trace_and_a_trace_json_that_rolls_up(traced):
    import tools.trace_report as tr

    trace_dir, _out = traced
    assert glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    # every phase: the job has more span names than the default top 20
    report = tr.build_report(os.path.join(trace_dir, "trace.json"), top=100)
    phases = {r["phase"]: r for r in report["phases"]}
    assert {"job.cli", "dataset.parse", "knn.index.build", "nb.feature_prob",
            "knn.query.fetch", "jax.profiler.trace"} <= set(phases)
    cli = phases["job.cli"]
    assert cli["self_ms"] < 0.5 * cli["total_ms"]
    assert phases["nb.fit"]["self_ms"] == phases["nb.fit"]["total_ms"]
    # the session encloses the root, and self times add up to it
    events = tr.add_self_times(tr.load_events(
        os.path.join(trace_dir, "trace.json"))[0])
    root = max(events, key=lambda e: e["dur_ms"])
    assert root["name"] == "jax.profiler.trace"
    mine = [e for e in events if e["tid"] == root["tid"]]
    assert sum(e["self_ms"] for e in mine) == pytest.approx(root["dur_ms"],
                                                            rel=1e-9)
    assert all(e["self_ms"] >= -1e-6 for e in events)
    assert tr.main([trace_dir]) == 0


def test_tracing_off_records_nothing_and_changes_no_output_byte(files, traced,
                                                               tmp_path):
    _trace_dir, traced_out = traced
    out_on, out_off = str(tmp_path / "on.csv"), str(tmp_path / "off.csv")
    run_from_cli(_argv(files, "weighted", out_on))
    with obs.capture() as rec:
        was = obs.set_enabled(False)      # what AVENIR_TRACE=0 sets
        try:
            run_from_cli(_argv(files, "weighted", out_off))
        finally:
            obs.set_enabled(was)
    assert len(rec) == 0
    with open(out_on, "rb") as a, open(out_off, "rb") as b, \
            open(traced_out, "rb") as c:
        on = a.read()
        assert on == b.read() == c.read() and on.count(b"\n") == TEST_ROWS
    # without the flag nothing was started or written
    assert sorted(os.listdir(tmp_path)) == ["off.csv", "on.csv"]


def test_the_block_route_names_no_phases(files):
    schema = FeatureSchema.from_file(files["schema"])
    with open(files["test"], "rb") as fh:
        data = fh.read()
    with obs.capture() as rec:
        by_block = Dataset.from_csv(data, schema)
    assert [s.name for s in rec.spans()] == []
    with obs.capture() as rec:
        by_path = Dataset.from_csv(files["test"], schema)
    # the vocabulary is settled before the parse that encodes against it
    assert [s.name for s in rec.spans()] == [
        "dataset.read", "dataset.encode", *PARSE_LEAVES,
        "dataset.parse.native", "dataset.range", "dataset.parse"]
    assert len(by_block) == len(by_path) == TEST_ROWS
    np.testing.assert_array_equal(by_block.labels(), by_path.labels())


# ------------------------------ the exact kernel's counter (ISSUE 37)
COUNTER_SCHEMA = {"fields": [
    {"name": "studentID", "ordinal": 0, "id": True, "dataType": "string"},
    {"name": "a1", "ordinal": 1, "dataType": "int", "feature": True,
     "min": 0, "max": 9999},
    {"name": "a2", "ordinal": 2, "dataType": "int", "feature": True,
     "min": 0, "max": 9999},
    {"name": "status", "ordinal": 3, "dataType": "categorical",
     "cardinality": ["fail", "pass"]}]}


def _counter_dataset(a):
    schema = FeatureSchema.from_json(COUNTER_SCHEMA)
    text = "".join(f"S{i},{x},{y},{'pass' if (x + y) % 2 else 'fail'}\n"
                   for i, (x, y) in enumerate(a.tolist()))
    return Dataset.from_csv(text.encode(), schema)


@pytest.mark.parametrize("corpus", ["falling", "shuffled"])
def test_the_fetch_span_carries_the_exact_kernels_count(corpus, monkeypatch):
    """`knn.query.fetch` says how many train slices the exact kernel
    tested and how many it extracted. In falling order of distance to the
    cohort every slice holds a nearer row, and the kernel does all of
    today's work; on a shuffled corpus of whole numbers a query soon holds
    five rows no later one beats, and most slices are passed over."""
    import functools

    import avenir_tpu.ops.pallas_knn as pk
    from avenir_tpu.models.knn import NearestNeighborClassifier

    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    monkeypatch.setattr(pk, "knn_topk_pallas",
                        functools.partial(pk.knn_topk_pallas, interpret=True))
    rng = np.random.default_rng(37)
    if corpus == "falling":
        # the cohort is one point (the queries' pad rows are zeros too)
        train = np.stack([9999 - np.arange(8192), np.zeros(8192, int)], 1)
        test = np.zeros((300, 2), int)
    else:
        train = rng.integers(0, 16, (65_536, 2))
        test = rng.integers(0, 16, (256, 2))
    knn = NearestNeighborClassifier(_counter_dataset(train))
    assert knn.index.kernel == "exact"
    with obs.capture() as rec:
        knn.predict(_counter_dataset(test))
    fetch, = [s for s in rec.spans() if s.name == "knn.query.fetch"]
    width = pk.slice_rows(knn.index.block)
    blocks = -(-len(test) // 256)
    assert fetch.attrs["rows"] == len(test)
    assert fetch.attrs["slice_rows"] == width == 2048
    assert fetch.attrs["slices"] == blocks * (knn.index.n_padded // width)
    if corpus == "falling":
        assert fetch.attrs["slices"] == 8
        assert fetch.attrs["extracted"] == fetch.attrs["slices"]
    else:
        assert fetch.attrs["slices"] == 32
        assert 1 <= fetch.attrs["extracted"] < fetch.attrs["slices"] / 2
    dispatch, = [s for s in rec.spans() if s.name == "knn.query.dispatch"]
    assert dispatch.attrs["kernel"] == "exact"
