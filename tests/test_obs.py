"""avenir-trace: span flight recorder, latency histograms, coverage.

The telemetry contracts this suite pins:
1. Ring — bounded memory under overflow, NEWEST spans retained, the
   drop count surfaced; Chrome-trace export matches the complete-event
   schema (cat/ph/ts/dur) Perfetto and chrome://tracing load.
2. Histograms — ``merge`` is associative/commutative and exact
   (counts/sums additive, the repo's fold-state algebra); quantiles are
   exact on known inputs; JSON round-trip is lossless.
3. Coverage — a real manifest stream entry passes the mandatory-span
   audit; a deliberately de-instrumented fold FAILS it (instrumentation
   cannot silently rot); a broken entry raises, not passes.
4. Surfaces — metrics.json renders; trace_report rolls a real export
   into phase/stall tables.
"""

import json
import threading

import pytest

from avenir_tpu.analysis.manifest import stream_kernel_names
from avenir_tpu.obs import trace
from avenir_tpu.obs.histogram import LatencyHistogram
from avenir_tpu.obs.trace import SpanRecorder


def _given(attrs):
    """A span's attributes less the resource counters every span adds."""
    return {k: v for k, v in (attrs or {}).items()
            if k not in trace.USAGE_ATTRS}


# ------------------------------------------------------------------- ring
def test_ring_overflow_keeps_newest_spans():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.record(f"s{i}", t0=float(i), dur=0.001)
    assert len(rec) == 8
    assert rec.dropped == 12
    names = [sp.name for sp in rec.spans()]
    assert names == [f"s{i}" for i in range(12, 20)]  # oldest dropped
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_ring_is_thread_safe_under_concurrent_records():
    rec = SpanRecorder(capacity=64)
    n_threads, per_thread = 8, 500

    def hammer(k):
        for i in range(per_thread):
            rec.record(f"t{k}", t0=0.0, dur=1e-6)

    threads = [threading.Thread(target=hammer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec) == 64
    assert rec.dropped == n_threads * per_thread - 64


def test_chrome_export_schema(tmp_path):
    rec = SpanRecorder(capacity=16)
    rec.record("stream.read", t0=1.0, dur=0.25, attrs={"nbytes": 7})
    rec.record("stream.fold", t0=1.25, dur=0.5)
    path = rec.export_chrome(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert len(events) == 2
    for ev in events:
        # the Chrome-trace complete-event contract: cat/ph/ts/dur with
        # microsecond timestamps
        assert ev["ph"] == "X"
        assert ev["cat"] == "avenir"
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
    assert events[0]["name"] == "stream.read"
    assert events[0]["ts"] == pytest.approx(1.0e6)
    assert events[0]["dur"] == pytest.approx(0.25e6)
    assert events[0]["args"] == {"nbytes": 7}
    assert doc["metadata"]["dropped_spans"] == 0


def test_record_is_noop_when_disabled():
    with trace.capture() as rec:
        trace.record("on", trace.now())
        prev = trace.set_enabled(False)
        try:
            trace.record("off", trace.now())
            trace.observe("off_hist", 1.0)
            with trace.span("off_span"):
                pass
        finally:
            trace.set_enabled(prev)
        trace.record("on2", trace.now())
    names = [sp.name for sp in rec.spans()]
    assert names == ["on", "on2"]


def test_span_context_manager_records_on_exception():
    with trace.capture() as rec:
        with pytest.raises(RuntimeError):
            with trace.span("risky", tag="x"):
                raise RuntimeError("boom")
    spans = rec.spans()
    assert [sp.name for sp in spans] == ["risky"]
    assert _given(spans[0].attrs) == {"tag": "x"}
    assert set(trace.USAGE_ATTRS) <= set(spans[0].attrs)


def test_span_yields_its_attributes_so_the_end_can_set_them():
    with trace.capture() as rec:
        with trace.span("parse", path="p") as note:
            note["rows"] = 7
        prev = trace.set_enabled(False)
        try:
            with trace.span("off", path="q") as note:
                note["rows"] = 8          # the same code runs with tracing off
        finally:
            trace.set_enabled(prev)
        with trace.span("bare"):
            pass
    assert [(sp.name, _given(sp.attrs)) for sp in rec.spans()] == [
        ("parse", {"path": "p", "rows": 7}), ("bare", {})]


def test_span_annotates_for_the_profiler_only_when_jax_is_there(monkeypatch):
    """`obs` imports nothing but the standard library: `span` looks `jax`
    up among the loaded modules, and records all the same without it."""
    import sys
    import types

    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    fake = types.SimpleNamespace(
        profiler=types.SimpleNamespace(TraceAnnotation=Annotation))
    with trace.capture() as rec:
        monkeypatch.setitem(sys.modules, "jax", fake)
        with trace.span("outer"):
            with trace.span("inner"):
                pass
        prev = trace.set_enabled(False)
        try:
            with trace.span("off"):
                pass
        finally:
            trace.set_enabled(prev)
        monkeypatch.delitem(sys.modules, "jax")
        with trace.span("no_jax"):
            pass
    assert entered == ["outer", "inner", "/inner", "/outer"]
    assert [sp.name for sp in rec.spans()] == ["inner", "outer", "no_jax"]
    with open(trace.__file__) as fh:
        source = fh.read()
    assert "import jax" not in source and "import numpy" not in source


def test_record_min_suppresses_instant_spans():
    with trace.capture() as rec:
        trace.record_min("stall", trace.now(), min_dur=10.0)   # instant
        trace.record_min("stall", trace.now() - 1.0, min_dur=0.5)
    assert len(rec.spans()) == 1
    assert rec.spans()[0].dur >= 0.5


def test_capture_restores_previous_recorder_and_flag():
    outer = trace.recorder()
    prev = trace.set_enabled(False)
    try:
        with trace.capture() as rec:
            assert trace.enabled()                 # forced on inside
            assert trace.recorder() is rec
        assert trace.recorder() is outer
        assert not trace.enabled()                 # flag restored
    finally:
        trace.set_enabled(prev)


# -------------------------------------------------------------- histograms
def test_histogram_quantiles_exact_on_known_inputs():
    h = LatencyHistogram()
    # 100 samples of one value per decade bucket: every quantile lands
    # on a bucket holding ONE distinct value, so it is exact
    for v, n in ((1.0, 50), (100.0, 45), (10_000.0, 5)):
        for _ in range(n):
            h.add(v)
    assert h.count == 100
    assert h.quantile(0) == 1.0
    assert h.quantile(50) == 1.0
    assert h.quantile(51) == 100.0
    assert h.quantile(95) == 100.0
    assert h.quantile(96) == 10_000.0
    assert h.quantile(99) == 10_000.0
    assert h.quantile(100) == 10_000.0
    assert h.mean == pytest.approx((50 + 4500 + 50_000) / 100.0)
    assert h.min_val == 1.0 and h.max_val == 10_000.0
    with pytest.raises(ValueError):
        h.quantile(101)


def test_histogram_merge_is_associative_and_exact():
    import random

    rng = random.Random(7)
    samples = [rng.lognormvariate(2.0, 1.5) for _ in range(3000)]
    whole = LatencyHistogram().add_many(samples)
    a = LatencyHistogram().add_many(samples[:1000])
    b = LatencyHistogram().add_many(samples[1000:2100])
    c = LatencyHistogram().add_many(samples[2100:])

    def merged(*hs):
        out = LatencyHistogram()
        for h in hs:
            out.merge(h)
        return out

    left = merged(merged(a, b), c)      # (a+b)+c
    right = merged(a, merged(b, c))     # a+(b+c)
    for m in (left, right):
        assert m.counts == whole.counts
        assert m.count == whole.count
        assert m.total == pytest.approx(whole.total)
        assert m.min_val == whole.min_val and m.max_val == whole.max_val
        for p in (50, 95, 99):
            assert m.quantile(p) == pytest.approx(whole.quantile(p))


def test_histogram_empty_and_clamped_values():
    h = LatencyHistogram()
    assert h.summary() == {"count": 0, "mean": 0.0, "min": 0.0,
                           "max": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    h.add(0.0)          # below the lowest edge: clamps into bucket 0
    h.add(-1.0)
    assert h.count == 2
    assert h.quantile(50) in (-1.0, -0.5)   # bucket mean stays exact-ish
    assert h.min_val == -1.0


def test_histogram_json_round_trip():
    h = LatencyHistogram().add_many([0.5, 3.0, 3.0, 250.0])
    blob = json.dumps(h.to_dict())
    back = LatencyHistogram.from_dict(json.loads(blob))
    assert back.counts == h.counts and back.sums == h.sums
    assert back.count == h.count and back.total == h.total
    assert back.min_val == h.min_val and back.max_val == h.max_val
    assert back.summary() == h.summary()


def test_package_hist_accessor_is_the_function_not_a_module():
    """Regression: the histogram submodule was once named ``hist``, and
    importing it shadowed the ``obs.hist(name)`` accessor on the
    package — the __all__-advertised call raised TypeError. The
    submodule is ``histogram`` now; the accessor must stay callable."""
    from avenir_tpu import obs

    assert callable(obs.hist)
    trace.reset_hists()
    try:
        obs.observe("t_pkg_ms", 2.0)
        assert obs.hist("t_pkg_ms").count == 1
        assert obs.hist("t_pkg_never") is None
    finally:
        trace.reset_hists()


def test_process_global_histograms():
    trace.reset_hists()
    try:
        trace.observe("t_obs_ms", 5.0)
        trace.observe("t_obs_ms", 15.0)
        h = trace.hist("t_obs_ms")
        assert h.count == 2
        h.add(1.0)                       # a COPY: the global is untouched
        assert trace.hist("t_obs_ms").count == 2
        assert trace.hist_summaries()["t_obs_ms"]["count"] == 2
        assert trace.hist("never_observed") is None
    finally:
        trace.reset_hists()


# ---------------------------------------------------------------- coverage
class _FakeSpec:
    """A stream-entry stand-in for the auditor's negative paths."""

    name = "fake_stream"
    layouts = (0.01,)

    def __init__(self, run):
        self._run = run

    def prepare(self, workdir):
        return {"dir": workdir}

    def run(self, ctx, layout_mb):
        return self._run(ctx, layout_mb)


def test_coverage_passes_on_real_stream_entry():
    from avenir_tpu.analysis.manifest import stream_entries
    from avenir_tpu.obs.coverage import MANDATORY_SPANS, audit_entry

    spec = next(s for s in stream_entries() if s.name == "nb_stream")
    row = audit_entry(spec)
    assert row["span_coverage_validated"], row
    assert row["missing"] == []
    for name in MANDATORY_SPANS:
        assert row["span_counts"][name] >= 1
    # the tiny audit layout chunks the corpus: per-chunk spans repeat
    assert row["span_counts"]["stream.read"] > 1


@pytest.mark.parametrize("name", stream_kernel_names())
def test_every_stream_entry_emits_the_mandatory_spans(name):
    """The coverage gate over the whole manifest, a case an entry: the
    six one-job-one-scan folds and the two fused scans each leave at
    least one read, parse, fold and finish span."""
    from avenir_tpu.analysis.manifest import stream_entries
    from avenir_tpu.obs.coverage import audit_span_coverage

    spec = next(s for s in stream_entries() if s.name == name)
    (row,) = audit_span_coverage([spec])
    assert row["kernel"] == name
    assert row["span_coverage_validated"] and row["missing"] == [], row


def test_coverage_fails_deliberately_deinstrumented_fold():
    """A fold driven around the instrumented paths (raw reads, no
    SharedScan, no finish span) must FAIL the audit — this is the
    regression the coverage gate exists to catch."""
    from avenir_tpu.obs.coverage import audit_entry

    def blind_run(ctx, layout_mb):
        total = 0
        for chunk in (b"a,b\n" * 10, b"c,d\n" * 10):
            total += len(chunk)          # folds without any spans
        return bytes(total)

    row = audit_entry(_FakeSpec(blind_run))
    assert not row["span_coverage_validated"]
    assert set(row["missing"]) == {"stream.read", "stream.parse",
                                   "stream.fold", "job.finish"}


def test_coverage_broken_entry_raises_not_passes():
    from avenir_tpu.obs.coverage import SpanCoverageError, audit_entry

    def broken_run(ctx, layout_mb):
        raise OSError("corpus went missing")

    with pytest.raises(SpanCoverageError, match="failed to run"):
        audit_entry(_FakeSpec(broken_run))


# ---------------------------------------------------------------- surfaces
def test_stats_renderer_round_trip(tmp_path):
    from avenir_tpu.obs.report import load_metrics, render_metrics

    snap = {"ts_unix": 0.0, "uptime_s": 12.5,
            "queues": {"a": 2, "b": 1},
            "inflight": {"priced_bytes": 1 << 20,
                         "budget_bytes": 3 << 30,
                         "peak_priced_bytes": 2 << 20, "batches": 1},
            "warm": {"pinned_sources": 1, "pinned_bytes": 4096,
                     "hits": 3, "misses": 1},
            "stats": {"served": 7, "failed": 0, "batches": 2,
                      "coalesced": 1, "admission_holds": 0,
                      "compile_warm_dispatches": 2, "warm_hits": 3},
            "hists": {"queue_wait_ms": LatencyHistogram().add_many(
                [2.0, 8.0, 40.0]).summary()}}
    path = tmp_path / "metrics.json"
    path.write_text(json.dumps(snap))
    text = render_metrics(load_metrics(str(tmp_path)))   # dir form
    assert "3 queued across 2 tenant(s)" in text
    assert "a=2" in text and "b=1" in text
    assert "queue_wait_ms" in text and "p99" in text
    assert "served: 7" in text


def test_trace_report_rolls_phases_and_stalls(tmp_path):
    import tools.trace_report as tr

    rec = SpanRecorder()
    rec.record("stream.read", t0=0.0, dur=0.010)
    rec.record("stream.parse", t0=0.010, dur=0.020)
    for i in range(3):
        rec.record("stream.fold", t0=0.030 + i * 0.1, dur=0.090,
                   attrs={"sink": "nb", "chunk": i})
    rec.record("stream.stall.consumer", t0=0.35, dur=0.200,
               attrs={"nbytes": 100})
    path = rec.export_chrome(str(tmp_path / "trace.json"))
    report = tr.build_report(path)
    assert report["spans"] == 6
    phases = {r["phase"]: r for r in report["phases"]}
    assert phases["stream.fold"]["count"] == 3
    assert phases["stream.fold"]["total_ms"] == pytest.approx(270.0)
    # stalls rank separately and never hide inside the work phases
    assert "stream.stall.consumer" not in phases
    assert report["stalls"][0]["stall"] == "stream.stall.consumer"
    assert report["stalls"][0]["total_ms"] == pytest.approx(200.0)
    folds = {r["sink"]: r for r in report["folds"]}
    assert folds["nb"]["chunks"] == 3
    # nothing nests here: every span's self time is its own
    assert all(r["self_ms"] == r["total_ms"] for r in report["phases"])
    # the CLI renders without error and exits 0
    assert tr.main([path]) == 0
    # the bare JSON-array Chrome-trace form loads too
    doc = json.load(open(path))
    alt = str(tmp_path / "array.json")
    json.dump(doc["traceEvents"], open(alt, "w"))
    assert tr.build_report(alt)["spans"] == 6
    # a malformed file is a friendly rc=2, not a traceback
    bad = str(tmp_path / "bad.json")
    open(bad, "w").write("not json")
    assert tr.main([bad]) == 2


def test_trace_report_self_time_counts_nested_spans_once(tmp_path):
    """A parent only encloses: its self time is its duration less what
    the spans inside it on its own thread cover, and self times add up
    to the outermost span's duration."""
    import tools.trace_report as tr

    rec = SpanRecorder()
    rec.record("job.cli", t0=0.0, dur=1.0, tid=1)
    rec.record("dataset.parse", t0=0.1, dur=0.4, tid=1)
    rec.record("dataset.read", t0=0.1, dur=0.1, tid=1)
    rec.record("dataset.encode", t0=0.25, dur=0.25, tid=1)
    rec.record("knn.query.fetch", t0=0.6, dur=0.1, tid=1)
    rec.record("knn.query.fetch", t0=0.8, dur=0.1, tid=1)
    rec.record("stream.stall.consumer", t0=0.5, dur=0.05, tid=1)
    # another thread's span inside the root's interval is not the root's
    rec.record("stream.parse", t0=0.3, dur=0.2, tid=2)
    report = tr.build_report(rec.export_chrome(str(tmp_path / "t.json")))
    self_ms = {r["phase"]: r["self_ms"] for r in report["phases"]}
    assert self_ms == pytest.approx({
        "job.cli": 350.0, "dataset.parse": 50.0, "dataset.read": 100.0,
        "dataset.encode": 250.0, "knn.query.fetch": 200.0,
        "stream.parse": 200.0})
    total = {r["phase"]: r["total_ms"] for r in report["phases"]}
    assert total["job.cli"] == pytest.approx(1000.0)
    assert total["dataset.parse"] == pytest.approx(400.0)
    # with the stall's 50 ms the root thread's self times are the root
    assert sum(v for k, v in self_ms.items() if k != "stream.parse") + 50.0 \
        == pytest.approx(1000.0)
