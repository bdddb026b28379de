"""What every `obs.span` says of the process's resources, and
`obs.landed`, the span of a put's landing: the counters read first
touches of fresh pages and CPU time and nothing while the process sleeps;
with tracing off nothing is read; a landing is recorded on a waiter
thread, never before its put ends, and a deleted array ends it without a
raise. The jobs' own landings: `test_parse_spans.py`."""

import mmap
import resource
import threading
import time

import numpy as np
import pytest

from avenir_tpu import obs
from avenir_tpu.obs import trace

MB64 = 64 << 20


def _one(rec, name):
    (sp,) = [s for s in rec.spans() if s.name == name]
    return sp


def test_a_span_counts_the_first_touch_of_fresh_pages():
    """Anonymous pages from `mmap` (4 KB pages: numpy's own large
    allocations may ask for huge pages, a fault each 2 MB)."""
    pages = MB64 // resource.getpagesize()
    buf = mmap.mmap(-1, MB64)
    try:
        fresh = np.frombuffer(buf, np.uint8)
        with obs.capture() as rec:
            with obs.span("touch"):
                fresh[:] = 1
        del fresh
    finally:
        buf.close()
    attrs = _one(rec, "touch").attrs
    assert attrs["minflt"] >= 0.9 * pages
    assert attrs["cpu_ms"] > 0
    assert attrs["nivcsw"] >= 0


def test_a_sleeping_span_burns_next_to_no_cpu():
    with obs.capture() as rec:
        with obs.span("nap"):
            time.sleep(0.05)
    sp = _one(rec, "nap")
    assert sp.dur >= 0.05
    assert 0 <= sp.attrs["cpu_ms"] < 20


def test_the_counters_add_to_what_the_caller_set_and_record_adds_none():
    with obs.capture() as rec:
        with obs.span("parse", path="p") as note:
            note["rows"] = 3
        obs.record("stream.read", obs.now(), nbytes=5)
    assert set(_one(rec, "parse").attrs) == {"path", "rows",
                                             *obs.USAGE_ATTRS}
    assert _one(rec, "stream.read").attrs == {"nbytes": 5}


def test_with_tracing_off_no_counter_is_read(monkeypatch):
    def refuse(who):
        raise AssertionError("getrusage read with tracing off")

    monkeypatch.setattr(trace, "getrusage", refuse)
    with obs.capture() as rec:
        was = obs.set_enabled(False)
        try:
            with obs.span("off", tag=1) as note:
                note["rows"] = 2
            assert obs.landed("off.landed", np.zeros(3), obs.now()) is None
        finally:
            obs.set_enabled(was)
    assert len(rec) == 0 and note == {"tag": 1, "rows": 2}
    # and the same patch is what a span with tracing on runs into
    with obs.capture(), pytest.raises(AssertionError, match="tracing off"):
        with obs.span("on"):
            pass


def test_without_resource_the_counters_are_left_out(monkeypatch):
    monkeypatch.setattr(trace, "getrusage", None)
    with obs.capture() as rec:
        with obs.span("bare", tag="x"):
            pass
    assert _one(rec, "bare").attrs == {"tag": "x"}


def test_a_landing_is_recorded_on_a_waiter_thread_after_its_put():
    import jax.numpy as jnp

    host = np.arange(1 << 20, dtype=np.float32)
    with obs.capture() as rec:
        with obs.span("put"):
            issued = obs.now()
            dev = jnp.asarray(host)
        waiter = obs.landed("put.landed", (dev, None, {"b": dev[:2]}), issued,
                            what="index")
        waiter.join(timeout=30)
    assert not waiter.is_alive()
    put, got = _one(rec, "put"), _one(rec, "put.landed")
    assert got.tid != put.tid == threading.get_ident()
    assert got.t0 == issued >= put.t0
    assert got.t0 + got.dur >= put.t0 + put.dur
    assert got.attrs == {"what": "index", "nbytes": host.nbytes + 8,
                         "landed": True}


def test_a_deleted_array_ends_its_landing_without_a_raise():
    import jax.numpy as jnp

    dev = jnp.ones(1024)
    dev.delete()
    with obs.capture() as rec:
        obs.landed("gone.landed", dev, obs.now())
    # the capture's end waited for the waiter
    got = _one(rec, "gone.landed")
    assert got.attrs == {"nbytes": 4096, "landed": False}


def test_without_jax_imported_nothing_waits(monkeypatch):
    import sys

    monkeypatch.delitem(sys.modules, "jax")
    with obs.capture() as rec:
        assert obs.landed("x.landed", [], obs.now()) is None
    assert len(rec) == 0
