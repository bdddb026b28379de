"""avenir-trace: the always-on, low-overhead telemetry subsystem.

Three pieces, all stdlib-pure (imported by core.stream at package init,
so nothing here may import jax/numpy at module scope):

- **Span flight recorder** (:mod:`avenir_tpu.obs.trace`): a thread-safe
  ring buffer of ``(name, tid, t0, dur, attrs)`` span events with
  bounded memory and Chrome-trace/Perfetto JSON export. Instrumentation
  points live in core/stream (per-chunk read/parse/fold spans plus
  producer/consumer stall attribution), runner (the ``job.cli`` root of
  a batch job, per-job phase spans for the solo, shared, incremental
  and fused-incremental paths), core/dataset, models/knn and
  models/naive_bayes (the phases inside a kNN job: parse, index build,
  NB fit and posterior, query prepare/dispatch/fetch, output) and
  server/jobserver (per-request queued/held/dispatch spans with batch
  linkage attrs). ``obs.span(name)`` is the one way to time a phase: it
  records into the ring on the host's ``perf_counter`` clock and, when
  ``jax`` is already imported, enters a ``jax.profiler.TraceAnnotation``
  of the same name, so that under a profiler session (``python -m
  avenir_tpu <job> --trace DIR``) the span stands on the host's line of
  the device trace, on the device operations' clock. ``jax`` is looked
  up, never imported. Every ``obs.span`` also carries what the process
  spent over it, from ``getrusage``: ``cpu_ms``, ``minflt`` (first
  touches of fresh pages) and ``nivcsw`` (the CPU taken away), process-
  wide since the native passes run on the library's threads.
  ``obs.landed(name, arrays, t0)`` records how long a large put took to
  land on the device, from a waiter thread, so the job never waits.
- **Streaming histograms** (:mod:`avenir_tpu.obs.histogram`): fixed
  log-spaced bucket accumulators that merge like ``RunningStats``
  (counts and sums are additive, so ``merge`` is associative and
  shard/worker results combine exactly); quantiles come from per-bucket
  means, so they are exact whenever a bucket holds one distinct value.
- **Span-coverage auditor** (:mod:`avenir_tpu.obs.coverage`): runs every
  registered stream entry (analysis/manifest.stream_entries) and
  asserts it emits the mandatory span set (read/parse/fold/finish) —
  instrumentation can never silently rot; held for every entry by
  ``tests/test_obs.py::test_every_stream_entry_emits_the_mandatory_spans``.

Contract: tracing is observation only, so a fused run with tracing ON
writes the bytes of the run with tracing OFF
(``tests/test_shared_scan.py::test_fused_outputs_byte_identical_under_tracing``).
What the spans cost on the chip is in ``PERF.md`` (PR 25: nothing one
can measure; the counters two ``getrusage`` calls a span). Tracing is
ON by default (``AVENIR_TRACE=0`` or :func:`set_enabled` turns it off);
every record call is one enabled-flag load away from free when off, and
then no counter is read and no waiter started.
"""

# the submodule is named ``histogram`` (not ``hist``) on purpose: a
# submodule named ``hist`` would shadow the ``obs.hist(name)`` accessor
# __all__ advertises below
from avenir_tpu.obs.histogram import LatencyHistogram
from avenir_tpu.obs.trace import (USAGE_ATTRS, Span, SpanRecorder, capture,
                                  enabled, hist, hist_summaries, landed, now,
                                  no_span, observe, record, record_min,
                                  recorder, reset_hists, set_enabled, span)

__all__ = [
    "Span", "SpanRecorder", "LatencyHistogram", "USAGE_ATTRS",
    "capture", "enabled", "set_enabled", "recorder",
    "now", "record", "record_min", "span", "no_span", "landed",
    "observe", "hist", "hist_summaries", "reset_hists",
]
