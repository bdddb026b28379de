"""Span flight recorder: a bounded, thread-safe ring of timing events.

The recorder is process-global and always on (module docstring of
:mod:`avenir_tpu.obs` has the overhead contract). A span is a host-side
wall-clock interval: ``t0``/``dur`` are ``time.perf_counter`` seconds,
``tid`` the recording thread, ``attrs`` a small dict of primitives.
Device work dispatches asynchronously, so a span around a jitted fold
measures dispatch+host time, not device occupancy. What the device did
meanwhile is the profiler's to say: :func:`span` also enters a
``jax.profiler.TraceAnnotation``, so under a profiler session the same
span stands on the host's line of the device trace, on its clock.

Export is Chrome-trace JSON (the ``traceEvents`` complete-event form:
``ph:"X"`` with microsecond ``ts``/``dur``), loadable by Perfetto and
chrome://tracing; ``tools/trace_report.py`` rolls the same file into a
per-phase table.

Memory bound: the ring keeps the NEWEST ``capacity`` spans (overflow
drops the oldest and counts them in ``dropped``) — a resident server
can trace forever in O(capacity).

Resource counters: every :func:`span` also carries what the process
spent over it, read by ``getrusage(RUSAGE_SELF)`` at open and close
(:data:`USAGE_ATTRS`): ``cpu_ms`` (user plus system time), ``minflt``
(minor page faults: first touches of fresh pages) and ``nivcsw``
(involuntary context switches: the CPU taken away). The readings are
the whole process's, not the span's thread's, because the native passes
run on the library's own threads; a span that overlaps another thread's
work counts that work too. The retroactive :func:`record` sites carry
none.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

try:
    from resource import RUSAGE_SELF, getrusage
except ImportError:             # no such module on this platform: no counters
    getrusage = None

#: default ring capacity (spans); ~100 bytes each -> a few MB bound
DEFAULT_CAPACITY = 65_536

#: shortest producer/consumer stall worth a span (seconds) — queue
#: handoffs complete in microseconds; recording every one would be
#: noise, not attribution
STALL_MIN_SECS = 1e-3

#: the longest :func:`capture` waits at its end for each put still on its
#: way to the device, so that the landing joins the captured ring
LANDING_WAIT_SECS = 60.0


class Span(NamedTuple):
    name: str
    tid: int
    t0: float
    dur: float
    attrs: Optional[Dict]


class SpanRecorder:
    """Thread-safe ring buffer of :class:`Span` events."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._buf: List[Span] = []
        self._n = 0                      # total spans ever recorded

    def record(self, name: str, t0: float, dur: float,
               tid: Optional[int] = None,
               attrs: Optional[Dict] = None) -> None:
        sp = Span(name, tid if tid is not None else threading.get_ident(),
                  t0, dur, attrs)
        with self._lock:
            if self._n < self.capacity:
                self._buf.append(sp)
            else:
                self._buf[self._n % self.capacity] = sp
            self._n += 1

    @property
    def dropped(self) -> int:
        """Spans the ring overwrote (oldest-first) since the last clear."""
        with self._lock:
            return max(self._n - self.capacity, 0)

    def __len__(self) -> int:
        with self._lock:
            return min(self._n, self.capacity)

    def spans(self) -> List[Span]:
        """Retained spans, oldest to newest."""
        with self._lock:
            if self._n <= self.capacity:
                return list(self._buf)
            head = self._n % self.capacity
            return self._buf[head:] + self._buf[:head]

    def clear(self) -> None:
        with self._lock:
            self._buf = []
            self._n = 0

    def chrome_events(self) -> List[Dict]:
        """The retained spans as Chrome-trace complete events (``ph:X``,
        microsecond ``ts``/``dur`` on the perf_counter timeline)."""
        pid = os.getpid()
        return [{"name": sp.name, "cat": "avenir", "ph": "X",
                 "ts": sp.t0 * 1e6, "dur": sp.dur * 1e6,
                 "pid": pid, "tid": sp.tid,
                 "args": sp.attrs or {}}
                for sp in self.spans()]

    def export_chrome(self, path: str) -> str:
        """Write the Chrome-trace JSON file (atomic tmp+rename; open it
        in Perfetto / chrome://tracing). Returns `path`."""
        doc = {"traceEvents": self.chrome_events(),
               "displayTimeUnit": "ms",
               "metadata": {"dropped_spans": self.dropped}}
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh)
        os.replace(tmp, path)
        return path


# --------------------------------------------------------------------------
# module-global surface (what the instrumentation points call)
# --------------------------------------------------------------------------
_ENABLED = os.environ.get("AVENIR_TRACE", "1") not in ("0", "false", "off")
_recorder = SpanRecorder()

now = time.perf_counter


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> bool:
    """Toggle recording; returns the previous state. Tests use it for
    their traced-against-untraced byte comparison; production leaves it
    on."""
    global _ENABLED
    prev, _ENABLED = _ENABLED, bool(on)
    return prev


def recorder() -> SpanRecorder:
    return _recorder


def record(name: str, t0: float, **attrs) -> None:
    """Record a span that began at `t0` (from :func:`now`) and ends now.
    One flag load when disabled — cheap enough for per-chunk call sites."""
    if not _ENABLED:
        return
    _recorder.record(name, t0, time.perf_counter() - t0,
                     attrs=attrs or None)


def record_min(name: str, t0: float, min_dur: float = STALL_MIN_SECS,
               **attrs) -> None:
    """Record the span only when it lasted at least `min_dur` seconds —
    the stall-attribution call sites use this so instantaneous queue
    handoffs don't flood the ring."""
    if not _ENABLED:
        return
    dur = time.perf_counter() - t0
    if dur >= min_dur:
        _recorder.record(name, t0, dur, attrs=attrs or None)


#: what :func:`span` adds to its attributes from the process's usage
USAGE_ATTRS = ("cpu_ms", "minflt", "nivcsw")


def _usage():
    """(CPU seconds, minor faults, involuntary switches) of the whole
    process so far, or None where the platform has no ``resource``."""
    if getrusage is None:
        return None
    ru = getrusage(RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime, ru.ru_minflt, ru.ru_nivcsw


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Dict]:
    """Context-manager span around a region (exception-safe: the span
    records however the block exits). Yields the span's attribute dict,
    so what is known only at the end (``rows``, ``nbytes``) is set there.
    At the close the process's usage over the span is added to it
    (``cpu_ms``, ``minflt``, ``nivcsw``: the module docstring).

    With tracing on and ``jax`` already imported the region also enters
    a ``jax.profiler.TraceAnnotation`` of the same name: under a live
    profiler session the span stands on the host's line of the
    ``.xplane.pb``, on the device operations' clock; with none live the
    annotation costs one activity check. ``jax`` is looked up, never
    imported, so this module stays stdlib-pure."""
    if not _ENABLED:
        yield attrs
        return
    jax = sys.modules.get("jax")
    note = jax.profiler.TraceAnnotation(name) if jax is not None \
        else contextlib.nullcontext()
    # the counters are read outside the timed interval, at both ends
    before = _usage()
    t0 = time.perf_counter()
    try:
        with note:
            yield attrs
    finally:
        dur = time.perf_counter() - t0
        if before is not None:
            after = _usage()
            attrs.update(cpu_ms=1e3 * (after[0] - before[0]),
                         minflt=after[1] - before[1],
                         nivcsw=after[2] - before[2])
        if _ENABLED:
            _recorder.record(name, t0, dur, attrs=attrs or None)


def no_span(name: str, **attrs):
    """What stands where :func:`span` would on a route that names no
    phases: the same attribute dict, nothing recorded."""
    return contextlib.nullcontext(attrs)


def landed(name: str, arrays, t0: float,
           **attrs) -> Optional[threading.Thread]:
    """Record `name` from `t0` (from :func:`now`: when the put of the
    device `arrays`, any pytree, was issued) to the moment they are
    ready on the device, with their ``nbytes``. A short-lived daemon
    thread waits for them, so the caller never does and nothing is
    synchronised; the span stands on that thread, in the ring that was
    in place at the call, and :func:`capture` joins the waiters at its
    end. An array deleted or donated before it lands ends the span with
    ``landed`` False, and nothing is raised. Returns the thread, or None
    with tracing off or ``jax`` not imported, when nothing is read."""
    jax = sys.modules.get("jax")
    if not _ENABLED or jax is None:
        return None
    leaves = jax.tree_util.tree_leaves(arrays)
    attrs["nbytes"] = sum(int(a.nbytes) for a in leaves)
    ring = _recorder

    def wait() -> None:
        try:
            jax.block_until_ready(leaves)
            attrs["landed"] = True
        except RuntimeError:    # deleted or donated: it will not land
            attrs["landed"] = False
        ring.record(name, t0, time.perf_counter() - t0, attrs=attrs)

    waiter = threading.Thread(target=wait, name=name, daemon=True)
    with _waiters_lock:
        _waiters[:] = [w for w in _waiters if w.is_alive()]
        _waiters.append(waiter)
    waiter.start()
    return waiter


#: the landing waiters started and not yet joined
_waiters: List[threading.Thread] = []
_waiters_lock = threading.Lock()


def _join_waiters(timeout: float = LANDING_WAIT_SECS) -> None:
    """Join every landing waiter started so far, each for `timeout` at
    most, so that a captured run holds the landings of its puts."""
    with _waiters_lock:
        pending, _waiters[:] = list(_waiters), []
    for waiter in pending:
        waiter.join(timeout)


@contextlib.contextmanager
def capture(capacity: int = DEFAULT_CAPACITY) -> Iterator[SpanRecorder]:
    """Swap in a FRESH recorder (and force tracing on) for the duration
    — the span-coverage auditor and tests capture one run's spans in
    isolation this way — then wait for the landings of the puts made
    inside it (:func:`landed`) and restore the previous recorder and
    flag."""
    global _recorder
    fresh = SpanRecorder(capacity)
    prev_rec, _recorder = _recorder, fresh
    prev_on = set_enabled(True)
    try:
        yield fresh
    finally:
        _join_waiters()
        _recorder = prev_rec
        set_enabled(prev_on)


# --------------------------------------------------------------------------
# process-global streaming histograms
# --------------------------------------------------------------------------
_hist_lock = threading.Lock()
_hists: Dict[str, "object"] = {}


def observe(name: str, value: float) -> None:
    """Fold one sample into the process-global histogram `name` (created
    on first use) — the always-on aggregate view next to the span ring
    (e.g. ``chunk_latency_ms`` fed by SharedScan)."""
    if not _ENABLED:
        return
    from avenir_tpu.obs.histogram import LatencyHistogram

    with _hist_lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = LatencyHistogram()
        h.add(value)


def hist(name: str):
    """A merged COPY of the process-global histogram `name` (None when
    nothing observed it yet) — a copy, so callers can merge/mutate
    without racing the live accumulator."""
    from avenir_tpu.obs.histogram import LatencyHistogram

    with _hist_lock:
        h = _hists.get(name)
        return None if h is None else LatencyHistogram().merge(h)


def hist_summaries() -> Dict[str, Dict[str, float]]:
    """{name: summary} of every process-global histogram."""
    with _hist_lock:
        return {name: h.summary() for name, h in sorted(_hists.items())}


def reset_hists() -> None:
    with _hist_lock:
        _hists.clear()
