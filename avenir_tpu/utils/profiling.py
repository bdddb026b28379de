"""Tracing / profiling / running stats.

The reference has no tracing or profiling at all — only log4j debug flags
and Hadoop counters (SURVEY §5: "New framework: jax.profiler traces +
per-phase wall clock; this is green-field"). This module is that
green-field piece (per-phase wall clock is `avenir_tpu.obs.span`):

- trace(): context manager around jax.profiler for TensorBoard-readable
  device traces of a region; `python -m avenir_tpu <job> --trace DIR`
  runs a job inside it.
- RunningStats: mergeable count/mean/variance/min/max accumulator (the
  chombo SimpleStat role, SURVEY §0 dependency table) — moments add, so
  shard results combine exactly like the device psum path.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Iterator


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """jax.profiler device trace of the enclosed region, written for
    TensorBoard / xprof. No-ops cleanly if the profiler can't start (e.g.
    an already-active trace). The Python tracer is off: the program's
    own spans stand on the host's line (`obs.span` annotates), and an
    event per Python call would slow the job it measures.

    The region also records into the avenir-trace span recorder
    (``jax.profiler.trace`` span with the device trace dir and whether
    the profiler actually started as attrs), so a host-side Chrome
    trace links each device-trace capture to the phase that took it."""
    import jax

    from avenir_tpu import obs

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    started = False
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        started = True
    except Exception:
        pass
    t0 = obs.now()
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        obs.record("jax.profiler.trace", t0, log_dir=log_dir,
                   started=started)


@dataclass
class RunningStats:
    """Mergeable first/second-moment accumulator (chombo SimpleStat role)."""

    count: float = 0.0
    total: float = 0.0
    total_sq: float = 0.0
    min_val: float = math.inf
    max_val: float = -math.inf

    def add(self, *values: float) -> "RunningStats":
        for v in values:
            self.count += 1
            self.total += v
            self.total_sq += v * v
            self.min_val = min(self.min_val, v)
            self.max_val = max(self.max_val, v)
        return self

    def add_array(self, arr) -> "RunningStats":
        import numpy as np

        a = np.asarray(arr, np.float64).ravel()
        if a.size:
            self.count += a.size
            self.total += float(a.sum())
            self.total_sq += float((a * a).sum())
            self.min_val = min(self.min_val, float(a.min()))
            self.max_val = max(self.max_val, float(a.max()))
        return self

    def merge(self, other: "RunningStats") -> "RunningStats":
        """Moments are additive — the host-side analog of psum-merging
        per-shard stats."""
        self.count += other.count
        self.total += other.total
        self.total_sq += other.total_sq
        self.min_val = min(self.min_val, other.min_val)
        self.max_val = max(self.max_val, other.max_val)
        return self

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    @property
    def variance(self) -> float:
        if self.count < 2:
            return 0.0
        m = self.mean
        return max((self.total_sq - self.count * m * m) / (self.count - 1), 0.0)

    @property
    def std(self) -> float:
        return math.sqrt(self.variance)
