"""avenir-autotune: close the loop from trace telemetry to streaming
knobs.

PR 10 made the stack measure everything — per-chunk read/parse/fold
spans, producer/consumer stall attribution, queue-wait and
admission-hold histograms, predicted-vs-measured RSS on every streamed
JobResult — and this package is the actuator that reads those signals
and moves the knobs they implicate. Chunk invariance (graftlint --flow,
8/8 byte-identity under adversarial chunkings) means a tuner can NEVER
change results, only speed, so the policies are aggressive by design;
``tests/test_tune.py::TestTunedByteIdentity`` holds that half (the
artifacts under the chosen knobs are the static default's bytes, solo
and fused). Whether the chosen knobs are faster is a question for a chip
cell, and none asks it yet.

Four pieces:

- **knob registry** (:mod:`~avenir_tpu.tune.knobs`): every tunable conf
  key with its safe range and driving signal; unknown/out-of-range keys
  in a tuned profile fail LOUDLY (:class:`KnobError`).
- **signal extraction** (:mod:`~avenir_tpu.tune.signals`): captured
  spans -> read/parse/fold totals, stall attribution shares, per-sink
  fold-cost means.
- **policy engine** (:mod:`~avenir_tpu.tune.policy`): deterministic
  signal -> knob-move rules, clamped to the registry ranges; plus the
  residual-corrected admission factor (clamped >= 1.0 so the learned
  correction can never price a request UNDER the validated model) and
  the server's fold-cost batch-balance predicate.
- **profile store** (:mod:`~avenir_tpu.tune.store`): atomic per-(job,
  corpus) JSON profiles — run signals, residual history, fold costs,
  chosen knobs + reasons — consulted by ``runner.run_job``/``run_shared``
  behind the ``stream.autotune`` conf/CLI flag and by the JobServer's
  scheduler/pricer via ``JobServer(autotune_dir=...)``. ``python -m
  avenir_tpu tune <dir>`` renders and explains the decisions.

This module adds the runner-facing glue: :func:`begin_run` (overlay the
stored knobs onto the job configs, hand back a session that records the
run's telemetry and chooses the next knobs) and
:func:`make_tuned_pricer` (the residual-corrected admission oracle).
Everything here is host-side stdlib + obs — no jax at module scope.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Sequence

from avenir_tpu import obs as _obs
from avenir_tpu.tune.knobs import (CONTROL_KEYS, KNOBS, Knob, KnobError,
                                   format_value, knob_defaults, knob_keys,
                                   validate_knobs)
from avenir_tpu.tune.policy import (BATCH_BALANCE_RATIO,
                                    RESIDUAL_FACTOR_CAP, batch_balanced,
                                    choose_knobs, residual_factor)
from avenir_tpu.tune.signals import RunSignals, extract_signals
from avenir_tpu.tune.store import ProfileStore, corpus_digest, resolve_dir

__all__ = [
    "KNOBS", "Knob", "KnobError", "CONTROL_KEYS",
    "knob_keys", "knob_defaults", "validate_knobs", "format_value",
    "RunSignals", "extract_signals",
    "choose_knobs", "residual_factor", "batch_balanced",
    "BATCH_BALANCE_RATIO", "RESIDUAL_FACTOR_CAP",
    "ProfileStore", "corpus_digest", "resolve_dir",
    "begin_run", "record_residual", "make_tuned_pricer",
    "placement_cost_ms",
]


def _effective_knobs(cfg) -> Dict[str, object]:
    """The knob values a run will actually use, read back through the
    config AFTER any overlay — so the recorded ``knobs_used`` reflects
    tuned values, explicit conf keys and defaults alike."""
    out: Dict[str, object] = {}
    for key, knob in KNOBS.items():
        if knob.kind == "int":
            out[key] = int(cfg.get_float(key, knob.default))
        else:
            out[key] = float(cfg.get_float(key, knob.default))
    return out


#: sessions currently between begin_run and finish — when two overlap,
#: the process-global span ring holds BOTH runs' spans, so neither
#: window can be attributed to one corpus; every overlapping session is
#: marked contaminated and skips its signal/knob recording (the run
#: itself, the overlay it already applied, and the residual history are
#: unaffected)
_session_lock = threading.Lock()
_active_sessions: set = set()


class RunSession:
    """One autotuned run: constructed by :func:`begin_run` (which has
    already overlaid the stored knobs onto the configs); ``finish()``
    extracts the run's spans from the process-global recorder, records
    the signal row, and commits the next run's knobs."""

    def __init__(self, store: ProfileStore, profile_job: str, digest: str,
                 canonicals: Sequence[str], knobs_used: Dict,
                 knobs_applied: Dict):
        self.store = store
        self.profile_job = profile_job
        self.digest = digest
        self.canonicals = list(canonicals)
        self.knobs_used = dict(knobs_used)
        self.knobs_applied = dict(knobs_applied)
        self.contaminated = False
        with _session_lock:
            if _active_sessions:
                self.contaminated = True
                for other in _active_sessions:
                    other.contaminated = True
            _active_sessions.add(self)
        self.t0 = _obs.now()

    def close(self) -> None:
        """Abandon the session without recording anything — the
        runner's failure path. MUST be called when the run raises, or
        this session would sit in ``_active_sessions`` forever and mark
        every later session in the process contaminated."""
        with _session_lock:
            _active_sessions.discard(self)

    def finish(self, results: Dict) -> Optional[Dict]:
        """Record the run and choose the next knobs. Advisory end to
        end: any failure here must never fail a job that already ran,
        so errors are swallowed. The knobs committed forward are the
        profile values this run APPLIED plus this round's clamped
        moves — an operator's explicit conf value is never adopted as
        a tuned knob, so set_knobs' validation cannot trip on legal
        conf outside the registry range. Returns the committed knob
        dict, or None when this session was skipped (concurrent
        session contamination) or recording failed."""
        with _session_lock:
            _active_sessions.discard(self)
        if self.contaminated:
            return None
        try:
            wall_s = _obs.now() - self.t0
            spans = [sp for sp in _obs.recorder().spans()
                     if sp.t0 >= self.t0]
            # the session guard only sees other AUTOTUNED sessions; a
            # concurrent UNTUNED streamed job (another server worker)
            # shares the same span ring too. Its fold spans carry its
            # canonical job name as the sink label — any registered
            # stream job folding in this window that is not ours means
            # the window cannot be attributed to this run: skip.
            from avenir_tpu.runner import stream_fold_names

            sinks = {(sp.attrs or {}).get("sink") for sp in spans
                     if sp.name == "stream.fold"}
            if (sinks & set(stream_fold_names())) - set(self.canonicals):
                return None
            sig = extract_signals(spans, wall_s=wall_s)
            counters: Dict[str, float] = {}
            for res in results.values():
                for key, val in getattr(res, "counters", {}).items():
                    counters[key] = max(counters.get(key, 0.0),
                                        float(val))
            moves, reasons = choose_knobs(sig, counters, self.knobs_used)
            chosen = dict(self.knobs_applied)
            chosen.update(moves)
            self.store.record_run(self.profile_job, self.digest,
                                  sig.to_json(), self.knobs_used, wall_s)
            self.store.set_knobs(self.profile_job, self.digest, chosen,
                                 reasons)
            # a fused run's per-sink fold means feed each member job's
            # own profile — the numbers the server's batch balancer
            # compares when composing future batches
            if len(self.canonicals) > 1:
                for canonical in self.canonicals:
                    cost = sig.fold_ms_by_sink.get(canonical)
                    if cost:
                        self.store.note_fold_cost(canonical, self.digest,
                                                  cost)
            return chosen
        except Exception:
            return None


def begin_run(canonicals: Sequence[str], cfgs: Sequence,
              inputs: Sequence[str]) -> RunSession:
    """Start one autotuned run: load the (job, corpus) profile, overlay
    its validated knobs onto EVERY config (fused jobs must agree on the
    scan-shaping keys, so one knob set serves the group), and return
    the session whose ``finish()`` closes the loop.

    Raises :class:`KnobError` when the stored profile names an unknown
    or out-of-range knob — the loud-guard contract; every other storage
    problem degrades to an untuned run."""
    cfg0 = cfgs[0]
    store = ProfileStore(resolve_dir(cfg0, inputs))
    profile_job = "+".join(sorted(canonicals))
    digest = corpus_digest(inputs)
    prof = store.load(profile_job, digest)       # may raise KnobError
    knobs = dict(prof.get("knobs") or {}) if prof else {}
    for cfg in cfgs:
        for key, value in knobs.items():
            pref = f"{cfg.prefix}.{key}" if cfg.prefix else key
            cfg.props[pref] = format_value(key, value)
    return RunSession(store, profile_job, digest, canonicals,
                      _effective_knobs(cfg0), knobs)


def record_residual(canonical: str, cfg, inputs: Sequence[str],
                    predicted: float, measured: float) -> None:
    """Persist one predicted-vs-measured RSS residual into the job's
    profile — called from ``runner._add_mem_counters`` on EVERY
    streamed result (not gated on the autotune flag), so the tuner's
    model-refinement leg has history from day one. Advisory: a store
    that cannot be written (read-only input dir, races) is silently
    skipped."""
    try:
        store = ProfileStore(resolve_dir(cfg, inputs))
        store.record_residual(canonical, corpus_digest(inputs),
                              predicted, measured)
    except Exception:
        return


def make_tuned_pricer(profile_dir: str,
                      base: Optional[Callable] = None) -> Callable:
    """The residual-corrected admission oracle: wraps the analytic
    pricer with the per-(job, corpus) learned correction factor
    (:func:`~avenir_tpu.tune.policy.residual_factor`, clamped into
    [1.0, cap]) — so the correction can RAISE a price whose job
    historically measured over its prediction, and can NEVER lower one
    below the uncorrected model's floor (pinned by a unit test)."""
    if base is None:
        from avenir_tpu.server.jobserver import price_request_bytes
        base = price_request_bytes

    store = ProfileStore(profile_dir)

    def pricer(requests, reserve_bytes: int) -> int:
        raw = base(requests, reserve_bytes)
        factor = 1.0
        try:
            from avenir_tpu.runner import _job_cfg

            for req in requests:
                canonical = _job_cfg(req.job, req.conf)[0]
                try:
                    prof = store.load(canonical,
                                      corpus_digest(req.inputs))
                except KnobError:
                    prof = None          # bad knob entry: the run will
                if prof is None:         # fail loudly on it, not pricing
                    continue
                factor = max(factor, residual_factor(
                    prof.get("residuals") or []))
        except Exception:
            factor = 1.0
        return int(raw * max(factor, 1.0))

    return pricer


def placement_cost_ms(profile_dir: Optional[str], job: str, conf,
                      inputs: Sequence[str]) -> Optional[float]:
    """The measured mean per-chunk fold cost (ms) of one (job, corpus)
    from a profile store — the fleet router's placement weight: a
    corpus whose folds are measured expensive counts for more pending
    load on its host than its bytes alone say. None (and never an
    exception) when there is no store, no profile, or no measurement —
    placement must degrade to bytes-only, not refuse to route."""
    if not profile_dir:
        return None
    try:
        from avenir_tpu.runner import _job_cfg

        canonical = _job_cfg(job, conf)[0]
    except Exception:  # noqa: BLE001 — unresolvable job: bytes-only
        canonical = job
    try:
        return ProfileStore(profile_dir).fold_cost_ms(
            canonical, corpus_digest(inputs))
    except Exception:  # noqa: BLE001 — unreadable store: bytes-only
        return None
