"""avenir-autotune: the telemetry->knob loop's contracts.

1. The knob registry is the tuner's whole authority: unknown or
   out-of-range keys in a tuned profile fail LOUDLY (KnobError) — at
   validate, at store load, and from an autotuned run — never silently
   running defaults.
2. Policy rules are pure and clamped: a synthetic signal in yields the
   documented knob move out, and range edges hold under any signal.
3. Tuned configs may only change SPEED: for >= 2 stream entries (one
   Dataset-fold, one byte-fold) the artifact under the autotuner-chosen
   (block, prefetch, checkpoint) triple is byte-identical to the static
   default's.
4. Admission safety: the residual-learned price correction never drops
   a price below the uncorrected model's floor, and caps above it.
5. The `stream.prefetch.depth` key actually reaches every prefetched()
   job feed, and the footprint model's in-flight terms price it.
"""

import json
import os

import pytest

from avenir_tpu import tune
from avenir_tpu.tune.knobs import KNOBS, KnobError, validate_knobs
from avenir_tpu.tune.policy import (batch_balanced, choose_block_mb,
                                    choose_cache_budget_mb,
                                    choose_checkpoint_interval_mb,
                                    choose_knobs, choose_prefetch_depth,
                                    residual_factor)
from avenir_tpu.tune.signals import RunSignals, extract_signals
from avenir_tpu.tune.store import ProfileStore, corpus_digest


def _churn(tmp_path, rows=1500):
    from avenir_tpu.data import churn_schema, generate_churn

    csv = tmp_path / "churn.csv"
    csv.write_text(generate_churn(rows, seed=7, as_csv=True))
    schema = tmp_path / "churn.json"
    churn_schema().save(str(schema))
    return str(csv), str(schema)


def _seq(tmp_path, rows=400):
    import numpy as np

    rng = np.random.default_rng(5)
    states = ["L", "M", "H"]
    csv = tmp_path / "seq.csv"
    with open(csv, "w") as fh:
        for i in range(rows):
            up = i % 2 == 0
            s, toks = 1, []
            for _ in range(6):
                p = [0.1, 0.3, 0.6] if up else [0.6, 0.3, 0.1]
                s = int(np.clip(s + rng.choice([-1, 0, 1], p=p), 0, 2))
                toks.append(states[s])
            fh.write(f"c{i},{'T' if up else 'F'}," + ",".join(toks) + "\n")
    return str(csv)


def _bytes_of(res):
    return b"\n".join(open(p, "rb").read() for p in sorted(res.outputs))


# ========================================================== knob registry
class TestKnobRegistry:
    def test_defaults_inside_ranges(self):
        for knob in KNOBS.values():
            assert knob.lo <= knob.default <= knob.hi
            assert knob.signal and knob.description

    def test_validate_accepts_known_in_range(self):
        out = validate_knobs({"stream.block.size.mb": 8,
                              "stream.prefetch.depth": 4.0})
        assert out == {"stream.block.size.mb": 8.0,
                       "stream.prefetch.depth": 4}
        assert isinstance(out["stream.prefetch.depth"], int)

    def test_unknown_key_is_loud(self):
        with pytest.raises(KnobError, match="stream.blokc.size.mb"):
            validate_knobs({"stream.blokc.size.mb": 8})

    def test_out_of_range_is_loud(self):
        with pytest.raises(KnobError, match="safe range"):
            validate_knobs({"stream.prefetch.depth": 99})
        with pytest.raises(KnobError, match="not numeric"):
            validate_knobs({"stream.block.size.mb": "eight"})

    def test_store_load_guards_typoed_profile(self, tmp_path):
        store = ProfileStore(str(tmp_path))
        path = store.path("mutualInformation", "cafe")
        with open(path, "w") as fh:
            json.dump({"format": 1, "job": "mutualInformation",
                       "corpus_digest": "cafe",
                       "knobs": {"stream.blokc.size.mb": 8}}, fh)
        with pytest.raises(KnobError, match="stream.blokc"):
            store.load("mutualInformation", "cafe")

    def test_autotuned_run_fails_loud_on_bad_profile(self, tmp_path):
        from avenir_tpu.runner import run_job

        csv, schema = _churn(tmp_path)
        tune_dir = tmp_path / "tune"
        store = ProfileStore(str(tune_dir))
        path = store.path("mutualInformation", corpus_digest([csv]))
        os.makedirs(str(tune_dir), exist_ok=True)
        with open(path, "w") as fh:
            json.dump({"format": 1, "job": "mutualInformation",
                       "corpus_digest": corpus_digest([csv]),
                       "knobs": {"stream.block.size.mb": 99999}}, fh)
        conf = {"mut.feature.schema.file.path": schema,
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization",
                "mut.stream.autotune": "true",
                "mut.stream.autotune.dir": str(tune_dir)}
        with pytest.raises(KnobError, match="safe range"):
            run_job("mutualInformation", conf, [csv],
                    str(tmp_path / "out.txt"))


# ========================================================== policy rules
class TestPolicyRules:
    def test_block_consumer_bound_shrinks(self):
        sig = RunSignals(wall_s=10, read_s=1, parse_s=1, fold_s=6,
                         chunks=6, bytes_read=384 << 20)
        value, reason = choose_block_mb(sig, 64.0)
        assert value == 8.0                      # 384/24 = 16, halved
        assert "consumer-bound" in reason

    def test_block_producer_bound_grows(self):
        sig = RunSignals(wall_s=10, read_s=4, parse_s=4, fold_s=2,
                         chunks=96, bytes_read=384 << 20)
        value, reason = choose_block_mb(sig, 4.0)
        assert value == 32.0                     # 384/24 = 16, doubled
        assert "producer-bound" in reason

    def test_block_clamps_at_range_edges(self):
        lo, hi = KNOBS["stream.block.size.mb"].lo, \
            KNOBS["stream.block.size.mb"].hi
        tiny = RunSignals(wall_s=1, fold_s=0.6, read_s=0.1, parse_s=0.1,
                          chunks=3, bytes_read=1 << 17)      # 128KB corpus
        assert choose_block_mb(tiny, 64.0)[0] == lo
        huge = RunSignals(wall_s=1, read_s=0.6, fold_s=0.1,
                          chunks=1000, bytes_read=1 << 40)   # 1TB corpus
        assert choose_block_mb(huge, 64.0)[0] == hi

    def test_block_keeps_when_no_signal(self):
        assert choose_block_mb(RunSignals(), 64.0) == (None, None)

    def test_prefetch_deepens_when_producer_bound(self):
        sig = RunSignals(wall_s=10, producer_bound_s=2.0)
        assert choose_prefetch_depth(sig, 2)[0] == 4

    def test_prefetch_clamps_at_hi(self):
        sig = RunSignals(wall_s=10, producer_bound_s=9.0)
        assert choose_prefetch_depth(sig, 8) == (None, None)  # already max

    def test_prefetch_backs_off_when_consumer_bound(self):
        sig = RunSignals(wall_s=10, consumer_bound_s=5.0)
        value, reason = choose_prefetch_depth(sig, 8)
        assert value == 4
        # never below the default on the back-off path
        assert choose_prefetch_depth(sig, 2) == (None, None)

    def test_checkpoint_doubles_over_budget_and_clamps(self):
        sig = RunSignals(wall_s=10, checkpoint_s=1.0)        # 10% > 5%
        assert choose_checkpoint_interval_mb(sig, 256.0)[0] == 512.0
        hi = KNOBS["stream.checkpoint.interval.mb"].hi
        assert choose_checkpoint_interval_mb(sig, hi) == (None, None)
        calm = RunSignals(wall_s=10, checkpoint_s=0.1)
        assert choose_checkpoint_interval_mb(calm, 256.0) == (None, None)

    def test_cache_budget_grows_over_spill(self):
        counters = {"Cache:EvictedBytes": 200 << 20,
                    "Cache:SpillBytes": 600 << 20}
        value, reason = choose_cache_budget_mb(counters, 512.0)
        assert value == 1024.0                   # pow2(1.5 * 600MB)
        assert choose_cache_budget_mb({}, 512.0) == (None, None)

    def test_choose_knobs_returns_only_moves(self):
        # no signal, no move — even when the run's effective values sit
        # off the defaults (an operator's conf must never be adopted as
        # a tuned knob; the session carries earlier PROFILE knobs)
        chosen, reasons = choose_knobs(RunSignals(), {},
                                       {"stream.block.size.mb": 512.0,
                                        "stream.prefetch.depth": 2})
        assert chosen == {} and reasons == []

    def test_session_keeps_earlier_profile_moves(self, tmp_path):
        from avenir_tpu.core.config import JobConfig

        csv, _schema = _churn(tmp_path, rows=50)
        store = ProfileStore(str(tmp_path / "t"))
        digest = corpus_digest([csv])
        store.set_knobs("mutualInformation", digest,
                        {"stream.block.size.mb": 8.0}, ["earlier round"])
        cfg = JobConfig({"stream.autotune.dir": str(tmp_path / "t")},
                        "mut")
        session = tune.begin_run(["mutualInformation"], [cfg], [csv])
        # the overlay applied the profile knob onto the prefixed conf
        assert cfg.props["mut.stream.block.size.mb"] == "8"
        # an empty run (no spans, no counters) must not drop it
        chosen = session.finish({})
        assert chosen == {"stream.block.size.mb": 8.0}
        prof = store.load("mutualInformation", digest)
        assert prof["knobs"] == {"stream.block.size.mb": 8.0}

    def test_user_conf_never_persists_as_tuned_knob(self, tmp_path):
        """An explicit conf value the tuner did not choose — even one
        outside the registry range — must not land in the profile (and
        must not silently break knob persistence via a refused
        set_knobs)."""
        from avenir_tpu.runner import run_job

        csv, schema = _churn(tmp_path)
        conf = {"mut.feature.schema.file.path": schema,
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization",
                "mut.stream.block.size.mb": "0.01",
                "mut.stream.checkpoint.interval.mb": "0.001",  # < range lo
                "mut.stream.autotune": "true",
                "mut.stream.autotune.dir": str(tmp_path / "t")}
        run_job("mutualInformation", conf, [csv],
                str(tmp_path / "out.txt"))
        prof = ProfileStore(str(tmp_path / "t")).load(
            "mutualInformation", corpus_digest([csv]))
        assert prof is not None and prof["runs"], \
            "set_knobs/record_run silently no-opped"
        # the block rule MAY move (clamped), but the raw conf values
        # must not appear, and the untouched checkpoint conf (outside
        # the registry range) must not be adopted
        assert "stream.checkpoint.interval.mb" not in prof["knobs"]
        assert 0.01 not in prof["knobs"].values()

    def test_failed_run_does_not_poison_later_sessions(self, tmp_path):
        """A run that raises must close its session: a leaked one would
        mark every later session in the process contaminated and
        silently disable recording forever."""
        from avenir_tpu.runner import run_job

        csv, schema = _churn(tmp_path, rows=100)
        bad = {"mut.feature.schema.file.path":
                   str(tmp_path / "missing.json"),
               "mut.mutual.info.score.algorithms":
                   "mutual.info.maximization",
               "mut.stream.autotune": "true",
               "mut.stream.autotune.dir": str(tmp_path / "t")}
        with pytest.raises(Exception):
            run_job("mutualInformation", bad, [csv],
                    str(tmp_path / "boom.txt"))
        good = dict(bad, **{"mut.feature.schema.file.path": schema})
        run_job("mutualInformation", good, [csv],
                str(tmp_path / "ok.txt"))
        prof = ProfileStore(str(tmp_path / "t")).load(
            "mutualInformation", corpus_digest([csv]))
        assert prof is not None and prof["runs"], \
            "leaked failed session contaminated the next run"

    def test_untuned_concurrent_fold_contaminates_window(self, tmp_path):
        """The session guard only sees other autotuned sessions; a
        concurrent UNTUNED streamed job shares the span ring too — its
        fold spans (sink = its canonical name) must make this window
        unattributable."""
        from avenir_tpu import obs as _obs
        from avenir_tpu.core.config import JobConfig

        csv, _schema = _churn(tmp_path, rows=50)
        cfg = lambda: JobConfig(                            # noqa: E731
            {"stream.autotune.dir": str(tmp_path / "t")}, "mut")
        s = tune.begin_run(["mutualInformation"], [cfg()], [csv])
        _obs.recorder().record("stream.fold", _obs.now(), 0.001,
                               attrs={"sink": "bayesianDistr"})
        assert s.finish({}) is None
        # a window holding only OUR sink's folds records fine
        s2 = tune.begin_run(["mutualInformation"], [cfg()], [csv])
        _obs.recorder().record("stream.fold", _obs.now(), 0.001,
                               attrs={"sink": "mutualInformation"})
        assert s2.finish({}) is not None

    def test_concurrent_sessions_skip_recording(self, tmp_path):
        from avenir_tpu.core.config import JobConfig

        csv, _schema = _churn(tmp_path, rows=50)
        cfg = lambda: JobConfig(                            # noqa: E731
            {"stream.autotune.dir": str(tmp_path / "t")}, "mut")
        a = tune.begin_run(["mutualInformation"], [cfg()], [csv])
        b = tune.begin_run(["bayesianDistr"], [cfg()], [csv])
        # overlapping windows share the global span ring: neither may
        # attribute it, so both skip their signal/knob recording
        assert a.finish({}) is None
        assert b.finish({}) is None
        store = ProfileStore(str(tmp_path / "t"))
        assert store.load("mutualInformation", corpus_digest([csv])) is None
        # a later, un-overlapped session records again
        c = tune.begin_run(["mutualInformation"], [cfg()], [csv])
        assert c.finish({}) is not None


# ======================================================= signal extraction
class TestSignals:
    def test_extract_from_captured_spans(self, tmp_path):
        from avenir_tpu.obs import trace
        from avenir_tpu.runner import run_job

        csv, schema = _churn(tmp_path)
        conf = {"mut.feature.schema.file.path": schema,
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization",
                "mut.stream.block.size.mb": "0.01"}
        with trace.capture() as rec:
            run_job("mutualInformation", conf, [csv],
                    str(tmp_path / "out.txt"))
        sig = extract_signals(rec.spans())
        assert sig.chunks > 1
        assert sig.bytes_read == os.path.getsize(csv)
        assert sig.read_s > 0 and sig.parse_s > 0 and sig.fold_s > 0
        assert "mutualInformation" in sig.fold_ms_by_sink
        # round-trips through the store's JSON form
        back = RunSignals.from_json(sig.to_json())
        assert back.chunks == sig.chunks
        assert back.fold_ms_by_sink.keys() == sig.fold_ms_by_sink.keys()


# ================================================= tuned-config identity
class TestTunedByteIdentity:
    """Satellite contract: for >= 2 stream entries, the artifact under
    an autotuner-chosen (block, prefetch, checkpoint) triple is
    byte-identical to the static default's — the tuner may only change
    speed."""

    def _tuned_conf(self, conf, prefix, store_dir, job, inputs):
        """Run once autotuned (records + chooses), then pin the chosen
        triple as explicit keys."""
        prof = ProfileStore(store_dir).load(job, corpus_digest(inputs))
        knobs = dict((prof or {}).get("knobs") or {})
        # the policy saw a tiny corpus: it must at least have re-sized
        # the block (clamped at the range floor), so the tuned side
        # really differs from the static one
        assert knobs, f"no knobs chosen for {job}"
        out = dict(conf)
        out.pop(f"{prefix}.stream.autotune", None)
        for key, val in knobs.items():
            out[f"{prefix}.{key}"] = f"{val:g}"
        # pin the full triple: knobs the policy left alone run at their
        # defaults on both sides, explicitly on the tuned one
        out.setdefault(f"{prefix}.stream.checkpoint.interval.mb", "256")
        out.setdefault(f"{prefix}.stream.prefetch.depth", "2")
        return out

    def test_dataset_fold_mi(self, tmp_path):
        from avenir_tpu.runner import run_job

        csv, schema = _churn(tmp_path)
        static_conf = {"mut.feature.schema.file.path": schema,
                       "mut.mutual.info.score.algorithms":
                           "mutual.info.maximization",
                       "mut.stream.block.size.mb": "0.01"}
        static = run_job("mutualInformation", static_conf, [csv],
                         str(tmp_path / "static.txt"))
        tuning = dict(static_conf,
                      **{"mut.stream.autotune": "true",
                         "mut.stream.autotune.dir": str(tmp_path / "t")})
        first = run_job("mutualInformation", tuning, [csv],
                        str(tmp_path / "first.txt"))
        tuned_conf = self._tuned_conf(static_conf, "mut",
                                      str(tmp_path / "t"),
                                      "mutualInformation", [csv])
        assert tuned_conf != static_conf
        tuned = run_job("mutualInformation", tuned_conf, [csv],
                        str(tmp_path / "tuned.txt"))
        assert _bytes_of(tuned) == _bytes_of(static) == _bytes_of(first)

    def test_bytes_fold_apriori(self, tmp_path):
        from avenir_tpu.runner import run_job

        csv = _seq(tmp_path)
        static_conf = {"fia.support.threshold": "0.3",
                       "fia.item.set.length": "2",
                       "fia.skip.field.count": "2",
                       "fia.stream.block.size.mb": "0.003"}
        static = run_job("frequentItemsApriori", static_conf, [csv],
                         str(tmp_path / "static"))
        tuning = dict(static_conf,
                      **{"fia.stream.autotune": "true",
                         "fia.stream.autotune.dir": str(tmp_path / "t")})
        first = run_job("frequentItemsApriori", tuning, [csv],
                        str(tmp_path / "first"))
        tuned_conf = self._tuned_conf(static_conf, "fia",
                                      str(tmp_path / "t"),
                                      "frequentItemsApriori", [csv])
        tuned = run_job("frequentItemsApriori", tuned_conf, [csv],
                        str(tmp_path / "tuned"))
        assert _bytes_of(tuned) == _bytes_of(static) == _bytes_of(first)


    def test_fused_churn_trio(self, tmp_path):
        """The same through `run_shared`: one profile for the batch,
        under the jobs' joined name, chosen from the fused pass's own
        telemetry; the trio under those knobs writes the static bytes."""
        from avenir_tpu.runner import run_shared

        csv, schema = _churn(tmp_path)
        prefixes = {"bayesianDistr": "bad", "mutualInformation": "mut",
                    "fisherDiscriminant": "fid"}
        base = {job: {f"{p}.feature.schema.file.path": schema,
                      f"{p}.stream.block.size.mb": "0.01"}
                for job, p in prefixes.items()}
        base["mutualInformation"]["mut.mutual.info.score.algorithms"] = \
            "mutual.info.maximization"
        tune_dir = str(tmp_path / "t")

        def fused(tag, overlay):
            return run_shared(
                [(job, {**base[job], **overlay(prefixes[job])},
                  str(tmp_path / f"{tag}_{prefixes[job]}"))
                 for job in prefixes], [csv])

        static = fused("static", lambda p: {})
        first = fused("first", lambda p: {
            f"{p}.stream.autotune": "true",
            f"{p}.stream.autotune.dir": tune_dir})
        prof = ProfileStore(tune_dir).load("+".join(sorted(prefixes)),
                                           corpus_digest([csv]))
        knobs = dict((prof or {}).get("knobs") or {})
        assert knobs, f"no knobs chosen for the batch (profile={prof})"
        assert (prof or {}).get("reasons"), prof
        tuned = fused("tuned", lambda p: {
            f"{p}.{key}": f"{val:g}" for key, val in knobs.items()})
        for job in prefixes:
            assert len(tuned[job].outputs) == len(static[job].outputs)
            assert _bytes_of(tuned[job]) == _bytes_of(static[job]) \
                == _bytes_of(first[job]), job


# ============================================== incremental checkpoint knob
class TestIncrementalCheckpointKnob:
    def test_checkpoint_rule_fires_on_incremental_run(self, tmp_path):
        """run_incremental is the one path emitting job.checkpoint
        spans; an autotuned refresh whose serialization exceeds the
        wall budget must move stream.checkpoint.interval.mb — and stay
        byte-identical to the cold solo run."""
        from avenir_tpu.runner import run_incremental, run_job

        csv, schema = _churn(tmp_path, rows=2500)
        base = {"mut.feature.schema.file.path": schema,
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization",
                "mut.stream.block.size.mb": "0.01",
                "mut.stream.checkpoint.interval.mb": "0.005"}
        cold = run_job("mutualInformation", base, [csv],
                       str(tmp_path / "cold.txt"))
        conf = dict(base, **{"mut.stream.autotune": "true",
                             "mut.stream.autotune.dir":
                                 str(tmp_path / "t")})
        incr = run_incremental("mutualInformation", conf, [csv],
                               str(tmp_path / "incr.txt"),
                               state_dir=str(tmp_path / "state"))
        assert _bytes_of(incr) == _bytes_of(cold)
        prof = ProfileStore(str(tmp_path / "t")).load(
            "mutualInformation", corpus_digest([csv]))
        assert prof is not None and prof["runs"]
        sig = prof["runs"][-1]["signals"]
        assert sig["checkpoint_s"] > 0      # the span reached the tuner
        knob = prof["knobs"].get("stream.checkpoint.interval.mb")
        if sig["checkpoint_s"] / max(sig["wall_s"], 1e-9) > 0.05:
            assert knob is not None and knob >= 32.0


# ====================================================== store + residuals
class TestProfileStore:
    def test_roundtrip_and_windows(self, tmp_path):
        store = ProfileStore(str(tmp_path / "t"))
        sig = RunSignals(wall_s=1.0, chunks=2).to_json()
        for i in range(40):
            store.record_run("j", "d", sig, {"stream.prefetch.depth": 2},
                             1.0)
            store.record_residual("j", "d", 100, 150 + i)
        prof = store.load("j", "d")
        from avenir_tpu.tune.store import MAX_RESIDUALS, MAX_RUNS

        assert len(prof["runs"]) == MAX_RUNS
        assert len(prof["residuals"]) == MAX_RESIDUALS
        assert prof["residuals"][-1]["measured"] == 189

    def test_set_knobs_validates(self, tmp_path):
        store = ProfileStore(str(tmp_path / "t"))
        with pytest.raises(KnobError):
            store.set_knobs("j", "d", {"nope": 1}, [])

    def test_residuals_recorded_when_run_sets_process_peak(
            self, tmp_path, monkeypatch):
        """Residual recording is gated on the run RAISING the process
        peak RSS: ru_maxrss is a lifetime peak, so inside a resident
        process re-recording the biggest job's number against every
        later small job would poison the learned admission factor."""
        from avenir_tpu import runner
        from avenir_tpu.runner import run_job

        csv, schema = _churn(tmp_path)
        conf = {"mut.feature.schema.file.path": schema,
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization",
                "mut.stream.block.size.mb": "0.01"}
        # pin the RSS readings: the gate under test compares lifetime
        # peaks across runs, and real ru_maxrss moves by a page or two
        # of allocator jitter between otherwise-identical runs — fake a
        # flat 1 GiB peak so run 2 provably does NOT raise it
        import resource

        class _Rusage:
            ru_maxrss = 1 << 20            # linux ru_maxrss is in KB
        monkeypatch.setattr(resource, "getrusage",
                            lambda who: _Rusage())
        monkeypatch.setattr(runner, "_rss_now", lambda: 0)
        monkeypatch.setattr(runner, "_residual_peak_seen", 0)
        run_job("mutualInformation", conf, [csv],
                str(tmp_path / "out.txt"))       # no autotune flag
        store = ProfileStore(os.path.join(str(tmp_path), ".avenir_tune"))
        prof = store.load("mutualInformation", corpus_digest([csv]))
        assert prof is not None
        assert len(prof["residuals"]) == 1
        rec = prof["residuals"][0]
        assert rec["predicted"] > 0 and rec["measured"] > 0
        # a second run in the same process does not move the lifetime
        # peak — no stale residual may be appended
        run_job("mutualInformation", conf, [csv],
                str(tmp_path / "out2.txt"))
        prof = store.load("mutualInformation", corpus_digest([csv]))
        assert len(prof["residuals"]) == 1


# ==================================================== admission correction
class TestResidualPricing:
    def test_factor_floor_and_cap(self):
        # measured UNDER predicted: the factor may never drop below 1.0
        assert residual_factor(
            [{"predicted": 100, "measured": 10}]) == 1.0
        assert residual_factor([]) == 1.0
        # over-prediction raises it; the cap bounds a wild sample
        assert residual_factor(
            [{"predicted": 100, "measured": 250}]) == 2.5
        assert residual_factor(
            [{"predicted": 1, "measured": 10 ** 9}]) == \
            tune.RESIDUAL_FACTOR_CAP

    def test_pricer_never_under_base_floor(self, tmp_path):
        """Acceptance pin: the residual correction never lowers an
        admission price below the uncorrected model's floor."""
        from avenir_tpu.server.jobserver import JobRequest

        csv, schema = _churn(tmp_path)
        req = JobRequest("mutualInformation",
                         {"mut.feature.schema.file.path": schema,
                          "mut.mutual.info.score.algorithms":
                              "mutual.info.maximization"},
                         [csv], str(tmp_path / "o"))
        base = lambda requests, reserve: 1000           # noqa: E731
        store = ProfileStore(str(tmp_path / "t"))
        digest = corpus_digest([csv])
        # history says the job measured at HALF its prediction: the
        # correction must clamp to 1.0, never discount below base
        store.record_residual("mutualInformation", digest, 1000, 500)
        pricer = tune.make_tuned_pricer(str(tmp_path / "t"), base=base)
        assert pricer([req], 0) == 1000
        # history says 3x over-prediction -> price rises with it
        store.record_residual("mutualInformation", digest, 1000, 3000)
        assert pricer([req], 0) == 3000
        # a wild sample caps at RESIDUAL_FACTOR_CAP x base
        store.record_residual("mutualInformation", digest, 1, 10 ** 12)
        assert pricer([req], 0) == int(1000 * tune.RESIDUAL_FACTOR_CAP)

    def test_admission_prices_the_overlaid_knobs(self, tmp_path):
        """An autotuned request is priced at the knobs the runner will
        OVERLAY, not the static conf — otherwise a tuned-up block size
        runs at a multiple of its admitted bytes."""
        from avenir_tpu.server.jobserver import (JobRequest,
                                                 price_request_bytes)

        csv, schema = _churn(tmp_path)
        tune_dir = str(tmp_path / "t")
        conf = {"mut.feature.schema.file.path": schema,
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization",
                "mut.stream.autotune": "true",
                "mut.stream.autotune.dir": tune_dir}
        req = JobRequest("mutualInformation", conf, [csv],
                         str(tmp_path / "o"))
        untuned = price_request_bytes([req])
        ProfileStore(tune_dir).set_knobs(
            "mutualInformation", corpus_digest([csv]),
            {"stream.block.size.mb": 256.0, "stream.prefetch.depth": 8},
            [])
        tuned = price_request_bytes([req])
        assert tuned > untuned
        # without the opt-in flag the profile is not consulted
        req_off = JobRequest(
            "mutualInformation",
            {k: v for k, v in conf.items() if "autotune" not in k},
            [csv], str(tmp_path / "o2"))
        assert price_request_bytes([req_off]) == untuned

    def test_server_uses_tuned_pricer_with_autotune_dir(self, tmp_path):
        from avenir_tpu.server.jobserver import JobServer

        srv = JobServer(autotune_dir=str(tmp_path / "t"),
                        state_root=str(tmp_path / "s"))
        try:
            assert srv._pricer is not None
            assert srv._pricer.__name__ == "pricer"   # the tuned wrapper
        finally:
            srv.shutdown(drain=False)


# ===================================================== batch composition
class TestBatchBalance:
    def test_balanced_predicate(self):
        assert batch_balanced([], 100.0)
        assert batch_balanced([None, None], 100.0)
        assert batch_balanced([50.0], None)
        assert batch_balanced([50.0], 150.0, ratio=4.0)
        assert not batch_balanced([50.0], 250.0, ratio=4.0)
        assert not batch_balanced([250.0], 50.0, ratio=4.0)

    def test_scheduler_splits_imbalanced_batch(self, tmp_path):
        """Two compatible requests whose profiled fold costs sit far
        apart must NOT ride one SharedScan when the autotune dir says
        so — each dispatches in its own batch."""
        from avenir_tpu.server.jobserver import JobRequest, JobServer

        csv, schema = _churn(tmp_path, rows=300)
        tune_dir = str(tmp_path / "t")
        store = ProfileStore(tune_dir)
        digest = corpus_digest([csv])
        store.note_fold_cost("bayesianDistr", digest, 1.0)
        store.note_fold_cost("mutualInformation", digest, 50.0)
        conf = lambda p: {f"{p}.feature.schema.file.path": schema}  # noqa: E731
        mi_conf = {**conf("mut"),
                   "mut.mutual.info.score.algorithms":
                       "mutual.info.maximization"}
        srv = JobServer(workers=1, autotune_dir=tune_dir,
                        state_root=str(tmp_path / "s"))
        try:
            t1 = srv.submit(JobRequest("bayesianDistr", conf("bad"), [csv],
                                       str(tmp_path / "nb"), tenant="a"))
            t2 = srv.submit(JobRequest("mutualInformation", mi_conf, [csv],
                                       str(tmp_path / "mi"), tenant="b"))
            srv.start()
            r1 = t1.result(timeout=120)
            r2 = t2.result(timeout=120)
            assert r1.counters["Server:BatchSize"] == 1.0
            assert r2.counters["Server:BatchSize"] == 1.0
        finally:
            srv.shutdown()
        # same submissions with costs inside the band DO batch (fresh
        # store: note_fold_cost EWMA-blends, so overwrite, don't nudge)
        tune_dir2 = str(tmp_path / "t2")
        store2 = ProfileStore(tune_dir2)
        store2.note_fold_cost("bayesianDistr", digest, 1.0)
        store2.note_fold_cost("mutualInformation", digest, 2.0)
        srv = JobServer(workers=1, autotune_dir=tune_dir2,
                        state_root=str(tmp_path / "s2"))
        try:
            t1 = srv.submit(JobRequest("bayesianDistr", conf("bad"), [csv],
                                       str(tmp_path / "nb2"), tenant="a"))
            t2 = srv.submit(JobRequest("mutualInformation", mi_conf, [csv],
                                       str(tmp_path / "mi2"), tenant="b"))
            srv.start()
            assert t1.result(timeout=120).counters["Server:BatchSize"] == 2.0
            assert t2.result(timeout=120).counters["Server:BatchSize"] == 2.0
        finally:
            srv.shutdown()


# ================================================== prefetch depth wiring
class TestPrefetchDepthKey:
    def test_feeds_honor_the_key(self, monkeypatch, tmp_path):
        from avenir_tpu.core import stream
        from avenir_tpu.core.config import JobConfig
        from avenir_tpu.core.schema import FeatureSchema

        csv, schema = _churn(tmp_path, rows=50)
        seen = []
        real = stream.prefetched

        def spy(items, depth=2):
            seen.append(depth)
            return real(items, depth=depth)

        monkeypatch.setattr(stream, "prefetched", spy)
        cfg = JobConfig({"stream.prefetch.depth": "5",
                         "stream.block.size.mb": "0.001"})
        fs = FeatureSchema.from_file(schema)
        list(stream.stream_job_inputs(cfg, [csv], fs))
        assert 5 in seen
        seen.clear()
        list(stream.stream_job_byte_blocks(cfg, [csv]))
        assert 5 in seen
        seen.clear()
        list(stream.stream_job_lines(cfg, [csv]))
        assert 5 in seen
        # floor: a zero/negative conf value degrades to depth 1
        assert stream.prefetch_depth(
            JobConfig({"stream.prefetch.depth": "0"})) == 1
        # default unchanged
        assert stream.prefetch_depth(JobConfig({})) == 2

    def test_footprint_model_prices_depth(self):
        from avenir_tpu.analysis.mem import footprint_model

        base = footprint_model("mutualInformation", 1 << 20)
        deep = footprint_model("mutualInformation", 1 << 20,
                               prefetch_depth=6)
        assert deep.total_bytes > base.total_bytes
        # default depth unchanged: the graftlint --mem band is priced
        # exactly as before this key existed
        assert footprint_model("mutualInformation", 1 << 20,
                               prefetch_depth=2).total_bytes == \
            base.total_bytes
        byte_base = footprint_model("markovStateTransitionModel", 1 << 20)
        byte_deep = footprint_model("markovStateTransitionModel", 1 << 20,
                                    prefetch_depth=6)
        assert byte_deep.terms["raw_blocks_in_flight"] == \
            byte_base.terms["raw_blocks_in_flight"] * 2  # (6+2)/(2+2)


# ============================================================ CLI surface
class TestTuneCli:
    def test_tune_renders_profiles(self, tmp_path, capsys):
        from avenir_tpu.tune.report import tune_main

        store = ProfileStore(str(tmp_path / "t"))
        store.record_run("mutualInformation", "beef",
                         RunSignals(wall_s=2.0, chunks=4,
                                    read_s=0.5).to_json(),
                         {"stream.prefetch.depth": 2}, 2.0)
        store.set_knobs("mutualInformation", "beef",
                        {"stream.block.size.mb": 8.0},
                        ["block 64->8MB (test)"])
        store.record_residual("mutualInformation", "beef", 100, 220)
        assert tune_main([str(tmp_path / "t")]) == 0
        out = capsys.readouterr().out
        assert "stream.block.size.mb=8" in out
        assert "block 64->8MB (test)" in out
        assert "residual_factor=2.2" in out
        assert tune_main([str(tmp_path / "t"), "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert rows[0]["job"] == "mutualInformation"
        assert rows[0]["defaults_moved"] == ["stream.block.size.mb"]

    def test_tune_missing_dir(self, tmp_path, capsys):
        from avenir_tpu.tune.report import tune_main

        assert tune_main([str(tmp_path / "nope")]) == 0
        assert "no autotune profiles" in capsys.readouterr().out
