"""Properties-driven job runner: the L6/L5 surface of the reference.

The reference is driven as `hadoop jar avenir.jar <ToolClass>
-Dconf.path=<props> IN OUT` from bash case-statement scripts
(resource/detr.sh:52, resource/knn.sh:76); every job reads namespaced keys
from one flat properties file (SURVEY §2.11, §5 config). This module keeps
that surface: a registry of jobs addressed by the reference's job names /
Tool class names, each reading the *same* config keys (`bad.*`, `nen.*`,
`dtb.*`, `fia.*`, `mst.*`, ...) from the same properties files, plus a
`Pipeline` that replaces the shell case statements.

What changes is the execution: a "job" here is an in-process call into the
jitted TPU kernels — no JVM spawn, no HDFS round trip between stages. Jobs
that the reference chains through intermediate HDFS files (e.g. the 5-stage
KNN pipeline, SURVEY §3.3) collapse into fused single jobs, but each stage
name is still addressable for drop-in pipeline parity.

Model/state files between iterative rounds stay plain files (SURVEY §5
checkpoint/resume): DecisionPathList JSON, itemset CSVs per Apriori k,
Markov matrix files, LR coefficient history.
"""

from __future__ import annotations

import contextlib
import io
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from avenir_tpu import obs as _obs
from avenir_tpu.core.config import (JobConfig, MissingConfigError,
                                    load_properties)
from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.utils.metrics import ConfusionMatrix, throughput_counters


@dataclass
class JobResult:
    """What a job hands back to the driver: Hadoop-counter-style counters
    (the reference's "Validation:*" groups, BayesianPredictor.java:170-180)
    plus produced file paths and an optional in-memory payload."""

    name: str
    counters: Dict[str, float] = field(default_factory=dict)
    outputs: List[str] = field(default_factory=list)
    payload: object = None

    def __repr__(self) -> str:
        return f"JobResult({self.name}, counters={self.counters}, outputs={self.outputs})"


JobFn = Callable[[JobConfig, List[str], str], JobResult]

# registry key (job name or Tool class alias) -> (canonical name, prefix, fn)
_REGISTRY: Dict[str, Tuple[str, str, JobFn]] = {}


def job(name: str, prefix: str, *aliases: str):
    """Register a job under its pipeline name + reference Tool class name."""

    def deco(fn: JobFn) -> JobFn:
        for key in (name, *aliases):
            _REGISTRY[key] = (name, prefix, fn)
        return fn

    return deco


def job_names() -> List[str]:
    return sorted(_REGISTRY)


def job_prefix(name: str) -> str:
    """The reference config prefix a registered job reads (e.g.
    greedyRandomBandit -> 'grb'); accepts aliases."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown job {name!r}")
    return _REGISTRY[name][1]


def _job_cfg(name: str, conf) -> Tuple[str, str, JobConfig]:
    """(canonical name, prefix, scoped JobConfig) for a registered job.
    `conf` is a properties file path, a dict, or a JobConfig."""
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown job {name!r}; known: {', '.join(job_names())}"
        )
    canonical, prefix, _fn = _REGISTRY[name]
    if isinstance(conf, str):
        if conf.endswith(".conf"):
            # Spark-surface HOCON config: one block per job name
            # (resource/atmTrans.conf, chombo-spark JobConfiguration)
            cfg = JobConfig.from_hocon(conf, canonical, prefix)
        else:
            cfg = JobConfig(load_properties(conf), prefix)
    elif isinstance(conf, dict):
        cfg = JobConfig(conf, prefix)
    else:
        cfg = conf.scoped(prefix)
    cfg.props["__job_name__"] = canonical
    return canonical, prefix, cfg


def run_job(name: str, conf, inputs: Sequence[str], output: str = "") -> JobResult:
    """Run a registered job. `conf` is a properties file path, a dict, or a
    JobConfig; the job sees it scoped under its reference prefix.

    Every streamed job's result additionally carries the memory-oracle
    counter pair: `Mem:PredictedPeakBytes` (the analysis/mem analytic
    footprint model at the job's block size and corpus) next to the
    measured `Mem:PeakRSS`, so a long-running process records the
    model's error over time.

    Where the process sees several chips, a job that has a route over
    them builds one mesh over all of them (`utils.devices.job_mesh`) and
    takes it; none is chosen by a key. Today that is the itemset miner's
    resident route (`frequentItemsApriori`); every other family keeps to
    the first device until a PR gives it a route."""
    canonical, _prefix, cfg = _job_cfg(name, conf)
    fn = _REGISTRY[canonical][2]
    if output:
        parent = os.path.dirname(os.path.abspath(output))
        os.makedirs(parent, exist_ok=True)
    session = _autotune_begin([canonical], [cfg], inputs)
    rss0 = _rss_now()
    sc0 = _sidecar_counters()
    t0 = _obs.now()
    try:
        res = fn(cfg, list(inputs), output)
    except BaseException:
        if session is not None:
            session.close()   # a leaked session would contaminate
        raise                 # every later one in this process
    _obs.record("job.run", t0, job=canonical)
    _note_sidecar_counters(canonical, res, sc0)
    _add_mem_counters(canonical, cfg, inputs, res, rss0=rss0)
    if session is not None:
        session.finish({canonical: res})
    return res


#: highest process-lifetime peak RSS (bytes) already attributed to a
#: streamed result. ru_maxrss is a LIFETIME peak: inside a resident
#: process every later job re-reads the biggest job's number, so a
#: residual recorded from it would poison the learned admission factor
#: for every small job that follows. Only a run that RAISES the peak
#: records one — exact for the one-job-per-process scale anchors (the
#: designed signal source), silent for the jobs residency dwarfs.
#: Unlocked int: a racing double/missed record costs one advisory
#: history sample, never a wrong knob or price.
_residual_peak_seen = 0


def _rss_now() -> int:
    """Current (not peak) resident bytes via /proc/self/statm; 0 where
    unavailable. Snapshotted at job start so the residual record can
    price the job's INCREMENTAL footprint (peak minus the resident
    baseline already paid — interpreter, jax, earlier jobs' sticky
    arenas), which is what the analytic model predicts; pairing the
    absolute peak against an incremental prediction would bake the
    process baseline into the learned admission factor."""
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * (os.sysconf("SC_PAGE_SIZE")
                                                or 4096)
    except (OSError, ValueError, IndexError):
        return 0


def _add_mem_counters(canonical: str, cfg: JobConfig,
                      inputs: Sequence[str], res: JobResult,
                      rss0: Optional[int] = None) -> None:
    """Attach the memory-oracle counters to a streamed job's result.
    Advisory by contract: a failure to PREDICT must never fail a job
    that already ran, so any error here drops the counters silently.

    Every streamed result also carries the delta-scan accounting triple
    next to the Mem:*/Cache:* counters — run_incremental fills the real
    numbers before this runs; a plain (cold) run keeps the zeros, so
    every streamed JobResult speaks one counter schema."""
    if canonical not in _STREAM_FOLDS:
        return
    res.counters.setdefault("Cache:HitBlocks", 0.0)
    res.counters.setdefault("Cache:DeltaBlocks", 0.0)
    res.counters.setdefault("Resume:SkippedBytes", 0.0)
    res.counters.setdefault("Sidecar:HitBlocks", 0.0)
    res.counters.setdefault("Sidecar:DeltaBlocks", 0.0)
    try:
        import resource

        from avenir_tpu.analysis.mem import corpus_stats, footprint_model

        paths = [p for p in inputs if os.path.exists(p)]
        if not paths:
            return
        # linux ru_maxrss is KB; this is the process peak at job end —
        # exact for the one-job-per-process scale anchors, an upper
        # bound inside long-lived processes
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        if "Mem:PredictedPeakBytes" not in res.counters:
            # run_incremental already priced the scan (its checkpoint
            # advisory) and pre-set the counter — don't re-sample the
            # corpus for the same number
            from avenir_tpu.core.stream import prefetch_depth

            block = int(cfg.get_float("stream.block.size.mb", 64.0)
                        * (1 << 20))
            stats = corpus_stats(paths, delim=cfg.field_delim_regex)
            schema = None
            schema_path = cfg.get("feature.schema.file.path")
            if schema_path:
                schema = FeatureSchema.from_file(schema_path)
            est = footprint_model(canonical, block, schema, stats,
                                  prefetch_depth=prefetch_depth(cfg))
            res.counters["Mem:PredictedPeakBytes"] = float(est.total_bytes)
        res.counters["Mem:PeakRSS"] = float(rss)
        # the tuner's model-refinement history: a streamed result whose
        # run RAISED the process peak (see _residual_peak_seen) lands
        # its predicted-vs-measured pair in the per-(job, corpus)
        # profile store — from day one, not only when autotune is on.
        # measured is the INCREMENTAL growth over the run's starting
        # RSS (rss0, captured by the caller), matching what the model
        # predicts; callers without a start snapshot (the warm-miner
        # fast path) record nothing.
        global _residual_peak_seen
        if rss > _residual_peak_seen:
            _residual_peak_seen = rss
            if rss0 is not None and rss - rss0 > 0:
                from avenir_tpu import tune

                tune.record_residual(
                    canonical, cfg, paths,
                    res.counters["Mem:PredictedPeakBytes"], rss - rss0)
    except Exception:
        pass


def _sidecar_counters() -> Optional[dict]:
    """Snapshot of the process-global sidecar hit/delta counters taken
    before a scan; _note_sidecar_counters pairs it with a second one to
    attribute the delta to a JobResult. None (and no attribution) when
    the sidecar layer cannot load."""
    try:
        from avenir_tpu.native import sidecar

        return sidecar.counters_snapshot()
    except Exception:
        return None


def _note_sidecar_counters(canonical: str, res: JobResult,
                           before: Optional[dict]) -> None:
    """Report the sidecar blocks this scan replayed (Sidecar:HitBlocks)
    vs parsed cold into the sidecar (Sidecar:DeltaBlocks). Counters are
    process-global, so a FUSED run attributes the shared scan's totals
    to every fold it fed — the replays genuinely served each of them.
    Advisory: any failure leaves the zeros _add_mem_counters installs."""
    if before is None or canonical not in _STREAM_FOLDS:
        return
    try:
        from avenir_tpu.native import sidecar

        after = sidecar.counters_snapshot()
        res.counters["Sidecar:HitBlocks"] = float(
            after["hit_blocks"] - before["hit_blocks"])
        res.counters["Sidecar:DeltaBlocks"] = float(
            after["delta_blocks"] - before["delta_blocks"])
    except Exception:
        pass


def _autotune_begin(canonicals: Sequence[str], cfgs: Sequence[JobConfig],
                    inputs: Sequence[str]):
    """Start an autotuned run when the (first) config opts in with the
    `stream.autotune` key and every job is streamed: overlays the
    profile store's chosen knobs onto the configs and returns the
    session whose ``finish(results)`` records this run's telemetry and
    chooses the next knobs (avenir_tpu.tune.begin_run). Returns None
    when autotune is off or inapplicable.

    Advisory EXCEPT for the knob guard: a profile naming an unknown or
    out-of-range knob key raises KnobError — loudly, so a typo'd tuned
    profile can never silently run defaults; any other storage failure
    degrades to an untuned run."""
    cfg0 = cfgs[0]
    if not cfg0.get_bool("stream.autotune", False):
        return None
    if not inputs or any(c not in _STREAM_FOLDS for c in canonicals):
        return None
    from avenir_tpu import tune

    try:
        return tune.begin_run(list(canonicals), list(cfgs), list(inputs))
    except tune.KnobError:
        raise
    except Exception:
        return None


# ---------------------------------------------------------------- helpers
def _out_file(output: str, part: str = "part-r-00000") -> str:
    """Output path contract: a directory (Hadoop-style `part-r-00000`
    inside) when the path ends with '/' or already is a directory, else a
    plain file."""
    if output.endswith(os.sep) or os.path.isdir(output):
        os.makedirs(output, exist_ok=True)
        return os.path.join(output, part)
    parent = os.path.dirname(os.path.abspath(output))
    os.makedirs(parent, exist_ok=True)
    return output


def _schema(cfg: JobConfig) -> FeatureSchema:
    return FeatureSchema.from_file(cfg.assert_get("feature.schema.file.path"))


def _dataset(path: str, cfg: JobConfig, keep_raw: bool = False) -> Dataset:
    return Dataset.from_csv(path, _schema(cfg), delim=cfg.field_delim_regex,
                            keep_raw=keep_raw)


def _read_lines(path: str) -> List[str]:
    with open(path) as fh:
        return [ln.rstrip("\n") for ln in fh if ln.strip()]


def _parse_sequences(lines: Sequence[str], delim: str, skip: int,
                     class_ord: Optional[int] = None):
    """Rows -> (ids, sequences, labels). First `skip` fields are meta
    (id/class); `class_ord` points into the full row. Token trim set is
    space/tab/CR — exactly the native seq_encode trim, so the python and
    native sequence paths tokenize identically."""
    ids, seqs, labels = [], [], []
    for ln in lines:
        toks = [t.strip(" \t\r") for t in ln.split(delim)]
        ids.append(toks[0] if skip > 0 else "")
        labels.append(toks[class_ord] if class_ord is not None else None)
        seqs.append(toks[skip:])
    return ids, seqs, labels


def _read_sequences(path: str, delim: str, skip: int,
                    class_ord: Optional[int] = None):
    return _parse_sequences(_read_lines(path), delim, skip, class_ord)


def _validate(class_values: Sequence[str], actual: np.ndarray,
              predicted: np.ndarray, pos_class: int) -> Dict[str, float]:
    """ConfusionMatrix.counters() — the reference's "Validation" Hadoop
    counter group (BayesianPredictor.java:170-180, int-percent scaled)."""
    cm = ConfusionMatrix(class_values, pos_class=pos_class)
    cm.add(actual, predicted)
    return cm.counters()


def _drive_fold(fold, chunks, job: str) -> int:
    """Drive one fold sink over a chunk iterator through ``SharedScan``
    — the single-sink special case of the scan-sharing executor, which
    is exactly what the one-job-one-scan paths always were. Routing the
    solo paths through it means per-chunk ``stream.fold`` spans and the
    ``chunk_latency_ms`` histogram come from ONE instrumentation point,
    so the solo and fused executions can never drift apart in what they
    report (or in how they close an abandoned prefetch worker)."""
    from avenir_tpu.core.stream import SharedScan

    scan = SharedScan(chunks)
    scan.add_sink(fold, label=job)
    return scan.run()


def _finish_fold(fold, output: str, job: str) -> JobResult:
    """fold.finish(output) under the ``job.finish`` span — the artifact
    write + fold seal phase of every streamed job, one call site shape
    for the solo, shared and incremental drivers."""
    t0 = _obs.now()
    res = fold.finish(output)
    _obs.record("job.finish", t0, job=job)
    return res


# ============================================================ scan sharing
# One disk read + one parse per chunk, fanned out to N registered fold
# sinks (core.stream.SharedScan). Every fold below is ALSO the body of its
# single-job streaming path, so the fused and one-job-one-scan executions
# share one implementation — which is what makes their outputs
# byte-identical (asserted by the chunk-invariance auditor's fused
# entries and tests/test_shared_scan.py).

class _NBDistrFold:
    """bayesianDistr (tabular) as a shared-scan sink: the donated-carry
    deferred NB fold (models/naive_bayes.py:_fold_batch_kernel) per
    Dataset chunk."""

    def __init__(self, cfg: JobConfig, inputs: Sequence[str], schema):
        self.cfg = cfg
        self.schema = schema
        self.model = None
        self.rows = 0

    def consume(self, ds: Dataset) -> None:
        from avenir_tpu.models.naive_bayes import NaiveBayesModel

        if self.model is None:
            # after the first parse, so data-discovered categorical
            # vocabularies are sized into the count tensors
            self.model = NaiveBayesModel.empty(self.schema)
        codes, bins = ds.feature_codes(self.model.binned_fields)
        if bins != self.model.bins:
            raise ValueError(
                "categorical vocabulary grew mid-stream (a chunk saw a "
                "value absent from the first chunk / declared "
                "cardinality); declare full cardinalities in the schema "
                "to stream")
        x_cont = ds.feature_matrix(self.model.cont_fields)
        self.model.accumulate(codes, ds.labels(), x_cont, defer=True)
        self.rows += len(ds)

    def finish(self, output: str) -> JobResult:
        from avenir_tpu.models.naive_bayes import NaiveBayesModel

        out = _out_file(output)
        model = self.model
        if model is None:
            model = NaiveBayesModel.empty(self.schema)
        model.flush()
        model.save(out, delim=self.cfg.field_delim)
        return JobResult("bayesianDistr",
                         {"Distribution Data:Records": self.rows},
                         [out], model)

    # ----------------------------------------------- merge algebra ops
    def merge(self, other: "_NBDistrFold") -> "_NBDistrFold":
        """Shard-merge: NB sufficient statistics are additive
        (NaiveBayesModel.merge — the reducer algebra), so merging shard
        folds equals folding the concatenated shards."""
        if other.model is not None:
            if self.model is None:
                self.model = other.model
            else:
                self.model.merge(other.model)
        self.rows += other.rows
        return self

    def state_dict(self) -> Dict[str, object]:
        meta = {"rows": self.rows, "cards": None}
        arrays: Dict[str, object] = {}
        if self.model is not None:
            m = self.model
            m.flush()
            # data-discovered categorical vocabularies are part of the
            # carry: codes in later chunks must keep meaning the same
            # tokens after a restore into a freshly-loaded schema
            meta["cards"] = {str(f.ordinal): list(f.cardinality)
                             for f in m.binned_fields if f.is_categorical}
            arrays = {"post": m.post_counts, "mom": m.cont_moments,
                      "cls": m.class_counts}
        return {"meta": np.array(json.dumps(meta)), **arrays}

    def load_state(self, state: Dict[str, object]) -> None:
        from avenir_tpu.models.naive_bayes import NaiveBayesModel

        meta = json.loads(str(state["meta"]))
        self.rows = int(meta["rows"])
        if meta["cards"] is None:
            return                      # checkpoint taken before any chunk
        by_ord = {f.ordinal: f for f in self.schema.fields}
        for o, card in meta["cards"].items():
            fld = by_ord[int(o)]
            if fld.is_categorical and list(fld.cardinality or []) != card:
                fld.cardinality = list(card)
                fld.discovered_cardinality = True
        self.model = NaiveBayesModel.empty(self.schema)
        for key, attr in (("post", "post_counts"), ("mom", "cont_moments"),
                          ("cls", "class_counts")):
            arr = np.asarray(state[key], np.float64)
            if arr.shape != getattr(self.model, attr).shape:
                raise ValueError(
                    f"checkpointed NB {attr} shape {arr.shape} does not "
                    f"match the schema-derived model "
                    f"{getattr(self.model, attr).shape}")
            setattr(self.model, attr, arr)


class _MutualInfoFold:
    """mutualInformation as a shared-scan sink: additive contingency
    tables folded per Dataset chunk (MutualInformationAnalyzer.add)."""

    def __init__(self, cfg: JobConfig, inputs: Sequence[str], schema):
        from avenir_tpu.models.explore import MutualInformationAnalyzer

        self.cfg = cfg
        self.inputs = list(inputs)
        self.schema = schema
        self.mi = MutualInformationAnalyzer()

    def consume(self, ds: Dataset) -> None:
        self.mi.add(ds)

    # ----------------------------------------------- merge algebra ops
    def merge(self, other: "_MutualInfoFold") -> "_MutualInfoFold":
        """Shard-merge: every MI table is an additive integer-count
        tensor (MutualInformationAnalyzer.merge)."""
        self.mi.merge(other.mi)
        return self

    def state_dict(self) -> Dict[str, object]:
        mi = self.mi
        meta = {"n": mi.n, "k": mi.k, "bins": list(mi.bins),
                "ordinals": ([f.ordinal for f in mi.fields]
                             if mi.fields is not None else None),
                "pairs": sorted(mi._pair)}
        arrays: Dict[str, object] = {}
        if mi.fields is not None:
            for i, fc in enumerate(mi._fc):
                arrays[f"fc_{i}"] = fc
            for (i, j) in mi._pair:
                arrays[f"pair_{i}_{j}"] = mi._pair[(i, j)]
                arrays[f"pairc_{i}_{j}"] = mi._pairc[(i, j)]
        return {"meta": np.array(json.dumps(meta)), **arrays}

    def load_state(self, state: Dict[str, object]) -> None:
        meta = json.loads(str(state["meta"]))
        if meta["ordinals"] is None:
            return                      # checkpoint taken before any chunk
        if self.schema is None:
            self.schema = _schema(self.cfg)
        mi = self.mi
        # the encodable field set is schema-derived, exactly what the
        # first add() would have installed (Dataset.encodable_feature_fields)
        mi.fields = [f for f in self.schema.feature_fields
                     if f.num_bins() > 0]
        if [f.ordinal for f in mi.fields] != list(meta["ordinals"]):
            raise ValueError(
                "checkpointed MI field ordinals do not match the schema")
        mi.k = int(meta["k"])
        mi.bins = [int(b) for b in meta["bins"]]
        mi.n = int(meta["n"])
        mi._fc = [np.asarray(state[f"fc_{i}"], np.float64)
                  for i in range(len(mi.fields))]
        mi._pair = {(i, j): np.asarray(state[f"pair_{i}_{j}"], np.float64)
                    for i, j in (tuple(p) for p in meta["pairs"])}
        mi._pairc = {(i, j): np.asarray(state[f"pairc_{i}_{j}"], np.float64)
                     for i, j in (tuple(p) for p in meta["pairs"])}

    def finish(self, output: str) -> JobResult:
        cfg, mi = self.cfg, self.mi
        if mi.fields is None:
            raise ValueError(f"mutualInformation: empty input "
                             f"(no records in {self.inputs})")
        mi.finalize()
        algos = cfg.get_list("mutual.info.score.algorithms", [])
        out = _out_file(output)
        delim = cfg.field_delim
        with open(out, "w") as fh:
            if cfg.get_bool("output.mutual.info", True):
                for f, fld in enumerate(mi.fields):
                    fh.write(f"featureClassMI{delim}{fld.ordinal}{delim}"
                             f"{mi.feature_class_mi[f]:.6f}\n")
            for algo in algos:
                scores = mi.score(algo,
                                  cfg.get_float("redundancy.factor", 1.0))
                for ordinal, s in scores:
                    fh.write(f"{algo}{delim}{ordinal}{delim}{s:.6f}\n")
        return JobResult("mutualInformation",
                         {"Basic:Records": mi.n}, [out], mi)


class _FisherFold:
    """fisherDiscriminant as a shared-scan sink: per-class moment fold
    per Dataset chunk (FisherDiscriminant.accumulate)."""

    def __init__(self, cfg: JobConfig, inputs: Sequence[str], schema):
        from avenir_tpu.models.discriminant import FisherDiscriminant

        self.cfg = cfg
        self.inputs = list(inputs)
        self.schema = schema
        self.fd = FisherDiscriminant()
        self.rows = 0

    def consume(self, ds: Dataset) -> None:
        self.fd.accumulate(ds)
        self.rows += len(ds)

    # ----------------------------------------------- merge algebra ops
    def merge(self, other: "_FisherFold") -> "_FisherFold":
        """Shard-merge: per-class (count, sum, sum-sq) moments are
        additive (FisherDiscriminant.merge)."""
        self.fd.merge(other.fd)
        self.rows += other.rows
        return self

    def state_dict(self) -> Dict[str, object]:
        fd = self.fd
        meta = {"rows": self.rows,
                "ordinals": ([f.ordinal for f in fd.fields]
                             if fd._cnt is not None else None)}
        arrays: Dict[str, object] = {}
        if fd._cnt is not None:
            arrays = {"cnt": fd._cnt, "s1": fd._s1, "s2": fd._s2}
        return {"meta": np.array(json.dumps(meta)), **arrays}

    def load_state(self, state: Dict[str, object]) -> None:
        meta = json.loads(str(state["meta"]))
        self.rows = int(meta["rows"])
        if meta["ordinals"] is None:
            return                      # checkpoint taken before any chunk
        if self.schema is None:
            self.schema = _schema(self.cfg)
        fd = self.fd
        fd.fields = [f for f in self.schema.feature_fields if f.is_numeric]
        if [f.ordinal for f in fd.fields] != list(meta["ordinals"]):
            raise ValueError(
                "checkpointed discriminant field ordinals do not match "
                "the schema")
        fd._cnt = np.asarray(state["cnt"], np.float64)
        fd._s1 = np.asarray(state["s1"], np.float64)
        fd._s2 = np.asarray(state["s2"], np.float64)

    def finish(self, output: str) -> JobResult:
        if self.rows == 0:
            raise ValueError(f"fisherDiscriminant: empty input "
                             f"(no records in {self.inputs})")
        self.fd.finalize()
        out = _out_file(output)
        self.fd.save(out, delim=self.cfg.field_delim)
        return JobResult("fisherDiscriminant", {}, [out], self.fd)


class _MarkovPerClassFold:
    """markovStateTransitionModel (per-class mode) as a shared-scan sink
    over RAW BYTE BLOCKS: native CSR encode + fit_csr per block when the
    C encoder is built, line decode + fit otherwise. The per-entity mode
    (mst.id.field.ordinals) keeps its own scan — its open-vocabulary key
    extraction is not a fan-out fold."""

    def __init__(self, cfg: JobConfig, inputs: Sequence[str], schema=None):
        from avenir_tpu.models.markov import MarkovStateTransitionModel
        from avenir_tpu.native.ingest import native_seq_ready

        if cfg.get_int_list("id.field.ordinals") is not None:
            raise ValueError(
                "markovStateTransitionModel per-entity mode "
                "(id.field.ordinals) is not shared-scan fusable")
        self.cfg = cfg
        self.inputs = list(inputs)
        states = cfg.get_list("model.states") or cfg.assert_list("state.list")
        scale = cfg.get_int("trans.prob.scale", 1000)
        self.class_ord = cfg.get_int("class.label.field.ord")
        self.skip = cfg.get_int("skip.field.count", 1)
        self.class_labels = cfg.get_list("class.labels")
        self.model = MarkovStateTransitionModel(
            states, scale=scale, class_labels=self.class_labels)
        self.delim = cfg.field_delim_regex
        # one shared vocabulary: states first (codes 0..S-1), then any
        # class labels that are not themselves state names
        vocab = list(states)
        for lab in self.class_labels or []:
            if lab not in vocab:
                vocab.append(lab)
        self.vocab = vocab
        self._index = {t: i for i, t in enumerate(vocab)}
        self.label_codes = np.asarray([vocab.index(lab)
                                       for lab in self.class_labels or []])
        self.native = native_seq_ready(self.delim)
        self.rows = 0

    def consume_encoded(self, blk) -> None:
        """Fold one sidecar-replayed block (native.sidecar.
        SidecarBytesBlock): rebuild the CSR code array seq_encode_native
        would have produced — meta columns re-encoded from their token
        buffers, tail codes mapped through a sidecar-vocab -> state-vocab
        LUT (unknown tokens and the empty token both land on -1, exactly
        the cold encode's sentinels) — and feed fit_csr. No tokenizer,
        no parse span: this is the parse-free repeat path."""
        from avenir_tpu.native.ingest import csr_region_mask

        lens = blk.counts + blk.skip
        offsets = np.zeros(blk.n + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        total = int(offsets[-1])
        codes = np.empty(total, np.int32)
        idx = self._index
        starts = offsets[:-1]
        for j in range(blk.skip):
            codes[starts + j] = [idx.get(t, -1) for t in blk.meta[j]]
        lut = np.full(blk.vocab_end + 1, -1, np.int32)
        for k in range(blk.vocab_end):
            lut[k + 1] = idx.get(blk.vocab[k], -1)
        if blk.skip:
            tail = csr_region_mask(offsets, blk.skip, total)
            codes[tail] = lut[blk.codes]
        else:
            codes[:] = lut[blk.codes]
        self.model.fit_csr(
            codes, offsets, skip=self.skip,
            class_ord=self.class_ord if self.class_labels else None,
            label_codes=self.label_codes)
        self.rows += blk.n

    def consume(self, data) -> None:
        if not isinstance(data, (bytes, bytearray)):
            self.consume_encoded(data)
        elif self.native:
            from avenir_tpu.native.ingest import seq_encode_native

            # cannot be None: availability + 1-byte delim pre-checked
            t0 = _obs.now()
            enc = seq_encode_native(data, self.delim, self.vocab)
            _obs.record("stream.parse", t0, sink="markov_csr",
                        nbytes=len(data))
            self.model.fit_csr(
                *enc, skip=self.skip,
                class_ord=self.class_ord if self.class_labels else None,
                label_codes=self.label_codes)
            self.rows += enc[1].shape[0] - 1
        else:
            t0 = _obs.now()
            lines = [ln.rstrip("\r")
                     for ln in data.decode("utf-8", "replace").split("\n")
                     if ln.strip()]
            _, seqs, labels = _parse_sequences(lines, self.delim, self.skip,
                                               self.class_ord)
            _obs.record("stream.parse", t0, sink="markov_lines",
                        nbytes=len(data))
            self.model.fit(seqs, labels if self.class_labels else None)
            self.rows += len(seqs)

    def finish(self, output: str) -> JobResult:
        out = _out_file(output)
        self.model.save(out, delim=self.cfg.field_delim)
        return JobResult("markovStateTransitionModel",
                         {"Basic:Records": self.rows}, [out], self.model)

    # ----------------------------------------------- merge algebra ops
    def merge(self, other: "_MarkovPerClassFold") -> "_MarkovPerClassFold":
        """Shard-merge: per-class bigram counts are additive
        (MarkovStateTransitionModel.merge)."""
        self.model.merge(other.model)
        self.rows += other.rows
        return self

    def state_dict(self) -> Dict[str, object]:
        meta = {"rows": self.rows, "states": self.model.states,
                "class_labels": self.model.class_labels}
        return {"meta": np.array(json.dumps(meta)),
                "counts": self.model.counts}

    def load_state(self, state: Dict[str, object]) -> None:
        meta = json.loads(str(state["meta"]))
        if meta["states"] != self.model.states \
                or meta["class_labels"] != self.model.class_labels:
            raise ValueError(
                "checkpointed markov states/class labels do not match "
                "the job config")
        arr = np.asarray(state["counts"], np.float64)
        if arr.shape != self.model.counts.shape:
            raise ValueError(
                f"checkpointed markov counts shape {arr.shape} does not "
                f"match {self.model.counts.shape}")
        self.model.counts = arr
        self.rows = int(meta["rows"])


def _cache_budget(cfg: JobConfig) -> int:
    """The encoded-block spill cache's on-disk byte budget
    (`stream.encoded.cache.budget.mb`, default generous — see
    native.ingest.DEFAULT_CACHE_BUDGET_BYTES). Exceeding it evicts whole
    least-recently-replayed sources; the job re-parses those and reports
    the eviction through Cache:EvictedBytes."""
    from avenir_tpu.native.ingest import DEFAULT_CACHE_BUDGET_BYTES

    return int(cfg.get_float("stream.encoded.cache.budget.mb",
                             DEFAULT_CACHE_BUDGET_BYTES / (1 << 20))
               * (1 << 20))


def _cache_counters(src) -> Dict[str, float]:
    """Spill-cache counters for a miner JobResult: on-disk spill bytes
    and what the byte budget evicted (0 in the healthy case — a nonzero
    value is the admission layer's signal that this corpus outgrew its
    cache budget)."""
    return {"Cache:SpillBytes": float(src.cache_nbytes),
            "Cache:EvictedBytes": float(src.cache_evicted_bytes)}


def _write_apriori_outputs(cfg: JobConfig, output: str, levels) -> List[str]:
    # the miners' artifact-write phase is their "finish": spanned here so
    # every miner path (solo job, fused fold sink, warm-source serve)
    # emits job.finish from one place
    t0 = _obs.now()
    outs = []
    with _obs.span("fia.output.write", files=len(levels)):
        os.makedirs(output or ".", exist_ok=True)
        for k, isl in enumerate(levels, start=1):
            p = os.path.join(output, f"itemsets-{k}.txt")
            isl.save(p, delim=cfg.field_delim)
            outs.append(p)
    _obs.record("job.finish", t0, job="frequentItemsApriori")
    return outs


def _write_gsp_outputs(cfg: JobConfig, output: str, levels) -> List[str]:
    t0 = _obs.now()
    os.makedirs(output or ".", exist_ok=True)
    outs = []
    delim = cfg.field_delim
    for k, seqs in sorted(levels.items()):
        p = os.path.join(output, f"sequences-{k}.txt")
        with open(p, "w") as fh:
            for cand, support in sorted(seqs.items()):
                fh.write(delim.join([*cand, f"{support:.6f}"]) + "\n")
        outs.append(p)
    _obs.record("job.finish", t0, job="candidateGenerationWithSelfJoin")
    return outs


def finish_miner_levels(canonical: str, cfg: JobConfig, levels,
                        n_rows: int, wall_s: float, output: str,
                        extra_counters: Optional[Dict[str, float]] = None
                        ) -> "JobResult":
    """Artifact write + counter assembly for a miner whose per-k levels
    were computed OUTSIDE a fold sink — the sharded per-k driver's
    finish: same writers and counter names as ``_MinerScanFold.finish``
    (and the warm-serve path), so a sharded miner's artifacts and
    result row are indistinguishable from the solo runner's."""
    if canonical == "frequentItemsApriori":
        counters = {"Apriori:MaxLength": len(levels),
                    **throughput_counters(n_rows, wall_s)}
        outs = _write_apriori_outputs(cfg, output, levels)
    else:
        counters = {"GSP:MaxLength": max(levels) if levels else 0,
                    **throughput_counters(n_rows, wall_s)}
        outs = _write_gsp_outputs(cfg, output, levels)
    counters.update(extra_counters or {})
    return JobResult(canonical, counters, outs, levels)


def _build_miner(canonical: str, cfg: JobConfig):
    """The miner object one prefixed conf describes — ONE constructor
    shared by the miner fold sink, the warm-serve path and the sharded
    per-k driver/worker, so a new mining knob cannot land in one of
    them and silently miss the others."""
    if canonical == "frequentItemsApriori":
        from avenir_tpu.models.association import FrequentItemsApriori

        return FrequentItemsApriori(
            support_threshold=cfg.assert_float("support.threshold"),
            max_length=cfg.get_int("item.set.length", 3),
            emit_trans_id=cfg.get_bool("emit.trans.id", False))
    if canonical == "candidateGenerationWithSelfJoin":
        from avenir_tpu.models.sequence import GSPMiner

        return GSPMiner(
            support_threshold=cfg.assert_float("support.threshold"),
            max_length=cfg.get_int("item.set.length", 3))
    raise ValueError(f"job {canonical!r} is not a multi-pass miner")


def _build_miner_source(canonical: str, cfg: JobConfig,
                        inputs: Sequence[str], spill: bool):
    """The streaming source a miner conf describes (the companion of
    :func:`_build_miner`): the association transaction reader or the
    GSP sequence reader, with the shared block/cache knobs applied."""
    block = int(cfg.get_float("stream.block.size.mb", 64.0) * (1 << 20))
    skip = cfg.get_int("skip.field.count", 1)
    if canonical == "frequentItemsApriori":
        from avenir_tpu.models.association import StreamingTransactionSource

        src = StreamingTransactionSource(
            list(inputs), delim=cfg.field_delim_regex,
            trans_id_ord=cfg.get_int("tans.id.ord", 0),
            skip_field_count=skip, marker=cfg.get("infreq.item.marker"),
            block_bytes=block, spill_cache=spill,
            cache_budget_bytes=_cache_budget(cfg))
    else:
        from avenir_tpu.models.sequence import StreamingSequenceSource

        src = StreamingSequenceSource(
            list(inputs), delim=cfg.field_delim_regex,
            skip_field_count=skip, block_bytes=block, spill_cache=spill,
            cache_budget_bytes=_cache_budget(cfg))
    _attach_sidecar_opts(src, cfg)
    return src


def _attach_sidecar_opts(src, cfg: JobConfig) -> None:
    """Point a miner source's own-read discovery scan at the cross-run
    columnar sidecar (SpillScanMixin._scan_all); a per-job
    `stream.sidecar=false` (or a load failure) leaves the attribute
    None and the scan cold."""
    try:
        from avenir_tpu.native import sidecar

        src.sidecar_opts = sidecar.opts_from_cfg(cfg)
    except Exception:
        pass


class _MinerScanFold:
    """A multi-pass miner's DISCOVERY pass as a shared-scan sink over raw
    byte blocks: pass 1 (vocabulary + k=1 supports) folds from the shared
    read — and spills the encoded-block cache — then finish() runs the
    remaining per-k rounds, which replay the cache instead of re-reading
    the corpus. Fusing markov + a miner's k=1 scan makes the whole
    multi-job, multi-pass flow cost ONE CSV read of the corpus."""

    def __init__(self, cfg: JobConfig, inputs: Sequence[str], job: str):
        self.cfg = cfg
        self.job = job
        self.t0 = time.perf_counter()
        self.miner = _build_miner(job, cfg)
        self.src = _build_miner_source(
            job, cfg, inputs, cfg.get_bool("stream.encoded.cache", True))
        self._sink = self.src.scan_consumer()
        self._sealed = False
        self._shards: List["_MinerScanFold"] = []
        # the job server's warm-state layer sets this (via run_shared's
        # fold_hook) to ADOPT the still-open source — and its committed
        # encoded-block cache — after finish(), so a repeat mining
        # request replays encoded blocks instead of re-parsing CSV
        self.keep_sources = False

    def consume(self, data: bytes) -> None:
        self._sink.consume(data)

    def _seal(self) -> None:
        """Finish the pass-1 scan exactly once (commits the spill cache;
        idempotent so merge() and finish() compose in any order)."""
        if not self._sealed:
            self._sink.finish()
            self._sealed = True

    def _n_rows(self) -> int:
        return (self.src.n_trans if self.job == "frequentItemsApriori"
                else self.src.n_rows)

    def finish(self, output: str) -> JobResult:
        self._seal()
        srcs = [self.src] + [f.src for f in self._shards]
        levels = (self.miner.mine_stream(self.src) if len(srcs) == 1
                  else self.miner.mine_stream_merged(srcs))
        n_rows = self._n_rows() + sum(f._n_rows() for f in self._shards)
        if self.job == "frequentItemsApriori":
            counters = {"Apriori:MaxLength": len(levels),
                        **throughput_counters(
                            n_rows, time.perf_counter() - self.t0),
                        **_cache_counters(self.src)}
            outs = _write_apriori_outputs(self.cfg, output, levels)
        else:
            counters = {"GSP:MaxLength": max(levels) if levels else 0,
                        **throughput_counters(
                            n_rows, time.perf_counter() - self.t0),
                        **_cache_counters(self.src)}
            outs = _write_gsp_outputs(self.cfg, output, levels)
        if not self.keep_sources:
            for src in srcs:
                src.close()
        return JobResult(self.job, counters, outs, levels)

    # ----------------------------------------------- merge algebra ops
    def merge(self, other: "_MinerScanFold") -> "_MinerScanFold":
        """Shard-merge: seal both shards' pass-1 scans and keep the
        shard sources side by side; finish() then drives the miner's
        sharded per-k driver (mine_stream_merged), which counts every
        candidate per shard through the one _stream_support fold and
        sums supports via the registered support-merge
        (models.association.merge_support_counts)."""
        if other.job != self.job:
            raise ValueError(
                f"cannot merge {other.job!r} fold into {self.job!r}")
        self._seal()
        other._seal()
        self._shards.append(other)
        self._shards.extend(other._shards)
        other._shards = []
        return self

    def state_dict(self) -> Dict[str, object]:
        if self._shards:
            raise ValueError(
                "checkpoint a miner fold before merging shards into it")
        src = self.src
        meta = {"job": self.job, "vocab": list(src.vocab),
                "n": self._n_rows(), "sealed": self._sealed,
                "t_max": getattr(src, "t_max", None)}
        return {"meta": np.array(json.dumps(meta)),
                "counts": np.asarray(src._scan_counts, np.int64)}

    def load_state(self, state: Dict[str, object]) -> None:
        meta = json.loads(str(state["meta"]))
        if meta["job"] != self.job:
            raise ValueError(
                f"checkpointed {meta['job']!r} state for a {self.job!r} "
                f"fold")
        src = self.src
        src.restore_scan_state(meta["vocab"], state["counts"])
        if self.job == "frequentItemsApriori":
            src.n_trans = int(meta["n"])
        else:
            src.n_rows = int(meta["n"])
            src.t_max = max(int(meta["t_max"] or 1), 1)
        if meta["sealed"]:
            self._sink.finish()
            self._sealed = True


def _apriori_fold(cfg, inputs, schema=None):
    return _MinerScanFold(cfg, inputs, "frequentItemsApriori")


def _gsp_fold(cfg, inputs, schema=None):
    return _MinerScanFold(cfg, inputs, "candidateGenerationWithSelfJoin")


def _merge_folds(a, b):
    """Default merge_states op: every registered fold sink implements
    the in-place additive merge contract."""
    return a.merge(b)


@dataclass(frozen=True)
class StreamFoldOps:
    """One streamed job's fold-sink registration: the scan kind, the
    sink factory, and the MERGE ALGEBRA ops that make its carry a
    mergeable, serializable fold state —
    ``merge_states(fold(A), fold(B)).finish() == fold(A++B).finish()``
    byte-identically, and ``restore_state(serialize_state(fold))``
    resumes a mid-scan carry to the same bytes. graftlint --merge
    (analysis/merge.py) proves both properties mechanically every
    round; the multi-host NB merge (tests/test_multihost.py) and the
    incremental/resumable-scan work build on the same ops.

    ``kind``: "dataset" folds consume schema-parsed Dataset chunks;
    "bytes" folds consume raw byte blocks (sequence-shaped corpora).
    ``factory(cfg, inputs, schema)`` builds the sink; ``merge_states``
    folds one sink's carry into another (default: ``a.merge(b)``)."""

    kind: str
    factory: Callable
    merge_states: Callable = _merge_folds

    def serialize_state(self, fold) -> bytes:
        """Checkpoint a fold's carry: an npz of the fold's
        ``state_dict()`` — numpy arrays plus one JSON ``meta`` entry,
        no pickle (a checkpoint must be loadable by a DIFFERENT process
        with no trust in the writer)."""
        buf = io.BytesIO()
        np.savez(buf, **fold.state_dict())
        return buf.getvalue()

    def restore_state(self, cfg: JobConfig, inputs: Sequence[str],
                      blob: bytes, schema=None):
        """Rebuild a fold sink from a checkpoint: a FRESH factory sink
        (same config surface a resumed process would construct) with
        the serialized carry loaded into it, ready to consume the
        remaining chunks."""
        if schema is None and self.kind == "dataset":
            schema = _schema(cfg)
        fold = self.factory(cfg, list(inputs), schema)
        with np.load(io.BytesIO(blob), allow_pickle=False) as z:
            state = {k: z[k] for k in z.files}
        fold.load_state(state)
        return fold


#: canonical job name -> StreamFoldOps (see the dataclass above)
_STREAM_FOLDS: Dict[str, StreamFoldOps] = {
    "bayesianDistr": StreamFoldOps("dataset", _NBDistrFold),
    "mutualInformation": StreamFoldOps("dataset", _MutualInfoFold),
    "fisherDiscriminant": StreamFoldOps("dataset", _FisherFold),
    "markovStateTransitionModel": StreamFoldOps("bytes",
                                                _MarkovPerClassFold),
    "frequentItemsApriori": StreamFoldOps("bytes", _apriori_fold),
    "candidateGenerationWithSelfJoin": StreamFoldOps("bytes", _gsp_fold),
}


def stream_fold_names() -> List[str]:
    """Jobs the scan-sharing executor can fuse."""
    return sorted(_STREAM_FOLDS)


def stream_fold_ops(job: str) -> StreamFoldOps:
    """The registered fold-sink ops of a streamed job (accepts
    aliases) — the public handle the merge auditor, the multi-host
    merge path and the incremental delta-scan driver
    (:func:`run_incremental`) all share."""
    canonical = _REGISTRY[job][0] if job in _REGISTRY else job
    if canonical not in _STREAM_FOLDS:
        raise KeyError(
            f"job {job!r} has no registered stream fold; streamed jobs: "
            f"{', '.join(stream_fold_names())}")
    return _STREAM_FOLDS[canonical]


def run_shared(specs: Sequence[Tuple[str, object, str]],
               inputs: Sequence[str],
               fold_hook: Optional[Callable] = None) -> Dict[str, JobResult]:
    """Run N registered jobs over the SAME inputs with ONE scan.

    `specs` is a sequence of (job name, conf, output path); every job
    must be shared-scan capable (stream_fold_names()) and they must
    agree on scan kind, stream block size and (for Dataset folds) the
    schema file + delimiter — one read, one parse, N folds. Each job
    still reads its own prefixed config and writes its own outputs;
    results come back keyed by canonical job name, byte-identical to
    running the jobs one scan each (the existing run_job path stays as
    the fallback and as the equivalence oracle).

    `fold_hook(canonical, fold)`, when given, is called with each fold
    sink right after construction — the job server's warm-state tap
    (e.g. setting a miner fold's ``keep_sources`` so the server can pin
    its encoded-block cache after the run). Purely observational: it
    must not consume chunks."""
    from avenir_tpu.core.schema import FeatureSchema as _FS
    from avenir_tpu.core.stream import (SharedScan, stream_job_byte_blocks,
                                        stream_job_inputs)

    if not specs:
        return {}
    built = []
    for name, conf, output in specs:
        canonical, _prefix, cfg = _job_cfg(name, conf)
        if canonical not in _STREAM_FOLDS:
            raise ValueError(
                f"job {name!r} is not shared-scan capable; fusable jobs: "
                f"{', '.join(stream_fold_names())}")
        ops = _STREAM_FOLDS[canonical]
        kind, factory = ops.kind, ops.factory
        if any(canonical == b[0] for b in built):
            raise ValueError(
                f"job {canonical!r} appears twice in one shared scan")
        built.append((canonical, kind, cfg, factory, output))
    # autotune overlay BEFORE the compatibility checks: one knob set
    # (the fused group's profile) lands on every member config, so the
    # block-size/delimiter agreement below judges the tuned values
    session = _autotune_begin([b[0] for b in built],
                              [b[2] for b in built], inputs)
    rss0 = _rss_now()
    try:
        kinds = {k for _, k, _, _, _ in built}
        if len(kinds) != 1:
            raise ValueError(
                f"cannot fuse jobs of mixed scan kinds {kinds}")
        kind = kinds.pop()
        blocks = {cfg.get_float("stream.block.size.mb", 64.0)
                  for _, _, cfg, _, _ in built}
        if len(blocks) != 1:
            raise ValueError(
                f"fused jobs disagree on stream.block.size.mb: {blocks}")
        delims = {cfg.field_delim_regex for _, _, cfg, _, _ in built}
        if len(delims) != 1:
            raise ValueError(
                f"fused jobs disagree on field delimiter: {delims}")
        cfg0 = built[0][2]
        schema = None
        if kind == "dataset":
            spaths = {cfg.assert_get("feature.schema.file.path")
                      for _, _, cfg, _, _ in built}
            if len(spaths) != 1:
                raise ValueError(
                    f"fused jobs disagree on the schema file: {spaths}")
            schema = _FS.from_file(spaths.pop())
            chunks = stream_job_inputs(cfg0, list(inputs), schema)
        else:
            # bytes-kind folds all dispatch on SidecarBytesBlock, so the
            # shared feed opts into the bytes sidecar when the fused
            # configs agree on the meta skip count (they must: the
            # packed format is skip-specific); disagreement keeps the
            # raw feed
            skips = {cfg.get_int("skip.field.count", 1)
                     for _, _, cfg, _, _ in built}
            chunks = stream_job_byte_blocks(
                cfg0, list(inputs),
                sidecar_skip=skips.pop() if len(skips) == 1 else None)
        sc0 = _sidecar_counters()
        scan = SharedScan(chunks)
        folds = []
        for canonical, _kind, cfg, factory, output in built:
            fold = factory(cfg, list(inputs), schema)
            if fold_hook is not None:
                fold_hook(canonical, fold)
            folds.append((canonical, fold, output))
            scan.add_sink(fold, label=canonical)
        t0 = _obs.now()
        chunks_scanned = scan.run()
        _obs.record("job.dispatch", t0, mode="shared",
                    chunks=chunks_scanned,
                    jobs=",".join(c for c, _f, _o in folds))
        results: Dict[str, JobResult] = {}
        for canonical, fold, output in folds:
            if output:
                parent = os.path.dirname(os.path.abspath(output))
                os.makedirs(parent, exist_ok=True)
            results[canonical] = _finish_fold(fold, output, canonical)
            _note_sidecar_counters(canonical, results[canonical], sc0)
            _add_mem_counters(canonical, next(
                cfg for c, _k, cfg, _f, _o in built if c == canonical),
                inputs, results[canonical], rss0=rss0)
    except BaseException:
        if session is not None:
            session.close()   # a leaked session would contaminate
        raise                 # every later one in this process
    if session is not None:
        session.finish(results)
    return results


def run_warm_miner(name: str, conf, inputs: Sequence[str], output: str,
                   src) -> JobResult:
    """Serve a multi-pass miner from a WARM, already-scanned streaming
    source: pass 1 is already folded (``scan_items``/``scan`` memoize
    the discovery counts) and every per-k pass replays the source's
    committed encoded-block cache, so an unchanged corpus serves with
    ZERO CSV parses — the job server's pinned-cache fast path.

    The caller owns ``src`` and its validity (the server checks the
    cache's per-block content gate, ``SpillScanMixin.cache_ready``,
    before routing here); this function never closes it. Mining
    parameters come from the REQUEST's conf — pass 1 does not depend on
    them, so one warm source serves any thresholds. Output files are
    byte-identical to the cold runner path: same miner, same per-k
    device folds, same writers (the warm path only skips re-deriving
    state the source already memoizes); throughput counters price the
    mining wall time alone, which is the point."""
    canonical, _prefix, cfg = _job_cfg(name, conf)
    if canonical not in ("frequentItemsApriori",
                         "candidateGenerationWithSelfJoin"):
        raise ValueError(
            f"job {name!r} has no warm-source path; warm-servable jobs: "
            f"frequentItemsApriori, candidateGenerationWithSelfJoin")
    t0 = time.perf_counter()
    miner = _build_miner(canonical, cfg)
    levels = miner.mine_stream(src)
    n_rows = (src.n_trans if canonical == "frequentItemsApriori"
              else src.n_rows)
    res = finish_miner_levels(canonical, cfg, levels, n_rows,
                              time.perf_counter() - t0, output,
                              extra_counters=_cache_counters(src))
    _add_mem_counters(canonical, cfg, inputs, res)
    return res


# ====================================================== incremental driver
def _incremental_state_dir(cfg: JobConfig, canonical: str,
                           inputs: Sequence[str]) -> str:
    """Where a job's delta-scan state (block fingerprints + fold-carry
    checkpoints) lives across runs: `stream.incremental.state.dir` when
    configured, else a `.avenir_incremental/<job>_<corpus digest>`
    directory next to the first input — deterministic per (job, input
    set), so a rerun of the same job over the same corpus finds its own
    state and two jobs over one corpus never collide."""
    from avenir_tpu.core import keys as _keys

    explicit = cfg.get("stream.incremental.state.dir")
    if explicit:
        return explicit
    digest = _keys.state_digest(canonical, inputs)
    base = os.path.dirname(os.path.abspath(inputs[0]))
    return os.path.join(base, ".avenir_incremental",
                        f"{canonical}_{digest}")


def _conf_digest(cfg: JobConfig) -> str:
    """Content digest of the configuration a checkpoint's carry was
    folded under — the canonical recipe lives in
    :func:`avenir_tpu.core.keys.conf_digest` (view-neutral keys are
    declared in ``core.keys.VIEW_NEUTRAL_KEYS``, verified by
    ``graftlint --keys``); this name survives for its importers."""
    from avenir_tpu.core import keys as _keys

    return _keys.conf_digest(cfg)


class _IncrementalPlan:
    """One job's restore plan + delta-fold state — the per-job half of
    an incremental run, shared by the solo driver (:func:`run_incremental`)
    and the fused one (:func:`run_incremental_shared`) so the two can
    never disagree on restore gating or checkpoint layout."""

    def __init__(self, canonical: str, cfg: JobConfig, ops: StreamFoldOps,
                 inputs: List[str], output: str, schema, store,
                 conf_digest: str):
        self.canonical = canonical
        self.cfg = cfg
        self.ops = ops
        self.inputs = inputs
        self.abs_inputs = [os.path.abspath(p) for p in inputs]
        self.output = output
        self.schema = schema
        self.store = store
        self.conf_digest = conf_digest
        self.block = int(cfg.get_float("stream.block.size.mb", 64.0)
                         * (1 << 20))
        self.interval = int(
            cfg.get_float("stream.checkpoint.interval.mb", 256.0)
            * (1 << 20))
        self.delim = cfg.field_delim_regex
        self.fold = None
        self.watermarks = [0] * len(inputs)
        self.fps: List[list] = [[] for _ in inputs]
        self.hit_blocks = 0
        self.skipped = 0
        self.seq = 0
        self.delta_blocks = 0
        self.since_ckpt = 0
        self.predicted: Optional[int] = None
        self.rss0 = _rss_now()


def _prepare_incremental(canonical: str, cfg: JobConfig, inputs: List[str],
                         output: str, state_dir: Optional[str],
                         schema=None) -> _IncrementalPlan:
    """Build one job's restore plan: load the newest checkpoint, verify
    its recorded fingerprints against the current files, and restore
    the carry when — and only when — the covered prefix still content-
    matches; anything else (torn/truncated checkpoint, in-place edit,
    changed job/conf/inputs, mid-line watermark on a grown file,
    unloadable carry) leaves a fresh cold fold. `schema` lets the fused
    driver hand every plan ONE schema object (the run_shared contract);
    the solo driver loads the job's own."""
    from avenir_tpu.core import incremental as incr

    ops = stream_fold_ops(canonical)
    if schema is None and ops.kind == "dataset":
        schema = _schema(cfg)
    conf_digest = _conf_digest(cfg)
    store = incr.CheckpointStore(
        state_dir or _incremental_state_dir(cfg, canonical, inputs))
    plan = _IncrementalPlan(canonical, cfg, ops, inputs, output, schema,
                            store, conf_digest)

    t_restore = _obs.now()
    loaded = store.load()
    if loaded is not None:
        meta, blob = loaded
        plan.seq = int(meta.get("seq", 0))
        old_inputs = [str(p) for p in meta.get("inputs", [])]
        # the recorded input list must be a PREFIX of the current one
        # (append-only at the corpus level too: new source files fold
        # wholly, like appended bytes); any other change — including a
        # conf or schema-content change, which would parse the delta
        # under a different view than the restored prefix — is a cold
        # scan
        usable = (meta.get("format") == 1
                  and meta.get("format_version", 1) == 1
                  and meta.get("job") == canonical
                  and meta.get("conf_digest") == conf_digest
                  and old_inputs == plan.abs_inputs[:len(old_inputs)])
        fold = None
        if usable:
            wm, kept = [], []
            for path, src_fps in zip(inputs, meta.get("fingerprints", [])):
                n, covered = incr.verified_prefix(path, src_fps)
                if n != len(src_fps):
                    usable = False      # stale: an in-place edit — cold
                    break
                if covered < os.path.getsize(path) \
                        and not incr.ends_at_newline(path, covered):
                    # the corpus' last line had no terminator, so the
                    # appended bytes EXTEND the already-folded row —
                    # resuming would skip its continuation: cold scan
                    usable = False
                    break
                wm.append(covered)
                kept.append(list(src_fps))
            if usable:
                try:
                    fold = ops.restore_state(cfg, inputs, blob,
                                             schema=schema)
                except Exception:
                    fold = None         # unloadable carry: cold scan
            if fold is not None:
                plan.fold = fold
                plan.watermarks[:len(wm)] = wm
                plan.fps[:len(kept)] = kept
                plan.hit_blocks = sum(len(x) for x in kept)
                plan.skipped = sum(wm)
    restored = plan.fold is not None
    if plan.fold is None:
        plan.watermarks = [0] * len(inputs)
        plan.fps = [[] for _ in inputs]
        plan.hit_blocks = 0
        plan.skipped = 0
        plan.fold = ops.factory(cfg, inputs, schema)
    _obs.record("job.restore", t_restore, job=canonical,
                restored=restored, skipped_bytes=plan.skipped)

    # the checkpoint footprint is priced against the graftlint-mem
    # analytic model (advisory: the oracle the job-server admission
    # layer consumes; a failure to predict never fails the scan)
    try:
        from avenir_tpu.analysis.mem import corpus_stats, footprint_model
        from avenir_tpu.core.stream import prefetch_depth

        stats = corpus_stats([p for p in inputs if os.path.exists(p)],
                             delim=plan.delim)
        plan.predicted = int(footprint_model(
            canonical, plan.block, schema, stats,
            prefetch_depth=prefetch_depth(cfg)).total_bytes)
    except Exception:
        pass
    return plan


def _plan_checkpoint(plan: _IncrementalPlan, complete: bool) -> None:
    """Commit one atomic checkpoint of a plan's carry + fingerprints."""
    from avenir_tpu.core import incremental as incr

    t0 = _obs.now()
    plan.seq += 1
    blob = plan.ops.serialize_state(plan.fold)
    meta = {"format": 1, "format_version": 1,
            "job": plan.canonical, "seq": plan.seq,
            "conf_digest": plan.conf_digest,
            "inputs": plan.abs_inputs, "block_bytes": plan.block,
            "watermarks": list(plan.watermarks),
            "fingerprints": plan.fps,
            "complete": complete,
            "predicted_peak_bytes": plan.predicted}
    saved = plan.store.save(meta, blob)
    _obs.record("job.checkpoint", t0, job=plan.canonical, seq=plan.seq,
                complete=complete, nbytes=len(blob))
    hook = incr._checkpoint_hook
    if hook is not None:
        hook(saved)


def _plan_finish(plan: _IncrementalPlan,
                 checkpoint: bool = True) -> JobResult:
    """Final (complete) checkpoint — written BEFORE finish() so the
    carry never reflects a finished/sealed fold — then the artifact and
    the delta-accounting counters. ``checkpoint=False`` (the sharded
    refresh's missing-worker-fingerprints fallback) emits the artifact
    without touching the store: the PREVIOUS checkpoint stays the
    newest — its carry and fingerprints are still mutually consistent,
    whereas stamping this carry with partial fingerprints would make
    the next refresh re-fold bytes the carry already covers."""
    if checkpoint:
        _plan_checkpoint(plan, complete=True)
    if plan.output:
        parent = os.path.dirname(os.path.abspath(plan.output))
        os.makedirs(parent, exist_ok=True)
    res = _finish_fold(plan.fold, plan.output, plan.canonical)
    res.counters["Cache:HitBlocks"] = float(plan.hit_blocks)
    res.counters["Cache:DeltaBlocks"] = float(plan.delta_blocks)
    res.counters["Resume:SkippedBytes"] = float(plan.skipped)
    if plan.predicted is not None:
        res.counters["Mem:PredictedPeakBytes"] = float(plan.predicted)
    _add_mem_counters(plan.canonical, plan.cfg, plan.inputs, res,
                      rss0=plan.rss0)
    return res


def _cold_delta_feed(plan: _IncrementalPlan, path: str, start: int,
                     size: int):
    """The historical delta loop body as a (offset, length, hash,
    payload) tuple feed: raw blocks of [start, size), blanks as payload
    None, dataset-kind blocks parsed under the stream.parse span."""
    from avenir_tpu.core import incremental as incr
    from avenir_tpu.core.stream import (is_blank_block, iter_byte_blocks,
                                        prefetched)

    feed = prefetched(iter_byte_blocks(path, plan.block,
                                       byte_range=(start, size),
                                       with_offsets=True), depth=1)
    try:
        for off, data in feed:
            fp = incr.block_fingerprint(off, data)
            if is_blank_block(data):
                yield off, len(data), fp["hash"], None
                continue
            if plan.ops.kind == "dataset":
                t0 = _obs.now()
                payload = Dataset.from_csv(data, plan.schema,
                                           delim=plan.delim)
                _obs.record("stream.parse", t0, path=path,
                            nbytes=len(data), rows=len(payload))
            else:
                payload = data
            yield off, len(data), fp["hash"], payload
    finally:
        feed.close()


def _delta_feed(plan: _IncrementalPlan, path: str, start: int, size: int):
    """One source's delta range as a tuple feed, preferring the columnar
    sidecar: a refresh whose delta bytes were already packed (by a
    plain run, or by the previous refresh's extension) replays them
    parse-free, the genuinely new tail parses cold AND extends the
    sidecar. Any doubt — no manifest, boundary mismatch with the
    checkpoint watermark, content drift — falls back to the cold loop,
    byte-identically."""
    feed = None
    try:
        from avenir_tpu.native import sidecar

        opts = sidecar.opts_from_cfg(plan.cfg)
        if plan.ops.kind == "dataset":
            feed = sidecar.dataset_blocks(
                opts, path, plan.schema, plan.delim, plan.block,
                byte_range=(start, size))
        else:
            feed = sidecar.byte_blocks(
                opts, path, plan.delim,
                plan.cfg.get_int("skip.field.count", 1), plan.block,
                byte_range=(start, size))
    except Exception:
        feed = None
    return feed if feed is not None \
        else _cold_delta_feed(plan, path, start, size)


def run_incremental(name: str, conf, inputs: Sequence[str],
                    output: str = "",
                    state_dir: Optional[str] = None) -> JobResult:
    """Run a streamed job INCREMENTALLY: restore the last serialized
    fold carry, fold only the byte blocks past its watermark, and
    re-emit the artifact — O(delta) instead of O(corpus) for an
    append-mostly corpus, byte-identical to a cold full scan by the
    proven fold-state merge algebra (graftlint --merge re-proves it
    every round).

    Mechanism: a per-(job, corpus) CheckpointStore
    (core.incremental, see `state_dir` / the
    `stream.incremental.state.dir` key) holds the newest carry
    (StreamFoldOps.serialize_state npz) plus the content fingerprints
    (offset + length + hash) of every block it covers. On entry the
    recorded fingerprints are re-verified against the current files:
    a verified prefix restores the carry and skips its bytes; anything
    else — a torn/truncated checkpoint, an in-place edit, a different
    input list — falls back to a cold scan (never to a wrong artifact).
    While scanning, the carry is re-checkpointed every
    `stream.checkpoint.interval.mb` (atomic write; a torn checkpoint
    never commits), so a killed scan resumes mid-corpus from its last
    watermark instead of byte 0. The final checkpoint (complete=True)
    is what the next append-refresh restores.

    The result carries the delta accounting next to the usual stream
    counters: Cache:HitBlocks (restored, fingerprint-verified blocks),
    Cache:DeltaBlocks (blocks folded this run) and Resume:SkippedBytes
    (bytes the restored carry covered)."""
    canonical, _prefix, cfg = _job_cfg(name, conf)
    inputs = [str(p) for p in inputs]
    # autotune overlay BEFORE the restore plan: the knobs land in the
    # conf digest, so a knob CHANGE re-scans cold (the documented
    # conservative gate for any conf change) and the next refresh under
    # the same knobs restores warm. This is also the only path that
    # emits job.checkpoint spans — the checkpoint-interval rule's
    # signal lives here.
    session = _autotune_begin([canonical], [cfg], inputs)
    try:
        plan = _prepare_incremental(canonical, cfg, inputs, output,
                                    state_dir)
        sc0 = _sidecar_counters()

        # --------------------------------------------------- delta fold
        for si, path in enumerate(inputs):
            size = os.path.getsize(path)
            start = plan.watermarks[si]
            if start >= size:
                continue
            feed = _delta_feed(plan, path, start, size)
            try:
                for off, length, fp_hash, payload in feed:
                    if payload is not None:
                        t0 = _obs.now()
                        plan.fold.consume(payload)
                        _obs.record("stream.fold", t0,
                                    sink=plan.canonical)
                    plan.fps[si].append({"offset": int(off),
                                         "length": int(length),
                                         "hash": fp_hash})
                    plan.watermarks[si] = off + length
                    plan.delta_blocks += 1
                    plan.since_ckpt += length
                    if plan.since_ckpt >= plan.interval:
                        _plan_checkpoint(plan, complete=False)
                        plan.since_ckpt = 0
            finally:
                feed.close()
        res = _plan_finish(plan)
        _note_sidecar_counters(canonical, res, sc0)
    except BaseException:
        if session is not None:
            session.close()   # a leaked session would contaminate
        raise                 # every later one in this process
    if session is not None:
        session.finish({canonical: res})
    return res


def run_incremental_shared(specs: Sequence[Tuple[str, object, str]],
                           inputs: Sequence[str],
                           state_dirs: Optional[Dict[str, str]] = None
                           ) -> Dict[str, JobResult]:
    """Refresh N streamed jobs over the SAME appended corpus with ONE
    delta scan: each job restores its own checkpointed carry
    (:func:`_prepare_incremental`, the exact solo restore gate), and
    jobs whose verified watermarks agree fold the appended blocks
    through one ``SharedScan`` pass — N refreshes, one disk read + one
    parse of the delta. Jobs whose watermarks differ (one was seeded at
    a different corpus size, one fell back to a cold scan) group
    separately and still run, so fusion is an optimization, never a
    correctness gate. Results are byte-identical to running
    :func:`run_incremental` per job — the merge auditor's
    fused-incremental leg re-proves this every round.

    `specs` is (job name, conf, output) like :func:`run_shared`, with
    the same compatibility contract (one scan kind, one block size, one
    delimiter, one schema file); `state_dirs` optionally maps canonical
    job names to checkpoint dirs (the job server's managed store) —
    unmapped jobs use their per-(job, corpus) default."""
    from avenir_tpu.core.stream import SharedScan

    if not specs:
        return {}
    inputs = [str(p) for p in inputs]
    built = []
    for name, conf, output in specs:
        canonical, _prefix, cfg = _job_cfg(name, conf)
        ops = stream_fold_ops(canonical)
        if any(canonical == b[0] for b in built):
            raise ValueError(
                f"job {canonical!r} appears twice in one shared refresh")
        built.append((canonical, cfg, ops, output))
    kinds = {ops.kind for _c, _cfg, ops, _o in built}
    if len(kinds) != 1:
        raise ValueError(f"cannot fuse refreshes of mixed scan kinds "
                         f"{kinds}")
    kind = kinds.pop()
    blocks = {cfg.get_float("stream.block.size.mb", 64.0)
              for _c, cfg, _o2, _o in built}
    if len(blocks) != 1:
        raise ValueError(
            f"fused refreshes disagree on stream.block.size.mb: {blocks}")
    delims = {cfg.field_delim_regex for _c, cfg, _o2, _o in built}
    if len(delims) != 1:
        raise ValueError(
            f"fused refreshes disagree on field delimiter: {delims}")
    delim = delims.pop()
    schema = None
    if kind == "dataset":
        spaths = {cfg.assert_get("feature.schema.file.path")
                  for _c, cfg, _o2, _o in built}
        if len(spaths) != 1:
            raise ValueError(
                f"fused refreshes disagree on the schema file: {spaths}")
        schema = FeatureSchema.from_file(spaths.pop())

    plans = []
    for canonical, cfg, ops, output in built:
        sd = (state_dirs or {}).get(canonical)
        plans.append(_prepare_incremental(canonical, cfg, inputs, output,
                                          sd, schema=schema))
    block = plans[0].block

    # one SharedScan per watermark group: every plan restored to the
    # same coverage folds the same delta blocks from one read + parse
    groups: Dict[tuple, List[_IncrementalPlan]] = {}
    for plan in plans:
        groups.setdefault(tuple(plan.watermarks), []).append(plan)

    def delta_feed(group: List[_IncrementalPlan]):
        """(source index, offset, length, hash, parsed-once payload)
        past the group's common watermark; payload is None for blank
        blocks (folds skip them, fingerprints still cover them). Routes
        through the columnar sidecar (_delta_feed) unless the group's
        bytes-kind configs disagree on the meta skip count the packed
        format is keyed to."""
        sidecar_ok = kind == "dataset" or len(
            {p.cfg.get_int("skip.field.count", 1) for p in group}) == 1
        for si, path in enumerate(inputs):
            size = os.path.getsize(path)
            start = group[0].watermarks[si]
            if start >= size:
                continue
            feed = (_delta_feed(group[0], path, start, size)
                    if sidecar_ok
                    else _cold_delta_feed(group[0], path, start, size))
            try:
                for off, length, fp_hash, payload in feed:
                    yield si, off, length, fp_hash, payload
            finally:
                feed.close()

    def fold_sink(plan: _IncrementalPlan):
        def consume(item) -> None:
            payload = item[4]
            if payload is not None:
                plan.fold.consume(payload)
        return consume

    def bookkeeper(group: List[_IncrementalPlan]):
        # runs AFTER the folds (sink order), so an interval checkpoint
        # serializes carries that already folded the current block —
        # the solo driver's exact ordering
        def consume(item) -> None:
            si, off, length, fp_hash, _payload = item
            for plan in group:
                plan.fps[si].append({"offset": int(off),
                                     "length": int(length),
                                     "hash": fp_hash})
                plan.watermarks[si] = off + length
                plan.delta_blocks += 1
                plan.since_ckpt += length
                if plan.since_ckpt >= plan.interval:
                    _plan_checkpoint(plan, complete=False)
                    plan.since_ckpt = 0
        return consume

    sc0 = _sidecar_counters()
    for group in groups.values():
        scan = SharedScan(delta_feed(group))
        for plan in group:
            scan.add_sink(fold_sink(plan), label=plan.canonical)
        scan.add_sink(bookkeeper(group), label="bookkeeper")
        t0 = _obs.now()
        chunks_scanned = scan.run()
        _obs.record("job.dispatch", t0, mode="incremental_shared",
                    chunks=chunks_scanned,
                    jobs=",".join(p.canonical for p in group))

    results: Dict[str, JobResult] = {}
    for plan in plans:
        res = _plan_finish(plan)
        _note_sidecar_counters(plan.canonical, res, sc0)
        results[plan.canonical] = res
    return results


# =================================================================== bayesian
@job("bayesianDistr", "bad", "org.avenir.bayesian.BayesianDistribution")
def bayesian_distribution(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """NB sufficient-stats training -> CSV model file (SURVEY §3.1).
    `bad.tabular.input=false` switches to the free-text mode: rows are
    `text,classVal`, each token contributes a (classVal, token) count
    (BayesianDistribution.mapText, :186-195)."""
    out = _out_file(output)
    if not cfg.get_bool("tabular.input", True):
        from avenir_tpu.models.text import TextNaiveBayes

        # token counts fold per streamed line block: the free-text mode
        # streams like the tabular one (mapText's per-line contract)
        from avenir_tpu.core.stream import iter_line_blocks, prefetched

        tmodel = TextNaiveBayes()
        rows = 0
        block = int(cfg.get_float("stream.block.size.mb", 64.0) * (1 << 20))
        for path in inputs:
            lineno = 0
            for lines in prefetched(iter_line_blocks(path, block)):
                texts, labels = [], []
                for ln in lines:
                    lineno += 1
                    text, sep, cls = ln.rpartition(cfg.field_delim_regex)
                    if not sep:
                        raise ValueError(
                            f"{path}:{lineno}: text-mode row has no "
                            f"{cfg.field_delim_regex!r} delimiter "
                            f"(want text,classVal)")
                    texts.append(text)
                    labels.append(cls.strip())
                tmodel.accumulate(texts, labels)
                rows += len(texts)
        tmodel.finish()
        tmodel.save(out, delim=cfg.field_delim)
        return JobResult("bayesianDistr",
                         {"Distribution Data:Records": rows},
                         [out], tmodel)

    from avenir_tpu.core.stream import stream_job_inputs

    # block streaming keeps host RSS O(block) however large the input —
    # the mapper's one-line-at-a-time contract at block granularity
    # (BayesianDistribution.java:137); counts are additive so chunking
    # cannot change the model. The fold sink IS the shared-scan sink
    # (_NBDistrFold): one-job-one-scan is the single-sink special case,
    # driven through SharedScan so the per-chunk fold spans come from
    # the same instrumentation point as the fused path.
    schema = _schema(cfg)
    fold = _NBDistrFold(cfg, inputs, schema)
    _drive_fold(fold, stream_job_inputs(cfg, inputs, schema),
                "bayesianDistr")
    return _finish_fold(fold, output, "bayesianDistr")


@job("bayesianPredictor", "bap", "org.avenir.bayesian.BayesianPredictor")
def bayesian_predictor(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Map-only NB posterior prediction (SURVEY §3.2). With
    `bap.output.feature.prob.only=true` emits per-row feature posterior
    P(features|actual class) — the quantity the KNN class-conditional
    pipeline joins in (BayesianPredictor.java:262-286)."""
    from avenir_tpu.models.naive_bayes import NaiveBayesModel, NaiveBayesPredictor

    from avenir_tpu.utils.metrics import CostBasedArbitrator

    schema = _schema(cfg)
    model = NaiveBayesModel.load(cfg.assert_get("bayesian.model.file.path"),
                                 schema, delim=cfg.field_delim)
    # cost-based arbitration (BayesianPredictor.java:140-144):
    # bap.predict.class.cost = falseNegCost,falsePosCost with
    # bap.predict.class = negClass,posClass (cardinality order fallback)
    arbitrator = None
    costs = cfg.get_list("predict.class.cost", delim=cfg.field_delim)
    if costs:
        classes = cfg.get_list("predict.class",
                               delim=cfg.field_delim) or schema.class_values()
        arbitrator = CostBasedArbitrator(classes[0], classes[1],
                                         int(costs[0]), int(costs[1]))
    pred = NaiveBayesPredictor(model, arbitrator=arbitrator)
    prob_only = cfg.get_bool("output.feature.prob.only", False)
    validate = cfg.get_bool("validation.mode", False)
    delim = cfg.field_delim
    out = _out_file(output)
    counters: Dict[str, float] = {}
    cls_vals = schema.class_values()
    # validation folds a ConfusionMatrix PER CHUNK (its count matrix is
    # additive), instead of collecting per-chunk label/code arrays and
    # concatenating at the end — that carry grew with rows seen, the
    # exact mem-unbounded-carry shape graftlint --mem flags
    cm: Optional[ConfusionMatrix] = None
    # map-only job: test rows stream in blocks (host RSS O(block))
    from avenir_tpu.core.stream import stream_job_inputs

    with open(out, "w") as fh:
        for ds in stream_job_inputs(cfg, inputs, schema, keep_raw=True):
            if prob_only:
                probs = pred.feature_prob(ds)
                for rid, p in zip(ds.ids(), probs):
                    fh.write(f"{rid}{delim}{p:.6g}\n")
            else:
                codes, post = pred.predict(ds)
                for raw, c, row_post in zip(ds.raw_rows, codes, post):
                    # row_post is the reference's int-percent-scaled
                    # unnormalized posterior; normalize across classes for
                    # the appended confidence field
                    tot = float(np.sum(row_post)) or 1.0
                    prob = int(np.rint(100.0 * row_post[int(c)] / tot))
                    fh.write(delim.join(raw + [cls_vals[int(c)], str(prob)]) + "\n")
                if validate:
                    if cm is None:
                        pos = cfg.get("positive.class.value")
                        cm = ConfusionMatrix(
                            cls_vals,
                            pos_class=cls_vals.index(pos) if pos else 1)
                    cm.add(ds.labels(), codes)
    if cm is not None:
        counters = cm.counters()
    return JobResult("bayesianPredictor", counters, [out])


# ======================================================================== knn
@job("nearestNeighbor", "nen", "org.avenir.knn.NearestNeighbor")
def nearest_neighbor(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Fused KNN: inputs = [train CSV, test CSV]. Replaces stages (1)-(5)
    of resource/knn.sh — all-pairs distance, NB feature-posterior weighting
    and the secondary-sorted top-k vote run as one device program
    (SURVEY §3.3). Key names follow knn.properties (incl. the reference's
    `class.condtion.weighted` spelling, NearestNeighbor.java:92)."""
    from avenir_tpu.models.knn import NearestNeighborClassifier

    from avenir_tpu.core.stream import stream_job_inputs

    train_path, test_path = inputs[0], inputs[-1]
    schema = _schema(cfg)
    delim = cfg.field_delim_regex
    train = Dataset.from_csv(train_path, schema, delim=delim)
    clf = NearestNeighborClassifier(
        train,
        top_match_count=cfg.get_int("top.match.count", 5),
        kernel_function=cfg.get("kernel.function", "none"),
        kernel_param=cfg.get_float("kernel.param", 1.0),
        class_cond_weighted=cfg.get_bool("class.condtion.weighted", False)
        or cfg.get_bool("class.condition.weighted", False),
        inverse_distance_weighted=cfg.get_bool("inverse.distance.weighted", False),
        decision_threshold=cfg.get_float("decision.threshold", -1.0),
        positive_class=cfg.get("positive.class.value"),
        # framework-specific fast-path toggles (no reference analog): the
        # lane-resident packed top-k kernel and the in-kernel fused vote
        packed=cfg.get_bool("device.packed.kernel", False),
        fused=cfg.get_bool("device.fused.vote", False),
    )
    out = _out_file(output)
    out_delim = cfg.field_delim
    cls_vals = schema.class_values()
    with_distr = cfg.get_bool("output.class.distr", False)
    validate = cfg.get_bool("validation.mode", False)
    # cost-based arbitration (NearestNeighbor.java:264-277, :383-387):
    # nen.misclassification.cost = falsePosCost,falseNegCost with
    # nen.class.attribute.values = posClass,negClass
    arbitrator = pos_i = neg_i = None
    if cfg.get_bool("use.cost.based.classifier", False):
        from avenir_tpu.utils.metrics import CostBasedArbitrator

        cav = cfg.get_list("class.attribute.values") or [
            cls_vals[1], cls_vals[0]]
        pos_v, neg_v = cav[0], cav[1]
        costs = cfg.assert_list("misclassification.cost")
        fp_cost, fn_cost = int(costs[0]), int(costs[1])
        arbitrator = CostBasedArbitrator(neg_v, pos_v, fn_cost, fp_cost)
        pos_i, neg_i = cls_vals.index(pos_v), cls_vals.index(neg_v)
        clf.positive_class = pos_i
    # queries stream in blocks against the resident train index — test-set
    # size never bounds host RSS (the model is the index, not the
    # queries); validation folds the additive ConfusionMatrix per chunk
    # instead of carrying every chunk's labels to the end
    cm: Optional[ConfusionMatrix] = None
    with open(out, "w") as fh:
        for test in stream_job_inputs(cfg, [test_path], schema):
            codes, scores = clf.predict(test)
            with _obs.span("knn.output.write", rows=len(test)):
                if arbitrator is not None:
                    # getClassProb int-percent scale
                    # (Neighborhood.java:319-334)
                    tot = np.maximum(scores.sum(axis=1), 1e-9)
                    pos_prob = np.floor(100.0 * scores[:, pos_i] / tot)
                    codes = np.where(arbitrator.classify(pos_prob),
                                     pos_i, neg_i).astype(np.int32)
                for i, (rid, c) in enumerate(zip(test.ids(), codes)):
                    fields = [str(rid), cls_vals[int(c)]]
                    if with_distr:
                        tot = float(np.sum(scores[i])) or 1.0
                        fields += [f"{cls_vals[j]}:{scores[i][j] / tot:.3f}"
                                   for j in range(len(cls_vals))]
                    fh.write(out_delim.join(fields) + "\n")
                if validate:
                    if cm is None:
                        cm = ConfusionMatrix(cls_vals,
                                             pos_class=clf.positive_class)
                    cm.add(test.labels(), codes)
    counters: Dict[str, float] = cm.counters() if cm is not None else {}
    return JobResult("nearestNeighbor", counters, [out])


# ================================================================= similarity
def _similarity_schema(cfg: JobConfig) -> FeatureSchema:
    """Accept any of the three reference key spellings for the schema:
    sifarish `sts.same.schema.file.path`, spark `rich.attr.schema.path`,
    or the framework-wide `feature.schema.file.path`."""
    for key in ("feature.schema.file.path", "same.schema.file.path",
                "rich.attr.schema.path"):
        path = cfg.get(key)
        if path:
            return FeatureSchema.from_file(path)
    raise MissingConfigError(
        f"missing schema config param: {cfg.prefix}.feature.schema.file.path")


@job("recordSimilarity", "sts", "sameTypeSimilarity",
     "org.avenir.spark.similarity.RecordSimilarity")
def record_similarity_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """All-pairs record distance file (the sifarish SameTypeSimilarity stage
    of resource/knn.sh:44-57 / RecordSimilarity.scala:34). One input =
    intra-set i<j pairs; two inputs (or sts.inter.set.matching=true) =
    cross-set pairs. Output rows: id1,id2,scaled-int-distance."""
    from avenir_tpu.models.similarity import RecordSimilarity

    schema = _similarity_schema(cfg)
    delim = cfg.field_delim_regex
    sim = RecordSimilarity(
        metric=cfg.get("distance.metric", "manhattan"),
        scale=cfg.get_int("distance.scale", 1000),
        num_weights=cfg.get_float_list("num.attribute.weights"),
        cat_weights=cfg.get_float_list("cat.attribute.weights"),
    )
    out = _out_file(output)
    inter = cfg.get_bool("inter.set.matching", len(inputs) > 1)
    if inter:
        base = Dataset.from_csv(inputs[0], schema, delim=delim)
        other = Dataset.from_csv(inputs[-1], schema, delim=delim)
        n = sim.save(sim.inter(base, other), out, delim=cfg.field_delim,
                     id_first=cfg.get_bool("output.id.first", True))
    else:
        ds = Dataset.from_csv(inputs[0], schema, delim=delim)
        n = sim.save(sim.intra(ds), out, delim=cfg.field_delim,
                     id_first=cfg.get_bool("output.id.first", True))
    return JobResult("recordSimilarity", {"Similarity:Pairs": n}, [out])


@job("groupedRecordSimilarity", "grs",
     "org.avenir.spark.similarity.GroupedRecordSimilarity")
def grouped_similarity_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.similarity import GroupedRecordSimilarity

    schema = _similarity_schema(cfg)
    ds = Dataset.from_csv(inputs[0], schema, delim=cfg.field_delim_regex)
    sim = GroupedRecordSimilarity(
        [int(o) for o in cfg.assert_list("group.field.ordinals")],
        metric=cfg.get("distance.metric", "manhattan"),
        scale=cfg.get_int("distance.scale", 1000),
    )
    out = _out_file(output)
    delim = cfg.field_delim
    n = 0
    with open(out, "w") as fh:
        for key, id1, id2, d in sim.grouped_intra(ds):
            sd = int(round(d * sim.scale))
            fh.write(delim.join([*key, id1, id2, str(sd)]) + "\n")
            n += 1
    return JobResult("groupedRecordSimilarity", {"Similarity:Pairs": n}, [out])


@job("featureCondProbJoiner", "fcb", "org.avenir.knn.FeatureCondProbJoiner")
def feature_cond_prob_joiner(cfg: JobConfig, inputs: List[str], output: str
                             ) -> JobResult:
    """Stage (4) of the 5-job KNN pipeline: join the pairwise-distance
    file (recordSimilarity output, `id1,id2,dist` tail fields) with the
    per-train-entity feature posterior file (bayesianPredictor
    bap.output.feature.prob.only output, `id,prob` rows) on the train
    entity. The fused nearestNeighbor job computes this weighting
    in-process; this job keeps the stage individually addressable for
    drop-in pipeline parity (FeatureCondProbJoiner.java:46; input split
    detection by filename prefix, :97-98 — here via
    fcb.feature.cond.prob.split.prefix, falling back to treating the LAST
    input as the probability file). Output rows:
    testId,trainId,distance,trainFeaturePostProb."""
    # both inputs are sibling-job OUTPUTS: split with the output delim
    # (field_delim_regex is the user-input delimiter and may differ)
    delim = cfg.field_delim
    prefix = cfg.get("feature.cond.prob.split.prefix", "condProb")
    prob_files = [p for p in inputs
                  if os.path.basename(p).startswith(prefix)]
    dist_files = [p for p in inputs if p not in prob_files]
    if not prob_files:
        prob_files, dist_files = [inputs[-1]], inputs[:-1]
    probs: Dict[str, str] = {}
    for p in prob_files:
        for ln in _read_lines(p):
            toks = [t.strip() for t in ln.split(delim)]
            probs[toks[0]] = toks[-1]
    # the distance file's column order follows the sts job's own key
    id_first = cfg.scoped("sts").get_bool("output.id.first", True)
    out = _out_file(output)
    od = cfg.field_delim
    n = 0
    with open(out, "w") as fh:
        for p in dist_files:
            for ln in _read_lines(p):
                toks = [t.strip() for t in ln.split(delim)]
                if id_first:
                    id1, id2, dist = toks[-3], toks[-2], toks[-1]
                else:
                    dist, id1, id2 = toks[-3], toks[-2], toks[-1]
                pr = probs.get(id2)
                if pr is None and id1 in probs:
                    # distance rows carry (test, train) in either slot
                    id1, id2 = id2, id1
                    pr = probs[id2]
                if pr is None:
                    continue
                fh.write(od.join([id1, id2, dist, pr]) + "\n")
                n += 1
    return JobResult("featureCondProbJoiner", {"Join:Pairs": n}, [out])


# ======================================================================= tree
def _tree_arguments(cfg: JobConfig, attr_strategy: str) -> Dict:
    """The `dtb.*` keys of a tree build, read once for `decTree` and
    `randomForest` alike; `attr_strategy` is the job's default for
    `dtb.split.attribute.selection.strategy`."""
    return dict(
        split_algorithm=cfg.get("split.algorithm", "entropy"),
        max_depth=cfg.get_int("max.depth.limit", 3),
        min_info_gain=cfg.get_float("min.info.gain.limit", -1.0),
        min_population=cfg.get_int("min.population.limit", -1),
        stopping_strategy=cfg.get("path.stopping.strategy", "maxDepth"),
        attr_selection_strategy=cfg.get("split.attribute.selection.strategy",
                                        attr_strategy),
    )


def _tree_builder(cfg: JobConfig, schema: FeatureSchema):
    from avenir_tpu.models.tree import DecisionTreeBuilder

    return DecisionTreeBuilder(schema, **_tree_arguments(cfg, "notUsedYet"))


@job("decTree", "dtb", "org.avenir.tree.DecisionTreeBuilder", "decisionTree")
def decision_tree(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Decision-tree build; the reference's per-level MR iteration with
    decPathIn/decPathOut file rotation (resource/detr.sh:34-54) runs as an
    internal device loop, but the DecisionPathList JSON still lands at
    `dtb.decision.file.path.out` for checkpoint parity."""
    ds = _dataset(inputs[0], cfg)
    # build against the dataset's OWN schema object: parsing may have
    # discovered vocabularies (e.g. an undeclared class cardinality in
    # the reference's call_hangup.json) that a fresh load lacks
    builder = _tree_builder(cfg, ds.schema)
    paths = builder.fit(ds)
    out = cfg.get("decision.file.path.out") or _out_file(output, "decPathOut.txt")
    with _obs.span("tree.write", files=1):
        paths.save(out)
    return JobResult("decTree", {"Tree:Paths": len(paths.paths)}, [out], paths)


@job("randomForest", "dtb", "org.avenir.tree.RandomForestBuilder")
def random_forest(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """The forest of resource/rafo.sh: `dtb.num.trees` trees over bootstrap
    samples (`dtb.sub.sampling.strategy`, `.rate`), each built as `decTree`
    builds its one from the same `dtb.*` keys, with `randomNotUsedYet`
    the default attribute selection; one `tree-NNN.json` a tree."""
    from avenir_tpu.models.tree import RandomForestBuilder

    ds = _dataset(inputs[0], cfg)
    forest = RandomForestBuilder(
        ds.schema,
        num_trees=cfg.get_int("num.trees", 10),
        sampling=cfg.get("sub.sampling.strategy", "withReplace"),
        sample_rate=cfg.get_float("sub.sampling.rate", 0.7),
        **_tree_arguments(cfg, "randomNotUsedYet"),
    ).fit(ds)
    outs = []
    if output:
        with _obs.span("tree.write", files=len(forest.trees)):
            os.makedirs(output, exist_ok=True)
            for t, tree in enumerate(forest.trees):
                p = os.path.join(output, f"tree-{t:03d}.json")
                tree.save(p)
                outs.append(p)
    return JobResult("randomForest", {"Tree:Trees": len(forest.trees)},
                     outs, forest)


@job("classPartitionGenerator", "cpg",
     "org.avenir.explore.ClassPartitionGenerator",
     "splitGenerator", "org.avenir.tree.SplitGenerator")
def class_partition_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Candidate-split class-histogram stats (cpg.* keys; the reference's
    two-job tree flow stage, ClassPartitionGenerator.java:61).

    Also answers to org.avenir.tree.SplitGenerator — the tree package's
    candidate-split stats base job (DecisionTreeBuilder extends it, which
    is how it slipped the original implements-Tool addressability scan:
    the Tool surface is inherited, not spelled in the subclass source)."""
    from avenir_tpu.models.explore import ClassPartitionGenerator

    ds = _dataset(inputs[0], cfg)
    attrs = cfg.get_int_list("split.attributes")
    cpg = ClassPartitionGenerator(
        ds, attributes=attrs,
        algorithm=cfg.get("split.algorithm", cfg.get("algorithm", "giniIndex")),
    )
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for s, stat in cpg.split_stats():
            fh.write(f"{s.attribute}{delim}{s.split_id}{delim}{stat:.6f}\n")
    return JobResult("classPartitionGenerator",
                     {"Splits:Candidates": len(cpg.splits)}, [out], cpg)


@job("dataPartitioner", "dap", "org.avenir.tree.DataPartitioner")
def data_partitioner_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.tree import DataPartitioner

    # keep_raw: partition output must pass rows through byte-identical
    # (reconstruction would reformat numerics and break on missing values)
    ds = _dataset(inputs[0], cfg, keep_raw=True)
    dp = DataPartitioner(
        ds.schema,
        algorithm=cfg.get("split.algorithm", "giniIndex"),
        split_attribute=cfg.get_int("split.attribute"),
    )
    base = cfg.get("project.base.path") or output
    paths = dp.partition(ds, base, delim=cfg.field_delim)
    return JobResult("dataPartitioner", {"Partition:Segments": len(paths)},
                     paths)


@job("contTimeStateTransitionStats", "cts",
     "org.avenir.spark.markov.ContTimeStateTransitionStats")
def ctmc_stats_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """CTMC statistics by uniformization (ContTimeStateTransitionStats.scala:34).
    `cts.state.trans.file.path` holds the rate matrix rows; input rows are
    `id,initState[,endState]`; `cts.state.trans.stat` picks stateDwellTime
    (target = cts.target.states[0]) or StateTransitionCount (targets[0:2]).

    Output-compat deviation vs the Scala job (documented on the model class
    too): the transition-count inner loop bound and the conditional
    normalization differ, so stats for identical inputs are close but not
    byte-identical to the reference's."""
    from avenir_tpu.models.markov import ContTimeStateTransitionStats

    states = cfg.assert_list("state.values")
    horizon = cfg.assert_float("time.horizon")
    rate_path = cfg.assert_get("state.trans.file.path")
    # two accepted rate-file shapes (the Scala job's cts.key.field.len
    # contract): a plain S x S numeric matrix, or stateTransitionRate's
    # per-entity output (`key,state,r0,...,rS-1` rows) — the supplier-
    # fulfillment flow (sup.sh transRate -> rateStat) hands the second
    # straight through, and stats are then looked up by the input row's
    # entity key
    per_entity: Dict[str, np.ndarray] = {}
    # shape sniffing by STRUCTURE, not parse failure (numeric entity ids
    # and state labels would make a per-entity file loadtxt-able): a
    # plain matrix row has S tokens; a per-entity row has S + 2 with the
    # second token being a state label
    first = next(iter(_read_lines(rate_path)), "")
    ftoks = [t.strip() for t in first.split(cfg.field_delim_regex)]
    if len(ftoks) == len(states) + 2 and ftoks[1] in states:
        rows: Dict[str, Dict[str, List[float]]] = {}
        for ln in _read_lines(rate_path):
            toks = [t.strip() for t in ln.split(cfg.field_delim_regex)]
            key, state, vals = toks[0], toks[1], [float(v) for v in toks[2:]]
            if state not in states or len(vals) != len(states):
                raise ValueError(
                    f"rate file row for {key!r} does not match "
                    f"state.values {states}")
            rows.setdefault(key, {})[state] = vals
        for key, by_state in rows.items():
            missing = [s for s in states if s not in by_state]
            if missing:
                raise ValueError(
                    f"entity {key!r} in {rate_path} has no rate row for "
                    f"state(s) {missing}")
            per_entity[key] = np.array([by_state[s] for s in states])
        rates = None
    else:
        rates = np.loadtxt(rate_path, delimiter=cfg.field_delim_regex,
                           ndmin=2)
        if rates.shape != (len(states), len(states)):
            raise ValueError(
                f"rate matrix in {rate_path} has shape {rates.shape}; "
                f"expected {(len(states), len(states))} for state.values "
                f"{states} (or stateTransitionRate per-entity rows)")

    stats_cache: Dict[str, ContTimeStateTransitionStats] = {}

    def stats_for(rid: str) -> ContTimeStateTransitionStats:
        if rates is not None:
            key = ""
        else:
            if rid not in per_entity:
                raise KeyError(f"no rate matrix for entity {rid!r} in "
                               f"{rate_path}")
            key = rid
        if key not in stats_cache:
            q = rates if rates is not None else per_entity[key]
            stats_cache[key] = ContTimeStateTransitionStats(
                q, states, horizon)
        return stats_cache[key]

    stat_kind = cfg.get("state.trans.stat", "stateDwellTime")
    targets = cfg.assert_list("target.states")
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for path in inputs:
            for ln in _read_lines(path):
                toks = [t.strip() for t in ln.split(cfg.field_delim_regex)]
                rid, init = toks[0], toks[1]
                end = toks[2] if len(toks) > 2 else None
                st = stats_for(rid)
                if stat_kind == "stateDwellTime":
                    v = st.dwell_time(init, targets[0], end)
                else:
                    v = st.transition_count(init, targets[0], targets[1], end)
                fh.write(f"{rid}{delim}{v:.6f}\n")
    return JobResult("contTimeStateTransitionStats", {},
                     [out], stats_cache)


@job("stateTransitionRate", "str",
     "org.avenir.spark.markov.StateTransitionRate")
def state_transition_rate_job(cfg: JobConfig, inputs: List[str],
                              output: str) -> JobResult:
    """Per-entity CTMC transition-rate matrices from timestamped state
    rows (StateTransitionRate.scala:30): group by str.key.field.ordinals,
    sort by the epoch-time field, rate(i->j) = count(i->j) / dwell(i)
    with dwell scaled to str.rate.time.unit (hour/day/week) and diagonal
    set to -sum(off-diagonal row) as the Scala job does. Input timestamps
    are ms, sec, or s-since-epoch per str.input.time.unit."""
    from avenir_tpu.models.markov import StateTransitionRate

    key_ords = cfg.get_int_list("key.field.ordinals", [0])
    time_ord = cfg.assert_int("time.field.ordinal")
    state_ord = cfg.assert_int("state.field.ordinal")
    states = cfg.assert_list("state.values")
    in_unit = cfg.get("input.time.unit", "ms")
    try:
        to_ms = {"ms": 1.0, "sec": 1000.0, "s": 1000.0}[in_unit]
    except KeyError:
        raise ValueError(f"invalid input time unit {in_unit!r}")
    rate_unit = cfg.get("rate.time.unit", "hour")
    try:
        unit_ms = {"hour": 3.6e6, "day": 8.64e7, "week": 6.048e8}[rate_unit]
    except KeyError:
        raise ValueError(f"invalid rate time unit {rate_unit!r}")
    prec = cfg.get_int("trans.rate.output.precision", 6)

    by_key: Dict[str, List[Tuple[float, str]]] = {}
    for p in inputs:
        for ln in _read_lines(p):
            toks = [t.strip() for t in ln.split(cfg.field_delim_regex)]
            key = cfg.field_delim.join(toks[o] for o in key_ords)
            by_key.setdefault(key, []).append(
                (float(toks[time_ord]) * to_ms, toks[state_ord]))
    out = _out_file(output)
    delim = cfg.field_delim
    models: Dict[str, StateTransitionRate] = {}
    with open(out, "w") as fh:
        for key, events in sorted(by_key.items()):
            events.sort(key=lambda e: e[0])
            seq = [(s, t / unit_ms) for t, s in events]
            model = StateTransitionRate(states).fit([seq])
            models[key] = model
            q = model.rates()
            q = q - np.diag(q.sum(axis=1))
            for i, s in enumerate(states):
                row = delim.join(f"{v:.{prec}f}" for v in q[i])
                fh.write(f"{key}{delim}{s}{delim}{row}\n")
    return JobResult("stateTransitionRate",
                     {"Basic:Entities": len(by_key)}, [out], models)


# ==================================================================== explore
@job("mutualInformation", "mut", "org.avenir.explore.MutualInformation")
def mutual_information_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.core.stream import stream_job_inputs

    # block streaming: MI's count tables fold additively per chunk, so
    # host RSS stays O(block) at any input size (the mapper contract of
    # MutualInformation.java:138-216); the fold sink doubles as the
    # shared-scan sink (_MutualInfoFold)
    fold = _MutualInfoFold(cfg, inputs, None)
    _drive_fold(fold, stream_job_inputs(cfg, inputs, _schema(cfg)),
                "mutualInformation")
    return _finish_fold(fold, output, "mutualInformation")


@job("ruleEvaluator", "rue", "org.avenir.explore.RuleEvaluator")
def rule_evaluator(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """rue.rule.<name> definitions `cond1 & cond2 => cons` evaluated for
    support/confidence (RuleEvaluator.java:48)."""
    from avenir_tpu.core.stream import stream_job_inputs
    from avenir_tpu.models.explore import Rule

    names = cfg.assert_list("rule.names")
    cond_delim = cfg.get("cond.delim", "&")
    rules = {}
    for name in names:
        expr = cfg.assert_get(f"rule.{name}")
        if expr.count("=>") != 1:
            raise ValueError(
                f"{cfg.prefix}.rule.{name} must contain exactly one '=>' "
                f"(cond => cons), got: {expr!r}")
        cond_part, cons_part = expr.split("=>")
        rules[name] = Rule(
            [c.strip() for c in cond_part.split(cond_delim) if c.strip()],
            [c.strip() for c in cons_part.split(cond_delim) if c.strip()],
        )
    # all rules fold their (rows, cond, both) counts per streamed chunk
    totals = {name: [0, 0, 0] for name in names}
    rows_seen = 0
    for chunk in stream_job_inputs(cfg, inputs, _schema(cfg)):
        rows_seen += len(chunk)
        for name, rule in rules.items():
            for i, v in enumerate(rule.counts(chunk)):
                totals[name][i] += v
    if rows_seen == 0:
        raise ValueError(f"ruleEvaluator: empty input "
                         f"(no records in {inputs})")
    out = _out_file(output)
    delim = cfg.field_delim
    results = {}
    with open(out, "w") as fh:
        for name in names:
            res = Rule.finalize(*totals[name])
            results[name] = res
            fh.write(f"{name}{delim}{res['support']:.6f}{delim}"
                     f"{res['confidence']:.6f}\n")
    return JobResult("ruleEvaluator", {"Basic:Records": rows_seen},
                     [out], results)


@job("cramerCorrelation", "crc", "org.avenir.explore.CramerCorrelation")
@job("categoricalCorrelation", "cac",
     "org.avenir.explore.CategoricalCorrelation")
def cramer_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Cramér-index categorical<->class correlation (crc.*); the cac.* job
    computes the same contingency-table stat (CramerCorrelation.java:54)."""
    from avenir_tpu.core.stream import stream_job_inputs
    from avenir_tpu.models.explore import ContingencyAccumulator

    name = cfg.props.get("__job_name__", "cramerCorrelation")
    acc = ContingencyAccumulator()
    for chunk in stream_job_inputs(cfg, inputs, _schema(cfg)):
        acc.add(chunk)
    if acc.n == 0:
        raise ValueError(f"{name}: empty input (no records in {inputs})")
    corr = acc.cramer()
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for ordinal, v in sorted(corr.items()):
            fh.write(f"{ordinal}{delim}{v:.6f}\n")
    return JobResult(name, {"Basic:Records": acc.n}, [out], corr)


@job("heterogeneityReduction", "hrc",
     "org.avenir.explore.HeterogeneityReductionCorrelation")
def heterogeneity_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.core.stream import stream_job_inputs
    from avenir_tpu.models.explore import ContingencyAccumulator

    acc = ContingencyAccumulator()
    for chunk in stream_job_inputs(cfg, inputs, _schema(cfg)):
        acc.add(chunk)
    if acc.n == 0:
        raise ValueError(f"heterogeneityReduction: empty input "
                         f"(no records in {inputs})")
    corr = acc.heterogeneity(cfg.get("heterogeneity.algorithm", "entropy"))
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for ordinal, v in sorted(corr.items()):
            fh.write(f"{ordinal}{delim}{v:.6f}\n")
    return JobResult("heterogeneityReduction",
                     {"Basic:Records": acc.n}, [out], corr)


@job("numericalCorrelation", "nuc",
     "org.avenir.explore.NumericalCorrelation")
def numerical_corr_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.core.stream import stream_job_inputs
    from avenir_tpu.models.explore import NumericMomentAccumulator

    schema = _schema(cfg)
    acc = NumericMomentAccumulator()
    for chunk in stream_job_inputs(cfg, inputs, schema):
        acc.add(chunk)
    if acc.n == 0:
        raise ValueError(f"numericalCorrelation: empty input "
                         f"(no records in {inputs})")
    corr = acc.correlation()           # [D+1, D+1]: class is the last column
    fields = [f.ordinal for f in schema.feature_fields if f.is_numeric]
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for i, oi in enumerate(fields):
            for j, oj in enumerate(fields):
                if j > i:
                    fh.write(f"{oi}{delim}{oj}{delim}{corr[i, j]:.6f}\n")
            # feature-vs-class correlation: the relevance signal this
            # family of jobs exists to emit
            fh.write(f"{oi}{delim}class{delim}{corr[i, -1]:.6f}\n")
    return JobResult("numericalCorrelation",
                     {"Basic:Records": acc.n}, [out], corr)


@job("reliefFeatureRelevance", "ffr",
     "org.avenir.explore.ReliefFeatureRelevance")
def relief_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.explore import relief_relevance

    ds = _dataset(inputs[0], cfg)
    rel = relief_relevance(ds, sample_size=cfg.get_int("sample.size"))
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for ordinal, v in sorted(rel.items()):
            fh.write(f"{ordinal}{delim}{v:.6f}\n")
    return JobResult("reliefFeatureRelevance", {}, [out], rel)


@job("categoricalClassAffinity", "cca",
     "org.avenir.explore.CategoricalClassAffinity")
def class_affinity_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.core.stream import stream_job_inputs
    from avenir_tpu.models.explore import (ContingencyAccumulator,
                                           class_affinity_from_table)

    schema = _schema(cfg)
    acc = ContingencyAccumulator()
    for chunk in stream_job_inputs(cfg, inputs, schema):
        acc.add(chunk)
    if acc.n == 0:
        raise ValueError(f"categoricalClassAffinity: empty input "
                         f"(no records in {inputs})")
    top_n = cfg.get_int("top.count", 3)
    out = _out_file(output)
    delim = cfg.field_delim
    payload = {}
    with open(out, "w") as fh:
        for fld in schema.feature_fields:
            if not fld.is_categorical or fld.ordinal not in acc.tables:
                continue
            aff = class_affinity_from_table(
                acc.tables[fld.ordinal], fld, schema.class_values(), top_n)
            payload[fld.ordinal] = aff
            for cv, pairs in aff.items():
                for val, score in pairs:
                    fh.write(f"{fld.ordinal}{delim}{cv}{delim}{val}"
                             f"{delim}{score:.6f}\n")
    return JobResult("categoricalClassAffinity",
                     {"Basic:Records": acc.n}, [out], payload)


@job("categoricalContinuousEncoding", "coe",
     "org.avenir.explore.CategoricalContinuousEncoding")
def supervised_encoding_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.core.stream import stream_job_inputs
    from avenir_tpu.models.explore import (ContingencyAccumulator,
                                           supervised_encoding_from_table)

    schema = _schema(cfg)
    acc = ContingencyAccumulator()
    for chunk in stream_job_inputs(cfg, inputs, schema):
        acc.add(chunk)
    if acc.n == 0:
        raise ValueError(f"categoricalContinuousEncoding: empty input "
                         f"(no records in {inputs})")
    strategy = cfg.get("encoding.strategy", "supervisedRatio")
    pos = cfg.get("pos.class.attr.value")
    out = _out_file(output)
    delim = cfg.field_delim
    payload = {}
    with open(out, "w") as fh:
        for fld in schema.feature_fields:
            if not fld.is_categorical or fld.ordinal not in acc.tables:
                continue
            enc = supervised_encoding_from_table(
                acc.tables[fld.ordinal], fld, schema.class_values(),
                strategy=strategy, pos_class=pos)
            payload[fld.ordinal] = enc
            for val, code in enc.items():
                fh.write(f"{fld.ordinal}{delim}{val}{delim}{code:.6f}\n")
    return JobResult("categoricalContinuousEncoding",
                     {"Basic:Records": acc.n}, [out], payload)


@job("topMatchesByClass", "tmc", "org.avenir.explore.TopMatchesByClass")
def top_matches_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.explore import top_matches_by_class

    ds = _dataset(inputs[0], cfg)
    matches = top_matches_by_class(ds, k=cfg.get_int("top.match.count", 3))
    out = _out_file(output)
    delim = cfg.field_delim
    ids = ds.ids()
    y = ds.labels()
    cls_vals = ds.schema.class_values()
    n = 0
    with open(out, "w") as fh:
        for cv, (dist, idx) in matches.items():
            rows = np.flatnonzero(y == cls_vals.index(cv))
            for r in range(dist.shape[0]):
                # entity ids on both sides so rows join back to the data
                row = [cv, str(ids[rows[r]])] + [
                    f"{ids[idx[r, j]]}:{dist[r, j]:.4f}"
                    for j in range(dist.shape[1])]
                fh.write(delim.join(row) + "\n")
                n += 1
    return JobResult("topMatchesByClass", {"Basic:Records": n}, [out], matches)


@job("underSamplingBalancer", "usb",
     "org.avenir.explore.UnderSamplingBalancer")
@job("baggingSampler", "bas", "org.avenir.explore.BaggingSampler")
def sampler_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Map-only row samplers: class rebalancing by undersampling (usb.*)
    or bootstrap sampling (bas.*); rows pass through byte-identical."""
    from avenir_tpu.models.explore import bagging_sample, undersample_balance

    name = cfg.props.get("__job_name__", "underSamplingBalancer")
    ds = _dataset(inputs[0], cfg, keep_raw=True)
    if name == "baggingSampler":
        sampled = bagging_sample(ds, rate=cfg.get_float("sample.rate", 1.0),
                                 seed=cfg.get_int("seed", 0))
    else:
        sampled = undersample_balance(ds, seed=cfg.get_int("seed", 0))
    out = _out_file(output)
    with open(out, "w") as fh:
        fh.write(sampled.to_csv(cfg.field_delim) if len(sampled) else "")
    return JobResult(name, {"Basic:Records": len(sampled)}, [out])


# ==================================================================== cluster
@job("agglomerativeGraphical", "agg",
     "org.avenir.cluster.AgglomerativeGraphical")
def agglomerative_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Greedy agglomerative clustering over a pairwise-distance file (the
    EntityDistanceMapFileAccessor input, AgglomerativeGraphical.java:108)."""
    from avenir_tpu.models.cluster import AgglomerativeGraphical
    from avenir_tpu.models.similarity import (distance_matrix_from_file,
                                              read_distance_file)

    dist_path = cfg.get("distance.file.path") or inputs[0]
    pairs = read_distance_file(dist_path, delim=cfg.field_delim_regex,
                               scale=cfg.get_int("distance.scale", 1000))
    ids = sorted({a for a, _ in pairs})
    m = distance_matrix_from_file(dist_path, ids, pairs=pairs)
    model = AgglomerativeGraphical(
        num_clusters=cfg.get_int("num.clusters", 2),
        max_avg_distance=cfg.get_float("max.avg.distance"),
    ).fit(m)
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for i, rid in enumerate(ids):
            fh.write(f"{rid}{delim}{int(model.labels_[i])}\n")
    return JobResult("agglomerativeGraphical",
                     {"Cluster:Count": len(set(model.labels_.tolist()))},
                     [out], model)


@job("clusterTrain", "train", "kmeansCluster")
def cluster_train_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """The python-layer cluster.py surface (train.* jprops keys,
    unsupv/cluster.py:24-60): kmeans / dbscan over the schema's numeric
    features, with cohesion model selection output."""
    from avenir_tpu.models.cluster import DBSCAN, KMeans, cohesion

    ds = _dataset(inputs[0], cfg)
    x = ds.feature_matrix()
    algo = cfg.get("algo", "kmeans")
    if algo == "kmeans":
        model = KMeans(k=cfg.get_int("num.clusters", 3),
                       iters=cfg.get_int("num.iters", 100)).fit(x)
        labels = model.labels_          # fit already assigned the train rows
    elif algo == "dbscan":
        from avenir_tpu.models.cluster import dataset_distance_matrix

        model = DBSCAN(eps=cfg.get_float("eps", 0.5),
                       min_samples=cfg.get_int("min.samples", 4))
        model.fit(dataset_distance_matrix(ds))
        labels = model.labels_
    else:
        raise ValueError(f"unknown cluster algo {algo!r}")
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for rid, lab in zip(ds.ids(), labels):
            fh.write(f"{rid}{delim}{int(lab)}\n")
    coh = float(cohesion(x, np.asarray(labels))) if len(set(labels)) > 1 else 0.0
    return JobResult("clusterTrain", {"Cluster:Cohesion": coh}, [out], model)


# =================================================================== sequence
@job("candidateGenerationWithSelfJoin", "cgs",
     "org.avenir.sequence.CandidateGenerationWithSelfJoin", "gspMiner")
def gsp_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """GSP frequent-sequence mining; the reference's per-k self-join rounds
    (CandidateGenerationWithSelfJoin.java:44-49) run internally up to
    cgs.item.set.length, with per-k output files."""
    from avenir_tpu.models.sequence import (GSPMiner, SequenceSet,
                                            StreamingSequenceSource)

    skip = cfg.get_int("skip.field.count", 1)
    miner = GSPMiner(
        support_threshold=cfg.assert_float("support.threshold"),
        max_length=cfg.get_int("item.set.length", 3),
    )
    total_bytes = sum(os.path.getsize(p) for p in inputs
                      if os.path.exists(p))
    in_ram = (cfg.get("stream.block.size.mb") is None
              and total_bytes < (256 << 20))
    # timer starts BEFORE the in-RAM probe reads the file: RowsPerSec
    # must price the whole job's I/O identically on both paths, or the
    # rate steps when a corpus crosses the in-RAM gate
    t0 = time.perf_counter()
    if in_ram:
        rows = [[t.strip(" \t\r") for t in ln.split(cfg.field_delim_regex)]
                for p in inputs for ln in _read_lines(p)]
        # the in-RAM cost is the padded [N, T] matrix: one anomalously
        # long row must not blow it up — gate on the footprint
        t_max = max((len(r) - skip for r in rows), default=1)
        in_ram = len(rows) * max(t_max, 1) * 4 < (2 << 30)
    if in_ram:
        # in-RAM: one [N, T] upload, device-resident across k rounds
        levels = miner.mine(SequenceSet.from_token_rows(
            rows, skip_field_count=skip))
        n_rows = len(rows)
    else:
        # beyond-RAM (or explicitly chunked): one streamed scan per k,
        # per-k re-scans replaying the pass-1 encoded-block cache
        src = StreamingSequenceSource(
            inputs, delim=cfg.field_delim_regex, skip_field_count=skip,
            block_bytes=int(cfg.get_float("stream.block.size.mb", 64.0)
                            * (1 << 20)),
            spill_cache=cfg.get_bool("stream.encoded.cache", True),
            cache_budget_bytes=_cache_budget(cfg))
        _attach_sidecar_opts(src, cfg)
        levels = miner.mine_stream(src)
        n_rows = src.n_rows
        cache_counters = _cache_counters(src)
        src.close()
    counters = {"GSP:MaxLength": max(levels) if levels else 0,
                **throughput_counters(n_rows, time.perf_counter() - t0),
                **(cache_counters if not in_ram else {})}
    outs = _write_gsp_outputs(cfg, output, levels)
    return JobResult("candidateGenerationWithSelfJoin", counters,
                     outs, levels)


@job("sequencePositionalCluster", "spc",
     "org.avenir.sequence.SequencePositionalCluster")
def positional_cluster_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.sequence import EventLocalityAnalyzer, positional_cluster

    analyzer = EventLocalityAnalyzer(
        window_time_span=cfg.assert_float("window.time.span"),
        time_step=cfg.get_float("window.time.step", 1.0),
        score_threshold=cfg.get_float("score.threshold", 0.5),
        min_occurence=cfg.get_int("min.occurence", 2),
    )
    rows = [[t.strip() for t in ln.split(cfg.field_delim_regex)]
            for p in inputs for ln in _read_lines(p)]
    quant_ord = cfg.get_int("quant.field.ordinal", 2)
    seq_ord = cfg.get_int("seq.num.field.ordinal", 1)
    thresh = cfg.get_float("quant.threshold")
    cond = (lambda v: v >= thresh) if thresh is not None else (lambda v: True)
    clusters = positional_cluster(rows, analyzer, quant_ord, seq_ord, cond)
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for pos, score in clusters:
            fh.write(f"{pos:.4f}{delim}{score:.6f}\n")
    return JobResult("sequencePositionalCluster",
                     {"Windows:Found": len(clusters)}, [out], clusters)


@job("eventTimeDistribution", "etd",
     "org.avenir.spark.sequence.EventTimeDistribution")
def event_time_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Inter-arrival time histogram (EventTimeDistribution.scala:27):
    rows are id,timestamp... grouped by id."""
    from avenir_tpu.models.markov import event_time_distribution

    ts_ord = cfg.get_int("time.stamp.field.ordinal", 1)
    by_id: Dict[str, List[float]] = {}
    for p in inputs:
        for ln in _read_lines(p):
            toks = [t.strip() for t in ln.split(cfg.field_delim_regex)]
            by_id.setdefault(toks[0], []).append(float(toks[ts_ord]))
    seqs = [sorted(v) for v in by_id.values()]
    hist = event_time_distribution(
        seqs, num_buckets=cfg.get_int("num.buckets", 24),
        bucket_width=cfg.get_float("bucket.width", 3600.0))
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for b, c in enumerate(hist):
            fh.write(f"{b}{delim}{int(c)}\n")
    return JobResult("eventTimeDistribution",
                     {"Basic:Entities": len(by_id)}, [out], hist)


@job("sequenceGenerator", "seg",
     "org.avenir.spark.sequence.SequenceGenerator")
def sequence_generator_job(cfg: JobConfig, inputs: List[str],
                           output: str) -> JobResult:
    """Sequence formation from event rows (SequenceGenerator.scala:31):
    group rows by seg.id.field.ordinals, project seg.val.field.ordinals,
    sort each group's value records by seg.seq.field (an index INTO the
    projected value record, matching the Scala withSortFields contract),
    emit one line per entity: key fields then the sorted value records
    flattened."""
    key_ords = cfg.get_int_list("id.field.ordinals", [0])
    val_ords = cfg.assert_list("val.field.ordinals")
    val_ords = [int(v) for v in val_ords]
    seq_field = cfg.assert_int("seq.field")

    def sort_key(rec: List[str]) -> Tuple[float, str]:
        v = rec[seq_field]
        try:
            f = float(v)
            # NaN sort keys would silently scramble the group order
            if math.isnan(f):
                return (float("inf"), v)
            return (f, "")
        except ValueError:
            return (float("inf"), v)

    by_key: Dict[str, List[List[str]]] = {}
    for p in inputs:
        for ln in _read_lines(p):
            toks = [t.strip() for t in ln.split(cfg.field_delim_regex)]
            key = cfg.field_delim.join(toks[o] for o in key_ords)
            by_key.setdefault(key, []).append([toks[o] for o in val_ords])
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for key, recs in sorted(by_key.items()):
            recs.sort(key=sort_key)
            flat = delim.join(tok for rec in recs for tok in rec)
            fh.write(f"{key}{delim}{flat}\n")
    return JobResult("sequenceGenerator",
                     {"Basic:Entities": len(by_key)}, [out], by_key)


# ================================================================ association
@job("frequentItemsApriori", "fia",
     "org.avenir.association.FrequentItemsApriori", "apriori")
def apriori_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """All k-rounds internal; per-k itemset files written like the
    reference's per-round outputs (FrequentItemsApriori.java:123-126)."""
    from avenir_tpu.models.association import (FrequentItemsApriori,
                                               StreamingTransactionSource)
    from avenir_tpu.utils.devices import job_mesh

    miner = FrequentItemsApriori(
        support_threshold=cfg.assert_float("support.threshold"),
        max_length=cfg.get_int("item.set.length", 3),
        emit_trans_id=cfg.get_bool("emit.trans.id", False),
    )
    skip = cfg.get_int("skip.field.count", 1)
    marker = cfg.get("infreq.item.marker")
    t0 = time.perf_counter()
    # two routes that write the same bytes. Resident: the file read whole,
    # two native passes, the packed baskets on the chip from the scan to
    # the last round (FrequentItemsApriori.mine_whole says when it cannot
    # be). A conf that states a block size asks for the block scan, and
    # gets the streamed route with its cache, sidecar and O(block) RSS.
    whole = None
    if cfg.get("stream.block.size.mb") is None:
        whole = miner.mine_whole(inputs, delim=cfg.field_delim_regex,
                                 skip_field_count=skip, marker=marker,
                                 mesh=job_mesh())
    cache_counters = {}
    if whole is not None:
        levels, n_rows = whole
    else:
        # one streamed scan per itemset length — the reference's per-k MR
        # jobs over the same HDFS input, bit-packed over the frequent
        # vocabulary after k=1, and per-k re-scans replay the pass-1
        # encoded-block cache instead of re-parsing CSV; host RSS stays
        # O(block) at any size
        src = StreamingTransactionSource(
            inputs, delim=cfg.field_delim_regex,
            trans_id_ord=cfg.get_int("tans.id.ord", 0),
            skip_field_count=skip, marker=marker,
            block_bytes=int(cfg.get_float("stream.block.size.mb", 64.0)
                            * (1 << 20)),
            spill_cache=cfg.get_bool("stream.encoded.cache", True),
            cache_budget_bytes=_cache_budget(cfg))
        _attach_sidecar_opts(src, cfg)
        levels = miner.mine_stream(src)
        n_rows = src.n_trans
        cache_counters = _cache_counters(src)
        src.close()
    counters = {"Apriori:MaxLength": len(levels),
                **throughput_counters(n_rows, time.perf_counter() - t0),
                **cache_counters}
    outs = _write_apriori_outputs(cfg, output, levels)
    return JobResult("frequentItemsApriori", counters, outs, levels)


@job("associationRuleMiner", "arm",
     "org.avenir.association.AssociationRuleMiner")
def rule_miner_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.association import AssociationRuleMiner, ItemSetList

    miner = AssociationRuleMiner(
        conf_threshold=cfg.assert_float("conf.threshold"),
        max_ante_size=cfg.get_int("max.ante.size", 3),
    )
    levels = []
    for k, path in enumerate(inputs, start=1):
        levels.append(ItemSetList.load(path, k, delim=cfg.field_delim))
    rules = miner.mine(levels)
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for r in rules:
            fh.write(f"{':'.join(r.antecedent)}{delim}{':'.join(r.consequent)}"
                     f"{delim}{r.confidence:.6f}{delim}{r.support:.6f}\n")
    return JobResult("associationRuleMiner", {"Rules:Count": len(rules)},
                     [out], rules)


@job("infrequentItemMarker", "iim",
     "org.avenir.association.InfrequentItemMarker")
def infrequent_item_marker_job(cfg: JobConfig, inputs: List[str],
                               output: str) -> JobResult:
    """Map-only pass replacing items not in the frequent-1-itemset file
    with a marker token (InfrequentItemMarker.java:41-46, run after the
    k=1 Apriori round to shrink later scans). Reads iim.item.set.file.path
    (must hold length-1 itemsets), iim.infreq.item.marker (default '*'),
    iim.skip.field.count (default 1)."""
    from avenir_tpu.models.association import InfrequentItemMarker, ItemSetList

    length = cfg.get_int("item.set.length", 1)
    if length != 1:
        raise ValueError("expecting item set of length 1")
    isl = ItemSetList.load(
        cfg.assert_get("item.set.file.path"), length,
        with_trans_ids=cfg.get_bool("contains.trans.id", True),
        delim=cfg.get("itemset.delim", ","))
    marker = InfrequentItemMarker(
        frequent_items=(s.items[0] for s in isl.item_sets),
        marker=cfg.get("infreq.item.marker", "*"),
        skip_field_count=cfg.get_int("skip.field.count", 1))
    out = _out_file(output)
    delim = cfg.field_delim
    n = marked = 0
    with open(out, "w") as fh:
        for path in inputs:
            for ln in _read_lines(path):
                row = [t.strip() for t in ln.split(cfg.field_delim_regex)]
                marked_row = marker.mark_row(row)
                marked += sum(a != b for a, b in zip(row, marked_row))
                n += 1
                fh.write(delim.join(marked_row) + "\n")
    return JobResult("infrequentItemMarker",
                     {"Basic:Records": n, "Marker:Replaced": marked}, [out])


# ===================================================================== markov
@job("markovStateTransitionModel", "mst",
     "org.avenir.markov.MarkovStateTransitionModel",
     "org.avenir.spark.sequence.MarkovStateTransitionModel")
def markov_model_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Per-class matrices via mst.* keys (the Hadoop job). With
    `id.field.ordinals` set (the Spark surface's HOCON key,
    MarkovStateTransitionModel.scala:51-52), builds one matrix PER ENTITY
    key — the multi-tenant mode — with `seq.start.ordinal` marking where
    the state sequence begins and optional `class.attr.ordinal` splitting
    each entity's matrix by class; sections are emitted as `entity:<key>`."""
    from avenir_tpu.core.stream import stream_job_lines
    from avenir_tpu.models.markov import MarkovStateTransitionModel

    states = cfg.get_list("model.states") or cfg.assert_list("state.list")
    scale = cfg.get_int("trans.prob.scale", 1000)
    id_ords = cfg.get_int_list("id.field.ordinals")
    out = _out_file(output)
    # bigram counts are additive, so both modes fold streamed line blocks
    # (the mapper's one-line-at-a-time contract,
    # MarkovStateTransitionModel.java:116-133) at O(block) host RSS
    if id_ords is not None:
        class_ord = cfg.get_int("class.attr.ordinal")
        # mandatory in the Spark reference (getMandatoryIntParam, :54);
        # the convenience default must skip the class column too
        key_ords = list(id_ords) + ([class_ord]
                                    if class_ord is not None else [])
        seq_start = cfg.get_int(
            "seq.start.ordinal",
            max(key_ords) + 1 if key_ords else 0)
        delim = cfg.field_delim_regex
        model = MarkovStateTransitionModel(states, scale=scale)
        from avenir_tpu.native.ingest import (extract_column_native,
                                              native_seq_ready,
                                              seq_encode_native)

        if native_seq_ready(delim):
            # native path: states CSR-encode natively; only the (open-
            # vocabulary) entity key columns materialize as strings
            from avenir_tpu.core.stream import stream_job_byte_blocks

            model.class_labels = []
            model.counts = np.zeros((0,) + model.counts.shape[1:],
                                    np.float64)
            index: Dict[str, int] = {}
            for data in stream_job_byte_blocks(cfg, inputs):
                enc = seq_encode_native(data, delim, states)
                lens = np.diff(enc[1])
                if key_ords:
                    # rows too short to carry every key column are a
                    # crisp error on BOTH engines
                    short = lens <= max(key_ords)
                    if short.any():
                        raise ValueError(
                            f"row {int(np.argmax(short))} has no "
                            f"id/class field (ordinal {max(key_ords)})")
                    cols = [extract_column_native(data, delim, o)
                            for o in key_ords]
                    keys = cols[0]
                    for col in cols[1:]:
                        keys = np.char.add(np.char.add(keys, ","), col)
                else:
                    # degenerate config (no id/class columns): one key
                    keys = np.full(lens.shape[0], "")
                # first-seen entity order, vectorized: unique keys
                # ordered by first occurrence, then row indices
                uniq, first, inv = np.unique(
                    keys, return_index=True, return_inverse=True)
                gidx = np.empty(uniq.shape[0], np.int64)
                for u in np.argsort(first):
                    key = str(uniq[u])
                    gi = index.get(key)
                    if gi is None:
                        gi = len(index)
                        index[key] = gi
                        model.class_labels.append(key)
                    gidx[u] = gi
                if len(index) > model.counts.shape[0]:
                    model.counts = np.pad(
                        model.counts,
                        ((0, len(index) - model.counts.shape[0]),
                         (0, 0), (0, 0)))
                model.fit_csr(enc[0], enc[1], skip=seq_start, y=gidx[inv])
        else:
            for lines in stream_job_lines(cfg, inputs):
                seqs: List[List[str]] = []
                entity_of_row: List[str] = []
                for ln in lines:
                    toks = [t.strip(" \t\r") for t in ln.split(delim)]
                    if key_ords and len(toks) <= max(key_ords):
                        raise ValueError(
                            f"row {len(entity_of_row)} has no id/class "
                            f"field (ordinal {max(key_ords)})")
                    key = ",".join(toks[o] for o in id_ords)
                    if class_ord is not None:
                        key += f",{toks[class_ord]}"
                    entity_of_row.append(key)
                    seqs.append(toks[seq_start:])
                model.fit_entities(seqs, entity_of_row)
        entities = model.class_labels or []
        if not entities:
            raise ValueError(
                f"markovStateTransitionModel: empty input "
                f"(no records in {inputs})")
        model.save(out, delim=cfg.field_delim, marker="entity")
        return JobResult("markovStateTransitionModel",
                         {"Entities:Count": len(entities)}, [out], model)

    # per-class mode: the fold sink doubles as the shared-scan sink
    # (_MarkovPerClassFold) — native CSR encode per raw byte block when
    # the C encoder is built, line decode + fit otherwise
    from avenir_tpu.core.stream import stream_job_byte_blocks

    fold = _MarkovPerClassFold(cfg, inputs)
    # the fold dispatches on SidecarBytesBlock (consume_encoded), so the
    # feed opts into the bytes-kind sidecar at this job's skip count —
    # a verified repeat scan fits from packed codes without a tokenizer
    _drive_fold(fold,
                stream_job_byte_blocks(cfg, inputs,
                                       sidecar_skip=fold.skip
                                       if fold.native else None),
                "markovStateTransitionModel")
    return _finish_fold(fold, output, "markovStateTransitionModel")


@job("markovModelClassifier", "mmc",
     "org.avenir.markov.MarkovModelClassifier",
     "org.avenir.spark.sequence.MarkovModelClassifier")
def markov_classifier_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.markov import (MarkovModelClassifier,
                                          MarkovStateTransitionModel)

    model = MarkovStateTransitionModel.load(
        cfg.assert_get("mm.model.path"), delim=cfg.field_delim)
    pos, neg = cfg.assert_list("class.labels")
    clf = MarkovModelClassifier(
        model, pos, neg,
        threshold=cfg.get_float("log.odds.threshold", 0.0))
    skip = cfg.get_int("skip.field.count", 1)
    class_ord = cfg.get_int("class.label.field.ord") \
        if cfg.get_bool("validation.mode", False) else None
    from avenir_tpu.core.stream import stream_job_lines

    out = _out_file(output)
    delim = cfg.field_delim
    counters: Dict[str, float] = {}
    actual, predicted = [], []
    with open(out, "w") as fh:
        # map-only row transform at O(block): classify per line block
        for lines in stream_job_lines(cfg, inputs):
            ids, seqs, labels = _parse_sequences(
                lines, cfg.field_delim_regex, skip, class_ord)
            cls, scores = clf.predict(seqs)
            for rid, c, s in zip(ids, cls, scores):
                fh.write(f"{rid}{delim}{c}{delim}{s:.6f}\n")
            if class_ord is not None:
                actual += labels
                predicted += list(cls)
    if actual:
        lab = [pos, neg]
        counters = _validate(
            lab, np.array([lab.index(a) for a in actual]),
            np.array([lab.index(p) for p in predicted]), 0)
    return JobResult("markovModelClassifier", counters, [out])


@job("hiddenMarkovModelBuilder", "hmmb",
     "org.avenir.markov.HiddenMarkovModelBuilder")
def hmm_builder_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """Fully-tagged input: `obs<sub.field.delim>state` tokens after the skip
    fields (HiddenMarkovModelBuilder.java:136-153). With
    `hmmb.partially.tagged=true`, tokens are bare observations except the
    ones matching hmmb.model.states, and `hmmb.window.function` spreads the
    state->obs counts around each tagged position (:174-259)."""
    from avenir_tpu.core.stream import stream_job_lines
    from avenir_tpu.models.markov import HiddenMarkovModelBuilder

    states = cfg.assert_list("model.states")
    obs = cfg.assert_list("model.observations")
    sub = cfg.get("sub.field.delim", ":")
    skip = cfg.get_int("skip.field.count", 1)
    builder = HiddenMarkovModelBuilder(states, obs)
    # per-sequence count accumulation over streamed line blocks (the
    # mapper contract, HiddenMarkovModelBuilder.java:136-153)
    if cfg.get_bool("partially.tagged", False):
        wf = [int(v) for v in cfg.assert_list("window.function")]
        for lines in stream_job_lines(cfg, inputs):
            _, seqs, _ = _parse_sequences(lines, cfg.field_delim_regex, skip)
            for seq in seqs:
                builder.add_partially_tagged(seq, wf)
    else:
        delim = cfg.field_delim_regex
        from avenir_tpu.native.ingest import (native_seq_ready,
                                              seq_encode_native)

        if native_seq_ready(delim):
            # native path: encode whole `obs:state` pair tokens against
            # the state-major pair vocabulary straight from byte blocks
            from avenir_tpu.core.stream import stream_job_byte_blocks

            vocab = [f"{ov}{sub}{sv}" for sv in states for ov in obs]
            for data in stream_job_byte_blocks(cfg, inputs):
                # cannot be None: availability + delim pre-checked
                enc = seq_encode_native(data, delim, vocab)
                builder.add_csr(*enc, skip=skip)
        else:
            for lines in stream_job_lines(cfg, inputs):
                _, seqs, _ = _parse_sequences(lines, delim, skip)
                for seq in seqs:
                    pairs = [tok.split(sub) for tok in seq]
                    builder.add([p[1] for p in pairs], [p[0] for p in pairs])
    hmm = builder.finish()
    out = _out_file(output)
    hmm.save(out, delim=cfg.field_delim)
    return JobResult("hiddenMarkovModelBuilder", {}, [out], hmm)


@job("viterbiStatePredictor", "vsp",
     "org.avenir.markov.ViterbiStatePredictor")
def viterbi_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.markov import HiddenMarkovModel, ViterbiDecoder

    hmm = HiddenMarkovModel.load(cfg.assert_get("hmm.model.path"),
                                 delim=cfg.field_delim)
    decoder = ViterbiDecoder(hmm)
    skip = 1 if cfg.get_int("id.field.ordinal", 0) >= 0 else 0
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for path in inputs:
            ids, seqs, _ = _read_sequences(path, cfg.field_delim_regex, skip)
            decoded = decoder.decode(seqs)
            for rid, states in zip(ids, decoded):
                fh.write(delim.join([rid] + list(states)) + "\n")
    return JobResult("viterbiStatePredictor", {}, [out])


@job("probabilisticSuffixTree", "pstg",
     "org.avenir.markov.ProbabilisticSuffixTreeGenerator")
def pst_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.markov import ProbabilisticSuffixTree

    skip = cfg.get_int("skip.field.count", 1)
    seqs = []
    for path in inputs:
        _, ss, _ = _read_sequences(path, cfg.field_delim_regex, skip)
        seqs += ss
    symbols = sorted({s for seq in seqs for s in seq})
    pst = ProbabilisticSuffixTree(
        symbols, max_depth=cfg.get_int("max.seq.length", 3)).fit(seqs)
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for ctx in sorted(pst.counts):
            counts = pst.counts[ctx]
            total = float(counts.sum()) or 1.0
            for si, sym in enumerate(pst.symbols):
                if counts[si] > 0:
                    fh.write(f"{''.join(ctx) or '$'}{delim}{sym}{delim}"
                             f"{counts[si] / total:.6f}\n")
    return JobResult("probabilisticSuffixTree", {}, [out], pst)


# ============================================================ regress / discr
@job("logisticRegression", "lrj",
     "org.avenir.regress.LogisticRegressionJob")
def logistic_regression_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """In-process epochs replace the driver loop of SURVEY §3.6; the
    coefficient history still appends to `coeff.file.path` and the result
    counters carry the reference's CONVERGED(100)/NOT_CONVERGED(101) exit
    status (LogisticRegressionJob.java:95-119)."""
    from avenir_tpu.models.regress import LogisticRegression

    ds = _dataset(inputs[0], cfg)
    lr = LogisticRegression(
        iteration_limit=cfg.get_int("iteration.limit", 10),
        convergence_criteria=cfg.get("convergence.criteria", "iterLimit"),
        convergence_threshold=cfg.get_float("convergence.threshold", 5.0),
        pos_class=cfg.get("positive.class.value"),
    ).fit(ds)
    coeff_path = cfg.get("coeff.file.path") or _out_file(output, "coeff.txt")
    lr.save_coeff_history(coeff_path, delim=cfg.field_delim)
    return JobResult(
        "logisticRegression",
        {"Regression:ExitStatus": lr.check_convergence()}, [coeff_path], lr)


@job("fisherDiscriminant", "fid",
     "org.avenir.discriminant.FisherDiscriminant")
def fisher_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.core.stream import stream_job_inputs

    # the fold sink doubles as the shared-scan sink (_FisherFold)
    fold = _FisherFold(cfg, inputs, None)
    _drive_fold(fold, stream_job_inputs(cfg, inputs, _schema(cfg)),
                "fisherDiscriminant")
    return _finish_fold(fold, output, "fisherDiscriminant")


# ======================================================================= text
@job("wordCounter", "wco", "org.avenir.text.WordCounter",
     "org.avenir.sanity.WordCount")
def word_counter_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    from avenir_tpu.models.text import WordCounter

    from avenir_tpu.core.stream import stream_job_lines

    wc = WordCounter(
        text_field_ordinal=cfg.get_int("text.field.ordinal", -1),
        delim=cfg.field_delim_regex,
    )
    # token counts fold per streamed line block: host RSS is O(block +
    # vocabulary), never O(file) (WordCounter's mapper contract)
    counts: Dict[str, int] = {}
    for lines in stream_job_lines(cfg, inputs):
        for word, c in wc.count(lines):
            counts[word] = counts.get(word, 0) + c
    out = _out_file(output)
    delim = cfg.field_delim
    with open(out, "w") as fh:
        for word in sorted(counts):
            fh.write(f"{word}{delim}{counts[word]}\n")
    return JobResult("wordCounter", {"Words:Unique": len(counts)}, [out])


# ==================================================================== bandits
@job("greedyRandomBandit", "grb", "org.avenir.reinforce.GreedyRandomBandit")
@job("auerDeterministic", "aue", "org.avenir.reinforce.AuerDeterministic")
@job("randomFirstGreedyBandit", "rfg",
     "org.avenir.reinforce.RandomFirstGreedyBandit")
@job("softMaxBandit", "smb", "org.avenir.reinforce.SoftMaxBandit")
def bandit_job(cfg: JobConfig, inputs: List[str], output: str) -> JobResult:
    """One decision round of a batch bandit: input = group item stats rows
    `group,item,count,reward` (chombo RunningAggregator output the tutorial
    loops back, resource/price_optimize_tutorial.txt:55-82); output = the
    selected items per group for the round."""
    from avenir_tpu.models.bandits import GroupBanditData, make_bandit_job

    # job name = the registry key the caller used (one impl serves all four)
    name = cfg.props.get("__job_name__", "greedyRandomBandit")
    batch = cfg.get_int("global.batch.size", 1)
    kw = {}
    if name == "greedyRandomBandit":
        kw = {
            "random_selection_prob": cfg.get_float("random.selection.prob", 0.1),
            "prob_reduction_algorithm": cfg.get("prob.reduction.algorithm",
                                                "linear"),
            "prob_reduction_constant": cfg.get_float("prob.reduction.constant",
                                                     1.0),
            "auer_greedy_constant": cfg.get_float("auer.greedy.constant", 1.0),
            "selection_unique": cfg.get_bool("selection.unique", False),
        }
    elif name == "softMaxBandit":
        kw = {"temp_constant": cfg.get_float("temp.constant", 1.0)}
    round_num = cfg.get_int("current.round.num", 1)
    data = GroupBanditData.from_rows(
        [[t.strip() for t in ln.split(cfg.field_delim_regex)]
         for p in inputs for ln in _read_lines(p)],
        count_ord=cfg.get_int("count.ordinal", 2),
        reward_ord=cfg.get_int("reward.ordinal", 3),
    )
    bj = make_bandit_job(name, batch, **kw)
    sel = bj.select(data, round_num)
    out = _out_file(output)
    with open(out, "w") as fh:
        data.write_selections(
            sel, fh, cfg.field_delim,
            output_decision_count=cfg.get_bool("output.decision.count",
                                               False))
    return JobResult(name, {"Bandit:Groups": len(data.group_ids)}, [out], sel)


# =================================================================== pipeline
@dataclass
class Stage:
    name: str
    job: str
    inputs: List[str]
    output: str
    conf_overrides: Dict[str, str] = field(default_factory=dict)


class Pipeline:
    """Replaces the resource/*.sh case-statement drivers: ordered named
    stages over one shared properties file; stage outputs feed later stage
    inputs by path (e.g. the knn.sh 5-stage flow, SURVEY §3.3). Run all
    stages or a single named one — the same way the shell scripts were
    invoked per-stage by hand.

    Failure handling (SURVEY §5): the reference delegates retry to Hadoop
    (`mapreduce.map.maxattempts=2`, knn.properties:5-6) and relies on jobs
    being re-runnable because all state is files. The same two properties
    hold here: a failed stage re-runs up to `mapreduce.map.maxattempts`
    times (every job rewrites its outputs from its inputs, so a retry is
    exactly a Hadoop task re-attempt), and `on_retry` is the observability
    hook (attempt log / fault-injection point in tests)."""

    def __init__(self, conf, stages: Sequence[Stage], on_retry=None):
        self.props = (load_properties(conf) if isinstance(conf, str)
                      else dict(conf))
        self.stages = list(stages)
        self.results: Dict[str, JobResult] = {}
        self.max_attempts = max(
            int(self.props.get("mapreduce.map.maxattempts", "2")), 1)
        self.on_retry = on_retry
        self.attempts: Dict[str, int] = {}

    def _stage_props(self, st: Stage) -> Dict[str, str]:
        props = dict(self.props)
        props.update(st.conf_overrides)
        return props

    def _run_stage(self, st: Stage) -> None:
        for attempt in range(1, self.max_attempts + 1):
            self.attempts[st.name] = attempt
            try:
                self.results[st.name] = run_job(
                    st.job, self._stage_props(st), st.inputs, st.output)
                break
            except Exception as exc:
                if attempt >= self.max_attempts:
                    raise
                if self.on_retry is not None:
                    self.on_retry(st.name, attempt, exc)

    def _fusable(self, st: Stage) -> bool:
        key = _REGISTRY.get(st.job)
        return key is not None and key[0] in _STREAM_FOLDS

    def run(self, only: Optional[str] = None,
            fuse: bool = False) -> Dict[str, JobResult]:
        """Run the stages. With fuse=True, maximal runs of CONSECUTIVE
        stages that read the same inputs and are shared-scan capable
        (stream_fold_names()) execute as ONE SharedScan pass via
        run_shared() — N jobs, one disk read + parse of the corpus. Any
        fused-group failure falls back to the existing one-job-one-scan
        per-stage path (with its usual retry semantics), so fusion is a
        pure optimization, never a new failure mode."""
        stages = [st for st in self.stages
                  if only is None or st.name == only]
        i = 0
        while i < len(stages):
            group = [stages[i]]
            if fuse and self._fusable(stages[i]):
                seen = {_REGISTRY[stages[i].job][0]}
                j = i + 1
                while (j < len(stages) and self._fusable(stages[j])
                       and stages[j].inputs == stages[i].inputs
                       and _REGISTRY[stages[j].job][0] not in seen):
                    group.append(stages[j])
                    seen.add(_REGISTRY[stages[j].job][0])
                    j += 1
            if len(group) >= 2:
                specs = [(st.job, self._stage_props(st), st.output)
                         for st in group]
                try:
                    shared = run_shared(specs, group[0].inputs)
                    for st in group:
                        # keyed lookup, not positional zip: immune to any
                        # future reordering of run_shared's result dict
                        self.results[st.name] = shared[_REGISTRY[st.job][0]]
                        self.attempts[st.name] = 1
                    i += len(group)
                    continue
                except Exception as exc:
                    # fused attempt failed (mixed configs, a job error,
                    # ...): the one-job-one-scan path is the fallback
                    if self.on_retry is not None:
                        self.on_retry(
                            "+".join(st.name for st in group), 1, exc)
            for st in group:
                self._run_stage(st)
            i += len(group)
        return self.results


def run_from_cli(argv: Sequence[str]) -> JobResult:
    """`python -m avenir_tpu <jobName> --conf <props> IN... OUT` — the
    `hadoop jar avenir.jar <class> -Dconf.path=<props> IN OUT` surface.

    `python -m avenir_tpu serve ...` instead starts the resident
    multi-tenant job server — over a stdin/filesystem request spool
    (avenir_tpu.server.spool — batched shared scans, warm caches,
    byte-budget admission; no network dependency) or, with
    `--listen HOST:PORT`, behind the JSON-over-HTTP edge
    (avenir_tpu.net.listener — 429 backpressure wired to the admission
    model). `python -m avenir_tpu fleet --root DIR --hosts N` runs N
    server processes behind the affinity router (avenir_tpu.net.fleet),
    and `python -m avenir_tpu stats <paths...>` renders one server's
    live metrics.json — or a fleet's, merged through the additive
    histogram algebra (avenir_tpu.obs.report).

    A job runs under the root span `job.cli`. `--trace DIR` also runs it
    inside a `jax.profiler` session (utils.profiling.trace) that writes
    the device trace under DIR, and when the job ends writes the span
    ring to `DIR/trace.json` (`tools/trace_report.py DIR` rolls it up;
    Perfetto opens both). Without the flag nothing is started or
    written."""
    if argv and argv[0] == "serve":
        from avenir_tpu.server.spool import serve_main

        rc = serve_main(list(argv[1:]))
        if rc:
            sys.exit(rc)
        return JobResult("serve")

    if argv and argv[0] == "fleet":
        from avenir_tpu.net.fleet import fleet_main

        rc = fleet_main(list(argv[1:]))
        if rc:
            sys.exit(rc)
        return JobResult("fleet")

    if argv and argv[0] == "stats":
        from avenir_tpu.obs.report import stats_main

        rc = stats_main(list(argv[1:]))
        if rc:
            sys.exit(rc)
        return JobResult("stats")

    if argv and argv[0] == "tune":
        from avenir_tpu.tune.report import tune_main

        rc = tune_main(list(argv[1:]))
        if rc:
            sys.exit(rc)
        return JobResult("tune")

    # looked up before the root span opens, so that the profiler session
    # encloses it and `job.cli` stands in the device trace as well
    trace_dir = _trace_flag().parse_known_args(list(argv))[0].trace
    if trace_dir:
        from avenir_tpu.utils.profiling import trace

        os.makedirs(trace_dir, exist_ok=True)
        session = trace(trace_dir)
    else:
        session = contextlib.nullcontext()
    try:
        with session, _obs.span("job.cli") as note:
            return _job_from_cli(argv, note)
    finally:
        if trace_dir:
            _obs.recorder().export_chrome(
                os.path.join(trace_dir, "trace.json"))


def _trace_flag():
    """The parser of `--trace DIR` alone (a parent of the job parser)."""
    import argparse

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="run the job under jax.profiler: the device "
                         "trace goes under DIR, the span ring to "
                         "DIR/trace.json")
    return ap


def _job_from_cli(argv: Sequence[str], note: Dict) -> JobResult:
    """The job surface of `run_from_cli`, inside its `job.cli` span
    (`note` is that span's attributes): argument parse, device rule,
    the job, the result line."""
    import argparse

    ap = argparse.ArgumentParser(prog="avenir_tpu", parents=[_trace_flag()])
    ap.add_argument("jobname", help="job name or reference Tool class")
    ap.add_argument("--conf", required=False, default=None,
                    help="properties file (the -Dconf.path analog)")
    ap.add_argument("--incremental", action="store_true",
                    help="delta-scan a streamed job: restore the last "
                         "fold-state checkpoint and fold only appended "
                         "blocks (run_incremental)")
    ap.add_argument("--shard", type=int, default=0, metavar="N",
                    help="run a streamed job's scan across N worker "
                         "processes: over-partitioned byte-range blocks "
                         "claimed through the first-commit-wins block "
                         "ledger, merged via the registered fold-state "
                         "algebra (avenir_tpu.dist.run_sharded); "
                         "byte-identical to the solo scan")
    ap.add_argument("--autotune", action="store_true",
                    help="close the telemetry loop: apply the profile "
                         "store's tuned knobs to this run and record its "
                         "signals for the next (sets stream.autotune)")
    ap.add_argument("paths", nargs="*", help="input paths... output path")
    # intermixed: `jobname --conf props IN OUT` splits the positionals
    # around the optional, which plain parse_args cannot reassemble
    args = ap.parse_intermixed_args(argv)
    if not args.paths:
        ap.error("expected IN... OUT paths (at least an output path)")
    from avenir_tpu.utils.devices import require_backend

    require_backend()
    # a .conf path routes through the HOCON block loader in run_job
    props = args.conf if args.conf else {}
    if args.autotune:
        # splice the opt-in key into the properties; HOCON confs carry
        # per-block keys, so the flag cannot reach inside one — set
        # stream.autotune in the job's block instead
        if isinstance(props, str):
            if props.endswith(".conf"):
                ap.error("--autotune cannot rewrite a HOCON .conf; set "
                         "stream.autotune = true in the job's block")
            props = dict(load_properties(props))
        else:
            props = dict(props)
        props["stream.autotune"] = "true"
    short = args.jobname.rsplit(".", 1)[-1]
    name = args.jobname if args.jobname in _REGISTRY else short[0].lower() + short[1:]
    note["job"] = name
    inputs, output = args.paths[:-1], args.paths[-1]
    if args.shard and args.incremental and (
            _REGISTRY[name][0] if name in _REGISTRY else name) in (
            "frequentItemsApriori", "candidateGenerationWithSelfJoin"):
        # every other family composes the two drivers (run_sharded_refresh);
        # the miners' per-k rounds re-scan the whole corpus per candidate
        # length, so their 'incremental refresh' would be a hidden full
        # re-mine — loud over silent
        ap.error("--shard and --incremental cannot compose for the "
                 "miners: per-k candidate rounds re-scan the whole "
                 "corpus; run --shard (full re-mine) or --incremental "
                 "alone")
    if args.shard and args.autotune:
        # the sharded driver does not consult the profile store yet;
        # accepting the flag would silently tune nothing — the same
        # loud-over-silent contract the knob guard holds everywhere
        ap.error("--shard does not support --autotune yet; the sharded "
                 "driver applies no tuned knobs")
    if args.shard and args.incremental:
        from avenir_tpu.dist.driver import run_sharded_refresh

        res = run_sharded_refresh(name, props, inputs, output,
                                  procs=args.shard)
    elif args.shard:
        from avenir_tpu.dist import run_sharded

        res = run_sharded(name, props, inputs, output,
                          procs=args.shard)
    else:
        runner = run_incremental if args.incremental else run_job
        res = runner(name, props, inputs, output)
    print(json.dumps({"job": res.name, "counters": res.counters,
                      "outputs": res.outputs}))
    return res


if __name__ == "__main__":           # `python -m avenir_tpu.runner ...`
    run_from_cli(sys.argv[1:])       # same surface as `python -m avenir_tpu`
