"""Traceable-kernel manifest: what graftlint-ir analyzes, and how.

The AST rules (rules.py) see code shapes; the IR rules (ir.py) see what
tracing actually produced. That needs a registry of *traceable units*:
for each hot kernel an entry point plus the abstract shapes/dtypes to
trace it with, and for each distributed family additionally the mesh to
lower on and the analytic collective-payload model
(`parallel/scaling.collective_payload_model`) its compiled HLO must
match byte-for-byte.

Shapes here are deliberately tiny — the auditor checks *structure*
(dtypes, callbacks, collective bytes), not performance, and every dim
that feeds a payload model is pinned in the entry so the analytic number
is derivable by eye. Coverage is enforced two ways: the manifest must
name every family in ``distributed.FAMILIES``
(tests/test_graftlint_ir.py), and a family without a payload model
cannot report ``payload_model_validated``.
"""

from __future__ import annotations

import inspect
import os
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: every family entry lowers on this many virtual devices — the same
#: 8-device mesh the test harness pins (tests/conftest.py)
AUDIT_DEVICES = 8


@dataclass(frozen=True)
class KernelSpec:
    """One traceable unit.

    ``build(mesh)`` returns ``(fn, args)`` ready for ``jax.make_jaxpr``
    (and, for families, for ``fn.lower(*args).compile()`` — `fn` must be
    jitted and `args` device-placed on `mesh`). `mesh` is None for plain
    op entries. ``payload_model(mesh)`` gives the family's analytic
    collective bytes; None marks a non-distributed entry."""

    name: str                     # finding scope (rule keys use it)
    path: str                     # repo-relative module the kernel lives in
    line: int
    build: Callable
    model_parallel: int = 1       # family mesh: devices//mp x mp
    payload_model: Optional[Callable] = None

    @property
    def is_family(self) -> bool:
        return self.payload_model is not None


def _loc(obj) -> Tuple[str, int]:
    """(repo-relative posix path, first line) of a kernel's def."""
    src = inspect.getsourcefile(inspect.unwrap(obj))
    rel = os.path.relpath(os.path.abspath(src), _REPO_ROOT)
    try:
        line = inspect.getsourcelines(inspect.unwrap(obj))[1]
    except OSError:
        line = 1
    return rel.replace(os.sep, "/"), line


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


# ------------------------------------------------------------- op entries
def _op_entries() -> List[KernelSpec]:
    from avenir_tpu.ops import bitset, infotheory, pallas_knn, reduce

    def spec(name, ref, build):
        path, line = _loc(ref)
        return KernelSpec(name, path, line, build)

    def bitset_counts(_mesh):
        return (bitset.bitset_contain_counts,
                (_sds((256, 4), np.uint32), _sds((64, 4), np.uint32)))

    def bitset_mask(_mesh):
        return (bitset.bitset_contain_mask,
                (_sds((256, 4), np.uint32), _sds((64, 4), np.uint32)))

    def keyed(_mesh):
        return (lambda k, v: reduce.keyed_reduce(k, v, 64),
                (_sds((1024,), np.int32), _sds((1024,), np.float32)))

    def onehot(_mesh):
        return (lambda c: reduce.one_hot_count(c, 32),
                (_sds((1024, 4), np.int32),))

    def split_score(_mesh):
        return (lambda c: infotheory.weighted_split_score(c, "entropy"),
                (_sds((16, 4, 3), np.float32),))

    def mi(_mesh):
        return (infotheory.mutual_information, (_sds((8, 4), np.float32),))

    def pallas(_mesh):
        # interpret mode: the kernel traces (and its jaxpr is lintable)
        # with no TPU attached; the compiled path is
        # tools/tpu_kernel_check.py's job
        return (lambda q, t: pallas_knn.knn_topk_pallas(
                    q, t, k=5, block_q=128, block_t=256, interpret=True),
                (_sds((128, 8), np.float32), _sds((256, 8), np.float32)))

    return [
        spec("bitset_contain_counts", bitset.bitset_contain_counts,
             bitset_counts),
        spec("bitset_contain_mask", bitset.bitset_contain_mask, bitset_mask),
        spec("keyed_reduce", reduce.keyed_reduce, keyed),
        spec("one_hot_count", reduce.one_hot_count, onehot),
        spec("weighted_split_score", infotheory.weighted_split_score,
             split_score),
        spec("mutual_information", infotheory.mutual_information, mi),
        spec("knn_topk_pallas", pallas_knn.knn_topk_pallas, pallas),
    ]


# --------------------------------------------------------- family entries
def _family_entries() -> List[KernelSpec]:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from avenir_tpu.parallel import distributed as D
    from avenir_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    from avenir_tpu.parallel.scaling import (_NB_BMAX, _NB_CLASSES, _NB_FEAT,
                                             collective_payload_model)

    def put(mesh, arr, *spec):
        return jax.device_put(arr, NamedSharding(mesh, P(*spec)))

    def row(mesh):
        return tuple(a for a in (DATA_AXIS, MODEL_AXIS)
                     if a in mesh.axis_names)

    ROWS = 8 * AUDIT_DEVICES

    # dims every payload model below derives from — one place, tiny values
    KNN_K, KNN_D = 5, 8
    TREE = dict(n_leaves=4, n_splits=3, smax=2, num_classes=2)
    LR_D = 8
    MARKOV = dict(n_states=3, n_classes=2)
    APRIORI_CAND, APRIORI_VOCAB, APRIORI_K = 16, 12, 2
    BANDIT_ARMS, BANDIT_BATCH = 6, 2
    CROSS = dict(bins_a=10, bins_b=2)

    def knn_build(mesh):
        data_n = mesh.shape[DATA_AXIS]
        model_n = mesh.shape.get(MODEL_AXIS, 1)
        nq, train = 8 * data_n, 16 * model_n
        fn = D.distributed_topk_fn(mesh, k=KNN_K, metric="euclidean")
        return fn, (
            put(mesh, np.zeros((nq, KNN_D), np.float32), DATA_AXIS, None),
            put(mesh, np.zeros((train, KNN_D), np.float32), MODEL_AXIS, None),
            put(mesh, np.zeros((train,), np.int32), MODEL_AXIS),
        )

    def knn_payload(mesh):
        return collective_payload_model(
            "knn_topk", dict(mesh.shape), nq=8 * mesh.shape[DATA_AXIS],
            k=KNN_K)

    def nb_build(mesh):
        r = row(mesh)
        fn = D.distributed_nb_train_fn(mesh, _NB_CLASSES, _NB_BMAX)
        return fn, (
            put(mesh, np.zeros((ROWS, _NB_FEAT), np.int32), r),
            put(mesh, np.zeros((ROWS,), np.int32), r),
            put(mesh, np.ones((ROWS,), np.float32), r),
        )

    def nb_payload(mesh):
        return collective_payload_model(
            "nb_train", dict(mesh.shape), n_feat=_NB_FEAT,
            num_classes=_NB_CLASSES, bmax=_NB_BMAX)

    def tree_build(mesh):
        from avenir_tpu.models.tree import LANES

        r = row(mesh)
        fn = D.distributed_tree_level_fn(
            mesh, TREE["n_leaves"], TREE["n_splits"], TREE["smax"],
            TREE["num_classes"])
        return fn, (
            put(mesh, np.zeros((ROWS, LANES), np.int32), r),
            put(mesh, np.zeros((TREE["n_splits"], ROWS, LANES), np.int8),
                None, r),
            put(mesh, np.zeros((ROWS, LANES), np.int32), r),
            put(mesh, np.ones((ROWS, LANES), np.int32), r),
        )

    def tree_payload(mesh):
        return collective_payload_model("tree_level", dict(mesh.shape),
                                        **TREE)

    def lr_build(mesh):
        r = row(mesh)
        fn = D.distributed_lr_step_fn(mesh, learning_rate=0.5)
        return fn, (
            put(mesh, np.zeros((LR_D,), np.float32)),
            put(mesh, np.zeros((ROWS, LR_D), np.float32), r),
            put(mesh, np.zeros((ROWS,), np.float32), r),
            put(mesh, np.ones((ROWS,), np.float32), r),
        )

    def lr_payload(mesh):
        return collective_payload_model("lr_step", dict(mesh.shape), d=LR_D)

    def markov_build(mesh):
        r = row(mesh)
        fn = D.distributed_markov_counts_fn(
            mesh, MARKOV["n_states"], MARKOV["n_classes"])
        return fn, (
            put(mesh, np.zeros((ROWS, 6), np.int32), r),
            put(mesh, np.zeros((ROWS,), np.int32), r),
        )

    def markov_payload(mesh):
        return collective_payload_model("markov_counts", dict(mesh.shape),
                                        **MARKOV)

    def apriori_build(mesh):
        r = row(mesh)
        fn = D.distributed_apriori_support_fn(mesh, APRIORI_K)
        return fn, (
            put(mesh, np.zeros((ROWS, APRIORI_VOCAB), np.float32), r),
            put(mesh, np.zeros((APRIORI_CAND, APRIORI_VOCAB), np.float32)),
        )

    def apriori_payload(mesh):
        return collective_payload_model("apriori_support", dict(mesh.shape),
                                        n_cand=APRIORI_CAND)

    def bandit_build(mesh):
        r = row(mesh)
        fn = D.distributed_bandit_select_fn(mesh, batch_size=BANDIT_BATCH)
        return fn, (
            put(mesh, np.zeros((ROWS, BANDIT_ARMS), np.int32), r),
            put(mesh, np.zeros((ROWS, BANDIT_ARMS), np.float32), r),
            put(mesh, np.ones((ROWS, BANDIT_ARMS), bool), r),
            put(mesh, np.float32(5.0)),
        )

    def bandit_payload(mesh):
        return collective_payload_model("bandit_select", dict(mesh.shape))

    def cross_build(mesh):
        r = row(mesh)
        fn = D.distributed_crosscount_fn(mesh, CROSS["bins_a"],
                                         CROSS["bins_b"])
        return fn, (
            put(mesh, np.zeros((ROWS,), np.int32), r),
            put(mesh, np.zeros((ROWS,), np.int32), r),
            put(mesh, np.ones((ROWS,), np.float32), r),
        )

    def cross_payload(mesh):
        return collective_payload_model("crosscount", dict(mesh.shape),
                                        **CROSS)

    builders = {
        "knn_topk": (D.distributed_topk_fn, knn_build, knn_payload, 2),
        "nb_train": (D.distributed_nb_train_fn, nb_build, nb_payload, 1),
        "tree_level": (D.distributed_tree_level_fn, tree_build,
                       tree_payload, 1),
        "lr_step": (D.distributed_lr_step_fn, lr_build, lr_payload, 1),
        "markov_counts": (D.distributed_markov_counts_fn, markov_build,
                          markov_payload, 1),
        "apriori_support": (D.distributed_apriori_support_fn, apriori_build,
                            apriori_payload, 1),
        "bandit_select": (D.distributed_bandit_select_fn, bandit_build,
                          bandit_payload, 1),
        "crosscount": (D.distributed_crosscount_fn, cross_build,
                       cross_payload, 1),
    }
    out = []
    for name, (ref, build, payload, mp) in builders.items():
        path, line = _loc(ref)
        out.append(KernelSpec(name, path, line, build,
                              model_parallel=mp, payload_model=payload))
    return out


def manifest_entries() -> List[KernelSpec]:
    """The full manifest: hot ops + every distributed family."""
    return _op_entries() + _family_entries()


def family_names() -> List[str]:
    return [s.name for s in _family_entries()]


# ------------------------------------------------------ streamed fold kernels
@dataclass(frozen=True)
class StreamKernelSpec:
    """One streamed fold kernel for the chunk-invariance auditor
    (analysis/flow.py).

    ``prepare(workdir)`` writes the kernel's corpus (deterministic,
    seeded) and returns a context dict; ``run(ctx, block_mb)`` executes
    the REAL streamed job over that corpus with the given stream block
    size and returns the output artifact's bytes. `layouts` holds >= 3
    block sizes chosen so the corpus chunks into visibly different
    layouts (single block / a dozen / dozens) — the auditor verifies the
    chunk counts actually differ, then asserts the bytes don't.

    ``jobs`` names the registered runner job(s) the spec drives (several
    for the fused shared-scan entries): the memory auditor
    (analysis/mem.py) keys its per-job analytic footprint model on
    them, so every stream entry is memory-auditable by construction.

    ``fold_specs`` carries the same jobs as ``(job, prefix, conf)``
    triples (conf values may hold ``{schema}``-style ctx placeholders,
    formatted exactly like ``_job_runner`` does): the shard-merge/
    resume auditor (analysis/merge.py) drives each job's REGISTERED
    fold sink (runner.stream_fold_ops) directly with them, so every
    stream entry is merge-auditable by construction too."""

    name: str
    path: str                     # repo-relative module of the fold kernel
    line: int
    prepare: Callable             # workdir -> ctx dict
    run: Callable                 # (ctx, block_mb) -> bytes
    layouts: Tuple[float, ...] = (64.0, 0.002, 0.0005)
    jobs: Tuple[str, ...] = ()
    fold_specs: Tuple[Tuple[str, str, dict], ...] = ()


def _job_runner(job: str, prefix: str, conf: dict, inputs_key: str = "csv"):
    """run(ctx, block_mb) driving a registered runner job with the
    kernel's corpus and `<prefix>.stream.block.size.mb` pinned to the
    layout under test — the full streamed path (prefetched block reads,
    shared-schema chunk parses, double-buffered device folds, output
    writer), not a unit-sized re-implementation of it."""

    def run(ctx: dict, block_mb: float) -> bytes:
        from avenir_tpu.runner import run_job

        ctx["runs"] = ctx.get("runs", 0) + 1
        out = os.path.join(ctx["dir"], f"out_{ctx['runs']}.txt")
        props = dict(conf)
        for key, val in list(props.items()):
            props[key] = val.format(**ctx) if isinstance(val, str) else val
        props[f"{prefix}.stream.block.size.mb"] = repr(float(block_mb))
        res = run_job(job, props, [ctx[inputs_key]], out)
        # the artifact is every output file the job wrote (the miners
        # emit one per itemset length), name-tagged so a missing per-k
        # file can't alias a reordered one
        blobs = []
        for p in sorted(res.outputs):
            rel = os.path.relpath(p, out)   # run-invariant name ('.'
            with open(p, "rb") as fh:       # for single-file outputs)
                blobs.append(rel.encode() + b"\0" + fh.read())
        return b"\n".join(blobs)

    return run


def _shared_runner(specs):
    """run(ctx, block_mb) driving N registered jobs through runner.
    run_shared — the REAL scan-sharing executor (one SharedScan read +
    parse, N fold sinks) — with every job's stream block size pinned to
    the layout under test. The artifact is every output file of every
    fused job, name-tagged, so a drift in ANY sink's fold fails the
    byte-identity assertion. `specs` is [(job, prefix, conf)]."""

    def run(ctx: dict, block_mb: float) -> bytes:
        from avenir_tpu.runner import run_shared

        ctx["runs"] = ctx.get("runs", 0) + 1
        blobs = []
        shared_specs = []
        outs = []
        for job, prefix, conf in specs:
            out = os.path.join(ctx["dir"], f"out_{ctx['runs']}_{job}")
            props = {k: (v.format(**ctx) if isinstance(v, str) else v)
                     for k, v in conf.items()}
            props[f"{prefix}.stream.block.size.mb"] = repr(float(block_mb))
            shared_specs.append((job, props, out))
            outs.append(out)
        results = run_shared(shared_specs, [ctx["csv"]])
        for (job, _prefix, _conf), out in zip(specs, outs):
            res = results[job]
            for p in sorted(res.outputs):
                rel = os.path.relpath(p, out)
                with open(p, "rb") as fh:
                    blobs.append(f"{job}:{rel}".encode() + b"\0" + fh.read())
        return b"\n".join(blobs)

    return run


def _churn_corpus(workdir: str) -> dict:
    from avenir_tpu.data import churn_schema, generate_churn

    csv = os.path.join(workdir, "churn.csv")
    with open(csv, "w") as fh:
        fh.write(generate_churn(600, seed=11, as_csv=True))
    schema = os.path.join(workdir, "churn.json")
    churn_schema().save(schema)
    return {"dir": workdir, "csv": csv, "schema": schema}


def _seq_corpus(workdir: str) -> dict:
    """Markov/miner corpus: 3-state token sequences with a class column,
    at auditor size."""
    rng = np.random.default_rng(12)
    states = ["L", "M", "H"]
    csv = os.path.join(workdir, "seq.csv")
    with open(csv, "w") as fh:
        for i in range(400):
            up = i % 2 == 0
            s, toks = 1, []
            for _ in range(6):
                p = [0.1, 0.3, 0.6] if up else [0.6, 0.3, 0.1]
                s = int(np.clip(s + rng.choice([-1, 0, 1], p=p), 0, 2))
                toks.append(states[s])
            fh.write(f"c{i},{'T' if up else 'F'}," + ",".join(toks) + "\n")
    return {"dir": workdir, "csv": csv}


def stream_entries() -> List[StreamKernelSpec]:
    """The streamed fold kernels the chunk-invariance auditor proves
    deterministic every run: NB, MI, Markov, Apriori, GSP, discriminant
    — every additive-count fold the 1B-row path is built on. Each
    `path:line` points at the fold kernel itself (the accumulate /
    mine_stream the job drives), so findings land on the code that owns
    the invariant."""
    from avenir_tpu.core.stream import SharedScan
    from avenir_tpu.models.association import FrequentItemsApriori
    from avenir_tpu.models.discriminant import FisherDiscriminant
    from avenir_tpu.models.explore import MutualInformationAnalyzer
    from avenir_tpu.models.markov import MarkovStateTransitionModel
    from avenir_tpu.models.naive_bayes import NaiveBayesModel
    from avenir_tpu.models.sequence import GSPMiner

    def spec(name, ref, prepare, run, fold_specs):
        path, line = _loc(ref)
        return StreamKernelSpec(
            name, path, line, prepare, run,
            jobs=tuple(job for job, _prefix, _conf in fold_specs),
            fold_specs=tuple((job, prefix, dict(conf))
                            for job, prefix, conf in fold_specs))

    schema_conf = lambda prefix: {
        f"{prefix}.feature.schema.file.path": "{schema}"}
    # ONE definition of each job's audit config, shared by the runner
    # closures (chunk-invariance / footprint audits) and the fold_specs
    # (shard-merge/resume audit) so the tiers can never drift apart
    nb_spec = ("bayesianDistr", "bad", schema_conf("bad"))
    mi_spec = ("mutualInformation", "mut", {
        **schema_conf("mut"),
        "mut.mutual.info.score.algorithms":
            "mutual.info.maximization,min.redundancy.max.relevance",
    })
    fid_spec = ("fisherDiscriminant", "fid", schema_conf("fid"))
    mst_spec = ("markovStateTransitionModel", "mst", {
        "mst.model.states": "L,M,H",
        "mst.class.label.field.ord": "1",
        "mst.skip.field.count": "2",
        "mst.class.labels": "T,F",
    })
    fia_spec = ("frequentItemsApriori", "fia", {
        "fia.support.threshold": "0.3",
        "fia.item.set.length": "2",
        "fia.skip.field.count": "2",
    })
    cgs_spec = ("candidateGenerationWithSelfJoin", "cgs", {
        "cgs.support.threshold": "0.3",
        "cgs.item.set.length": "2",
        "cgs.skip.field.count": "2",
    })

    def solo(name, ref, prepare, job_spec):
        job, prefix, conf = job_spec
        return spec(name, ref, prepare, _job_runner(job, prefix, conf),
                    [job_spec])

    return [
        solo("nb_stream", NaiveBayesModel.accumulate, _churn_corpus,
             nb_spec),
        solo("mi_stream", MutualInformationAnalyzer.add, _churn_corpus,
             mi_spec),
        solo("discriminant_stream", FisherDiscriminant.accumulate,
             _churn_corpus, fid_spec),
        solo("markov_stream", MarkovStateTransitionModel.fit_csr,
             _seq_corpus, mst_spec),
        solo("apriori_stream", FrequentItemsApriori.mine_stream,
             _seq_corpus, fia_spec),
        solo("gsp_stream", GSPMiner.mine_stream, _seq_corpus, cgs_spec),
        # fused shared-scan entries: the SAME jobs through the
        # scan-sharing executor (ONE read + parse, N fold sinks). The
        # auditor re-proves every round that fan-out changes nothing —
        # fused outputs must be byte-identical under all chunk layouts
        # and the adversarial prefetch scheduler, exactly like the
        # one-job-one-scan entries above.
        spec("shared_churn_stream", SharedScan.run, _churn_corpus,
             _shared_runner([nb_spec, mi_spec, fid_spec]),
             [nb_spec, mi_spec, fid_spec]),
        spec("shared_seq_stream", SharedScan.run, _seq_corpus,
             _shared_runner([mst_spec, fia_spec]),
             [mst_spec, fia_spec]),
    ]


def stream_kernel_names() -> List[str]:
    return [s.name for s in stream_entries()]
