"""Chunked-ingest == whole-file equality for every additive-count job.

The reference streams every job's input one record at a time (the mapper
contract: MutualInformation.java:138-216, MarkovStateTransitionModel.java:
116-133, FrequentItemsApriori.java:138-150, HiddenMarkovModelBuilder.java:
136-153). The TPU-native analog folds per-block count tensors; these tests
force many tiny blocks (stream.block.size.mb ~ 2KB) and assert the output
is identical to the single-block run — the algebraic guarantee that makes
the unbounded-size path trustworthy.
"""

import os

import numpy as np
import pytest

from avenir_tpu.data import generate_churn, churn_schema
from avenir_tpu.runner import run_job

TINY_BLOCK = "0.002"        # ~2KB blocks -> dozens of chunks per file


@pytest.fixture(scope="module")
def churn(tmp_path_factory):
    d = tmp_path_factory.mktemp("streamjobs")
    schema_path = str(d / "churn.json")
    churn_schema().save(schema_path)
    train = str(d / "train.csv")
    with open(train, "w") as fh:
        fh.write(generate_churn(600, seed=11, as_csv=True))
    return {"schema": schema_path, "train": train, "dir": str(d)}


def _run_both(job, props, inputs, tmp_path, prefix):
    whole = str(tmp_path / f"{job}_whole.txt")
    chunked = str(tmp_path / f"{job}_chunked.txt")
    run_job(job, props, inputs, whole)
    run_job(job, {**props, f"{prefix}.stream.block.size.mb": TINY_BLOCK},
            inputs, chunked)
    return open(whole).read(), open(chunked).read()


def test_mutual_information_chunked_equals_whole(churn, tmp_path):
    props = {
        "mut.feature.schema.file.path": churn["schema"],
        "mut.mutual.info.score.algorithms":
            "mutual.info.maximization,joint.mutual.info,"
            "min.redundancy.max.relevance",
    }
    whole, chunked = _run_both("mutualInformation", props,
                               [churn["train"]], tmp_path, "mut")
    assert whole == chunked
    assert "featureClassMI" in whole


def test_cramer_chunked_equals_whole(churn, tmp_path):
    props = {"crc.feature.schema.file.path": churn["schema"]}
    whole, chunked = _run_both("cramerCorrelation", props,
                               [churn["train"]], tmp_path, "crc")
    assert whole == chunked and whole.strip()


def test_heterogeneity_chunked_equals_whole(churn, tmp_path):
    props = {"hrc.feature.schema.file.path": churn["schema"]}
    whole, chunked = _run_both("heterogeneityReduction", props,
                               [churn["train"]], tmp_path, "hrc")
    assert whole == chunked and whole.strip()


def test_numerical_corr_chunked_close_to_whole(churn, tmp_path):
    # moment sums reassociate across chunk boundaries: allclose, not bytes
    props = {"nuc.feature.schema.file.path": churn["schema"]}
    whole, chunked = _run_both("numericalCorrelation", props,
                               [churn["train"]], tmp_path, "nuc")

    def parse(text):
        return np.array([float(ln.rsplit(",", 1)[1])
                         for ln in text.splitlines()])

    np.testing.assert_allclose(parse(whole), parse(chunked), atol=1e-5)


def _markov_file(tmp_path, per_entity=False):
    rng = np.random.default_rng(7)
    states = ["L", "M", "H"]
    path = str(tmp_path / ("seq_ent.csv" if per_entity else "seq.csv"))
    with open(path, "w") as fh:
        for i in range(150):
            up = i % 2 == 0
            s, toks = 1, []
            for _ in range(10):
                p = [0.1, 0.3, 0.6] if up else [0.6, 0.3, 0.1]
                s = int(np.clip(s + rng.choice([-1, 0, 1], p=p), 0, 2))
                toks.append(states[s])
            ent = f"e{i % 7}" if per_entity else ("T" if up else "F")
            fh.write(f"{ent},{'T' if up else 'F'}," + ",".join(toks) + "\n")
    return path


def test_markov_per_class_chunked_equals_whole(tmp_path):
    path = _markov_file(tmp_path)
    props = {
        "mst.model.states": "L,M,H",
        "mst.class.label.field.ord": "1",
        "mst.skip.field.count": "2",
        "mst.class.labels": "T,F",
    }
    whole, chunked = _run_both("markovStateTransitionModel", props,
                               [path], tmp_path, "mst")
    assert whole == chunked and "classLabel:T" in whole


def test_markov_per_entity_chunked_equals_whole(tmp_path):
    path = _markov_file(tmp_path, per_entity=True)
    props = {
        "mst.model.states": "L,M,H",
        "mst.id.field.ordinals": "0",
        "mst.class.attr.ordinal": "1",
        "mst.seq.start.ordinal": "2",
    }
    whole, chunked = _run_both("markovStateTransitionModel", props,
                               [path], tmp_path, "mst")
    assert whole == chunked and "entity:" in whole


def test_hmm_chunked_equals_whole(tmp_path):
    rng = np.random.default_rng(3)
    states, obs = ["A", "B"], ["x", "y"]
    path = str(tmp_path / "tagged.csv")
    with open(path, "w") as fh:
        for i in range(120):
            s = rng.integers(0, 2)
            toks = []
            for _ in range(8):
                s = s if rng.random() < 0.8 else 1 - s
                o = s if rng.random() < 0.9 else 1 - s
                toks.append(f"{obs[o]}:{states[s]}")
            fh.write(f"e{i}," + ",".join(toks) + "\n")
    props = {
        "hmmb.model.states": "A,B",
        "hmmb.model.observations": "x,y",
        "hmmb.skip.field.count": "1",
    }
    whole, chunked = _run_both("hiddenMarkovModelBuilder", props,
                               [path], tmp_path, "hmmb")
    assert whole == chunked and whole.strip()


def test_hmm_partially_tagged_chunked_equals_whole(tmp_path):
    rng = np.random.default_rng(4)
    path = str(tmp_path / "partial.csv")
    with open(path, "w") as fh:
        for i in range(80):
            toks = []
            for t in range(12):
                toks.append("A" if t % 5 == 2 and rng.random() < 0.8
                            else ("x" if rng.random() < 0.5 else "y"))
            fh.write(f"e{i}," + ",".join(toks) + "\n")
    props = {
        "hmmb.model.states": "A,B",
        "hmmb.model.observations": "x,y",
        "hmmb.skip.field.count": "1",
        "hmmb.partially.tagged": "true",
        "hmmb.window.function": "3,2,1",
    }
    whole, chunked = _run_both("hiddenMarkovModelBuilder", props,
                               [path], tmp_path, "hmmb")
    assert whole == chunked and whole.strip()


def test_word_counter_chunked_equals_whole(tmp_path):
    rng = np.random.default_rng(5)
    vocab = ["alpha", "beta", "gamma", "delta"]
    path = str(tmp_path / "text.csv")
    with open(path, "w") as fh:
        for _ in range(300):
            fh.write(" ".join(rng.choice(vocab, 6)) + "\n")
    props = {"wco.text.field.ordinal": "-1", "wco.field.delim.regex": " "}
    whole, chunked = _run_both("wordCounter", props, [path], tmp_path, "wco")
    assert whole == chunked
    assert len(whole.splitlines()) == len(vocab)


def _trans_file(tmp_path):
    rng = np.random.default_rng(6)
    path = str(tmp_path / "trans.csv")
    with open(path, "w") as fh:
        for i in range(200):
            items = {"milk"} if rng.random() < 0.8 else set()
            if "milk" in items and rng.random() < 0.75:
                items.add("bread")
            if rng.random() < 0.3:
                items.add("beer")
            if items:
                fh.write(f"T{i}," + ",".join(sorted(items)) + "\n")
    return path


def test_apriori_chunked_equals_whole(tmp_path):
    path = _trans_file(tmp_path)
    props = {"fia.support.threshold": "0.2", "fia.item.set.length": "2",
             "fia.skip.field.count": "1"}
    whole_dir = str(tmp_path / "iw")
    chunk_dir = str(tmp_path / "ic")
    res_w = run_job("frequentItemsApriori", props, [path], whole_dir)
    res_c = run_job("frequentItemsApriori",
                    {**props, "fia.stream.block.size.mb": TINY_BLOCK},
                    [path], chunk_dir)
    assert len(res_w.outputs) == len(res_c.outputs) >= 2
    for a, b in zip(res_w.outputs, res_c.outputs):
        assert open(a).read() == open(b).read()


@pytest.mark.parametrize("job,prefix", [
    ("mutualInformation", "mut"),
    ("cramerCorrelation", "crc"),
    ("heterogeneityReduction", "hrc"),
    ("numericalCorrelation", "nuc"),
])
def test_empty_input_fails_crisply(churn, tmp_path, job, prefix):
    empty = str(tmp_path / "empty.csv")
    open(empty, "w").write("")
    props = {f"{prefix}.feature.schema.file.path": churn["schema"]}
    with pytest.raises(ValueError, match="empty input"):
        run_job(job, props, [empty], str(tmp_path / "out.txt"))


def test_miner_jobs_report_throughput_counters(tmp_path):
    """The two slowest streamed jobs must report non-null Basic:Records
    and Basic:RowsPerSec (VERDICT Weak #3: both came back rows:null at
    100M rows, so no throughput regression could even be detected), and
    the streamed results must stay identical to the in-RAM batch path."""
    apath = _trans_file(tmp_path)
    props = {"fia.support.threshold": "0.2", "fia.item.set.length": "2",
             "fia.skip.field.count": "1"}
    res_batch = run_job("frequentItemsApriori", props, [apath],
                        str(tmp_path / "cb"))
    res_stream = run_job("frequentItemsApriori",
                         {**props, "fia.stream.block.size.mb": TINY_BLOCK},
                         [apath], str(tmp_path / "cs"))
    n_rows = sum(1 for _ in open(apath))
    for res in (res_batch, res_stream):
        assert res.counters["Basic:Records"] == n_rows
        assert res.counters["Basic:RowsPerSec"] > 0
    for a, b in zip(res_batch.outputs, res_stream.outputs):
        assert open(a).read() == open(b).read()

    gpath = _gsp_file(tmp_path)
    gprops = {"cgs.support.threshold": "0.2", "cgs.item.set.length": "3",
              "cgs.skip.field.count": "1",
              "cgs.stream.block.size.mb": TINY_BLOCK}
    res_g = run_job("candidateGenerationWithSelfJoin", gprops, [gpath],
                    str(tmp_path / "gt"))
    assert res_g.counters["Basic:Records"] == sum(1 for _ in open(gpath))
    assert res_g.counters["Basic:RowsPerSec"] > 0


def test_apriori_emit_trans_id_streams(tmp_path):
    path = _trans_file(tmp_path)
    props = {"fia.support.threshold": "0.2", "fia.item.set.length": "2",
             "fia.skip.field.count": "1", "fia.emit.trans.id": "true",
             "fia.stream.block.size.mb": TINY_BLOCK}
    res = run_job("frequentItemsApriori", props, [path],
                  str(tmp_path / "ids"))
    first = open(res.outputs[0]).read().splitlines()[0]
    # per-set exact transaction id lists ride along (fia.emit.trans.id)
    assert any(tok.startswith("T") for tok in first.split(","))


def test_rule_evaluator_chunked_equals_whole(churn, tmp_path):
    props = {"rue.feature.schema.file.path": churn["schema"],
             "rue.rule.names": "r1",
             "rue.rule.r1": "3 eq high => 6 eq closed"}
    whole, chunked = _run_both("ruleEvaluator", props,
                               [churn["train"]], tmp_path, "rue")
    assert whole == chunked and whole.strip()


def test_class_affinity_chunked_equals_whole(churn, tmp_path):
    props = {"cca.feature.schema.file.path": churn["schema"]}
    whole, chunked = _run_both("categoricalClassAffinity", props,
                               [churn["train"]], tmp_path, "cca")
    assert whole == chunked and whole.strip()


def test_supervised_encoding_chunked_equals_whole(churn, tmp_path):
    props = {"coe.feature.schema.file.path": churn["schema"],
             "coe.encoding.strategy": "weightOfEvidence"}
    whole, chunked = _run_both("categoricalContinuousEncoding", props,
                               [churn["train"]], tmp_path, "coe")
    assert whole == chunked and whole.strip()


def test_mi_fused_and_fallback_paths_agree(churn, monkeypatch):
    """The fused 3-dispatch MI chunk kernel and the per-pair cross_count
    fallback (taken when int32 keys would wrap) must produce identical
    tables."""
    from avenir_tpu.core.dataset import Dataset
    from avenir_tpu.core.schema import FeatureSchema
    from avenir_tpu.models import explore

    ds = Dataset.from_csv(open(churn["train"]).read(),
                          FeatureSchema.from_file(churn["schema"]))
    fused = explore.MutualInformationAnalyzer(ds)
    monkeypatch.setattr(explore, "_FUSED_KEYSPACE_LIMIT", 1)
    fallback = explore.MutualInformationAnalyzer(ds)
    np.testing.assert_array_equal(fused.feature_class_mi,
                                  fallback.feature_class_mi)
    np.testing.assert_array_equal(fused.pair_class_mi,
                                  fallback.pair_class_mi)
    np.testing.assert_array_equal(fused.pair_mi, fallback.pair_mi)


def test_markov_native_and_python_paths_agree(tmp_path, monkeypatch):
    """The native CSR encode path and the python split path must produce
    identical models (the native lib may be unavailable on some hosts)."""
    import avenir_tpu.native.ingest as ingest

    path = _markov_file(tmp_path)
    props = {
        "mst.model.states": "L,M,H",
        "mst.class.label.field.ord": "1",
        "mst.skip.field.count": "2",
        "mst.class.labels": "T,F",
    }
    native_out = str(tmp_path / "mn.txt")
    run_job("markovStateTransitionModel", props, [path], native_out)
    monkeypatch.setattr(ingest, "native_available", lambda: False)
    py_out = str(tmp_path / "mp.txt")
    run_job("markovStateTransitionModel", props, [path], py_out)
    assert open(native_out).read() == open(py_out).read()


def test_markov_class_label_collides_with_state(tmp_path, monkeypatch):
    """A class label that IS a state name must work identically on the
    native and python paths (shared-vocabulary disambiguation)."""
    import avenir_tpu.native.ingest as ingest

    path = str(tmp_path / "seq.csv")
    with open(path, "w") as fh:
        fh.write("a,H,L,M,H\nb,F,H,M,L\nc,H,M,M,H\n")
    props = {
        "mst.model.states": "L,M,H",
        "mst.class.label.field.ord": "1",
        "mst.skip.field.count": "2",
        "mst.class.labels": "H,F",       # 'H' is also a state
    }
    out_n = str(tmp_path / "n.txt")
    run_job("markovStateTransitionModel", props, [path], out_n)
    assert "classLabel:H" in open(out_n).read()
    monkeypatch.setattr(ingest, "native_available", lambda: False)
    out_p = str(tmp_path / "p.txt")
    run_job("markovStateTransitionModel",
            {**props, "mst.stream.block.size.mb": TINY_BLOCK}, [path], out_p)
    assert open(out_n).read() == open(out_p).read()


def test_hmm_native_and_python_paths_agree(tmp_path, monkeypatch):
    import avenir_tpu.native.ingest as ingest

    rng = np.random.default_rng(9)
    path = str(tmp_path / "tagged2.csv")
    with open(path, "w") as fh:
        for i in range(100):
            s = rng.integers(0, 2)
            toks = []
            for _ in range(7):
                s = s if rng.random() < 0.8 else 1 - s
                o = s if rng.random() < 0.9 else 1 - s
                toks.append(f"{['x','y'][o]}:{['A','B'][s]}")
            fh.write(f"e{i}," + ",".join(toks) + "\n")
    props = {"hmmb.model.states": "A,B", "hmmb.model.observations": "x,y",
             "hmmb.skip.field.count": "1"}
    out_n = str(tmp_path / "hn.txt")
    run_job("hiddenMarkovModelBuilder", props, [path], out_n)
    monkeypatch.setattr(ingest, "native_available", lambda: False)
    out_p = str(tmp_path / "hp.txt")
    run_job("hiddenMarkovModelBuilder", props, [path], out_p)
    assert open(out_n).read() == open(out_p).read()


def test_apriori_native_and_python_chunks_agree(tmp_path, monkeypatch):
    import avenir_tpu.native.ingest as ingest

    path = _trans_file(tmp_path)
    props = {"fia.support.threshold": "0.2", "fia.item.set.length": "2",
             "fia.skip.field.count": "1",
             "fia.stream.block.size.mb": TINY_BLOCK}
    res_n = run_job("frequentItemsApriori", props, [path],
                    str(tmp_path / "an"))
    monkeypatch.setattr(ingest, "native_available", lambda: False)
    res_p = run_job("frequentItemsApriori", props, [path],
                    str(tmp_path / "ap"))
    assert len(res_n.outputs) == len(res_p.outputs) >= 2
    for a, b in zip(res_n.outputs, res_p.outputs):
        assert open(a).read() == open(b).read()


def test_fisher_chunked_close_to_whole(churn, tmp_path):
    # per-class moment sums reassociate across chunks: allclose
    props = {"fid.feature.schema.file.path": churn["schema"]}
    whole, chunked = _run_both("fisherDiscriminant", props,
                               [churn["train"]], tmp_path, "fid")

    def parse(text):
        return np.array([[float(v) for v in ln.split(",")[1:]]
                         for ln in text.splitlines()])

    np.testing.assert_allclose(parse(whole), parse(chunked), atol=1e-4)


def _state_walk_file(tmp_path, rows):
    """`rows` lines of `c<i>,<T|F>,` and six L/M/H states of a clipped
    random walk that drifts up for T and down for F."""
    rng = np.random.default_rng(12)
    up = np.arange(rows) % 2 == 0
    steps = np.where(up[:, None],
                     rng.choice([-1, 0, 1], (rows, 6), p=[0.1, 0.3, 0.6]),
                     rng.choice([-1, 0, 1], (rows, 6), p=[0.6, 0.3, 0.1]))
    state, cols = np.ones(rows, np.int64), []
    for j in range(6):
        state = np.clip(state + steps[:, j], 0, 2)
        cols.append(state)
    toks = np.array(["L", "M", "H"])[np.stack(cols, axis=1)]
    path = str(tmp_path / "walk.csv")
    with open(path, "w") as fh:
        fh.write("".join(
            f"c{i},{'T' if up[i] else 'F'}," + ",".join(toks[i]) + "\n"
            for i in range(rows)))
    return path


def test_streamed_miners_compile_within_their_shape_buckets(tmp_path):
    """Both streamed miners over a corpus of several 1 MB blocks compile
    the GSP support kernels once per shape bucket (block and candidate
    axes are padded to powers of two), not once per block: no more than
    16 variants, and none on a second pass over the same file. It is the
    runtime check behind graftlint's `recompile-hazard` rule."""
    from avenir_tpu.models.sequence import (_subseq_fold_kernel,
                                            _subseq_support_kernel)
    from avenir_tpu.utils.metrics import jit_cache_size

    def variants():
        return (jit_cache_size(_subseq_support_kernel)
                + jit_cache_size(_subseq_fold_kernel))

    if jit_cache_size(_subseq_support_kernel) < 0:
        pytest.skip("this jax does not expose a jitted function's cache size")
    rows = 150_000
    path = _state_walk_file(tmp_path, rows)
    assert os.path.getsize(path) > 3 << 20          # four blocks of 1 MB

    def both(tag):
        for job, p in (("frequentItemsApriori", "fia"),
                       ("candidateGenerationWithSelfJoin", "cgs")):
            res = run_job(job, {f"{p}.support.threshold": "0.3",
                                f"{p}.item.set.length": "2",
                                f"{p}.skip.field.count": "2",
                                f"{p}.stream.block.size.mb": "1"},
                          [path], str(tmp_path / f"{job}_{tag}"))
            assert res.counters["Basic:Records"] == rows

    before = variants()
    both("first")
    first = variants()
    # growth, not the absolute size: other tests of this worker compile
    # the same kernels at their own shapes
    assert 1 <= first - before <= 16
    both("second")
    assert variants() == first


def test_markov_per_entity_native_and_python_agree(tmp_path, monkeypatch):
    import avenir_tpu.native.ingest as ingest

    path = _markov_file(tmp_path, per_entity=True)
    props = {
        "mst.model.states": "L,M,H",
        "mst.id.field.ordinals": "0",
        "mst.class.attr.ordinal": "1",
        "mst.seq.start.ordinal": "2",
    }
    out_n = str(tmp_path / "en.txt")
    run_job("markovStateTransitionModel", props, [path], out_n)
    monkeypatch.setattr(ingest, "native_available", lambda: False)
    out_p = str(tmp_path / "ep.txt")
    run_job("markovStateTransitionModel", props, [path], out_p)
    assert open(out_n).read() == open(out_p).read()
    assert "entity:" in open(out_n).read()


def test_text_nb_chunked_equals_whole(tmp_path):
    rng = np.random.default_rng(13)
    path = str(tmp_path / "docs.csv")
    pos = ["great product works fine", "love the service quality",
           "excellent fast support"]
    neg = ["terrible broken product", "awful slow support experience",
           "bad service never again"]
    with open(path, "w") as fh:
        for _ in range(200):
            good = rng.random() < 0.5
            fh.write(f"{rng.choice(pos if good else neg)},"
                     f"{'P' if good else 'N'}\n")
    props = {"bad.tabular.input": "false"}
    whole, chunked = _run_both("bayesianDistr", props, [path],
                               tmp_path, "bad")
    assert whole == chunked and whole.strip()


def _gsp_file(tmp_path):
    rng = np.random.default_rng(21)
    path = str(tmp_path / "gseq.csv")
    with open(path, "w") as fh:
        for i in range(250):
            seq = ["login", "browse"]
            if rng.random() < 0.6:
                seq += ["cart", "buy"]
            if rng.random() < 0.3:
                seq.append("logout")
            fh.write(f"u{i}," + ",".join(seq) + "\n")
    return path


def test_gsp_chunked_equals_whole(tmp_path):
    path = _gsp_file(tmp_path)
    props = {"cgs.support.threshold": "0.2", "cgs.item.set.length": "3",
             "cgs.skip.field.count": "1"}
    res_w = run_job("candidateGenerationWithSelfJoin", props, [path],
                    str(tmp_path / "gw"))
    res_c = run_job("candidateGenerationWithSelfJoin",
                    {**props, "cgs.stream.block.size.mb": TINY_BLOCK},
                    [path], str(tmp_path / "gc"))
    assert len(res_w.outputs) == len(res_c.outputs) >= 2
    for a, b in zip(res_w.outputs, res_c.outputs):
        assert open(a).read() == open(b).read()


def test_gsp_stream_native_and_python_agree(tmp_path, monkeypatch):
    import avenir_tpu.native.ingest as ingest

    path = _gsp_file(tmp_path)
    props = {"cgs.support.threshold": "0.2", "cgs.item.set.length": "3",
             "cgs.skip.field.count": "1",
             "cgs.stream.block.size.mb": TINY_BLOCK}
    res_n = run_job("candidateGenerationWithSelfJoin", props, [path],
                    str(tmp_path / "gn"))
    monkeypatch.setattr(ingest, "native_available", lambda: False)
    res_p = run_job("candidateGenerationWithSelfJoin", props, [path],
                    str(tmp_path / "gp"))
    for a, b in zip(res_n.outputs, res_p.outputs):
        assert open(a).read() == open(b).read()


def test_byte_block_splits_cover_every_line_once(tmp_path):
    """iter_byte_blocks(byte_range=...) follows the LineRecordReader
    split contract: disjoint ranges covering the file yield every line
    exactly once — partial Markov models from splits merge to the whole
    model (the multi-host sequence ingest story)."""
    from avenir_tpu.core.stream import iter_byte_blocks
    from avenir_tpu.models.markov import MarkovStateTransitionModel
    from avenir_tpu.native.ingest import seq_encode_native

    path = _markov_file(tmp_path)
    size = os.path.getsize(path)
    # awkward split points (mid-line) across 3 ranges
    cuts = [0, size // 3 + 7, 2 * size // 3 + 3, size]
    merged_lines = []
    part_counts = np.zeros((2, 3, 3))
    label_codes = np.asarray([3, 4])
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        m = MarkovStateTransitionModel(["L", "M", "H"],
                                       class_labels=["T", "F"])
        for blk in iter_byte_blocks(path, 512, byte_range=(lo, hi)):
            merged_lines += [ln for ln in
                             blk.decode().split("\n") if ln.strip()]
            enc = seq_encode_native(blk, ",", ["L", "M", "H", "T", "F"])
            m.fit_csr(*enc, skip=2, class_ord=1, label_codes=label_codes)
        part_counts += m.counts
    assert sorted(merged_lines) == sorted(
        ln for ln in open(path).read().split("\n") if ln.strip())
    whole = MarkovStateTransitionModel(["L", "M", "H"],
                                       class_labels=["T", "F"])
    for blk in iter_byte_blocks(path, 1 << 20):
        enc = seq_encode_native(blk, ",", ["L", "M", "H", "T", "F"])
        whole.fit_csr(*enc, skip=2, class_ord=1, label_codes=label_codes)
    np.testing.assert_array_equal(part_counts, whole.counts)
