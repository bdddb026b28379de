"""Scan-sharing executor + encoded-block cache: the PR's contracts.

1. Equivalence — run_shared (one disk read + parse, N fold sinks) must
   produce outputs BYTE-IDENTICAL to the one-job-one-scan path for both
   scan kinds (Dataset churn corpus; raw-byte sequence corpus), and
   Pipeline.run(fuse=True) must group fusable stages and agree with the
   sequential run.
2. Failure isolation — a sink raising mid-scan closes the underlying
   prefetched() feed (worker cancelled AND joined, the PR-4 _Prefetcher
   guarantee): no wedged or leaked producer thread.
3. Cache — cold build / warm replay identity / invalidation when a
   source file changes, at both the EncodedBlockCache level and the
   miner-source level. Validity is per-block (content fingerprints):
   an APPENDED source replays its committed prefix and re-parses only
   the tail (source_delta); an in-place edit, or a writer that never
   recorded fingerprints, invalidates the whole source as before.
"""

import os

import numpy as np
import pytest

from avenir_tpu.core.stream import SharedScan, prefetched
from avenir_tpu.native.ingest import EncodedBlockCache
from avenir_tpu.runner import run_job, run_shared, stream_fold_names


def _churn(tmp_path, rows=1200):
    from avenir_tpu.data import churn_schema, generate_churn

    csv = tmp_path / "churn.csv"
    csv.write_text(generate_churn(rows, seed=11, as_csv=True))
    schema = tmp_path / "churn.json"
    churn_schema().save(str(schema))
    return str(csv), str(schema)


def _seq(tmp_path, rows=800):
    rng = np.random.default_rng(12)
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


def _read_outputs(res) -> bytes:
    return b"\n".join(open(p, "rb").read() for p in sorted(res.outputs))


# ------------------------------------------------------------- equivalence
def test_dataset_fused_outputs_byte_identical(tmp_path):
    csv, schema = _churn(tmp_path)
    conf = lambda p: {f"{p}.feature.schema.file.path": schema,  # noqa: E731
                      f"{p}.stream.block.size.mb": "0.005"}
    mi_conf = {**conf("mut"),
               "mut.mutual.info.score.algorithms":
                   "mutual.info.maximization,min.redundancy.max.relevance"}
    seq = {
        "bayesianDistr": run_job("bayesianDistr", conf("bad"), [csv],
                                 str(tmp_path / "nb1.csv")),
        "mutualInformation": run_job("mutualInformation", mi_conf, [csv],
                                     str(tmp_path / "mi1.txt")),
        "fisherDiscriminant": run_job("fisherDiscriminant", conf("fid"),
                                      [csv], str(tmp_path / "fd1.txt")),
    }
    fused = run_shared([
        ("bayesianDistr", conf("bad"), str(tmp_path / "nb2.csv")),
        ("mutualInformation", mi_conf, str(tmp_path / "mi2.txt")),
        ("fisherDiscriminant", conf("fid"), str(tmp_path / "fd2.txt")),
    ], [csv])
    assert set(fused) == set(seq)
    for name in seq:
        assert _read_outputs(fused[name]) == _read_outputs(seq[name]), name
        # Mem:PeakRSS is a process measurement, not a job output — it
        # legitimately differs between the two passes; the sidecar
        # hit/delta split depends on cache warmth (the solo pass wrote
        # the sidecar, the fused pass replays it — which the output
        # byte-identity above proves is invisible); everything else
        # (including the deterministic Mem:PredictedPeakBytes) must match
        drop = {"Mem:PeakRSS", "Sidecar:HitBlocks", "Sidecar:DeltaBlocks"}
        assert {k: v for k, v in fused[name].counters.items()
                if k not in drop} \
            == {k: v for k, v in seq[name].counters.items()
                if k not in drop}
        assert fused[name].counters["Mem:PeakRSS"] > 0
        assert seq[name].counters["Mem:PeakRSS"] > 0


def test_bytes_fused_outputs_byte_identical(tmp_path):
    csv = _seq(tmp_path)
    mst = {"mst.model.states": "L,M,H", "mst.class.label.field.ord": "1",
           "mst.skip.field.count": "2", "mst.class.labels": "T,F",
           "mst.stream.block.size.mb": "0.003"}
    fia = {"fia.support.threshold": "0.3", "fia.item.set.length": "2",
           "fia.skip.field.count": "2",
           "fia.stream.block.size.mb": "0.003"}
    cgs = {"cgs.support.threshold": "0.3", "cgs.item.set.length": "2",
           "cgs.skip.field.count": "2",
           "cgs.stream.block.size.mb": "0.003"}
    seq = {
        "markovStateTransitionModel": run_job(
            "markovStateTransitionModel", mst, [csv],
            str(tmp_path / "mst1.txt")),
        "frequentItemsApriori": run_job(
            "frequentItemsApriori", fia, [csv], str(tmp_path / "fia1")),
        "candidateGenerationWithSelfJoin": run_job(
            "candidateGenerationWithSelfJoin", cgs, [csv],
            str(tmp_path / "gsp1")),
    }
    fused = run_shared([
        ("markovStateTransitionModel", mst, str(tmp_path / "mst2.txt")),
        ("frequentItemsApriori", fia, str(tmp_path / "fia2")),
        ("candidateGenerationWithSelfJoin", cgs, str(tmp_path / "gsp2")),
    ], [csv])
    for name in seq:
        assert _read_outputs(fused[name]) == _read_outputs(seq[name]), name


def test_fused_scan_adds_no_nb_fold_variant(tmp_path):
    """The sinks of a fused scan see the chunk shapes the solo jobs saw,
    so fan-out compiles no further variant of the NB fold kernel. The
    account age is declared without a bucket width: with a continuous
    feature the fold takes `_fold_batch_kernel` on every backend (the
    all-binned churn schema folds by host `bincount` on the CPU and
    would make this vacuous)."""
    import json

    from avenir_tpu.models.naive_bayes import _fold_batch_kernel
    from avenir_tpu.utils.metrics import jit_cache_size

    if jit_cache_size(_fold_batch_kernel) < 0:
        pytest.skip("this jax does not expose a jitted function's cache size")
    csv, schema = _churn(tmp_path, rows=6000)
    with open(schema) as fh:
        fields = json.load(fh)
    for field in fields["fields"]:
        if field["name"] == "acctAge":
            del field["bucketWidth"]
    with open(schema, "w") as fh:
        json.dump(fields, fh)
    conf = lambda p: {f"{p}.feature.schema.file.path": schema,  # noqa: E731
                      f"{p}.stream.block.size.mb": "0.1"}
    mi_conf = {**conf("mut"),
               "mut.mutual.info.score.algorithms": "mutual.info.maximization"}
    trio = [("bayesianDistr", conf("bad"), "nb"),
            ("mutualInformation", mi_conf, "mi"),
            ("fisherDiscriminant", conf("fid"), "fd")]
    # growth, not the absolute size: other tests of this worker compile
    # the same kernel at their own shapes
    before = jit_cache_size(_fold_batch_kernel)
    solo = {job: run_job(job, c, [csv], str(tmp_path / f"solo_{o}"))
            for job, c, o in trio}
    after_solo = jit_cache_size(_fold_batch_kernel)
    assert after_solo > before, "the solo NB fold never ran the kernel"
    fused = run_shared([(job, c, str(tmp_path / f"fused_{o}"))
                        for job, c, o in trio], [csv])
    assert jit_cache_size(_fold_batch_kernel) == after_solo
    for job in solo:
        assert _read_outputs(fused[job]) == _read_outputs(solo[job]), job


def test_pipeline_fuse_groups_and_agrees(tmp_path):
    from avenir_tpu.core import stream
    from avenir_tpu.pipelines import profile_pipeline

    csv, schema = _churn(tmp_path, rows=600)
    props = {p + ".stream.block.size.mb": "0.005"
             for p in ("bad", "mut", "fid")}

    class Counter:
        def __init__(self):
            self.n = 0

        def __call__(self):
            self.n += 1

    plain = profile_pipeline(props, csv, str(tmp_path / "w1"),
                             schema_path=schema)
    c1 = Counter()
    prev = stream._produce_hook
    stream._produce_hook = c1
    try:
        r1 = plain.run()
    finally:
        stream._produce_hook = prev
    fused = profile_pipeline(props, csv, str(tmp_path / "w2"),
                             schema_path=schema)
    c2 = Counter()
    stream._produce_hook = c2
    try:
        r2 = fused.run(fuse=True)
    finally:
        stream._produce_hook = prev
    assert set(r1) == set(r2)
    for name in r1:
        assert _read_outputs(r2[name]) == _read_outputs(r1[name]), name
    # the fused run scanned the corpus ONCE, not three times: its
    # producer counter must be ~1/3 of the sequential run's
    assert c1.n >= 3 * c2.n - 3, (c1.n, c2.n)


def test_pipeline_fuse_falls_back_on_group_failure(tmp_path):
    """A fused-group failure (here: a schema the NB fold rejects only at
    consume time is fine — use a bogus conf that only breaks run_shared's
    agreement checks) must fall back to the per-stage path."""
    from avenir_tpu.pipelines import profile_pipeline

    csv, schema = _churn(tmp_path, rows=400)
    props = {"bad.stream.block.size.mb": "0.005",
             # disagreeing block sizes make run_shared refuse the group;
             # the sequential fallback must still complete every stage
             "mut.stream.block.size.mb": "0.01",
             "fid.stream.block.size.mb": "0.005"}
    retries = []
    pipe = profile_pipeline(props, csv, str(tmp_path / "w"),
                            schema_path=schema)
    pipe.on_retry = lambda name, attempt, exc: retries.append(name)
    results = pipe.run(fuse=True)
    assert set(results) == {"bayesianDistr", "mutualInformation",
                            "fisherDiscriminant"}
    assert any("+" in name for name in retries)   # the fused attempt


def test_run_shared_rejects_bad_groups(tmp_path):
    csv, schema = _churn(tmp_path, rows=200)
    conf = {"bad.feature.schema.file.path": schema}
    with pytest.raises(ValueError, match="not shared-scan capable"):
        run_shared([("wordCounter", {}, str(tmp_path / "x"))], [csv])
    with pytest.raises(ValueError, match="mixed scan kinds"):
        run_shared([("bayesianDistr", conf, str(tmp_path / "a")),
                    ("frequentItemsApriori",
                     {"fia.support.threshold": "0.3"},
                     str(tmp_path / "b"))], [csv])
    with pytest.raises(ValueError, match="appears twice"):
        run_shared([("bayesianDistr", conf, str(tmp_path / "a")),
                    ("bayesianDistr", conf, str(tmp_path / "b"))], [csv])
    assert "bayesianDistr" in stream_fold_names()


# -------------------------------------------------------------- telemetry
def test_fused_outputs_byte_identical_under_tracing(tmp_path):
    """avenir-trace is observation-only: the fused scan with the span
    recorder capturing must produce byte-identical artifacts to the
    same scan with tracing disabled, and the capture must hold the
    per-chunk read/parse/fold span set for every sink of the churn
    trio."""
    from collections import Counter

    from avenir_tpu.obs import trace

    csv, schema = _churn(tmp_path, rows=600)
    # sidecar off: this test audits the COLD scan's per-chunk span set;
    # a warm replay is parse-free by design (test_sidecar proves that)
    conf = lambda p: {f"{p}.feature.schema.file.path": schema,  # noqa: E731
                      f"{p}.stream.block.size.mb": "0.005",
                      f"{p}.stream.sidecar": "false"}
    mi_conf = {**conf("mut"),
               "mut.mutual.info.score.algorithms": "mutual.info.maximization"}
    specs = lambda tag: [  # noqa: E731
        ("bayesianDistr", conf("bad"), str(tmp_path / f"nb_{tag}")),
        ("mutualInformation", mi_conf, str(tmp_path / f"mi_{tag}")),
        ("fisherDiscriminant", conf("fid"), str(tmp_path / f"fd_{tag}"))]
    prev = trace.set_enabled(False)
    try:
        untraced = run_shared(specs("off"), [csv])
    finally:
        trace.set_enabled(prev)
    with trace.capture() as rec:
        traced = run_shared(specs("on"), [csv])
    for name in untraced:
        assert _read_outputs(traced[name]) == _read_outputs(untraced[name])
    spans = rec.spans()
    chunks = next(int(sp.attrs["chunks"]) for sp in spans
                  if sp.name == "job.dispatch")
    assert chunks > 1, "corpus did not chunk — the per-chunk claim is vacuous"
    names = Counter(sp.name for sp in spans)
    assert names["stream.read"] >= chunks
    assert names["stream.parse"] >= chunks
    folds = Counter(sp.attrs["sink"] for sp in spans
                    if sp.name == "stream.fold")
    assert folds == {job: chunks for job in untraced}
    assert names["job.finish"] == 3
    # every chunk's fan-out also fed the process-global latency histogram
    h = trace.hist("chunk_latency_ms")
    assert h is not None and h.count >= chunks


# ------------------------------------------------------- failure isolation
def test_sink_failure_joins_prefetch_worker():
    """A sink raising mid-scan must not wedge or leak the prefetch
    worker: SharedScan closes the feed (cancel AND join) before the
    exception propagates — the PR-4 _Prefetcher join guarantee."""

    def source():
        for i in range(1000):
            yield i

    feed = prefetched(source(), depth=2)
    scan = SharedScan(feed)
    seen = []

    class Boom(Exception):
        pass

    def sink(chunk):
        seen.append(chunk)
        if len(seen) == 3:
            raise Boom()

    scan.add_sink(sink)
    with pytest.raises(Boom):
        scan.run()
    # close() ran: the worker thread is joined and discarded
    assert feed._thread is None
    assert len(seen) == 3


def test_sink_failure_closes_generator_feeds(tmp_path):
    """stream_job_inputs-style generator feeds delegate close() to their
    inner _Prefetcher via yield from — a failing sink must not leak the
    inner worker either."""
    import threading

    def blocks():
        for i in range(100):
            yield bytes([i]) * 10

    def gen():
        yield from prefetched(blocks(), depth=1)

    before = threading.active_count()
    scan = SharedScan(gen())
    scan.add_sink(lambda chunk: (_ for _ in ()).throw(RuntimeError("x")))
    with pytest.raises(RuntimeError):
        scan.run()
    # the inner worker exits; give the join its bounded wait
    deadline = 50
    while threading.active_count() > before and deadline:
        import time
        time.sleep(0.02)
        deadline -= 1
    assert threading.active_count() <= before


# ------------------------------------------------------------------ cache
def test_cache_cold_warm_and_source_invalidation(tmp_path):
    src_file = tmp_path / "corpus.csv"
    src_file.write_text("a,b,c\n" * 100)
    cache = EncodedBlockCache([str(src_file)], cache_dir=str(tmp_path / "c"),
                              byte_budget=1 << 20)
    # cold: nothing committed, replay refuses
    assert not cache.valid
    with pytest.raises(RuntimeError):
        list(cache.blocks())
    # build
    cache.begin()
    counts1 = np.array([2, 0, 3], np.int64)
    codes1 = np.array([0, 1, 2, 2, 1], np.int32)
    cache.add_block(counts1, codes1)
    cache.add_block(np.array([1], np.int64), np.array([300], np.int32))
    assert cache.commit()
    assert cache.valid and cache.n_blocks == 2
    # warm replay: exact round trip (incl. the uint16 code block)
    blocks = list(cache.blocks())
    assert cache.replays == 1
    np.testing.assert_array_equal(blocks[0][0], counts1)
    np.testing.assert_array_equal(blocks[0][1], codes1)
    np.testing.assert_array_equal(blocks[1][1], [300])
    assert blocks[1][1].dtype == np.int32
    # invalidation: the source grew — fingerprint mismatch
    with open(src_file, "a") as fh:
        fh.write("d,e,f\n")
    assert not cache.valid
    with pytest.raises(RuntimeError):
        list(cache.blocks())
    cache.close()


def test_cache_commit_detects_mid_scan_source_change(tmp_path):
    src_file = tmp_path / "corpus.csv"
    src_file.write_text("a,b\n" * 10)
    cache = EncodedBlockCache([str(src_file)], cache_dir=str(tmp_path / "c"),
                              byte_budget=1 << 20)
    cache.begin()
    cache.add_block(np.array([1], np.int64), np.array([0], np.int32))
    with open(src_file, "a") as fh:
        fh.write("z,z\n")               # source changed while scanning
    assert not cache.commit()
    assert not cache.valid


def test_miner_source_replays_warm_and_invalidates_on_change(tmp_path):
    from avenir_tpu.models.association import (FrequentItemsApriori,
                                               StreamingTransactionSource)

    csv = _seq(tmp_path, rows=400)
    # warm: cache-backed mining == cache-disabled mining, byte for byte
    src_c = StreamingTransactionSource([csv], skip_field_count=2,
                                       block_bytes=2048)
    src_n = StreamingTransactionSource([csv], skip_field_count=2,
                                       block_bytes=2048, spill_cache=False)
    miner = FrequentItemsApriori(0.3, 3)
    lv_c = miner.mine_stream(src_c)
    lv_n = miner.mine_stream(src_n)
    assert [(l.length, [(s.items, s.count) for s in l.item_sets])
            for l in lv_c] == \
           [(l.length, [(s.items, s.count) for s in l.item_sets])
            for l in lv_n]
    assert src_c.cache_replays >= 1
    assert src_n.cache_replays == 0
    assert 0 < src_c.cache_nbytes < os.path.getsize(csv)
    # invalidation: touch the CSV after pass 1 — the per-k pass must NOT
    # serve stale encoded blocks; it falls back to re-parsing the (new)
    # file, so the multi-hot chunks reflect the appended row
    src2 = StreamingTransactionSource([csv], skip_field_count=2,
                                      block_bytes=2048)
    src2.scan_items()
    assert src2._cache is not None and src2._cache.valid
    with open(csv, "a") as fh:
        fh.write("cX,T,L,L,L,L,L,L\n")
    assert not src2._cache.valid
    vm = src2.mask_items(range(len(src2.vocab)))
    rows_seen = sum(int(mh.any(axis=1).sum())
                    for mh in src2._dense_chunks(8192))
    assert rows_seen == 401      # the appended row IS seen (no stale cache)
    src_c.close()
    src2.close()


def test_miner_cache_appended_source_replays_prefix(tmp_path):
    """Per-block fingerprints: an append no longer invalidates the whole
    cached source — the committed blocks replay (prefix gate) and only
    the appended tail re-parses; the per-k counting still sees every
    current row. An mtime-only touch keeps even the full-coverage
    gate."""
    from avenir_tpu.models.association import StreamingTransactionSource

    csv = _seq(tmp_path, rows=400)
    src = StreamingTransactionSource([csv], skip_field_count=2,
                                     block_bytes=2048)
    src.scan_items()
    cache = src._cache
    assert cache is not None and cache.valid
    old_size = os.path.getsize(csv)
    # mtime churn without a content change: content fingerprints re-prove
    # the bytes, the cache stays fully valid
    os.utime(csv, (10 ** 9, 10 ** 9))
    assert cache.valid and cache.source_valid(0)
    # append: full-coverage gates drop, the prefix gate holds
    with open(csv, "a") as fh:
        fh.write("cX,T,L,L,L,L,L,L\n")
    assert not cache.valid and not cache.source_valid(0)
    assert cache.source_delta(0) == old_size
    replays_before = cache.replays
    src.mask_items(range(len(src.vocab)))
    rows_seen = sum(int(mh.any(axis=1).sum())
                    for mh in src._dense_chunks(8192))
    assert rows_seen == 401              # prefix replayed + tail parsed
    assert cache.replays > replays_before
    # in-place edit: the prefix gate drops too — full re-parse
    data = bytearray(open(csv, "rb").read())
    data[0] = ord("X")
    open(csv, "wb").write(bytes(data))
    assert cache.source_delta(0) is None
    src.close()


def test_cache_blocks_prefix_gate_contract(tmp_path):
    """blocks(i, prefix=True) serves an appended source and refuses an
    edited one; the fingerprint-free direct-write path (no note_block)
    never gains the prefix gate."""
    src_file = tmp_path / "corpus.csv"
    src_file.write_text("a,b,c\n" * 50)
    cache = EncodedBlockCache([str(src_file)],
                              cache_dir=str(tmp_path / "c"),
                              byte_budget=1 << 20)
    cache.begin()
    cache.set_source(0)
    data = src_file.read_bytes()
    cache.note_block(0, data)
    cache.add_block(np.array([3], np.int64), np.array([0, 1, 2], np.int32))
    assert cache.commit()
    with open(src_file, "a") as fh:
        fh.write("d,e,f\n")
    assert not cache.source_valid(0)
    assert cache.source_delta(0) == len(data)
    got = list(cache.blocks(0, prefix=True))
    assert len(got) == 1
    # without prefix=True the appended source still refuses
    with pytest.raises(RuntimeError):
        list(cache.blocks(0))
    # a writer that recorded no fingerprints has no prefix gate
    cache2 = EncodedBlockCache([str(src_file)],
                               cache_dir=str(tmp_path / "c2"),
                               byte_budget=1 << 20)
    cache2.begin()
    cache2.add_block(np.array([1], np.int64), np.array([0], np.int32))
    assert cache2.commit()
    with open(src_file, "a") as fh:
        fh.write("g,h,i\n")
    assert cache2.source_delta(0) is None
    cache.close()
    cache2.close()


def test_cache_prefix_gate_refuses_midline_coverage(tmp_path):
    """An appended source whose scanned bytes ended WITHOUT a trailing
    newline keeps full-coverage replay while unchanged, but has no
    prefix gate once it grows: the appended bytes extend the last
    encoded row, so splicing cached replay with a tail re-parse would
    split one line into two."""
    src_file = tmp_path / "corpus.csv"
    src_file.write_bytes(b"a,b,c\n" * 50 + b"x,y,z")   # no terminator
    cache = EncodedBlockCache([str(src_file)],
                              cache_dir=str(tmp_path / "c"),
                              byte_budget=1 << 20)
    cache.begin()
    cache.set_source(0)
    data = src_file.read_bytes()
    cache.note_block(0, data)
    cache.add_block(np.array([3], np.int64), np.array([0, 1, 2], np.int32))
    assert cache.commit()
    # unchanged: mid-line END of a fully-covered file is fine
    assert cache.source_valid(0)
    assert cache.source_delta(0) == len(data)
    with open(src_file, "ab") as fh:
        fh.write(b",w\nq,r,s\n")            # the last row grew a tail
    assert not cache.source_valid(0)
    assert cache.source_delta(0) is None    # full re-parse, no splice
    cache.close()


def test_gsp_source_replay_matches_reparse(tmp_path):
    from avenir_tpu.models.sequence import GSPMiner, StreamingSequenceSource

    csv = _seq(tmp_path, rows=400)
    m = GSPMiner(0.3, 3)
    s1 = StreamingSequenceSource([csv], skip_field_count=2,
                                 block_bytes=2048)
    s2 = StreamingSequenceSource([csv], skip_field_count=2,
                                 block_bytes=2048, spill_cache=False)
    assert m.mine_stream(s1) == m.mine_stream(s2)
    assert s1.cache_replays >= 1 and s2.cache_replays == 0
    s1.close()
    # appended source: the prefix replays from the cache, the tail
    # re-parses, and the padded chunks match a cache-less source's
    s3 = StreamingSequenceSource([csv], skip_field_count=2,
                                 block_bytes=2048)
    s3.scan()
    old = os.path.getsize(csv)
    with open(csv, "a") as fh:
        fh.write("cX,T,L,M,H,L,M,H\n")
    assert s3._cache.source_delta(0) == old
    s4 = StreamingSequenceSource([csv], skip_field_count=2,
                                 block_bytes=2048, spill_cache=False)
    s4.scan()
    s3.mask_tokens(range(len(s3.vocab)))
    s4.mask_tokens(range(len(s4.vocab)))
    a = [blk for blk in s3.chunks(1024)]
    b = [blk for blk in s4.chunks(1024)]
    assert sum(int((blk >= 0).any(axis=1).sum()) for blk in a) \
        == sum(int((blk >= 0).any(axis=1).sum()) for blk in b) == 401
    assert s3.cache_replays >= 1
    s3.close()
    s4.close()


# ------------------------------------------------------ auditor coverage
def test_fused_entries_registered_in_manifest():
    from avenir_tpu.analysis.manifest import stream_kernel_names

    names = stream_kernel_names()
    assert "shared_churn_stream" in names
    assert "shared_seq_stream" in names
    assert len(names) >= 8
