"""The columnar sidecar's contracts (perf PR: parse-free repeat scans).

1. Equivalence — every registered fold family produces BYTE-IDENTICAL
   artifacts three ways: sidecar disabled (cold), sidecar packing its
   first pass, and sidecar replaying a warm pass — across the Dataset
   feed, the raw-byte feed, and the miners' own-read discovery scans.
2. Parse-free — the warm pass records ZERO `stream.parse` spans and
   >= 1 `stream.sidecar.replay` span: the repeat scan never touches
   the CSV text.
3. Never serve a wrong block — a torn columns.bin write (manifest is
   committed LAST, so a crash leaves a stale or absent manifest), an
   in-place content edit, or a schema/config change all re-prove
   against the file and fall back to parsing from the first divergent
   block; outputs stay byte-identical to a cold scan of the CURRENT
   bytes.
4. Append — only the tail past the verified prefix is parsed; the
   prefix replays.
5. Bounded cache — a tiny byte budget (writer-side abort, or a
   WarmStore eviction rmtree-ing the directory) only ever costs speed,
   never correctness.
"""

import glob
import os

import numpy as np
import pytest

from avenir_tpu.native import sidecar
from avenir_tpu.runner import run_job


# ---------------------------------------------------------------- fixtures
def _churn(tmp_path, rows=1500):
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


def _conf(prefix, tmp_path, schema=None, block="0.01", **extra):
    c = {f"{prefix}.stream.block.size.mb": block,
         f"{prefix}.stream.sidecar.dir": str(tmp_path / "sc")}
    if schema is not None:
        c[f"{prefix}.feature.schema.file.path"] = schema
    c.update({f"{prefix}.{k}": v for k, v in extra.items()})
    return c


def _mi_conf(tmp_path, schema, **kw):
    return _conf("mut", tmp_path, schema,
                 **{"mutual.info.score.algorithms":
                    "mutual.info.maximization", **kw})


def _mst_conf(tmp_path, **kw):
    return _conf("mst", tmp_path, **{
        "model.states": "L,M,H", "class.label.field.ord": "1",
        "skip.field.count": "2", "class.labels": "T,F", **kw})


def _bytes_of(res):
    blobs = []
    for p in sorted(res.outputs):
        with open(p, "rb") as fh:
            blobs.append(fh.read())
    return b"\n".join(blobs)


def _sc(res, key):
    return res.counters.get(f"Sidecar:{key}", 0.0)


def _manifest_dirs(tmp_path):
    return sorted(os.path.dirname(p) for p in glob.glob(
        str(tmp_path / "sc" / "*" / sidecar.MANIFEST)))


# ------------------------------------------------- 1. equivalence, all six
_FAMILIES = [
    ("bayesianDistr", "bad", "churn", {}),
    ("mutualInformation", "mut", "churn",
     {"mutual.info.score.algorithms": "mutual.info.maximization"}),
    ("fisherDiscriminant", "fid", "churn", {}),
    ("markovStateTransitionModel", "mst", "seq",
     {"model.states": "L,M,H", "class.label.field.ord": "1",
      "skip.field.count": "2", "class.labels": "T,F"}),
    ("frequentItemsApriori", "fia", "seq",
     {"support.threshold": "0.3", "item.set.length": "2",
      "skip.field.count": "2"}),
    ("candidateGenerationWithSelfJoin", "cgs", "seq",
     {"support.threshold": "0.3", "item.set.length": "2",
      "skip.field.count": "2"}),
]


@pytest.mark.parametrize("job,prefix,corpus,extra",
                         _FAMILIES, ids=[f[0] for f in _FAMILIES])
def test_cold_pack_warm_byte_identical(tmp_path, job, prefix, corpus, extra):
    """Disabled vs packing vs replaying: one artifact, three scans."""
    churn_csv, schema = _churn(tmp_path)
    csv = churn_csv if corpus == "churn" else _seq(tmp_path)
    conf = _conf(prefix, tmp_path,
                 schema=schema if corpus == "churn" else None, **extra)
    cold = run_job(job, {**conf, f"{prefix}.stream.sidecar": "false"},
                   [csv], str(tmp_path / "out_cold"))
    pack = run_job(job, conf, [csv], str(tmp_path / "out_pack"))
    warm = run_job(job, conf, [csv], str(tmp_path / "out_warm"))
    assert _bytes_of(pack) == _bytes_of(cold)
    assert _bytes_of(warm) == _bytes_of(cold)
    assert _sc(cold, "DeltaBlocks") == 0 and _sc(cold, "HitBlocks") == 0
    assert _sc(pack, "DeltaBlocks") >= 1, pack.counters
    assert _sc(warm, "HitBlocks") == _sc(pack, "DeltaBlocks")
    assert _sc(warm, "DeltaBlocks") == 0, warm.counters


@pytest.mark.parametrize("family", ["dataset", "bytes"])
def test_warm_replay_is_parse_free(tmp_path, family):
    """The acceptance bar stated literally: zero `stream.parse` spans on
    the happy replay path, asserted from a trace capture."""
    from avenir_tpu.obs import trace

    if family == "dataset":
        csv, schema = _churn(tmp_path)
        job, conf = "mutualInformation", _mi_conf(tmp_path, schema)
    else:
        csv = _seq(tmp_path)
        job, conf = "markovStateTransitionModel", _mst_conf(tmp_path)
    run_job(job, conf, [csv], str(tmp_path / "out_pack"))
    with trace.capture() as rec:
        warm = run_job(job, conf, [csv], str(tmp_path / "out_warm"))
    spans = rec.spans()
    parse = [s for s in spans if s.name == "stream.parse"]
    replay = [s for s in spans if s.name == "stream.sidecar.replay"]
    assert not parse, f"warm replay parsed {len(parse)} block(s)"
    assert len(replay) == _sc(warm, "HitBlocks") >= 1


_TRIO = [("bayesianDistr", "bad"), ("mutualInformation", "mut"),
         ("fisherDiscriminant", "fid")]


@pytest.mark.parametrize("surface",
                         ["fused", "sharded", "incremental", "served"])
def test_packed_corpus_replays_parse_free_on_every_surface(tmp_path,
                                                           surface):
    """One fused pass of the churn trio packs the sidecar; every other
    way of scanning the same corpus then replays it without parsing a
    block, to the cold scan's bytes: the fused scan again, two sharded
    workers, the incremental driver's cold seed, and a job-server batch,
    which also pins the sidecar and leaves it on disk at shutdown."""
    from avenir_tpu.obs import trace
    from avenir_tpu.runner import run_incremental, run_shared

    csv, schema = _churn(tmp_path, rows=3000)
    # some twelve blocks: the sharded planner snaps its cuts onto the
    # sidecar's verified offsets only when there are procs * factor of them
    block = f"{os.path.getsize(csv) / (1 << 20) / 12:.4f}"

    def conf(prefix, **extra):
        if prefix == "mut":
            return _mi_conf(tmp_path, schema, block=block, **extra)
        return _conf(prefix, tmp_path, schema, block=block, **extra)

    def specs(tag, **extra):
        return [(job, conf(p, **extra), str(tmp_path / f"{tag}_{p}"))
                for job, p in _TRIO]

    def parse_free(rec):
        names = [s.name for s in rec.spans()]
        return ("stream.parse" not in names
                and "stream.sidecar.replay" in names)

    pack = run_shared(specs("pack"), [csv])
    cold = run_shared(specs("cold", **{"stream.sidecar": "false"}), [csv])
    for job, _p in _TRIO:
        assert _bytes_of(pack[job]) == _bytes_of(cold[job]), job
        assert _sc(pack[job], "DeltaBlocks") >= 8, pack[job].counters
    mi_cold = _bytes_of(cold["mutualInformation"])

    if surface == "fused":
        with trace.capture() as rec:
            warm = run_shared(specs("warm"), [csv])
        for job, _p in _TRIO:
            assert _bytes_of(warm[job]) == _bytes_of(cold[job]), job
            assert _sc(warm[job], "HitBlocks") >= 1, warm[job].counters
            assert _sc(warm[job], "DeltaBlocks") == 0, warm[job].counters
        assert parse_free(rec)
    elif surface == "sharded":
        from avenir_tpu.dist import run_sharded

        # the workers' own captures come home through the stats files
        shard = run_sharded("mutualInformation", conf("mut"), [csv],
                            str(tmp_path / "shard_out.txt"), procs=2)
        assert _bytes_of(shard) == mi_cold
        assert shard.counters["Shard:ParseSpans"] == 0, shard.counters
        assert shard.counters["Shard:ReplaySpans"] >= 1, shard.counters
        assert _sc(shard, "HitBlocks") >= 1, shard.counters
    elif surface == "incremental":
        with trace.capture() as rec:
            seed = run_incremental(
                "mutualInformation", conf("mut"), [csv],
                str(tmp_path / "incr_out.txt"),
                state_dir=str(tmp_path / "incr_state"))
        assert _bytes_of(seed) == mi_cold
        assert parse_free(rec)
        assert _sc(seed, "HitBlocks") >= 1, seed.counters
    else:
        from avenir_tpu.server import JobRequest, JobServer

        with trace.capture() as rec:
            with JobServer(workers=1,
                           state_root=str(tmp_path / "srv_state")) as srv:
                tickets = [srv.submit(JobRequest(
                    job, conf(p), [csv], str(tmp_path / f"srv_{p}")))
                    for job, p in _TRIO]
                served = {job: t.result(timeout=300)
                          for (job, _p), t in zip(_TRIO, tickets)}
                pinned = srv.warm.stats()["pinned_sources"]
        for job, _p in _TRIO:
            assert _bytes_of(served[job]) == _bytes_of(cold[job]), job
            assert _sc(served[job], "HitBlocks") >= 1, served[job].counters
        assert parse_free(rec)
        assert pinned >= 1
        # shutdown drops the pins, not the cache on disk
        assert sum(sidecar.sidecar_nbytes(d)
                   for d in _manifest_dirs(tmp_path)) > 0


# --------------------------------------------- 3. torn writes and drift
def test_torn_write_never_commits(tmp_path):
    """The manifest is written LAST: a truncated segment (crash between
    the columns.bin append and the manifest rename — here the inverse,
    a manifest surviving a lost segment tail), a leftover staging tmp,
    and a manifest-less garbage dir must all re-prove, re-parse, and
    reproduce the cold artifact — never replay a torn block."""
    csv, schema = _churn(tmp_path)
    conf = _mi_conf(tmp_path, schema)
    cold = run_job("mutualInformation",
                   {**conf, "mut.stream.sidecar": "false"},
                   [csv], str(tmp_path / "out_cold"))
    run_job("mutualInformation", conf, [csv], str(tmp_path / "out_pack"))
    (scdir,) = _manifest_dirs(tmp_path)
    seg = os.path.join(scdir, sidecar.SEGMENT)
    # a) segment torn mid-block: manifest entries now point past EOF
    with open(seg, "rb+") as fh:
        fh.truncate(max(os.path.getsize(seg) // 2, 1))
    torn = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "out_torn"))
    assert _bytes_of(torn) == _bytes_of(cold)
    # the repack healed the sidecar; b) a leftover writer staging file
    # (the crash-BEFORE-rename artifact) must not disturb a full replay
    with open(os.path.join(scdir, sidecar.SEGMENT + ".tmp.99999"),
              "wb") as fh:
        fh.write(b"\x00garbage")
    warm = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "out_tmpfile"))
    assert _bytes_of(warm) == _bytes_of(cold)
    assert _sc(warm, "HitBlocks") >= 1 and _sc(warm, "DeltaBlocks") == 0
    # c) no manifest at all (crash before the FIRST commit): garbage
    # segment alone is never trusted
    os.remove(os.path.join(scdir, sidecar.MANIFEST))
    with open(seg, "wb") as fh:
        fh.write(b"\x00" * 64)
    fresh = run_job("mutualInformation", conf, [csv],
                    str(tmp_path / "out_nomanifest"))
    assert _bytes_of(fresh) == _bytes_of(cold)
    assert _sc(fresh, "HitBlocks") == 0 and _sc(fresh, "DeltaBlocks") >= 1


def test_content_drift_invalidates_from_edit_point(tmp_path):
    """An in-place edit mid-file: blocks before the edit still replay
    (content re-proof passes), the edited block and everything after
    re-parse; the artifact tracks the CURRENT bytes."""
    csv, schema = _churn(tmp_path)
    conf = _mi_conf(tmp_path, schema)
    pack = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "out_pack"))
    n_blocks = _sc(pack, "DeltaBlocks")
    assert n_blocks >= 3, "need a multi-block corpus for this test"
    blob = bytearray(open(csv, "rb").read())
    # flip one digit ~60% in (same length: offsets, and therefore every
    # block boundary, stay put — only content hashes diverge)
    at = blob.index(b"1", int(len(blob) * 0.6))
    blob[at:at + 1] = b"7"
    with open(csv, "wb") as fh:
        fh.write(bytes(blob))
    cold = run_job("mutualInformation",
                   {**conf, "mut.stream.sidecar": "false"},
                   [csv], str(tmp_path / "out_cold_edited"))
    warm = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "out_warm_edited"))
    assert _bytes_of(warm) == _bytes_of(cold)
    assert 1 <= _sc(warm, "HitBlocks") < n_blocks
    assert _sc(warm, "DeltaBlocks") >= 1
    assert _sc(warm, "HitBlocks") + _sc(warm, "DeltaBlocks") == n_blocks


def test_schema_and_config_drift_select_fresh_sidecars(tmp_path):
    """Schema content, delimiter, block size and (for byte feeds) the
    skip count are all baked into the directory digest: drifting any of
    them can NEVER alias onto a stale cache. Discovery side effects are
    normalized OUT, so the same schema re-loaded (or mutated by a scan)
    keeps hitting its own sidecar."""
    from avenir_tpu.core.schema import FeatureSchema

    csv, schema = _churn(tmp_path)
    opts = {"dir": str(tmp_path / "sc"), "budget": 1 << 30}
    sch = FeatureSchema.from_file(schema)
    base = sidecar.dataset_dir(opts, csv, sch, ",", 1 << 16)
    # discovery normalization: a reload maps to the SAME directory
    assert sidecar.dataset_dir(
        opts, csv, FeatureSchema.from_file(schema), ",", 1 << 16) == base
    variants = {
        "block": sidecar.dataset_dir(opts, csv, sch, ",", 1 << 17),
        "delim": sidecar.dataset_dir(opts, csv, sch, ";", 1 << 16),
        "kind": sidecar.bytes_dir(opts, csv, ",", 2, 1 << 16),
        "skip": sidecar.bytes_dir(opts, csv, ",", 3, 1 << 16),
    }
    sch2 = FeatureSchema.from_file(schema)
    list(sch2)[0].name = "renamed"
    variants["schema"] = sidecar.dataset_dir(opts, csv, sch2, ",", 1 << 16)
    dirs = [base] + list(variants.values())
    assert len(set(dirs)) == len(dirs), variants
    # and a manifest written at one block size refuses to serve another
    run_job("mutualInformation",
            _mi_conf(tmp_path, schema), [csv], str(tmp_path / "o"))
    (scdir,) = _manifest_dirs(tmp_path)
    packed_block = int(0.01 * (1 << 20))      # _mi_conf's 0.01MB blocks
    assert sidecar.verified_offsets(scdir, csv, packed_block)
    assert sidecar.verified_offsets(scdir, csv, packed_block * 2) == []


def test_multi_input_warm_scan_disjoint_vocabularies(tmp_path):
    """Each input has its OWN sidecar with an independent first-seen
    vocabulary: the miners' vocab-merge watermark must restart at every
    source. A watermark carried over from input 1 made input 2's replay
    skip its unseen tokens and crash the LUT build (KeyError) — the
    'sidecar makes a scan faster, never wrong' regression."""
    def write(path, toks):
        with open(path, "w") as fh:
            for i in range(300):
                row = [toks[(i + j) % len(toks)] for j in range(4)]
                fh.write(f"c{i},T," + ",".join(row) + "\n")

    a, b = str(tmp_path / "a.csv"), str(tmp_path / "b.csv")
    write(a, ["aa", "ab", "ac"])
    write(b, ["ba", "bb", "bc"])          # fully disjoint from a's
    conf = _conf("fia", tmp_path, **{"support.threshold": "0.2",
                                     "item.set.length": "2",
                                     "skip.field.count": "2"})
    cold = run_job("frequentItemsApriori",
                   {**conf, "fia.stream.sidecar": "false"},
                   [a, b], str(tmp_path / "out_cold"))
    run_job("frequentItemsApriori", conf, [a, b],
            str(tmp_path / "out_pack"))
    warm = run_job("frequentItemsApriori", conf, [a, b],
                   str(tmp_path / "out_warm"))
    assert _bytes_of(warm) == _bytes_of(cold)
    assert _sc(warm, "HitBlocks") >= 2      # >= 1 per input
    assert _sc(warm, "DeltaBlocks") == 0


# ----------------------------------------------------------- 4. append
def test_append_replays_prefix_parses_tail(tmp_path):
    """After an append, the committed prefix replays and ONLY the tail
    is parsed: parse spans == delta blocks, replay spans == hit blocks,
    and the hit/delta split covers the new block count exactly."""
    from avenir_tpu.data import generate_churn
    from avenir_tpu.obs import trace

    csv, schema = _churn(tmp_path, rows=2000)
    conf = _mi_conf(tmp_path, schema)
    pack = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "out_pack"))
    n0 = _sc(pack, "DeltaBlocks")
    assert n0 >= 3
    with open(csv, "a") as fh:
        fh.write(generate_churn(200, seed=13, as_csv=True))
    cold = run_job("mutualInformation",
                   {**conf, "mut.stream.sidecar": "false"},
                   [csv], str(tmp_path / "out_cold_app"))
    with trace.capture() as rec:
        warm = run_job("mutualInformation", conf, [csv],
                       str(tmp_path / "out_warm_app"))
    assert _bytes_of(warm) == _bytes_of(cold)
    hits, delta = _sc(warm, "HitBlocks"), _sc(warm, "DeltaBlocks")
    # the old final block was partial: the append grew it, so it (plus
    # the genuinely new blocks) parses; every full old block replays
    assert hits >= n0 - 1 >= 1 and delta >= 1
    spans = rec.spans()
    assert len([s for s in spans if s.name == "stream.parse"]) == delta
    assert len([s for s in spans
                if s.name == "stream.sidecar.replay"]) == hits
    # and the healed sidecar now covers the whole appended file
    again = run_job("mutualInformation", conf, [csv],
                    str(tmp_path / "out_again"))
    assert _bytes_of(again) == _bytes_of(cold)
    assert _sc(again, "HitBlocks") == hits + delta
    assert _sc(again, "DeltaBlocks") == 0


# ----------------------------------------------------- 5. bounded cache
def test_tiny_budget_never_costs_correctness(tmp_path):
    """A budget smaller than one packed block: the writer aborts rather
    than commit a partial lie, every run stays cold — and byte-identical."""
    csv, schema = _churn(tmp_path)
    conf = _mi_conf(tmp_path, schema,
                    **{"stream.sidecar.budget.mb": "0.001"})
    cold = run_job("mutualInformation",
                   {**conf, "mut.stream.sidecar": "false"},
                   [csv], str(tmp_path / "out_cold"))
    first = run_job("mutualInformation", conf, [csv],
                    str(tmp_path / "out_first"))
    second = run_job("mutualInformation", conf, [csv],
                     str(tmp_path / "out_second"))
    assert _bytes_of(first) == _bytes_of(cold)
    assert _bytes_of(second) == _bytes_of(cold)
    assert _sc(second, "HitBlocks") == 0       # nothing fit: no replay
    for scdir in _manifest_dirs(tmp_path):
        assert sidecar.sidecar_nbytes(scdir) <= 1024


def test_warmstore_eviction_keeps_byte_identity(tmp_path):
    """The server-side landlord: evicting a pinned SidecarHandle rmtrees
    the directory; the next scan repacks cold and reproduces the same
    bytes. A zero-budget store must never hold (or half-delete) a dir."""
    from avenir_tpu.server.jobserver import WarmStore

    csv, schema = _churn(tmp_path)
    conf = _mi_conf(tmp_path, schema)
    cold = run_job("mutualInformation",
                   {**conf, "mut.stream.sidecar": "false"},
                   [csv], str(tmp_path / "out_cold"))
    run_job("mutualInformation", conf, [csv], str(tmp_path / "out_pack"))
    (scdir,) = _manifest_dirs(tmp_path)
    handle = sidecar.SidecarHandle(csv, scdir)
    assert handle.cache_ready() and handle.cache_nbytes > 0
    store = WarmStore(byte_budget=1)          # tinier than any sidecar
    store.pin(("sidecar", csv, os.path.basename(scdir)), handle)
    assert store.stats()["pinned_sources"] == 0
    assert not os.path.exists(scdir), "eviction must rmtree the sidecar"
    repack = run_job("mutualInformation", conf, [csv],
                     str(tmp_path / "out_repack"))
    assert _bytes_of(repack) == _bytes_of(cold)
    assert _sc(repack, "HitBlocks") == 0 and _sc(repack, "DeltaBlocks") >= 1
    store.close()


# ------------------------------- 7. a categorical of discovered vocabulary
#: what a sidecar on disk holds for the block below, written out by hand:
#: the float32 page of `x`, then the `t` column, the native parser's
#: newline-joined trimmed tokens with a short row as the empty token
_DISCOVERED_BLOCK = b"1,pass\n2, fail \n3\n4,pass\r\n5,alpha\n"
_DISCOVERED_TOKENS = b"pass\nfail\n\npass\nalpha\n"
_DISCOVERED_ENTRY = {"rows": 5, "cols": [[0, "f", 0, 20], [1, "t", 0, 22]]}


def _status_schema():
    from avenir_tpu.core.schema import FeatureSchema

    return FeatureSchema.from_json({"fields": [
        {"name": "x", "ordinal": 0, "dataType": "double", "feature": True},
        {"name": "status", "ordinal": 1, "dataType": "categorical"}]})


@pytest.mark.parametrize("segment", ["packed_now", "as_on_disk_today"])
def test_discovered_categorical_packs_tokens_and_replays_codes(segment):
    """The parser encodes a discovered categorical itself now; the sidecar
    keeps its format all the same (kind `t`, the raw column bytes), and a
    replay against a fresh schema discovers the vocabulary the cold parse
    did and gives its codes."""
    import io

    from avenir_tpu.core.dataset import Dataset

    cold_schema = _status_schema()
    cold = Dataset.from_csv(_DISCOVERED_BLOCK, cold_schema, engine="native")
    x_page = np.arange(1, 6, dtype=np.float32).tobytes()
    if segment == "packed_now":
        fh = io.BytesIO()
        cols = sidecar._pack_dataset_block(_DISCOVERED_BLOCK, cold,
                                           cold_schema, ",", fh)
        assert {"rows": len(cold), "cols": cols} == _DISCOVERED_ENTRY
        buf = fh.getvalue()
        assert buf == x_page + _DISCOVERED_TOKENS
    else:
        buf = x_page + _DISCOVERED_TOKENS
    warm_schema = _status_schema()
    warm = sidecar._unpack_dataset_block(buf, _DISCOVERED_ENTRY,
                                         warm_schema, ",")
    status = warm_schema.field_by_name("status")
    assert status.cardinality == ["", "alpha", "fail", "pass"] \
        == cold_schema.field_by_name("status").cardinality
    assert status.discovered_cardinality
    assert warm.column(1).dtype == np.int32
    np.testing.assert_array_equal(warm.column(1), [3, 2, 0, 3, 1])
    np.testing.assert_array_equal(warm.column(1), cold.column(1))
    np.testing.assert_array_equal(warm.column(0), cold.column(0))
