"""graftlint-mem: tier-1 gate + per-rule fixture corpus + footprint audit.

Three jobs, mirroring the other analyzer test modules one layer over:
1. Gate — the gated repo surface lints clean under the mem rules and
   every streamed job in the manifest reports footprint_model_validated
   at >= 2 block sizes (the tier's acceptance invariant, held here).
2. Corpus — every mem rule has a bad fixture that MUST fire and a good
   twin that MUST stay silent.
3. Contract — the footprint auditor catches a wrong model (finding under
   mem-footprint-model), job run failures surface as MemAuditError (CLI
   exit 2), the band holds under the PR-4 adversarial chunk layouts, mem
   findings round-trip through the shared baseline, and the --mem CLI
   speaks the same JSON schema as the other modes. Plus the satellite
   surfaces: EncodedBlockCache's byte budget/eviction and the
   Mem:*/Cache:* JobResult counters.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from avenir_tpu.analysis import load_baseline
from avenir_tpu.analysis.engine import BaselineEntry, run_paths
from avenir_tpu.analysis.manifest import StreamKernelSpec, stream_entries
from avenir_tpu.analysis.mem import (ALL_MEM_RULES, AUDIT_SLACK_BYTES,
                                     AUDIT_TIGHTNESS, MEM_AUDIT_RULE,
                                     CacheSpillUnbudgetedRule,
                                     CorpusScaledTemporaryRule,
                                     DtypeExpansionAtParseRule,
                                     MemAuditError, UnboundedCarryRule,
                                     audit_footprint, combined_footprint,
                                     corpus_stats, footprint_model,
                                     mem_rule_ids, memory_manifest, run_mem)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- gate
def test_mem_gate_clean_and_all_stream_jobs_within_band():
    report = run_mem(baseline=load_baseline(), root=REPO)
    assert not report.errors, [f.render() for f in report.errors]
    assert not report.findings, "\n" + "\n".join(
        f.render() for f in report.findings)
    assert not report.stale, [e.key for e in report.stale]
    audit = report.footprint_audit
    assert len(audit) == len(stream_entries()) >= 8
    bad = [a["kernel"] for a in audit if not a["footprint_model_validated"]]
    assert not bad, (bad, audit)
    for row in audit:
        assert len(row["block_sizes_mb"]) >= 2
        assert row["jobs"], row["kernel"]
        for run in row["runs"]:
            # model and measurement both recorded, band + the raw-block
            # accounting cross-check both held
            assert run["predicted_bytes"] > 0
            assert run["within_band"] and run["block_accounting_ok"], row
            assert run["observed_max_block_bytes"] > 0, (
                "no raw block flowed through the byte-accounting hook "
                "— the audit did not exercise the streaming path", row)


def test_every_stream_entry_names_modeled_jobs():
    from avenir_tpu.analysis.mem import _JOB_MODELS
    from avenir_tpu.runner import stream_fold_names

    # every stream entry names runner jobs, every named job has a model,
    # and every shared-scan-fusable job is modeled — the admission oracle
    # covers the whole streamed surface by construction
    for spec in stream_entries():
        assert spec.jobs, spec.name
        for job in spec.jobs:
            assert job in _JOB_MODELS, (spec.name, job)
    assert set(stream_fold_names()) <= set(_JOB_MODELS)


# ------------------------------------------------- fixture corpus helpers
def _lint(tmp_path, source, rule_cls, name="snippet.py"):
    p = tmp_path / name
    p.write_text(source)
    report = run_paths([str(p)], rules=[rule_cls()], baseline=[],
                       root=str(tmp_path))
    assert not report.errors, [f.render() for f in report.errors]
    return report.findings


_CARRY_BAD = """
from avenir_tpu.core.stream import prefetched

def fold(chunks, out):
    rows = []
    index = {}
    for blk in prefetched(chunks):
        rows.extend(blk)               # grows with rows seen: fires
        index[len(index)] = blk        # keyed growth: fires
    return rows, index
"""

_CARRY_GOOD = """
from avenir_tpu.core.stream import prefetched

def fold(chunks, out_fh):
    total = 0
    buf = []
    for blk in prefetched(chunks):
        total += len(blk)              # scalar statistic: silent
        buf.extend(blk)
        while len(buf) >= 10:          # drained in the loop: bounded
            out_fh.write(str(buf[:10]))
            buf = buf[10:]
        per_chunk = []                 # init inside the loop: resets
        per_chunk.append(len(blk))
        out_fh.write(str(per_chunk))
    return total
"""


def test_unbounded_carry_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _CARRY_BAD, UnboundedCarryRule)
    assert {f.rule for f in findings} == {"mem-unbounded-carry"}
    assert len(findings) == 2, [f.render() for f in findings]
    assert {f.scope for f in findings} == {"fold"}


def test_unbounded_carry_silent_on_good(tmp_path):
    assert _lint(tmp_path, _CARRY_GOOD, UnboundedCarryRule) == []


_TEMP_BAD = """
import numpy as np
from avenir_tpu.core.stream import double_buffered

def fold(chunks):
    parts = []
    for blk in double_buffered(chunks):
        parts.append(blk.sum(axis=0))
    return np.concatenate(parts)       # whole stream in one array: fires
"""

_TEMP_GOOD = """
import numpy as np
from avenir_tpu.core.stream import double_buffered

def fold(chunks):
    acc = np.zeros(4, np.int64)
    for blk in double_buffered(chunks):
        acc += blk.sum(axis=0)         # fixed-size fold: silent
    return np.concatenate([acc, acc])  # O(model) arg, not a grown list
"""


def test_corpus_scaled_temporary_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _TEMP_BAD, CorpusScaledTemporaryRule)
    assert {f.rule for f in findings} == {"mem-corpus-scaled-temporary"}
    assert len(findings) == 1, [f.render() for f in findings]


def test_corpus_scaled_temporary_silent_on_good(tmp_path):
    assert _lint(tmp_path, _TEMP_GOOD, CorpusScaledTemporaryRule) == []


_CACHE_BAD = """
from avenir_tpu.native.ingest import EncodedBlockCache

def build(paths):
    return EncodedBlockCache(paths)    # unbudgeted spill: fires
"""

_CACHE_GOOD = """
from avenir_tpu.native.ingest import DEFAULT_CACHE_BUDGET_BYTES, EncodedBlockCache

def build(paths, budget=None):
    return EncodedBlockCache(
        paths, byte_budget=budget or DEFAULT_CACHE_BUDGET_BYTES)
"""


def test_cache_spill_unbudgeted_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _CACHE_BAD, CacheSpillUnbudgetedRule)
    assert {f.rule for f in findings} == {"mem-cache-spill-unbudgeted"}
    assert len(findings) == 1


def test_cache_spill_unbudgeted_silent_on_good(tmp_path):
    assert _lint(tmp_path, _CACHE_GOOD, CacheSpillUnbudgetedRule) == []


_DTYPE_BAD = """
import numpy as np

def fold(blocks):
    out = 0.0
    for blk in blocks:
        wide = blk.astype(np.float64)          # widening in a loop: fires
        keys = np.asarray(blk, dtype=np.int64)  # 8-byte wrap: fires
        out += wide.sum() + keys.sum()
    return out
"""

_DTYPE_GOOD = """
import numpy as np

def fold(blocks):
    acc = np.zeros(8, np.int64)        # fresh 64-bit ALLOCATION: silent
    for blk in blocks:
        codes = blk.astype(np.int32)   # narrow conversion: silent
        acc += np.bincount(codes.ravel(), minlength=8)
    total = acc.astype(np.float64)     # outside the loop: silent
    return total
"""


def test_dtype_expansion_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _DTYPE_BAD, DtypeExpansionAtParseRule)
    assert {f.rule for f in findings} == {"mem-dtype-expansion-at-parse"}
    assert len(findings) == 2, [f.render() for f in findings]


def test_dtype_expansion_silent_on_good(tmp_path):
    assert _lint(tmp_path, _DTYPE_GOOD, DtypeExpansionAtParseRule) == []


def test_every_mem_rule_has_corpus_coverage():
    covered = {"mem-unbounded-carry", "mem-corpus-scaled-temporary",
               "mem-cache-spill-unbudgeted", "mem-dtype-expansion-at-parse"}
    assert {r.rule_id for r in ALL_MEM_RULES} == covered
    assert set(mem_rule_ids()) == covered | {MEM_AUDIT_RULE}


# ------------------------------------------------------- footprint model
def test_footprint_model_caps_block_at_corpus(tmp_path):
    csv = tmp_path / "tiny.csv"
    csv.write_text("a,b,c\n" * 100)
    stats = corpus_stats([str(csv)])
    small = footprint_model("bayesianDistr", 1 << 10, stats=stats)
    huge = footprint_model("bayesianDistr", 1 << 30, stats=stats)
    # a 1GB nominal block over a 600B corpus prices 600B of blocks plus
    # the O(model) constants — not 1GB
    assert huge.total_bytes < 2 << 20
    assert small.total_bytes <= huge.total_bytes


def test_combined_footprint_counts_ingest_once():
    jobs = ["bayesianDistr", "mutualInformation", "fisherDiscriminant"]
    fused = combined_footprint(jobs, 64 << 20)
    solo_sum = sum(footprint_model(j, 64 << 20).total_bytes for j in jobs)
    solo_max = max(footprint_model(j, 64 << 20).total_bytes for j in jobs)
    # one shared scan: cheaper than N scans, at least as big as any one
    assert fused.total_bytes < solo_sum
    assert fused.total_bytes >= solo_max


def test_footprint_model_rejects_unmodeled_jobs():
    with pytest.raises(ValueError, match="no footprint model"):
        footprint_model("definitelyNotAJob", 1 << 20)


def test_memory_manifest_shape():
    man = memory_manifest(block_sizes_mb=(8.0,), include_kernels=False)
    assert man["version"] == 1
    assert man["tolerance"]["slack_bytes"] == AUDIT_SLACK_BYTES
    assert man["tolerance"]["tightness"] == AUDIT_TIGHTNESS
    from avenir_tpu.runner import stream_fold_names
    assert set(stream_fold_names()) <= set(man["jobs"])
    for job, per_block in man["jobs"].items():
        est = per_block["8MB"]
        assert est["predicted_peak_bytes"] > 0 and est["terms"], job


def test_kernel_device_entries_walk():
    from avenir_tpu.analysis.manifest import manifest_entries
    from avenir_tpu.analysis.mem import kernel_device_entries

    specs = [s for s in manifest_entries() if not s.is_family][:2]
    rows = kernel_device_entries(entries=specs)
    assert len(rows) == 2
    for row in rows:
        assert row["peak_live_bytes"] >= row["argument_bytes"] > 0
        assert row["source"] in ("hlo_buffer_assignment", "jaxpr")


# ------------------------------------------------------ footprint auditor
def _toy_spec(run, name="toy_mem_kernel", prepare=None):
    def _prepare(workdir):
        csv = os.path.join(workdir, "toy.csv")
        with open(csv, "w") as fh:
            fh.write("r,a,b\n" * 200)
        return {"dir": workdir, "csv": csv}

    return StreamKernelSpec(name, "toy.py", 1, prepare or _prepare, run,
                            jobs=("bayesianDistr",))


def _quiet_run(ctx, block_mb):
    # stream the corpus through a real prefetched byte-block read so the
    # byte-accounting hook sees raw blocks; allocate almost nothing
    from avenir_tpu.core.stream import iter_byte_blocks, prefetched

    total = 0
    for blk in prefetched(iter_byte_blocks(
            ctx["csv"], max(int(block_mb * (1 << 20)), 64)), depth=1):
        total += len(blk)
    return total


def test_auditor_validates_a_well_modeled_job():
    row, finding = audit_footprint(
        _toy_spec(_quiet_run),
        model_fn=lambda bb: combined_footprint(["bayesianDistr"], bb))
    assert row["footprint_model_validated"] is True and finding is None
    assert len(row["runs"]) >= 2
    assert all(r["observed_max_block_bytes"] > 0 for r in row["runs"])


def test_auditor_catches_a_vacuous_model():
    from avenir_tpu.analysis.mem import FootprintEstimate

    # a "model" predicting ~4GB for a job that allocates nothing breaks
    # the tightness side of the band: the oracle admits nothing useful
    row, finding = audit_footprint(
        _toy_spec(_quiet_run, name="vacuous_model"),
        model_fn=lambda bb: FootprintEstimate(
            "toy", bb, {"nonsense": 4 << 30}))
    assert row["footprint_model_validated"] is False
    assert finding is not None and finding.rule == MEM_AUDIT_RULE
    assert finding.scope == "vacuous_model"


def test_auditor_catches_an_underpredicting_model():
    import time

    def hungry_run(ctx, block_mb):
        _quiet_run(ctx, block_mb)
        # allocate well past predicted + slack, hold it long enough for
        # the 4ms sampler to see it, release before returning
        ball = np.ones((AUDIT_SLACK_BYTES + (32 << 20)) // 8, np.float64)
        time.sleep(0.08)
        return float(ball[0])

    from avenir_tpu.analysis.mem import FootprintEstimate

    row, finding = audit_footprint(
        _toy_spec(hungry_run, name="underpredicted"),
        model_fn=lambda bb: FootprintEstimate("toy", bb, {"tiny": 1 << 20}))
    assert row["footprint_model_validated"] is False
    assert finding is not None and finding.rule == MEM_AUDIT_RULE


def test_auditor_wraps_job_failures_as_exit2_errors():
    def run(ctx, block_mb):
        raise ValueError("synthetic job failure")

    with pytest.raises(MemAuditError, match="boomjob"):
        audit_footprint(_toy_spec(run, name="boomjob"))


def test_auditor_requires_two_block_sizes():
    with pytest.raises(MemAuditError, match=">= 2 block sizes"):
        audit_footprint(_toy_spec(_quiet_run), block_sizes_mb=[0.5])


def test_band_holds_under_adversarial_chunk_layouts():
    # the PR-4 invariance harness's layouts (whole-file down to 512B
    # blocks) on the un-inflated proxy corpus: the tolerance band must
    # hold under adversarial chunkings too, not just the default pair
    spec = next(s for s in stream_entries() if s.name == "nb_stream")
    row, finding = audit_footprint(spec, block_sizes_mb=spec.layouts,
                                   inflate_to=1)
    assert finding is None, row
    assert row["footprint_model_validated"] is True
    assert [r["block_mb"] for r in row["runs"]] == list(spec.layouts)


def test_mem_findings_roundtrip_through_baseline(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(_CARRY_BAD)
    key = "mod.py::mem-unbounded-carry::fold"
    report = run_mem(paths=[str(p)], baseline=[
        BaselineEntry(key, "fixture", 1)], root=str(tmp_path), audit=False)
    assert not report.findings and len(report.suppressed) == 2

    p.write_text(_CARRY_GOOD)
    report = run_mem(paths=[str(p)], baseline=[
        BaselineEntry(key, "fixture", 1)], root=str(tmp_path), audit=False)
    assert [e.key for e in report.stale] == [key]


# ---------------------------------------------- cache budget + counters
def test_cache_budget_evicts_least_recently_replayed_source(tmp_path):
    from avenir_tpu.native.ingest import EncodedBlockCache

    srcs = []
    for i in range(2):
        p = tmp_path / f"s{i}.csv"
        p.write_text(f"src{i},a,b\n" * 50)
        srcs.append(str(p))
    counts = np.full(64, 4, np.int64)
    codes = np.arange(256, dtype=np.int32) % 7
    cache = EncodedBlockCache(srcs, cache_dir=str(tmp_path / "c"),
                              byte_budget=600)
    cache.begin()
    cache.set_source(0)
    cache.add_block(counts, codes)          # ~340B: fits
    cache.set_source(1)
    cache.add_block(counts, codes)          # pushes past 600B: evicts s0
    assert cache.commit()
    assert cache.evicted_bytes > 0
    assert not cache.valid                  # all-or-nothing gate broken
    assert not cache.source_valid(0)        # the evicted (LRR) source
    assert cache.source_valid(1)            # the survivor replays
    blocks = list(cache.blocks(1))
    assert len(blocks) == 1
    np.testing.assert_array_equal(blocks[0][0], counts)
    with pytest.raises(RuntimeError):
        list(cache.blocks(0))
    with pytest.raises(RuntimeError):
        list(cache.blocks())
    cache.close()


def test_cache_rejects_writes_after_commit_and_appends_on_reopen(tmp_path):
    from avenir_tpu.native.ingest import EncodedBlockCache

    srcs = []
    for i in range(2):
        p = tmp_path / f"s{i}.csv"
        p.write_text(f"src{i},a\n" * 20)
        srcs.append(str(p))
    c1 = np.array([2, 1], np.int64)
    k1 = np.array([0, 1, 2], np.int32)
    cache = EncodedBlockCache(srcs, cache_dir=str(tmp_path / "c"),
                              byte_budget=1 << 20)
    cache.begin()
    # interleaved source writes: returning to a segment must EXTEND it,
    # not truncate the blocks already written
    cache.set_source(0)
    cache.add_block(c1, k1)
    cache.set_source(1)
    cache.add_block(c1, k1)
    cache.set_source(0)
    cache.add_block(np.array([3], np.int64), np.array([4, 4, 4], np.int32))
    assert cache.commit()
    blocks0 = list(cache.blocks(0))
    assert len(blocks0) == 2
    np.testing.assert_array_equal(blocks0[0][1], k1)
    np.testing.assert_array_equal(blocks0[1][1], [4, 4, 4])
    # a sealed cache never grows: writes after commit raise instead of
    # silently truncating the committed segment
    with pytest.raises(RuntimeError, match="after commit"):
        cache.add_block(c1, k1)
    cache.close()


def test_miner_with_tiny_cache_budget_matches_unbudgeted_output(tmp_path):
    """Eviction degrades throughput, never correctness: a budget too
    small for even one block falls back to the re-parse path and the
    mined output stays byte-identical, with Cache:EvictedBytes > 0."""
    from avenir_tpu.runner import run_job

    csv = tmp_path / "seq.csv"
    rng = np.random.default_rng(5)
    states = ["L", "M", "H"]
    with open(csv, "w") as fh:
        for i in range(600):
            toks = [states[int(x)] for x in rng.integers(0, 3, 5)]
            fh.write(f"c{i},T," + ",".join(toks) + "\n")
    base = {"fia.support.threshold": "0.2", "fia.item.set.length": "2",
            "fia.skip.field.count": "2", "fia.stream.block.size.mb": "0.002"}
    res_free = run_job("frequentItemsApriori", dict(base), [str(csv)],
                       str(tmp_path / "free"))
    tight = dict(base)
    tight["fia.stream.encoded.cache.budget.mb"] = "0.0001"   # ~100 bytes
    res_tight = run_job("frequentItemsApriori", tight, [str(csv)],
                        str(tmp_path / "tight"))
    assert res_free.counters["Cache:EvictedBytes"] == 0
    assert res_free.counters["Cache:SpillBytes"] > 0
    assert res_tight.counters["Cache:EvictedBytes"] > 0
    for a, b in zip(sorted(res_free.outputs), sorted(res_tight.outputs)):
        assert open(a, "rb").read() == open(b, "rb").read(), (a, b)


def test_streamed_jobs_carry_the_memory_oracle_counters(tmp_path):
    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.runner import run_job

    csv = tmp_path / "churn.csv"
    csv.write_text(generate_churn(300, seed=3, as_csv=True))
    schema = tmp_path / "churn.json"
    churn_schema().save(str(schema))
    res = run_job("bayesianDistr",
                  {"bad.feature.schema.file.path": str(schema)},
                  [str(csv)], str(tmp_path / "nb.txt"))
    assert res.counters["Mem:PredictedPeakBytes"] > 0
    assert res.counters["Mem:PeakRSS"] > 0
    # the measured peak is a whole-process number; the prediction is the
    # job's incremental footprint — both present is the contract


# -------------------------------------------------------------------- CLI
def _cli(args, cwd=REPO, env=None):
    e = dict(os.environ)
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py")] + args,
        capture_output=True, text=True, cwd=cwd, timeout=600, env=e)


def test_cli_mem_exit_code_contract_and_schema(tmp_path):
    # bad fixture + rule subset (audit skipped -> fast): findings = 1
    (tmp_path / "bad.py").write_text(_CACHE_BAD)
    proc = _cli(["--mem", "bad.py", "--rules", "mem-cache-spill-unbudgeted",
                 "--no-baseline", "--json"], cwd=str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["counts"] == {"mem-cache-spill-unbudgeted": 1}
    assert rep["footprint_audit"] == []       # subset skipped the audit
    # one schema across all modes: same top-level keys as the golden
    golden = json.load(open(os.path.join(
        REPO, "tests", "data", "graftlint_json_golden.json")))
    assert set(rep) == set(golden)

    # good twin: clean = 0
    (tmp_path / "good.py").write_text(_CACHE_GOOD)
    proc = _cli(["--mem", "good.py", "--rules", "mem-cache-spill-unbudgeted",
                 "--no-baseline"], cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # usage errors = 2: unknown rule, and mixed tiers
    assert _cli(["--mem", "--rules", "nope"]).returncode == 2
    assert _cli(["--mem", "--ir"]).returncode == 2
    assert _cli(["--mem", "--flow"]).returncode == 2
