"""Incremental delta-scan driver: the PR's contracts.

1. Equivalence — run_incremental must reproduce run_job's artifact
   BYTE-IDENTICALLY: on a cold first run, after an append (folding only
   the delta into the restored carry), and after any fallback.
2. Crash resume — a subprocess killed mid-scan (hard exit from the
   checkpoint hook, after >= 1 committed mid-scan checkpoint) reruns to
   the cold-scan bytes, resuming from the watermark instead of byte 0.
3. Never commit a wrong carry — a truncated/corrupt checkpoint, an
   in-place edit under the recorded fingerprints, or a changed job all
   fall back to a cold scan (Cache:HitBlocks == 0), never to a stale
   resume.
4. Mechanics — offset-tagged byte blocks tile the file gap-free and
   resume exactly at a watermark; the CheckpointStore round-trips and
   detects torn writes.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from avenir_tpu.core.incremental import (CheckpointStore, block_fingerprint,
                                         verified_prefix)
from avenir_tpu.core.stream import iter_byte_blocks
from avenir_tpu.runner import run_incremental, run_job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _churn(tmp_path, rows=1000):
    from avenir_tpu.data import churn_schema, generate_churn

    csv = tmp_path / "churn.csv"
    csv.write_text(generate_churn(rows, seed=11, as_csv=True))
    schema = tmp_path / "churn.json"
    churn_schema().save(str(schema))
    return str(csv), str(schema)


def _append_churn(csv, rows, seed):
    from avenir_tpu.data import generate_churn

    with open(csv, "a") as fh:
        fh.write(generate_churn(rows, seed=seed, as_csv=True))


def _seq(tmp_path, rows=600, start=0, mode="a"):
    rng = np.random.default_rng(12 + start)
    states = ["L", "M", "H"]
    csv = tmp_path / "seq.csv"
    with open(csv, mode) as fh:
        for i in range(start, start + rows):
            up = i % 2 == 0
            s, toks = 1, []
            for _ in range(6):
                p = [0.1, 0.3, 0.6] if up else [0.6, 0.3, 0.1]
                s = int(np.clip(s + rng.choice([-1, 0, 1], p=p), 0, 2))
                toks.append(states[s])
            fh.write(f"c{i},{'T' if up else 'F'}," + ",".join(toks) + "\n")
    return str(csv)


def _mi_conf(schema):
    return {"mut.feature.schema.file.path": schema,
            "mut.mutual.info.score.algorithms": "mutual.info.maximization",
            "mut.stream.block.size.mb": "0.01"}


def _bytes_of(res):
    return b"\n".join(open(p, "rb").read() for p in sorted(res.outputs))


# ------------------------------------------------------------ mechanics
def test_offset_blocks_tile_and_resume(tmp_path):
    p = tmp_path / "f.csv"
    p.write_text("".join(f"row{i},a,b\n" for i in range(500)))
    raw = p.read_bytes()
    pairs = list(iter_byte_blocks(str(p), 487, with_offsets=True))
    assert b"".join(b for _off, b in pairs) == raw
    assert pairs[0][0] == 0
    for (o1, b1), (o2, _b2) in zip(pairs, pairs[1:]):
        assert o2 == o1 + len(b1)           # gap-free tiling
    # default mode unchanged: bare blocks, same cuts
    assert list(iter_byte_blocks(str(p), 487)) == [b for _o, b in pairs]
    # resume from a mid-file watermark reproduces exactly the tail
    wm = pairs[3][0]
    tail = list(iter_byte_blocks(str(p), 487, byte_range=(wm, len(raw)),
                                 with_offsets=True))
    assert tail[0][0] == wm
    assert b"".join(b for _o, b in tail) == raw[wm:]


def test_verified_prefix_append_vs_inplace_edit(tmp_path):
    p = tmp_path / "f.csv"
    p.write_text("".join(f"row{i},a,b\n" for i in range(300)))
    size = os.path.getsize(p)
    fps = [block_fingerprint(o, b)
           for o, b in iter_byte_blocks(str(p), 331, with_offsets=True)]
    assert verified_prefix(str(p), fps) == (len(fps), size)
    # append: every recorded block still verifies
    with open(p, "a") as fh:
        fh.write("tail,x,y\n")
    assert verified_prefix(str(p), fps) == (len(fps), size)
    # in-place edit: verification stops at the edited block
    data = bytearray(p.read_bytes())
    data[0] = ord("X")
    p.write_bytes(bytes(data))
    n, covered = verified_prefix(str(p), fps)
    assert n == 0 and covered == 0
    # shrink below the recorded coverage: nothing verifies past the cut
    p.write_bytes(bytes(data[: size // 2]))
    n, _covered = verified_prefix(str(p), fps)
    assert n < len(fps)


def test_checkpoint_store_roundtrip_and_torn_writes(tmp_path):
    store = CheckpointStore(str(tmp_path / "state"))
    assert store.load() is None
    meta = store.save({"seq": 1, "job": "j", "complete": True}, b"carry-1")
    got = store.load()
    assert got is not None
    assert got[0]["job"] == "j" and got[1] == b"carry-1"
    # a newer save supersedes (and removes) the old carry
    meta2 = store.save({"seq": 2, "job": "j", "complete": True}, b"carry-22")
    assert store.load()[1] == b"carry-22"
    assert not os.path.exists(os.path.join(store.dir, meta["carry_file"]))
    # truncated carry: load refuses (cold-fallback signal), no raise
    carry = os.path.join(store.dir, meta2["carry_file"])
    with open(carry, "wb") as fh:
        fh.write(b"carry")
    assert store.load() is None
    # corrupt manifest: same
    store.save({"seq": 3, "job": "j", "complete": True}, b"carry-3")
    with open(os.path.join(store.dir, store.MANIFEST), "w") as fh:
        fh.write("{not json")
    assert store.load() is None
    store.clear()
    assert os.listdir(store.dir) == []


# ---------------------------------------------------------- equivalence
def test_cold_and_append_refresh_byte_identical(tmp_path):
    csv, schema = _churn(tmp_path)
    conf = _mi_conf(schema)
    state = str(tmp_path / "state")
    cold = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "cold.txt"))
    incr0 = run_incremental("mutualInformation", conf, [csv],
                            str(tmp_path / "incr0.txt"), state_dir=state)
    assert _bytes_of(cold) == _bytes_of(incr0)
    # first run is all-delta, and the plain run_job result carries the
    # same counter schema with zeros
    assert incr0.counters["Cache:HitBlocks"] == 0
    assert incr0.counters["Cache:DeltaBlocks"] > 0
    assert cold.counters["Cache:HitBlocks"] == 0
    assert cold.counters["Resume:SkippedBytes"] == 0

    _append_churn(csv, 80, seed=12)
    cold2 = run_job("mutualInformation", conf, [csv],
                    str(tmp_path / "cold2.txt"))
    incr1 = run_incremental("mutualInformation", conf, [csv],
                            str(tmp_path / "incr1.txt"), state_dir=state)
    assert _bytes_of(cold2) == _bytes_of(incr1)
    assert incr1.counters["Cache:HitBlocks"] > 0
    assert incr1.counters["Resume:SkippedBytes"] > 0
    # the delta really was a delta: far fewer blocks than the cold scan
    assert incr1.counters["Cache:DeltaBlocks"] \
        < incr0.counters["Cache:DeltaBlocks"]


def test_append_refresh_miner_multi_pass(tmp_path):
    csv = _seq(tmp_path, rows=500, mode="w")
    conf = {"fia.support.threshold": "0.3", "fia.item.set.length": "2",
            "fia.skip.field.count": "2", "fia.stream.block.size.mb": "0.003"}
    state = str(tmp_path / "state")
    run_incremental("frequentItemsApriori", conf, [csv],
                    str(tmp_path / "fia0"), state_dir=state)
    _seq(tmp_path, rows=40, start=500)      # append
    cold = run_job("frequentItemsApriori", conf, [csv],
                   str(tmp_path / "fia_cold"))
    incr = run_incremental("frequentItemsApriori", conf, [csv],
                           str(tmp_path / "fia_incr"), state_dir=state)
    assert _bytes_of(cold) == _bytes_of(incr)
    assert incr.counters["Resume:SkippedBytes"] > 0


def test_unchanged_corpus_refresh_folds_nothing(tmp_path):
    csv, schema = _churn(tmp_path, rows=400)
    conf = _mi_conf(schema)
    state = str(tmp_path / "state")
    first = run_incremental("mutualInformation", conf, [csv],
                            str(tmp_path / "a.txt"), state_dir=state)
    again = run_incremental("mutualInformation", conf, [csv],
                            str(tmp_path / "b.txt"), state_dir=state)
    assert _bytes_of(first) == _bytes_of(again)
    assert again.counters["Cache:DeltaBlocks"] == 0
    assert again.counters["Resume:SkippedBytes"] == os.path.getsize(csv)


# -------------------------------------------------------- never-commit
def test_truncated_checkpoint_falls_back_cold(tmp_path):
    csv, schema = _churn(tmp_path, rows=400)
    conf = _mi_conf(schema)
    state = str(tmp_path / "state")
    run_incremental("mutualInformation", conf, [csv],
                    str(tmp_path / "a.txt"), state_dir=state)
    store = CheckpointStore(state)
    meta, _blob = store.load()
    with open(os.path.join(state, meta["carry_file"]), "wb") as fh:
        fh.write(b"torn")                    # truncated carry
    cold = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "cold.txt"))
    incr = run_incremental("mutualInformation", conf, [csv],
                           str(tmp_path / "b.txt"), state_dir=state)
    assert _bytes_of(cold) == _bytes_of(incr)
    assert incr.counters["Cache:HitBlocks"] == 0   # cold, not resumed


def test_inplace_edit_falls_back_cold(tmp_path):
    csv, schema = _churn(tmp_path, rows=400)
    conf = _mi_conf(schema)
    state = str(tmp_path / "state")
    run_incremental("mutualInformation", conf, [csv],
                    str(tmp_path / "a.txt"), state_dir=state)
    # rewrite the first row's id in place (valid CSV, same length)
    data = open(csv, "rb").read()
    cut = data.index(b",")
    open(csv, "wb").write(b"Z" * cut + data[cut:])
    cold = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "cold.txt"))
    incr = run_incremental("mutualInformation", conf, [csv],
                           str(tmp_path / "b.txt"), state_dir=state)
    assert _bytes_of(cold) == _bytes_of(incr)
    assert incr.counters["Cache:HitBlocks"] == 0


def test_unterminated_last_line_append_falls_back_cold(tmp_path):
    """A corpus whose last line has NO trailing newline leaves the
    watermark mid-line: appended bytes extend the already-folded row, so
    a resume would silently skip the row's continuation. The driver must
    detect the mid-line coverage and cold-scan instead."""
    csv, schema = _churn(tmp_path, rows=300)
    with open(csv, "rb+") as fh:
        fh.seek(-1, 2)
        fh.truncate()                       # strip the trailing newline
    conf = _mi_conf(schema)
    state = str(tmp_path / "state")
    seeded = run_incremental("mutualInformation", conf, [csv],
                             str(tmp_path / "a.txt"), state_dir=state)
    assert seeded.counters["Cache:DeltaBlocks"] > 0
    with open(csv, "a") as fh:
        fh.write("\n")                      # the last row grows a tail
    _append_churn(csv, 60, seed=14)
    cold = run_job("mutualInformation", conf, [csv],
                   str(tmp_path / "cold.txt"))
    incr = run_incremental("mutualInformation", conf, [csv],
                           str(tmp_path / "b.txt"), state_dir=state)
    assert _bytes_of(cold) == _bytes_of(incr)
    assert incr.counters["Cache:HitBlocks"] == 0    # cold, not spliced


def test_changed_conf_or_schema_content_falls_back_cold(tmp_path):
    """The checkpoint records a conf digest: a changed property or a
    changed schema FILE CONTENT (same path) means the restored carry
    would have parsed its prefix under a different view than the delta —
    conservative cold fallback, never a mixed-view artifact."""
    csv, schema = _churn(tmp_path, rows=300)
    state = str(tmp_path / "state")
    run_incremental("mutualInformation", _mi_conf(schema), [csv],
                    str(tmp_path / "a.txt"), state_dir=state)
    conf2 = dict(_mi_conf(schema), **{"mut.stream.block.size.mb": "0.02"})
    cold = run_job("mutualInformation", conf2, [csv],
                   str(tmp_path / "cold.txt"))
    r2 = run_incremental("mutualInformation", conf2, [csv],
                         str(tmp_path / "b.txt"), state_dir=state)
    assert _bytes_of(cold) == _bytes_of(r2)
    assert r2.counters["Cache:HitBlocks"] == 0
    # r2 reseeded under conf2; an edit to the schema file's BYTES (the
    # path is unchanged, so the props alone cannot see it) also re-scans
    with open(schema, "a") as fh:
        fh.write("\n")
    r3 = run_incremental("mutualInformation", conf2, [csv],
                         str(tmp_path / "c.txt"), state_dir=state)
    assert r3.counters["Cache:HitBlocks"] == 0
    # and with nothing changed, the same conf resumes
    r4 = run_incremental("mutualInformation", conf2, [csv],
                         str(tmp_path / "d.txt"), state_dir=state)
    assert r4.counters["Cache:HitBlocks"] > 0


def test_state_of_other_job_or_inputs_is_ignored(tmp_path):
    csv, schema = _churn(tmp_path, rows=400)
    state = str(tmp_path / "state")
    run_incremental("mutualInformation", _mi_conf(schema), [csv],
                    str(tmp_path / "a.txt"), state_dir=state)
    # same state dir, different job: must cold-scan, not resume
    conf = {"fid.feature.schema.file.path": schema,
            "fid.stream.block.size.mb": "0.01"}
    cold = run_job("fisherDiscriminant", conf, [csv],
                   str(tmp_path / "fd_cold.txt"))
    incr = run_incremental("fisherDiscriminant", conf, [csv],
                           str(tmp_path / "fd.txt"), state_dir=state)
    assert _bytes_of(cold) == _bytes_of(incr)
    assert incr.counters["Cache:HitBlocks"] == 0


def test_default_state_dir_is_deterministic_per_job_and_corpus(tmp_path):
    from avenir_tpu.runner import _incremental_state_dir, _job_cfg

    csv, schema = _churn(tmp_path, rows=300)
    _c, _p, cfg = _job_cfg("mutualInformation", _mi_conf(schema))
    d1 = _incremental_state_dir(cfg, "mutualInformation", [csv])
    d2 = _incremental_state_dir(cfg, "mutualInformation", [csv])
    d3 = _incremental_state_dir(cfg, "bayesianDistr", [csv])
    assert d1 == d2 and d1 != d3
    assert d1.startswith(os.path.join(str(tmp_path), ".avenir_incremental"))
    # and the explicit key wins
    cfg.props["mut.stream.incremental.state.dir"] = "/tmp/explicit"
    assert _incremental_state_dir(
        cfg, "mutualInformation", [csv]) == "/tmp/explicit"


# --------------------------------------------------------- crash resume
_KILL_CHILD = r'''
import json, os, sys
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
from avenir_tpu.core import incremental

seen = {"n": 0}
def bomb(meta):
    if not meta.get("complete"):
        seen["n"] += 1
        if seen["n"] >= %(kills)d:
            os._exit(137)        # hard kill mid-scan, no cleanup
incremental._checkpoint_hook = bomb

from avenir_tpu.runner import run_incremental
run_incremental(%(job)r, json.loads(%(conf)r), [%(csv)r], %(out)r,
                state_dir=%(state)r)
print("COMPLETED")               # must be unreachable on the kill run
'''


@pytest.mark.parametrize("job,conf_fn", [
    ("markovStateTransitionModel", lambda schema: {
        "mst.model.states": "L,M,H", "mst.class.label.field.ord": "1",
        "mst.skip.field.count": "2", "mst.class.labels": "T,F",
        "mst.stream.block.size.mb": "0.002",
        "mst.stream.checkpoint.interval.mb": "0.001"}),
    ("mutualInformation", lambda schema: {
        "mut.feature.schema.file.path": schema,
        "mut.mutual.info.score.algorithms": "mutual.info.maximization",
        "mut.stream.block.size.mb": "0.005",
        "mut.stream.checkpoint.interval.mb": "0.004"}),
])
def test_mid_scan_kill_then_rerun_reproduces_cold_bytes(tmp_path, job,
                                                        conf_fn):
    if job == "mutualInformation":
        csv, schema = _churn(tmp_path, rows=800)
        conf = conf_fn(schema)
    else:
        csv = _seq(tmp_path, rows=800, mode="w")
        conf = conf_fn(None)
    state = str(tmp_path / "state")
    out = str(tmp_path / "killed_out")
    child = _KILL_CHILD % {"repo": REPO, "kills": 2, "job": job,
                           "conf": json.dumps(conf), "csv": csv,
                           "out": out, "state": state}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", child],
                          capture_output=True, text=True, timeout=600,
                          env=env, cwd=REPO)
    assert proc.returncode == 137, proc.stderr[-800:]
    assert "COMPLETED" not in proc.stdout
    # the kill left a committed MID-SCAN checkpoint behind
    store = CheckpointStore(state)
    loaded = store.load()
    assert loaded is not None and loaded[0]["complete"] is False
    covered = sum(loaded[0]["watermarks"])
    assert 0 < covered < os.path.getsize(csv)
    # rerun resumes from the watermark and reproduces the cold bytes
    cold = run_job(job, conf, [csv], str(tmp_path / "cold_out"))
    incr = run_incremental(job, conf, [csv], str(tmp_path / "resumed_out"),
                           state_dir=state)
    assert _bytes_of(cold) == _bytes_of(incr)
    assert incr.counters["Resume:SkippedBytes"] == covered
    assert incr.counters["Cache:DeltaBlocks"] > 0


def test_cli_incremental_flag(tmp_path):
    from avenir_tpu.runner import run_from_cli

    csv, schema = _churn(tmp_path, rows=300)
    props = tmp_path / "job.properties"
    props.write_text(
        f"mut.feature.schema.file.path={schema}\n"
        "mut.mutual.info.score.algorithms=mutual.info.maximization\n"
        "mut.stream.block.size.mb=0.01\n"
        f"mut.stream.incremental.state.dir={tmp_path / 'state'}\n")
    out1 = str(tmp_path / "o1.txt")
    res = run_from_cli(["mutualInformation", "--incremental",
                        "--conf", str(props), csv, out1])
    assert res.counters["Cache:DeltaBlocks"] > 0
    _append_churn(csv, 50, seed=13)
    out2 = str(tmp_path / "o2.txt")
    res2 = run_from_cli(["mutualInformation", "--incremental",
                         "--conf", str(props), csv, out2])
    assert res2.counters["Resume:SkippedBytes"] > 0
    cold = run_job("mutualInformation", {
        "mut.feature.schema.file.path": schema,
        "mut.mutual.info.score.algorithms": "mutual.info.maximization",
        "mut.stream.block.size.mb": "0.01"}, [csv],
        str(tmp_path / "cold.txt"))
    assert open(out2, "rb").read() == open(cold.outputs[0], "rb").read()
