"""avenir-shard (avenir_tpu/dist): planner, ledger, sharded driver.

The contracts under test are the ones the subsystem's correctness
rests on:

- the shard planner's blocks are newline-aligned and tile every input
  gap-free, including the satellite edge set (no trailing newline,
  corpus smaller than the block count, single-line corpus);
- the block ledger admits exactly ONE winner per claim under
  contention, rejects duplicate commits of the same block id
  (first-commit-wins — the dedup every NON-idempotent fold family
  requires), and treats a torn claim file as unclaimed;
- run_sharded reproduces the solo runner's artifact byte-for-byte for
  a Dataset-chunk family, a raw-byte-block family, and a multi-pass
  miner (whose per-block states finish against newline-aligned byte
  slices), and a deterministically held straggler block is stolen,
  redundantly folded, and deduped — Shard:DedupBlocks fires and the
  bytes still match.
"""

import json
import os
import threading

import pytest

from avenir_tpu.dist import (BlockLedger, PlanError, StragglerPolicy,
                             load_plan, mirror_after_s, plan_shards,
                             run_sharded, write_plan)
from avenir_tpu.dist.detect import per_block_seconds
from avenir_tpu.tune.signals import RunSignals

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from avenir_tpu.data import churn_schema, generate_churn

    d = tmp_path_factory.mktemp("dist")
    csv = str(d / "churn.csv")
    with open(csv, "w") as fh:
        fh.write(generate_churn(2500, seed=17, as_csv=True))
    schema = str(d / "churn.json")
    churn_schema().save(schema)
    seq = str(d / "seq.csv")
    with open(seq, "w") as fh:
        for i in range(1500):
            fh.write(f"c{i},{'T' if i % 2 else 'F'},L,M,H,M,L\n")
    return {"dir": str(d), "csv": csv, "schema": schema, "seq": seq}


# ---------------------------------------------------------------- planner
class TestPlanner:
    def test_blocks_tile_input_newline_aligned(self, corpus):
        plan = plan_shards([corpus["csv"]], procs=2, factor=4)
        size = os.path.getsize(corpus["csv"])
        assert len(plan.blocks) == 8
        assert plan.blocks[0].start == 0
        assert plan.blocks[-1].end == size
        with open(corpus["csv"], "rb") as fh:
            data = fh.read()
        pos = 0
        for blk in plan.blocks:
            assert blk.start == pos, "blocks must tile gap-free"
            pos = blk.end
            # every interior boundary sits just past a newline
            if blk.end < size:
                assert data[blk.end - 1:blk.end] == b"\n"
        assert pos == size

    def test_home_runs_are_contiguous(self, corpus):
        plan = plan_shards([corpus["csv"]], procs=2, factor=4)
        homes = [b.home for b in plan.blocks]
        assert homes == [0, 0, 0, 0, 1, 1, 1, 1]
        assert len(plan.blocks_for(0)) == len(plan.blocks_for(1)) == 4

    def test_corpus_smaller_than_block_count(self, tmp_path):
        # 3 lines cut into 8 blocks: trailing EMPTY blocks tile
        # gap-free (the split_byte_ranges edge contract)
        p = str(tmp_path / "tiny.csv")
        with open(p, "w") as fh:
            fh.write("a,1\nb,2\nc,3\n")
        plan = plan_shards([p], procs=4, factor=2)
        size = os.path.getsize(p)
        assert len(plan.blocks) == 8
        pos = 0
        for blk in plan.blocks:
            assert blk.start == pos
            pos = blk.end
        assert pos == size
        nonempty = [b for b in plan.blocks if b.end > b.start]
        covered = b"".join(
            open(p, "rb").read()[b.start:b.end] for b in nonempty)
        assert covered == open(p, "rb").read()

    def test_single_line_no_trailing_newline(self, tmp_path):
        p = str(tmp_path / "one.csv")
        with open(p, "w") as fh:
            fh.write("onlyline,42")                 # no newline at all
        plan = plan_shards([p], procs=2, factor=2)
        size = os.path.getsize(p)
        # no interior newline exists: the first boundary collapses to
        # EOF and every later block is empty — still tiling
        assert plan.blocks[0].start == 0
        assert any(b.end == size for b in plan.blocks)
        pos = 0
        for blk in plan.blocks:
            assert blk.start == pos
            pos = blk.end
        assert pos == size

    def test_manifest_roundtrip_atomic(self, corpus, tmp_path):
        plan = plan_shards([corpus["csv"]], procs=2, factor=2,
                           policy=StragglerPolicy().to_dict())
        plan.job = "mutualInformation"
        plan.prefix = "mut"
        plan.props = {"mut.feature.schema.file.path": corpus["schema"]}
        path = str(tmp_path / "plan.json")
        write_plan(plan, path)
        assert not [f for f in os.listdir(str(tmp_path))
                    if ".tmp" in f], "manifest write must be atomic"
        loaded = load_plan(path)
        assert loaded.to_dict() == plan.to_dict()
        assert loaded.blocks[0].start == 0
        assert loaded.policy["mirror_multiple"] == 4.0

    def test_rejects_bad_args(self, corpus):
        with pytest.raises(PlanError):
            plan_shards([], procs=2)
        with pytest.raises(PlanError):
            plan_shards([corpus["csv"]], procs=0)
        with pytest.raises(PlanError):
            plan_shards([corpus["csv"]], procs=2, factor=0)
        with pytest.raises(PlanError):
            plan_shards(["/nonexistent/x.csv"], procs=2)


# ----------------------------------------------------------------- ledger
class TestLedger:
    def test_exactly_one_claim_winner_under_contention(self, tmp_path):
        ledger = BlockLedger(str(tmp_path))
        wins = []
        barrier = threading.Barrier(8)

        def racer(w):
            barrier.wait()
            if ledger.claim(7, worker=w):
                wins.append(w)

        threads = [threading.Thread(target=racer, args=(w,))
                   for w in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(wins) == 1, f"claim winners: {wins}"
        assert ledger.claim_info(7)["worker"] == wins[0]

    def test_duplicate_commit_rejected_and_marked(self, tmp_path):
        ledger = BlockLedger(str(tmp_path))
        assert ledger.commit(3, worker=0, blob=b"first-state")
        assert not ledger.commit(3, worker=1, blob=b"late-duplicate")
        # first commit wins: the state the merge will see is worker 0's
        assert ledger.load_state(3) == b"first-state"
        assert ledger.dup_count() == 1
        assert ledger.committed() == [3]

    def test_racing_commits_exactly_one_wins(self, tmp_path):
        ledger = BlockLedger(str(tmp_path))
        outcomes = {}
        barrier = threading.Barrier(6)

        def committer(w):
            barrier.wait()
            outcomes[w] = ledger.commit(0, w, f"state-{w}".encode())

        threads = [threading.Thread(target=committer, args=(w,))
                   for w in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(outcomes.values()) == 1
        winner = next(w for w, won in outcomes.items() if won)
        assert ledger.load_state(0) == f"state-{winner}".encode()
        assert ledger.dup_count() == 5

    def test_commit_publishes_winner_fps_only(self, tmp_path):
        # refresh plans: the WINNING commit's folded-chunk fingerprints
        # are what the coordinator stamps into the checkpoint — a losing
        # duplicate (which may have re-read different bytes) must never
        # replace them, and a block committed without fps reads None
        ledger = BlockLedger(str(tmp_path))
        fps = [{"offset": 0, "length": 4, "hash": "aa"},
               {"offset": 4, "length": 3, "hash": "bb"}]
        assert ledger.commit(5, worker=0, blob=b"s0", fps=fps)
        assert not ledger.commit(
            5, worker=1, blob=b"s1",
            fps=[{"offset": 0, "length": 7, "hash": "cc"}])
        assert ledger.load_fps(5) == fps
        assert ledger.committed() == [5]
        assert ledger.commit(6, worker=0, blob=b"s")
        assert ledger.load_fps(6) is None

    def test_level_namespaces_are_independent(self, tmp_path):
        # per-k rounds ride the same ledger under ledger/k<k>/: one
        # block id claims/commits independently per level, and a
        # level's dedup never bleeds into pass-1 counters
        ledger = BlockLedger(str(tmp_path))
        k2 = ledger.level("k2")
        assert ledger.commit(0, worker=0, blob=b"pass1-state")
        assert k2.commit(0, worker=1, blob=b"k2-counts")
        assert ledger.load_state(0) == b"pass1-state"
        assert k2.load_state(0) == b"k2-counts"
        assert not k2.commit(0, worker=0, blob=b"late-dup")
        assert k2.dup_count() == 1
        assert ledger.dup_count() == 0
        assert ledger.level("k2").committed() == [0]
        with pytest.raises(ValueError):
            ledger.level("k2/../escape")

    def test_perk_racing_commits_one_winner_plus_dup_marker(
            self, tmp_path):
        # two workers racing one k-block commit: exactly one count
        # vector wins, the loser lands as a dup marker — the fold-
        # exactly-once-per-level contract the merged supports rest on
        ledger = BlockLedger(str(tmp_path)).level("k3")
        outcomes = {}
        barrier = threading.Barrier(2)

        def committer(w):
            barrier.wait()
            outcomes[w] = ledger.commit(5, w, f"counts-{w}".encode())

        threads = [threading.Thread(target=committer, args=(w,))
                   for w in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(outcomes.values()) == 1
        winner = next(w for w, won in outcomes.items() if won)
        assert ledger.load_state(5) == f"counts-{winner}".encode()
        assert ledger.dup_count() == 1

    def test_torn_claim_treated_as_unclaimed(self, tmp_path):
        ledger = BlockLedger(str(tmp_path))
        with open(ledger.claim_path(5), "w") as fh:
            fh.write('{"block": 5, "wor')           # torn mid-write
        assert ledger.claim_info(5) is None
        assert 5 in ledger.unclaimed(8)
        # a worker re-claims it: the torn file is swept aside and the
        # fresh claim holds
        assert ledger.claim(5, worker=1)
        assert ledger.claim_info(5)["worker"] == 1

    def test_stale_claims_oldest_first(self, tmp_path):
        import time

        ledger = BlockLedger(str(tmp_path))
        now = time.time()
        ledger.claim(0, worker=0)
        ledger.claim(1, worker=1)
        ledger.commit(1, worker=1, blob=b"s")      # committed: not stale
        assert ledger.stale_claims(4, older_than_s=0.0,
                                   now=now + 10) == [0]
        assert ledger.stale_claims(4, older_than_s=60.0,
                                   now=now + 10) == []


# --------------------------------------------------------------- detector
class TestDetector:
    def test_per_block_seconds_from_signals(self):
        sig = RunSignals(read_s=1.0, parse_s=0.5, fold_s=2.5)
        assert per_block_seconds(sig, 4) == pytest.approx(1.0)
        assert per_block_seconds(sig, 0) == 0.0

    def test_mirror_threshold_clamped(self):
        pol = StragglerPolicy(mirror_multiple=4.0, mirror_floor_s=1.0,
                              mirror_cap_s=10.0)
        fast = RunSignals(read_s=0.01, parse_s=0.01, fold_s=0.02)
        # tiny observed blocks: the floor holds (no jitter mirroring)
        assert mirror_after_s(pol, fast, 4) == 1.0
        slow = RunSignals(read_s=40.0, parse_s=0.0, fold_s=40.0)
        # huge observed blocks: the cap holds (a straggler cannot gate
        # the run forever)
        assert mirror_after_s(pol, slow, 4) == 10.0
        mid = RunSignals(read_s=2.0, parse_s=0.0, fold_s=2.0)
        assert mirror_after_s(pol, mid, 4) == pytest.approx(4.0)
        # no evidence yet: the floor, not zero
        assert mirror_after_s(pol, RunSignals(), 0) == 1.0


# ---------------------------------------------------------------- sharded
class TestRunSharded:
    def test_dataset_family_byte_identical(self, corpus, tmp_path):
        from avenir_tpu.runner import run_job

        conf = {"mut.feature.schema.file.path": corpus["schema"],
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization"}
        solo = str(tmp_path / "mi_solo.txt")
        run_job("mutualInformation", conf, [corpus["csv"]], solo)
        # a quiet-path policy: this test is about byte-identity and the
        # counter surface, so the mirror floor is parked high enough
        # that a loaded CI box's slow first fold can't trigger
        # redundant work (the held-straggler test covers mirroring)
        res = run_sharded("mutualInformation", conf, [corpus["csv"]],
                          str(tmp_path / "mi_sharded.txt"), procs=2,
                          factor=2,
                          policy=StragglerPolicy(mirror_floor_s=60.0))
        assert open(solo, "rb").read() == \
            open(str(tmp_path / "mi_sharded.txt"), "rb").read()
        assert res.counters["Shard:Blocks"] == 4.0
        assert res.counters["Shard:DedupBlocks"] == 0.0
        assert res.counters["Shard:MergeMs"] > 0.0
        assert res.counters["Shard:Workers"] == 2.0

    def test_bytes_family_byte_identical(self, corpus, tmp_path):
        from avenir_tpu.runner import run_job

        conf = {"mst.model.states": "L,M,H",
                "mst.class.label.field.ord": "1",
                "mst.skip.field.count": "2", "mst.class.labels": "T,F",
                "mst.stream.block.size.mb": "0.005"}
        solo = str(tmp_path / "mst_solo.txt")
        run_job("markovStateTransitionModel", conf, [corpus["seq"]],
                solo)
        res = run_sharded("markovStateTransitionModel", conf,
                          [corpus["seq"]],
                          str(tmp_path / "mst_sharded.txt"), procs=2,
                          factor=2)
        assert open(solo, "rb").read() == \
            open(str(tmp_path / "mst_sharded.txt"), "rb").read()
        assert res.counters["Shard:Blocks"] == 4.0

    def test_miner_family_byte_identical(self, corpus, tmp_path):
        # the miners' per-k candidate rounds run DISTRIBUTED: workers
        # stay resident after pass 1, count each level's candidates
        # per block through the k-namespaced ledger (replaying their
        # own encoded-block caches), and the coordinator only merges —
        # the artifacts must still equal the solo miner's byte for byte
        from avenir_tpu.runner import run_job

        conf = {"fia.support.threshold": "0.3",
                "fia.item.set.length": "2", "fia.skip.field.count": "2",
                "fia.stream.block.size.mb": "0.005"}
        solo = run_job("frequentItemsApriori", conf, [corpus["seq"]],
                       str(tmp_path / "fia_solo"))
        res = run_sharded("frequentItemsApriori", conf, [corpus["seq"]],
                          str(tmp_path / "fia_sharded"), procs=2,
                          factor=2)
        assert len(solo.outputs) == len(res.outputs) >= 1
        for pa, pb in zip(sorted(solo.outputs), sorted(res.outputs)):
            assert open(pa, "rb").read() == open(pb, "rb").read(), \
                (pa, pb)
        # the per-k phase really ran distributed: one k=2 round over
        # every plan block, zero coordinator-side candidate counting
        assert res.counters["Shard:PerKRounds"] >= 1.0
        assert res.counters["Shard:PerKBlocks"] >= \
            res.counters["Shard:Blocks"]

    def test_gsp_miner_byte_identical(self, corpus, tmp_path):
        # the second miner family through the same distributed per-k
        # path: GSP candidates are token tuples counted by the subseq
        # scan kernel — sharded output must equal solo byte for byte
        from avenir_tpu.runner import run_job

        conf = {"cgs.support.threshold": "0.3",
                "cgs.item.set.length": "3", "cgs.skip.field.count": "2",
                "cgs.stream.block.size.mb": "0.005"}
        solo = run_job("candidateGenerationWithSelfJoin", conf,
                       [corpus["seq"]], str(tmp_path / "cgs_solo"))
        res = run_sharded("candidateGenerationWithSelfJoin", conf,
                          [corpus["seq"]],
                          str(tmp_path / "cgs_sharded"), procs=2,
                          factor=2)
        assert len(solo.outputs) == len(res.outputs) >= 1
        for pa, pb in zip(sorted(solo.outputs), sorted(res.outputs)):
            assert open(pa, "rb").read() == open(pb, "rb").read(), \
                (pa, pb)
        assert res.counters["Shard:PerKRounds"] >= 1.0
        assert res.counters["Shard:PerKBlocks"] >= \
            res.counters["Shard:Blocks"]

    def test_perk_straggler_is_mirrored_and_deduped(self, corpus,
                                                    tmp_path):
        # a straggler INSIDE the per-k loop: worker 0 claims a k=2
        # count block and stalls on it (deterministic hold); worker 1
        # finishes the level's tail, prices the stale claim off its own
        # measured per-k wall, and mirrors it — the level completes,
        # worker 0's late commit is REJECTED first-commit-wins
        # (Shard:DedupBlocks fires), and the bytes still match solo
        from avenir_tpu.runner import run_job

        conf = {"fia.support.threshold": "0.3",
                "fia.item.set.length": "2", "fia.skip.field.count": "2",
                "fia.stream.block.size.mb": "0.005"}
        solo = run_job("frequentItemsApriori", conf, [corpus["seq"]],
                       str(tmp_path / "pk_solo"))
        os.environ["AVENIR_SHARD_TEST_HOLD"] = "0:k2:0:8"
        try:
            res = run_sharded(
                "frequentItemsApriori", conf, [corpus["seq"]],
                str(tmp_path / "pk_sharded"), procs=2, factor=2,
                policy=StragglerPolicy(mirror_floor_s=0.3,
                                       mirror_multiple=2.0,
                                       poll_s=0.02))
        finally:
            del os.environ["AVENIR_SHARD_TEST_HOLD"]
        assert res.counters["Shard:DedupBlocks"] >= 1.0
        assert res.counters["Shard:MirroredBlocks"] >= 1.0
        assert res.counters["Shard:PerKRounds"] >= 1.0
        assert len(solo.outputs) == len(res.outputs) >= 1
        for pa, pb in zip(sorted(solo.outputs), sorted(res.outputs)):
            assert open(pa, "rb").read() == open(pb, "rb").read(), \
                (pa, pb)

    def test_miner_trans_ids_byte_identical(self, corpus, tmp_path):
        # fia.emit.trans.id distributes as one more ledger level
        # ("tids"): per-block id lists concatenate in plan order ==
        # corpus order, so the exact-id artifacts match solo too
        from avenir_tpu.runner import run_job

        conf = {"fia.support.threshold": "0.3",
                "fia.item.set.length": "2", "fia.skip.field.count": "2",
                "fia.emit.trans.id": "true",
                "fia.stream.block.size.mb": "0.005"}
        solo = run_job("frequentItemsApriori", conf, [corpus["seq"]],
                       str(tmp_path / "tid_solo"))
        res = run_sharded("frequentItemsApriori", conf, [corpus["seq"]],
                          str(tmp_path / "tid_sharded"), procs=2,
                          factor=2)
        assert len(solo.outputs) == len(res.outputs) >= 1
        for pa, pb in zip(sorted(solo.outputs), sorted(res.outputs)):
            assert open(pa, "rb").read() == open(pb, "rb").read(), \
                (pa, pb)

    def test_held_straggler_block_is_stolen_and_deduped(self, corpus,
                                                        tmp_path):
        # deterministic straggler: worker 0 holds its first claimed
        # block; worker 1 exhausts the tail (steals), the detector
        # prices the stalled claim off worker 1's own span telemetry,
        # the block is redundantly folded, and worker 0's late commit
        # is REJECTED — dedup fires, bytes unchanged
        from avenir_tpu.runner import run_job

        conf = {"mst.model.states": "L,M,H",
                "mst.class.label.field.ord": "1",
                "mst.skip.field.count": "2", "mst.class.labels": "T,F"}
        solo = str(tmp_path / "mh_solo.txt")
        run_job("markovStateTransitionModel", conf, [corpus["seq"]],
                solo)
        os.environ["AVENIR_SHARD_TEST_HOLD"] = "0:0:12"
        try:
            res = run_sharded(
                "markovStateTransitionModel", conf, [corpus["seq"]],
                str(tmp_path / "mh_sharded.txt"), procs=2, factor=2,
                policy=StragglerPolicy(mirror_floor_s=0.3,
                                       mirror_multiple=2.0,
                                       poll_s=0.02))
        finally:
            del os.environ["AVENIR_SHARD_TEST_HOLD"]
        assert res.counters["Shard:DedupBlocks"] >= 1.0
        assert res.counters["Shard:StolenBlocks"] >= 1.0
        assert res.counters["Shard:MirroredBlocks"] >= 1.0
        assert open(solo, "rb").read() == \
            open(str(tmp_path / "mh_sharded.txt"), "rb").read()

    def test_wedged_worker_cannot_hold_a_finished_scan(self, corpus,
                                                       tmp_path):
        # a PERMANENTLY stalled worker (held far past the run) strands
        # its block; the survivor mirrors it, every block commits, and
        # the exit grace bounds how long the coordinator waits for the
        # wedged process before killing it and merging — the scan
        # completes instead of burning the whole run timeout
        from avenir_tpu.runner import run_job

        conf = {"mst.model.states": "L,M,H",
                "mst.class.label.field.ord": "1",
                "mst.skip.field.count": "2", "mst.class.labels": "T,F"}
        solo = str(tmp_path / "wg_solo.txt")
        run_job("markovStateTransitionModel", conf, [corpus["seq"]],
                solo)
        os.environ["AVENIR_SHARD_TEST_HOLD"] = "0:0:600"
        try:
            res = run_sharded(
                "markovStateTransitionModel", conf, [corpus["seq"]],
                str(tmp_path / "wg_sharded.txt"), procs=2, factor=2,
                policy=StragglerPolicy(mirror_floor_s=0.3,
                                       mirror_multiple=2.0,
                                       poll_s=0.02, exit_grace_s=2.0),
                timeout_s=120.0)
        finally:
            del os.environ["AVENIR_SHARD_TEST_HOLD"]
        # the held worker never committed (killed at grace expiry), so
        # no dedup — but its block WAS redundantly completed and the
        # bytes are right
        assert res.counters["Shard:MirroredBlocks"] >= 1.0
        assert open(solo, "rb").read() == \
            open(str(tmp_path / "wg_sharded.txt"), "rb").read()

    def test_cli_shard_flag(self, corpus, tmp_path):
        import subprocess
        import sys

        from avenir_tpu.runner import run_job

        conf_path = str(tmp_path / "mi.properties")
        with open(conf_path, "w") as fh:
            fh.write(f"mut.feature.schema.file.path={corpus['schema']}\n")
        solo = str(tmp_path / "cli_solo.txt")
        run_job("mutualInformation", conf_path, [corpus["csv"]], solo)
        out = str(tmp_path / "cli_sharded.txt")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m", "avenir_tpu", "mutualInformation",
             "--shard", "2", "--conf", conf_path, corpus["csv"], out],
            capture_output=True, text=True, timeout=600, cwd=REPO,
            env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        assert row["counters"]["Shard:Blocks"] >= 2
        assert open(solo, "rb").read() == open(out, "rb").read()

    @pytest.mark.parametrize("job,combo,msg", [
        # --shard + --incremental composes for fold families now
        # (run_sharded_refresh); it stays a loud error ONLY for the
        # miners, whose per-k rounds re-scan the whole corpus
        ("frequentItemsApriori", ["--shard", "2", "--incremental"],
         "cannot compose for the miners"),
        ("candidateGenerationWithSelfJoin",
         ["--shard", "2", "--incremental"],
         "cannot compose for the miners"),
        ("mutualInformation", ["--shard", "2", "--autotune"],
         "does not support --autotune"),
    ])
    def test_shard_flag_combinations_rejected_loudly(self, job, combo,
                                                     msg):
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, "-m", "avenir_tpu", job,
             *combo, "in.csv", "out.txt"],
            capture_output=True, text=True, timeout=120, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=REPO + os.pathsep
                     + os.environ.get("PYTHONPATH", "")))
        assert proc.returncode != 0
        assert msg in proc.stderr

    def test_fold_block_fingerprints_the_folded_bytes(self, corpus,
                                                      tmp_path):
        # the sharded-refresh checkpoint contract: fps_out describes the
        # EXACT bytes the fold consumed, tiling [start, end) gap-free —
        # so a concurrent append AFTER the fold can never leak
        # never-folded content into the fingerprints
        import shutil

        from avenir_tpu.core import incremental as incr
        from avenir_tpu.dist.worker import fold_block
        from avenir_tpu.runner import _job_cfg, _schema, stream_fold_ops

        csv = str(tmp_path / "copy.csv")
        shutil.copy(corpus["csv"], csv)
        canonical, _p, cfg = _job_cfg(
            "mutualInformation",
            {"mut.feature.schema.file.path": corpus["schema"],
             "mut.stream.block.size.mb": "0.02",
             "mut.stream.sidecar.dir": str(tmp_path / "sc")})
        ops = stream_fold_ops(canonical)
        schema = _schema(cfg)
        size = os.path.getsize(csv)
        with open(csv, "rb") as fh:
            before = fh.read()
        fps = []
        fold_block(canonical, cfg, ops, schema, [csv], csv, 0, size,
                   fps_out=fps)
        # the concurrent-writer scenario: the file grows after the fold
        with open(csv, "a") as fh:
            fh.write("zz,77,1,2,3\n")
        assert len(fps) >= 2
        expect = 0
        for fp in fps:
            assert fp["offset"] == expect
            chunk = before[fp["offset"]:fp["offset"] + fp["length"]]
            assert fp["hash"] == incr.block_hash(chunk)
            expect += fp["length"]
        assert expect == size

    def test_sharded_refresh_checkpoint_from_worker_fps(self, tmp_path):
        # --shard + --incremental: the delta blocks' fingerprints come
        # from the workers' committed fps (never a coordinator re-read);
        # the extended checkpoint must verify cleanly on the next solo
        # refresh, and the artifact must match a solo refresh twin
        import shutil

        from avenir_tpu.data import churn_schema, generate_churn
        from avenir_tpu.dist.driver import run_sharded_refresh
        from avenir_tpu.runner import run_incremental

        rows = generate_churn(2000, seed=23, as_csv=True)
        cut = rows.rindex("\n", 0, len(rows) * 2 // 3) + 1
        csv = str(tmp_path / "churn.csv")
        with open(csv, "w") as fh:
            fh.write(rows[:cut])
        schema = str(tmp_path / "churn.json")
        churn_schema().save(schema)
        conf = {"mut.feature.schema.file.path": schema,
                "mut.stream.block.size.mb": "0.02",
                "mut.stream.sidecar.dir": str(tmp_path / "sc")}
        sd_shard = str(tmp_path / "state_shard")
        run_incremental("mutualInformation", conf, [csv],
                        str(tmp_path / "seed.txt"), state_dir=sd_shard)
        sd_solo = str(tmp_path / "state_solo")
        shutil.copytree(sd_shard, sd_solo)
        with open(csv, "a") as fh:
            fh.write(rows[cut:])
        solo = str(tmp_path / "solo.txt")
        run_incremental("mutualInformation", conf, [csv], solo,
                        state_dir=sd_solo)
        res = run_sharded_refresh(
            "mutualInformation", conf, [csv],
            str(tmp_path / "shard.txt"), procs=2,
            policy=StragglerPolicy(mirror_floor_s=60.0),
            state_dir=sd_shard)
        assert open(solo, "rb").read() == \
            open(str(tmp_path / "shard.txt"), "rb").read()
        assert res.counters["Shard:Workers"] == 2.0
        assert res.counters["Cache:DeltaBlocks"] >= 1.0
        # the sharded-extended checkpoint verifies end to end: the
        # follow-up solo refresh restores the WHOLE file warm
        again = run_incremental("mutualInformation", conf, [csv],
                                str(tmp_path / "again.txt"),
                                state_dir=sd_shard)
        assert again.counters["Cache:DeltaBlocks"] == 0.0
        assert again.counters["Resume:SkippedBytes"] == \
            float(os.path.getsize(csv))
        assert open(str(tmp_path / "again.txt"), "rb").read() == \
            open(solo, "rb").read()

    def test_sharded_refresh_missing_fps_fall_back_cold(self, tmp_path,
                                                        monkeypatch):
        # a crash between the state link and the fps publish leaves a
        # committed block with no fingerprints: the coordinator must
        # keep the PREVIOUS checkpoint (the merged carry already holds
        # that block — stamping it with partial fingerprints would
        # double-fold on the next refresh), so the next refresh
        # re-parses the delta — cold, never wrong
        import shutil

        from avenir_tpu.data import churn_schema, generate_churn
        from avenir_tpu.dist.driver import run_sharded_refresh
        from avenir_tpu.runner import run_incremental

        rows = generate_churn(1200, seed=29, as_csv=True)
        cut = rows.rindex("\n", 0, len(rows) // 2) + 1
        csv = str(tmp_path / "churn.csv")
        with open(csv, "w") as fh:
            fh.write(rows[:cut])
        schema = str(tmp_path / "churn.json")
        churn_schema().save(schema)
        conf = {"mut.feature.schema.file.path": schema,
                "mut.stream.block.size.mb": "0.02",
                "mut.stream.sidecar.dir": str(tmp_path / "sc")}
        sd = str(tmp_path / "state")
        run_incremental("mutualInformation", conf, [csv],
                        str(tmp_path / "seed.txt"), state_dir=sd)
        sd_solo = str(tmp_path / "state_solo")
        shutil.copytree(sd, sd_solo)
        with open(csv, "a") as fh:
            fh.write(rows[cut:])
        solo = str(tmp_path / "solo.txt")
        run_incremental("mutualInformation", conf, [csv], solo,
                        state_dir=sd_solo)
        # the coordinator sees no fps (workers still commit states
        # normally in their own processes)
        monkeypatch.setattr(BlockLedger, "load_fps",
                            lambda self, bid: None)
        res = run_sharded_refresh(
            "mutualInformation", conf, [csv],
            str(tmp_path / "shard.txt"), procs=2,
            policy=StragglerPolicy(mirror_floor_s=60.0), state_dir=sd)
        assert open(solo, "rb").read() == \
            open(str(tmp_path / "shard.txt"), "rb").read()
        assert res.counters["Cache:DeltaBlocks"] >= 1.0
        # checkpoint was NOT rewritten: the next solo refresh restores
        # the OLD one, re-parses the delta, and lands on the same bytes
        again = run_incremental("mutualInformation", conf, [csv],
                                str(tmp_path / "again.txt"),
                                state_dir=sd)
        assert again.counters["Cache:DeltaBlocks"] >= 1.0
        assert open(str(tmp_path / "again.txt"), "rb").read() == \
            open(solo, "rb").read()

    def test_lost_workers_raise_with_blocks_outstanding(self, corpus,
                                                        tmp_path):
        from avenir_tpu.dist import ShardError

        def kill_all(pids, root):
            import signal

            for pid in pids:
                os.kill(pid, signal.SIGKILL)

        with pytest.raises(ShardError, match="lost its workers"):
            run_sharded("mutualInformation",
                        {"mut.feature.schema.file.path":
                             corpus["schema"]},
                        [corpus["csv"]],
                        str(tmp_path / "dead.txt"), procs=2, factor=2,
                        worker_hook=kill_all)


# -------------------------------------------------------------- collective
class TestCollective:
    def test_cpu_gate_refuses_loudly(self):
        # jaxlib CPU refuses compiled multiprocess computation
        # (tests/test_multihost.py pins the backend message); the
        # collective merge must refuse at the gate, never silently
        # compute something else
        from avenir_tpu.dist.collective import (CollectiveUnavailable,
                                                allsum_carry,
                                                collective_ready)

        assert collective_ready() is False
        with pytest.raises(CollectiveUnavailable, match="CPU"):
            allsum_carry({"counts": __import__("numpy").zeros(3)})
