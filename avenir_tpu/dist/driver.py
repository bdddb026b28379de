"""Sharded streaming driver: N worker processes, one merged artifact.

:func:`run_sharded` is the multi-process sibling of ``run_job`` /
``run_shared`` / ``run_incremental``: same registered jobs, same conf
surface, same artifact contract — byte-identical output to the solo
runner — but the STREAMING pass runs across ``procs`` worker processes
on this host. The machinery:

1. The shard planner over-partitions every input into newline-aligned
   byte-range blocks (``factor`` × ``procs``) and publishes the atomic
   plan manifest.
2. Workers (:mod:`avenir_tpu.dist.worker`) claim blocks through the
   block ledger — home run first, then stealing the unclaimed tail —
   fold each block through the registered ``StreamFoldOps`` sink, and
   commit the serialized carry first-commit-wins. Stragglers' in-flight
   blocks are redundantly re-dispatched past the telemetry-derived
   threshold; the ledger dedups, because every fold family is
   NON-idempotent (the merge auditor's overlap probe) and a block must
   fold into the final state exactly once.
3. The coordinator restores every committed block state with the
   registered ``restore_state``, merges them IN PLAN ORDER with the
   registered ``merge_states`` (the algebra graftlint --merge proves
   byte-exact for merge chains every round), and finishes the fold once
   — CPU path. The cross-process collective merge
   (``jax.make_array_from_process_local_data`` + psum) lives behind the
   backend gate in :mod:`avenir_tpu.dist.collective` and is exercised
   on TPU/GPU rounds only: jaxlib's CPU backend refuses compiled
   multiprocess computation (tests/test_multihost.py pins the
   limitation).

**Miner jobs run their per-k candidate rounds distributed too**
(``plan.per_k``): after the pass-1 merge the coordinator does ZERO
candidate counting itself. It thresholds the merged k=1 supports,
publishes each level's candidates as an atomic token-space manifest
(``<root>/candidates/k<k>.json`` — candidates translate per block via
``token_code``), and the resident workers re-enter the claim/steal/
mirror loop against the level-namespaced ledger (``k<k>/b<id>``),
counting each claimed block's candidate supports by replaying their
own committed encoded-block cache segments (no CSV re-parse on the
happy path). The coordinator merges each level's per-block count
vectors through ``merge_support_counts`` — the same reducer algebra
``mine_stream_merged`` uses, driven through the miner's OWN
``_merged_rounds`` control loop, so the kept sets and counts are
identical to the in-process sharded miner by construction — prunes,
publishes k+1, and releases the workers with ``final.json`` when the
frontier empties.

Every sharded JobResult carries the shard counters next to the standard
streamed set: ``Shard:Blocks`` (plan blocks), ``Shard:StolenBlocks``
(claims outside the claimant's home run, across every ledger
namespace), ``Shard:DedupBlocks`` (rejected duplicate commits across
every namespace — redundancy that actually fired), ``Shard:MergeMs``
(restore+merge wall), and — miner jobs — ``Shard:PerKRounds`` (the
distributed candidate-counting levels) and ``Shard:PerKBlocks`` (the
per-level block commits merged).
"""

from __future__ import annotations

import io
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from avenir_tpu import obs as _obs
from avenir_tpu.core.atomic import publish_bytes, sched_point
from avenir_tpu.dist.detect import StragglerPolicy
from avenir_tpu.dist.ledger import BlockLedger
from avenir_tpu.dist.plan import (DEFAULT_FACTOR, ShardPlan, plan_shards,
                                  write_json_atomic, write_plan)
from avenir_tpu.dist.worker import RESCAN_AT_FINISH
from avenir_tpu.utils.devices import checkout_root, cpu_children_env


class ShardError(RuntimeError):
    """A sharded run that lost workers or blocks."""


def _worker_env() -> Dict[str, str]:
    env = cpu_children_env(dict(os.environ), "--shard")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (checkout_root(), env.get("PYTHONPATH")) if p)
    return env


def _sidecar_snap(canonical: str, cfg, ops,
                  inputs: Sequence[str], procs: int, factor: int,
                  schema=None) -> Optional[List[Optional[List[int]]]]:
    """Per-input sidecar block-start offsets for the shard planner to
    snap its cuts to — only for inputs whose VERIFIED sidecar coverage
    is at least as fine as the plan (>= procs*factor blocks; a coarser
    sidecar would collapse plan blocks together and starve workers).
    None when no input qualifies: the planner keeps its newline scan
    and the workers fold cold, exactly the pre-sidecar behavior."""
    try:
        from avenir_tpu.native import sidecar as sc

        opts = sc.opts_from_cfg(cfg)
        if opts is None:
            return None
        block_bytes = int(cfg.get_float("stream.block.size.mb", 64.0)
                          * (1 << 20))
        delim = cfg.field_delim_regex
        if ops.kind == "dataset" and schema is None:
            from avenir_tpu.runner import _schema

            schema = _schema(cfg)
        snap: List[Optional[List[int]]] = []
        for path in inputs:
            if ops.kind == "dataset":
                dirpath = sc.dataset_dir(opts, path, schema, delim,
                                         block_bytes)
            else:
                dirpath = sc.bytes_dir(
                    opts, path, delim,
                    cfg.get_int("skip.field.count", 1), block_bytes)
            offs = sc.verified_offsets(dirpath, path, block_bytes)
            snap.append(offs if len(offs) >= procs * factor else None)
        return snap if any(s is not None for s in snap) else None
    except Exception:
        return None


def _restore_inputs(canonical: str, plan: ShardPlan, block,
                    inputs: Sequence[str], workdir: str) -> List[str]:
    """The input list a restored block state folds/finishes against.
    The miners' ``finish()`` re-scans its inputs per itemset length, so
    each of their block states must see exactly ITS block's lines — a
    byte slice of the input, legal because plan blocks are
    newline-aligned. Every other family's finish never re-reads inputs,
    so the real input list (better error messages, zero extra disk)
    is kept. (run_sharded's own miner path distributes the per-k
    rounds instead and never takes this slice; the graftlint --merge
    sharded-steal leg's in-process merge still does.)"""
    if canonical not in RESCAN_AT_FINISH:
        return list(inputs)
    src = plan.inputs[block.input]["path"]
    slice_path = os.path.join(workdir, f"slice_b{block.id}.bin")
    if not os.path.exists(slice_path):
        with open(src, "rb") as fh:
            fh.seek(block.start)
            data = fh.read(block.end - block.start)
        publish_bytes(data, slice_path)
    return [slice_path]


def merge_block_states(canonical: str, cfg, ops, plan: ShardPlan,
                       states: Dict[int, bytes], inputs: Sequence[str],
                       workdir: str, schema=None):
    """Restore every committed block state and merge IN PLAN ORDER —
    the coordinator's half of the dedup contract (exactly one state per
    block id ever reaches this table) and the merge-algebra chain the
    auditor proves byte-exact. Returns the merged fold, ready for
    ``finish()``. Shared with the graftlint --merge sharded-steal leg."""
    merged = None
    for blk in plan.blocks:
        if blk.id not in states:
            raise ShardError(f"block {blk.id} has no committed state")
        rins = _restore_inputs(canonical, plan, blk, inputs, workdir)
        fold = ops.restore_state(cfg, rins, states[blk.id], schema=schema)
        merged = fold if merged is None else ops.merge_states(merged, fold)
    if merged is None:
        raise ShardError("shard plan has no blocks")
    return merged


# ----------------------------------------------------------- per-k rounds
def _miner_scan_state(blob: bytes):
    """(vocab, k=1 counts, row count) out of one committed pass-1 miner
    block state — the npz ``serialize_state`` wrote; the per-k merge
    needs only the discovery triple, never a rebuilt fold."""
    with np.load(io.BytesIO(blob), allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
        counts = np.asarray(z["counts"], np.int64)
    return list(meta["vocab"]), counts, int(meta["n"])


def _level_counts(blob: bytes) -> np.ndarray:
    with np.load(io.BytesIO(blob), allow_pickle=False) as z:
        return np.asarray(z["counts"], np.int64)


def _level_tids(blob: bytes) -> List[List[str]]:
    return json.loads(blob.decode("utf-8"))["tids"]


def publish_candidates(cand_dir: str, name: str, man: dict) -> str:
    """Publish one per-k candidates manifest (``k<k>.json`` / ``tids
    .json`` / ``final.json``) into `cand_dir` — the coordinator's side
    of the manifest-vs-worker-poll seam the race auditor steps."""
    path = os.path.join(cand_dir, f"{name}.json")
    sched_point("cand.publish")
    write_json_atomic(man, path)
    return path


def _wait_commits(ledger: BlockLedger, n_blocks: int, workers, logs: str,
                  deadline: float, poll_s: float) -> None:
    """Wait until every block id is committed in ``ledger``'s
    namespace; raise when every worker died or the deadline passed."""
    while True:
        done = len(ledger.committed())
        if done >= n_blocks:
            return
        if not any(p.poll() is None for _log, p in workers):
            _raise_workers_dead(workers, logs, done, n_blocks)
        if time.perf_counter() > deadline:
            raise ShardError(
                f"sharded scan incomplete at run deadline "
                f"({done}/{n_blocks} blocks committed in namespace "
                f"{ledger.ns or 'pass-1'})")
        time.sleep(poll_s)


def _coordinate_per_k(canonical: str, cfg, plan: ShardPlan,
                      ledger: BlockLedger, root: str, workers,
                      logs: str, deadline: float,
                      policy: StragglerPolicy) -> Dict:
    """The miners' distributed per-k rounds, coordinator half: merge
    the committed pass-1 block states into the global k=1 supports,
    then drive the miner's OWN ``_merged_rounds`` control loop with a
    count function that publishes each level's candidate manifest,
    waits for every block's first-committed count vector in the
    level-namespaced ledger, and merges them via
    ``merge_support_counts``. Zero coordinator-side candidate
    counting; the counts — and therefore the kept sets — are the
    in-process ``mine_stream_merged``'s by construction."""
    from avenir_tpu.models.association import (frequent_tokens,
                                               merge_support_counts)
    from avenir_tpu.runner import _build_miner

    t_perk = t0 = time.perf_counter()
    blocks_meta = []
    committed = set(ledger.committed())
    for blk in plan.blocks:
        if blk.id not in committed:
            raise ShardError(
                f"block {blk.id} has no committed pass-1 state")
        blocks_meta.append(_miner_scan_state(ledger.load_state(blk.id)))
    n = sum(nb for _v, _c, nb in blocks_meta)
    support1 = merge_support_counts(
        *[{vocab[i]: int(counts[i]) for i in range(len(vocab))}
          for vocab, counts, _nb in blocks_meta])
    miner = _build_miner(canonical, cfg)
    # the mask every per-block source installs before counting — the
    # global frequent-token frontier, same rule mine_stream_merged
    # masks its shard sources with
    mask = frequent_tokens(support1, miner.support_threshold * n)
    stats = {"rounds": 0, "blocks": 0, "tags": [],
             "merge_s": time.perf_counter() - t0}

    cand_dir = os.path.join(root, "candidates")
    os.makedirs(cand_dir, exist_ok=True)
    n_blocks = len(plan.blocks)

    def run_level(tag: str, cands, c_pad: int, parse_state):
        lk = ledger.level(tag)
        publish_candidates(
            cand_dir, tag,
            {"tag": tag, "job": canonical, "mask": mask,
             "cands": [list(cd) for cd in cands], "c_pad": int(c_pad)})
        _wait_commits(lk, n_blocks, workers, logs, deadline,
                      policy.poll_s)
        t1 = time.perf_counter()
        payloads = [parse_state(lk.load_state(bid))
                    for bid in range(n_blocks)]
        stats["merge_s"] += time.perf_counter() - t1
        stats["blocks"] += n_blocks
        stats["tags"].append(tag)
        return payloads

    def count_level(k: int, cands, c_pad: int) -> np.ndarray:
        payloads = run_level(f"k{k}", cands, c_pad, _level_counts)
        t1 = time.perf_counter()
        merged = merge_support_counts(
            *[dict(zip(cands, p)) for p in payloads])
        out = np.array([int(merged.get(cd, 0)) for cd in cands],
                       np.int64)
        stats["merge_s"] += time.perf_counter() - t1
        stats["rounds"] += 1
        return out

    if canonical == "frequentItemsApriori":
        rounds = miner._merged_rounds(support1, n, count_level)
        tids = None
        if miner.emit_trans_id:
            all_sets = [cd for _k, sets_k, _c in rounds
                        for cd in sets_k]
            tids = [[] for _ in all_sets]
            if all_sets:
                c_pad = max(64, 1 << (len(all_sets) - 1).bit_length())
                payloads = run_level("tids", all_sets, c_pad,
                                     _level_tids)
                for p in payloads:    # plan order == corpus order
                    for ci in range(len(all_sets)):
                        tids[ci].extend(p[ci])
        levels = miner._pack_merged_rounds(rounds, n, tids)
    else:
        levels = miner._merged_rounds(support1, n, count_level)
    # release the workers: no further manifests are coming
    publish_candidates(cand_dir, "final",
                       {"done": True, "rounds": stats["rounds"]})
    return {"levels": levels, "n": n, "rounds": stats["rounds"],
            "blocks": stats["blocks"], "tags": stats["tags"],
            "merge_s": stats["merge_s"],
            "perk_s": time.perf_counter() - t_perk}


def run_sharded(name: str, conf, inputs: Sequence[str], output: str,
                procs: int = 2, factor: int = DEFAULT_FACTOR,
                shard_root: Optional[str] = None,
                policy: Optional[StragglerPolicy] = None,
                pin_cores: Optional[Sequence[int]] = None,
                worker_hook: Optional[Callable] = None,
                timeout_s: float = 7200.0) -> "JobResult":
    """Run one registered streamed job across ``procs`` worker
    processes — byte-identical artifact to ``run_job``, wall clock
    scaled by the host's process parallelism (miner jobs: BOTH the
    pass-1 scan and every per-k candidate round run distributed).

    ``worker_hook(pids, root)`` is the chaos/test tap, called once the
    workers are spawned (before the go barrier releases them) — the
    SIGSTOP chaos leg arms its watcher here. ``pin_cores`` pins worker
    i to core ``pin_cores[i % len]`` (the fleet convention: one core
    per worker makes a same-box N-vs-1 comparison measure scale-out,
    not XLA's intra-op oversubscription)."""
    from avenir_tpu.runner import (JobResult, _finish_fold, _job_cfg,
                                   finish_miner_levels, stream_fold_ops)

    canonical, prefix, cfg = _job_cfg(name, conf)
    ops = stream_fold_ops(canonical)
    policy = policy or StragglerPolicy()
    worker_env = _worker_env()      # refuses before anything is planned
    root = shard_root or tempfile.mkdtemp(prefix="avenir_shard_")
    own_root = shard_root is None
    procs = max(int(procs), 1)
    per_k = canonical in RESCAN_AT_FINISH
    try:
        plan = plan_shards(list(inputs), procs, factor,
                           policy=policy.to_dict(),
                           snap=_sidecar_snap(canonical, cfg, ops,
                                              list(inputs), procs,
                                              factor))
        plan.job = canonical
        plan.prefix = prefix
        plan.props = {k: str(v) for k, v in cfg.props.items()
                      if k != "__job_name__"}
        plan.per_k = per_k
        write_plan(plan, os.path.join(root, "plan.json"))
        ledger = BlockLedger(root)
        logs = os.path.join(root, "logs")
        os.makedirs(logs, exist_ok=True)

        workers = []
        for w in range(procs):
            preexec = None
            if pin_cores and hasattr(os, "sched_setaffinity"):
                core = pin_cores[w % len(pin_cores)]
                preexec = (lambda c=core: os.sched_setaffinity(0, {c}))
            log = open(os.path.join(logs, f"w{w}.log"), "ab")
            workers.append((log, subprocess.Popen(
                [sys.executable, "-m", "avenir_tpu.dist.worker",
                 root, str(w)],
                stdout=log, stderr=log, env=worker_env,
                cwd=checkout_root(), preexec_fn=preexec)))
        mined = None
        try:
            if worker_hook is not None:
                worker_hook([p.pid for _log, p in workers], root)
            # boot barrier: the measured scan starts when every worker
            # has finished its (concurrent) interpreter+jax boot — the
            # solo arm's convention too (its child times run_job, not
            # imports), so the A/B compares scans, not boots
            deadline = time.perf_counter() + timeout_s
            ready = os.path.join(root, "ready")
            while True:
                try:
                    n_ready = len(os.listdir(ready))
                except OSError:
                    n_ready = 0
                if n_ready >= procs:
                    break
                _reap_check(workers, ledger, plan, logs)
                if time.perf_counter() > deadline:
                    raise ShardError(
                        f"{n_ready}/{procs} workers ready within "
                        f"{timeout_s}s")
                time.sleep(0.01)
            t_scan = time.perf_counter()
            publish_bytes(b"go", os.path.join(root, "go"))

            n_blocks = len(plan.blocks)
            if per_k:
                # pass 1: wait for every block's committed state — the
                # workers stay resident for the per-k rounds
                _wait_commits(ledger, n_blocks, workers, logs,
                              deadline, policy.poll_s)
                mined = _coordinate_per_k(canonical, cfg, plan, ledger,
                                          root, workers, logs, deadline,
                                          policy)
            # once the scan is complete (pass 1 for single-pass
            # families; final.json published for miners), straggling
            # workers get a BOUNDED grace to exit on their own — long
            # enough for a woken straggler to finish its in-flight fold
            # and record the rejected duplicate in the dedup counters,
            # short enough that a permanently wedged worker (the
            # mirroring exists to survive) cannot hold a finished scan
            # hostage for the run timeout; past it the finally kills
            # the stragglers and the merge proceeds
            grace_until = None
            while True:
                alive = [p for _log, p in workers if p.poll() is None]
                done = len(ledger.committed())
                if per_k or done >= n_blocks:
                    if not alive:
                        break
                    if grace_until is None:
                        grace_until = time.perf_counter() \
                            + policy.exit_grace_s
                    elif time.perf_counter() > grace_until:
                        break
                elif not alive:
                    _raise_workers_dead(workers, logs, done, n_blocks)
                if time.perf_counter() > deadline:
                    raise ShardError(
                        f"sharded scan incomplete after {timeout_s}s "
                        f"({done}/{n_blocks} blocks committed)")
                time.sleep(0.02)
        finally:
            for log, proc in workers:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
                log.close()

        # ------------------------------------------------------- merge
        stats = _worker_stats(root, procs)
        if per_k:
            # the levels are already merged (per-k rounds); only the
            # artifact write remains — zero coordinator-side counting
            merge_ms = mined["merge_s"] * 1e3
            t0 = _obs.now()
            res = finish_miner_levels(
                canonical, cfg, mined["levels"], mined["n"],
                time.perf_counter() - t_scan, output,
                extra_counters={
                    "Cache:SpillBytes": float(sum(
                        s.get("cache_bytes", 0) for s in stats)),
                    "Cache:EvictedBytes": float(sum(
                        s.get("cache_evicted", 0) for s in stats))})
            _obs.record("job.dispatch", t0, mode="sharded",
                        procs=procs, blocks=n_blocks,
                        perk_rounds=mined["rounds"], jobs=canonical)
        else:
            t_merge = time.perf_counter()
            states = {bid: ledger.load_state(bid)
                      for bid in ledger.committed()}
            schema = None
            if ops.kind == "dataset":
                from avenir_tpu.runner import _schema

                schema = _schema(cfg)
            merged = merge_block_states(canonical, cfg, ops, plan,
                                        states, list(inputs), root,
                                        schema=schema)
            merge_ms = (time.perf_counter() - t_merge) * 1e3
            if output:
                parent = os.path.dirname(os.path.abspath(output))
                os.makedirs(parent, exist_ok=True)
            t0 = _obs.now()
            res = _finish_fold(merged, output, canonical)
            _obs.record("job.dispatch", t0, mode="sharded", procs=procs,
                        blocks=n_blocks, jobs=canonical)

        by_id = {b.id: b for b in plan.blocks}
        ledgers = [ledger] + [ledger.level(tag)
                              for tag in (mined["tags"] if mined else ())]
        stolen = dups = 0
        for led in ledgers:
            dups += led.dup_count()
            stolen += sum(1 for bid, info in led.claims().items()
                          if bid in by_id
                          and by_id[bid].home != info["worker"])
        res.counters["Shard:Blocks"] = float(n_blocks)
        res.counters["Shard:StolenBlocks"] = float(stolen)
        res.counters["Shard:DedupBlocks"] = float(dups)
        res.counters["Shard:MergeMs"] = round(merge_ms, 3)
        res.counters["Shard:ScanSeconds"] = round(
            time.perf_counter() - t_scan, 4)
        res.counters["Shard:Workers"] = float(procs)
        if stats:
            res.counters["Shard:MirroredBlocks"] = float(
                sum(s.get("mirrored", 0) + s.get("perk_mirrored", 0)
                    for s in stats))
            _add_worker_sidecar_counters(res, stats)
        if per_k:
            res.counters["Shard:PerKRounds"] = float(mined["rounds"])
            res.counters["Shard:PerKBlocks"] = float(mined["blocks"])
            # the distributed per-k phase's wall (pass-1 merge through
            # final.json)
            res.counters["Shard:PerKSeconds"] = round(
                mined["perk_s"], 4)
        return res
    finally:
        if own_root:
            shutil.rmtree(root, ignore_errors=True)


def run_sharded_refresh(name: str, conf, inputs: Sequence[str],
                        output: str, procs: int = 2,
                        factor: int = DEFAULT_FACTOR,
                        shard_root: Optional[str] = None,
                        policy: Optional[StragglerPolicy] = None,
                        pin_cores: Optional[Sequence[int]] = None,
                        worker_hook: Optional[Callable] = None,
                        timeout_s: float = 7200.0,
                        state_dir: Optional[str] = None) -> "JobResult":
    """``--shard`` and ``--incremental`` composed: restore the last
    fold-carry checkpoint exactly like :func:`runner.run_incremental`
    (same store, same content-fingerprint gate, cold fallback on any
    doubt), then fold ONLY the verified prefix's delta tail — sharded
    across ``procs`` worker processes when there is one. The committed
    per-block delta states merge IN PLAN ORDER into the restored carry
    through the registered merge algebra, the delta blocks' content
    fingerprints extend the checkpoint, and the artifact is
    byte-identical to a solo incremental refresh (and therefore to a
    cold full scan).

    The miners stay a loud error: their per-k candidate rounds re-scan
    the whole corpus per level, so a 'delta refresh' of one is not an
    O(delta) operation and pretending otherwise would silently hide a
    full re-mine behind an incremental flag."""
    from avenir_tpu.runner import (_job_cfg, _note_sidecar_counters,
                                   _plan_finish, _prepare_incremental,
                                   _sidecar_counters, stream_fold_ops)

    canonical, prefix, cfg = _job_cfg(name, conf)
    if canonical in RESCAN_AT_FINISH:
        raise ShardError(
            f"{canonical} cannot refresh incrementally under --shard: "
            f"the miners' per-k rounds re-scan the whole corpus per "
            f"candidate length; run --shard (full re-mine) or "
            f"--incremental alone")
    ops = stream_fold_ops(canonical)
    policy = policy or StragglerPolicy()
    worker_env = _worker_env()      # refuses before anything is planned
    inputs = [str(p) for p in inputs]
    iplan = _prepare_incremental(canonical, cfg, inputs, output,
                                 state_dir)
    sc0 = _sidecar_counters()
    sizes = [os.path.getsize(p) for p in inputs]
    if all(w >= s for w, s in zip(iplan.watermarks, sizes)):
        # nothing appended anywhere: re-emit from the carry alone —
        # zero worker processes, zero bytes read
        res = _plan_finish(iplan)
        _note_sidecar_counters(canonical, res, sc0)
        res.counters["Shard:Blocks"] = 0.0
        res.counters["Shard:Workers"] = 0.0
        return res

    root = shard_root or tempfile.mkdtemp(prefix="avenir_refresh_")
    own_root = shard_root is None
    procs = max(int(procs), 1)
    try:
        plan = plan_shards(inputs, procs, factor,
                           policy=policy.to_dict(),
                           starts=list(iplan.watermarks),
                           snap=_sidecar_snap(canonical, cfg, ops,
                                              inputs, procs, factor,
                                              schema=iplan.schema))
        plan.job = canonical
        plan.prefix = prefix
        plan.props = {k: str(v) for k, v in cfg.props.items()
                      if k != "__job_name__"}
        plan.record_fps = True
        write_plan(plan, os.path.join(root, "plan.json"))
        ledger = BlockLedger(root)
        logs = os.path.join(root, "logs")
        os.makedirs(logs, exist_ok=True)
        workers = []
        for w in range(procs):
            preexec = None
            if pin_cores and hasattr(os, "sched_setaffinity"):
                core = pin_cores[w % len(pin_cores)]
                preexec = (lambda c=core: os.sched_setaffinity(0, {c}))
            log = open(os.path.join(logs, f"w{w}.log"), "ab")
            workers.append((log, subprocess.Popen(
                [sys.executable, "-m", "avenir_tpu.dist.worker",
                 root, str(w)],
                stdout=log, stderr=log, env=worker_env,
                cwd=checkout_root(), preexec_fn=preexec)))
        try:
            if worker_hook is not None:
                worker_hook([p.pid for _log, p in workers], root)
            deadline = time.perf_counter() + timeout_s
            ready = os.path.join(root, "ready")
            while True:
                try:
                    n_ready = len(os.listdir(ready))
                except OSError:
                    n_ready = 0
                if n_ready >= procs:
                    break
                _reap_check(workers, ledger, plan, logs)
                if time.perf_counter() > deadline:
                    raise ShardError(
                        f"{n_ready}/{procs} workers ready within "
                        f"{timeout_s}s")
                time.sleep(0.01)
            t_scan = time.perf_counter()
            publish_bytes(b"go", os.path.join(root, "go"))
            n_blocks = len(plan.blocks)
            _wait_commits(ledger, n_blocks, workers, logs, deadline,
                          policy.poll_s)
            grace_until = time.perf_counter() + policy.exit_grace_s
            while any(p.poll() is None for _log, p in workers) \
                    and time.perf_counter() < grace_until:
                time.sleep(0.02)
        finally:
            for log, proc in workers:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
                log.close()

        # ---- merge the delta INTO the restored carry, in plan order
        t_merge = time.perf_counter()
        states = {bid: ledger.load_state(bid)
                  for bid in ledger.committed()}
        delta = merge_block_states(canonical, cfg, ops, plan, states,
                                   inputs, root, schema=iplan.schema)
        iplan.fold = (ops.merge_states(iplan.fold, delta)
                      if iplan.hit_blocks > 0 else delta)
        # the delta blocks' fingerprints extend the checkpoint — the
        # WORKER-recorded fingerprints of the exact chunks each fold
        # consumed (ledger.load_fps), never a coordinator re-read: a
        # source appended to between a worker's fold and this merge
        # must not stamp never-folded bytes into the checkpoint. A
        # block whose fingerprints are missing or do not tile its
        # range (commit-crash window) poisons the whole extension: the
        # merged carry already contains that block, so a checkpoint
        # stamped without its fingerprints would double-fold it on the
        # next refresh — keep the PREVIOUS checkpoint instead (the next
        # refresh re-parses the delta: a cold fallback, never a wrong
        # one).
        gap = False
        for blk in plan.blocks:
            if blk.start >= blk.end:
                continue
            iplan.delta_blocks += 1
            if gap:
                continue
            fps = ledger.load_fps(blk.id)
            ok = bool(fps)
            if ok:
                expect = blk.start
                try:
                    for fp in fps:
                        if int(fp["offset"]) != expect:
                            ok = False
                            break
                        expect += int(fp["length"])
                except (KeyError, TypeError, ValueError):
                    ok = False
                ok = ok and expect == blk.end
            if not ok:
                gap = True
                continue
            iplan.fps[blk.input].extend(fps)
            iplan.watermarks[blk.input] = blk.end
        merge_ms = (time.perf_counter() - t_merge) * 1e3
        t0 = _obs.now()
        res = _plan_finish(iplan, checkpoint=not gap)
        _obs.record("job.dispatch", t0, mode="sharded-refresh",
                    procs=procs, blocks=n_blocks, jobs=canonical)
        _note_sidecar_counters(canonical, res, sc0)
        stats = _worker_stats(root, procs)
        by_id = {b.id: b for b in plan.blocks}
        res.counters["Shard:Blocks"] = float(n_blocks)
        res.counters["Shard:StolenBlocks"] = float(
            sum(1 for bid, info in ledger.claims().items()
                if bid in by_id and by_id[bid].home != info["worker"]))
        res.counters["Shard:DedupBlocks"] = float(ledger.dup_count())
        res.counters["Shard:MergeMs"] = round(merge_ms, 3)
        res.counters["Shard:ScanSeconds"] = round(
            time.perf_counter() - t_scan, 4)
        res.counters["Shard:Workers"] = float(procs)
        if stats:
            res.counters["Shard:MirroredBlocks"] = float(
                sum(s.get("mirrored", 0) for s in stats))
            _add_worker_sidecar_counters(res, stats)
        return res
    finally:
        if own_root:
            shutil.rmtree(root, ignore_errors=True)


def _add_worker_sidecar_counters(res, stats: List[Dict]) -> None:
    """Sum the workers' own sidecar/parse accounting into the result —
    the cross-process half of the parse-free-replay proof: a sharded
    run whose plan snapped to a warm sidecar reports Shard:ParseSpans
    == 0 and Sidecar:HitBlocks == the plan's block tally."""
    res.counters["Sidecar:HitBlocks"] = float(
        sum(s.get("sidecar_hit_blocks", 0) for s in stats))
    res.counters["Sidecar:DeltaBlocks"] = float(
        sum(s.get("sidecar_delta_blocks", 0) for s in stats))
    res.counters["Shard:ParseSpans"] = float(
        sum(s.get("parse_spans", 0) for s in stats))
    res.counters["Shard:ReplaySpans"] = float(
        sum(s.get("replay_spans", 0) for s in stats))


def _worker_stats(root: str, procs: int) -> List[Dict]:
    out = []
    for w in range(procs):
        try:
            with open(os.path.join(root, "stats", f"w{w}.json")) as fh:
                out.append(json.load(fh))
        except (OSError, ValueError):
            pass                  # a killed worker writes no stats
    return out


def _reap_check(workers, ledger, plan, logs: str) -> None:
    """Boot-phase liveness: a worker dead before the barrier is a
    config error the caller must see immediately."""
    if all(p.poll() is None for _log, p in workers):
        return
    _raise_workers_dead(workers, logs, len(ledger.committed()),
                        len(plan.blocks))


def _raise_workers_dead(workers, logs: str, done: int,
                        n_blocks: int) -> None:
    dead = [(i, p.returncode) for i, (_log, p) in enumerate(workers)
            if p.poll() is not None and p.returncode != 0]
    tails = []
    for i, rc in dead[:2]:
        try:
            with open(os.path.join(logs, f"w{i}.log"), "rb") as fh:
                tails.append(f"w{i} rc={rc}: "
                             + fh.read()[-800:].decode("utf-8", "replace"))
        except OSError:
            tails.append(f"w{i} rc={rc}: <no log>")
    raise ShardError(
        f"sharded scan lost its workers with {done}/{n_blocks} blocks "
        f"committed; dead={[(i, rc) for i, rc in dead]}\n"
        + "\n".join(tails))
