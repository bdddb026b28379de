"""Sharded-scan worker process: claim blocks, fold, commit states.

``python -m avenir_tpu.dist.worker <shard-root> <worker-id>`` — spawned
by :func:`avenir_tpu.dist.driver.run_sharded`, one process per worker.
The loop:

1. **Boot barrier** — write ``ready/w<i>`` once imports and the plan
   load are done, then wait for the coordinator's ``go`` file. The
   measured sharded wall starts at ``go``, so interpreter/jax boot
   (paid once per worker, concurrently) is no part of it.
2. **Home blocks** — claim and fold this worker's contiguous home run
   first (disk-sequential reads).
3. **Steal the tail** — when the home run is done, claim from the
   global unclaimed tail: a fast worker absorbs a slow one's
   never-started blocks with zero redundancy.
4. **Mirror stragglers** — when nothing is unclaimed but blocks remain
   uncommitted, consult the straggler detector: this worker's own
   per-block telemetry (``stream.read/parse/fold`` spans →
   :func:`avenir_tpu.tune.signals.extract_signals`) prices a block, and
   a peer's claim older than the policy multiple is folded REDUNDANTLY.
   The block ledger's first-commit-wins keeps the fold-exactly-once
   invariant; the rejected duplicate lands in ``Shard:DedupBlocks``.
5. **Per-k rounds** (miner plans, ``plan.per_k``) — the worker stays
   resident after pass 1, keeps its folded per-block sources (and
   their committed encoded-block caches) alive, and re-enters the SAME
   claim/steal/mirror loop once per candidate length against the
   level-namespaced ledger (``k<k>/b<id>``): the coordinator publishes
   an atomic token-space candidate manifest under
   ``<root>/candidates/``, the worker counts each claimed block's
   candidate supports by REPLAYING its own committed cache segments
   (zero CSV re-parses on the happy path; a stolen block re-folds its
   byte range once, then replays), and commits the per-block count
   vector first-commit-wins — so a block's counts fold into a level's
   merged support exactly once. ``final.json`` releases the worker.

Every block folds through the REAL streamed machinery: the registered
``StreamFoldOps`` factory builds the sink, ``SharedScan`` drives it (one
instrumentation point with the solo/fused/incremental paths), and the
carry crosses processes via the registered ``serialize_state`` — the
same ops the graftlint --merge auditor proves byte-exact every round.
"""

from __future__ import annotations

import io
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from avenir_tpu import obs as _obs
from avenir_tpu.core.atomic import (publish_bytes, publish_json,
                                    sched_point)
from avenir_tpu.dist.detect import (StragglerPolicy, mirror_after_s,
                                    mirror_after_wall_s)
from avenir_tpu.dist.ledger import BlockLedger
from avenir_tpu.dist.plan import ShardBlock, ShardPlan, load_plan

#: test-only chaos hook (cross-process, so an env var):
#: "worker:block:secs" makes that worker sleep that long after CLAIMING
#: the pass-1 block and before folding it; "worker:level:block:secs"
#: (level = "k2", "tids", ...) holds a per-k count block the same way —
#: deterministic stragglers for the dedup tests
#: (tests/test_dist.py::TestRunSharded)
_HOLD_ENV = "AVENIR_SHARD_TEST_HOLD"

#: the fold families whose finish() re-scans their inputs (the miners'
#: per-k passes): run_sharded distributes those passes as per-k count
#: rounds through the level-namespaced ledger (plan.per_k); the merge
#: auditor's in-process path instead restores their per-block states
#: against per-block SLICES of the corpus — see driver._restore_inputs
RESCAN_AT_FINISH = ("frequentItemsApriori", "candidateGenerationWithSelfJoin")


def _sidecar_range_feed(canonical: str, cfg, ops, schema, path: str,
                        start: int, end: int, block_bytes: int):
    """A write=False sidecar feed over one claimed byte range, or None.
    The ranged contract replays ALL of [start, end) from verified
    sidecar blocks or nothing — a worker never writes the shared
    sidecar (N processes racing an append would tear it) and never
    splices replay with cold parse mid-range; when the plan boundaries
    were snapped to sidecar block starts the whole range replays."""
    try:
        from avenir_tpu.native import sidecar as sc

        opts = sc.opts_from_cfg(cfg)
        if ops.kind == "dataset":
            return sc.dataset_blocks(opts, path, schema,
                                     cfg.field_delim_regex, block_bytes,
                                     byte_range=(start, end), write=False)
        return sc.byte_blocks(opts, path, cfg.field_delim_regex,
                              cfg.get_int("skip.field.count", 1),
                              block_bytes, byte_range=(start, end),
                              write=False)
    except Exception:
        return None


def fold_block(canonical: str, cfg, ops, schema, inputs: List[str],
               path: str, start: int, end: int,
               fps_out: Optional[list] = None):
    """Fold ONE plan block — the byte range ``[start, end)`` of
    ``path`` — through the registered fold sink, and return the fed
    fold. Newline-aligned plan blocks make the range self-contained:
    the LineRecordReader contract in the readers degrades to a plain
    slice read. When the whole range re-proves against the columnar
    sidecar, the fold streams replayed payloads instead of parsing the
    CSV (the fold sinks dispatch on payload type). Shared by the worker
    loop and the graftlint --merge sharded-steal leg, so the audited
    fold path IS the production one.

    ``fps_out`` (refresh plans) collects the content fingerprints of
    the EXACT chunks the fold consumed — the sidecar feed's verified
    hashes, or a hash of each raw block as it is read — tiling
    [start, end) gap-free. The coordinator extends the incremental
    checkpoint from these instead of re-reading the file, so a source
    appended to between this fold and the merge can never stamp
    never-folded bytes into the checkpoint."""
    from avenir_tpu.core import incremental as incr
    from avenir_tpu.core.stream import (CsvBlockReader, iter_byte_blocks,
                                        prefetched)
    from avenir_tpu.runner import _drive_fold

    fold = ops.factory(cfg, list(inputs), schema)
    block_bytes = int(cfg.get_float("stream.block.size.mb", 64.0)
                      * (1 << 20))
    feed = None
    if start < end:
        feed = _sidecar_range_feed(canonical, cfg, ops, schema, path,
                                   start, end, block_bytes)
    if feed is not None:
        def _sidecar_chunks():
            for off, length, hsh, payload in feed:
                if fps_out is not None:
                    fps_out.append({"offset": int(off),
                                    "length": int(length), "hash": hsh})
                if payload is not None:
                    yield payload
        chunks = _sidecar_chunks()
    elif fps_out is not None:
        reader = CsvBlockReader(path, schema, cfg.field_delim_regex,
                                block_bytes, byte_range=(start, end)) \
            if ops.kind == "dataset" else None

        def _fingerprinted_chunks():
            for off, data in prefetched(
                    iter_byte_blocks(path, block_bytes,
                                     byte_range=(start, end),
                                     with_offsets=True), depth=1):
                fps_out.append(incr.block_fingerprint(off, data))
                yield reader._parse(data) if reader is not None else data
        chunks = _fingerprinted_chunks()
    elif ops.kind == "dataset":
        chunks = iter(CsvBlockReader(path, schema, cfg.field_delim_regex,
                                     block_bytes, byte_range=(start, end)))
    else:
        chunks = iter_byte_blocks(path, block_bytes,
                                  byte_range=(start, end))
    _drive_fold(fold, chunks, canonical)
    return fold


def _hold(worker: int, block_id: int, level: Optional[str] = None) -> None:
    spec = os.environ.get(_HOLD_ENV, "")
    parts = spec.split(":")
    try:
        if len(parts) == 4:
            w, lvl, b, secs = parts
            if lvl != (level or ""):
                return
        else:
            w, b, secs = parts
            if level is not None:
                return
        if int(w) == worker and int(b) == block_id:
            time.sleep(float(secs))
    except ValueError:
        pass


class _Worker:
    def __init__(self, root: str, worker: int):
        self.root = root
        self.worker = worker
        self.plan: ShardPlan = load_plan(os.path.join(root, "plan.json"))
        self.policy = StragglerPolicy.from_dict(self.plan.policy)
        self.ledger = BlockLedger(root)
        self.per_k = bool(self.plan.per_k)
        self.stats = {"worker": worker, "claimed": 0, "stolen": 0,
                      "mirrored": 0, "dedup_rejected": 0, "folded": 0,
                      "perk_claimed": 0, "perk_stolen": 0,
                      "perk_mirrored": 0, "perk_dedup": 0,
                      "perk_folded": 0, "perk_levels": 0,
                      "scan_s": 0.0, "perk_s": 0.0}
        from avenir_tpu.runner import _job_cfg, stream_fold_ops

        self.canonical, self.prefix, cfg = _job_cfg(self.plan.job,
                                                    dict(self.plan.props))
        self.ops = stream_fold_ops(self.canonical)
        if self.canonical in RESCAN_AT_FINISH and not self.per_k:
            # legacy (non-per-k) sharded miner plans never run per-k
            # passes in the worker — spilling an encoded-block cache
            # per block would be pure waste. Per-k plans NEED the
            # cache: it is what the per-k count rounds replay.
            cfg.props[f"{self.prefix}.stream.encoded.cache"] = "false"
        self.cfg = cfg
        self.schema = None
        if self.ops.kind == "dataset":
            from avenir_tpu.runner import _schema

            self.schema = _schema(cfg)
        self.inputs = self.plan.input_paths()
        # ---- per-k state (miner plans only) ----
        self._folds: Dict[int, object] = {}    # block id -> kept fold
        self._miner = None
        if self.per_k:
            from avenir_tpu.runner import _build_miner

            self._miner = _build_miner(self.canonical, cfg)
        self._perk_wall = 0.0       # measured seconds over per-k blocks
        self._perk_done = 0         # ...the straggler detector's input
        #: the coordinator's pid at boot: per-k workers can only exit
        #: when the coordinator publishes the next manifest, so a
        #: coordinator that dies hard (SIGKILL/OOM — its finally never
        #: runs) must not leave workers polling forever; reparenting
        #: (getppid() change) is the death signal
        self._coord_pid = os.getppid()

    # ------------------------------------------------------- lifecycle
    def barrier(self, timeout_s: float = 300.0) -> None:
        ready = os.path.join(self.root, "ready")
        os.makedirs(ready, exist_ok=True)
        marker = os.path.join(ready, f"w{self.worker}")
        publish_bytes(str(os.getpid()).encode("utf-8"), marker)
        deadline = time.perf_counter() + timeout_s
        go = os.path.join(self.root, "go")
        while not os.path.exists(go):
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"worker {self.worker}: no go signal in {timeout_s}s")
            time.sleep(0.01)

    def write_stats(self, signals) -> None:
        self.stats["signals"] = signals.to_json()
        if self.per_k:
            # per-k replay folds only (keys >= 0): the tids slice folds
            # (negative keys) cover the same byte ranges again — summing
            # them would double-count the spill on emit.trans.id runs
            replay = [f for bid, f in self._folds.items() if bid >= 0]
            self.stats["cache_bytes"] = float(sum(
                f.src.cache_nbytes for f in replay))
            self.stats["cache_evicted"] = float(sum(
                f.src.cache_evicted_bytes for f in replay))
        path = os.path.join(self.root, "stats", f"w{self.worker}.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        publish_json(self.stats, path)

    # ------------------------------------------------------- fold path
    def _fold_and_commit(self, blk: ShardBlock) -> None:
        src = self.plan.inputs[blk.input]["path"]
        # refresh plans: fingerprint the exact chunks this fold reads so
        # the coordinator extends the checkpoint from folded bytes, not
        # from a post-hoc re-read a concurrent writer may have changed
        fps = [] if self.plan.record_fps else None
        fold = fold_block(self.canonical, self.cfg, self.ops, self.schema,
                          self.inputs, src, blk.start, blk.end,
                          fps_out=fps)
        if self.per_k:
            # seal NOW: commits this block's encoded spill cache, so the
            # per-k rounds replay it instead of re-parsing the CSV. The
            # serialized meta records sealed=True; the coordinator's
            # per-k merge reads only vocab/counts/n from it.
            fold._seal()
        blob = self.ops.serialize_state(fold)
        if self.ledger.commit(blk.id, self.worker, blob, fps=fps):
            self.stats["folded"] += 1
        else:
            self.stats["dedup_rejected"] += 1
        if self.per_k:
            # keep the fold (and its committed cache) for the per-k
            # rounds — even a dedup-rejected redundant fold is a usable
            # per-k replay source for this worker
            self._folds[blk.id] = fold
        else:
            close = getattr(getattr(fold, "src", None), "close", None)
            if close is not None:
                close()

    def _next_unclaimed(self, ledger: BlockLedger
                        ) -> Optional[Tuple[ShardBlock, bool]]:
        """Home blocks first, then the global unclaimed tail (a steal);
        returns (block, stolen) or None. One loop serves pass 1 and
        every per-k level — only the ledger namespace changes."""
        by_id = {b.id: b for b in self.plan.blocks}
        done = set(ledger.committed())
        claims = ledger.claims()
        home = [b.id for b in self.plan.blocks if b.home == self.worker]
        tail = [b.id for b in self.plan.blocks if b.home != self.worker]
        for bid in home + tail:
            if bid in done or bid in claims:
                continue
            if ledger.claim(bid, self.worker):
                blk = by_id[bid]
                return blk, blk.home != self.worker
        return None

    def _stale_peer_block(self, ledger: BlockLedger,
                          threshold: float) -> Optional[int]:
        """Oldest claimed-but-uncommitted peer block past the mirror
        threshold (never this worker's own claim), or None."""
        n_blocks = len(self.plan.blocks)
        stale = ledger.stale_claims(n_blocks, threshold)
        claims = ledger.claims()   # ONE snapshot
        stale = [b for b in stale
                 if (claims.get(b) or {}).get("worker") != self.worker]
        return stale[0] if stale else None

    def run(self) -> None:
        self.barrier()
        by_id = {b.id: b for b in self.plan.blocks}
        t_run = time.perf_counter()
        sc0 = None
        try:
            from avenir_tpu.native import sidecar as _sc

            sc0 = _sc.counters_snapshot()
        except Exception:
            pass
        try:
            with _obs.capture() as rec:
                from avenir_tpu.tune.signals import extract_signals

                while True:
                    nxt = self._next_unclaimed(self.ledger)
                    if nxt is not None:
                        blk, stolen = nxt
                        self.stats["claimed"] += 1
                        if stolen:
                            self.stats["stolen"] += 1
                        _hold(self.worker, blk.id)
                        self._fold_and_commit(blk)
                        continue
                    pending = self.ledger.pending(len(self.plan.blocks))
                    if not pending:
                        break
                    # nothing unclaimed, blocks outstanding: the
                    # straggler detector prices a block from THIS
                    # worker's telemetry and mirrors any claim older
                    # than the policy multiple
                    if self.policy.mirror:
                        signals = extract_signals(rec.spans())
                        threshold = mirror_after_s(self.policy, signals,
                                                   self.stats["folded"])
                        bid = self._stale_peer_block(self.ledger,
                                                     threshold)
                        if bid is not None:
                            self.stats["mirrored"] += 1
                            self._fold_and_commit(by_id[bid])
                            continue
                    time.sleep(self.policy.poll_s)
                self.stats["scan_s"] = round(
                    time.perf_counter() - t_run, 4)
                if self.per_k:
                    self._run_per_k(by_id)
                    self.stats["perk_s"] = round(self._perk_wall, 4)
                # the parse-free-replay proof the coordinator surfaces:
                # this worker's own span record (how many blocks hit the
                # CSV parser vs the sidecar) plus the sidecar counter
                # delta — cross-process, so it rides the stats file
                spans = rec.spans()
                self.stats["parse_spans"] = sum(
                    1 for sp in spans if sp.name == "stream.parse")
                self.stats["replay_spans"] = sum(
                    1 for sp in spans
                    if sp.name == "stream.sidecar.replay")
                if sc0 is not None:
                    try:
                        now = _sc.counters_snapshot()
                        self.stats["sidecar_hit_blocks"] = \
                            now["hit_blocks"] - sc0["hit_blocks"]
                        self.stats["sidecar_delta_blocks"] = \
                            now["delta_blocks"] - sc0["delta_blocks"]
                    except Exception:
                        pass
                self.write_stats(extract_signals(spans))
        finally:
            for fold in self._folds.values():
                fold.src.close()

    # ------------------------------------------------------ per-k path
    def _load_manifest(self, path: str) -> Optional[Dict]:
        sched_point("cand.poll")
        try:
            with open(path) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None          # not published yet (writes are atomic)

    def _coordinator_gone(self) -> bool:
        """True when this worker was reparented — the coordinator died
        hard and no further manifest (or final.json) is ever coming."""
        return os.getppid() != self._coord_pid

    def _run_per_k(self, by_id: Dict[int, ShardBlock]) -> None:
        """The per-k rounds: follow the coordinator's candidate
        manifests in publish order (k2, k3, ..., optionally tids),
        claim/steal/mirror count blocks per level through the
        level-namespaced ledger, exit at final.json — or when the
        coordinator itself died (a hard-killed coordinator must not
        orphan workers polling for a manifest nobody will publish)."""
        cand_dir = os.path.join(self.root, "candidates")
        next_k = 2
        tids_done = False
        while True:
            man = self._load_manifest(
                os.path.join(cand_dir, f"k{next_k}.json"))
            if man is not None:
                self._count_level(f"k{next_k}", man, by_id)
                next_k += 1
                continue
            if not tids_done:
                man = self._load_manifest(
                    os.path.join(cand_dir, "tids.json"))
                if man is not None:
                    self._count_level("tids", man, by_id)
                    tids_done = True
                    continue
            if os.path.exists(os.path.join(cand_dir, "final.json")):
                return
            if self._coordinator_gone():
                raise RuntimeError(
                    f"worker {self.worker}: coordinator died mid per-k "
                    f"rounds (no final.json will come)")
            time.sleep(self.policy.poll_s)

    def _count_level(self, tag: str, man: Dict,
                     by_id: Dict[int, ShardBlock]) -> None:
        """One level's claim/steal/mirror loop — the pass-1 discipline
        against the ``ledger/<tag>/`` namespace, with the count fold
        (cache replay) in place of the pass-1 parse fold."""
        cands = [tuple(cd) for cd in man["cands"]]
        c_pad = int(man["c_pad"])
        mask = [str(t) for t in man.get("mask", [])]
        ledger = self.ledger.level(tag)
        n_blocks = len(self.plan.blocks)
        self.stats["perk_levels"] += 1
        while True:
            nxt = self._next_unclaimed(ledger)
            if nxt is not None:
                blk, stolen = nxt
                self.stats["perk_claimed"] += 1
                if stolen:
                    self.stats["perk_stolen"] += 1
                _hold(self.worker, blk.id, tag)
                self._count_and_commit(ledger, tag, blk, cands, c_pad,
                                       mask)
                continue
            if not ledger.pending(n_blocks):
                return
            if self.policy.mirror:
                threshold = mirror_after_wall_s(
                    self.policy, self._perk_wall, self._perk_done)
                bid = self._stale_peer_block(ledger, threshold)
                if bid is not None:
                    self.stats["perk_mirrored"] += 1
                    self._count_and_commit(ledger, tag, by_id[bid],
                                           cands, c_pad, mask)
                    continue
            if self._coordinator_gone():
                raise RuntimeError(
                    f"worker {self.worker}: coordinator died waiting "
                    f"on level {tag} commits")
            time.sleep(self.policy.poll_s)

    def _count_and_commit(self, ledger: BlockLedger, tag: str,
                          blk: ShardBlock, cands, c_pad: int,
                          mask: List[str]) -> None:
        t0 = time.perf_counter()
        if tag == "tids":
            from avenir_tpu.models.association import \
                collect_token_trans_ids

            # the id pass needs per-row ids (not in the cache): a
            # slice-backed source whose python feed sees exactly this
            # block's lines
            src = self._slice_source(blk, mask)
            tids = collect_token_trans_ids(src, cands, c_pad,
                                           self._miner.block)
            blob = json.dumps({"tids": tids}).encode()
        else:
            src = self._block_source(blk, mask)
            counts = self._count_supports(src, cands, c_pad)
            buf = io.BytesIO()
            np.savez(buf, counts=np.asarray(counts, np.int64))
            blob = buf.getvalue()
        self._perk_wall += time.perf_counter() - t0
        self._perk_done += 1
        if ledger.commit(blk.id, self.worker, blob):
            self.stats["perk_folded"] += 1
        else:
            self.stats["perk_dedup"] += 1

    def _count_supports(self, src, cands, c_pad: int) -> np.ndarray:
        if self.canonical == "frequentItemsApriori":
            from avenir_tpu.models.association import count_token_supports
        else:
            from avenir_tpu.models.sequence import count_token_supports
        return count_token_supports(src, cands, c_pad, self._miner.block)

    def _install_mask(self, src, mask: List[str]) -> None:
        """Install the global frequent-token mask once per source (the
        remap is the installed-flag: every level publishes the same
        mask, so re-installation is never needed)."""
        if src._remap is not None:
            return
        keep = [src.index[t] for t in mask if t in src.index]
        if self.canonical == "frequentItemsApriori":
            src.mask_items(keep)
        else:
            src.mask_tokens(keep)

    def _replayable(self, fold) -> bool:
        """True when per-k counts over this fold's source are correct:
        its committed cache can replay this block's rows, or the
        source is slice-backed (its re-parse paths see exactly the
        block's lines — the cache-off / budget-evicted fallback)."""
        if getattr(fold, "_perk_slice", False):
            return True
        cache = fold.src._cache
        return cache is not None and cache.valid

    def _block_source(self, blk: ShardBlock, mask: List[str]):
        """The per-block streaming source a per-k count folds over —
        this worker's kept pass-1 fold when its committed cache can
        replay (the zero-re-parse happy path), else a rebuilt fold
        (a stolen block: one pass-1 re-fold of the byte range, then
        cache replay for every later level)."""
        fold = self._folds.get(blk.id)
        if fold is None or not self._replayable(fold):
            if fold is not None:
                fold.src.close()
            fold = self._rebuild_fold(blk)
            self._folds[blk.id] = fold
        self._install_mask(fold.src, mask)
        return fold.src

    def _rebuild_fold(self, blk: ShardBlock):
        """Pass-1 re-fold of a block this worker never folded (stolen
        per-k work) or whose cache can no longer replay (budget
        eviction). When even the fresh cache cannot serve — the block
        alone exceeds the cache budget — fall back to a slice-file
        source whose re-parse paths see exactly the block's lines:
        correctness over throughput."""
        src_path = self.plan.inputs[blk.input]["path"]
        fold = fold_block(self.canonical, self.cfg, self.ops,
                          self.schema, self.inputs, src_path,
                          blk.start, blk.end)
        fold._seal()
        if self._replayable(fold):
            return fold
        fold.src.close()
        slice_path = self._slice_path(blk)
        fold = fold_block(self.canonical, self.cfg, self.ops,
                          self.schema, [slice_path], slice_path, 0,
                          os.path.getsize(slice_path))
        fold._seal()
        fold._perk_slice = True
        return fold

    def _slice_path(self, blk: ShardBlock) -> str:
        """Materialize (once) this block's bytes as a standalone file —
        legal because plan blocks are newline-aligned."""
        path = os.path.join(self.root, "slices",
                            f"w{self.worker}_b{blk.id}.bin")
        if os.path.exists(path):
            return path
        os.makedirs(os.path.dirname(path), exist_ok=True)
        src = self.plan.inputs[blk.input]["path"]
        with open(src, "rb") as fh:
            fh.seek(blk.start)
            data = fh.read(blk.end - blk.start)
        publish_bytes(data, path)
        return path

    def _slice_source(self, blk: ShardBlock, mask: List[str]):
        """A slice-backed source for the row-bearing passes (the tids
        level): its python feed parses exactly this block's lines, its
        vocabulary comes from a pass-1 fold of the same bytes (so
        token_code agrees with the count folds)."""
        slice_path = self._slice_path(blk)
        fold = fold_block(self.canonical, self.cfg, self.ops,
                          self.schema, [slice_path], slice_path, 0,
                          os.path.getsize(slice_path))
        fold._seal()
        key = -(blk.id + 1)     # kept for closing; never collides with
        old = self._folds.get(key)  # the per-k replay folds keyed >= 0
        if old is not None:
            old.src.close()
        self._folds[key] = fold
        self._install_mask(fold.src, mask)
        return fold.src


def worker_main(argv) -> int:
    from avenir_tpu.utils.devices import require_backend

    require_backend()
    root, worker = argv[0], int(argv[1])
    _Worker(root, worker).run()
    return 0


if __name__ == "__main__":
    sys.exit(worker_main(sys.argv[1:]))
