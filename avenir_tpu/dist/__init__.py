"""avenir-shard: multi-process sharded streaming with coded straggler
tolerance.

The streaming path, finally across processes: a shard planner
over-partitions inputs into newline-aligned byte-range blocks
(:mod:`avenir_tpu.dist.plan`), workers claim them through a
first-commit-wins block ledger (:mod:`avenir_tpu.dist.ledger`) — fast
workers steal the unclaimed tail, stragglers' in-flight blocks are
redundantly re-dispatched past a telemetry-derived threshold
(:mod:`avenir_tpu.dist.detect`) — and the coordinator merges committed
block states in plan order through the registered fold-state algebra
(:mod:`avenir_tpu.dist.driver`), byte-identical to the solo runner.
Miner jobs distribute END TO END: their per-k candidate rounds re-enter
the same claim/steal/mirror loop against level-namespaced ledgers
(``k<k>/b<id>``), workers counting by replaying their own committed
encoded-block caches while the coordinator only publishes candidate
manifests and merges supports. The TPU/GPU psum merge lives behind the
backend gate in :mod:`avenir_tpu.dist.collective`.

Held by ``tests/test_dist.py::TestRunSharded``: 2-process byte-identity
(single-pass families AND the miner per-k leg, with
``Shard:PerKRounds >= 1``), and a held straggler whose block is stolen,
folded redundantly and deduped (``Shard:DedupBlocks >= 1``) to the solo
bytes.
"""

from avenir_tpu.dist.detect import (StragglerPolicy, mirror_after_s,
                                    mirror_after_wall_s)
from avenir_tpu.dist.driver import (ShardError, merge_block_states,
                                    run_sharded)
from avenir_tpu.dist.ledger import BlockLedger
from avenir_tpu.dist.plan import (DEFAULT_FACTOR, PlanError, ShardBlock,
                                  ShardPlan, load_plan, plan_shards,
                                  write_json_atomic, write_plan)

__all__ = [
    "BlockLedger",
    "DEFAULT_FACTOR",
    "PlanError",
    "ShardBlock",
    "ShardError",
    "ShardPlan",
    "StragglerPolicy",
    "load_plan",
    "merge_block_states",
    "mirror_after_s",
    "mirror_after_wall_s",
    "plan_shards",
    "run_sharded",
    "write_json_atomic",
    "write_plan",
]
