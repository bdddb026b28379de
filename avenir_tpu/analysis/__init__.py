"""graftlint: AST-based JAX/TPU hazard analysis for this repo.

PR 1 won its miner speedups by hand-hunting accidental int64 temporaries,
host-sync points and recompile hazards; this package finds the same code
shapes mechanically (the Casper move, arXiv:1801.09802: treat the shapes
worth rewriting as a statically recognizable class, not archaeology).

Entry points:
  - ``python tools/graftlint.py <paths>`` / the ``graftlint`` console
    script (avenir_tpu.analysis.cli) — text or ``--json`` output;
    ``graftlint --ir`` runs the IR layer instead of source paths;
  - :func:`run_paths` — the in-process AST API (tests/test_graftlint.py
    runs it over the whole gated surface);
  - ``avenir_tpu.analysis.ir.run_ir`` — the IR layer: jaxpr rules +
    the distributed-family collective-payload audit over the kernel
    manifest (``avenir_tpu.analysis.manifest``). Imported lazily, never
    from this package root: AST mode must not pull in jax;
  - ``avenir_tpu.analysis.flow.run_flow`` — the flow layer
    (``graftlint --flow``): interprocedural concurrency/determinism
    rules over the host streaming surface + the chunk-invariance audit
    of the manifest's streamed fold kernels (jax pulled in only when
    the audit actually runs);
  - ``avenir_tpu.analysis.mem.run_mem`` — the mem layer
    (``graftlint --mem``): memory-footprint rules + the analytic
    footprint model and its mechanical RSS auditor, which proves the
    model against sampled peak RSS for every streamed job at >= 2
    block sizes (``mem.memory_manifest()`` exports the machine-
    readable admission oracle);
  - ``avenir_tpu.analysis.merge.run_merge`` — the merge layer
    (``graftlint --merge``): fold-state merge-algebra rules + the
    mechanical shard-merge/resume auditor, which proves every streamed
    job's carry merges across P ∈ {2, 4} shards and checkpoint-resumes
    byte-identically through the registered ``runner.StreamFoldOps``;
  - ``avenir_tpu.analysis.proto.run_proto`` — the proto layer
    (``graftlint --proto``): shared-filesystem protocol-discipline
    rules + the commit-point crash auditor, which hard-kills a real
    publish per registered commit site and proves recovery
    byte-identical;
  - ``avenir_tpu.analysis.race.run_race`` — the race layer
    (``graftlint --race``): cross-process race rules + the
    deterministic-interleaving explorer, which steps two real actor
    subprocesses through every registered interleave site's
    ``sched_point`` schedule space and proves exactly-one-winner /
    conservation / solo byte-identity per schedule, every failure a
    replayable ``--schedule`` trace;
  - ``avenir_tpu.analysis.keys.run_keys`` — the keys layer
    (``graftlint --keys``): cache-key completeness rules + the
    stale-serve perturbation auditor, which seeds every registered
    key site's cache, moves each registered input dimension one at a
    time, and proves view-affecting changes move the key with served
    bytes equal to a cold recompute, view-neutral changes warm-hit
    byte-identically, and version-skewed manifests refuse-and-go-cold
    (``graftlint --all`` runs all eight tiers with one worst-of exit;
    ``--all --parallel`` fans them out as subprocesses);
  - ``graftlint_baseline.txt`` — the allowlist: accepted findings keyed
    by ``path::rule::scope`` with a one-line justification each, shared
    by both modes.

See docs/graftlint.md for the rule catalogs and allowlisting policy.
"""

from avenir_tpu.analysis.engine import (Finding, Report, default_baseline_path,
                                        load_baseline, run_paths)
from avenir_tpu.analysis.rules import ALL_RULES, rule_ids

__all__ = ["Finding", "Report", "run_paths", "load_baseline",
           "default_baseline_path", "ALL_RULES", "rule_ids"]
