"""graftlint-mem: static memory-footprint analysis of the streaming layer,
plus the mechanical RSS/live-bytes auditor.

The flow tier (analysis/flow.py) proves streamed folds *deterministic*;
nothing yet proves them *admissible* — whether a scan stays under the
3,072MB RSS ceiling is only learned after it finishes. Both framework papers this repo leans on say
memory is the product once folds are vectorized: buffer sizing dominates
on SIMD-saturated MapReduce (arXiv:1309.0215) and ingest/buffer overhead
is the Spark-vs-MPI gap (arXiv:1811.04875). A resident multi-tenant job
server (the ROADMAP tentpole) therefore needs a memory *oracle*: predict
a job's peak footprint from its block size and schema BEFORE running it.

Two layers, mirroring the ir/flow split:

- **Mem rules** — lexical/structural shapes whose cost is O(corpus)
  instead of O(block): a fold carry that grows with rows seen
  (``mem-unbounded-carry``), a temporary that materializes the whole
  stream (``mem-corpus-scaled-temporary``), an encoded-block spill with
  no byte budget (``mem-cache-spill-unbudgeted``), and a 64-bit widening
  of a block-proportional array on a hot path
  (``mem-dtype-expansion-at-parse``).
- **Analytic footprint model + mechanical audit** —
  :func:`footprint_model` composes, per registered streamed job, the
  host-side byte terms (raw blocks in flight x prefetch depth,
  parse-time dtype expansion, CSR/region-mask transients, fold buffers,
  miner replay/packing pages) into a predicted peak; ``audit_footprint``
  then runs every ``manifest.stream_entries()`` job through the REAL
  runner while a sampler thread watches ``/proc/self/statm`` (and jax
  live buffers where the backend exposes them), asserting at >= 2 block
  sizes that the measured peak sits inside the documented tolerance
  band of the prediction — ``footprint_model_validated`` per job. The
  model is an ADMISSION BOUND: measured must not exceed predicted +
  slack, and predicted must not be vacuous (bounded multiple of
  measured). The byte-accounting hook in ``core.stream`` additionally
  proves the model's effective-block term against the raw blocks that
  actually flowed.

Tolerance policy (documented in docs/graftlint.md): at auditor scale
(about a 1MB proxy corpus) the band's job is to catch order-of-magnitude
model breakage and keep the oracle's mechanics proven every round; the
true model error is recorded at real scale by the
``Mem:PredictedPeakBytes`` / ``Mem:PeakRSS`` counters every streamed
job's result carries.

Findings flow through the shared engine (same ``path::rule::scope``
keys, same allowlist baseline); entry points: ``graftlint --mem``
(analysis/cli.py) or :func:`run_mem` in-process. A stream kernel that
fails to RUN (or a host without ``/proc``) raises :class:`MemAuditError`
— the CLI maps that to exit code 2; a footprint outside the band is a
finding under ``mem-footprint-model`` (exit 1): fix the model or the
job, never allowlist the drift.
"""

from __future__ import annotations

import ast
import math
import os
import shutil
import tempfile
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import (Callable, Dict, Iterator, List, Optional, Sequence, Set,
                    Tuple)

from avenir_tpu.analysis.engine import (BaselineEntry, Finding, ModuleContext,
                                        Report, apply_baseline,
                                        collect_findings)
from avenir_tpu.analysis.flow import _body_nodes, default_flow_paths

#: the audit's pseudo-rule id: a measured peak outside the model's band
#: surfaces as a finding under it (never allowlist one — a memory oracle
#: that mispredicts is worse than none: it admits jobs that OOM)
MEM_AUDIT_RULE = "mem-footprint-model"

#: allocator/compile-residue slack of the tolerance band (bytes): what a
#: warmed-up CPython+jax process may legitimately grow by during one
#: streamed job without the model being wrong (glibc arenas, numpy pool
#: growth, late XLA autotuning buffers)
AUDIT_SLACK_BYTES = 48 << 20
#: non-vacuity bound: predicted must stay within this multiple of
#: (measured + slack), or the "oracle" admits nothing useful
AUDIT_TIGHTNESS = 8.0
#: block sizes (MB) the audit measures at — two layouts whose dominant
#: model term (blocks in flight) differs 8x on the inflated proxy corpus
DEFAULT_AUDIT_BLOCKS_MB = (0.5, 0.0625)
#: the proxy corpus is byte-replicated up to this size so block-
#: proportional terms dominate schema constants at both audit layouts
AUDIT_CORPUS_BYTES = 1 << 20

#: iterator factories whose `for` loops are streamed chunk/fold loops for
#: the mem rules — wider than flow's set: the miners' per-k feeds
#: (chunks/packed_chunks/blocks) are exactly where corpus-scaled state
#: would hide
_MEM_FOLD_TAILS = {
    "double_buffered", "prefetched", "stream_job_inputs",
    "stream_job_lines", "stream_job_byte_blocks", "iter_csv_chunks",
    "iter_byte_blocks", "iter_line_blocks", "scan_encode_blocks",
    "chunks", "packed_chunks", "_dense_chunks", "_row_blocks",
    "_line_blocks", "blocks",
}

_64BIT_DTYPES = {"int64", "float64", "uint64", "complex128", "longdouble"}


class MemAuditError(RuntimeError):
    """A streamed job could not be prepared/run, or RSS is unobservable."""


# --------------------------------------------------------------------------
# shared AST helpers
# --------------------------------------------------------------------------
def _mem_fold_loops(ctx: ModuleContext) -> Iterator[ast.For]:
    """`for` statements iterating a streamed chunk source (the widened
    tail set above) — the loops whose per-iteration state must stay
    O(block)."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.For, ast.AsyncFor)):
            continue
        for sub in ast.walk(node.iter):
            if isinstance(sub, ast.Call):
                name = ctx.dotted(sub.func)
                if name is not None \
                        and name.rpartition(".")[2] in _MEM_FOLD_TAILS:
                    yield node
                    break


def _bind_key(node: ast.AST) -> Optional[str]:
    """Identifier key of a binding/receiver: plain names as ``name``,
    self-attributes as ``.attr`` (the flow tier's keying)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return "." + node.attr
    return None


def _is_empty_container(value: ast.AST) -> bool:
    if isinstance(value, (ast.List, ast.Dict, ast.Set)) \
            and not getattr(value, "elts", getattr(value, "keys", ())):
        return True
    if isinstance(value, ast.Call) and isinstance(value.func, ast.Name) \
            and value.func.id in ("list", "dict", "set") and not value.args:
        return True
    return False


def _empty_inits_before(owner: ast.AST, loop: ast.For) -> Set[str]:
    """Names bound to an EMPTY container in `owner` (not nested defs) at a
    statement starting before `loop` — the carries the loop could grow."""
    out: Set[str] = set()
    stack = list(ast.iter_child_nodes(owner))
    while stack:
        node = stack.pop()
        if node is loop or isinstance(node, (ast.FunctionDef,
                                             ast.AsyncFunctionDef,
                                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))
        if getattr(node, "lineno", 10 ** 9) >= loop.lineno:
            continue
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        else:
            continue
        if not _is_empty_container(node.value):
            continue
        for t in targets:
            key = _bind_key(t)
            if key is not None:
                out.add(key)
    return out


_GROW_METHODS = {"append", "extend", "update", "add"}
_DRAIN_METHODS = {"clear", "pop", "popitem", "popleft"}


def _loop_growths(loop: ast.For) -> Iterator[Tuple[str, ast.AST]]:
    """(carry key, mutation node) for every growth of a name/self-attr in
    the loop body: ``X.append/extend/update/add``, ``X += ...`` and
    ``X[k] = ...`` (a dict keyed by stream values grows too).
    Subscript receivers fall through to their base name, so
    ``tids[ci].append(...)`` charges ``tids``."""
    for node in _body_nodes(loop):
        if isinstance(node, ast.Call) and isinstance(node.func,
                                                     ast.Attribute) \
                and node.func.attr in _GROW_METHODS:
            base = node.func.value
            if isinstance(base, ast.Subscript):
                base = base.value
            key = _bind_key(base)
            if key is not None:
                yield key, node
        elif isinstance(node, ast.AugAssign) and isinstance(node.op, ast.Add):
            key = _bind_key(node.target)
            if key is not None:
                yield key, node
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    key = _bind_key(t.value)
                    if key is not None:
                        yield key, node


def _loop_drains(loop: ast.For) -> Set[str]:
    """Carry keys the loop body also RESETS or SHRINKS (reassignment,
    slice-reassignment, clear/pop, del): bounded buffers, not carries —
    the page buffer `buf.extend(rows); buf = buf[block_rows:]` shape."""
    out: Set[str] = set()
    for node in _body_nodes(loop):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                key = _bind_key(t)
                if key is not None:
                    out.add(key)
        elif isinstance(node, ast.Call) and isinstance(node.func,
                                                       ast.Attribute) \
                and node.func.attr in _DRAIN_METHODS:
            key = _bind_key(node.func.value)
            if key is not None:
                out.add(key)
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                base = t.value if isinstance(t, ast.Subscript) else t
                key = _bind_key(base)
                if key is not None:
                    out.add(key)
    return out


def _loop_owner(ctx: ModuleContext, loop: ast.For) -> ast.AST:
    owners = ctx.enclosing_functions(loop)
    return owners[0] if owners else ctx.tree


# --------------------------------------------------------------------------
# rules
# --------------------------------------------------------------------------
class MemRule:
    rule_id: str = ""
    description: str = ""
    hint: str = ""

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleContext, node: ast.AST, message: str,
                hint: Optional[str] = None) -> Finding:
        return Finding(ctx.path, getattr(node, "lineno", 1), self.rule_id,
                       message, hint or self.hint, ctx.scope_of(node))


class UnboundedCarryRule(MemRule):
    """A container initialized empty BEFORE a streamed fold loop and
    grown inside it (append/extend/update/``+=``/keyed assignment)
    without ever being drained in the loop. Its size tracks rows SEEN,
    not rows per chunk — the fold's host RSS is O(corpus) and the
    O(block) contract the 1B-row path advertises is silently gone.
    Buffers the loop also reassigns/slices/clears are bounded and stay
    silent."""

    rule_id = "mem-unbounded-carry"
    description = "fold carry grows with rows seen, not with the chunk"
    hint = ("fold a fixed-size sufficient statistic instead (counts, "
            "moments — the NaiveBayesModel.accumulate algebra), write "
            "per-chunk results out as you go, or drain the buffer inside "
            "the loop; allowlist only when the corpus-sized output IS the "
            "job's contract")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for loop in _mem_fold_loops(ctx):
            owner = _loop_owner(ctx, loop)
            carries = _empty_inits_before(owner, loop)
            if not carries:
                continue
            drains = _loop_drains(loop)
            seen: Set[str] = set()
            for key, node in _loop_growths(loop):
                if key not in carries or key in drains or key in seen:
                    continue
                seen.add(key)
                yield self.finding(
                    ctx, node,
                    f"`{key.lstrip('.')}` is grown once per streamed "
                    f"chunk and never drained: the fold carry scales "
                    f"with rows seen, so host RSS is O(corpus), not "
                    f"O(block)")


class CorpusScaledTemporaryRule(MemRule):
    """``np.concatenate``/``vstack``/``hstack``/``stack`` (or
    ``np.array``/``np.asarray``) over a list that a streamed fold loop
    appends to: one expression that materializes the WHOLE stream as a
    single array — the exact shape whose deletion was PR 1's biggest RSS
    win, reintroduced one level up."""

    rule_id = "mem-corpus-scaled-temporary"
    description = "temporary proportional to the full corpus in a streamed fold"
    hint = ("reduce per chunk instead of collecting (fold the statistic, "
            "write results incrementally); if a whole-stream array is "
            "truly required, the job is not streamable — say so in its "
            "contract and allowlist with that justification")

    _MATERIALIZERS = {"concatenate", "vstack", "hstack", "stack", "array",
                      "asarray"}

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for loop in _mem_fold_loops(ctx):
            owner = _loop_owner(ctx, loop)
            grown = {key for key, _ in _loop_growths(loop)} \
                - _loop_drains(loop)
            if not grown:
                continue
            for node in ast.walk(owner):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                name = ctx.dotted(node.func)
                if name is None:
                    continue
                mod, _, func = name.rpartition(".")
                if mod not in ("numpy", "jax.numpy") \
                        or func not in self._MATERIALIZERS:
                    continue
                arg = node.args[0]
                key = _bind_key(arg)
                if key in grown:
                    yield self.finding(
                        ctx, node,
                        f"np.{func}(`{key.lstrip('.')}`) materializes "
                        f"every streamed chunk as one array — a "
                        f"corpus-proportional temporary inside a "
                        f"streamed fold")


class CacheSpillUnbudgetedRule(MemRule):
    """An ``EncodedBlockCache`` constructed without an explicit
    ``byte_budget``. The spill cache writes region-compacted codes for
    EVERY block of the corpus; unbudgeted, a 1B-row scan spills O(corpus)
    bytes to disk (and the job server's cache pool grows without bound).
    The budget is cheap to pass — the cache evicts whole
    least-recently-replayed sources atomically when it is exceeded."""

    rule_id = "mem-cache-spill-unbudgeted"
    description = "EncodedBlockCache spill with no byte budget"
    hint = ("pass byte_budget= (the stream.encoded.cache.budget.mb "
            "config key is the job surface; native.ingest."
            "DEFAULT_CACHE_BUDGET_BYTES is the generous default), so "
            "the spill is bounded and evictable")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.dotted(node.func)
            if name is None \
                    or name.rpartition(".")[2] != "EncodedBlockCache":
                continue
            if len(node.args) >= 3 or any(kw.arg == "byte_budget"
                                          for kw in node.keywords):
                continue
            yield self.finding(
                ctx, node,
                "EncodedBlockCache(...) without byte_budget: the "
                "encoded-block spill grows with the corpus, unbounded "
                "and unevictable")


class DtypeExpansionAtParseRule(MemRule):
    """A 64-bit widening of an existing array on a hot path (lexically
    inside a loop): ``x.astype(np.int64/np.float64/float/int)`` or
    ``np.asarray/np.array(x, dtype=<64-bit>)``. Between parse and device
    every element is supposed to NARROW (codes int32, measures float32);
    an 8-byte widening of a block-proportional array doubles the very
    buffers the streaming layer exists to keep small. Fresh 64-bit
    ALLOCATIONS (``np.zeros(..., np.int64)`` count tensors) are a
    deliberate exact-algebra choice and stay silent — this rule is about
    conversions."""

    rule_id = "mem-dtype-expansion-at-parse"
    description = "64-bit widening of an array on a streamed hot path"
    hint = ("keep block-proportional arrays narrow end to end (int32 "
            "codes, float32 measures — the csr_region_mask form); widen "
            "only O(model)-sized results, outside the loop, or allowlist "
            "with the bound that makes the widening noise")

    _WRAPPERS = {"numpy.asarray", "numpy.array", "jax.numpy.asarray",
                 "jax.numpy.array"}

    def _dtype_is_wide(self, ctx: ModuleContext, node: ast.AST) -> bool:
        name = ctx.dotted(node)
        if name is not None:
            tail = name.rpartition(".")[2]
            return tail in _64BIT_DTYPES or name in ("float", "int")
        return isinstance(node, ast.Constant) \
            and str(node.value) in _64BIT_DTYPES

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not ctx.in_loop(node):
                continue
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "astype" and node.args:
                if self._dtype_is_wide(ctx, node.args[0]):
                    yield self.finding(
                        ctx, node,
                        ".astype(<64-bit>) inside a loop doubles a "
                        "block-proportional array on the hot path")
                continue
            name = ctx.dotted(node.func)
            if name not in self._WRAPPERS:
                continue
            dtype = next((kw.value for kw in node.keywords
                          if kw.arg == "dtype"), None)
            if dtype is None and len(node.args) > 1:
                dtype = node.args[1]
            if dtype is not None and self._dtype_is_wide(ctx, dtype):
                yield self.finding(
                    ctx, node,
                    f"{name.rpartition('.')[2]}(..., dtype=<64-bit>) "
                    f"inside a loop widens the array it wraps to 8-byte "
                    f"elements on the hot path")


ALL_MEM_RULES = [UnboundedCarryRule, CorpusScaledTemporaryRule,
                 CacheSpillUnbudgetedRule, DtypeExpansionAtParseRule]


def mem_rule_ids() -> List[str]:
    return [r.rule_id for r in ALL_MEM_RULES] + [MEM_AUDIT_RULE]


# --------------------------------------------------------------------------
# corpus statistics (what the analytic model derives its terms from)
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class CorpusStats:
    """Cheap head-sample statistics of a CSV/sequence corpus: everything
    the footprint model needs, gathered without a full scan (the model
    must be usable BEFORE admission — that is its point)."""

    total_bytes: int
    rows: int                 # extrapolated from the sample's avg row
    avg_row_bytes: float
    avg_fields: float         # delimited fields per row (meta included)
    distinct_tokens: int      # non-leading-field vocab estimate (capped)

    def to_json(self) -> dict:
        return {"total_bytes": self.total_bytes, "rows": self.rows,
                "avg_row_bytes": round(self.avg_row_bytes, 2),
                "avg_fields": round(self.avg_fields, 2),
                "distinct_tokens": self.distinct_tokens}


def corpus_stats(paths: Sequence[str], delim: str = ",",
                 sample_bytes: int = 256 << 10) -> CorpusStats:
    """Sample the head of the first input (whole lines only) and
    extrapolate; token vocabulary estimate excludes each row's leading
    field (ids never dictionary-encode) and caps at 4096."""
    total = sum(os.path.getsize(p) for p in paths)
    with open(paths[0], "rb") as fh:
        head = fh.read(sample_bytes)
    cut = head.rfind(b"\n")
    if cut > 0:
        head = head[:cut + 1]
    lines = [ln for ln in head.decode("utf-8", "replace").split("\n")
             if ln.strip()]
    n = max(len(lines), 1)
    avg_row = max(len(head) / n, 1.0)
    fields = sum(ln.count(delim) + 1 for ln in lines) / n
    vocab: Set[str] = set()
    for ln in lines:
        for tok in ln.split(delim)[1:]:
            vocab.add(tok.strip(" \t\r"))
            if len(vocab) >= 4096:
                break
        if len(vocab) >= 4096:
            break
    return CorpusStats(total_bytes=total, rows=int(total / avg_row),
                       avg_row_bytes=avg_row, avg_fields=max(fields, 1.0),
                       distinct_tokens=max(len(vocab), 1))


def _unbounded_stats(avg_row_bytes: float = 40.0, avg_fields: float = 8.0,
                     distinct_tokens: int = 64) -> CorpusStats:
    """Stats for the admission manifest's nominal corpus: effectively
    unbounded size, so every block-proportional term prices a FULL block
    — the upper-bound posture an admission oracle needs."""
    return CorpusStats(total_bytes=1 << 62, rows=1 << 40,
                       avg_row_bytes=avg_row_bytes, avg_fields=avg_fields,
                       distinct_tokens=distinct_tokens)


# --------------------------------------------------------------------------
# analytic footprint model
# --------------------------------------------------------------------------
@dataclass
class FootprintEstimate:
    """One job's predicted peak incremental host bytes at one block size,
    decomposed into named terms so a drifted prediction is debuggable
    (which buffer grew?) instead of a bare number."""

    job: str
    block_bytes: int
    terms: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return int(sum(self.terms.values()))

    def to_json(self) -> dict:
        return {"job": self.job, "block_bytes": self.block_bytes,
                "predicted_peak_bytes": self.total_bytes,
                "predicted_peak_mb": round(self.total_bytes / (1 << 20), 2),
                "terms": {k: int(v) for k, v in sorted(self.terms.items())}}


def _pow2ceil(x: float, lo: int) -> int:
    return max(lo, 1 << max(int(x) - 1, 0).bit_length())


def _schema_cols(schema) -> Tuple[int, int, int]:
    """(numeric, categorical, string/id) column counts of a FeatureSchema
    (defaults approximate the churn shape when no schema is known)."""
    if schema is None:
        return 1, 5, 1
    n_num = sum(1 for f in schema if f.is_numeric)
    n_cat = sum(1 for f in schema if f.is_categorical)
    return n_num, n_cat, max(len(list(schema)) - n_num - n_cat, 0)


def _eff_block(stats: CorpusStats, block_bytes: int) -> int:
    """A block never exceeds the corpus: the reader cuts at EOF."""
    return max(1, min(int(block_bytes), stats.total_bytes))


#: default depth of the outer prefetched() job feeds (the
#: `stream.prefetch.depth` conf key; core.stream.DEFAULT_PREFETCH_DEPTH)
DEFAULT_MODEL_PREFETCH_DEPTH = 2


def _dataset_ingest(stats: CorpusStats, block_bytes: int, schema,
                    prefetch_depth: int = DEFAULT_MODEL_PREFETCH_DEPTH
                    ) -> Dict[str, int]:
    """Shared-schema Dataset ingest: CsvBlockReader's inner depth-1 byte
    prefetch (producer copy + queued + parsing = 3 raw blocks), the
    native parse writing float32/int32 column outputs plus the lazy
    string-column raw bytes, and the outer depth-D Dataset prefetch of
    stream_job_inputs (D queued + producing + consuming = D+2 parsed
    chunks; D is the `stream.prefetch.depth` key, default 2)."""
    eff = _eff_block(stats, block_bytes)
    rows = eff / stats.avg_row_bytes
    n_num, n_cat, n_str = _schema_cols(schema)
    depth = max(int(prefetch_depth), 1)
    parsed = rows * 4.0 * (n_num + n_cat) + 0.3 * eff * max(n_str, 1)
    return {
        "raw_blocks_in_flight": int(3 * eff),
        "parse_transient": int(parsed),
        "parsed_chunks_in_flight": int((depth + 2) * parsed),
        # columnar-sidecar transient, one block either way: the cold
        # pass serializes the parsed block before appending it to
        # columns.bin; the warm pass materializes one block's columns
        # from the replay read
        "sidecar_pages": int(eff),
    }


def _bytes_ingest(stats: CorpusStats, block_bytes: int,
                  prefetch_depth: int = DEFAULT_MODEL_PREFETCH_DEPTH
                  ) -> Dict[str, int]:
    """Raw byte-block ingest for the sequence-shaped jobs: depth-D
    outer prefetch (D queued + producing + consuming = D+2 raw blocks
    in flight; D = `stream.prefetch.depth`, default 2) plus the CSR
    encode transients — int32 codes + int32 row_of + bool region per
    token, int64 offsets/starts per row, and one decoded copy on the
    vocabulary-extension path. Without the native encoder every token
    becomes a Python string (~64B each), and the model says so."""
    eff = _eff_block(stats, block_bytes)
    rows = eff / stats.avg_row_bytes
    toks = rows * stats.avg_fields
    depth = max(int(prefetch_depth), 1)
    terms = {
        "raw_blocks_in_flight": int((depth + 2) * eff),
        "csr_transients": int(toks * 9 + rows * 16 + eff),
        # columnar-sidecar transient (write-side serialize / read-side
        # materialize of ONE block's encoded columns)
        "sidecar_pages": int(eff),
    }
    try:
        from avenir_tpu.native.ingest import native_available
        native = native_available()
    except Exception:
        native = False
    if not native:
        terms["python_tokenize"] = int(toks * 64)
    return terms


def _model_nb(stats, block_bytes, schema,
              prefetch_depth=DEFAULT_MODEL_PREFETCH_DEPTH) -> Dict[str, int]:
    t = _dataset_ingest(stats, block_bytes, schema, prefetch_depth)
    rows = _eff_block(stats, block_bytes) / stats.avg_row_bytes
    n_num, n_cat, _ = _schema_cols(schema)
    # deferred-fold code matrix per chunk (host int32 + device copy)
    t["nb_fold_buffers"] = int(rows * 4 * (n_num + n_cat) * 2)
    t["nb_model_state"] = 1 << 20
    return t


def _model_mi(stats, block_bytes, schema,
              prefetch_depth=DEFAULT_MODEL_PREFETCH_DEPTH) -> Dict[str, int]:
    t = _dataset_ingest(stats, block_bytes, schema, prefetch_depth)
    rows = _eff_block(stats, block_bytes) / stats.avg_row_bytes
    # per-pair bincount keys (int64) and their intp cast, per chunk
    t["mi_pair_keys"] = int(rows * 8 * 2)
    t["mi_tables"] = 1 << 20
    return t


def _model_fisher(stats, block_bytes, schema,
                  prefetch_depth=DEFAULT_MODEL_PREFETCH_DEPTH
                  ) -> Dict[str, int]:
    t = _dataset_ingest(stats, block_bytes, schema, prefetch_depth)
    t["fisher_moments"] = 1 << 20
    return t


def _model_markov(stats, block_bytes, schema,
                  prefetch_depth=DEFAULT_MODEL_PREFETCH_DEPTH
                  ) -> Dict[str, int]:
    t = _bytes_ingest(stats, block_bytes, prefetch_depth)
    t["markov_counts"] = 1 << 20
    return t


def _miner_common(stats: CorpusStats, block_bytes: int,
                  prefetch_depth: int = DEFAULT_MODEL_PREFETCH_DEPTH
                  ) -> Dict[str, int]:
    """Pass-1 scan + spill write + per-k replay transients shared by both
    miners: the replay pass re-reads narrow codes + per-row counts and
    re-expands them to int32 working arrays."""
    t = _bytes_ingest(stats, block_bytes, prefetch_depth)
    eff = _eff_block(stats, block_bytes)
    rows = eff / stats.avg_row_bytes
    toks = rows * stats.avg_fields
    t["replay_transients"] = int(toks * (1 + 4 + 4) + rows * 16)
    return t


def _model_apriori(stats, block_bytes, schema,
                   prefetch_depth=DEFAULT_MODEL_PREFETCH_DEPTH
                   ) -> Dict[str, int]:
    t = _miner_common(stats, block_bytes, prefetch_depth)
    v = stats.distinct_tokens
    words = max((v + 31) // 32, 1)
    c_pad = _pow2ceil(min(v * v, 4096), 64)
    # uint8 multi-hot page + packed bitset page, double-buffered + device
    t["apriori_pages"] = int(3 * 8192 * (v + 4 * words))
    t["apriori_candidates"] = int(c_pad * (4 * words + 8))
    return t


def _model_gsp(stats, block_bytes, schema,
               prefetch_depth=DEFAULT_MODEL_PREFETCH_DEPTH) -> Dict[str, int]:
    t = _miner_common(stats, block_bytes, prefetch_depth)
    eff = _eff_block(stats, block_bytes)
    rows_page = _pow2ceil(min(eff / stats.avg_row_bytes, 65536), 1024)
    t_bucket = _pow2ceil(stats.avg_fields, 16)
    c_pad = _pow2ceil(min(stats.distinct_tokens ** 2, 4096), 16)
    # padded int32 pages (double buffer + device) and the scan kernel's
    # [rows, candidates] pointer state + hit temporaries on device
    t["gsp_pages"] = int(3 * rows_page * t_bucket * 4)
    t["gsp_scan_state"] = int(3 * rows_page * c_pad * 4)
    return t


#: canonical runner job name -> term builder(stats, block_bytes, schema)
_JOB_MODELS: Dict[str, Callable] = {
    "bayesianDistr": _model_nb,
    "mutualInformation": _model_mi,
    "fisherDiscriminant": _model_fisher,
    "markovStateTransitionModel": _model_markov,
    "frequentItemsApriori": _model_apriori,
    "candidateGenerationWithSelfJoin": _model_gsp,
}

#: the ingest terms shared by every sink of one fused scan — counted
#: once (max across jobs) when jobs fuse, exactly like the scan itself
_INGEST_TERMS = {"raw_blocks_in_flight", "parse_transient",
                 "parsed_chunks_in_flight", "csr_transients",
                 "python_tokenize", "sidecar_pages"}


def footprint_model(job: str, block_bytes: int, schema=None,
                    stats: Optional[CorpusStats] = None,
                    prefetch_depth: int = DEFAULT_MODEL_PREFETCH_DEPTH
                    ) -> FootprintEstimate:
    """Predicted peak incremental host bytes of one registered streamed
    job at `block_bytes` with `prefetch_depth` queued chunks (the
    `stream.prefetch.depth` key — the in-flight terms scale with it, so
    an autotuned depth re-prices admission honestly). With no `stats`
    the corpus is assumed unbounded (every block term prices a full
    block) — the admission-oracle posture the memory manifest exports."""
    if job not in _JOB_MODELS:
        raise ValueError(
            f"no footprint model for job {job!r}; modeled jobs: "
            f"{', '.join(sorted(_JOB_MODELS))}")
    st = stats if stats is not None else _unbounded_stats()
    terms = _JOB_MODELS[job](st, int(block_bytes), schema,
                             max(int(prefetch_depth), 1))
    return FootprintEstimate(job, int(block_bytes),
                             {k: int(v) for k, v in terms.items()})


def combined_footprint(jobs: Sequence[str], block_bytes: int, schema=None,
                       stats: Optional[CorpusStats] = None,
                       prefetch_depth: int = DEFAULT_MODEL_PREFETCH_DEPTH
                       ) -> FootprintEstimate:
    """Footprint of N jobs fused on ONE shared scan: ingest terms are
    paid once (the scan-sharing executor's whole point), per-job state
    terms sum, prefixed by job so the decomposition stays readable."""
    ests = [footprint_model(j, block_bytes, schema, stats, prefetch_depth)
            for j in jobs]
    terms: Dict[str, int] = {}
    for est in ests:
        for k, v in est.terms.items():
            if k in _INGEST_TERMS:
                terms[k] = max(terms.get(k, 0), v)
            else:
                terms[f"{est.job}:{k}" if len(ests) > 1 else k] = v
    return FootprintEstimate("+".join(jobs), int(block_bytes), terms)


# --------------------------------------------------------------------------
# device-side live bytes of the kernel manifest
# --------------------------------------------------------------------------
def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(math.prod(shape)) * int(dtype.itemsize)


def _lower_compiled(fn, args):
    """Lower + compile one manifest entry for its buffer assignment —
    wrapping plain-op entries in a fresh jit (each entry is distinct and
    compiled exactly once, so the wrapper's empty cache is the point,
    not a hazard)."""
    import jax

    lowered = (fn.lower(*args) if hasattr(fn, "lower")
               else jax.jit(fn).lower(*args))
    return lowered.compile()


def kernel_device_entries(entries: Optional[Sequence] = None) -> List[dict]:
    """Per manifest kernel: argument/output/temp bytes and their peak sum
    — the device half of the memory manifest. Temp bytes come from the
    compiled HLO buffer assignment (``compiled.memory_analysis()``, the
    PR-3 lowering harness) where the backend exposes it; otherwise the
    largest single equation output of the traced jaxpr stands in, and
    the row says which source it used. Distributed families lower on the
    audit mesh and are skipped (with a note) when the device pool is too
    small — a partial manifest must say it is partial."""
    import jax

    from avenir_tpu.analysis.ir import _audit_mesh, iter_eqns
    from avenir_tpu.analysis.manifest import AUDIT_DEVICES, manifest_entries

    devices = jax.devices()
    rows: List[dict] = []
    for spec in (list(entries) if entries is not None
                 else manifest_entries()):
        if spec.is_family and len(devices) < AUDIT_DEVICES:
            rows.append({"kernel": spec.name, "path": spec.path,
                         "skipped": f"needs {AUDIT_DEVICES} devices, "
                                    f"found {len(devices)}"})
            continue
        mesh = _audit_mesh(spec, devices) if spec.is_family else None
        fn, args = spec.build(mesh)
        jaxpr = jax.make_jaxpr(fn)(*args)
        arg_b = sum(_aval_bytes(v) for v in jaxpr.jaxpr.invars)
        out_b = sum(_aval_bytes(v) for v in jaxpr.jaxpr.outvars)
        temp_b, source = None, "jaxpr"
        try:
            ma = _lower_compiled(fn, args).memory_analysis()
            if ma is not None:
                temp_b = int(getattr(ma, "temp_size_in_bytes", 0))
                arg_b = int(getattr(ma, "argument_size_in_bytes", arg_b))
                out_b = int(getattr(ma, "output_size_in_bytes", out_b))
                source = "hlo_buffer_assignment"
        except Exception:
            pass
        if temp_b is None:
            temp_b = max((sum(_aval_bytes(o) for o in eqn.outvars)
                          for eqn, _ in iter_eqns(jaxpr.jaxpr)), default=0)
        rows.append({
            "kernel": spec.name, "path": spec.path,
            "family": bool(spec.is_family),
            "argument_bytes": arg_b, "output_bytes": out_b,
            "temp_bytes": temp_b,
            "peak_live_bytes": arg_b + out_b + temp_b,
            "source": source,
        })
    return rows


def memory_manifest(block_sizes_mb: Sequence[float] = (64.0, 8.0),
                    include_kernels: bool = True) -> dict:
    """The machine-readable memory manifest — the admission oracle the
    future job server consumes: per streamed job x block size, the
    predicted peak host bytes against a nominal unbounded corpus (churn
    schema for the tabular jobs); plus the per-kernel device live bytes.
    Derived live wherever it is needed; no file holds it."""
    from avenir_tpu.data import churn_schema

    schema = churn_schema()
    tabular = {"bayesianDistr", "mutualInformation", "fisherDiscriminant"}
    jobs: Dict[str, dict] = {}
    for job in sorted(_JOB_MODELS):
        per_block = {}
        for mb in block_sizes_mb:
            est = footprint_model(job, int(mb * (1 << 20)),
                                  schema if job in tabular else None)
            per_block[f"{mb:g}MB"] = est.to_json()
        jobs[job] = per_block
    out = {
        "version": 1,
        "tolerance": {"slack_bytes": AUDIT_SLACK_BYTES,
                      "tightness": AUDIT_TIGHTNESS,
                      "policy": "measured <= predicted + slack and "
                                "predicted <= tightness * (measured + "
                                "slack), at >= 2 block sizes"},
        "jobs": jobs,
    }
    if include_kernels:
        out["kernels"] = kernel_device_entries()
    return out


# --------------------------------------------------------------------------
# mechanical audit: sampled RSS vs the model
# --------------------------------------------------------------------------
_STATM = "/proc/self/statm"


def _read_rss_bytes() -> int:
    try:
        with open(_STATM) as fh:
            return int(fh.read().split()[1]) * (os.sysconf("SC_PAGE_SIZE")
                                                or 4096)
    except (OSError, IndexError, ValueError) as e:
        raise MemAuditError(
            f"cannot sample RSS from {_STATM}: {e!r} (the footprint "
            f"auditor needs a Linux procfs)") from e


class _RssSampler:
    """Background thread sampling resident bytes (and, every few ticks,
    jax live device-buffer bytes where the backend exposes them) while
    one streamed job runs. The peaks are worker-private while sampling
    and exposed through read-only properties — the auditor reads them
    only after ``__exit__`` joined the thread, so there is no shared
    mutable surface mid-run (our own flow-shared-state-unlocked rule
    applies to this module too)."""

    def __init__(self, interval: float = 0.004):
        self.interval = interval
        self._peak_rss = 0
        self._peak_live = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    @property
    def peak_rss(self) -> int:
        return self._peak_rss

    @property
    def peak_live(self) -> int:
        return self._peak_live

    def _loop(self) -> None:
        tick = 0
        while not self._stop.is_set():
            try:
                self._peak_rss = max(self._peak_rss, _read_rss_bytes())
            except MemAuditError:
                break
            if tick % 16 == 0:
                try:
                    import jax
                    self._peak_live = max(
                        self._peak_live,
                        sum(int(a.nbytes) for a in jax.live_arrays()))
                except Exception:
                    pass
            tick += 1
            self._stop.wait(self.interval)

    def __enter__(self) -> "_RssSampler":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(5.0)


class _BlockRecorder:
    """core.stream byte-accounting consumer: the largest raw byte block
    any prefetch worker produced — the mechanical proof that the model's
    effective-block term matches the blocks that actually flowed."""

    def __init__(self):
        self.max_bytes = 0
        self._lock = threading.Lock()

    def __call__(self, n: int) -> None:
        if n:
            with self._lock:
                if n > self.max_bytes:
                    self.max_bytes = n


@contextmanager
def _bytes_hook(recorder):
    from avenir_tpu.core import stream

    prev = stream._bytes_hook
    stream._bytes_hook = recorder
    try:
        yield
    finally:
        stream._bytes_hook = prev


def _inflate_corpus(ctx: dict, target_bytes: int) -> dict:
    """Byte-replicate the spec's seeded corpus up to `target_bytes` (the
    jobs are line-streamed; replication preserves every row shape) so
    block-proportional terms dominate at audit block sizes."""
    src = ctx["csv"]
    with open(src, "rb") as fh:
        blob = fh.read()
    if not blob:
        raise MemAuditError(f"audit corpus {src!r} is empty")
    reps = max(1, -(-target_bytes // len(blob)))
    if reps == 1:
        return ctx
    big = os.path.join(ctx["dir"], "inflated.csv")
    with open(big, "wb") as fh:
        for _ in range(reps):
            fh.write(blob)
    out = dict(ctx)
    out["csv"] = big
    return out


def audit_footprint(spec, block_sizes_mb: Optional[Sequence[float]] = None,
                    model_fn: Optional[Callable] = None,
                    inflate_to: int = AUDIT_CORPUS_BYTES
                    ) -> Tuple[dict, Optional[Finding]]:
    """Run one streamed job at >= 2 block sizes on its (inflated) proxy
    corpus, sampling peak RSS, and judge the analytic prediction's band
    at every size. Each size runs TWICE: the first run absorbs jit
    compiles and allocator growth for that exact layout, the second is
    measured — the model predicts steady-state transients, not one-time
    runtime warmup. Returns (audit row, band-violation finding or None);
    a job that fails to run raises :class:`MemAuditError`."""
    sizes = [float(mb) for mb in (block_sizes_mb or DEFAULT_AUDIT_BLOCKS_MB)]
    if len(sizes) < 2:
        raise MemAuditError(
            f"{spec.name}: the footprint audit needs >= 2 block sizes, "
            f"got {sizes}")
    workdir = tempfile.mkdtemp(prefix=f"graftlint_mem_{spec.name}_")
    per_size: List[dict] = []
    try:
        ctx = spec.prepare(workdir)
        ctx = _inflate_corpus(ctx, inflate_to)
        stats = corpus_stats([ctx["csv"]])
        schema = None
        if "schema" in ctx:
            from avenir_tpu.core.schema import FeatureSchema
            schema = FeatureSchema.from_file(ctx["schema"])
        if model_fn is None:
            jobs = list(spec.jobs)
            if not jobs:
                raise MemAuditError(
                    f"{spec.name}: stream entry names no runner jobs; "
                    f"the footprint model is keyed on them")
            model_fn = lambda bb: combined_footprint(  # noqa: E731
                jobs, bb, schema, stats)
        for mb in sizes:
            bb = int(mb * (1 << 20))
            est = model_fn(bb)
            recorder = _BlockRecorder()
            with _bytes_hook(recorder):
                spec.run(ctx, mb)              # warmup: compile + arenas
                rss0 = _read_rss_bytes()
                t0 = time.perf_counter()
                with _RssSampler() as sampler:
                    spec.run(ctx, mb)
                dt = time.perf_counter() - t0
            measured = max(0, max(sampler.peak_rss, rss0) - rss0)
            predicted = est.total_bytes
            upper_ok = measured <= predicted + AUDIT_SLACK_BYTES
            lower_ok = predicted <= AUDIT_TIGHTNESS * (
                measured + AUDIT_SLACK_BYTES)
            eff = _eff_block(stats, bb)
            block_ok = (recorder.max_bytes == 0
                        or recorder.max_bytes <= eff + 65536)
            per_size.append({
                "block_mb": mb,
                "predicted_bytes": predicted,
                "predicted_mb": round(predicted / (1 << 20), 2),
                "measured_bytes": measured,
                "measured_mb": round(measured / (1 << 20), 2),
                "peak_live_device_bytes": sampler.peak_live,
                "observed_max_block_bytes": recorder.max_bytes,
                "terms": est.to_json()["terms"],
                "seconds": round(dt, 3),
                "within_band": upper_ok and lower_ok,
                "block_accounting_ok": block_ok,
            })
    except MemAuditError:
        raise
    except Exception as e:
        raise MemAuditError(
            f"{spec.name}: streamed job failed to run: {e!r}") from e
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    ok = all(s["within_band"] and s["block_accounting_ok"]
             for s in per_size)
    row = {
        "kernel": spec.name,
        "jobs": list(getattr(spec, "jobs", ()) or ()),
        "corpus": stats.to_json(),
        "block_sizes_mb": sizes,
        "tolerance": {"slack_bytes": AUDIT_SLACK_BYTES,
                      "tightness": AUDIT_TIGHTNESS},
        "runs": per_size,
        "footprint_model_validated": ok,
    }
    finding = None
    if not ok:
        bad = [s for s in per_size
               if not (s["within_band"] and s["block_accounting_ok"])]
        why = "; ".join(
            (f"{s['block_mb']:g}MB: measured {s['measured_mb']}MB vs "
             f"predicted {s['predicted_mb']}MB"
             + ("" if s["block_accounting_ok"]
                else f", observed block {s['observed_max_block_bytes']}B "
                     f"exceeds the modeled effective block"))
            for s in bad)
        finding = Finding(
            spec.path, spec.line, MEM_AUDIT_RULE,
            f"streamed job `{spec.name}` broke its footprint band: {why}",
            "re-derive the job's terms in analysis/mem.py (which buffer "
            "grew?) or fix the job if a carry went O(corpus); never "
            "allowlist a memory-oracle drift",
            spec.name)
    return row, finding


# --------------------------------------------------------------------------
# runner
# --------------------------------------------------------------------------
def run_mem(paths: Optional[Sequence[str]] = None,
            rules: Optional[Sequence[MemRule]] = None,
            baseline: Optional[Sequence[BaselineEntry]] = None,
            root: Optional[str] = None, include_md: bool = True,
            audit: bool = True, entries: Optional[Sequence] = None,
            block_sizes_mb: Optional[Sequence[float]] = None) -> Report:
    """Lint `paths` (default: the gated repo surface) with the mem rules,
    run the footprint auditor over the streamed-kernel manifest, and
    apply the allowlist baseline to both finding sets."""
    active = list(rules) if rules is not None else \
        [r() for r in ALL_MEM_RULES]
    root = os.path.abspath(root or os.getcwd())
    scan = list(paths) if paths else default_flow_paths(root)
    report, raw = collect_findings(scan, active, root, include_md)
    if audit:
        specs = list(entries) if entries is not None else None
        if specs is None:
            from avenir_tpu.analysis.manifest import stream_entries
            specs = stream_entries()
        for spec in specs:
            # NOT added to report.scanned — same reasoning as the flow
            # auditor: the audit runs the kernel, it does not lint its
            # file, and claiming a scan would falsely stale baseline
            # entries when an explicit path subset excludes it
            row, finding = audit_footprint(spec,
                                           block_sizes_mb=block_sizes_mb)
            report.footprint_audit.append(row)
            if finding is not None:
                raw.append(finding)
    active_ids = {r.rule_id for r in active}
    if audit:
        active_ids.add(MEM_AUDIT_RULE)
    apply_baseline(report, raw, baseline, active_ids)
    return report
