"""graftlint engine: source discovery, AST context, baseline, rule runner.

The engine is deliberately dumb about semantics — every rule is a lexical
pattern over one module's AST plus a little import-alias resolution. That
is the Casper lesson (arXiv:1801.09802): the code shapes worth rewriting
for an accelerator are *syntactically* recognizable, so recognize them at
review time instead of re-deriving them from RSS graphs after the fact.

Findings are keyed ``path::rule::scope`` (scope = dotted enclosing
class/function, ``<module>`` at top level) rather than by line number, so
the allowlist baseline survives unrelated edits to the same file.
Markdown files contribute their ```python fences (the docs/ tutorials are
executable via tests/test_tutorials.py, so they are lintable surface —
the unseeded-stochastic-test rule exists because one of them flaked).
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

_FENCE = re.compile(r"```python[ \t]*\n(.*?)```", re.DOTALL)

#: modules whose attribute calls the rules resolve through import aliases
_LOOP_NODES = (ast.For, ast.AsyncFor, ast.While,
               ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)
_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                ast.ClassDef)


@dataclass(frozen=True)
class Finding:
    """One rule hit: location, rule id, message and a concrete fix hint."""

    path: str          # posix path relative to the scan root
    line: int
    rule: str
    message: str
    hint: str
    scope: str         # dotted enclosing def/class chain, '<module>' at top

    @property
    def key(self) -> str:
        """Baseline-matching identity (line numbers drift; scopes don't)."""
        return f"{self.path}::{self.rule}::{self.scope}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}: {self.rule} [{self.scope}] "
                f"{self.message}\n    fix: {self.hint}")

    def to_json(self) -> dict:
        return {"path": self.path, "line": self.line, "rule": self.rule,
                "scope": self.scope, "message": self.message,
                "hint": self.hint, "key": self.key}


@dataclass
class BaselineEntry:
    key: str
    justification: str
    lineno: int
    used: int = 0


@dataclass
class Report:
    """One analyzer run: surviving findings + what the baseline absorbed.

    `payload_audit` is filled only by IR runs (analysis/ir.py): one entry
    per distributed family with its HLO-vs-analytic collective payload
    verdict. `invariance_audit` is filled only by flow runs
    (analysis/flow.py): one entry per streamed fold kernel with its
    chunk-layout/scheduler byte-identity verdict. `footprint_audit` is
    filled only by mem runs (analysis/mem.py): one entry per streamed
    job with its measured-RSS-vs-analytic-footprint verdict.
    `merge_audit` is filled only by merge runs (analysis/merge.py): one
    entry per streamed fold kernel with its shard-merge/checkpoint-
    resume byte-identity verdict. `proto_audit` is filled only by proto
    runs (analysis/proto.py): one entry per registered commit site with
    its kill-injection crash/recovery byte-identity verdict.
    `race_audit` is filled only by race runs (analysis/race.py): one
    entry per registered interleave site with its schedule-exploration
    verdict. `key_audit` is filled only by keys runs
    (analysis/keys.py): one entry per registered key site with its
    perturbation verdict. Other modes leave them empty — the keys are always
    present in the JSON so whoever reads it parses one schema."""

    findings: List[Finding] = field(default_factory=list)
    suppressed: List[Finding] = field(default_factory=list)
    stale: List[BaselineEntry] = field(default_factory=list)
    scanned: List[str] = field(default_factory=list)
    errors: List[Finding] = field(default_factory=list)
    payload_audit: List[dict] = field(default_factory=list)
    invariance_audit: List[dict] = field(default_factory=list)
    footprint_audit: List[dict] = field(default_factory=list)
    merge_audit: List[dict] = field(default_factory=list)
    proto_audit: List[dict] = field(default_factory=list)
    race_audit: List[dict] = field(default_factory=list)
    key_audit: List[dict] = field(default_factory=list)

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    @property
    def clean(self) -> bool:
        return not self.findings and not self.stale and not self.errors

    def to_json(self) -> dict:
        return {
            "findings": [f.to_json() for f in self.findings],
            "counts": self.counts(),
            "suppressed": len(self.suppressed),
            "stale_baseline_entries": [e.key for e in self.stale],
            "errors": [f.to_json() for f in self.errors],
            "files_scanned": len(self.scanned),
            "payload_audit": self.payload_audit,
            "invariance_audit": self.invariance_audit,
            "footprint_audit": self.footprint_audit,
            "merge_audit": self.merge_audit,
            "proto_audit": self.proto_audit,
            "race_audit": self.race_audit,
            "key_audit": self.key_audit,
            "clean": self.clean,
        }


class ModuleContext:
    """Parsed module + the shared lookups every rule needs: parent links,
    import-alias resolution, loop/scope ancestry, jit-decoration info."""

    def __init__(self, path: str, tree: ast.Module):
        self.path = path
        self.tree = tree
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.aliases = self._collect_aliases(tree)
        self.module_names = self._module_level_names(tree)
        self.jitted_names = self._collect_jitted_names(tree)
        self.jitted_donating = self._collect_donating_names(tree)

    # ------------------------------------------------------------ imports
    @staticmethod
    def _collect_aliases(tree: ast.Module) -> Dict[str, str]:
        aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
        return aliases

    def dotted(self, node: ast.AST) -> Optional[str]:
        """Canonical dotted name of an expression like ``np.random.choice``
        (import aliases resolved), or None for non-name expressions."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        parts.append(node.id)
        parts.reverse()
        head = self.aliases.get(parts[0], parts[0])
        return ".".join([head] + parts[1:])

    # ------------------------------------------------------------ ancestry
    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self.parents.get(node)

    def in_loop(self, node: ast.AST) -> bool:
        """True when `node` executes per-iteration of a lexical loop
        (for/while/comprehension), stopping at function boundaries — the
        analyzer's structural proxy for "hot path". A `for` statement's
        iterable and a comprehension's first source evaluate once, so
        they don't count for the loop they feed (an enclosing loop still
        does)."""
        path = [node]
        cur = self.parent(node)
        while cur is not None:
            path.append(cur)
            cur = self.parent(cur)
        for i in range(1, len(path)):
            anc, below = path[i], path[i - 1]
            if isinstance(anc, _SCOPE_NODES):
                return False
            if isinstance(anc, (ast.For, ast.AsyncFor)):
                if below is not anc.iter:
                    return True
            elif isinstance(anc, ast.While):
                return True
            elif isinstance(anc, (ast.ListComp, ast.SetComp, ast.DictComp,
                                  ast.GeneratorExp)):
                gens = anc.generators
                if gens and gens[0].iter in path[:i]:
                    continue
                return True
        return False

    def scope_of(self, node: ast.AST) -> str:
        names: List[str] = []
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                names.append(cur.name)
            cur = self.parent(cur)
        return ".".join(reversed(names)) if names else "<module>"

    def enclosing_functions(self, node: ast.AST
                            ) -> List[ast.FunctionDef]:
        """Function defs lexically containing `node`, innermost first."""
        out: List[ast.FunctionDef] = []
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(cur)
            cur = self.parent(cur)
        return out

    # ---------------------------------------------------------------- jit
    def jit_static_names(self, fn: ast.FunctionDef) -> Optional[Set[str]]:
        """None when `fn` is not jit-decorated; else the set of parameter
        names marked static (via static_argnums / static_argnames)."""
        for dec in getattr(fn, "decorator_list", ()):
            st = self._jit_call_static(dec, fn)
            if st is not None:
                return st
        return None

    def _jit_call_static(self, expr: ast.AST, fn: Optional[ast.FunctionDef]
                         ) -> Optional[Set[str]]:
        if self.dotted(expr) in ("jax.jit", "jit"):
            return set()
        if not isinstance(expr, ast.Call):
            return None
        callee = self.dotted(expr.func)
        if callee in ("jax.jit", "jit"):
            return self._static_names(expr, fn)
        if callee in ("functools.partial", "partial") and expr.args:
            if self.dotted(expr.args[0]) in ("jax.jit", "jit"):
                return self._static_names(expr, fn)
        return None

    @staticmethod
    def _static_names(call: ast.Call, fn: Optional[ast.FunctionDef]
                      ) -> Set[str]:
        static: Set[str] = set()
        params = ([a.arg for a in fn.args.posonlyargs + fn.args.args]
                  if fn is not None else [])
        for kw in call.keywords:
            vals = (kw.value.elts if isinstance(kw.value, ast.Tuple)
                    else [kw.value])
            if kw.arg == "static_argnums":
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(v.value, int) \
                            and v.value < len(params):
                        static.add(params[v.value])
            elif kw.arg == "static_argnames":
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(v.value, str):
                        static.add(v.value)
        return static

    @staticmethod
    def _jit_donates(call: ast.Call) -> bool:
        """True when a jit(...) call donates at least one argument. An
        explicitly EMPTY donate_argnums=() donates nothing (the repo uses
        it to DOCUMENT a non-donating kernel) and counts as False."""
        for kw in call.keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                if isinstance(kw.value, (ast.Tuple, ast.List)) \
                        and not kw.value.elts:
                    continue
                return True
        return False

    def _jit_call_donates(self, expr: ast.AST) -> Optional[bool]:
        """None when `expr` is not a jit wrapper expression; else whether
        that wrapper donates any argument."""
        if self.dotted(expr) in ("jax.jit", "jit"):
            return False                       # bare @jax.jit: no donation
        if not isinstance(expr, ast.Call):
            return None
        callee = self.dotted(expr.func)
        if callee in ("jax.jit", "jit"):
            return self._jit_donates(expr)
        if callee in ("functools.partial", "partial") and expr.args:
            if self.dotted(expr.args[0]) in ("jax.jit", "jit"):
                return self._jit_donates(expr)
        return None

    def _collect_donating_names(self, tree: ast.Module) -> Set[str]:
        """The subset of jitted names whose jit wrapper donates at least
        one argument — the fold-undonated-carry rule's pass list."""
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(self._jit_call_donates(dec)
                       for dec in node.decorator_list):
                    names.add(node.name)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and self._jit_call_donates(node.value):
                names.add(node.targets[0].id)
        return names

    def _collect_jitted_names(self, tree: ast.Module) -> Set[str]:
        """Names bound (at any nesting level) to jit-compiled callables:
        ``@jax.jit def f`` and ``f = jax.jit(g)`` — the device-value
        producers the host-sync rule recognizes."""
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if self.jit_static_names(node) is not None:
                    names.add(node.name)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and self._jit_call_static(node.value, None) is not None:
                names.add(node.targets[0].id)
        return names

    @staticmethod
    def _module_level_names(tree: ast.Module) -> Set[str]:
        names: Set[str] = set()
        for node in tree.body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)) \
                    and isinstance(node.target, ast.Name):
                names.add(node.target.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                names.add(node.name)
        return names


def assigned_names(fn: ast.FunctionDef) -> Set[str]:
    """Names bound inside `fn` (params, assignments, loop targets, withitems)
    — NOT descending into nested function defs."""
    out: Set[str] = {a.arg for a in
                     fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs}
    out.update(a.arg for a in (fn.args.vararg, fn.args.kwarg) if a)

    def collect_target(t: ast.AST) -> None:
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                collect_target(e)
        elif isinstance(t, ast.Starred):
            collect_target(t.value)

    def visit(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.add(child.name)
                continue
            if isinstance(child, ast.Assign):
                for t in child.targets:
                    collect_target(t)
            elif isinstance(child, (ast.AugAssign, ast.AnnAssign)):
                collect_target(child.target)
            elif isinstance(child, (ast.For, ast.AsyncFor)):
                collect_target(child.target)
            elif isinstance(child, (ast.With, ast.AsyncWith)):
                for item in child.items:
                    if item.optional_vars is not None:
                        collect_target(item.optional_vars)
            visit(child)

    visit(fn)
    return out


# --------------------------------------------------------------- discovery
def iter_sources(paths: Sequence[str], include_md: bool = True
                 ) -> Iterator[Tuple[str, str, int]]:
    """Yield (file_path, python_source, line_offset) units to lint.

    Directories walk recursively; ``.py`` files are one unit each at
    offset 0; ``.md`` files contribute one unit per ```python fence at
    the fence's line offset (so findings point into the real file)."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(d for d in dirnames
                                     if not d.startswith((".", "__pycache__")))
                files.extend(os.path.join(dirpath, f)
                             for f in sorted(filenames)
                             if f.endswith(".py")
                             or (include_md and f.endswith(".md")))
        else:
            files.append(p)
    for f in files:
        if f.endswith(".md"):
            if not include_md:
                continue
            text = open(f, encoding="utf-8").read()
            for m in _FENCE.finditer(text):
                offset = text[:m.start(1)].count("\n")
                yield f, m.group(1), offset
        else:
            yield f, open(f, encoding="utf-8").read(), 0


# ---------------------------------------------------------------- baseline
def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "graftlint_baseline.txt")


def load_baseline(path: Optional[str] = None) -> List[BaselineEntry]:
    """Parse the allowlist: one ``key -- justification`` per line, ``#``
    comments. A missing file is an empty baseline (fresh checkouts lint
    hard)."""
    path = path or default_baseline_path()
    entries: List[BaselineEntry] = []
    if not os.path.exists(path):
        return entries
    for i, raw in enumerate(open(path, encoding="utf-8"), start=1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        key, sep, why = line.partition(" -- ")
        if not sep or not why.strip():
            raise ValueError(
                f"{path}:{i}: baseline entries need a ' -- justification' "
                f"suffix (got {line!r})")
        if key.count("::") != 2:
            raise ValueError(
                f"{path}:{i}: baseline key must be path::rule::scope "
                f"(got {key!r})")
        entries.append(BaselineEntry(key.strip(), why.strip(), i))
    return entries


# -------------------------------------------------------------------- run
def collect_findings(paths: Sequence[str], rules: Sequence,
                     root: Optional[str] = None, include_md: bool = True
                     ) -> Tuple[Report, List[Finding]]:
    """Parse and lint `paths` with `rules`, returning the partial report
    (scanned files + parse errors) and the RAW findings, before any
    baseline split. Shared by run_paths and the flow runner
    (analysis/flow.py), which appends its audit findings to the raw list
    so one apply_baseline pass governs both."""
    root = os.path.abspath(root or os.getcwd())
    report = Report()
    raw: List[Finding] = []
    for file_path, source, offset in iter_sources(paths, include_md):
        rel = os.path.relpath(os.path.abspath(file_path), root)
        rel = rel.replace(os.sep, "/")
        if rel.startswith("../"):
            rel = file_path.replace(os.sep, "/")
        if rel not in report.scanned:
            report.scanned.append(rel)
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            report.errors.append(Finding(
                rel, offset + (e.lineno or 1), "parse-error",
                f"could not parse: {e.msg}", "fix the syntax error",
                "<module>"))
            continue
        if offset:
            ast.increment_lineno(tree, offset)
        ctx = ModuleContext(rel, tree)
        for rule in rules:
            raw.extend(rule.check(ctx))
    return report, raw


def run_paths(paths: Sequence[str], rules: Optional[Sequence] = None,
              baseline: Optional[Sequence[BaselineEntry]] = None,
              root: Optional[str] = None, include_md: bool = True) -> Report:
    """Lint `paths` with `rules` (default: all), splitting findings into
    surviving vs baseline-suppressed; baseline entries pointing at scanned
    files that no longer fire are reported stale (the allowlist must
    shrink with the code it excuses)."""
    from avenir_tpu.analysis.rules import ALL_RULES

    active = list(rules) if rules is not None else [r() for r in ALL_RULES]
    report, raw = collect_findings(paths, active, root, include_md)
    apply_baseline(report, raw, baseline, {r.rule_id for r in active})
    return report


def apply_baseline(report: Report, raw: Sequence[Finding],
                   baseline: Optional[Sequence[BaselineEntry]],
                   active_ids: Set[str]) -> Report:
    """Split `raw` into surviving vs baseline-suppressed findings on
    `report` (which already carries `scanned` and any errors), and flag
    stale allowlist entries. Shared by the AST runner above and the IR
    runner (analysis/ir.py) so both honor one baseline contract:
    an entry is stale only when its file was scanned AND its rule was
    active this run — a --rules subset must not condemn the rest of the
    allowlist."""
    entries = list(baseline) if baseline is not None else []
    by_key: Dict[str, BaselineEntry] = {}
    for e in entries:
        by_key.setdefault(e.key, e)
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.rule)):
        hit = by_key.get(f.key)
        if hit is not None:
            hit.used += 1
            report.suppressed.append(f)
        else:
            report.findings.append(f)
    scanned = set(report.scanned)
    report.stale = [e for e in entries
                    if not e.used
                    and e.key.split("::")[0] in scanned
                    and e.key.split("::")[1] in active_ids]
    return report
