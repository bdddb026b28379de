"""graftlint rules — each grounded in a bug class this repo already paid for.

default-int64            PR 1's biggest RSS wins were deleting accidental
                         int64/float64 temporaries from streaming folds.
host-sync-in-fold        a host transfer inside a chunk/fold loop silently
                         serializes core/stream.double_buffered.
recompile-hazard         per-iteration jit wrappers / non-static shape
                         params defeat the XLA compile cache (bench
                         watches utils.metrics.jit_cache_size at runtime).
tracer-leak              traced values stored on self/globals under jit
                         escape the trace and blow up at the next call.
unseeded-stochastic-test asserts over unpinned randomness flake — the
                         tutorial_inventory_mcmc Geweke burn-in case.

Rules are lexical (see engine.py); anything they flag is either fixed or
allowlisted with a one-line justification in graftlint_baseline.txt.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set

from avenir_tpu.analysis.engine import Finding, ModuleContext, assigned_names

_NUMPY = "numpy"
_NP_MODS = ("numpy", "jax.numpy")


class Rule:
    rule_id: str = ""
    description: str = ""
    hint: str = ""

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleContext, node: ast.AST, message: str,
                hint: Optional[str] = None) -> Finding:
        return Finding(ctx.path, getattr(node, "lineno", 1), self.rule_id,
                       message, hint or self.hint, ctx.scope_of(node))


class DefaultInt64Rule(Rule):
    """numpy constructors/accumulators on hot paths (lexically inside a
    loop) without an explicit narrow dtype, plus the numpy index-producing
    calls whose result is always int64.

    Scope is numpy only: jax.numpy already defaults to 32-bit unless
    jax_enable_x64 is set, and the repo never sets it. The hot-path proxy
    is lexical loop nesting — exactly where the miners' per-block folds
    live, and where a doubled temporary is paid once per block instead of
    once per process."""

    rule_id = "default-int64"
    description = ("numpy call on a hot path defaults to a 64-bit dtype "
                   "(or always returns int64 indices)")
    hint = ("pass an explicit narrow dtype (np.int32/np.float32), or use an "
            "int32 cumsum/region-mask form (see native.ingest.csr_region_mask "
            "and models/sequence.py chunks()) for index math")

    # func -> index of the positional dtype argument
    DTYPE_POS = {"zeros": 1, "ones": 1, "empty": 1, "full": 2,
                 "arange": 3, "cumsum": 2, "cumprod": 2}
    ALWAYS_INT64 = {"argsort", "flatnonzero", "nonzero", "searchsorted"}

    @staticmethod
    def _fill_sets_narrow_dtype(node: ast.Call) -> bool:
        fill = (node.args[1] if len(node.args) > 1 else
                next((kw.value for kw in node.keywords
                      if kw.arg == "fill_value"), None))
        return (isinstance(fill, ast.Constant)
                and isinstance(fill.value, (str, bool)))

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.dotted(node.func)
            if name is None or "." not in name:
                continue
            mod, _, func = name.rpartition(".")
            if mod != _NUMPY or not ctx.in_loop(node):
                continue
            if func in self.DTYPE_POS:
                has_dtype = (len(node.args) > self.DTYPE_POS[func]
                             or any(kw.arg == "dtype"
                                    for kw in node.keywords))
                if func == "full" and self._fill_sets_narrow_dtype(node):
                    continue        # dtype follows a str/bool fill value
                if not has_dtype:
                    yield self.finding(
                        ctx, node,
                        f"np.{func} inside a loop without an explicit "
                        f"dtype defaults to a 64-bit element type")
            elif func in self.ALWAYS_INT64:
                yield self.finding(
                    ctx, node,
                    f"np.{func} inside a loop materializes int64 indices "
                    f"(8 bytes/element) on a hot path")


class HostSyncInFoldRule(Rule):
    """Host transfers of device values inside chunk/fold loops: `.item()`,
    `jax.device_get`, `float()/int()` of a jitted-kernel result, and
    `np.asarray/np.array` wrapping a jitted-kernel call. Each one blocks
    until the device finishes, defeating the encode/count overlap
    core/stream.double_buffered exists to provide — unless the transfer
    IS the fold accumulation, in which case it is allowlisted with that
    justification."""

    rule_id = "host-sync-in-fold"
    description = "host sync of a device value inside a chunk/fold loop"
    hint = ("keep the accumulator on device (fold jnp arrays, transfer once "
            "after the loop), or allowlist if the once-per-block transfer is "
            "the fold itself and is overlapped by double_buffered")

    # numpy only: jnp.asarray of a device value is a no-op, not a sync
    WRAPPERS = {"numpy.asarray", "numpy.array", "numpy.copy"}

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not ctx.in_loop(node):
                continue
            name = ctx.dotted(node.func)
            if name == "jax.device_get":
                yield self.finding(ctx, node,
                                   "jax.device_get inside a loop blocks on "
                                   "the device every iteration")
                continue
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args \
                    and not node.keywords:
                yield self.finding(ctx, node,
                                   ".item() inside a loop is a scalar "
                                   "device->host sync per iteration")
                continue
            first_call = (node.args[0] if node.args
                          and isinstance(node.args[0], ast.Call) else None)
            if first_call is None:
                continue
            inner = ctx.dotted(first_call.func)
            inner_tail = inner.rpartition(".")[2] if inner else None
            if inner_tail not in ctx.jitted_names:
                continue
            if name in self.WRAPPERS or name in ("float", "int", "bool"):
                yield self.finding(
                    ctx, node,
                    f"{name}(...) of jitted `{inner_tail}` result inside a "
                    f"loop synchronizes host and device every iteration")


class RecompileHazardRule(Rule):
    """Compile-cache misses the type system can't see: (a) a fresh
    jax.jit wrapper built inside a loop (a new wrapper never hits the
    cache); (b) a jitted function using a plain parameter as a shape
    without marking it static; (c) a jitted closure using an enclosing
    function's local as a shape — re-traced for every distinct value.
    utils.metrics.jit_cache_size is the runtime cross-check
    (tests/test_stream_jobs.py::test_streamed_miners_compile_within_their_shape_buckets,
    tests/test_shared_scan.py::test_fused_scan_adds_no_nb_fold_variant),
    so this rule can't silently rot."""

    rule_id = "recompile-hazard"
    description = "jit wrapper or shape argument that defeats the compile cache"
    hint = ("hoist jax.jit out of the loop / mark shape-like params "
            "static_argnames / derive shapes from operand .shape instead of "
            "closure scalars")

    SHAPE_ARG = {f"{m}.{f}": 0 for m in _NP_MODS
                 for f in ("zeros", "ones", "empty", "full")}
    SHAPE_ARG.update({f"{m}.broadcast_to": 1 for m in _NP_MODS})
    ARANGE = {f"{m}.arange" for m in _NP_MODS}

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) \
                    and ctx.dotted(node.func) in ("jax.jit", "jit") \
                    and ctx.in_loop(node):
                yield self.finding(
                    ctx, node,
                    "jax.jit(...) inside a loop builds a fresh wrapper per "
                    "iteration; its compile cache starts empty every time",
                    "build the jitted callable once, outside the loop")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                static = ctx.jit_static_names(node)
                if static is None:
                    continue
                yield from self._check_jitted_fn(ctx, node, static)

    def _shape_names(self, ctx: ModuleContext, call: ast.Call
                     ) -> List[ast.Name]:
        name = ctx.dotted(call.func)
        exprs: List[ast.AST] = []
        if name in self.ARANGE:
            exprs = list(call.args)
        elif name in self.SHAPE_ARG and len(call.args) > self.SHAPE_ARG[name]:
            exprs = [call.args[self.SHAPE_ARG[name]]]
        names: List[ast.Name] = []
        for e in exprs:
            for sub in ast.walk(e):
                # bare value names only: `rows.shape[0]` walks its Name
                # through an Attribute and is shape-derived, hence fine
                if isinstance(sub, ast.Name) and not isinstance(
                        ctx.parent(sub), ast.Attribute):
                    names.append(sub)
        return names

    def _check_jitted_fn(self, ctx: ModuleContext, fn: ast.FunctionDef,
                         static: Set[str]) -> Iterator[Finding]:
        params = {a.arg for a in
                  fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs}
        own = assigned_names(fn)
        enclosing: Set[str] = set()
        for outer in ctx.enclosing_functions(fn):
            enclosing |= assigned_names(outer)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            for nm in self._shape_names(ctx, node):
                if nm.id in params and nm.id not in static:
                    yield self.finding(
                        ctx, nm,
                        f"jitted `{fn.name}` uses parameter `{nm.id}` as a "
                        f"shape; traced values cannot size arrays",
                        f"add static_argnames=('{nm.id}',) (recompiles per "
                        f"value — quantize it) or derive the size from an "
                        f"operand's .shape")
                elif nm.id in enclosing and nm.id not in own \
                        and nm.id not in ctx.module_names:
                    yield self.finding(
                        ctx, nm,
                        f"jitted `{fn.name}` closes over `{nm.id}` from an "
                        f"enclosing function and uses it as a shape: every "
                        f"distinct value re-traces and recompiles")


class TracerLeakRule(Rule):
    """Traced values escaping the trace: assignment to `self.*` or to a
    `global`-declared name anywhere inside a jit-decorated function. The
    stored tracer outlives the trace and poisons the next call (or leaks
    a stale constant)."""

    rule_id = "tracer-leak"
    description = "traced value stored on self/globals inside jit"
    hint = ("return the value from the jitted function and store it on the "
            "host side, after the call")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if ctx.jit_static_names(fn) is None:
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    yield self.finding(
                        ctx, node,
                        f"`global {', '.join(node.names)}` inside jitted "
                        f"`{fn.name}`: assigning it stores a tracer past "
                        f"the trace")
                elif isinstance(node, (ast.Assign, ast.AugAssign,
                                       ast.AnnAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    for t in targets:
                        for leaf in ast.walk(t):
                            if isinstance(leaf, ast.Attribute) \
                                    and isinstance(leaf.value, ast.Name) \
                                    and leaf.value.id == "self":
                                yield self.finding(
                                    ctx, node,
                                    f"assignment to self.{leaf.attr} inside "
                                    f"jitted `{fn.name}` stores a traced "
                                    f"value on the instance")
                                break


class UnseededStochasticTestRule(Rule):
    """A scope that asserts AND draws unpinned randomness: global
    numpy/python RNG draws, `np.random.default_rng()` with no seed, or a
    jax PRNG key built from a non-constant. Statistical assertions are
    fine — run-to-run varying statistical assertions are flakes
    (tutorial_inventory_mcmc's Geweke burn-in was this class)."""

    rule_id = "unseeded-stochastic-test"
    description = "assert over unpinned randomness (flaky by construction)"
    hint = ("pin the seed: np.random.default_rng(<int>), jax.random.key(<int>)"
            ", or thread an explicit seeded Generator through the test")

    NP_GLOBAL_DRAWS = {"normal", "uniform", "choice", "rand", "randn",
                       "randint", "random", "permutation", "shuffle",
                       "binomial", "poisson", "standard_normal", "sample"}
    PY_DRAWS = {"random", "uniform", "randint", "choice", "shuffle",
                "sample", "gauss", "randrange", "betavariate"}

    @staticmethod
    def _walk_scope(root: ast.AST) -> Iterator[ast.AST]:
        """Nodes owned by `root`'s scope: descend everywhere except nested
        function defs (their draws/asserts attribute to the inner scope)."""
        stack = list(ast.iter_child_nodes(root))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                stack.extend(ast.iter_child_nodes(node))

    def _unseeded_calls(self, ctx: ModuleContext, nodes: List[ast.AST]
                        ) -> Iterator[ast.Call]:
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            name = ctx.dotted(node.func)
            if name is None:
                continue
            if name == "numpy.random.default_rng" and not node.args \
                    and not node.keywords:
                yield node
            elif name.startswith("numpy.random.") \
                    and name.rpartition(".")[2] in self.NP_GLOBAL_DRAWS:
                yield node
            elif name.startswith("random.") \
                    and name.rpartition(".")[2] in self.PY_DRAWS:
                yield node
            elif name in ("jax.random.key", "jax.random.PRNGKey") \
                    and node.args and any(
                        isinstance(sub, ast.Call)
                        for sub in ast.walk(node.args[0])):
                # a call inside the seed expression (time.time(),
                # os.getpid(), ...) is an entropy source; arithmetic over
                # constants/loop indices is deterministic and fine
                yield node

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        scopes: List[ast.AST] = [ctx.tree]
        scopes += [n for n in ast.walk(ctx.tree)
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for scope in scopes:
            nodes = list(self._walk_scope(scope))
            if not any(isinstance(n, ast.Assert) for n in nodes):
                continue
            for call in self._unseeded_calls(ctx, nodes):
                name = ctx.dotted(call.func)
                yield self.finding(
                    ctx, call,
                    f"`{name}` draws unpinned randomness in a scope that "
                    f"asserts on the result")


class ShardedHostMaterializeRule(Rule):
    """np.asarray / np.array / np.copy applied to a device-placed array
    (a direct jax.device_put(...) result, or a name bound from
    jax.device_put / mesh.shard_rows / mesh.replicated in the same
    module). Materializing a sharded array on the host gathers EVERY
    shard through one process — the all-to-one transfer the mesh layer
    exists to avoid — and on multi-host meshes it deadlocks outright
    (non-addressable shards). Lexical, like every rule here: values that
    become sharded through a mesh kernel's return slip past, but the
    placement-then-materialize shape is the one that has actually
    appeared in review."""

    rule_id = "sharded-host-materialize"
    description = "np.asarray/np.array of a device-placed (sharded) array"
    hint = ("keep the consumer on device (jnp ops see sharded arrays "
            "natively), or jax.device_get once after the last device step "
            "— never re-wrap a device_put result with host numpy")

    WRAPPERS = {"numpy.asarray", "numpy.array", "numpy.copy"}
    PLACERS_DOTTED = {"jax.device_put"}
    # mesh-layer placement helpers, recognized by tail name so both
    # `from ..mesh import shard_rows` and `mesh.shard_rows(...)` match
    PLACER_TAILS = {"device_put", "shard_rows", "replicated"}

    def _is_placer(self, ctx: ModuleContext, call: ast.Call) -> bool:
        name = ctx.dotted(call.func)
        if name in self.PLACERS_DOTTED:
            return True
        return (name is not None
                and name.rpartition(".")[2] in self.PLACER_TAILS)

    def _placed_names(self, ctx: ModuleContext) -> Set[str]:
        """Names bound (anywhere in the module) from a placement call —
        including tuple-to-tuple unpacks like `a, b = put(x), put(y)`."""
        out: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            pairs = []
            tgt, val = node.targets[0], node.value
            if isinstance(tgt, ast.Name):
                pairs.append((tgt, val))
            elif isinstance(tgt, ast.Tuple) and isinstance(val, ast.Tuple) \
                    and len(tgt.elts) == len(val.elts):
                pairs.extend(zip(tgt.elts, val.elts))
            for t, v in pairs:
                if isinstance(t, ast.Name) and isinstance(v, ast.Call) \
                        and self._is_placer(ctx, v):
                    out.add(t.id)
        return out

    def _feeds_placement(self, ctx: ModuleContext, node: ast.AST) -> bool:
        """True when `node` sits inside a placer call's arguments — e.g.
        ``shard_rows(mesh, np.asarray(x))``: that asarray PREPARES the
        placement (flow runs host->device), it doesn't materialize a
        placed value."""
        cur = ctx.parent(node)
        while cur is not None:
            if isinstance(cur, ast.Call) and self._is_placer(ctx, cur):
                return True
            cur = ctx.parent(cur)
        return False

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        placed = self._placed_names(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if ctx.dotted(node.func) not in self.WRAPPERS:
                continue
            if self._feeds_placement(ctx, node):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Call) and self._is_placer(ctx, arg):
                yield self.finding(
                    ctx, node,
                    "host materialization of a jax.device_put result: "
                    "every shard transfers back through this process")
            elif isinstance(arg, ast.Name) and arg.id in placed:
                yield self.finding(
                    ctx, node,
                    f"np wrapper over `{arg.id}` (device-placed above) "
                    f"gathers all shards to host")


class Int64LiteralInJnpRule(Rule):
    """A Python int literal outside int32 range flowing into a jax.numpy
    call. With jax_enable_x64 off (this repo never sets it) such a
    literal either raises OverflowError at runtime or silently truncates
    through a weak-typed promotion — both discovered at the worst time,
    on device, mid-stream. Folds constant int arithmetic (<<, **, *, +,
    -, |) so `1 << 40` and `2**40` are caught, not just spelled-out
    literals."""

    rule_id = "int64-literal-in-jnp"
    description = "int literal beyond int32 range in a jnp call"
    hint = ("keep 64-bit id/hash math in host numpy (np.int64 arrays) and "
            "hand the device narrow codes, or split the constant into "
            "32-bit halves before it reaches jnp")

    _INT32_MAX = 2 ** 31 - 1
    _OPS = {ast.LShift: lambda a, b: a << b, ast.Pow: lambda a, b: a ** b,
            ast.Mult: lambda a, b: a * b, ast.Add: lambda a, b: a + b,
            ast.Sub: lambda a, b: a - b, ast.BitOr: lambda a, b: a | b}

    def _fold(self, node: ast.AST) -> Optional[int]:
        """Constant-fold small int expressions; None when not constant."""
        if isinstance(node, ast.Constant):
            return node.value if type(node.value) is int else None
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            v = self._fold(node.operand)
            return -v if v is not None else None
        if isinstance(node, ast.BinOp):
            fn = self._OPS.get(type(node.op))
            if fn is None:
                return None
            a, b = self._fold(node.left), self._fold(node.right)
            if a is None or b is None:
                return None
            if isinstance(node.op, ast.Pow) and (abs(a) > 64 or b > 64):
                return None          # keep folding cheap and bounded
            try:
                return fn(a, b)
            except (OverflowError, ValueError):
                return None
        return None

    @staticmethod
    def _walk_pruning_calls(root: ast.AST) -> Iterator[ast.AST]:
        """Walk `root` WITHOUT descending into nested calls — a literal
        inside `np.asarray(1 << 40)` belongs to that (host) call, which
        is judged on its own if it's a jnp one."""
        stack = [root]
        while stack:
            node = stack.pop()
            yield node
            stack.extend(c for c in ast.iter_child_nodes(node)
                         if not isinstance(c, ast.Call))

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.dotted(node.func)
            if name is None or not name.startswith("jax.numpy."):
                continue
            exprs = list(node.args) + [kw.value for kw in node.keywords]
            for e in exprs:
                for sub in self._walk_pruning_calls(e):
                    v = self._fold(sub)
                    # only report the outermost folded expression: a
                    # parent BinOp that folded already covers its leaves
                    parent = ctx.parent(sub)
                    if v is not None and abs(v) > self._INT32_MAX \
                            and (not isinstance(parent, (ast.BinOp,
                                                         ast.UnaryOp))
                                 or self._fold(parent) is None):
                        yield self.finding(
                            ctx, sub if hasattr(sub, "lineno") else node,
                            f"int constant {v} exceeds int32 range inside "
                            f"`{name}`: with x64 disabled this overflows "
                            f"or silently truncates on device")


class FoldUndonatedCarryRule(Rule):
    """A jitted fold carry re-dispatched per chunk without a donated
    accumulator: ``acc = fold(acc, chunk)`` inside a lexical loop, where
    `fold` is a module-local jitted callable whose jit wrapper has no
    (non-empty) donate_argnums/donate_argnames. Every iteration then
    allocates a fresh device accumulator and keeps the previous one
    alive until the add completes — on a fan-out shared scan the per-
    chunk allocation multiplies by the sink count. The NB deferred fold
    (models/naive_bayes.py `_fold_batch_kernel`) and the miners' device
    count folds (ops/bitset.bitset_fold_counts, models/sequence.py
    `_subseq_fold_kernel`) are the donated pattern this rule enforces.
    Module-local like every rule here: an imported jitted fold is judged
    in its defining module."""

    rule_id = "fold-undonated-carry"
    description = ("jitted fold carry re-dispatched per chunk without a "
                   "donated accumulator")
    hint = ("donate the carry: @partial(jax.jit, donate_argnums=(0,)) on "
            "the fold kernel so the chunk loop reuses ONE device buffer "
            "(the models/naive_bayes.py _fold_batch_kernel pattern), or "
            "allowlist if the loop is few-iteration host-driven control")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.Assign, ast.AugAssign)) \
                    or not ctx.in_loop(node):
                continue
            value = node.value
            if not isinstance(value, ast.Call):
                continue
            fname = ctx.dotted(value.func)
            tail = fname.rpartition(".")[2] if fname else None
            if tail not in ctx.jitted_names or tail in ctx.jitted_donating:
                continue
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            tnames = {ctx.dotted(t) for t in targets} - {None}
            if not tnames:
                continue
            args = list(value.args) + [kw.value for kw in value.keywords]
            carry = next((ctx.dotted(a) for a in args
                          if ctx.dotted(a) in tnames), None)
            if carry is not None:
                yield self.finding(
                    ctx, node,
                    f"`{carry} = {tail}({carry}, ...)` in a loop: the "
                    f"jitted fold's carry is not donated, so every chunk "
                    f"allocates a fresh device accumulator")


ALL_RULES = [DefaultInt64Rule, HostSyncInFoldRule, RecompileHazardRule,
             TracerLeakRule, UnseededStochasticTestRule,
             ShardedHostMaterializeRule, Int64LiteralInJnpRule,
             FoldUndonatedCarryRule]


def rule_ids() -> List[str]:
    return [r.rule_id for r in ALL_RULES]
