"""The repro-lint rules (see repro.analysis.__doc__ for the codes).

RPL001–005 are call-graph-LOCAL: they resolve names within one module
(plus the declared cross-file anchors — the kernel registry in
kernels/policy.py, `@worker_only` decorators, registry-named test
files).  RPL006–008 are interprocedural: they run over the whole-project
symbol table + call graph in `analysis/callgraph.py` with the bounded
two-level summaries in `analysis/interproc.py` (may-raise, collectives,
PartitionSpec literals, axis-name value sets).  The bound is the
contract: anything the two-level inlining cannot resolve is "unknown"
and unknown is never flagged, so adding reach never adds guesswork.
Contracts that still need runtime observation keep their guard in
`repro.analysis.guards`.
"""
from __future__ import annotations

import ast
import pathlib
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.analysis.core import Context, Finding, ParsedModule

# attribute reads that yield STATIC Python values even on a tracer
_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding"}
# module roots whose calls produce tracer values inside a jit trace
_ARRAY_ROOTS = {"jnp", "jax", "lax"}
_JIT_WRAPPERS = {"jit"}                 # jax.jit
_TRACE_CONSUMERS = {                    # callable-arg positions traced by jax
    "jit": (0,), "shard_map": (0,), "scan": (0,), "vmap": (0,),
    "pallas_call": (0,), "while_loop": (0, 1), "fori_loop": (2,),
    "checkpoint": (0,), "remat": (0,), "grad": (0,), "value_and_grad": (0,),
}


def _attr_tail(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _attr_root(node: ast.AST) -> Optional[str]:
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _const_strs(node: ast.AST) -> List[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return [s for elt in node.elts for s in _const_strs(elt)]
    return []


# ---------------------------------------------------------------------------
# RPL001 — jit hazards
# ---------------------------------------------------------------------------

class _JitRoot:
    def __init__(self, fn, static_names: Set[str], static_nums: Set[int]):
        self.fn = fn
        self.static_names = static_names
        self.static_nums = static_nums


def _jit_call_info(call: ast.Call) -> Tuple[Set[str], Set[int]]:
    names: Set[str] = set()
    nums: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names |= set(_const_strs(kw.value))
        elif kw.arg == "static_argnums":
            if isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, int):
                nums.add(kw.value.value)
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                nums |= {e.value for e in kw.value.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, int)}
    return names, nums


def _decorator_jit(deco: ast.AST) -> Optional[Tuple[Set[str], Set[int]]]:
    """(static_argnames, static_argnums) if `deco` is a jit decorator:
    @jax.jit, @jit, @partial(jax.jit, ...), @functools.partial(jax.jit)."""
    if _attr_tail(deco) in _JIT_WRAPPERS:
        return set(), set()
    if isinstance(deco, ast.Call):
        tail = _attr_tail(deco.func)
        if tail in _JIT_WRAPPERS:
            return _jit_call_info(deco)
        if tail == "partial" and deco.args and \
                _attr_tail(deco.args[0]) in _JIT_WRAPPERS:
            return _jit_call_info(deco)
    return None


def _collect_jit_roots(mod: ParsedModule) -> List[_JitRoot]:
    """Functions traced by jax, resolved module-locally: jit-decorated
    defs, plus defs/lambdas whose NAME is passed to a trace-consuming
    call (jax.jit(step), shard_map(step, ...), lax.scan(body, ...))."""
    defs: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, []).append(node)

    roots: List[_JitRoot] = []
    seen: Set[ast.AST] = set()

    def add(fn, names=frozenset(), nums=frozenset()):
        if fn not in seen:
            seen.add(fn)
            roots.append(_JitRoot(fn, set(names), set(nums)))

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            for deco in node.decorator_list:
                info = _decorator_jit(deco)
                if info is not None:
                    add(node, *info)
        if isinstance(node, ast.Call):
            tail = _attr_tail(node.func)
            if tail not in _TRACE_CONSUMERS:
                continue
            static = _jit_call_info(node) if tail in _JIT_WRAPPERS \
                else (set(), set())
            for pos in _TRACE_CONSUMERS[tail]:
                if pos >= len(node.args):
                    continue
                arg = node.args[pos]
                if isinstance(arg, ast.Lambda):
                    add(arg, *static)
                elif isinstance(arg, ast.Name):
                    for fn in defs.get(arg.id, []):
                        add(fn, *static)
    return roots


class _TaintScope:
    """Conservative intra-function tracer taint: which local names may
    hold tracers at trace time."""

    def __init__(self, tainted: Set[str]):
        self.tainted = set(tainted)

    def expr(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _SHAPE_ATTRS:
                return False
            return self.expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value) or self.expr(node.slice)
        if isinstance(node, ast.Call):
            tail = _attr_tail(node.func)
            if tail == "len":
                return False               # static under tracing
            if isinstance(node.func, ast.Attribute):
                root = _attr_root(node.func)
                if root in _ARRAY_ROOTS:
                    return True            # jnp./jax.lax. op -> tracer
                return self.expr(node.func.value)   # x.sum() on a tracer
            if tail == "range":
                return any(self.expr(a) for a in node.args)
            return False
        if isinstance(node, (ast.BinOp,)):
            return self.expr(node.left) or self.expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is a static structural check
            # even when x may hold a tracer — identity against None is
            # resolved at trace time, never on device values.
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops) \
                    and all(isinstance(c, ast.Constant) and c.value is None
                            for c in node.comparators):
                return False
            return self.expr(node.left) or \
                any(self.expr(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            return (self.expr(node.body) or self.expr(node.test)
                    or self.expr(node.orelse))
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        return False

    def assign_target(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self.assign_target(elt, tainted)
        elif isinstance(target, ast.Starred):
            self.assign_target(target.value, tainted)


def _fn_params(fn) -> List[Tuple[int, str, Optional[ast.AST]]]:
    a = fn.args
    params = [*a.posonlyargs, *a.args]
    defaults: List[Optional[ast.AST]] = \
        [None] * (len(params) - len(a.defaults)) + list(a.defaults)
    out = [(i, p.arg, d) for i, (p, d) in enumerate(zip(params, defaults))]
    out += [(None, p.arg, d)
            for p, d in zip(a.kwonlyargs, a.kw_defaults)]
    return out


def _check_jit_body(fn, scope: _TaintScope, mod: ParsedModule,
                    findings: List[Finding]) -> None:
    def flag(node, msg):
        findings.append(Finding(mod.rel, node.lineno, node.col_offset,
                                "RPL001", msg))

    def walk(stmts, scope):
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner = _TaintScope(scope.tainted)
                for _, name, _ in _fn_params(st):
                    inner.tainted.add(name)    # nested defs are traced too
                walk(st.body, inner)
                continue
            for node in ast.walk(st):
                if isinstance(node, ast.Call):
                    tail = _attr_tail(node.func)
                    if tail in ("int", "float", "bool") and node.args and \
                            isinstance(node.func, ast.Name) and \
                            scope.expr(node.args[0]):
                        flag(node, f"`{tail}()` on a tracer-derived value "
                                   "inside a jit-traced function forces a "
                                   "trace-time concretization error or a "
                                   "silent per-value retrace")
                    if isinstance(node.func, ast.Attribute) and \
                            node.func.attr == "item" and not node.args and \
                            scope.expr(node.func.value):
                        flag(node, "`.item()` on a tracer-derived value "
                                   "inside a jit-traced function")
            if isinstance(st, (ast.If, ast.While)):
                if scope.expr(st.test):
                    kind = "if" if isinstance(st, ast.If) else "while"
                    flag(st, f"Python `{kind}` on a tracer-derived value "
                             "inside a jit-traced function (use jnp.where/"
                             "lax.cond, or hoist to a static arg)")
                walk(st.body, scope)
                walk(st.orelse, scope)
            elif isinstance(st, ast.For):
                if scope.expr(st.iter):
                    flag(st, "Python `for` over a tracer-derived value "
                             "inside a jit-traced function (loop bounds "
                             "must be static; use lax.scan/fori_loop)")
                walk(st.body, scope)
                walk(st.orelse, scope)
            elif isinstance(st, (ast.Assign,)):
                tainted = scope.expr(st.value)
                for t in st.targets:
                    scope.assign_target(t, tainted)
            elif isinstance(st, ast.AnnAssign) and st.value is not None:
                scope.assign_target(st.target, scope.expr(st.value))
            elif isinstance(st, ast.AugAssign):
                if scope.expr(st.value):
                    scope.assign_target(st.target, True)
            elif isinstance(st, (ast.With,)):
                walk(st.body, scope)
            elif isinstance(st, ast.Try):
                walk(st.body, scope)
                for h in st.handlers:
                    walk(h.body, scope)
                walk(st.orelse, scope)
                walk(st.finalbody, scope)

    walk(fn.body if not isinstance(fn, ast.Lambda) else [], scope)


def rule_rpl001(mod: ParsedModule, ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for root in _collect_jit_roots(mod):
        fn = root.fn
        if isinstance(fn, ast.Lambda):
            continue                      # no statements to mis-branch on
        tainted: Set[str] = set()
        for pos, name, default in _fn_params(fn):
            static = name in root.static_names or \
                (pos is not None and pos in root.static_nums)
            if static:
                if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                    findings.append(Finding(
                        mod.rel, default.lineno, default.col_offset,
                        "RPL001",
                        f"static jit arg `{name}` has a non-hashable "
                        "(mutable) default: jit static args must be "
                        "hashable or every call re-traces"))
            else:
                tainted.add(name)
        _check_jit_body(fn, _TaintScope(tainted), mod, findings)
    return findings


# ---------------------------------------------------------------------------
# RPL002 — kernel contract (global rule)
# ---------------------------------------------------------------------------

_KERNEL_EXEMPT = {"policy", "ref", "ops", "__init__"}


def _load_registry(policy_mod: ParsedModule):
    for node in ast.walk(policy_mod.tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "KERNEL_REGISTRY":
                    try:
                        return ast.literal_eval(node.value), node.lineno
                    except ValueError:
                        return None, node.lineno
    return None, 1


def _module_has(mod: ParsedModule, pred) -> bool:
    return any(pred(n) for n in ast.walk(mod.tree))


def rule_rpl002(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    ref_defs_cache: Dict[pathlib.Path, Set[str]] = {}

    def sibling(mod: ParsedModule, stem: str) -> Optional[ParsedModule]:
        path = mod.path.parent / f"{stem}.py"
        key = str(path)
        if key in ctx.modules:
            return ctx.modules[key]
        if path.exists():
            from repro.analysis.core import parse_file
            return parse_file(path, ctx.root)
        return None

    for mod in list(ctx.modules.values()):
        if mod.path.parent.name != "kernels" or \
                mod.path.stem in _KERNEL_EXEMPT:
            continue
        calls = [n for n in ast.walk(mod.tree)
                 if isinstance(n, ast.Call)
                 and _attr_tail(n.func) == "pallas_call"]
        if not calls:
            continue
        at = calls[0]
        policy = sibling(mod, "policy")
        if policy is None:
            findings.append(Finding(mod.rel, at.lineno, at.col_offset,
                                    "RPL002",
                                    "pallas_call with no kernels/policy.py "
                                    "to hold the KERNEL_REGISTRY entry"))
            continue
        registry, reg_line = _load_registry(policy)
        if registry is None:
            findings.append(Finding(policy.rel, reg_line, 0, "RPL002",
                                    "KERNEL_REGISTRY missing or not a pure "
                                    "dict literal in kernels/policy.py"))
            continue
        entry = registry.get(mod.path.stem)
        if entry is None:
            findings.append(Finding(
                mod.rel, at.lineno, at.col_offset, "RPL002",
                f"pallas_call site `{mod.path.stem}` has no "
                "KERNEL_REGISTRY entry in kernels/policy.py (every "
                "kernel needs a ref twin + interpret-parity test)"))
            continue
        missing = {"ref", "test", "shape_guard"} - set(entry)
        if missing:
            findings.append(Finding(
                policy.rel, reg_line, 0, "RPL002",
                f"KERNEL_REGISTRY[{mod.path.stem!r}] missing keys: "
                f"{sorted(missing)}"))
            continue
        ref_mod = sibling(mod, "ref")
        ref_path = mod.path.parent / "ref.py"
        if ref_path not in ref_defs_cache:
            ref_defs_cache[ref_path] = set() if ref_mod is None else {
                n.name for n in ast.walk(ref_mod.tree)
                if isinstance(n, ast.FunctionDef)}
        refs = entry["ref"] if isinstance(entry["ref"], (list, tuple)) \
            else [entry["ref"]]
        for ref_name in refs:
            if ref_name not in ref_defs_cache[ref_path]:
                findings.append(Finding(
                    mod.rel, at.lineno, at.col_offset, "RPL002",
                    f"registered ref twin `{ref_name}` is not defined in "
                    "kernels/ref.py"))
        test_path = ctx.root / entry["test"]
        if not test_path.exists():
            findings.append(Finding(
                mod.rel, at.lineno, at.col_offset, "RPL002",
                f"registered parity test `{entry['test']}` does not exist"))
        else:
            text = test_path.read_text()
            if mod.path.stem not in text and \
                    not any(r in text for r in refs):
                findings.append(Finding(
                    mod.rel, at.lineno, at.col_offset, "RPL002",
                    f"parity test `{entry['test']}` references neither "
                    f"`{mod.path.stem}` nor its ref twin"))
        guard = entry["shape_guard"]
        if guard == "checked":
            if not _module_has(mod, lambda n: isinstance(n, ast.Mod)):
                findings.append(Finding(
                    mod.rel, at.lineno, at.col_offset, "RPL002",
                    "shape_guard declared 'checked' but the module has no "
                    "divisibility (%) check guarding its grid/BlockSpec "
                    "assumptions"))
        elif not (isinstance(guard, str) and guard.startswith("fallback:")):
            findings.append(Finding(
                policy.rel, reg_line, 0, "RPL002",
                f"KERNEL_REGISTRY[{mod.path.stem!r}] shape_guard must be "
                "'checked' or a documented 'fallback: ...' note"))
    return findings


# ---------------------------------------------------------------------------
# RPL003 — engine-state aliasing
# ---------------------------------------------------------------------------

# attributes holding (or caching) engine/slot state arrays —
# `_prepared` (sharded int8 weight shards) and `_slot_steps` (per-slot
# step counters) joined with the 2D-mesh sharded engine step
_STATE_ATTRS = {"result", "_slot_bufs", "_beam", "_stream_state", "_gen",
                "_tokens", "cache", "_prepared", "_slot_steps",
                "_fault_log"}   # _fault_log: per-engine fault forensics
                                # (PR 9 quarantine layer)
# engine receivers state may hang off
_ENGINE_NAMES = {"self", "eng", "engine", "sess", "session"}
# engine methods whose return values are materialized views over
# engine-owned buffers: callers must route them through copy_result
_READOUT_CALLS = {"slot_best"}
# calls that SANITIZE (deep-copy) a tainted payload
_SANITIZERS = {"copy_result", "deepcopy", "list", "jsonable", "copy"}


def _receiver_ok(node: ast.AST) -> bool:
    root = _attr_root(node)
    return root in _ENGINE_NAMES or (
        isinstance(node, ast.Attribute) and "engine" in node.attr)


class _AliasScope(_TaintScope):
    def expr(self, node: ast.AST) -> bool:       # noqa: C901 - small DFA
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATE_ATTRS and _receiver_ok(node.value):
                return True
            return self.expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value)
        if isinstance(node, ast.Call):
            tail = _attr_tail(node.func)
            if tail in _SANITIZERS:
                return False
            if tail in _READOUT_CALLS:
                return True
            if tail == "dict":                   # shallow: aliasing survives
                return any(self.expr(a) for a in node.args) or \
                    any(self.expr(kw.value) for kw in node.keywords)
            return False
        if isinstance(node, ast.Dict):
            return any(v is not None and self.expr(v) for v in node.values)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return self.expr(node.body) or self.expr(node.orelse)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        return False


def rule_rpl003(mod: ParsedModule, ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        scope = _AliasScope(set())
        for st in ast.walk(fn):
            if isinstance(st, ast.Assign):
                tainted = scope.expr(st.value)
                for t in st.targets:
                    scope.assign_target(t, tainted)
            elif isinstance(st, ast.Return) and st.value is not None:
                if scope.expr(st.value):
                    findings.append(Finding(
                        mod.rel, st.lineno, st.col_offset, "RPL003",
                        f"`{fn.name}` returns a payload aliasing engine "
                        "slot state without routing through copy_result "
                        "(caller mutation corrupts, or read-only views "
                        "escape, the engine's stored results)"))
            elif isinstance(st, ast.Call) and \
                    _attr_tail(st.func) == "set_result" and st.args and \
                    scope.expr(st.args[0]):
                findings.append(Finding(
                    mod.rel, st.lineno, st.col_offset, "RPL003",
                    "future resolved with a payload aliasing engine slot "
                    "state: route it through copy_result first"))
    return findings


# ---------------------------------------------------------------------------
# RPL004 — thread discipline
# ---------------------------------------------------------------------------

# sync functions that ALSO run on the event-loop thread (not the
# engine worker): supervisor / watchdog / health entry points, matched
# by name.  They observe, abandon, and restart workers, so a direct
# @worker_only call from one of them is the same cross-thread race an
# asyncio handler would have.
_LOOP_SIDE_NAMES = ("supervis", "watchdog", "healthz")


def rule_rpl004(mod: ParsedModule, ctx: Context) -> List[Finding]:
    if not ctx.worker_only_names:
        return []
    findings: List[Finding] = []

    def scan(node: ast.AST, in_lambda: bool, where: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.Lambda):
                scan(child, True, where)
                continue
            if isinstance(child, ast.Call) and not in_lambda:
                tail = _attr_tail(child.func)
                if isinstance(child.func, ast.Attribute) and \
                        tail in ctx.worker_only_names:
                    findings.append(Finding(
                        mod.rel, child.lineno, child.col_offset, "RPL004",
                        f"@worker_only engine method `{tail}` called from "
                        f"{where}: only the engine's "
                        "EngineWorker thread may drive it — submit a "
                        "thunk via worker.call/submit instead"))
            scan(child, in_lambda, where)

    for fn in ast.walk(mod.tree):
        if isinstance(fn, ast.AsyncFunctionDef):
            scan(fn, False, "an asyncio handler")
        elif isinstance(fn, ast.FunctionDef) and \
                any(k in fn.name.lower() for k in _LOOP_SIDE_NAMES):
            scan(fn, False, f"supervisor/watchdog entry point `{fn.name}`")
    return findings


# ---------------------------------------------------------------------------
# RPL005 — RNG discipline
# ---------------------------------------------------------------------------

def rule_rpl005(mod: ParsedModule, ctx: Context) -> List[Finding]:
    calls = [n for n in ast.walk(mod.tree) if isinstance(n, ast.Call)]
    # sharded compute in this module: a jit with explicit shardings, or
    # a shard_map call (the serving engines' 2D ('data','model') step —
    # mesh-dependent RNG would fork per data shard just like it forked
    # per topology in the PR 5 elastic-restart bug)
    has_sharded = any(
        (any(kw.arg in ("out_shardings", "in_shardings")
             for kw in c.keywords) and _attr_tail(c.func) in _JIT_WRAPPERS)
        or _attr_tail(c.func) == "shard_map"
        for c in calls)
    if not has_sharded:
        return []
    key_calls = [c for c in calls if _attr_tail(c.func) == "PRNGKey"]
    if not key_calls:
        return []
    if any(_attr_tail(c.func) == "mesh_invariant_rng" for c in calls):
        return []
    return [Finding(
        mod.rel, c.lineno, c.col_offset, "RPL005",
        "PRNGKey in a module that runs sharded compute (jit with "
        "out_shardings, or shard_map) but never calls "
        "mesh_invariant_rng(): legacy threefry makes the generated "
        "values depend on the mesh — elastic restarts on a different "
        "topology silently fork the trajectory (PR 5 bug), and a "
        "('data','model') serving mesh would fork it per data shard")
        for c in key_calls]


# ---------------------------------------------------------------------------
# RPL006 — collective/axis discipline (interprocedural)
# ---------------------------------------------------------------------------

def _guarded_axes(fi, index) -> Set[str]:
    """Axis names `fi` checks against `mesh.axis_names` before use:
    `"model" in mesh.axis_names`, or a comprehension filtering a
    constant iterable through such a membership test."""
    guarded: Set[str] = set()
    comp_iters: Dict[str, List[ast.expr]] = {}
    for n in index.owned(fi):
        if isinstance(n, (ast.GeneratorExp, ast.ListComp, ast.SetComp)):
            for gen in n.generators:
                if isinstance(gen.target, ast.Name):
                    comp_iters.setdefault(gen.target.id, []) \
                        .append(gen.iter)
    for n in index.owned(fi):
        if not (isinstance(n, ast.Compare) and len(n.ops) == 1
                and isinstance(n.ops[0], (ast.In, ast.NotIn))):
            continue
        if not any(isinstance(a, ast.Attribute)
                   and a.attr == "axis_names"
                   for a in ast.walk(n.comparators[0])):
            continue
        guarded |= set(_const_strs(n.left))
        if isinstance(n.left, ast.Name):
            for it in comp_iters.get(n.left.id, []):
                guarded |= set(_const_strs(it))
    return guarded


def _rpl006_partial(fi, summ, index) -> List[Finding]:
    """Two-level taint inside one shard_map-reachable function:
    level 1 = a shard-local slice (axis_index + dynamic_slice pattern),
    level 2 = a matmul-derived partial product over it.  A level-2
    value escaping via return (or committed to engine state) without a
    dominating psum is each shard's DIFFERENT partial sum — the PR 8
    bug class."""
    from repro.analysis.interproc import MATMUL_TAILS, PSUM_TAILS
    findings: List[Finding] = []
    lv: Dict[str, int] = {}

    def level(expr) -> int:
        if isinstance(expr, ast.Name):
            return lv.get(expr.id, 0)
        if isinstance(expr, ast.Call):
            tail = _attr_tail(expr.func)
            argl = max((level(a) for a in expr.args), default=0)
            argl = max(argl, max((level(kw.value)
                                  for kw in expr.keywords), default=0))
            if tail in PSUM_TAILS:
                return 0
            callees = index.resolve_callable(expr.func, fi, fi.mod)
            if callees:
                c = callees[0]
                if summ.is_shard_local_slicer(c):
                    return 1
                if summ.contains_psum(c):
                    return 0
                if argl and summ.contains_matmul(c):
                    return 2
                return argl
            if tail in MATMUL_TAILS and argl:
                return 2
            return argl
        if isinstance(expr, ast.BinOp):
            sub = max(level(expr.left), level(expr.right))
            if isinstance(expr.op, ast.MatMult) and sub:
                return 2
            return sub
        if isinstance(expr, ast.Attribute):
            return 0 if expr.attr in _SHAPE_ATTRS else level(expr.value)
        if isinstance(expr, ast.Subscript):
            return level(expr.value)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return max((level(e) for e in expr.elts), default=0)
        if isinstance(expr, ast.IfExp):
            return max(level(expr.body), level(expr.orelse))
        if isinstance(expr, ast.UnaryOp):
            return level(expr.operand)
        return 0

    def assign(target, val):
        if isinstance(target, ast.Name):
            lv[target.id] = val
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                assign(e, val)
        elif isinstance(target, ast.Starred):
            assign(target.value, val)
        elif isinstance(target, ast.Attribute):
            if val >= 2 and target.attr in _STATE_ATTRS and \
                    _attr_root(target) in _ENGINE_NAMES:
                findings.append(Finding(
                    fi.mod.rel, target.lineno, target.col_offset,
                    "RPL006",
                    f"partial matmul product committed to engine state "
                    f"`{target.attr}` without a dominating psum: under "
                    "shard_map each shard stores a different partial "
                    "sum"))
        elif isinstance(target, ast.Subscript):
            assign(target.value, val)

    def walk(stmts):
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, ast.Assign):
                val = level(st.value)
                for t in st.targets:
                    assign(t, val)
            elif isinstance(st, ast.AugAssign):
                assign(st.target, max(level(st.value),
                                      level(st.target)))
            elif isinstance(st, ast.AnnAssign) and st.value is not None:
                assign(st.target, level(st.value))
            elif isinstance(st, ast.Return) and st.value is not None:
                if level(st.value) >= 2:
                    findings.append(Finding(
                        fi.mod.rel, st.lineno, st.col_offset, "RPL006",
                        f"`{fi.name}` returns a matmul over a "
                        "shard-local slice with no dominating psum on "
                        "the path: under shard_map every shard returns "
                        "a DIFFERENT partial sum — wrap the product in "
                        "jax.lax.psum(..., axis) (or route through a "
                        "psum-carrying helper)"))
            else:
                for blk_name in ("body", "orelse", "finalbody"):
                    blk = getattr(st, blk_name, None)
                    if blk:
                        walk(blk)
                for h in getattr(st, "handlers", []):
                    walk(h.body)

    walk(fi.node.body)
    return findings


def rule_rpl006(ctx: Context) -> List[Finding]:
    from repro.analysis.interproc import Summaries
    index = ctx.project()
    summ = Summaries(index)
    findings: List[Finding] = []

    # (c) mesh.shape["axis"] on a mesh PARAMETER without an axis_names
    # membership guard anywhere in the function: helpers taking a
    # caller's mesh must not assume its topology.
    for fi in index.functions.values():
        if "mesh" not in index.param_names(fi):
            continue
        guarded = _guarded_axes(fi, index)
        for n in index.owned(fi):
            if not (isinstance(n, ast.Subscript)
                    and isinstance(n.value, ast.Attribute)
                    and n.value.attr == "shape"
                    and isinstance(n.value.value, ast.Name)
                    and n.value.value.id == "mesh"):
                continue
            sl = n.slice
            if isinstance(sl, ast.Constant) and \
                    isinstance(sl.value, str) and sl.value not in guarded:
                findings.append(Finding(
                    fi.mod.rel, n.lineno, n.col_offset, "RPL006",
                    f"`mesh.shape[{sl.value!r}]` in `{fi.name}` without "
                    f"checking {sl.value!r} in mesh.axis_names: "
                    "KeyErrors (or silently mis-shards) on meshes that "
                    "don't declare the axis — guard the lookup or use "
                    "mesh.shape.get"))

    # (a)+(b): shard_map-reachable functions
    roots = index.shard_map_roots()
    declared_of = {id(r): (summ.p_literals(r.binder)
                           if r.binder is not None else set())
                   for r in roots}
    reach = index.reachable([r.fn for r in roots])
    for fi, root_fns in reach.items():
        rs = [r for r in roots if r.fn in root_fns]
        declared: Set[str] = set()
        for r in rs:
            declared |= declared_of[id(r)]
        declared_known = bool(rs) and \
            all(declared_of[id(r)] for r in rs)
        if declared_known:
            for coll in summ.collectives(fi):
                vals, complete = summ.axis_values(coll.axis, fi)
                if complete and vals and not vals <= declared:
                    related = tuple((r.binder.mod.rel, r.call.lineno)
                                    for r in rs if r.binder is not None)
                    findings.append(Finding(
                        fi.mod.rel, coll.call.lineno,
                        coll.call.col_offset, "RPL006",
                        f"`{coll.kind}` over axis "
                        f"{sorted(vals - declared)} inside "
                        f"shard_map-reachable `{fi.name}`, but the "
                        "binding shard_map's PartitionSpecs only "
                        f"declare {sorted(declared)}: an undeclared "
                        "axis name fails at trace time (or silently "
                        "no-ops under a differently-named mesh)",
                        related=related))
        findings.extend(_rpl006_partial(fi, summ, index))
    return findings


# ---------------------------------------------------------------------------
# RPL007 — Pallas block contract (interprocedural)
# ---------------------------------------------------------------------------

def _sibling_module(ctx: Context, mod: ParsedModule,
                    stem: str) -> Optional[ParsedModule]:
    path = mod.path.parent / f"{stem}.py"
    key = str(path)
    if key in ctx.modules:
        return ctx.modules[key]
    if path.exists():
        from repro.analysis.core import parse_file
        return parse_file(path, ctx.root)
    return None


def _required_params(fn) -> Set[str]:
    a = fn.args
    pos = [*a.posonlyargs, *a.args]
    required = {p.arg for p in pos[:len(pos) - len(a.defaults)]}
    required |= {p.arg for p, d in zip(a.kwonlyargs, a.kw_defaults)
                 if d is None}
    return required


def _all_params(fn) -> Set[str]:
    a = fn.args
    return {p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)}


def _index_map_lambda(expr, fi, index):
    """Resolve a BlockSpec index_map argument to a Lambda node: either
    inline, or a local name bound to one (`row = lambda b: (b, 0)`)."""
    if isinstance(expr, ast.Lambda):
        return expr
    if isinstance(expr, ast.Name) and fi is not None:
        for rhs in index.local_assignments(fi, expr.id):
            if isinstance(rhs, ast.Lambda):
                return rhs
    return None


def _index_map_violations(lam) -> List[str]:
    params = {a.arg for a in (*lam.args.posonlyargs, *lam.args.args,
                              *lam.args.kwonlyargs)}
    fn_names = set()
    for n in ast.walk(lam.body):
        if isinstance(n, ast.Call):
            for f in ast.walk(n.func):
                if isinstance(f, ast.Name):
                    fn_names.add(id(f))
    out = []
    for n in ast.walk(lam.body):
        if isinstance(n, ast.Name) and n.id not in params and \
                id(n) not in fn_names:
            out.append(f"closes over `{n.id}`")
        elif isinstance(n, ast.Constant) and \
                not isinstance(n.value, int):
            out.append(f"non-integer constant {n.value!r}")
    return sorted(set(out))


def _guards_divisibility(fi, index, depth: int = 2,
                         _seen=None) -> bool:
    if fi is None:
        return False
    if _seen is None:
        _seen = set()
    if id(fi) in _seen:
        return False
    _seen.add(id(fi))
    # operator nodes are interpreter singletons, so test the BinOp /
    # AugAssign carriers rather than the ast.Mod instances themselves
    if any(isinstance(n, (ast.BinOp, ast.AugAssign))
           and isinstance(n.op, ast.Mod)
           for n in ast.walk(fi.node)):
        return True
    if depth > 0:
        return any(_guards_divisibility(callee, index, depth - 1, _seen)
                   for _, callee in index.callees(fi))
    return False


def rule_rpl007(ctx: Context) -> List[Finding]:
    index = ctx.project()
    findings: List[Finding] = []
    for mod in list(ctx.modules.values()):
        if mod.path.parent.name != "kernels" or \
                mod.path.stem in _KERNEL_EXEMPT:
            continue
        calls = [n for n in ast.walk(mod.tree)
                 if isinstance(n, ast.Call)
                 and _attr_tail(n.func) == "pallas_call"]
        if not calls:
            continue
        at = calls[0]
        policy = _sibling_module(ctx, mod, "policy")
        registry, reg_line = _load_registry(policy) \
            if policy is not None else (None, 1)
        entry_meta = (registry or {}).get(mod.path.stem)
        if entry_meta is None:
            continue                       # RPL002's finding; don't dup
        entry_name = entry_meta.get("entry")
        if not entry_name:
            findings.append(Finding(
                policy.rel, reg_line, 0, "RPL007",
                f"KERNEL_REGISTRY[{mod.path.stem!r}] has no 'entry' "
                "metadata naming the public wrapper whose signature "
                "mirrors the ref twin and whose body guards the grid"))
            continue
        entry_fn = next(
            (n for n in mod.tree.body
             if isinstance(n, ast.FunctionDef) and n.name == entry_name),
            None)
        if entry_fn is None:
            findings.append(Finding(
                mod.rel, at.lineno, at.col_offset, "RPL007",
                f"registered entry wrapper `{entry_name}` is not "
                f"defined at module level in {mod.rel}"))
            continue

        # signature parity: some registered ref twin's REQUIRED params
        # must all appear in the entry wrapper's signature, so the
        # policy can swap entry<->ref call-compatibly.
        ref_mod = _sibling_module(ctx, mod, "ref")
        refs = entry_meta.get("ref", [])
        refs = refs if isinstance(refs, (list, tuple)) else [refs]
        ref_fns = []
        if ref_mod is not None:
            ref_fns = [n for n in ref_mod.tree.body
                       if isinstance(n, ast.FunctionDef)
                       and n.name in refs]
        if ref_fns:
            entry_params = _all_params(entry_fn)
            if not any(_required_params(r) <= entry_params
                       for r in ref_fns):
                want = sorted(_required_params(ref_fns[0]) - entry_params)
                findings.append(Finding(
                    mod.rel, entry_fn.lineno, entry_fn.col_offset,
                    "RPL007",
                    f"entry wrapper `{entry_name}` matches no "
                    f"registered ref twin's required signature "
                    f"(e.g. `{ref_fns[0].name}` needs {want}): policy "
                    "dispatch between kernel and ref would TypeError"))

        # index_map outputs must be pure functions of the grid indices
        for call in ast.walk(mod.tree):
            if not (isinstance(call, ast.Call)
                    and _attr_tail(call.func) == "BlockSpec"):
                continue
            im = next((kw.value for kw in call.keywords
                       if kw.arg == "index_map"),
                      call.args[1] if len(call.args) > 1 else None)
            if im is None:
                continue
            lam = _index_map_lambda(im, index.owner.get(call), index)
            if lam is None:
                continue
            for why in _index_map_violations(lam):
                findings.append(Finding(
                    mod.rel, lam.lineno, lam.col_offset, "RPL007",
                    f"BlockSpec index_map {why}: index maps must be "
                    "pure functions of the grid indices (plus int "
                    "literals) or the block offsets silently read the "
                    "wrong tiles"))

        # shape_guard 'checked' means the divisibility check must
        # dominate each pallas_call (same function or a callee)
        if entry_meta.get("shape_guard") == "checked":
            for call in calls:
                encl = index.owner.get(call)
                if not _guards_divisibility(encl, index):
                    findings.append(Finding(
                        mod.rel, call.lineno, call.col_offset, "RPL007",
                        "pallas_call under shape_guard 'checked' whose "
                        "enclosing function (and two callee levels) has "
                        "no divisibility (%) check: the grid contract "
                        "is asserted by the registry but not enforced "
                        "on this call path"))
    return findings


# ---------------------------------------------------------------------------
# RPL008 — commit discipline (interprocedural)
# ---------------------------------------------------------------------------

# transactional slot/pool state: RPL003's attrs minus the readout
# payload (`result`, owned per-session) and the forensics log
# (`_fault_log`, append-only and harvested after recovery)
_RPL008_ATTRS = _STATE_ATTRS - {"result", "_fault_log"}
_RPL008_RECEIVERS = {"self", "eng", "engine"}
_MUTATOR_METHODS = {"append", "extend", "update", "clear", "pop",
                    "remove", "insert", "fill", "setdefault"}


def _state_attr_of(node) -> Optional[str]:
    t = node
    if isinstance(t, ast.Subscript):
        t = t.value
    if isinstance(t, ast.Attribute) and t.attr in _RPL008_ATTRS and \
            _attr_root(t) in _RPL008_RECEIVERS:
        return t.attr
    return None


def _rpl008_fn(fi, summ, index) -> List[Finding]:
    """Execution-order walk flagging a DIRECT engine-state mutation
    followed by a may-raise call (jit dispatch, fault-injector probe,
    or a callee that raises — two levels deep).  Loop bodies are walked
    once (each iteration is its own transaction), except-handler bodies
    are recovery code and skipped, and a try with handlers or a
    state-restoring finally protects its calls."""
    findings: List[Finding] = []
    pending: List[Tuple[str, int]] = []

    def hazard_of(call):
        h = summ.call_hazard(call)
        if h is not None:
            return h, ()
        for tgt in index.resolve_callable(call.func, fi, fi.mod):
            if tgt is fi:
                continue
            mr = summ.may_raise(tgt)
            if mr is not None:
                return (f"calls `{tgt.name}()` which {mr.reason}",
                        ((mr.where, mr.line),))
        return None

    def check_calls(node, protected):
        for n in ast.walk(node):
            if not (isinstance(n, ast.Call)
                    and index.owner.get(n) is fi):
                continue
            hz = hazard_of(n)
            if hz is None or not pending or protected:
                continue
            attr, mline = pending[0]
            reason, related = hz
            findings.append(Finding(
                fi.mod.rel, n.lineno, n.col_offset, "RPL008",
                f"engine state `{attr}` mutated at line {mline} and "
                f"then a may-raise call runs ({reason}): a raise "
                "leaves the slot/pool half-committed — stage results "
                "locally and commit after the call, probe with "
                "commit=False first, or restore in a finally",
                related=((fi.mod.rel, mline),) + related))

    def record(st):
        if isinstance(st, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = st.targets if isinstance(st, ast.Assign) \
                else [st.target]
            for t in targets:
                attr = _state_attr_of(t)
                if attr is not None:
                    pending.append((attr, st.lineno))
        elif isinstance(st, ast.Expr) and isinstance(st.value, ast.Call):
            c = st.value
            if isinstance(c.func, ast.Attribute) and \
                    c.func.attr in _MUTATOR_METHODS:
                attr = _state_attr_of(c.func.value)
                if attr is not None:
                    pending.append((attr, st.lineno))

    def finally_restores(st) -> bool:
        for blk_st in st.finalbody:
            for n in ast.walk(blk_st):
                if isinstance(n, (ast.Assign, ast.AugAssign,
                                  ast.AnnAssign)):
                    targets = n.targets if isinstance(n, ast.Assign) \
                        else [n.target]
                    if any(_state_attr_of(t) is not None
                           for t in targets):
                        return True
        return False

    def walk(stmts, protected):
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, ast.Try):
                prot = protected or bool(st.handlers) or \
                    finally_restores(st)
                walk(st.body, prot)
                walk(st.orelse, prot)
                walk(st.finalbody, protected)
            elif isinstance(st, (ast.If, ast.While)):
                check_calls(st.test, protected)
                walk(st.body, protected)
                walk(st.orelse, protected)
            elif isinstance(st, ast.For):
                check_calls(st.iter, protected)
                walk(st.body, protected)
                walk(st.orelse, protected)
            elif isinstance(st, ast.With):
                for item in st.items:
                    check_calls(item.context_expr, protected)
                walk(st.body, protected)
            else:
                check_calls(st, protected)
                record(st)

    walk(fi.node.body, False)
    return findings


def rule_rpl008(ctx: Context) -> List[Finding]:
    from repro.analysis.interproc import Summaries
    index = ctx.project()
    summ = Summaries(index)
    findings: List[Finding] = []
    for fi in index.functions.values():
        findings.extend(_rpl008_fn(fi, summ, index))
    return findings


PER_FILE_RULES = {
    "RPL001": rule_rpl001,
    "RPL003": rule_rpl003,
    "RPL004": rule_rpl004,
    "RPL005": rule_rpl005,
}

GLOBAL_RULES = {
    "RPL002": rule_rpl002,
    "RPL006": rule_rpl006,
    "RPL007": rule_rpl007,
    "RPL008": rule_rpl008,
}


def iter_rule_codes() -> Iterable[str]:
    yield from PER_FILE_RULES
    yield from GLOBAL_RULES
