"""Repo AST lints — pure-stdlib ``ast`` pass over the codebase itself.

The runtime's correctness leans on conventions no unit test can see
whole: jit kernels must stay trace-pure (the kernels' design rules
exist because host round-trips inside kernels silently retrace or
pin stale values), ``faults.fire`` literals must match the registry in
``faults.py`` (a drifted literal = a chaos plan that injects nothing),
config/metric name literals must stay inside their declared grammars,
durable tiers must route writes through the fs.py seam (PR 14), and
the epoch-fenced lease discipline (PRs 9/18) must gate every fenced
publication. ``python -m flink_tpu lint`` and the tier-1 dogfood gate
(tests/test_analysis.py) keep the shipped tree at zero findings.

The pass is INTERPROCEDURAL: every linted file is indexed into one
project call graph (``analysis/callgraph.py`` — defs, methods via
self-type, import aliases, binding-type lock tracking), and the rules
that need it follow calls to arbitrary depth. Rules group into PLANES
(the ``--plane`` CLI filter keys on these):

- ``tracer`` — TRACER_HOST_CALL / TRACER_BRANCH (error): host
  conversions (``float()/int()/bool()``, ``np.asarray``,
  ``.item()/.tolist()``) or Python control flow on a value derived
  from a traced parameter, inside a jitted kernel OR any helper the
  kernel's traced arguments flow into (taint maps actuals to formals
  across resolved calls; a helper that only ever receives concrete
  values stays out of scope).
- ``registry`` — FAULT_POINT_DRIFT (error): a ``faults.fire`` literal
  outside ``faults.KNOWN_FAULT_POINTS``; FAULT_POINT_UNFIRED (warn),
  the REVERSE direction: a registered point with no fire site
  anywhere in the linted set is dead registry. Fire sites resolve
  through module string constants (``fire(TASK_FAULT_POINT)``) and
  one parameter-forwarding hop (``fire(fsync_point)`` + a call site
  passing ``fsync_point="state.run.fsync"``); intentionally
  registered-first points live in ``faults.UNFIRED_ALLOWLIST``. The
  rule only runs when the linted set contains the registry
  assignment itself — lint the whole tree for a meaningful result.
- ``config`` — CONFIG_KEY_DRIFT / CONFIG_OPTION_DUP (error): literals
  outside the declared option grammar / duplicate declarations.
- ``metrics`` — METRIC_NAME_INVALID (warn): names outside the
  snake_case grammar dashboards key on.
- ``concurrency`` — HOSTPOOL_SHARED_WRITE (warn): a closure submitted
  to ``HostPool.run_tasks`` assigns through a free variable outside a
  lock guard, followed through ANY number of same-module call hops
  (a helper called with shared state keeps the shared tag on the
  bound formal). Locks are recognized by BINDING TYPE — a name or
  ``self`` attribute assigned ``threading.Lock()/RLock()/...`` —
  with the legacy ``*lock*`` name-substring accepted for locks that
  arrive as parameters.
- ``durability`` — DURABILITY_SEAM_BYPASS (error): a raw
  ``open(..., 'w')`` / ``os.fsync`` / ``os.replace`` / ``os.rename``
  in a durable-tier module (the PR-14 seam contract; the
  tests/test_architecture.py gate is a thin wrapper over this rule).
  ``os.rename`` of lock/lease/grave files is the documented
  local-lock-primitive residue and exempt.
- ``locking`` — LOCK_ORDER_CYCLE (warn): a lock-acquisition graph
  from nested ``with`` guards ACROSS call edges; two tracked locks
  taken in opposite orders on two paths is a potential ABBA
  deadlock, reported with both acquisition paths named. Reentrant
  self-acquisition (RLock) is not an edge.
- ``fencing`` — FENCE_UNVERIFIED_PUBLISH (error): in a LEASED class
  (one whose methods call ``self.<attr>.verify(...)``), a public
  method that reaches a ``write_atomic``/``put_if`` of a fenced
  record (marker/manifest/offset/status/membership path text) with
  no lease ``verify()``/renew earlier on the path — the PR-9/18
  fencing discipline checked statically. Publishing the lease/lock
  record itself IS the fence and is exempt.

Honest scope (syntactic, flow-insensitive): name resolution is the
call graph's — no values-as-functions, no conditional rebinding, no
symbolic shapes. Taint has no aliasing; values reached only through
static attributes (``.shape``/``.ndim``/``.dtype``/``.size``),
``len()``, ``is``/``in`` tests are NOT tainted. Only functions jitted
DIRECTLY are kernel roots. Hostpool closure discovery is unchanged:
lambdas/defs in the ``run_tasks`` argument list, names the file binds
such closures to; writes through per-task PARAMETERS stay out of
scope, as do mutating method calls (``shared.append(x)``). Fence/
lock-order walks flatten branches in source order (a fence inside an
``if`` counts).
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from flink_tpu.analysis.core import Finding
from flink_tpu.analysis.callgraph import (
    LOCK_CONSTRUCTORS,
    CallGraph,
    FuncInfo,
    ModuleInfo,
    _call_ctor_name,
    build_graph,
)

# (rule id, severity, plane, one-line description, fix hint) — the
# "pylint" planes of RULES.md (analysis/docs.py renders this next to
# the plan/config/dataflow catalog in core.rule_catalog_full()).
LINT_CATALOG: Tuple[Tuple[str, str, str, str, str], ...] = (
    ("TRACER_HOST_CALL", "error", "tracer",
     "Host conversion (float/int/bool, np.asarray, .item/.tolist) on a "
     "traced value inside a jit kernel or a helper its traced "
     "arguments flow into.",
     "keep it on device (jnp) or hoist the conversion out"),
    ("TRACER_BRANCH", "error", "tracer",
     "Python if/while/ternary or range() on a traced value inside a "
     "jit kernel or a helper its traced arguments flow into.",
     "use lax.cond / jnp.where / lax.fori_loop"),
    ("FAULT_POINT_DRIFT", "error", "registry",
     "A faults.fire literal outside faults.KNOWN_FAULT_POINTS.",
     "register the point or fix the literal"),
    ("FAULT_POINT_UNFIRED", "warn", "registry",
     "A registered fault point with no faults.fire site anywhere in "
     "the linted tree — dead registry chaos plans can never hit.",
     "instrument the seam with faults.fire, delete the point, or add "
     "it to faults.UNFIRED_ALLOWLIST"),
    ("CONFIG_KEY_DRIFT", "error", "config",
     "A get_raw/Configuration key literal outside the declared option "
     "grammar.",
     "declare a ConfigOption / dynamic prefix, or fix the literal"),
    ("CONFIG_OPTION_DUP", "error", "config",
     "One option key declared by two ConfigOption literals — last "
     "registration silently wins.",
     "reuse the existing ConfigOption constant"),
    ("METRIC_NAME_INVALID", "warn", "metrics",
     "A metric/group name literal outside the snake_case grammar.",
     "rename to lowercase snake_case"),
    ("HOSTPOOL_SHARED_WRITE", "warn", "concurrency",
     "A closure submitted to HostPool.run_tasks writes shared mutable "
     "state (free-variable attribute/subscript target, nonlocal/"
     "global) outside a lock guard, at any call depth.",
     "guard the write with a lock, or return a partial and combine on "
     "the caller"),
    ("DURABILITY_SEAM_BYPASS", "error", "durability",
     "A raw open(mode w/a/+), os.fsync, os.replace or os.rename in a "
     "durable-tier module bypasses the fs.py FileSystem seam.",
     "route through fs.open_write(sync=)/fs.fsync/fs.rename/"
     "write_atomic"),
    ("LOCK_ORDER_CYCLE", "warn", "locking",
     "Two tracked locks acquired in opposite orders on two call paths "
     "— a potential ABBA deadlock.",
     "pick one global acquisition order (lock hierarchy) or collapse "
     "them into one lock"),
    ("FENCE_UNVERIFIED_PUBLISH", "error", "fencing",
     "A fenced record (marker/manifest/offset/status/membership) "
     "published from a leased class's method with no lease "
     "verify()/renew on the path.",
     "call the lease verify()/renew gate before the publication"),
)
LINT_RULES: Tuple[Tuple[str, str], ...] = tuple(
    (r, s) for r, s, _p, _d, _f in LINT_CATALOG)
LINT_PLANES: Dict[str, str] = {r: p for r, _s, p, _d, _f in LINT_CATALOG}
_SEV = dict(LINT_RULES)

_METRIC_KINDS = ("counter", "gauge", "meter", "histogram")
_METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)*$")
# attribute reads that are STATIC under tracing — a name reached only
# through these never carries the tracer into host code
_STATIC_ATTRS = frozenset(("shape", "ndim", "dtype", "size"))
_HOST_CONVERSIONS = frozenset(("float", "int", "bool"))
_HOST_METHODS = frozenset(("item", "tolist"))
_NP_MATERIALIZERS = frozenset(("asarray", "array"))

# the tiers whose on-disk state must survive a power cut — the PR-14
# seam contract (tests/test_architecture.py gates on this rule)
DURABLE_MODULES = frozenset(
    "flink_tpu/" + m for m in (
        "log/topic.py", "log/bus.py", "log/connectors.py",
        "checkpoint/storage.py", "checkpoint/coordinator.py",
        "api/sinks.py", "connectors.py",
        "runtime/ha.py", "runtime/blob.py", "runtime/session.py",
        "fsck.py", "state/lsm.py"))

# path-text tokens that mark a FENCED record (the 2PC markers, the
# compaction/LSM manifests, group offsets/membership, cleaner status)
_FENCED_TOKENS = ("marker", "manifest", "offset", "status", "membership")

_TAINT_DEPTH = 8        # tracer call-descent cap
_POOL_DEPTH = 6         # hostpool call-descent cap
_FENCE_DEPTH = 6        # fence-walk call-descent cap


def _finding(rule: str, message: str, file: str, line: int,
             fix: str = "") -> Finding:
    return Finding(rule=rule, severity=_SEV[rule], message=message,
                   fix=fix, file=file, line=line)


def _iter_skip_nested(node: ast.AST):
    """Pre-order (source-order) walk that does NOT enter nested
    function/lambda bodies — they run in another frame (or thread)."""
    for c in ast.iter_child_nodes(node):
        yield c
        if not isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            yield from _iter_skip_nested(c)


def _enclosing_map(mi: ModuleInfo) -> Dict[int, FuncInfo]:
    """id(node) -> innermost enclosing FuncInfo for every node inside
    any indexed function of the module."""
    fis = [fi for fns in mi.functions.values() for fi in fns]
    # largest subtrees first so inner defs overwrite their enclosers
    sized = sorted(((len(list(ast.walk(fi.node))), fi) for fi in fis),
                   key=lambda t: -t[0])
    encl: Dict[int, FuncInfo] = {}
    for _, fi in sized:
        for n in ast.walk(fi.node):
            encl[id(n)] = fi
    return encl


def _all_param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


# -- jit-kernel discovery ---------------------------------------------------

@dataclasses.dataclass
class _Kernel:
    fn: ast.AST                  # FunctionDef / AsyncFunctionDef / Lambda
    static_names: Set[str]


def _is_jit_expr(node: ast.AST) -> bool:
    """``jit`` / ``jax.jit`` (any attribute path ending in .jit)."""
    if isinstance(node, ast.Name):
        return node.id == "jit"
    if isinstance(node, ast.Attribute):
        return node.attr == "jit"
    return False


def _static_names(jit_call: Optional[ast.Call],
                  fn: ast.AST) -> Set[str]:
    """Param names excluded from tracing via static_argnums/names."""
    out: Set[str] = set()
    if jit_call is None:
        return out
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for kw in jit_call.keywords:
        if kw.arg == "static_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, int):
                    if 0 <= c.value < len(params):
                        out.add(params[c.value])
        elif kw.arg == "static_argnames":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    out.add(c.value)
    return out


def _collect_kernels(mi: ModuleInfo) -> List[_Kernel]:
    """Functions DIRECTLY jitted in this file: decorator forms
    (``@jit``, ``@jax.jit``, ``@partial(jax.jit, ...)``,
    ``@jax.jit(...)`` with kwargs) and call forms (``jax.jit(f)``,
    ``jax.jit(shard_map(f, ...))`` where ``f`` is a local def)."""
    defs_by_name: Dict[str, List[ast.AST]] = {
        name: [fi.node for fi in fns]
        for name, fns in mi.functions.items()}

    kernels: List[_Kernel] = []
    seen: Set[int] = set()

    def add(fn: ast.AST, jit_call: Optional[ast.Call]) -> None:
        if id(fn) in seen:
            return
        seen.add(id(fn))
        kernels.append(_Kernel(fn, _static_names(jit_call, fn)))

    for node in mi.nodes:
        # decorator forms
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_expr(dec):
                    add(node, None)
                elif isinstance(dec, ast.Call):
                    if _is_jit_expr(dec.func):
                        add(node, dec)
                    elif (isinstance(dec.func, (ast.Name, ast.Attribute))
                          and (dec.func.attr if isinstance(
                              dec.func, ast.Attribute) else dec.func.id)
                          == "partial"
                          and dec.args and _is_jit_expr(dec.args[0])):
                        add(node, dec)
        # call forms: jax.jit(f) / jax.jit(shard_map(f, ...))
        elif isinstance(node, ast.Call) and _is_jit_expr(node.func):
            if not node.args:
                continue
            target = node.args[0]
            if (isinstance(target, ast.Call)
                    and isinstance(target.func, (ast.Name, ast.Attribute))
                    and (target.func.attr if isinstance(
                        target.func, ast.Attribute) else target.func.id)
                    == "shard_map" and target.args):
                target = target.args[0]
            if isinstance(target, ast.Name):
                for fn in defs_by_name.get(target.id, ()):
                    add(fn, node)
            elif isinstance(target, ast.Lambda):
                add(target, node)
    return kernels


# -- taint walk over a kernel body and the helpers it reaches ---------------

class _TaintVisitor(ast.NodeVisitor):
    """One in-order pass over a function body. ``tainted`` starts as
    the traced parameter set; a single assignment hop propagates it
    within the body, and resolved calls with tainted actuals recurse
    into the callee with the matching FORMALS tainted (the
    interprocedural extension). The visitor flags host conversions and
    Python control flow on tainted expressions."""

    def __init__(self, graph: CallGraph, mi: ModuleInfo,
                 ctx: Optional[FuncInfo], file: str, where: str,
                 kernel: str, tainted: Set[str],
                 visited: Set[Tuple[int, frozenset]],
                 depth: int = 0) -> None:
        self.graph = graph
        self.mi = mi
        self.ctx = ctx
        self.file = file
        self.where = where          # "jit kernel 'k'" / helper phrasing
        self.kernel = kernel
        self.tainted = set(tainted)
        self.visited = visited
        self.depth = depth
        self.findings: List[Finding] = []

    # -- taint test -------------------------------------------------------
    def _expr_tainted(self, node: ast.AST) -> bool:
        """Does this expression carry a traced value into host code?
        Names under static attributes / len() / `is`/`in` tests don't."""
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return False
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id == "len":
                return False  # len() of arrays/dicts is static
            if isinstance(fn, ast.Name) and fn.id == "isinstance":
                return False
        if isinstance(node, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                for op in node.ops):
            # `x is None` / `"col" in data` are static under tracing
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        return any(self._expr_tainted(c) for c in ast.iter_child_nodes(node))

    # -- taint propagation (one hop, source order) ------------------------
    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)
        names = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if self._expr_tainted(node.value):
            self.tainted.update(names)
        else:
            self.tainted.difference_update(names)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.visit(node.value)
        if (isinstance(node.target, ast.Name)
                and self._expr_tainted(node.value)):
            self.tainted.add(node.target.id)

    # -- flagged sites ----------------------------------------------------
    def _flag(self, rule: str, line: int, what: str, fix: str) -> None:
        self.findings.append(_finding(
            rule, f"{what} inside {self.where} — host round-trips on "
            "traced values retrace or pin stale constants",
            self.file, line, fix=fix))

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if (isinstance(fn, ast.Name) and fn.id in _HOST_CONVERSIONS
                and node.args and self._expr_tainted(node.args[0])):
            self._flag("TRACER_HOST_CALL", node.lineno,
                       f"{fn.id}() on a traced value",
                       "keep it on device (jnp.astype/where) or hoist "
                       "the conversion out of the kernel")
        elif (isinstance(fn, ast.Attribute)
              and fn.attr in _NP_MATERIALIZERS
              and isinstance(fn.value, ast.Name)
              and fn.value.id in ("np", "numpy")
              and node.args and self._expr_tainted(node.args[0])):
            self._flag("TRACER_HOST_CALL", node.lineno,
                       f"np.{fn.attr}() on a traced value",
                       "use jnp inside kernels; numpy materializes on "
                       "the host")
        elif (isinstance(fn, ast.Attribute) and fn.attr in _HOST_METHODS
              and self._expr_tainted(fn.value)):
            self._flag("TRACER_HOST_CALL", node.lineno,
                       f".{fn.attr}() on a traced value",
                       "fetch after the kernel returns, not inside it")
        self._descend(node)
        self.generic_visit(node)

    def _descend(self, node: ast.Call) -> None:
        """Map tainted actuals to formals of every resolvable callee
        and lint the callee body under that taint set."""
        if self.depth >= _TAINT_DEPTH:
            return
        for fi in self.graph.resolve(node, self.ctx, self.mi):
            pos = fi.params()
            offset = 1 if (fi.is_method and pos[:1] == ["self"]
                           and isinstance(node.func, ast.Attribute)) else 0
            names = set(_all_param_names(fi.node))
            tset: Set[str] = set()
            for i, arg in enumerate(node.args):
                if isinstance(arg, ast.Starred):
                    continue
                j = i + offset
                if j < len(pos) and self._expr_tainted(arg):
                    tset.add(pos[j])
            for kw in node.keywords:
                if kw.arg and kw.arg in names \
                        and self._expr_tainted(kw.value):
                    tset.add(kw.arg)
            if not tset:
                continue  # only concrete values flow in — out of scope
            key = (id(fi.node), frozenset(tset))
            if key in self.visited:
                continue
            self.visited.add(key)
            sub = _TaintVisitor(
                self.graph, self.graph.modules.get(fi.module, self.mi),
                fi, fi.file,
                f"helper {fi.name!r} (traced arguments flow in from jit "
                f"kernel {self.kernel!r})",
                self.kernel, tset, self.visited, self.depth + 1)
            for stmt in fi.node.body:
                sub.visit(stmt)
            self.findings.extend(sub.findings)

    def _check_test(self, test: ast.AST, line: int, kind: str) -> None:
        if self._expr_tainted(test):
            self._flag("TRACER_BRANCH", line,
                       f"Python {kind} on a traced value",
                       "use lax.cond/lax.select/jnp.where or a mask; "
                       "host control flow cannot see device values")

    def visit_If(self, node: ast.If) -> None:
        self._check_test(node.test, node.lineno, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_test(node.test, node.lineno, "while")
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:
        self._check_test(node.test, node.lineno, "conditional expression")
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        it = node.iter
        if (isinstance(it, ast.Call) and isinstance(it.func, ast.Name)
                and it.func.id == "range"
                and any(self._expr_tainted(a) for a in it.args)):
            self._flag("TRACER_BRANCH", node.lineno,
                       "range() over a traced value",
                       "use lax.fori_loop/lax.scan for traced trip "
                       "counts")
        self.generic_visit(node)

    # nested defs: their params shadow the outer taint
    def _visit_nested(self, node) -> None:
        params = {a.arg for a in node.args.posonlyargs + node.args.args}
        saved = self.tainted
        self.tainted = saved - params
        self.generic_visit(node)
        self.tainted = saved

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_nested(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_nested(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._visit_nested(node)


def _lint_tracer_leaks(graph: CallGraph, mi: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    visited: Set[Tuple[int, frozenset]] = set()
    for kernel in _collect_kernels(mi):
        fn = kernel.fn
        if isinstance(fn, ast.Lambda):
            params = {a.arg for a in fn.args.posonlyargs + fn.args.args}
            name = "<lambda>"
            body: Sequence[ast.AST] = [fn.body]
        else:
            params = {a.arg for a in fn.args.posonlyargs + fn.args.args}
            name = fn.name
            body = fn.body
        tainted = params - kernel.static_names - {"self"}
        v = _TaintVisitor(graph, mi, graph.func_of_node(fn), mi.file,
                          f"jit kernel {name!r}", name, tainted, visited)
        for stmt in body:
            v.visit(stmt)
        out.extend(v.findings)
    # two kernels can reach the same helper line — report it once
    seen: Set[Tuple[str, str, int]] = set()
    deduped: List[Finding] = []
    for f in out:
        k = (f.rule, f.file, f.line)
        if k not in seen:
            seen.add(k)
            deduped.append(f)
    return deduped


# -- registry-drift lints ---------------------------------------------------

def _str_arg(node: ast.Call, i: int = 0) -> Optional[str]:
    if len(node.args) > i and isinstance(node.args[i], ast.Constant) \
            and isinstance(node.args[i].value, str):
        return node.args[i].value
    return None


def _is_fire_call(fn: ast.AST) -> bool:
    return ((isinstance(fn, ast.Attribute) and fn.attr == "fire"
             and isinstance(fn.value, ast.Name)
             and fn.value.id == "faults")
            or (isinstance(fn, ast.Name) and fn.id == "fire"))


def _lint_fault_points(mi: ModuleInfo) -> List[Finding]:
    from flink_tpu.faults import KNOWN_FAULT_POINTS

    out: List[Finding] = []
    for node in mi.calls:
        if not _is_fire_call(node.func):
            continue
        point = _str_arg(node)
        if point is not None and point not in KNOWN_FAULT_POINTS:
            out.append(_finding(
                "FAULT_POINT_DRIFT",
                f"faults.fire({point!r}) is not in "
                "faults.KNOWN_FAULT_POINTS — chaos rules targeting it "
                "can never be validated, and the analyzer will reject "
                "confs that name it", mi.file, node.lineno,
                fix="add the point to KNOWN_FAULT_POINTS (and the "
                    "module docstring's point list) or fix the literal"))
    return out


def _lint_unfired_points(graph: CallGraph) -> List[Finding]:
    """Reverse drift: registry entries with NO fire site in the linted
    set. Fire-site resolution: string literals, module constants
    (``fire(TASK_FAULT_POINT)`` / ``fire(mod.CONST)``), and ONE
    parameter-forwarding hop — ``fire(p)`` where ``p`` is a parameter
    of the enclosing function, matched against every call site of a
    function with that name passing a string literal (or module
    constant) in that position/keyword."""
    registry: List[Tuple[str, str, int]] = []
    allow: Set[str] = set()
    reg_present = False
    for mi in graph.modules.values():
        for node in mi.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            names = {t.id for t in node.targets if isinstance(t, ast.Name)}
            if "KNOWN_FAULT_POINTS" in names:
                reg_present = True
                for c in ast.walk(node.value):
                    if isinstance(c, ast.Constant) \
                            and isinstance(c.value, str):
                        registry.append((c.value, mi.file, c.lineno))
            elif "UNFIRED_ALLOWLIST" in names:
                for c in ast.walk(node.value):
                    if isinstance(c, ast.Constant) \
                            and isinstance(c.value, str):
                        allow.add(c.value)
    if not reg_present:
        return []  # registry not in the linted set — nothing to check

    fired: Set[str] = set()
    param_sites: Dict[Tuple[str, str], FuncInfo] = {}
    for mi in graph.modules.values():
        encl: Optional[Dict[int, FuncInfo]] = None
        for node in mi.calls:
            if not _is_fire_call(node.func):
                continue
            arg = node.args[0] if node.args else None
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                fired.add(arg.value)
            elif isinstance(arg, ast.Name):
                if arg.id in mi.str_constants:
                    fired.add(mi.str_constants[arg.id])
                else:
                    if encl is None:
                        encl = _enclosing_map(mi)
                    fi = encl.get(id(node))
                    if fi is not None \
                            and arg.id in _all_param_names(fi.node):
                        param_sites[(fi.name, arg.id)] = fi
            elif (isinstance(arg, ast.Attribute)
                  and isinstance(arg.value, ast.Name)):
                tgt = mi.import_aliases.get(arg.value.id)
                if tgt in graph.modules \
                        and arg.attr in graph.modules[tgt].str_constants:
                    fired.add(graph.modules[tgt].str_constants[arg.attr])

    if param_sites:
        for mi in graph.modules.values():
            for node in mi.calls:
                fn = node.func
                cname = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else "")
                for (fname, pname), fi in param_sites.items():
                    if cname != fname:
                        continue
                    pos = fi.params()
                    offset = 1 if (fi.is_method and pos[:1] == ["self"]
                                   and isinstance(fn, ast.Attribute)) else 0
                    vals: List[ast.AST] = []
                    if pname in pos:
                        i = pos.index(pname) - offset
                        if 0 <= i < len(node.args):
                            vals.append(node.args[i])
                    vals.extend(kw.value for kw in node.keywords
                                if kw.arg == pname)
                    for v in vals:
                        if isinstance(v, ast.Constant) \
                                and isinstance(v.value, str):
                            fired.add(v.value)
                        elif isinstance(v, ast.Name) \
                                and v.id in mi.str_constants:
                            fired.add(mi.str_constants[v.id])

    out: List[Finding] = []
    for point, file, line in registry:
        if point in fired or point in allow:
            continue
        out.append(_finding(
            "FAULT_POINT_UNFIRED",
            f"fault point {point!r} is registered in KNOWN_FAULT_POINTS "
            "but has no faults.fire(...) site anywhere in the linted "
            "tree — dead registry that chaos plans can target but "
            "never hit", file, line,
            fix="instrument the seam with faults.fire, delete the "
                "point, or add it to faults.UNFIRED_ALLOWLIST"))
    return out


def _lint_config_keys(mi: ModuleInfo) -> List[Finding]:
    from flink_tpu.config import is_declared_key

    file = mi.file
    out: List[Finding] = []
    for node in mi.calls:
        fn = node.func
        keys: List[Tuple[str, int]] = []
        if isinstance(fn, ast.Attribute) and fn.attr == "get_raw":
            k = _str_arg(node)
            if k is not None:
                keys.append((k, node.lineno))
        elif (isinstance(fn, (ast.Name, ast.Attribute))
              and (fn.attr if isinstance(fn, ast.Attribute) else fn.id)
              == "Configuration" and node.args
              and isinstance(node.args[0], ast.Dict)):
            for kn in node.args[0].keys:
                if isinstance(kn, ast.Constant) and isinstance(kn.value, str):
                    keys.append((kn.value, kn.lineno))
        for key, line in keys:
            if not is_declared_key(key):
                out.append(_finding(
                    "CONFIG_KEY_DRIFT",
                    f"config key {key!r} is outside the declared option "
                    "grammar — the runtime ignores it", file, line,
                    fix="declare a ConfigOption (or dynamic prefix) in "
                        "config.py, or fix the literal"))
    return out


def _option_decls(mi: ModuleInfo) -> List[Tuple[str, str, int]]:
    """(key, file, line) of every ConfigOption/duration_option literal."""
    decls = []
    for node in mi.calls:
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else "")
        if name in ("ConfigOption", "duration_option"):
            key = _str_arg(node)
            if key is not None:
                decls.append((key, mi.file, node.lineno))
    return decls


def _lint_metric_names(mi: ModuleInfo) -> List[Finding]:
    file = mi.file
    out: List[Finding] = []
    for node in mi.calls:
        fn = node.func
        if not isinstance(fn, ast.Attribute):
            continue
        names: List[str] = []
        if fn.attr in _METRIC_KINDS:
            n = _str_arg(node)
            if n is not None:
                names.append(n)
        elif fn.attr == "group":
            names.extend(
                a.value for a in node.args
                if isinstance(a, ast.Constant) and isinstance(a.value, str))
        for n in names:
            if not _METRIC_NAME_RE.match(n):
                out.append(_finding(
                    "METRIC_NAME_INVALID",
                    f"metric name {n!r} is outside the snake_case "
                    "grammar ([a-z0-9_] dotted segments) dashboards "
                    "key on", file, node.lineno,
                    fix="rename to lowercase snake_case"))
    return out


# -- durability-seam lint ---------------------------------------------------

def _lint_durability(mi: ModuleInfo) -> List[Finding]:
    """Raw durable-write constructs in the PR-14 durable tiers: every
    write must route through fs.py (open_write sync, fs.fsync,
    fs.rename, write_atomic) so CrashFS recording and the ENOSPC
    policy cover it. Allowed residue: os.open(O_CREAT|O_EXCL) +
    os.fdopen lock primitives, and os.rename of lock/lease -> grave
    files (local-lock bookkeeping, never durable payload)."""
    file = mi.file
    norm = file.replace("\\", "/")
    if norm not in DURABLE_MODULES:
        return []
    out: List[Finding] = []
    for node in mi.calls:
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "open":
            mode = ""
            if len(node.args) >= 2 and isinstance(node.args[1],
                                                  ast.Constant):
                mode = str(node.args[1].value)
            for kw in node.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                    mode = str(kw.value.value)
            if "w" in mode or "a" in mode or "+" in mode:
                out.append(_finding(
                    "DURABILITY_SEAM_BYPASS",
                    f"raw open(..., {mode!r}) in durable module {norm} "
                    "bypasses the fs.py seam — no CrashFS recording, no "
                    "ENOSPC policy, silently re-opens the power-cut "
                    "hole the crash explorer verifies closed",
                    file, node.lineno,
                    fix="route through fs.open_write(sync=) / "
                        "fs.write_atomic"))
        elif (isinstance(fn, ast.Attribute)
              and isinstance(fn.value, ast.Name) and fn.value.id == "os"
              and fn.attr in ("fsync", "replace", "rename")):
            if fn.attr == "rename":
                text = " ".join(_unparse(a) for a in node.args).lower()
                if any(t in text for t in ("lock", "lease", "grave")):
                    continue  # documented local-lock-primitive residue
            out.append(_finding(
                "DURABILITY_SEAM_BYPASS",
                f"os.{fn.attr}(...) in durable module {norm} bypasses "
                "the fs.py seam — no CrashFS recording, no ENOSPC "
                "policy", file, node.lineno,
                fix="route through fs.fsync / fs.rename / write_atomic"))
    return out


# -- concurrency lint: shared writes in HostPool.run_tasks closures ---------

def _root_name(node: ast.AST) -> Optional[str]:
    """The base Name of an Attribute/Subscript chain (``self`` of
    ``self.panes[p]``), or None when the base is not a plain name."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _lock_guarded_expr(node: ast.AST) -> bool:
    """Legacy name-substring lock marker (any Name or attribute segment
    containing 'lock', case-insensitive) — kept for locks that arrive
    as parameters, where no binding is visible. The binding-type check
    (CallGraph.is_lock_expr) is the primary mechanism."""
    for c in ast.walk(node):
        if isinstance(c, ast.Name) and "lock" in c.id.lower():
            return True
        if isinstance(c, ast.Attribute) and "lock" in c.attr.lower():
            return True
    return False


def _local_locks(fn: ast.AST) -> Set[str]:
    """Names this function body binds to a Lock/RLock/... constructor."""
    out: Set[str] = set()
    body = [fn.body] if isinstance(fn, ast.Lambda) else fn.body
    for stmt in body:
        for c in ast.walk(stmt):
            if isinstance(c, ast.Assign) \
                    and _call_ctor_name(c.value) in LOCK_CONSTRUCTORS:
                out.update(t.id for t in c.targets
                           if isinstance(t, ast.Name))
    return out


class _SharedWriteVisitor(ast.NodeVisitor):
    """Walk one task closure's body: flag Assign/AugAssign whose target
    routes through a FREE variable (not a parameter, not a local)
    unless the statement sits under a with-lock guard. Resolvable
    same-module calls are followed to any depth; a formal bound to a
    shared actual (including the implicit ``self`` receiver) keeps the
    shared tag in the callee."""

    def __init__(self, graph: CallGraph, mi: ModuleInfo,
                 ctx: Optional[FuncInfo], file: str, closure_name: str,
                 local_names: Set[str], local_locks: Set[str],
                 visited: Set, shared: Optional[Set[str]] = None,
                 lock_depth: int = 0, depth: int = 0) -> None:
        self.graph = graph
        self.mi = mi
        self.ctx = ctx
        self.file = file
        self.closure = closure_name
        self.locals = set(local_names)
        # formals bound to shared actuals at the call site: rebinding
        # one is a harmless local rebind, but mutating THROUGH it
        # (attribute/subscript store) reaches the caller's object
        self.shared = set(shared or ())
        self.local_locks = set(local_locks)
        self.visited = visited
        self.lock_depth = lock_depth
        self.depth = depth
        self.findings: List[Finding] = []

    def _shared_root(self, name: str) -> bool:
        return name in self.shared or name not in self.locals

    def _flag(self, line: int, target_src: str) -> None:
        self.findings.append(_finding(
            "HOSTPOOL_SHARED_WRITE",
            f"task closure {self.closure!r} writes shared state "
            f"({target_src}) without a lock — run_tasks executes it on "
            "a pool worker thread; unguarded read-modify-writes lose "
            "updates (the obs/metrics.py Counter race class)",
            self.file, line,
            fix="guard the write with a `with <lock>:` block, or "
                "return a partial and combine on the caller (results "
                "arrive in submission order)"))

    def _check_target(self, target: ast.AST, line: int) -> None:
        if self.lock_depth > 0:
            return
        if isinstance(target, (ast.Attribute, ast.Subscript)):
            root = _root_name(target)
            if root is not None and self._shared_root(root):
                self._flag(line, _unparse(target) or "<target>")
        elif isinstance(target, ast.Name):
            # a bare-name write is local unless declared otherwise
            # (visit_Nonlocal/Global remove such names from `locals`)
            if target.id not in self.locals:
                self._flag(line, target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._check_target(el, line)

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._check_target(t, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_target(node.target, node.lineno)
        self.generic_visit(node)

    def visit_Nonlocal(self, node: ast.Nonlocal) -> None:
        self.locals.difference_update(node.names)

    def visit_Global(self, node: ast.Global) -> None:
        self.locals.difference_update(node.names)

    def _guarded(self, expr: ast.AST) -> bool:
        return (_lock_guarded_expr(expr)
                or self.graph.is_lock_expr(expr, self.ctx,
                                           self.local_locks, self.mi))

    def _visit_with(self, node) -> None:
        guarded = any(self._guarded(i.context_expr) for i in node.items)
        if guarded:
            self.lock_depth += 1
        self.generic_visit(node)
        if guarded:
            self.lock_depth -= 1

    def visit_With(self, node: ast.With) -> None:
        self._visit_with(node)

    def visit_AsyncWith(self, node: ast.AsyncWith) -> None:
        self._visit_with(node)

    def visit_Call(self, node: ast.Call) -> None:
        self._descend(node)
        self.generic_visit(node)

    def _descend(self, node: ast.Call) -> None:
        if self.depth >= _POOL_DEPTH:
            return
        for fi in self.graph.resolve(node, self.ctx, self.mi):
            if fi.module != self.mi.name:
                continue  # same-module discipline only
            pos = fi.params()
            offset = 1 if (fi.is_method and pos[:1] == ["self"]
                           and isinstance(node.func, ast.Attribute)) else 0
            shared: Set[str] = set()
            if offset == 1:
                r = _root_name(node.func.value)
                if r is not None and self._shared_root(r):
                    shared.add("self")
            for i, arg in enumerate(node.args):
                j = i + offset
                if j >= len(pos):
                    break
                if isinstance(arg, (ast.Name, ast.Attribute,
                                    ast.Subscript)):
                    r = _root_name(arg)
                    if r is not None and self._shared_root(r):
                        shared.add(pos[j])
            names = set(_all_param_names(fi.node))
            for kw in node.keywords:
                if kw.arg and kw.arg in names and isinstance(
                        kw.value, (ast.Name, ast.Attribute, ast.Subscript)):
                    r = _root_name(kw.value)
                    if r is not None and self._shared_root(r):
                        shared.add(kw.arg)
            key = (id(fi.node), frozenset(shared), self.lock_depth > 0)
            if key in self.visited:
                continue
            self.visited.add(key)
            sub = _SharedWriteVisitor(
                self.graph, self.mi, fi, fi.file,
                f"{self.closure} -> {fi.name}",
                _fn_locals(fi.node), _local_locks(fi.node),
                self.visited, shared=shared,
                lock_depth=1 if self.lock_depth > 0 else 0,
                depth=self.depth + 1)
            for stmt in fi.node.body:
                sub.visit(stmt)
            self.findings.extend(sub.findings)

    # nested defs/lambdas get their own scope; don't descend into their
    # bodies here (a nested def submitted separately is its own root)
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        pass

    def visit_Lambda(self, node: ast.Lambda) -> None:
        pass


def _fn_params(fn: ast.AST) -> Set[str]:
    a = fn.args
    names = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    return names


def _binding_names(t: ast.AST) -> Iterator[str]:
    """Names a binding target introduces — Name / Tuple / List /
    Starred structure only, so ``d[k], x = ...`` yields ``x`` but not
    ``d`` or ``k`` (a subscript store mutates, it doesn't bind)."""
    if isinstance(t, ast.Name):
        yield t.id
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            yield from _binding_names(e)
    elif isinstance(t, ast.Starred):
        yield from _binding_names(t.value)


def _fn_locals(fn: ast.AST) -> Set[str]:
    """Parameters + bare names the body binds (assignments, for/with
    targets, comprehension-free walk at this scope)."""
    names = _fn_params(fn)
    body = [fn.body] if isinstance(fn, ast.Lambda) else fn.body
    for stmt in body:
        for c in ast.walk(stmt):
            if isinstance(c, ast.Assign):
                for t in c.targets:
                    names.update(_binding_names(t))
            elif isinstance(c, (ast.AnnAssign, ast.AugAssign,
                                ast.NamedExpr)):
                # `n: int = 0`, `n += 1` (local unless nonlocal/global
                # — the visitor re-frees declared names), `(n := ...)`
                if isinstance(c.target, ast.Name):
                    names.add(c.target.id)
            elif isinstance(c, (ast.For, ast.AsyncFor)):
                names.update(_binding_names(c.target))
            elif isinstance(c, (ast.With, ast.AsyncWith)):
                for i in c.items:
                    if i.optional_vars is not None:
                        names.update(_binding_names(i.optional_vars))
    return names


def _lint_hostpool_writes(graph: CallGraph,
                          mi: ModuleInfo) -> List[Finding]:
    tree, file = mi.tree, mi.file
    defs_by_name: Dict[str, List[ast.AST]] = {
        name: [fi.node for fi in fns]
        for name, fns in mi.functions.items()}

    # name → closures the file binds into it (list/tuple literals,
    # listcomp values, .append(lambda ...) / .append(local_def)) —
    # resolves `run_tasks(tasks)`. Name references resolve to local
    # defs only where the expression IS the closure (a bare name, a
    # literal element, a comprehension elt) — resolving every Name in
    # an arbitrary value would mis-tag caller-thread helpers as tasks.
    bound: Dict[str, List[ast.AST]] = {}

    def closures_in(expr: ast.AST) -> List[ast.AST]:
        out = [c for c in ast.walk(expr) if isinstance(c, ast.Lambda)]
        names: List[str] = []
        if isinstance(expr, ast.Name):
            names = [expr.id]
        elif isinstance(expr, (ast.List, ast.Tuple)):
            names = [e.id for e in expr.elts if isinstance(e, ast.Name)]
        elif isinstance(expr, (ast.ListComp, ast.GeneratorExp)) \
                and isinstance(expr.elt, ast.Name):
            names = [expr.elt.id]
        for nm in names:
            out.extend(bound.get(nm, ()))
            out.extend(defs_by_name.get(nm, ()))
        return out

    for node in mi.nodes:
        if isinstance(node, ast.Assign):
            closures = closures_in(node.value)
            if closures:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        bound.setdefault(t.id, []).extend(closures)
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Attribute)
              and node.func.attr == "append"
              and isinstance(node.func.value, ast.Name)):
            for a in node.args:
                bound.setdefault(node.func.value.id, []).extend(
                    closures_in(a))

    encl: Optional[Dict[int, FuncInfo]] = None

    def ctx_for(fn: ast.AST) -> Optional[FuncInfo]:
        """The closure's own FuncInfo (nested defs carry their class
        tag), else the innermost enclosing function (lambdas)."""
        nonlocal encl
        fi = graph.func_of_node(fn)
        if fi is not None:
            return fi
        if encl is None:
            encl = _enclosing_map(mi)
        return encl.get(id(fn))

    out: List[Finding] = []
    visited: Set = set()
    for node in mi.calls:
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr == "run_tasks"):
            continue
        closures: List[ast.AST] = []
        for a in node.args:
            closures.extend(closures_in(a))
        for fn in closures:
            key = (id(fn), "root")
            if key in visited:
                continue
            visited.add(key)
            name = getattr(fn, "name", "<lambda>")
            v = _SharedWriteVisitor(graph, mi, ctx_for(fn), file, name,
                                    _fn_locals(fn), _local_locks(fn),
                                    visited)
            body = ([fn.body] if isinstance(fn, ast.Lambda)
                    else fn.body)
            for stmt in body:
                v.visit(stmt)
            out.extend(v.findings)
    return out


# -- lock-order lint --------------------------------------------------------

def _lint_lock_order(graph: CallGraph) -> List[Finding]:
    """Build the lock-acquisition-order graph: an edge A -> B when some
    path acquires tracked lock B while holding A — directly nested
    ``with`` guards, or a call made under A whose (transitive) callee
    acquires B. A 2-cycle (A -> B and B -> A) is a potential ABBA
    deadlock; the finding names both acquisition paths. Nested defs/
    lambdas are excluded from their encloser's walk (they run in
    another frame), and self-edges (RLock reentrancy) are not edges."""
    memo: Dict[int, Dict[str, str]] = {}

    def acquires(fi: FuncInfo, seen: frozenset) -> Dict[str, str]:
        """Transitive lock-id -> witness-path summary for one function."""
        key = id(fi.node)
        if key in memo:
            return memo[key]
        if key in seen or len(seen) > 16:
            return {}
        seen2 = seen | {key}
        out: Dict[str, str] = {}
        for node in _iter_skip_nested(fi.node):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for i in node.items:
                    lid = graph.lock_id(i.context_expr, fi)
                    if lid:
                        out.setdefault(
                            lid, f"{fi.file}:{node.lineno} in {fi.qname}")
            elif isinstance(node, ast.Call):
                for callee in graph.resolve(node, fi):
                    for lid, w in acquires(callee, seen2).items():
                        out.setdefault(
                            lid, f"{fi.file}:{node.lineno} in "
                                 f"{fi.qname} -> {w}")
        memo[key] = out
        return out

    edges: Dict[Tuple[str, str], Tuple[str, int, str]] = {}

    def record(held: List[Tuple[str, str]], lid: str,
               file: str, line: int, via: str) -> None:
        for h, hw in held:
            if h != lid:  # reentrant self-acquire (RLock) is not an edge
                edges.setdefault((h, lid), (file, line,
                                            f"{hw}, then {via}"))

    def visit(fi: FuncInfo, node: ast.AST,
              held: List[Tuple[str, str]]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)) and node is not fi.node:
            return  # another frame/thread
        if isinstance(node, (ast.With, ast.AsyncWith)):
            lids = [lid for i in node.items
                    for lid in [graph.lock_id(i.context_expr, fi)] if lid]
            site = f"{fi.file}:{node.lineno} in {fi.qname}"
            for lid in lids:
                record(held, lid, fi.file, node.lineno,
                       f"{lid} at {site}")
            held = held + [(lid, f"{lid} at {site}") for lid in lids]
        elif isinstance(node, ast.Call) and held:
            for callee in graph.resolve(node, fi):
                for lid, w in acquires(callee, frozenset()).items():
                    record(held, lid, fi.file, node.lineno,
                           f"{lid} via the call at {fi.file}:"
                           f"{node.lineno} in {fi.qname} -> {w}")
        for c in ast.iter_child_nodes(node):
            visit(fi, c, held)

    def module_has_tracked_with(mi: ModuleInfo) -> bool:
        """Can any `with` in this module acquire a TRACKED lock? held
        stacks only grow from such withs in a function's own frame, so
        a module without one cannot originate a lock-order edge and
        its functions need no visit (callees elsewhere are reached via
        the `acquires` summaries on demand)."""
        lock_attrs: Set[str] = set()
        for ci in mi.classes.values():
            lock_attrs |= ci.lock_attrs
        for w in mi.withs:
            for i in w.items:
                e = i.context_expr
                if isinstance(e, ast.Name) and e.id in mi.lock_names:
                    return True
                if (isinstance(e, ast.Attribute)
                        and isinstance(e.value, ast.Name)
                        and e.value.id == "self"
                        and e.attr in lock_attrs):
                    return True
        return False

    for mi in graph.modules.values():
        if not module_has_tracked_with(mi):
            continue
        for fns in mi.functions.values():
            for fi in fns:
                # skip functions with no `with` in their own subtree —
                # they can never build a held stack
                if any(isinstance(n, (ast.With, ast.AsyncWith))
                       for n in ast.walk(fi.node)):
                    visit(fi, fi.node, [])

    out: List[Finding] = []
    for (a, b) in sorted(edges):
        if a >= b or (b, a) not in edges:
            continue
        file, line, desc = edges[(a, b)]
        _rf, _rl, rdesc = edges[(b, a)]
        out.append(_finding(
            "LOCK_ORDER_CYCLE",
            f"lock-order cycle between {a} and {b}: one path acquires "
            f"{desc}; the opposite path acquires {rdesc} — two threads "
            "interleaving these paths deadlock", file, line,
            fix="pick one global acquisition order for these locks "
                "(lock hierarchy) or collapse them into one lock"))
    return out


# -- fencing lint -----------------------------------------------------------

def _is_fence_call(fn: ast.AST) -> bool:
    name = fn.attr if isinstance(fn, ast.Attribute) else (
        fn.id if isinstance(fn, ast.Name) else "")
    return "verify" in name.lower() or name == "renew"


def _publish_call_name(fn: ast.AST) -> str:
    name = fn.attr if isinstance(fn, ast.Attribute) else (
        fn.id if isinstance(fn, ast.Name) else "")
    if name == "put_if" or name.endswith("write_atomic"):
        return name
    return ""


def _is_leased_class(ci) -> bool:
    """A class whose methods call ``self.<attr>.verify(...)`` — the
    syntactic signature of holding an epoch-fenced lease (detected at
    index time, see callgraph ClassInfo.leased)."""
    return ci.leased


def _lint_fence_publish(graph: CallGraph) -> List[Finding]:
    """For every PUBLIC method of a leased class, walk statements in
    source order threading a verified-flag through resolved calls: a
    fence call (``*verify*``/``renew``) sets it; a
    ``write_atomic``/``put_if`` whose argument text (with one hop of
    local-variable substitution) names a fenced record while the flag
    is unset is a publication a deposed leaseholder could make after
    takeover. Publishing the lease/lock record itself IS the fence
    mechanism and is exempt."""
    out: List[Finding] = []
    memo: Dict[Tuple[int, bool], bool] = {}

    def walk(fi: FuncInfo, state: bool, origin: str, depth: int) -> bool:
        key = (id(fi.node), state)
        if key in memo or depth > _FENCE_DEPTH:
            return memo.get(key, state)
        memo[key] = state  # provisional (recursion guard)
        env: Dict[str, str] = {}
        for node in _iter_skip_nested(fi.node):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                env[node.targets[0].id] = _unparse(node.value).lower()
            elif isinstance(node, ast.Call):
                fn = node.func
                if _is_fence_call(fn):
                    state = True
                    continue
                if _publish_call_name(fn):
                    texts = []
                    for a in list(node.args) + [k.value
                                                for k in node.keywords]:
                        texts.append(_unparse(a).lower())
                        if isinstance(a, ast.Name) and a.id in env:
                            texts.append(env[a.id])
                    text = " ".join(texts)
                    if "lease" in text or "lock" in text:
                        continue  # the lease/lock record IS the fence
                    tokens = [t for t in _FENCED_TOKENS if t in text]
                    if tokens and not state:
                        out.append(_finding(
                            "FENCE_UNVERIFIED_PUBLISH",
                            f"{origin} reaches a "
                            f"{'/'.join(tokens)}-record publication in "
                            f"{fi.qname} with no lease verify()/renew "
                            "on the path — a deposed leaseholder could "
                            "publish after takeover", fi.file,
                            node.lineno,
                            fix="call the lease verify()/renew gate "
                                "before this publication"))
                    continue
                for callee in graph.resolve(node, fi):
                    state = walk(callee, state, origin, depth + 1)
        memo[key] = state
        return state

    for mi in graph.modules.values():
        for ci in mi.classes.values():
            if not _is_leased_class(ci):
                continue
            for name, fi in sorted(ci.methods.items()):
                if name.startswith("_"):
                    continue  # helpers inherit state from their callers
                walk(fi, False, f"leased {ci.name}.{name}()", 0)
    return out


# -- entry points -----------------------------------------------------------

def _lint_graph(graph: CallGraph) -> List[Finding]:
    """Every rule over one indexed module set (the per-file rules plus
    the interprocedural planes), deduplicated and sorted."""
    out: List[Finding] = []
    for mi in graph.modules.values():
        out.extend(_lint_tracer_leaks(graph, mi))
        out.extend(_lint_fault_points(mi))
        out.extend(_lint_config_keys(mi))
        out.extend(_lint_metric_names(mi))
        out.extend(_lint_hostpool_writes(graph, mi))
        out.extend(_lint_durability(mi))
    out.extend(_lint_lock_order(graph))
    out.extend(_lint_fence_publish(graph))
    out.extend(_lint_unfired_points(graph))
    seen: Set[Tuple[str, str, int, str]] = set()
    deduped: List[Finding] = []
    for f in out:
        k = (f.rule, f.file, f.line, f.message)
        if k not in seen:
            seen.add(k)
            deduped.append(f)
    deduped.sort(key=lambda f: (f.file, f.line, f.rule))
    return deduped


def lint_source(source: str, file: str) -> List[Finding]:
    """Lint one file's source text (the unit every test fixture uses).
    The file becomes a single-module call graph, so the
    interprocedural rules run within it; pass a durable-module relpath
    as ``file`` to exercise the durability plane."""
    tree = ast.parse(source, filename=file)
    graph = build_graph({file.replace("\\", "/"): tree})
    return _lint_graph(graph)


def repo_root() -> str:
    """The directory holding the flink_tpu package (lint path base)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


DEFAULT_LINT_PATHS = ("flink_tpu", "tools", "chip_smoke.py")


def lint_paths(paths: Optional[Sequence[str]] = None,
               root: Optional[str] = None) -> List[Finding]:
    """Lint every ``*.py`` under ``paths`` (files or directories,
    resolved against ``root`` — defaults to the shipped tree) as ONE
    call graph, so cross-module call edges resolve. Also runs the
    cross-file CONFIG_OPTION_DUP check over the whole set."""
    from flink_tpu.analysis.plan_rules import load_option_grammar

    load_option_grammar()
    root = root or repo_root()
    files: List[str] = []
    for p in (paths or DEFAULT_LINT_PATHS):
        full = p if os.path.isabs(p) else os.path.join(root, p)
        if not os.path.exists(full) and not os.path.isabs(p):
            full = os.path.abspath(p)  # CLI arg relative to the cwd
        if os.path.isfile(full):
            files.append(full)
        elif os.path.isdir(full):
            for dirpath, dirnames, fnames in os.walk(full):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                files.extend(os.path.join(dirpath, f)
                             for f in sorted(fnames) if f.endswith(".py"))
        else:
            # a typo'd path silently linting NOTHING would leave a CI
            # drift gate green while checking nothing — fail loudly
            raise ValueError(f"lint path does not exist: {p!r} "
                             f"(resolved against {root!r} and the cwd)")
    trees: Dict[str, ast.Module] = {}
    for f in sorted(set(files)):
        rel = os.path.relpath(f, root).replace(os.sep, "/")
        with open(f, "r", encoding="utf-8") as fh:
            src = fh.read()
        trees[rel] = ast.parse(src, filename=rel)
    graph = build_graph(trees)
    out = _lint_graph(graph)
    decls: List[Tuple[str, str, int]] = []
    for mi in graph.modules.values():
        decls.extend(_option_decls(mi))
    by_key: Dict[str, List[Tuple[str, str, int]]] = {}
    for key, file, line in decls:
        by_key.setdefault(key, []).append((key, file, line))
    for key, sites in sorted(by_key.items()):
        if len(sites) > 1:
            sites.sort(key=lambda s: (s[1], s[2]))
            first = f"{sites[0][1]}:{sites[0][2]}"
            for _, file, line in sites[1:]:
                out.append(_finding(
                    "CONFIG_OPTION_DUP",
                    f"option key {key!r} already declared at {first} — "
                    "re-declaration silently replaces it in the "
                    "registry", file, line,
                    fix="reuse the existing ConfigOption constant"))
    out.sort(key=lambda f: (f.file, f.line, f.rule))
    return out
