"""tpurace static prong: whole-program lockset & lock-order analysis.

Unlike the per-module tpulint rules, this pass parses EVERY module first
and reasons across them, because the bug classes it hunts are invisible
to any single file:

- a field guarded in ``store/datastore.py`` but assigned bare from a
  helper in another method (or another class's method holding a typed
  reference to the instance),
- a lock-order inversion where ``stream/journal.py`` takes A then calls
  into code that takes B while ``store/datastore.py`` nests B then A.

The model is deliberately lightweight — pure ``ast``, no imports of the
analyzed code — with just enough type inference to resolve the repo's
idioms:

- lock discovery: ``self.x = threading.Lock()/RLock()/Condition()``
  inside methods, and ``NAME = threading.Lock()`` at module scope. Lock
  identity is ``Class.attr`` / ``module:NAME`` — one node per *site*,
  not per instance (the order DISCIPLINE is per lock role).
- object typing: ``self.x = ClassName(...)`` (anywhere in the
  constructor expression), ``self.x: ClassName`` / ``x: ClassName``
  annotations, ``dict[str, ClassName]``-style container annotations for
  subscripted reads, and method return annotations
  (``def _state(...) -> _TypeState``). That is what lets
  ``st = self._state(name); st.table = ...`` attribute writes to
  ``_TypeState.table`` and ``with st.lock:`` acquisitions to
  ``_TypeState.lock``.
- held-lock tracking: a per-function walk maintains the lock stack from
  ``with`` statements; entry-held sets propagate inter-procedurally —
  ``*_locked`` methods are caller-holds-lock by repo convention, and a
  private function's entry set is the intersection of the held sets at
  its observed call sites (fixpoint).

R001 infers the guard map by majority: a field with ≥ 2 tracked writes,
more than half of them under one lock, is guarded by that lock, and
every write outside it is flagged. Reads are NOT flagged (Eraser-style
read checking is future work — the write-side race is the lost-update
class that corrupts state). R002 builds the global acquisition graph
(direct nestings plus, through the call graph, locks a callee may
acquire while the caller holds one) and reports each strongly-connected
component as one violation. R003 flags DIRECT blocking calls under a
hot-path lock; it does not chase calls, so a blocking helper invoked
under a lock needs the helper inlined or the call site reviewed (the
dynamic sanitizer covers what static depth misses).

Heuristics, not proofs: the expected answer for an intentional site is
a ``# tpurace: disable=Rxxx`` waiver with a one-line justification.
"""

from __future__ import annotations

import ast
from collections import defaultdict
from dataclasses import dataclass, field

from geomesa_tpu.analysis.astutils import ImportMap
from geomesa_tpu.analysis.core import (
    LintConfig,
    Module,
    Violation,
    iter_py_files,
    parse_module,
)

__all__ = [
    "RACE_RULE_IDS", "analyze_modules", "analyze_race_paths", "guard_map",
    "load_modules", "build_flow_graph",
]

RACE_RULE_IDS = ("R001", "R002", "R003")

LOCK_FACTORIES = frozenset({
    "threading.Lock", "threading.RLock", "threading.Condition",
    "threading.Semaphore", "threading.BoundedSemaphore",
})
MUTATING_METHODS = frozenset({
    "append", "extend", "insert", "add", "update", "setdefault", "pop",
    "popitem", "remove", "discard", "clear", "appendleft", "popleft",
})
# construction is single-threaded; writes there never need the lock
_CTOR_METHODS = frozenset({"__init__", "__post_init__", "__new__"})

# Canonical dotted names of calls that block the calling thread.
BLOCKING_CALLS = frozenset({
    "time.sleep",
    "open", "io.open", "os.open", "os.fsync", "os.fdatasync",
    "socket.create_connection", "socket.getaddrinfo",
    "urllib.request.urlopen",
    "subprocess.run", "subprocess.Popen", "subprocess.check_output",
    "subprocess.check_call",
    "fcntl.flock", "fcntl.lockf",
})
# Method names that block regardless of receiver type. ``join`` is only
# blocking for thread-likes — disambiguated from str.join at the call
# site (str.join always passes the iterable positionally).
BLOCKING_METHODS = frozenset({
    "block_until_ready", "sendall", "recv", "connect", "wait",
})


def _module_id(relpath: str) -> str:
    """``stream/journal.py`` → ``stream.journal`` (lock-id namespace)."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    return p.replace("/", ".")


@dataclass
class _ClassInfo:
    name: str
    module: Module
    node: ast.ClassDef
    lock_attrs: set[str] = field(default_factory=set)
    methods: dict[str, ast.FunctionDef] = field(default_factory=dict)
    attr_class: dict[str, str] = field(default_factory=dict)
    # attrs annotated as containers of a class: subscripting yields it
    attr_elem_class: dict[str, str] = field(default_factory=dict)
    method_returns: dict[str, str] = field(default_factory=dict)

    def lock_ids(self) -> set[str]:
        return {f"{self.name}.{a}" for a in self.lock_attrs}


@dataclass
class _Acquire:
    lock: str
    line: int
    held: tuple[str, ...]


@dataclass
class _Write:
    owner: str  # class name
    attr: str
    line: int
    col: int
    held: tuple[str, ...]
    what: str
    module: Module
    method: str  # enclosing function name (ctor writes are exempt)


@dataclass
class _CallSite:
    callee: tuple  # ("method", cls, name) | ("fn", module_id, name)
    line: int
    held: tuple[str, ...]


@dataclass
class _Blocking:
    what: str
    line: int
    col: int
    held: tuple[str, ...]
    module: Module


@dataclass
class _FnSummary:
    key: tuple
    name: str
    cls: _ClassInfo | None
    module: Module
    acquires: list[_Acquire] = field(default_factory=list)
    writes: list[_Write] = field(default_factory=list)
    calls: list[_CallSite] = field(default_factory=list)
    blocking: list[_Blocking] = field(default_factory=list)


class _Project:
    """Everything discovered in pass 1: classes, locks, typings."""

    def __init__(self, modules: list[Module]):
        self.modules = modules
        self.imports: dict[str, ImportMap] = {}
        self.classes: dict[str, _ClassInfo] = {}
        self.ambiguous: set[str] = set()
        # module_id -> {name: lockid} for module-scope locks
        self.module_locks: dict[str, dict[str, str]] = {}
        # lockid -> owning module relpath (R003 hot-path scoping)
        self.lock_home: dict[str, str] = {}
        # module_id -> top-level function defs
        self.functions: dict[str, dict[str, ast.FunctionDef]] = {}
        # (module_id, fn) -> class its return annotation resolves to —
        # lets cross-module scans type ``devmon.costs().forget(...)``
        # through the ``def costs() -> PlanTable`` accessor idiom
        self.fn_returns: dict[tuple[str, str], str] = {}

        for mod in modules:
            imports = ImportMap(mod.tree)
            self.imports[mod.relpath] = imports
            mid = _module_id(mod.relpath)
            self.module_locks[mid] = {}
            self.functions[mid] = {}
            for node in mod.tree.body:
                if isinstance(node, ast.Assign) and _is_lock_call(
                    node.value, imports
                ):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            lid = f"{mid}:{t.id}"
                            self.module_locks[mid][t.id] = lid
                            self.lock_home[lid] = mod.relpath
                elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self.functions[mid][node.name] = node
                elif isinstance(node, ast.ClassDef):
                    self._index_class(mod, imports, node)

        # resolve attr/return annotations to known classes (second pass —
        # all class names must exist first)
        for info in list(self.classes.values()):
            self._type_class(info)
        self._fn_homes: dict[str, list[str]] = defaultdict(list)
        for mod in modules:
            mid = _module_id(mod.relpath)
            imports = self.imports[mod.relpath]
            for name, fn in self.functions[mid].items():
                self._fn_homes[name].append(mid)
                ret = self._ann_class(fn.returns, imports)
                if ret:
                    self.fn_returns[(mid, name)] = ret

    def local_fn_key(self, dotted: str | None) -> tuple | None:
        """Canonical ``geomesa_tpu.<mod>.<fn>`` path → ``("fn", mid, fn)``
        when the target is a known top-level function of an analyzed
        module (the cross-module half of the call graph). Fixture trees
        analyzed from outside the package have path-derived module ids, so
        an import path is also matched as a module-id suffix."""
        if dotted is None or "." not in dotted:
            return None
        head, _, name = dotted.rpartition(".")
        if head.startswith("geomesa_tpu."):
            head = head[len("geomesa_tpu."):]
        if name in self.functions.get(head, {}):
            return ("fn", head, name)
        for mid in self._fn_homes.get(name, ()):
            if mid.endswith("." + head) or head.endswith("." + mid):
                return ("fn", mid, name)
        return None

    # -- pass 1a: class inventory -------------------------------------------
    def _index_class(self, mod, imports, node: ast.ClassDef) -> None:
        info = _ClassInfo(name=node.name, module=mod, node=node)
        for m in node.body:
            if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info.methods[m.name] = m
                for sub in ast.walk(m):
                    if isinstance(sub, ast.Assign) and _is_lock_call(
                        sub.value, imports
                    ):
                        for t in sub.targets:
                            attr = _self_attr_of(t, _self_name(m))
                            if attr is not None:
                                info.lock_attrs.add(attr)
        if node.name in self.classes:
            # duplicate top-level name (the repo has e.g. two Histograms):
            # BARE-name typing becomes unresolvable, but the class itself
            # must still be analyzed — re-key it under a module-qualified
            # name so its methods, locks, and writes stay in the pass and
            # its lock ids never conflate with the namesake's
            self.ambiguous.add(node.name)
            info.name = f"{_module_id(mod.relpath)}.{node.name}"
            if info.name in self.classes:  # same name twice in one module
                return
        self.classes[info.name] = info
        for lid in info.lock_ids():
            self.lock_home[lid] = mod.relpath

    # -- pass 1b: light type inference --------------------------------------
    def resolve_class(self, dotted: str | None) -> str | None:
        """Canonical dotted path (or bare name) → known class name."""
        if dotted is None:
            return None
        name = dotted.rsplit(".", 1)[-1]
        if name in self.classes and name not in self.ambiguous:
            return name
        return None

    def _ann_class(self, ann: ast.AST | None, imports) -> str | None:
        if ann is None:
            return None
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            try:
                ann = ast.parse(ann.value, mode="eval").body
            except SyntaxError:
                return None
        # X | None unions: take the first resolvable arm
        if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
            return (self._ann_class(ann.left, imports)
                    or self._ann_class(ann.right, imports))
        return self.resolve_class(imports.resolve(ann))

    def _ann_elem_class(self, ann: ast.AST | None, imports) -> str | None:
        """``dict[str, C]`` / ``list[C]`` → C (what subscripting yields)."""
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            try:
                ann = ast.parse(ann.value, mode="eval").body
            except SyntaxError:
                return None
        if not isinstance(ann, ast.Subscript):
            return None
        sl = ann.slice
        if isinstance(sl, ast.Tuple) and sl.elts:
            sl = sl.elts[-1]  # dict value type
        return self._ann_class(sl, imports)

    def _type_class(self, info: _ClassInfo) -> None:
        imports = self.imports[info.module.relpath]
        for m in info.methods.values():
            ret = self._ann_class(m.returns, imports)
            if ret:
                info.method_returns[m.name] = ret
            sn = _self_name(m)
            # annotated params type the attrs they're stored into
            # (``def __init__(self, reg: Registry): self.reg = reg``)
            panns = {
                a.arg: c
                for a in (m.args.posonlyargs + m.args.args
                          + m.args.kwonlyargs)
                if (c := self._ann_class(a.annotation, imports))
            }
            for sub in ast.walk(m):
                if isinstance(sub, ast.AnnAssign):
                    attr = _self_attr_of(sub.target, sn)
                    if attr is None:
                        continue
                    c = self._ann_class(sub.annotation, imports)
                    if c:
                        info.attr_class[attr] = c
                    e = self._ann_elem_class(sub.annotation, imports)
                    if e:
                        info.attr_elem_class[attr] = e
                elif isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        attr = _self_attr_of(t, sn)
                        if attr is None or attr in info.attr_class:
                            continue
                        c = self._ctor_class(sub.value, imports)
                        if c is None and isinstance(sub.value, ast.Name):
                            c = panns.get(sub.value.id)
                        if c:
                            info.attr_class[attr] = c

    def _ctor_class(self, expr: ast.AST, imports) -> str | None:
        """Class constructed anywhere in ``expr`` (covers the
        ``x if x is not None else DataStore(...)`` idiom)."""
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                c = self.resolve_class(imports.resolve(node.func))
                if c:
                    return c
        return None


def _is_lock_call(expr: ast.AST, imports: ImportMap) -> bool:
    return (isinstance(expr, ast.Call)
            and imports.resolve(expr.func) in LOCK_FACTORIES)


def _self_name(method: ast.FunctionDef) -> str:
    args = method.args.posonlyargs + method.args.args
    return args[0].arg if args else "self"


def _self_attr_of(node: ast.AST, self_name: str) -> str | None:
    """``self.X`` (possibly through subscripts) → ``X``."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == self_name
    ):
        return node.attr
    return None


# ---------------------------------------------------------------------------
# pass 2: per-function scan with held-lock tracking
# ---------------------------------------------------------------------------

class _FnScan(ast.NodeVisitor):
    def __init__(self, project: _Project, summary: _FnSummary,
                 fn: ast.FunctionDef, *, cross_module: bool = False):
        self.p = project
        self.s = summary
        self.mod = summary.module
        self.imports = project.imports[self.mod.relpath]
        self.mid = _module_id(self.mod.relpath)
        self.cls = summary.cls
        self.self_name = _self_name(fn) if self.cls is not None else None
        # opt-in (flow prong only): resolve imported geomesa_tpu functions
        # to call-graph edges and type accessor-call returns. Kept OFF for
        # the race prong so its edge set — and therefore R001-R003
        # findings and the committed baseline — stays byte-identical.
        self.cross_module = cross_module
        self.held: list[str] = []
        self.var_class: dict[str, str] = {}
        # annotated params type locals too
        for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs:
            c = project._ann_class(a.annotation, self.imports)
            if c:
                self.var_class[a.arg] = c

    # -- typing -------------------------------------------------------------
    def _expr_class(self, expr: ast.AST) -> str | None:
        if isinstance(expr, ast.Name):
            if self.self_name is not None and expr.id == self.self_name:
                return self.cls.name
            return self.var_class.get(expr.id)
        if isinstance(expr, ast.Attribute):
            base = self._expr_class(expr.value)
            if base is not None and base in self.p.classes:
                return self.p.classes[base].attr_class.get(expr.attr)
            return None
        if isinstance(expr, ast.Subscript):
            base = expr.value
            if isinstance(base, ast.Attribute):
                owner = self._expr_class(base.value)
                if owner is not None and owner in self.p.classes:
                    return self.p.classes[owner].attr_elem_class.get(base.attr)
            return None
        if isinstance(expr, ast.Call):
            c = self.p.resolve_class(self.imports.resolve(expr.func))
            if c:
                return c
            f = expr.func
            if isinstance(f, ast.Attribute):
                recv = self._expr_class(f.value)
                if recv is not None and recv in self.p.classes:
                    return self.p.classes[recv].method_returns.get(f.attr)
            if self.cross_module:
                # module-level accessor returns: ``devmon.costs()`` types
                # as PlanTable through ``def costs() -> PlanTable``
                key = self.p.local_fn_key(self.imports.resolve(f))
                if key is None and isinstance(f, ast.Name):
                    if f.id in self.p.functions.get(self.mid, {}):
                        key = ("fn", self.mid, f.id)
                if key is not None:
                    return self.p.fn_returns.get((key[1], key[2]))
            return None
        if isinstance(expr, ast.IfExp):
            return self._expr_class(expr.body) or self._expr_class(expr.orelse)
        return None

    def _lock_id(self, expr: ast.AST) -> str | None:
        if isinstance(expr, ast.Name):
            return self.p.module_locks.get(self.mid, {}).get(expr.id)
        if isinstance(expr, ast.Attribute):
            owner = self._expr_class(expr.value)
            if owner is not None and owner in self.p.classes:
                if expr.attr in self.p.classes[owner].lock_attrs:
                    return f"{owner}.{expr.attr}"
        return None

    def _owner_attr(self, node: ast.AST) -> tuple[str, str] | None:
        while isinstance(node, ast.Subscript):
            node = node.value
        if isinstance(node, ast.Attribute):
            owner = self._expr_class(node.value)
            if owner is not None:
                return (owner, node.attr)
        return None

    # -- visiting -----------------------------------------------------------
    def visit_With(self, node: ast.With):
        acquired = []
        for item in node.items:
            lid = self._lock_id(item.context_expr)
            if lid is not None:
                if lid not in self.held:  # RLock re-entry is not an edge
                    self.s.acquires.append(_Acquire(
                        lock=lid, line=node.lineno, held=tuple(self.held)))
                acquired.append(lid)
                self.held.append(lid)
            else:
                # ``with open(...)``: the context expression itself is a
                # call site (blocking detection must see it)
                self.visit(item.context_expr)
        for stmt in node.body:
            self.visit(stmt)
        for _ in acquired:
            self.held.pop()

    visit_AsyncWith = visit_With

    def _record_write(self, target: ast.AST, node: ast.AST, what: str):
        oa = self._owner_attr(target)
        if oa is None:
            return
        owner, attr = oa
        if owner in self.p.classes and attr in self.p.classes[owner].lock_attrs:
            return  # swapping the lock object itself is not a field write
        self.s.writes.append(_Write(
            owner=owner, attr=attr, line=node.lineno, col=node.col_offset,
            held=tuple(self.held), what=what, module=self.mod,
            method=self.s.name))

    def visit_Assign(self, node: ast.Assign):
        for t in node.targets:
            if isinstance(t, ast.Name):
                c = self._expr_class(node.value)
                if c:
                    self.var_class[t.id] = c
            for el in _flat_targets(t):
                self._record_write(el, node, "assignment")
        self.visit(node.value)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        if node.value is None:
            return
        if isinstance(node.target, ast.Name):
            c = (self.p._ann_class(node.annotation, self.imports)
                 or self._expr_class(node.value))
            if c:
                self.var_class[node.target.id] = c
        self._record_write(node.target, node, "assignment")
        self.visit(node.value)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._record_write(node.target, node, "augmented assignment")
        self.visit(node.value)

    def visit_Call(self, node: ast.Call):
        f = node.func
        dotted = self.imports.resolve(f)
        # blocking-call detection (direct sites only)
        blocked = None
        if dotted in BLOCKING_CALLS:
            blocked = dotted
        elif dotted is not None and self.imports.is_device_namespace(dotted):
            blocked = f"{dotted} (jax dispatch)"
        elif isinstance(f, ast.Attribute):
            if f.attr in BLOCKING_METHODS:
                blocked = f".{f.attr}()"
            elif f.attr == "join" and (
                not node.args or any(k.arg == "timeout" for k in node.keywords)
            ):
                blocked = ".join()"  # thread join; str.join passes args
        if blocked is not None and self.held:
            self.s.blocking.append(_Blocking(
                what=blocked, line=node.lineno, col=node.col_offset,
                held=tuple(self.held), module=self.mod))
        # mutating container methods are writes to the container attr
        if isinstance(f, ast.Attribute) and f.attr in MUTATING_METHODS:
            oa = self._owner_attr(f.value)
            if oa is not None:
                owner, attr = oa
                if not (owner in self.p.classes
                        and attr in self.p.classes[owner].lock_attrs):
                    self.s.writes.append(_Write(
                        owner=owner, attr=attr, line=node.lineno,
                        col=node.col_offset, held=tuple(self.held),
                        what=f".{f.attr}()", module=self.mod,
                        method=self.s.name))
        # call-graph edges
        callee = self._callee_key(f)
        if callee is not None:
            self.s.calls.append(_CallSite(
                callee=callee, line=node.lineno, held=tuple(self.held)))
        self.generic_visit(node)

    def _callee_key(self, f: ast.AST) -> tuple | None:
        if isinstance(f, ast.Name):
            if f.id in self.p.functions.get(self.mid, {}):
                return ("fn", self.mid, f.id)
            if self.cross_module:
                return self.p.local_fn_key(self.imports.resolve(f))
            return None
        if isinstance(f, ast.Attribute):
            recv = self._expr_class(f.value)
            if recv is not None and recv in self.p.classes:
                if f.attr in self.p.classes[recv].methods:
                    return ("method", recv, f.attr)
            if self.cross_module:
                # ``_traj_state.invalidate(...)`` through a module alias
                return self.p.local_fn_key(self.imports.resolve(f))
        return None

    # nested defs / lambdas run who-knows-where; don't attribute their
    # bodies to this function's lockset
    def visit_FunctionDef(self, node: ast.FunctionDef):
        pass

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda):
        pass

    def visit_ClassDef(self, node: ast.ClassDef):
        pass


def _flat_targets(t: ast.AST):
    if isinstance(t, (ast.Tuple, ast.List)):
        for el in t.elts:
            yield from _flat_targets(el)
    elif isinstance(t, ast.Starred):
        yield from _flat_targets(t.value)
    else:
        yield t


# ---------------------------------------------------------------------------
# pass 3: inter-procedural propagation + rule evaluation
# ---------------------------------------------------------------------------

def _summaries(project: _Project, config: LintConfig, *,
               prefixes: tuple[str, ...] | None = None,
               cross_module: bool = False) -> dict[tuple, _FnSummary]:
    out: dict[tuple, _FnSummary] = {}
    scope = prefixes if prefixes is not None else config.race_paths
    for mod in project.modules:
        if not config.in_scope(mod.relpath, scope):
            continue
        mid = _module_id(mod.relpath)
        for name, fn in project.functions[mid].items():
            key = ("fn", mid, name)
            s = _FnSummary(key=key, name=name, cls=None, module=mod)
            scan = _FnScan(project, s, fn, cross_module=cross_module)
            for stmt in fn.body:
                scan.visit(stmt)
            out[key] = s
        for cname, info in project.classes.items():
            if info.module is not mod:
                continue
            for mname, m in info.methods.items():
                key = ("method", cname, mname)
                s = _FnSummary(key=key, name=mname, cls=info, module=mod)
                scan = _FnScan(project, s, m, cross_module=cross_module)
                for stmt in m.body:
                    scan.visit(stmt)
                out[key] = s
    return out


def _entry_held(summaries: dict[tuple, _FnSummary],
                universe: frozenset[str]) -> dict[tuple, frozenset[str]]:
    """Locks provably held at function entry.

    ``*_locked`` methods hold their class's locks by repo convention.
    Other PRIVATE functions start at top (all locks) and narrow to the
    intersection over observed call sites — standard optimistic fixpoint.
    Public names are entry points (callable bare from anywhere): ∅."""
    entry: dict[tuple, frozenset[str]] = {}
    callers: dict[tuple, list[tuple[tuple, tuple[str, ...]]]] = defaultdict(list)
    for key, s in summaries.items():
        for c in s.calls:
            if c.callee in summaries:
                callers[c.callee].append((key, c.held))
    fixed: set[tuple] = set()
    for key, s in summaries.items():
        name = s.name
        if name.endswith("_locked") and s.cls is not None:
            entry[key] = frozenset(s.cls.lock_ids())
            fixed.add(key)
        elif not name.startswith("_") or name.startswith("__"):
            entry[key] = frozenset()
            fixed.add(key)
        elif not callers[key]:
            entry[key] = frozenset()
            fixed.add(key)
        else:
            entry[key] = universe
    changed = True
    while changed:
        changed = False
        for key in summaries:
            if key in fixed:
                continue
            acc = None
            for caller, held in callers[key]:
                site = frozenset(held) | entry.get(caller, frozenset())
                acc = site if acc is None else (acc & site)
            acc = acc if acc is not None else frozenset()
            if acc != entry[key]:
                entry[key] = acc
                changed = True
    return entry


def _may_acquire(summaries: dict[tuple, _FnSummary]) -> dict[tuple, frozenset[str]]:
    ma = {k: frozenset(a.lock for a in s.acquires)
          for k, s in summaries.items()}
    changed = True
    while changed:
        changed = False
        for k, s in summaries.items():
            acc = ma[k]
            for c in s.calls:
                if c.callee in ma:
                    acc = acc | ma[c.callee]
            if acc != ma[k]:
                ma[k] = acc
                changed = True
    return ma


def _grouped_writes(
    summaries: dict[tuple, _FnSummary],
    entry: dict[tuple, frozenset[str]],
) -> dict[tuple[str, str], list[_Write]]:
    """Tracked non-constructor writes per (class, attr), with entry-held
    locks folded into each write's held set."""
    by_field: dict[tuple[str, str], list[_Write]] = defaultdict(list)
    for key, s in summaries.items():
        for w in s.writes:
            if w.method in _CTOR_METHODS:
                continue
            by_field[(w.owner, w.attr)].append(
                _Write(owner=w.owner, attr=w.attr, line=w.line, col=w.col,
                       held=tuple(frozenset(w.held) | entry[key]),
                       what=w.what, module=w.module, method=w.method))
    return by_field


def _infer_guard(writes: list[_Write]) -> tuple[str | None, int]:
    """Majority vote: the lock held across >50% of a field's tracked
    writes (≥ 2 writes required) is its guard."""
    if len(writes) < 2:
        return None, 0
    counts: dict[str, int] = defaultdict(int)
    for w in writes:
        for lid in set(w.held):
            counts[lid] += 1
    for lid, n in sorted(counts.items()):
        if n * 2 > len(writes):
            return lid, n
    return None, 0


def guard_map(modules: list[Module],
              config: LintConfig | None = None) -> dict[str, dict]:
    """The inferred guard map: ``Class.attr`` → guard lock + coverage
    (the ``--race --guards`` CLI view, and the docs/concurrency.md
    source of truth)."""
    config = config or LintConfig()
    project = _Project(modules)
    summaries = _summaries(project, config)
    entry = _entry_held(summaries, frozenset(project.lock_home))
    out: dict[str, dict] = {}
    for (owner, attr), writes in sorted(_grouped_writes(summaries, entry).items()):
        guard, n = _infer_guard(writes)
        if guard is not None:
            out[f"{owner}.{attr}"] = {
                "guard": guard, "guarded_writes": n,
                "total_writes": len(writes),
            }
    return out


def active_race_rules(config: LintConfig) -> set[str]:
    """The race rules this run evaluates (``--rules`` filters here just
    like it does the per-module pass)."""
    if config.rules is None:
        return set(RACE_RULE_IDS)
    return set(config.rules) & set(RACE_RULE_IDS)


def analyze_modules(modules: list[Module],
                    config: LintConfig | None = None) -> list[Violation]:
    """Run R001/R002/R003 over a parsed module set (the whole-program
    entry point; waivers/baseline are the caller's passes)."""
    config = config or LintConfig()
    active = active_race_rules(config)
    project = _Project(modules)
    summaries = _summaries(project, config)
    universe = frozenset(project.lock_home)
    entry = _entry_held(summaries, universe)
    ma = _may_acquire(summaries)
    violations: list[Violation] = []

    # ---- R001: guard-map inference + bare writes --------------------------
    by_field = (
        _grouped_writes(summaries, entry) if "R001" in active else {}
    )
    for (owner, attr), writes in sorted(by_field.items()):
        guard, guard_n = _infer_guard(writes)
        if guard is None:
            continue
        for w in writes:
            if guard in w.held:
                continue
            guarded = next(x for x in writes if guard in x.held)
            violations.append(Violation(
                rule="R001", path=w.module.path, line=w.line, col=w.col,
                message=(
                    f"{owner}.{attr} is written here ({w.what}) without "
                    f"{guard}, which guards {guard_n}/{len(writes)} "
                    f"tracked writes (e.g. "
                    f"{guarded.module.relpath}:{guarded.line}) — take the "
                    f"lock, or waive with a justification if the bare "
                    f"publication is intentional"),
            ))

    # ---- R002: lock-order inversions (SCCs of the acquisition graph) ------
    edges: dict[tuple[str, str], tuple[str, int, str]] = {}

    def _edge(a: str, b: str, mod: Module, line: int, how: str):
        if a == b:
            return
        key = (a, b)
        cur = (mod.path, line, how)
        if key not in edges or cur[:2] < edges[key][:2]:
            edges[key] = cur

    for key, s in (summaries.items() if "R002" in active else ()):
        ent = entry[key]
        for a in s.acquires:
            for h in frozenset(a.held) | ent:
                _edge(h, a.lock, s.module, a.line, "acquired here")
        for c in s.calls:
            held = frozenset(c.held) | ent
            if not held or c.callee not in ma:
                continue
            for b in ma[c.callee]:
                for h in held:
                    _edge(h, b, s.module, c.line,
                          f"via {c.callee[1]}.{c.callee[2]}()")
    for scc in _cycle_components(edges):
        members = sorted(scc)
        detail = []
        anchor = None
        for (a, b), (path, line, how) in sorted(edges.items(),
                                                key=lambda kv: kv[1][:2]):
            if a in scc and b in scc:
                detail.append(f"{a} -> {b} ({path}:{line}, {how})")
                anchor = (path, line)
        if anchor is None:
            continue
        violations.append(Violation(
            rule="R002", path=anchor[0], line=anchor[1], col=0,
            message=(
                f"lock-order cycle among {', '.join(members)}: "
                f"{'; '.join(detail)} — pick one global order "
                f"(docs/concurrency.md)"),
        ))

    # ---- R003: blocking calls under a hot-path lock -----------------------
    for key, s in (summaries.items() if "R003" in active else ()):
        ent = entry[key]
        for b in s.blocking:
            hot = sorted(
                lid for lid in frozenset(b.held) | ent
                if config.in_scope(
                    project.lock_home.get(lid, ""), config.r003_paths)
            )
            if not hot:
                continue
            violations.append(Violation(
                rule="R003", path=b.module.path, line=b.line, col=b.col,
                message=(
                    f"blocking call {b.what} while holding hot-path lock "
                    f"{'/'.join(hot)} — hoist the I/O out of the critical "
                    f"section, or waive if this lock exists to serialize "
                    f"exactly this I/O"),
            ))

    violations.sort(key=lambda v: (v.path, v.line, v.col, v.rule))
    return violations


def _cycle_components(edges: dict[tuple[str, str], tuple]) -> list[frozenset[str]]:
    """Strongly-connected components with ≥ 2 nodes (each is one deadlock
    knot; iterative Tarjan so pathological graphs can't blow the stack)."""
    adj: dict[str, list[str]] = defaultdict(list)
    nodes: set[str] = set()
    for a, b in edges:
        adj[a].append(b)
        nodes.update((a, b))
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    counter = [0]
    out: list[frozenset[str]] = []

    for root in sorted(nodes):
        if root in index:
            continue
        work = [(root, iter(sorted(adj[root])))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            v, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(adj[w]))))
                    advanced = True
                    break
                if w in on_stack:
                    low[v] = min(low[v], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                pv = work[-1][0]
                low[pv] = min(low[pv], low[v])
            if low[v] == index[v]:
                comp = set()
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.add(w)
                    if w == v:
                        break
                if len(comp) > 1:
                    out.append(frozenset(comp))
    return out


# ---------------------------------------------------------------------------
# driver: paths → violations with waivers + stale-waiver hygiene applied
# ---------------------------------------------------------------------------

def load_modules(paths: list[str]) -> tuple[list[Module], list[Violation]]:
    """Parse every ``.py`` under ``paths`` → (modules, E000 violations for
    unparseable files). The one file-loading loop every whole-program
    consumer (race driver, ``--guards``) shares."""
    modules: list[Module] = []
    errors: list[Violation] = []
    for fp in iter_py_files(paths):
        with open(fp, encoding="utf-8") as f:
            source = f.read()
        mod = parse_module(source, fp)
        if isinstance(mod, Violation):
            errors.append(mod)
        else:
            modules.append(mod)
    return modules, errors


def build_flow_graph(
    modules: list[Module], config: LintConfig | None = None,
) -> tuple[_Project, dict[tuple, _FnSummary]]:
    """Shared fixpoint machinery for the flow prong: index the project
    and scan every function with CROSS-MODULE call-graph edges enabled
    (``devmon.costs().forget(...)`` and ``_traj_state.invalidate(...)``
    resolve). The race prong keeps its narrower edge set — this helper
    exists so F-rules ride the same type inference without perturbing
    R001-R003 results."""
    config = config or LintConfig()
    project = _Project(modules)
    summaries = _summaries(project, config, prefixes=("",),
                           cross_module=True)
    return project, summaries


def analyze_race_paths(paths: list[str],
                       config: LintConfig | None = None) -> list[Violation]:
    """The ``--race`` entry point: parse every file, run the whole-program
    analysis, apply per-line waivers, and flag stale tpurace waivers."""
    from geomesa_tpu.analysis.core import finalize_module_violations
    from geomesa_tpu.analysis.rules import all_rules

    config = config or LintConfig()
    if config.rules is not None:
        unknown = set(config.rules) - set(all_rules())
        if unknown:
            raise ValueError(f"unknown rule ids: {sorted(unknown)}")
    modules, violations = load_modules(paths)
    violations = list(violations)
    violations.extend(analyze_modules(modules, config))
    by_path: dict[str, list[Violation]] = defaultdict(list)
    for v in violations:
        by_path[v.path].append(v)
    # waivers are judged stale only against the rules that RAN this pass
    judged = active_race_rules(config)
    emit_w001 = config.rules is None or "W001" in config.rules
    for mod in modules:
        vs = by_path.get(mod.path, [])
        violations.extend(finalize_module_violations(
            mod, vs, judged, emit_w001=emit_w001))
    violations.sort(key=lambda v: (v.path, v.line, v.col, v.rule))
    return violations
