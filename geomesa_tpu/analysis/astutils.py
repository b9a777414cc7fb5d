"""Shared AST machinery for tpulint rules.

Everything here is pure ``ast`` — no JAX import, no execution of the
linted code. The two load-bearing pieces:

- :class:`ImportMap` — canonicalizes local names to dotted import paths
  (``jnp.where`` → ``jax.numpy.where``) so rules match semantics, not
  spelling. ``import jax.numpy as jnp`` and ``from jax import jit``
  resolve to the same canonical names.
- taint propagation — a per-function forward pass marking names that
  (transitively) hold traced/device values, with shape/dtype-style
  accesses shielded because they are static under tracing.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

# Canonical names of the jit entry points (pjit is jit's sharded spelling).
JIT_NAMES = frozenset({
    "jax.jit",
    "jax.pjit",
    "jax.experimental.pjit.pjit",
})
PARTIAL_NAMES = frozenset({"functools.partial", "partial"})
CACHE_DECORATORS = frozenset({
    "functools.lru_cache",
    "functools.cache",
    "lru_cache",
    "cache",
})
# Attribute accesses on a tracer that yield STATIC (trace-time) values —
# conditioning Python control flow on these is fine.
STATIC_ATTRS = frozenset({
    "shape", "ndim", "dtype", "size", "aval", "sharding", "itemsize",
})
# Builtins whose result over a tracer is static (len) or type-level.
STATIC_CALLS = frozenset({"len", "isinstance", "type", "hasattr"})


class ImportMap:
    """Local name → canonical dotted path, from a module's import statements."""

    def __init__(self, tree: ast.Module):
        self.names: dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.asname:
                        self.names[alias.asname] = alias.name
                    else:
                        # ``import jax.numpy`` binds ``jax``
                        root = alias.name.split(".")[0]
                        self.names[root] = root
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    local = alias.asname or alias.name
                    self.names[local] = f"{node.module}.{alias.name}"

    def resolve(self, node: ast.AST) -> str | None:
        """Canonical dotted path of a Name/Attribute chain, or None."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.names.get(node.id, node.id)
        parts.append(root)
        return ".".join(reversed(parts))

    def is_jit(self, node: ast.AST) -> bool:
        return self.resolve(node) in JIT_NAMES

    def is_device_namespace(self, dotted: str | None) -> bool:
        """Does this canonical path live in the traced/device value world?"""
        if dotted is None:
            return False
        return dotted == "jax" or dotted.startswith("jax.")


def build_parents(root: ast.AST) -> dict[ast.AST, ast.AST]:
    return {
        child: parent
        for parent in ast.walk(root)
        for child in ast.iter_child_nodes(parent)
    }


@dataclass
class StaticSpec:
    """Static-argument declaration parsed off a jit decoration."""

    names: set[str] = field(default_factory=set)
    nums: set[int] = field(default_factory=set)
    unhashable_nodes: list[ast.AST] = field(default_factory=list)

    def static_params(self, fn: ast.FunctionDef) -> set[str]:
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        out = set(self.names)
        for i in self.nums:
            if 0 <= i < len(params):
                out.add(params[i])
        return out


def _const_strs(node: ast.AST) -> list[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
        return out
    return []


def _const_ints(node: ast.AST) -> list[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return [
            e.value for e in node.elts
            if isinstance(e, ast.Constant) and isinstance(e.value, int)
        ]
    return []


def parse_static_spec(call: ast.Call) -> StaticSpec:
    """Static-arg spec from a ``jax.jit(...)``/``partial(jax.jit, ...)`` call."""
    spec = StaticSpec()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            spec.names.update(_const_strs(kw.value))
        elif kw.arg == "static_argnums":
            spec.nums.update(_const_ints(kw.value))
        else:
            continue
        if isinstance(kw.value, (ast.List, ast.Set, ast.Dict,
                                 ast.ListComp, ast.SetComp, ast.DictComp)):
            spec.unhashable_nodes.append(kw.value)
    return spec


def jit_decoration(dec: ast.AST, imports: ImportMap) -> StaticSpec | None:
    """StaticSpec if ``dec`` is a jit decoration (bare, called, or wrapped
    in ``functools.partial``); None otherwise."""
    if imports.is_jit(dec):
        return StaticSpec()
    if isinstance(dec, ast.Call):
        if imports.is_jit(dec.func):
            return parse_static_spec(dec)
        if imports.resolve(dec.func) in PARTIAL_NAMES and dec.args:
            if imports.is_jit(dec.args[0]):
                return parse_static_spec(dec)
    return None


def jitted_functions(
    tree: ast.Module, imports: ImportMap
) -> list[tuple[ast.FunctionDef, StaticSpec]]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            spec = jit_decoration(dec, imports)
            if spec is not None:
                out.append((node, spec))
                break
    return out


def pallas_kernels(tree: ast.Module, imports: ImportMap) -> list[ast.FunctionDef]:
    """FunctionDefs referenced as the kernel of a ``pl.pallas_call``."""
    kernel_names: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = imports.resolve(node.func)
        if dotted is None or not dotted.endswith("pallas_call"):
            continue
        cands = list(node.args[:1]) + [
            kw.value for kw in node.keywords if kw.arg == "kernel"
        ]
        for c in cands:
            if isinstance(c, ast.Name):
                kernel_names.add(c.id)
            elif isinstance(c, ast.Call):
                # functools.partial(kernel, ...) — common pallas idiom
                for a in c.args[:1]:
                    if isinstance(a, ast.Name):
                        kernel_names.add(a.id)
    return [
        node for node in ast.walk(tree)
        if isinstance(node, ast.FunctionDef) and node.name in kernel_names
    ]


class _MentionScan(ast.NodeVisitor):
    """Does an expression mention a tainted name (unshielded) or call into
    the jax namespace? Shielded positions: ``x.shape``-style static
    attributes and ``len(x)``-style static builtins."""

    def __init__(self, tainted: set[str], imports: ImportMap):
        self.tainted = tainted
        self.imports = imports
        self.hit: ast.AST | None = None

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in STATIC_ATTRS:
            return  # x.shape / cols[0].ndim / ... — static under tracing
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in STATIC_CALLS:
            return  # len(x), isinstance(x, T): static results
        dotted = self.imports.resolve(fn)
        if dotted is not None and self.imports.is_device_namespace(dotted):
            if self.hit is None:
                self.hit = node
            return
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name):
        if node.id in self.tainted and self.hit is None:
            self.hit = node

    def visit_Lambda(self, node: ast.Lambda):
        pass  # deferred body — not evaluated here


def mentions_traced(expr: ast.AST, tainted: set[str], imports: ImportMap) -> bool:
    scan = _MentionScan(tainted, imports)
    scan.visit(expr)
    return scan.hit is not None


def _target_names(target: ast.AST) -> list[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out = []
        for e in target.elts:
            out.extend(_target_names(e))
        return out
    if isinstance(target, ast.Starred):
        return _target_names(target.value)
    return []


def iter_body_stmts(body: list[ast.stmt]):
    """All statements in a body, recursing into compound statements but NOT
    into nested function/class definitions (those are separate scopes)."""
    for stmt in body:
        yield stmt
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        for attr in ("body", "orelse", "finalbody"):
            yield from iter_body_stmts(getattr(stmt, attr, []))
        for handler in getattr(stmt, "handlers", []):
            yield from iter_body_stmts(handler.body)


def propagate_taint(
    fn: ast.FunctionDef, initial: set[str], imports: ImportMap
) -> set[str]:
    """Forward taint pass over ``fn``'s body: a name assigned from an
    expression that mentions a tainted name (or calls into jax.*) becomes
    tainted. Iterates to a fixpoint so loop-carried taint converges."""
    tainted = set(initial)
    while True:
        before = len(tainted)
        for stmt in iter_body_stmts(fn.body):
            if isinstance(stmt, ast.Assign):
                if mentions_traced(stmt.value, tainted, imports):
                    for t in stmt.targets:
                        tainted.update(_target_names(t))
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                if mentions_traced(stmt.value, tainted, imports):
                    tainted.update(_target_names(stmt.target))
            elif isinstance(stmt, ast.AugAssign):
                if mentions_traced(stmt.value, tainted, imports):
                    tainted.update(_target_names(stmt.target))
            elif isinstance(stmt, ast.For):
                if mentions_traced(stmt.iter, tainted, imports):
                    tainted.update(_target_names(stmt.target))
            elif isinstance(stmt, ast.With):
                for item in stmt.items:
                    if item.optional_vars is not None and mentions_traced(
                        item.context_expr, tainted, imports
                    ):
                        tainted.update(_target_names(item.optional_vars))
        if len(tainted) == before:
            return tainted


def nested_functions(fn: ast.FunctionDef) -> list[ast.FunctionDef]:
    out = []
    for stmt in iter_body_stmts(fn.body):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.append(stmt)
    return out
