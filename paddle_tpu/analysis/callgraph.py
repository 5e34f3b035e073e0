"""Traced-function call-graph resolution for tracelint.

Static (AST-only — nothing is imported) discovery of which functions
in the package run UNDER A JAX TRACE, resolved outward from the trace
entries the framework actually uses:

* ``instrumented_jit(fn, name, ...)`` (`jit/functional.py`) and plain
  ``jax.jit(fn, ...)``
* ``parallel.shard_map(body, mesh=..., in_specs=..., out_specs=...)``
  (the package's one door to ``jax.shard_map``) and ``jax.shard_map``
* ``jax.lax.scan(body, ...)`` bodies

The function argument is resolved through the package's real idioms:
a bare name (module function or in-scope nested def), a method
reference (``self._fn``), a ``functools.partial(fn, ...)``, a lambda,
a local name previously bound (``body = self._step_body(cfg)``), or —
the serving-engine pattern — a CALL of a builder whose return value is
a traced function (``instrumented_jit(self._build_step(), ...)``
resolves `_build_step` -> `return self._step_body(...)` ->
`_step_body` -> ``return step`` -> the nested ``step`` def). From the
resolved entries, tracedness propagates transitively through every
call the AST can resolve inside the package: bare names in scope,
``self.method`` within the same class, and ``from`` -imported package
functions — cross-module propagation included (the mixed step's
``_ffn_dense`` / ``_ln`` helpers in `incubate/nn/fused_transformer.py`
are reached from `serving/engine.py` this way).

Unresolvable targets (attribute chains on unknown objects, dynamic
dispatch) are skipped: the analysis UNDER-approximates tracedness, so
every rule it fires inside a traced function is real with respect to
the call graph. Jit handles (``self._step_fn = instrumented_jit(...)``)
are also recorded with their ``static_argnums`` / ``donate_argnums``
so call-site rules (unhashable static args, use-after-donation) can
check the caller side.
"""
from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Tuple

#: dotted-name suffixes that make a call a trace entry; value = index
#: of the traced-function argument (`lax.while_loop` traces TWO
#: arguments — cond at 0 and body at 1; `_entry_kind` returns the
#: full index tuple)
TRACE_ENTRIES = {
    "instrumented_jit": 0,
    "jax.jit": 0,
    "shard_map": 0,
    "lax.scan": 0,
    "lax.while_loop": 0,
}

#: imported-module targets that count for the bare ``shard_map`` /
#: ``lax.scan`` suffixes (a user-defined shard_map in some unrelated
#: module must not create trace roots)
_SHARD_MAP_HOMES = ("parallel", "jax")
_SCAN_HOMES = ("jax.lax", "jax")


@dataclasses.dataclass
class FunctionInfo:
    module: "ModuleIndex"
    qualname: str
    node: ast.AST                     # FunctionDef | Lambda
    params: Tuple[str, ...]
    class_name: Optional[str] = None
    parent: Optional["FunctionInfo"] = None
    nested: Dict[str, "FunctionInfo"] = dataclasses.field(
        default_factory=dict)
    traced: bool = False
    #: True when this function is the DIRECT argument of a trace entry
    #: (its parameters are traced values); transitively-traced callees
    #: get context-free rules only
    trace_entry: bool = False
    static_argnums: Tuple[int, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    #: leading params bound host-side by functools.partial at the
    #: trace root — NOT traced values
    partial_bound: int = 0
    #: which trace entry made it traced ("jit" | "shard_map" | "scan"
    #: | "while_loop")
    entry_kind: Optional[str] = None
    #: True when the function runs INSIDE a device loop — it is a
    #: scan/while_loop body (or cond), or transitively called from
    #: one. Host escapes here stall/fail per iteration, not per trace:
    #: TL107's scope
    loop_reachable: bool = False

    @property
    def name(self):
        return self.qualname.rsplit(".", 1)[-1]

    @property
    def lineno(self):
        return self.node.lineno


@dataclasses.dataclass
class JitHandle:
    """A name a jitted callable was bound to (`self._step = jax.jit(f,
    donate_argnums=(0, 1))`), for caller-side rules."""
    module: "ModuleIndex"
    #: "name" for plain locals/globals, "self.attr" for attributes
    target: str
    static_argnums: Tuple[int, ...]
    donate_argnums: Tuple[int, ...]
    lineno: int


def _dotted(node):
    """'a.b.c' for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _int_tuple(node):
    """Literal int / tuple-or-list-of-int -> tuple of ints, else ()."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
            else:
                return ()
        return tuple(out)
    return ()


class ModuleIndex:
    """One parsed module: imports, functions (by dotted qualname),
    classes, and per-function local-binding maps."""

    def __init__(self, path, relpath, dotted_module, tree,
                 is_package=False):
        self.path = path
        self.relpath = relpath
        self.dotted = dotted_module
        self.tree = tree
        self.is_package = is_package
        #: local alias -> imported dotted target
        self.imports: Dict[str, str] = {}
        self.functions: Dict[str, FunctionInfo] = {}
        #: class name -> {method name -> FunctionInfo}
        self.classes: Dict[str, Dict[str, FunctionInfo]] = {}
        self.jit_handles: Dict[str, JitHandle] = {}
        self._collect()

    # ------------------------------------------------------- collection
    def _resolve_relative(self, node):
        """Absolute dotted module for a `from ...x import y` node.
        For a plain module `pkg.mod`, level 1 is `pkg` (strip one
        segment); for a PACKAGE (`__init__.py`, whose dotted name IS
        the package), level 1 is the package itself (strip none)."""
        if not node.level:
            return node.module or ""
        base = self.dotted.split(".")
        strip = node.level - (1 if self.is_package else 0)
        if strip:
            base = base[:len(base) - strip]
        if node.module:
            base.append(node.module)
        return ".".join(base)

    def _collect(self):
        index = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.scope: List[FunctionInfo] = []
                self.cls: List[str] = []

            # imports (any scope: the repo imports inside functions)
            def visit_Import(self, node):
                for a in node.names:
                    index.imports[a.asname or a.name.split(".")[0]] = \
                        a.name

            def visit_ImportFrom(self, node):
                mod = index._resolve_relative(node)
                for a in node.names:
                    if a.name == "*":
                        continue
                    index.imports[a.asname or a.name] = \
                        f"{mod}.{a.name}" if mod else a.name

            def _function(self, node):
                if self.scope:
                    qual = self.scope[-1].qualname + "." + node.name
                elif self.cls:
                    qual = self.cls[-1] + "." + node.name
                else:
                    qual = node.name
                a = node.args
                params = tuple(
                    p.arg for p in (a.posonlyargs + a.args))
                info = FunctionInfo(
                    module=index, qualname=qual, node=node,
                    params=params,
                    class_name=(self.cls[-1] if self.cls
                                and not self.scope else None),
                    parent=self.scope[-1] if self.scope else None)
                index.functions[qual] = info
                if info.class_name:
                    index.classes.setdefault(
                        info.class_name, {})[node.name] = info
                if self.scope:
                    self.scope[-1].nested[node.name] = info
                self.scope.append(info)
                self.generic_visit(node)
                self.scope.pop()

            visit_FunctionDef = _function
            visit_AsyncFunctionDef = _function

            def visit_ClassDef(self, node):
                if self.scope:
                    # classes inside functions: out of scope
                    return
                self.cls.append(node.name)
                self.generic_visit(node)
                self.cls.pop()

        V().visit(self.tree)

    # ------------------------------------------------------- resolution
    def resolve_alias(self, dotted_name):
        """Expand the leading alias of 'a.b.c' through this module's
        imports -> absolute dotted name (best effort)."""
        if dotted_name is None:
            return None
        head, _, rest = dotted_name.partition(".")
        target = self.imports.get(head)
        if target is None:
            return dotted_name
        return f"{target}.{rest}" if rest else target


class PackageIndex:
    """Every module under a root directory, plus cross-module lookup."""

    def __init__(self, root, package_name=None):
        self.root = os.path.abspath(root)
        base = package_name or os.path.basename(self.root.rstrip("/"))
        self.modules: Dict[str, ModuleIndex] = {}      # dotted -> index
        self.by_path: Dict[str, ModuleIndex] = {}
        self.errors: List[Tuple[str, str]] = []
        for dirpath, dirnames, filenames in os.walk(self.root):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__")
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, self.root)
                dotted = base + "." + rel[:-3].replace(os.sep, ".")
                is_package = dotted.endswith(".__init__")
                if is_package:
                    dotted = dotted[:-len(".__init__")]
                try:
                    with open(path, "r", encoding="utf-8") as f:
                        tree = ast.parse(f.read(), filename=path)
                except (SyntaxError, UnicodeDecodeError) as e:
                    self.errors.append((rel, str(e)))
                    continue
                mi = ModuleIndex(path, rel, dotted, tree,
                                 is_package=is_package)
                self.modules[dotted] = mi
                self.by_path[rel] = mi

    def lookup(self, dotted_fn):
        """Absolute 'pkg.mod.func' (or 'pkg.mod.Class.method') ->
        FunctionInfo, or None."""
        if not dotted_fn:
            return None
        parts = dotted_fn.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            mod = self.modules.get(".".join(parts[:cut]))
            if mod is not None:
                return mod.functions.get(".".join(parts[cut:]))
        return None


# ------------------------------------------------------------ resolution


class Resolver:
    """Resolve expressions to FunctionInfos and run the traced-set
    fixpoint."""

    def __init__(self, index: PackageIndex):
        self.index = index
        self.roots: List[FunctionInfo] = []

    # -- scope utilities
    def _scope_lookup(self, name, scope: Optional[FunctionInfo],
                      module: ModuleIndex):
        """A bare name -> FunctionInfo via nested defs of enclosing
        functions, then module-level defs, then imports."""
        f = scope
        while f is not None:
            if name in f.nested:
                return f.nested[name]
            f = f.parent
        if name in module.functions:
            return module.functions[name]
        target = module.imports.get(name)
        if target:
            return self.index.lookup(target)
        return None

    def _local_binding(self, name, scope: Optional[FunctionInfo]):
        """Last single-name assignment `name = <expr>` in the scope's
        body (best effort, no flow analysis)."""
        if scope is None or not isinstance(
                scope.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return None
        found = None
        for node in ast.walk(scope.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == name:
                found = node.value
        return found

    def resolve_function_expr(self, expr, scope, module, _depth=0):
        """Expression in traced-argument position -> [FunctionInfo]."""
        if _depth > 8 or expr is None:
            return []
        if isinstance(expr, ast.Lambda):
            qual = (scope.qualname + ".<lambda>") if scope \
                else "<lambda>"
            info = module.functions.get(qual)
            if info is None:
                a = expr.args
                info = FunctionInfo(
                    module=module, qualname=qual, node=expr,
                    params=tuple(p.arg for p in
                                 (a.posonlyargs + a.args)),
                    parent=scope)
                module.functions[qual] = info
            return [info]
        if isinstance(expr, ast.Name):
            f = self._scope_lookup(expr.id, scope, module)
            if f is not None:
                return [f]
            bound = self._local_binding(expr.id, scope)
            if bound is not None and bound is not expr:
                return self.resolve_function_expr(bound, scope, module,
                                                 _depth + 1)
            return []
        if isinstance(expr, ast.Attribute):
            # self._fn / cls._fn -> method of the enclosing class
            if isinstance(expr.value, ast.Name) \
                    and expr.value.id in ("self", "cls"):
                cls = self._enclosing_class(scope)
                if cls:
                    m = module.classes.get(cls, {}).get(expr.attr)
                    if m is not None:
                        return [m]
                return []
            f = self.index.lookup(
                module.resolve_alias(_dotted(expr)))
            return [f] if f is not None else []
        if isinstance(expr, ast.Call):
            callee = _dotted(expr.func)
            if callee is not None and \
                    module.resolve_alias(callee) is not None and \
                    module.resolve_alias(callee).endswith(
                        "functools.partial") and expr.args:
                fns = self.resolve_function_expr(
                    expr.args[0], scope, module, _depth + 1)
                for f in fns:
                    # partial-bound leading positionals are host
                    # values, not traced arguments
                    f.partial_bound = max(f.partial_bound,
                                          len(expr.args) - 1)
                return fns
            # builder call: traced fns are whatever the builder returns
            builders = self.resolve_function_expr(expr.func, scope,
                                                 module, _depth + 1)
            out = []
            for b in builders:
                out.extend(self._returned_functions(b, _depth + 1))
            return out
        return []

    def _enclosing_class(self, scope):
        f = scope
        while f is not None:
            if f.class_name:
                return f.class_name
            f = f.parent
        return None

    def _returned_functions(self, fn: FunctionInfo, _depth):
        """Functions a builder returns (resolving `return step`,
        `return self._step_body(cfg)` chains)."""
        if not isinstance(fn.node,
                          (ast.FunctionDef, ast.AsyncFunctionDef)):
            return []
        out = []
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Return) and node.value is not None:
                out.extend(self.resolve_function_expr(
                    node.value, fn, fn.module, _depth))
        return out

    # -------------------------------------------------- root discovery
    def _entry_kind(self, call, scope, module):
        """(kind, traced-arg index tuple) when `call` is a trace
        entry. while_loop traces both its cond (arg 0) and body
        (arg 1)."""
        name = _dotted(call.func)
        if name is None:
            return None
        resolved = module.resolve_alias(name) or name
        tail = resolved.rsplit(".", 1)[-1]
        if tail == "instrumented_jit" or resolved == "jax.jit" \
                or resolved.endswith("jax.jit"):
            return ("jit", (0,))
        if tail == "shard_map":
            if any(h in resolved for h in _SHARD_MAP_HOMES):
                return ("shard_map", (0,))
            return None
        if resolved.endswith("lax.scan") or resolved == "lax.scan":
            return ("scan", (0,))
        if resolved.endswith("lax.while_loop") \
                or resolved == "lax.while_loop":
            return ("while_loop", (0, 1))
        return None

    def find_roots(self):
        """Walk every module for trace-entry calls; mark the resolved
        traced functions and record jit handles."""
        for module in self.index.modules.values():
            for scope, call in _calls_with_scope(module):
                ek = self._entry_kind(call, scope, module)
                if ek is None:
                    continue
                kind, arg_idx = ek
                static = donate = ()
                for kw in call.keywords:
                    if kw.arg == "static_argnums":
                        static = _int_tuple(kw.value)
                    elif kw.arg == "donate_argnums":
                        donate = _int_tuple(kw.value)
                for argi in arg_idx:
                    if len(call.args) <= argi:
                        continue
                    for fn in self.resolve_function_expr(
                            call.args[argi], scope, module):
                        fn.traced = True
                        fn.trace_entry = True
                        fn.entry_kind = fn.entry_kind or kind
                        if kind in ("scan", "while_loop"):
                            fn.loop_reachable = True
                        fn.static_argnums = fn.static_argnums \
                            or static
                        fn.donate_argnums = fn.donate_argnums \
                            or donate
                        self.roots.append(fn)
                if kind == "jit":
                    self._record_handle(call, scope, module,
                                        static, donate)

    def _record_handle(self, call, scope, module, static, donate):
        """`target = jax.jit(...)` / `self.x = instrumented_jit(...)`:
        remember the bound name for caller-side rules."""
        parent = getattr(call, "_tracelint_parent", None)
        if not isinstance(parent, ast.Assign) \
                or len(parent.targets) != 1:
            return
        t = parent.targets[0]
        if isinstance(t, ast.Name):
            target = t.id
        elif isinstance(t, ast.Attribute) and \
                isinstance(t.value, ast.Name) \
                and t.value.id in ("self", "cls"):
            target = f"self.{t.attr}"
        else:
            return
        module.jit_handles[target] = JitHandle(
            module=module, target=target, static_argnums=static,
            donate_argnums=donate, lineno=call.lineno)

    # ------------------------------------------------------ propagation
    def propagate(self):
        """Transitive closure: calls inside traced functions mark
        their resolvable package-internal callees traced, and callees
        of scan/while_loop bodies (or anything already loop-reachable)
        additionally `loop_reachable` — a function may be revisited
        ONCE more to push a newly-gained loop flag through callees
        first discovered via a non-loop path."""
        work = [f for f in self.roots]
        seen = {id(f) for f in work}
        while work:
            fn = work.pop()
            if not isinstance(fn.node, (ast.FunctionDef,
                                        ast.AsyncFunctionDef,
                                        ast.Lambda)):
                continue
            in_loop = (fn.loop_reachable
                       or fn.entry_kind in ("scan", "while_loop"))
            body = fn.node.body if isinstance(fn.node, ast.Lambda) \
                else fn.node
            for node in ast.walk(body):
                if not isinstance(node, ast.Call):
                    continue
                for callee in self.resolve_function_expr(
                        node.func, fn, fn.module):
                    # only package-internal, non-builder targets
                    if callee.module.dotted.startswith("jax"):
                        continue
                    gained_loop = in_loop and not callee.loop_reachable
                    if gained_loop:
                        callee.loop_reachable = True
                    if id(callee) in seen and not gained_loop:
                        continue
                    callee.traced = True
                    seen.add(id(callee))
                    work.append(callee)

    def traced_functions(self):
        return [f for m in self.index.modules.values()
                for f in m.functions.values() if f.traced]


def _calls_with_scope(module: ModuleIndex):
    """Yield (enclosing FunctionInfo | None, Call) for every call in
    the module, annotating each call with its parent statement (for
    assignment-target recovery)."""
    out = []

    class V(ast.NodeVisitor):
        def __init__(self):
            self.scope: List[FunctionInfo] = []
            self.cls: List[str] = []
            self.stmt = None

        def visit(self, node):
            if isinstance(node, ast.stmt):
                prev, self.stmt = self.stmt, node
                super().visit(node)
                self.stmt = prev
                return
            super().visit(node)

        def _function(self, node):
            if self.scope:
                qual = self.scope[-1].qualname + "." + node.name
            elif self.cls:
                qual = self.cls[-1] + "." + node.name
            else:
                qual = node.name
            info = module.functions.get(qual)
            if info is None:
                self.generic_visit(node)
                return
            self.scope.append(info)
            self.generic_visit(node)
            self.scope.pop()

        visit_FunctionDef = _function
        visit_AsyncFunctionDef = _function

        def visit_ClassDef(self, node):
            if self.scope:
                return
            self.cls.append(node.name)
            self.generic_visit(node)
            self.cls.pop()

        def visit_Call(self, node):
            node._tracelint_parent = self.stmt
            out.append((self.scope[-1] if self.scope else None, node))
            self.generic_visit(node)

    V().visit(module.tree)
    return out


def build_traced_set(root, package_name=None):
    """(PackageIndex, Resolver) with roots found and tracedness
    propagated — the tracelint driver's entry point."""
    index = PackageIndex(root, package_name)
    res = Resolver(index)
    res.find_roots()
    res.propagate()
    return index, res
