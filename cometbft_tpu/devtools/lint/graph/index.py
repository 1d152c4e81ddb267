"""Whole-program index: locks, classes, functions, imports, types.

Everything downstream (facts extraction, propagation, the rules) works
off this one structure, built in a single sweep over the engine's
already-parsed :class:`FileContext` list.

Lock identity: a lock's node in the graph is its *runtime name* — the
string passed to ``libsync.Mutex("consensus.state")`` — so the static
graph and the ``COMETBFT_TPU_LOCK_ORDER`` recorder speak the same
vocabulary.  Names label roles, not instances (every ``Peer`` shares
``p2p.peer._data_mtx``); same-name edges are therefore excluded from
ordering on both sides.  Unnamed locks/conditions get a synthesized
``<module>.<class>.<attr>`` key that never appears at runtime.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from . import hints

_SYNC_PRIMS = ("Mutex", "RLock", "Condition")

# stdlib modules whose aliases the blocking classifier needs to track
_STDLIB_MODULES = (
    "time", "os", "select", "subprocess", "socket", "queue", "threading",
    "jax",
)


@dataclass
class LockDef:
    key: str            # runtime name (graph node id)
    kind: str           # "mutex" | "rlock" | "cond"
    module: str
    cls: str | None
    attr: str
    relpath: str
    line: int
    assoc: str | None = None   # for conditions: key of the wrapped lock
    assoc_expr: object = None  # AST of the ctor's lock arg, pre-resolution


@dataclass
class FuncInfo:
    qual: str           # "module:Class.meth" / "module:func"
    module: str
    cls: str | None
    name: str
    node: object        # ast.FunctionDef | ast.AsyncFunctionDef
    ctx: object         # engine.FileContext
    nested: dict[str, "FuncInfo"] = field(default_factory=dict)


@dataclass
class ClassInfo:
    name: str
    module: str
    bases: tuple[str, ...]
    methods: dict[str, FuncInfo] = field(default_factory=dict)


def _is_container_value(value) -> bool:
    """True when an assigned value is (or contains at top level) a
    container literal / ctor — ``{}``, ``[None] * n``, ``deque()``,
    a comprehension — so mutator-method calls on the attribute count
    as writes in the guarded-field pass."""
    for sub in ast.walk(value):
        if isinstance(
            sub, (ast.Dict, ast.List, ast.Set, ast.DictComp, ast.ListComp,
                  ast.SetComp),
        ):
            return True
        if isinstance(sub, ast.Call):
            fn = sub.func
            name = (
                fn.id if isinstance(fn, ast.Name)
                else fn.attr if isinstance(fn, ast.Attribute)
                else None
            )
            if name in hints.CONTAINER_CTORS:
                return True
    return False


def module_name(relpath: str) -> str:
    mod = relpath[:-3] if relpath.endswith(".py") else relpath
    mod = mod.replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod or "__root__"


def _resolve_relative(module: str, level: int, target: str | None) -> str:
    """Resolve ``from ..x import y`` to a package-rooted module path."""
    if level == 0:
        return target or ""
    parts = module.split(".")
    # 'a.b.c' is a MODULE: level 1 = its package 'a.b'
    base = parts[: len(parts) - level] if len(parts) >= level else []
    if target:
        base = base + target.split(".")
    return ".".join(base)


class ProgramIndex:
    def __init__(self, contexts):
        # contexts: list of engine.FileContext
        self.contexts = {ctx.relpath: ctx for ctx in contexts}
        self.locks: dict[str, LockDef] = {}
        self.attr_locks: dict[tuple[str, str], LockDef] = {}
        self.module_locks: dict[tuple[str, str], LockDef] = {}
        self.classes: dict[str, list[ClassInfo]] = {}
        self.subclasses: dict[str, set[str]] = {}
        self.funcs: dict[str, FuncInfo] = {}
        self.module_funcs: dict[tuple[str, str], FuncInfo] = {}
        self.methods_by_name: dict[str, list[FuncInfo]] = {}
        # per-module import maps
        self.stdlib_alias: dict[str, dict[str, str]] = {}   # mod -> alias -> std
        self.modalias: dict[str, dict[str, str]] = {}       # mod -> alias -> pkg mod
        self.from_funcs: dict[str, dict[str, tuple[str, str]]] = {}
        self.attr_types: dict[tuple[str, str], set[str]] = {}
        # guarded-field pass tables: every self.X assigned anywhere in a
        # class's own methods, and the (cls, attr) pairs whose value is
        # a container literal/ctor (the mutator-call write rule)
        self.class_attrs: dict[str, set[str]] = {}
        self.container_attrs: set[tuple[str, str]] = set()
        for ctx in contexts:
            self._scan_file(ctx)
        self._link_hierarchy()
        self._infer_attr_types()
        self._collect_class_attrs()
        self._resolve_cond_assocs()

    # ------------------------------------------------------------- scan

    def _scan_file(self, ctx) -> None:
        mod = module_name(ctx.relpath)
        std: dict[str, str] = {}
        pkg: dict[str, str] = {}
        ffuncs: dict[str, tuple[str, str]] = {}
        sync_aliases: set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    top = a.name.split(".")[0]
                    alias = a.asname or top
                    if top in _STDLIB_MODULES:
                        std[alias] = top
                    if a.name.endswith("libs.sync"):
                        sync_aliases.add(a.asname or a.name)
            elif isinstance(node, ast.ImportFrom):
                target = _resolve_relative(mod, node.level, node.module)
                for a in node.names:
                    alias = a.asname or a.name
                    if a.name == "sync":
                        sync_aliases.add(alias)
                    full = f"{target}.{a.name}" if target else a.name
                    if a.name[:1].islower():
                        # imported module (``from ..libs import metrics``)
                        # or function (``from .engine import lint_root``)
                        pkg[alias] = full
                        ffuncs[alias] = (target, a.name)
                    if node.level == 0 and node.module in _STDLIB_MODULES:
                        std.setdefault(alias, node.module)
        self.stdlib_alias[mod] = std
        self.modalias[mod] = pkg
        self.from_funcs[mod] = ffuncs

        for stmt in ctx.tree.body:
            if isinstance(stmt, ast.ClassDef):
                self._scan_class(ctx, mod, stmt, sync_aliases)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._add_func(ctx, mod, None, stmt)
            else:
                self._scan_lock_assign(ctx, mod, None, stmt, sync_aliases)

    def _scan_class(self, ctx, mod, cnode, sync_aliases) -> None:
        bases = tuple(
            b.id if isinstance(b, ast.Name) else b.attr
            for b in cnode.bases
            if isinstance(b, (ast.Name, ast.Attribute))
        )
        ci = ClassInfo(cnode.name, mod, bases)
        self.classes.setdefault(cnode.name, []).append(ci)
        for stmt in cnode.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fi = self._add_func(ctx, mod, cnode.name, stmt)
                ci.methods[stmt.name] = fi
                for sub in ast.walk(stmt):
                    if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                        self._scan_lock_assign(
                            ctx, mod, cnode.name, sub, sync_aliases
                        )

    def _add_func(self, ctx, mod, cls, node) -> FuncInfo:
        qual = f"{mod}:{cls}.{node.name}" if cls else f"{mod}:{node.name}"
        fi = FuncInfo(qual, mod, cls, node.name, node, ctx)
        self.funcs[qual] = fi
        if cls is None:
            self.module_funcs[(mod, node.name)] = fi
        else:
            self.methods_by_name.setdefault(node.name, []).append(fi)
        for stmt in node.body:
            self._add_nested(fi, stmt)
        return fi

    def _add_nested(self, parent: FuncInfo, stmt) -> None:
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{parent.qual}.<locals>.{sub.name}"
                fi = FuncInfo(
                    qual, parent.module, parent.cls, sub.name, sub, parent.ctx
                )
                self.funcs[qual] = fi
                parent.nested[sub.name] = fi

    def _scan_lock_assign(self, ctx, mod, cls, stmt, sync_aliases) -> None:
        if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
            return
        value = stmt.value
        if value is None:
            return
        call = None
        for sub in ast.walk(value):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and isinstance(sub.func.value, ast.Name)
                and sub.func.value.id in sync_aliases
                and sub.func.attr in _SYNC_PRIMS
            ):
                call = sub
                break
        if call is None:
            return
        prim = call.func.attr
        kind = {"Mutex": "mutex", "RLock": "rlock", "Condition": "cond"}[prim]
        targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
        cls_attr = var = None
        for t in targets:
            if (
                isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == "self"
            ):
                cls_attr = t.attr
                break
            if isinstance(t, ast.Name):
                var = t.id
                break
        name = None
        assoc_expr = None
        if kind == "cond":
            if call.args:
                assoc_expr = call.args[0]
            for kw in call.keywords:
                if kw.arg == "name" and isinstance(kw.value, ast.Constant):
                    name = kw.value.value
            if name is None and len(call.args) > 1 and isinstance(
                call.args[1], ast.Constant
            ):
                name = call.args[1].value
        elif call.args and isinstance(call.args[0], ast.Constant) and isinstance(
            call.args[0].value, str
        ):
            name = call.args[0].value
        attr = cls_attr or var or f"line{call.lineno}"
        if name:
            key = name
        else:
            key = f"{mod}.{cls}.{attr}" if cls else f"{mod}.{attr}"
        ld = LockDef(
            key=key, kind=kind, module=mod, cls=cls, attr=attr,
            relpath=ctx.relpath, line=call.lineno, assoc_expr=assoc_expr,
        )
        self.locks.setdefault(key, ld)
        if cls is not None and cls_attr is not None:
            self.attr_locks.setdefault((cls, cls_attr), ld)
        elif var is not None:
            self.module_locks.setdefault((mod, var), ld)

    # ------------------------------------------------------- hierarchy

    def _link_hierarchy(self) -> None:
        direct: dict[str, set[str]] = {}
        for name, infos in self.classes.items():
            for ci in infos:
                for b in ci.bases:
                    direct.setdefault(b, set()).add(name)
        # transitive closure
        def desc(name, seen):
            for child in direct.get(name, ()):
                if child not in seen:
                    seen.add(child)
                    desc(child, seen)
            return seen

        self.subclasses = {name: desc(name, set()) for name in self.classes}

    def mro(self, cls: str):
        """Class names up the (name-resolved) base chain, self first."""
        out, todo, seen = [], [cls], set()
        while todo:
            c = todo.pop(0)
            if c in seen or c not in self.classes:
                continue
            seen.add(c)
            out.append(c)
            for ci in self.classes[c]:
                todo.extend(ci.bases)
        return out

    def lock_for_attr(self, cls: str | None, attr: str) -> LockDef | None:
        if cls is None:
            return None
        for c in self.mro(cls):
            ld = self.attr_locks.get((c, attr))
            if ld is not None:
                return ld
        return None

    # ------------------------------------------------------- type table

    def _ctor_tokens(self, expr) -> set[str]:
        """Class / pseudo-type tokens constructed anywhere in ``expr``."""
        out: set[str] = set()
        for sub in ast.walk(expr):
            if not isinstance(sub, ast.Call):
                continue
            fn = sub.func
            if isinstance(fn, ast.Name):
                if fn.id in self.classes:
                    out.add(fn.id)
                else:
                    out.update(hints.RETURN_TYPE_HINTS.get(fn.id, ()))
            elif isinstance(fn, ast.Attribute):
                if isinstance(fn.value, ast.Name):
                    std = None
                    for m in self.stdlib_alias.values():
                        if fn.value.id in m:
                            std = m[fn.value.id]
                            break
                    pseudo = hints.PSEUDO_CONSTRUCTORS.get((std, fn.attr))
                    if pseudo:
                        out.add(pseudo)
                        continue
                if fn.attr in self.classes:
                    out.add(fn.attr)
                else:
                    out.update(hints.RETURN_TYPE_HINTS.get(fn.attr, ()))
        return out

    def _infer_attr_types(self) -> None:
        # pass A: direct constructor / hinted-param assignments to self.X
        for fi in list(self.funcs.values()):
            if fi.cls is None:
                continue
            for stmt in ast.walk(fi.node):
                if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                    continue
                value = stmt.value
                if value is None:
                    continue
                targets = (
                    stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
                )
                for t in targets:
                    if not (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        continue
                    toks = self._ctor_tokens(value)
                    if isinstance(value, ast.Name):
                        toks |= set(hints.RECEIVER_HINTS.get(value.id, ()))
                    # hints UNION with inference: a partial inference
                    # (the ternary's NopMempool arm) must not shadow the
                    # documented possibilities for the attribute name
                    toks |= set(hints.RECEIVER_HINTS.get(t.attr, ()))
                    if toks:
                        self.attr_types.setdefault(
                            (fi.cls, t.attr), set()
                        ).update(toks)
        # pass B: assignments through a typed local (rs.votes = HVS(...))
        for fi in list(self.funcs.values()):
            local = self.local_types(fi)
            for stmt in ast.walk(fi.node):
                if not isinstance(stmt, ast.Assign):
                    continue
                for t in stmt.targets:
                    if not (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id != "self"
                    ):
                        continue
                    toks = self._ctor_tokens(stmt.value)
                    if not toks:
                        continue
                    for base in local.get(t.value.id, ()):  # typed receivers
                        self.attr_types.setdefault(
                            (base, t.attr), set()
                        ).update(toks)

    def _collect_class_attrs(self) -> None:
        """Own-class attribute table for the guarded-field pass: every
        ``self.X`` assignment target in a class's methods, plus which of
        them are container-typed (dict/list/set/deque literals or
        ctors — the receivers whose mutator-method calls count as
        writes)."""
        for fi in self.funcs.values():
            if fi.cls is None:
                continue
            attrs = self.class_attrs.setdefault(fi.cls, set())
            for stmt in ast.walk(fi.node):
                value = None
                if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                    targets = (
                        stmt.targets
                        if isinstance(stmt, ast.Assign)
                        else [stmt.target]
                    )
                    value = stmt.value
                elif isinstance(stmt, ast.AugAssign):
                    targets = [stmt.target]
                else:
                    continue
                for t in targets:
                    if not (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        continue
                    attrs.add(t.attr)
                    if value is not None and _is_container_value(value):
                        self.container_attrs.add((fi.cls, t.attr))

    def local_types(self, fi: FuncInfo) -> dict[str, set[str]]:
        """Flow-insensitive local-variable type tokens for one function:
        constructor calls, self-attr loads, hinted params."""
        out: dict[str, set[str]] = {}
        args = fi.node.args
        for a in (
            list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        ):
            hint = hints.RECEIVER_HINTS.get(a.arg)
            if hint:
                out[a.arg] = set(hint)
        for stmt in ast.walk(fi.node):
            if not isinstance(stmt, ast.Assign):
                continue
            for t in stmt.targets:
                if not isinstance(t, ast.Name):
                    continue
                toks = self._ctor_tokens(stmt.value)
                v = stmt.value
                if (
                    isinstance(v, ast.Attribute)
                    and isinstance(v.value, ast.Name)
                    and v.value.id == "self"
                    and fi.cls is not None
                ):
                    for c in self.mro(fi.cls):
                        toks |= self.attr_types.get((c, v.attr), set())
                    if not toks:
                        toks |= set(hints.RECEIVER_HINTS.get(v.attr, ()))
                if not toks and isinstance(v, ast.Name):
                    toks |= out.get(v.id, set())
                if toks:
                    out.setdefault(t.id, set()).update(toks)
        return out

    # ------------------------------------------------------- conditions

    def _resolve_cond_assocs(self) -> None:
        for ld in self.locks.values():
            if ld.kind != "cond" or ld.assoc_expr is None:
                continue
            e = ld.assoc_expr
            target = None
            if (
                isinstance(e, ast.Attribute)
                and isinstance(e.value, ast.Name)
                and e.value.id == "self"
            ):
                target = self.lock_for_attr(ld.cls, e.attr)
            elif isinstance(e, ast.Name):
                target = self.module_locks.get((ld.module, e.id))
            if target is not None:
                ld.assoc = target.key

    # ------------------------------------------------------- resolution

    def expr_types(self, expr, fi: FuncInfo, local: dict) -> set[str]:
        """Possible type tokens of a receiver expression."""
        if isinstance(expr, ast.Name):
            if expr.id == "self" and fi.cls is not None:
                return {fi.cls}
            toks = set(local.get(expr.id, ()))
            if not toks:
                toks = set(hints.RECEIVER_HINTS.get(expr.id, ()))
            return toks
        if isinstance(expr, ast.Attribute):
            base = self.expr_types(expr.value, fi, local)
            toks: set[str] = set()
            for b in base:
                for c in self.mro(b) if b in self.classes else (b,):
                    toks |= self.attr_types.get((c, expr.attr), set())
            toks |= set(hints.RECEIVER_HINTS.get(expr.attr, ()))
            if not toks and hints.queueish(expr.attr):
                toks = {"@queue"}
            return toks
        if isinstance(expr, ast.Call):
            return self._ctor_tokens(expr)
        return set()

    def resolve_lock_expr(self, expr, fi: FuncInfo) -> LockDef | None:
        """``with <expr>:`` / ``<expr>.acquire()`` -> LockDef, else None."""
        if (
            isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"
        ):
            return self.lock_for_attr(fi.cls, expr.attr)
        if isinstance(expr, ast.Name):
            return self.module_locks.get((fi.module, expr.id))
        if isinstance(expr, ast.Attribute):
            # other_obj._mtx: resolve by receiver type
            base = self.expr_types(expr.value, fi, {})
            for b in base:
                ld = self.lock_for_attr(b, expr.attr)
                if ld is not None:
                    return ld
        if isinstance(expr, ast.Call):
            # with self._region(): a @contextmanager helper that yields
            # inside ``with <lock>:`` holds that lock for the caller's body
            for callee in self.resolve_call(expr, fi, {}):
                ld = self._lock_held_at_yield(callee)
                if ld is not None:
                    return ld
        return None

    def _lock_held_at_yield(self, fi: FuncInfo) -> LockDef | None:
        """The innermost lock a ``@contextmanager`` function holds
        lexically at its first ``yield`` under one, else None."""
        node = fi.node
        if not any(
            (d.id if isinstance(d, ast.Name) else getattr(d, "attr", None))
            == "contextmanager"
            for d in node.decorator_list
        ):
            return None

        def walk(n, held: LockDef | None) -> LockDef | None:
            if isinstance(n, (ast.With, ast.AsyncWith)):
                for item in n.items:
                    if not isinstance(item.context_expr, ast.Call):
                        held = (
                            self.resolve_lock_expr(item.context_expr, fi)
                            or held
                        )
                children = n.body
            elif isinstance(
                n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                return None
            elif isinstance(n, (ast.Yield, ast.YieldFrom)):
                return held
            else:
                children = ast.iter_child_nodes(n)
            for child in children:
                found = walk(child, held)
                if found is not None:
                    return found
            return None

        for stmt in node.body:
            found = walk(stmt, None)
            if found is not None:
                return found
        return None

    def all_methods(self, types: set[str]) -> list[FuncInfo]:
        """Every method on ``types`` and their subclasses — the model
        for ``getattr(obj, dynamic_name)(...)`` dispatch (LocalClient
        routing ABCI methods by request name)."""
        out: list[FuncInfo] = []
        seen: set[str] = set()
        for t in types:
            if t not in self.classes:
                continue
            candidates = set(self.mro(t)) | self.subclasses.get(t, set())
            for c in candidates:
                for ci in self.classes.get(c, ()):
                    for fi in ci.methods.values():
                        if fi.qual not in seen:
                            seen.add(fi.qual)
                            out.append(fi)
        return out

    def methods_named(self, types: set[str], name: str) -> list[FuncInfo]:
        """Methods ``name`` on any of ``types`` (up the MRO) plus
        overrides in their subclasses — dynamic dispatch over the part
        of the hierarchy the receiver could be."""
        out: list[FuncInfo] = []
        seen: set[str] = set()
        for t in types:
            if t not in self.classes:
                continue
            candidates = set(self.mro(t)) | self.subclasses.get(t, set())
            for c in candidates:
                for ci in self.classes.get(c, ()):
                    fi = ci.methods.get(name)
                    if fi is not None and fi.qual not in seen:
                        seen.add(fi.qual)
                        out.append(fi)
        return out

    def resolve_call(self, call, fi: FuncInfo, local: dict) -> list[FuncInfo]:
        """Candidate callees for a Call node (empty = unresolved)."""
        fn = call.func
        if isinstance(fn, ast.Name):
            if fn.id in fi.nested:
                return [fi.nested[fn.id]]
            mf = self.module_funcs.get((fi.module, fn.id))
            if mf is not None:
                return [mf]
            imp = self.from_funcs.get(fi.module, {}).get(fn.id)
            if imp is not None:
                mf = self.module_funcs.get(imp)
                if mf is not None:
                    return [mf]
            if fn.id in self.classes:  # constructor -> __init__
                return self.methods_named({fn.id}, "__init__")
            return []
        if not isinstance(fn, ast.Attribute):
            return []
        # module-attr call: libmetrics.node_metrics()
        if isinstance(fn.value, ast.Name):
            target_mod = self.modalias.get(fi.module, {}).get(fn.value.id)
            if target_mod is not None:
                mf = self.module_funcs.get((target_mod, fn.attr))
                if mf is not None:
                    return [mf]
        if fn.attr in ("acquire", "release", "locked"):
            return []
        recv_types = self.expr_types(fn.value, fi, local)
        out = self.methods_named(
            {t for t in recv_types if not t.startswith("@")}, fn.attr
        )
        if out:
            return out
        if fn.attr in self.classes:  # mod.ClassName(...) constructor
            return self.methods_named({fn.attr}, "__init__")
        # unique-name fallback, gated on project-distinctive names so
        # bare verbs (read/next/remove) never wire subsystems together
        if hints.distinctive(fn.attr):
            cands = self.methods_by_name.get(fn.attr, ())
            if 0 < len(cands) <= hints.UNIQUE_NAME_CAP:
                return list(cands)
        return []
