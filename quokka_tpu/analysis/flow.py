"""qkflow: interprocedural dataflow engine for the lint rules.

The name-heuristic rules (QK004/QK008) matched *names*: any function
whose bare name appeared in a call was "reachable", every parameter was a
potential tracer, every config mutation was a finding.  This module gives
them actual program structure to stand on:

- **module-resolved symbol tables**: per-module import aliases
  (``import quokka_tpu.config as qconfig``), from-imports
  (``from .engine import push``), classes/methods, and *scoped* function
  qualnames (``Engine.push``, ``_partition_fn.<locals>.part``) — nested
  defs no longer collide on bare names;
- **a call graph** over the analyzed file set: plain-name calls resolve
  through the local scope chain, then module functions, then from-imports;
  ``self.m()`` resolves to the enclosing class's method; ``alias.f()``
  resolves through the import table; class-name calls resolve to
  ``__init__``; unresolvable attribute calls fall back to a *same-module*
  name over-approximation (never wider than the old heuristic);
- **reachability summaries** from configurable entry sets (jit entries,
  the push path, the ``handle_*`` task-dispatch surface);
- **all-call-sites static-argument propagation**: a parameter is *static*
  when every call site in the file set passes a literal, trace-time
  metadata (``x.dtype``/``.shape``/``.ndim``/``.size``), or a value that
  is itself static — branching on it is trace-time control flow, not a
  tracer sync (fixpoint over (function, param)).

The context is built once per lint invocation over the whole file set;
single-file invocations (fixtures) get a one-module context, so rules
behave identically in both settings — just with less cross-module
knowledge.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["FlowContext", "FuncInfo", "module_name_of", "build_context"]

# attribute tails that read trace-time metadata, not tracer values
STATIC_METADATA_ATTRS = ("dtype", "shape", "ndim", "size")

# functions whose result is a trace-time constant when every argument is
# static (so `jnp.issubdtype(dtype, ...)` stays static when `dtype` is)
_STATIC_PRESERVING_CALLS = {
    "issubdtype", "isinstance", "len", "result_type", "canonicalize_dtype",
}


def module_name_of(rel: str) -> str:
    """Dotted module name for a lint-relative path: files under the
    ``quokka_tpu`` tree get their real package path (so cross-module
    imports resolve); loose files (fixtures) get their stem."""
    r = rel.replace("\\", "/")
    if r.endswith(".py"):
        r = r[:-3]
    if r.endswith("/__init__"):
        r = r[: -len("/__init__")]
    if r.startswith("quokka_tpu/") or r == "quokka_tpu":
        return r.replace("/", ".")
    return r.rsplit("/", 1)[-1]


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class FuncInfo:
    """One function/method in the analyzed set."""

    __slots__ = ("fid", "module", "qualname", "name", "node", "cls",
                 "parent")

    def __init__(self, fid: str, module: str, qualname: str,
                 node: ast.AST, cls: Optional[str],
                 parent: Optional[str]):
        self.fid = fid              # "module:Qual.name" — globally unique
        self.module = module
        self.qualname = qualname    # "Engine.push", "f.<locals>.g"
        self.name = node.name       # bare name
        self.node = node
        self.cls = cls              # enclosing class qualname, if a method
        self.parent = parent        # fid of the enclosing function, if nested

    def params(self) -> Set[str]:
        a = self.node.args
        return {p.arg for p in a.args + a.kwonlyargs + a.posonlyargs
                if p.arg not in ("self", "cls")}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"FuncInfo({self.fid})"


class _ModuleTable:
    __slots__ = ("name", "rel", "tree", "import_alias", "from_imports",
                 "functions", "by_name", "classes", "class_methods")

    def __init__(self, name: str, rel: str, tree: ast.Module):
        self.name = name
        self.rel = rel
        self.tree = tree
        # "qconfig" -> "quokka_tpu.config"
        self.import_alias: Dict[str, str] = {}
        # local name -> (source module, original name)
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        self.functions: Dict[str, FuncInfo] = {}     # qualname -> info
        self.by_name: Dict[str, List[FuncInfo]] = {}  # bare name index
        self.classes: Dict[str, ast.ClassDef] = {}
        # class qualname -> {method bare name -> FuncInfo}
        self.class_methods: Dict[str, Dict[str, FuncInfo]] = {}


class FlowContext:
    """Symbol tables + call graph + reachability/static-arg summaries over
    one analyzed file set."""

    def __init__(self) -> None:
        self.modules: Dict[str, _ModuleTable] = {}
        self._rel_to_module: Dict[str, str] = {}
        self.funcs: Dict[str, FuncInfo] = {}
        self._by_node: Dict[int, FuncInfo] = {}
        self.calls: Dict[str, Set[str]] = {}
        # callee fid -> [(caller fid | None for module scope, Call node)]
        self.callsites: Dict[str, List[Tuple[Optional[str], ast.Call]]] = {}
        self._static_params: Optional[Dict[str, Set[str]]] = None

    # -- construction -------------------------------------------------------

    def add_module(self, rel: str, tree: ast.Module) -> None:
        name = module_name_of(rel)
        if name in self.modules:
            # two loose files with the same stem in one run (fixture dirs):
            # keep both, first owns the importable name
            name = f"{name}#{len(self.modules)}"
        mt = _ModuleTable(name, rel, tree)
        self.modules[name] = mt
        self._rel_to_module[rel] = name
        self._index_functions(mt)

    def finalize(self) -> None:
        """Resolve imports and the call graph after every module is added
        (`from pkg import submodule` vs `from pkg import name` is decided by
        whether the target module exists in the set, and cross-module call
        edges need the full symbol table)."""
        for mt in self.modules.values():
            self._index_imports(mt)
        for mt in self.modules.values():
            for fi in mt.functions.values():
                self.calls[fi.fid] = self._resolve_calls(mt, fi)
            self._resolve_module_scope_calls(mt)

    def _index_imports(self, mt: _ModuleTable) -> None:
        is_pkg = mt.rel.replace("\\", "/").endswith("__init__.py")
        parts = mt.name.split(".")
        for node in ast.walk(mt.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".")[0]
                    target = (alias.name if alias.asname
                              else alias.name.split(".")[0])
                    mt.import_alias[local] = target
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    # level 1 in a module = its package; in a package
                    # __init__ = the package itself; each extra level strips
                    # one more component
                    drop = node.level - (1 if is_pkg else 0)
                    pkg = ".".join(parts[: len(parts) - drop]) \
                        if drop < len(parts) else ""
                    src = f"{pkg}.{node.module}" if node.module and pkg \
                        else (node.module or pkg)
                else:
                    src = node.module or ""
                if not src:
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    local = alias.asname or alias.name
                    if f"{src}.{alias.name}" in self.modules or (
                            node.module is None):
                        # `from pkg import submodule` binds a MODULE name
                        mt.import_alias[local] = f"{src}.{alias.name}"
                    else:
                        mt.from_imports[local] = (src, alias.name)

    def _index_functions(self, mt: _ModuleTable) -> None:
        def visit(node: ast.AST, prefix: str, cls: Optional[str],
                  parent: Optional[str]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = prefix + child.name
                    fid = f"{mt.name}:{qual}"
                    fi = FuncInfo(fid, mt.name, qual, child, cls, parent)
                    mt.functions[qual] = fi
                    mt.by_name.setdefault(child.name, []).append(fi)
                    if cls is not None:
                        mt.class_methods.setdefault(cls, {})[child.name] = fi
                    self.funcs[fid] = fi
                    self._by_node[id(child)] = fi
                    visit(child, qual + ".<locals>.", None, fid)
                elif isinstance(child, ast.ClassDef):
                    cq = prefix + child.name
                    mt.classes[cq] = child
                    # nested classes keep the full qualname; methods of a
                    # class nested in a function belong to that function
                    visit(child, cq + ".", cq, parent)
                elif not isinstance(child, ast.Lambda):
                    visit(child, prefix, cls, parent)

        visit(mt.tree, "", None, None)

    # -- call resolution ----------------------------------------------------

    def _lookup_plain(self, mt: _ModuleTable, fi: Optional[FuncInfo],
                      name: str) -> List[FuncInfo]:
        """Scope-chain resolution of a bare name: enclosing functions'
        nested defs, then module functions, then from-imports, then
        classes (-> __init__)."""
        # nested defs visible on the lexical chain
        cur = fi
        while cur is not None:
            nested = mt.functions.get(cur.qualname + ".<locals>." + name)
            if nested is not None:
                return [nested]
            cur = self.funcs.get(cur.parent) if cur.parent else None
        top = mt.functions.get(name)
        if top is not None:
            return [top]
        if name in mt.from_imports:
            src_mod, orig = mt.from_imports[name]
            smt = self.modules.get(src_mod)
            if smt is not None:
                hit = smt.functions.get(orig)
                if hit is not None:
                    return [hit]
                init = smt.class_methods.get(orig, {}).get("__init__")
                if init is not None:
                    return [init]
            return []
        init = mt.class_methods.get(name, {}).get("__init__")
        if init is not None:
            return [init]
        return []

    def _lookup_dotted(self, mt: _ModuleTable, fi: Optional[FuncInfo],
                       d: str) -> List[FuncInfo]:
        base, _, tail = d.rpartition(".")
        if base in ("self", "cls") and fi is not None and fi.cls is not None:
            hit = mt.class_methods.get(fi.cls, {}).get(tail)
            if hit is not None:
                return [hit]
            # method not defined on this class in this file set (inherited):
            # over-approximate by same-module name match below
        if base in mt.import_alias:
            smt = self.modules.get(mt.import_alias[base])
            if smt is not None:
                hit = smt.functions.get(tail)
                if hit is not None:
                    return [hit]
                init = smt.class_methods.get(tail, {}).get("__init__")
                if init is not None:
                    return [init]
            return []  # call into a module we can't see: no edge
        if base in mt.from_imports:
            # Class imported by name: Class.method / instance conventions
            src_mod, orig = mt.from_imports[base]
            smt = self.modules.get(src_mod)
            if smt is not None:
                hit = smt.class_methods.get(orig, {}).get(tail)
                if hit is not None:
                    return [hit]
        if "." in base:
            # alias chain like pkg.mod.f with `import pkg.mod`
            root = base.split(".", 1)[0]
            if root in mt.import_alias:
                cand = mt.import_alias[root]
                full = base if base.startswith(cand) else base.replace(
                    root, cand, 1)
                smt = self.modules.get(full)
                if smt is not None:
                    hit = smt.functions.get(tail)
                    if hit is not None:
                        return [hit]
                return []
        # unknown receiver: SAME-MODULE name over-approximation (matches the
        # old heuristic's scope, so precision only ever removes edges)
        return list(mt.by_name.get(tail, []))

    def _call_targets(self, mt: _ModuleTable, fi: Optional[FuncInfo],
                      call: ast.Call) -> List[FuncInfo]:
        d = _dotted(call.func)
        if d is None:
            return []
        if "." not in d:
            return self._lookup_plain(mt, fi, d)
        return self._lookup_dotted(mt, fi, d)

    def _resolve_calls(self, mt: _ModuleTable, fi: FuncInfo) -> Set[str]:
        out: Set[str] = set()
        referenced: Set[str] = set()
        for node in self._own_nodes(fi.node):
            if isinstance(node, ast.Call):
                for tgt in self._call_targets(mt, fi, node):
                    out.add(tgt.fid)
                    self.callsites.setdefault(tgt.fid, []).append(
                        (fi.fid, node))
                # function references passed as arguments run as callbacks
                for a in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(a, ast.Name):
                        referenced.add(a.id)
            elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load):
                referenced.add(node.id)
        # a nested def whose name is referenced (returned, stored, passed)
        # escapes into the caller's dynamic extent — count the edge
        for name in referenced:
            for tgt in self._lookup_plain(mt, fi, name):
                out.add(tgt.fid)
        return out

    def _resolve_module_scope_calls(self, mt: _ModuleTable) -> None:
        """Call sites at module/class scope still count for static-argument
        propagation (a module-level `f(CONST)` is a static call site)."""
        for node in self._own_nodes(mt.tree):
            if isinstance(node, ast.Call):
                for tgt in self._call_targets(mt, None, node):
                    self.callsites.setdefault(tgt.fid, []).append(
                        (None, node))

    @staticmethod
    def _own_nodes(root: ast.AST) -> Iterable[ast.AST]:
        """Walk root WITHOUT descending into nested function bodies (their
        calls belong to the nested function's own summary)."""
        stack = list(ast.iter_child_nodes(root))
        while stack:
            n = stack.pop()
            yield n
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(n))

    # -- queries ------------------------------------------------------------

    def function_of_node(self, node: ast.AST) -> Optional[FuncInfo]:
        return self._by_node.get(id(node))

    def module_table(self, rel: str) -> Optional[_ModuleTable]:
        name = self._rel_to_module.get(rel, module_name_of(rel))
        return self.modules.get(name)

    def reachable(self, seeds: Iterable[str]) -> Set[str]:
        """Transitive closure over the call graph from seed fids."""
        seen: Set[str] = set()
        frontier = [s for s in seeds if s in self.funcs]
        while frontier:
            fid = frontier.pop()
            if fid in seen:
                continue
            seen.add(fid)
            frontier.extend(self.calls.get(fid, ()) - seen)
        return seen

    def funcs_named(self, pred) -> List[FuncInfo]:
        """All functions whose BARE name satisfies pred (callable or a
        collection of names)."""
        if not callable(pred):
            names = set(pred)
            pred = names.__contains__
        return [fi for fi in self.funcs.values() if pred(fi.name)]

    # -- static-argument propagation ----------------------------------------

    def static_params(self, fid: str) -> Set[str]:
        """Parameters of `fid` that are static at EVERY call site in the
        analyzed set (constants, trace-time metadata, or values that are
        themselves static parameters of the caller).  A function with no
        visible call sites has NO static params (conservative: it may be
        an entry point taking tracers)."""
        if self._static_params is None:
            self._static_params = self._compute_static_params()
        return self._static_params.get(fid, set())

    def _compute_static_params(self) -> Dict[str, Set[str]]:
        # optimistically assume every called-with-args param static, then
        # strike params until fixpoint (a param fed by a non-static arg, or
        # by a static-param-dependent arg whose source gets struck, falls)
        state: Dict[str, Set[str]] = {}
        sigs: Dict[str, Tuple[List[str], Dict[str, int]]] = {}
        for fid, fi in self.funcs.items():
            a = fi.node.args
            pos = [p.arg for p in a.posonlyargs + a.args]
            if pos and pos[0] in ("self", "cls"):
                pos = pos[1:]
            sigs[fid] = (pos, {p: i for i, p in enumerate(pos)})
            sites = self.callsites.get(fid, [])
            state[fid] = set(fi.params()) if sites else set()

        def arg_static(expr: ast.AST, caller: Optional[str]) -> bool:
            if isinstance(expr, ast.Constant):
                return True
            if isinstance(expr, ast.UnaryOp):
                return arg_static(expr.operand, caller)
            if (isinstance(expr, ast.Attribute)
                    and expr.attr in STATIC_METADATA_ATTRS):
                return True
            if isinstance(expr, ast.Name):
                if caller is not None and expr.id in state.get(caller, ()):
                    return True
                return False
            if isinstance(expr, ast.Call):
                d = _dotted(expr.func)
                tail = d.rsplit(".", 1)[-1] if d else ""
                return (tail in _STATIC_PRESERVING_CALLS
                        and all(arg_static(a, caller) for a in expr.args))
            return False

        changed = True
        while changed:
            changed = False
            for fid, fi in self.funcs.items():
                cur = state[fid]
                if not cur:
                    continue
                pos, idx = sigs[fid]
                keep = set(cur)
                for caller, call in self.callsites.get(fid, []):
                    if any(isinstance(a, ast.Starred) for a in call.args) \
                            or any(k.arg is None for k in call.keywords):
                        keep.clear()  # *args/**kwargs: every param tainted
                        break
                    bound_pos = min(len(call.args), len(pos))
                    for i in range(bound_pos):
                        p = pos[i]
                        if p in keep and not arg_static(call.args[i], caller):
                            keep.discard(p)
                    for kw in call.keywords:
                        if kw.arg in keep and not arg_static(kw.value, caller):
                            keep.discard(kw.arg)
                if keep != cur:
                    state[fid] = keep
                    changed = True
        return state


def build_context(files: Sequence[Tuple[str, ast.Module]]) -> FlowContext:
    """files: (lint-relative path, parsed tree) pairs."""
    ctx = FlowContext()
    for rel, tree in files:
        ctx.add_module(rel, tree)
    ctx.finalize()
    return ctx
