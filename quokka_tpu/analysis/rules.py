"""AST lint rules for the engine's hand-argued invariants.

Each rule is a function ``(module: ast.Module, path: str, rel: str) ->
List[Finding]`` registered in ``RULES``.  Rules are deliberately
heuristic-but-deterministic: they over-approximate (a flagged line that is
actually fine goes into ``baseline.json`` with a rationale) and never
under-approximate on the concrete failure modes that motivated them
(round-5 verdict: module-level pjit dispatch race, import-time listener
registration, private-API probe silently defaulting into the racy path).

Rule ids:
  QK001 module-level-jit        jit/pjit/shard_map objects built at import
  QK002 import-time-side-effect registrations/device queries/thread starts/
                                filesystem mutation at module scope
  QK003 private-api             jax._src / jax.core.* outside analysis/compat
  QK004 host-sync-in-jit        host round-trips + python control flow on
                                parameters inside functions reachable from
                                jitted entry points
  QK005 unlocked-shared-state   lock-owning classes/modules mutating their
                                shared containers without holding the lock
  QK006 swallowed-exception     except handlers whose body is only ``pass``
  QK007 bare-print              print(...) in library code outside CLI entry
                                points (route through quokka_tpu.obs.diag)
  QK008 global-config-mutation  mutation of process-global configuration
                                (jax.config.update, os.environ, config.py
                                module globals) — with the query service
                                many queries share one process, so a query
                                mutating globals corrupts its neighbors
  QK009 unbounded-io-timeout    network/socket/fsspec calls without an
                                explicit timeout — a wedged socket or
                                object-store request hangs a worker to the
                                stall timeout instead of failing fast into
                                the retry/recovery path
  QK010 adhoc-counter-dict      counter-shaped increments on plain dicts in
                                runtime code (``stats["hits"] += 1``) —
                                counters must go through the typed
                                obs.REGISTRY so the Prometheus exporter,
                                bench snapshots and /status see them
  QK011 device-read-outside-funnel  blocking host readbacks (np.asarray /
                                .item() / device_get / block_until_ready /
                                .tolist()) in any function under ops/,
                                executors/, runtime/, service/ that do not
                                go through obs.spans.device_read — every
                                wait for the device is a sync.<site> span
                                the query record counts, and the exchange
                                critical path never drains the device
                                pipeline; deliberate readbacks carry
                                baseline rationales
  QK012 raw-len-cache-key       jit-program cache keys built from raw
                                (un-bucketed) batch lengths (.padded_len /
                                .shape[0]) outside ops/sigkey.py — every
                                raw length in a key multiplies the compile
                                space per 2x rung; keys must derive through
                                sigkey (bucket_rows/batch_sig/aval_sig/
                                make_key) so warmup compiles stay counted
                                and canonical
  QK013 platform-gate           jax.default_backend()/config._platform()
                                probes and platform-string comparisons
                                outside ops/strategy.py + config.py — a
                                scattered platform gate is a kernel choice
                                the strategy matrix cannot see, calibrate,
                                or record, which is exactly how the bench
                                came to measure a path the target backend
                                never runs (VERDICT r5 #2)
  QK018 unledgered-device-alloc eager device allocations (jax.device_put,
                                jnp.* array constructors on non-traced
                                paths) in runtime/executors/streaming/
                                service code — residency created outside
                                the ledgered choke points (bridge + caches
                                + HBQ) is invisible to the memory ledger
                                (obs/memplane.py), so per-query footprints
                                and OOM forensics under-report exactly the
                                allocation that mattered
  QK019 adhoc-operator-tally    per-operator row/byte tallies grown by hand
                                in runtime/executors/streaming/service code
                                (``self.rows_in += ...``,
                                ``tally["bytes_out"] += ...``) — operator
                                cardinality accounting must go through the
                                opstats ledger (obs/opstats.py: OPSTATS
                                record paths or opstats.note()) so EXPLAIN
                                ANALYZE, skew detection and the persisted
                                cardinality profile see the same numbers;
                                operational state (bare ``rows``,
                                ``pending_rows``, build buffers) is not a
                                stat and is not flagged
  QK020 multi-program-chain     executor bodies dispatching a CHAIN of
                                single-expression jit programs per batch —
                                ``evaluate_predicate``/``evaluate_to_column``
                                inside a per-expression loop, or more than
                                two straight-line calls in one function.
                                Each call launches its own program over the
                                whole batch; a linear chain of them is
                                exactly what whole-stage fusion collapses
                                into ONE program (ops/stagefuse.py
                                FusedElementwise, ops/fuse.py builders).
                                Deliberate fallback/finalize paths baseline
                                with a rationale
  QK025 obs-lock-blocking-io    blocking I/O (``open``/``time.sleep``/
                                socket/``urlopen``) executed — directly or
                                through a reachable helper — while holding
                                an obs-plane ``*_lock``.  The registry lock
                                serializes every hot-path counter increment
                                and histogram observe; a file write or
                                sleep under it stalls every engine thread
                                at once.  Snapshot under the lock, do the
                                I/O outside (obs/progress.py
                                ``_profile_for`` is the pattern)
  QK027 adhoc-wall-timing       bare ``time.time()``/``time.perf_counter()``
                                deltas used for timing outside ``obs/``
                                — a hand-rolled timer is invisible
                                to the span aggregator (obs/spans.py), the
                                flight recorder and the query records;
                                durations route through obs.span()
                                (its ``dur``/``self_s`` after the block),
                                deliberate low-level sites baseline with a
                                rationale

Finding keys (``Finding.key``) are line-number-free — ``rule::relpath::
scope::snippet[::n]`` — so a baseline survives unrelated edits above the
flagged line and goes stale (reported, prunable) when the flagged code
itself changes.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from quokka_tpu.analysis.flow import FlowContext

_JIT_MAKERS = ("jit", "pjit", "shard_map")

_REGISTRATION_CALLS = (
    "register_event_listener",
    "register_event_duration_secs_listener",
    "ensure_registered",
)
_DEVICE_QUERY_CALLS = (
    "jax.devices",
    "jax.local_devices",
    "jax.device_count",
    "jax.local_device_count",
    "jax.default_backend",
)
_FS_MUTATION_CALLS = ("os.makedirs", "os.mkdir", "os.mkdirs")

_HOST_SYNC_CALLS = (
    "asarray",          # np.asarray(tracer) -> blocking d2h
    "block_until_ready",
    "device_get",
    "item",
    "tolist",
)
_HOST_SYNC_BASES = ("np", "numpy", "onp", "jax")
_SCALAR_CONVERSIONS = ("float", "int", "bool")


@dataclass
class Finding:
    rule: str
    name: str
    path: str       # absolute or as-given path (for printing)
    rel: str        # stable relative path (for baseline keys)
    line: int
    scope: str      # qualified enclosing scope, '<module>' at top level
    message: str
    snippet: str    # stripped source of the flagged line
    occurrence: int = 0  # disambiguates identical snippets in one scope

    def key(self) -> str:
        base = f"{self.rule}::{self.rel}::{self.scope}::{self.snippet}"
        return base if self.occurrence == 0 else f"{base}::{self.occurrence}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}: {self.rule} [{self.name}] "
                f"{self.message}  ({self.scope})")


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _snippet(src_lines: Sequence[str], line: int) -> str:
    if 1 <= line <= len(src_lines):
        return src_lines[line - 1].strip()[:120]
    return ""


def _mk(rule: str, name: str, path: str, rel: str, node: ast.AST, scope: str,
        message: str, src_lines: Sequence[str]) -> Finding:
    line = getattr(node, "lineno", 0)
    return Finding(rule, name, path, rel, line, scope, message,
                   _snippet(src_lines, line))


def _is_jit_maker(d: Optional[str]) -> bool:
    return d is not None and (d in _JIT_MAKERS
                              or d.rsplit(".", 1)[-1] in _JIT_MAKERS)


def _own_exprs(st: ast.stmt) -> List[ast.expr]:
    """Expressions evaluated BY this statement itself — excluding child
    statements (compound bodies are yielded separately by
    ``_module_scope_statements``, so walking them here would double-count)."""
    out: List[ast.expr] = []
    for field in ("value", "test", "iter", "exc", "msg", "cause"):
        v = getattr(st, field, None)
        if isinstance(v, ast.expr):
            out.append(v)
    for t in getattr(st, "targets", []) or []:
        out.append(t)
    tgt = getattr(st, "target", None)
    if isinstance(tgt, ast.expr):
        out.append(tgt)
    for item in getattr(st, "items", []) or []:  # with-statement items
        out.append(item.context_expr)
    return out


def _module_scope_statements(tree: ast.Module) -> Iterable[ast.stmt]:
    """Statements executed at import time: module body, descending into
    module-level if/try/with/for blocks (still import time) but NOT into
    function bodies.  Class bodies also run at import and are included."""
    stack: List[ast.stmt] = list(tree.body)
    while stack:
        st = stack.pop(0)
        yield st
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(st, ast.ClassDef):
            # class body executes at import; method bodies do not
            stack = [s for s in st.body
                     if not isinstance(s, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))] + stack
            continue
        extra: List[ast.stmt] = []
        for field in ("body", "orelse", "finalbody"):
            extra.extend(getattr(st, field, []) or [])
        for h in getattr(st, "handlers", []) or []:
            extra.extend(h.body)
        stack = extra + stack


# ---------------------------------------------------------------------------
# QK001 — module-level jit objects
# ---------------------------------------------------------------------------


def check_module_level_jit(tree: ast.Module, path: str, rel: str,
                           src_lines: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    for st in _module_scope_statements(tree):
        if isinstance(st, (ast.Import, ast.ImportFrom)):
            continue
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a def's body runs later; but its DECORATORS run at import —
            # @jax.jit at module scope builds a module-level pjit object
            for dec in st.decorator_list:
                for sub in ast.walk(dec):
                    d = _dotted(sub)
                    if _is_jit_maker(d):
                        out.append(_mk(
                            "QK001", "module-level-jit", path, rel, dec,
                            "<module>",
                            f"decorator builds a module-level "
                            f"{d.rsplit('.', 1)[-1]} object for "
                            f"'{st.name}' at import time (jit-dispatch "
                            "race across engine threads; build lazily or "
                            "route via a traced/untraced dispatcher)",
                            src_lines))
            continue
        for expr in _own_exprs(st):
            for node in ast.walk(expr):
                if isinstance(node, ast.Lambda):
                    continue
                d = _dotted(node) if isinstance(node, (ast.Name,
                                                       ast.Attribute)) \
                    else None
                if _is_jit_maker(d):
                    out.append(_mk(
                        "QK001", "module-level-jit", path, rel, node,
                        "<module>",
                        f"'{d}' referenced at module scope: jit/pjit/"
                        "shard_map objects built at import time are shared "
                        "across engine threads and raced jit dispatch on "
                        "the 1-core CPU backend (build inside a function, "
                        "or dispatch via _in_trace-style routing)",
                        src_lines))
    return out


# ---------------------------------------------------------------------------
# QK002 — import-time side effects
# ---------------------------------------------------------------------------


def check_import_time_side_effects(tree: ast.Module, path: str, rel: str,
                                   src_lines: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    for st in _module_scope_statements(tree):
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef, ast.Import, ast.ImportFrom)):
            continue
        for node in [n for expr in _own_exprs(st) for n in ast.walk(expr)]:
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if d is None:
                continue
            tail = d.rsplit(".", 1)[-1]
            reason = None
            if tail in _REGISTRATION_CALLS or d == "atexit.register":
                reason = "listener/handler registration"
            elif d in _DEVICE_QUERY_CALLS:
                reason = "device/backend query (initializes the backend)"
            elif d in _FS_MUTATION_CALLS:
                reason = "filesystem mutation"
            elif tail == "Thread" or d.endswith("start_new_thread"):
                reason = "thread construction"
            elif tail == "start" and isinstance(node.func, ast.Attribute):
                reason = "thread/service start"
            if reason is not None:
                out.append(_mk(
                    "QK002", "import-time-side-effect", path, rel, node,
                    "<module>",
                    f"'{d}(...)' runs at import time ({reason}); import of "
                    "this module from a worker/trace context inherits the "
                    "side effect — make it lazy or baseline it with a "
                    "rationale",
                    src_lines))
    return out


# ---------------------------------------------------------------------------
# QK003 — private JAX API use
# ---------------------------------------------------------------------------

# the one module allowed to touch private surfaces (version-guarded shims)
PRIVATE_API_EXEMPT_SUFFIXES = ("analysis/compat.py",)


def check_private_api(tree: ast.Module, path: str, rel: str,
                      src_lines: Sequence[str]) -> List[Finding]:
    if rel.replace("\\", "/").endswith(PRIVATE_API_EXEMPT_SUFFIXES):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        d = None
        if isinstance(node, ast.Attribute):
            full = _dotted(node)
            if full and (full.startswith("jax._src")
                         or full.startswith("jax.core.")):
                d = full
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module.startswith(("jax._src", "jax.core")):
                d = node.module
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith(("jax._src", "jax.core")):
                    d = alias.name
        if d is not None:
            out.append(_mk(
                "QK003", "private-api", path, rel, node, _scope_of(tree, node),
                f"private JAX API '{d}' used directly; route through "
                "quokka_tpu.analysis.compat (fails loudly at import when a "
                "jax upgrade moves the symbol, instead of a defensive except "
                "silently changing behavior)",
                src_lines))
    return out


# ---------------------------------------------------------------------------
# QK004 — host syncs / python control flow in jit-reachable code
# ---------------------------------------------------------------------------


def _scope_of(tree: ast.Module, target: ast.AST) -> str:
    """Qualified name of the innermost function/class containing target."""
    best = "<module>"

    def walk(node: ast.AST, prefix: str):
        nonlocal best
        for child in ast.iter_child_nodes(node):
            name = None
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                name = (prefix + "." if prefix else "") + child.name
            if child is target or _contains(child, target):
                if name is not None:
                    best = name
                    walk(child, name)
                else:
                    walk(child, prefix)
                return

    walk(tree, "")
    return best


def _contains(node: ast.AST, target: ast.AST) -> bool:
    for sub in ast.walk(node):
        if sub is target:
            return True
    return False


def _collect_functions(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    """name -> def node, innermost-last (nested defs keyed by bare name too:
    call-graph edges here are resolved by simple name)."""
    fns: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns.setdefault(node.name, node)
    return fns


def _static_argnames(call: Optional[ast.Call]) -> Set[str]:
    """Literal static_argnames of a jit(...) / partial(jax.jit, ...) call."""
    out: Set[str] = set()
    if call is None:
        return out
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        v = kw.value
        elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
        for e in elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.add(e.value)
    return out


def _jit_entry_names(tree: ast.Module) -> Dict[str, Set[str]]:
    """Module functions handed to jit/pjit/shard_map anywhere in the file,
    mapped to their literal static_argnames (params excluded from the
    control-flow-on-tracers check)."""
    entries: Dict[str, Set[str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                for sub in ast.walk(dec):
                    if _is_jit_maker(_dotted(sub)):
                        statics = _static_argnames(
                            dec if isinstance(dec, ast.Call) else None)
                        entries.setdefault(node.name, set()).update(statics)
                        break
        if not isinstance(node, ast.Call):
            continue
        maker = _is_jit_maker(_dotted(node.func))
        statics: Set[str] = set()
        if maker and isinstance(node.func, ast.Attribute):
            statics = _static_argnames(node)
        if not maker and isinstance(node.func, ast.Call):
            # functools.partial(jax.jit, ...)(fn)
            inner = node.func
            if _dotted(inner.func) in ("functools.partial", "partial"):
                maker = any(_is_jit_maker(_dotted(a)) for a in inner.args)
                statics = _static_argnames(inner)
        if maker:
            statics |= _static_argnames(node if isinstance(node, ast.Call)
                                        else None)
            for a in node.args:
                if isinstance(a, ast.Name):
                    entries.setdefault(a.id, set()).update(statics)
    return entries


def _callees(fn: ast.FunctionDef, known: Dict[str, ast.FunctionDef]
             ) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d is None:
                continue
            tail = d.rsplit(".", 1)[-1]
            if tail in known:
                out.add(tail)
            # closures handed to lax control flow count as calls
            for a in node.args:
                if isinstance(a, ast.Name) and a.id in known:
                    out.add(a.id)
    return out


def _module_reachable(ctx: FlowContext, mt, seeds: Iterable[str]) -> Set[str]:
    """Call-graph closure restricted to `mt`'s own functions (a helper in
    another module cannot re-enter the old same-file scope, so dataflow
    precision only ever REMOVES findings relative to the name heuristic)."""
    seen: Set[str] = set()
    frontier = list(seeds)
    while frontier:
        fid = frontier.pop()
        if fid in seen:
            continue
        seen.add(fid)
        frontier.extend(
            c for c in ctx.calls.get(fid, ())
            if c not in seen and ctx.funcs[c].module == mt.name
        )
    return seen


def check_host_sync_in_jit(tree: ast.Module, path: str, rel: str,
                           src_lines: Sequence[str],
                           ctx: FlowContext) -> List[Finding]:
    mt = ctx.module_table(rel)
    if mt is None:
        return []
    entry_statics = _jit_entry_names(tree)
    seeds = [fi.fid for fi in mt.functions.values()
             if fi.name in entry_statics]

    out: List[Finding] = []
    for fid in sorted(_module_reachable(ctx, mt, seeds)):
        fi = ctx.funcs[fid]
        name = fi.name
        params = fi.params()
        params -= entry_statics.get(name, set())
        # interprocedurally static parameters (literal/metadata at EVERY
        # call site in the analyzed set) are trace-time config, not tracers
        params -= ctx.static_params(fid)
        for node in FlowContext._own_nodes(fi.node):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d is not None:
                    base, _, tail = d.rpartition(".")
                    if (tail in _HOST_SYNC_CALLS
                            and (base == "" or base in _HOST_SYNC_BASES
                                 or tail in ("block_until_ready", "item",
                                             "tolist"))):
                        out.append(_mk(
                            "QK004", "host-sync-in-jit", path, rel, node,
                            name,
                            f"'{d}(...)' inside '{name}' (reachable from a "
                            "jitted entry point) forces a host round-trip "
                            "or fails on tracers; hoist it out of the "
                            "traced region",
                            src_lines))
                    elif (d in _SCALAR_CONVERSIONS and len(node.args) == 1
                          and not isinstance(node.args[0], ast.Constant)):
                        out.append(_mk(
                            "QK004", "host-sync-in-jit", path, rel, node,
                            name,
                            f"'{d}(...)' scalar conversion inside '{name}' "
                            "(reachable from a jitted entry point) blocks "
                            "on device values and raises on tracers",
                            src_lines))
            elif isinstance(node, (ast.If, ast.While)):
                # names used only as the base of static-metadata attribute
                # access (arr.dtype / arr.shape / arr.ndim) branch on trace-
                # time constants, not on tracer VALUES — not flagged
                static_bases = {
                    n.value.id for n in ast.walk(node.test)
                    if isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name)
                    and n.attr in ("dtype", "shape", "ndim", "size")}
                names_in_test = {n.id for n in ast.walk(node.test)
                                 if isinstance(n, ast.Name)}
                hit = (names_in_test - static_bases) & params
                if hit:
                    out.append(_mk(
                        "QK004", "host-sync-in-jit", path, rel, node, name,
                        f"python {'if' if isinstance(node, ast.If) else 'while'}"
                        f" on parameter(s) {sorted(hit)} of jit-reachable "
                        f"'{name}': control flow on tracers raises "
                        "ConcretizationTypeError (use lax.cond/where, or "
                        "mark the argument static)",
                        src_lines))
    return out


check_host_sync_in_jit._needs_flow = True


# ---------------------------------------------------------------------------
# QK005 — shared state mutated without the owning lock
# ---------------------------------------------------------------------------

_LOCK_FACTORIES = ("Lock", "RLock", "Condition", "Semaphore")
_MUTATORS = ("append", "add", "pop", "popitem", "clear", "update", "extend",
             "remove", "appendleft", "discard", "setdefault", "insert")


def _is_lock_value(value: ast.AST) -> bool:
    for node in ast.walk(value):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d and d.rsplit(".", 1)[-1] in _LOCK_FACTORIES:
                return True
    return False


def _is_container_value(value: ast.AST) -> bool:
    if isinstance(value, (ast.Dict, ast.Set, ast.List, ast.DictComp,
                          ast.SetComp, ast.ListComp)):
        return True
    if isinstance(value, ast.Call):
        d = _dotted(value.func)
        if d and d.rsplit(".", 1)[-1] in ("dict", "set", "list", "deque",
                                          "defaultdict", "OrderedDict",
                                          "Counter"):
            return True
    return False


def _self_attr(node: ast.AST) -> Optional[str]:
    if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _with_holds_lock(with_stack: List[ast.With], lock_names: Set[str],
                     owner: str) -> bool:
    for w in with_stack:
        for item in w.items:
            d = _dotted(item.context_expr)
            if d is None and isinstance(item.context_expr, ast.Call):
                d = _dotted(item.context_expr.func)
            if d is None:
                continue
            parts = d.split(".")
            if owner in parts[:1] and any(p in lock_names for p in parts):
                return True
            # e.g. with self._lock / with self._lock.acquire_timeout(...)
            if parts[0] == owner and len(parts) > 1 and parts[1] in lock_names:
                return True
    return False


def _check_scope_mutations(body: Iterable[ast.stmt], owner: str,
                           lock_names: Set[str], containers: Set[str],
                           scope: str, path: str, rel: str,
                           src_lines: Sequence[str]) -> List[Finding]:
    """Walk one function body tracking the with-statement stack; flag
    mutations of `owner.<container>` outside `with owner.<lock>`.  `owner`
    is 'self' for classes or the module-global sentinel '' for modules."""
    out: List[Finding] = []

    def attr_of(node: ast.AST) -> Optional[str]:
        if owner == "self":
            return _self_attr(node)
        if isinstance(node, ast.Name):
            return node.id
        return None

    def flag(node: ast.AST, target: str, verb: str):
        prefix = "self." if owner == "self" else ""
        out.append(_mk(
            "QK005", "unlocked-shared-state", path, rel, node, scope,
            f"{verb} on shared '{prefix}{target}' in '{scope}' without "
            f"holding the owning lock "
            f"({prefix}{'/'.join(sorted(lock_names))}) — racy against the "
            "exec/IO loops",
            src_lines))

    def scan_stmt(st: ast.stmt, held: bool):
        """Mutations performed by this statement itself (not children)."""
        if isinstance(st, (ast.Assign, ast.AugAssign)):
            tgts = st.targets if isinstance(st, ast.Assign) else [st.target]
            for t in tgts:
                if isinstance(t, ast.Subscript):
                    a = attr_of(t.value)
                    if a in containers and not held:
                        flag(st, a, "subscript assignment")
        elif isinstance(st, ast.Delete):
            for t in st.targets:
                if isinstance(t, ast.Subscript):
                    a = attr_of(t.value)
                    if a in containers and not held:
                        flag(st, a, "del")
        for expr in _own_exprs(st):
            for node in ast.walk(expr):
                if isinstance(node, ast.Call):
                    f = node.func
                    if isinstance(f, ast.Attribute) and f.attr in _MUTATORS:
                        a = attr_of(f.value)
                        if a in containers and not held:
                            flag(node, a, f"...{f.attr}()")

    def walk(stmts: Iterable[ast.stmt], withs: List[ast.With]):
        held = _with_holds_lock(withs, lock_names, owner) if owner == "self" \
            else _module_with_holds(withs, lock_names)
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs get their own pass if ever needed
            scan_stmt(st, held)
            if isinstance(st, (ast.With, ast.AsyncWith)):
                walk(st.body, withs + [st])
            else:
                for field in ("body", "orelse", "finalbody"):
                    sub = getattr(st, field, None)
                    if sub:
                        walk(sub, withs)
                for h in getattr(st, "handlers", []) or []:
                    walk(h.body, withs)

    walk(list(body), [])
    return out


def _module_with_holds(with_stack: List[ast.With],
                       lock_names: Set[str]) -> bool:
    for w in with_stack:
        for item in w.items:
            d = _dotted(item.context_expr)
            if d and d.split(".")[0] in lock_names:
                return True
    return False


def check_unlocked_shared_state(tree: ast.Module, path: str, rel: str,
                                src_lines: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    # -- class-level: classes whose __init__ assigns self.<lock> ------------
    for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
        init = next((m for m in cls.body
                     if isinstance(m, ast.FunctionDef)
                     and m.name == "__init__"), None)
        if init is None:
            continue
        locks: Set[str] = set()
        containers: Set[str] = set()
        for node in ast.walk(init):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    a = _self_attr(t)
                    if a is None:
                        continue
                    if _is_lock_value(node.value):
                        locks.add(a)
                    elif _is_container_value(node.value):
                        containers.add(a)
        if not locks or not containers:
            continue
        for m in cls.body:
            if not isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if m.name == "__init__":
                continue
            out.extend(_check_scope_mutations(
                m.body, "self", locks, containers,
                f"{cls.name}.{m.name}", path, rel, src_lines))
    # -- module-level: a module-global lock guarding module-global dicts ----
    mod_locks: Set[str] = set()
    mod_containers: Set[str] = set()
    for st in tree.body:
        if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                and isinstance(st.targets[0], ast.Name):
            nm = st.targets[0].id
            if _is_lock_value(st.value):
                mod_locks.add(nm)
            elif _is_container_value(st.value):
                mod_containers.add(nm)
    if mod_locks and mod_containers:
        for fn in tree.body:
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(_check_scope_mutations(
                    fn.body, "", mod_locks, mod_containers, fn.name,
                    path, rel, src_lines))
    return out


# ---------------------------------------------------------------------------
# QK006 — swallowed exceptions
# ---------------------------------------------------------------------------


def check_swallowed_exceptions(tree: ast.Module, path: str, rel: str,
                               src_lines: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if all(isinstance(s, ast.Pass) for s in node.body):
            if node.type is None:
                typ = "<bare>"
            elif isinstance(node.type, ast.Tuple):
                typ = "(" + ", ".join(
                    _dotted(e) or "?" for e in node.type.elts) + ")"
            else:
                typ = _dotted(node.type) or "?"
            out.append(_mk(
                "QK006", "swallowed-exception", path, rel, node,
                _scope_of(tree, node),
                f"'except {typ}: pass' swallows failures silently — log, "
                "narrow the type, re-raise, or baseline with a rationale "
                "(runtime loops that swallow errors wedge instead of "
                "failing)",
                src_lines))
    return out


# ---------------------------------------------------------------------------
# QK007 — bare print in library code
# ---------------------------------------------------------------------------

# CLI drivers whose job IS printing (argparse entry points)
BARE_PRINT_EXEMPT_SUFFIXES = ("analysis/lint.py",)
# functions that are process entry points: `main`-style CLI drivers
_BARE_PRINT_EXEMPT_FUNCS = ("main", "_main")


def check_bare_print(tree: ast.Module, path: str, rel: str,
                     src_lines: Sequence[str]) -> List[Finding]:
    """Library code must not print: stdout lines from a worker process are
    invisible (spawned children), interleave across processes, and carry no
    timestamp/ordering.  Diagnostics route through quokka_tpu.obs.diag()
    (stderr + a flight-recorder event) so they land in merged timelines.
    Exempt: CLI entry points (``main``/``_main`` functions and the lint
    driver itself)."""
    if rel.replace("\\", "/").endswith(BARE_PRINT_EXEMPT_SUFFIXES):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"):
            continue
        scope = _scope_of(tree, node)
        if scope.rsplit(".", 1)[-1] in _BARE_PRINT_EXEMPT_FUNCS:
            continue
        out.append(_mk(
            "QK007", "bare-print", path, rel, node, scope,
            "bare 'print(...)' in library code — route diagnostics through "
            "quokka_tpu.obs.diag() (stderr + flight-recorder event, visible "
            "in merged timelines) or baseline with a rationale",
            src_lines))
    return out


# ---------------------------------------------------------------------------
# QK008 — process-global config mutation
# ---------------------------------------------------------------------------

_ENV_MUTATOR_TAILS = ("pop", "update", "setdefault", "clear")
# module aliases under which quokka_tpu.config is imported in this codebase
_CONFIG_MODULE_NAMES = ("config", "qconfig")


def _is_environ(node: ast.AST) -> bool:
    d = _dotted(node)
    return d in ("os.environ", "environ")


def _exec_surface(ctx: FlowContext) -> Set[str]:
    """Functions reachable from the query-execution surface: the task
    dispatch handlers (``handle_*``), the shuffle push path, and every
    jitted entry.  Code OUTSIDE this closure runs pre-query (import-time
    setup, process bootstrap, CLI/soak drivers) where a process-global
    mutation has no concurrently-running neighbor to corrupt."""
    cached = getattr(ctx, "_qk_exec_surface", None)
    if cached is not None:
        return cached
    seeds: Set[str] = set()
    for mt in ctx.modules.values():
        jit_entries = _jit_entry_names(mt.tree)
        for fi in mt.functions.values():
            if (fi.name.startswith("handle_")
                    or fi.name in _PUSH_PATH_ENTRY_FUNCS
                    or fi.name in jit_entries):
                seeds.add(fi.fid)
    surface = ctx.reachable(seeds)
    ctx._qk_exec_surface = surface
    return surface


def check_global_config_mutation(tree: ast.Module, path: str, rel: str,
                                 src_lines: Sequence[str],
                                 ctx: FlowContext) -> List[Finding]:
    """With the query service, many queries share one process: jax.config,
    quokka_tpu.config module globals and os.environ are PROCESS-global, so
    code reachable inside query execution mutating them corrupts every
    concurrently-running neighbor (dtype regime flips mid-pipeline, kernel
    strategy changes between a build and its probe, ...).  Only mutations
    inside functions reachable from the execution surface (task handlers,
    push path, jit entries — see ``_exec_surface``) are flagged: import-time
    setup, spawned-worker bootstrap and soak drivers are pre-query by
    construction, which the old name-heuristic could not see and baselined
    one rationale at a time."""
    mt = ctx.module_table(rel)
    if mt is None:
        return []
    surface = _exec_surface(ctx)
    owner: Dict[int, object] = {}
    for fi in mt.functions.values():
        for n in FlowContext._own_nodes(fi.node):
            owner[id(n)] = fi

    def gated(node: ast.AST) -> bool:
        fi = owner.get(id(node))
        return fi is not None and fi.fid in surface

    out: List[Finding] = []

    def flag(node: ast.AST, what: str):
        if not gated(node):
            return
        out.append(_mk(
            "QK008", "global-config-mutation", path, rel, node,
            _scope_of(tree, node),
            f"{what} mutates process-global configuration; with the query "
            "service a query doing this mid-flight corrupts its "
            "concurrently-running neighbors — move it to process startup "
            "(pre-service), thread it per-query, or baseline with a "
            "rationale",
            src_lines))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d is None:
                continue
            parts = d.split(".")
            if parts[-2:] == ["config", "update"] and parts[0] != "self":
                flag(node, f"'{d}(...)' (jax.config.update)")
            elif d in ("os.putenv", "os.unsetenv"):
                flag(node, f"'{d}(...)'")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _ENV_MUTATOR_TAILS
                  and _is_environ(node.func.value)):
                flag(node, f"'{d}(...)' (os.environ mutation)")
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            tgts = (node.targets if isinstance(node, ast.Assign)
                    else [node.target])
            for t in tgts:
                if isinstance(t, ast.Subscript) and _is_environ(t.value):
                    flag(node, "subscript assignment to os.environ")
                elif (isinstance(t, ast.Attribute)
                      and isinstance(t.value, ast.Name)
                      and t.value.id in _CONFIG_MODULE_NAMES):
                    flag(node,
                         f"assignment to '{t.value.id}.{t.attr}' "
                         "(config-module global)")
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                if isinstance(t, ast.Subscript) and _is_environ(t.value):
                    flag(node, "del on os.environ")
    return out


check_global_config_mutation._needs_flow = True


# ---------------------------------------------------------------------------
# QK009 — network/socket/fsspec IO without an explicit timeout
# ---------------------------------------------------------------------------

# dotted-call tails that open a network connection and accept a timeout
_NET_CALLS_NEED_TIMEOUT = ("create_connection",)
# fsspec AbstractFileSystem methods that perform remote IO; flagged when
# called on an fs-named receiver (`fs`, `self._fs`, ...), since the bound-
# filesystem idiom `fs = fsspec...; fs.open(...)` never spells "fsspec."
_FS_METHODS = ("open", "cat_file", "pipe_file", "mv", "copy", "rm", "glob",
               "exists", "makedirs", "info", "ls", "get", "put")


def check_unbounded_io(tree: ast.Module, path: str, rel: str,
                       src_lines: Sequence[str]) -> List[Finding]:
    """Runtime code must never block unboundedly on network/remote IO: a
    wedged socket or object-store request otherwise hangs a worker until
    the coordinator's stall timeout instead of failing fast into the
    retry/backoff/recovery path the chaos plane exercises.  Flags:

    - ``socket.create_connection(...)`` with neither a ``timeout=`` kwarg
      nor a positional timeout;
    - explicit ``.settimeout(None)`` (unbounded by declaration);
    - any ``fsspec.*`` call, and any ``_FS_METHODS`` call on an fs-named
      receiver (``fs.open``, ``self._fs.mv``, ...), without a ``timeout=``
      kwarg — fsspec has no portable timeout parameter, so every site is
      flagged and the deliberate ones carry baseline rationales (bounded
      by caller-side deadlines/retries/watchdogs instead).
    """
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None:
            continue
        tail = d.rsplit(".", 1)[-1]
        # timeout=None is the unbounded pattern itself, not a bound
        has_timeout_kw = any(
            kw.arg == "timeout"
            and not (isinstance(kw.value, ast.Constant)
                     and kw.value.value is None)
            for kw in node.keywords)
        if tail in _NET_CALLS_NEED_TIMEOUT:
            if not has_timeout_kw and len(node.args) < 2:
                out.append(_mk(
                    "QK009", "unbounded-io-timeout", path, rel, node,
                    _scope_of(tree, node),
                    f"'{d}(...)' without an explicit timeout blocks forever "
                    "on a wedged peer — pass timeout= so the call fails "
                    "fast into the retry/recovery path",
                    src_lines))
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr == "settimeout"
              and len(node.args) == 1
              and isinstance(node.args[0], ast.Constant)
              and node.args[0].value is None):
            out.append(_mk(
                "QK009", "unbounded-io-timeout", path, rel, node,
                _scope_of(tree, node),
                "'settimeout(None)' makes the socket block unboundedly — "
                "use a finite timeout, or baseline with the rationale for "
                "why this wait is legitimately unbounded",
                src_lines))
        elif d.startswith("fsspec.") and not has_timeout_kw:
            out.append(_mk(
                "QK009", "unbounded-io-timeout", path, rel, node,
                _scope_of(tree, node),
                f"'{d}(...)' (remote filesystem IO) has no timeout — bound "
                "it with a caller-side deadline/retry and baseline with "
                "that rationale",
                src_lines))
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in _FS_METHODS
              and not has_timeout_kw):
            recv = _dotted(node.func.value)
            base = recv.rsplit(".", 1)[-1] if recv else ""
            if base == "fs" or base.endswith("_fs"):
                out.append(_mk(
                    "QK009", "unbounded-io-timeout", path, rel, node,
                    _scope_of(tree, node),
                    f"'{d}(...)' (bound-filesystem remote IO) has no "
                    "timeout — bound it with a caller-side deadline/retry "
                    "and baseline with that rationale",
                    src_lines))
    return out


# ---------------------------------------------------------------------------
# QK010 — ad-hoc counter dicts in runtime code
# ---------------------------------------------------------------------------

# the typed Registry itself (and its exporter) legitimately manipulate raw
# count stores; everything else routes through it
ADHOC_COUNTER_EXEMPT_PREFIXES = ("quokka_tpu/obs/",)
# receiver names that mark a dict as a metrics store
_COUNTERISH_TOKENS = ("counter", "metric", "stat", "count", "hit", "miss")


def _counterish(name: Optional[str]) -> bool:
    if not name:
        return False
    low = name.lower()
    return any(tok in low for tok in _COUNTERISH_TOKENS)


def _sub_base_name(node: ast.AST) -> Optional[str]:
    """The base identifier of a subscript target: ``stats`` for
    ``stats[k]``, ``_hits`` for ``self._hits[k]``, dotted tail otherwise."""
    if not isinstance(node, ast.Subscript):
        return None
    base = node.value
    d = _dotted(base)
    if d is not None:
        return d.rsplit(".", 1)[-1]
    return None


def check_adhoc_counter_dict(tree: ast.Module, path: str, rel: str,
                             src_lines: Sequence[str]) -> List[Finding]:
    """Runtime code must not grow hand-rolled counter dicts: they are
    invisible to the Prometheus exporter (obs/export.py), to bench's
    counter snapshot and to /status, they race without the Registry lock,
    and every one eventually grows its own flush/reset idiom.  Flags the
    two counter-increment shapes on counter-named subscript bases:

    - ``stats["hits"] += n`` (AugAssign-Add on a subscript);
    - ``stats[k] = stats.get(k, 0) + n`` (read-modify-write via .get).

    The typed Registry (quokka_tpu/obs/metrics.py) is exempt — it is what
    the rule points at.  Pre-existing stores carry baseline rationales.
    """
    if rel.replace("\\", "/").startswith(ADHOC_COUNTER_EXEMPT_PREFIXES):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        hit = None
        if (isinstance(node, ast.AugAssign)
                and isinstance(node.op, (ast.Add, ast.Sub))
                and isinstance(node.target, ast.Subscript)):
            base = _sub_base_name(node.target)
            if _counterish(base):
                op = "+=" if isinstance(node.op, ast.Add) else "-="
                hit = (node, base, f"'{base}[...] {op} ...'")
        elif (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Subscript)):
            base = _sub_base_name(node.targets[0])
            if _counterish(base):
                for sub in ast.walk(node.value):
                    d = (_dotted(sub.func.value)
                         if isinstance(sub, ast.Call)
                         and isinstance(sub.func, ast.Attribute) else None)
                    if (isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Attribute)
                            and sub.func.attr == "get"
                            and d is not None
                            and d.rsplit(".", 1)[-1] == base
                            and isinstance(node.value, ast.BinOp)
                            and isinstance(node.value.op, ast.Add)):
                        hit = (node, base,
                               f"'{base}[k] = {base}.get(k, ...) + ...'")
                        break
        if hit is not None:
            n, base, shape = hit
            out.append(_mk(
                "QK010", "adhoc-counter-dict", path, rel, n,
                _scope_of(tree, n),
                f"{shape} grows an ad-hoc counter dict — route it through "
                "the typed registry (quokka_tpu.obs.REGISTRY: "
                "Counter.inc() for monotone counts, Gauge.set() for "
                "up-and-down quantities) so the /metrics exporter, bench "
                "snapshots and stall reports see it, or baseline with a "
                "rationale",
                src_lines))
    return out


# ---------------------------------------------------------------------------
# QK011 — blocking device reads outside the funnel (obs/spans.device_read)
# ---------------------------------------------------------------------------

# Function names that ARE the shuffle push path: Engine.push, the partition-
# fn lowering (and the closures it builds), the range splitter and the
# multi-partition kernels (a seed set of the query-execution surface, QK008).
# _spill_one is deliberately NOT an entry: it is the background spill worker,
# whose whole job is an off-critical-path d2h.
_PUSH_PATH_ENTRY_FUNCS = (
    "push", "_partition_fn", "_range_split",
    "split_by_partition", "partition_ids",
)
# where a thread of the program serves a request: every function of these
# directories is in the rule's sight (and a fixture named qk011*)
_QK011_SCOPED_DIRS = ("quokka_tpu/ops/", "quokka_tpu/executors/",
                      "quokka_tpu/runtime/", "quokka_tpu/service/")
# the readback shapes an AST can see; int()/bool()/float() of a device
# value cannot be told from a host conversion here — jax's transfer guard
# on the chip finds those (PERF.md section 6, PR 36)
_DEVICE_READ_TAILS = ("asarray", "item", "tolist", "device_get",
                      "block_until_ready")


def check_device_read_outside_funnel(tree: ast.Module, path: str, rel: str,
                                     src_lines: Sequence[str]
                                     ) -> List[Finding]:
    """Every place a thread blocks until the device has produced something
    goes through ``obs.spans.device_read`` (a ``sync.<site>`` span: the ring,
    the trace and the query record's ``syncs`` / ``sync.wait`` /
    ``d2h_bytes`` see it).  Flags np.asarray / .item() / .tolist() /
    device_get / block_until_ready in any function under ops/, executors/,
    runtime/ and service/: a read beside the funnel is host time the record
    calls work, and on the shuffle push path it drains the queued device
    pipeline once per batch per edge.  A site left on purpose (a path no
    served request reaches; ``np.asarray`` of what is already a host array)
    carries a baseline rationale."""
    r = rel.replace("\\", "/")
    base = r.rsplit("/", 1)[-1]
    if not (any(d in r for d in _QK011_SCOPED_DIRS)
            or base.startswith("qk011")):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None:
            # chained-call receivers (x.sum().item()) defeat _dotted; the
            # attribute tail alone decides for the no-base shapes
            if not (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _DEVICE_READ_TAILS
                    and node.func.attr != "asarray"):
                continue
            d = f"...{node.func.attr}"
        else:
            mod, _, tail = d.rpartition(".")
            if tail not in _DEVICE_READ_TAILS:
                continue
            # jnp.asarray is an h2d upload, not a readback; np/numpy/bare
            # asarray (and any-receiver .item()/.tolist()/device_get/
            # block_until_ready) are the blocking shapes
            if tail == "asarray" and mod not in ("np", "numpy", "onp", ""):
                continue
        scope = _scope_of(tree, node)
        out.append(_mk(
            "QK011", "device-read-outside-funnel", path, rel, node, scope,
            f"'{d}(...)' inside '{scope}' blocks on a device->host read "
            "beside the funnel — go through obs.spans.device_read(site, "
            "value) so the read is a sync.<site> span the query record "
            "counts (and keep the shuffle push path free of reads: async "
            "counts / masked views / background spill), or baseline with a "
            "rationale",
            src_lines))
    return out


# ---------------------------------------------------------------------------
# QK012 — jit cache keys built from raw (un-bucketed) batch lengths
# ---------------------------------------------------------------------------

# the one module allowed to turn raw lengths into key material
_SIGKEY_EXEMPT_SUFFIX = "ops/sigkey.py"
# receivers that are program/kernel caches: .get()/subscript on these with
# a raw length inside the key is the flagged shape
_PROGRAM_CACHE_NAMES = ("PROGRAMS", "CACHE", "CACHES")


def _raw_len_in(node: ast.AST) -> Optional[str]:
    """'.padded_len' / '.shape[0]' when the expression embeds a raw batch
    length, else None."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "padded_len":
            return ".padded_len"
        if (isinstance(sub, ast.Subscript)
                and isinstance(sub.value, ast.Attribute)
                and sub.value.attr == "shape"):
            return ".shape[...]"
    return None


def _cacheish(name: Optional[str]) -> bool:
    return name is not None and any(
        name.upper().endswith(s) for s in _PROGRAM_CACHE_NAMES)


def check_raw_len_cache_key(tree: ast.Module, path: str, rel: str,
                            src_lines: Sequence[str]) -> List[Finding]:
    """The compile plane's whole premise is ONE canonical key space: a jit
    cache key built from a raw batch length fragments per 2x rung and per
    call site, exactly the 11-15-compiles-per-query warmup join queries
    once paid.  Flags, outside ops/sigkey.py: (a) sig/key-named tuples
    embedding .padded_len or .shape[...], (b) .get()/subscript access on
    *_PROGRAMS/*_CACHE receivers whose key embeds one.  Canonical lengths
    come from sigkey.bucket_rows/batch_sig/aval_sig/make_key."""
    if rel.replace("\\", "/").endswith(_SIGKEY_EXEMPT_SUFFIX):
        return []
    out: List[Finding] = []

    def _flag(node: ast.AST, what: str, shape: str) -> None:
        out.append(_mk(
            "QK012", "raw-len-cache-key", path, rel, node,
            _scope_of(tree, node),
            f"{shape} builds a jit cache key from a raw (un-bucketed) "
            f"batch length ({what}) — every raw length fragments the "
            "compile space per 2x rung; derive key dimensions through "
            "quokka_tpu.ops.sigkey (bucket_rows / batch_sig / aval_sig / "
            "make_key), or baseline with a rationale",
            src_lines))

    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            tname = node.targets[0].id.lower()
            if (("sig" in tname or tname.endswith("key"))
                    and isinstance(node.value, ast.Tuple)):
                what = _raw_len_in(node.value)
                if what is not None:
                    _flag(node, what, f"'{node.targets[0].id} = (...)'")
            # subscript-store into a program cache: _CACHE[(... len ...)] = fn
            continue
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr == "get":
                recv = _dotted(node.func.value)
                if _cacheish(recv) and node.args:
                    what = _raw_len_in(node.args[0])
                    if what is not None:
                        _flag(node, what, f"'{recv}.get(...)'")
            continue
        if isinstance(node, ast.Subscript):
            recv = _dotted(node.value)
            if _cacheish(recv):
                what = _raw_len_in(node.slice)
                if what is not None:
                    _flag(node, what, f"'{recv}[...]'")
    return out


# ---------------------------------------------------------------------------
# QK013 — platform probes / platform-string gates outside the strategy matrix
# ---------------------------------------------------------------------------

# the two modules allowed to ask "what backend am I on": the strategy matrix
# (which turns the answer into a calibrated, recorded kernel choice) and
# config.py (its delegates + dtype policy)
_PLATFORM_EXEMPT_SUFFIXES = ("ops/strategy.py", "/config.py")
_PLATFORM_LITERALS = {"cpu", "gpu", "tpu", "cuda", "rocm"}
_PLATFORM_PROBE_CALLS = ("default_backend", "_platform")


def check_platform_gate(tree: ast.Module, path: str, rel: str,
                        src_lines: Sequence[str]) -> List[Finding]:
    """Flags, outside ops/strategy.py + config.py: (a) direct backend
    probes (``jax.default_backend()``, ``config._platform()``), (b)
    comparisons of a platform/backend-named expression against a platform
    string literal.  Kernel choices keyed on the platform must route
    through the strategy matrix; non-strategy uses (cache namespacing)
    carry baseline rationales."""
    r = rel.replace("\\", "/")
    if r.endswith(_PLATFORM_EXEMPT_SUFFIXES) or r == "config.py":
        return []
    out: List[Finding] = []
    flagged: Set[int] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name is None:
            continue
        last = name.rsplit(".", 1)[-1]
        if last in _PLATFORM_PROBE_CALLS:
            flagged.add(id(node))
            out.append(_mk(
                "QK013", "platform-gate", path, rel, node,
                _scope_of(tree, node),
                f"backend probe '{name}(...)' outside the strategy matrix "
                "— per-backend kernel decisions belong in "
                "quokka_tpu.ops.strategy (choice()/calibrate(), recorded "
                "via note_used) so the bench can verify what actually ran; "
                "non-strategy uses baseline with a rationale",
                src_lines))
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Compare) and len(node.comparators) == 1):
            continue
        sides = (node.left, node.comparators[0])
        lit = next(
            (s for s in sides
             if isinstance(s, ast.Constant) and isinstance(s.value, str)
             and s.value.lower() in _PLATFORM_LITERALS), None)
        if lit is None:
            continue
        other = sides[0] if lit is sides[1] else sides[1]
        if any(id(x) in flagged for x in ast.walk(other)):
            continue  # the probe call inside is already its own finding
        mention = _dotted(other)
        if mention is None and isinstance(other, ast.Call):
            mention = _dotted(other.func)
        txt = (mention or "").lower()
        if "platform" in txt or "backend" in txt:
            out.append(_mk(
                "QK013", "platform-gate", path, rel, node,
                _scope_of(tree, node),
                f"platform-string gate ('{mention}' vs "
                f"{lit.value!r}) outside the strategy matrix — route the "
                "decision through quokka_tpu.ops.strategy.choice() or "
                "baseline with a rationale",
                src_lines))
    return out


# ---------------------------------------------------------------------------
# QK018 — eager device allocations outside the ledgered choke points
# ---------------------------------------------------------------------------

# where the rule applies: the code that creates device/host residency the
# memory ledger must see (obs/memplane.py).  ops/ is exempt — the bridge
# and kernels are themselves the ledgered helpers — as are tests.
_QK018_SCOPED_DIRS = ("quokka_tpu/runtime/", "quokka_tpu/executors/",
                      "quokka_tpu/streaming/", "quokka_tpu/service/")
_QK018_CONSTRUCTORS = {
    "array", "asarray", "zeros", "ones", "full", "arange", "empty",
    "linspace", "zeros_like", "ones_like", "full_like", "empty_like",
}
_QK018_JNP_BASES = ("jnp", "jax.numpy")


def _qk018_traced_functions(tree: ast.Module) -> List[ast.AST]:
    """Function nodes whose bodies trace under jit — decorated with a jit
    maker (directly or via functools.partial), or wrapped by a ``jit(fn)``
    call anywhere in the module.  ``jnp`` constructors there are lazy
    tracer ops the compiler fuses, not eager device allocations."""
    jit_wrapped: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_maker(_dotted(node.func)):
            for a in node.args[:1]:
                if isinstance(a, ast.Name):
                    jit_wrapped.add(a.id)
    out: List[ast.AST] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name in jit_wrapped:
            out.append(node)
            continue
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            d = _dotted(target) or ""
            if _is_jit_maker(d):
                out.append(node)
                break
            if (d.rsplit(".", 1)[-1] == "partial"
                    and isinstance(dec, ast.Call) and dec.args
                    and _is_jit_maker(_dotted(dec.args[0]))):
                out.append(node)
                break
    return out


def check_unledgered_device_alloc(tree: ast.Module, path: str, rel: str,
                                  src_lines: Sequence[str]) -> List[Finding]:
    """Flags eager device allocations — ``jax.device_put`` and ``jnp.*``
    array constructors on non-traced paths — in runtime/executors/
    streaming/service code.  Device residency must be created through the
    ledgered choke points (ops/bridge, BatchCache, ScanCache, HBQ) so the
    memory ledger (obs/memplane.py) accounts for it; a raw allocation here
    is bytes the per-query footprints, the OOM forensics bundle and
    measured admission never see.  Deliberate small allocations baseline
    with a rationale (shrink-only contract)."""
    r = rel.replace("\\", "/")
    base = r.rsplit("/", 1)[-1]
    if not (any(d in r for d in _QK018_SCOPED_DIRS)
            or base.startswith("qk018")):
        return []
    exempt: Set[int] = set()
    for fn in _qk018_traced_functions(tree):
        for sub in ast.walk(fn):
            exempt.add(id(sub))
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or id(node) in exempt:
            continue
        name = _dotted(node.func)
        if name is None:
            continue
        head, _, attr = name.rpartition(".")
        hit = None
        if attr == "device_put" and head in ("jax", ""):
            hit = f"'{name}(...)'"
        elif attr in _QK018_CONSTRUCTORS and head in _QK018_JNP_BASES:
            hit = f"array constructor '{name}(...)'"
        if hit is None:
            continue
        out.append(_mk(
            "QK018", "unledgered-device-alloc", path, rel, node,
            _scope_of(tree, node),
            f"eager device allocation {hit} outside the ledgered choke "
            "points — this residency is invisible to the memory ledger "
            "(obs/memplane.py): route it through the bridge/cache/HBQ "
            "helpers that LEDGER.track() it, or baseline with a rationale",
            src_lines))
    return out


# ---------------------------------------------------------------------------
# QK019 — ad-hoc per-operator row/byte tallies outside the opstats ledger
# ---------------------------------------------------------------------------

# where the rule applies: the code that moves operator rows/bytes the
# EXPLAIN ANALYZE ledger (obs/opstats.py) must see.  obs/ is exempt — the
# ledger and its exporter are what the rule points at.
_QK019_SCOPED_DIRS = ("quokka_tpu/runtime/", "quokka_tpu/executors/",
                      "quokka_tpu/streaming/", "quokka_tpu/service/")
_QK019_EXEMPT_PREFIXES = ("quokka_tpu/obs/",)
# the ledger's field vocabulary, matched EXACTLY (modulo leading
# underscores): bare ``rows``, ``_build_rows``, ``pending_rows`` and
# friends are operational state — buffers a channel drains — not
# statistics, and substring matching would drown the rule in them.
_QK019_STAT_NAMES = {
    "rows_in", "rows_out", "bytes_in", "bytes_out", "batches_in",
    "batches_out", "rows_seen", "bytes_seen", "rows_emitted",
    "rows_delivered", "total_rows", "total_bytes_in", "total_bytes_out",
    "dispatches", "padded_in", "rows_unknown",
}


def _qk019_stat_name(node: ast.AST) -> Optional[str]:
    """The stats-shaped identifier behind a tally target: an attribute
    name, a bare name, or a string-literal subscript key."""
    if isinstance(node, ast.Attribute):
        n = node.attr
    elif isinstance(node, ast.Name):
        n = node.id
    elif isinstance(node, ast.Subscript) \
            and isinstance(node.slice, ast.Constant) \
            and isinstance(node.slice.value, str):
        n = node.slice.value
    else:
        return None
    return n if n.lstrip("_") in _QK019_STAT_NAMES else None


def check_adhoc_operator_tally(tree: ast.Module, path: str, rel: str,
                               src_lines: Sequence[str]) -> List[Finding]:
    """Flags hand-grown per-operator row/byte statistics — increments of
    stat-vocabulary names (``rows_in``, ``bytes_out``, ...) as attributes,
    locals, or string-keyed dict slots — in runtime/executors/streaming/
    service code.  Operator cardinality accounting must flow through the
    opstats ledger (obs/opstats.py) so EXPLAIN ANALYZE, the skew report,
    /status and the persisted cardinality profile all read ONE set of
    numbers; a private tally is a second bookkeeping that drifts from the
    one admission and calibration trust.  Deliberate exceptions baseline
    with a rationale (shrink-only contract)."""
    r = rel.replace("\\", "/")
    base = r.rsplit("/", 1)[-1]
    if r.startswith(_QK019_EXEMPT_PREFIXES):
        return []
    if not (any(d in r for d in _QK019_SCOPED_DIRS)
            or base.startswith("qk019")):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        hit = None
        if (isinstance(node, ast.AugAssign)
                and isinstance(node.op, (ast.Add, ast.Sub))):
            name = _qk019_stat_name(node.target)
            if name is not None:
                op = "+=" if isinstance(node.op, ast.Add) else "-="
                hit = (node, f"'... {name} {op} ...'")
        elif (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Subscript)):
            # t["rows_in"] = t.get("rows_in", 0) + n — the RMW spelling
            name = _qk019_stat_name(node.targets[0])
            if (name is not None and isinstance(node.value, ast.BinOp)
                    and isinstance(node.value.op, ast.Add)
                    and any(isinstance(s, ast.Call)
                            and isinstance(s.func, ast.Attribute)
                            and s.func.attr == "get"
                            for s in ast.walk(node.value))):
                hit = (node, f"'[{name!r}] = .get({name!r}, ...) + ...'")
        if hit is not None:
            n, shape = hit
            out.append(_mk(
                "QK019", "adhoc-operator-tally", path, rel, n,
                _scope_of(tree, n),
                f"{shape} grows an ad-hoc per-operator row/byte tally — "
                "route operator statistics through the opstats ledger "
                "(quokka_tpu.obs.opstats: the engine's scan/exec_in/"
                "exec_out record paths, or opstats.note() from inside an "
                "executor) so EXPLAIN ANALYZE, skew detection and the "
                "cardinality profile see it, or baseline with a rationale",
                src_lines))
    return out


# ---------------------------------------------------------------------------
# QK020 — per-batch chains of single-expression program dispatches
# ---------------------------------------------------------------------------

# where the rule applies: executor bodies — the code the optimizer's
# whole-stage fusion rewrites past.  ops/ is exempt: the fused builders
# themselves own the deliberate expression-at-a-time fallback paths.
_QK020_SCOPED_DIRS = ("quokka_tpu/executors/",)
# each of these launches ONE jit program over the whole batch
# (expr_compile compiles per expression); a chain of them per batch is
# exactly what ops/stagefuse.FusedElementwise / the ops/fuse.py builders
# collapse into a single program dispatch.
_QK020_DISPATCH_CALLS = ("evaluate_predicate", "evaluate_to_column")
# straight-line dispatches tolerated per function body before the chain
# counts as fusible (two ~= one predicate + one projection; a third says
# "pipeline of expression programs" rather than "a kernel and its guard")
_QK020_MAX_STRAIGHT = 2


def _qk020_dispatch_name(node: ast.Call) -> Optional[str]:
    """'evaluate_predicate' / 'evaluate_to_column' behind a call, matched
    bare or attribute-qualified (``expr_compile.evaluate_to_column``)."""
    d = _dotted(node.func)
    if d is None:
        return None
    last = d.rsplit(".", 1)[-1]
    return last if last in _QK020_DISPATCH_CALLS else None


def check_multi_program_chain(tree: ast.Module, path: str, rel: str,
                              src_lines: Sequence[str]) -> List[Finding]:
    """Flags executor bodies that dispatch a CHAIN of single-expression jit
    programs per batch: ``evaluate_predicate``/``evaluate_to_column`` calls
    inside a per-expression ``for``/``while`` loop (one program launch per
    expression per batch), or more than ``_QK020_MAX_STRAIGHT`` straight-line
    calls in one function.  Each call compiles and launches its own program
    over the whole padded batch; a linear chain of them re-reads every
    column from HBM per step — the exact dispatch shape whole-stage fusion
    (ops/stagefuse.py, ops/fuse.py) collapses into one program.  Deliberate
    CompileError fallbacks and once-per-query finalize paths baseline with
    a rationale (shrink-only contract)."""
    r = rel.replace("\\", "/")
    base = r.rsplit("/", 1)[-1]
    if not (any(d in r for d in _QK020_SCOPED_DIRS)
            or base.startswith("qk020")):
        return []
    # (owner function, call node, callee, inside-loop?) with the OWNER being
    # the innermost enclosing def — a whole-tree walk per function would
    # double-count calls under nested defs
    hits: List[Tuple[ast.AST, ast.Call, str, bool]] = []

    def visit(node: ast.AST, fn: Optional[ast.AST], loop_depth: int) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn, loop_depth = node, 0
        elif isinstance(node, (ast.For, ast.While)):
            loop_depth += 1
        elif isinstance(node, ast.Call) and fn is not None:
            nm = _qk020_dispatch_name(node)
            if nm is not None:
                hits.append((fn, node, nm, loop_depth > 0))
        for child in ast.iter_child_nodes(node):
            visit(child, fn, loop_depth)

    visit(tree, None, 0)
    out: List[Finding] = []
    straight_seen: Dict[int, int] = {}
    for fn, call, nm, looped in hits:
        if looped:
            out.append(_mk(
                "QK020", "multi-program-chain", path, rel, call,
                _scope_of(tree, call),
                f"'{nm}(...)' inside a loop dispatches one jit program per "
                "expression per batch — lower the chain through a fused "
                "single-program builder (ops/fuse.py Prepass idiom) or let "
                "stage fusion collapse it (ops/stagefuse.FusedElementwise), "
                "or baseline with a rationale",
                src_lines))
            continue
        n = straight_seen.get(id(fn), 0) + 1
        straight_seen[id(fn)] = n
        if n > _QK020_MAX_STRAIGHT:
            out.append(_mk(
                "QK020", "multi-program-chain", path, rel, call,
                _scope_of(tree, call),
                f"'{nm}(...)' is straight-line program dispatch #{n} in "
                "this body (> " f"{_QK020_MAX_STRAIGHT} per batch) — a "
                "fusible elementwise chain; fold it into one program "
                "(ops/stagefuse.FusedElementwise / ops/fuse.py builders) "
                "or baseline with a rationale",
                src_lines))
    return out


# ---------------------------------------------------------------------------
# QK025 — blocking I/O while holding an obs-plane lock
# ---------------------------------------------------------------------------

# where the rule applies: the observability plane.  Its locks (the metrics
# Registry's, the opstats ledger's, the history ring's, the alert engine's,
# the progress tracker's) sit on every hot-path counter increment; blocking
# under any of them stalls all engine threads at once.
_QK025_SCOPED_DIRS = ("quokka_tpu/obs/",)


def _qk025_blocking_name(node: ast.Call) -> Optional[str]:
    """The dotted name when `node` is a blocking I/O call: file opens,
    sleeps, socket construction/connection, urllib fetches.  Condition/
    event ``wait`` is deliberately NOT here — waiting on a condition under
    its own lock is the correct pattern, not a defect."""
    d = _dotted(node.func)
    if d is None:
        return None
    base, _, tail = d.rpartition(".")
    if tail == "open" and base in ("", "io", "os", "gzip"):
        return d
    if tail == "sleep" and base in ("", "time"):
        return d
    if tail == "urlopen":
        return d
    if base == "socket" or base.endswith(".socket") \
            or tail == "create_connection":
        return d
    return None


def _qk025_lock_name(item: ast.withitem) -> Optional[str]:
    """The dotted lock name when a with-item acquires an obs-style lock
    (last path segment ends in ``_lock``: ``self._lock``,
    ``_sampler_lock``, ``REGISTRY._lock``)."""
    d = _dotted(item.context_expr)
    if d is not None and d.rsplit(".", 1)[-1].endswith("_lock"):
        return d
    return None


def _qk025_body_calls(stmts: Sequence[ast.stmt]) -> Iterable[ast.Call]:
    """Every call executed WITHIN the with-body's dynamic extent: nested
    defs/lambdas are skipped — their bodies run later, after release."""
    stack: List[ast.AST] = list(stmts)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        if isinstance(n, ast.Call):
            yield n
        stack.extend(ast.iter_child_nodes(n))


def _qk025_reached_blocking(ctx: FlowContext, tgt) -> Optional[Tuple[str,
                                                                     str]]:
    """(blocking dotted name, owning qualname) for the first blocking call
    in `tgt`'s same-module call-graph closure, else None."""
    tmt = ctx.modules.get(tgt.module)
    if tmt is None:
        return None
    for fid in sorted(_module_reachable(ctx, tmt, [tgt.fid])):
        fi = ctx.funcs[fid]
        for node in FlowContext._own_nodes(fi.node):
            if isinstance(node, ast.Call):
                b = _qk025_blocking_name(node)
                if b is not None:
                    return b, fi.qualname
    return None


def check_obs_lock_blocking_io(tree: ast.Module, path: str, rel: str,
                               src_lines: Sequence[str],
                               ctx: FlowContext) -> List[Finding]:
    """Flags blocking I/O reachable while an obs-plane ``*_lock`` is held:
    ``open``/``time.sleep``/socket/``urlopen`` either directly inside a
    ``with <lock>:`` body, or inside a helper the body calls (same-module
    call-graph closure via the flow engine).  The registry lock is on the
    increment path of every operator in every engine thread — one /status
    scrape doing file I/O under it would stall the whole data plane.  The
    correct shape copies the figures under the lock and performs the I/O
    outside (``HistoryRing.record``, ``ProgressTracker._profile_for``).
    Nested defs under the lock are exempt: their bodies run after release."""
    r = rel.replace("\\", "/")
    base = r.rsplit("/", 1)[-1]
    if not (any(d in r for d in _QK025_SCOPED_DIRS)
            or base.startswith("qk025")):
        return []
    mt = ctx.module_table(rel)
    if mt is None:
        return []
    out: List[Finding] = []
    for fi in mt.functions.values():
        for node in FlowContext._own_nodes(fi.node):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            locks = [nm for nm in map(_qk025_lock_name, node.items)
                     if nm is not None]
            if not locks:
                continue
            for call in _qk025_body_calls(node.body):
                d = _qk025_blocking_name(call)
                if d is not None:
                    out.append(_mk(
                        "QK025", "obs-lock-blocking-io", path, rel, call,
                        _scope_of(tree, call),
                        f"'{d}(...)' runs while holding '{locks[0]}' — "
                        "blocking I/O under an obs lock stalls every "
                        "thread incrementing through it; copy the figures "
                        "under the lock and do the I/O outside, or "
                        "baseline with a rationale",
                        src_lines))
                    continue
                for tgt in ctx._call_targets(mt, fi, call):
                    hit = _qk025_reached_blocking(ctx, tgt)
                    if hit is not None:
                        blk, owner = hit
                        cd = _dotted(call.func) or call.func.__class__.__name__
                        out.append(_mk(
                            "QK025", "obs-lock-blocking-io", path, rel,
                            call, _scope_of(tree, call),
                            f"'{cd}(...)' called while holding "
                            f"'{locks[0]}' reaches blocking '{blk}(...)' "
                            f"(in '{owner}') — hoist the helper call out "
                            "of the critical section, or baseline with a "
                            "rationale",
                            src_lines))
                        break
    return out


check_obs_lock_blocking_io._needs_flow = True


# ---------------------------------------------------------------------------
# QK027 — ad-hoc wall timing outside the obs plane
# ---------------------------------------------------------------------------

# the clock calls whose subtraction means "someone hand-rolled a timer"
_QK027_TIMER_CALLS = ("time.time", "time.perf_counter", "perf_counter")
# the obs plane OWNS timing (spans, opstats, critpath, history);
# benchmarks/ keeps its own clock but lives outside quokka_tpu/ and is
# never scanned
_QK027_EXEMPT_DIRS = ("quokka_tpu/obs/",)


def _qk027_is_timer_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _dotted(node.func) in _QK027_TIMER_CALLS)


def _qk027_own_nodes(scope: ast.AST):
    """The scope's own statements/expressions, not descending into nested
    function bodies (their clock names are a different scope)."""
    stack = list(scope.body)
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


def check_adhoc_wall_timing(tree: ast.Module, path: str, rel: str,
                            src_lines: Sequence[str]) -> List[Finding]:
    """Flags bare wall-clock deltas used for timing outside the obs plane:
    a name assigned from ``time.time()``/``time.perf_counter()`` and later
    subtracted (``t1 - t0``, ``time.perf_counter() - t0``).  A hand-rolled
    timer is invisible to the span aggregator (``obs/spans.py``), the
    flight recorder and the bench breakdown — the measurement exists only
    in whatever local variable it landed in, which is exactly how the
    engine accumulated three private timing idioms before PR 13.  Route
    durations through ``obs.span()`` (the block's ``dur`` and ``self_s``
    are on the span afterwards; it also lands in the merged timeline, the
    query's record and a running profiler trace) or baseline deliberate
    low-level sites with a rationale.  Deadline arithmetic (``deadline - time.monotonic()``) is
    not flagged: both operands must be clock readings."""
    r = rel.replace("\\", "/")
    base = r.rsplit("/", 1)[-1]
    if not base.startswith("qk027"):
        if ("quokka_tpu/" not in r
                or any(d in r for d in _QK027_EXEMPT_DIRS)):
            return []
    out: List[Finding] = []
    scopes = [tree] + [n for n in ast.walk(tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
    for scope in scopes:
        own = list(_qk027_own_nodes(scope))
        clock_names: Set[str] = set()
        for n in own:
            if isinstance(n, ast.Assign) and _qk027_is_timer_call(n.value):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        clock_names.add(t.id)
        if not clock_names and not any(_qk027_is_timer_call(n)
                                       for n in own):
            continue

        def _clockish(x: ast.AST) -> bool:
            return (_qk027_is_timer_call(x)
                    or (isinstance(x, ast.Name) and x.id in clock_names))

        for n in own:
            if (isinstance(n, ast.BinOp) and isinstance(n.op, ast.Sub)
                    and _clockish(n.left) and _clockish(n.right)):
                out.append(_mk(
                    "QK027", "adhoc-wall-timing", path, rel, n,
                    _scope_of(tree, n),
                    "bare wall-clock delta — a hand-rolled timer is "
                    "invisible to the span aggregator, the flight "
                    "recorder and the bench breakdown; route the "
                    "duration through obs.span() (obs/spans.py: dur and "
                    "self_s after the block), or baseline with a rationale",
                    src_lines))
    return out


RULES = (
    check_module_level_jit,
    check_import_time_side_effects,
    check_private_api,
    check_host_sync_in_jit,
    check_unlocked_shared_state,
    check_swallowed_exceptions,
    check_bare_print,
    check_global_config_mutation,
    check_unbounded_io,
    check_adhoc_counter_dict,
    check_device_read_outside_funnel,
    check_raw_len_cache_key,
    check_platform_gate,
    check_unledgered_device_alloc,
    check_adhoc_operator_tally,
    check_multi_program_chain,
    check_obs_lock_blocking_io,
    check_adhoc_wall_timing,
)


def run_rules(source: str, path: str, rel: str,
              ctx: Optional[FlowContext] = None) -> List[Finding]:
    """ctx: the whole-file-set flow context built by ``lint.run_lint``;
    when absent (single-file callers, fixtures) a one-module context is
    built here so the flow-aware rules behave identically — just without
    cross-module knowledge."""
    if ctx is not None and ctx.module_table(rel) is not None:
        # reuse the context's tree: flow tables are keyed by node identity
        tree = ctx.module_table(rel).tree
    else:
        tree = ast.parse(source, filename=path)
        ctx = FlowContext()
        ctx.add_module(rel, tree)
        ctx.finalize()
    src_lines = source.splitlines()
    findings: List[Finding] = []
    for rule in RULES:
        if getattr(rule, "_needs_flow", False):
            findings.extend(rule(tree, path, rel, src_lines, ctx))
        else:
            findings.extend(rule(tree, path, rel, src_lines))
    findings.sort(key=lambda f: (f.line, f.rule))
    # occurrence-number duplicate (rule, scope, snippet) triples so baseline
    # keys are unique and stable in file order
    seen: Dict[Tuple[str, str, str], int] = {}
    for f in findings:
        k = (f.rule, f.scope, f.snippet)
        f.occurrence = seen.get(k, 0)
        seen[k] = f.occurrence + 1
    return findings
