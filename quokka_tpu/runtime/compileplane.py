"""Compile plane: AOT program acquisition, executable persistence, and
plan-driven pre-warm.

The engine's static-shape discipline means a query shape compiles a finite
program set and then reuses it forever — but warmup still cost 3-6x
steady state: every fused program paid trace + lower +
compile-or-cache-load serialized with its first dispatch.  This module makes
compilation a first-class, front-loaded concern with three layers:

- **AOT acquisition** (``acquire``): a program cache miss compiles the
  program EXPLICITLY (``jit(...).lower(args).compile()``) instead of
  letting the first dispatch pay an implicit trace, and wraps the compiled
  executable with a jit fallback so an aval drift can never error.
- **cross-restart persistence**: compiled executables are serialized
  (``jax.experimental.serialize_executable``) into
  ``<cache>/aot/<backend fingerprint>/`` with the same checksummed framing
  the spill/checkpoint tier uses (runtime/integrity.py).  A restarted
  replica deserializes the executable directly — no trace, no lower, no
  XLA cache lookup.  Corrupt or foreign artifacts are quarantined and fall
  back to a fresh compile, never an error.
- **plan ledger + pre-warm**: every program a query uses is recorded under
  the query's plan fingerprint (``plans/<fp>.json``).  ``prewarm_plan``
  replays that ledger on a background pool at submit time (QueryService)
  or query start (one-shot path), so executables load while admission/scan
  run instead of serializing with the first dispatch.

Counters (obs.REGISTRY, exported via /metrics): ``compile.cache_hit`` (a
persisted executable answered a miss), ``compile.miss`` (a fresh backend
compile), ``compile.prewarm_hit`` (a dispatch found its program already
installed by pre-warm), plus per-query twins GC'd with the query namespace.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import queue
import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax

from quokka_tpu import config
from quokka_tpu.ops import sigkey
from quokka_tpu.runtime.errors import CorruptArtifactError
from quokka_tpu.runtime.integrity import frame, unframe

# process-wide program cache: key (a sigkey.make_key tuple) -> callable.
# Dispatch hot paths read this dict directly (one dict get per batch);
# acquire()/prewarm fill it.
PROGRAMS: Dict[Tuple, object] = {}

# v2: entries record the device assignment the program was compiled for
# (v1 artifacts were loaded across every local device and are ignored)
_ENTRY_VERSION = 2


def _enabled() -> bool:
    v = os.environ.get("QUOKKA_AOT_CACHE", "1").lower()
    return v not in ("0", "false", "no", "off")


# ---------------------------------------------------------------------------
# backend/topology fingerprint (lazy: reading device kind/count initializes
# the backend, which must not happen at import time)
# ---------------------------------------------------------------------------

_fp_lock = threading.Lock()
_fingerprint: Optional[str] = None


def backend_fingerprint() -> str:
    """Platform + device kind + device count + x64 mode + jax version +
    host uarch: serialized executables are valid only on the topology (and
    under the dtype policy — program keys do not carry it) that compiled
    them, so the artifact directory is namespaced by this — a foreign
    host/backend/jax is a cache MISS instead of a load error."""
    global _fingerprint
    with _fp_lock:
        if _fingerprint is not None:
            return _fingerprint
        devs = jax.devices()
        platform = jax.default_backend()
        raw = "|".join([
            platform, str(devs[0].device_kind), str(len(devs)),
            f"x64={config.x64_enabled()}",
            jax.__version__, config._host_fingerprint(),
        ])
        h = hashlib.sha256(raw.encode()).hexdigest()[:12]
        _fingerprint = f"{platform}-{len(devs)}x-{h}"
        return _fingerprint


def _root_dir() -> Optional[str]:
    if not _enabled():
        return None
    base = os.environ.get("QUOKKA_AOT_CACHE_DIR", "")
    return base or os.path.join(config.CACHE_ROOT, "aot")


def _aot_dir(create: bool = False) -> Optional[str]:
    base = _root_dir()
    if base is None:
        return None
    d = os.path.join(base, backend_fingerprint())
    if create:
        try:
            os.makedirs(d, exist_ok=True)
        except OSError:
            return None
    return d


def _plans_dir(create: bool = False) -> Optional[str]:
    base = _root_dir()
    if base is None:
        return None
    d = os.path.join(base, "plans")
    if create:
        try:
            os.makedirs(d, exist_ok=True)
        except OSError:
            return None
    return d


def key_hash(key: Tuple) -> str:
    """Stable filename for a program key (keys are tuples of builtins, so
    repr is deterministic across processes)."""
    return hashlib.sha256(repr(key).encode()).hexdigest()[:24]


# ---------------------------------------------------------------------------
# per-query attribution scope (the engine enters it around dispatch, same
# once-resolved discipline as kernels.shuffle_sync_scope)
# ---------------------------------------------------------------------------

_SCOPE = threading.local()


@contextlib.contextmanager
def query_scope(counters: Optional[Dict[str, object]], plan_fp: Optional[str]):
    """counters: {"cache_hit"/"miss"/"prewarm_hit": Counter} per-query twins
    (or None); plan_fp: the plan fingerprint program uses are recorded
    under."""
    prev = (getattr(_SCOPE, "counters", None), getattr(_SCOPE, "fp", None))
    _SCOPE.counters, _SCOPE.fp = counters, plan_fp
    try:
        yield
    finally:
        _SCOPE.counters, _SCOPE.fp = prev


def _count(event: str) -> None:
    from quokka_tpu import obs

    obs.REGISTRY.counter(f"compile.{event}").inc()
    c = getattr(_SCOPE, "counters", None)
    if c is not None:
        qc = c.get(event)
        if qc is not None:
            qc.inc()


# ---------------------------------------------------------------------------
# plan ledger: plan fingerprint -> set of program key hashes
# ---------------------------------------------------------------------------

_plan_lock = threading.Lock()
_PLAN_SIGS: Dict[str, set] = {}
# key-hash -> pickled key + entry (kept so prewarm can install by hash)
_KEY_BY_HASH: Dict[str, Tuple] = {}
# key hashes whose program is already resident: prewarm filters on this
# BEFORE touching disk, so per-query prewarm of an already-warm plan is a
# set lookup, not a re-deserialization of the whole executable set
_INSTALLED_HASHES: set = set()


def _describe(obj, depth: int = 0) -> str:
    """Deterministic structural description of a plan component (executor
    factories are functools.partials over executor classes, expressions,
    and plain data — never described by object repr, which embeds
    addresses)."""
    import functools

    if depth > 6:
        return "..."
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return repr(obj)
    if isinstance(obj, functools.partial):
        inner = [_describe(obj.func, depth + 1)]
        inner += [_describe(a, depth + 1) for a in obj.args]
        inner += [f"{k}={_describe(v, depth + 1)}"
                  for k, v in sorted(obj.keywords.items())]
        return f"partial({', '.join(inner)})"
    if isinstance(obj, type):
        return obj.__name__
    if isinstance(obj, (list, tuple)):
        return "[" + ",".join(_describe(x, depth + 1) for x in obj) + "]"
    if isinstance(obj, dict):
        return "{" + ",".join(
            f"{_describe(k, depth + 1)}:{_describe(v, depth + 1)}"
            for k, v in sorted(obj.items(), key=lambda kv: str(kv[0]))
        ) + "}"
    sql = getattr(obj, "sql", None)
    if callable(sql):
        try:
            return f"sql:{sql()}"
        except Exception:  # noqa: BLE001 — partial exprs still fingerprint
            return f"sql?:{type(obj).__name__}"
    if callable(obj):
        return getattr(obj, "__name__", type(obj).__name__)
    # dataclass-ish plan objects (AggPlan): stable field dump
    d = getattr(obj, "__dict__", None)
    if d:
        return type(obj).__name__ + _describe(d, depth + 1)
    return type(obj).__name__


def plan_fingerprint(graph) -> str:
    """Structural fingerprint of a lowered TaskGraph: executor shapes,
    expression text, and reader size classes (``size_hint`` bucketed to the
    canonical ladder) — everything that decides which programs the query
    will request, nothing that varies per run (query ids, paths, object
    addresses)."""
    parts: List[str] = []
    for aid in sorted(graph.actors):
        info = graph.actors[aid]
        desc = [str(aid), info.kind, str(info.channels)]
        if info.reader is not None:
            desc.append(type(info.reader).__name__)
            hint_fn = getattr(info.reader, "size_hint", None)
            if hint_fn is not None:
                try:
                    # bucket the byte hint: plans over same-scale data share
                    # a fingerprint; a 4x data change is a different shape
                    desc.append(str(sigkey.pow2_dim(max(1, int(hint_fn())))))
                except Exception:  # noqa: BLE001 — hintless readers still
                    desc.append("hint?")  # fingerprint structurally
        if info.executor_factory is not None:
            desc.append(_describe(info.executor_factory))
        if info.predicate is not None:
            desc.append(_describe(getattr(info.predicate, "expr", None)))
        if info.projection:
            desc.append(",".join(info.projection))
        parts.append("|".join(desc))
    raw = ";".join(parts)
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


# key tuple -> hash memo so the per-dispatch note costs a dict get, not a
# repr + sha256 (reads are GIL-atomic; writes take the plan lock)
_HASH_BY_KEY: Dict[Tuple, str] = {}


def note_program(key: Tuple, installed: bool = False) -> None:
    """Record a program use under the current query scope's plan.  Called
    on EVERY dispatch-path resolution — including in-memory hits, so a
    plan that reuses another plan's programs still records the full set —
    with a lock-free fast path once (key, plan) is known."""
    fp = getattr(_SCOPE, "fp", None)
    h = _HASH_BY_KEY.get(key)
    known = h is not None
    if known and not installed:
        s = _PLAN_SIGS.get(fp) if fp is not None else None
        if fp is None or (s is not None and h in s):
            return  # steady state: nothing new to record
    if not known:
        h = key_hash(key)
    with _plan_lock:
        _HASH_BY_KEY[key] = h
        _KEY_BY_HASH[h] = key
        if installed:
            _INSTALLED_HASHES.add(h)
        if fp is not None:
            _PLAN_SIGS.setdefault(fp, set()).add(h)


def _plan_path(fp: str, create: bool = False) -> Optional[str]:
    d = _plans_dir(create=create)
    return None if d is None else os.path.join(d, f"{fp}.json")


# a ledger merge takes milliseconds; a lock file older than this was left
# by a dead holder (chaos kill between O_EXCL create and unlink) and is
# broken, otherwise EVERY later flush of that plan would pay the full
# bounded wait on teardown forever
_LOCK_STALE_S = 5.0


@contextlib.contextmanager
def _merge_lock(path: str, attempts: int = 40, pause: float = 0.025):
    """Best-effort cross-process exclusion for the read-merge-replace on
    one ledger file: two replicas sharing a cache dir must not overwrite
    each other's merges (lost update = the 'shrink-never' promise broken).
    O_EXCL lock file with bounded wait and stale-lock takeover; on timeout
    the merge proceeds unlocked — a possible lost update beats a stuck
    teardown, and the loser's sigs return on its next flush."""
    import time

    lock = path + ".lock"
    held = False
    for _ in range(attempts):
        try:
            os.close(os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY))
            held = True
            break
        except FileExistsError:
            try:
                stale = time.time() - os.path.getmtime(lock) > _LOCK_STALE_S
            except OSError:
                continue  # holder just released it: retry immediately
            if stale:
                with contextlib.suppress(OSError):
                    os.unlink(lock)
                continue
            time.sleep(pause)
        except OSError:
            break  # unwritable dir: the write below will say so loudly
    try:
        yield
    finally:
        if held:
            with contextlib.suppress(OSError):
                os.unlink(lock)


def flush_plan(fp: Optional[str]) -> None:
    """Merge this process's recorded program hashes for ``fp`` into the
    persistent plan ledger (cross-process merge lock + atomic tmp+rename;
    shrink-never)."""
    if fp is None:
        return
    with _plan_lock:
        sigs = set(_PLAN_SIGS.get(fp, ()))
    if not sigs:
        return
    path = _plan_path(fp, create=True)
    if path is None:
        return
    try:
        with _merge_lock(path):
            existing = []
            if os.path.exists(path):
                with open(path, "r", encoding="utf-8") as f:
                    existing = json.load(f).get("sigs", [])
            merged = sorted(set(existing) | sigs)
            if merged == sorted(existing):
                return  # nothing new: skip the write entirely
            tmp = path + f".tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"v": _ENTRY_VERSION, "sigs": merged}, f)
            os.replace(tmp, path)
    except (OSError, ValueError) as e:
        # the ledger is an optimization; never fail a query over it
        from quokka_tpu import obs

        obs.diag(f"[compileplane] plan ledger write failed for {fp}: {e!r}")


def plan_sig_hashes(fp: str) -> List[str]:
    path = _plan_path(fp)
    if path is None or not os.path.exists(path):
        return []
    try:
        with open(path, "r", encoding="utf-8") as f:
            return list(json.load(f).get("sigs", []))
    except (OSError, ValueError):
        return []


# ---------------------------------------------------------------------------
# AOT programs
# ---------------------------------------------------------------------------


# Compiled.__call__'s argument-mismatch class: TypeError for aval/pytree
# drift, ValueError for input-sharding drift (a multi-device process places
# arrays jit would silently re-place; a compiled executable refuses).
# Both degrade to the jit fallback, never an error.
_MISMATCH_ERRORS = (TypeError, ValueError)


class AotProgram:
    """A compiled executable with a build-on-demand jit fallback.  The
    fallback fires when the caller's avals/shardings drift from the
    compiled ones — the program keeps answering, one
    ``compile.aot_mismatch`` counter richer."""

    __slots__ = ("compiled", "_builder", "_fallback", "prewarmed", "_counted",
                 "_unproven")

    def __init__(self, compiled, builder: Optional[Callable[[], object]] = None,
                 prewarmed: bool = False):
        self.compiled = compiled
        self._builder = builder
        self._fallback = None
        self.prewarmed = prewarmed
        self._counted = False
        # an executable LOADED from the store (those carry no builder, see
        # acquire) has never run here: until its first call returns, a
        # runtime refusal is a mismatch between artifact and process, not a
        # query failure
        self._unproven = builder is None

    def __call__(self, *args):
        if self.prewarmed and not self._counted:
            self._counted = True
            _count("prewarm_hit")
        c = self.compiled
        if c is not None:
            try:
                out = c(*args)
                if self._unproven:
                    # a loaded executable that lacks a kernel fails at its
                    # first result's read, outside this guard, unless the
                    # first call waits for it here
                    jax.block_until_ready(out)
                    self._unproven = False
                return out
            except _MISMATCH_ERRORS:
                # aval/sharding drift: drop to the jitted fallback for good
                _count("aot_mismatch")
                self.compiled = None
            except jax.errors.JaxRuntimeError:
                if not self._unproven:
                    raise
                _count("aot_mismatch")
                self.compiled = None
        fb = self._fallback
        if fb is None:
            if self._builder is None:
                raise AotMismatch(
                    "pre-warmed executable does not match this call's "
                    "shapes and no builder is attached")
            fb = self._fallback = self._builder()
        return fb(*args)


class AotMismatch(TypeError):
    """A prewarm-loaded executable saw different shapes; the call site
    rebuilds from its own builder."""


def _entry_path(key: Tuple, create: bool = False) -> Optional[str]:
    d = _aot_dir(create=create)
    return None if d is None else os.path.join(d, key_hash(key) + ".aot")


def _quarantine(path: str) -> None:
    from quokka_tpu import obs

    obs.REGISTRY.counter("compile.aot_corrupt").inc()
    with contextlib.suppress(OSError):
        os.replace(path, path + ".corrupt")


def _load_entry(path: str):
    """(key, callable) from a persisted executable, or None (quarantining
    the file) on any corruption/mismatch."""
    from jax.experimental.serialize_executable import deserialize_and_load

    try:
        with open(path, "rb") as f:
            data = f.read()
        payload = unframe(data, source=path)
        entry = pickle.loads(payload)
        if entry.get("v") != _ENTRY_VERSION:
            raise CorruptArtifactError(f"{path}: unknown entry version")
        by_id = {d.id: d for d in jax.devices()}
        compiled = deserialize_and_load(
            entry["exe"], entry["in_tree"], entry["out_tree"],
            execution_devices=[by_id[i] for i in entry["devices"]])
        from quokka_tpu.obs import memplane

        # a loaded executable is host residency for the process lifetime
        # (same token as the persist path: load-after-persist replaces)
        memplane.LEDGER.track(("aot", path), memplane.SITE_EXEC,
                              len(payload), device=memplane.HOST)
        return entry["key"], compiled
    except Exception:  # noqa: BLE001 — any load failure means "not cached"
        _quarantine(path)
        return None


# persistence runs on ONE background writer thread: serialization costs
# milliseconds and must never sit on the dispatch path
_write_q: "queue.Queue[Tuple[Tuple, object]]" = queue.Queue()
_writer_started = False
_writer_lock = threading.Lock()


def _writer_loop() -> None:
    while True:
        key, compiled = _write_q.get()
        try:
            _persist_now(key, compiled)
        except Exception as e:  # noqa: BLE001 — persistence is best-effort
            from quokka_tpu import obs

            obs.diag(f"[compileplane] persist of {key[0]} failed: {e!r}")
        finally:
            _write_q.task_done()


def _ensure_writer() -> None:
    global _writer_started
    with _writer_lock:
        if not _writer_started:
            t = threading.Thread(target=_writer_loop, daemon=True,
                                 name="qk-aot-writer")
            t.start()
            _writer_started = True


def _persist_now(key: Tuple, compiled) -> None:
    from jax.experimental.serialize_executable import (
        deserialize_and_load,
        serialize,
    )

    path = _entry_path(key, create=True)
    if path is None or os.path.exists(path):
        return
    exe, in_tree, out_tree = serialize(compiled)
    # the ordered device assignment the program was compiled for: a reader
    # must load onto exactly these (deserialize_and_load's default is every
    # local device, which breaks a one-device program in a process that
    # has several)
    devices = compiled.runtime_executable().local_devices()
    # verify the round trip BEFORE writing, loading as a reader will: an
    # artifact that cannot be loaded back here would poison every future
    # restart with a quarantine cycle
    try:
        deserialize_and_load(exe, in_tree, out_tree,
                             execution_devices=devices)
    except Exception:  # noqa: BLE001 — any load failure means "don't ship"
        from quokka_tpu import obs

        obs.REGISTRY.counter("compile.aot_unserializable").inc()
        return
    payload = pickle.dumps({
        "v": _ENTRY_VERSION, "key": key, "exe": exe,
        "in_tree": in_tree, "out_tree": out_tree,
        "devices": [d.id for d in devices],
    })
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(frame(payload))
    os.replace(tmp, path)
    from quokka_tpu.obs import memplane

    memplane.LEDGER.track(("aot", path), memplane.SITE_EXEC, len(payload),
                          device=memplane.HOST)


def drain_writes(timeout: float = 10.0) -> None:
    """Block until queued persists hit disk (tests / warmup-smoke).  Waits
    on the queue's task accounting (``put`` increments, ``task_done``
    decrements under ``all_tasks_done``), so a ``put`` racing the writer's
    last ``task_done`` can never report drained early — the failure mode
    an emptiness-probe idle flag had."""
    import time

    deadline = time.monotonic() + timeout
    with _write_q.all_tasks_done:
        while _write_q.unfinished_tasks:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return
            _write_q.all_tasks_done.wait(remaining)


def acquire(key: Tuple, builder: Callable[[], object], args,
            lowerer: Optional[Callable[[], object]] = None) -> object:
    """Resolve a program cache miss: persisted executable if one exists
    (``compile.cache_hit``), else an explicit AOT compile of ``builder()``
    at ``args``'s shapes (``compile.miss``), persisted in the background.
    Always returns a callable and installs it in PROGRAMS; on any AOT
    failure the plain jitted builder result stands in.  ``lowerer``
    overrides how the jitted function lowers (kernels with trailing static
    args lower with them but are CALLED without).

    Every call is one ``compile.acquire`` span naming the program (the
    key's kind and hash, its signature cut to 200 characters), whether it
    was loaded or built (``hit``), whether this thread really compiled
    (``real``), and the query and task that asked; the same lands in that
    query's record (obs/querylog.py ``compiled``)."""
    from quokka_tpu.obs import querylog, spans
    from quokka_tpu.utils import compilestats

    kind = key[0] if key and isinstance(key[0], str) else "?"
    h = key_hash(key)
    with spans.span("compile.acquire") as sp:
        before = compilestats.thread_real_compiles()
        prog, hit = _acquire(key, builder, args, lowerer)
        real = compilestats.thread_real_compiles() > before
        sp.args = {"kind": kind, "key_hash": h, "sig": repr(key[1:])[:200],
                   "hit": hit, "real": real}
        if sp.root is not None:
            sp.args["task"] = sp.root.label
    querylog.compiled(sp.q, kind, h, sp.dur, real, hit)
    return prog


def _acquire(key: Tuple, builder: Callable[[], object], args,
             lowerer: Optional[Callable[[], object]]):
    """(program, "cache_hit" | "miss"): acquire's work."""
    note_program(key, installed=True)
    path = _entry_path(key)
    if path is not None and os.path.exists(path):
        loaded = _load_entry(path)
        if loaded is not None:
            _count("cache_hit")
            # deliberately NO builder: the caller's builder lambda closes
            # over the triggering batch (device arrays, for fuse programs)
            # and PROGRAMS never evicts — retaining it would pin that
            # batch's memory for the process lifetime.  Aval/sharding
            # drift raises AotMismatch instead, and every dispatch site
            # rebuilds from its own CURRENT builder.
            prog = AotProgram(loaded[1])
            PROGRAMS[key] = prog
            return prog, "cache_hit"
    _count("miss")
    fn = builder()
    prog: object = fn
    if _enabled():
        try:
            from quokka_tpu.utils import compilestats

            lowered = lowerer() if lowerer is not None else fn.lower(*args)
            hits = compilestats.thread_cache_hits()
            compiled = lowered.compile()
            prog = AotProgram(compiled, builder=lambda: fn)
            # an executable the XLA persistent cache answered re-serializes
            # without its kernels: the copy loads, then fails its first
            # run with an asynchronous NOT_FOUND no call site can catch.
            # Only a real compile is shipped.
            if compilestats.thread_cache_hits() == hits:
                _ensure_writer()
                _write_q.put((key, compiled))
        except Exception:  # noqa: BLE001 — AOT is an optimization layer:
            prog = fn      # the jitted callable is always a valid program
    PROGRAMS[key] = prog
    return prog, "miss"


def aot_kernel_call(kind: str, jit_fn, args: Tuple, statics: Tuple = (),
                    form: Tuple = ()):
    """Dispatch a module-level jitted kernel through the compile plane.

    ``args`` are the traced (array) positional arguments; ``statics`` are
    TRAILING static positional arguments.  The program key derives from the
    canonical aval signature (ops/sigkey) + statics, so one ladder bucket =
    one program; ``form`` are further parts of the key alone, naming the
    traced body (keys carry no version of the code: a kernel whose body
    changes under unchanged shapes changes its form).  Inside an active
    trace the jitted function is called directly (it inlines); a compiled
    executable cannot trace.  Any aval drift falls back to the plain jit
    call — never an error."""
    from quokka_tpu.analysis import compat

    if not compat.trace_state_clean():
        return jit_fn(*args, *statics)
    key = sigkey.make_key(kind, sigkey.aval_sig(args), *statics, *form)
    prog = PROGRAMS.get(key)
    if prog is not None:
        # in-memory hits still record under the current plan: a plan that
        # REUSES another plan's programs must prewarm the full set
        note_program(key)
    else:
        if statics:
            def builder():
                return lambda *a: jit_fn(*a, *statics)
        else:
            def builder():
                return jit_fn
        prog = acquire(key, builder, args,
                       lowerer=lambda: jit_fn.lower(*args, *statics))
    try:
        return prog(*args)
    except AotMismatch:
        PROGRAMS[key] = builder2 = (lambda *a: jit_fn(*a, *statics))
        return builder2(*args)


# ---------------------------------------------------------------------------
# pre-warm
# ---------------------------------------------------------------------------


def _install_hash(h: str) -> bool:
    """Load one persisted executable by hash and install it (prewarm).
    The hash is CLAIMED in the installed set before the expensive
    deserialize (and released on failure), so two replays racing over the
    same plan — e.g. the lowering-fired background thread and an explicit
    ``prewarm_all`` — never both pay the load."""
    with _plan_lock:
        if h in _INSTALLED_HASHES:
            return False
        _INSTALLED_HASHES.add(h)
    ok = False
    try:
        d = _aot_dir()
        if d is None:
            return False
        path = os.path.join(d, h + ".aot")
        if not os.path.exists(path):
            return False
        loaded = _load_entry(path)
        if loaded is None:
            return False
        key, compiled = loaded
        with _plan_lock:
            _HASH_BY_KEY[key] = h
            _KEY_BY_HASH[h] = key
        if key not in PROGRAMS:
            PROGRAMS[key] = AotProgram(compiled, prewarmed=True)
        ok = True
        return True
    finally:
        if not ok:
            with _plan_lock:
                _INSTALLED_HASHES.discard(h)


# plan fingerprints already replayed by THIS process: the per-lowering
# prewarm of a steadily re-submitted plan must cost a set lookup, never a
# ledger open/parse (the programs a replay would find are resident — either
# installed by the first replay or compiled by the first run's dispatches).
# _REPLAY_THREADS keeps the live thread per fp so a caller that needs a
# SYNCHRONOUS warm (QueryService.prewarm) can join an in-flight replay it
# didn't start instead of silently returning before the loads finish.
_REPLAYED_FPS: set = set()
_REPLAY_THREADS: Dict[str, threading.Thread] = {}


def prewarm_plan(fp: Optional[str], wait: bool = False,
                 timeout: float = 60.0) -> Optional[threading.Thread]:
    """Load every persisted executable the plan ledger records for ``fp``
    on a background thread (daemon — a dying process must not wait on
    warmup).  ``wait=True`` blocks until done (startup prewarm API).
    One replay per plan per process: a warm plan's re-lowering is a set
    lookup, not a ledger read — but while that one replay is still in
    flight, its thread is returned (and joined under ``wait``) so every
    caller synchronizes with the real work."""
    if fp is None or not _enabled():
        return None
    with _plan_lock:
        claimed = fp not in _REPLAYED_FPS
        if claimed:
            _REPLAYED_FPS.add(fp)
            installed = set(_INSTALLED_HASHES)
        else:
            t = _REPLAY_THREADS.get(fp)
            if t is not None and not t.is_alive():
                del _REPLAY_THREADS[fp]
                t = None
    if not claimed:
        # the one replay already happened (t None: done, plan is as warm
        # as it gets) or is still in flight: synchronize with it
        if t is not None and wait:
            t.join(timeout)
        return t
    hashes = [h for h in plan_sig_hashes(fp) if h not in installed]
    if not hashes:
        return None

    def _run() -> None:
        n = 0
        from quokka_tpu import obs

        for h in hashes:
            try:
                n += bool(_install_hash(h))
            except Exception as e:  # noqa: BLE001 — warmup never kills
                obs.diag(f"[compileplane] prewarm of {h} failed: {e!r}")
        t.installed = n  # read by prewarm_all after join
        if n:
            obs.REGISTRY.counter("compile.prewarm_loaded").inc(n)
            obs.RECORDER.record("compile.prewarm", fp, n=n)

    t = threading.Thread(target=_run, daemon=True, name="qk-prewarm")
    t.installed = 0
    with _plan_lock:
        _REPLAY_THREADS[fp] = t
    t.start()
    if wait:
        t.join(timeout)
    return t


def prewarm_all(wait: bool = True, timeout: float = 120.0) -> int:
    """Service-startup prewarm: replay EVERY recorded plan ledger.
    ``wait=True`` returns the number of plans that actually loaded >= 1
    persisted executable (a ledger whose artifacts are missing — foreign
    fingerprint, wiped store — contributes 0, so a cold start reports as
    one); ``wait=False`` can only report the number of plan warmups
    dispatched.  ``timeout`` bounds the WHOLE wait (one deadline shared
    across plan threads, not one timeout per plan)."""
    import time

    d = _plans_dir()
    if d is None or not os.path.isdir(d):
        return 0
    threads = []
    for name in sorted(os.listdir(d)):
        if name.endswith(".json"):
            t = prewarm_plan(name[:-5])
            if t is not None:
                threads.append(t)
    if not wait:
        return len(threads)
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    return sum(1 for t in threads if getattr(t, "installed", 0))


def stats() -> Dict[str, int]:
    from quokka_tpu import obs

    snap = obs.REGISTRY.snapshot()
    return {k.split(".", 1)[1]: int(v) for k, v in snap.items()
            if k.startswith("compile.") and k.count(".") == 1}
