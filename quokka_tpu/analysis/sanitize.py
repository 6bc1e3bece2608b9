"""Runtime sanitizer mode (``QK_SANITIZE=1``).

Three instruments, all off unless the env flag is set (zero overhead on the
production path):

- **Deadlock watchdog** (``Watchdog``): every worker's main loop beats a
  per-process watchdog; when the loop stops beating for
  ``QK_SANITIZE_DEADLINE`` seconds (a dispatch blocked on a lock/pipe — the
  round-5 ``test_placement``/``test_distributed`` wedge), the watchdog
  writes a banner + faulthandler dump of EVERY thread's stack to stderr and
  exits the process with ``WATCHDOG_EXIT_CODE``.  The coordinator sees a
  dead worker within its 50 ms poll and raises — the run fails in seconds
  with stacks in hand instead of wedging to a 600 s timeout.

- **Lock-order recorder** (``maybe_instrument``): the runtime's shared locks
  (ControlStore, BatchCache) are wrapped so every acquisition records the
  held->acquired edge per thread; acquiring B while holding A after A-held-
  while-acquiring-B was seen in the other order reports a lock-order
  inversion (the classic two-lock deadlock precursor) to stderr and
  ``lock_inversions()``.

- **Recompile sentinel** (``check_no_recompiles`` / ``recompile_guard``):
  fails a run when real backend compiles happened after warmup — the
  static-shape discipline says a warmed query shape never recompiles, and a
  silent recompile is both a perf cliff and a symptom of an unstable jit
  signature.  ``benchmarks/run.py`` reports the same count as
  ``compiles_in_window``.
"""

from __future__ import annotations

import contextlib
import faulthandler
import io
import os
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

WATCHDOG_EXIT_CODE = 86  # distinctive: "the sanitizer shot the process"
_DEFAULT_DEADLINE = 120.0  # long jit compiles legitimately stall workers


def enabled() -> bool:
    return os.environ.get("QK_SANITIZE", "").strip().lower() not in (
        "", "0", "false", "no", "off")


def dump_all_stacks(stream) -> None:
    """Every thread's python stack to `stream`.  faulthandler when the
    stream is a real file (signal-safe, exactly what a wedged process
    needs); pure-python fallback for fd-less streams (pytest capture)."""
    try:
        stream.fileno()
        has_fd = True
    except (OSError, AttributeError, ValueError, io.UnsupportedOperation):
        has_fd = False
    if has_fd:
        faulthandler.dump_traceback(file=stream)
        return
    frames = sys._current_frames()
    for t in threading.enumerate():
        frame = frames.get(t.ident)
        stream.write(f"\nThread {t.name} (id {t.ident}):\n")
        if frame is not None:
            stream.write("".join(traceback.format_stack(frame)))


def deadline_seconds() -> float:
    try:
        return float(os.environ.get("QK_SANITIZE_DEADLINE",
                                    _DEFAULT_DEADLINE))
    except ValueError:
        return _DEFAULT_DEADLINE


# ---------------------------------------------------------------------------
# Deadlock watchdog
# ---------------------------------------------------------------------------


class Watchdog:
    """Heartbeat-deadline watchdog.  ``beat()`` from the monitored loop;
    miss the deadline and the process dumps all thread stacks and exits.

    ``_exit`` is injectable for tests (default ``os._exit``: a wedged
    process cannot be trusted to unwind Python frames — some thread holds
    the lock everything is stuck on)."""

    def __init__(self, name: str, deadline: Optional[float] = None,
                 exit_code: int = WATCHDOG_EXIT_CODE,
                 _exit: Callable[[int], None] = os._exit,
                 stream=None):
        self.name = name
        self.deadline = deadline_seconds() if deadline is None else deadline
        self.exit_code = exit_code
        self._exit = _exit
        self._stream = stream
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"qk-watchdog[{name}]")

    def start(self) -> "Watchdog":
        self._thread.start()
        return self

    def beat(self) -> None:
        self._last = time.monotonic()

    def stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        poll = max(0.05, min(self.deadline / 4.0, 1.0))
        while not self._stop.wait(poll):
            stalled = time.monotonic() - self._last
            if stalled <= self.deadline:
                continue
            stream = self._stream or sys.stderr
            try:
                stream.write(
                    f"\n[qk-sanitize] WATCHDOG '{self.name}' (pid "
                    f"{os.getpid()}): no progress for {stalled:.1f}s "
                    f"(deadline {self.deadline:.1f}s) — dumping all thread "
                    f"stacks and exiting {self.exit_code}\n")
                dump_all_stacks(stream)
                # the flight recorder's tail + per-thread current activity:
                # stacks say WHERE the process is stuck, the recorder says
                # WHAT it was doing on the way there (obs/recorder.py)
                with contextlib.suppress(Exception):
                    from quokka_tpu.obs import recorder as _flight

                    _flight.RECORDER.dump_text(stream, last_n=50)
                inv = lock_inversions()
                if inv:
                    stream.write(
                        f"[qk-sanitize] {len(inv)} lock-order inversion(s) "
                        f"recorded this run: {inv}\n")
                stream.flush()
            finally:
                self._exit(self.exit_code)
            return  # only reached with an injected non-exiting _exit


def start_watchdog(name: str) -> Optional[Watchdog]:
    """Sanitize-mode entry point for runtime loops: a started watchdog when
    enabled (plus faulthandler for hard crashes), else None."""
    if not enabled():
        return None
    # non-file stderr (pytest-captured streams) can refuse enable(); the
    # watchdog's explicit dump_traceback still works there
    with contextlib.suppress(Exception):
        faulthandler.enable()
    return Watchdog(name).start()


# ---------------------------------------------------------------------------
# Lock-order recorder
# ---------------------------------------------------------------------------

_order_mu = threading.Lock()
# (held, acquired) -> first-seen thread name
_order_edges: Dict[Tuple[str, str], str] = {}
_order_inversions: List[Tuple[str, str]] = []
_held = threading.local()


def _held_stack() -> List[str]:
    st = getattr(_held, "stack", None)
    if st is None:
        st = _held.stack = []
    return st


def _record_acquire(name: str) -> None:
    stack = _held_stack()
    with _order_mu:
        for h in stack:
            if h == name:  # RLock re-entry: not an ordering edge
                continue
            _order_edges.setdefault((h, name), threading.current_thread().name)
            if (name, h) in _order_edges:
                pair = (name, h) if (name, h) < (h, name) else (h, name)
                if pair not in _order_inversions:
                    _order_inversions.append(pair)
                    sys.stderr.write(
                        f"[qk-sanitize] LOCK-ORDER INVERSION: '{h}' -> "
                        f"'{name}' here, but '{name}' -> '{h}' was seen on "
                        f"thread '{_order_edges[(name, h)]}' — two-lock "
                        "deadlock precursor\n")
                    sys.stderr.flush()
    stack.append(name)


def _record_release(name: str) -> None:
    stack = _held_stack()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i] == name:
            del stack[i]
            break


def lock_inversions() -> List[Tuple[str, str]]:
    with _order_mu:
        return list(_order_inversions)


def reset_lock_order() -> None:
    with _order_mu:
        _order_edges.clear()
        del _order_inversions[:]


class InstrumentedLock:
    """Wraps a Lock/RLock recording acquisition order under its name.
    Contended acquisitions (wait > _SLOW_ACQUIRE_S) additionally land in
    the flight recorder as ``lock`` events — the "lock acquire" channel of
    the merged timeline."""

    _SLOW_ACQUIRE_S = 0.005

    def __init__(self, name: str, lock):
        self.name = name
        self._lock = lock

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        t0 = time.monotonic()
        got = self._lock.acquire(blocking, timeout)
        if got:
            waited = time.monotonic() - t0
            if waited > self._SLOW_ACQUIRE_S:
                from quokka_tpu.obs import recorder as _flight

                _flight.RECORDER.record("lock", self.name, dur=waited)
            _record_acquire(self.name)
        return got

    def release(self) -> None:
        _record_release(self.name)
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False


def maybe_instrument(name: str, lock):
    """Sanitize mode: wrap `lock` in the order recorder; otherwise return it
    unchanged (the production hot path pays nothing)."""
    return InstrumentedLock(name, lock) if enabled() else lock


# ---------------------------------------------------------------------------
# Recompile sentinel
# ---------------------------------------------------------------------------


class RecompileError(RuntimeError):
    """Real backend compiles happened after warmup: the static-shape /
    signature-stability discipline is broken for this run."""


def real_compiles_delta(before: Dict, after: Dict) -> int:
    """Real-compilation delta between two compilestats snapshots (persistent-
    cache hits are not real compiles — same derivation as snapshot())."""
    b = before.get("backend_compiles", 0) - before.get("cache_hits", 0)
    a = after.get("backend_compiles", 0) - after.get("cache_hits", 0)
    return max(0, a - b)


def check_no_recompiles(before: Dict, after: Dict, context: str = "",
                        force: bool = False) -> int:
    """Raise RecompileError when sanitize mode is on and real compiles
    happened between the two snapshots; returns the delta either way.
    ``force`` checks regardless of the env flag (tests, explicit gates)."""
    delta = real_compiles_delta(before, after)
    if delta > 0 and (force or enabled()):
        raise RecompileError(
            f"{delta} real backend compile(s) after warmup"
            + (f" during {context}" if context else "")
            + " — warmed query shapes must reuse their executables "
            "(compile counters: quokka_tpu/utils/compilestats.py)")
    return delta


class recompile_guard:
    """``with recompile_guard('timed runs'):`` — snapshot on entry, check on
    clean exit (no check when the body raised)."""

    def __init__(self, context: str = "", force: bool = False):
        self.context = context
        self.force = force
        self.before: Optional[Dict] = None

    def __enter__(self):
        from quokka_tpu.utils import compilestats

        self.before = compilestats.snapshot()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            from quokka_tpu.utils import compilestats

            check_no_recompiles(self.before, compilestats.snapshot(),
                                self.context, self.force)
        return False
