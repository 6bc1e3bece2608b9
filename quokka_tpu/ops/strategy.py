"""Per-backend, per-operator kernel-strategy matrix.

The engine has more than one implementation of its hot relational kernels —
sort-based and hash-table group-by/join, host-native and two device as-of
kernels, masked and compacted shuffle splits — and which one wins is a
property of the BACKEND (scatter throughput, sort cost, d2h latency), not of
the query.  Until PR 8 the picks were scattered platform gates in config.py
("hash tables off on TPU", "host asof on CPU"), which meant the benched path
on one backend could be a path another backend never runs (VERDICT r5
finding #2).  This module is now the one place a kernel strategy is decided,
and the decision is MEASURED, not asserted:

- ``choice(op)`` resolves an operator's strategy:
    1. ``QK_KERNEL_STRATEGY="op=choice,..."`` — forced override (tests,
       experiments).  Unknown ops/choices raise: a forced choice that
       silently no-ops is how wrong benchmarks happen.
    2. legacy envs ``QUOKKA_HASH_TABLES`` (group-by + join build) and
       ``QUOKKA_HOST_ASOF`` (asof), kept working verbatim.
    3. a persisted calibration profile for THIS backend fingerprint
       (``calibrate()`` micro-times every candidate kernel on live arrays
       and stores the winners under ``<cache>/strategy/<fingerprint>.json``).
       A foreign fingerprint — different platform, device kind/count, jax —
       is ignored entirely, never partially applied.
    4. static per-platform safe defaults (the pre-PR-8 gates).

- ``note_used(op, choice)`` records what actually RAN (dispatch sites call
  it), feeding the ``strategy.<op>.<choice>`` counters on ``/metrics``;
  ``invalid_for_platform`` names a recorded choice its platform gates off.

Operators and choices:

  groupby     sort | hashtable      (kernels.sorted_groupby: sort, scans,
                                     sort, no indexed op, vs
                                     hashtable.hash_groupby)
  join_build  sort | hashtable      (join._pk_probe_sorted vs
                                     hashtable build_table/pk_probe)
  asof        host | sort | searchsorted
                                    (native O(n+m) host merge vs the
                                     device merge as sort, running maxima,
                                     sort vs the cached-quote-sort device
                                     binary search)
  shuffle     masked | compacted    (kernels.split_by_partition modes)

This module and config.py are the ONLY places allowed to probe the platform
(lint rule QK013): a platform string check anywhere else is a scattered gate
waiting to diverge from the matrix.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional, Tuple

from quokka_tpu import config

OPS: Dict[str, Tuple[str, ...]] = {
    "groupby": ("sort", "hashtable"),
    "join_build": ("sort", "hashtable"),
    "asof": ("host", "sort", "searchsorted"),
    "shuffle": ("masked", "compacted"),
}

# The pre-calibration safe defaults — the argued per-platform gates that
# config.use_hash_tables()/use_host_asof() used to hard-code.  CPU/GPU:
# scatter/gather fast, sorts slow -> tables; TPU: random scatters
# serialize, multi-operand sort is the idiom.  Host asof only where
# np.asarray is zero-copy (CPU).  The GPU gets the device binary search over
# a cached quote sort; the TPU, where a gather costs 8-13 ns an element and
# the search pays 72 a trade, the merge as sort, running maxima, sort
# (ops/asof.py _asof_match: nothing indexed over the quote slots; PERF.md
# section 6, PR 35).  Neither needs a host round trip.
_PLATFORM_DEFAULTS: Dict[str, Dict[str, str]] = {
    "cpu": {"groupby": "hashtable", "join_build": "hashtable",
            "asof": "host", "shuffle": "masked"},
    "gpu": {"groupby": "hashtable", "join_build": "hashtable",
            "asof": "searchsorted", "shuffle": "masked"},
    "tpu": {"groupby": "sort", "join_build": "sort",
            "asof": "sort", "shuffle": "masked"},
}
_PLATFORM_DEFAULTS["cuda"] = _PLATFORM_DEFAULTS["gpu"]
_PLATFORM_DEFAULTS["rocm"] = _PLATFORM_DEFAULTS["gpu"]
_FALLBACK_DEFAULTS = {"groupby": "sort", "join_build": "sort",
                      "asof": "sort", "shuffle": "masked"}

_CALIB_VERSION = 1

_lock = threading.Lock()
# parsed QK_KERNEL_STRATEGY cache, keyed by the raw env string so tests that
# monkeypatch the env see their change on the next call
_env_cache: Tuple[Optional[str], Dict[str, str]] = (None, {})
# loaded-or-computed calibration choices for THIS process's backend;
# _calib_state: "unloaded" | "loaded" (None result = no usable profile)
_calibrated: Optional[Dict[str, str]] = None
_calib_state = "unloaded"

_used_lock = threading.Lock()
_used: Dict[str, list] = {}


class StrategyError(ValueError):
    """Malformed QK_KERNEL_STRATEGY / unknown operator or choice."""


def _validate(op: str, choice_: str, origin: str) -> None:
    if op not in OPS:
        raise StrategyError(
            f"{origin}: unknown operator {op!r} (known: {sorted(OPS)})")
    if choice_ not in OPS[op]:
        raise StrategyError(
            f"{origin}: unknown choice {choice_!r} for {op!r} "
            f"(known: {OPS[op]})")


def _env_overrides() -> Dict[str, str]:
    raw = os.environ.get("QK_KERNEL_STRATEGY")
    global _env_cache
    cached_raw, cached = _env_cache
    if raw == cached_raw:
        return cached
    parsed: Dict[str, str] = {}
    if raw:
        for item in raw.split(","):
            item = item.strip()
            if not item:
                continue
            if "=" not in item:
                raise StrategyError(
                    f"QK_KERNEL_STRATEGY: expected op=choice, got {item!r}")
            op, _, ch = item.partition("=")
            op, ch = op.strip(), ch.strip()
            _validate(op, ch, "QK_KERNEL_STRATEGY")
            parsed[op] = ch
    _env_cache = (raw, parsed)
    return parsed


def _legacy_env(op: str) -> Optional[str]:
    """QUOKKA_HASH_TABLES / QUOKKA_HOST_ASOF keep their documented meaning."""
    if op in ("groupby", "join_build"):
        v = os.environ.get("QUOKKA_HASH_TABLES", "auto").lower()
        if v in ("1", "true", "yes", "on"):
            return "hashtable"
        if v in ("0", "false", "no", "off"):
            return "sort"
        return None
    if op == "asof":
        v = os.environ.get("QUOKKA_HOST_ASOF", "auto").lower()
        if v in ("1", "true", "yes", "on"):
            return "host"
        if v in ("0", "false", "no", "off"):
            # "no host walk" — take the backend's device pick
            dev = _calibrated_choice(op) or _default(op)
            return dev if dev != "host" else "searchsorted"
        return None
    return None


def _default(op: str) -> str:
    plat = config._platform()
    return _PLATFORM_DEFAULTS.get(plat, _FALLBACK_DEFAULTS)[op]


# ---------------------------------------------------------------------------
# persisted calibration
# ---------------------------------------------------------------------------


def _dir() -> Optional[str]:
    """Calibration profile directory; None disables persistence (and
    loading).  QK_STRATEGY_DIR="" explicitly disables — tests set this so a
    developer box's calibration can never change test behavior."""
    d = os.environ.get("QK_STRATEGY_DIR")
    if d is not None:
        return d or None
    return os.path.join(config.CACHE_ROOT, "strategy")


def _fingerprint() -> str:
    from quokka_tpu.runtime import compileplane

    return compileplane.backend_fingerprint()


def _profile_path() -> Optional[str]:
    d = _dir()
    if d is None:
        return None
    return os.path.join(d, f"{_fingerprint()}.json")


def _load_profile() -> Optional[Dict[str, str]]:
    """Choices from the persisted profile for THIS fingerprint, else None.
    A corrupt file or a foreign fingerprint inside it is ignored wholesale —
    safe defaults beat a half-trusted profile."""
    path = _profile_path()
    if path is None or not os.path.exists(path):
        return None
    try:
        with open(path, "r", encoding="utf-8") as f:
            prof = json.load(f)
        if not isinstance(prof, dict):
            return None
        if prof.get("version") != _CALIB_VERSION:
            return None
        if prof.get("fingerprint") != _fingerprint():
            return None
        choices = prof.get("choices")
        if not isinstance(choices, dict):
            return None
        for op, ch in choices.items():
            _validate(op, ch, path)
        return dict(choices)
    except (OSError, ValueError, StrategyError):
        from quokka_tpu.obs import diag

        diag(f"strategy: ignoring unusable calibration profile {path}")
        return None


def _calibrated_choice(op: str) -> Optional[str]:
    global _calibrated, _calib_state
    with _lock:
        if _calib_state == "unloaded":
            _calibrated = _load_profile()
            _calib_state = "loaded"
        return None if _calibrated is None else _calibrated.get(op)


def reset() -> None:
    """Forget cached env parses and the loaded calibration profile (tests)."""
    global _env_cache, _calibrated, _calib_state
    with _lock:
        _env_cache = (None, {})
        _calibrated = None
        _calib_state = "unloaded"


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------


def resolve(op: str) -> Tuple[str, str]:
    """(choice, source) for an operator; source is one of
    "env" | "legacy-env" | "calibrated" | "default"."""
    if op not in OPS:
        raise StrategyError(f"unknown operator {op!r} (known: {sorted(OPS)})")
    env = _env_overrides()
    if op in env:
        return env[op], "env"
    legacy = _legacy_env(op)
    if legacy is not None:
        return legacy, "legacy-env"
    cal = _calibrated_choice(op)
    if cal is not None:
        return cal, "calibrated"
    return _default(op), "default"


def choice(op: str) -> str:
    return resolve(op)[0]


def choices() -> Dict[str, str]:
    return {op: resolve(op)[0] for op in OPS}


def sources() -> Dict[str, str]:
    return {op: resolve(op)[1] for op in OPS}


# ---------------------------------------------------------------------------
# what actually ran
# ---------------------------------------------------------------------------


def note_used(op: str, ran: str) -> None:
    """Record that a dispatch site actually executed `ran` for `op` — the
    fallback paths (diverged hash build, missing native lib) report the
    kernel that ran, not the one the matrix asked for.  Every distinct
    kernel is kept (a mesh query's timed shard kernel and its
    coordinator-side recombine may legitimately differ): the snapshot must
    name them all, not whichever dispatched last."""
    with _used_lock:
        ops_ran = _used.setdefault(op, [])
        if ran not in ops_ran:
            ops_ran.append(ran)
            from quokka_tpu import obs

            obs.REGISTRY.counter(f"strategy.{op}.{ran}").inc()


def used_snapshot() -> Dict[str, str]:
    """{op: choice} of what ran since the last reset; when more than one
    kernel ran for an op the value is every choice sorted and '+'-joined
    (e.g. ``groupby: "hashtable+sort"``)."""
    with _used_lock:
        return {op: "+".join(sorted(v)) for op, v in _used.items()}


def reset_used() -> None:
    with _used_lock:
        _used.clear()


def invalid_for_platform(platform: str, op: str,
                         ran: str) -> Optional[str]:
    """Why a recorded (op, choice) could never be the production path on
    `platform`, or None when it is legitimate.  ``ran`` may be a '+'-joined
    multi-value from used_snapshot; every component must be runnable: a
    timed host-asof is one a TPU will never run."""
    parts = ran.split("+") if ran else [ran]
    if op not in OPS or any(p not in OPS.get(op, ()) for p in parts):
        return (f"unknown strategy {op}={ran!r} — the bench recorded a "
                "choice the matrix does not define")
    if op == "asof" and "host" in parts and platform != "cpu":
        return ("host-native asof is a CPU-only fast path (each time/key/"
                f"valid column pays a blocking d2h copy on {platform}); a "
                f"{platform} deployment never runs it, so timing it says "
                "nothing about that backend")
    return None


# ---------------------------------------------------------------------------
# calibration microbench
# ---------------------------------------------------------------------------


def _time_best(fn, reps: int) -> float:
    fn()  # warm: compiles + first-dispatch costs are not the steady state
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _calib_batches(rows: int):
    """Synthetic batches shared by the shuffle/asof candidates."""
    import numpy as np
    import pyarrow as pa

    from quokka_tpu.ops import bridge

    r = np.random.default_rng(11)
    n_sym = 64
    tt = np.sort(r.integers(0, 1 << 20, rows)).astype(np.int64)
    qt = np.sort(r.integers(0, 1 << 20, 2 * rows)).astype(np.int64)
    trades = bridge.arrow_to_device(pa.table({
        "time": tt, "sym": r.integers(0, n_sym, rows).astype(np.int64),
        "size": r.integers(1, 500, rows).astype(np.int64)}))
    quotes = bridge.arrow_to_device(pa.table({
        "time": qt, "sym": r.integers(0, n_sym, 2 * rows).astype(np.int64),
        "bid": r.uniform(10, 500, 2 * rows)}))
    return trades, quotes


def calibrate(rows: Optional[int] = None, reps: int = 3,
              persist: bool = True) -> Dict[str, object]:
    """Micro-time every candidate kernel on live device arrays and pick the
    winners; persists (atomically) under the backend fingerprint and
    installs the result in-process.  Returns {"choices", "timings_s",
    "fingerprint", "rows"}.  One-time per backend: ``ensure_calibrated``
    answers from the persisted profile on every later run."""
    import jax.numpy as jnp
    import numpy as np

    from quokka_tpu.ops import asof as asof_ops
    from quokka_tpu.ops import hashtable, join as join_ops, kernels

    if rows is None:
        env = os.environ.get("QK_STRATEGY_CALIB_ROWS")
        if env:
            rows = int(env)
        else:
            # prefer measured cardinalities (obs/opstats.py cardprofile):
            # probe at the batch sizes real plans on this backend actually
            # produced, not a fixed guess.  Clamped — the calibration matrix
            # times dozens of candidates and must stay sub-second-ish.
            from quokka_tpu.obs import opstats

            measured = opstats.measured_calib_rows()
            rows = min(max(int(measured), 1 << 12), 1 << 20) \
                if measured else (1 << 16)
    rows = int(rows)
    r = np.random.default_rng(7)
    timings: Dict[str, Dict[str, float]] = {}

    # group-by: one int32 key limb, medium cardinality, one summed column
    limbs = (jnp.asarray(r.integers(0, rows // 16, rows).astype(np.int32)),)
    vals = (jnp.asarray(r.uniform(0, 1, rows).astype(np.float32)),)
    valid = jnp.ones(rows, dtype=bool)
    timings["groupby"] = {
        "sort": _time_best(
            lambda: kernels.sorted_groupby(limbs, vals, ("sum",), valid)[
                0][0].block_until_ready(), reps),
        "hashtable": _time_best(
            lambda: hashtable._hash_groupby_jit(
                limbs, vals, ("sum",), valid,
                hashtable.capbits_for(rows))[0][0].block_until_ready(), reps),
    }

    # join build+probe: unique build keys, probe twice the build size
    bl = (jnp.asarray(r.permutation(rows).astype(np.int32)),)
    pl = (jnp.asarray(r.integers(0, rows, 2 * rows).astype(np.int32)),)
    bok = jnp.ones(rows, dtype=bool)
    pok = jnp.ones(2 * rows, dtype=bool)
    steps = max(1, int(np.ceil(np.log2(max(2, rows)))) + 1)

    def _join_sort():
        sl, perm, nv = join_ops._sort_build_keys(bl, bok)
        out = join_ops._pk_probe_sorted(sl, perm, nv, pl, pok, steps)
        out[1].block_until_ready()

    def _join_ht():
        capbits = hashtable.capbits_for(rows)
        cl = hashtable.canonical_limbs(bl, nan_unique=False)
        _, tbl, _ = hashtable._insert_jit(cl, bok, capbits)
        out = hashtable._probe_jit(
            tbl, cl, hashtable.canonical_limbs(pl, nan_unique=False), pok,
            capbits)
        out[1].block_until_ready()

    timings["join_build"] = {
        "sort": _time_best(_join_sort, reps),
        "hashtable": _time_best(_join_ht, reps),
    }

    # asof + shuffle work on real DeviceBatches through the public entries
    trades, quotes = _calib_batches(rows)
    asof_t: Dict[str, float] = {}
    for cand in OPS["asof"]:
        def _run(c=cand):
            # pay the quote-sort cost every rep (the executor's buffer
            # grows between flushes, so the cached sort rarely carries)
            quotes.__dict__.pop("_asof_ss_cache", None)
            out = asof_ops.asof_join(
                trades, quotes, "time", "time", ["sym"], ["sym"], ["bid"],
                strategy=c)
            out.columns["bid"].data.block_until_ready()

        try:
            if cand == "host":
                from quokka_tpu.utils import native

                if not native.has_asof() or config._platform() != "cpu":
                    continue
            asof_t[cand] = _time_best(_run, reps)
        except Exception:  # noqa: BLE001 — a missing candidate is a skip
            continue
    timings["asof"] = asof_t

    # shuffle is timed for the profile's information but NEVER picked by
    # calibration: the masked/compacted tradeoff is a PIPELINE property —
    # masked split counts ride asynchronous d2h copies that consumers read
    # batches later, while the compacted plan's counts readback BLOCKS the
    # push path (shuffle.host_syncs).  A standalone microbench observes
    # only kernel walls, so it flips to compacted on noise margins and
    # reintroduces the per-split pipeline drain PR 6 removed (measured: a
    # 1.4% microbench "win" cost the SF1 join queries ~3x in transfer
    # stalls).  The masked default + SHUFFLE_MASKED_CAP heuristic stands;
    # QK_KERNEL_STRATEGY=shuffle=compacted remains for experiments.
    n_parts = 8
    pids = kernels.partition_ids(trades, ["sym"], n_parts)

    def _shuffle(compact: bool):
        parts = kernels.split_by_partition(trades, pids, n_parts,
                                           compact=compact)
        if not compact:
            parts = [kernels.compact(p) for p in parts]  # consumer densify
        parts[-1].valid.block_until_ready()

    timings["shuffle"] = {
        "masked": _time_best(lambda: _shuffle(False), reps),
        "compacted": _time_best(lambda: _shuffle(True), reps),
    }

    picks: Dict[str, str] = {}
    for op, t in timings.items():
        if t and op != "shuffle":
            picks[op] = min(t, key=t.get)
    result = {
        "version": _CALIB_VERSION,
        "fingerprint": _fingerprint(),
        "platform": config._platform(),
        "rows": rows,
        "choices": picks,
        "timings_s": {op: {c: round(v, 6) for c, v in t.items()}
                      for op, t in timings.items()},
    }
    global _calibrated, _calib_state
    with _lock:
        _calibrated = dict(picks)
        _calib_state = "loaded"
    if persist:
        path = _profile_path()
        if path is not None:
            try:
                os.makedirs(os.path.dirname(path), exist_ok=True)
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump(result, f, indent=2, sort_keys=True)
                os.replace(tmp, path)
            except OSError:
                from quokka_tpu.obs import diag

                diag(f"strategy: could not persist calibration to {path}")
    return result


def ensure_calibrated(rows: Optional[int] = None) -> Dict[str, str]:
    """Load the persisted profile for this backend, calibrating once if none
    exists.  QK_STRATEGY_CALIBRATE=0 skips the (potentially multi-second)
    microbench and leaves the platform defaults in charge."""
    loaded = _calibrated_choice("groupby")  # forces one load attempt
    with _lock:
        have = _calibrated is not None
    del loaded
    if have:
        with _lock:
            return dict(_calibrated or {})
    if os.environ.get("QK_STRATEGY_CALIBRATE", "1") in ("0", "false", "no"):
        return {}
    return dict(calibrate(rows=rows)["choices"])
