"""Global configuration for quokka-tpu.

Dtype and shape policy for the device kernel layer.  The reference engine
(pyquokka) runs ragged Polars batches; XLA wants static shapes, so every batch
is padded up to a "bucket" size and carries a validity mask.  Buckets are
geometric so each (kernel, bucket, dtype-signature) compiles at most once and
the compile cache stays small.

Float policy: on CPU test meshes we enable x64 and compute in float64 (exact
oracle comparisons); on TPU we keep float32 data with float64 host-side final
combines (TPU f64 is software-emulated and slow, and the MXU/VPU want 32-bit).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

def _host_fingerprint() -> str:
    """Host half of the AOT store's namespace (runtime/compileplane.py adds
    device kind/count lazily — reading them here would initialize the
    backend at import time): XLA:CPU executables are compiled for the build
    host's CPU features, so an artifact written on one machine can SIGILL
    on another.  CPU flag set + requested platform + jax version (whose
    executable serialization format drifts) make a foreign host a MISS
    instead of a crash."""
    import hashlib
    import platform as _plat

    feat = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    feat = line
                    break
    except OSError:
        pass
    # the env-requested platform is known without initializing the backend
    feat += "|" + os.environ.get("JAX_PLATFORMS", "")
    feat += "|" + jax.__version__
    h = hashlib.sha256(feat.encode()).hexdigest()[:10]
    return f"{_plat.machine()}-{h}"


# Persistent compile cache, default ON for every backend: a fresh process
# otherwise recompiles the whole kernel set.  The directory is part of the
# cache's key, so it must not move between runs: JAX_COMPILATION_CACHE_DIR
# places it from outside (jax reads that variable itself and this module
# sets no other directory); QUOKKA_JAX_CACHE_DIR is the tests' scratch
# override; otherwise one fixed path inside the checkout.  CACHE_ROOT is
# also the root of the AOT executable store, the plan ledger
# (runtime/compileplane.py) and the strategy/mem/card profile
# stores, so all of them move together.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ROOT = (
    os.environ.get("JAX_COMPILATION_CACHE_DIR")
    or os.environ.get("QUOKKA_JAX_CACHE_DIR")
    or os.path.join(_REPO_ROOT, ".jax_cache")
)
os.makedirs(CACHE_ROOT, exist_ok=True)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", CACHE_ROOT)
# Cache every program: the engine's per-batch kernels are individually fast
# to compile but number in the hundreds per query shape, and the cache-hit
# path costs ~ms.  Override with QUOKKA_JAX_CACHE_MIN_SECS.
jax.config.update(
    "jax_persistent_cache_min_compile_time_secs",
    float(os.environ.get("QUOKKA_JAX_CACHE_MIN_SECS", "0")))
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# Compile counters observe every compilation from process start (listeners
# must exist before the first jit runs; config is the package's first import).
from quokka_tpu.utils import compilestats as _compilestats  # noqa: E402

_compilestats.ensure_registered()

# ---------------------------------------------------------------------------
# Padding buckets
# ---------------------------------------------------------------------------

# MIN_BUCKET / MAX_BUCKET resolve lazily (module __getattr__ below) from
# ops/sigkey — the canonical ladder.  An eager `from quokka_tpu.ops import
# sigkey` here would execute the ops package __init__ (batch, bridge, jax
# array machinery) while config is still half-initialized: the cycle only
# works as long as those modules touch config strictly at call time.


def bucket_size(n: int) -> int:
    """Smallest padding bucket that fits n rows.  Static-shape discipline:
    all kernels see bucketed lengths.  The ladder (ops/sigkey.bucket_rows)
    is pow2 with 4x rung spacing below 64Ki rows, so the compile-key space
    over small intermediates stays half the size of a pure 2x ladder."""
    from quokka_tpu.ops import sigkey

    return sigkey.bucket_rows(n)


def __getattr__(name: str):
    if name in ("MIN_BUCKET", "MAX_BUCKET"):
        from quokka_tpu.ops import sigkey

        return getattr(sigkey, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ---------------------------------------------------------------------------
# Kernel strategy
# ---------------------------------------------------------------------------


def use_hash_tables() -> bool:
    """Whether equality-keyed group-by kernels use the device hash table
    (ops/hashtable.py) instead of the sort-based paths.  Since PR 8 this is
    a thin delegate to the kernel-strategy matrix (ops/strategy.py): env
    overrides (QUOKKA_HASH_TABLES, QK_KERNEL_STRATEGY) > persisted
    per-backend calibration > the original platform gates (on for CPU/GPU
    where scatter/gather is fast, off for TPU where random scatters
    serialize and the multi-operand sort is the idiom)."""
    from quokka_tpu.ops import strategy

    return strategy.choice("groupby") == "hashtable"


def stage_fuse_enabled() -> bool:
    """Whole-stage fusion escape hatch (ops/stagefuse.py): QK_STAGE_FUSE=0
    disables the optimizer's fuse_stages pass so a suspect plan can be
    re-run with per-operator actors.  Read dynamically (not cached at
    import) so one process can plan both variants — the fusion smoke
    compares fused vs unfused results in-process."""
    return os.environ.get("QK_STAGE_FUSE", "1") not in ("0", "false", "no")


def adapt_enabled() -> bool:
    """Runtime adaptive re-partitioning kill switch (planner/adapt.py):
    QK_ADAPT=0 disables both the plan-time eligibility pass and the
    mid-query skew trigger, so a suspect adapted plan can be re-run
    statically.  Read dynamically (not cached at import) so one process can
    run both variants — the adapt smoke compares adaptive vs static
    results in-process."""
    return os.environ.get("QK_ADAPT", "1") not in ("0", "false", "no")


def adapt_min_rows() -> int:
    """Floor on total rows delivered to a join's build edge before the
    skew trigger may fire (QK_ADAPT_MIN_ROWS).  Below this, re-partitioning
    buys nothing — the whole build fits one channel comfortably."""
    try:
        return int(os.environ.get("QK_ADAPT_MIN_ROWS", 1 << 15))
    except ValueError:
        return 1 << 15


def broadcast_bytes_threshold() -> int:
    """Measured-bytes ceiling for the cost-based broadcast-join choice
    (planner/decide.py): a build side whose MEASURED cardprofile bytes fit
    under QK_BROADCAST_BYTES is replicated to every probe channel instead
    of hash-partitioning both sides.  Only consulted when a measured figure
    exists; cold plans keep the row-estimate threshold
    (optimizer.BROADCAST_THRESHOLD)."""
    try:
        return int(os.environ.get("QK_BROADCAST_BYTES", 8 << 20))
    except ValueError:
        return 8 << 20


def replay_retry_deadline_s() -> float:
    """Upper bound on how long a recovering consumer waits for a lost
    object's producer replay before declaring the loss irrecoverable
    (QK_REPLAY_DEADLINE, runtime/engine.py).  The deadline exists so a
    producer that died holding un-replayable state fails the query loudly
    instead of wedging it forever; it is env-tunable because the right
    bound is load-dependent — a 1-core CI box replaying a long exec tape
    under kill-storm chaos legitimately needs minutes, while a test suite
    that *expects* irrecoverable losses wants the verdict in seconds."""
    try:
        return float(os.environ.get("QK_REPLAY_DEADLINE", 600.0))
    except ValueError:
        return 600.0


def use_host_asof() -> bool:
    """Whether the as-of match runs as a native sequential merge on host
    (ops/asof._asof_match_host -> native/columnar.cpp).  Thin delegate to
    the strategy matrix (ops/strategy.py) — host stays the CPU-backend
    default (np.asarray is zero-copy there); TPU *and* GPU resolve to a
    device kernel since every host column would pay a blocking d2h copy.
    QUOKKA_HOST_ASOF / QK_KERNEL_STRATEGY override; calibration can flip
    the CPU pick to the device searchsorted kernel when measured faster."""
    from quokka_tpu.ops import strategy

    return strategy.choice("asof") == "host"


# ---------------------------------------------------------------------------
# Dtype policy
# ---------------------------------------------------------------------------


def _platform() -> str:
    return jax.default_backend()


def x64_enabled() -> bool:
    return bool(jax.config.read("jax_enable_x64"))


def float_dtype():
    """float64 when x64 is on (CPU test meshes), else float32 (TPU)."""
    return jnp.float64 if x64_enabled() else jnp.float32


def int_dtype():
    return jnp.int64 if x64_enabled() else jnp.int32


# Default batch target: how many rows a reader should aim to emit per batch.
DEFAULT_BATCH_ROWS = int(os.environ.get("QUOKKA_TPU_BATCH_ROWS", 1 << 20))

# Executor/runtime defaults (mirrors the reference's exec_config knobs,
# pyquokka/df.py:63-66, rebuilt as a flat dict).
DEFAULT_EXEC_CONFIG = {
    "hbq_path": "/tmp/quokka_tpu_spill/",
    "fault_tolerance": False,
    "memory_limit": 0.25,
    "max_pipeline_batches": 30,
    "checkpoint_interval": None,
    "checkpoint_bucket": None,
    "max_pipeline": 4,
    "batch_attempt": 4,
}


# ---------------------------------------------------------------------------
# Spill tier (external sort / grace join) — reference sql_executors.py:88-188
# (SuperFastSortExecutor) and 456-515 (DiskBuildProbeJoinExecutor).
# Thresholds are ROWS accumulated before an operator switches to disk; the
# defaults keep small queries fully in memory.  Tests lower them to force the
# spill paths on tiny data.
# ---------------------------------------------------------------------------
# Shuffle data plane
# ---------------------------------------------------------------------------
# Masked-split cap: a partition split stays in masked-view mode (zero host
# syncs, shared column buffers) while n_parts * padded_len is at or below
# this; past it the one-kernel compacted split runs instead (bounds the
# downstream padded-row inflation for very wide fan-outs).
SHUFFLE_MASKED_CAP = int(os.environ.get("QUOKKA_SHUFFLE_MASKED_CAP", 1 << 25))
# Async HBQ spill (Engine.push): background threads doing the device->host
# copy + checksummed disk write off the critical path.  QK_SPILL_ASYNC=0
# restores the old synchronous spill; QK_SPILL_POOL sizes the thread pool
# (1 keeps spill-file write order identical to submission order, which the
# seeded chaos corruption streams key off); QK_SPILL_INFLIGHT bounds the
# device batches pinned by pending spills.
# streaming plane: minimum seconds between source polls of an idle standing
# query (bounds filesystem stats when no data is arriving)
STREAM_POLL_S = float(os.environ.get("QK_STREAM_POLL_S", "0.05"))
SPILL_ASYNC = os.environ.get("QK_SPILL_ASYNC", "1") not in ("0", "false", "no")
SPILL_POOL = int(os.environ.get("QK_SPILL_POOL", "1"))
SPILL_INFLIGHT = int(os.environ.get("QK_SPILL_INFLIGHT", "4"))

SPILL_SORT_ROWS = int(os.environ.get("QUOKKA_TPU_SPILL_SORT_ROWS", 1 << 22))
SPILL_MERGE_CHUNK_ROWS = int(os.environ.get("QUOKKA_TPU_SPILL_CHUNK_ROWS", 1 << 16))
SPILL_JOIN_BUILD_ROWS = int(os.environ.get("QUOKKA_TPU_SPILL_JOIN_ROWS", 1 << 22))
SPILL_JOIN_FANOUT = int(os.environ.get("QUOKKA_TPU_SPILL_JOIN_FANOUT", 8))
SPILL_DIR = os.environ.get("QUOKKA_TPU_SPILL_DIR", "/tmp/quokka_tpu_spill")
