"""Compile plane: canonical signature ladder, AOT export/import round
trips, corrupt-artifact fallback, plan-ledger prewarm, and the signature
cardinality budget for a Q3-shaped plan."""

import functools
import json
import os
import threading

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from quokka_tpu import config
from quokka_tpu.ops import sigkey
from quokka_tpu.runtime import compileplane


# ---------------------------------------------------------------------------
# signature ladder
# ---------------------------------------------------------------------------


def test_ladder_rungs_are_pow2_and_monotone():
    prev = 0
    for n in range(1, 200000, 997):
        b = sigkey.bucket_rows(n)
        assert b >= n
        assert b & (b - 1) == 0, f"bucket {b} not a power of two"
        assert b >= prev or n <= prev
        prev = b


def test_ladder_coarse_below_knee():
    # 4x rung spacing below the knee: 2048 and 4096 share the 4096 rung
    assert sigkey.bucket_rows(2048) == sigkey.bucket_rows(4096) == 4096
    assert sigkey.bucket_rows(8192) == 16384
    # above the knee the ladder is pure pow2 (padding waste is real there)
    assert sigkey.bucket_rows((1 << 16) + 1) == 1 << 17
    assert sigkey.bucket_rows((1 << 20) + 1) == 1 << 21


def test_ladder_bounds():
    assert sigkey.bucket_rows(0) == sigkey.MIN_BUCKET
    assert sigkey.bucket_rows(sigkey.MAX_BUCKET) == sigkey.MAX_BUCKET
    with pytest.raises(ValueError):
        sigkey.bucket_rows(sigkey.MAX_BUCKET + 1)


def test_config_bucket_size_delegates():
    assert config.bucket_size(3000) == sigkey.bucket_rows(3000)
    assert config.MIN_BUCKET == sigkey.MIN_BUCKET


def test_batch_sig_drops_kind_keeps_dtype():
    from quokka_tpu.ops.batch import NumCol

    d = NumCol(jnp.zeros(256, jnp.int32), "d")
    i = NumCol(jnp.zeros(256, jnp.int32), "i")
    # a date and an int column of the same device dtype trace to the same
    # program (kinds re-derive from dtypes inside the trace): canonical
    # signatures must differ only by name
    assert sigkey.col_sig("a", d)[1:] == sigkey.col_sig("a", i)[1:]
    # dtype and wide-limb presence DO decide the program
    w = NumCol(jnp.zeros(256, jnp.int32), "i", hi=jnp.zeros(256, jnp.int32))
    assert sigkey.col_sig("a", i) != sigkey.col_sig("a", w)


def test_make_key_records_in_ledger():
    sigkey.reset_ledger()
    k1 = sigkey.make_key("t_kind", 256, "a")
    sigkey.make_key("t_kind", 256, "a")  # duplicate: one ledger entry
    sigkey.make_key("t_kind", 1024, "a")
    assert sigkey.ledger_counts()["t_kind"] == 2
    assert k1 in sigkey.ledger_keys("t_kind")


# ---------------------------------------------------------------------------
# AOT persistence round trip
# ---------------------------------------------------------------------------


@pytest.fixture
def aot_dir(tmp_path, monkeypatch):
    d = tmp_path / "aotcache"
    monkeypatch.setenv("QUOKKA_AOT_CACHE_DIR", str(d))
    monkeypatch.setenv("QUOKKA_AOT_CACHE", "1")
    yield d


# Unique per test run: the shared XLA test cache must MISS on these toy
# programs (an executable the XLA persistent cache answered is never
# persisted — see test_xla_cache_answered_compile_is_not_persisted — and
# the AOT round-trip tests would have nothing to test).
_RUN_TOKEN = int.from_bytes(os.urandom(4), "little") % 100_000


def _toy_builder(salt=0):
    import jax

    k = _RUN_TOKEN + salt

    @jax.jit
    def f(x, y):
        return x * 2 + y + k, jnp.sum(x)

    return f


def test_aot_roundtrip_bit_exact(aot_dir):
    key = sigkey.make_key("t_roundtrip", _RUN_TOKEN, 1, ((8,), "float32"))
    args = (jnp.arange(8.0, dtype=jnp.float32),
            jnp.ones(8, dtype=jnp.float32))
    prog = compileplane.acquire(key, functools.partial(_toy_builder, 1), args)
    out1 = prog(*args)
    compileplane.drain_writes()
    files = [f for f in os.listdir(compileplane._aot_dir()) if
             f.endswith(".aot")]
    assert files, "executable was not persisted"

    # a fresh program store (restarted process) must answer from disk
    compileplane.PROGRAMS.pop(key, None)
    prog2 = compileplane.acquire(key, functools.partial(_toy_builder, 1), args)
    assert isinstance(prog2, compileplane.AotProgram)
    out2 = prog2(*args)
    for a, b in zip(out1, out2):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_real_compile_leaves_the_executable_and_nothing_beside_it(aot_dir):
    """The AOT directory holds one ``<key hash>.aot`` per persisted program
    and no sidecar: nothing analyses an executable as it is compiled."""
    key = sigkey.make_key("t_alone", _RUN_TOKEN, 11, ((8,), "float32"))
    args = (jnp.arange(8.0, dtype=jnp.float32),
            jnp.ones(8, dtype=jnp.float32))
    compileplane.acquire(key, functools.partial(_toy_builder, 11), args)
    compileplane.drain_writes()
    left = os.listdir(compileplane._aot_dir())
    assert left and all(f.endswith(".aot") for f in left), left


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(**extra):
    """This process's environment for a child that imports the checkout."""
    env = dict(os.environ, **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO] + [p for p in [env.get("PYTHONPATH", "")] if p])
    return env


_CROSS_PROCESS_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from quokka_tpu.ops import sigkey
from quokka_tpu.runtime import compileplane

assert jax.device_count() == 8, jax.devices()
key = sigkey.make_key("t_xproc", int(sys.argv[1]), ((8,), "float32"))
x = jnp.arange(8.0, dtype=jnp.float32)
builder = lambda: jax.jit(lambda a: (a * 3 + int(sys.argv[1]), jnp.sum(a)))
prog = compileplane.acquire(key, builder, (x,))
out = prog(x)
compileplane.drain_writes()
print(json.dumps({"out": [float(v) for v in out[0]], "sum": float(out[1]),
                  "stats": compileplane.stats()}))
"""


def test_persisted_executable_loads_and_runs_in_second_process(tmp_path):
    """Persist in one process, load AND CALL in a second, both with eight
    local devices: a one-device program must load onto its one device (the
    jax default loads it across all eight and the first call is refused)."""
    import subprocess
    import sys

    # conftest's 8 forced host devices ride along in os.environ
    env = _child_env(QUOKKA_AOT_CACHE_DIR=str(tmp_path / "aot"),
                     QUOKKA_AOT_CACHE="1")

    def run():
        r = subprocess.run(
            [sys.executable, "-c", _CROSS_PROCESS_CHILD, str(_RUN_TOKEN)],
            capture_output=True, text=True, timeout=300, env=env)
        assert r.returncode == 0, r.stderr[-3000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    first, second = run(), run()
    assert first["stats"].get("miss") == 1
    assert second["stats"].get("cache_hit") == 1, second
    assert not second["stats"].get("miss"), second
    assert not second["stats"].get("aot_mismatch"), second
    assert second["out"] == first["out"] and second["sum"] == first["sum"]


def test_xla_cache_answered_compile_is_not_persisted(aot_dir):
    """An executable the XLA persistent cache loaded re-serializes without
    its kernels (it loads, then fails its first run asynchronously), so
    only a real compile reaches the AOT store."""
    args = (jnp.arange(8.0, dtype=jnp.float32),
            jnp.ones(8, dtype=jnp.float32))
    k1 = sigkey.make_key("t_xla_hit", _RUN_TOKEN, 1, ((8,), "float32"))
    k2 = sigkey.make_key("t_xla_hit", _RUN_TOKEN, 2, ((8,), "float32"))
    # same program under two keys: the second compile is an XLA cache hit
    compileplane.acquire(k1, functools.partial(_toy_builder, 7), args)
    compileplane.acquire(k2, functools.partial(_toy_builder, 7), args)
    compileplane.drain_writes()
    assert os.path.exists(compileplane._entry_path(k1))
    assert not os.path.exists(compileplane._entry_path(k2))


def test_runtime_refusal_on_first_call_of_loaded_executable_is_a_mismatch(
        aot_dir):
    """An artifact the process cannot run (written for another device
    layout) costs one ``compile.aot_mismatch`` and a rebuild at the call
    site, never the query."""
    import jax

    class Refuses:
        def __call__(self, *a):
            raise jax.errors.JaxRuntimeError("INVALID_ARGUMENT: 8 shards")

    before = compileplane.stats().get("aot_mismatch", 0)
    prog = compileplane.AotProgram(Refuses())  # no builder: loaded
    with pytest.raises(compileplane.AotMismatch):
        prog(jnp.ones(4))
    assert compileplane.stats().get("aot_mismatch", 0) == before + 1
    # a program that has already answered here is proven: a later runtime
    # error is the query's own (OOM, ...) and must surface
    fresh = compileplane.AotProgram(Refuses(), builder=lambda: None)
    with pytest.raises(jax.errors.JaxRuntimeError):
        fresh(jnp.ones(4))


def test_corrupt_artifact_falls_back_to_fresh_compile(aot_dir):
    key = sigkey.make_key("t_corrupt", _RUN_TOKEN, 2, ((4,), "float32"))
    args = (jnp.arange(4.0, dtype=jnp.float32),
            jnp.ones(4, dtype=jnp.float32))
    prog = compileplane.acquire(key, functools.partial(_toy_builder, 2), args)
    expect = [np.asarray(x) for x in prog(*args)]
    compileplane.drain_writes()
    path = compileplane._entry_path(key)
    assert os.path.exists(path)
    # flip bytes mid-file: the checksummed frame must catch it
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        f.write(b"\xff\xff\xff\xff")
    compileplane.PROGRAMS.pop(key, None)
    prog2 = compileplane.acquire(key, functools.partial(_toy_builder, 2), args)  # never raises
    got = [np.asarray(x) for x in prog2(*args)]
    for a, b in zip(expect, got):
        assert np.array_equal(a, b)
    # the bad file was quarantined (a HEALTHY artifact may legitimately be
    # re-persisted at the same path by the fresh compile's writer)
    assert os.path.exists(path + ".corrupt")


def test_truncated_artifact_falls_back(aot_dir):
    key = sigkey.make_key("t_trunc", _RUN_TOKEN, 3, ((4,), "float32"))
    args = (jnp.arange(4.0, dtype=jnp.float32),
            jnp.ones(4, dtype=jnp.float32))
    compileplane.acquire(key, functools.partial(_toy_builder, 3), args)
    compileplane.drain_writes()
    path = compileplane._entry_path(key)
    with open(path, "r+b") as f:
        f.truncate(10)
    compileplane.PROGRAMS.pop(key, None)
    prog = compileplane.acquire(key, functools.partial(_toy_builder, 3), args)
    out = prog(*args)
    assert np.asarray(out[1]) == np.asarray(args[0]).sum()


def test_aval_mismatch_falls_back_to_jit(aot_dir):
    key = sigkey.make_key("t_mismatch", _RUN_TOKEN, 4, ((8,), "float32"))
    args8 = (jnp.arange(8.0, dtype=jnp.float32),
             jnp.ones(8, dtype=jnp.float32))
    prog = compileplane.acquire(key, functools.partial(_toy_builder, 4), args8)
    assert isinstance(prog, compileplane.AotProgram)
    # same program object called at DIFFERENT shapes (defensive: a key
    # collision must degrade to the jit fallback, not error)
    args4 = (jnp.arange(4.0, dtype=jnp.float32),
             jnp.ones(4, dtype=jnp.float32))
    out = prog(*args4)
    assert np.asarray(out[1]) == 6.0


def test_aot_kernel_call_inside_trace_inlines(aot_dir):
    import jax

    @jax.jit
    def inner(x):
        return x + 1

    @jax.jit
    def outer(x):
        # a compiled executable cannot trace; the guard must route to the
        # plain jitted callable (which inlines)
        return compileplane.aot_kernel_call("t_traced", inner, (x,)) * 2

    out = outer(jnp.arange(4.0))
    assert np.array_equal(np.asarray(out), [2.0, 4.0, 6.0, 8.0])


def test_aot_kernel_call_with_trailing_static(aot_dir):
    import functools

    import jax

    @functools.partial(jax.jit, static_argnames=("k",))
    def topk(x, k):
        return x[:k] + _RUN_TOKEN

    x = jnp.arange(8.0)
    expect = np.asarray(x)[:3] + _RUN_TOKEN
    out = compileplane.aot_kernel_call("t_static", topk, (x,), (3,))
    assert np.array_equal(np.asarray(out), expect)
    compileplane.drain_writes()
    # restart: the persisted executable answers, statics baked in
    key = sigkey.make_key("t_static", sigkey.aval_sig((x,)), 3)
    compileplane.PROGRAMS.pop(key, None)
    compileplane._INSTALLED_HASHES.discard(compileplane.key_hash(key))
    out2 = compileplane.aot_kernel_call("t_static", topk, (x,), (3,))
    assert np.array_equal(np.asarray(out2), expect)
    assert isinstance(compileplane.PROGRAMS[key], compileplane.AotProgram)


# ---------------------------------------------------------------------------
# plan ledger + prewarm
# ---------------------------------------------------------------------------


def test_plan_ledger_roundtrip_and_prewarm(aot_dir):
    key = sigkey.make_key("t_prewarm", _RUN_TOKEN, 5, ((8,), "float32"))
    args = (jnp.arange(8.0, dtype=jnp.float32),
            jnp.ones(8, dtype=jnp.float32))
    fp = "test-plan-fp"
    with compileplane.query_scope(None, fp):
        prog = compileplane.acquire(key, functools.partial(_toy_builder, 5), args)
    expect = [np.asarray(x) for x in prog(*args)]
    compileplane.drain_writes()
    compileplane.flush_plan(fp)
    assert compileplane.key_hash(key) in compileplane.plan_sig_hashes(fp)

    # "restart": drop the in-memory program, prewarm reinstalls from disk
    compileplane.PROGRAMS.pop(key, None)
    compileplane._INSTALLED_HASHES.discard(compileplane.key_hash(key))
    t = compileplane.prewarm_plan(fp, wait=True)
    assert t is not None
    prog2 = compileplane.PROGRAMS[key]
    assert isinstance(prog2, compileplane.AotProgram)
    assert prog2.prewarmed
    got = [np.asarray(x) for x in prog2(*args)]
    for a, b in zip(expect, got):
        assert np.array_equal(a, b)


def test_flush_plan_merges_not_overwrites(aot_dir, monkeypatch):
    fp = "test-merge-fp"
    path = compileplane._plan_path(fp, create=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"v": 1, "sigs": ["deadbeef"]}, f)
    with compileplane.query_scope(None, fp):
        compileplane.note_program(("t_merge", 1))
    compileplane.flush_plan(fp)
    sigs = compileplane.plan_sig_hashes(fp)
    assert "deadbeef" in sigs
    assert compileplane.key_hash(("t_merge", 1)) in sigs


def test_per_query_counters_through_scope(aot_dir):
    from quokka_tpu import obs

    counters = {ev: obs.REGISTRY.counter(f"compile.{ev}.test-q")
                for ev in ("cache_hit", "miss", "prewarm_hit")}
    key = sigkey.make_key("t_counters", _RUN_TOKEN, 6, ((8,), "float32"))
    args = (jnp.arange(8.0, dtype=jnp.float32),
            jnp.ones(8, dtype=jnp.float32))
    with compileplane.query_scope(counters, None):
        compileplane.acquire(key, functools.partial(_toy_builder, 6), args)
    assert counters["miss"].value == 1
    compileplane.drain_writes()
    compileplane.PROGRAMS.pop(key, None)
    with compileplane.query_scope(counters, None):
        compileplane.acquire(key, functools.partial(_toy_builder, 6), args)
    assert counters["cache_hit"].value == 1
    obs.REGISTRY.remove(*(c.name for c in counters.values()))


@pytest.mark.parametrize("jax_dir,quokka_dir,expect", [
    ("from_jax", None, "from_jax"),         # placed from outside
    ("from_jax", "from_quokka", "from_jax"),  # the tests' override loses
    (None, "from_quokka", "from_quokka"),   # the tests' scratch override
    (None, None, os.path.join(_REPO, ".jax_cache")),  # fixed, in-checkout
])
def test_cache_root_placement(tmp_path, jax_dir, quokka_dir, expect):
    """JAX_COMPILATION_CACHE_DIR places jax's cache AND every store under
    config.CACHE_ROOT; unset, both land on one fixed path in the checkout
    (the directory is part of the cache key: it must never move)."""
    import subprocess
    import sys

    env = _child_env()
    for k in ("JAX_COMPILATION_CACHE_DIR", "QUOKKA_JAX_CACHE_DIR",
              "QUOKKA_AOT_CACHE_DIR", "QK_STRATEGY_DIR"):
        env.pop(k, None)
    if jax_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / jax_dir)
    if quokka_dir:
        env["QUOKKA_JAX_CACHE_DIR"] = str(tmp_path / quokka_dir)
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, jax\n"
         "from quokka_tpu import config\n"
         "from quokka_tpu.ops import strategy\n"
         "from quokka_tpu.runtime import compileplane\n"
         "print(json.dumps([jax.config.jax_compilation_cache_dir,"
         " config.CACHE_ROOT, compileplane._root_dir(), strategy._dir()]))"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-3000:]
    jax_cache, root, aot, strat = json.loads(r.stdout.strip().splitlines()[-1])
    want = expect if os.path.isabs(expect) else str(tmp_path / expect)
    assert jax_cache == want and root == want
    assert aot == os.path.join(want, "aot")
    assert strat == os.path.join(want, "strategy")


def test_backend_fingerprint_shape():
    fp = compileplane.backend_fingerprint()
    assert fp.count("-") >= 2
    # a different topology is a different namespace (directory), so a
    # foreign artifact can never be loaded
    assert compileplane.backend_fingerprint() == fp  # stable within process


# ---------------------------------------------------------------------------
# signature cardinality budget (Q3-shaped plan)
# ---------------------------------------------------------------------------

# Checked-in budget: distinct fused/kernel program keys a Q3-shaped
# join+join+groupby query may create.  Signature fragmentation once cost
# 11-15 REAL compiles per join query; the canonical
# ladder + normalized column signatures hold the whole per-kind key space
# to this budget.  If this fails after a change, either the change leaks
# signature cardinality (fix it) or it legitimately adds a program kind
# (bump the budget in the same PR that argues why).
SIG_BUDGETS = {
    "partial_agg": 4,
    "partial_agg_small": 2,
    "predicate": 3,
    "pk_probe_sorted": 4,
    # the sort branch's direct-address table (ISSUE 31; read by
    # tests/test_join_direct.py, which runs this plan with join_build=sort).
    # The probe is keyed on (table rung, probe rung) where the search is on
    # (build rung, probe rung, steps): the same two dimensions, the same 4
    # (one table rung x the probe rungs `_coalesce` makes of four 16,384-row
    # scan batches).  The build is keyed on (build rung, table rung): one a
    # join, 2 where the two channels' shares of the build straddle a rung.
    "pk_probe_direct": 4,
    "pk_direct_build": 2,
    "ht_probe": 4,
    "gather": 24,
    "fused_concat": 10,
}


@pytest.mark.parametrize("unused", [0])
def test_q3_shaped_plan_signature_budget(tmp_path, unused):
    import pyarrow.parquet as pq

    from quokka_tpu import QuokkaContext
    from quokka_tpu.expression import col

    r = np.random.default_rng(7)
    n_fact, n_dim = 60_000, 5_000
    fact = pa.table({
        "fk": r.integers(0, n_dim, n_fact).astype(np.int64),
        "v": r.integers(0, 1000, n_fact).astype(np.int64),
        "flag": r.integers(0, 4, n_fact).astype(np.int64),
    })
    dim = pa.table({
        "pk": np.arange(n_dim, dtype=np.int64),
        "grp": r.integers(0, 64, n_dim).astype(np.int64),
    })
    fp_, dp_ = str(tmp_path / "fact.parquet"), str(tmp_path / "dim.parquet")
    pq.write_table(fact, fp_, row_group_size=1 << 14)
    pq.write_table(dim, dp_)

    sigkey.reset_ledger()
    ctx = QuokkaContext(io_channels=2, exec_channels=2)
    out = (
        ctx.read_parquet(fp_)
        .filter(col("flag") < 3)
        .join(ctx.read_parquet(dp_), left_on="fk", right_on="pk")
        .groupby("grp")
        .agg_sql("sum(v) as sv, count(*) as n")
        .collect()
    )
    assert len(out) > 0
    counts = sigkey.ledger_counts()
    over = {k: (n, SIG_BUDGETS[k]) for k, n in counts.items()
            if k in SIG_BUDGETS and n > SIG_BUDGETS[k]}
    assert not over, (
        f"signature cardinality over budget: {over} (all: {counts}) — "
        "a cache-key dimension fragmented; derive it through ops/sigkey"
    )
