"""A compaction's live rows' indices as one sort of a packed key
(``kernels.live_indices``, the ``compact`` row of the strategy matrix):
equal to ``jnp.nonzero(..., fill_value=0)`` value for value; the same
batches out of ``kernels.compact``, the fused concat and the asof row
buffer under either strategy; the counters that say which ran; and a served
Q3 and Q18 under the sort equal to the benchmark's references."""

import os
import sys
import types

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from quokka_tpu import QuokkaContext
from quokka_tpu.obs import opstats, querylog
from quokka_tpu.ops import asof, bridge, kernels, strategy
from quokka_tpu.runtime import compileplane
from quokka_tpu.service import QueryService

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TPU = "groupby=sort,join_build=sort,compact=sort"


@pytest.fixture(autouse=True)
def _fresh(tmp_path, monkeypatch):
    """No strategy noted, and a program store of the test's own: what is
    compared is the two strategies' answers, not executables another
    process persisted (PERF.md section 7: a loaded CPU executable can fail
    at its result's read)."""
    monkeypatch.setenv("QUOKKA_AOT_CACHE_DIR", str(tmp_path / "aot"))
    monkeypatch.setattr(compileplane, "PROGRAMS", {})
    strategy.reset()
    strategy.reset_used()
    yield
    strategy.reset()
    strategy.reset_used()


# -- the index against jnp.nonzero -------------------------------------------


def _index_cases():
    cases = []
    for n in (1000, 65536, 1 << 20):
        for live in (0.0, 0.015, 0.25, 0.5, 1.0):
            count = int(round(n * live))
            sizes = {"below": max(1, count // 2), "at": max(1, count),
                     "above": count + 7, "past_the_mask": 2 * n}
            for where, size in sizes.items():
                if n == 1 << 20 and live in (0.0, 0.5) and where != "at":
                    continue  # the long mask's corners: the shorter cover them
                cases.append(pytest.param(
                    n, live, size, id=f"{n}-{live}-{where}"))
    return cases


@pytest.mark.parametrize("n, live, size", _index_cases())
def test_live_indices_equal_nonzero(n, live, size):
    r = np.random.default_rng(n + int(live * 1000))
    valid = r.permutation(n) < int(round(n * live))
    want = np.asarray(jnp.nonzero(jnp.asarray(valid), size=size,
                                  fill_value=0)[0])
    got = np.asarray(kernels._compact_idx(jnp.asarray(valid), size, "sort"))
    assert got.shape == want.shape == (size,)
    np.testing.assert_array_equal(got, want)


# -- the same batches under either strategy ----------------------------------


@pytest.fixture
def slots_noted(monkeypatch):
    """The ``compact_*_slots`` figures noted while the test runs."""
    seen = []
    note = opstats.note

    def spy(**figures):
        seen.extend((k, v) for k, v in figures.items()
                    if k.startswith("compact_"))
        note(**figures)

    monkeypatch.setattr(opstats, "note", spy)
    return seen


def _table(r, n):
    words = np.array(["ash", "birch", "cedar", "fir"], dtype=object)
    return pa.table({
        "i": r.integers(-1000, 1000, n).astype(np.int64),
        "f": r.uniform(0, 1, n),
        "s": pa.array(words[r.integers(0, 4, n)]),
    })


def _masked(r, n, live):
    b = bridge.arrow_to_device(_table(r, n))
    mask = jnp.asarray(r.uniform(0, 1, b.padded_len) < live)
    return kernels.apply_mask(b, mask)


def _frame(batch):
    return bridge.device_to_arrow(batch).to_pandas()


def _under_each(monkeypatch, run):
    """``run()``'s frames under compact=nonzero and compact=sort."""
    out = {}
    for method in ("nonzero", "sort"):
        monkeypatch.setenv("QK_KERNEL_STRATEGY", f"compact={method}")
        strategy.reset_used()
        out[method] = run()
        assert strategy.used_snapshot()["compact"] == method
    return out


def _assert_equal(frames):
    a, b = frames["nonzero"], frames["sort"]
    if not isinstance(a, list):
        a, b = [a], [b]
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert len(x) > 0
        assert x.equals(y)


@pytest.mark.parametrize("live", [0.015, 0.3, 1.0])
def test_compact_gives_the_same_batch(live, monkeypatch, slots_noted):
    padded = []

    def run():
        r = np.random.default_rng(39)
        b = _masked(r, 5000, live)
        padded.append(b.padded_len)
        return _frame(kernels.compact(b))

    _assert_equal(_under_each(monkeypatch, run))
    assert slots_noted == [("compact_nonzero_slots", padded[0]),
                           ("compact_sort_slots", padded[1])]


@pytest.mark.parametrize("parts", [2, 3])
def test_fused_concat_gives_the_same_batch(parts, monkeypatch, slots_noted):
    captured = []
    fused = bridge._try_fused_concat

    def spy(*a):
        out = fused(*a)
        captured.append(out is not None)
        return out

    monkeypatch.setattr(bridge, "_try_fused_concat", spy)

    padded = []

    def run():
        r = np.random.default_rng(parts)
        batches = [_masked(r, n, live) for n, live in
                   ((3000, 0.4), (700, 0.9), (5000, 0.05))[:parts]]
        padded.append(sum(b.padded_len for b in batches))
        out = bridge.concat_batches(batches)
        assert out.nrows == sum(b.count_valid() for b in batches)
        return _frame(out)

    _assert_equal(_under_each(monkeypatch, run))
    assert captured == [True, True], "the fused path ran"
    assert slots_noted == [("compact_nonzero_slots", padded[0]),
                           ("compact_sort_slots", padded[1])]


def _buffer(r):
    """A row buffer of 8,192 slots with three parts appended, a third of
    its rows dropped."""
    like = _masked(r, 2000, 1.0)
    buf = asof.RowBuffer(like, 8192)
    for n, live in ((2000, 0.8), (1500, 0.5), (2000, 1.0)):
        buf.append(_masked(r, n, live))
    buf.keep(jnp.asarray(r.uniform(0, 1, buf.capacity) < 0.66))
    return buf


def test_row_buffer_take_gives_the_same_chunk(monkeypatch, slots_noted):
    def run():
        r = np.random.default_rng(28)
        buf = _buffer(r)
        ready = buf.valid & jnp.asarray(r.uniform(0, 1, buf.capacity) < 0.5)
        n = int(np.asarray(ready).sum()) - 3
        chunk = buf.take(ready, n, 4096)
        assert chunk.count_valid() == n
        return [_frame(chunk), _frame(buf.view())]

    _assert_equal(_under_each(monkeypatch, run))
    assert ("compact_sort_slots", 8192) in slots_noted


def test_row_buffer_make_room_gives_the_same_rows(monkeypatch, slots_noted):
    def run():
        r = np.random.default_rng(29)
        buf = _buffer(r)
        rows = buf.rows
        assert buf.end + 4096 > buf.capacity  # the next part closes holes
        buf.append(_masked(r, 3000, 0.7))
        assert buf.rows > rows and buf.capacity == 8192
        return _frame(buf.view())

    _assert_equal(_under_each(monkeypatch, run))
    assert ("compact_sort_slots", 8192) in slots_noted


# -- served Q3 and Q18 under the sort ----------------------------------------


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The benchmark's queries and the comparison that decides ``correct``,
    read and not touched, over its generators' tables at a small size."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check, spec, tables

        q3 = spec.load_module("queries", "q3")
        q18 = spec.load_module("queries", "q18")
    finally:
        sys.path.remove(BENCH)
    root = tmp_path_factory.mktemp("compact_sort")
    return types.SimpleNamespace(
        check=check, q3=q3, q18=q18,
        q3_paths=tables.ensure(str(root / "q3"), "tpch", {"sf": 0.02}, 39),
        q18_paths=tables.ensure(str(root / "q18"), "tpch_q18", {"sf": 0.02},
                                38))


@pytest.mark.parametrize("query, params", [
    ("q3", {"segment": "BUILDING", "date": "1995-03-15"}),
    ("q18", {"quantity": 280}),
])
def test_served_query_under_the_sort_equals_the_reference(
        query, params, bench, monkeypatch):
    monkeypatch.setenv("QK_KERNEL_STRATEGY", TPU)
    mod, paths = getattr(bench, query), getattr(bench, f"{query}_paths")
    svc = QueryService(pool_size=2)
    try:
        handle = svc.submit(mod.build(
            QuokkaContext(io_channels=3, exec_channels=2), paths, params))
        got = handle.to_df(timeout=600)
    finally:
        svc.shutdown()
    ref = mod.reference(paths, params)
    assert len(ref) > 0
    numbers, _ = bench.check.compare([got], ref, mod.SORT_KEYS, mod.EXACT)
    assert bench.check.judge(numbers, mod.LIMITS), numbers
    rec = [r for r in querylog.records() if r["q"] == handle.query_id][0]
    assert rec["compact_sort_slots"] > 0 and rec["compact_nonzero_slots"] == 0
