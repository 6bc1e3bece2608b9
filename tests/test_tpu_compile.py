"""The main path's kernels compile for a TPU v5e chip at the SF1 bucket.

No chip is attached here: the TPU's compiler is installed and compiles for a
DESCRIBED ``v5e:2x2`` device, so whatever it would refuse on the chip (a
program that does not fit HBM, an unsupported dtype, a sort it cannot
lower) fails this file at no chip time.  Shapes are the ones
``chip_smoke.py`` drives at SF 1: ``config.DEFAULT_BATCH_ROWS`` = 1<<20
rows, 32-bit dtypes (x64 is OFF on the chip; pytest turns it on, so every
lowering here happens under ``jax.enable_x64(False)``), the kernel
strategies the TPU picks (sort group-by, sorted join build, sort asof; the
searchsorted asof the GPU picks stays compiled beside it).  A compile that passes is not a chip run.

Only one process at a time may load the TPU's library, so the topology is
described inside a module-scoped fixture (never at import, in a ``skipif``
or in ``parametrize``) and every test of the chip's compiler lives in this
one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest
from jax.sharding import SingleDeviceSharding

from quokka_tpu import config

N = config.DEFAULT_BATCH_ROWS  # the bucket one SF1 scan batch lands in


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """A builder of ShapeDtypeStructs on one described chip, with the
    persistent cache off around the module (an executable compiled for a
    described device is written to the cache but cannot be read back
    without a chip: the next run would warn and compile again)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def sds(dtype, shape=N):
        shape = (shape,) if isinstance(shape, int) else tuple(shape)
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one)

    yield sds
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _compile(jit_fn, *args, **static):
    """Lower as the chip does (x64 off), compile with the chip's compiler,
    and hold the program's device bytes (args + outputs + temps) to one
    chip's HBM."""
    with jax.enable_x64(False):
        compiled = jit_fn.lower(*args, **static).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < 16 << 30, f"{total} bytes do not fit one v5e chip"
    return compiled


def test_compact_and_split(chip):
    from quokka_tpu.ops import kernels

    _compile(kernels._compact_idx, chip(bool), out_size=N >> 2)
    _compile(kernels._partition_ids, (chip("int32"),), n_parts=2)
    _compile(kernels._split_masks, chip("int32"), chip(bool), n_parts=2)
    _compile(kernels._mask_and_count, chip(bool), chip(bool))


def _indexed_ops(compiled):
    text = compiled.as_text()
    return {op: len(re.findall(rf"\b{op}\(", text))
            for op in ("sort", "scatter", "gather")}


def test_sort_groupby(chip):
    """The sort group-by the TPU takes where the CPU takes a hash table:
    one key limb, a float sum and a count (the tick query's per-symbol
    aggregate at one scan batch).  Sort, scans, sort: nothing indexed."""
    from quokka_tpu.ops import kernels

    compiled = _compile(
        kernels.sorted_groupby, (chip("int32"),),
        (chip("float32"), chip("int32")), ("sum", "count"), chip(bool))
    assert _indexed_ops(compiled) == {"sort": 2, "scatter": 0, "gather": 0}


def test_sort_groupby_at_the_h2o_q5_shape(chip):
    """``h2o_g1_1e7.q5_s2``'s partial and its largest merge: one int32 key
    limb, sums of two int32 columns and a float32 one over 1<<20 slots.
    What the chip's trace charges it is in the module's text.  Until PR 33
    one sort, five ``scatter``s (the segment reductions, 8.8-9.2 ms each,
    sorted ids or not) and three ``gather``s (the values by the
    permutation, 7.3-7.5 ms each): 61.4 ms a batch.  Since PR 33 (PERF.md
    section 6) the values ride a six-operand sort, the reductions are
    prefix sums and one segmented scan, and a five-operand sort on one key
    compacts to rank order: two sorts and no indexed op, 4.99 ms a batch
    on the chip (the sorts 2.34 and 1.92 ms, three ``cumsum``s 0.18 each,
    the float sum's 20 shifted steps under 0.1)."""
    from quokka_tpu.ops import kernels

    compiled = _compile(
        kernels.sorted_groupby, (chip("int32"),),
        (chip("int32"), chip("int32"), chip("float32")),
        ("sum", "sum", "sum"), chip(bool))
    assert _indexed_ops(compiled) == {"sort": 2, "scatter": 0, "gather": 0}


def test_sort_and_top_k(chip):
    from quokka_tpu.ops import kernels

    _compile(kernels._sort_perm, (chip("float32"),), chip(bool))
    _compile(kernels._prefix_mask, chip(bool))


def test_join_sorted_build_and_probe(chip):
    """Q3's orders build side (1.5M rows over two channels: the 1<<19
    bucket) sorted once, probed by a lineitem batch."""
    from quokka_tpu.ops import join

    b = N >> 1
    _compile(join._sort_build_keys, (chip("int32", b),), chip(bool, b))
    _compile(join._pk_probe_sorted, (chip("int32", b),), chip("int32", b),
             chip("int32", ()), (chip("int32"),), chip(bool),
             steps=b.bit_length())
    # the same build's direct-address table (ISSUE 31): order keys span
    # 6.0M, the 1<<23 rung.  What the claim rests on, read off the compiled
    # text: the probe is ONE gather of the probe's length and no loop; the
    # build is ONE scatter the compiler was told is sorted and unique (what
    # it costs, only the chip says: PERF.md section 6, PR 31), and no sort
    t = 8 * N
    scalar = chip("int32", ())
    probe = _compile(join._pk_probe_direct, chip("int32", t), scalar, scalar,
                     chip("int32"), chip(bool)).as_text()
    assert len(re.findall(r" gather\(", probe)) == 1
    assert re.search(rf"s32\[{N}\]\S* gather\(", probe)
    assert not re.search(r" (while|sort|scatter)\(", probe)
    build = _compile(join._pk_direct_build, chip("int32", b),
                     chip("int32", b), scalar, scalar, size=t).as_text()
    scatters = re.findall(r" scatter\(.*", build)
    assert len(scatters) == 1
    assert "indices_are_sorted=true, unique_indices=true" in scatters[0]
    assert not re.search(r" (while|sort|gather)\(", build)


def test_join_search_on_a_two_column_key_at_the_q9_shape(chip):
    """Q9's partsupp build (800 K rows over two channels: the 1<<19 bucket,
    two key limbs) searched by what the semi join leaves of a lineitem
    batch once it is compacted (ISSUE 34: the 65,536 rung): a gather a limb
    a halving and the last lookup, none of the probe's length times the
    build's."""
    from quokka_tpu.ops import join

    b, p = N >> 1, 1 << 16
    limbs = (chip("int32", b), chip("int32", b))
    text = _compile(join._pk_probe_sorted, limbs, chip("int32", b),
                    chip("int32", ()), (chip("int32", p), chip("int32", p)),
                    chip(bool, p), steps=b.bit_length()).as_text()
    assert not re.search(r" (while|sort|scatter)\(", text)
    assert re.search(rf"s32\[{p}\]\S* gather\(", text)


def test_asof_searchsorted(chip):
    """The asof kernels the TPU takes (the CPU default is the host merge)
    at the shapes of ``ticks_1d``: a channel's quote buffer at the 8M rung
    over the table's 6.0M rows, sorted by (symbol hash hi, lo, time) and
    probed by one chunk of 1<<18 trades; and the program that appends one
    1<<20-row part (time, symbol codes through a remap table, bid, the
    symbol's two hash limbs) to it."""
    from quokka_tpu.ops import asof

    q, chunk = 8 * N, N >> 2
    ops = (chip("int32", q),) * 3
    _compile(asof._ss_sort_quotes, ops, chip(bool, q))
    _compile(asof._ss_probe, ops, chip("int32", q), chip("int32", ()),
             (chip("int32", chunk),) * 3, chip(bool, chunk),
             steps=q.bit_length(), upper=True, nkey=2)
    cols = ("int32", "int32", "float32", "int32", "int32")
    _compile(asof.append_kernel(), tuple(chip(d, q) for d in cols),
             chip(bool, q), tuple(chip(d) for d in cols), chip(bool),
             tuple(chip("int32", 128 if i == 1 else 0) for i in range(5)),
             chip("int32", ()))


def test_asof_sort_match(chip):
    """The asof match the TPU takes since PR 35, at ``ticks_1d``'s flush: a
    chunk of 1<<18 trade slots merged with a channel's 8M-slot quote buffer
    (two symbol hash limbs, one int32 time; the side, the validity and the
    row index share a fourth operand) as ONE program.  What the chip
    charges by the element is not in its text: no ``scatter``, no loop (a
    ``cummax`` that lowered to ``associative_scan``'s ``while`` would be
    one), the merged sort and the compacting sort and no third, and no
    ``gather`` wider than the chunk (the quote's row, for the trade slots
    alone).  On the chip 50-80 ms a flush where the quote sort and the
    24-halving search were 38 + 265 (PERF.md section 6, PR 35)."""
    from quokka_tpu.ops import asof

    q, chunk = 8 * N, N >> 2
    side = lambda n: ((chip("int32", n),) * 2, (chip("int32", n),),  # noqa: E731
                      chip(bool, n))
    text = _compile(asof.match_kernel(), *side(chunk), *side(q),
                    forward=False).as_text()
    assert "jit__asof_match" in text.splitlines()[0]
    assert not re.search(r"\b(scatter|while)\(", text)
    assert len(re.findall(r"\bsort\(", text)) == 2
    gathers = re.findall(r"(\w+)\[([\d,]*)\]\S* gather\(", text)
    assert gathers, "the quote's row is gathered for the chunk"
    for dtype, dims in gathers:
        assert (dtype, dims) == ("s32", str(chunk)), (dtype, dims)


def test_pack_decode(chip):
    """The wire->logical decode program for a lineitem-shaped scan batch:
    narrowed ints, a dictionary float, a full float, a bool, the validity
    count."""
    from quokka_tpu.ops import pack

    layout = (
        ("valid", N),
        ("widen", "int32", (N,)),   # l_orderkey, offset-encoded
        ("widen", "int32", (N,)),   # l_shipdate (date32) as uint16 + bias
        ("dict", (N,)),             # l_discount: 11 distinct values
        ("pass",),                  # l_extendedprice
        ("bool", (N,)),
    )
    wires = (
        chip("int32", 1),
        chip("uint16"), chip("int32", 1),
        chip("uint16"), chip("int32", 1),
        chip("uint8"), chip("float32", 16),
        chip("float32"),
        chip("uint8"),
    )
    _compile(pack._build_decode(layout), wires)


def test_fused_q1_stage(chip, tmp_path, monkeypatch):
    """The whole-stage Q1 partial aggregate (ops/fuse.py small-key path,
    one one-hot matmul on the MXU): the engine builds it for a toy Q1 here,
    and the SAME builder is lowered at the SF1 bucket in the chip's dtypes.
    The plan holds an integer partial (avg's sum(__nncount(x))): the
    compiled module must reduce it over the one-hot and hold no scatter
    (three scatter-adds were 98 % of this program's 27.5 ms a batch on the
    chip), without writing the rows x buckets integer select out."""
    from quokka_tpu import QuokkaContext
    from quokka_tpu.runtime import compileplane

    captured = {}
    acquire = compileplane.acquire

    def recording_acquire(key, builder, args, lowerer=None):
        if key[0] == "partial_agg_small":
            captured[key] = (builder, args)
        return acquire(key, builder, args, lowerer)

    monkeypatch.setattr(compileplane, "acquire", recording_acquire)
    monkeypatch.setenv("QUOKKA_AOT_CACHE_DIR", str(tmp_path / "aot"))
    # the TPU's group-by strategy: under the CPU's default (hashtable) the
    # engine would hand over _build_small_scatter, which no chip runs
    monkeypatch.setenv("QK_KERNEL_STRATEGY", "groupby=sort")
    # a fresh program store: a program another test already installed would
    # never reach acquire
    monkeypatch.setattr(compileplane, "PROGRAMS", {})
    from quokka_tpu.ops import fuse

    monkeypatch.setattr(fuse, "_FUSED_PROGRAMS", compileplane.PROGRAMS)
    r = np.random.default_rng(0)
    n = 1000
    t = pa.table({
        "l_returnflag": np.array(["A", "N", "R"])[r.integers(0, 3, n)],
        "l_linestatus": np.array(["F", "O"])[r.integers(0, 2, n)],
        "l_quantity": r.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": r.uniform(900, 100000, n).round(2),
        "l_discount": r.integers(0, 11, n) / 100.0,
        "l_tax": r.integers(0, 9, n) / 100.0,
    })
    got = (QuokkaContext().from_arrow(t)
           .groupby(["l_returnflag", "l_linestatus"])
           .agg_sql("sum(l_quantity) as q, "
                    "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) "
                    "as charge, avg(l_discount) as d, count(*) as n")
           .collect())
    assert len(got) == 6 and captured, (got, list(captured))
    key, (builder, args) = next(iter(captured.items()))

    rows = args[-1].shape[0]  # the toy batch's padded length (valid mask)

    def at_sf1(a):
        # row arrays at the SF1 bucket, 64-bit narrowed to the chip's 32;
        # small operands (tables, empty hi limbs) as they are
        shape = (N,) + a.shape[1:] if a.shape[:1] == (rows,) else a.shape
        dtype = {"float64": "float32", "int64": "int32"}.get(
            str(a.dtype), str(a.dtype))
        return chip(dtype, shape)

    *_, pre_exprs, _partials, use_tables, form = key
    assert (use_tables, form) == (False, "onehot_reduce"), key
    assert any("__nncount" in sql for _, sql in pre_exprs), key
    compiled = _compile(builder(), *jax.tree_util.tree_map(at_sf1, args))
    assert "scatter" not in compiled.as_text()
    # the scatter form read 0 bytes of HBM temporaries here (the row arrays
    # and the pred[rows, 17] one-hot live in the compiler's fast memory);
    # a written-out s32[rows, 16] select would be 64 MiB
    assert compiled.memory_analysis().temp_size_in_bytes <= 1 << 20


def test_agg_tail_programs(chip, monkeypatch):
    """The aggregation tail's two programs (ops/aggtail.py) as the chip's
    cells ask for them: Q1's merge of 8 x 256-row partials over two string
    keys and eight sums, Q1's final tail (eight finals, ORDER BY two string
    keys), and Q3's final tail over a 16,384-row state (ORDER BY a float
    descending and a date, LIMIT 10), with the TPU's sort group-by."""
    from quokka_tpu import sqlparse
    from quokka_tpu.ops import aggtail, strategy
    from quokka_tpu.ops.expr_compile import plan_aggregation

    monkeypatch.setenv("QK_KERNEL_STRATEGY", "groupby=sort")
    strategy.reset()
    try:
        parts, rows, sums = 8, 256, ("sum",) * 8
        recombine = aggtail._build_recombine((True, True), sums, rows)
        _compile(
            recombine,
            tuple((chip("int32", rows),) * parts for _ in range(2))
            + tuple((chip(dt, rows),) * parts
                    for dt in ("float32",) * 7 + ("int32",)),
            ((),) * 10,
            ((chip("int32", (parts, 4)), chip("int32", 4), chip("int32", 4)),
             (chip("int32", (parts, 2)), chip("int32", 2), chip("int32", 2))),
            (chip(bool, rows),) * parts)
        plan = plan_aggregation(sqlparse.parse_select_list(
            "sum(q) as sum_qty, sum(p) as sum_base, sum(p * (1 - d)) as sum_disc, "
            "sum(p * (1 - d) * (1 + t)) as sum_charge, avg(q) as avg_qty, "
            "avg(p) as avg_price, avg(d) as avg_disc, count(*) as n"))
        num_meta = tuple(
            (name, "int32" if op == "count" else "float32", False,
             "i" if op == "count" else "f", None)
            for name, op, _ in plan.partials)
        keys = ["rf", "ls"]
        out_names = keys + [n for n, _ in plan.finals]
        q1 = aggtail._tail_body(num_meta, keys, plan.finals, None, out_names,
                                keys, (False, False), keys, None, rows, [])
        _compile(jax.jit(q1),
                 tuple(chip(m[1], rows) for m in num_meta),
                 (chip("int32", 0),) * len(num_meta),
                 (chip("int32", rows),) * 2,
                 (chip("int32", 4), chip("int32", 2)), chip(bool, rows))
        plan3 = plan_aggregation(sqlparse.parse_select_list(
            "sum(p * (1 - d)) as revenue"))
        state = 1 << 14
        meta3 = (("l_orderkey", "int32", False, "i", None),
                 ("o_orderdate", "int32", False, "d", None),
                 ("o_shippriority", "int32", False, "i", None),
                 (plan3.partials[0][0], "float32", False, "f", None))
        out3 = ["l_orderkey", "o_orderdate", "o_shippriority", "revenue"]
        q3 = aggtail._tail_body(meta3, [], plan3.finals, None, out3,
                                ["revenue", "o_orderdate"], (True, False),
                                [], 10, 256, [])
        _compile(jax.jit(q3),
                 tuple(chip(m[1], state) for m in meta3),
                 (chip("int32", 0),) * 4, (), (), chip(bool, state))
    finally:
        monkeypatch.undo()
        strategy.reset()
