"""``kernels.sorted_groupby`` called directly, against numpy in float64.

The CPU's default group-by is the hash table (ops/strategy.py), so tier-1
reaches the sort path (what the TPU runs: sort, scans, sort) only where a test
forces ``groupby=sort`` or calls the kernel itself.  Here every op of
``AGG_OPS`` over int32 and float32 values meets every key and mask shape the
callers produce; the contract is ``(outs, counts, rep, num)`` indexed by dense
rank (ascending lexicographic key order), ``rep`` the least original row index
of each group.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from quokka_tpu.ops import kernels

N = 512


def _scenario(name, rng):
    """(limbs, valid) as numpy arrays, N rows."""
    valid = np.ones(N, dtype=bool)
    if name == "one_limb":
        limbs = (rng.integers(-20, 20, N).astype(np.int32),)
    elif name == "three_limbs":
        limbs = (rng.integers(0, 3, N).astype(np.int32),
                 rng.integers(-2, 2, N).astype(np.int32),
                 rng.integers(0, 4, N).astype(np.float32) / 2)
    elif name == "wide_key":
        # an int64 key as the engine carries it: (hi, lo) 32-bit limbs
        k = rng.choice(np.array([-(1 << 40), -1, 0, 1, 1 << 31, (1 << 40) + 7,
                                 (1 << 40) + 8], dtype=np.int64), N)
        limbs = ((k >> 32).astype(np.int32),
                 ((k & 0xFFFFFFFF) - (1 << 31)).astype(np.int32))
    elif name == "masked":
        limbs = (rng.integers(0, 40, N).astype(np.int32),)
        valid = rng.random(N) > 0.3
    elif name == "all_invalid":
        limbs = (rng.integers(0, 40, N).astype(np.int32),)
        valid[:] = False
    elif name == "one_group":
        limbs = (np.full(N, 7, dtype=np.int32),)
    elif name == "singletons":
        limbs = (rng.permutation(N).astype(np.int32),)
    else:
        raise AssertionError(name)
    return limbs, valid


SCENARIOS = ("one_limb", "three_limbs", "wide_key", "masked", "all_invalid",
             "one_group", "singletons")


def _groups(limbs, valid):
    """The groups in the kernel's order: (member row indices, ascending) per
    group, groups ascending by lexicographic key."""
    rows = np.flatnonzero(valid)
    if rows.size == 0:
        return []
    keys = np.stack([l[rows].astype(np.float64) for l in limbs], axis=1)
    _, inverse = np.unique(keys, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    return [rows[inverse == g] for g in range(inverse.max() + 1)]


def _reference(op, values, members):
    x = values[members].astype(np.float64)
    if op == "sum":
        return x.sum()
    if op == "count":
        return np.count_nonzero(~np.isnan(x))
    if op == "min":
        return x.min()
    if op == "max":
        return x.max()
    if op == "mean":
        return x.sum() / len(x)
    if op == "first":
        return x[0]
    raise AssertionError(op)


def _values(dtype, rng, n=N):
    if dtype == "int32":
        return rng.integers(-1000, 1000, n).astype(np.int32)
    return rng.uniform(-50, 100, n).astype(np.float32)


def _run(limbs, arrays, ops, valid):
    outs, counts, rep, num = kernels.sorted_groupby(
        tuple(jnp.asarray(l) for l in limbs),
        tuple(jnp.asarray(a) for a in arrays), tuple(ops), jnp.asarray(valid))
    return [np.asarray(o) for o in outs], np.asarray(counts), \
        np.asarray(rep), int(num)


def _check(op, dtype, got, want):
    if op in ("count",) or (dtype == "int32" and op != "mean"):
        np.testing.assert_array_equal(got, np.asarray(want, dtype=np.int64))
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("op", kernels.AGG_OPS)
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_op_matches_float64_reference(scenario, op, dtype):
    rng = np.random.default_rng(SCENARIOS.index(scenario))
    limbs, valid = _scenario(scenario, rng)
    values = _values(dtype, rng)
    (out,), counts, rep, num = _run(limbs, (values,), (op,), valid)
    groups = _groups(limbs, valid)
    assert num == len(groups)
    np.testing.assert_array_equal(counts[:num], [len(g) for g in groups])
    assert (counts[num:] == 0).all()
    assert out.shape == (N,) and rep.shape == (N,)
    assert ((rep >= 0) & (rep < N)).all()  # callers gather keys by it
    _check(op, dtype, out[:num], [_reference(op, values, g) for g in groups])
    if op in ("sum", "count"):
        assert (out[num:] == 0).all()


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_rep_is_the_least_original_index(scenario):
    rng = np.random.default_rng(100 + SCENARIOS.index(scenario))
    limbs, valid = _scenario(scenario, rng)
    _, _, rep, num = _run(limbs, (), (), valid)
    groups = _groups(limbs, valid)
    assert num == len(groups)
    np.testing.assert_array_equal(rep[:num], [g[0] for g in groups])
    # the representative holds the group's key
    for l in limbs:
        np.testing.assert_array_equal(l[rep[:num]], [l[g[0]] for g in groups])


@pytest.mark.parametrize("op", ["sum", "mean", "min", "max"])
def test_nan_and_inf_stay_in_their_groups(op):
    """A differenced float prefix would carry a NaN (inf - inf) into every
    later group; a segmented scan reads only its own segment."""
    rng = np.random.default_rng(7)
    key = rng.integers(0, 30, N).astype(np.int32)
    values = rng.uniform(1, 2, N).astype(np.float32)
    values[np.flatnonzero(key == 3)[1]] = np.nan
    values[np.flatnonzero(key == 11)[0]] = np.inf
    valid = np.ones(N, dtype=bool)
    (out,), _, _, num = _run((key,), (values,), (op,), valid)
    groups = _groups((key,), valid)
    want = np.array([_reference(op, values, g) for g in groups])
    assert num == 30
    np.testing.assert_array_equal(np.isnan(out[:num]), np.arange(30) == 3)
    # (the least of a group with an inf in it is finite)
    np.testing.assert_array_equal(
        np.isinf(out[:num]), (np.arange(30) == 11) & (op != "min"))
    fin = np.isfinite(want)
    np.testing.assert_allclose(out[:num][fin], want[fin], rtol=1e-6)


def test_count_of_a_float_column_skips_nans_and_masked_rows():
    rng = np.random.default_rng(8)
    key = rng.integers(0, 25, N).astype(np.int32)
    values = rng.uniform(0, 1, N).astype(np.float32)
    values[rng.random(N) < 0.2] = np.nan
    valid = rng.random(N) > 0.2
    ints = rng.integers(0, 9, N).astype(np.int32)
    (nn, rows, s), counts, _, num = _run(
        (key,), (values, ints, ints), ("count", "count", "sum"), valid)
    groups = _groups((key,), valid)
    assert num == len(groups)
    np.testing.assert_array_equal(
        nn[:num], [np.count_nonzero(~np.isnan(values[g])) for g in groups])
    np.testing.assert_array_equal(rows[:num], [len(g) for g in groups])
    np.testing.assert_array_equal(rows, counts)
    np.testing.assert_array_equal(s[:num], [ints[g].sum() for g in groups])


def test_integer_sums_wrap_like_segment_sum():
    """A prefix that overflows int32 still differences to the exact group
    sums (two's complement), as long as each group's own sum fits."""
    key = np.repeat(np.arange(8, dtype=np.int32), N // 8)
    values = np.full(N, (1 << 31) // (N // 8) - 1, dtype=np.int32)
    (out,), _, _, num = _run((key,), (values,), ("sum",),
                             np.ones(N, dtype=bool))
    assert num == 8
    np.testing.assert_array_equal(
        out[:8], np.full(8, int(values[0]) * (N // 8)))


def test_float_sum_at_a_scan_batch_of_h2o_q5():
    """1<<20 rows of ``v3``-like values (float32, 0-100, 1e5 groups of about
    ten): the float sum's largest relative error against float64 stays under
    1e-6.  A float32 prefix sum differenced at the group boundaries does not
    (the prefix reaches 5e7, where one ulp is 4): the form the kernel must
    never take."""
    n = 1 << 20
    rng = np.random.default_rng(33)
    key = rng.integers(1, 100001, n).astype(np.int32)
    v3 = np.round(rng.uniform(0, 100, n), 6).astype(np.float32)
    valid = np.ones(n, dtype=bool)
    (out,), counts, rep, num = _run((key,), (v3,), ("sum",), valid)
    want = np.bincount(key, weights=v3.astype(np.float64), minlength=100001)
    present = np.flatnonzero(np.bincount(key, minlength=100001))
    assert num == len(present)
    np.testing.assert_array_equal(key[rep[:num]], present)
    err = np.abs(out[:num] - want[present]) / want[present]
    assert err.max() < 1e-6, err.max()
    order = np.argsort(key, kind="stable")
    prefix = np.cumsum(v3[order], dtype=np.float32)
    ends = np.cumsum(np.bincount(key, minlength=100001)[present]) - 1
    diffed = np.diff(np.concatenate([[np.float32(0)], prefix[ends]]))
    assert (np.abs(diffed - want[present]) / want[present]).max() > 1e-5
