"""The asof match as one merged sort, two running maxima and a compacting
sort (ISSUE 35; ops/asof.py ``_asof_match``), through
``asof_join(strategy="sort")``: pandas' ``merge_asof`` answer AND the
``searchsorted`` strategy's, frame for frame (which quote row every trade
slot got, matched or not), over both directions, every key shape (none, a
string's two hash limbs, an integer) and both time layouts (one limb; the
wide ``(hi, lo)`` pair a chip without x64 holds ns timestamps in).

The quote's ``bid`` is its original row index, so equality pins WHICH of
several tied quotes was chosen; the trade's ``size`` is its own, so the
chunk comes back slot for slot."""

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from quokka_tpu.executors.ts_execs import SortedAsofExecutor
from quokka_tpu.ops import asof as asof_ops
from quokka_tpu.ops import bridge
from quokka_tpu.ops.batch import DeviceBatch

N_TRADES, N_QUOTES, N_KEYS = 230, 410, 5
SCENARIOS = ("ties", "early_trades", "key_without_quote", "holes",
             "all_invalid_quotes", "keep_unmatched")
BY = {"none": [], "str": ["symbol"], "int": ["sym_id"]}
# 40 coarse ticks: exact (key, time) collisions among quotes and between a
# quote and a trade.  Wide: 3 s steps of ns, so the low limb wraps 28 times
STEP = {"narrow": 1, "wide": 3_000_000_000}
BASE = {"narrow": 0, "wide": 1_600_000_000_000_000_000}


@pytest.fixture
def time_layout(request):
    """x64 off for the wide cases: an int64 time that no int32 holds is
    then stored as (hi, lo) limbs, as on the chip."""
    if request.param == "wide":
        jax.config.update("jax_enable_x64", False)
    try:
        yield request.param
    finally:
        jax.config.update("jax_enable_x64", True)


def _ticks(scenario, layout, direction, seed):
    r = np.random.default_rng(seed)
    q_lo, q_hi = 0, 40
    if scenario == "early_trades":
        # trades on the far side of every quote of their key: earlier than
        # the first (backward), later than the last (forward)
        q_lo, q_hi = (15, 40) if direction == "backward" else (0, 25)
    tt = np.sort(r.integers(0, 40, N_TRADES))
    qt = np.sort(r.integers(q_lo, q_hi, N_QUOTES))
    tk = r.integers(0, N_KEYS, N_TRADES)
    qk = r.integers(0, N_KEYS, N_QUOTES)
    if scenario == "key_without_quote":
        tk = np.where(r.random(N_TRADES) < 0.2, N_KEYS, tk)      # no quote
        qk = np.where(r.random(N_QUOTES) < 0.2, N_KEYS + 1, qk)  # no trade
    names = np.array([f"S{i}" for i in range(N_KEYS + 2)])

    def time(ticks):
        return BASE[layout] + ticks.astype(np.int64) * STEP[layout]

    trades = pd.DataFrame({
        "time": time(tt), "symbol": names[tk], "sym_id": tk.astype(np.int64),
        "size": np.arange(N_TRADES, dtype=np.int32)})
    quotes = pd.DataFrame({
        "time": time(qt), "symbol": names[qk], "sym_id": qk.astype(np.int64),
        "bid": np.arange(N_QUOTES, dtype=np.float32)})
    keep_t = np.ones(N_TRADES, dtype=bool)
    keep_q = np.ones(N_QUOTES, dtype=bool)
    if scenario == "holes":
        keep_t = r.random(N_TRADES) < 0.7
        keep_q = r.random(N_QUOTES) < 0.6
    if scenario == "all_invalid_quotes":
        keep_q[:] = False
    return trades, quotes, keep_t, keep_q


def _masked(frame, keep) -> DeviceBatch:
    b = bridge.arrow_to_device(pa.Table.from_pandas(frame))
    mask = np.zeros(b.padded_len, dtype=bool)
    mask[:len(keep)] = keep
    return DeviceBatch(dict(b.columns), b.valid & mask, None, b.sorted_by)


def _expected(trades, quotes, keep_t, keep_q, by, direction):
    exp = pd.merge_asof(
        trades[keep_t], quotes[keep_q].drop(
            columns=[c for c in ("symbol", "sym_id") if c not in by]),
        on="time", by=by or None, direction=direction)
    return pd.DataFrame({
        "size": exp["size"].to_numpy(np.int64),
        "matched": exp.bid.notna().to_numpy(),
        "bid": exp.bid.fillna(-1).to_numpy(np.float32)})


def _frame(out: DeviceBatch, matched=None) -> pd.DataFrame:
    """Every valid trade slot in slot order: its own index, whether a quote
    matched, and which."""
    valid = np.asarray(out.valid)
    if matched is None:
        matched = np.asarray(out.columns["__asof_matched__"].data)
    bid = np.asarray(out.columns["bid"].data, dtype=np.float32)
    return pd.DataFrame({
        "size": np.asarray(out.columns["size"].data, dtype=np.int64)[valid],
        "matched": matched[valid],
        "bid": np.where(matched, bid, np.float32(-1))[valid]})


def _through_executor(trades, quotes, by, direction, strategy, monkeypatch):
    """``keep_unmatched``: the executor's option, so the executor is driven
    (quotes, then trades, then both ends), under the forced strategy."""
    monkeypatch.setenv("QK_KERNEL_STRATEGY", f"asof={strategy}")
    ex = SortedAsofExecutor("time", "time", by, by, keep_unmatched=True,
                            direction=direction)
    outs = [ex.execute([quotes], 1, 0), ex.execute([trades], 0, 0),
            ex.source_done(1, 0), ex.source_done(0, 0)]
    final = ex.done(0)
    outs.extend(final if isinstance(final, list) else [final])
    frames = []
    for o in (o for o in outs if o is not None):
        bid = np.asarray(o.columns["bid"].data)
        frames.append(_frame(o, matched=~np.isnan(bid)))
    return pd.concat(frames).sort_values("size").reset_index(drop=True)


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("time_layout", ["narrow", "wide"], indirect=True)
@pytest.mark.parametrize("by", list(BY))
@pytest.mark.parametrize("direction", ["backward", "forward"])
def test_sort_match_equals_pandas_and_searchsorted(
        direction, by, time_layout, scenario, monkeypatch):
    seed = 100 + SCENARIOS.index(scenario)
    trades, quotes, keep_t, keep_q = _ticks(
        scenario, time_layout, direction, seed)
    exp = _expected(trades, quotes, keep_t, keep_q, BY[by], direction)
    frames = {}
    for strategy in ("sort", "searchsorted"):
        tb, qb = _masked(trades, keep_t), _masked(quotes, keep_q)
        assert (tb.columns["time"].hi is not None) == (time_layout == "wide")
        if scenario == "keep_unmatched":
            frames[strategy] = _through_executor(
                tb, qb, BY[by], direction, strategy, monkeypatch)
        else:
            frames[strategy] = _frame(asof_ops.asof_join(
                tb, qb, "time", "time", BY[by], BY[by], ["bid"],
                direction=direction, strategy=strategy))
    pd.testing.assert_frame_equal(frames["sort"], exp)
    pd.testing.assert_frame_equal(frames["sort"], frames["searchsorted"])
    if scenario == "all_invalid_quotes":
        assert not frames["sort"].matched.any()
    elif scenario != "holes":
        assert frames["sort"].matched.any() and len(exp) == N_TRADES
    if scenario == "early_trades" or (
            scenario == "key_without_quote" and by != "none"):
        assert not frames["sort"].matched.all()
