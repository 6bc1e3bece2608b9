"""Kernel-strategy matrix (ops/strategy.py): resolution precedence,
calibration persistence, foreign-fingerprint fallback, forced overrides,
bench-honesty validation, and device-asof bit-equality across strategies."""

import json
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from quokka_tpu import config
from quokka_tpu.ops import asof as asof_ops
from quokka_tpu.ops import bridge, kernels
from quokka_tpu.ops import strategy


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Each test starts with no calibration loaded and no overrides; the
    conftest-level QK_STRATEGY_DIR="" keeps box profiles out."""
    monkeypatch.delenv("QK_KERNEL_STRATEGY", raising=False)
    monkeypatch.delenv("QUOKKA_HASH_TABLES", raising=False)
    monkeypatch.delenv("QUOKKA_HOST_ASOF", raising=False)
    strategy.reset()
    strategy.reset_used()
    yield
    strategy.reset()
    strategy.reset_used()


class TestResolution:
    def test_platform_defaults(self, monkeypatch):
        for plat, want_gb, want_asof in (
            ("cpu", "hashtable", "host"),
            ("gpu", "hashtable", "searchsorted"),
            ("tpu", "sort", "sort"),
        ):
            monkeypatch.setattr(config, "_platform", lambda p=plat: p)
            assert strategy.resolve("groupby") == (want_gb, "default")
            assert strategy.resolve("asof") == (want_asof, "default")
            assert strategy.choice("shuffle") == "masked"

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("QK_KERNEL_STRATEGY",
                           "groupby=sort, asof=searchsorted")
        monkeypatch.setenv("QUOKKA_HASH_TABLES", "1")  # loses to QK_KERNEL_
        assert strategy.resolve("groupby") == ("sort", "env")
        assert strategy.resolve("asof") == ("searchsorted", "env")
        # unlisted op falls through to the legacy env
        assert strategy.resolve("join_build") == ("hashtable", "legacy-env")

    def test_env_override_rejects_unknown(self, monkeypatch):
        monkeypatch.setenv("QK_KERNEL_STRATEGY", "groupby=btree")
        with pytest.raises(strategy.StrategyError, match="btree"):
            strategy.choice("groupby")
        monkeypatch.setenv("QK_KERNEL_STRATEGY", "quantum=sort")
        with pytest.raises(strategy.StrategyError, match="quantum"):
            strategy.choice("groupby")

    def test_legacy_envs_keep_meaning(self, monkeypatch):
        monkeypatch.setenv("QUOKKA_HASH_TABLES", "0")
        assert strategy.choice("groupby") == "sort"
        assert strategy.choice("join_build") == "sort"
        monkeypatch.setenv("QUOKKA_HOST_ASOF", "1")
        assert strategy.choice("asof") == "host"
        monkeypatch.setenv("QUOKKA_HOST_ASOF", "0")
        assert strategy.choice("asof") != "host"
        # config delegates answer the same question
        assert config.use_hash_tables() is False
        assert config.use_host_asof() is False


class TestCalibrationPersistence:
    def test_round_trip(self, monkeypatch, tmp_path):
        monkeypatch.setenv("QK_STRATEGY_DIR", str(tmp_path))
        strategy.reset()
        res = strategy.calibrate(rows=2048, reps=1)
        # shuffle is never picked by calibration (a pipeline property, not
        # a kernel wall — see calibrate(); it is still timed for the
        # profile's information)
        assert set(res["choices"]) == set(strategy.OPS) - {"shuffle"}
        for op, ch in res["choices"].items():
            assert ch in strategy.OPS[op]
        assert res["timings_s"]["shuffle"].keys() == {"masked", "compacted"}
        # a fresh resolution state answers from the persisted profile
        strategy.reset()
        assert {op: strategy.choice(op) for op in res["choices"]} \
            == res["choices"]
        assert strategy.resolve("shuffle") == ("masked", "default")
        files = list(tmp_path.iterdir())
        assert len(files) == 1
        prof = json.loads(files[0].read_text())
        assert prof["fingerprint"] == strategy._fingerprint()
        assert prof["choices"] == res["choices"]
        # every candidate that ran has a timing
        assert prof["timings_s"]["groupby"].keys() == {"sort", "hashtable"}

    def test_foreign_fingerprint_falls_back_to_defaults(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("QK_STRATEGY_DIR", str(tmp_path))
        strategy.reset()
        prof = {"version": strategy._CALIB_VERSION,
                "fingerprint": "tpu-8x-deadbeef0000",
                "choices": {op: strategy.OPS[op][0] for op in strategy.OPS}}
        (tmp_path / f"{strategy._fingerprint()}.json").write_text(
            json.dumps(prof))
        # fingerprint inside the file is foreign -> ignored wholesale
        assert set(strategy.sources().values()) == {"default"}

    def test_corrupt_profile_ignored(self, monkeypatch, tmp_path):
        monkeypatch.setenv("QK_STRATEGY_DIR", str(tmp_path))
        strategy.reset()
        (tmp_path / f"{strategy._fingerprint()}.json").write_text("{not json")
        assert set(strategy.sources().values()) == {"default"}
        strategy.reset()
        bad = {"version": strategy._CALIB_VERSION,
               "fingerprint": strategy._fingerprint(),
               "choices": {"groupby": "btree"}}
        (tmp_path / f"{strategy._fingerprint()}.json").write_text(
            json.dumps(bad))
        assert strategy.resolve("groupby")[1] == "default"

    def test_ensure_calibrated_loads_without_rerun(self, monkeypatch,
                                                   tmp_path):
        monkeypatch.setenv("QK_STRATEGY_DIR", str(tmp_path))
        strategy.reset()
        first = strategy.calibrate(rows=2048, reps=1)["choices"]
        strategy.reset()
        # a second process would load, not re-bench: forbid calibration and
        # the answer must still be the persisted choices
        monkeypatch.setenv("QK_STRATEGY_CALIBRATE", "0")
        assert strategy.ensure_calibrated() == first


class TestHonesty:
    def test_note_used_and_snapshot(self):
        strategy.note_used("asof", "searchsorted")
        strategy.note_used("groupby", "hashtable")
        assert strategy.used_snapshot() == {
            "asof": "searchsorted", "groupby": "hashtable"}
        strategy.reset_used()
        assert strategy.used_snapshot() == {}

    def test_invalid_for_platform(self):
        assert strategy.invalid_for_platform("tpu", "asof", "host")
        assert strategy.invalid_for_platform("gpu", "asof", "host")
        assert strategy.invalid_for_platform("cpu", "asof", "host") is None
        assert strategy.invalid_for_platform(
            "tpu", "asof", "searchsorted") is None
        assert strategy.invalid_for_platform("cpu", "groupby", "btree")
        assert strategy.invalid_for_platform("cpu", "quantum", "sort")

    def test_join_and_shuffle_record_used(self, monkeypatch):
        r = np.random.default_rng(3)
        n = 500
        probe = bridge.arrow_to_device(pa.table({
            "k": r.integers(0, 100, n).astype(np.int64),
            "v": r.uniform(0, 1, n)}))
        build = bridge.arrow_to_device(pa.table({
            "k": np.arange(100, dtype=np.int64),
            "w": r.uniform(0, 1, 100)}))
        from quokka_tpu.ops import join as join_ops

        for forced in ("hashtable", "sort"):
            strategy.reset_used()
            monkeypatch.setenv("QK_KERNEL_STRATEGY", f"join_build={forced}")
            build2 = bridge.arrow_to_device(pa.table({
                "k": np.arange(100, dtype=np.int64),
                "w": r.uniform(0, 1, 100)}))
            join_ops.hash_join_pk(probe, build2, ["k"], ["k"], "inner",
                                  ["w"])
            assert strategy.used_snapshot()["join_build"] == forced
        strategy.reset_used()
        monkeypatch.setenv("QK_KERNEL_STRATEGY", "shuffle=masked")
        big = bridge.arrow_to_device(pa.table({
            "k": r.integers(0, 1 << 20, 1 << 17).astype(np.int64)}))
        pids = kernels.partition_ids(big, ["k"], 4)
        kernels.split_by_partition(big, pids, 4)
        assert strategy.used_snapshot()["shuffle"] == "masked"

    def test_multiple_kernels_per_op_all_recorded(self):
        """A mesh query's timed shard kernel and its coordinator-side
        recombine may run DIFFERENT groupby kernels; the snapshot must name
        both, not whichever dispatched last."""
        strategy.note_used("groupby", "sort")
        strategy.note_used("groupby", "hashtable")
        strategy.note_used("groupby", "sort")  # dedup, no re-count
        assert strategy.used_snapshot() == {"groupby": "hashtable+sort"}
        assert strategy.invalid_for_platform(
            "tpu", "groupby", "hashtable+sort") is None
        # every component must be runnable: host asof hiding in a
        # multi-value is still gated off non-CPU platforms
        assert strategy.invalid_for_platform("tpu", "asof", "host+sort")
        assert strategy.invalid_for_platform("cpu", "groupby", "sort+btree")


def _ticks(seed, n_t=400, n_q=900, dup_times=True):
    r = np.random.default_rng(seed)
    span = 50 if dup_times else 1 << 20  # coarse span -> many exact ties
    tt = np.sort(r.integers(0, span, n_t)).astype(np.int64)
    qt = np.sort(r.integers(0, span, n_q)).astype(np.int64)
    syms = np.array(["A", "B", "C"])
    trades = pa.table({"time": tt, "symbol": syms[r.integers(0, 3, n_t)],
                       "size": r.integers(1, 9, n_t).astype(np.int32)})
    quotes = pa.table({"time": qt, "symbol": syms[r.integers(0, 3, n_q)],
                       "bid": np.arange(n_q, dtype=np.float64)})
    return trades, quotes


class TestAsofStrategyEquality:
    """The satellite contract: device searchsorted == host native == device
    sort kernel, bit for bit, fwd + bwd, including duplicate timestamps
    (tie-break pins WHICH quote), unmatched rows, and empty sides."""

    @pytest.mark.parametrize("direction", ["backward", "forward"])
    @pytest.mark.parametrize("dup_times", [True, False])
    def test_three_strategies_bit_equal(self, direction, dup_times):
        trades, quotes = _ticks(17, dup_times=dup_times)
        frames = {}
        for strat in ("host", "sort", "searchsorted"):
            tb = bridge.arrow_to_device(trades)
            qb = bridge.arrow_to_device(quotes)
            out = asof_ops.asof_join(
                tb, qb, "time", "time", ["symbol"], ["symbol"], ["bid"],
                direction=direction, strategy=strat)
            matched = out.columns.pop("__asof_matched__").data
            out = kernels.compact(kernels.apply_mask(out, matched))
            df = bridge.device_to_arrow(out).to_pandas()
            frames[strat] = df.sort_values(
                ["time", "symbol", "size", "bid"]).reset_index(drop=True)
        pd.testing.assert_frame_equal(frames["host"], frames["searchsorted"])
        pd.testing.assert_frame_equal(frames["sort"], frames["searchsorted"])
        # and all of them match the pandas oracle
        exp = pd.merge_asof(
            trades.to_pandas(), quotes.to_pandas(), on="time", by="symbol",
            direction=direction).dropna(subset=["bid"])
        exp = exp.sort_values(
            ["time", "symbol", "size", "bid"]).reset_index(drop=True)
        np.testing.assert_array_equal(
            frames["searchsorted"].bid.to_numpy(), exp.bid.to_numpy())

    @pytest.mark.parametrize("direction", ["backward", "forward"])
    def test_empty_quotes_all_unmatched(self, direction):
        trades, _ = _ticks(5)
        qb = bridge.arrow_to_device(pa.table({
            "time": np.array([], dtype=np.int64),
            "symbol": pa.array([], type=pa.string()),
            "bid": np.array([], dtype=np.float64)}))
        tb = bridge.arrow_to_device(trades)
        out = asof_ops.asof_join(
            tb, qb, "time", "time", ["symbol"], ["symbol"], ["bid"],
            direction=direction, strategy="searchsorted")
        assert not np.asarray(out.columns["__asof_matched__"].data).any()

    def test_empty_trades(self):
        _, quotes = _ticks(6)
        tb = bridge.arrow_to_device(pa.table({
            "time": np.array([], dtype=np.int64),
            "symbol": pa.array([], type=pa.string()),
            "size": np.array([], dtype=np.int32)}))
        qb = bridge.arrow_to_device(quotes)
        out = asof_ops.asof_join(
            tb, qb, "time", "time", ["symbol"], ["symbol"], ["bid"],
            strategy="searchsorted")
        assert int(np.asarray(out.columns["__asof_matched__"].data)
                   .sum()) == 0

    @pytest.mark.parametrize("direction", ["backward", "forward"])
    def test_mixed_time_dtypes_match_sort_path(self, direction):
        """float trade times vs int quote times: the quote side must be
        cast to the TRADE dtype before the search (the sort kernel's
        convention) — casting the probe side instead truncated 5.7 -> 5 and
        forward-matched a quote EARLIER than the trade."""
        tb = bridge.arrow_to_device(pa.table({
            "time": np.array([5.7, 0.2, 8.0]),
            "symbol": ["A", "A", "A"]}))
        frames = {}
        for strat in ("sort", "searchsorted"):
            qb = bridge.arrow_to_device(pa.table({
                "time": np.array([5, 6, 9], dtype=np.int64),
                "symbol": ["A", "A", "A"],
                "bid": np.array([100.0, 200.0, 300.0])}))
            out = asof_ops.asof_join(
                tb, qb, "time", "time", ["symbol"], ["symbol"], ["bid"],
                direction=direction, strategy=strat)
            matched = out.columns.pop("__asof_matched__").data
            out = kernels.compact(kernels.apply_mask(out, matched))
            df = bridge.device_to_arrow(out).to_pandas()
            frames[strat] = df.sort_values("time").reset_index(drop=True)
        pd.testing.assert_frame_equal(frames["sort"], frames["searchsorted"])
        want = ({5.7: 100.0, 0.2: None, 8.0: 200.0} if direction == "backward"
                else {5.7: 200.0, 0.2: 100.0, 8.0: 300.0})
        got = dict(zip(frames["searchsorted"].time,
                       frames["searchsorted"].bid))
        assert got == {t: b for t, b in want.items() if b is not None}

    def test_quote_sort_cached_on_batch(self):
        trades, quotes = _ticks(8)
        tb = bridge.arrow_to_device(trades)
        qb = bridge.arrow_to_device(quotes)
        asof_ops.asof_join(tb, qb, "time", "time", ["symbol"], ["symbol"],
                           ["bid"], strategy="searchsorted")
        cache = qb._asof_ss_cache
        assert len(cache) == 1
        key = next(iter(cache))
        before = cache[key]
        asof_ops.asof_join(tb, qb, "time", "time", ["symbol"], ["symbol"],
                           ["bid"], direction="forward",
                           strategy="searchsorted")
        # both directions share the one cached quote sort
        assert cache[key] is before and len(cache) == 1

    def test_forced_host_falls_back_on_device_when_declined(self):
        """int trade times vs float quote times: the native merge declines
        (encodings not comparable); the recorded strategy must be the
        device kernel that actually answered."""
        strategy.reset_used()
        tb = bridge.arrow_to_device(pa.table({
            "time": np.array([1, 5, 9], dtype=np.int64),
            "symbol": ["A", "A", "A"]}))
        qb = bridge.arrow_to_device(pa.table({
            "time": np.array([0.5, 4.5, 8.5]),
            "symbol": ["A", "A", "A"],
            "bid": np.array([1.0, 2.0, 3.0])}))
        out = asof_ops.asof_join(
            tb, qb, "time", "time", ["symbol"], ["symbol"], ["bid"],
            strategy="host")
        assert np.asarray(
            out.columns["__asof_matched__"].data)[:3].all()
        assert strategy.used_snapshot()["asof"] == "searchsorted"
