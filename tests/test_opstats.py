"""Operator-statistics ledger (obs/opstats.py) + EXPLAIN rendering
(obs/explain.py): unit coverage over a synthetic plan, no engine runs.
The end-to-end path (engine choke points, zero added syncs, admission
feedback) is proven by `make explain-smoke`."""

import json
import os
import threading

import pytest

from quokka_tpu import obs
from quokka_tpu.obs import explain
from quokka_tpu.obs import opstats
from quokka_tpu.obs.opstats import OpStats


class _Reader:
    def __init__(self, hint):
        self._hint = hint

    def size_hint(self):
        return self._hint


class _Actor:
    def __init__(self, kind, channels=2, targets=(), stage=0, reader=None):
        self.kind = kind
        self.channels = channels
        self.targets = {t: None for t in targets}
        self.stage = stage
        if reader is not None:
            self.reader = reader


class _Graph:
    """The minimal TaskGraph surface register_plan reads."""

    def __init__(self, qid, actors, plan_fp="fp-test"):
        self.query_id = qid
        self.actors = actors
        self.plan_fp = plan_fp


def _two_stage_graph(qid="qtest"):
    return _Graph(qid, {
        0: _Actor("input", channels=2, targets=(1,),
                  reader=_Reader(1 << 20)),
        1: _Actor("exec", channels=2, targets=(2,), stage=1),
        2: _Actor("exec", channels=1, stage=2),
    })


class _Dev:
    """Stands in for a device scalar: resolvable via int() like the async
    d2h copies the engine queues."""

    def __init__(self, n):
        self._n = n

    def __int__(self):
        return self._n


class _Valid:
    nbytes = 128


class _Batch:
    def __init__(self, nrows=None, nrows_dev=None, padded_len=0):
        self.nrows = nrows
        self.nrows_dev = nrows_dev
        self.padded_len = padded_len
        self.valid = _Valid()  # _batch_nbytes sums valid + column buffers
        self.columns = {}


@pytest.fixture
def ledger():
    s = OpStats()
    yield s
    s.reset()


def _feed(s, qid="qtest"):
    """A complete little run: 1000 scan rows (900 past the predicate),
    skewed exchange onto a1, aggregate down to 10 rows at a2."""
    s.register_plan(_two_stage_graph(qid))
    s.scan(qid, 0, 0, rows_raw=600, rows_out=500, nbytes=6000, padded=640)
    s.scan(qid, 0, 1, rows_raw=400, rows_out=400, nbytes=4000, padded=512)
    # every row lands on channel 0: max/mean = 2.0 on 2 channels, the
    # highest ratio a 2-channel edge can show — exactly at the threshold
    s.edge(qid, 0, 1, 0, 900)
    s.exec_in(qid, 1, 0, [_Batch(nrows=900, padded_len=1024)])
    s.exec_out(qid, 1, 0, 900)
    s.edge(qid, 1, 2, 0, 900)
    s.exec_in(qid, 2, 0, [_Batch(nrows=900, padded_len=1024)])
    s.exec_out(qid, 2, 0, 10)
    s.dispatch_time(qid, 1, 0, 0.3)
    s.dispatch_time(qid, 2, 0, 0.1)


class TestLedger:
    def test_snapshot_reconciles_and_flags_skew(self, ledger):
        _feed(ledger)
        snap = ledger.snapshot("qtest")
        ops = {o["actor"]: o for o in snap["operators"]}
        assert ops[0]["rows_in"] == 1000 and ops[0]["rows_out"] == 900
        assert ops[0]["selectivity"] == 0.9
        assert ops[0]["size_hint_bytes"] == 1 << 20
        assert ops[1]["rows_in"] == 900 and ops[2]["rows_out"] == 10
        # pad_waste: 900 live rows in 1024 padded slots on a1
        assert ops[1]["pad_waste"] == round(1 - 900 / 1024, 4)
        edges = {e["edge"]: e for e in snap["edges"]}
        e01 = edges["a0->a1"]
        assert e01["channel_rows"] == [900, 0]
        assert e01["skew_ratio"] == 2.0
        assert e01["skewed"] is True  # default threshold 2.0
        assert edges["a1->a2"]["skewed"] is False  # single channel
        assert snap["rows_unknown"] == 0
        # a1 carried 0.3s of 0.4s total
        assert snap["top_operators"][0]["actor"] == 1
        assert ops[1]["time_share"] == 0.75

    def test_unregistered_query_records_nothing(self, ledger):
        ledger.scan("ghost", 0, 0, rows_raw=5, rows_out=5, nbytes=1,
                    padded=8)
        ledger.edge("ghost", 0, 1, 0, 5)
        assert ledger.snapshot("ghost") is None
        assert ledger.live_queries() == []

    def test_device_scalars_resolve_at_flush_cadence(self, ledger):
        qid = "qdev"
        ledger.register_plan(_two_stage_graph(qid))
        ledger.exec_in(qid, 1, 0, [_Batch(nrows_dev=_Dev(70),
                                          padded_len=128)])
        ledger.exec_out(qid, 1, 0, _Dev(30))
        ledger.edge(qid, 0, 1, 0, _Dev(70))
        snap = ledger.snapshot(qid)  # snapshot() drains pending first
        op1 = next(o for o in snap["operators"] if o["actor"] == 1)
        assert op1["rows_in"] == 70 and op1["rows_out"] == 30
        assert snap["edges"][0]["rows_total"] == 70
        assert op1["rows_unknown"] == 0

    def test_unresolvable_rows_counted_never_synced(self, ledger):
        qid = "qunk"
        ledger.register_plan(_two_stage_graph(qid))
        ledger.exec_in(qid, 1, 0, [_Batch()])  # no nrows, no nrows_dev
        snap = ledger.snapshot(qid)
        assert snap["rows_unknown"] == 1

    def test_note_attributes_through_current_op(self, ledger):
        qid = "qnote"
        ledger.register_plan(_two_stage_graph(qid))
        orig = opstats.OPSTATS
        opstats.OPSTATS = ledger  # note() routes via the module singleton
        try:
            with ledger.current_op(qid, 1, 0):
                opstats.note(join_build_rows=40)
                opstats.note(join_build_rows=2)
            opstats.note(join_build_rows=999)  # outside a dispatch: no-op
        finally:
            opstats.OPSTATS = orig
        snap = ledger.snapshot(qid)
        op1 = next(o for o in snap["operators"] if o["actor"] == 1)
        assert op1["join_build_rows"] == 42

    def test_note_defers_a_device_count_to_the_snapshot(self, ledger):
        """A device scalar handed to ``note`` is never read in the dispatch:
        it waits with the rows' counts and lands in the snapshot, summed
        with the host-known figures of the same name."""
        class _Scalar(_Dev):
            def __int__(self):
                reads.append(self._n)
                return self._n

            def copy_to_host_async(self):
                pass

        qid, reads = "qnotedev", []
        ledger.register_plan(_two_stage_graph(qid))
        orig = opstats.OPSTATS
        opstats.OPSTATS = ledger
        try:
            with ledger.current_op(qid, 1, 0):
                opstats.note(groupby_groups_out=_Scalar(70),
                             groupby_sort_slots=128)
                opstats.note(groupby_groups_out=5)
                opstats.note(groupby_groups_out=_Scalar(25))
            ledger.resolve_pending()  # the flush cadence leaves them be
            assert reads == []
            with ledger.current_op("ghost", 1, 0):
                opstats.note(groupby_groups_out=_Scalar(999))  # unregistered
        finally:
            opstats.OPSTATS = orig
        snap = ledger.snapshot(qid)
        op1 = next(o for o in snap["operators"] if o["actor"] == 1)
        assert op1["groupby_groups_out"] == 100 and sorted(reads) == [25, 70]
        assert op1["groupby_sort_slots"] == 128

    def test_gc_drops_state_keeps_last_snapshot(self, ledger):
        _feed(ledger)
        snap = ledger.on_query_gc("qtest", plan_fp=None)
        assert snap and snap["query_id"] == "qtest"
        assert ledger.live_queries() == []
        # straggler reports after GC never resurrect the query
        ledger.scan("qtest", 0, 0, rows_raw=5, rows_out=5, nbytes=1,
                    padded=8)
        assert ledger.last_finished()["operators"] == snap["operators"]
        # per-query gauge twins were removed from the registry
        reg = obs.REGISTRY.snapshot()
        assert not any(k.startswith("opstats.rows_in.qtest") for k in reg)

    def test_top_operator_line(self, ledger):
        _feed(ledger)
        line = ledger.top_operator("qtest")
        assert line and line.startswith("exec(a1)") and "rows=900" in line


class TestCardinalityProfile:
    def test_roundtrip_and_max_merge(self, ledger, tmp_path, monkeypatch):
        monkeypatch.setenv("QK_CARDPROFILE_DIR", str(tmp_path))
        _feed(ledger)
        snap = ledger.on_query_gc("qtest", plan_fp="fp-test")
        assert opstats.measured_source_bytes("fp-test") == \
            snap["operators"][0]["bytes_out"] == 10000
        assert opstats.measured_calib_rows() == 900
        assert opstats.measured_source_bytes("fp-other") is None
        # a smaller rerun max-merges: measured figures never shrink
        s2 = OpStats()
        s2.register_plan(_two_stage_graph("q2"))
        s2.scan("q2", 0, 0, rows_raw=10, rows_out=10, nbytes=100, padded=16)
        s2.on_query_gc("q2", plan_fp="fp-test")
        assert opstats.measured_source_bytes("fp-test") == 10000
        path = os.path.join(
            str(tmp_path), os.listdir(tmp_path)[0])
        prof = json.load(open(path))
        assert prof["plans"]["fp-test"]["runs"] == 2

    def test_corrupt_or_foreign_profile_ignored(self, tmp_path, monkeypatch):
        monkeypatch.setenv("QK_CARDPROFILE_DIR", str(tmp_path))
        path = opstats._profile_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write("{not json")
        assert opstats.measured_source_bytes("fp-test") is None
        with open(path, "w") as f:
            json.dump({"version": 1, "fingerprint": "other-backend",
                       "plans": {"fp-test": {"source_bytes": 7}}}, f)
        assert opstats.measured_source_bytes("fp-test") is None

    def test_disabled_dir_skips_persist_and_load(self, ledger, monkeypatch):
        monkeypatch.setenv("QK_CARDPROFILE_DIR", "")
        _feed(ledger)
        ledger.on_query_gc("qtest", plan_fp="fp-test")
        assert opstats.measured_source_bytes("fp-test") is None
        assert opstats.measured_calib_rows() is None


class TestExplainRendering:
    def test_render_and_detail(self, ledger):
        _feed(ledger)
        snap = ledger.snapshot("qtest")
        text = explain.render(snap)
        assert "EXPLAIN ANALYZE qtest" in text
        assert "skew report" in text and "** SKEWED **" in text
        assert "top operators by dispatch time:" in text
        det = explain.operators_detail(snap)
        assert len(det["operators"]) == 3
        assert det["skew"][0]["ratio"] == snap["edges"][0]["skew_ratio"]
        assert det["rows_unknown"] == 0
        assert explain.skew_flags(snap) == ["a0->a1"]

    def test_render_empty(self):
        assert "no operator statistics" in explain.render(None)
        assert explain.operators_detail(None) is None
        assert explain.skew_flags(None) == []

    def test_snapshot_is_the_operators_own_figures(self, ledger):
        """No section joins static program costs against host seconds: the
        snapshot and its rendering carry what the operators counted."""
        _feed(ledger)
        snap = ledger.snapshot("qtest")
        assert "efficiency" not in snap
        assert "efficiency" not in (ledger.on_query_gc("qtest") or {})
        assert "efficiency" not in explain.render(snap)


class TestSkewGaugeReset:
    def test_global_skew_gauge_tracks_live_queries_only(self):
        """Regression: the global shuffle.skew gauge was a process-lifetime
        ratchet (set(max(old, new))) — one skewed query pinned it forever
        and /health skew alerts never cleared.  It must drop to the worst
        LIVE query at GC, and to 0 when idle."""
        s = OpStats()
        for qid in ("qa", "qb"):
            s.register_plan(_Graph(qid, {
                0: _Actor("input", targets=(1,), reader=_Reader(1 << 20)),
                1: _Actor("exec", stage=1),
            }))
        # qa: 900/100 over 2 channels -> ratio 1.8; qb: 600/400 -> 1.2
        s.edge("qa", 0, 1, 0, 900)
        s.edge("qa", 0, 1, 1, 100)
        s.edge("qb", 0, 1, 0, 600)
        s.edge("qb", 0, 1, 1, 400)
        s.snapshot("qa")
        s.snapshot("qb")
        g = obs.REGISTRY.gauge("shuffle.skew")
        assert g.value == pytest.approx(1.8)
        s.on_query_gc("qa")
        assert g.value == pytest.approx(1.2)  # worst LIVE query, not ratchet
        s.on_query_gc("qb")
        assert g.value == 0.0
        s.reset()


def test_concurrent_recording_is_consistent(ledger):
    """The hot-path mutators race from engine worker threads; totals must
    land exactly (single-lock discipline, no lost increments)."""
    qid = "qrace"
    ledger.register_plan(_two_stage_graph(qid))

    def pump(ch):
        for _ in range(200):
            ledger.scan(qid, 0, ch, rows_raw=3, rows_out=2, nbytes=10,
                        padded=4)
            ledger.edge(qid, 0, 1, ch, 2)
            ledger.exec_in(qid, 1, ch, [_Batch(nrows=2, padded_len=4)])

    ts = [threading.Thread(target=pump, args=(ch,)) for ch in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = ledger.snapshot(qid)
    ops = {o["actor"]: o for o in snap["operators"]}
    assert ops[0]["rows_in"] == 1200 and ops[0]["rows_out"] == 800
    assert ops[1]["rows_in"] == 800
    assert snap["edges"][0]["rows_total"] == 800
    assert snap["edges"][0]["channel_rows"] == [400, 400]
