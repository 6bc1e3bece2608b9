"""Health & progress plane (ISSUE 17): the progress estimator's known
answers (cold size_hint fallback, warm cardprofile blend), the monotone
clamp under out-of-order ledger views, ETA math on a synthetic timeline,
GC idempotence; the alert rules' known-answer matrix with edge-triggered
counting and the ok/degraded/critical fold; the history ring's rate math
and depth eviction."""

import pytest

from quokka_tpu import obs
from quokka_tpu.obs import alerts, opstats
from quokka_tpu.obs.alerts import AlertEngine
from quokka_tpu.obs.history import HistoryRing
from quokka_tpu.obs.progress import ProgressTracker, _estimate

# ---------------------------------------------------------------------------
# progress: the pure estimator
# ---------------------------------------------------------------------------


def _view(scanned=0, hint=0, ops=None, qid="q1", fp="fp1", t0=0.0):
    return {"query_id": qid, "plan_fp": fp, "t0": t0,
            "size_hint_bytes": hint, "scanned_bytes": scanned,
            "scanned_rows": 0, "op_rows_out": ops or {}}


class TestEstimate:
    def test_cold_plan_falls_back_to_size_hint(self):
        raw, basis, detail = _estimate(_view(scanned=50, hint=100), None)
        assert (raw, basis) == (0.5, "size_hint")
        assert detail["source_bytes_total"] == 100
        assert detail["source_bytes_done"] == 50

    def test_no_denominator_reports_none_basis(self):
        raw, basis, _ = _estimate(_view(scanned=50, hint=0), None)
        assert (raw, basis) == (0.0, "none")

    def test_warm_plan_blends_scan_and_operator_completion(self):
        profile = {"source_bytes": 200, "rows": {"a2:agg": 10, "a3:x": 0}}
        raw, basis, detail = _estimate(
            _view(scanned=100, ops={"a2:agg": 5, "a3:x": 7}), profile)
        # scan 100/200 = 0.5; op a2 5/10 = 0.5 (a3 has no prior: skipped);
        # blend = 0.5*0.5 + 0.5*0.5
        assert basis == "cardprofile"
        assert raw == pytest.approx(0.5)
        assert detail["profiled_ops"] == 1
        assert detail["op_completion"] == pytest.approx(0.5)
        assert detail["source_bytes_total"] == 200

    def test_warm_plan_without_op_priors_uses_scan_fraction(self):
        raw, basis, detail = _estimate(
            _view(scanned=150), {"source_bytes": 200, "rows": {}})
        assert basis == "cardprofile"
        assert raw == pytest.approx(0.75)
        assert detail["profiled_ops"] == 0

    def test_overshoot_clamps_to_one(self):
        profile = {"source_bytes": 100, "rows": {"a2:agg": 4}}
        raw, _, _ = _estimate(
            _view(scanned=300, ops={"a2:agg": 9}), profile)
        assert raw == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# progress: the tracker (monotone clamp, ETA, GC)
# ---------------------------------------------------------------------------


@pytest.fixture
def ledger(monkeypatch):
    """A synthetic opstats ledger: tests mutate views[qid] to feed the
    tracker; plan profiles resolve to None (cold) unless overridden."""
    views = {}
    monkeypatch.setattr(opstats.OPSTATS, "progress_view",
                        lambda qid: views.get(qid))
    monkeypatch.setattr(opstats, "_plan_entry", lambda fp: None)
    return views


class TestTracker:
    def test_unknown_query_returns_none(self, ledger):
        assert ProgressTracker().snapshot("nope") is None

    def test_fraction_monotone_under_out_of_order_views(self, ledger):
        tr = ProgressTracker()
        ledger["qm"] = _view(scanned=80, hint=100, qid="qm")
        assert tr.snapshot("qm", now=1.0)["fraction"] == pytest.approx(0.8)
        # an out-of-order (shrinking) ledger report never moves the bar back
        ledger["qm"] = _view(scanned=40, hint=100, qid="qm")
        assert tr.snapshot("qm", now=2.0)["fraction"] == pytest.approx(0.8)
        # and a live query never claims completion: capped below 1.0
        ledger["qm"] = _view(scanned=100, hint=100, qid="qm")
        snap = tr.snapshot("qm", now=3.0)
        assert snap["fraction"] == pytest.approx(0.99)
        assert snap["basis"] == "size_hint"
        tr.on_query_gc("qm")

    def test_eta_known_answer_on_synthetic_timeline(self, ledger):
        tr = ProgressTracker()
        ledger["qe"] = _view(scanned=20, hint=100, qid="qe")
        first = tr.snapshot("qe", now=100.0)
        assert first["eta_s"] is None  # one sample: no rate yet
        ledger["qe"] = _view(scanned=40, hint=100, qid="qe")
        snap = tr.snapshot("qe", now=110.0)
        # rate = (0.4 - 0.2) / 10s = 0.02/s; eta = (1 - 0.4) / 0.02 = 30s
        assert snap["rate_per_s"] == pytest.approx(0.02)
        assert snap["eta_s"] == pytest.approx(30.0)
        tr.on_query_gc("qe")

    def test_gauges_exported_live_and_removed_on_gc(self, ledger):
        tr = ProgressTracker()
        ledger["qg"] = _view(scanned=50, hint=100, qid="qg")
        tr.snapshot("qg", now=1.0)
        snap = obs.REGISTRY.snapshot()
        assert snap["progress.fraction.qg"] == pytest.approx(0.5)
        assert snap["progress.eta_s.qg"] == -1.0  # no rate yet -> no ETA
        tr.on_query_gc("qg")
        snap = obs.REGISTRY.snapshot()
        assert "progress.fraction.qg" not in snap
        assert "progress.eta_s.qg" not in snap

    def test_gc_stamps_finished_and_is_idempotent(self, ledger):
        tr = ProgressTracker()
        ledger["qd"] = _view(scanned=50, hint=100, qid="qd")
        tr.snapshot("qd", now=1.0)
        final = tr.on_query_gc("qd", finished=True)
        assert final["fraction"] == 1.0 and final["eta_s"] == 0.0
        assert tr.last_finished()["query_id"] == "qd"

    def test_failed_query_keeps_honest_fraction_across_double_gc(
            self, ledger):
        tr = ProgressTracker()
        ledger["qf"] = _view(scanned=40, hint=100, qid="qf")
        tr.snapshot("qf", now=1.0)
        # session.finish() GCs with finished=False on error ...
        snap = tr.on_query_gc("qf", finished=False)
        assert snap["fraction"] == pytest.approx(0.4)
        del ledger["qf"]
        # ... then the engine's cleanup hook fires again with the default
        # finished=True: the stash must NOT be restamped to 1.0
        again = tr.on_query_gc("qf")
        assert again["fraction"] == pytest.approx(0.4)
        assert tr.last_finished()["fraction"] == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# alerts: the rule matrix
# ---------------------------------------------------------------------------


def _sample(counters=None, gauges=None, hists=None, t=0.0):
    return {"t": t, "counters": counters or {}, "gauges": gauges or {},
            "histograms": hists or {}}


class TestAlertRules:
    def test_channel_skew_fires_on_per_edge_gauge_only(self):
        hot = _sample(gauges={"shuffle.skew.q1.a0-a1": 3.0})
        assert "a0-a1" in alerts._rule_channel_skew(hot, None, {})
        cool = _sample(gauges={"shuffle.skew.q1.a0-a1": 1.5})
        assert alerts._rule_channel_skew(cool, None, {}) is None
        # the process-lifetime max gauge never resets: it must not pin the
        # alert after the skewed query is long gone
        global_max = _sample(gauges={"shuffle.skew": 99.0})
        assert alerts._rule_channel_skew(global_max, None, {}) is None

    def test_watermark_lag_threshold(self, monkeypatch):
        monkeypatch.setenv("QK_ALERT_WM_LAG_S", "30")
        hot = _sample(gauges={"stream.watermark_lag_s.s1": 45.0})
        assert "45.0s" in alerts._rule_watermark_lag(hot, None, {})
        cool = _sample(gauges={"stream.watermark_lag_s.s1": 5.0})
        assert alerts._rule_watermark_lag(cool, None, {}) is None

    def test_mem_budget_critical_threshold(self, monkeypatch):
        monkeypatch.setenv("QK_SERVICE_MEM_BUDGET", "1000")
        hot = _sample(gauges={"mem.live_bytes.q1": 950.0})
        assert "95%" in alerts._rule_mem_budget(hot, None, {})
        cool = _sample(gauges={"mem.live_bytes.q1": 500.0})
        assert alerts._rule_mem_budget(cool, None, {}) is None

    def test_queue_wait_needs_high_p95_and_fresh_arrivals(self, monkeypatch):
        monkeypatch.setenv("QK_ALERT_QUEUE_P95_S", "0.5")
        obs.REGISTRY.remove("admission.queue_wait_s")
        try:
            h = obs.REGISTRY.histogram("admission.queue_wait_s")
            for _ in range(10):
                h.observe(2.0)
            cur = _sample(hists={"admission.queue_wait_s": (10, 20.0)})
            prev = _sample(hists={"admission.queue_wait_s": (4, 8.0)})
            assert "p95" in alerts._rule_queue_wait(cur, prev, {})
            # same count since last sample: the pileup is historical — the
            # cumulative histogram must not pin the alert forever
            assert alerts._rule_queue_wait(cur, cur, {}) is None
        finally:
            obs.REGISTRY.remove("admission.queue_wait_s")

    def test_no_progress_streak_then_recovery(self, monkeypatch):
        monkeypatch.setenv("QK_ALERT_STALL_EVALS", "3")
        state = {}
        stuck = _sample(gauges={"progress.fraction.q1": 0.42})
        assert alerts._rule_no_progress(stuck, stuck, state) is None
        assert alerts._rule_no_progress(stuck, stuck, state) is None
        msg = alerts._rule_no_progress(stuck, stuck, state)
        assert msg is not None and "q1" in msg and "42%" in msg
        # progress resumes: streak resets, three more evals to re-fire
        moved = _sample(gauges={"progress.fraction.q1": 0.43})
        assert alerts._rule_no_progress(moved, stuck, state) is None
        assert state["streaks"] == {}

    def test_no_progress_ignores_nearly_done_queries(self):
        state = {}
        tail = _sample(gauges={"progress.fraction.q1": 0.99})
        for _ in range(5):
            assert alerts._rule_no_progress(tail, tail, state) is None

    def test_mem_leak_and_integrity_fire_on_counter_deltas(self):
        cur = _sample(counters={"mem.leaked": 3, "integrity.corrupt": 2})
        prev = _sample(counters={"mem.leaked": 1, "integrity.corrupt": 2})
        assert "2 allocation(s)" in alerts._rule_mem_leak(cur, prev, {})
        assert alerts._rule_integrity(cur, prev, {}) is None
        prev2 = _sample(counters={"mem.leaked": 3, "integrity.corrupt": 0})
        assert alerts._rule_mem_leak(cur, prev2, {}) is None
        assert "2 checksum" in alerts._rule_integrity(cur, prev2, {})


class TestAlertEngine:
    def test_edge_triggered_fire_refresh_clear(self):
        eng = AlertEngine()
        hot = {"shuffle.skew.q9.a0-a1": 9.0}
        fired0 = obs.REGISTRY.snapshot().get("alert.channel_skew", 0)
        fired = eng.evaluate(_sample(gauges=hot, t=1.0))
        assert [f["rule"] for f in fired] == ["channel_skew"]
        assert eng.health()["status"] == "degraded"
        since = eng.health()["firing"][0]["since"]
        # staying hot: no new fire, no counter bump, edge time kept
        assert eng.evaluate(_sample(gauges=hot, t=2.0)) == []
        assert eng.health()["firing"][0]["since"] == since
        assert obs.REGISTRY.snapshot().get(
            "alert.channel_skew", 0) - fired0 == 1
        # clearing recovers
        eng.evaluate(_sample(t=3.0))
        assert eng.health() == {"status": "ok", "firing": [],
                                "evaluated_at": 3.0}

    def test_critical_rule_wins_the_verdict(self, monkeypatch):
        monkeypatch.setenv("QK_SERVICE_MEM_BUDGET", "1000")
        eng = AlertEngine()
        eng.evaluate(_sample(gauges={"mem.live_bytes.q1": 990.0,
                                     "shuffle.skew.q1.a0-a1": 5.0}, t=1.0))
        h = eng.health()
        assert h["status"] == "critical"
        assert [f["rule"] for f in h["firing"]] == ["channel_skew",
                                                    "mem_budget"]
        assert obs.REGISTRY.snapshot().get("health.status") == 2.0
        eng.evaluate(_sample(t=2.0))
        assert obs.REGISTRY.snapshot().get("health.status") == 0.0

    def test_broken_rule_does_not_sink_the_evaluation(self, monkeypatch):
        eng = AlertEngine()
        monkeypatch.setattr(alerts, "RULES", alerts.RULES + (
            ("boom", "warn",
             lambda cur, prev, st: (_ for _ in ()).throw(RuntimeError())),
        ))
        assert eng.evaluate(_sample(t=1.0)) == []
        assert eng.health()["status"] == "ok"


# ---------------------------------------------------------------------------
# history: the sample ring
# ---------------------------------------------------------------------------


class TestHistoryRing:
    def test_depth_eviction_keeps_newest(self, monkeypatch):
        monkeypatch.setenv("QK_HISTORY_DEPTH", "3")
        ring = HistoryRing()
        for i in range(5):
            ring.record(now=float(i))
        kept = ring.samples()
        assert [s["t"] for s in kept] == [2.0, 3.0, 4.0]
        assert ring.payload()["depth"] == 3

    def test_rates_derive_only_for_moved_counters(self):
        ring = HistoryRing()
        moving = obs.REGISTRY.counter("healthtest.moving")
        obs.REGISTRY.counter("healthtest.static").inc()
        try:
            ring.record(now=100.0)
            moving.inc(5)
            obs.REGISTRY.histogram("healthtest.h_s").observe(0.5)
            ring.record(now=110.0)
            rates = ring.rates()
            assert rates["healthtest.moving"] == [
                {"t": 110.0, "rate": 0.5}]
            assert "healthtest.static" not in rates
            # histogram observation rates under the .count key
            assert rates["healthtest.h_s.count"] == [
                {"t": 110.0, "rate": 0.1}]
        finally:
            obs.REGISTRY.remove("healthtest.moving", "healthtest.static",
                                "healthtest.h_s")

    def test_record_counts_itself(self):
        ring = HistoryRing()
        before = obs.REGISTRY.snapshot().get("history.samples", 0)
        sample = ring.record(now=1.0)
        assert sample["t"] == 1.0
        assert {"counters", "gauges", "histograms"} <= set(sample)
        assert obs.REGISTRY.snapshot()["history.samples"] == before + 1
