"""The arithmetic of a completion log, on a synthetic log with one long
stall and one request that timed out; and the closed loop itself on a fake
service."""

import json
import time

import pytest

from harness import loadgen
from harness.loadgen import Request


def synthetic_log():
    """4 clients x 5 requests of 1 s each, back to back from t=100; client
    0's third request stalls for 11 s; client 3's last times out after 4 s
    and is never answered."""
    log = []
    for c in range(4):
        t = 100.0
        for i in range(5):
            dur = 11.0 if (c, i) == (0, 2) else 1.0
            r = Request(client=c, query="q", params={}, t_submit=t,
                        t_submitted=t + 0.01)
            if (c, i) == (3, 4):
                r.error, r.t_end = "TimeoutError: gave up", t + 4.0
            else:
                r.t_done = r.t_end = t + dur
                r.ok = True
            t = r.t_end
            log.append(r)
    return log


def test_rate_is_over_the_whole_window_stall_included():
    log = synthetic_log()
    # client 0 ends at 100 + 4*1 + 11 = 115; the timed-out request at 108
    assert loadgen.window_s(log) == pytest.approx(15.0)
    assert loadgen.queries_per_s(log) == pytest.approx(19 / 15.0)


def test_percentiles_are_over_all_answered_requests():
    log = synthetic_log()
    lat = loadgen.latencies_ms(log)
    assert len(lat) == 19 and max(lat) == pytest.approx(11000.0)
    assert loadgen.percentile(lat, 50) == pytest.approx(1000.0)
    # 19 values: rank 0.9*18 = 16.2 lies among the 1 s ones, the stall is
    # the last; the 100th is the stall itself
    assert loadgen.percentile(lat, 90) == pytest.approx(1000.0)
    assert loadgen.percentile(lat, 100) == pytest.approx(11000.0)
    assert loadgen.percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert loadgen.percentile([], 90) is None


def test_timed_out_request_is_failed_and_in_no_latency():
    log = synthetic_log()
    assert sum(not r.ok for r in log) == 1
    assert all(r.latency_s is not None for r in log if r.ok)
    assert [r.latency_s for r in log if not r.ok] == [None]
    assert loadgen.queries_per_s([]) is None


TRAFFIC = {
    "clients": 2, "mix": {"a": 3, "b": 1},
    "params": {"a": {"days": {"int_range": [60, 120]}},
               "b": {"day": {"date_range": ["1995-02-27", "1995-03-02"]},
                     "seg": {"choice": ["X", "Y"]}}},
    "values_per_run": {"a": 16},
}


def test_plan_draws_the_runs_parameter_sets_from_the_seed():
    sets = loadgen.plan(TRAFFIC, 2**31 + 5)
    assert sets == loadgen.plan(TRAFFIC, 2**31 + 5)
    assert sets != loadgen.plan(TRAFFIC, 2**31 + 6)
    days = [p["days"] for p in sets["a"]]
    assert len(days) == len(set(days)) == 16
    assert all(60 <= d <= 120 for d in days)
    # no values_per_run for b: the whole grid, both ends of the range in it
    assert sorted((p["day"], p["seg"]) for p in sets["b"]) == [
        (d, s) for d in ("1995-02-27", "1995-02-28", "1995-03-01",
                         "1995-03-02") for s in "XY"]
    # a query without parameters has the one empty set
    assert loadgen.plan({"mix": {"q": 1}}, 3) == {"q": [{}]}
    with pytest.raises(ValueError):
        loadgen.values("gaussian", [0, 1])


def test_schedule_follows_seed_and_weights_and_walks_the_sets():
    sets = loadgen.plan(TRAFFIC, 7)

    def first(n, seed, client):
        draws = loadgen.schedule(TRAFFIC["mix"], sets, seed, client, 2)
        return [next(draws) for _ in range(n)]

    assert first(50, 7, 0) == first(50, 7, 0)
    assert first(50, 7, 0) != first(50, 8, 0)
    assert [q for q, _ in first(50, 2**31 + 5, 1)].count("a") > 25
    # a client sends its query's sets in turn, from its own starting place
    mine = [p for q, p in first(200, 7, 1) if q == "a"][:16]
    assert mine == sets["a"][8:] + sets["a"][:8]


def test_warm_up_pass_sends_every_set_once_and_every_client_something():
    sets = {"a": [{"v": i} for i in range(5)], "b": [{}]}
    dealt = [loadgen.warm_up_pass(sets, c, 4) for c in range(4)]
    assert sorted(json.dumps(x) for d in dealt for x in d) == sorted(
        json.dumps([q, p]) for q in sets for p in sets[q])
    one = [loadgen.warm_up_pass({"q": [{}]}, c, 3) for c in range(3)]
    assert one == [[("q", {})]] * 3


class FakeHandle:
    def __init__(self, delay, fail):
        self.delay, self.fail = delay, fail

    def to_df(self, timeout):
        time.sleep(self.delay)
        if self.fail:
            raise TimeoutError("did not finish")
        return "frame"

    def timings(self):
        return {"queue_s": 0.001, "run_s": self.delay}

    def latency_stats(self):
        return {"count": 7}

    def cancel(self, wait=True):
        self.cancelled = True


def test_closed_loop_drains_and_counts_failures():
    sent = []

    def submit(query, params):
        sent.append((query, params))
        return FakeHandle(0.05, fail=len(sent) == 3)

    log = loadgen.run_closed(
        submit, 2,
        lambda c: loadgen.schedule({"q": 1}, {"q": [{"v": 1}]}, 5, c, 2),
        timeout_s=1.0, seconds=0.4)
    assert len(log) == len(sent) >= 8
    failed = [r for r in log if r.t_done is None]
    assert len(failed) == 1 and failed[0].error.startswith("TimeoutError")
    assert all(r.t_end >= r.t_submit for r in log)
    # nothing is submitted after the window closes; in-flight ones finish
    t_open = min(r.t_submit for r in log)
    assert max(r.t_submit for r in log) - t_open < 0.4
    assert all(r.tasks == 7 and r.answer == "frame"
               for r in log if r.t_done is not None)
    assert all(r.params == {"v": 1} for r in log)
    warm = loadgen.run_closed(
        submit, 3, lambda c: [("q", {"v": c}), ("q", {})], timeout_s=1.0)
    assert len(warm) == 6 and {r.params.get("v") for r in warm} == {
        0, 1, 2, None}
