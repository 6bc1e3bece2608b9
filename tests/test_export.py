"""Prometheus exporter + /metrics //status sidecar: text-format rendering
(escaping, label families, histogram series), histogram quantiles, and an
e2e scrape of a live 2-query QueryService run (ISSUE 5)."""

import json
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from quokka_tpu import QuokkaContext
from quokka_tpu.obs import export
from quokka_tpu.obs.metrics import Registry
from quokka_tpu.service import QueryService

# one Prometheus text-format sample line: name{labels} value
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9eE.+-]+$|^# (TYPE|HELP) ")


def _valid_exposition(text):
    for line in text.strip().splitlines():
        assert _SAMPLE.match(line) or line.startswith("# "), line
    return True


class TestRender:
    def test_label_escaping(self):
        r = Registry()
        r.counter('cache.plan_hit.we"ird\\q\nid').inc(2)
        text = export.render(r)
        assert (r'quokka_cache_plan_hit_total{query="we\"ird\\q\nid"} 2'
                in text)
        assert _valid_exposition(text)

    def test_counter_gauge_histogram_families(self):
        r = Registry()
        r.counter("integrity.corrupt").inc()
        r.counter("rpc.tget").inc(5)
        r.gauge("pool.size").set(2)
        h = r.histogram("task.latency_s")
        for v in (0.001, 0.02, 3.0):
            h.observe(v)
        text = export.render(r)
        assert "# TYPE quokka_integrity_corrupt_total counter" in text
        assert 'quokka_rpc_calls_total{method="tget"} 5' in text
        assert "quokka_pool_size 2" in text
        # histogram: cumulative buckets, +Inf, sum and count.  The
        # process-wide aggregate renders as its OWN family (every dispatch
        # also lands in the per-query labeled family; sharing one family
        # would double-count under sum()-style PromQL)
        assert "# TYPE quokka_task_latency_all_seconds histogram" in text
        assert 'quokka_task_latency_all_seconds_bucket{le="+Inf"} 3' in text
        assert "quokka_task_latency_all_seconds_count 3" in text
        m = re.search(r"quokka_task_latency_all_seconds_sum ([\d.]+)", text)
        assert m and float(m.group(1)) == pytest.approx(3.021)
        # cumulative monotonicity across the series
        buckets = [int(x) for x in re.findall(
            r'quokka_task_latency_all_seconds_bucket\{le="[^"]+"\} (\d+)',
            text)]
        assert buckets == sorted(buckets) and buckets[-1] == 3
        assert _valid_exposition(text)

    def test_aggregate_and_per_query_families_are_distinct(self):
        """One observation into both the aggregate and a per-query series
        must NOT appear twice in one family (scrape-side sum() would
        double-count the task rate)."""
        r = Registry()
        r.histogram("task.latency_s").observe(0.01)
        r.histogram("task.latency_s.q1").observe(0.01)
        r.counter("cache.plan_hit").inc()
        r.counter("cache.plan_hit.q1").inc()
        text = export.render(r)
        assert "quokka_task_latency_seconds_count 1" not in text
        assert ('quokka_task_latency_seconds_count{query="q1"} 1'
                in text)
        assert "quokka_task_latency_all_seconds_count 1" in text
        assert "quokka_cache_plan_hit_all_total 1" in text
        assert 'quokka_cache_plan_hit_total{query="q1"} 1' in text
        assert "quokka_cache_plan_hit_total 1\n" not in text

    def test_mem_families_render_with_labels(self):
        """Memory-plane gauges: per-query and per-site twins render as
        labeled families (escaping included); the aggregates keep their own
        _all names so sum() over the labeled family never double-counts."""
        r = Registry()
        r.gauge("mem.live_bytes").set(1024)
        r.gauge('mem.live_bytes.q"1').set(512)
        r.gauge('mem.peak_bytes.q"1').set(2048)
        r.gauge('mem.spill_resident_bytes.q"1').set(128)
        r.gauge("mem.peak_bytes").set(4096)
        r.gauge("mem.spill_resident_bytes").set(256)
        r.gauge("mem.site_bytes.shuffle").set(640)
        text = export.render(r)
        assert "quokka_mem_live_bytes_all 1024" in text
        assert "quokka_mem_peak_bytes_all 4096" in text
        assert "quokka_mem_spill_resident_bytes_all 256" in text
        assert 'quokka_mem_live_bytes{query="q\\"1"} 512' in text
        assert 'quokka_mem_peak_bytes{query="q\\"1"} 2048' in text
        assert ('quokka_mem_spill_resident_bytes{query="q\\"1"} 128'
                in text)
        assert 'quokka_mem_site_bytes{site="shuffle"} 640' in text
        # the aggregate never renders bare under the labeled family name
        assert "quokka_mem_live_bytes 1024" not in text
        assert _valid_exposition(text)

    def test_per_query_histogram_renders_as_label(self):
        r = Registry()
        r.histogram("task.latency_s.qfoo").observe(0.01)
        text = export.render(r)
        assert ('quokka_task_latency_seconds_count{query="qfoo"} 1'
                in text)

    def test_extra_gauges(self):
        text = export.render(Registry(),
                             extra_gauges={"obs_dropped_events": 7})
        assert "quokka_obs_dropped_events 7" in text


class TestHistogramQuantiles:
    def test_quantiles_track_observations(self):
        r = Registry()
        h = r.histogram("task.latency_s")
        assert h.quantile(0.5) is None  # empty
        for _ in range(90):
            h.observe(0.003)
        for _ in range(10):
            h.observe(1.8)
        st = h.stats()
        assert st["count"] == 100
        assert 0.0025 <= st["p50"] <= 0.005
        assert 1.0 <= st["p95"] <= 2.5  # rank 95 falls in the tail mass
        assert st["sum"] == pytest.approx(90 * 0.003 + 10 * 1.8)

    def test_overflow_bucket_reports_last_bound(self):
        r = Registry()
        h = r.histogram("x_s", buckets=(0.1, 1.0))
        h.observe(50.0)
        assert h.quantile(0.5) == 1.0

    def test_conflicting_bucket_request_raises(self):
        r = Registry()
        r.histogram("x_s", buckets=(0.1, 1.0))
        with pytest.raises(ValueError, match="already exists"):
            r.histogram("x_s", buckets=(0.5, 5.0))
        assert r.histogram("x_s").bounds == (0.1, 1.0)  # no-buckets reuse ok


def _scrape(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers.get("Content-Type"), \
            resp.read().decode()


class TestHttpSidecar:
    def test_metrics_status_and_404(self):
        server = export.MetricsServer(port=0)
        try:
            code, ctype, text = _scrape(server.url("/metrics"))
            assert code == 200 and ctype.startswith("text/plain")
            assert "quokka_obs_dropped_events" in text
            code, ctype, body = _scrape(server.url("/status"))
            assert code == 200 and ctype == "application/json"
            status = json.loads(body)
            assert status["obs"]["recorder_enabled"] in (True, False)
            assert "service" not in status  # none attached
            with pytest.raises(urllib.error.HTTPError) as ei:
                _scrape(server.url("/nope"))
            assert ei.value.code == 404
        finally:
            server.close()

    def test_status_format_twins(self):
        """/status?format=json is the explicit machine spelling of the
        default JSON payload; ?format=text renders the same dict for
        humans (ISSUE 17 satellite)."""
        server = export.MetricsServer(port=0)
        try:
            _, ctype, body = _scrape(server.url("/status?format=json"))
            assert ctype == "application/json"
            explicit = json.loads(body)
            _, _, default_body = _scrape(server.url("/status"))
            assert set(explicit) == set(json.loads(default_body))
            assert "obs" in explicit and "pid" in explicit
            code, ctype, text = _scrape(server.url("/status?format=text"))
            assert code == 200 and ctype.startswith("text/plain")
            assert text.startswith("quokka pid=")
            assert "health=" in text
        finally:
            server.close()

    def test_history_and_health_endpoints(self):
        from quokka_tpu.obs import alerts, history

        server = export.MetricsServer(port=0)
        try:
            history.RING.record()
            history.RING.record()
            code, ctype, body = _scrape(server.url("/history"))
            assert code == 200 and ctype == "application/json"
            hist = json.loads(body)
            assert {"interval_s", "depth", "samples", "rates"} <= set(hist)
            assert len(hist["samples"]) >= 2
            assert {"t", "counters", "gauges", "histograms"} <= set(
                hist["samples"][-1])
            code, ctype, body = _scrape(server.url("/health"))
            assert code == 200 and ctype == "application/json"
            health = json.loads(body)
            assert health["status"] in ("ok", "degraded", "critical")
            assert isinstance(health["firing"], list)
            assert health["status"] == alerts.ENGINE.health()["status"]
        finally:
            server.close()

    def test_start_from_env(self, monkeypatch):
        monkeypatch.delenv("QK_METRICS_PORT", raising=False)
        assert export.start_from_env() is None
        monkeypatch.setenv("QK_METRICS_PORT", "0")
        server = export.start_from_env()
        try:
            assert server is not None and server.port > 0
        finally:
            server.close()


def _slow_query(ctx, n=40_000, delay_s=0.02):
    from quokka_tpu.dataset.readers import InputArrowDataset

    r = np.random.default_rng(1)
    table = pa.table({"k": r.integers(0, 16, n).astype(np.int64),
                      "v": r.integers(0, 1000, n).astype(np.int64)})

    class Slow(InputArrowDataset):
        def execute(self, channel, lineage):
            time.sleep(delay_s)
            return super().execute(channel, lineage)

    return (ctx.read_dataset(Slow(table, batch_rows=2048))
            .groupby("k").agg_sql("sum(v) as sv, count(*) as n"))


class TestLiveServiceScrape:
    def test_scrape_during_two_query_run(self, monkeypatch):
        """ISSUE 5 acceptance: curl :$QK_METRICS_PORT/metrics during a live
        2-query service run returns valid Prometheus text exposition
        including per-query histograms; /status names the live queries."""
        monkeypatch.setenv("QK_METRICS_PORT", "0")
        with QueryService(pool_size=2) as svc:
            assert svc.metrics_server is not None
            h1 = svc.submit(_slow_query(QuokkaContext()))
            h2 = svc.submit(_slow_query(QuokkaContext()))
            qids = {h1.query_id, h2.query_id}
            # poll until both queries are live AND have dispatched tasks
            deadline = time.time() + 30
            status = text = None
            while time.time() < deadline:
                _, _, body = _scrape(svc.metrics_server.url("/status"))
                status = json.loads(body)
                sess = status["service"]["sessions"]
                if (set(sess) == qids
                        and all(s["status"] == "running"
                                and s["tasks"] > 0 for s in sess.values())):
                    _, ctype, text = _scrape(
                        svc.metrics_server.url("/metrics"))
                    assert ctype.startswith("text/plain")
                    break
                time.sleep(0.01)
            assert text is not None, f"queries never ran concurrently: " \
                                     f"{status}"
            assert _valid_exposition(text)
            for qid in qids:  # per-query task-latency histograms, live
                assert (f'quokka_task_latency_seconds_count'
                        f'{{query="{qid}"}}' in text), text[:800]
            sess = status["service"]["sessions"]
            for qid in qids:
                assert sess[qid]["task_p50_s"] is None or \
                    sess[qid]["task_p50_s"] > 0
            assert "admission" in status["service"]
            assert status["service"]["workers_alive"] == 2
            for h in (h1, h2):
                assert h.to_df(timeout=300) is not None
            # the per-query latency snapshot survives the namespace GC
            lat = h1.latency_stats()
            assert lat["count"] > 0 and lat["p50"] > 0
        # sidecar stops with the service: the socket must refuse
        with pytest.raises((urllib.error.URLError, ConnectionError,
                            OSError)):
            _scrape(svc.metrics_server.url("/metrics"), timeout=2)


# -- what a finished query leaves on each surface ----------------------------


@pytest.fixture(scope="module")
def after_a_query():
    """One service query with the sidecar on: (/status after it finished,
    the families that carried a sample labelled with the query while it
    ran, the handle's explain dict)."""
    def labelled(svc, qid):
        _, _, text = _scrape(svc.metrics_server.url("/metrics"))
        assert _valid_exposition(text)
        found, family = set(), None
        for line in text.splitlines():
            if line.startswith("# TYPE "):
                family = line.split()[2]
            elif f'"{qid}' in line:
                found.add(family)
        return found

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("QK_METRICS_PORT", "0")
        with QueryService(pool_size=2) as svc:
            h = svc.submit(_slow_query(QuokkaContext()))
            families = set()
            while not h.done:
                families |= labelled(svc, h.query_id)
                time.sleep(0.01)
            assert len(h.to_df(timeout=300)) == 16
            _, _, body = _scrape(svc.metrics_server.url("/status"))
            return json.loads(body), families, h.explain(as_dict=True)


def test_status_is_the_process_and_its_service(after_a_query):
    """No plane hangs a digest of its own on /status: what is there is the
    process, the recorder, the incident counters and the service."""
    status, _, _ = after_a_query
    assert set(status) == {"pid", "time", "uptime_s", "obs",
                           "integrity_corrupt", "chaos", "service"}


def test_every_family_a_running_query_labels_is_documented(after_a_query):
    """test_metrics_doc.py holds the README's table to the source; this
    holds it to what a running query really puts on /metrics under its own
    label (a family of a deleted plane would show here first).  Only the
    query's own samples: the registry is the process's, and other tests'
    instruments are in it."""
    import test_metrics_doc

    _, families, _ = after_a_query
    assert "quokka_task_latency_seconds" in families
    assert families <= test_metrics_doc._documented_families(), sorted(
        families - test_metrics_doc._documented_families())


def test_explain_dict_is_operators_edges_and_decisions(after_a_query):
    """The machine-readable EXPLAIN carries what the operators counted and
    what the planner chose; no section derived from host seconds."""
    _, _, snap = after_a_query
    assert {"query_id", "operators", "edges", "planner",
            "top_operators"} <= set(snap)
    assert "efficiency" not in snap
    assert all("efficiency" not in op for op in snap["operators"])
