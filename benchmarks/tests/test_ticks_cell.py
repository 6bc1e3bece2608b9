"""The ``ticks_1d.asof_s2`` cell (ISSUE 28): it resolves from entries alone,
its two per-layer metrics read a run as their files say (and nothing, never
an error, from a program that lacks what they read), and adding it edited no
file the benchmark already had."""

import os
import subprocess
import types

import pytest

from conftest import ROOT
from harness import loadgen, spec

CELL = "ticks_1d.asof_s2"
PEAKS = {"hbm_bytes_per_s": 819e9}


def reader(name):
    return spec.load_module("metrics", name)


def test_cell_resolves_from_its_entries():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.config_name == "ticks_1d"
    assert cell.config["rows"] == {"quotes": 6000000, "trades": 1150000}
    assert cell.traffic["clients"] == 2 and cell.traffic["mix"] == {"asof": 1}
    assert list(cell.queries) == ["asof"]
    conf = {c["name"]: c for c in cell.bench["configs"]}["ticks_1d"]
    assert conf["reduced"] == ["rows"]
    assert "blog/orderedstreams.md:51" in conf["source"]
    assert {e["name"] for e, _ in cell.metrics("end_to_end")} == {
        "queries_per_s", "latency_p50_ms", "setup_s"}
    per_layer = {e["name"]: e for e, _ in cell.metrics("per_layer")}
    for name in ("asof_match_roofline", "asof_flushes_per_query"):
        assert per_layer[name]["workloads"] == [CELL]
    # the accepted per-layer metrics without a list are this cell's too
    assert {"scan_roofline", "device_idle_pct", "pad_waste_pct",
            "compiles_in_window"} <= set(per_layer)
    other = spec.Cell("tpch_sf1.q3_s2")
    assert not {"asof_match_roofline", "asof_flushes_per_query"} & {
        e["name"] for e, _ in other.metrics("per_layer")}


def traced_run(device_ops, requests=((100.0, 104.0, 4.0, True),),
               span=(100.0, 105.0), peaks=PEAKS, query="asof"):
    """A run whose traced span is ``span`` and whose log holds requests
    (t_submit, t_done, run_s, ok)."""
    log = []
    for t_submit, t_done, run_s, ok in requests:
        log.append(loadgen.Request(
            client=0, query=query, params={}, t_submit=t_submit,
            t_done=t_done, t_end=t_done, run_s=run_s, ok=ok))
    return types.SimpleNamespace(
        log=log, trace={"device_ops": device_ops, "busy_s": 4.9},
        trace_span=span, peaks=peaks)


def test_match_roofline_counts_the_match_modules_only():
    m = reader("asof_match_roofline")
    least = m.match_least_bytes(1150000, 6000000)
    assert least == 1150000 * 12 + 6000000 * 8 == 61_800_000
    ops = [["jit__ss_probe", 3.0], ["jit_gather", 0.8],
           ["jit__ss_sort_quotes", 0.1], ["jit__append_rows", 0.3],
           ["jit__asof_match", 0.05], ["jit_asof_fused", 0.05],
           ["jit__fused_concat_kernel", 0.2]]
    assert m.match_seconds(ops) == pytest.approx(3.2)
    # one whole request inside the span: its bytes over the match's seconds
    got = m.read(traced_run(ops))
    assert got == pytest.approx(100 * least / 819e9 / 3.2)
    assert 0 < got < 100
    # half of a request inside the span counts half; a failed one nothing
    half = m.read(traced_run(ops, requests=((98.0, 102.0, 4.0, True),
                                            (100.0, 104.0, 4.0, False))))
    assert half == pytest.approx(got / 2)
    # requests of another query are not the match's work
    assert m.read(traced_run(ops, query="q3")) is None


def test_match_roofline_cannot_pass_100_and_reports_nothing_unread():
    m = reader("asof_match_roofline")
    least = m.match_least_bytes(1150000, 6000000)
    # the span's requests in the least time the peak allows: 100 exactly.
    # No program moves the bytes faster, so no reading lies above it
    floor_s = least / 819e9
    assert m.read(traced_run([["jit__ss_probe", floor_s]])) == (
        pytest.approx(100.0))
    # nothing to read: no trace, no peaks (a rehearsal), no match module in
    # the span's modules (a program without these kernels), no request
    assert m.read(types.SimpleNamespace(
        log=[], trace=None, trace_span=None, peaks=PEAKS)) is None
    assert m.read(traced_run([["jit__ss_probe", 3.0]], peaks=None)) is None
    assert m.read(traced_run([["jit__pk_probe_sorted", 3.0]])) is None
    assert m.read(traced_run([["jit__ss_probe", 3.0]], requests=())) is None


def test_flushes_per_query_reads_the_window_records(monkeypatch):
    from quokka_tpu.obs import querylog

    m = reader("asof_flushes_per_query")
    run = types.SimpleNamespace(log=[loadgen.Request(
        client=0, query="asof", params={}, t_submit=100.0, t_end=110.0)])
    recs = [{"status": "done", "done": 99.0, "asof_flushes": 50},
            {"status": "done", "done": 103.0, "asof_flushes": 4},
            {"status": "done", "done": 108.0, "asof_flushes": 5},
            {"status": "failed", "done": 109.0, "asof_flushes": 9}]
    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs if r["done"] > (since or 0)])
    assert m.read(run) == pytest.approx(4.5)
    # a program whose records lack the counter, and one with no record
    for r in recs:
        del r["asof_flushes"]
    assert m.read(run) is None
    monkeypatch.setattr(querylog, "records", lambda since=None: [])
    assert m.read(run) is None
    assert m.read(types.SimpleNamespace(log=[])) is None


def test_no_existing_benchmark_file_was_edited():
    """Against the checkout's HEAD: files under ``benchmarks/`` may be added
    (``??`` / ``A``), never changed, renamed or deleted."""
    try:
        out = subprocess.run(
            ["git", "status", "--porcelain", "--", "benchmarks"], cwd=ROOT,
            capture_output=True, text=True, timeout=60, check=True).stdout
    except (OSError, subprocess.SubprocessError):
        pytest.skip("not a git checkout")
    edited = [line for line in out.splitlines()
              if line[:2].strip() not in ("??", "A")]
    assert not edited, edited
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                       "asof_match_roofline.py"))
