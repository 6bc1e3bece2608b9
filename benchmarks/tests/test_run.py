"""A whole run on the CPU (``--rehearse`` skips the look for a chip, all
else is the run the driver makes): sound, then with the timed path broken
underneath, where ``correct`` has to come out false; and the data-driven
property: a cell whose configuration, generator, traffic mix, query and
per-layer metric are new files plus entries, no file edited."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT

WORKLOAD = "tpch_sf1.q1_s2"


def run_cell(capsys, *extra):
    import run

    rc = run.main(["--workload", WORKLOAD, "--seed", str(2**31 + 77),
                   "--seconds", "2", "--rehearse", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_sound_run_is_correct_and_well_formed(capsys):
    rc, result = run_cell(capsys, "--trace", "0")
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "queries_per_s", "latency_p50_ms", "latency_p90_ms", "setup_s"}
    assert result["window"]["requests"] == result["attempted"]
    assert result["window"]["parameter_sets"] == 16
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "compared"
    assert result["compared"]["q1.wrong_cells"] == {"value": 0, "limit": 0}
    assert result["device"]["platform"] == "cpu"  # never passes for a chip


@pytest.mark.parametrize("which", ["count", "sum"])
def test_one_altered_answer_is_not_correct(which, capsys, monkeypatch):
    """The second answer of the window (not of a warm-up pass) altered where
    it is produced; every other answer is right."""
    from quokka_tpu.service.session import QueryHandle

    from harness import loadgen

    to_df, run_closed, state = QueryHandle.to_df, loadgen.run_closed, {}

    def window_aware(*a, **kw):
        if kw.get("seconds") is not None:
            state["answers"] = 0
        return run_closed(*a, **kw)

    def altered(self, timeout=None):
        frame = to_df(self, timeout)
        if "answers" in state:
            state["answers"] += 1
            if state["answers"] == 2:
                frame = frame.copy()
                if which == "count":
                    frame.loc[0, "count_order"] += 1
                else:
                    frame.loc[0, "sum_qty"] *= 1.001
        return frame

    monkeypatch.setattr(loadgen, "run_closed", window_aware)
    monkeypatch.setattr(QueryHandle, "to_df", altered)
    rc, result = run_cell(capsys, "--trace", "0")
    assert rc == 0 and result["correct"] is False
    assert result["failed"] == 1 and result["attempted"] > 2
    key = "q1.wrong_cells" if which == "count" else "q1.sum_rel_err"
    assert result["compared"][key]["value"] > result["compared"][key]["limit"]


def test_half_of_the_rows_left_out_is_not_correct(capsys, monkeypatch,
                                                  tmp_path):
    import pyarrow.parquet as pq

    from harness import spec

    q1 = spec.load_module("queries", "q1")
    build = q1.build

    def build_on_half(ctx, paths, params):
        table = pq.read_table(paths["lineitem"])
        half = str(tmp_path / "half.parquet")
        if not os.path.exists(half):
            pq.write_table(table.slice(0, table.num_rows // 2), half)
        return build(ctx, dict(paths, lineitem=half), params)

    monkeypatch.setattr(q1, "build", build_on_half)
    rc, result = run_cell(capsys, "--trace", "0")
    assert rc == 0 and result["correct"] is False
    assert result["failed"] == result["attempted"] > 0
    assert result["compared"]["q1.wrong_cells"]["value"] > 0
    assert "queries_per_s" not in result["metrics"]  # none answered right


def test_no_chip_no_result():
    """Without ``--rehearse`` a run that finds no TPU prints no result and
    exits non-zero."""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         WORKLOAD, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0 and done.stdout.strip() == ""


# -- the data-driven property -------------------------------------------------

NEW_FILES = {
    "configs/tmpcfg.json": json.dumps({
        "name": "tmpcfg", "source": "a test",
        "datagen": {"module": "tmpgen", "args": {"rows": 5000},
                    "rehearsal_args": {"rows": 5000}},
        "service": {"pool_size": 2, "io_channels": 2, "exec_channels": 2}}),
    "datagen/tmpgen.py": '''
import numpy as np
import pyarrow as pa


def generate(seed, rows):
    r = np.random.default_rng(seed)
    return {"t": pa.table({"k": r.integers(0, 4, rows).astype(np.int64),
                           "v": r.uniform(0, 1, rows)})}
''',
    "traffic/tmpmix_c2.json": json.dumps({
        "clients": 2, "mix": {"tmpq": 1}, "request_timeout_s": 60,
        "params": {"tmpq": {"floor": {"choice": [0.1, 0.2, 0.3]}}}}),
    "queries/tmpq.py": '''
from harness.tables import read_columns, row_count

SORT_KEYS = ["k"]
EXACT = ["k", "n"]
LIMITS = {"wrong_cells": 0, "sum_rel_err": 1e-4}


def build(ctx, paths, params):
    return (ctx.read_parquet(paths["t"], columns=["k", "v"])
            .filter_sql(f"v >= {params['floor']}")
            .groupby("k").agg_sql("sum(v) as s, count(*) as n"))


def reference(paths, params):
    t = read_columns(paths, "t", ["k", "v"])
    t = t[t.v >= params["floor"]]
    return t.groupby("k").agg(s=("v", "sum"), n=("v", "size")).reset_index()


def least_bytes(paths):
    return row_count(paths, "t") * 8
''',
    "metrics/tmp-metric.v2.py": '''
def read(run):
    return float(len(run.log))
''',
}


def _digests(root):
    out = {}
    for base, _, files in os.walk(root):
        if ".cache" in base or "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmarks", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", ".pytest_cache"))
    before = _digests(root / "benchmarks")
    for rel, text in NEW_FILES.items():
        path = root / "benchmarks" / rel
        assert not path.exists()
        path.write_text(text)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tmpcfg", "source": "a test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/tmpcfg.json"})
    bench["workloads"].append({
        "name": "tmpcfg.tmpmix_c2", "config": "tmpcfg",
        "traffic": "tmpmix_c2", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "tmp-metric.v2", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "service",
        "moves": "queries_per_s", "workloads": ["tmpcfg.tmpmix_c2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(root / "benchmarks")
    assert {k: after[k] for k in before} == before  # no file edited

    results = {}
    for trace in ("0", "1"):
        done = subprocess.run(
            [sys.executable, str(root / "benchmarks" / "run.py"),
             "--workload", "tmpcfg.tmpmix_c2", "--seed", "5", "--seconds", "1",
             "--trace", trace, "--rehearse"],
            capture_output=True, text=True, timeout=600, cwd=str(root),
            env=dict(os.environ, PYTHONPATH=ROOT))
        assert done.returncode == 0, done.stderr[-2000:]
        results[trace] = json.loads(done.stdout.strip().splitlines()[-1])
    assert results["0"]["correct"] and results["1"]["correct"]
    assert "queries_per_s" in results["0"]["metrics"]
    new = results["1"]["metrics"]["tmp-metric.v2"]
    assert new == {"value": float(results["1"]["attempted"]),
                   "unit": "requests"}
    # the cells that were there do not report the new cell's metric
    from harness import spec

    assert "tmp-metric.v2" not in {
        e["name"] for e, _ in spec.Cell(WORKLOAD, str(root)).metrics(
            "per_layer")}
