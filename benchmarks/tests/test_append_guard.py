"""A new cell has to be files and appended entries only, so the tests that
assert a cell's or a metric's entries in ``BENCHMARK.json`` must hold them by
name, never by where they sit.  This file appends a configuration, a one-chip
cell and a per-layer metric listing that cell to a copy of ``BENCHMARK.json``,
points ``harness.spec.load_json`` at the copy, and calls every such test of
``benchmarks/tests/test_*.py`` against it: a test that asserts a place fails
here.  The tests are found by name, so a later cell's test is covered without
an edit to this file."""

import ast
import glob
import importlib
import inspect
import json
import os
import sys
import types

import pytest

from conftest import BENCH_DIR, ROOT
from harness import spec

GUARD_CONFIG = "guard_cfg"
GUARD_CELL = "guard_cfg.guard_s2"
GUARD_METRIC = "guard_metric"
# a test function whose name starts with one of these asserts entries ...
PREFIXES = ("test_cell_resolves_from_its_entries", "test_entry_names_")
# ... and so do these, named otherwise
NAMED = ("test_the_five_entries_were_appended_and_name_layer_and_moves",
         "test_every_new_metric_has_its_entry_and_its_reader")


def entry_tests():
    """``<file stem>::<function>`` of every entry-asserting test function."""
    found = []
    here = os.path.splitext(os.path.basename(__file__))[0]
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "tests",
                                              "test_*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        if stem == here:
            continue
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        found += [f"{stem}::{node.name}" for node in tree.body
                  if isinstance(node, ast.FunctionDef)
                  and (node.name.startswith(PREFIXES) or node.name in NAMED)]
    return found


def appended(bench: dict) -> dict:
    """``bench`` with a configuration, a cell and a per-layer metric
    appended, as a ``model_config`` PR appends them."""
    bench = json.loads(json.dumps(bench))
    bench["configs"].append({
        "name": GUARD_CONFIG, "source": "a configuration appended by a test",
        "file": f"benchmarks/configs/{GUARD_CONFIG}.json", "reduced": [],
        "why": "holds the entry tests to names, not places"})
    bench["workloads"].append({
        "name": GUARD_CELL, "config": GUARD_CONFIG, "traffic": "guard_s2",
        "chips": 1, "why": "a cell appended by a test"})
    bench["per_layer"].append({
        "name": GUARD_METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "queries_per_s", "workloads": [GUARD_CELL]})
    return bench


@pytest.fixture
def appended_copy(tmp_path, monkeypatch):
    """``spec.load_json`` reads a copy of the checkout's ``BENCHMARK.json``
    with the three entries appended, and the appended configuration's and
    traffic mix's files (copies of ``tpch_sf1``'s and ``q1_s2``'s) from
    ``tmp_path``; every other file as it is."""
    load_json = spec.load_json
    copies = {
        "BENCHMARK.json": appended(load_json(os.path.join(
            ROOT, "BENCHMARK.json"))),
        f"benchmarks/configs/{GUARD_CONFIG}.json": load_json(os.path.join(
            BENCH_DIR, "configs", "tpch_sf1.json")),
        "benchmarks/traffic/guard_s2.json": load_json(os.path.join(
            BENCH_DIR, "traffic", "q1_s2.json")),
    }
    for rel, data in copies.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(data, indent=2))

    def load_copy(path):
        copy = tmp_path / os.path.relpath(os.path.abspath(path), ROOT)
        return load_json(str(copy) if copy.exists() else path)

    monkeypatch.setattr(spec, "load_json", load_copy)
    stub = types.ModuleType(f"bench_metrics_{GUARD_METRIC}")
    stub.read = lambda run: None
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    # the copy is what a cell resolves through, and the appended metric
    # reaches the appended cell alone
    assert spec.Cell("tpch_sf1.q1_s2").bench == copies["BENCHMARK.json"]
    assert GUARD_METRIC in {
        e["name"] for e, _ in spec.Cell(GUARD_CELL).metrics("per_layer")}
    assert GUARD_METRIC not in {
        e["name"] for e, _ in spec.Cell("tpch_sf1.q1_s2").metrics(
            "per_layer")}
    return copies["BENCHMARK.json"]


def test_the_guard_finds_the_entry_tests_of_every_cell_and_metric_file():
    files = {t.split("::")[0] for t in entry_tests()}
    assert {"test_ticks_cell", "test_h2o_cell", "test_tpch8_cell",
            "test_q18_cell", "test_join_direct_metric",
            "test_asof_sort_match_metric", "test_join_payload_slots_metric",
            "test_compact_sort_metric", "test_host_wait_metrics",
            "test_querylog_metrics"} <= files


@pytest.mark.parametrize("test", entry_tests())
def test_entry_tests_hold_with_a_cell_appended(test, appended_copy):
    stem, name = test.split("::")
    function = getattr(importlib.import_module(stem), name)
    assert not inspect.signature(function).parameters, (
        f"{test} takes arguments: an entry test reads BENCHMARK.json alone")
    function()
