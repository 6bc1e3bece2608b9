"""Tier-1 runs the benchmark's own tests: the yardstick the driver judges
by (``benchmarks/run.py``, its harness, its trace reduction) is the one this
suite guards.

``benchmarks/tests`` needs its own process (its conftest: x64 off, the TPU's
kernel strategies; this directory's: x64 on, eight host devices), so one
child pytest runs it once and every test function there is one case here:
it passes when every junit case of that function passed.
"""

import ast
import glob
import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join("benchmarks", "tests")
TIMEOUT_S = 600


def _functions():
    """``<file stem>::<function>`` (``::<Class>`` between them for a
    method) of every test function in ``benchmarks/tests/test_*.py``."""
    found = []
    for path in sorted(glob.glob(os.path.join(ROOT, SUITE, "test_*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in tree.body:
            if isinstance(node, ast.FunctionDef) and node.name.startswith(
                    "test_"):
                found.append(f"{stem}::{node.name}")
            elif isinstance(node, ast.ClassDef) and node.name.startswith(
                    "Test"):
                found += [f"{stem}::{node.name}::{m.name}" for m in node.body
                          if isinstance(m, ast.FunctionDef)
                          and m.name.startswith("test_")]
    return found


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    """function id -> the outcomes of its junit cases, from one child run
    in a clean environment: none of this directory's conftest settings
    (forced host devices, ``QK_PLAN_VERIFY``, the emptied ``QK_*_DIR``),
    its compile cache under the tests' scratch."""
    xml = str(tmp_path_factory.mktemp("benchmark_suite") / "junit.xml")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("QK_", "QUOKKA_", "PYTEST_"))}
    env["XLA_FLAGS"] = " ".join(
        flag for flag in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in flag)
    env["JAX_PLATFORMS"] = "cpu"
    # a directory of its own under the tests' scratch: the child compiles
    # with x64 off and one host device, beside five workers that do not
    env["QUOKKA_JAX_CACHE_DIR"] = os.path.join(
        os.environ["QUOKKA_JAX_CACHE_DIR"], "benchmarks")
    child = subprocess.run(
        [sys.executable, "-m", "pytest", SUITE, "-q", "-p",
         "no:cacheprovider", f"--junitxml={xml}"],
        cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=TIMEOUT_S)
    assert os.path.exists(xml), (
        f"the child run wrote no junit file (exit {child.returncode}):\n"
        f"{child.stdout[-4000:]}\n{child.stderr[-4000:]}")
    by_function = {}
    for case in ET.parse(xml).getroot().iter("testcase"):
        parts = case.get("classname").split(".")
        where = parts[max(i for i, p in enumerate(parts)
                          if p.startswith("test_")):]
        name = case.get("name").split("[")[0]
        bad = [c for c in case if c.tag in ("failure", "error", "skipped")]
        by_function.setdefault("::".join(where + [name]), []).append(
            (case.get("name"), bad[0].tag if bad else "passed",
             (bad[0].get("message") or "")[:500] if bad else ""))
    return by_function, child.stdout[-4000:]


@pytest.mark.parametrize("function", _functions())
def test_benchmark_test_passes(function, outcomes):
    by_function, tail = outcomes
    cases = by_function.get(function)
    assert cases, f"the child run has no case of {function}:\n{tail}"
    if all(outcome == "skipped" for _, outcome, _ in cases):
        pytest.skip(cases[0][2])
    wrong = [c for c in cases if c[1] not in ("passed", "skipped")]
    assert not wrong, f"{wrong}\n{tail}"
