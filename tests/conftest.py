"""Test harness: run everything on a virtual 8-device CPU mesh with x64 so
results compare exactly against the pandas oracle.  Must set env before jax
initializes (hence top-of-module, before any quokka_tpu import)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Persistent compile cache across test runs: CPU compiles are cheap singly but
# the suite compiles thousands of programs; warm runs skip nearly all of it.
# A fixed directory inside the checkout, apart from the program's own.
os.environ.setdefault(
    "QUOKKA_JAX_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache", "tests"),
)
os.environ.setdefault("QUOKKA_JAX_CACHE_MIN_SECS", "0")
# Bound the distributed coordinator's run timeout for the whole suite: the
# default 600s means one wedged kill-recovery race (a known, pre-existing
# flake in the adopter's lost-object wait — see ROADMAP) eats the entire
# tier-1 budget before failing.  120s is ~5x the slowest healthy
# distributed test on a loaded 1-core box; a genuine wedge now fails THAT
# test loudly (with its stall dump) instead of timing out the suite.
os.environ.setdefault("QK_COORD_TIMEOUT", "120")
# Kernel-strategy calibration must never leak into tests: a developer box
# whose bench calibrated (ops/strategy.py) would otherwise flip which
# kernels tests exercise.  "" disables profile load/persist; tests that
# exercise calibration point QK_STRATEGY_DIR at a tmp dir and reset().
os.environ.setdefault("QK_STRATEGY_DIR", "")
# Same discipline for the admission feedback profiles (obs/memplane.py
# measured footprints, obs/opstats.py measured cardinalities): a developer
# box with populated caches would flip est_bytes in admission tests.
os.environ.setdefault("QK_MEMPROFILE_DIR", "")
os.environ.setdefault("QK_CARDPROFILE_DIR", "")
# Plan-invariant verification (analysis/planck.py QK021-QK024) is default-ON
# for every test: each optimizer pass's (before, after) plan pair is checked
# and a violation fails the test naming the pass and offending node.
os.environ.setdefault("QK_PLAN_VERIFY", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_enable_x64", True)

assert jax.default_backend() == "cpu", jax.devices()
assert jax.device_count() == 8, jax.devices()

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


def make_table(n=1000, seed=0):
    """A mixed-type test table with strings, ints, floats, dates."""
    r = np.random.default_rng(seed)
    return pa.table(
        {
            "k": r.integers(0, 20, n).astype(np.int64),
            "v": r.normal(size=n),
            "q": r.integers(1, 50, n).astype(np.int64),
            "s": np.array([["apple", "banana", "cherry", "date"][i] for i in r.integers(0, 4, n)]),
            "d": pa.array(r.integers(8000, 12000, n).astype(np.int32), type=pa.int32()).cast(
                pa.date32()
            ),
        }
    )


@pytest.fixture
def table():
    return make_table()


@pytest.fixture
def pdf(table):
    return table.to_pandas()


# -- slow tier (SF>=1 correctness passes) -------------------------------------
# `pytest -m slow` runs them; default runs skip them so the suite stays fast.


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: SF>=1 correctness passes with production spill "
        "thresholds (run with -m slow)"
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("-m"):
        return  # an explicit marker expression decides what runs
    skip = pytest.mark.skip(reason="slow tier; run with `pytest -m slow`")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
