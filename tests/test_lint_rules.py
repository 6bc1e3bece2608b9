"""qklint: each rule fires on its seeded fixture, the CLI gates on it, and
the private-API compat shim behaves (satellite: pinned-version test)."""

import os
import subprocess
import sys

import pytest

from quokka_tpu.analysis import compat
from quokka_tpu.analysis.lint import main as lint_main
from quokka_tpu.analysis.lint import run_lint

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")

CASES = [
    ("QK001", "qk001_module_jit.py", 3),     # call, partial, decorator
    ("QK002", "qk002_import_side_effect.py", 3),  # register, makedirs, Thread
    ("QK003", "qk003_private_api.py", 1),
    ("QK004", "qk004_host_sync.py", 3),      # asarray, branch, block_until_ready
    ("QK005", "qk005_unlocked.py", 2),       # dict store, list append
    ("QK006", "qk006_swallow.py", 1),
    ("QK007", "qk007_print.py", 1),          # library print; main() exempt
    ("QK008", "qk008_global_config.py", 3),  # jax.config, environ, module
    ("QK009", "qk009_io_timeout.py", 5),     # create_connection, settimeout(None), timeout=None, fsspec.open, fs.mv
    ("QK010", "qk010_counter_dict.py", 3),   # 2x dict +=, 1x .get()+1 RMW
    ("QK011", "qk011_push_sync.py", 3),      # np.asarray, .item(), device_get
    ("QK011", "qk011_exec_sync.py", 3),      # the same three in an executor
    ("QK012", "qk012_raw_len_key.py", 3),    # sig tuple, .get key, store key
    ("QK013", "qk013_platform_gate.py", 3),  # probe, string gate, _platform
    ("QK018", "qk018_device_alloc.py", 3),   # jnp.zeros, device_put, asarray
    ("QK019", "qk019_row_tally.py", 3),      # attr +=, dict-slot +=, .get RMW
    ("QK020", "qk020_program_chain.py", 3),  # loop dispatch, straight #3, #4
    ("QK025", "qk025_lock_io.py", 3),        # open, sleep, helper->open
    ("QK027", "qk027_wall_timing.py", 3),    # dotted, name pair, bare
]


def _fixture(name):
    return os.path.join(FIXTURES, name)


# a rule with a second fixture is told apart by the fixture's name
IDS = [c[0] if c[1].startswith(c[0].lower() + "_") and not any(
    o[0] == c[0] for o in CASES[:i]) else c[1][:-3]
       for i, c in enumerate(CASES)]


@pytest.mark.parametrize("rule,fixture,expected", CASES, ids=IDS)
def test_rule_fires_on_fixture(rule, fixture, expected):
    findings = run_lint([_fixture(fixture)])
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) == expected, [f.render() for f in findings]
    # each fixture seeds exactly its own rule — cross-rule noise would make
    # fixtures useless as per-rule regression anchors
    assert {f.rule for f in findings} == {rule}, \
        [f.render() for f in findings]


@pytest.mark.parametrize("rule,fixture,expected", CASES, ids=IDS)
def test_cli_exits_nonzero_on_fixture(rule, fixture, expected, capsys):
    rc = lint_main([_fixture(fixture), "--no-baseline", "--quiet"])
    assert rc == 1


def test_qk011_sees_an_executors_reads_and_passes_the_funnel():
    """The widened scope: every function of an executor-shaped file is in
    sight, whatever calls it; the reads that go through
    ``spans.device_read`` are not findings."""
    findings = run_lint([_fixture("qk011_exec_sync.py")])
    assert {f.scope for f in findings} == {"FakeJoinExecutor.execute"}
    assert sorted(f.snippet.split("#")[0].strip() for f in findings) == [
        "dup, n_ok = jax.device_get(build.stats)",
        'keys = np.asarray(build.columns["k"].data)',
        "nready = jnp.sum(build.valid).item()"]
    assert all("obs.spans.device_read" in f.message for f in findings)


def test_qk011_baselined_read_passes(tmp_path):
    """A read left on purpose carries a rationale in the baseline and the
    gate lets it by; the other two still fail it."""
    import json

    fixture = _fixture("qk011_exec_sync.py")
    keys = sorted(f.key() for f in run_lint([fixture]))
    assert len(keys) == 3
    whole = tmp_path / "whole.json"
    whole.write_text(json.dumps({"findings": {
        k: "a path no served request reaches" for k in keys}}))
    assert lint_main([fixture, "--baseline", str(whole), "--quiet"]) == 0
    part = tmp_path / "part.json"
    part.write_text(json.dumps({"findings": {
        keys[0]: "a path no served request reaches"}}))
    assert lint_main([fixture, "--baseline", str(part), "--quiet"]) == 1


def test_cli_subprocess_entry_point():
    """`python -m quokka_tpu.analysis.lint` works as a real process (the
    in-process tests above cover each rule; this covers the module entry)."""
    r = subprocess.run(
        [sys.executable, "-m", "quokka_tpu.analysis.lint",
         _fixture("qk006_swallow.py"), "--no-baseline"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "QK006" in r.stdout


def test_clean_code_produces_no_findings(tmp_path):
    p = tmp_path / "clean.py"
    p.write_text(
        "import threading\n"
        "import jax\n\n\n"
        "class Store:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.kv = {}\n\n"
        "    def put(self, k, v):\n"
        "        with self._lock:\n"
        "            self.kv[k] = v\n\n\n"
        "def make(f):\n"
        "    return jax.jit(f)\n"
    )
    assert run_lint([str(p)]) == []


def test_baseline_workflow(tmp_path):
    """New finding fails, baselined finding passes, baseline-only-shrinks:
    a fixed finding shows up as stale rather than silently lingering."""
    from quokka_tpu.analysis.lint import load_baseline, write_baseline

    fixture = _fixture("qk006_swallow.py")
    bl = tmp_path / "baseline.json"
    # no baseline: gate fails
    assert lint_main([fixture, "--baseline", str(bl), "--quiet"]) == 1
    # GROWING the baseline requires a real --reason (no TODO placeholder
    # auto-fill: every accepted finding ships with its rationale)
    assert lint_main([fixture, "--baseline", str(bl),
                      "--write-baseline"]) == 2
    assert lint_main([fixture, "--baseline", str(bl), "--write-baseline",
                      "--reason", "short"]) == 2          # < 10 chars
    assert lint_main([fixture, "--baseline", str(bl), "--write-baseline",
                      "--reason", "TODO: rationale"]) == 2  # placeholder
    assert lint_main([fixture, "--baseline", str(bl), "--write-baseline",
                      "--reason",
                      "fixture code swallows on purpose"]) == 0
    assert lint_main([fixture, "--baseline", str(bl), "--quiet"]) == 0
    from quokka_tpu.analysis.lint import load_baseline as _lb

    assert all(v == "fixture code swallows on purpose"
               for v in _lb(str(bl)).values())
    # SHRINK-only rewrites (no new entries) need no --reason
    assert lint_main([fixture, "--baseline", str(bl),
                      "--write-baseline"]) == 0
    # rationales survive a rewrite
    entries = load_baseline(str(bl))
    key = next(iter(entries))
    entries[key] = "accepted because reasons"
    write_baseline(str(bl), run_lint([fixture]), entries)
    assert load_baseline(str(bl))[key] == "accepted because reasons"
    # stale entries fail the gate too (baseline may only shrink, in the
    # same PR that fixes the finding) — same answer as test_lint_clean.py
    import json

    data = json.loads(bl.read_text())
    data["findings"]["QK999::gone/file.py::<module>::nothing"] = "fixed"
    bl.write_text(json.dumps(data))
    assert lint_main([fixture, "--baseline", str(bl), "--quiet"]) == 1


# -- satellite: version-guarded private-API shim ----------------------------


def test_compat_trace_state_clean_pinned_version():
    """The pinned jax must expose the API through the shim, and the shim
    must answer correctly in both dispatch contexts (the answer routes
    hashtable kernels around the nested-pjit dispatch race)."""
    import jax

    assert compat.trace_state_clean() is True
    seen = []

    def probe(x):
        seen.append(compat.trace_state_clean())
        return x

    jax.jit(probe)(1)
    assert seen == [False]


def test_compat_missing_api_fails_loudly():
    with pytest.raises(ImportError, match="trace_state_clean"):
        compat._resolve("trace_state_clean",
                        (("nonexistent_module", "nope"),
                         ("core", "definitely_not_there")))
