"""The embedded engine in its PRODUCTION configuration — x64 OFF, one
device, no conftest env — exercised in a clean subprocess.

Regression for a deterministic 'Execution supplied 4 buffers but compiled
program expected 8 buffers' failure: the hashtable module used to be
first-imported lazily INSIDE an active jit trace (FusedPartialAgg's fused
program calls kernels.groupby_limbs), and creating its module-level pjit
objects mid-trace mis-primed jit dispatch for later top-level calls.  The
test suite's x64/8-device conftest masked it, so this guard runs the real
config end to end.
"""

import json
import os
import subprocess
import sys
import textwrap


def test_nonx64_engine_groupby_join_subprocess(tmp_path):
    script = textwrap.dedent("""
        import jax
        import numpy as np
        import pyarrow as pa

        from quokka_tpu import QuokkaContext
        from quokka_tpu.expression import col

        assert not jax.config.jax_enable_x64

        r = np.random.default_rng(0)
        t = pa.table({"k": r.integers(0, 50, 20000).astype(np.int64),
                      "v": r.uniform(0, 1, 20000)})
        dim = pa.table({"k": np.arange(50, dtype=np.int64),
                        "w": np.arange(50, dtype=np.int64) * 2})
        ctx = QuokkaContext()
        got = (ctx.from_arrow(t)
               .join(ctx.from_arrow(dim), on="k")
               .groupby("k").agg_sql("sum(v) as s, sum(w) as ws, count(*) as n")
               .collect())
        assert len(got) == 50, len(got)
        assert int(got.n.sum()) == 20000
        print("SUBPROCESS_OK")
    """)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "QUOKKA_JAX_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    # persistent cache: the subprocess compiles the whole non-x64 kernel
    # set, ~60s cold on one core — warm after run 1
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["QUOKKA_JAX_CACHE_DIR"] = os.path.join(
        repo, ".jax_cache", "tests_nonx64")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=420, cwd=str(tmp_path),
    )
    assert "SUBPROCESS_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-2000:])
