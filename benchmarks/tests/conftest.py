"""The benchmark's own tests run on the CPU, with the TPU's kernel
strategies and x64 off as on the chip (``run.py`` itself sets neither)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "QK_KERNEL_STRATEGY", "groupby=sort,join_build=sort,asof=searchsorted")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
