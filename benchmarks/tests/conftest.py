"""The benchmark's own tests run on the CPU, with x64 off as on the chip and
the TPU's kernel strategies for the group-by, the join build and the asof
match (``sort``: ``quokka_tpu/ops/strategy.py``'s TPU defaults); ``run.py``
itself sets neither.  ``compact`` stays at the CPU's default, ``nonzero``: a
CPU sort of the mask is the slow path there, and XLA:CPU's AOT serializer
cannot persist a program that holds it, so every run would compile it anew;
the tests that read the TPU's compaction set ``compact=sort`` themselves."""

import hashlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "QK_KERNEL_STRATEGY", "groupby=sort,join_build=sort,asof=sort")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

# The benchmark as last accepted: one ``<sha256>  <path from ROOT>`` line for
# every file it had.  A change that may alter the benchmark rewrites the lines
# of the files it alters; any other change only adds files beside them.
ACCEPTED = os.path.join(BENCH_DIR, "tests", "accepted_files.sha256")


def edited_benchmark_files():
    """``<path>: changed`` / ``<path>: missing`` for every file ``ACCEPTED``
    lists whose bytes differ from their digest or that is gone: files under
    ``benchmarks/`` may be added, never changed, renamed or deleted."""
    with open(ACCEPTED, encoding="utf-8") as f:
        listed = [line.split(None, 1) for line in f.read().splitlines()
                  if line.strip()]
    assert listed, f"{ACCEPTED} lists no file"
    edited = []
    for digest, rel in listed:
        path = os.path.join(ROOT, rel)
        if not os.path.isfile(path):
            edited.append(f"{rel}: missing")
            continue
        with open(path, "rb") as f:
            if hashlib.sha256(f.read()).hexdigest() != digest:
                edited.append(f"{rel}: changed")
    return edited
