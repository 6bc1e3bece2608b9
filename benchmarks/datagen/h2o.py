"""The h2oai db-benchmark's group-by table from a seed.

After ``_data/groupby-datagen.R`` of github.com/h2oai/db-benchmark (data set
``G1_<N>_<K>_0_0``: no NAs, unsorted): nine columns, every key drawn uniformly
with replacement.  ``id1``, ``id2``: strings ``id%03d`` of 1..K; ``id3``:
strings ``id%010d`` of 1..N/K; ``id4``, ``id5``: integers 1..K; ``id6``:
integers 1..N/K; ``v1``: integers 1..5; ``v2``: integers 1..15; ``v3``:
``round(runif(N, max=100), 6)``.  Changed from it: numpy's generator in place
of R's, so the rows of a seed are not the R script's rows; the domains,
formats and widths are.
"""

import numpy as np
import pyarrow as pa


def _strings(codes: np.ndarray, width: int, distinct: int) -> pa.Array:
    """``id%0<width>d`` of ``codes`` + 1, built once per distinct value and
    spread over the rows by arrow's dictionary cast."""
    names = pa.array([f"id{i:0{width}d}" for i in range(1, distinct + 1)])
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32)), names).cast(pa.string())


def generate(seed: int, n: int = 10_000_000, k: int = 100) -> dict:
    """Return {"g1": pyarrow.Table} of ``n`` rows."""
    r = np.random.default_rng([seed, 5])
    small = n // k  # distinct values of the small-group keys id3 and id6
    cols = {
        "id1": _strings(r.integers(0, k, n), 3, k),
        "id2": _strings(r.integers(0, k, n), 3, k),
        "id3": _strings(r.integers(0, small, n), 10, small),
        "id4": r.integers(1, k + 1, n).astype(np.int32),
        "id5": r.integers(1, k + 1, n).astype(np.int32),
        "id6": r.integers(1, small + 1, n).astype(np.int32),
        "v1": r.integers(1, 6, n).astype(np.int32),
        "v2": r.integers(1, 16, n).astype(np.int32),
        "v3": r.uniform(0, 100, n).round(6),
    }
    return {"g1": pa.table(cols)}
