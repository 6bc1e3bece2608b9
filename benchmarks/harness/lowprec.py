"""The precisions a reference is computed in: float64 (the reference) and
two controls in bfloat16, the nearest precision below the float32 the
configurations state.

``bfloat16``: what the same computation gives with bfloat16 arrays in jax:
values, elementwise arithmetic and every aggregate's result rounded to
bfloat16, the sums inside an aggregate taken in float32 (a bfloat16
``jnp.sum`` accumulates in float32 and rounds its result).

``bfloat16_columns``: the mildest step down, and the one that halves a scan:
only the stored measure columns are bfloat16; arithmetic, sums and results
stay float32.
"""

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


class Precision:
    """``column`` casts a stored measure column, ``result`` an aggregate's
    result; arithmetic happens in whatever type ``column`` returned."""

    def __init__(self, name, column, result):
        self.name, self.column, self._result = name, column, result

    @staticmethod
    def accumulator(values):
        """The type sums are taken in: float32 for bfloat16, else as it is."""
        return values.astype(np.float32) if values.dtype == BF16 else values

    def results(self, frame, exact):
        """The answer's measure columns (all but ``exact``) as an
        aggregate's result is left, back in float64 for the comparison."""
        out = frame.copy()
        for c in out.columns:
            if c not in exact:
                out[c] = self._result(out[c].to_numpy()).astype(np.float64)
        return out


def _f64(values):
    return np.asarray(values, dtype=np.float64)


FLOAT64 = Precision("float64", _f64, _f64)
BFLOAT16 = Precision("bfloat16", lambda v: _f64(v).astype(BF16),
                     lambda v: _f64(v).astype(BF16))
BFLOAT16_COLUMNS = Precision(
    "bfloat16_columns", lambda v: _f64(v).astype(BF16).astype(np.float32),
    lambda v: _f64(v).astype(np.float32))
CONTROLS = (BFLOAT16, BFLOAT16_COLUMNS)
