"""One trading day of quotes and trades from a seed.

Copied from chip_smoke.py (make_data) at commit 949ddc4: times in ms over one
day, sorted; about 5.2 quotes per trade.  Changed from it: a row's symbol is
drawn with probability proportional to 1 / rank**zipf_s (the original draws
symbols uniformly, which no day of real ticks does), the same weights for
trades and quotes.
"""

import numpy as np
import pyarrow as pa

DAY_MS = 86_400_000


def generate(seed: int, quotes: int = 6_000_000, trades: int = 1_150_000,
             symbols: int = 100, zipf_s: float = 1.0) -> dict:
    """Return {table: pyarrow.Table}, both sorted by ``time``."""
    syms = pa.array([f"S{i:03d}" for i in range(symbols)])
    weights = 1.0 / np.arange(1, symbols + 1) ** zipf_s
    weights /= weights.sum()
    out = {}
    for name, n_rows, salt in (("trades", trades, 1), ("quotes", quotes, 2)):
        r = np.random.default_rng([seed, salt])
        cols = {
            "time": np.sort(r.integers(0, DAY_MS, n_rows)).astype(np.int64),
            "symbol": pa.DictionaryArray.from_arrays(
                pa.array(r.choice(symbols, n_rows, p=weights).astype(np.int32)),
                syms).cast(pa.string()),
        }
        if name == "trades":
            cols["size"] = r.integers(1, 500, n_rows).astype(np.int64)
        else:
            cols["bid"] = r.uniform(10, 500, n_rows).round(3)
        out[name] = pa.table(cols)
    return out
