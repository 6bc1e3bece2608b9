"""Property P of the streaming asof join (ISSUE 28): for fixed data, the set
of compile-plane keys ``SortedAsofExecutor`` asks for does not depend on the
order, the grouping per ``execute`` or the interleaving in which the batches
of its two streams arrive, and every arrival schedule gives pandas'
``merge_asof`` answer.

One executor per channel is driven directly with the same seeded,
Zipf-skewed, time-sorted quotes and trades under each schedule; the flush
thresholds are shrunk so that several flushes happen at test size."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from quokka_tpu import config
from quokka_tpu.executors.ts_execs import SortedAsofExecutor
from quokka_tpu.ops import bridge, kernels, sigkey
from quokka_tpu.ops.batch import DeviceBatch

CHANNELS = 2
N_QUOTES, N_TRADES, N_SYMBOLS = 6000, 1400, 20
QUOTE_BATCH, TRADE_BATCH = 1000, 400
# the kinds whose key sets followed arrival before (PERF.md section 7's
# table), the two the in-place buffers brought and the match's one program
# (the TPU's pick since PR 35; the search's two stay listed and stay empty)
KINDS = ("fused_concat", "gather", "compact_idx", "asof_ss_sort",
         "asof_ss_probe", "asof_write", "asof_take", "asof_match")


def _ticks(seed=11):
    r = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, N_SYMBOLS + 1)
    symbols = np.array([f"S{i:02d}" for i in range(N_SYMBOLS)])

    def table(n, value, lo, hi):
        return pa.table({
            # distinct times: no (symbol, time) ties between quotes, so the
            # reference's answer does not hang on a tie-break
            "time": np.sort(r.choice(1_000_000, n, replace=False)).astype(
                np.int64),
            "symbol": symbols[r.choice(N_SYMBOLS, n, p=p / p.sum())],
            value: r.uniform(lo, hi, n),
        })

    return table(N_TRADES, "size", 1, 500), table(N_QUOTES, "bid", 10, 500)


def _parts(table, batch_rows):
    """Per channel, the stream's parts in order: each source batch split by
    the symbol's hash as the exchange does it (masked views)."""
    out = [[] for _ in range(CHANNELS)]
    for start in range(0, table.num_rows, batch_rows):
        b = bridge.arrow_to_device(table.slice(start, batch_rows))
        pids = kernels.partition_ids(b, ["symbol"], CHANNELS)
        for ch, part in enumerate(
                kernels.split_by_partition(b, pids, CHANNELS)):
            out[ch].append(part)
    return out


def _fresh(part: DeviceBatch) -> DeviceBatch:
    """A part as the engine hands it over: its own object, count unread."""
    return DeviceBatch(dict(part.columns), part.valid, None, part.sorted_by,
                       part.nrows_dev)


def _calls(order, group, done_early):
    """The executor calls of one schedule.  ``order``: the stream ids of the
    parts in arrival order; ``group(i)``: how many consecutive parts of one
    stream an ``execute`` may hold at call i; ``done_early``: the quotes'
    ``source_done`` right after their last part, else after every part."""
    calls, i = [], 0
    last_quote = max(k for k, s in enumerate(order) if s == 1)
    while i < len(order):
        j = i + 1
        while (j < len(order) and order[j] == order[i]
               and j - i < group(len(calls))):
            j += 1
        calls.append(("execute", order[i], j - i))
        if done_early and i <= last_quote < j:
            calls.append(("source_done", 1, 0))
        i = j
    if not done_early:
        calls.append(("source_done", 1, 0))
    calls.append(("source_done", 0, 0))
    return calls


def _schedules(nt, nq):
    quotes_first = [1] * nq + [0] * nt
    trades_first = [0] * nt + [1] * nq
    alternate = [s for k in range(max(nq, nt))
                 for s, n in ((1, nq), (0, nt)) if k < n]
    out = {
        "quotes_first": (quotes_first, lambda i: 1, True),
        "quotes_first_done_late": (quotes_first, lambda i: 1, False),
        "trades_first": (trades_first, lambda i: 1, False),
        "trades_first_all_at_once": (trades_first, lambda i: 99, True),
        "alternate": (alternate, lambda i: 1, True),
        "alternate_done_late": (alternate, lambda i: 1, False),
    }
    for seed in range(8):
        r = np.random.default_rng(seed)
        order = np.array([0] * nt + [1] * nq)
        r.shuffle(order)
        sizes = r.integers(1, 5, 64)
        out[f"random{seed}"] = (list(order), lambda i, s=sizes: int(s[i % 64]),
                                bool(seed % 2))
    return out


@pytest.fixture(scope="module")
def data():
    trades, quotes = _ticks()
    exp = pd.merge_asof(trades.to_pandas(), quotes.to_pandas(), on="time",
                        by="symbol").dropna(subset=["bid"])
    return {"trades": _parts(trades, TRADE_BATCH),
            "quotes": _parts(quotes, QUOTE_BATCH),
            "expected": exp.sort_values(["time", "symbol"]).reset_index(
                drop=True)}


NT = -(-N_TRADES // TRADE_BATCH)
NQ = -(-N_QUOTES // QUOTE_BATCH)
SCHEDULES = _schedules(NT, NQ)


PLAN = (config.bucket_size(N_TRADES), config.bucket_size(N_QUOTES))


def _run(data, schedule, capacity=PLAN):
    order, group, done_early = schedule
    frames, flushes = [], 0
    for ch in range(CHANNELS):
        ex = SortedAsofExecutor(
            "time", "time", ["symbol"], ["symbol"], capacity=capacity)
        streams = {0: iter(data["trades"][ch]), 1: iter(data["quotes"][ch])}
        outs = []
        for call, stream, n in _calls(order, group, done_early):
            if call == "execute":
                outs.append(ex.execute(
                    [_fresh(next(streams[stream])) for _ in range(n)],
                    stream, ch))
            else:
                outs.append(ex.source_done(stream, ch))
        final = ex.done(ch)
        outs.extend(final if isinstance(final, list) else [final])
        outs = [o for o in outs if o is not None]
        flushes += len(outs)
        frames.extend(bridge.device_to_arrow(o).to_pandas() for o in outs)
    got = pd.concat(frames).sort_values(["time", "symbol"]).reset_index(
        drop=True)
    return got, flushes


def _keys():
    return {kind: set(sigkey.ledger_keys(kind)) for kind in KINDS}


def _assert_reference(got, exp):
    """The reference's answer: exact on rows, symbols and counts, sums
    within the cell's limit."""
    assert len(got) == len(exp)
    assert (got.symbol.to_numpy() == exp.symbol.to_numpy()).all()
    np.testing.assert_array_equal(got.time.to_numpy(), exp.time.to_numpy())
    np.testing.assert_array_equal(got.bid.to_numpy(), exp.bid.to_numpy())
    agg = lambda df: df.assign(notional=df.bid * df["size"]).groupby(  # noqa: E731
        "symbol").notional.agg(["sum", "size"])
    a, b = agg(got), agg(exp)
    assert (a["size"] == b["size"]).all()
    assert float(((a["sum"] - b["sum"]).abs() / b["sum"].abs()).max()) < 4e-5


@pytest.mark.parametrize("name", list(SCHEDULES))
def test_schedule_answers_and_asks_for_no_new_program(name, data,
                                                      monkeypatch):
    monkeypatch.setenv("QK_KERNEL_STRATEGY",
                       "asof=sort,groupby=sort,join_build=sort")
    monkeypatch.setattr(SortedAsofExecutor, "MIN_FLUSH_ROWS", 256)
    monkeypatch.setattr(SortedAsofExecutor, "COALESCE_ROWS", 64)
    sigkey.reset_ledger()
    _run(data, SCHEDULES["quotes_first"])
    first = _keys()
    got, flushes = _run(data, SCHEDULES[name])
    _assert_reference(got, data["expected"])  # (a)
    assert flushes > 2 * CHANNELS, "thresholds too large for several flushes"
    # (b) no program key that the first schedule did not ask for
    later = _keys()
    new = {k: sorted(later[k] - first[k], key=repr) for k in KINDS
           if later[k] - first[k]}
    assert not new, f"{name} asked for programs outside the first set: {new}"
    assert first["asof_write"] and first["asof_take"]
    assert first["asof_match"] and not first["asof_ss_probe"]


@pytest.mark.parametrize("name", ["quotes_first", "alternate", "random3"])
def test_a_source_that_knows_no_row_count_still_answers(name, data,
                                                        monkeypatch):
    """No capacity from the plan: the buffers start at the first part's
    length, close their holes and double as parts arrive."""
    monkeypatch.setenv("QK_KERNEL_STRATEGY", "asof=sort")
    monkeypatch.setattr(SortedAsofExecutor, "MIN_FLUSH_ROWS", 256)
    monkeypatch.setattr(SortedAsofExecutor, "COALESCE_ROWS", 64)
    got, _ = _run(data, SCHEDULES[name], capacity=(None, None))
    _assert_reference(got, data["expected"])
