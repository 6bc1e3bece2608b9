"""Time-series executors: asof join, tumbling/hopping/sliding/session windows.

Reference parity: pyquokka/executors/ts_executors.py — SortedAsofExecutor:324,
HoppingWindowExecutor:12, SlidingWindowExecutor:147, SessionWindowExecutor:197.
The sequential frontier walks become batched device kernels (merged sort +
segmented scans, ops/asof.py); executors keep only watermark state and the
buffered tail that future batches can still affect.

All executors assume their channel receives a per-key time-ordered stream —
guaranteed by sorted sources (SAT interleaved delivery, runtime/cache.py) and
hash-by-key partitioning.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from quokka_tpu import config
from quokka_tpu.executors.base import Executor
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops import asof as asof_ops
from quokka_tpu.ops import bridge, kernels, timewide
from quokka_tpu.ops.batch import DeviceBatch, NumCol
from quokka_tpu.ops.expr_compile import AggPlan, evaluate_to_column
from quokka_tpu.windows import (
    HoppingWindow,
    OnCompletionTrigger,
    SessionWindow,
    SlidingWindow,
    TumblingWindow,
    Trigger,
    Window,
)


def _time_max(batch: DeviceBatch, col: str):
    """Watermark: float for float times, exact host int for (wide) int times."""
    c = batch.columns[col]
    if c.hi is not None:
        return timewide.host_max_i64(c, batch.valid)
    return float(tracing.device_read(
        "asof.watermark", kernels.reduce_array(c.data, batch.valid, "max")))


def _time_min(batch: DeviceBatch, col: str, valid=None):
    """Min over `valid` (default batch.valid): float or exact host int."""
    c = batch.columns[col]
    v = batch.valid if valid is None else valid
    if c.hi is not None:
        return timewide.host_min_i64(c, v)
    return float(tracing.device_read(
        "asof.cutoff", kernels.reduce_array(c.data, v, "min")))


def _cmp_time(col, v, op: str):
    """col <op> v where v is a host watermark (int, float, or +/-inf) and col
    may be a two-limb wide column."""
    if isinstance(v, float) and not np.isfinite(v):
        full = jnp.ones(col.padded_len, dtype=bool)
        hit = (v > 0) if op in ("<", "<=") else (v < 0)
        return full if hit else ~full
    if col.hi is None:
        d = col.data
        return {"<": d < v, "<=": d <= v, ">": d > v, ">=": d >= v,
                "=": d == v, "!=": d != v}[op]
    return timewide.cmp_scalar(col, int(v), op)


class _TimeRebase:
    """Exact int32 rebase for wide (two-limb int64) time columns.

    Streaming executors do single-array time arithmetic (watermarks, ``t //
    hop``, ``t - size``).  Wide columns are rebased once per executor onto an
    int32 window relative to a host base taken from the first batch (minus
    2**29 slack for late/out-of-order starts, floor-aligned to the window hop
    so absolute window boundaries stay epoch-aligned).  The rebase is exact or
    it raises — never a silent low-limb truncation (see ops/timewide.py).
    Emitted absolute times are reconstructed with ``add_base``.
    """

    _tbase: Optional[int] = None
    _t_kind: Optional[str] = None
    _t_unit: Optional[str] = None

    def _rebase_batch(self, batch: DeviceBatch, col_name: str, align: int = 1,
                      headroom: int = 0) -> DeviceBatch:
        col = batch.columns[col_name]
        if jnp.issubdtype(col.data.dtype, jnp.floating):
            return batch
        if self._tbase is None:
            # The base is fixed by the FIRST batch — including a narrow-int32
            # one (base 0, passthrough).  A later wide batch then rebases
            # against base 0 and raises cleanly instead of silently mixing
            # absolute and rebased window coordinates in one executor state.
            # Narrow int64 (x64 mode) keeps absolute coordinates while they
            # fit int32 window arithmetic (parity with the non-x64 narrow
            # path) and rebases like wide when they don't (ns epochs — the
            # downstream ``wid.astype(int32)`` would overflow).
            if col.hi is None and col.data.dtype != jnp.int64:
                self._tbase = 0
            else:
                if batch.count_valid():
                    mn = timewide.host_min_i64(col, batch.valid)
                    mx = timewide.host_max_i64(col, batch.valid)
                else:
                    mn = mx = 0
                if (
                    col.hi is None
                    and mn > -(2**31)
                    and mx < 2**31 - 1 - headroom
                ):
                    self._tbase = 0
                else:
                    align = max(1, int(align))
                    self._tbase = ((mn - 2**29) // align) * align
            self._t_kind = col.kind
            self._t_unit = col.unit
        if self._tbase == 0 and col.hi is None:
            if col.data.dtype == jnp.int64 and batch.count_valid():
                # absolute-coordinate mode was fixed by the first batch:
                # verify every later batch still fits int32 instead of
                # silently overflowing downstream casts
                mx = timewide.host_max_i64(col, batch.valid)
                mn = timewide.host_min_i64(col, batch.valid)
                if mn <= -(2**31) or mx >= 2**31 - 1 - headroom:
                    raise ValueError(
                        "time column left the int32 window range fixed by "
                        "the stream's first batch; cast to a coarser unit "
                        "(e.g. ms/s)"
                    )
            return batch  # narrow stream: absolute int32 coordinates as-is
        rel = timewide.rebase_narrow(col, batch.valid, self._tbase, headroom)
        return batch.with_column(col_name, rel)

    def _restore_time(self, data, kind: str = "i") -> NumCol:
        if self._tbase is None:
            return NumCol(data, kind)
        return timewide.add_base(data, self._tbase, self._t_kind or kind, self._t_unit)


class SortedAsofExecutor(Executor):
    SUPPORTS_CHECKPOINT = True

    """Streaming backward asof join.  Stream 0 = left/trades, stream 1 =
    right/quotes.  Trades are emitted once the quote watermark passes their
    timestamp; the quote buffer is pruned to the last quote per key below the
    frontier plus everything above it.

    Both sides live in a ``RowBuffer`` (ops/asof.py) whose capacity the plan
    gives (``capacity``: a ladder rung over each source's row count, from
    ``AsofJoinNode.lower``), appended in place part by part, and a flush
    probes one chunk of one size.  So every program the executor asks for is
    keyed on (capacity rung, part or chunk rung): how many batches a
    dispatch held and how the two streams interleaved decide how MANY
    flushes run, never their shapes."""

    # large streams flush once this many trades are ready, in chunks of
    # exactly this many slots (the last, short one under its mask): each
    # flush's probe searches the whole quote buffer.  A probe costs by the
    # slot, so a channel pays for half a chunk of padding on average; a
    # flush costs a quote sort and a take besides.  On the chip 1 << 18
    # answers ticks_1d 40 % faster than 1 << 19 (PERF.md section 6, PR 28)
    MIN_FLUSH_ROWS = 1 << 18

    # prune the quote buffer only past this many live rows: pruning costs
    # a full-buffer sort, so below the valve it is pure overhead — keeping
    # already-matched quotes around is semantically harmless for backward
    # asof (they simply lose to later quotes)
    PRUNE_ROWS = 1 << 23

    # mid-size streams hold ready trades until at least this many
    # accumulate so each flush's quote sort amortizes over one worthwhile
    # probe instead of per-dispatch slivers.  Safe to hold: quotes arrive
    # at/after the watermark that made these trades ready, so a later flush
    # computes the identical matches.
    COALESCE_ROWS = 1 << 15

    def __init__(self, left_on: str, right_on: str, left_by, right_by,
                 suffix: str = "_2", keep_unmatched: bool = False,
                 direction: str = "backward",
                 capacity: Tuple[Optional[int], Optional[int]] = (None, None)):
        if direction not in ("backward", "forward"):
            raise ValueError(direction)
        self.direction = direction
        self.left_on = left_on
        self.right_on = right_on
        self.left_by = list(left_by or [])
        self.right_by = list(right_by or [])
        self.suffix = suffix
        self.keep_unmatched = keep_unmatched
        self.capacity = tuple(capacity)
        self.trades: Optional[asof_ops.RowBuffer] = None
        self.quotes: Optional[asof_ops.RowBuffer] = None
        self.q_watermark: Optional[float] = None
        self.t_watermark: Optional[float] = None
        self.q_done = False
        self.payload: Optional[List[str]] = None
        self.rename: Dict[str, str] = {}
        # renamed view of the current quote buffer, cached by the view's
        # identity: DeviceBatch.rename builds a NEW object, which would
        # discard the searchsorted strategy's cached quote sort
        # (ops/asof._ss_quote_sorted) on every flush even when no quotes
        # arrived — derived state, deliberately not checkpointed
        self._renamed_src: Optional[DeviceBatch] = None
        self._renamed: Optional[DeviceBatch] = None

    # gate decisions key on CONTENT (the buffers' live counts), never on
    # padded lengths or capacities — padding is not preserved across
    # checkpoint/restore, and a padded-length gate would flip emission
    # decisions during tape replay (the engine asserts re_emitted == emitted)
    @property
    def _t_rows(self) -> int:
        return 0 if self.trades is None else self.trades.rows

    @property
    def _q_rows(self) -> int:
        return 0 if self.quotes is None else self.quotes.rows

    def _append(self, stream_id: int, part: DeviceBatch) -> None:
        name = "quotes" if stream_id else "trades"
        buf = getattr(self, name)
        if buf is None:
            cap = self.capacity[stream_id] or part.padded_len
            # the quotes are the searched side: their key limbs ride along
            buf = asof_ops.RowBuffer(part, config.bucket_size(cap),
                                     key=self.right_by if stream_id else ())
            setattr(self, name, buf)
        buf.append(part)

    def execute(self, batches, stream_id, channel):
        from quokka_tpu.obs import opstats

        live = [b for b in batches if b is not None and b.count_valid() > 0]
        on = self.right_on if stream_id else self.left_on
        with tracing.span("asof.append"):
            for b in live:
                self._append(stream_id, b)
                wm = _time_max(b, on)
                if stream_id:
                    if self.q_watermark is None or wm > self.q_watermark:
                        self.q_watermark = wm
                elif self.t_watermark is None or wm > self.t_watermark:
                    self.t_watermark = wm
        # the quote side is the asof's build analog (counts already host-
        # resolved by the live filter above — no extra sync)
        rows = sum(b.nrows for b in live)
        opstats.note(**{"join_build_rows" if stream_id
                        else "join_probe_rows": rows})
        return self._flush()

    def source_done(self, stream_id, channel):
        if stream_id == 1:
            self.q_done = True
            return self._flush()
        return None

    def done(self, channel):
        self.q_done = True
        out = self._flush(final=True)
        # what was emitted shares nothing with the buffers: release them (and
        # the quote sort cached on their view) now, not when a collector
        # finds the finished query's graph
        self.trades = self.quotes = self._renamed = self._renamed_src = None
        return out

    def _setup_payload(self, probe_names):
        if self.payload is None:
            payload = [c for c in self.quotes.columns
                       if c not in set(self.right_by) and c != self.right_on]
            self.rename = {c: c + self.suffix for c in payload if c in probe_names}
            self.payload = [self.rename.get(c, c) for c in payload]

    def _renamed_quotes(self) -> DeviceBatch:
        """The (possibly renamed) quote buffer to join against, one rename
        per view of the buffer: repeated flushes of an unchanged buffer
        reuse the same DeviceBatch, keeping its cached quote-side sort
        warm."""
        quotes = self.quotes.view()
        if not self.rename:
            return quotes
        if self._renamed_src is not quotes:
            self._renamed_src = quotes
            self._renamed = quotes.rename(self.rename)
            limbs = getattr(quotes, "_asof_key_limbs", None)
            if limbs is not None:
                self._renamed._asof_key_limbs = limbs
        return self._renamed

    def _flush(self, final: bool = False):
        """One chunk of ready trades joined and emitted (``final``: every
        chunk that is left, as a list)."""
        if not self._t_rows:
            return None
        if not self._q_rows:
            if self.q_done:
                out, self.trades = self.trades.view(), None
                return out if self.keep_unmatched else None
            return None
        if self.direction == "forward":
            return self._flush_forward()
        if self.q_done:
            safe = float("inf")
        elif self.q_watermark is None:
            return None
        else:
            safe = self.q_watermark
        trades = self.trades
        tcol = trades.columns[self.left_on]
        # strictly below the quote watermark: a future quote batch can still
        # contain quotes at exactly `safe` (ties must win per backward-asof)
        op = "<=" if safe == float("inf") else "<"
        ready_mask = trades.valid & _cmp_time(tcol, safe, op)
        nready = int(tracing.device_read(
            "asof.ready", jnp.sum(ready_mask.astype(jnp.int32))))
        if nready == 0:
            return None
        # each flush searches the ENTIRE quote buffer (and sorts it, when
        # quotes arrived since the last) — at scale, emitting per event
        # makes that quadratic-ish.  Large streams accumulate ready trades
        # into full chunks; small streams (below the threshold) keep
        # per-event emission.  Gates key on live counts (content-
        # deterministic across replay)
        big = self._t_rows + self._q_rows > 4 * self.MIN_FLUSH_ROWS
        if big and not self.q_done and nready < self.MIN_FLUSH_ROWS:
            return None
        # mid-size streams also hold sliver flushes until a worthwhile
        # probe accumulates.  Content-identical output — quotes arriving
        # after the hold are at/above the watermark that made these trades
        # ready, so they can't change a held trade's match
        if (
            not self.q_done
            and nready < self.COALESCE_ROWS
            and self._t_rows + self._q_rows > 2 * self.COALESCE_ROWS
        ):
            return None
        # the chunk's slots follow the plan (the capacity), the rows taken
        # follow the content: replay takes the same rows whatever the rung
        slots = config.bucket_size(min(self.MIN_FLUSH_ROWS, trades.capacity))
        outs = []
        while nready:
            n = min(nready, self.MIN_FLUSH_ROWS)
            outs.append(self._join_chunk(ready_mask, n, slots))
            nready -= n
            if not final:
                break
            ready_mask = ready_mask & self.trades.valid
        # prune only below what BOTH streams have passed: future trades can
        # still arrive below the quote watermark when quotes run ahead —
        # and only past the memory valve (pruning costs a full-buffer sort;
        # the count-based gate keys on content, so replay reproduces it)
        # — and only once no ready trade is left waiting for its chunk
        if self._q_rows >= self.PRUNE_ROWS and not nready:
            prune_to = safe
            if self.t_watermark is not None:
                prune_to = min(prune_to, self.t_watermark)
            self._prune_quotes(prune_to)
        return outs if final else outs[0]

    def _join_chunk(self, ready_mask, n: int, slots: int) -> DeviceBatch:
        from quokka_tpu.obs import opstats

        with tracing.span("asof.emit"):
            ready = self.trades.take(ready_mask, n, slots)
            self._setup_payload(ready.names)
            quotes = self._renamed_quotes()
        with tracing.span("asof.match"):
            out = asof_ops.asof_join(
                ready, quotes, self.left_on, self.right_on,
                self.left_by, self.right_by, self.payload,
            )
        opstats.note(asof_flushes=1, asof_probe_rows=n,
                     asof_probe_padded=slots,
                     asof_quote_padded=quotes.padded_len)
        with tracing.span("asof.emit"):
            matched = out.columns.pop("__asof_matched__")
            if not self.keep_unmatched:
                out = kernels.apply_mask(out, matched.data)
        return out

    def _flush_forward(self):
        """Forward asof: a trade's match is the FIRST quote of its key at/after
        its time.  A global quote watermark can't tell us a per-key match has
        arrived, so instead: join the whole buffer, and a matched trade is
        final (future quotes arrive later in time and can't beat the match).
        To keep the output time-ordered, matched trades are held back until no
        earlier trade remains unmatched."""
        trades = self.trades.view()
        self._setup_payload(trades.names)
        quotes = self._renamed_quotes()
        out = asof_ops.asof_join(
            trades, quotes, self.left_on, self.right_on,
            self.left_by, self.right_by, self.payload, direction="forward",
        )
        matched = out.columns.pop("__asof_matched__").data
        if self.q_done:
            result = out if self.keep_unmatched else kernels.compact(
                kernels.apply_mask(out, matched)
            )
            self.trades = None
            self.quotes = None
            return result if result.count_valid() > 0 else None
        tcol = trades.columns[self.left_on]
        unmatched = trades.valid & ~matched
        emit = trades.valid & matched
        if bool(tracing.device_read("asof.unmatched", jnp.any(unmatched))):
            cutoff = _time_min(trades, self.left_on, unmatched)
            emit = emit & _cmp_time(tcol, cutoff, "<")
        result = kernels.compact(kernels.apply_mask(out, emit))
        # compact() hands a full batch back as it is: over the buffer's own
        # arrays, which the next append donates
        result = self.trades.detach(result)
        self.trades.keep(~emit)
        # prune quotes below every retained and every possible future trade —
        # forward matches need quote time >= trade time, so those can't match
        bound = self.t_watermark
        if self._t_rows:
            tmin = _time_min(self.trades.view(), self.left_on)
            bound = tmin if bound is None else min(bound, tmin)
        if bound is not None:
            self.quotes.keep(
                _cmp_time(self.quotes.columns[self.right_on], bound, ">="))
        return result if result.count_valid() > 0 else None

    def _prune_quotes(self, safe):
        """Drop quotes no future trade can match: everything at/below the
        frontier except the latest quote per key.  Sort-based so it is exact
        for wide (two-limb) time columns — sort_batch keys are limb-aware."""
        if safe == float("inf"):
            return  # the stream is over: the buffers go with the executor
        q = self.quotes.view()
        qt = q.columns[self.right_on]
        above = q.valid & _cmp_time(qt, safe, ">")
        below = q.valid & ~above
        if self.right_by:
            s = kernels.sort_batch(q, self.right_by + [self.right_on])
            st = s.columns[self.right_on]
            s_below = s.valid & _cmp_time(st, safe, "<=")
            from quokka_tpu.ops.batch import key_limbs

            n = s.padded_len
            limbs = key_limbs(s, self.right_by)
            next_key_same = jnp.ones(n, dtype=bool)
            for l in limbs:
                next_key_same = next_key_same & (l == jnp.roll(l, -1))
            next_key_same = next_key_same.at[n - 1].set(False)
            next_below = jnp.roll(s_below, -1).at[n - 1].set(False) & jnp.roll(
                s.valid, -1
            ).at[n - 1].set(False)
            # last below-frontier quote in its key run: successor is out of
            # key, invalid, or above the frontier
            is_last_below = s_below & ~(next_key_same & next_below)
            keep_s = (s.valid & _cmp_time(st, safe, ">")) | is_last_below
            pruned = kernels.apply_mask(s, keep_s)
        else:
            if bool(tracing.device_read("asof.prune_below", jnp.any(below))):
                maxt = _time_max(
                    DeviceBatch(
                        {self.right_on: qt}, below, None, None
                    ),
                    self.right_on,
                )
                keep = above | (below & _cmp_time(qt, maxt, "="))
            else:
                keep = above
            pruned = kernels.apply_mask(q, keep)
        # the survivors restart the log, at the capacity it had
        capacity, self.quotes = self.quotes.capacity, None
        if pruned.count_valid() > 0:
            self.quotes = asof_ops.RowBuffer(pruned, capacity,
                                             key=self.right_by)
            self.quotes.append(pruned)

    def checkpoint(self):
        def table(buf):
            return None if buf is None else bridge.device_to_arrow(buf.view())

        return {
            "trades": table(self.trades),
            "quotes": table(self.quotes),
            "q_watermark": self.q_watermark,
            "t_watermark": self.t_watermark,
            "q_done": self.q_done,
        }

    def restore(self, state):
        self.trades = self.quotes = None
        if state is None:
            return
        for stream_id, name in enumerate(("trades", "quotes")):
            if state[name] is not None and state[name].num_rows:
                self._append(stream_id, bridge.arrow_to_device(state[name]))
        self.q_watermark = state["q_watermark"]
        self.t_watermark = state.get("t_watermark")
        self.q_done = state["q_done"]


class _PartialWindowAgg:
    """Shared helper: turn a raw batch into partial-agg rows over
    (keys + window id), and recombine partial batches."""

    def __init__(self, keys: Sequence[str], plan: AggPlan, wid_col: str = "__wid"):
        self.keys = list(keys)
        self.plan = plan
        self.wid_col = wid_col

    def partial(self, batch: DeviceBatch) -> DeviceBatch:
        b = batch
        for name, e in self.plan.pre:
            b = b.with_column(name, evaluate_to_column(e, b))
        aggs = [
            (p, op, None if tmp is None else b.columns[tmp].data)
            for (p, op, tmp) in self.plan.partials
        ]
        g = kernels.groupby_aggregate(b, self.keys + [self.wid_col], aggs)
        return kernels.compact(
            g.select(self.keys + [self.wid_col] + [p for p, _, _ in self.plan.partials])
        )

    def recombine(self, parts: List[DeviceBatch]) -> DeviceBatch:
        merged = bridge.concat_batches(parts) if len(parts) > 1 else parts[0]
        aggs = [(p, op, merged.columns[p].data) for (p, op) in self.plan.recombine]
        g = kernels.groupby_aggregate(merged, self.keys + [self.wid_col], aggs)
        return kernels.compact(
            g.select(self.keys + [self.wid_col] + [p for p, _ in self.plan.recombine])
        )

    def finalize(self, g: DeviceBatch, extra: Sequence[str] = ()) -> DeviceBatch:
        for name, e in self.plan.finals:
            g = g.with_column(name, evaluate_to_column(e, g))
        cols = self.keys + list(extra) + [n for n, _ in self.plan.finals]
        seen, out = set(), []
        for c in cols:
            if c not in seen:
                seen.add(c)
                out.append(c)
        return g.select(out)


class HoppingWindowExecutor(_TimeRebase, Executor):
    """Hopping (and tumbling: hop == size) window aggregation.  Rows are
    replicated size//hop times onto their covering windows (static factor),
    partially aggregated, and windows are emitted once the watermark passes
    their end (OnEventTrigger) or all at done (OnCompletionTrigger)."""

    def __init__(self, time_col: str, keys: Sequence[str], window: Window,
                 plan: AggPlan, trigger: Optional[Trigger] = None):
        if isinstance(window, TumblingWindow):
            self.size, self.hop = window.size, window.size
        elif isinstance(window, HoppingWindow):
            self.size, self.hop = window.size, window.hop
        else:
            raise TypeError(f"expected Tumbling/HoppingWindow, got {type(window)}")
        self.time_col = time_col
        self.keys = list(keys)
        self.plan = plan
        self.emit_incremental = not isinstance(trigger, OnCompletionTrigger)
        self.helper = _PartialWindowAgg(self.keys, plan)
        self.state: Optional[DeviceBatch] = None

    def _assign_windows(self, batch: DeviceBatch) -> DeviceBatch:
        k = self.size // self.hop
        t = batch.columns[self.time_col].data
        reps = []
        for j in range(k):
            wid = t // self.hop - j
            ok = (wid >= 0) & (t < (wid * self.hop + self.size)) & (t >= wid * self.hop)
            b = batch.with_column("__wid", NumCol(wid.astype(jnp.int32), "i"))
            reps.append(kernels.apply_mask(b, ok))
        return bridge.concat_batches(reps) if len(reps) > 1 else reps[0]

    def execute(self, batches, stream_id, channel):
        parts = []
        watermark = None
        for b in batches:
            if b is None or b.count_valid() == 0:
                continue
            b = self._rebase_batch(
                b, self.time_col, align=self.hop, headroom=self.size + self.hop
            )
            watermark = _time_max(b, self.time_col)
            parts.append(self.helper.partial(self._assign_windows(b)))
        if self.state is not None:
            parts.append(self.state)
        if not parts:
            return None
        self.state = self.helper.recombine(parts)
        if not self.emit_incremental or watermark is None:
            return None
        # windows fully below the watermark cannot receive future rows
        wid = self.state.columns["__wid"].data
        closed = self.state.valid & ((wid * self.hop + self.size) <= watermark)
        ready = kernels.compact(kernels.apply_mask(self.state, closed))
        if ready.count_valid() == 0:
            return None
        rest = kernels.compact(kernels.apply_mask(self.state, self.state.valid & ~closed))
        self.state = rest if rest.count_valid() > 0 else None
        return self._emit(ready)

    def _emit(self, g: DeviceBatch) -> DeviceBatch:
        start = g.columns["__wid"].data * self.hop
        g = g.with_column("window_start", self._restore_time(start))
        g = g.with_column("window_end", self._restore_time(start + self.size))
        out = self.helper.finalize(g, extra=["window_start", "window_end"])
        return out

    def done(self, channel):
        if self.state is None:
            return None
        out, self.state = self._emit(self.state), None
        return out


TumblingWindowExecutor = HoppingWindowExecutor


class SessionWindowExecutor(_TimeRebase, Executor):
    """Gap-based session windows: sessions close when the per-key gap exceeds
    the timeout; open sessions are carried as partial rows across batches
    (ts_executors.py:197 semantics, batched)."""

    def __init__(self, time_col: str, keys: Sequence[str], window: SessionWindow,
                 plan: AggPlan):
        self.time_col = time_col
        self.keys = list(keys)
        self.timeout = window.timeout
        self.plan = plan
        self.open: Optional[DeviceBatch] = None  # partial rows of open sessions
        self.watermark: Optional[float] = None

    def _to_partial_rows(self, batch: DeviceBatch) -> DeviceBatch:
        """Raw rows -> partial-agg rows (count=1 etc.) + first/last time."""
        b = batch
        for name, e in self.plan.pre:
            b = b.with_column(name, evaluate_to_column(e, b))
        t = b.columns[self.time_col].data
        cols = {k: b.columns[k] for k in self.keys}
        for pname, op, tmp in self.plan.partials:
            if op == "count":
                cols[pname] = NumCol(
                    b.valid.astype(jnp.int32), "i"
                )
            else:
                cols[pname] = b.columns[tmp]
        cols["__first_t"] = NumCol(t, "i")
        cols["__last_t"] = NumCol(t, "i")
        return DeviceBatch(cols, b.valid, b.nrows, None)

    def _sessionize(self, rows: DeviceBatch) -> DeviceBatch:
        """Assign session ids over key+time-sorted partial rows and combine."""
        s = kernels.sort_batch(rows, self.keys + ["__last_t"])
        from quokka_tpu.ops.batch import key_limbs

        limbs = key_limbs(s, self.keys) if self.keys else []
        n = s.padded_len
        iota = jnp.arange(n, dtype=jnp.int32)
        key_changed = jnp.zeros(n, dtype=bool)
        for l in limbs:
            key_changed = key_changed | (l != jnp.roll(l, 1))
        first_t = s.columns["__first_t"].data
        last_t = s.columns["__last_t"].data
        prev_last = jnp.roll(last_t, 1)
        gap = first_t - prev_last
        new_sess = (iota == 0) | key_changed | (gap > self.timeout)
        sess_id = jnp.cumsum(new_sess.astype(jnp.int32)) - 1
        s = s.with_column("__sess", NumCol(sess_id, "i"))
        aggs = [(p, op, s.columns[p].data) for (p, op) in self.plan.recombine]
        aggs += [("__first_t", "min", first_t), ("__last_t", "max", last_t)]
        g = kernels.groupby_aggregate(s, self.keys + ["__sess"], aggs)
        return kernels.compact(
            g.select(self.keys + [p for p, _ in self.plan.recombine]
                     + ["__first_t", "__last_t"])
        )

    def execute(self, batches, stream_id, channel):
        parts = []
        for b in batches:
            if b is None or b.count_valid() == 0:
                continue
            b = self._rebase_batch(b, self.time_col, headroom=self.timeout + 1)
            self.watermark = _time_max(b, self.time_col)
            parts.append(self._to_partial_rows(b))
        if self.open is not None:
            parts.append(self.open)
        if not parts:
            return None
        merged = bridge.concat_batches(parts) if len(parts) > 1 else parts[0]
        sessions = self._sessionize(merged)
        if self.watermark is None:
            self.open = sessions
            return None
        last = sessions.columns["__last_t"].data
        closed = sessions.valid & (last < self.watermark - self.timeout)
        ready = kernels.compact(kernels.apply_mask(sessions, closed))
        rest = kernels.compact(kernels.apply_mask(sessions, sessions.valid & ~closed))
        self.open = rest if rest.count_valid() > 0 else None
        if ready.count_valid() == 0:
            return None
        return self._emit(ready)

    def _emit(self, g: DeviceBatch) -> DeviceBatch:
        g = g.rename({"__first_t": "session_start", "__last_t": "session_end"})
        if self._tbase is not None:
            for c in ("session_start", "session_end"):
                g = g.with_column(c, self._restore_time(g.columns[c].data))
        helper = _PartialWindowAgg(self.keys, self.plan, wid_col="session_start")
        return helper.finalize(g, extra=["session_start", "session_end"])

    def done(self, channel):
        if self.open is None:
            return None
        out, self.open = self._emit(self.open), None
        return out


class SlidingWindowExecutor(_TimeRebase, Executor):
    """Per-event trailing window [t - size, t] aggregates (groupby_rolling,
    ts_executors.py:147).  Sum/count/avg via segmented prefix sums + a
    vectorized lower-bound search; each batch needs the previous tail rows,
    kept in state."""

    def __init__(self, time_col: str, keys: Sequence[str], window: SlidingWindow,
                 plan: AggPlan):
        self.time_col = time_col
        self.keys = list(keys)
        self.size = window.size_before
        self.plan = plan
        for _, op, _ in plan.partials:
            if op not in ("sum", "count", "min", "max"):
                raise NotImplementedError(
                    f"sliding windows support sum/count/avg/min/max (got {op})"
                )
        self.tail: Optional[DeviceBatch] = None

    def execute(self, batches, stream_id, channel):
        outs = []
        for b in batches:
            if b is None or b.count_valid() == 0:
                continue
            out = self._process(b)
            if out is not None:
                outs.append(out)
        if not outs:
            return None
        return bridge.concat_batches(outs) if len(outs) > 1 else outs[0]

    def _process(self, batch: DeviceBatch) -> Optional[DeviceBatch]:
        b = self._rebase_batch(batch, self.time_col, headroom=int(self.size) + 1)
        for name, e in self.plan.pre:
            b = b.with_column(name, evaluate_to_column(e, b))
        b = b.with_column("__new", NumCol(jnp.ones(b.padded_len, dtype=jnp.bool_), "b"))
        if self.tail is not None:
            t0 = self.tail
            t0 = t0.with_column(
                "__new", NumCol(jnp.zeros(t0.padded_len, dtype=jnp.bool_), "b")
            )
            missing = [c for c in b.names if c not in t0.columns]
            for c in missing:
                col = b.columns[c]
                if isinstance(col, NumCol):
                    t0 = t0.with_column(
                        c, NumCol(jnp.zeros(t0.padded_len, col.data.dtype), col.kind)
                    )
            merged = bridge.concat_batches([t0.select(b.names), b])
        else:
            merged = b
        out = self._rolling(merged)
        # new tail: rows within `size` of the max time
        wm = _time_max(b, self.time_col)
        t = merged.columns[self.time_col].data
        tail_mask = merged.valid & (t >= wm - self.size)
        tail = kernels.compact(kernels.apply_mask(merged, tail_mask))
        self.tail = tail.drop(["__new"]) if tail.count_valid() > 0 else None
        if out is not None and self._tbase is not None and self.time_col in out.columns:
            out = out.with_column(
                self.time_col, self._restore_time(out.columns[self.time_col].data)
            )
        return out

    def _rolling(self, merged: DeviceBatch) -> Optional[DeviceBatch]:
        s = kernels.sort_batch(merged, self.keys + [self.time_col])
        from quokka_tpu.ops.batch import key_limbs

        n = s.padded_len
        iota = jnp.arange(n, dtype=jnp.int32)
        limbs = key_limbs(s, self.keys) if self.keys else []
        key_changed = jnp.zeros(n, dtype=bool)
        for l in limbs:
            key_changed = key_changed | (l != jnp.roll(l, 1))
        seg_start_flag = key_changed | (iota == 0)
        seg_start = asof_ops._seg_fill_forward(
            jnp.where(seg_start_flag, iota, -1), seg_start_flag
        )
        t = s.columns[self.time_col].data
        lo_t = t - self.size
        # window rows within the key segment: [first time >= t-size, last time == t]
        left = _bisect_left_segmented(t, lo_t, seg_start, iota)
        n_total = s.padded_len
        seg_end = iota + _rows_from_segment_end(iota, seg_start_flag, n_total)
        right = _bisect_right_segmented(t, t, iota, seg_end)
        outs = {}
        for pname, op, tmp in self.plan.partials:
            if op in ("min", "max"):
                # arbitrary [left, right] range min/max via a sparse table:
                # log2(n) doubling levels, query = two overlapping power-of-2
                # blocks (prefix sums can't invert min/max)
                x = s.columns[tmp].data
                fill = _max_fill(x.dtype) if op == "min" else _min_fill(x.dtype)
                x = jnp.where(s.valid, x, fill)
                outs[pname] = _range_minmax(x, left, right, op)
                continue
            if op == "count":
                x = s.valid.astype(jnp.float32 if not kernels.config.x64_enabled() else jnp.float64)
            else:
                x = jnp.where(s.valid, s.columns[tmp].data, 0)
            cs = jnp.cumsum(x)
            before = jnp.where(left > 0, cs[jnp.maximum(left - 1, 0)], 0)
            outs[pname] = cs[right] - before
        g = s
        for pname in outs:
            g = g.with_column(pname, NumCol(outs[pname], "f"))
        for name, e in self.plan.finals:
            g = g.with_column(name, evaluate_to_column(e, g))
        only_new = kernels.apply_mask(g, g.valid & g.columns["__new"].data)
        keep = [c for c in merged.names if c != "__new" and not c.startswith("__pre")]
        keep += [nm for nm, _ in self.plan.finals if nm not in keep]
        keep = [c for c in keep if c in g.columns and not c.startswith("__agg")]
        return kernels.compact(only_new.select(keep))

    def done(self, channel):
        self.tail = None
        return None


class ShiftExecutor(Executor):
    """Per-key lag: value of `columns` n rows earlier within the key partition
    (orderedstream.py:13 shift).  Keeps the last n rows per key as carry."""

    def __init__(self, time_col: str, keys: Sequence[str], columns: Sequence[str], n: int):
        self.time_col = time_col
        self.keys = list(keys)
        self.columns = list(columns)
        self.n = n
        self.tail: Optional[DeviceBatch] = None

    def execute(self, batches, stream_id, channel):
        outs = []
        for b in batches:
            if b is None or b.count_valid() == 0:
                continue
            out = self._process(b)
            if out is not None:
                outs.append(out)
        if not outs:
            return None
        return bridge.concat_batches(outs) if len(outs) > 1 else outs[0]

    def _process(self, batch: DeviceBatch) -> Optional[DeviceBatch]:
        b = batch.with_column(
            "__new", NumCol(jnp.ones(batch.padded_len, dtype=jnp.bool_), "b")
        )
        if self.tail is not None:
            t0 = self.tail.with_column(
                "__new", NumCol(jnp.zeros(self.tail.padded_len, dtype=jnp.bool_), "b")
            )
            merged = bridge.concat_batches([t0.select(b.names), b])
        else:
            merged = b
        s = kernels.sort_batch(merged, self.keys + [self.time_col])
        from quokka_tpu.ops.batch import key_limbs

        n = s.padded_len
        iota = jnp.arange(n, dtype=jnp.int32)
        limbs = key_limbs(s, self.keys) if self.keys else []
        key_changed = jnp.zeros(n, dtype=bool)
        for l in limbs:
            key_changed = key_changed | (l != jnp.roll(l, 1))
        seg_start_flag = key_changed | (iota == 0)
        seg_start = asof_ops._seg_fill_forward(
            jnp.where(seg_start_flag, iota, -1), seg_start_flag
        )
        src = iota - self.n
        ok = src >= seg_start
        src = jnp.clip(src, 0, n - 1)
        from quokka_tpu.ops.batch import with_nulls

        out = s
        for c in self.columns:
            col = s.columns[c]
            taken = col.take(src)
            # rows with no history (under n predecessors in their key
            # segment) get NULL, not a clipped gather's garbage — polars
            # shift semantics for every column kind, not just floats
            taken = with_nulls(taken, ~ok)
            out = out.with_column(f"{c}_shifted_{self.n}", taken)
        # keep last n rows per key as the next batch's carry
        rank_from_end = _rows_from_segment_end(iota, seg_start_flag, n)
        tail_mask = s.valid & (rank_from_end < self.n)
        tail = kernels.compact(kernels.apply_mask(s, tail_mask))
        self.tail = tail.select(batch.names) if tail.count_valid() > 0 else None
        only_new = kernels.apply_mask(out, out.valid & out.columns["__new"].data)
        keep = [c for c in out.names if not c.startswith("__")]
        return kernels.compact(only_new.select(keep))


def _max_fill(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def _min_fill(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def _range_minmax(x, left, right, op: str):
    """Per-row min/max over x[left[i] .. right[i]] (inclusive), arbitrary
    ranges: O(n log n) sparse table + two-block queries, all vectorized."""
    import math

    combine = jnp.minimum if op == "min" else jnp.maximum
    n = x.shape[0]
    levels = [x]
    span = 1
    while span < n:
        prev = levels[-1]
        shifted = jnp.concatenate([prev[span:], prev[-1:].repeat(span)])
        levels.append(combine(prev, shifted))
        span *= 2
    length = jnp.maximum(right - left + 1, 1)
    k = jnp.clip(
        jnp.floor(jnp.log2(length.astype(jnp.float32))).astype(jnp.int32),
        0, len(levels) - 1,
    )
    table = jnp.stack(levels)  # [L, n]
    a = table[k, left]
    b_start = jnp.clip(right - (1 << k) + 1, 0, n - 1)
    b = table[k, b_start]
    return combine(a, b)


def _rows_from_segment_end(iota, seg_start_flag, n):
    """Distance from each row to its segment's last row (0 = last).  The
    segment end is (next start strictly after i) - 1, found with a suffix-min
    scan over start indices."""
    import jax

    starts_idx = jnp.where(seg_start_flag, iota, n)
    suffix_min = jnp.flip(jax.lax.associative_scan(jnp.minimum, jnp.flip(starts_idx)))
    after = jnp.concatenate([suffix_min[1:], jnp.array([n], dtype=suffix_min.dtype)])
    seg_end = after - 1
    return seg_end - iota


def _bisect_left_segmented(times, targets, seg_start, iota):
    """For each i: smallest j in [seg_start[i], i] with times[j] >= targets[i]
    (times sorted within segments)."""
    import jax

    lo = seg_start
    hi = iota

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) // 2
        go_right = times[mid] < targets[iota]
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
    return lo


def _bisect_right_segmented(times, targets, iota, seg_end):
    """For each i: largest j in [i, seg_end[i]] with times[j] <= targets[i]."""
    import jax

    lo = iota
    hi = seg_end

    def body(_, carry):
        lo, hi = carry
        # find first j with times[j] > target, then step back
        mid = (lo + hi + 1) // 2
        le = times[jnp.clip(mid, 0, times.shape[0] - 1)] <= targets[iota]
        lo = jnp.where(le, mid, lo)
        hi = jnp.where(le, hi, mid - 1)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
    return lo
