"""Lower expression ASTs onto DeviceBatch columns as jnp computations.

Replaces the reference's dual path of sqlglot->polars `evaluate`
(pyquokka/sql_utils.py:86) and "give up and run DuckDB SQL" (pyquokka/
core.py:156-163): here there is exactly one compile path and it emits JAX ops,
so filters/projections fuse into the surrounding jitted kernel.

String rules (TPU-first): predicates and transforms evaluate on the host over
the (small) dictionary once, then a device gather by code applies them to all
rows.  Date math runs on int32 days with the civil-calendar algorithm
vectorized in jnp.
"""

from __future__ import annotations

import fnmatch
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from quokka_tpu import config
from quokka_tpu.obs import spans as tracing
from quokka_tpu.expression import (
    Agg,
    Alias,
    BinOp,
    Case,
    Cast,
    ColRef,
    DateLit,
    DtField,
    Expr,
    Func,
    InList,
    IntervalLit,
    IsNull,
    Literal,
    StrOp,
    UnaryOp,
)
from quokka_tpu.ops.batch import (
    NULL_I32,
    DeviceBatch,
    NumCol,
    StrCol,
    StringDict,
    null_mask,
)


class CompileError(Exception):
    pass


Value = object  # NumCol | StrCol | python scalar | IntervalLit


def evaluate(e: Expr, batch: DeviceBatch):
    """Evaluate an expression against a batch -> NumCol / StrCol / scalar."""
    if isinstance(e, Alias):
        return evaluate(e.expr, batch)
    if isinstance(e, ColRef):
        if e.name not in batch.columns:
            raise CompileError(f"unknown column {e.name}; have {batch.names}")
        return batch.columns[e.name]
    if isinstance(e, Literal):
        return e.value
    if isinstance(e, DateLit):
        return _DateScalar(e.days)
    if isinstance(e, IntervalLit):
        return e
    if isinstance(e, BinOp):
        return _binop(e.op, evaluate(e.left, batch), evaluate(e.right, batch))
    if isinstance(e, UnaryOp):
        if e.op == "not":
            # push NOT into comparisons (op flip / De Morgan) so SQL 3VL holds:
            # NOT (x = 5) with x null must be false, not ~false
            pushed = _negate_expr(e.operand)
            if pushed is not None:
                return evaluate(pushed, batch)
            res = ~_as_bool(evaluate(e.operand, batch))
            # fallback invert (LIKE/contains/...): still exclude null operands
            if isinstance(e.operand, StrOp):
                v = evaluate(e.operand.expr, batch)
                if isinstance(v, (NumCol, StrCol)):
                    res = res & ~null_mask(v)
            return NumCol(res, "b")
        v = evaluate(e.operand, batch)
        if e.op == "-":
            if isinstance(v, NumCol):
                return NumCol(-v.data, v.kind)
            return -v
        raise CompileError(e.op)
    if isinstance(e, Case):
        return _case(e, batch)
    if isinstance(e, InList):
        return _in_list(e, batch)
    if isinstance(e, IsNull):
        return _is_null(e, batch)
    if isinstance(e, StrOp):
        return _str_op(e, batch)
    if isinstance(e, DtField):
        return _dt_field(e, batch)
    if isinstance(e, Cast):
        return _cast(e, batch)
    if isinstance(e, Func):
        return _func(e, batch)
    if isinstance(e, Agg):
        raise CompileError("aggregate expression used in a scalar context")
    raise CompileError(f"cannot compile {type(e).__name__}")


def evaluate_predicate(e: Expr, batch: DeviceBatch) -> jnp.ndarray:
    return _as_bool(evaluate(e, batch))


def evaluate_to_column(e: Expr, batch: DeviceBatch):
    return value_to_column(evaluate(e, batch), batch)


def value_to_column(v, batch: DeviceBatch):
    if isinstance(v, (NumCol, StrCol)):
        return v
    if isinstance(v, _DateScalar):
        return NumCol(jnp.full(batch.padded_len, v.days, dtype=jnp.int32), "d")
    if isinstance(v, str):
        return StrCol(
            jnp.zeros(batch.padded_len, dtype=jnp.int32),
            StringDict(np.array([v], dtype=object)),
        )
    if isinstance(v, bool):
        return NumCol(jnp.full(batch.padded_len, v, dtype=jnp.bool_), "b")
    if isinstance(v, int):
        return NumCol(jnp.full(batch.padded_len, v, dtype=config.int_dtype()), "i")
    if isinstance(v, float):
        return NumCol(jnp.full(batch.padded_len, v, dtype=config.float_dtype()), "f")
    raise CompileError(f"cannot materialize {type(v)} as a column")


class _DateScalar:
    __slots__ = ("days",)

    def __init__(self, days: int):
        self.days = days


# ---------------------------------------------------------------------------
# coercion helpers
# ---------------------------------------------------------------------------


def _as_bool(v) -> jnp.ndarray:
    if isinstance(v, NumCol):
        return v.data.astype(jnp.bool_) if v.data.dtype != jnp.bool_ else v.data
    if isinstance(v, bool):
        return jnp.asarray(v)
    raise CompileError(f"expected boolean, got {type(v)}")


def _numeric_data(v):
    if isinstance(v, NumCol):
        if v.hi is not None:
            raise CompileError("arithmetic on wide ints requires x64 (CPU) mode")
        return v.data
    if isinstance(v, _DateScalar):
        return v.days
    if isinstance(v, (int, float, bool)):
        return v
    raise CompileError(f"expected numeric, got {type(v)}")


def _result_kind(a, b, op):
    ka = a.kind if isinstance(a, NumCol) else _scalar_kind(a)
    kb = b.kind if isinstance(b, NumCol) else _scalar_kind(b)
    if op == "/":
        return "f"
    if "d" in (ka, kb) and op in ("+", "-"):
        # date - date -> int days; date +/- interval -> date
        if ka == "d" and kb == "d":
            return "i"
        return "d"
    if "f" in (ka, kb):
        return "f"
    return "i"


def _scalar_kind(v):
    if isinstance(v, _DateScalar):
        return "d"
    if isinstance(v, bool):
        return "b"
    if isinstance(v, int):
        return "i"
    if isinstance(v, float):
        return "f"
    return "?"


_CMP = {"=", "!=", "<", "<=", ">", ">="}


def _binop(op, a, b):
    if op in ("and", "or"):
        xa, xb = _as_bool(a), _as_bool(b)
        return NumCol(xa & xb if op == "and" else xa | xb, "b")

    # string comparisons -> dictionary trick / hash equality
    if isinstance(a, StrCol) or isinstance(b, StrCol):
        return _string_compare(op, a, b)

    # interval arithmetic on dates
    if isinstance(b, IntervalLit):
        return _date_interval(op, a, b)
    if isinstance(a, IntervalLit):
        raise CompileError("interval must be on the right-hand side")

    # wide-int comparisons (two-limb)
    wa = isinstance(a, NumCol) and a.hi is not None
    wb = isinstance(b, NumCol) and b.hi is not None
    if (wa or wb) and op in _CMP:
        return _wide_compare(op, a, b)

    da, db = _numeric_data(a), _numeric_data(b)
    if op in _CMP:
        fn = {
            "=": lambda x, y: x == y,
            "!=": lambda x, y: x != y,
            "<": lambda x, y: x < y,
            "<=": lambda x, y: x <= y,
            ">": lambda x, y: x > y,
            ">=": lambda x, y: x >= y,
        }[op]
        res = fn(da, db)
        # SQL three-valued logic: a null operand makes the predicate false
        for side in (a, b):
            if isinstance(side, NumCol) and side.kind in ("i", "d", "t", "f"):
                res = res & ~null_mask(side)
        return NumCol(res, "b")

    kind = _result_kind(a, b, op)
    if op == "+":
        out = da + db
    elif op == "-":
        out = da - db
    elif op == "*":
        out = da * db
    elif op == "/":
        fa = jnp.asarray(da, dtype=config.float_dtype()) if not isinstance(da, (int, float)) else da
        fb = jnp.asarray(db, dtype=config.float_dtype()) if not isinstance(db, (int, float)) else db
        out = fa / fb
    elif op == "//":
        out = da // db
    elif op == "%":
        out = da % db
    else:
        raise CompileError(f"binop {op}")
    out = jnp.asarray(out)
    # arithmetic would destroy int sentinels (INT_MIN + 1 is no longer null):
    # re-mark the result null wherever a sentinel-kind operand was null
    nulls = None
    for side in (a, b):
        if isinstance(side, NumCol) and side.kind in ("i", "d", "t"):
            nm = null_mask(side)
            nulls = nm if nulls is None else nulls | nm
    if nulls is not None:
        if kind == "f" or jnp.issubdtype(out.dtype, jnp.floating):
            out = jnp.where(nulls, jnp.nan, out)
        else:
            out = jnp.where(nulls, jnp.iinfo(out.dtype).min, out)
    return NumCol(out, kind)


def _days_in_month(y, m):
    """Vectorized month lengths with Gregorian leap years."""
    lengths = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31],
                          dtype=jnp.int32)
    leap = ((y % 4 == 0) & ((y % 100 != 0) | (y % 400 == 0))).astype(jnp.int32)
    return lengths[m - 1] + jnp.where(m == 2, leap, 0)


def _add_months_days(days, delta_months):
    """date(days since epoch) + N calendar months, day-of-month clamped to the
    target month's length (SQL interval-month semantics)."""
    y, m, d = _civil_from_days(days)
    mt = y * 12 + (m - 1) + delta_months
    y2 = jnp.floor_divide(mt, 12)
    m2 = mt - y2 * 12 + 1
    d2 = jnp.minimum(d, _days_in_month(y2, m2))
    return _days_from_civil(y2, m2, d2)


def _add_months_scalar(days: int, delta_months: int) -> int:
    import calendar
    import datetime

    dt = datetime.date(1970, 1, 1) + datetime.timedelta(days=int(days))
    mt = dt.year * 12 + (dt.month - 1) + int(delta_months)
    y2, m2 = mt // 12, mt % 12 + 1
    d2 = min(dt.day, calendar.monthrange(y2, m2)[1])
    return (datetime.date(y2, m2, d2) - datetime.date(1970, 1, 1)).days


def _date_interval(op, a, iv: IntervalLit):
    if iv.months:
        delta_m = -iv.months if op == "-" else iv.months
        if iv.micros:
            raise CompileError("mixed month+day intervals")
        if isinstance(a, _DateScalar):
            return _DateScalar(_add_months_scalar(a.days, delta_m))
        if isinstance(a, NumCol) and a.kind == "d":
            out = _add_months_days(a.data, delta_m).astype(jnp.int32)
            nm = null_mask(a)  # civil math would turn the sentinel into a date
            return NumCol(jnp.where(nm, jnp.int32(NULL_I32), out), "d")
        raise CompileError("month/year interval arithmetic on non-date")
    if not isinstance(a, NumCol):
        if isinstance(a, _DateScalar):
            d = a.days + (iv.days if op == "+" else -iv.days)
            return _DateScalar(d)
        raise CompileError("interval arithmetic on non-date")
    if a.kind == "d":
        delta = iv.days
    elif a.kind == "t":
        delta = _micros_to_unit(iv.micros, a.unit or "us")
    else:
        raise CompileError(f"interval arithmetic on kind {a.kind}")
    if op == "-":
        delta = -delta
    if a.hi is not None:
        raise CompileError("interval arithmetic on wide timestamps requires x64")
    return NumCol(a.data + delta, a.kind, unit=a.unit)


def _micros_to_unit(micros: int, unit: str) -> int:
    scale = {"s": 1_000_000, "ms": 1_000, "us": 1, "ns": 1 / 1000}[unit]
    return int(micros / scale)


def _wide_compare(op, a, b):
    def limbs(v):
        if isinstance(v, NumCol):
            if v.hi is not None:
                return v.hi, v.data
            # narrow col vs wide: widen
            hi = jnp.where(v.data < 0, -1, 0).astype(v.data.dtype)
            lo = _lo_sortable_from_narrow(v.data)
            return hi, lo
        val = int(v.days if isinstance(v, _DateScalar) else v)
        hi = np.int32(val >> 32)
        lo_u = np.uint32(val & 0xFFFFFFFF)
        lo = np.int32(int(lo_u) - 2**31)
        return hi, lo

    ahi, alo = limbs(a)
    bhi, blo = limbs(b)
    eq = (ahi == bhi) & (alo == blo)
    lt = (ahi < bhi) | ((ahi == bhi) & (alo < blo))
    table = {
        "=": eq,
        "!=": ~eq,
        "<": lt,
        "<=": lt | eq,
        ">": ~(lt | eq),
        ">=": ~lt,
    }
    res = table[op]
    for side in (a, b):
        if isinstance(side, NumCol):
            res = res & ~null_mask(side)
    return NumCol(res, "b")


def _lo_sortable_from_narrow(x):
    u = x.astype(jnp.uint32)
    return (u ^ jnp.uint32(0x80000000)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# strings
# ---------------------------------------------------------------------------


def _dict_gather(col: StrCol, host_values: np.ndarray, kind: str) -> NumCol:
    """Evaluate something per-dictionary-entry on host, gather by code.
    Null rows (code < 0) yield False for predicates, NULL sentinel for ints."""
    g = jnp.asarray(host_values)[jnp.maximum(col.codes, 0)]
    isnull = col.codes < 0
    if kind == "b":
        g = g & ~isnull
    elif kind == "f":
        g = jnp.where(isnull, jnp.nan, g)
    else:
        sent = NULL_I32 if g.dtype != jnp.int64 else -(2**63)
        g = jnp.where(isnull, sent, g)
    return NumCol(g, kind)


def _notnone(d: StringDict) -> np.ndarray:
    """Host mask of dictionary entries that are real strings (None = null).
    Reuses the cached StringDict.none_entries mask — no per-batch host loop."""
    none = d.none_entries
    if none is None:
        return np.ones(len(d), dtype=bool)
    return ~none


def _string_compare(op, a, b):
    if isinstance(a, str) and isinstance(b, StrCol):
        a, b, op = b, a, _flip(op)
    if isinstance(a, StrCol) and isinstance(b, str):
        vals = a.dictionary.values.astype(str)
        nn = _notnone(a.dictionary)  # null strings never match (3VL)
        if op == "=":
            return _dict_gather(a, (vals == b) & nn, "b")
        if op == "!=":
            return _dict_gather(a, (vals != b) & nn, "b")
        cmp = {"<": vals < b, "<=": vals <= b, ">": vals > b, ">=": vals >= b}[op]
        return _dict_gather(a, cmp & nn, "b")
    if isinstance(a, StrCol) and isinstance(b, StrCol):
        if op not in ("=", "!="):
            raise CompileError("ordering comparison between two string columns (todo)")
        ahi, alo = a.hash_limbs()
        bhi, blo = b.hash_limbs()
        eq = (ahi == bhi) & (alo == blo)
        out = eq if op == "=" else ~eq
        out = out & ~null_mask(a) & ~null_mask(b)
        return NumCol(out, "b")
    raise CompileError(f"string comparison {type(a)} {op} {type(b)}")


def _flip(op):
    return {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "!=": "!="}[op]


_NEG_CMP = {"=": "!=", "!=": "=", "<": ">=", "<=": ">", ">": "<=", ">=": "<"}


def _negate_expr(e: Expr) -> Optional[Expr]:
    """Push a logical NOT one level down, or None if it can't be pushed.
    Negated comparisons keep their null guard (null operand -> false), which a
    plain bitwise invert would wrongly turn into true (SQL three-valued logic)."""
    if isinstance(e, BinOp):
        if e.op in _NEG_CMP:
            return BinOp(_NEG_CMP[e.op], e.left, e.right)
        if e.op in ("and", "or"):
            la, lb = _negate_expr(e.left), _negate_expr(e.right)
            if la is None:
                la = UnaryOp("not", e.left)
            if lb is None:
                lb = UnaryOp("not", e.right)
            return BinOp("or" if e.op == "and" else "and", la, lb)
        return None
    if isinstance(e, UnaryOp) and e.op == "not":
        return e.operand
    if isinstance(e, IsNull):
        return IsNull(e.expr, negated=not e.negated)
    if isinstance(e, InList):
        return InList(e.expr, e.values, negated=not e.negated)
    return None


def _like_to_regex(pat: str) -> str:
    out = []
    for ch in pat:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "^" + "".join(out) + "$"


def _str_op(e: StrOp, batch: DeviceBatch):
    v = evaluate(e.expr, batch)
    if not isinstance(v, StrCol):
        raise CompileError(f"str op {e.op} on non-string")
    vals = v.dictionary.values
    if e.op != "hash":
        # every other op walks the batch's dictionary on the host
        from quokka_tpu.obs import opstats

        opstats.note(str_pred_dict_rows=len(vals))
    svals = vals.astype(str)
    if e.op == "like":
        rx = re.compile(_like_to_regex(e.args[0]))
        mask = np.array([bool(rx.match(s)) for s in svals])
        return _dict_gather(v, mask, "b")
    if e.op == "contains":
        return _dict_gather(v, np.char.find(svals, e.args[0]) >= 0, "b")
    if e.op == "starts_with":
        return _dict_gather(v, np.char.startswith(svals, e.args[0]), "b")
    if e.op == "ends_with":
        return _dict_gather(v, np.char.endswith(svals, e.args[0]), "b")
    if e.op == "length":
        return _dict_gather(v, np.char.str_len(svals).astype(np.int32), "i")
    if e.op == "hash":
        hi = jnp.asarray(v.dictionary.hash_hi)[jnp.maximum(v.codes, 0)]
        return NumCol(jnp.where(v.codes < 0, 0, hi), "i")
    # string -> string transforms: rewrite the dictionary, keep codes
    if e.op == "lower":
        return StrCol(v.codes, StringDict(np.char.lower(svals).astype(object)))
    if e.op == "upper":
        return StrCol(v.codes, StringDict(np.char.upper(svals).astype(object)))
    if e.op == "strip":
        return StrCol(v.codes, StringDict(np.char.strip(svals).astype(object)))
    if e.op == "slice":
        off, length = e.args[0], e.args[1]
        if length is None:
            new = np.array([s[off:] for s in svals], dtype=object)
        else:
            new = np.array([s[off : off + int(length)] for s in svals], dtype=object)
        return StrCol(v.codes, StringDict(new))
    if e.op == "json_extract":
        import json

        path = e.args[0].lstrip("$.")

        def get(s):
            try:
                return str(json.loads(s).get(path))
            except Exception:
                return None

        new = np.array([get(s) for s in svals], dtype=object)
        return StrCol(v.codes, StringDict(new))
    raise CompileError(f"str op {e.op}")


def _in_list(e: InList, batch: DeviceBatch):
    v = evaluate(e.expr, batch)
    if isinstance(v, StrCol):
        mask = np.isin(v.dictionary.values.astype(str), [str(x) for x in e.values])
        mask = mask & _notnone(v.dictionary)
        out = _dict_gather(v, mask, "b")
    else:
        data = _numeric_data(v)
        acc = jnp.zeros_like(data, dtype=jnp.bool_)
        for val in e.values:
            acc = acc | (data == val)
        out = NumCol(acc, "b")
    if e.negated:
        out = NumCol(~out.data, "b")
    # null operand: both IN and NOT IN are null -> false under 3VL
    if isinstance(v, (NumCol, StrCol)):
        out = NumCol(out.data & ~null_mask(v), "b")
    return out


def _is_null(e: IsNull, batch: DeviceBatch):
    v = evaluate(e.expr, batch)
    if isinstance(v, (StrCol, NumCol)):
        out = NumCol(null_mask(v), "b")
    else:
        out = NumCol(jnp.zeros(batch.padded_len, dtype=jnp.bool_), "b")
    if e.negated:
        out = NumCol(~out.data, "b")
    return out


# ---------------------------------------------------------------------------
# dates
# ---------------------------------------------------------------------------


def _civil_from_days(days):
    """days since 1970-01-01 -> (year, month, day); Hinnant's algorithm in
    pure int32 jnp ops."""
    z = days + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(doe - doe // 1460 + doe // 36524 - doe // 146096, 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = jnp.floor_divide(5 * doy + 2, 153)
    d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def _ts_to_seconds(col: NumCol):
    scale = {"s": 1, "ms": 1_000, "us": 1_000_000, "ns": 1_000_000_000}[col.unit or "us"]
    if col.hi is not None:
        raise CompileError("timestamp field extraction on wide ints requires x64")
    return col.data // scale


def _dt_field(e: DtField, batch: DeviceBatch):
    v = evaluate(e.expr, batch)
    if not isinstance(v, NumCol) or v.kind not in ("d", "t"):
        raise CompileError(f"extract({e.field}) on non-temporal column")
    if v.kind == "d":
        days = v.data
        secs_in_day = None
    else:
        secs = _ts_to_seconds(v)
        days = jnp.floor_divide(secs, 86400)
        secs_in_day = secs - days * 86400
    f = e.field
    if f in ("year", "month", "day"):
        y, m, d = _civil_from_days(days)
        out = {"year": y, "month": m, "day": d}[f]
        return NumCol(out.astype(jnp.int32), "i")
    if f == "weekday":
        return NumCol(((days + 4) % 7).astype(jnp.int32), "i")  # 0=Sunday
    if secs_in_day is None:
        raise CompileError(f"extract({f}) from a date")
    if f == "hour":
        return NumCol((secs_in_day // 3600).astype(jnp.int32), "i")
    if f == "minute":
        return NumCol(((secs_in_day // 60) % 60).astype(jnp.int32), "i")
    if f == "second":
        return NumCol((secs_in_day % 60).astype(jnp.int32), "i")
    raise CompileError(f"extract field {f}")


# ---------------------------------------------------------------------------
# misc scalar funcs
# ---------------------------------------------------------------------------


def _case(e: Case, batch: DeviceBatch):
    # string-valued CASE: any string branch routes to the dictionary path
    raw_vals = [evaluate(v, batch) for _, v in e.whens]
    raw_default = evaluate(e.default, batch) if e.default is not None else None
    if any(isinstance(v, (StrCol, str)) for v in raw_vals + [raw_default]):
        return _case_string(e, batch, raw_vals, raw_default)
    # numeric path: reuse the already-evaluated branch values (a second
    # evaluate() would re-run every branch subtree on device)
    default = (
        value_to_column(raw_default, batch)
        if raw_default is not None
        else NumCol(jnp.full(batch.padded_len, jnp.nan, dtype=config.float_dtype()), "f")
    )
    conds = [evaluate_predicate(cond, batch) for cond, _ in e.whens]
    vals = [value_to_column(v, batch) for v in raw_vals]
    # promote all branches to a common dtype before any where()
    dtype = jnp.result_type(default.data, *(v.data for v in vals))
    out = default.data.astype(dtype)
    kind = "f" if jnp.issubdtype(dtype, jnp.floating) else default.kind
    for c, vcol in zip(reversed(conds), reversed(vals)):
        out = jnp.where(c, vcol.data.astype(dtype), out)
    return NumCol(out, kind)


def _case_string(e: Case, batch: DeviceBatch, raw_vals, raw_default):
    """String-valued CASE: merge the branch dictionaries, pick codes with
    nested where (the string work stays host-side over small dictionaries;
    per-row selection is int32 code arithmetic on device)."""
    from quokka_tpu.ops import bridge

    n = batch.padded_len
    branches = list(raw_vals) + ([raw_default] if raw_default is not None else [])
    dicts = []
    for v in branches:
        if isinstance(v, StrCol):
            dicts.append(v.dictionary)
        elif isinstance(v, str):
            dicts.append(StringDict(np.array([v], dtype=object)))
        elif v is None:
            dicts.append(StringDict(np.array([None], dtype=object)))
        else:
            raise CompileError("CASE mixes string and non-string branches")
    merged, remaps = bridge.merge_dicts(dicts)

    def codes_of(v, remap):
        if isinstance(v, StrCol):
            if remap is None:
                return v.codes
            g = jnp.asarray(remap)[jnp.maximum(v.codes, 0)]
            return jnp.where(v.codes < 0, -1, g)
        code = 0 if remap is None else int(remap[0])
        return jnp.full(n, code, dtype=jnp.int32)

    if raw_default is not None:
        out = codes_of(raw_default, remaps[-1])
    else:
        out = jnp.full(n, -1, dtype=jnp.int32)  # ELSE missing -> null
    conds = [evaluate_predicate(c, batch) for c, _ in e.whens]
    for cond, v, remap in zip(reversed(conds), reversed(raw_vals),
                              reversed(remaps[: len(raw_vals)])):
        out = jnp.where(cond, codes_of(v, remap), out)
    return StrCol(out, merged)


def _cast(e: Cast, batch: DeviceBatch):
    v = evaluate(e.expr, batch)
    to = e.to
    if to.startswith(("double", "float", "real", "decimal", "numeric")):
        if isinstance(v, (int, float)):
            return float(v)
        if isinstance(v, StrCol):
            vals = np.array(
                [float(x) if x not in (None, "") else np.nan for x in v.dictionary.values]
            )
            return _dict_gather(v, vals.astype(np.float64 if config.x64_enabled() else np.float32), "f")
        return NumCol(v.data.astype(config.float_dtype()), "f")
    if to.startswith(("int", "bigint", "smallint", "tinyint")):
        if isinstance(v, (int, float)):
            return int(v)
        return NumCol(v.data.astype(config.int_dtype()), "i")
    if to.startswith("bool"):
        return NumCol(_as_bool(v), "b")
    if to.startswith("date"):
        if isinstance(v, str):
            return _DateScalar(DateLit(v).days)
        if isinstance(v, NumCol) and v.kind == "t":
            secs = _ts_to_seconds(v)
            return NumCol((secs // 86400).astype(jnp.int32), "d")
        if isinstance(v, NumCol):
            return NumCol(v.data.astype(jnp.int32), "d")
    if to.startswith(("varchar", "string", "text")):
        return _cast_to_string(v, batch)
    raise CompileError(f"cast to {to}")


def _cast_to_string(v, batch: DeviceBatch) -> StrCol:
    """Numeric/date -> dictionary-encoded string.  Costs one host sync per
    batch (string materialization is host work by design); distinct values
    become the dictionary, rows gather by code."""
    if isinstance(v, StrCol):
        return v
    if isinstance(v, str):
        return StrCol(
            jnp.zeros(batch.padded_len, dtype=jnp.int32),
            StringDict(np.array([v], dtype=object)),
        )
    if isinstance(v, bool):
        # match the bool COLUMN stringification ("true"/"false"), not str(True)
        return StrCol(
            jnp.zeros(batch.padded_len, dtype=jnp.int32),
            StringDict(np.array(["true" if v else "false"], dtype=object)),
        )
    if isinstance(v, (int, float)):
        return StrCol(
            jnp.zeros(batch.padded_len, dtype=jnp.int32),
            StringDict(np.array([str(v)], dtype=object)),
        )
    if not isinstance(v, NumCol):
        raise CompileError(f"cast to string from {type(v).__name__}")
    from quokka_tpu.ops import timewide
    from quokka_tpu.ops.batch import null_mask

    # stringify only VALID, non-null rows: padded/invalid slots hold garbage
    # that would bloat the dictionary and waste host time
    valid, nm, data = tracing.device_read(
        "expr.cast_string", (batch.valid, null_mask(v), v.data))
    live = valid & ~nm
    idx = np.nonzero(live)[0]
    if v.kind == "d":
        days = data[idx].astype("datetime64[D]")
        host = np.array([str(x) for x in days], dtype=object)
    elif v.kind == "t" or v.hi is not None:
        vals = timewide.host_i64(v, jnp.asarray(live))
        if v.kind == "t":
            unit = v.unit or "us"
            host = np.array(
                [str(x) for x in vals.astype(f"datetime64[{unit}]")], dtype=object
            )
        else:
            host = np.array([str(int(x)) for x in vals], dtype=object)
    elif v.kind == "b":
        host = np.array(
            ["true" if x else "false" for x in data[idx]], dtype=object
        )
    else:
        data = data[idx]
        if v.kind == "f":
            host = np.array([str(float(x)) for x in data], dtype=object)
        else:
            host = np.array([str(int(x)) for x in data], dtype=object)
    uniq, live_codes = np.unique(host, return_inverse=True)
    codes = np.full(batch.padded_len, -1, dtype=np.int32)
    codes[idx] = live_codes.astype(np.int32)
    return StrCol(jnp.asarray(codes), StringDict(uniq.astype(object)))


def _func(e: Func, batch: DeviceBatch):
    name = e.name
    args = [evaluate(a, batch) for a in e.args]

    def num(i):
        return _numeric_data(args[i])

    if name in ("__nn0", "__nnhigh", "__nnlow", "__nncount"):
        # internal null-skipping wrappers injected by AggPlan.rewrite: replace
        # nulls with the aggregate's identity element before the kernel agg
        v = args[0]
        if not isinstance(v, (NumCol, StrCol)):
            if name == "__nncount":
                return NumCol(jnp.ones(batch.padded_len, dtype=jnp.int32), "i")
            return v
        nm = null_mask(v)
        if name == "__nncount":
            return NumCol((~nm).astype(jnp.int32), "i")
        if isinstance(v, StrCol):
            raise CompileError("numeric aggregate over a string column")
        if v.hi is not None:
            raise CompileError("aggregate over wide ints requires x64")
        if v.kind == "f":
            repl = {"__nn0": 0.0, "__nnhigh": jnp.inf, "__nnlow": -jnp.inf}[name]
        else:
            ii = jnp.iinfo(v.data.dtype)
            repl = {"__nn0": 0, "__nnhigh": ii.max, "__nnlow": ii.min}[name]
        return NumCol(jnp.where(nm, repl, v.data), v.kind, unit=v.unit)

    if name == "abs":
        return NumCol(jnp.abs(num(0)), _kind_of(args[0]))
    if name == "round":
        nd = int(args[1]) if len(args) > 1 else 0
        return NumCol(jnp.round(num(0), nd), "f")
    if name == "sqrt":
        return NumCol(jnp.sqrt(jnp.asarray(num(0), config.float_dtype())), "f")
    if name == "exp":
        return NumCol(jnp.exp(jnp.asarray(num(0), config.float_dtype())), "f")
    if name in ("ln", "log"):
        return NumCol(jnp.log(jnp.asarray(num(0), config.float_dtype())), "f")
    if name == "floor":
        return NumCol(jnp.floor(num(0)), "f")
    if name == "ceil":
        return NumCol(jnp.ceil(num(0)), "f")
    if name == "power":
        return NumCol(jnp.power(jnp.asarray(num(0), config.float_dtype()), num(1)), "f")
    if name == "sign":
        return NumCol(jnp.sign(num(0)), _kind_of(args[0]))
    if name in ("sin", "cos"):
        f = jnp.sin if name == "sin" else jnp.cos
        return NumCol(f(jnp.asarray(num(0), config.float_dtype())), "f")
    if name == "coalesce":
        v = args[0]
        if not isinstance(v, NumCol):
            return v  # scalar first arg is never null
        if v.hi is not None:
            raise CompileError("coalesce on wide ints requires x64")
        kind = v.kind
        out = v.data
        for i in range(1, len(args)):
            # sentinel-aware: detect nulls of the CURRENT accumulator (NaN for
            # floats, INT_MIN for int kinds), not just NaN
            nm = null_mask(NumCol(out, kind))
            nxt = args[i]
            nxt_data = nxt.data if isinstance(nxt, NumCol) else nxt
            if isinstance(nxt, NumCol) and nxt.kind == "f" and kind != "f":
                out = out.astype(config.float_dtype())
                kind = "f"
                nm = jnp.isnan(out) | nm.astype(bool)
            out = jnp.where(nm, nxt_data, out)
        return NumCol(jnp.asarray(out), kind)
    if name in ("greatest", "least"):
        f = jnp.maximum if name == "greatest" else jnp.minimum
        out = num(0)
        for i in range(1, len(args)):
            out = f(out, num(i))
        return NumCol(jnp.asarray(out), _kind_of(args[0]))
    if name == "date_trunc":
        every = args[0]
        v = args[1]
        if not isinstance(v, NumCol):
            raise CompileError("date_trunc on scalar")
        if v.kind == "d" and every in ("month", "year"):
            y, m, _ = _civil_from_days(v.data)
            if every == "year":
                m = jnp.ones_like(m)
            return NumCol(_days_from_civil(y, m, jnp.ones_like(m)), "d")
        raise CompileError(f"date_trunc {every} on kind {v.kind}")
    raise CompileError(f"function {name}")


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = m + jnp.where(m > 2, -3, 9)
    doy = jnp.floor_divide(153 * mp + 2, 5) + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return (era * 146097 + doe - 719468).astype(jnp.int32)


def _kind_of(v):
    if isinstance(v, NumCol):
        return v.kind
    return _scalar_kind(v)


# ---------------------------------------------------------------------------
# aggregation decomposition (partial -> final), mirroring the semantics of
# pyquokka/sql_utils.py:299-412 parse_multiple_aggregations
# ---------------------------------------------------------------------------


class AggPlan:
    """Decomposed aggregation:
    - pre: [(tmp_name, Expr)]           per-batch scalar columns to compute
    - partials: [(pname, op, tmp|None)] kernel aggs over (keys, tmp columns)
    - recombine: [(pname, op)]          how to merge partial results
    - finals: [(out_name, Expr over partial names)]
    """

    def __init__(self):
        self.pre: List[Tuple[str, Expr]] = []
        self.partials: List[Tuple[str, str, Optional[str]]] = []
        self.recombine: List[Tuple[str, str]] = []
        self.finals: List[Tuple[str, Expr]] = []
        self._memo: Dict[str, str] = {}

    def _tmp(self, e: Expr) -> str:
        key = "pre:" + e.sql()
        if key in self._memo:
            return self._memo[key]
        name = f"__pre_{len(self.pre)}"
        self.pre.append((name, e))
        self._memo[key] = name
        return name

    def _partial(self, op: str, arg: Optional[Expr]) -> str:
        key = f"agg:{op}:{arg.sql() if arg is not None else '*'}"
        if key in self._memo:
            return self._memo[key]
        name = f"__agg_{len(self.partials)}"
        tmp = self._tmp(arg) if arg is not None else None
        self.partials.append((name, op, tmp))
        self.recombine.append((name, {"count": "sum"}.get(op, op)))
        self._memo[key] = name
        return name

    def rewrite(self, e: Expr) -> Expr:
        if isinstance(e, Agg):
            if e.distinct:
                raise CompileError("count(distinct) requires the holistic agg path")
            # null skipping: wrap args so nulls become the agg's identity and
            # count(col) counts only non-null rows (SQL semantics)
            def nn_count(arg):
                if arg is None:
                    return ColRef(self._partial("count", None))
                return ColRef(self._partial("sum", Func("__nncount", [arg])))

            if e.op == "sum":
                return ColRef(self._partial("sum", Func("__nn0", [e.arg])))
            if e.op == "min":
                return ColRef(self._partial("min", Func("__nnhigh", [e.arg])))
            if e.op == "max":
                return ColRef(self._partial("max", Func("__nnlow", [e.arg])))
            if e.op == "count":
                return nn_count(e.arg)
            if e.op == "avg":
                s = ColRef(self._partial("sum", Func("__nn0", [e.arg])))
                c = nn_count(e.arg)
                return BinOp("/", s, c)
            if e.op in ("stddev", "var"):
                x = Func("__nn0", [e.arg])
                s1 = ColRef(self._partial("sum", x))
                s2 = ColRef(self._partial("sum", BinOp("*", x, x)))
                c = nn_count(e.arg)
                mean = BinOp("/", s1, c)
                var = BinOp("-", BinOp("/", s2, c), BinOp("*", mean, mean))
                if e.op == "var":
                    return var
                return Func("sqrt", [var])
            raise CompileError(f"aggregate {e.op}")
        kids = e.children()
        if not kids:
            return e
        from quokka_tpu.expression import _rebuild

        return _rebuild(e, [self.rewrite(k) for k in kids])


def plan_aggregation(outputs: Sequence[Expr]) -> AggPlan:
    """outputs: Alias-wrapped expressions containing Agg nodes."""
    plan = AggPlan()
    for i, e in enumerate(outputs):
        name = e.name if isinstance(e, Alias) else f"col{i}"
        inner = e.expr if isinstance(e, Alias) else e
        plan.finals.append((name, plan.rewrite(inner)))
    return plan
