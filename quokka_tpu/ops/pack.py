"""Coalesced, compile-stable host<->device transfers.

Every batch crosses the host/device boundary with ONE runtime call in each
direction: ``jax.device_put`` of the whole list of (narrowed) column arrays,
and ``jax.device_get`` of the whole list coming back.  Decoding back to the
logical dtypes happens in one small jitted elementwise program per *layout*
(astype + bias add, table gathers, ``arange < count`` for validity).

Design note — why a list of typed arrays and not one byte buffer: the first
cut of this module packed all columns into a single uint8 buffer and sliced/
bitcast it apart on device.  That unpack program is compile-hostile on TPU
(uint8 reshapes + bitcasts across lane tiling): a single 7-column/1M-row
layout took minutes of XLA compile, and because the
layout (offsets, widths) changed whenever a batch's value ranges changed,
queries recompiled it repeatedly.  A pytree ``device_put`` costs the same
single RPC, and the decode program here is plain elementwise/gather code
that compiles in ~1 s.

Wire narrowing (kept from the first cut): integer columns whose value range
fits 8/16 bits travel as offset-encoded uint8/uint16 and are widened back on
device (the bias rides as a tiny data array, NOT in the compile key); float
columns with few distinct values (TPC-H's 2-decimal discounts/taxes, rates)
travel as uint8/uint16 codes plus a small value table and are re-gathered on
device.  This typically halves wire bytes — host->device bandwidth, not
device compute, is the scan bottleneck (SURVEY.md §7 hard part 4).

Narrowing decisions are STICKY per batch-signature (dtypes + shapes): the
first batch picks each column's wire format and later batches conform,
widening the plan monotonically (at most two recompiles per column ever)
when a batch's range no longer fits.  This keeps the decode program's
compile key stable across batches — the property whose absence caused the
pathological recompiles above.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from quokka_tpu.obs import spans as tracing

# below this many elements a min/max or distinct scan costs more than it saves
_NARROW_MIN_ELEMS = 4096
# float columns: sample-distinct cutoff before paying for a full unique()
_FLOAT_DICT_SAMPLE_DISTINCT = 200
_FLOAT_DICT_MAX = 65535

_WIDTH = {"uint8": 0, "uint16": 1}  # narrowing lattice; full width = 2


def _int_wire_needed(mn: int, mx: int) -> str:
    width = mx - mn
    if width <= 0xFF:
        return "uint8"
    if width <= 0xFFFF:
        return "uint16"
    return "full"


class ValidCount:
    """Marker leaf for pack_put: becomes a bool[padded] validity mask computed
    on device as ``arange(padded) < nrows`` (only the count crosses the wire)."""

    def __init__(self, padded: int, nrows: int):
        self.padded = padded
        self.nrows = nrows


class _IntPlan:
    __slots__ = ("wire",)

    def __init__(self, wire: str):
        self.wire = wire  # "uint8" | "uint16" | "full"


class _FloatPlan:
    __slots__ = ("mode", "tlen")

    def __init__(self, mode: str, tlen: int = 0):
        self.mode = mode  # "dict" | "full"
        self.tlen = tlen  # power-of-two table length when mode == "dict"


# batch signature -> per-leaf sticky plans
_PLANS: Dict[Tuple, List] = {}
# decode layout -> jitted program
_DECODE_PROGRAMS: Dict[Tuple, object] = {}


def _float_dict_encode(flat: np.ndarray, plan: Optional[_FloatPlan]):
    """Dictionary-encode a float column per the (possibly new) sticky plan.
    Returns (codes, table, plan) or (None, None, full_plan)."""
    if plan is not None and plan.mode == "full":
        return None, None, plan
    if plan is None:
        # cheap host sample decides whether to pay for a full encode at all
        stride = max(1, flat.size // 4096)
        sample = flat[::stride][:4096]
        if np.unique(sample).size > _FLOAT_DICT_SAMPLE_DISTINCT:
            return None, None, _FloatPlan("full")
    import pyarrow as pa
    import pyarrow.compute as pc

    enc = pc.dictionary_encode(pa.array(flat))
    uniq = enc.dictionary.to_numpy(zero_copy_only=False).astype(flat.dtype)
    if uniq.size > _FLOAT_DICT_MAX or uniq.size == 0:
        return None, None, _FloatPlan("full")
    tlen = max(16, 1 << (int(uniq.size - 1).bit_length()))
    if plan is None:
        plan = _FloatPlan("dict", tlen)
    elif tlen > plan.tlen:
        plan = _FloatPlan("dict", tlen)  # grow monotonically (recompile once)
    wdt = np.uint8 if plan.tlen <= 256 else np.uint16
    codes = enc.indices.to_numpy(zero_copy_only=False).astype(wdt)
    if plan.tlen > uniq.size:
        uniq = np.concatenate(
            [uniq, np.full(plan.tlen - uniq.size, uniq[-1], uniq.dtype)]
        )
    if codes.nbytes + uniq.nbytes >= flat.nbytes:
        # a stream that STARTED low-cardinality can drift high-cardinality;
        # once codes+table stop saving wire bytes, stop paying the encode on
        # every future batch too (sticky degrade, one recompile)
        return None, None, _FloatPlan("full")
    return codes, uniq, plan


def _build_decode(layout: Tuple):
    """One jitted program decoding the whole wire list back to logical arrays.
    Elementwise widen/bias, small table gathers, and arange<count masks only —
    nothing layout-hostile; compile cost is ~1 s and the key (``layout``) is
    stable across batches thanks to sticky plans."""

    @jax.jit
    def decode(wires):
        outs = []
        i = 0
        for spec in layout:
            kind = spec[0]
            if kind == "valid":
                _, padded = spec
                cnt = wires[i][0]
                outs.append(jnp.arange(padded, dtype=jnp.int32) < cnt)
                i += 1
            elif kind == "bool":
                _, shape = spec
                outs.append((wires[i] != 0).reshape(shape))
                i += 1
            elif kind == "widen":
                _, target, shape = spec
                arr = wires[i].astype(jnp.dtype(target)) + wires[i + 1][0]
                outs.append(arr.reshape(shape))
                i += 2
            elif kind == "dict":
                _, shape = spec
                codes, table = wires[i], wires[i + 1]
                outs.append(table[codes.astype(jnp.int32)].reshape(shape))
                i += 2
            else:  # pass
                outs.append(wires[i])
                i += 1
        return tuple(outs)

    return decode


def pack_put(leaves: Sequence) -> List[jax.Array]:
    """Transfer numpy arrays to device with one ``device_put``; returns device
    arrays with the original dtypes/shapes (bools stay bool, narrowed
    ints/floats widened back).  ``ValidCount`` leaves come back as device bool
    masks."""
    if not leaves:
        return []
    items = []
    sig = []
    for arr in leaves:
        if isinstance(arr, ValidCount):
            sig.append(("__valid__", arr.padded))
            items.append(arr)
        else:
            arr = np.ascontiguousarray(arr)
            sig.append((str(arr.dtype), arr.shape))
            items.append(arr)
    sig = tuple(sig)
    plans = _PLANS.setdefault(sig, [None] * len(items))

    wires: List[np.ndarray] = []
    layout: List[Tuple] = []
    for idx, arr in enumerate(items):
        if isinstance(arr, ValidCount):
            wires.append(np.array([arr.nrows], dtype=np.int32))
            layout.append(("valid", arr.padded))
            continue
        shape = arr.shape
        flat = arr.reshape(-1)
        n = flat.size
        if arr.dtype == np.bool_:
            wires.append(flat.view(np.uint8))
            layout.append(("bool", shape))
            continue
        if arr.dtype in (np.int32, np.int64) and n >= _NARROW_MIN_ELEMS:
            plan: Optional[_IntPlan] = plans[idx]
            mn = int(flat.min())
            mx = int(flat.max())
            needed = _int_wire_needed(mn, mx)
            if plan is None:
                plan = _IntPlan(needed)
            elif needed == "full" or (
                plan.wire != "full" and _WIDTH[needed] > _WIDTH[plan.wire]
            ):
                plan = _IntPlan(needed)  # widen monotonically
            plans[idx] = plan
            if plan.wire != "full":
                wdt = np.dtype(plan.wire)
                wires.append((flat - mn).astype(wdt))
                wires.append(np.array([mn], dtype=arr.dtype))
                layout.append(("widen", str(arr.dtype), shape))
                continue
            wires.append(arr)
            layout.append(("pass", str(arr.dtype), shape))
            continue
        if arr.dtype in (np.float32, np.float64) and n >= _NARROW_MIN_ELEMS:
            codes, table, plan = _float_dict_encode(flat, plans[idx])
            plans[idx] = plan
            if codes is not None:
                wires.append(codes)
                wires.append(table)
                layout.append(("dict", shape))
                continue
            wires.append(arr)
            layout.append(("pass", str(arr.dtype), shape))
            continue
        wires.append(arr)
        layout.append(("pass", str(arr.dtype), shape))

    # keyed by layout alone: the program is a function of the layout, and
    # jax.jit re-traces per input dtype/shape signature under one wrapper
    key = tuple(layout)
    prog = _DECODE_PROGRAMS.get(key)
    if prog is None:
        prog = _build_decode(key)
        _DECODE_PROGRAMS[key] = prog
    tracing.add_bytes(sum(w.nbytes for w in wires))
    dwires = jax.device_put(wires)
    return list(prog(dwires))


def get_packed(arrays: Sequence, site: str = "to_arrow") -> List[np.ndarray]:
    """Read device arrays back to host in one ``device_get`` under the span
    ``sync.<site>`` (``spans.device_read``; jax starts every leaf's transfer
    before it waits for the first); returns numpy arrays with the original
    dtypes/shapes.  No device program is involved — the d2h direction must
    never pay a compile."""
    if not arrays:
        return []
    if all(isinstance(a, np.ndarray) for a in arrays):
        return list(arrays)
    return tracing.device_read(site, list(arrays))
