"""Shims over private JAX APIs, resolved for the one installed jax.

The package needs a handful of facts only private JAX surfaces expose (am I
inside a trace?).  Using them ad hoc is how silent breakage happens: when a
jax upgrade removes the symbol, a defensive ``except`` turns the probe into a
wrong constant answer and the bug the probe exists to avoid comes back
(round-5 verdict: ``hashtable._in_trace`` swallowing a missing
``trace_state_clean`` would silently re-enable the nested-pjit dispatch
race).  This module is the single allowed consumer of ``jax._src``/
``jax.core`` (lint rule QK003 exempts it): each shim resolves AT IMPORT TIME
against an explicit candidate list and raises ``ImportError`` with the installed
version when none resolves — an upgrade that drops the API fails the whole
package loudly at import instead of corrupting behavior at a call site.
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax


def _resolve(name: str, candidates: Sequence[Tuple[str, str]]) -> Callable:
    """First resolvable ``(module_path, attr)`` wins; none -> ImportError.

    ``module_path`` is dotted relative to the already-imported ``jax``
    package (e.g. ``"core"`` or ``"_src.core"``).
    """
    for mod_path, attr in candidates:
        obj = jax
        try:
            for part in mod_path.split("."):
                obj = getattr(obj, part)
            fn = getattr(obj, attr)
        except AttributeError:
            continue
        if callable(fn):
            return fn
    raise ImportError(
        f"jax {jax.__version__} exposes none of the known locations of "
        f"{name!r} ({['jax.' + m + '.' + a for m, a in candidates]}); "
        "quokka_tpu.analysis.compat must be taught the new location — do NOT "
        "paper over this with a default, callers rely on a correct answer "
        "(see ops/hashtable._in_trace: a wrong False re-enables a "
        "jit-dispatch race)"
    )


# True when no trace is active (top-level eager context).  Callers use the
# negation to route nested calls to plain (traceable) bodies instead of
# hitting a jit-wrapped object from inside another trace.
trace_state_clean: Callable[[], bool] = _resolve(
    "trace_state_clean", (("_src.core", "trace_state_clean"),)
)


# Size of a named mesh axis from inside a shard_map trace.  Shapes derive
# from this (bucket capacity = axis size), so it must resolve, never default.
axis_size: Callable = jax.lax.axis_size
