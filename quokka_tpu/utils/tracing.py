"""Back-compat shim: the span API moved to quokka_tpu.obs.spans.

Spans now additionally land in the flight recorder (quokka_tpu/obs/
recorder.py) so merged timelines show where time went; the QUOKKA_TRACE=1
aggregate-summary behavior is unchanged.  Import from quokka_tpu.obs in
new code.
"""

from __future__ import annotations

from quokka_tpu.obs.spans import (  # noqa: F401 — re-export surface
    enabled,
    reset,
    set_enabled,
    span,
    stats,
    summary,
)
