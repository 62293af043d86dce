"""Wall-clock observability: host-timeline tracing.

Everything in this package is HOST-side: it measures what the Python
driver and the device queue do in real time. It never touches the
simulation's RNG streams, event schedules, or any device-visible value
— golden streams and gate-off bit-identity are unaffected by
construction (the lint D-rules' wall-clock/entropy bans are lifted
file-by-file here because measuring the wall clock IS the contract).

* `recorder` — `PerfRecorder` + contextvar span API (`maybe_span`),
  Chrome/Perfetto host-timeline export (`--perf-timeline`, `perf`).
"""

from .recorder import (
    PerfRecorder,
    current_recorder,
    maybe_count,
    maybe_note,
    maybe_span,
)

__all__ = [
    "PerfRecorder",
    "current_recorder",
    "maybe_count",
    "maybe_note",
    "maybe_span",
]
