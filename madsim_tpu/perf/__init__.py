"""Wall-clock observability: host-timeline tracing, interleaved A/B
gate costing, and drift-aware bench history.

Everything in this package is HOST-side: it measures what the Python
driver and the device queue do in real time. It never touches the
simulation's RNG streams, event schedules, or any device-visible value
— golden streams and gate-off bit-identity are unaffected by
construction (the lint D-rules' wall-clock/entropy bans are lifted
file-by-file here because measuring the wall clock IS the contract).

* `recorder` — `PerfRecorder` + contextvar span API (`maybe_span`),
  Chrome/Perfetto host-timeline export (`--perf-timeline`, `perf`);
* `ab` — interleaved ABAB… paired-delta gate costing with bootstrap CI
  and sign test (`bench-ab`, bench.py's `step_cost`);
* `history` — BENCH_HISTORY.jsonl append/import/neighbor-compare and
  the `bench report` trend renderer.
"""

from .ab import ABResult, bootstrap_ci, interleaved_ab, paired_stats, sign_test_p
from .recorder import (
    PerfRecorder,
    current_recorder,
    maybe_count,
    maybe_note,
    maybe_span,
)

__all__ = [
    "ABResult",
    "PerfRecorder",
    "bootstrap_ci",
    "current_recorder",
    "interleaved_ab",
    "maybe_count",
    "maybe_note",
    "maybe_span",
    "paired_stats",
    "sign_test_p",
]
