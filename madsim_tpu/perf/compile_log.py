"""Compile stages by program — which stage of which program took the time?

jax times every compile request in three stages and says so through
`jax.monitoring`: tracing the Python into a jaxpr, lowering the jaxpr to
an MLIR module, and the backend compile — which, with the persistent
cache on, is a cache read (hit) or a real XLA compile (miss). This
module keeps those events, process-wide and always on: set-up is where
they matter most, and no recorder is active there. The listener runs
once per stage event (a nested `jit`'s trace is one too: hundreds in
the trace of a step program), never on a stream's hot path.

`install()` registers the one listener (idempotent; `enable_compile_cache`
calls it). `snapshot(t0, t1)` reduces the events between two
`time.perf_counter` readings to totals per stage and per program. A
stage's total is the UNION of its events' intervals, because jax times a
nested `jit` inside the trace of the function that calls it and a plain
sum would count that time twice.

A program is named by the site that first calls it: `program(name)`
wraps the first call of a jitted function (`supersegment`, `init_carry`,
`reset_rings`, `replay.run`, `replay.step`, beside the `compile` span of
`perf/recorder.py`); an event outside any such site keeps jax's own
function name (`jit_` cut off). With a recorder active every event also
bumps a `compile.<stage>` / `compile.cache_miss` / `compile.cache_hit`
counter.
"""

from __future__ import annotations

# madsim: allow-file(D001) — this module's *contract* is reading the
# host wall clock: it stamps jax's compile-stage events. Nothing here
# can reach simulation state.
import contextlib
import re
import time
from typing import Dict, List, Optional, Tuple

from .recorder import maybe_count
from .xprof import _union_us  # merged length of (start, end) intervals

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_JIT_NAME_RE = re.compile(r"^jit[_(](.*?)\)?$")


class CompileLog:
    """The events of one process, on `time.perf_counter`."""

    def __init__(self) -> None:
        self.events: List[Tuple[float, str, str, float]] = []  # (t_end, stage, program, s)
        self.cache_misses: List[float] = []  # t of each persistent-cache miss
        self.cache_reads: List[Tuple[float, float]] = []  # (t_end, s) of each hit
        self._programs: List[str] = []  # open `program(...)` sites, innermost last

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        now = time.perf_counter()
        stage = STAGES.get(event)
        if stage is not None:
            name = self._programs[-1] if self._programs else _JIT_NAME_RE.sub(
                r"\1", str(kw.get("fun_name", "?")))
            self.events.append((now, stage, name, float(seconds)))
            maybe_count("compile." + stage)
        elif event == CACHE_READ_EVENT:
            self.cache_reads.append((now, float(seconds)))
            maybe_count("compile.cache_hit")

    def on_event(self, event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            self.cache_misses.append(time.perf_counter())
            maybe_count("compile.cache_miss")

    def snapshot(self, t0: Optional[float] = None,
                 t1: Optional[float] = None) -> dict:
        """Totals between `t0` and `t1` (perf_counter; None = unbounded):
        `trace_s` / `lower_s` / `backend_s` (union of the stage's event
        intervals, clipped to the window), `cache_misses`, `cache_hits`,
        `cache_read_s`, `requests` (backend events), and `by_program`
        {name: {trace_s, lower_s, backend_s, requests}}, slowest first."""
        lo = float("-inf") if t0 is None else t0
        hi = float("inf") if t1 is None else t1
        per_stage: Dict[str, list] = {s: [] for s in STAGES.values()}
        per_program: Dict[str, Dict[str, list]] = {}
        requests: Dict[str, int] = {}
        for t_end, stage, name, seconds in self.events:
            a, b = max(t_end - seconds, lo), min(t_end, hi)
            if b <= a:
                continue
            per_stage[stage].append((a, b))
            per_program.setdefault(
                name, {s: [] for s in STAGES.values()})[stage].append((a, b))
            if stage == "backend":
                requests[name] = requests.get(name, 0) + 1
        by_program = {
            name: dict(
                {f"{s}_s": _union_us(iv) for s, iv in stages.items()},
                requests=requests.get(name, 0))
            for name, stages in per_program.items()
        }
        reads = [s for t, s in self.cache_reads if lo <= t <= hi]
        return {
            **{f"{s}_s": _union_us(iv) for s, iv in per_stage.items()},
            "requests": sum(requests.values()),
            "cache_misses": sum(lo <= t <= hi for t in self.cache_misses),
            "cache_hits": len(reads),
            "cache_read_s": sum(reads),
            "by_program": dict(sorted(
                by_program.items(),
                key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]
                                 + kv[1]["backend_s"]))),
        }


_LOG: Optional[CompileLog] = None


def install() -> CompileLog:
    """Register the process's one listener (idempotent) and return its log."""
    global _LOG
    if _LOG is None:
        import jax

        _LOG = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(_LOG.on_duration)
        jax.monitoring.register_event_listener(_LOG.on_event)
    return _LOG


def current() -> Optional[CompileLog]:
    """The process's log if `install` has run, else None (no jax import)."""
    return _LOG


def snapshot(t0: Optional[float] = None, t1: Optional[float] = None) -> dict:
    """`install().snapshot(t0, t1)`: events from before the listener was
    registered are not in it."""
    return install().snapshot(t0, t1)


@contextlib.contextmanager
def program(name: str):
    """Attribute the compile events inside the block to `name`: wrap the
    first call of a jitted function."""
    log = install()
    log._programs.append(name)
    try:
        yield
    finally:
        log._programs.pop()


def slowest(snap: dict) -> Optional[str]:
    """"<stage> of <program>" for the largest stage total of a snapshot."""
    best = None
    for name, p in snap["by_program"].items():
        for stage in STAGES.values():
            if best is None or p[f"{stage}_s"] > best[0]:
                best = (p[f"{stage}_s"], stage, name)
    if best is None or best[0] <= 0.0:
        return None
    return f"{best[1]} of {best[2]} {best[0]:.2f}s"
