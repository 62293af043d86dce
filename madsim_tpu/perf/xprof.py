"""The third clock — device execution time, and the plane that unifies
all three.

The repo renders two clocks already: `engine/trace_export.py` draws a
seed's VIRTUAL-time schedule, `perf/recorder.py` draws the HOST
wall-clock timeline. The device program between them is a black box:
an `jax.profiler` capture of a hunt shows anonymous XLA fusions, and
"compile_s" is one opaque number even though trace, lowering and
backend compilation are three different problems (ROADMAP [perf]:
"TRACE-dominated" warm starts). This module closes both gaps:

* **Device-phase attribution** — `scope(name)` (trace-time
  `jax.named_scope("madsim.<name>")`) wraps every cost-model row of a
  step (``madsim.step.pop`` … ``madsim.step.invariants``), the stream
  quartet's segment-level phases and the registered collectives, so a
  profiler capture names simulation phases (``madsim.step.handlers``,
  ``madsim.harvest``, ``madsim.collective.cov-map-or``, …) instead of
  fusion soup. The scopes are ALWAYS in the traced program: a name
  stack lives in MLIR locations, which jax strips from a module before
  hashing it for the persistent compile cache, so they move no cache
  key and no compiled code — the run that is profiled runs the program
  that was timed. The host side of the same picture is the recorder's
  (`perf/recorder.py`): `PerfRecorder(annotate=True)` writes every host
  span into the capture as ``madsim.<span>`` on the device ops' clock.
  ``MADSIM_TPU_XPROF`` keeps one operator meaning: CAPTURE a device
  profile (`prof`, the fleet worker's per-unit capture). It selects no
  program.

* **Compile autopsy** — `compile_autopsy(jitted, avals)` splits a cold
  compile into trace_s / lower_s / backend_s via the AOT stages API
  and attaches `.cost_analysis()` flops/bytes and
  `.memory_analysis()` peak bytes, keyed per `cache_subkey` by the
  caller (`prof compile`). (The same three stages of the compiles a
  run really makes, by program, are `perf/compile_log.py`'s: no second
  compile needed.)

* **The merged plane** — `merge_plane(host_doc, device_events,
  virtual_doc)` aligns the host timeline, the device profile and a
  failing lane's virtual-time trace into ONE Perfetto session.
  Alignment is by explicit clock-sync markers: `sync_marker(point)`
  stamps the SAME monotonically-numbered marker into both planes (a
  recorder instant named ``madsim.sync`` with the seq in its args, and
  a zero-width ``madsim.sync:<seq>`` TraceAnnotation in the device
  profile); the merge matches seqs and shifts device time by the
  median host−device delta. Virtual-time tracks are NEVER shifted —
  they stay in virtual microseconds and are labelled as such.

Three clock domains, stated once:

=============  ==========================================================
host           µs since PerfRecorder entry (`time.perf_counter` based)
device         µs since profiler-session start (jax/XLA's TraceMe clock)
virtual        simulated µs from the seed's event schedule — NOT wall time
=============  ==========================================================
"""

from __future__ import annotations

# madsim: allow-file(D001) — this module's *contract* is wall-clock
# profiling: it times compile stages, stamps wall-epoch clock-sync
# markers and drives jax.profiler captures. Nothing here can reach
# simulation state: a named scope is trace-time metadata, a capture
# and its sync markers are host-side.
import contextlib
import glob
import gzip
import itertools
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .recorder import current_recorder

ENV_GATE = "MADSIM_TPU_XPROF"

#: every device-phase name the executor emits carries this prefix so
#: the merge (and the CI prof-smoke grep) can tell simulation phases
#: from XLA/python-tracer noise.
PHASE_PREFIX = "madsim."

#: clock-sync marker name: recorder instants are named exactly this
#: (seq in args); device-profile slices are named "madsim.sync:<seq>".
SYNC_NAME = "madsim.sync"

#: every device scope the program traces in, as named in the device
#: profile (``madsim.<name>``; scope sites in engine/core.py, pinned by
#: tests). Host-side names (dispatch, counters_poll, ...) are the
#: recorder's spans, not listed here.
DEVICE_PHASES = (
    "step",             # run_segment's event loop (its own control ops)
    "step.pop",         # queue pop + gather, or the Pallas kernel doing both
    "step.rng",         # the step's RNG word block
    "step.handlers",    # timer / message / fault branches + write-back
    "step.provenance",  # lineage fold (only where the gate adds ops)
    "step.outbox",      # message pushes: latency, loss, clog, duplicates
    "step.timers",      # timer pushes + the restart boot timer
    "step.recorder",    # trace ring + flight recorder
    "step.coverage",    # projection, slot hash, buffer append
    "step.invariants",  # invariant, termination, next-state assembly
    "cov_flush",        # buffered coverage slots folded into the maps
    "refill",           # harvested-lane refill (ranks + seed counter)
    "harvest",          # completion count + ring appends + folds
    "fr_fold",          # flight-recorder digest fold
    "cov_fold",         # coverage-map OR fold
    "counters",         # the small counters vector rebuild
    "ring_append",      # failing/abandoned ring append
)

_SYNC_SEQ = itertools.count()


def enabled() -> bool:
    """The MADSIM_TPU_XPROF gate: capture a device profile (`prof`, the
    fleet worker's per-unit capture). It selects no program — the phase
    scopes are always traced in — so it is read only on the host."""
    return os.environ.get(ENV_GATE, "") not in ("", "0")


def scope(name: str):
    """Trace-time phase scope: `jax.named_scope("madsim.<name>")`, on
    always. A name stack lives in MLIR locations, which jax strips
    before it hashes a module for the persistent compile cache
    (`jax._src.cache_key`, `strip-debuginfo`), so the scopes move no
    cache key and change no compiled code: the run that is profiled
    runs the program that was timed."""
    import jax

    return jax.named_scope(PHASE_PREFIX + name)


def collective_scope(name: str):
    """`scope` for a registered collective (srules.COLLECTIVES name):
    the device profile shows ``madsim.collective.<name>`` around the
    op the inline `# madsim: collective(...)` comment declares."""
    return scope("collective." + name)


def sync_marker(point: str, **args: Any) -> Optional[int]:
    """Stamp one clock-sync marker into BOTH planes: a zero-width
    ``madsim.sync:<seq>`` TraceAnnotation into the device profile and
    a ``madsim.sync`` instant (seq + wall-epoch µs in args) onto the
    active PerfRecorder. The executor calls this at dispatch/poll
    boundaries; `merge_plane` matches seqs across the two planes and
    aligns the device clock by the median host−device delta. Returns
    the seq, or None when the gate is off."""
    if not enabled():
        return None
    seq = next(_SYNC_SEQ)
    import jax

    with jax.profiler.TraceAnnotation(f"{SYNC_NAME}:{seq}"):
        pass
    rec = current_recorder()
    if rec is not None:
        rec.instant(
            SYNC_NAME, point=point, seq=seq,
            wall_epoch_us=time.time() * 1e6, **args,
        )
    return seq


# -- device capture ----------------------------------------------------------


@contextlib.contextmanager
def device_trace(logdir: str):
    """Capture a device profile around a block, a sync marker stamped
    at each boundary. Yields the logdir when capturing, None when the
    gate is off (zero side effects).

    Drives an XLA `ProfilerSession` directly with the PYTHON tracer
    off: `jax.profiler.start_trace` hardwires the default options,
    whose python-frame tracer floods the 1M-event trace buffer on a
    multi-second hunt and silently drops every later TraceAnnotation —
    exactly the phase markers this capture exists for. Device + host
    TraceMe tracing stay on. Falls back to `jax.profiler.start_trace`
    when the session API is unavailable."""
    if not enabled():
        yield None
        return
    import jax

    os.makedirs(logdir, exist_ok=True)
    sess = None
    try:
        from jaxlib import xla_client as _xc

        jax.devices()  # backends must exist before the session starts
        opts = _xc.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        sess = _xc.profiler.ProfilerSession(opts)
    except Exception:
        sess = None
        jax.profiler.start_trace(logdir, create_perfetto_trace=True)
    sync_marker("device_trace_start")
    try:
        yield logdir
    finally:
        sync_marker("device_trace_stop")
        if sess is not None:
            sess.export(sess.stop(), str(logdir))
        else:
            jax.profiler.stop_trace()


def find_device_trace(logdir: str) -> Optional[str]:
    """Newest trace artifact under a profiler logdir (the TensorBoard
    ``plugins/profile/<run>/`` layout): ``perfetto_trace.json.gz`` when
    present, else the exporter's ``<host>.trace.json.gz``. None when
    the capture left nothing."""
    for pattern in ("perfetto_trace.json.gz", "*.trace.json.gz"):
        hits = sorted(
            glob.glob(os.path.join(logdir, "**", pattern), recursive=True)
        )
        if hits:
            return hits[-1]
    return None


def load_device_events(path: str, keep_python: bool = False) -> List[dict]:
    """Parse a device-profile trace (gzipped or plain Chrome JSON) into
    its event list. The python-host-tracer slices (names starting with
    ``$`` — profiler.py frames, not simulation phases) are dropped
    unless `keep_python`: they dominate event count without adding
    attribution. Returns [] on a missing/unparseable artifact — the
    merge degrades to host+virtual rather than failing the run."""
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt") as f:
                doc = json.load(f)
        else:
            with open(path) as f:
                doc = json.load(f)
    except (OSError, ValueError):
        return []
    events = doc.get("traceEvents", []) if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        return []
    out = []
    for e in events:
        if not isinstance(e, dict):
            continue
        name = e.get("name") or ""
        if not keep_python and name.startswith("$"):
            continue
        out.append(e)
    return out


# -- compile autopsy ---------------------------------------------------------


def compile_autopsy(jitted, avals: Sequence[Any], label: str = "fn") -> dict:
    """Split one cold compile into its three stages via the AOT stages
    API: trace_s (`.trace`, abstract eval of the Python), lower_s
    (`.lower`, jaxpr -> StableHLO) and backend_s (`.compile`, XLA).
    Attaches `.cost_analysis()` flops / bytes accessed and
    `.memory_analysis()` peak bytes where the backend implements them
    (CPU typically reports cost but not memory — absent metrics are
    None, never fabricated). `jitted` is a jitted fn, `avals` its
    ShapeDtypeStructs; re-runs re-trace by construction (`.trace`
    ignores the executable cache), so an autopsy is honest even on a
    warm engine."""
    t0 = time.perf_counter()
    tracer = getattr(jitted, "trace", None)
    if tracer is not None:
        traced = tracer(*avals)
        t1 = time.perf_counter()
        lowered = traced.lower()
    else:  # older stages API: trace+lower are one step
        t1 = t0
        lowered = jitted.lower(*avals)
    t2 = time.perf_counter()
    compiled = lowered.compile()
    t3 = time.perf_counter()
    out: Dict[str, Any] = {
        "label": label,
        "trace_s": round(t1 - t0, 6),
        "lower_s": round(t2 - t1, 6),
        "backend_s": round(t3 - t2, 6),
        "total_s": round(t3 - t0, 6),
        "flops": None,
        "bytes_accessed": None,
        "peak_bytes": None,
    }
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if "flops" in ca:
                out["flops"] = float(ca["flops"])
            if "bytes accessed" in ca:
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        peak = 0
        for attr in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
        ):
            v = getattr(ma, attr, None)
            if v:
                peak += int(v)
        if peak:
            out["peak_bytes"] = peak
    except Exception:
        pass
    return out


# -- the merged plane --------------------------------------------------------


def _union_us(ivals: List[Tuple[float, float]]) -> float:
    """Merged length of (start, end) intervals."""
    covered = 0.0
    prev_end = None
    for start, end in sorted(ivals):
        if end <= start:
            continue
        if prev_end is None:
            covered += end - start
            prev_end = end
        else:
            covered += max(end - max(start, prev_end), 0.0)
            prev_end = max(prev_end, end)
    return covered


def _host_sync_points(events: List[dict]) -> Dict[int, float]:
    """seq -> host ts for every ``madsim.sync`` instant in a host doc."""
    out: Dict[int, float] = {}
    for e in events:
        if e.get("name") == SYNC_NAME and "seq" in (e.get("args") or {}):
            out[int(e["args"]["seq"])] = float(e.get("ts", 0.0))
    return out


def _device_sync_points(events: List[dict]) -> Dict[int, float]:
    """seq -> device ts for every ``madsim.sync:<seq>`` slice."""
    out: Dict[int, float] = {}
    prefix = SYNC_NAME + ":"
    for e in events:
        name = e.get("name") or ""
        if name.startswith(prefix):
            try:
                out[int(name[len(prefix):])] = float(e.get("ts", 0.0))
            except ValueError:
                continue
    return out


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def merge_plane(
    host_doc: dict,
    device_events: Optional[List[dict]] = None,
    virtual_doc: Optional[dict] = None,
    meta: Optional[Dict[str, Any]] = None,
) -> dict:
    """One Perfetto session from up to three clock planes.

    `host_doc` — a PerfRecorder `chrome_trace()` or a fleet
    `timeline_doc` (pids 0..N, host µs). Kept verbatim. `device_events`
    — raw events from `load_device_events` (device µs). Shifted onto
    the host clock by the median host−device delta over matched
    ``madsim.sync`` seqs; with no matched markers, anchored so the
    earliest device event lands at the earliest host slice (a capture
    taken around the host window — approximate but honest, and flagged
    in the summary as ``sync_points: 0``). `virtual_doc` — a
    `trace_export.trace_event_dict` document; its tracks are renamed
    onto their own pid and its timestamps are NOT touched: virtual
    microseconds are simulated time and converting them would be a lie.

    The ``madsim_xprof_summary`` key carries the attribution fraction
    the CI prof-smoke gates on: union(host slices ∪ shifted device
    ``madsim.*`` phase slices, clipped to the host window) / host wall.
    """
    host_events = [e for e in host_doc.get("traceEvents", [])]
    events: List[dict] = list(host_events)
    host_pids = {e.get("pid", 0) for e in host_events}
    next_pid = (max(host_pids) + 1) if host_pids else 1

    host_slices = [
        (float(e["ts"]), float(e["ts"]) + float(e["dur"]))
        for e in host_events
        if e.get("ph") == "X" and e.get("dur") is not None
    ]
    if host_slices:
        host_lo = min(s for s, _ in host_slices)
        host_hi = max(e for _, e in host_slices)
    else:
        host_lo, host_hi = 0.0, 0.0
    summary = host_doc.get("madsim_perf_summary") or {}
    wall_us = float(summary.get("wall_s", 0.0)) * 1e6
    if wall_us <= 0.0:
        wall_us = max(host_hi - host_lo, 0.0)

    offset_us = 0.0
    sync_points = 0
    phase_ivals: List[Tuple[float, float]] = []
    device_present = False
    if device_events:
        device_present = True
        h_sync = _host_sync_points(host_events)
        d_sync = _device_sync_points(device_events)
        matched = sorted(set(h_sync) & set(d_sync))
        sync_points = len(matched)
        if matched:
            offset_us = _median([h_sync[s] - d_sync[s] for s in matched])
        else:
            d_ts = [
                float(e["ts"]) for e in device_events
                if e.get("ph") == "X" and "ts" in e
            ]
            offset_us = (host_lo - min(d_ts)) if d_ts else 0.0
        pid_map: Dict[Any, int] = {}
        for e in device_events:
            e = dict(e)
            pid = e.get("pid", 0)
            if pid not in pid_map:
                pid_map[pid] = next_pid
                next_pid += 1
                events.append({
                    "ph": "M", "pid": pid_map[pid], "name": "process_name",
                    "args": {"name": "device (jax profiler, host-aligned)"},
                })
            e["pid"] = pid_map[pid]
            if "ts" in e and e.get("ph") != "M":
                e["ts"] = round(float(e["ts"]) + offset_us, 3)
            events.append(e)
            name = e.get("name") or ""
            if (
                e.get("ph") == "X"
                and e.get("dur") is not None
                and name.startswith(PHASE_PREFIX)
            ):
                s = float(e["ts"])
                phase_ivals.append(
                    (max(s, host_lo), min(s + float(e["dur"]), host_hi))
                )

    virtual_present = False
    if virtual_doc:
        v_events = virtual_doc.get("traceEvents", [])
        if v_events:
            virtual_present = True
            v_pid_map: Dict[Any, int] = {}
            for e in v_events:
                e = dict(e)
                pid = e.get("pid", 0)
                if pid not in v_pid_map:
                    v_pid_map[pid] = next_pid
                    next_pid += 1
                e["pid"] = v_pid_map[pid]
                if e.get("ph") == "M" and e.get("name") == "process_name":
                    base = (e.get("args") or {}).get("name", "virtual")
                    e["args"] = {
                        "name": f"{base} [VIRTUAL µs — simulated time]"
                    }
                # ts untouched: virtual microseconds stay virtual
                events.append(e)

    attributed = _union_us(
        [(max(s, host_lo), min(e, host_hi)) for s, e in host_slices]
        + phase_ivals
    )
    xprof_summary = {
        "attribution": round(attributed / wall_us, 4) if wall_us else 0.0,
        "host_wall_us": round(wall_us, 1),
        "clock_offset_us": round(offset_us, 3),
        "sync_points": sync_points,
        "tracks": {
            "host": bool(host_events),
            "device": device_present,
            "virtual": virtual_present,
        },
    }
    out = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "madsim_xprof_summary": xprof_summary,
    }
    for k in ("madsim_perf_summary", "madsim_perf_meta",
              "madsim_fleet_timeline_summary"):
        if k in host_doc:
            out[k] = host_doc[k]
    if meta:
        out["madsim_xprof_meta"] = dict(meta)
    return out


def write_doc(doc: dict, path: str) -> int:
    """Write a merged plane (gzipped when the path says so); returns
    the event count."""
    data = json.dumps(doc)
    if path.endswith(".gz"):
        with gzip.open(path, "wt") as f:
            f.write(data + "\n")
    else:
        with open(path, "w") as f:
            f.write(data + "\n")
    return len(doc.get("traceEvents", []))
