"""Host-timeline tracing — where does the *wall clock* go?

`engine/trace_export.py` renders a seed's VIRTUAL-time schedule; this
module renders the complementary view: what the HOST was doing, in real
microseconds, while the engine streamed — compiling, dispatching device
work, blocked on a counters poll, draining result rings, writing
checkpoints/stats. The ROADMAP's "win back the observability tax" item
is unanswerable without it: `stats["host_syncs"]` says *how many*
blocking syncs happened, the timeline says *how long each one took and
what sat between them*.

A `PerfRecorder` is a context manager that publishes itself through a
contextvar; instrumented code calls the module-level `maybe_span(name)`
which is a no-op (a shared null context) when no recorder is active —
the engine hot loop pays one contextvar read per instrumented call,
nothing else. Spans nest naturally (the recorder keeps a stack) and the
export is Chrome `trace_event` JSON: one process, one "host" thread
row, `ph: "X"` slices whose nesting the Perfetto UI draws by
containment.

Span taxonomy — one tree from a CLI command down to one replay. Every
span records its own ``id``, the id of the span that encloses it
(``parent``, -1 at the top) and the recorder's ``trace_id``, so a reader computes self time
as duration minus children without guessing from ``depth``:

=====================  =====================================================
``engine_build``       `_build_engine`: imports, model init, first backend
                       touch
``warmup_dispatch``    `_stream_batches`, before its timed loop: the
                       stream's programs made ready
                       (`Engine.prepare_stream`), nothing dispatched.
                       Args: ``ready`` (the engine held them already:
                       no child span), ``programs`` (how many were
                       made: each a ``compile`` child)
``run_stream``         one `Engine.run_stream` call (args: n_seeds, batch)
``compile``            a program being made; arg ``program``. Executor:
                       ``init_carry`` / ``supersegment`` / ``segment`` /
                       ``reset_rings`` — trace + lower +
                       compile-or-read, no dispatch (the executable is
                       kept and every dispatch calls it). Replay:
                       ``replay.run`` / ``replay.step`` — the first
                       invocation of the jitted fn, its dispatch
                       included. Near-zero on a warm persistent cache
``init``               an `init_carry` dispatch
``dispatch``           an async supersegment/segment dispatch (returns as
                       soon as the work is enqueued — short by design)
``counters_poll``      the blocking device->host counters read (where a
                       device-bound run spends its wall time)
``ring_drain``         failing/abandoned ring harvest + reset
``harvest``            final flight-recorder / coverage-map transfer
``checkpoint_write`` / ``stats_emit`` — host persistence riding a hunt
                       (children of ``batch_fold``)
``cli_parse``          `main`: the parser's build, `parse_args`, logging
                       and compile-cache priming, up to the subcommand.
                       Arg ``built``: the sub-parsers built, 1 (the
                       command the argv names: counter
                       ``cli.parser_one``) or every command's (no argv,
                       ``-h``, an unknown command: ``cli.parser_full``).
                       Only where a recorder is active before `main` is
                       entered (the benchmark, an in-process caller): a
                       ``--perf-timeline`` run opens its recorder after
                       the parse and has no ``cli_parse``
``stream_setup``       `_stream_batches`, entry to ``warmup_dispatch``:
                       emitter, plateau detector, the mesh
                       (`_stream_kwargs`), checkpoint restore
``batch_fold``         `_stream_batches`, one batch's `run_stream` return
                       to the next batch: aggregate, coverage OR,
                       heartbeat, stats record, checkpoint, plateau and
                       stop checks (arg ``batch``)
``stream_summary``     `_stream_batches` after its loop: coverage dict,
                       fault attribution, summary record
``explore_report``     `cmd_explore`: the prints after the stream
``stream_prepare``     inside ``run_stream``, entry to ``init``: the
                       quartet, the held executables, the seed vector
                       (sharded on a mesh), the seed budget placed
``stream_result``      inside ``run_stream``, after the last poll and
                       drain: the ``harvest`` transfers, the recorder's
                       and coverage's host forms, device memory, the
                       result, the stream's buffers let go
``hunt_report``        `cmd_hunt`: the stream's return to the first shrink
                       (prints, coverage file, corpus load)
``shrink_search``      one `engine.shrink` call, its ``shrink_candidate``
                       children and the glue between them (arg ``seed``)
``shrink_candidate``   one replay attempt of `engine.shrink`, the
                       candidate `Engine`'s construction included (args:
                       ``stage`` base/faults/loss/kinds/horizon,
                       ``accepted``)
``replay``             one CPU replay (args: seed, traced, steps)
``replay_run``         inside ``replay``: dispatch to result on the host
``corpus_record``      `cmd_hunt`: the digest-trail replay and the filing
``corpus_load`` / ``corpus_save`` — `regress` / `audit`: the corpus read,
                       and its write where entries changed
``regress_entry`` / ``audit_entry`` — `regress` / `audit`, per corpus entry
``gc``                 one collection of Python's garbage collector run
                       in the recorder's context, under whatever span is
                       open (arg ``generation``; noted ``collected``)
=====================  =====================================================

Counters (`maybe_count`): ``compile.trace`` / ``compile.lower`` /
``compile.backend`` / ``compile.cache_miss`` / ``compile.cache_hit`` —
one per jax compile-stage event while a recorder is active
(`perf/compile_log.py`, which also keeps them by program).
``stream.programs_ready_hit`` / ``stream.programs_ready_miss`` — one per
`Engine.prepare_stream` call: the engine held the stream's programs
(nothing traced, compiled or read) or had to make some.
``gc.collections`` / ``gc.collections.gen2`` — one per collection (one
per full collection) the recorder's ``gc`` hook saw, spanned or not.

`PerfRecorder(annotate=True)` also writes every span into a running
`jax.profiler` capture as a ``madsim.<name>`` TraceAnnotation, on the
clock of the device ops: `--perf-timeline` with `--xla-profile`, `prof`
and the fleet worker's capture turn it on.

The summary classifies a run: mostly ``compile`` => compile-bound (warm
the cache); mostly ``counters_poll``/``ring_drain`` => device-bound
(the host is waiting — optimize the kernel); large ``dispatch_gap``
(wall time between instrumented operations: the host-side Python loop)
=> dispatch-gap-bound (the 1-core host is the bottleneck).
"""

from __future__ import annotations

# madsim: allow-file(D001) — this module's *contract* is reading the
# host wall clock: it measures real elapsed time of host operations
# (compile, dispatch, poll). Nothing here can reach simulation state;
# virtual time stays in the engine.
import contextlib
import contextvars
import gc
import itertools
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

_CURRENT: contextvars.ContextVar[Optional["PerfRecorder"]] = contextvars.ContextVar(
    "madsim_tpu_perf_recorder", default=None
)

# default trace ids: process, wall clock, ordinal — unique without
# drawing OS entropy (lint D002)
_TRACE_SEQ = itertools.count()

# one shared, re-entered null context for the recorder-off path: no
# allocation per call in the engine hot loop
_NULL_CTX = contextlib.nullcontext()


def current_recorder() -> Optional["PerfRecorder"]:
    """The PerfRecorder active in this context, or None."""
    return _CURRENT.get()


def maybe_span(name: str, **args: Any):
    """`with maybe_span("dispatch"): ...` — a real span when a recorder
    is active, a shared no-op context otherwise (one contextvar read)."""
    rec = _CURRENT.get()
    if rec is None:
        return _NULL_CTX
    return rec.span(name, **args)


def _annotation(name: str):
    """The span's twin in a running `jax.profiler` capture."""
    import jax

    return jax.profiler.TraceAnnotation("madsim." + name)


def maybe_note(**args: Any) -> None:
    """Add args to the innermost open span of the active recorder (what
    a span learns only at its end: `steps`, `accepted`); no-op otherwise."""
    rec = _CURRENT.get()
    if rec is not None:
        rec.note(**args)


def maybe_count(name: str, n: int = 1) -> None:
    """Bump a recorder counter when one is active; no-op otherwise."""
    rec = _CURRENT.get()
    if rec is not None:
        rec.count(name, n)


class PerfRecorder:
    """Collects host spans + counters; exports a Chrome-trace timeline.

    `clock` is injectable for tests (defaults to `time.perf_counter`).
    All recorded times are MICROSECONDS since recorder entry (Chrome
    trace_event's native unit). Not thread-safe by design — the engine
    host loop is single-threaded on purpose.
    """

    def __init__(
        self,
        meta: Optional[Dict[str, Any]] = None,
        clock: Callable[[], float] = time.perf_counter,
        annotate: bool = False,
        trace_id: Optional[str] = None,
    ):
        self.meta = dict(meta or {})
        self._clock = clock
        # one id per CLI command / fleet job: spans of one request share it
        self.trace_id = trace_id or (
            f"{os.getpid():x}-{time.time_ns():x}-{next(_TRACE_SEQ)}")
        self.annotate = annotate
        # closed spans and instants, in closing order: {"name", "ts",
        # "dur", "depth", "id", "parent", "trace_id", "args"}
        self.spans: List[dict] = []
        self._next_id = 0
        self._open: List[dict] = []  # in-flight spans (crash-flush path)
        self.counters: Dict[str, int] = {}
        self._t0: Optional[float] = None
        self._t_end: Optional[float] = None
        self._token = None
        # the `gc` hook: the open collection's span, and whether the
        # recorder is inside its own bookkeeping (a collection that
        # starts there is counted, not spanned)
        self._gc_span = None
        self._busy = False

    # -- lifecycle ----------------------------------------------------------

    def __enter__(self) -> "PerfRecorder":
        if self._t0 is not None:
            raise RuntimeError("PerfRecorder is not re-enterable")
        self._t0 = self._clock()
        self._token = _CURRENT.set(self)
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)
        self._t_end = self._clock()
        _CURRENT.reset(self._token)
        self._token = None

    def _on_gc(self, phase: str, info: dict) -> None:
        """`gc.callbacks` hook while the recorder is entered: a
        collection run in this recorder's context (not a nested or an
        outer recorder's, not another thread's) is a ``gc`` span through
        `span`, so an annotating recorder writes it into the profiler
        trace like any other. The collector runs no other code of ours
        between "start" and "stop", so the span nests LIFO under
        whatever span is open."""
        if phase == "start":
            if _CURRENT.get() is not self:
                return
            self.count("gc.collections")
            if info["generation"] == 2:
                self.count("gc.collections.gen2")
            if self._busy:  # inside `span`'s own stack and id keeping
                return
            self._gc_span = self.span("gc", generation=info["generation"])
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            span, self._gc_span = self._gc_span, None
            self.note(collected=info["collected"])
            span.__exit__(None, None, None)

    def _now_us(self) -> float:
        if self._t0 is None:
            raise RuntimeError("PerfRecorder used outside its context")
        return (self._clock() - self._t0) * 1e6

    # -- recording ----------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, **args: Any):
        """Record one host span; spans nest. A span takes its ``id`` when
        it opens and names the enclosing span's id as ``parent`` (-1 at
        the top); it joins `spans` when it closes. Code inside the span
        adds to its ``args`` with `note`."""
        self._busy = True
        rec = self._stamp(name, args)
        self._open.append(rec)
        self._busy = False
        note = _annotation(name) if self.annotate else _NULL_CTX
        try:
            with note:
                yield self
        finally:
            self._busy = True
            self._open.pop()  # spans unwind LIFO, exceptions included
            rec["dur"] = max(self._now_us() - rec["ts"], 0.0)
            self.spans.append(rec)
            self._busy = False

    def _stamp(self, name: str, args: dict) -> dict:
        self._next_id += 1
        return {
            "name": name, "ts": self._now_us(), "dur": None,
            "depth": len(self._open), "id": self._next_id - 1,
            "parent": self._open[-1]["id"] if self._open else -1,
            "trace_id": self.trace_id, "args": args,
        }

    def note(self, **args: Any) -> None:
        """Add args to the innermost open span (no-op with none open)."""
        if self._open:
            self._open[-1]["args"].update(args)

    def instant(self, name: str, **args: Any) -> None:
        self._busy = True
        self.spans.append(self._stamp(name, args))
        self._busy = False

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def open_spans(self) -> List[dict]:
        """Still-open spans materialized as of NOW (dur = elapsed so
        far, args tagged ``partial``) — the crash/SIGTERM flush path:
        a worker killed mid-unit dumps these so its `fleet timeline`
        shows the span it died inside instead of nothing. Does not
        mutate recorder state; the spans keep accruing if the process
        survives."""
        if self._t0 is None or not self._open:
            return []
        now = (self._t_end - self._t0) * 1e6 if self._t_end is not None \
            else self._now_us()
        return [
            dict(s, dur=max(now - s["ts"], 0.0),
                 args=dict(s["args"], partial=True))
            for s in self._open
        ]

    def absorb(self, other: "PerfRecorder", ts_offset_us: float = 0.0) -> int:
        """Replay another recorder's spans/counters into this one,
        shifted by `ts_offset_us` (this recorder's clock at the moment
        the other one started). The fleet worker uses this to nest a
        per-unit recorder — whose spans also go to the store's span
        dump for cross-process correlation — under an outer
        `--perf-timeline` recorder without double-instrumenting.
        Returns the number of spans absorbed."""
        base = self._next_id  # keeps ids unique, parents pointing right
        for s in other.spans:
            self.spans.append(dict(
                s, ts=s["ts"] + ts_offset_us, id=s["id"] + base,
                parent=s["parent"] + base if s["parent"] >= 0 else -1,
                args=dict(s["args"]),
            ))
        self._next_id += other._next_id
        for name, n in other.counters.items():
            self.count(name, n)
        return len(other.spans)

    # -- analysis -----------------------------------------------------------

    @property
    def wall_us(self) -> float:
        """Recorder-entry to recorder-exit (or to now while active)."""
        if self._t0 is None:
            return 0.0
        end = self._t_end if self._t_end is not None else self._clock()
        return (end - self._t0) * 1e6

    def _top(self) -> List[dict]:
        """The closed top-level spans, by start time."""
        return sorted(
            (s for s in self.spans if s["depth"] == 0 and s["dur"] is not None),
            key=lambda s: s["ts"],
        )

    @staticmethod
    def _union_us(spans: List[dict]) -> float:
        """Merged-interval length (spans pre-sorted by ts)."""
        covered = 0.0
        prev_end = None
        for s in spans:
            start, end = s["ts"], s["ts"] + s["dur"]
            if prev_end is None:
                covered += end - start
            else:
                covered += max(end - max(start, prev_end), 0.0)
            prev_end = end if prev_end is None else max(prev_end, end)
        return covered

    def summary(self) -> dict:
        """Where the wall went, at two grains.

        `spans` — per-name totals over ALL spans, any nesting depth
        (the taxonomy names never nest within themselves, so each
        name's total is honest; a parent like `run_stream` naturally
        contains its children's time — percentages are per-name, not a
        partition). `span_coverage` — merged union of outermost spans
        over the recorder wall ("how much wall is explained at all").
        `dispatch_gap_s` — wall BETWEEN outermost spans: uninstrumented
        host Python. `device_wait_s` — time INSIDE outermost spans not
        covered by any inner span: for a streaming run this is the
        device executing (on a host that shares cores with the XLA
        compute threads, that time starves the host thread between
        inner spans rather than accruing to the blocking poll — the
        1-core reference box ALWAYS looks like this)."""
        top = self._top()
        by_name: Dict[str, dict] = {}
        for s in sorted(self.spans, key=lambda s: s["ts"]):
            if s["dur"] is None:
                continue
            d = by_name.setdefault(s["name"], {"total_us": 0.0, "count": 0})
            d["total_us"] += s["dur"]
            d["count"] += 1
        top_union = self._union_us(top)
        # device_wait is scoped to the streaming spans: interior of a
        # `run_stream` span (at any depth) that none of its child spans
        # covers is the
        # device executing (or starving the host thread on a shared-core
        # box); uncovered interior of anything else is just that span's
        # own host work
        children: Dict[int, List[dict]] = {}
        for s in self.spans:
            if s["dur"] is not None and s["parent"] >= 0:
                children.setdefault(s["parent"], []).append(s)
        device_wait = sum(
            max(r["dur"] - self._union_us(
                sorted(children.get(r["id"], ()), key=lambda s: s["ts"])), 0.0)
            for r in self.spans
            if r["name"] == "run_stream" and r["dur"] is not None
        )
        gap_us = 0.0
        prev_end = None
        for s in top:
            if prev_end is not None and s["ts"] > prev_end:
                gap_us += s["ts"] - prev_end
            prev_end = s["ts"] + s["dur"] if prev_end is None else max(
                prev_end, s["ts"] + s["dur"]
            )
        wall = self.wall_us
        spans_out = {
            name: {
                "total_s": round(d["total_us"] / 1e6, 6),
                "count": d["count"],
                "pct_of_wall": round(100.0 * d["total_us"] / wall, 2) if wall else 0.0,
            }
            for name, d in sorted(by_name.items())
        }
        return {
            "wall_s": round(wall / 1e6, 6),
            "spans": spans_out,
            "span_coverage": round(top_union / wall, 4) if wall else 0.0,
            "dispatch_gap_s": round(gap_us / 1e6, 6),
            "dispatch_gap_pct": round(100.0 * gap_us / wall, 2) if wall else 0.0,
            "device_wait_s": round(device_wait / 1e6, 6),
            "device_wait_pct": (
                round(100.0 * device_wait / wall, 2) if wall else 0.0
            ),
            "counters": dict(sorted(self.counters.items())),
        }

    def verdict(self) -> str:
        """One-line answer to "what is this run bound on?": compile vs
        device (blocked polls/drains/harvest + device_wait) vs
        dispatch-gap (everything else: host-side Python — the loop,
        engine build, emitter/checkpoint writes, uninstrumented gaps)."""
        s = self.summary()
        compile_s = s["spans"].get("compile", {}).get("total_s", 0.0)
        device_s = (
            s["spans"].get("counters_poll", {}).get("total_s", 0.0)
            + s["spans"].get("ring_drain", {}).get("total_s", 0.0)
            + s["spans"].get("harvest", {}).get("total_s", 0.0)
            + s["device_wait_s"]
        )
        buckets = {
            "compile-bound": compile_s,
            "device-bound": device_s,
            "dispatch-gap-bound": max(s["wall_s"] - compile_s - device_s, 0.0),
        }
        bound = max(buckets, key=lambda k: buckets[k])
        parts = ", ".join(f"{k.split('-bound')[0]} {v:.2f}s" for k, v in buckets.items())
        line = f"{bound} ({parts} of {s['wall_s']:.2f}s wall)"
        if bound == "compile-bound" and self._clock is time.perf_counter:
            # which stage of which program (perf/compile_log.py keeps
            # jax's compile-stage events on the same clock)
            from . import compile_log

            log = compile_log.current()
            worst = log and compile_log.slowest(log.snapshot(self._t0, self._t_end))
            if worst:
                line += f"; slowest: {worst}"
        return line

    # -- export -------------------------------------------------------------

    def chrome_trace(self) -> dict:
        """Chrome/Perfetto trace_event JSON (dict): pid 0, one "host"
        thread (tid 0), `ph: "X"` slices (nesting drawn by containment)
        + `ph: "i"` instants; `madsim_perf_summary` rides as a top-level
        key (trace_event readers ignore unknown top-level keys)."""
        events: List[dict] = [
            {
                "ph": "M", "pid": 0, "name": "process_name",
                "args": {"name": "madsim_tpu host"},
            },
            {
                "ph": "M", "pid": 0, "tid": 0, "name": "thread_name",
                "args": {"name": "host"},
            },
        ]
        for s in sorted(self.spans, key=lambda s: (s["ts"], -(s["dur"] or 0))):
            if s["dur"] is None:
                events.append(
                    {
                        "ph": "i", "s": "t", "pid": 0, "tid": 0,
                        "ts": round(s["ts"], 3), "name": s["name"],
                        "args": dict(s["args"]),
                    }
                )
            else:
                events.append(
                    {
                        "ph": "X", "pid": 0, "tid": 0,
                        "ts": round(s["ts"], 3),
                        "dur": round(max(s["dur"], 0.01), 3),
                        "name": s["name"],
                        "args": dict(s["args"]),
                    }
                )
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "madsim_perf_summary": self.summary(),
            "madsim_perf_meta": dict(self.meta),
        }

    def write(self, path: str) -> int:
        """Write the Perfetto/Chrome timeline; returns span+instant
        count (excluding metadata records)."""
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")
        return len(doc["traceEvents"]) - 2
