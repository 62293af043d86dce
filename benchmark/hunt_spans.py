"""The program's span tree of each hunt, for the hunt cells' readers.

A traced run records the program's own spans (`perf/recorder.py`): since
PR 25 a tree from `warmup_dispatch` and `run_stream` down to one
`replay_run`, each span with its `id`, its `parent` and its `args`. A
hunt's spans are those inside its record's (t0, t1); a reader sums what
it reads per hunt and reports the median over the window's hunts, as
`shrink_s` and `hunt_ring_drain_share` do. The benchmark's own spans
(`campaign`, `shrink`, `verify`: put around the program from outside)
sit in the same recorder and are left out.
"""

from __future__ import annotations

import statistics

from benchmark import trace_reduce

BENCHMARK_SPANS = ("campaign", "shrink", "verify")


def program_spans(obs) -> list:
    """The recorder's closed spans as dicts with `t0`/`t1` on
    perf_counter added; [] in an untraced session or where the program's
    spans carry no `parent` (a program from before the tree)."""
    rec = obs.session.recorder
    if rec is None:
        return []
    closed = [s for s in rec.spans if s["dur"] is not None]
    if not closed or any("parent" not in s for s in closed):
        return []
    return [dict(s, t0=t0, t1=t1)
            for s, (_name, t0, t1) in zip(closed, obs.session.spans())
            if s["name"] not in BENCHMARK_SPANS]


def self_s(span: dict, spans: list) -> float:
    """A span's duration minus what its child spans cover."""
    kids = trace_reduce.union(
        (s["t0"], s["t1"]) for s in spans if s["parent"] == span["id"])
    return (span["t1"] - span["t0"]) - trace_reduce.total(kids)


def under(span: dict, name: str, by_id: dict) -> bool:
    """Whether a span of that name encloses `span`."""
    while span["parent"] >= 0 and span["parent"] in by_id:
        span = by_id[span["parent"]]
        if span["name"] == name:
            return True
    return False


def per_hunt(obs, measure):
    """Median over the window's hunts of `measure(spans of the hunt,
    hunt wall seconds)`; None where no hunt has any program span, or
    `measure` finds nothing to read (returns None) in every hunt."""
    spans = program_spans(obs)
    values = []
    for r in obs.records:
        inside = [s for s in spans if r["t0"] <= s["t0"] and s["t1"] <= r["t1"]]
        value = measure(inside, r["t1"] - r["t0"]) if inside else None
        if value is not None:
            values.append(value)
    return statistics.median(values) if values else None


def named_total(names: tuple):
    """A `measure` for `per_hunt`: seconds in the spans of these names
    (None where the hunt has none: the program lacks them)."""
    def measure(spans, _wall):
        found = [s["t1"] - s["t0"] for s in spans if s["name"] in names]
        return sum(found) if found else None
    return measure
