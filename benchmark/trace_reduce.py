"""From a profiler trace to device busy/idle time, op times and named gaps.

The reduction is the benchmark's own code so that every PR computes the
same number the same way. It works on a plain form of the trace,

    {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [[name, start_ns, dur_ns, kind], ...]}]}]}

which `load_xplane` makes from the profiler's `.xplane.pb` with nothing
but jax, and which tests/benchmark_checks keeps a small recording of.

Definitions (on-chip-measurement guide, section 4):

* busy: the union of the intervals in which an operation runs on the
  device (the op line of each device plane), clipped to the window;
* window: the union of the benchmark's own `bench:campaign` host
  annotations (one per traced campaign), on the trace's clock;
* idle share: 1 - busy / window, averaged over the devices used;
* an op's time: its SELF time — its interval minus the ops nested in it
  (a `while` op spans its whole loop and would otherwise count twice);
* a gap is named by the innermost `bench:<span>` host annotation lying
  over it (the program's dispatch / counters_poll / ring_drain / ...
  spans and the benchmark's run_stream / shrink / verify spans).
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE_RE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OP_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench:"
WINDOW_SPAN = "campaign"
#: an op's kind is its HLO opcode; these patterns are matched on it
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast)"
)
#: ops that only hold other ops (their self time is loop/branch overhead)
CONTROL_RE = re.compile(r"^(while|conditional|call)$")
MOSAIC = "mosaic"  # the kind given to a Pallas kernel's custom call
OPCODE_RE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_identity(text: str) -> tuple:
    """(short name, kind) of a device op. The TPU trace names an op by
    its whole HLO instruction, `%body.11 = (s32[8192,1]{...}, ...)
    custom-call(...), custom_call_target="tpu_custom_call", ...`: the
    name is what stands before ` = `, the kind the opcode after the
    result shape (shapes hold no lower-case word before a bracket), and
    a custom call whose target is Mosaic's is a Pallas kernel."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    m = OPCODE_RE.search(rest)
    kind = m.group(1) if m else ""
    if kind == "custom-call" and 'custom_call_target="tpu_custom_call"' in rest:
        kind = MOSAIC
    return head.lstrip("%"), kind


def load_xplane(path: str) -> dict:
    """The profiler's xplane file in the plain form above. Host planes
    keep only the benchmark's annotations (they are all the reduction
    reads, and a host plane can hold millions of other events)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE_RE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name, kind = ev.name, ""
                if device:
                    name, kind = op_identity(name)
                elif not name.startswith(ANNOTATION_PREFIX):
                    continue
                events.append([name, float(ev.start_ns), float(ev.duration_ns), kind])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- intervals -----------------------------------------------------------------


def union(intervals) -> list:
    """Sorted, disjoint [(start, end)] covering the same points."""
    out: list = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list:
    """The part of sorted disjoint `xs` not covered by sorted disjoint `ys`."""
    out, j = [], 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def innermost(spans) -> list:
    """[(start, end, name)] of nested spans on one timeline, flattened
    to disjoint segments each carrying the name of the innermost span
    over it. A span's self time is the total of its segments."""
    segs: list = []
    stack: list = []
    cursor = 0.0

    def emit(a, b, name):
        if b > a:
            segs.append((a, b, name))

    for s in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= s[0]:
            top = stack.pop()
            emit(cursor, top[1], top[2])
            cursor = max(cursor, top[1])
        if stack:
            emit(cursor, s[0], stack[-1][2])
        cursor = s[0]
        stack.append(s)
    while stack:
        top = stack.pop()
        emit(cursor, top[1], top[2])
        cursor = max(cursor, top[1])
    return segs


# -- the reduction -------------------------------------------------------------


def host_annotations(trace: dict) -> list:
    """[(start, end, name)] of the benchmark's annotations, prefix cut."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE_RE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur, _cat in line["events"]:
                if name.startswith(ANNOTATION_PREFIX):
                    out.append((start, start + dur, name[len(ANNOTATION_PREFIX):]))
    return out


def device_ops(trace: dict) -> dict:
    """{device ordinal: [(start, end, name, category)]} from each device
    plane's op line (every line of the plane where it has none by that
    name, so a trace laid out otherwise still counts as busy)."""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE_RE.match(plane["name"])
        if not m:
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == OP_LINE] \
            or plane["lines"]
        out[int(m.group(2))] = [
            (start, start + dur, name, cat)
            for ln in lines for name, start, dur, cat in ln["events"]
        ]
    return out


def idle_share(reduced: dict | None):
    """Idle share (%) of a reduction's window, None where there is no
    device time to speak of (no trace, no device plane, no window)."""
    if not reduced or not reduced["window_s"] or not reduced["busy_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce(trace: dict, top: int = 10, min_gap_ns: float = 20_000.0) -> dict:
    """Everything the per-layer readers and `breakdown` take from a trace.
    Times are seconds; the per-device numbers are averaged over devices."""
    notes = host_annotations(trace)
    windows = union((a, b) for a, b, name in notes if name == WINDOW_SPAN)
    ops_by_device = device_ops(trace)
    if not windows:
        # no annotation reached the trace: fall back to the span of the
        # device's own activity, and say so
        every = [(a, b) for ops in ops_by_device.values() for a, b, *_ in ops]
        windows = [(min(a for a, _ in every), max(b for _, b in every))] if every else []
    window_ns = total(windows)
    n_dev = len(ops_by_device)
    out = {
        "devices": n_dev, "window_s": window_ns / 1e9,
        "window_from_annotations": any(n == WINDOW_SPAN for *_x, n in notes),
        "busy_s": 0.0, "self_s": {}, "mosaic_s": 0.0, "mosaic_calls": {},
        "collective_s": 0.0, "collective_exposed_s": 0.0, "op_events": 0,
        "gaps_s": {}, "device_ops": [], "idle_gaps": [],
    }
    if not n_dev or not window_ns:
        return out
    host_segs = innermost(notes)  # disjoint, in time order
    seg_ends = [sb for _sa, sb, _n in host_segs]
    for ops in ops_by_device.values():
        busy = intersect(union((a, b) for a, b, *_ in ops), windows)
        out["busy_s"] += total(busy) / 1e9 / n_dev
        out["op_events"] += len(ops)
        cats = {name: cat for _a, _b, name, cat in ops}
        segs = innermost([(a, b, name) for a, b, name, _cat in ops])
        compute = []
        for a, b, name in segs:
            for a2, b2 in intersect([(a, b)], windows):
                out["self_s"][name] = out["self_s"].get(name, 0.0) + (b2 - a2) / 1e9 / n_dev
                if cats[name] == MOSAIC:
                    out["mosaic_s"] += (b2 - a2) / 1e9 / n_dev
                elif COLLECTIVE_RE.match(cats[name]):
                    out["collective_s"] += (b2 - a2) / 1e9 / n_dev
                elif not CONTROL_RE.match(cats[name]):
                    compute.append((a2, b2))
        for a, b, name, cat in ops:
            if cat == MOSAIC and intersect([(a, b)], windows):
                out["mosaic_calls"][name] = out["mosaic_calls"].get(name, 0) + 1
        coll = intersect(
            union((a, b) for a, b, _n, cat in ops if COLLECTIVE_RE.match(cat)),
            windows,
        )
        out["collective_exposed_s"] += total(subtract(coll, union(compute))) / 1e9 / n_dev
        for a, b in subtract(windows, busy):
            if b - a < min_gap_ns:
                out["gaps_s"]["short_gaps"] = out["gaps_s"].get("short_gaps", 0.0) \
                    + (b - a) / 1e9 / n_dev
                continue
            named = 0.0
            k = bisect.bisect_right(seg_ends, a)
            while k < len(host_segs) and host_segs[k][0] < b:
                sa, sb, name = host_segs[k]
                k += 1
                lo, hi = max(a, sa), min(b, sb)
                if hi > lo:
                    out["gaps_s"][name] = out["gaps_s"].get(name, 0.0) + (hi - lo) / 1e9 / n_dev
                    named += hi - lo
            if b - a > named:
                out["gaps_s"]["unattributed"] = out["gaps_s"].get("unattributed", 0.0) \
                    + (b - a - named) / 1e9 / n_dev

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    kinds = {name: cat for ops in ops_by_device.values() for _a, _b, name, cat in ops}
    out["device_ops"] = [[f"{name} ({kinds[name]})" if kinds[name] else name, sec]
                         for name, sec in ranked(out["self_s"])]
    out["idle_gaps"] = ranked(out["gaps_s"])
    return out
