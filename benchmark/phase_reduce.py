"""From a profiler trace to device self time per `madsim.*` phase.

The program wraps every cost-model row of a step, and every segment-level
fold, in `jax.named_scope("madsim.<phase>")` (`madsim_tpu/perf/xprof.py`,
`engine/core.py`). XLA carries the name stack of the op a device
instruction came from as that instruction's `op_name`, and the TPU
profiler files it as the `tf_op` stat of the event's metadata, e.g.

    jit(supersegment)/while/body/.../madsim.step/while/body/vmap(madsim.step.rng)/xor

An op's phase is the INNERMOST `madsim.*` component of that string.
`jax.profiler.ProfileData` shows an event's own stats only, not its
metadata's, so `op_scopes` reads the `.xplane.pb` itself: a few lines of
protobuf wire format, no dependency. Everything else — an op's self
time, busy, the window — is `trace_reduce`'s, by import, so the shares
here are shares of the busy time `device_idle_share` is computed from.

The plain form is `trace_reduce`'s plus one key,

    {"planes": [...], "scopes": {"fusion.597": "jit(supersegment)/.../madsim.step.rng/xor"}}

A fusion carries ONE instruction's metadata, so a share is an
attribution, not a partition to the microsecond; `device_unscoped_share`
says how much busy time no scope claims. The scopes do not move the
compile cache's key, so a cache that an older program filled serves
executables without them (and a newer program's small, kernel-less
programs — `init_carry`, `reset_rings` — serve an older program with
them). So the shares are read only where the program that does the work
carries them: where no op in the window lies under a `madsim.step` scope,
every reader returns None — the metric is left out, never read as 0%
or 100%.
"""

from __future__ import annotations

import glob
import os
import re

from benchmark import trace_reduce

SCOPE_RE = re.compile(r"madsim\.([A-Za-z0-9_.\-]+)")
OP_NAME_STAT = "tf_op"

#: metric -> the phases whose self time it sums (shares of busy, %)
STEP_METRICS = {
    "step_pop_share": ("step.pop",),
    "step_rng_share": ("step.rng",),
    "step_handler_share": ("step.handlers", "step.provenance"),
    "step_push_share": ("step.outbox", "step.timers"),
    "step_recorder_share": ("step.recorder",),
    "step_coverage_share": ("step.coverage",),
    "step_invariant_share": ("step.invariants",),
}
STEP_PHASES = frozenset(p for ps in STEP_METRICS.values() for p in ps)


# -- the op names' scopes, from the file ---------------------------------------


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message; a length-delimited
    value is a slice of `buf`, a varint an int, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield tag >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def op_scopes(path: str) -> dict:
    """{short op name: op_name} of every device op whose metadata has
    one. XSpace.planes=1; XPlane: name=2, event_metadata=4 (map value=2),
    stat_metadata=5 (map value=2: id=1, name=2); XEventMetadata: name=2,
    stats=5; XStat: metadata_id=1, str_value=5, ref_value=7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf == 4:
                events += [v for f, v in _fields(pv) if f == 2]
            elif pf == 5:
                meta = dict(_fields(next(v for f, v in _fields(pv) if f == 2)))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not trace_reduce.DEVICE_PLANE_RE.match(name):
            continue
        for event in events:
            op, scope = "", None
            for ef, ev in _fields(event):
                if ef == 2:
                    op = trace_reduce.op_identity(_text(ev))[0]
                elif ef == 5:
                    stat = dict(_fields(ev))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                        scope = _text(stat[5]) if 5 in stat \
                            else stat_names.get(stat.get(7), "")
            if op and scope:
                out[op] = scope
    return out


def load(path: str) -> dict:
    """The profiler's xplane file in this module's plain form."""
    return dict(trace_reduce.load_xplane(path), scopes=op_scopes(path))


# -- the reduction -------------------------------------------------------------


def phase_of(op_name: str):
    """The innermost `madsim.*` component of an op_name, prefix cut."""
    found = SCOPE_RE.findall(op_name or "")
    return found[-1] if found else None


def phases(self_s: dict, scopes: dict) -> dict:
    """`trace_reduce`'s per-op self seconds (in the window, a chip) summed
    by phase: {"phases": {phase: s}, "unscoped_s", "busy_s", "scoped"}
    (`scoped`: some op lies under a `madsim.step` scope). Self times
    partition busy, so phases + unscoped = busy."""
    by_phase: dict = {}
    unscoped = 0.0
    for op, seconds in self_s.items():
        phase = phase_of(scopes.get(op, ""))
        if phase is None:
            unscoped += seconds
        else:
            by_phase[phase] = by_phase.get(phase, 0.0) + seconds
    return {"phases": by_phase, "unscoped_s": unscoped,
            "busy_s": unscoped + sum(by_phase.values()),
            "scoped": any(p.split(".")[0] == "step" for p in by_phase)}


def reduce(trace: dict) -> dict:
    """`phases` of a plain-form trace (self time, busy and the window are
    `trace_reduce.reduce`'s)."""
    return phases(trace_reduce.reduce(trace)["self_s"], trace.get("scopes", {}))


def shares(reduced: dict):
    """{metric: % of busy} for the nine device metrics, or None where the
    step loop carried no scope (or nothing ran)."""
    busy = reduced["busy_s"]
    if not reduced["scoped"] or not busy:
        return None
    by_phase = reduced["phases"]
    out = {m: 100.0 * sum(by_phase.get(p, 0.0) for p in ps) / busy
           for m, ps in STEP_METRICS.items()}
    out["segment_overhead_share"] = 100.0 * sum(
        s for p, s in by_phase.items() if p not in STEP_PHASES) / busy
    out["device_unscoped_share"] = 100.0 * reduced["unscoped_s"] / busy
    return out


# -- what a reader calls -------------------------------------------------------

_MEMO: dict = {}  # xplane path -> shares(...) of it: nine readers, one load


def share(obs, metric: str):
    """One of the nine device metrics for a traced run; None (and one
    `benchmark: ` line saying why) where there is nothing to read."""
    if not obs.trace or not obs.trace.get("self_s"):
        return None
    found = sorted(glob.glob(os.path.join(
        obs.session.workdir, "trace", "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    path = found[-1]
    if path not in _MEMO:
        reduced = phases(obs.trace["self_s"], op_scopes(path))
        _MEMO[path] = shares(reduced)
        if _MEMO[path] is None:
            print("benchmark: no device op in the window lies under a "
                  "madsim.step scope (a program from before the scopes, or an "
                  "executable the compile cache kept from then): the phase "
                  "metrics are left out", flush=True)
        else:
            top = sorted(reduced["phases"].items(), key=lambda kv: -kv[1])[:12]
            print("benchmark: device self time by phase (s a chip): "
                  + ", ".join(f"{p} {s:.3f}" for p, s in top)
                  + f"; unscoped {reduced['unscoped_s']:.3f} of busy "
                  f"{reduced['busy_s']:.3f}", flush=True)
    return None if _MEMO[path] is None else _MEMO[path][metric]
