"""Causal provenance — decoding "why did this seed fail?".

The step kernel (`EngineConfig.provenance`, engine/core.py) tags every
queued event and every node with a 32-bit lineage word: bit f = \"the
effects of scheduled fault f are in this value's causal past\", bits
30/31 = the two non-scheduled chaos channels (crash-with-amnesia wipes,
Bernoulli duplicate deliveries). Words OR along deliveries and the
violating lane's word is harvested with the failure ring. This module is
the host half:

  * `fault_schedule(engine, seed)` re-derives the seed's drawn fault
    schedule (kind, virtual time, target) from the same `init_lane`
    derivation the device ran — the decode table for the word's bits;
  * `implicated(engine, seed, word)` names the faults/kinds the word
    convicts (fault attribution: the hunt report / stats consumer);
  * `replay_with_lineage(engine, seed)` replays eagerly and
    reconstructs exact event-level causality from the queue sequence
    numbers (each step's push watermark says which step enqueued which
    seq), so `past_cone` can cut a trace to the violation's causal past
    — the `python -m madsim_tpu why` renderer and the Perfetto flow
    arrows (engine/trace_export.py) both read the result.

Soundness shape: the device word is an OVER-approximation of the true
cause set (a fault that touched a node marks everything the node later
influences, whether or not the influence mattered), never an
under-approximation for effects that flow through state and messages.
The consumers are honest about that: shrink treats attribution as a
candidate ORDER (every candidate is still verified by a full replay),
and `why` prints the word alongside the decoded faults.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from .core import (
    F_CHURN_HEAL,
    F_CHURN_TICK,
    F_CLOG_DIR,
    F_CLOG_GROUP,
    F_CLOG_PAIR,
    F_DELAY_END,
    F_DELAY_SPIKE,
    F_HASYM,
    F_HASYM_HEAL,
    F_LOSS_END,
    F_LOSS_STORM,
    F_UNCLOG_DIR,
    F_UNCLOG_GROUP,
    F_UNCLOG_PAIR,
    FAULT_KIND_NAMES,
    PROV_BIT_AMNESIA,
    PROV_BIT_DUP,
    PROV_FAULT_BITS,
    Engine,
)
from .replay import ReplayResult, TraceEvent, replay

# fault ops whose provenance touches both payload endpoints / every node
# (host mirror of the step kernel's touched-mask classes)
_PAIR_OPS = {
    F_CLOG_PAIR, F_UNCLOG_PAIR, F_CLOG_DIR, F_UNCLOG_DIR,
    F_HASYM, F_HASYM_HEAL,
}
_GLOBAL_OPS = {
    F_CLOG_GROUP, F_UNCLOG_GROUP, F_LOSS_STORM, F_LOSS_END,
    F_DELAY_SPIKE, F_DELAY_END,
    # a churn tick that applies a fault moves a link of every node (the
    # device touches none where the tick applied nothing: this mirror is
    # the looser of the two, which only widens a past cone)
    F_CHURN_TICK, F_CHURN_HEAL,
}

# attribution pseudo-kinds for the non-scheduled chaos bits — named like
# the CLI flags that enable them, so the implicated kind set is directly
# comparable with shrink's minimal `--fault-kinds` / `--strict-restart`
KIND_DUP = "dup"
KIND_AMNESIA = "strict-restart"
# the churn process (`FaultPlan.churn`): its faults are generated, not
# scheduled, and each sets the provenance bit of the NODE it cut off or
# brought back — hundreds of faults in N <= 30 bits, sound as an OR
KIND_CHURN = "churn"


@dataclasses.dataclass(frozen=True)
class ScheduledFault:
    """One drawn fault of a lane's schedule, decoded to host values."""

    index: int          # schedule position (provenance bit = min(index, 29))
    kind: int           # K_* index
    kind_name: str      # FAULT_KIND_NAMES[kind]
    t_apply_us: int
    t_undo_us: int
    arg1: int           # payload[1] of the apply op (node a / mask lo / rate)
    arg2: int           # payload[2] (node b / mask hi / q10 / damage mask)
    t_heal2_us: Optional[int] = None  # heal-asym second-direction heal time

    @property
    def bit(self) -> int:
        return min(self.index, PROV_FAULT_BITS - 1)

    @property
    def target(self) -> str:
        k = self.kind_name
        if k in ("pair", "heal-asym"):
            return f"nodes {self.arg1}<->{self.arg2}"
        if k == "dir":
            return f"link {self.arg1}->{self.arg2}"
        if k == "group":
            return f"group mask 0x{(self.arg2 << 30) | self.arg1:x}"
        if k == "storm":
            return f"loss {self.arg1}/65536 (all links)"
        if k == "delay":
            return "all links"
        return f"node {self.arg1}"

    def describe(self) -> str:
        extra = ""
        if self.t_heal2_us is not None:
            extra = f", heal2 t={self.t_heal2_us}us"
        return (
            f"fault #{self.index} [bit {self.bit}]: {self.kind_name} on "
            f"{self.target}, apply t={self.t_apply_us}us, "
            f"undo t={self.t_undo_us}us{extra}"
        )


def _sched_fn(engine: Engine):
    """Jitted `seed -> fault-slot arrays` cached on the machine object
    (same discipline as the compiled-replay cache: shrink and hunts
    build many Engines over one machine)."""
    import jax

    cache = engine.machine.__dict__.setdefault("_prov_sched_cache", {})
    key = (engine.config.faults, engine.config.queue_capacity,
           engine.config.provenance, engine.config.rng_stream)
    if key not in cache:
        n = engine.machine.NUM_NODES
        spf = engine.config.faults.slots_per_fault
        nf = engine.config.faults.n_faults
        lo, hi = n, n + spf * nf

        def sched(seed):
            s = engine.init_lane(seed)
            return (
                s.eq_time[lo:hi], s.eq_payload[lo:hi], s.eq_valid[lo:hi]
            )

        cache[key] = jax.jit(sched)
    return cache[key]


def fault_schedule(engine: Engine, seed: int) -> List[ScheduledFault]:
    """Re-derive the fault schedule lane `seed` ran under — the decode
    table for its provenance bits. Reads the fault slots of the same
    `init_lane` derivation the device executed (bit-identical by the
    determinism contract)."""
    import numpy as np

    fp = engine.config.faults
    if fp.n_faults == 0:
        return []
    times, pays, valids = (np.asarray(x) for x in _sched_fn(engine)(seed))
    spf = fp.slots_per_fault
    out = []
    for f in range(fp.n_faults):
        apply_t = int(times[spf * f])
        undo_t = int(times[spf * f + 1])
        op, a1, a2 = (int(x) for x in pays[spf * f][:3])
        heal2 = None
        if fp.allow_heal_asym and bool(valids[spf * f + 2]):
            heal2 = int(times[spf * f + 2])
        kind = op // 2
        out.append(
            ScheduledFault(
                index=f,
                kind=kind,
                kind_name=FAULT_KIND_NAMES[kind],
                t_apply_us=apply_t,
                t_undo_us=undo_t,
                arg1=a1,
                arg2=a2,
                t_heal2_us=heal2,
            )
        )
    return out


@dataclasses.dataclass
class Attribution:
    """A violation provenance word decoded against its fault schedule."""

    word: int
    faults: List[ScheduledFault]   # scheduled faults the word implicates
    kinds: Tuple[str, ...]         # implicated kind names (sorted), incl.
    #                                the dup / strict-restart pseudo-kinds
    aliased: bool                  # >30 scheduled faults: bit 29 is shared
    #                                (or the schedule shares bits with churn)
    churn_nodes: Tuple[int, ...] = ()  # nodes whose churn faults it implicates

    def describe(self) -> List[str]:
        lines = [f.describe() for f in self.faults]
        if self.churn_nodes:
            lines.append(
                "churn: a disconnect or reconnect of node(s) "
                + ",".join(str(i) for i in self.churn_nodes)
                + " in lineage [bit = node]"
            )
        if (self.word >> PROV_BIT_AMNESIA) & 1:
            lines.append(
                f"crash-with-amnesia wipe in lineage [bit {PROV_BIT_AMNESIA}]"
            )
        if (self.word >> PROV_BIT_DUP) & 1:
            lines.append(
                f"duplicate delivery in lineage [bit {PROV_BIT_DUP}]"
            )
        if self.aliased:
            lines.append(
                f"(schedule has more than {PROV_FAULT_BITS} faults: "
                f"bit {PROV_FAULT_BITS - 1} aliases the tail)"
            )
        return lines


def implicated(engine: Engine, seed: int, word: int) -> Attribution:
    """Decode a violation provenance word: which scheduled faults (and
    which non-scheduled chaos channels) are in the violation's past."""
    sched = fault_schedule(engine, seed)
    faults = [f for f in sched if (word >> f.bit) & 1]
    kinds: Set[str] = {f.kind_name for f in faults}
    if (word >> PROV_BIT_AMNESIA) & 1:
        kinds.add(KIND_AMNESIA)
    if (word >> PROV_BIT_DUP) & 1:
        kinds.add(KIND_DUP)
    churn_nodes: Tuple[int, ...] = ()
    if engine.config.faults.churn is not None:
        churn_nodes = tuple(
            i for i in range(engine.machine.NUM_NODES) if (word >> i) & 1
        )
        if churn_nodes:
            kinds.add(KIND_CHURN)
    return Attribution(
        word=word,
        faults=faults,
        kinds=tuple(sorted(kinds)),
        # a schedule beside the process shares the low bits with it
        aliased=len(sched) > PROV_FAULT_BITS or bool(sched and churn_nodes),
        churn_nodes=churn_nodes,
    )


def kind_counts(engine: Engine, prov_by_seed: Dict[int, int]) -> Dict[str, int]:
    """Per-kind fault-attribution marginals over a hunt's finds: how many
    failures implicate each chaos kind (a find counts once per kind).
    The per-find reward signal coverage-guided hunting needs, aggregated
    the way the stats JSONL / `/stats` service report it."""
    counts: Dict[str, int] = {}
    for seed, word in prov_by_seed.items():
        for k in implicated(engine, seed, word).kinds:
            counts[k] = counts.get(k, 0) + 1
    return dict(sorted(counts.items()))


# -- event-level lineage (the `why` cone) ------------------------------------


@dataclasses.dataclass
class Lineage:
    """Exact event-level causality of one replayed seed.

    `parents[i]` are trace indices that causally precede trace event i
    by one hop: the step that ENQUEUED it (send->delivery / arm->fire /
    schedule->injection), plus the previous step at each node the event
    touched (program order — the state it read). `seq_pusher` maps queue
    sequence numbers to the trace index that pushed them."""

    trace: List[TraceEvent]
    parents: List[Set[int]]
    seq_pusher: Dict[int, int]
    # per-step next_seq watermarks (after each step): step i pushed the
    # seqs in [watermark[i-1], watermark[i]) — kept so host oracles can
    # re-derive lineage words independently (tests/test_provenance.py)
    next_seq_after: List[int] = dataclasses.field(default_factory=list)

    def past_cone(self, target: int) -> List[int]:
        """Trace indices in the causal past of trace event `target`
        (inclusive), ascending."""
        seen = {target}
        frontier = [target]
        while frontier:
            nxt = []
            for i in frontier:
                for p in self.parents[i]:
                    if p not in seen:
                        seen.add(p)
                        nxt.append(p)
            frontier = nxt
        return sorted(seen)

    def message_flows(self) -> List[Tuple[int, int]]:
        """(sender trace index, delivery trace index) pairs for every
        delivered message with a known pusher — the Perfetto flow
        arrows."""
        out = []
        for j, ev in enumerate(self.trace):
            if ev.kind == "msg" and ev.seq in self.seq_pusher:
                out.append((self.seq_pusher[ev.seq], j))
        return out


def _touched_nodes(ev: TraceEvent, num_nodes: int) -> List[int]:
    """Host mirror of the step kernel's provenance touched-mask."""
    if ev.kind != "fault":
        return [ev.node]
    op = ev.payload[0]
    if op in _GLOBAL_OPS:
        return list(range(num_nodes))
    if op in _PAIR_OPS:
        return sorted({ev.payload[1], ev.payload[2]})
    return [ev.payload[1]]


def build_lineage(
    engine: Engine, trace: List[TraceEvent], next_seq_after: List[int]
) -> Lineage:
    """Reconstruct event-level causality from a replayed trace plus the
    per-step `next_seq` watermarks (`replay_with_lineage` captures
    them): step i pushed exactly the seqs in [watermark[i-1],
    watermark[i]), so every later pop of such a seq has step i as its
    enqueueing parent."""
    n = engine.machine.NUM_NODES
    fp = engine.config.faults
    init_seq = n + fp.slots_per_fault * fp.n_faults + (fp.churn is not None)
    horizon = engine.config.horizon_us
    seq_pusher: Dict[int, int] = {}
    prev = init_seq
    for i, after in enumerate(next_seq_after):
        for q in range(prev, after):
            seq_pusher[q] = i
        prev = after
    parents: List[Set[int]] = []
    last_touch: Dict[int, int] = {}
    for i, ev in enumerate(trace):
        ps: Set[int] = set()
        if ev.seq in seq_pusher and seq_pusher[ev.seq] < i:
            ps.add(seq_pusher[ev.seq])
        touched = _touched_nodes(ev, n)
        for node in touched:
            if node in last_touch:
                ps.add(last_touch[node])
        parents.append(ps)
        if ev.time_us < horizon:  # horizon-hit pops are never processed
            for node in touched:
                last_touch[node] = i
    return Lineage(
        trace=trace, parents=parents, seq_pusher=seq_pusher,
        next_seq_after=list(next_seq_after),
    )


def replay_with_lineage(
    engine: Engine, seed: int, max_steps: int = 10_000
) -> Tuple[ReplayResult, Lineage]:
    """Eager traced replay + exact lineage reconstruction. Works with the
    provenance gate on OR off (lineage needs only the queue sequence
    numbers); with the gate on, every TraceEvent additionally carries
    its device-identical provenance word and the final state carries
    `fail_prov`."""
    marks: List[int] = []

    def hook(_ev, state) -> None:
        marks.append(int(state.next_seq))

    rp = replay(engine, seed, max_steps=max_steps, on_step=hook)
    return rp, build_lineage(engine, rp.trace, marks)


def render_why(
    engine: Engine,
    seed: int,
    rp: ReplayResult,
    lineage: Lineage,
    cone: List[int],
    attribution: Attribution,
    max_events: int = 0,
) -> str:
    """The `why <seed>` text report: verdict line, decoded implicated
    faults, then the violation's past cone as an annotated event list
    (implicated-fault injections flagged, message hops shown)."""
    lines = [
        f"seed {seed} fails with code {rp.fail_code} at "
        f"t={int(rp.state.now_us)}us after {len(lineage.trace)} events",
        f"violation provenance word: 0x{attribution.word:08x}",
        "implicated faults:",
    ]
    lines += ["  " + d for d in attribution.describe()] or [
        "  none (violation is fault-free)"
    ]
    lines.append("implicated kinds: " + (",".join(attribution.kinds) or "none"))
    shown = cone if not max_events else cone[-max_events:]
    lines.append(
        f"causal past cone: {len(cone)} of {len(lineage.trace)} events"
        + (f" (last {len(shown)} shown)" if len(shown) < len(cone) else "")
    )
    implicated_steps = {
        lineage.trace[i].step
        for i in cone
        if lineage.trace[i].kind == "fault"
    }
    for i in shown:
        ev = lineage.trace[i]
        mark = "!" if ev.step in implicated_steps else " "
        hop = ""
        if ev.kind == "msg" and ev.seq in lineage.seq_pusher:
            hop = f"  <= #{lineage.trace[lineage.seq_pusher[ev.seq]].step}"
        lines.append(f" {mark} {ev!r}{hop}")
    return "\n".join(lines)
