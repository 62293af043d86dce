"""Bit-identical single-lane replay on the host CPU — the debugger path.

The TPU batch explores thousands of seeds; any failing seed is re-run
here, eagerly, one event at a time, with a full event trace the user can
print, filter, or step through. Because the replay executes the *same*
jax ops (threefry draws, int32 time math, argmin pops) outside jit on
CPU, the outcome is bit-identical to the lane's on-device execution —
the property the reference gets from reproduce-by-seed
(madsim/src/sim/runtime/mod.rs:205-210), upgraded to cross-engine.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Callable, List, Optional

import jax

from ..ops import pop_earliest
from ..perf import compile_log
from ..perf.recorder import maybe_note, maybe_span
from .core import EV_FAULT, EV_MSG, EV_TIMER, Engine, LaneState

_KIND_NAMES = {EV_TIMER: "timer", EV_MSG: "msg", EV_FAULT: "fault"}


@dataclasses.dataclass
class TraceEvent:
    step: int
    time_us: int
    kind: str
    node: int
    src: int
    payload: tuple
    # the event's queue sequence number — unique per lane, assigned at
    # push time, so (together with the per-step next_seq watermarks) the
    # host can reconstruct exactly which step enqueued which event: the
    # send->delivery / arm->fire lineage engine/provenance.py and the
    # Perfetto flow arrows are built from. -1 on traces recorded before
    # the field existed.
    seq: int = -1
    # the event's causal-provenance word (EngineConfig.provenance;
    # 0 when the gate is off): one bit per scheduled fault slot in the
    # event's lineage, bits 30/31 = strict-restart wipe / dup delivery
    prov: int = 0

    def __repr__(self) -> str:
        src = f" src={self.src}" if self.kind == "msg" else ""
        return (
            f"[{self.time_us:>10}us] #{self.step:<5} {self.kind:<5} "
            f"node={self.node}{src} payload={list(self.payload)}"
        )


@dataclasses.dataclass
class ReplayResult:
    state: LaneState
    trace: List[TraceEvent]

    @property
    def failed(self) -> bool:
        return bool(self.state.failed)

    @property
    def fail_code(self) -> int:
        return int(self.state.fail_code)


def replay_diff(
    engine: Engine,
    seed_a: int,
    seed_b: int,
    max_steps: int = 10_000,
    context: int = 3,
) -> Optional[int]:
    """Debugging aid: replay two seeds and report the first step where
    their event streams diverge (printing `context` events around it).
    Returns the diverging step index, or None if the shorter trace is a
    prefix of the longer (seeds that only differ later in latencies).

    Typical use: diff a failing seed against its nearest passing
    neighbor to see where the schedules fork."""
    ra = replay(engine, seed_a, max_steps=max_steps)
    rb = replay(engine, seed_b, max_steps=max_steps)

    def key(ev: TraceEvent):
        return (ev.time_us, ev.kind, ev.node, ev.src, ev.payload)

    for i, (ea, eb) in enumerate(zip(ra.trace, rb.trace)):
        if key(ea) != key(eb):
            lo = max(0, i - context)
            print(f"traces diverge at step {i}:")
            for j in range(lo, min(i + context + 1, min(len(ra.trace), len(rb.trace)))):
                marker = ">>" if j == i else "  "
                print(f"{marker} seed {seed_a}: {ra.trace[j]}")
                print(f"{marker} seed {seed_b}: {rb.trace[j]}")
            return i
    la, lb = len(ra.trace), len(rb.trace)
    if la != lb:
        print(f"trace of seed {seed_a} ({la} events) is a prefix-match of "
              f"seed {seed_b} ({lb} events); no per-event divergence")
    else:
        print(f"seeds {seed_a} and {seed_b} produced identical {la}-event traces")
    return None


def decode_ring(lane_ring) -> List[TraceEvent]:
    """Decode one lane's on-device event ring (Engine.ring_trace) into
    TraceEvents, oldest first. Entries with step < 0 are unused slots."""
    import numpy as np

    step = np.asarray(lane_ring["step"])
    order = np.argsort(step)  # unused (-1) sort first; slice them off
    order = order[step[order] >= 0]
    time_us = np.asarray(lane_ring["time"])
    kinds = np.asarray(lane_ring["kind"])
    node = np.asarray(lane_ring["node"])
    src = np.asarray(lane_ring["src"])
    pay = np.asarray(lane_ring["payload"])
    return [
        TraceEvent(
            step=int(step[i]),
            time_us=int(time_us[i]),
            kind=_KIND_NAMES.get(int(kinds[i]), "?"),
            node=int(node[i]),
            src=int(src[i]),
            payload=tuple(int(x) for x in pay[i]),
        )
        for i in order
    ]


def cpu_device():
    """The CPU backend's first device — every replay runs there, beside
    whatever accelerator found the seed."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as exc:
        raise RuntimeError(
            "replay runs on jax's CPU backend, and this process has none "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}: {exc}). "
            "Leave JAX_PLATFORMS unset, or list the CPU after the "
            "accelerator: JAX_PLATFORMS=tpu,cpu"
        ) from exc


def _replay_cache(engine: Engine) -> dict:
    """Compiled-replay cache, held on the MACHINE object so every Engine
    wrapping the same machine shares it (shrink builds a fresh Engine per
    candidate config; without sharing, each candidate pays a multi-second
    lane_step compile — the measured 10x collapse of high-find-rate
    hunts was exactly this, not the stream drain)."""
    return engine.machine.__dict__.setdefault("_replay_jit_cache", {})


def _trace_affecting_key(engine: Engine) -> tuple:
    """Config fields that change the lane_step trace. horizon_us is
    deliberately absent: the replay paths pass it as a traced value."""
    cfg = engine.config
    return (
        cfg.queue_capacity,
        cfg.latency_min_us,
        cfg.latency_max_us,
        cfg.packet_loss_rate,
        cfg.handler_rand_words,
        cfg.trace_ring,
        cfg.clog_packed,
        cfg.flight_recorder,
        cfg.fr_digest_every,
        cfg.fr_digest_ring,
        # PR-5/PR-6 chaos gates compiled INTO the step (defer logic,
        # skew scaling, amnesia/torn restarts, asymmetric-heal word
        # ops) — unlike the legacy kinds, which only shape the schedule
        # in the initial state
        cfg.faults.allow_pause,
        cfg.faults.allow_skew,
        cfg.faults.strict_restart,
        cfg.faults.allow_torn,
        cfg.faults.allow_heal_asym,
        cfg.provenance,  # lineage words compiled into the step
        engine._rng_layout,  # stream version + word-block layout (incl. dup)
        engine.use_pallas_pop,
    )


def _fast_outcome_fn(engine: Engine):
    """One jitted dispatch for a whole no-trace replay: while-loop of
    freeze-wrapped lane_steps (a done/failed lane passes through
    untouched, so the final state is bit-exactly the state at the
    stopping step). max_steps and horizon ride as traced scalars — one
    compile serves every shrink candidate and every seed."""
    from jax import lax

    cache = _replay_cache(engine)
    key = ("fast-outcome", _trace_affecting_key(engine))
    fresh = key not in cache
    if fresh:

        def run(state: LaneState, horizon_us, n_steps):
            def body(_i, s):
                return lax.cond(
                    s.done | s.failed,
                    lambda x: x,
                    lambda x: engine.lane_step(x, horizon_us=horizon_us),
                    s,
                )

            return lax.fori_loop(0, n_steps, body, state)

        cache[key] = jax.jit(run)
    return cache[key], fresh


@contextlib.contextmanager
def _first_call(program: str):
    """The first call of a jitted replay fn: trace + lower + compile-or-
    read before the dispatch — the executor's `compile` span convention
    (core.py `_dispatch`), its stages filed under `program`."""
    with compile_log.program(program), maybe_span("compile", program=program):
        yield


def replay_outcome(engine: Engine, seed: int, max_steps: int = 10_000) -> ReplayResult:
    """Traceless replay of one seed in a single compiled dispatch —
    bit-identical final state (same lane_step ops), ~1000x fewer host
    round-trips than the eager trace path. The shrink verification
    workhorse."""
    import jax.numpy as jnp

    with maybe_span("replay", seed=int(seed), traced=False), \
            jax.default_device(cpu_device()):
        state = engine.init_lane(seed)
        fn, fresh = _fast_outcome_fn(engine)
        args = (state, jnp.int32(engine.config.horizon_us), jnp.int32(max_steps))
        if fresh:
            with _first_call("replay.run"):
                out = fn(*args)  # returns once compiled and enqueued
        with maybe_span("replay_run"):
            state = jax.device_get(out if fresh else fn(*args))
        maybe_note(steps=int(state.step))
        return ReplayResult(state=state, trace=[])


def replay(
    engine: Engine,
    seed: int,
    max_steps: int = 10_000,
    on_step: Optional[Callable[[TraceEvent, LaneState], None]] = None,
    trace: bool = True,
) -> ReplayResult:
    """Replay one seed eagerly on CPU with a full event trace.

    `on_step(event, state)` is the debugging hook: runs as plain Python
    after every event — print, assert, drop into pdb, anything.

    With `trace=False` and no hook, the replay collapses into ONE
    compiled dispatch (`replay_outcome`) — same final state, none of the
    per-event host syncs.
    """
    if not trace and on_step is None:
        return replay_outcome(engine, seed, max_steps=max_steps)
    with maybe_span("replay", seed=int(seed), traced=True), \
            jax.default_device(cpu_device()):
        state = engine.init_lane(seed)
        # jit the single-lane step: still bit-identical (XLA integer ops are
        # exact and threefry is backend-stable), but the replay materializes
        # the full state between events so hooks can inspect anything.
        # Cached on the machine so repeated replays don't recompile.
        cache = _replay_cache(engine)
        skey = ("trace-step", _trace_affecting_key(engine), engine.config.horizon_us)
        fresh = skey not in cache
        if fresh:
            cache[skey] = jax.jit(engine.lane_step)
        step_fn = cache[skey]
        events: List[TraceEvent] = []
        step = 0
        prov_on = engine.config.provenance
        # one span for the whole loop: no per-event span, the loop's
        # Python stays uninstrumented
        with maybe_span("replay_run"):
            while not bool(state.done | state.failed) and step < max_steps:
                idx, any_valid = pop_earliest(state.eq_time, state.eq_seq, state.eq_valid)
                ev = TraceEvent(
                    step=step,
                    time_us=int(state.eq_time[idx]),
                    kind=_KIND_NAMES.get(int(state.eq_kind[idx]), "?"),
                    node=int(state.eq_node[idx]),
                    src=int(state.eq_src[idx]),
                    payload=tuple(int(x) for x in state.eq_payload[idx]),
                    seq=int(state.eq_seq[idx]),
                    prov=int(state.eq_prov[idx]) if prov_on else 0,
                ) if bool(any_valid) else None
                with _first_call("replay.step") if fresh \
                        else contextlib.nullcontext():
                    state = step_fn(state)
                fresh = False
                if ev is not None:
                    if trace:
                        events.append(ev)
                    if on_step is not None:
                        on_step(ev, state)
                step += 1
        maybe_note(steps=step)
        return ReplayResult(state=state, trace=events)
