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
from ..perf.recorder import maybe_count, maybe_note, maybe_span
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
    hunts was exactly this, not the stream drain). The CLI registry
    hands out one machine object per name per process
    (`__main__.build_machine`), so `record_entry`, `regress`, `audit` and
    a fleet worker's next job share it too; a library user's own
    `Machine` carries its own cache, and no two machine objects ever
    share one.

    What the key promises: two engines over one machine get the same
    program exactly when `_trace_affecting_key` is equal for both, and
    then nothing `lane_step` reads — off the config, the engine or the
    state's shapes — differs between them. The jitted closure keeps a
    twin of the FIRST engine that asked, so the key has to be complete:
    every `EngineConfig` / `FaultPlan` field is named in one of the
    tables below (a test enumerates the dataclasses), and what
    `Engine.__init__` derives for the step (`_rng_layout`,
    `cov_band_bits`) is in the key by value or follows from fields that
    are (`_cov_buffered`)."""
    return engine.machine.__dict__.setdefault("_replay_jit_cache", {})


# Where each EngineConfig / FaultPlan field stands with the step program.
# `lane_step` reads the _KEY_* fields directly: they are in the key by
# value, the recorder's and coverage's sizes only while their gate is on
# (a gate that is off traces none of them, and corpus entries drop both).
_KEY_CONFIG = (
    "queue_capacity", "latency_min_us", "latency_max_us",
    "packet_loss_rate", "handler_rand_words", "trace_ring", "clog_packed",
    "provenance", "flight_recorder", "coverage",
)
_KEY_IF_RECORDER = ("fr_digest_every", "fr_digest_ring")
_KEY_IF_COVERAGE = ("cov_slots_log2", "cov_buffer")
# the PR-5/PR-6 chaos gates compiled INTO the step (defer logic, skew
# scaling, amnesia/torn restarts, asymmetric-heal word ops)
_KEY_FAULTS = (
    "allow_pause", "allow_skew", "strict_restart", "allow_torn",
    "allow_heal_asym",
    # the churn process's generator and its parameters (None: not traced)
    "churn",
)
# Fields the step reads only through an attribute Engine.__init__
# derives from them; the key holds that attribute, so two settings that
# give the same step (loss already possible, one more storm kind) share
# a program as they always did.
_KEY_DERIVED = {
    "rng_stream": "_rng_layout",       # stream version + word-block layout
    "allow_kill": "_rng_layout",       # the restart key's section
    "allow_storm": "_rng_layout",      # loss section (with packet_loss_rate)
    "allow_delay": "_rng_layout",      # spike section
    "allow_dup": "_rng_layout",        # dup section (and cov_band_bits)
    "cov_band_bits_min": "cov_band_bits",  # read while coverage is on
}
# Fields no step program depends on: they shape the initial state's
# VALUES (`init_lane` runs eagerly, outside the program, and the fault
# schedule's slots live inside the fixed queue_capacity), ride as traced
# scalars, or only steer the host / the batched executor.
_INIT_ONLY = frozenset({
    "horizon_us",         # traced scalar (the eager step path keys it itself)
    "faults",             # the FaultPlan: placed field by field
    "n_faults",           # how many schedule slots init_lane fills
    "allow_partition",    # the legacy kinds: which ops the schedule draws
    "allow_dir_clog",     # (the fault branch handles every op regardless)
    "allow_group",
    "storm_loss_u16",     # a payload value in the schedule
    "t_min_us", "t_max_us", "dur_min_us", "dur_max_us",  # schedule draws
    "churn_until_us",     # a value in LaneState.churn (shrink bisects it)
    "pallas_megakernel",  # step_batch's kernel choice: never in lane_step
    "compile_cache_dir",  # host-side
})


def _trace_affecting_key(engine: Engine) -> tuple:
    """Everything the lane_step trace depends on besides the machine
    (tables above). `Engine.use_pallas_pop` / `use_megakernel` are absent
    like `pallas_megakernel`: they pick step_batch's batched pop, which
    no single-lane replay runs."""
    cfg = engine.config
    return (
        tuple(getattr(cfg, f) for f in _KEY_CONFIG),
        tuple(getattr(cfg, f) for f in _KEY_IF_RECORDER)
        if cfg.flight_recorder else None,
        (*(getattr(cfg, f) for f in _KEY_IF_COVERAGE), engine.cov_band_bits)
        if cfg.coverage else None,
        tuple(getattr(cfg.faults, f) for f in _KEY_FAULTS),
        engine._rng_layout,
    )


def _program(engine: Engine, key: tuple, build: Callable[[], Any]):
    """The cached replay program under `key`, built on a miss; whether
    it was a miss is the caller's cue to time its first call as a
    `compile`. Hits and misses are counted and noted on the open
    `replay` span."""
    cache = _replay_cache(engine)
    fresh = key not in cache
    if fresh:
        cache[key] = build()
    maybe_count("replay.program_miss" if fresh else "replay.program_hit")
    maybe_note(program_hit=not fresh)
    return cache[key], fresh


def _bare_twin(engine: Engine) -> Engine:
    """What a program's closure keeps in place of its first asker: the
    same machine, config and pop path, none of what the asker accrued.
    A hunt's engine carries its compiled stream programs, and the cache
    outlives it by the life of the machine — of the process, for a
    registry machine."""
    return Engine(engine.machine, engine.config,
                  use_pallas_pop=engine.use_pallas_pop)


def _fast_outcome_fn(engine: Engine):
    """One jitted dispatch for a whole no-trace replay: a `while_loop`
    of lane_steps that stops where its lane stops, on `done | failed`
    or at `n_steps`, whichever comes first — a lane that ends after 16
    events runs 16 iterations whatever `max_steps` is. It returns the
    final state (bit-exactly the state at the stopping step) and the
    trip count. max_steps and horizon ride as traced scalars — one
    compile serves every shrink candidate and every seed."""
    import jax.numpy as jnp
    from jax import lax

    def build():
        twin = _bare_twin(engine)

        def run(state: LaneState, horizon_us, n_steps):
            def live(carry):
                i, s = carry
                return (i < n_steps) & ~(s.done | s.failed)

            def body(carry):
                i, s = carry
                return i + 1, twin.lane_step(s, horizon_us=horizon_us)

            i, state = lax.while_loop(live, body, (jnp.zeros_like(n_steps), state))
            return state, i

        return jax.jit(run)

    return _program(engine, ("fast-outcome", _trace_affecting_key(engine)), build)


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
    workhorse. Its cost follows the lane, not `max_steps`: the loop's
    trip count (`trips` on the `replay` span, summed in the counter
    `replay.loop_trips`) is the lane's event count, or `max_steps` for a
    lane cut there."""
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
            state, trips = jax.device_get(out if fresh else fn(*args))
        trips = int(trips)
        maybe_note(steps=int(state.step), trips=trips)
        maybe_count("replay.loop_trips", trips)
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
        skey = ("trace-step", _trace_affecting_key(engine), engine.config.horizon_us)
        step_fn, fresh = _program(
            engine, skey, lambda: jax.jit(_bare_twin(engine).lane_step))
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
