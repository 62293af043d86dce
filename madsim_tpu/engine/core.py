"""The TPU engine — the discrete-event loop as a batched JAX computation.

This is the tpu-native re-design of the reference's hot loop
(`Executor::block_on` + timer queue + NetSim delivery,
madsim/src/sim/task/mod.rs:220-323, sim/time/mod.rs:45-59,
sim/net/mod.rs:298-334): one `lax.while_loop` advances a struct-of-arrays
state where the leading dimension is the *seed lane*. Thousands of
independent seeds + fault schedules run in lockstep on one chip; lanes
shard over a `jax.sharding.Mesh` for multi-chip scale-out
(seed-batch scaling, SURVEY.md §2.9).

Design rules that make host replay bit-identical (SURVEY.md §7):
  * integer virtual time (int32 microseconds), no float latency math
  * counter-based RNG (jax threefry via jax.random — bit-deterministic
    across CPU/TPU and eager/jit), one key per lane
  * fixed-shape everything: event slots, outbox slots, node arrays;
    overflow = lane failure (code OVERFLOW), never dynamic allocation

Chaos parity with the host fabric: uniform integer latency in
[min,max), Bernoulli loss, directional link clogging, node kill/restart
with re-init (reference: sim/net/network.rs:261-270 + supervisor ops
sim/runtime/mod.rs:272-301), driven by a per-lane `FaultPlan` drawn from
the lane seed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

import os

_stream_log = logging.getLogger("madsim_tpu.stream")

from .. import kinds as _kinds
from ..compile_cache import enable_compile_cache
from ..ops import free_slot_ranks, pop_earliest
from ..ops.coverage import (
    COV_BAND_AMNESIA,
    COV_BAND_DUP,
    COV_BUFFER_DEFAULT,
    COV_SLOTS_LOG2_DEFAULT,
    cov_band,
    cov_fold,
    cov_fold_words,
    cov_push,
    cov_slot,
    empty_cov_map,
)
from ..ops.pallas_pop import (
    cov_flush_batch,
    pop_earliest_batch,
    pop_gather_batch,
    step_megakernel,
)
from jax.extend.random import threefry_2x32

from ..ops.step_rng import (
    RNG_STREAM_COUNTER,
    RNG_STREAM_LEGACY,
    RNG_STREAM_VERSIONS,
    layout_for,
    step_words as draw_step_words,
)
from ..perf import xprof as _xprof
from ..utils import set2d, tree_where
from .machine import BOOT, Machine, Outbox, RoleRows, get_at

# Event kinds
EV_TIMER = 0
EV_MSG = 1
EV_FAULT = 2

# Fault ops (payload[0]). Apply ops are even, the matching undo is
# apply+1, and apply = 2*kind where kind indexes FaultPlan.enabled_kinds.
F_CLOG_PAIR = 0
F_UNCLOG_PAIR = 1
F_KILL = 2
F_RESTART = 3
F_CLOG_DIR = 4  # one-way clog a->b (reference Direction, sim/net/network.rs:108)
F_UNCLOG_DIR = 5
F_CLOG_GROUP = 6  # group partition: payload[1] is a node bitmask; every
F_UNCLOG_GROUP = 7  # link crossing the group boundary clogs both ways
F_LOSS_STORM = 8  # timed packet-loss storm: payload[1] = rate in 1/65536
F_LOSS_END = 9
F_DELAY_SPIKE = 10  # timed delay-spike window: ~10% of sends +1-5 virt s
F_DELAY_END = 11    # (the device analogue of the host buggify delay,
#                     reference sim/net/mod.rs:287-296)
F_PAUSE = 12   # pause window: node frozen (state survives; deliveries
F_RESUME = 13  # targeting it DEFER past resume, not drop) — the device
#                analogue of Handle::pause (reference runtime/mod.rs)
F_SKEW = 14      # clock-skew window: payload[2] is a q10 multiplier —
F_SKEW_END = 15  # the node's timer delays are stretched/compressed
F_TORN = 16          # torn/lost-write fault: kill node a; payload[2] is the
F_TORN_RESTART = 17  # schedule-drawn damage mask — the restart wipes
#                      volatile leaves AND damages durable leaves per the
#                      machine's torn_spec() atomicity contract ("the
#                      disk lied" — the FoundationDB buggify class)
F_HASYM = 18       # asymmetric partition: clog pair a<->b both ways; the
F_HASYM_HEAL = 19  # heal op unclogs ONE direction arg1->arg2 — the two
#                    directions heal at independently drawn times, so
#                    every partition tail is a one-way-link window

# The churn process (FaultPlan.churn) — not a scheduled kind: ONE queue slot
# that re-arms itself. A tick draws its own faults when it fires (so their
# number is not a shape of anything), the heal reconnects every node once
# the process is over. payload[1] of a tick is its index.
F_CHURN_TICK = 20
F_CHURN_HEAL = 21
# kind `kv3a` only: the heal kills every named node, and this op brings
# them back, one an event at one instant — payload[1] is the node, and
# the slot re-arms itself for the next until all are up
F_CHURN_RESTART = 22

# FaultPlan kind indices (op_apply = 2*kind)
K_PAIR = 0
K_KILL = 1
K_DIR = 2
K_GROUP = 3
K_STORM = 4
K_DELAY = 5
K_PAUSE = 6
K_SKEW = 7
K_TORN = 8
K_HEAL_ASYM = 9

# delay-spike parameters — the host fabric's buggify numbers
# (net/__init__.py rand_delay: 10% of sends suspended 1-5 s)
DELAY_PROB_U32 = int(0.1 * 0xFFFFFFFF)
DELAY_EXTRA_MIN_US = 1_000_000
DELAY_EXTRA_SPAN_US = 4_000_001

# message duplication (FaultPlan.allow_dup): Bernoulli per successful
# delivery-push, duplicate re-enqueued with an independently drawn
# latency (the at-least-once property real networks have and loss-only
# chaos never exercises)
DUP_PROB_U32 = int(0.1 * 0xFFFFFFFF)

# clock-skew factor: a q10 fixed-point timer-delay multiplier drawn
# uniform in [SKEW_Q10_MIN, SKEW_Q10_MIN + SKEW_Q10_SPAN) — 0.5x..2.0x,
# wide enough to break lease/heartbeat "my timer fires before your
# timeout" assumptions in both directions. Applied 32-bit-exactly as
#   scaled = (d >> 10) * q + (((d & 1023) * q) >> 10)
# (no int64, no floats — the determinism rules).
SKEW_Q10_ONE = 1024
SKEW_Q10_MIN = 512
SKEW_Q10_SPAN = 1536


def skew_scale_us(delay_us, q10):
    """Stretch/compress an int32 microsecond delay by the q10 factor,
    exactly, within int32 (delay < ~2^21 s-scale values stay exact:
    (d>>10)*q <= 2e7 and the remainder term <= 2.1e6)."""
    d = jnp.asarray(delay_us).astype(jnp.int32)
    q = jnp.asarray(q10).astype(jnp.int32)
    return (d >> 10) * q + (((d & 1023) * q) >> 10)

# Failure codes
OK = 0
OVERFLOW = 1  # event queue full — lane aborts (host fallback)

# The streaming quartet, in the order `Engine._stream_fns` returns it;
# also the names its programs go by in `perf/compile_log.py` and in the
# `program` arg of a `compile` span.
STREAM_PROGRAMS = ("init_carry", "segment", "supersegment", "reset_rings")

# -- flight recorder (observability) ----------------------------------------
# Rolling per-lane trace digest: a uint32[2] xor-rotate-multiply fold
# over every popped event tuple plus the step's RNG word block. Not
# cryptographic — built so any single-bit difference in any folded word
# avalanches into both halves within one step, which is all divergence
# detection needs. The IVs are pi's fractional bits (nothing-up-my-
# sleeve); the multipliers are the Weyl/golden-ratio constant and
# murmur3's fmix constant (both odd, so the map is a bijection on u32).
DIGEST_IV0 = 0x243F6A88
DIGEST_IV1 = 0x85A308D3
_DIGEST_M0 = 0x9E3779B1
_DIGEST_M1 = 0x85EBCA6B

# FaultPlan kind names, indexed by K_* — the fault-injection counter
# labels used by run_stream stats / audit output. The table
# lives in madsim_tpu/kinds.py (single source of truth for every host
# mirror; `python -m madsim_tpu lint` cross-checks the consumers).
FAULT_KIND_NAMES = _kinds.FAULT_KIND_NAMES

# -- causal provenance (observability) ---------------------------------------
# One uint32 word per queued event and per node (`EngineConfig.
# provenance`): bit f marks "scheduled fault f is in this value's causal
# past". Provenance is MONOTONE — words only OR, never clear — so a
# violation's word names every scheduled fault whose effects reached the
# violating node through any chain of deliveries (an over-approximation
# of the true cause set, never an under-approximation for fault effects
# that flow through state and messages; what it cannot see is
# absence-causality refinement — a clogged link's bit is planted on both
# endpoints at clog time rather than on each message the clog swallowed).
# Bits 30/31 are reserved for the two non-scheduled chaos channels, so
# attribution can name them even though they own no schedule slot:
# a crash-with-amnesia wipe (strict_restart) and a Bernoulli duplicate
# delivery (allow_dup). Scheduled fault indices clip into the remaining
# 30 bits (plans beyond 30 faults alias — attribution degrades to
# coarser, still-sound-as-OR reporting, never to wrong dataflow).
PROV_FAULT_BITS = 30
PROV_BIT_AMNESIA = 30
PROV_BIT_DUP = 31


def prov_fault_bit(fault_index: int) -> int:
    """The provenance bit a scheduled fault slot sets (python-level;
    the schedule is unrolled statically in init_lane)."""
    return 1 << min(fault_index, PROV_FAULT_BITS - 1)

# Non-scheduled chaos injection counters (flight recorder): Bernoulli
# message duplicates pushed, and strict (crash-with-amnesia) restarts
# applied. They ride fr_metrics after the per-kind totals.
FR_EXTRA_NAMES = _kinds.FR_EXTRA_NAMES

# StreamCarry.fr_metrics layout: per-kind injection totals, the extra
# chaos counters (all summed at harvest), then queue / clogged-link /
# killed-node high-water marks (maxed at harvest).
FR_METRICS_LEN = len(FAULT_KIND_NAMES) + len(FR_EXTRA_NAMES) + 3


def digest_fold(d0, d1, words):
    """One digest round per word: d0 takes an xor-multiply-xorshift, d1
    takes a rotated xor-multiply and absorbs d0 so the halves couple.
    `words` is a python list of traced scalars (static unroll)."""
    for w in words:
        w = jnp.asarray(w).astype(jnp.uint32)
        d0 = (d0 ^ w) * jnp.uint32(_DIGEST_M0)
        d0 = d0 ^ (d0 >> 16)
        d1 = (d1 ^ ((w << 13) | (w >> 19))) * jnp.uint32(_DIGEST_M1)
        d1 = d1 ^ (d1 >> 15) ^ d0
    return d0, d1

# Bit-packed clog rows: node j of row i lives in word j // 30, bit
# j % 30 — the SAME 30-bits-per-int32 encoding the group-partition
# payload masks use (payload args 1+2), so the two-word row covers the
# existing N <= 60 cap and the group fault becomes pure word ops.
CLOG_WORD_BITS = 30
CLOG_WORDS = 2
CLOG_MAX_NODES = CLOG_WORD_BITS * CLOG_WORDS


def _clog_bit_words(j):
    """One-hot (lo, hi) int32 words for a traced node index j."""
    lo = jnp.where(j < CLOG_WORD_BITS,
                   jnp.int32(1) << jnp.clip(j, 0, CLOG_WORD_BITS - 1),
                   jnp.int32(0))
    hi = jnp.where(j >= CLOG_WORD_BITS,
                   jnp.int32(1) << jnp.clip(j - CLOG_WORD_BITS, 0, CLOG_WORD_BITS - 1),
                   jnp.int32(0))
    return lo, hi


def _clog_row_bools(row, n):
    """Expand a packed int32[CLOG_WORDS] row to bool[n] link flags."""
    ii = jnp.arange(n)
    bits = jnp.where(
        ii < CLOG_WORD_BITS,
        row[0] >> jnp.clip(ii, 0, CLOG_WORD_BITS - 1),
        row[1] >> jnp.clip(ii - CLOG_WORD_BITS, 0, CLOG_WORD_BITS - 1),
    )
    return (bits & 1).astype(bool)


@dataclasses.dataclass(frozen=True)
class ChurnPlan:
    """A fault PROCESS (`FaultPlan.churn`): faults drawn as they fire.

    The loop of MIT 6.824's `TestFigure8Unreliable2C` (MadRaft's
    `figure_8_unreliable_2c`), one iteration a tick: sleep, then with
    probability `disconnect_permille`/1000 disconnect the victim — the
    node `Machine.churn_victim` names (Raft: the connected leader), or a
    uniformly drawn connected node for a machine without the hook — by
    clogging both directions of all its links; then, if fewer than
    `majority` nodes are connected, draw one node uniformly and
    reconnect it if it is disconnected. The sleep is U[0, long_sleep_us)
    with probability `long_sleep_permille`/1000, else U[0,
    short_sleep_us). At `FaultPlan.churn_until_us` every node is
    reconnected and the process stops.

    A link carries traffic iff both its ends are connected (labrpc's
    rule): reconnecting a node re-enables its links to the connected
    nodes only, and with it whatever scheduled clog lay on them.

    The draws are a counter stream of their own, keyed by the lane's
    seed (`churn_words`): draw 0 gives the sleep before tick 0, draw
    i + 1 the coins of tick i and the sleep after it. So the ticks'
    times, coins and reconnect picks are a function of the seed alone
    (`differential.churn_reference` re-derives them in plain Python),
    no other stream moves, and only the victim depends on the run.

    `kind="kv3a"` is another loop on the same slot and the same stream:
    the partitioner and the crash of 6.824's lab 3A tester
    (`TestPersistPartitionUnreliable3A`, MadRaft's
    `persist_partition_unreliable_3a`). Tick 0 fires at t = 0, then one
    every `period_us` + U[0, `jitter_us`). A tick draws a side, 0 or 1,
    for each node the machine names (`Machine.churn_nodes()`, default
    all: bit i of the draw's word 0) and sets the clog rows so that a
    link between two named nodes carries traffic iff they drew the same
    side; links with an unnamed end are not touched (the tester's
    clerks reach every server). At `churn_until_us` every link between
    named nodes heals and every named node is KILLED at that instant;
    `restart_after_us` later they restart, each through the machine's
    restart hook and its BOOT, one an event at one virtual instant."""

    disconnect_permille: int = 500
    long_sleep_permille: int = 100
    long_sleep_us: int = 500_000
    short_sleep_us: int = 13_000
    majority: int = 0  # 0 = NUM_NODES // 2 + 1
    kind: str = "fig8"  # or "kv3a"; the fields below are kv3a's
    period_us: int = 1_000_000
    jitter_us: int = 200_000
    restart_after_us: int = 150_000


CHURN_KINDS = ("fig8", "kv3a")
# `--churn <name>`: the named parameter sets
CHURN_PRESETS = {"fig8": ChurnPlan(), "kv3a": ChurnPlan(kind="kv3a")}

# second key word of the churn stream ("MADC"); the first is the seed
CHURN_KEY_TAG = 0x4D414443
CHURN_DRAW_WORDS = 6  # coin, reconnect pick, long coin, sleep, victim, spare
CHURN_DRAW_STRIDE = 8  # counters of draw d: 8*d + [0, 6)
# LaneState.churn's counters, in the order they ride fr_metrics' tail
CHURN_COUNTER_NAMES = _kinds.FR_CHURN_NAMES


def churn_counter_names(plan: "ChurnPlan") -> tuple:
    """The counters the process of `plan` keeps: kind `kv3a` adds its
    two AFTER the three every kind has, so those keep their places."""
    return CHURN_COUNTER_NAMES + (
        _kinds.FR_CHURN_KV3A_NAMES if plan.kind == "kv3a" else ()
    )


def churn_key(seed) -> jax.Array:
    """uint32[2] key of a lane's churn stream: (seed, CHURN_KEY_TAG)."""
    if not hasattr(seed, "dtype"):
        seed = jnp.uint32(int(seed) & 0xFFFFFFFF)
    return jnp.stack([seed.astype(jnp.uint32), jnp.uint32(CHURN_KEY_TAG)])


def churn_words(key, draw) -> jax.Array:
    """uint32[CHURN_DRAW_WORDS] of draw `draw`: raw Threefry-2x32 over
    the counters 8*draw + [0, 6) (the flag-independent kernel the v3
    step stream uses)."""
    counts = (
        jnp.asarray(draw).astype(jnp.uint32) * jnp.uint32(CHURN_DRAW_STRIDE)
        + jnp.arange(CHURN_DRAW_WORDS, dtype=jnp.uint32)
    )
    return threefry_2x32(key, counts)


def churn_sleep_us(plan: ChurnPlan, words) -> jax.Array:
    """The sleep a draw's words give (int32 us)."""
    if plan.kind == "kv3a":
        return (
            jnp.uint32(plan.period_us) + words[3] % jnp.uint32(plan.jitter_us)
        ).astype(jnp.int32)
    is_long = (words[2] % jnp.uint32(1000)) < jnp.uint32(plan.long_sleep_permille)
    return jnp.where(
        is_long,
        words[3] % jnp.uint32(plan.long_sleep_us),
        words[3] % jnp.uint32(plan.short_sleep_us),
    ).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Per-lane randomized fault schedule (drawn from the lane seed).

    Each fault picks a random kind, start time and duration:
      * partition: clog a random node pair both ways, heal after duration
      * kill: kill a random node, restart after duration
      * dir_clog: clog one direction of a random pair (the host fabric's
        `Direction` semantics, reference sim/net/network.rs:108)
      * group: partition a random non-trivial node subset from the rest
        (covers majority/minority splits; bitmask-encoded)
      * storm: raise the packet-loss rate to `storm_loss_u16`/65536 for
        the duration (timed loss storm on top of the static config rate)
      * delay: a delay-spike window — while active, ~10% of sent
        messages take +1-5 virtual seconds of extra latency (the device
        analogue of the host fabric's buggified rand_delay, reference
        sim/net/mod.rs:287-296; late-but-delivered messages find
        timeout-handling bugs that loss cannot)
      * pause: a pause window — the node is FROZEN, not killed: its
        state survives untouched and every delivery targeting it
        (timers and messages alike) is deferred past the resume time
        instead of dropped (the device analogue of `Handle::pause`,
        reference sim/runtime/mod.rs). Exercises the timeout paths
        kill cannot: peers see silence, then the node comes back with
        stale-but-intact state.
      * skew: a per-node clock-skew window — while active, every timer
        the node arms is stretched/compressed by a q10 factor drawn in
        [0.5x, 2.0x) (payload[2]); leases expire late, heartbeats fire
        early, election timeouts drift.
      * torn: a torn/lost-write storage fault — kill a random node,
        then restart it through the machine's `torn_spec()` atomicity
        contract instead of its restart hook: volatile leaves wipe
        (amnesia), and durable leaves marked non-atomically-written
        (TORN_LOSE / TORN_PREFIX) keep only a seeded prefix or revert
        entirely, per a damage word drawn in the schedule (payload[2])
        and salted by the step's torn RNG word. "The disk lied" — the
        FoundationDB buggify finding class. A machine with only a
        `durable_spec()` survives by construction (default spec: every
        durable write is atomic).
      * heal_asym: an asymmetric partition — clog a random pair both
        ways, then heal the two directions at INDEPENDENTLY drawn
        times (a->b at t+dur, b->a at t+dur2), so every partition tail
        is a one-way-link window: acks flow without requests, requests
        without acks. Each fault takes a third schedule slot for the
        second heal (only materialized when the kind is enabled).

    Plus two non-scheduled chaos gates:
      * `allow_dup`: Bernoulli per-delivery message duplication — each
        successfully pushed message has a DUP_PROB chance of a second
        copy enqueued with an independently drawn latency (idempotency
        chaos; the RNG block grows a tail section, recorded streams
        stay byte-stable with the flag off)
      * `strict_restart`: crash-with-amnesia — restart faults wipe
        every node-state leaf the machine's `durable_spec()` contract
        does not mark durable, instead of trusting the model's
        hand-written restart hook ("node restarts but illegally kept
        volatile state" is the classic DST finding class this makes
        expressible)

    The legacy two-kind derivation (partition/kill only) is byte-stable:
    seeds found by earlier sweeps (e.g. the 66531 LOG_MATCHING
    regression) replay unchanged unless a new kind is enabled, which
    switches the schedule to the v2 derivation. pause/skew ride the v2
    derivation with one extra per-fault draw (the skew factor), taken
    only when either flag is on — dir/group/storm/delay-era schedules
    are untouched.
    """

    n_faults: int = 0
    allow_partition: bool = True
    allow_kill: bool = True
    allow_dir_clog: bool = False
    allow_group: bool = False
    allow_storm: bool = False
    allow_delay: bool = False  # timed delay-spike windows (buggify analogue)
    allow_pause: bool = False  # pause/resume windows (freeze, defer deliveries)
    allow_skew: bool = False   # per-node clock-skew windows (q10 timer scale)
    allow_dup: bool = False    # Bernoulli per-delivery message duplication
    allow_torn: bool = False   # torn/lost-write faults via Machine.torn_spec()
    allow_heal_asym: bool = False  # asymmetric partition healing (one-way decay)
    strict_restart: bool = False  # crash-with-amnesia via Machine.durable_spec()
    storm_loss_u16: int = 52428  # ~80% loss while a storm is active
    t_min_us: int = 0
    t_max_us: int = 1_000_000
    dur_min_us: int = 100_000
    dur_max_us: int = 1_000_000
    # the fault process beside the schedule (None = off: nothing of it
    # is traced, and every recorded stream is as it was), and the
    # virtual time at which it reconnects every node and stops
    churn: Optional[ChurnPlan] = None
    churn_until_us: int = 0

    def __post_init__(self):
        # a corpus entry's JSON gives the plan back as a dict
        if isinstance(self.churn, dict):
            object.__setattr__(self, "churn", ChurnPlan(**self.churn))

    def enabled_kinds(self) -> tuple:
        kinds = []
        if self.allow_partition:
            kinds.append(K_PAIR)
        if self.allow_kill:
            kinds.append(K_KILL)
        if self.allow_dir_clog:
            kinds.append(K_DIR)
        if self.allow_group:
            kinds.append(K_GROUP)
        if self.allow_storm:
            kinds.append(K_STORM)
        if self.allow_delay:
            kinds.append(K_DELAY)
        if self.allow_pause:
            kinds.append(K_PAUSE)
        if self.allow_skew:
            kinds.append(K_SKEW)
        if self.allow_torn:
            kinds.append(K_TORN)
        if self.allow_heal_asym:
            kinds.append(K_HEAL_ASYM)
        return tuple(kinds)

    @property
    def uses_v2_kinds(self) -> bool:
        return (
            self.allow_dir_clog or self.allow_group or self.allow_storm
            or self.allow_delay or self.uses_window_kinds
            or self.uses_storage_kinds
        )

    @property
    def uses_window_kinds(self) -> bool:
        """The PR-5 scheduled kinds: they add one draw (the skew q10
        factor) to each fault's v2 derivation — kept behind this flag so
        dir/group/storm/delay-era schedules replay byte-identically."""
        return self.allow_pause or self.allow_skew

    @property
    def uses_storage_kinds(self) -> bool:
        """The PR-6 scheduled kinds (torn / heal_asym): one more
        per-fault draw — the torn damage mask, doubling as the second
        heal duration — taken only when either flag is on, so every
        window-kind-era schedule replays byte-identically."""
        return self.allow_torn or self.allow_heal_asym

    @property
    def slots_per_fault(self) -> int:
        """Event-queue slots each fault occupies. Asymmetric healing
        needs a third slot (the second direction's heal); it is drawn
        for every fault when the kind is enabled and left INVALID for
        non-heal_asym kinds, so it never perturbs them (an invalid slot
        is ordinary free queue space)."""
        return 3 if self.allow_heal_asym else 2


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine parameters (python-level; baked into the jit)."""

    horizon_us: int = 10_000_000  # 10 virtual seconds
    queue_capacity: int = 64
    latency_min_us: int = 1_000  # matches host NetConfig default 1-10ms
    latency_max_us: int = 10_000
    packet_loss_rate: float = 0.0
    handler_rand_words: int = 4
    faults: FaultPlan = dataclasses.field(default_factory=FaultPlan)
    # On-device event ring: keep the last `trace_ring` events per lane in
    # HBM so a failing lane has an immediate post-mortem without a full
    # replay (0 = off; the ring costs [lanes, trace_ring] masked writes
    # per step). Contents match the replay trace exactly (tests assert).
    trace_ring: int = 0
    # Per-step RNG stream version (ops/step_rng.py): 2 = legacy
    # split-chain (the seed-era stream — the default, so every recorded
    # seed and corpus entry replays byte-identically), 3 = counter-based
    # (one threefry per event, block sized to what this config can
    # consume — the fast stream new hunts should opt into). Corpus
    # entries record the version; entries predating the field are v2.
    rng_stream: int = RNG_STREAM_LEGACY
    # Clog-state representation: True packs each node's outbound clog
    # row into two int32 words (30 bits each, the group-mask encoding)
    # instead of an [N, N] bool matrix — fault-branch outer products
    # become word-wise bit ops and per-lane HBM state shrinks. Pure
    # representation swap: results are bit-identical either way (tests
    # assert); False keeps the bool-matrix oracle. Requires N <= 60.
    clog_packed: bool = True
    # Flight recorder (observability): a rolling per-lane trace digest —
    # a uint32[2] fold over each popped (time, kind, node, src, payload)
    # tuple plus the step-RNG word block — checkpointed into a small
    # on-device ring every `fr_digest_every` steps, plus on-device
    # fault-injection / queue / clog occupancy metrics. Rides the
    # existing result harvest (zero extra host syncs); the gate-off path
    # is bit-identical (tests assert). Two digest trails agree exactly
    # as far as the two executions agree, so the first divergent
    # checkpoint localizes a determinism break to one segment —
    # `python -m madsim_tpu audit` (engine/audit.py) is the consumer.
    flight_recorder: bool = False
    fr_digest_every: int = 64  # steps between digest checkpoints
    fr_digest_ring: int = 32  # checkpoints retained per lane (ring)
    # Scenario-coverage telemetry (observability): every popped event
    # hashes (model abstract-state projection, event kind, fault
    # context) into a per-lane AFL-style uint8 saturating-count map
    # (ops/coverage.py; 2^cov_slots_log2 slots, banded
    # [band|phase|mix] layout so the host can decode per-fault-kind and
    # per-phase marginals). The stream harvest OR-reduces lanes into one
    # device vector — zero extra host syncs, same discipline as the
    # flight recorder — and run_stream stats gain "coverage" (slots
    # hit / fraction / curve). The signal behind `--stop-on-plateau`:
    # a hunt that stops adding slots has saturated its scenario space.
    # Gate-off is bit-identical (tests assert); ON is also
    # result-identical — the map is write-only telemetry.
    coverage: bool = False
    cov_slots_log2: int = COV_SLOTS_LOG2_DEFAULT
    # Coverage band-layout floor: 0 = derive from the fault vocabulary
    # as always (3-bit legacy, 4-bit when a PR-5+ capability is on —
    # every recorded map keeps its layout and golden slot constants).
    # A guided hunt (madsim_tpu/search) pins 4 so the slot space stays
    # IDENTICAL across fault-vocabulary escalations: cumulative maps,
    # plateau deltas and parent detection must compare bits from every
    # escalation step in one address space. Write-only telemetry
    # layout, never result-affecting; excluded from corpus configs
    # like the other coverage knobs.
    cov_band_bits_min: int = 0
    # Per-lane coverage slot-buffer depth (flush-on-freeze buffered
    # fold, r12): > 0 buffers each popped event's slot index in a tiny
    # int32[cov_buffer] per-lane ring and folds the packed bit map only
    # on a fixed segment cadence, at segment exit, and therefore at
    # every freeze point — removing the per-event map RMW scatter.
    # 0 = the unbuffered per-event scatter (the differential oracle).
    # Final maps are bit-identical either way — OR is commutative and
    # idempotent, and the executor's segment-exit flush runs
    # unconditionally, so frozen lanes can never strand buffered slots.
    # Host-side perf knob: excluded from corpus serialization with the
    # other coverage knobs.
    cov_buffer: int = COV_BUFFER_DEFAULT
    # Causal provenance (observability): every queued event and every
    # node carries a 32-bit provenance word — one bit per scheduled
    # fault slot (bits 30/31: strict-restart wipes / duplicate
    # deliveries), ORed along deliveries: a delivered message folds its
    # lineage into the receiver, an injected fault plants its slot bit
    # on the nodes it touches, timers and sends inherit their node's
    # word. The violating lane's word is captured at the first invariant
    # failure and rides the existing failure-ring harvest — zero extra
    # host syncs, same discipline as recorder/coverage. Consumers:
    # per-find fault attribution in run_stream/hunt reports,
    # provenance-guided shrink (engine/shrink.py ablates non-implicated
    # faults first), and `python -m madsim_tpu why` (engine/
    # provenance.py decodes the word against the seed's re-derived
    # fault schedule and cuts the replay trace to the violation's past
    # cone). Consumes NO RNG words; gate-off is bit-identical (tests
    # assert under both stream versions).
    provenance: bool = False
    # Whole-event Pallas step megakernel (ops/pallas_pop.py): the
    # model-independent prefix of the step — lexicographic-argmin pop,
    # popped-tuple gather, the counter-based v3 RNG word block
    # (in-kernel Threefry-2x32, bit-exact vs jax's primitive) and,
    # under the flight recorder, the whole digest fold — fused into ONE
    # VMEM pass per lane block. None = auto: ON when the backend is TPU
    # and rng_stream is 3; MADSIM_TPU_PALLAS_MEGAKERNEL=0/1 forces
    # either way. Requires rng_stream=3 (the word block IS the v3
    # counter derivation). Pure fusion: results are bit-identical to
    # the XLA path, which stays the oracle (tests assert end-to-end
    # and per-kernel in interpreter mode). Host-side perf knob —
    # excluded from corpus serialization like compile_cache_dir.
    pallas_megakernel: Optional[bool] = None
    # JAX persistent compilation cache directory (also
    # $MADSIM_TPU_COMPILE_CACHE; None = the checkout default
    # `compile_cache.DEFAULT_CACHE_DIR`): hunts and sweeps pay each
    # multi-second compile once per machine instead of once per process.
    # $JAX_COMPILATION_CACHE_DIR, where set, wins over this field.
    # Host-side knob — never affects traces/results and is excluded
    # from corpus serialization.
    compile_cache_dir: Optional[str] = None


@struct.dataclass
class LaneState:
    now_us: jax.Array
    next_seq: jax.Array
    step: jax.Array
    rng_key: jax.Array  # uint32[2]
    done: jax.Array
    failed: jax.Array
    fail_code: jax.Array
    horizon_hit: jax.Array
    msg_count: jax.Array
    storm_loss: jax.Array  # int32: active storm loss rate in 1/65536 (0 = none)
    delay_spike: jax.Array  # int32: 1 while a delay-spike window is active
    eq_time: jax.Array  # int32[Q]
    eq_seq: jax.Array  # int32[Q]
    eq_kind: jax.Array  # int32[Q]
    eq_node: jax.Array  # int32[Q]
    eq_src: jax.Array  # int32[Q]
    eq_payload: jax.Array  # int32[Q, P]
    eq_valid: jax.Array  # bool[Q]
    clogged: jax.Array  # int32[N, CLOG_WORDS] packed rows (clog_packed) | bool[N, N]
    killed: jax.Array  # bool[N]
    # pause/skew windows: int32[N] when the kind is enabled, int32[0]
    # otherwise (the leaf exists so the pytree structure is uniform, but
    # a disabled kind carries — and computes — nothing)
    paused_until: jax.Array  # virtual us the node resumes at (0 = running)
    skew_q10: jax.Array  # active q10 timer-delay multiplier (0 = none)
    # causal provenance (EngineConfig.provenance): uint32 lineage words —
    # uint32[N] per node / uint32[Q] per queued event / uint32 scalar
    # captured at the first invariant failure; uint32[0] when the gate
    # is off (the leaves exist so the pytree structure is uniform, but a
    # disabled gate carries — and computes — nothing)
    node_prov: jax.Array
    eq_prov: jax.Array
    fail_prov: jax.Array
    nodes: Any
    ring: Any  # {} when trace_ring == 0, else dict of [R]/[R,P] arrays
    fr: Any  # {} unless flight_recorder: digest + checkpoint ring + metrics
    # {} unless coverage: {"map": int32[2^cov_slots_log2 / 32] bit words};
    # the buffered regime (cov_buffer > 0) adds {"buf": int32[cov_buffer]
    # pending slot indices, "buf_n": int32 live-entry count} — flushed
    # into "map" by run_segment's cadence/exit folds
    cov: Any
    # {} unless FaultPlan.churn: {"key": uint32[2] of the churn stream,
    # "down": int32 bitmask of the disconnected nodes, "until_us": int32
    # (a value, so shrink's candidates share one program), "last":
    # int32[2] node masks the latest churn event cut off / brought back
    # (what `differential.applied_churn_faults` reads), and the
    # applied-fault counters "ticks" / "disconnects" / "reconnects"}.
    # An empty dict has no leaf: a program without churn is the program
    # it was.
    churn: Any = struct.field(default_factory=dict)


@struct.dataclass
class StreamCarry:
    """Device-resident streaming state: lanes + seed counter + result
    rings. Everything run_stream needs per segment lives on-device; the
    host fetches only `counters` (one small uint32[6] transfer) and
    drains the rings when they near capacity."""

    state: LaneState
    seeds: jax.Array  # uint32[L] — seed currently owned by each lane
    done: jax.Array  # bool[L] — harvest mask; refilled at next segment start
    next_seed: jax.Array  # uint32 scalar
    completed: jax.Array  # int32 scalar
    segments: jax.Array  # int32 scalar — segments executed on device
    fail_seeds: jax.Array  # uint32[C]
    fail_codes: jax.Array  # int32[C]
    fail_provs: jax.Array  # uint32[C] violation provenance words ([0] when off)
    fail_count: jax.Array  # int32 scalar
    ab_seeds: jax.Array  # uint32[C]
    ab_count: jax.Array  # int32 scalar
    counters: jax.Array  # uint32[7]: completed, fail_count, ab_count, next_seed, flags, segments, cov_slots_hit
    fr_metrics: jax.Array  # int32[FR_METRICS_LEN] flight-recorder totals ([0] when off)
    cov_map: jax.Array  # int32[2^cov_slots_log2 / 32] global OR of lane bit maps ([0] when off)


@struct.dataclass
class BatchResult:
    seeds: jax.Array
    done: jax.Array
    failed: jax.Array
    fail_code: jax.Array
    fail_prov: jax.Array  # uint32[L] violation provenance words ([L, 0] when off)
    now_us: jax.Array
    steps: jax.Array
    msg_count: jax.Array
    summary: Any
    ring: Any  # per-lane event rings ({} unless config.trace_ring > 0)
    fr: Any  # per-lane flight-recorder state ({} unless flight_recorder)
    cov: Any  # per-lane coverage maps ({} unless config.coverage)


class Engine:
    """Bind a Machine + EngineConfig into jittable batch/replay runners."""

    def __init__(
        self,
        machine: Machine,
        config: EngineConfig = EngineConfig(),
        use_pallas_pop: Optional[bool] = None,
    ):
        self.machine = machine
        self.config = config
        enable_compile_cache(config.compile_cache_dir)
        # Batched event-pop backend: the fused Pallas pop+gather kernel
        # (ops/pallas_pop.py) vs the vmapped XLA reductions. Default ON
        # when the backend is TPU; the XLA path stays the default
        # elsewhere and the bit-identity oracle everywhere.
        # MADSIM_TPU_PALLAS_POP=0/1 (or the constructor arg) forces
        # either way; the CLI builds a meshed engine (--devices N > 1)
        # with both kernels off, because pallas_call blocks sharding
        # propagation. Resolved once at construction so jit caches stay
        # consistent. A selected kernel either runs or raises — there
        # is no fallback: on a TPU it compiles through Mosaic, and only
        # a kernel FORCED on another backend runs in interpreter mode
        # (slow — for equivalence tests, not production).
        if use_pallas_pop is None:
            env = os.environ.get("MADSIM_TPU_PALLAS_POP", "")
            if env == "":
                import jax as _jax

                use_pallas_pop = _jax.default_backend() == "tpu"
            else:
                use_pallas_pop = env != "0"
        self.use_pallas_pop = bool(use_pallas_pop)
        # Whole-event step megakernel (EngineConfig.pallas_megakernel /
        # MADSIM_TPU_PALLAS_MEGAKERNEL): resolved like the pop kernel —
        # auto means ON only on TPU — plus the static requirement that
        # the stream is v3 (the kernel computes the counter-based word
        # block; v2's split-chain key evolution is inherently
        # sequential host..er, XLA-side). A forced-on megakernel off-TPU
        # runs in interpreter mode (equivalence tests, not production).
        mk = config.pallas_megakernel
        if mk is None:
            env_mk = os.environ.get("MADSIM_TPU_PALLAS_MEGAKERNEL", "")
            if env_mk == "":
                import jax as _jax

                mk = _jax.default_backend() == "tpu"
            else:
                mk = env_mk != "0"
            # auto/env resolution degrades gracefully on a v2 engine
            # (legacy replays, shrink of recorded seeds): the kernel
            # simply cannot serve that stream, so it stays off
            mk = mk and config.rng_stream == RNG_STREAM_COUNTER
        elif mk and config.rng_stream != RNG_STREAM_COUNTER:
            # explicitly requested on a v2 engine is a config error
            raise ValueError(
                "pallas_megakernel requires rng_stream=3 (the kernel "
                "computes the counter-based v3 word block in the same "
                "VMEM pass as the pop; v2's per-step key split-chain "
                "cannot be expressed as a counter)"
            )
        self.use_megakernel = bool(mk)
        if self.use_pallas_pop or self.use_megakernel:
            import jax as _jax

            self._pallas_interpret = _jax.default_backend() != "tpu"
        else:
            self._pallas_interpret = False
        n, q = machine.NUM_NODES, config.queue_capacity
        fp = config.faults
        # the churn process holds ONE slot, however many faults it applies
        min_slots = (
            n + fp.slots_per_fault * fp.n_faults + (fp.churn is not None)
        )
        if q < min_slots + machine.MAX_MSGS + machine.MAX_TIMERS:
            raise ValueError(
                f"queue_capacity={q} too small for {n} nodes + "
                f"{config.faults.n_faults} faults + outbox headroom"
            )
        if fp.churn is not None:
            if not 2 <= n <= CLOG_WORD_BITS:
                raise ValueError(
                    f"the churn process keeps the disconnected set as one "
                    f"int32 bitmask: 2 <= NUM_NODES <= {CLOG_WORD_BITS}"
                )
            if fp.churn_until_us <= 0:
                raise ValueError(
                    "FaultPlan.churn needs churn_until_us > 0 (the virtual "
                    "time at which every node is reconnected)"
                )
            if min(fp.churn.long_sleep_us, fp.churn.short_sleep_us) < 1:
                raise ValueError("ChurnPlan sleeps are U[0, x) us with x >= 1")
            if fp.churn.kind not in CHURN_KINDS:
                raise ValueError(
                    f"ChurnPlan.kind {fp.churn.kind!r}: one of {CHURN_KINDS}"
                )
            if fp.churn.kind == "kv3a" and min(
                fp.churn.period_us, fp.churn.jitter_us, fp.churn.restart_after_us
            ) < 1:
                raise ValueError(
                    "ChurnPlan kv3a: period_us, jitter_us and "
                    "restart_after_us are >= 1"
                )
        # the nodes a kv3a process acts on, ascending (all by default)
        self._churn_nodes = tuple(sorted(
            (machine.churn_nodes() or range(n)) if fp.churn is not None else ()
        ))
        if self._churn_nodes and not (
            0 <= self._churn_nodes[0] and self._churn_nodes[-1] < n
        ):
            raise ValueError(
                f"{type(machine).__name__}.churn_nodes() {self._churn_nodes} "
                f"names nodes outside 0..{n - 1}"
            )
        self._churn_majority = (
            (fp.churn.majority or n // 2 + 1) if fp.churn is not None else 0
        )
        # StreamCarry.fr_metrics: the recorder's totals, the churn
        # process's counters after them; [0] with the recorder off
        # and after those the machine's own totals (`Machine.
        # STREAM_COUNTERS`: none on a machine that declares none)
        self._fr_metrics_len = (
            FR_METRICS_LEN
            + (len(churn_counter_names(fp.churn)) if fp.churn is not None else 0)
            + len(machine.STREAM_COUNTERS)
        ) if config.flight_recorder else 0
        _check_lane_spec(machine)
        if fp.n_faults > 0 and not fp.enabled_kinds():
            raise ValueError("FaultPlan has n_faults > 0 but every kind disabled")
        if fp.allow_group and (n < 2 or n > 60):
            raise ValueError(
                "group partitions need 2 <= NUM_NODES <= 60 (two-word "
                "int32 bitmask: payload args 1+2 carry 30 bits each)"
            )
        if not 0 <= fp.storm_loss_u16 <= 65535:
            raise ValueError("storm_loss_u16 must be in [0, 65535]")
        if config.clog_packed and n > CLOG_MAX_NODES:
            raise ValueError(
                f"clog_packed needs NUM_NODES <= {CLOG_MAX_NODES} (two-word "
                f"int32 rows); pass EngineConfig(clog_packed=False) for "
                f"{n} nodes"
            )
        if config.rng_stream not in RNG_STREAM_VERSIONS:
            raise ValueError(
                f"rng_stream={config.rng_stream!r} unknown; supported "
                f"versions: {RNG_STREAM_VERSIONS}"
            )
        if config.flight_recorder and (
            config.fr_digest_every < 1 or config.fr_digest_ring < 1
        ):
            raise ValueError(
                "flight_recorder needs fr_digest_every >= 1 and "
                "fr_digest_ring >= 1"
            )
        if fp.strict_restart and fp.allow_kill and machine.durable_spec() is None:
            raise ValueError(
                f"strict_restart (crash-with-amnesia) needs "
                f"{type(machine).__name__}.durable_spec() to declare the "
                f"durable-state contract (which leaves survive restart)"
            )
        if fp.allow_torn:
            spec = machine.durable_spec()
            if spec is None:
                raise ValueError(
                    f"allow_torn (torn/lost-write storage faults) needs "
                    f"{type(machine).__name__}.durable_spec() to declare "
                    f"the durable-state contract the torn restart damages"
                )
            tspec = machine.torn_spec()
            if tspec is not None:
                from .machine import TORN_ATOMIC, TORN_LOSE, TORN_PREFIX

                bad = [
                    c for c in jax.tree.leaves(tspec)
                    if c not in (TORN_ATOMIC, TORN_LOSE, TORN_PREFIX)
                ]
                if bad or jax.tree.structure(tspec) != jax.tree.structure(spec):
                    raise ValueError(
                        f"{type(machine).__name__}.torn_spec() must be "
                        f"congruent to durable_spec() with every leaf in "
                        f"{{TORN_ATOMIC, TORN_LOSE, TORN_PREFIX}}"
                    )
        # Coverage banded-slot layout version: the band field grows to 4
        # bits whenever any PR-5 chaos capability can occur (those are
        # new configs by definition, so every historical map keeps its
        # 3-bit layout and its golden slot constants).
        if config.cov_band_bits_min not in (0, 3, 4):
            raise ValueError(
                f"cov_band_bits_min={config.cov_band_bits_min!r} — "
                f"0 (derive), 3 or 4 are the known banded layouts"
            )
        self.cov_band_bits = max(
            config.cov_band_bits_min,
            4
            if (fp.allow_pause or fp.allow_skew or fp.allow_dup
                or fp.strict_restart or fp.allow_torn or fp.allow_heal_asym)
            else 3,
        )
        min_log2 = self.cov_band_bits + 3 + 1
        if config.coverage and not min_log2 <= config.cov_slots_log2 <= 20:
            raise ValueError(
                f"coverage needs {min_log2} <= cov_slots_log2 <= 20 "
                f"({self.cov_band_bits} band bits + 3 phase bits + at "
                f"least 1 mix bit; 2^20 slots = 1 MiB per lane is "
                f"already past any sane map size)"
            )
        # Static step-RNG block layout + compute-elision flags: which
        # chaos draws this (config, machine) pair can ever consume.
        # Deliberately independent of n_faults (kind FLAGS only): shrink
        # bisects n_faults per candidate, and the layout staying fixed
        # keeps (a) the v3 stream identical across candidates and (b)
        # the compiled-replay cache shared (one lane_step compile serves
        # every candidate — the r5 hunt-throughput fix relies on it).
        self._rng_layout = layout_for(
            config.rng_stream,
            config.handler_rand_words,
            machine.MAX_MSGS,
            loss_possible=config.packet_loss_rate > 0 or fp.allow_storm,
            spike_possible=fp.allow_delay,
            delay_enabled=fp.allow_delay,
            # torn restarts re-init through the machine like kill
            # restarts do, so they need the restart key too
            restart_possible=fp.allow_kill or fp.allow_torn,
            dup_possible=fp.allow_dup,
            torn_possible=fp.allow_torn,
        )
        # Buffered-coverage flush cadence: a step appends at most
        # `slots_per_step` slots (the popped event, plus the synthetic
        # dup-band slot when Bernoulli duplicates can occur), so
        # flushing every cov_buffer // slots_per_step segment
        # iterations makes buffer overflow impossible by construction —
        # no per-event overflow branch exists, because a masked
        # fallback fold would put the map RMW right back into every
        # step's program (the cost the buffer removes). Validated
        # here, after _rng_layout, because slots_per_step needs
        # layout.dup_active.
        self._cov_slots_per_step = 2 if self._rng_layout.dup_active else 1
        if config.cov_buffer < 0 or config.cov_buffer > 1024:
            raise ValueError(
                f"cov_buffer={config.cov_buffer!r} — 0 (unbuffered "
                f"per-event fold) or a depth in "
                f"[{self._cov_slots_per_step}, 1024]"
            )
        if config.coverage and 0 < config.cov_buffer < self._cov_slots_per_step:
            raise ValueError(
                f"cov_buffer={config.cov_buffer} is shallower than the "
                f"{self._cov_slots_per_step} slots one step can append "
                f"under this config (dup events add a synthetic band "
                f"slot); use 0 for the unbuffered fold or >= "
                f"{self._cov_slots_per_step}"
            )
        self._cov_buffered = bool(config.coverage and config.cov_buffer > 0)
        self._cov_flush_every = (
            config.cov_buffer // self._cov_slots_per_step
            if self._cov_buffered
            else 0
        )

    @classmethod
    def on_xla_step_path(cls, machine: Machine, config: EngineConfig) -> "Engine":
        """An engine with both Pallas kernels off whatever the backend:
        the bit-identity oracle for the kernels, and the only step path
        a meshed run can take (pallas_call blocks GSPMD sharding
        propagation, so `run_stream(mesh=...)` refuses a kernel)."""
        return cls(
            machine,
            dataclasses.replace(config, pallas_megakernel=False),
            use_pallas_pop=False,
        )

    # -- lane init -----------------------------------------------------------

    def init_lane(self, seed) -> LaneState:
        m, cfg = self.machine, self.config
        n, q, p = m.NUM_NODES, cfg.queue_capacity, m.PAYLOAD_WIDTH
        key = jax.random.PRNGKey(seed)
        key, k_init, k_faults = jax.random.split(key, 3)
        nodes = m.init(k_init)

        # BOOT timers for every node at t=0 in slots [0, n) (analogue of
        # node init closures); all arrays built by static masks, no scatters.
        slots = jnp.arange(q, dtype=jnp.int32)
        is_boot_slot = slots < n
        eq_time = jnp.zeros((q,), jnp.int32)
        eq_seq = jnp.where(is_boot_slot, slots, 0)
        eq_kind = jnp.zeros((q,), jnp.int32)  # EV_TIMER == 0
        eq_node = jnp.where(is_boot_slot, slots, 0)
        eq_src = jnp.full((q,), -1, jnp.int32)
        eq_payload = jnp.zeros((q, p), jnp.int32)  # timer id BOOT == 0
        eq_valid = is_boot_slot
        next_seq = n
        # provenance: boot timers are causal roots (word 0); each fault
        # slot carries its fault's bit so processing the event plants it
        eq_prov = jnp.zeros((q if cfg.provenance else 0,), jnp.uint32)

        # Fault schedule: apply + undo event per fault, slots [n, n+2F).
        fp = cfg.faults
        for f in range(fp.n_faults):
            if not fp.uses_v2_kinds:
                # v1 derivation (partition/kill) — byte-stable for replay
                # of historically found seeds
                k_faults, k1, k2, k3, k4, k5 = jax.random.split(k_faults, 6)
                t = jnp.int32(fp.t_min_us) + (
                    jax.random.bits(k1, (), jnp.uint32) % jnp.uint32(fp.t_max_us - fp.t_min_us)
                ).astype(jnp.int32)
                dur = jnp.int32(fp.dur_min_us) + (
                    jax.random.bits(k2, (), jnp.uint32) % jnp.uint32(fp.dur_max_us - fp.dur_min_us)
                ).astype(jnp.int32)
                a = (jax.random.bits(k3, (), jnp.uint32) % jnp.uint32(n)).astype(jnp.int32)
                b_off = 1 + (jax.random.bits(k4, (), jnp.uint32) % jnp.uint32(n - 1)).astype(
                    jnp.int32
                )
                b = (a + b_off) % n
                if fp.allow_partition and fp.allow_kill:
                    is_part = (jax.random.bits(k5, (), jnp.uint32) % 2) == 0
                elif fp.allow_partition:
                    is_part = jnp.bool_(True)
                else:
                    is_part = jnp.bool_(False)
                op_apply = jnp.where(is_part, F_CLOG_PAIR, F_KILL).astype(jnp.int32)
                op_undo = jnp.where(is_part, F_UNCLOG_PAIR, F_RESTART).astype(jnp.int32)
                arg1, arg2 = a, b
            else:
                # v2 derivation: uniform over the enabled kind set; every
                # argument is drawn unconditionally (constant draw count
                # keeps the schedule stable under config flag flips that
                # don't change the kind list)
                k_faults, k1, k2, k3, k4, k5, k6 = jax.random.split(k_faults, 7)
                t = jnp.int32(fp.t_min_us) + (
                    jax.random.bits(k1, (), jnp.uint32) % jnp.uint32(fp.t_max_us - fp.t_min_us)
                ).astype(jnp.int32)
                dur = jnp.int32(fp.dur_min_us) + (
                    jax.random.bits(k2, (), jnp.uint32) % jnp.uint32(fp.dur_max_us - fp.dur_min_us)
                ).astype(jnp.int32)
                a = (jax.random.bits(k3, (), jnp.uint32) % jnp.uint32(n)).astype(jnp.int32)
                b_off = 1 + (jax.random.bits(k4, (), jnp.uint32) % jnp.uint32(n - 1)).astype(
                    jnp.int32
                )
                b = (a + b_off) % n
                kinds = jnp.asarray(fp.enabled_kinds(), jnp.int32)
                kind = kinds[jax.random.bits(k5, (), jnp.uint32) % jnp.uint32(len(kinds))]
                # Group masks: payload arg1 carries bits [0, 30), arg2
                # bits [30, 60) — two int32 words, so group partitions
                # scale past the old 30-node cap (lifted round 5; the
                # constructor rejects n > 60). The low draw keeps the
                # historical ≤30-node derivation byte-stable; the high
                # word is drawn ONLY for n > 30 machines (new since the
                # lift), so recorded seeds replay unchanged.
                lo_bits = min(n, 30)
                mask_lo = 1 + (
                    jax.random.bits(k6, (), jnp.uint32) % jnp.uint32(2 ** lo_bits - 2)
                ).astype(jnp.int32)
                if n > 30:
                    k_faults, k7 = jax.random.split(k_faults)
                    hi_bits = n - 30
                    mask_hi = (
                        jax.random.bits(k7, (), jnp.uint32) % jnp.uint32(2 ** hi_bits)
                    ).astype(jnp.int32)
                else:
                    mask_hi = jnp.int32(0)
                op_apply = (2 * kind).astype(jnp.int32)
                op_undo = (2 * kind + 1).astype(jnp.int32)
                arg1 = jnp.where(
                    kind == K_GROUP,
                    mask_lo,
                    jnp.where(kind == K_STORM, jnp.int32(fp.storm_loss_u16), a),
                )
                arg2 = jnp.where(kind == K_GROUP, mask_hi, b)
                if fp.uses_window_kinds:
                    # one extra draw — the skew q10 factor — taken only
                    # when pause/skew are in the vocabulary, so every
                    # dir/group/storm/delay-era schedule stays
                    # byte-stable. Drawn unconditionally (constant draw
                    # count) like every other v2 argument.
                    k_faults, k8 = jax.random.split(k_faults)
                    skew_q10 = jnp.int32(SKEW_Q10_MIN) + (
                        jax.random.bits(k8, (), jnp.uint32)
                        % jnp.uint32(SKEW_Q10_SPAN)
                    ).astype(jnp.int32)
                    # pause: arg2 carries the resume time (the undo
                    # event's own timestamp), so the defer target needs
                    # no extra state; skew: arg2 carries the factor
                    arg2 = jnp.where(
                        kind == K_PAUSE,
                        t + dur,
                        jnp.where(kind == K_SKEW, skew_q10, arg2),
                    )
                if fp.uses_storage_kinds:
                    # one more draw — the torn damage mask, doubling as
                    # the heal_asym second-direction duration — taken
                    # only when torn/heal_asym are in the vocabulary, so
                    # every window-kind-era schedule stays byte-stable.
                    # Drawn unconditionally (constant draw count); a
                    # fault is exactly one kind, so the word serves
                    # whichever use that kind has.
                    k_faults, k9 = jax.random.split(k_faults)
                    storage_word = jax.random.bits(k9, (), jnp.uint32)
                    # torn: arg2 carries the damage mask (int31 — the
                    # payload is int32 and signs would survive replay,
                    # but non-negative reads cleaner in traces)
                    arg2 = jnp.where(
                        kind == K_TORN,
                        (storage_word & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32),
                        arg2,
                    )
                    dur2 = jnp.int32(fp.dur_min_us) + (
                        storage_word % jnp.uint32(fp.dur_max_us - fp.dur_min_us)
                    ).astype(jnp.int32)
            # slot layout per fault: [apply at t, undo at t+dur] plus,
            # when heal_asym is enabled, a third slot for the second
            # direction's heal at t+dur2 (valid only for heal_asym
            # faults — other kinds leave it invalid, i.e. free space)
            slot_events = [
                (t, op_apply, arg1, arg2, None),
                (t + dur, op_undo, arg1, arg2, None),
            ]
            if fp.allow_heal_asym:
                slot_events.append(
                    (t + dur2, jnp.int32(F_HASYM_HEAL), b, a, kind == K_HEAL_ASYM)
                )
            for slot_off, (tt, op, p1, p2, valid) in enumerate(slot_events):
                i = n + fp.slots_per_fault * f + slot_off
                msk = slots == i
                eq_time = jnp.where(msk, tt, eq_time)
                eq_seq = jnp.where(msk, next_seq + slot_off, eq_seq)
                eq_kind = jnp.where(msk, EV_FAULT, eq_kind)
                eq_node = jnp.where(msk, a, eq_node)
                pay = jnp.stack([op, p1, p2] + [jnp.int32(0)] * (p - 3))
                eq_payload = jnp.where(msk[:, None], pay[None, :], eq_payload)
                eq_valid = eq_valid | (msk if valid is None else (msk & valid))
                if cfg.provenance:
                    eq_prov = jnp.where(
                        msk, jnp.uint32(prov_fault_bit(f)), eq_prov
                    )
            next_seq += fp.slots_per_fault

        # The churn process: ONE slot after the schedule's, holding tick 0
        # (or the heal, where the first sleep already passes the end).
        churn = {}
        if fp.churn is not None:
            ckey = churn_key(seed)
            if fp.churn.kind == "kv3a":
                t0 = jnp.int32(0)  # the partitioner splits, then sleeps
            else:
                t0 = churn_sleep_us(fp.churn, churn_words(ckey, 0))
            over = t0 >= fp.churn_until_us
            msk = slots == n + fp.slots_per_fault * fp.n_faults
            eq_time = jnp.where(
                msk, jnp.where(over, jnp.int32(fp.churn_until_us), t0), eq_time
            )
            eq_seq = jnp.where(msk, next_seq, eq_seq)
            eq_kind = jnp.where(msk, EV_FAULT, eq_kind)
            pay = jnp.stack(
                [jnp.where(over, F_CHURN_HEAL, F_CHURN_TICK).astype(jnp.int32)]
                + [jnp.int32(0)] * (p - 1)
            )
            eq_payload = jnp.where(msk[:, None], pay[None, :], eq_payload)
            eq_valid = eq_valid | msk
            next_seq += 1
            churn = {
                "key": ckey,
                "down": jnp.int32(0),
                "until_us": jnp.int32(fp.churn_until_us),
                "last": jnp.zeros((2,), jnp.int32),
                **{k: jnp.int32(0) for k in churn_counter_names(fp.churn)},
            }

        return LaneState(
            now_us=jnp.int32(0),
            next_seq=jnp.int32(next_seq),
            step=jnp.int32(0),
            rng_key=key,
            done=jnp.bool_(False),
            failed=jnp.bool_(False),
            fail_code=jnp.int32(OK),
            horizon_hit=jnp.bool_(False),
            msg_count=jnp.int32(0),
            storm_loss=jnp.int32(0),
            delay_spike=jnp.int32(0),
            eq_time=eq_time,
            eq_seq=eq_seq,
            eq_kind=eq_kind,
            eq_node=eq_node,
            eq_src=eq_src,
            eq_payload=eq_payload,
            eq_valid=eq_valid,
            clogged=(
                jnp.zeros((n, CLOG_WORDS), jnp.int32)
                if cfg.clog_packed
                else jnp.zeros((n, n), bool)
            ),
            killed=jnp.zeros((n,), bool),
            paused_until=jnp.zeros((n if fp.allow_pause else 0,), jnp.int32),
            skew_q10=jnp.zeros((n if fp.allow_skew else 0,), jnp.int32),
            node_prov=jnp.zeros((n if cfg.provenance else 0,), jnp.uint32),
            eq_prov=eq_prov,
            fail_prov=(
                jnp.uint32(0) if cfg.provenance else jnp.zeros((0,), jnp.uint32)
            ),
            nodes=nodes,
            ring=self._empty_ring(),
            fr=self._empty_fr(eq_valid),
            cov=self._empty_cov(),
            churn=churn,
        )

    def _empty_cov(self):
        """Fresh coverage state: a zeroed per-lane hit map, plus — in
        the buffered regime (cov_buffer > 0) — the per-lane slot buffer
        and its live-entry count. Unbuffered keeps the map-only pytree,
        so cov_buffer=0 states are leaf-for-leaf identical to the
        pre-buffer layout."""
        if not self.config.coverage:
            return {}
        cov = {"map": empty_cov_map(self.config.cov_slots_log2)}
        if self._cov_buffered:
            cov["buf"] = jnp.zeros((self.config.cov_buffer,), jnp.int32)
            cov["buf_n"] = jnp.int32(0)
        return cov

    def _empty_fr(self, eq_valid=None):
        """Fresh flight-recorder state: digest at its IV, empty
        checkpoint ring (step -1 = unused slot), zeroed metrics.
        `eq_valid` (the lane's initial queue-valid plane) seeds the
        incremental occupancy counter `eq_n` — the step tracks queue
        occupancy as (-1 per pop, +1 per push) instead of re-summing
        the [Q] valid plane every event, so the q_hwm metric costs
        O(1) per step. Value-identical to the old per-step sum by
        construction (every pop clears exactly one valid slot, every
        push fills exactly one free slot); the host-oracle metrics
        differential asserts it."""
        cfg = self.config
        if not cfg.flight_recorder:
            return {}
        r = cfg.fr_digest_ring
        return {
            "d0": jnp.uint32(DIGEST_IV0),
            "d1": jnp.uint32(DIGEST_IV1),
            "eq_n": (
                jnp.int32(0) if eq_valid is None
                else eq_valid.sum(dtype=jnp.int32)
            ),
            "ck_step": jnp.full((r,), -1, jnp.int32),
            "ck_d0": jnp.zeros((r,), jnp.uint32),
            "ck_d1": jnp.zeros((r,), jnp.uint32),
            "inj": jnp.zeros((len(FAULT_KIND_NAMES),), jnp.int32),
            "dup": jnp.int32(0),
            "amnesia": jnp.int32(0),
            "q_hwm": jnp.int32(0),
            "clog_hwm": jnp.int32(0),
            "kill_hwm": jnp.int32(0),
        }

    def _empty_ring(self):
        r = self.config.trace_ring
        if not r:
            return {}
        return {
            "step": jnp.full((r,), -1, jnp.int32),
            "time": jnp.zeros((r,), jnp.int32),
            "kind": jnp.zeros((r,), jnp.int32),
            "node": jnp.zeros((r,), jnp.int32),
            "src": jnp.zeros((r,), jnp.int32),
            "payload": jnp.zeros((r, self.machine.PAYLOAD_WIDTH), jnp.int32),
        }

    # -- one event per lane --------------------------------------------------

    def lane_step(self, s: LaneState, horizon_us=None) -> LaneState:
        with _xprof.scope("step.pop"):
            idx, any_valid = pop_earliest(s.eq_time, s.eq_seq, s.eq_valid)
        return self._lane_step_popped(s, idx, any_valid, horizon_us=horizon_us)

    def _lane_step_popped(
        self, s: LaneState, idx, any_valid, popped=None, horizon_us=None,
        active=None, step_block=None,
    ) -> LaneState:
        """lane_step with the event-queue pop hoisted out, so step_batch
        can swap in the batched Pallas kernel for the whole [L, Q] block
        while the rest of the step stays vmapped. `popped`, when given,
        is the pre-gathered (time, kind, node, src, payload) event tuple
        from the fused pop+gather kernel — the 5 per-lane slot gathers
        below disappear; values are bit-identical by construction.

        `active` (traced bool), when given, folds the executor's
        per-lane freeze (a done/failed lane must pass through untouched)
        into the step's OWN write masks: every state write below is
        already a masked select, so gating the masks costs a handful of
        scalar ANDs — where the old `tree_where(active, new, state)`
        wrapper in step_batch re-selected every [L, Q] queue leaf and
        the whole nodes tree each step. `None` keeps the ungated step
        (replay paths freeze externally). Results are bit-identical:
        an inactive lane's every field provably writes back its old
        value.

        `horizon_us` optionally overrides the config horizon with a
        TRACED value — identical arithmetic, but one compiled replay
        serves every horizon candidate (shrink bisects the horizon
        per-seed; baking it would recompile per candidate).

        `step_block`, when given, is the megakernel's precomputed
        `(words,)` or `(words, nd0, nd1)` tuple — the v3 RNG word block
        (and, under the flight recorder, the already-folded digest)
        from the fused Pallas pass. The step then draws nothing and
        folds nothing itself; values are bit-identical by the kernel
        contract. Only meaningful on a v3 engine (the lane key is
        immutable; the restart key is a block slice)."""
        m, cfg = self.machine, self.config

        with _xprof.scope("step.pop"):
            if popped is None:
                ev_time = get_at(s.eq_time, idx)
                ev_kind = get_at(s.eq_kind, idx)
                ev_node = get_at(s.eq_node, idx)
                ev_src = get_at(s.eq_src, idx)
                ev_payload = get_at(s.eq_payload, idx)
            else:
                ev_time, ev_kind, ev_node, ev_src, ev_payload = popped

            if cfg.provenance:
                # the popped event's lineage word (fault slots carry their
                # bit from init; messages/timers carry their sender's word)
                ev_prov = get_at(s.eq_prov, idx)

            new_now = jnp.maximum(s.now_us, ev_time)
            hz = cfg.horizon_us if horizon_us is None else horizon_us
            # `live` = this lane pops an event this step (frozen lanes never
            # do; their popped tuple is junk-but-deterministic and every use
            # below is gated on live/process/effective)
            live = any_valid if active is None else any_valid & active
            horizon_hit = live & (new_now >= hz)
            process = live & ~horizon_hit
            node_alive = ~get_at(s.killed, ev_node)
            # pause windows: a handler event targeting a paused (alive) node
            # is DEFERRED — the popped slot stays valid and only its time
            # moves to the node's resume point (the state survives, nothing
            # is processed, nothing is dropped). Kill still dominates: a
            # dead node's events are consumed as before. The deferred pop
            # itself is a popped event (trace ring / digest / coverage see
            # it) — host replay pops it identically, so the contract holds.
            if cfg.faults.allow_pause:
                node_resume_us = get_at(s.paused_until, ev_node)
                defer = (
                    process
                    & (ev_kind != EV_FAULT)
                    & node_alive
                    & (node_resume_us > new_now)
                )
            else:
                defer = None
            pop_mask = (jnp.arange(s.eq_valid.shape[0]) == idx) & live
            if defer is not None:
                pop_mask = pop_mask & ~defer
            eq_valid = s.eq_valid & ~pop_mask

        with _xprof.scope("step.recorder"):
            # on-device trace ring: record every popped event (same condition
            # as the replay trace: popped, processed or not)
            ring = s.ring
            if cfg.trace_ring:
                slot = (jnp.arange(cfg.trace_ring) == s.step % cfg.trace_ring) & live
                ring = {
                    "step": jnp.where(slot, s.step, ring["step"]),
                    "time": jnp.where(slot, ev_time, ring["time"]),
                    "kind": jnp.where(slot, ev_kind, ring["kind"]),
                    "node": jnp.where(slot, ev_node, ring["node"]),
                    "src": jnp.where(slot, ev_src, ring["src"]),
                    "payload": jnp.where(slot[:, None], ev_payload[None, :], ring["payload"]),
                }

        with _xprof.scope("step.rng"):
            # One batched draw covers the step's randomness (handler words,
            # per-message latency draws, and whatever chaos draws this
            # config can consume). The block layout and draw count are the
            # versioned stream contract (ops/step_rng.py): v2 is the legacy
            # split-chain (two threefry invocations, fixed block), v3 is
            # counter-based off the immutable lane key and the step index
            # (ONE threefry invocation, block sized to the enabled config).
            layout = self._rng_layout
            if step_block is None:
                key, step_words, k_restart = draw_step_words(s.rng_key, s.step, layout)
            else:
                # megakernel path: the word block arrived from the fused
                # Pallas pass. v3 semantics exactly — the lane key is
                # immutable and the restart key is the block's restart
                # slice (step_words_v3's contract).
                step_words = step_block[0]
                key = s.rng_key
                if layout.restart_off is not None:
                    k_restart = step_words[layout.restart_off : layout.restart_off + 2]
                else:
                    k_restart = jnp.zeros((2,), jnp.uint32)
            rand_u32 = step_words[: layout.handler_words]
            if active is not None and layout.version == RNG_STREAM_LEGACY:
                # v2's key evolves per step — freeze it with the lane
                # (v3's lane key is immutable, nothing to gate)
                key = jnp.where(active, key, s.rng_key)

        with _xprof.scope("step.handlers"):
            def timer_branch(_):
                nodes, outbox = m.on_timer(s.nodes, ev_node, ev_payload[0], new_now, rand_u32)
                return (nodes, outbox, s.clogged, s.killed, s.storm_loss,
                        s.delay_spike, s.paused_until, s.skew_q10, jnp.int32(-1))

            def msg_branch(_):
                nodes, outbox = m.on_message(s.nodes, ev_node, ev_src, ev_payload, new_now, rand_u32)
                return (nodes, outbox, s.clogged, s.killed, s.storm_loss,
                        s.delay_spike, s.paused_until, s.skew_q10, jnp.int32(-1))

            def fault_branch(_):
                op, a, b = ev_payload[0], ev_payload[1], ev_payload[2]
                nn = s.killed.shape[0]
                pair_val = op == F_CLOG_PAIR
                touch_pair = (op == F_CLOG_PAIR) | (op == F_UNCLOG_PAIR)
                dir_val = op == F_CLOG_DIR
                touch_dir = (op == F_CLOG_DIR) | (op == F_UNCLOG_DIR)
                if cfg.faults.allow_heal_asym:
                    # asymmetric partition: the apply op clogs the pair both
                    # ways (pair word ops); each F_HASYM_HEAL op unclogs the
                    # single direction arg1->arg2 (the dir word ops with
                    # dir_val False), so the two heals land independently
                    pair_val = pair_val | (op == F_HASYM)
                    touch_pair = touch_pair | (op == F_HASYM)
                    touch_dir = touch_dir | (op == F_HASYM_HEAL)
                touch_group = (op == F_CLOG_GROUP) | (op == F_UNCLOG_GROUP)
                idxs = jnp.arange(nn)
                # group membership: `a` carries mask bits [0, 30), `b` bits
                # [30, 60) — nodes inside the group partition from the rest
                in_g = jnp.where(
                    idxs < 30,
                    (a >> jnp.clip(idxs, 0, 29)) & 1,
                    (b >> jnp.clip(idxs - 30, 0, 29)) & 1,
                ).astype(bool)
                if cfg.clog_packed:
                    # word-wise bit ops on the two-int32 rows: each fault
                    # event touches O(N) words, not an [N, N] outer product
                    w0, w1 = s.clogged[:, 0], s.clogged[:, 1]

                    def apply_bit(w0, w1, row_mask, bit_lo, bit_hi, val, touch):
                        msk = touch & row_mask
                        nw0 = jnp.where(val, w0 | bit_lo, w0 & ~bit_lo)
                        nw1 = jnp.where(val, w1 | bit_hi, w1 & ~bit_hi)
                        return jnp.where(msk, nw0, w0), jnp.where(msk, nw1, w1)

                    a_lo, a_hi = _clog_bit_words(a)
                    b_lo, b_hi = _clog_bit_words(b)
                    # pair partition: both directions
                    w0, w1 = apply_bit(w0, w1, idxs == a, b_lo, b_hi, pair_val, touch_pair)
                    w0, w1 = apply_bit(w0, w1, idxs == b, a_lo, a_hi, pair_val, touch_pair)
                    # directional clog: a->b only (Direction parity,
                    # network.rs:108)
                    w0, w1 = apply_bit(w0, w1, idxs == a, b_lo, b_hi, dir_val, touch_dir)
                    # group partition: row i's cross-boundary links are the
                    # group complement for members, the group for outsiders
                    # (bit i lands on neither side, so self-links are clean)
                    full_lo = jnp.int32((1 << min(nn, CLOG_WORD_BITS)) - 1)
                    full_hi = jnp.int32((1 << max(nn - CLOG_WORD_BITS, 0)) - 1)
                    cross_lo = jnp.where(in_g, ~a & full_lo, a & full_lo)
                    cross_hi = jnp.where(in_g, ~b & full_hi, b & full_hi)
                    g_on = op == F_CLOG_GROUP
                    nw0 = jnp.where(g_on, w0 | cross_lo, w0 & ~cross_lo)
                    nw1 = jnp.where(g_on, w1 | cross_hi, w1 & ~cross_hi)
                    w0 = jnp.where(touch_group, nw0, w0)
                    w1 = jnp.where(touch_group, nw1, w1)
                    clogged = jnp.stack([w0, w1], axis=1)
                else:
                    # bool-matrix oracle: outer-equality masked writes
                    clogged = jnp.where(
                        touch_pair,
                        set2d(set2d(s.clogged, a, b, pair_val), b, a, pair_val),
                        s.clogged,
                    )
                    clogged = jnp.where(touch_dir, set2d(clogged, a, b, dir_val), clogged)
                    cross = in_g[:, None] != in_g[None, :]
                    clogged = jnp.where(touch_group & cross, op == F_CLOG_GROUP, clogged)
                a_mask = jnp.arange(nn) == a
                kill_op = op == F_KILL
                restart_op = op == F_RESTART
                churn_restarts = (
                    cfg.faults.churn is not None and cfg.faults.churn.kind == "kv3a"
                )
                if churn_restarts:
                    # the process's restart of one named node (payload[1]):
                    # this branch's own restart, so the hook is traced once
                    restart_op = restart_op | (op == F_CHURN_RESTART)
                if cfg.faults.allow_torn:
                    # a torn fault is a kill whose restart goes through the
                    # torn_spec() storage contract instead of the model hook
                    kill_op = kill_op | (op == F_TORN)
                    restart_op = restart_op | (op == F_TORN_RESTART)
                killed = jnp.where(
                    kill_op,
                    s.killed | a_mask,
                    jnp.where(restart_op, s.killed & ~a_mask, s.killed),
                )
                # loss storm: `a` is the storm rate in 1/65536 units
                storm = jnp.where(
                    op == F_LOSS_STORM,
                    a,
                    jnp.where(op == F_LOSS_END, jnp.int32(0), s.storm_loss),
                ).astype(jnp.int32)
                # delay-spike window toggle (buggify analogue)
                delay = jnp.where(
                    op == F_DELAY_SPIKE,
                    jnp.int32(1),
                    jnp.where(op == F_DELAY_END, jnp.int32(0), s.delay_spike),
                ).astype(jnp.int32)
                # pause window: arg2 (`b`) carries the resume time the
                # schedule derivation baked in — deferral needs no clock
                # state beyond this per-node word
                paused = s.paused_until
                if cfg.faults.allow_pause:
                    paused = jnp.where(
                        (op == F_PAUSE) & a_mask,
                        b,
                        jnp.where((op == F_RESUME) & a_mask, jnp.int32(0), paused),
                    ).astype(jnp.int32)
                # clock-skew window: arg2 (`b`) is the drawn q10 factor
                skew = s.skew_q10
                if cfg.faults.allow_skew:
                    skew = jnp.where(
                        (op == F_SKEW) & a_mask,
                        b,
                        jnp.where((op == F_SKEW_END) & a_mask, jnp.int32(0), skew),
                    ).astype(jnp.int32)
                # cond folded into the machine's own row masks — no full-tree
                # select here (XLA CSEs it inside the fused loop, but eager
                # step_batch paid ~30% for it, and masked writes are strictly
                # less work for any backend)
                plain_restart = op == F_RESTART
                if churn_restarts:
                    plain_restart = plain_restart | (op == F_CHURN_RESTART)
                nodes = m.restart_node_if(
                    s.nodes, a, plain_restart, k_restart,
                    strict=cfg.faults.strict_restart,
                )
                if cfg.faults.allow_torn:
                    # torn/lost-write restart: the damage seed is the fault
                    # payload's schedule-drawn mask (b) salted by this
                    # step's torn RNG word — bit-deterministic on replay
                    torn_seed = b.astype(jnp.uint32) ^ step_words[layout.torn_off]
                    nodes = m.torn_restart_if(
                        nodes, a, op == F_TORN_RESTART, k_restart, torn_seed
                    )
                boot_node = jnp.where(restart_op, a, jnp.int32(-1))
                return (nodes, m.empty_outbox(), clogged, killed, storm, delay,
                        paused, skew, boot_node)

            (nodes, outbox, clogged, killed, storm_loss, delay_spike,
             paused_until, skew_q10, boot_node) = lax.switch(
                ev_kind, [timer_branch, msg_branch, fault_branch], None
            )

            # Killed nodes process nothing (reference: killed node's tasks are
            # dropped); fault events always apply. Deferred events (pause
            # windows) are not processed either — they re-deliver at resume.
            is_handler = ev_kind != EV_FAULT
            effective = process & (node_alive | ~is_handler)
            if defer is not None:
                effective = effective & ~defer
            nodes = tree_where(effective, nodes, s.nodes)
            clogged = jnp.where(effective, clogged, s.clogged)
            killed = jnp.where(effective, killed, s.killed)
            storm_loss = jnp.where(effective, storm_loss, s.storm_loss)
            delay_spike = jnp.where(effective, delay_spike, s.delay_spike)
            if cfg.faults.allow_pause:
                paused_until = jnp.where(effective, paused_until, s.paused_until)
            else:
                paused_until = s.paused_until
            if cfg.faults.allow_skew:
                skew_q10 = jnp.where(effective, skew_q10, s.skew_q10)
            else:
                skew_q10 = s.skew_q10
            outbox_valid_msgs = outbox.msg_valid & effective
            outbox_valid_timers = outbox.timer_valid & effective

        with _xprof.scope("step.churn"):
            # -- the churn process (FaultPlan.churn; off adds NO ops) ----------
            # A tick draws its faults now: the victim is read off the state
            # the tick finds, the coins and the next sleep off the lane's
            # churn stream at this tick's index. `churn_next` re-arms the
            # popped slot below, so the process never holds a second one.
            churn = s.churn
            churn_next = None
            if cfg.faults.churn is not None and cfg.faults.churn.kind == "kv3a":
                churn, churn_next, churn_prov_bits, clogged, killed = (
                    self._churn_kv3a(
                        s, effective, ev_kind, ev_payload, ev_time, clogged, killed
                    )
                )
            elif cfg.faults.churn is not None:
                cp = cfg.faults.churn
                nn = s.killed.shape[0]
                node_ids = jnp.arange(nn)
                is_churn = effective & (ev_kind == EV_FAULT)
                is_tick = is_churn & (ev_payload[0] == F_CHURN_TICK)
                is_heal = is_churn & (ev_payload[0] == F_CHURN_HEAL)
                cw = churn_words(churn["key"], ev_payload[1] + 1)
                down = churn["down"]
                up = ((down >> node_ids) & 1) == 0
                victim = m.churn_victim(s.nodes, up)
                if victim is None:
                    # no hook: the (word mod #connected)-th connected node
                    rank = jnp.cumsum(up.astype(jnp.int32)) - 1
                    n_conn = jnp.maximum(up.sum(dtype=jnp.int32), 1)
                    kth = (cw[4] % n_conn.astype(jnp.uint32)).astype(jnp.int32)
                    victim = jnp.where(
                        up.any(), jnp.argmax(up & (rank == kth)), -1
                    )
                victim = jnp.asarray(victim).astype(jnp.int32)
                do_disc = (
                    is_tick
                    & ((cw[0] % jnp.uint32(1000)) < jnp.uint32(cp.disconnect_permille))
                    & (victim >= 0)
                )
                disc_bits = jnp.where(
                    do_disc, jnp.int32(1) << jnp.clip(victim, 0, nn - 1), 0
                )
                down = down | disc_bits
                n_up = nn - lax.population_count(down)
                pick_bit = jnp.int32(1) << (cw[1] % jnp.uint32(nn)).astype(jnp.int32)
                do_reconn = (
                    is_tick & (n_up < self._churn_majority)
                    & ((down & pick_bit) != 0)
                )
                # the nodes coming back: the tick's pick, or all at the heal
                up_bits = jnp.where(
                    is_heal, down, jnp.where(do_reconn, pick_bit, 0)
                )
                down = down & ~up_bits
                clogged = _churn_clog(
                    clogged, disc_bits, up_bits, down, cfg.clog_packed
                )
                churn = dict(
                    churn,
                    down=down,
                    last=jnp.where(
                        is_tick | is_heal,
                        jnp.stack([disc_bits, up_bits]),
                        churn["last"],
                    ),
                    ticks=churn["ticks"] + is_tick.astype(jnp.int32),
                    disconnects=churn["disconnects"] + do_disc.astype(jnp.int32),
                    reconnects=churn["reconnects"]
                    + lax.population_count(up_bits),
                )
                # the next tick, or the heal once the sleep passes the end
                t_next = ev_time + churn_sleep_us(cp, cw)
                over = t_next >= churn["until_us"]
                churn_next = (
                    is_tick,
                    jnp.where(over, churn["until_us"], t_next),
                    jnp.where(over, F_CHURN_HEAL, F_CHURN_TICK).astype(jnp.int32),
                    ev_payload[1] + 1,
                )
                # provenance: which nodes' faults this event applied
                churn_prov_bits = (disc_bits | up_bits).astype(jnp.uint32)

        with _xprof.scope("step.provenance"):
            # -- causal provenance fold (gate-off adds NO ops) ------------------
            # A processed handler event folds its lineage into the handling
            # node; a processed fault event plants its word on the nodes it
            # touches — both endpoints for pair/dir/heal ops, node `a` for
            # node ops (kill/restart/pause/skew/torn), every node for the
            # global window/group ops (a loss storm touches every link; the
            # over-approximation is the documented contract). Everything the
            # node emits afterwards (messages, timers, the restart boot)
            # inherits the node's updated word.
            if cfg.provenance:
                nn_p = s.killed.shape[0]
                idxs_p = jnp.arange(nn_p)
                p_op = ev_payload[0]
                is_fault_ev = ev_kind == EV_FAULT
                prov_pair_ops = (
                    (p_op == F_CLOG_PAIR) | (p_op == F_UNCLOG_PAIR)
                    | (p_op == F_CLOG_DIR) | (p_op == F_UNCLOG_DIR)
                )
                if cfg.faults.allow_heal_asym:
                    prov_pair_ops = prov_pair_ops | (p_op == F_HASYM) | (p_op == F_HASYM_HEAL)
                prov_global_ops = (
                    (p_op == F_CLOG_GROUP) | (p_op == F_UNCLOG_GROUP)
                    | (p_op == F_LOSS_STORM) | (p_op == F_LOSS_END)
                    | (p_op == F_DELAY_SPIKE) | (p_op == F_DELAY_END)
                )
                touched = jnp.where(
                    is_fault_ev,
                    prov_global_ops
                    | (prov_pair_ops & ((idxs_p == ev_payload[1]) | (idxs_p == ev_payload[2])))
                    | (~prov_global_ops & ~prov_pair_ops & (idxs_p == ev_payload[1])),
                    idxs_p == ev_node,
                )
                add_word = ev_prov
                if cfg.faults.churn is not None:
                    # A generated fault sets the bit of the NODE it cuts
                    # off or brings back (hundreds of faults, N <= 30
                    # bits: sound as an OR, whatever their number), on
                    # every node — each is an end of a link it moved. A
                    # tick that applies nothing touches nothing.
                    churn_ev = is_fault_ev & (p_op >= F_CHURN_TICK)
                    touched = jnp.where(churn_ev, churn_prov_bits != 0, touched)
                    add_word = jnp.where(churn_ev, churn_prov_bits, add_word)
                if cfg.faults.strict_restart:
                    # a crash-with-amnesia wipe is its own attribution
                    # channel (bit 30): it has no schedule slot of its own
                    add_word = jnp.where(
                        is_fault_ev & (p_op == F_RESTART),
                        ev_prov | jnp.uint32(1 << PROV_BIT_AMNESIA),
                        add_word,
                    )
                node_prov = jnp.where(
                    touched & effective, s.node_prov | add_word, s.node_prov
                )
                # the word every push below inherits (fault events push only
                # the restart boot timer, whose node is ev_node == a)
                sender_prov = get_at(node_prov, ev_node)
            else:
                node_prov = s.node_prov
                sender_prov = None

        with _xprof.scope("step.outbox"):
            # -- push outbox messages with chaos (latency / loss / clog) --------
            eq = {
                "time": s.eq_time,
                "seq": s.eq_seq,
                "kind": s.eq_kind,
                "node": s.eq_node,
                "src": s.eq_src,
                "payload": s.eq_payload,
                "valid": eq_valid,
            }
            if cfg.provenance:
                eq["prov"] = s.eq_prov
            if defer is not None:
                # deferred delivery: rewrite the (still-valid) popped slot's
                # time to the node's resume point. Seq is untouched — at the
                # resume instant `paused_until > now` is already false, so
                # the event delivers regardless of its order relative to the
                # F_RESUME event, and same-time deferred events keep their
                # original relative order. No free slot is consumed, so
                # deferral can never overflow the queue.
                defer_slot = (jnp.arange(s.eq_valid.shape[0]) == idx) & defer
                eq["time"] = jnp.where(defer_slot, node_resume_us, eq["time"])
                if cfg.provenance:
                    # the deferral is caused by the pause window: the target
                    # node's word (which carries the pause fault's bit since
                    # the F_PAUSE apply touched it) folds into the deferred
                    # event's lineage
                    eq["prov"] = jnp.where(
                        defer_slot, eq["prov"] | get_at(s.node_prov, ev_node), eq["prov"]
                    )
            # The event's pushes are collected in push order — message 0,
            # (its duplicate), message 1, ..., timers, the restart boot —
            # and land together at the end of `step.timers`: nothing frees
            # a slot inside a step, so the k-th push that finds one takes
            # the k-th lowest slot free after the pop, and `_push_ranked`
            # ranks those slots once and writes every queue leaf once.
            pushes = []
            pinned = None
            if churn_next is not None:
                # re-arm the slot the tick was popped from: a pop and a
                # push in one, so the process can never overflow the queue
                rearm, t_next, next_op, next_tick = churn_next
                with _xprof.scope("step.churn"):
                    pinned = (idx, _Push(
                        rearm, t_next, EV_FAULT, jnp.int32(0), jnp.int32(-1),
                        # selects on an iota, not `make_payload`'s stack of
                        # scalars: that form read 2% more chip-us a seed
                        # (my chip run, PR 27)
                        jnp.where(
                            jnp.arange(m.PAYLOAD_WIDTH) == 0, next_op,
                            jnp.where(
                                jnp.arange(m.PAYLOAD_WIDTH) == 1, next_tick, 0
                            ),
                        ).astype(jnp.int32),
                        jnp.uint32(0),
                    ))

            lat_span = max(1, cfg.latency_max_us - cfg.latency_min_us)
            lat_bits = step_words[layout.lat_off : layout.lat_off + m.MAX_MSGS]
            # Sections that are statically inert for this (config, machine)
            # pair cost nothing: v3 doesn't even draw them; v2 draws them
            # (the legacy block is part of the stream contract) but the
            # consuming compute is elided — with loss_rate == 0 and storms
            # unreachable the drop compare is constant-False, so eliding it
            # is result-preserving in both versions.
            if layout.loss_active:
                drop_bits = step_words[layout.drop_off : layout.drop_off + m.MAX_MSGS]
                # static config loss + active storm (storm rate 65535 ~= drop
                # all), saturating at u32 max
                base_threshold = jnp.uint32(int(cfg.packet_loss_rate * 0xFFFFFFFF))
                storm_threshold = storm_loss.astype(jnp.uint32) * jnp.uint32(65537)
                summed = base_threshold + storm_threshold
                loss_threshold = jnp.where(
                    summed < storm_threshold, jnp.uint32(0xFFFFFFFF), summed
                )
            if layout.spike_active:
                # spike gate + magnitude are INDEPENDENT words: conditioning
                # the magnitude on the gate's sub-threshold bits would cap the
                # extra latency at ~2.7 s instead of the documented 1-5 s
                spike_bits = step_words[layout.spike_off : layout.spike_off + m.MAX_MSGS]
                spike_mag_bits = step_words[
                    layout.spike_off + m.MAX_MSGS : layout.spike_off + 2 * m.MAX_MSGS
                ]
            if layout.dup_active:
                # duplication gate + fresh-latency words (tail section of
                # the block — recorded streams are untouched with dup off)
                dup_bits = step_words[layout.dup_off : layout.dup_off + m.MAX_MSGS]
                dup_lat_bits = step_words[
                    layout.dup_off + m.MAX_MSGS : layout.dup_off + 2 * m.MAX_MSGS
                ]
            # the handling node's outbound clog row, read ONCE (pre-fault
            # state, matching the unpacked path's s.clogged[ev_node, dst])
            # and expanded to bool[N] so each message pays the same tiny
            # one-hot read as the bool-matrix path, not a shift/mask per slot
            if cfg.clog_packed:
                clog_row_bool = _clog_row_bools(get_at(s.clogged, ev_node), s.killed.shape[0])

            for mi in range(m.MAX_MSGS):
                want = outbox_valid_msgs[mi]
                dst = outbox.msg_dst[mi]
                if cfg.clog_packed:
                    blocked = get_at(clog_row_bool, dst)
                else:
                    blocked = get_at(s.clogged, (ev_node, dst))
                if layout.loss_active:
                    blocked = blocked | (drop_bits[mi] < loss_threshold)
                latency = jnp.int32(cfg.latency_min_us) + (
                    lat_bits[mi] % jnp.uint32(lat_span)
                ).astype(jnp.int32)
                if layout.spike_active:
                    # delay-spike window: ~10% of sends take +1-5 virtual s
                    # (the host buggify's numbers); the draws are consumed
                    # every step so windows don't perturb the stream shape
                    spiked = (delay_spike > 0) & (spike_bits[mi] < jnp.uint32(DELAY_PROB_U32))
                    extra = jnp.int32(DELAY_EXTRA_MIN_US) + (
                        spike_mag_bits[mi] % jnp.uint32(DELAY_EXTRA_SPAN_US)
                    ).astype(jnp.int32)
                    latency = latency + jnp.where(spiked, extra, 0)
                pushes.append(_Push(
                    want & ~blocked, new_now + latency, EV_MSG, dst, ev_node,
                    outbox.msg_payload[mi], sender_prov,
                ))
                if layout.dup_active:
                    # Bernoulli duplicate of a successfully pushed message,
                    # re-enqueued with an independently drawn latency (the
                    # idempotency chaos loss-only vocabularies can't
                    # express). Same overflow accounting as any push.
                    dup_latency = jnp.int32(cfg.latency_min_us) + (
                        dup_lat_bits[mi] % jnp.uint32(lat_span)
                    ).astype(jnp.int32)
                    pushes.append(_Push(
                        dup_bits[mi] < jnp.uint32(DUP_PROB_U32),
                        new_now + dup_latency, EV_MSG, dst, ev_node,
                        outbox.msg_payload[mi],
                        # the duplicate copy carries the dup attribution bit:
                        # a violation whose lineage includes it names `dup`
                        (
                            sender_prov | jnp.uint32(1 << PROV_BIT_DUP)
                            if sender_prov is not None else None
                        ),
                        dup_of=len(pushes) - 1,
                    ))

        with _xprof.scope("step.timers"):
            # -- push timers (for the handling node) ----------------------------
            slot0 = jnp.arange(m.PAYLOAD_WIDTH) == 0
            if cfg.faults.allow_skew:
                # clock-skew window: the handling node's armed timers are
                # stretched/compressed by its active q10 factor (read from
                # the pre-step state — handler events never change skew, and
                # fault events arm no timers, so pre == post here)
                node_skew_q10 = get_at(s.skew_q10, ev_node)
            for ti in range(m.MAX_TIMERS):
                tpay = jnp.where(slot0, outbox.timer_id[ti], 0).astype(jnp.int32)
                t_delay = outbox.timer_delay_us[ti]
                if cfg.faults.allow_skew:
                    t_delay = jnp.where(
                        node_skew_q10 > 0,
                        skew_scale_us(t_delay, node_skew_q10),
                        t_delay,
                    )
                pushes.append(_Push(
                    outbox_valid_timers[ti], new_now + t_delay, EV_TIMER,
                    ev_node, jnp.int32(-1), tpay, sender_prov,
                ))

            # -- restart boot timer ---------------------------------------------
            boot_pay = jnp.zeros((m.PAYLOAD_WIDTH,), jnp.int32)  # BOOT == 0
            pushes.append(_Push(
                effective & (boot_node >= 0), new_now, EV_TIMER, boot_node,
                jnp.int32(-1), boot_pay, sender_prov,
            ))

            # -- the one pass: rank the free slots, write every leaf ------------
            eq, landed, overflow, next_seq = _push_ranked(
                eq, pushes, s.next_seq, pinned
            )
            failed = s.failed | overflow
            fail_code = jnp.where(overflow, jnp.int32(OVERFLOW), s.fail_code)
            msg_count = s.msg_count + sum(
                ok.astype(jnp.int32)
                for ok, p in zip(landed, pushes) if p.kind == EV_MSG
            )
            if layout.dup_active:
                n_dups = sum(
                    ok.astype(jnp.int32)
                    for ok, p in zip(landed, pushes) if p.dup_of is not None
                )

        with _xprof.scope("step.recorder"):
            # -- flight recorder (observability; gate-off adds NO ops) ----------
            fr = s.fr
            if cfg.flight_recorder:
                stepped = jnp.bool_(True) if active is None else active
                new_step = s.step + stepped.astype(jnp.int32)
                # digest: fold the popped tuple + the step's whole RNG word
                # block — exactly the inputs that determine this step — on
                # every step that pops an event (same condition as the trace
                # ring / replay trace). The megakernel hands the fold in
                # pre-computed (same words, same order, same math — the
                # fused pass runs the identical chain in VMEM).
                if step_block is not None and len(step_block) == 3:
                    nd0, nd1 = step_block[1], step_block[2]
                else:
                    nd0, nd1 = digest_fold(
                        fr["d0"],
                        fr["d1"],
                        [ev_time, ev_kind, ev_node, ev_src]
                        + [ev_payload[i] for i in range(m.PAYLOAD_WIDTH)]
                        + [step_words[i] for i in range(layout.total_words)],
                    )
                d0 = jnp.where(live, nd0, fr["d0"])
                d1 = jnp.where(live, nd1, fr["d1"])
                # checkpoint ring: every `fr_digest_every`-th step the lane
                # actually executes lands (step, d0, d1) in slot
                # (step/every - 1) % ring — the host decodes by sorting on
                # step. Condition is "the step counter crossed a multiple",
                # not "popped": the audit's host-side trail reads the digest
                # at exact step multiples and must see the same checkpoints.
                every, rr = cfg.fr_digest_every, cfg.fr_digest_ring
                want_ck = stepped & (new_step % every == 0)
                ck_slot = ((new_step // every - 1) % rr == jnp.arange(rr)) & want_ck
                # fault-injection counters: one per FaultPlan kind, counted
                # when an APPLY op (even payload[0]) is processed
                is_inj = process & (ev_kind == EV_FAULT) & (ev_payload[0] % 2 == 0)
                kind_idx = ev_payload[0] // 2
                inj = fr["inj"] + (
                    (jnp.arange(len(FAULT_KIND_NAMES)) == kind_idx) & is_inj
                ).astype(jnp.int32)
                # non-scheduled chaos counters: duplicates pushed this step,
                # crash-with-amnesia wipes applied (strict restarts)
                fr_dup = fr["dup"]
                if layout.dup_active:
                    fr_dup = fr_dup + n_dups
                fr_amnesia = fr["amnesia"]
                if cfg.faults.strict_restart:
                    fr_amnesia = fr_amnesia + (
                        process & (ev_kind == EV_FAULT) & (ev_payload[0] == F_RESTART)
                    ).astype(jnp.int32)
                # occupancy high-water marks on the post-step state (frozen
                # lanes' state is unchanged, so their marks are stable).
                # Queue occupancy is tracked INCREMENTALLY: the pop clears
                # exactly one valid slot (when live and not deferred) and
                # every successful push — messages, duplicates, timers, the
                # restart boot — fills exactly one free slot and bumped
                # next_seq, so the delta is (next_seq' - next_seq) minus the
                # pop. Replaces a [Q]-wide re-sum of eq["valid"] per event
                # with three scalar ops; equal to the old sum by
                # construction (host-oracle differential asserts it).
                popped_one = live if defer is None else (live & ~defer)
                eq_n = (
                    fr["eq_n"]
                    - popped_one.astype(jnp.int32)
                    + (next_seq - s.next_seq)
                )
                n_clog = (
                    lax.population_count(clogged).sum()
                    if cfg.clog_packed
                    else clogged.sum()
                ).astype(jnp.int32)
                fr = {
                    "d0": d0,
                    "d1": d1,
                    "eq_n": eq_n,
                    "ck_step": jnp.where(ck_slot, new_step, fr["ck_step"]),
                    "ck_d0": jnp.where(ck_slot, d0, fr["ck_d0"]),
                    "ck_d1": jnp.where(ck_slot, d1, fr["ck_d1"]),
                    "inj": inj,
                    "dup": fr_dup,
                    "amnesia": fr_amnesia,
                    "q_hwm": jnp.maximum(fr["q_hwm"], eq_n),
                    "clog_hwm": jnp.maximum(fr["clog_hwm"], n_clog),
                    "kill_hwm": jnp.maximum(
                        fr["kill_hwm"], killed.sum().astype(jnp.int32)
                    ),
                }

        with _xprof.scope("step.coverage"):
            # -- scenario coverage (observability; gate-off adds NO ops) --------
            cov = s.cov
            if cfg.coverage:
                # abstract-state projection of the POST-step state: the
                # scenario this event's processing REACHED (the model
                # contract: Machine.coverage_projection, low 3 bits = its
                # coarsest "phase" notion)
                abs_word = m.coverage_projection(nodes, new_now)
                # fault-environment context: killed count + active chaos
                # windows — the same abstract state under partition vs storm
                # is a different scenario
                n_killed = jnp.clip(killed.sum().astype(jnp.int32), 0, 7)
                clog_any = jnp.any(clogged != 0)
                ctx = (
                    n_killed
                    | (clog_any.astype(jnp.int32) << 3)
                    | ((storm_loss > 0).astype(jnp.int32) << 4)
                    | ((delay_spike > 0).astype(jnp.int32) << 5)
                )
                # new chaos windows extend the context word only when their
                # kind is enabled — legacy configs hash identical inputs
                if cfg.faults.allow_pause:
                    ctx = ctx | (jnp.any(paused_until > 0).astype(jnp.int32) << 6)
                if cfg.faults.allow_skew:
                    ctx = ctx | (jnp.any(skew_q10 > 0).astype(jnp.int32) << 7)
                # event discriminant: payload[0] for msg (message type) and
                # fault (op) events; timers fold 0 — timer ids are
                # epoch-encoded, and counting every restart epoch as a new
                # scenario would inflate the map
                op_word = jnp.where(ev_kind == EV_TIMER, jnp.int32(0), ev_payload[0])
                band = cov_band(ev_kind, op_word, self.cov_band_bits)
                if cfg.faults.strict_restart:
                    # a strict restart is a different scenario class than a
                    # plain kill/restart: route it to the amnesia band
                    band = jnp.where(
                        (ev_kind == EV_FAULT) & (ev_payload[0] == F_RESTART),
                        jnp.int32(COV_BAND_AMNESIA),
                        band,
                    )
                slot = cov_slot(
                    abs_word, ev_kind, ev_node, op_word, ctx, cfg.cov_slots_log2,
                    band_bits=self.cov_band_bits, band=band,
                )
                # same condition as the trace ring / digest: popped events.
                # Buffered regime (cov_buffer > 0): append the slot index to
                # the tiny per-lane ring instead of scattering into the
                # 2 KiB map — the map never appears in the step program;
                # run_segment folds the buffer at the flush cadence, at
                # segment exit, and therefore at every freeze point. OR is
                # commutative + idempotent, so the final map is
                # bit-identical to the per-event fold (the cov_buffer=0
                # oracle; tests/test_coverage.py differentials).
                if self._cov_buffered:
                    buf, buf_n = cov_push(cov["buf"], cov["buf_n"], slot, live)
                    cov = dict(cov, buf=buf, buf_n=buf_n)
                else:
                    cov = {"map": cov_fold(cov["map"], slot, live)}
                if layout.dup_active:
                    # synthetic dup band: a step that enqueued >= 1 duplicate
                    # is its own scenario class (one extra word fold, only
                    # when the gate is on)
                    dup_slot = cov_slot(
                        abs_word, ev_kind, ev_node, n_dups, ctx,
                        cfg.cov_slots_log2, band_bits=self.cov_band_bits,
                        band=jnp.int32(COV_BAND_DUP),
                    )
                    dup_hit = live & (n_dups > 0)
                    if self._cov_buffered:
                        buf, buf_n = cov_push(
                            cov["buf"], cov["buf_n"], dup_slot, dup_hit
                        )
                        cov = dict(cov, buf=buf, buf_n=buf_n)
                    else:
                        cov = {"map": cov_fold(cov["map"], dup_slot, dup_hit)}

        with _xprof.scope("step.invariants"):
            # -- invariants / termination ---------------------------------------
            ok, code = m.invariant(nodes, new_now)
            inv_fail = process & ~ok
            if cfg.provenance:
                # the violation's provenance: the handling node's lineage
                # cone at the step whose transition broke the invariant
                # (its word already folds the popped event's). Captured at
                # the FIRST failure only — that is the violation the fail
                # code names.
                fail_prov = jnp.where(
                    inv_fail & ~s.failed, sender_prov | ev_prov, s.fail_prov
                )
            else:
                fail_prov = s.fail_prov
            failed = failed | inv_fail
            fail_code = jnp.where(inv_fail, code, fail_code)
            if active is None:
                done = s.done | ~any_valid | horizon_hit | m.is_done(nodes, new_now)
            else:
                done = (
                    s.done
                    | (active & ~any_valid)
                    | horizon_hit
                    | (active & m.is_done(nodes, new_now))
                )

            return LaneState(
                now_us=new_now if active is None else jnp.where(active, new_now, s.now_us),
                next_seq=next_seq,
                step=s.step + (1 if active is None else active.astype(jnp.int32)),
                rng_key=key,
                done=done,
                failed=failed,
                fail_code=fail_code,
                horizon_hit=s.horizon_hit | horizon_hit,
                msg_count=msg_count,
                storm_loss=storm_loss,
                delay_spike=delay_spike,
                eq_time=eq["time"],
                eq_seq=eq["seq"],
                eq_kind=eq["kind"],
                eq_node=eq["node"],
                eq_src=eq["src"],
                eq_payload=eq["payload"],
                eq_valid=eq["valid"],
                clogged=clogged,
                killed=killed,
                paused_until=paused_until,
                skew_q10=skew_q10,
                node_prov=node_prov,
                eq_prov=eq.get("prov", s.eq_prov),
                fail_prov=fail_prov,
                nodes=nodes,
                ring=ring,
                fr=fr,
                cov=cov,
                churn=churn,
            )

    def _churn_kv3a(self, s, effective, ev_kind, ev_payload, ev_time,
                    clogged, killed):
        """The churn slot's event under `ChurnPlan.kind == "kv3a"` (see
        ChurnPlan): a tick re-draws the split of the named nodes, the
        heal clears it and kills them all, a restart (applied by the
        fault branch) brings one back and re-arms the slot for the
        next. Returns (churn book, re-arm, provenance bits, clogged,
        killed)."""
        cfg = self.config
        cp = cfg.faults.churn
        churn = s.churn
        named = self._churn_nodes
        named_bits = sum(1 << i for i in named)
        ids = jnp.arange(s.killed.shape[0])
        is_named = ((jnp.int32(named_bits) >> ids) & 1) == 1
        is_churn = effective & (ev_kind == EV_FAULT)
        op, arg = ev_payload[0], ev_payload[1]
        is_tick = is_churn & (op == F_CHURN_TICK)
        is_heal = is_churn & (op == F_CHURN_HEAL)
        is_restart = is_churn & (op == F_CHURN_RESTART)
        cw = churn_words(churn["key"], arg + 1)

        # a tick: one coin a named node; row i's links to the named
        # nodes of the other side are cut, to those of its own side open
        sides = (cw[0] & jnp.uint32(named_bits)).astype(jnp.int32)
        on_one = ((sides >> ids) & 1) == 1
        if cfg.clog_packed:
            w0 = clogged[:, 0]
            across = jnp.where(on_one, ~sides & named_bits, sides)
            w0 = jnp.where(
                is_named & is_tick, (w0 & ~named_bits) | across,
                jnp.where(is_named & is_heal, w0 & ~named_bits, w0),
            )
            clogged = jnp.stack([w0, clogged[:, 1]], axis=1)
        else:
            both = is_named[:, None] & is_named[None, :]
            clogged = jnp.where(
                both & is_tick, on_one[:, None] != on_one[None, :],
                jnp.where(both & is_heal, False, clogged),
            )
        # the heal kills every named node (a restart of `arg` — the wipe,
        # the `killed` bit, the BOOT — is the fault branch's own)
        killed = killed | (is_named & is_heal)

        def minority(mask):
            n_one = lax.population_count(mask)
            return jnp.where(2 * n_one > len(named), ~mask & named_bits, mask)

        cut = jnp.where(is_tick, lax.population_count(minority(sides)), 0)
        healed = jnp.where(is_heal, lax.population_count(minority(churn["down"])), 0)
        back_bit = jnp.int32(1) << jnp.clip(arg, 0, ids.shape[0] - 1)
        churn = dict(
            churn,
            # the side-1 set of the split that stands (none after the heal)
            down=jnp.where(is_tick, sides, jnp.where(is_heal, 0, churn["down"])),
            # what `differential.applied_churn_faults` reads: a tick's
            # side-1 set; the set a heal killed, the node a restart raised
            last=jnp.where(
                is_tick, jnp.stack([sides, jnp.int32(0)]),
                jnp.where(
                    is_heal, jnp.stack([jnp.int32(0), jnp.int32(named_bits)]),
                    jnp.where(
                        is_restart, jnp.stack([jnp.int32(0), back_bit]),
                        churn["last"],
                    ),
                ),
            ),
            ticks=churn["ticks"] + is_tick.astype(jnp.int32),
            # nodes on the smaller side of a split, and brought back by the heal
            disconnects=churn["disconnects"] + cut,
            reconnects=churn["reconnects"] + healed,
            partitions=churn["partitions"] + is_tick.astype(jnp.int32),
            crashes=churn["crashes"] + is_heal.astype(jnp.int32),
        )
        # the slot's next event: the next tick or the heal; after the
        # heal the first restart; after a restart the next node's, at
        # the same instant, until the last is up
        t_tick = ev_time + churn_sleep_us(cp, cw)
        over = t_tick >= churn["until_us"]
        following = sum(
            jnp.where(arg == a, b, 0) for a, b in zip(named, named[1:])
        ) if len(named) > 1 else jnp.int32(0)
        churn_next = (
            is_tick | is_heal | (is_restart & (arg != named[-1])),
            jnp.where(
                is_tick, jnp.where(over, churn["until_us"], t_tick),
                jnp.where(is_heal, ev_time + cp.restart_after_us, ev_time),
            ),
            jnp.where(
                is_tick & ~over, F_CHURN_TICK,
                jnp.where(is_tick, F_CHURN_HEAL, F_CHURN_RESTART),
            ).astype(jnp.int32),
            jnp.where(
                is_tick, arg + 1, jnp.where(is_heal, named[0], following)
            ).astype(jnp.int32),
        )
        prov_bits = jnp.where(
            is_tick | is_heal, jnp.int32(named_bits),
            jnp.where(is_restart, back_bit, 0),
        ).astype(jnp.uint32)
        return churn, churn_next, prov_bits, clogged, killed

    # -- batch runners -------------------------------------------------------

    def init_batch(self, seeds: jax.Array) -> LaneState:
        return jax.vmap(self.init_lane)(seeds)

    def step_batch(self, state: LaneState) -> LaneState:
        # the per-lane freeze rides inside the step's write masks
        # (`active=`) instead of a post-hoc tree_where that re-selected
        # every [L, Q] queue leaf and the whole nodes tree each step
        active = ~(state.done | state.failed)
        if self.use_megakernel:
            # whole-event megakernel: pop + gather + the v3 RNG block
            # (+ the digest fold under the recorder) leave one fused
            # VMEM pass; the rest of the step consumes them via
            # step_block and draws/folds nothing itself
            fr_on = self.config.flight_recorder
            with _xprof.scope("step.pop"):
                idx, any_valid, popped, words, digest = step_megakernel(
                    state.eq_time, state.eq_seq, state.eq_valid,
                    state.eq_kind, state.eq_node, state.eq_src,
                    state.eq_payload,
                    state.rng_key, state.step, self._rng_layout.total_words,
                    d0=state.fr["d0"] if fr_on else None,
                    d1=state.fr["d1"] if fr_on else None,
                    digest_fold=digest_fold if fr_on else None,
                    interpret=self._pallas_interpret,
                )
            block = (words,) + digest
            return jax.vmap(
                lambda st, i, a, act, p, blk: self._lane_step_popped(
                    st, i, a, popped=p, active=act, step_block=blk
                )
            )(state, idx, any_valid, active, popped, block)
        if self.use_pallas_pop:
            # fused pop+gather: the popped event tuple leaves the kernel
            # in the same VMEM pass as the argmin
            with _xprof.scope("step.pop"):
                idx, any_valid, popped = pop_gather_batch(
                    state.eq_time, state.eq_seq, state.eq_valid,
                    state.eq_kind, state.eq_node, state.eq_src,
                    state.eq_payload,
                    use_pallas=True, interpret=self._pallas_interpret,
                )
            return jax.vmap(
                lambda st, i, a, act, p: self._lane_step_popped(
                    st, i, a, popped=p, active=act
                )
            )(state, idx, any_valid, active, popped)
        with _xprof.scope("step.pop"):
            idx, any_valid = pop_earliest_batch(
                state.eq_time, state.eq_seq, state.eq_valid, use_pallas=False
            )
        return jax.vmap(
            lambda st, i, a, act: self._lane_step_popped(st, i, a, active=act)
        )(state, idx, any_valid, active)

    def run_batch(self, seeds: jax.Array, max_steps: int = 10_000) -> BatchResult:
        """Run every seed lane to completion (or max_steps events/lane).

        jit-compile with `jax.jit(engine.run_batch, static_argnums=1)` or
        use `make_runner`.
        """
        state = self.init_batch(seeds)
        final = self.run_segment(state, max_steps)
        return BatchResult(
            seeds=seeds,
            done=final.done,
            failed=final.failed,
            fail_code=final.fail_code,
            fail_prov=final.fail_prov,
            now_us=final.now_us,
            steps=final.step,
            msg_count=final.msg_count,
            summary=jax.vmap(self.machine.summary)(final.nodes),
            ring=final.ring,
            fr=final.fr,
            cov=final.cov,
        )

    def _cov_flush_batch(self, state: LaneState) -> LaneState:
        """Fold every lane's buffered coverage slots into its packed
        bit map and reset the buffer counts. Bit-identical to having
        folded each slot at its original event (OR commutes and is
        idempotent); the buffer contents are left in place — only the
        live count resets, and cov_push masks dead entries to 0 anyway,
        so stale tails stay deterministic for check_determinism."""
        with _xprof.scope("cov_flush"):
            cov = state.cov
            new_map = cov_flush_batch(
                cov["map"], cov["buf"], cov["buf_n"],
                use_pallas=self.use_pallas_pop,
                interpret=self._pallas_interpret,
            )
            zeros = jnp.zeros_like(cov["buf_n"])
            return state.replace(cov=dict(cov, map=new_map, buf_n=zeros))

    def run_segment(self, state: LaneState, segment_steps: int) -> LaneState:
        """Advance the batch at most `segment_steps` events per lane (stops
        early if every lane finishes). Building block for streaming.

        In the buffered-coverage regime the body folds the slot buffers
        into the bit maps every `_cov_flush_every` iterations (a SCALAR
        cadence predicate — the untaken branch costs nothing), and an
        unconditional exit flush runs after the loop. The exit flush is
        what makes flush-on-freeze safe with no per-lane bookkeeping: a
        lane frozen mid-segment (done/failed; step_batch's `active`
        mask) simply stops appending, and whatever its buffer holds is
        folded here before any consumer — run_batch's harvest, the
        stream's cov-map OR — can observe the map."""

        def cond(carry):
            s, it = carry
            with _xprof.collective_scope("segment-done-any"):
                # madsim: collective(segment-done-any, reduce=any) — the
                # while-cond early-exit mask: under the mesh this is the one
                # designed per-event-step collective (a 1-bit or-all-reduce)
                return (it < segment_steps) & jnp.any(~(s.done | s.failed))

        def body(carry):
            s, it = carry
            s, it = self.step_batch(s), it + 1
            if self._cov_buffered:
                # cadence flush: overflow is impossible by construction
                # (cov_buffer // slots_per_step iterations fill at most
                # cov_buffer entries), so no per-event overflow branch
                # ever touches the map. The predicate is a scalar, so
                # only the taken branch executes.
                s = lax.cond(
                    it % self._cov_flush_every == 0,
                    self._cov_flush_batch,
                    lambda x: x,
                    s,
                )
            return s, it

        with _xprof.scope("step"):
            final, _ = lax.while_loop(cond, body, (state, jnp.int32(0)))
        if self._cov_buffered:
            # segment-exit flush — skipped only when NO lane holds a
            # buffered slot (e.g. segment_steps is a multiple of the
            # cadence, so the last body flush already drained; or every
            # lane froze before appending), which the any-reduce below
            # detects. cov-buffer-fold in srules.COLLECTIVES.
            with _xprof.scope("cov_flush"):
                with _xprof.collective_scope("cov-buffer-fold"):
                    # madsim: collective(cov-buffer-fold, reduce=or)
                    pending = jnp.any(final.cov["buf_n"] > 0)
                final = lax.cond(
                    pending, self._cov_flush_batch, lambda x: x, final
                )
        return final

    def _stream_fns(
        self,
        segment_steps: int,
        max_steps: int,
        ring_capacity: int,
        batch: int,
        donate: bool = True,
        segments_per_dispatch: int = 8,
        aot: bool = False,
        mesh=None,
    ):
        """Jitted building blocks for run_stream, cached per shape-affecting
        params (fresh jit wrappers would recompile on every call).

        Returns (init_carry, segment, supersegment, reset_rings).

        With `mesh` (a 1-D "batch" mesh, parallel.make_mesh), the four
        fns are jitted with EXPLICIT in/out_shardings derived from the
        declared carry-axis table (`parallel.carry_shardings` over
        `analysis.srules.CARRY_AXES`): every lane leaf pinned
        `NamedSharding(mesh, P("batch"))`, every global leaf replicated
        `P()` — one hunt spans all devices as a single jitted SPMD
        program, donation preserved. The pinned layout is what places
        the 17 registered collectives (srules.COLLECTIVES) at segment
        boundaries: per-lane state never crosses devices inside the
        per-event loop, because only the segment-level folds (refill
        count/ranks, harvest-completed, ring appends, fr folds,
        cov-map OR) read lane values into replicated leaves. `mesh` is
        part of the fns cache key; `aot` and `mesh` are mutually
        exclusive (exported modules are traced unsharded).

        `segment` / `supersegment` / `reset_rings` donate their
        StreamCarry argument when `donate` (the multi-MB lane state is
        aliased in place instead of copied in HBM every call;
        `donate=False` is the tests' reference for bit-identity). A
        donated carry is CONSUMED: never touch a carry after passing it
        back in — read counters/rings first.

        `supersegment` is the pipelined executor's device half: a
        `lax.scan` of `segments_per_dispatch` iterations, each a
        `lax.cond` on the go-predicate (`completed < need` and no ring
        past its drain mark) around one whole segment (refill + advance
        + harvest) — the exact conditions the r5 host loop evaluated
        between segments, checked ON DEVICE. The predicate is monotone
        within a dispatch: `completed` only grows and the rings only
        fill (drains happen on the host between dispatches), so once it
        flips false it stays false, the parked iterations execute
        nothing, and the executed segment sequence is bit-identical to
        the per-segment driver's. When a ring crosses its drain mark
        (count > cap - batch) the dispatch parks until the host drains,
        which bounds appends at `cap` regardless of how many dispatches
        are in flight."""
        cache = getattr(self, "_stream_cache", None)
        if cache is None:
            cache = self._stream_cache = {}
        if aot and mesh is not None:
            raise ValueError(
                "AOT stream fns cannot serve a meshed run: jax.export "
                "modules are traced with unsharded avals (run_stream "
                "gates aot to mesh=None)"
            )
        # jax.sharding.Mesh hashes by (devices, axis names), so two
        # calls with equal meshes share one quartet. The phase scopes
        # (perf/xprof.scope) are always in the traced program, so no
        # measurement gate selects a quartet: the run that is profiled
        # compiles the program that was timed.
        key = (segment_steps, max_steps, ring_capacity, batch, donate,
               segments_per_dispatch, aot, mesh)
        if key in cache:
            return cache[key]

        cap = ring_capacity
        drain_mark = cap - batch

        def _append_ring(buf, count, mask, values):
            """Scatter-free ordered append: masked lane of rank r (in lane
            order) lands at ring slot count+r. Inverted as a gather — slot
            j's source lane is the first lane whose inclusive cumsum equals
            j-count+1 (searchsorted: O(cap log L), vs O(cap*L) for a
            one-hot matrix) — so it stays cheap at pod-scale batches.
            Entries past capacity are dropped; the host's drain policy
            makes that unreachable."""
            with _xprof.scope("ring_append"):
                with _xprof.collective_scope("ring-append-ranks"):
                    # madsim: collective(ring-append-ranks, reduce=scan)
                    csum = jnp.cumsum(mask.astype(jnp.int32))  # [L], rank+1 at masked lanes
                n_new = csum[-1]
                want_rank = jnp.arange(cap, dtype=jnp.int32) - count + 1  # 1-based
                src = jnp.searchsorted(csum, want_rank, side="left").astype(jnp.int32)
                fills = (want_rank >= 1) & (want_rank <= n_new)
                with _xprof.collective_scope("ring-append-gather"):
                    # madsim: collective(ring-append-gather, reduce=gather)
                    vals = values[jnp.clip(src, 0, mask.shape[0] - 1)]
                buf = jnp.where(fills, vals, buf)
                return buf, count + n_new

        def _counters(c: StreamCarry) -> jax.Array:
            with _xprof.scope("counters"):
                return _counters_impl(c)

        def _counters_impl(c: StreamCarry) -> jax.Array:
            over = (c.fail_count > cap) | (c.ab_count > cap)
            return jnp.stack(
                [
                    c.completed.astype(jnp.uint32),
                    c.fail_count.astype(jnp.uint32),
                    c.ab_count.astype(jnp.uint32),
                    c.next_seed,
                    over.astype(jnp.uint32),
                    c.segments.astype(jnp.uint32),
                    # global coverage slots hit: rides the one small
                    # counters transfer the host polls anyway, so the
                    # live coverage curve costs zero extra syncs. Gate
                    # off = a literal zero — the popcount op itself is
                    # specialized out of the lowered segment (the
                    # gate-off HLO pin in tests/test_step_gates.py
                    # string-matches its absence).
                    (
                        lax.population_count(c.cov_map).sum(dtype=jnp.uint32)
                        if self.config.coverage
                        else jnp.uint32(0)
                    ),
                ]
            )

        def init_carry(seeds) -> StreamCarry:
            with _xprof.collective_scope("seed-counter-init"):
                # madsim: collective(seed-counter-init, reduce=gather)
                next_seed0 = seeds[-1] + jnp.uint32(1)
            c = StreamCarry(
                state=self.init_batch(seeds),
                seeds=seeds,
                done=jnp.zeros((seeds.shape[0],), bool),
                next_seed=next_seed0,
                completed=jnp.int32(0),
                segments=jnp.int32(0),
                fail_seeds=jnp.zeros((cap,), jnp.uint32),
                fail_codes=jnp.zeros((cap,), jnp.int32),
                fail_provs=jnp.zeros(
                    (cap if self.config.provenance else 0,), jnp.uint32
                ),
                fail_count=jnp.int32(0),
                ab_seeds=jnp.zeros((cap,), jnp.uint32),
                ab_count=jnp.int32(0),
                counters=jnp.zeros((7,), jnp.uint32),
                # recorder off: a ZERO-LENGTH leaf, not a vector of
                # zeros — the dead operand would otherwise ride the
                # whole supersegment while_loop carry (the host-visible
                # schema is unaffected: the stats dict synthesizes
                # nothing unless the gate is on)
                fr_metrics=jnp.zeros(
                    (self._fr_metrics_len,),
                    jnp.int32,
                ),
                cov_map=(
                    empty_cov_map(self.config.cov_slots_log2)
                    if self.config.coverage
                    else jnp.zeros((0,), jnp.int32)
                ),
            )
            return c.replace(counters=_counters(c))

        def _segment_impl(c: StreamCarry) -> StreamCarry:
            # 1. refill lanes harvested at the end of the previous segment
            #    (device-side ranks + seed counter: gapless, in lane order)
            with _xprof.scope("refill"):
                with _xprof.collective_scope("refill-count"):
                    n_refill = c.done.sum(dtype=jnp.int32)  # madsim: collective(refill-count, reduce=sum)

                def do_refill(_):
                    with _xprof.collective_scope("refill-ranks"):
                        # madsim: collective(refill-ranks, reduce=scan)
                        ranks = jnp.cumsum(c.done.astype(jnp.int32)) - 1
                    fresh_seeds = c.next_seed + ranks.astype(jnp.uint32)
                    fresh = self.init_batch(fresh_seeds)
                    return (
                        tree_where(c.done, fresh, c.state),
                        jnp.where(c.done, fresh_seeds, c.seeds),
                        c.next_seed + n_refill.astype(jnp.uint32),
                    )

                state, seeds, next_seed = lax.cond(
                    n_refill > 0,
                    do_refill,
                    lambda _: (c.state, c.seeds, c.next_seed),
                    None,
                )

            # 2. advance the batch one segment
            state = self.run_segment(state, segment_steps)

            # 3. harvest on-device: count completions, ring-append failing
            #    seeds/codes and abandoned (over-cap) seeds
            with _xprof.scope("harvest"):
                over_cap = state.step >= max_steps
                done = state.done | state.failed | over_cap
                with _xprof.collective_scope("harvest-completed"):
                    completed = c.completed + done.sum(dtype=jnp.int32)  # madsim: collective(harvest-completed, reduce=sum)
                fail_mask = done & state.failed
                fail_seeds, fail_count = _append_ring(
                    c.fail_seeds, c.fail_count, fail_mask, seeds
                )
                fail_codes, _ = _append_ring(
                    c.fail_codes, c.fail_count, fail_mask, state.fail_code
                )
                # violation provenance words ride the same failure ring —
                # harvested with the seeds/codes at the existing drain,
                # zero extra steady-state syncs
                fail_provs = c.fail_provs
                if self.config.provenance:
                    fail_provs, _ = _append_ring(
                        c.fail_provs, c.fail_count, fail_mask, state.fail_prov
                    )
                ab_mask = done & ~state.failed & over_cap
                ab_seeds, ab_count = _append_ring(
                    c.ab_seeds, c.ab_count, ab_mask, seeds
                )

            # flight-recorder totals ride the harvest: injection counts
            # of lanes finishing THIS segment sum in, high-water marks
            # max in — one small device-resident vector, read by the
            # host only at the final drain (zero extra steady-state
            # syncs)
            fr_metrics = c.fr_metrics
            if self.config.flight_recorder:
                with _xprof.scope("fr_fold"):
                    frs = state.fr
                    nk = len(FAULT_KIND_NAMES)
                    ne = len(FR_EXTRA_NAMES)
                    with _xprof.collective_scope("fr-fold"):
                        # madsim: collective(fr-fold, reduce=sum)
                        inj_tot = fr_metrics[:nk] + (
                            frs["inj"] * done[:, None].astype(jnp.int32)
                        ).sum(axis=0)
                        extra_tot = jnp.stack(
                            [
                                # madsim: collective(fr-fold, reduce=sum)
                                fr_metrics[nk + i] + jnp.where(done, frs[k], 0).sum()
                                for i, k in enumerate(FR_EXTRA_NAMES)
                            ]
                        )
                    with _xprof.collective_scope("fr-hwm"):
                        hwm = jnp.stack(
                            [
                                jnp.maximum(
                                    fr_metrics[nk + ne + i],
                                    # madsim: collective(fr-hwm, reduce=max)
                                    jnp.where(done, frs[k], 0).max(),
                                )
                                for i, k in enumerate(
                                    ("q_hwm", "clog_hwm", "kill_hwm")
                                )
                            ]
                        )
                    parts = [inj_tot, extra_tot, hwm]
                    if self.config.faults.churn is not None:
                        # the process's applied faults ride the tail
                        book = state.churn
                        base = FR_METRICS_LEN
                        with _xprof.collective_scope("fr-fold"):
                            parts.append(jnp.stack([
                                # madsim: collective(fr-fold, reduce=sum)
                                fr_metrics[base + i] + jnp.where(done, book[k], 0).sum()
                                for i, k in enumerate(
                                    churn_counter_names(self.config.faults.churn)
                                )
                            ]))
                    names = self.machine.STREAM_COUNTERS
                    if names:
                        # the machine's totals of the lanes finishing
                        # this segment: summed, or folded with max
                        mine = jax.vmap(self.machine.stream_counters)(state.nodes)
                        mine = mine * done[:, None].astype(jnp.int32)
                        prev = fr_metrics[self._fr_metrics_len - len(names):]
                        with _xprof.collective_scope("fr-fold"):
                            # madsim: collective(fr-fold, reduce=sum)
                            summed = prev + mine.sum(axis=0)
                        with _xprof.collective_scope("fr-hwm"):
                            # madsim: collective(fr-hwm, reduce=max)
                            maxed = jnp.maximum(prev, mine.max(axis=0))
                        folds_max = jnp.asarray(
                            [k in self.machine.STREAM_COUNTERS_MAX for k in names]
                        )
                        parts.append(jnp.where(folds_max, maxed, summed))
                    fr_metrics = jnp.concatenate(parts)

            # coverage rides the harvest too: OR every lane's bit map
            # into the global vector. ALL lanes, not just done ones —
            # lane maps are monotone (bits only set), so the fold is
            # idempotent and in-flight lanes contribute their partial
            # coverage to the live curve the host polls.
            cov_map = c.cov_map
            if self.config.coverage:
                # the cov-map-or collective lives in cov_fold_words
                with _xprof.scope("cov_fold"), _xprof.collective_scope(
                    "cov-map-or"
                ):
                    cov_map = cov_map | cov_fold_words(
                        state.cov["map"],
                        shards=mesh.size if mesh is not None else 1,
                    )

            new = StreamCarry(
                state=state,
                seeds=seeds,
                done=done,
                next_seed=next_seed,
                completed=completed,
                segments=c.segments + 1,
                fail_seeds=fail_seeds,
                fail_codes=fail_codes,
                fail_provs=fail_provs,
                fail_count=fail_count,
                ab_seeds=ab_seeds,
                ab_count=ab_count,
                counters=c.counters,
                fr_metrics=fr_metrics,
                cov_map=cov_map,
            )
            return new.replace(counters=_counters(new))

        def _dispatch_go(cc: StreamCarry, need):
            # The host loop's between-segment checks, moved on-device:
            # stop at the completion target (same crossing as the r5
            # per-segment driver — bit-identical executed-segment
            # sequence for any dispatch depth), park on ring pressure
            # (host must drain), else advance another whole segment.
            pressure = (cc.fail_count > drain_mark) | (cc.ab_count > drain_mark)
            return (cc.completed < need) & ~pressure

        def supersegment(c: StreamCarry, need) -> StreamCarry:
            def body(cc, _):
                cc = lax.cond(
                    _dispatch_go(cc, need),
                    _segment_impl,
                    lambda x: x,
                    cc,
                )
                return cc, None

            final, _ = lax.scan(body, c, None, length=segments_per_dispatch)
            return final

        def reset_rings(c: StreamCarry) -> StreamCarry:
            new = c.replace(fail_count=jnp.int32(0), ab_count=jnp.int32(0))
            return new.replace(counters=_counters(new))

        donate_kw = {"donate_argnums": (0,)} if donate else {}
        if mesh is not None:
            # The mesh path: pin every leaf's placement at the jit
            # boundary per the declared CARRY_AXES axis. Donation
            # composes because in_shardings == out_shardings per leaf —
            # XLA aliases each shard of the donated carry in place, the
            # same zero-copy contract as the single-device path (T003
            # guards the rebuild site). `need` is a replicated scalar.
            from ..parallel import carry_shardings, seed_sharding
            from jax.sharding import NamedSharding, PartitionSpec

            seeds_aval = jax.ShapeDtypeStruct((batch,), jnp.uint32)
            cshard = carry_shardings(
                mesh, jax.eval_shape(init_carry, seeds_aval)
            )
            repl = NamedSharding(mesh, PartitionSpec())
            fns = (
                jax.jit(
                    init_carry,
                    in_shardings=(seed_sharding(mesh),),
                    out_shardings=cshard,
                ),
                jax.jit(
                    _segment_impl,
                    in_shardings=(cshard,),
                    out_shardings=cshard,
                    **donate_kw,
                ),
                jax.jit(
                    supersegment,
                    in_shardings=(cshard, repl),
                    out_shardings=cshard,
                    **donate_kw,
                ),
                jax.jit(
                    reset_rings,
                    in_shardings=(cshard,),
                    out_shardings=cshard,
                    **donate_kw,
                ),
            )
            cache[key] = fns
            return fns
        fns = (
            jax.jit(init_carry),
            jax.jit(_segment_impl, **donate_kw),
            jax.jit(supersegment, **donate_kw),
            jax.jit(reset_rings, **donate_kw),
        )
        if aot:
            fns = self._aot_stream_fns(
                fns,
                (init_carry, _segment_impl, supersegment, reset_rings),
                donate_kw=donate_kw,
                batch=batch,
                fns_key=key,
            )
        cache[key] = fns
        return fns

    def _aot_stream_fns(self, jitted, raw, *, donate_kw, batch, fns_key):
        """AOT-serialize the streaming fns via `jax.export`, keyed so a
        warm fleet worker deserializes the traced+lowered StableHLO
        instead of re-tracing Python (the r11 flagship warm start was
        18.2 s, TRACE-dominated — the persistent XLA cache already
        covers the compile half).

        Key = `compile_cache.cache_subkey` (jax version / stream / lane
        shape / device topology) + a sha1 over the package source fingerprint, the full
        EngineConfig, the machine identity and scalar params, the
        stream-fns shape tuple, the kernel-backend flags and the jax
        backend — everything that can change the traced program. A key
        that misses (or a corrupt/stale artifact) degrades to a live
        trace which is then exported and saved for the next worker.
        Every path EXECUTES through `jax.jit(exported.call)` — never
        mixing "exported on warm, plain jit on cold" — so both paths
        compile the same exported-call HLO and share one persistent
        XLA cache entry.

        Only called with `mesh is None` (run_stream gates it): an
        exported module is traced with unsharded avals, and replaying
        it under explicit shardings would silently drop the layout
        contract."""
        import hashlib
        import time

        from jax import export as jexport

        from .. import compile_cache as _cc

        m = self.machine
        scalars = {
            k: v
            for k, v in sorted(vars(m).items())
            if isinstance(v, (int, float, str, bool))
        }
        ident = "|".join(
            [
                _cc.source_fingerprint(),
                repr(self.config),
                f"{type(m).__module__}.{type(m).__qualname__}",
                repr(scalars),
                repr(fns_key),
                repr(
                    (
                        self.use_pallas_pop,
                        self.use_megakernel,
                        self._pallas_interpret,
                    )
                ),
                jax.default_backend(),
            ]
        )
        # devices=1: an exported module is a SINGLE-device program by
        # construction (this path is gated to mesh=None). The explicit
        # topology in the key is the refusal contract — if meshed
        # exports ever land, their d{mesh.size} artifacts can never be
        # deserialized into an unsharded run or vice versa.
        subkey = (
            _cc.cache_subkey(
                rng_stream=self.config.rng_stream, lanes=batch, devices=1
            )
            + "-"
            + hashlib.sha1(ident.encode()).hexdigest()[:16]
        )
        avals = self._stream_avals(jitted[0], batch)
        carry_aval = avals["reset_rings"][0]
        # jax.export cannot serialize custom pytree nodes (the flax
        # struct dataclasses and model states riding the carry), so
        # each fn is exported over FLAT LEAF LISTS and the pytree
        # structure is rebuilt at the call boundary. The treedefs come
        # from a local eval_shape — abstract tracing, milliseconds —
        # never from the artifact, so structure drift between writer
        # and reader surfaces as a leaf-count/shape mismatch (a loud
        # error), not a misdecoded tree.
        out_tree = jax.tree.structure(carry_aval)

        def _make_flat(rfn, in_tree):
            def flat_fn(*leaves):
                args = jax.tree.unflatten(in_tree, list(leaves))
                return tuple(jax.tree.leaves(rfn(*args)))

            return flat_fn

        def _make_wrapped(exp):
            def from_export(*args):
                flat = exp.call(*jax.tree.leaves(args))
                return jax.tree.unflatten(out_tree, list(flat))

            return from_export

        timings = self.compile_timings = {
            "trace_s": 0.0,
            "aot_hits": [],
            "aot_misses": [],
            "aot_key": subkey,
        }
        out = []
        for name, jfn, rfn in zip(STREAM_PROGRAMS, jitted, raw):
            kw = {} if name == "init_carry" else donate_kw
            in_leaves, in_tree = jax.tree.flatten(avals[name])
            exp = None
            blob = _cc.load_aot(subkey, name)
            if blob is not None:
                try:
                    exp = jexport.deserialize(bytearray(blob))
                    timings["aot_hits"].append(name)
                except Exception as e:
                    _stream_log.warning(
                        "corrupt AOT artifact %s/%s (%s); re-tracing",
                        subkey, name, e,
                    )
                    exp = None
            if exp is None:
                t0 = time.perf_counter()  # madsim: allow(D001) — host-side timing
                try:
                    exp = jexport.export(jax.jit(_make_flat(rfn, in_tree)))(
                        *in_leaves
                    )
                    blob = bytes(exp.serialize())
                except Exception as e:
                    _stream_log.warning(
                        "jax.export failed for %s (%s); falling back to "
                        "plain jit for this process", name, e,
                    )
                    out.append(jfn)
                    continue
                timings["trace_s"] += time.perf_counter() - t0  # madsim: allow(D001)
                timings["aot_misses"].append(name)
                _cc.save_aot(subkey, name, blob)
            out.append(jax.jit(_make_wrapped(exp), **kw))
        return tuple(out)

    @staticmethod
    def _stream_avals(init_carry, batch: int) -> dict:
        """{program name: its argument avals} for the quartet at `batch`
        lanes (`init_carry` is the quartet's own: its output IS the
        carry the other three take)."""
        seeds_aval = jax.ShapeDtypeStruct((batch,), jnp.uint32)
        carry_aval = jax.eval_shape(init_carry, seeds_aval)
        need_aval = jax.ShapeDtypeStruct((), jnp.int32)
        return {
            "init_carry": (seeds_aval,),
            "segment": (carry_aval,),
            "supersegment": (carry_aval, need_aval),
            "reset_rings": (carry_aval,),
        }

    def _stream_quartet(
        self,
        *,
        batch: int,
        segment_steps: int,
        max_steps: int,
        mesh,
        segments_per_dispatch: int,
        donate: bool,
    ):
        """`_stream_fns` under the key a `run_stream` with these
        arguments uses, after refusing what a meshed run cannot take."""
        from ..compile_cache import aot_enabled

        if mesh is not None:
            from ..parallel import check_lane_split

            check_lane_split(mesh, batch)
            if mesh.size > 1 and (self.use_pallas_pop or self.use_megakernel):
                raise ValueError(
                    "meshed runs need the Pallas kernels off "
                    "(MADSIM_TPU_PALLAS_POP=0 / MADSIM_TPU_PALLAS_MEGAKERNEL=0, "
                    "or Engine(use_pallas_pop=False)): pallas_call blocks "
                    "GSPMD sharding propagation, so the lane-pinned layout "
                    "cannot cross it"
                )
        # Ring capacity 2 * batch: the device parks at the drain mark
        # (cap - batch), and one segment can complete at most `batch`
        # lanes, so the rings can never overflow no matter how many
        # dispatches are in flight. AOT deserialization of the
        # streaming fns ($MADSIM_TPU_AOT_CACHE,
        # compile_cache.aot_enabled) is gated to the unsharded path —
        # an exported module is traced without shardings, and replaying
        # it under a mesh would drop the layout contract.
        return self._stream_fns(
            segment_steps, max_steps, 2 * batch, batch,
            donate=donate, segments_per_dispatch=segments_per_dispatch,
            aot=mesh is None and aot_enabled(),
            mesh=mesh,
        )

    def _stream_executables(self, quartet, batch: int, pipelined: bool):
        """(held, made): `held` maps a jitted stream fn to the COMPILED
        executable this engine keeps for it, and holds — made here where
        missing — the three programs a `run_stream` over `quartet`
        calls: `init_carry`, `supersegment` (`segment` where not
        `pipelined`), `reset_rings`. `made` counts those this call had
        to make; at 0 nothing was traced, lowered, compiled or read.

        A program is made by the AOT stages of its jitted fn (plain,
        meshed or exported-call alike) over `_stream_avals` — trace,
        lower, compile-or-read-from-the-persistent-cache — and nothing
        is dispatched to the device. jax's AOT path does not feed
        `jit`'s call cache, so the executable itself is kept and
        `run_stream` calls what is kept: one way to a program, whether
        `prepare_stream` or a first `run_stream` asked for it. Each
        make is a `compile` span (arg `program`) and its stages go to
        `perf/compile_log.py` under the program's name."""
        from ..perf import compile_log
        from ..perf.recorder import maybe_span

        fns = dict(zip(STREAM_PROGRAMS, quartet))
        del fns["segment" if pipelined else "supersegment"]
        # keyed by the jitted fn (cached per stream key on the engine,
        # so the dict holds no extra lifetime)
        held = self.__dict__.setdefault("_stream_compiled", {})
        missing = [name for name, fn in fns.items() if fn not in held]
        if missing:
            avals = self._stream_avals(fns["init_carry"], batch)
            for name in missing:
                with compile_log.program(name), \
                        maybe_span("compile", program=name):
                    held[fns[name]] = fns[name].lower(*avals[name]).compile()
        return held, len(missing)

    def prepare_stream(
        self,
        batch: int = 1024,
        segment_steps: int = 256,
        max_steps: int = 10_000,
        mesh=None,
        pipelined: bool = True,
        segments_per_dispatch: int = 8,
        donate: bool = True,
    ) -> int:
        """Make ready the programs a `run_stream` with these arguments
        calls, WITHOUT running them: they are traced, lowered and
        compiled (or read from the persistent cache), nothing is
        dispatched to the device, and the `run_stream` calls that
        follow execute exactly these executables. Returns how many
        programs had to be made — 0 on an engine that already holds
        them, where this does nothing at all. Counted as
        `stream.programs_ready_hit` / `stream.programs_ready_miss`."""
        from ..perf.recorder import maybe_count

        quartet = self._stream_quartet(
            batch=batch, segment_steps=segment_steps, max_steps=max_steps,
            mesh=mesh, segments_per_dispatch=segments_per_dispatch,
            donate=donate,
        )
        _, made = self._stream_executables(quartet, batch, pipelined)
        maybe_count(
            "stream.programs_ready_miss" if made else "stream.programs_ready_hit"
        )
        return made

    def stream_compile_autopsy(
        self,
        batch: int,
        segment_steps: int = 256,
        max_steps: int = 10_000,
        segments_per_dispatch: int = 8,
        donate: bool = True,
        mesh=None,
    ) -> list:
        """Per-fn compile autopsy of the streaming quartet at this
        shape: trace_s / lower_s / backend_s plus cost_analysis flops /
        bytes and memory_analysis peak bytes for each of init_carry,
        segment, supersegment, reset_rings — the `compile_s` opaque
        total split into the three stages the [perf] open item needs
        apart (perf/xprof.compile_autopsy; `prof compile`).
        Re-traces by construction, so run it on a throwaway engine or
        accept the duplicate trace cost."""
        from ..perf import xprof

        fns = self._stream_fns(
            segment_steps, max_steps, 2 * batch, batch,
            donate=donate, segments_per_dispatch=segments_per_dispatch,
            mesh=mesh,
        )
        avals = self._stream_avals(fns[0], batch)
        return [
            xprof.compile_autopsy(fn, avals[name], label=name)
            for name, fn in zip(STREAM_PROGRAMS, fns)
        ]

    def run_stream(self, n_seeds: int, **kwargs):
        """See `_run_stream_impl` (the real docstring). This wrapper
        puts the WHOLE streaming call on the host timeline as one
        outer `run_stream` span when a PerfRecorder is active: on a
        host that shares cores with the XLA compute threads (the
        1-core CPU reference box), device execution shows up as the
        host thread being starved at arbitrary points BETWEEN the
        inner spans — the outer span captures it, and the recorder
        reports it as `device_wait` (outer-span time not covered by
        any inner span) instead of losing it to unattributed gaps."""
        from ..perf.recorder import current_recorder

        perf = current_recorder()
        if perf is None:
            return self._run_stream_impl(n_seeds, **kwargs)
        with perf.span(
            "run_stream", n_seeds=n_seeds, batch=kwargs.get("batch", 1024)
        ):
            return self._run_stream_impl(n_seeds, **kwargs)

    def _run_stream_impl(
        self,
        n_seeds: int,
        batch: int = 1024,
        segment_steps: int = 256,
        seed_start: int = 0,
        max_steps: int = 10_000,
        mesh=None,
        pipelined: bool = True,
        segments_per_dispatch: int = 8,
        dispatch_depth: int = 4,
        donate: bool = True,
    ):
        """Continuous seed streaming: run at least n_seeds simulations
        keeping every lane busy. Each segment — refill previously-finished
        lanes with fresh seeds (device-side cumsum ranks + a
        device-resident next-seed counter), advance `segment_steps`
        events, then harvest completions into on-device result rings —
        is fused device work; the host only ever reads the small
        `counters` array and drains the failing/abandoned rings when
        they near capacity.

        The PIPELINED executor dispatches `segments_per_dispatch`
        segments per jitted call (an inner device `lax.scan` with the
        termination and ring-pressure checks on-device) and keeps
        `dispatch_depth` such calls in flight before one blocking
        counters read — the steady state runs with ZERO blocking host
        syncs between segments, vs one per segment for the r5 driver
        (`pipelined=False`, the tests' reference; both executors run the
        bit-identical segment sequence, so results are equal by
        construction). All streaming ops donate the multi-MB StreamCarry
        (`donate=False` is the tests' reference), so XLA aliases the
        lane state in HBM instead of copying it every call.

        Seed coverage is gapless: exactly the range
        [seed_start, seed_start + seeds_consumed) enters lanes, in order.
        Lanes exceeding `max_steps` events are abandoned and reported.

        With `mesh` (a 1-D "batch" mesh, parallel.make_mesh), one hunt
        spans all mesh devices as a single jitted SPMD program: every
        StreamCarry leaf is PINNED at the jit boundary per its declared
        `analysis.srules.CARRY_AXES` axis (lane leaves
        `NamedSharding(mesh, P("batch"))`, global leaves replicated
        P()), donation preserved. The 17 registered collectives
        (srules.COLLECTIVES) become their declared all-reduce /
        all-gather at segment boundaries — per-lane state never crosses
        devices inside the per-event loop; the counters poll and the
        coverage-OR are tiny cross-device reductions read at poll
        cadence, and the ring drain gathers only failing lanes (the
        rings are replicated leaves, so host reads stay O(polls +
        drains), never O(devices)). Results are byte-identical to the
        unsharded run at ANY device count: lane keys derive from the
        seed alone (init_lane's per-seed PRNGKey), and every cross-lane
        op computes over the full logical [L] axis under GSPMD — the
        shard-count invariance tests/test_mesh.py pins. `batch` must be
        a multiple of the mesh size.

        Returns {"completed", "failing": [(seed, code)...], "infra":
        [(seed, code)...] (infrastructure artifacts: OVERFLOW lanes —
        queue-capacity aborts, not protocol findings), "abandoned":
        [seed...], "seeds_consumed", "stats": {host_syncs, drains,
        dispatches, device_segments, dispatch_depth,
        segments_per_dispatch, donation, pipelined}}. With
        `config.coverage`, stats additionally carry "coverage"
        (slots_hit / slots_total / fraction / by_band / curve — the
        (completed, slots_hit) pair at every poll) and the result dict a
        "coverage_map" bool array (the global OR of lane maps, the
        artifact `hunt --coverage-out` persists). With
        `config.provenance`, the result dict gains "provenance"
        {seed: violation provenance word} for every drained failing
        lane (engine/provenance.py decodes the words to implicated
        faults).
        """
        import numpy as np

        if segments_per_dispatch < 1 or dispatch_depth < 1:
            raise ValueError("segments_per_dispatch and dispatch_depth must be >= 1")

        init_carry, segment, supersegment, reset_rings = self._stream_quartet(
            batch=batch, segment_steps=segment_steps, max_steps=max_steps,
            mesh=mesh, segments_per_dispatch=segments_per_dispatch,
            donate=donate,
        )
        # a stream program is named by its jitted fn below and run as
        # the executable the engine holds for it
        programs, _ = self._stream_executables(
            (init_carry, segment, supersegment, reset_rings), batch, pipelined
        )
        ring_capacity = 2 * batch

        # host-made: a `jnp.arange` would compile an `iota` on a
        # process's first batch
        seeds = np.arange(seed_start, seed_start + batch, dtype=np.uint32)
        if mesh is not None:
            from ..parallel import shard_seeds

            seeds = shard_seeds(seeds, mesh)  # validates mesh axis + batch

        failing: list = []
        infra: list = []
        abandoned: list = []
        # seed -> violation provenance word (EngineConfig.provenance):
        # filled at the same ring drains that surface the seeds
        prov_by_seed: dict = {}
        stats = {"host_syncs": 0, "drains": 0, "dispatches": 0,
                 "dispatch_retries": 0}
        # (completed, slots_hit) at every blocking poll: the live
        # coverage curve — its deltas are the "new slots this poll
        # cycle" signal the plateau detector and StatsEmitter consume
        cov_curve: list = []

        # Transient-backend retry: device dispatches and the blocking
        # counter/ring reads ride a small retry-with-backoff so a
        # transient backend error doesn't abort an hour-long hunt; a
        # non-transient error (including "donated buffer deleted" — a
        # dispatch that died AFTER consuming its carry cannot be safely
        # replayed) propagates immediately, and exhausted retries fail
        # loud with the attempt count. Counted in stats.
        from .._dispatch_retry import retry_transient

        # Host-timeline tracing (madsim_tpu/perf): when a PerfRecorder
        # is active in this context (--perf-timeline / `perf`), every
        # dispatch/poll/drain below lands on the host timeline as a
        # span. Pure host-side wall-clock accounting — no RNG words, no
        # device-visible values, so streams are untouched by
        # construction. Every program called below is a held executable
        # (`_stream_executables`: made under its own `compile` span), so
        # a dispatch never traces or compiles, the first one included.
        from ..perf.recorder import current_recorder, maybe_span

        perf = current_recorder()

        def _dispatch(what, fn, *fn_args, span=None):
            def on_retry(attempt, exc, delay_s):
                stats["dispatch_retries"] += 1
                import logging

                logging.getLogger("madsim_tpu.stream").warning(
                    "transient backend error on %s (attempt %d, retrying "
                    "in %.2fs): %s", what, attempt, delay_s, exc,
                )

            # One name per executor operation: the recorder's span,
            # which an annotating recorder (PerfRecorder(annotate=True))
            # also writes into a jax.profiler capture as
            # "madsim.<name>" on the clock of the device ops.
            run = programs.get(fn, fn)  # `jax.device_get` is itself
            with maybe_span(span or what):
                return retry_transient(
                    lambda: run(*fn_args), what=what, on_retry=on_retry
                )

        carry = _dispatch("carry init", init_carry, seeds, span="init")

        def drain(c: StreamCarry) -> StreamCarry:
            # madsim: allow(T002) — this IS a designed sync point: the
            # ring drain runs only when a ring crosses its drain mark
            # (or once at stream end), and its cost is budgeted in
            # stats["drains"]; the T002 contract bans *hidden* fetches
            f_seeds, f_codes, f_provs, f_n, a_seeds, a_n = _dispatch(
                "ring drain",
                jax.device_get,
                (c.fail_seeds, c.fail_codes, c.fail_provs, c.fail_count,
                 c.ab_seeds, c.ab_count),
                span="ring_drain",
            )
            stats["drains"] += 1
            stats["host_syncs"] += 1
            for i, (s, code) in enumerate(
                zip(f_seeds[: int(f_n)], f_codes[: int(f_n)])
            ):
                # infra artifacts (fixed-shape overflow aborts) are kept
                # out of the findings bucket: an OVERFLOW lane means
                # "rerun with a bigger queue", not "protocol bug"
                (infra if int(code) == OVERFLOW else failing).append(
                    (int(s), int(code))
                )
                if self.config.provenance:
                    prov_by_seed[int(s)] = int(f_provs[i])
            abandoned.extend(int(s) for s in a_seeds[: int(a_n)])
            return _dispatch("ring reset", reset_rings, c, span="dispatch")

        def poll(c: StreamCarry):
            """The blocking device->host sync: one small counters read."""
            _xprof.sync_marker("counters_poll")
            counters = np.asarray(
                # madsim: allow(T002) — THE designed blocking poll: one
                # small counters read per dispatch_depth dispatches,
                # counted in stats["host_syncs"]; everything else in
                # the dispatch region must stay async
                _dispatch(
                    "counters poll", jax.device_get, c.counters,
                    span="counters_poll",
                )
            )
            stats["host_syncs"] += 1
            if counters[4]:
                raise RuntimeError(
                    "run_stream result ring overflowed (drain policy bug)"
                )
            if self.config.coverage:
                cov_curve.append((int(counters[0]), int(counters[6])))
            return counters

        drain_mark = ring_capacity - batch
        completed = 0
        # hard ceiling well above the expected segment count (progress is
        # guaranteed because over-cap lanes are abandoned at harvest);
        # pipelining adds at most dispatch_depth no-op dispatches per
        # poll cycle, which the per-dispatch ceiling below absorbs
        max_segments = (max_steps // segment_steps + 2) * (n_seeds // batch + 2)

        if pipelined:
            # placed, not converted: `jnp.int32(...)` is an eager op, a
            # compile of its own on a process's first batch
            need = jax.device_put(np.int32(min(n_seeds, 2**31 - 1)))
            max_dispatch = max_segments + dispatch_depth * (n_seeds // batch + 4)
            in_flight = 0
            while completed < n_seeds and stats["dispatches"] < max_dispatch:
                # async dispatch: returns immediately, device work queues
                # behind the donated carry chain
                _xprof.sync_marker("dispatch")
                carry = _dispatch(
                    "supersegment dispatch", supersegment, carry, need,
                    span="dispatch",
                )
                stats["dispatches"] += 1
                in_flight += 1
                if in_flight >= dispatch_depth:
                    in_flight = 0
                    counters = poll(carry)
                    completed = int(counters[0])
                    if (
                        int(counters[1]) > drain_mark
                        or int(counters[2]) > drain_mark
                    ):
                        carry = drain(carry)
        else:
            # r5 executor: one blocking counters read per segment
            while completed < n_seeds and stats["dispatches"] < max_segments:
                _xprof.sync_marker("dispatch")
                carry = _dispatch(
                    "segment dispatch", segment, carry, span="dispatch"
                )
                stats["dispatches"] += 1
                counters = poll(carry)
                completed = int(counters[0])
                if (
                    int(counters[1]) > drain_mark
                    or int(counters[2]) > drain_mark
                ):
                    carry = drain(carry)

        counters = poll(carry)
        carry = drain(carry)
        fr_stats = {}
        if self.config.flight_recorder:
            # one extra small transfer, after streaming is over
            from ..runtime.metrics import fr_metrics_dict

            with (
                perf.span("harvest") if perf else contextlib.nullcontext()
            ):
                fr_vec = jax.device_get(carry.fr_metrics)
            fr_stats = {"flight_recorder": fr_metrics_dict(
                fr_vec, self.machine.STREAM_COUNTERS)}
        cov_stats = {}
        cov_map_np = None
        if self.config.coverage:
            # one extra small transfer (2^14/32 words), after streaming
            # is over: the global map itself, unpacked to the bool[S]
            # form every host-side consumer reads
            from ..runtime.coverage import coverage_dict, unpack_map

            with (
                perf.span("harvest") if perf else contextlib.nullcontext()
            ):
                cov_words = jax.device_get(carry.cov_map)
            cov_map_np = unpack_map(
                np.asarray(cov_words),
                self.config.cov_slots_log2,
            )
            cov_stats = {
                "coverage": {
                    **coverage_dict(
                        cov_map_np, self.config.cov_slots_log2,
                        band_bits=self.cov_band_bits,
                    ),
                    "curve": cov_curve,
                }
            }
        # Device-memory high-water accounting: backends that implement
        # memory_stats (TPU, some GPU builds; CPU returns None) report
        # peak/live HBM for the device the stream ran on. Read only
        # under an active PerfRecorder — one host call, zero device
        # work — and surfaced in stats so the timeline's "is this run
        # memory-pressured" question has an answer next to it.
        mem_stats = {}
        if perf is not None:
            try:
                m = jax.local_devices()[0].memory_stats()
            except Exception:  # backend without the API
                m = None
            if m:
                mem_stats = {
                    "device_memory": {
                        k: int(m[k])
                        for k in (
                            "peak_bytes_in_use", "bytes_in_use", "bytes_limit"
                        )
                        if k in m
                    }
                }
                perf.count("device_peak_bytes",
                           int(m.get("peak_bytes_in_use", 0)))
        out = {
            "completed": int(counters[0]),
            "failing": failing,
            "infra": infra,
            "abandoned": abandoned,
            "seeds_consumed": int(counters[3]) - seed_start,
            "stats": {
                **stats,
                "device_segments": int(counters[5]),
                "dispatch_depth": dispatch_depth if pipelined else 1,
                "segments_per_dispatch": segments_per_dispatch if pipelined else 1,
                "donation": bool(donate),
                "pipelined": bool(pipelined),
                **mem_stats,
                **fr_stats,
                **cov_stats,
            },
        }
        if cov_map_np is not None:
            out["coverage_map"] = cov_map_np
        if self.config.provenance:
            out["provenance"] = prov_by_seed
        return out

    def make_runner(self, max_steps: int = 10_000, mesh=None):
        """A jitted `seeds -> BatchResult`, optionally sharded over a mesh
        axis "seeds" (lanes are embarrassingly parallel; XLA propagates
        the sharding through the whole while_loop)."""
        fn = jax.jit(partial(self.run_batch, max_steps=max_steps))
        if mesh is None:
            return fn

        from ..parallel import shard_seeds

        def sharded(seeds):
            return fn(shard_seeds(seeds, mesh))

        return sharded

    def run_seed_batch(self, seeds, max_steps: int = 10_000) -> dict:
        """Run an EXPLICIT seed vector — one lane per seed, every lane
        to completion, no streaming refill — and decode the result to
        the `run_stream` dict shape. The guided-search batch runner
        (madsim_tpu/search/guided.py): a guided batch is a *chosen* set
        of seeds (corpus mutants + fresh exploration), which the
        streaming executor's contiguous device-side seed counter cannot
        express; `run_batch` takes any vector, so guidance rides the
        fixed path and the streaming hot path stays byte-for-byte
        untouched when guidance is off.

        Returns {"completed", "failing": [(seed, code)...], "infra",
        "abandoned": [seed...], "seeds_consumed", "stats": {}} plus,
        under the coverage gate, "coverage_map" (bool[S] — the OR of
        all lanes) and "cov_lane_words" (the per-lane packed int32 bit
        maps, which is what parent detection diffs), and under the
        provenance gate "provenance" {seed: violation word}."""
        import numpy as np

        seeds = jnp.asarray(np.asarray(list(seeds), dtype=np.uint32))
        cache = self.__dict__.setdefault("_seed_batch_runners", {})
        fn = cache.get(max_steps)
        if fn is None:
            fn = cache[max_steps] = self.make_runner(max_steps=max_steps)
        res = fn(seeds)
        seeds_np = np.asarray(res.seeds)
        done = np.asarray(res.done)
        failed = np.asarray(res.failed)
        codes = np.asarray(res.fail_code)
        failing, infra = [], []
        # madsim: collective(final-fail-gather, reduce=gather)
        for s, c in zip(seeds_np[failed].tolist(), codes[failed].tolist()):
            (infra if int(c) == OVERFLOW else failing).append(
                (int(s), int(c))
            )
        out = {
            "completed": int(seeds_np.shape[0]),
            "failing": failing,
            "infra": infra,
            # over the step budget without finishing: the fixed path's
            # abandonment criterion, mirroring the streaming harvest
            # madsim: collective(final-abandoned-gather, reduce=gather)
            "abandoned": [int(s) for s in seeds_np[~done & ~failed]],
            "seeds_consumed": int(seeds_np.shape[0]),
            "stats": {},
        }
        if self.config.coverage:
            from ..runtime.coverage import unpack_map

            lane_words = np.asarray(res.cov["map"])
            out["cov_lane_words"] = lane_words
            out["coverage_map"] = unpack_map(
                # madsim: collective(final-cov-or, reduce=or)
                np.bitwise_or.reduce(lane_words, axis=0),
                self.config.cov_slots_log2,
            )
        if self.config.provenance:
            out["provenance"] = {
                int(s): int(p)
                for s, p in zip(
                    # madsim: collective(final-prov-gather, reduce=gather)
                    seeds_np[failed].tolist(),
                    # madsim: collective(final-prov-gather, reduce=gather)
                    np.asarray(res.fail_prov)[failed].tolist(),
                )
            }
        return out

    def failing_seeds(self, result: BatchResult) -> jax.Array:
        """Gather the failing lane seeds back to the host
        (the only device->host traffic besides summaries)."""
        # madsim: collective(final-fail-gather, reduce=gather)
        return result.seeds[result.failed]

    def ring_trace(self, result, lane: int):
        """Decode lane `lane`'s on-device event ring into TraceEvents
        (the last `config.trace_ring` events, oldest first) — immediate
        post-mortem without a replay. Requires `trace_ring > 0`."""
        from .replay import decode_ring

        if not self.config.trace_ring:
            raise ValueError("engine built with trace_ring=0 — no ring recorded")
        ring = result.ring
        lane_ring = jax.tree.map(lambda a: a[lane], ring)
        return decode_ring(lane_ring)

    def digest_checkpoints(self, result, lane: int):
        """Decode lane `lane`'s digest checkpoint ring into a list of
        (step, d0, d1) tuples, oldest first (the last
        `config.fr_digest_ring` checkpoints). Requires
        `flight_recorder=True`."""
        from .audit import decode_checkpoint_ring

        if not self.config.flight_recorder:
            raise ValueError(
                "engine built with flight_recorder=False — no digests recorded"
            )
        lane_fr = jax.tree.map(lambda a: a[lane], result.fr)
        return decode_checkpoint_ring(lane_fr)

    def check_determinism(self, seeds: jax.Array, max_steps: int = 10_000) -> BatchResult:
        """Run the batch twice and require exactly equal results — the
        engine-side analogue of `Runtime.check_determinism`
        (reference: sim/runtime/mod.rs:178-203). Catches machines that
        smuggle nondeterminism past the tracer (e.g. host callbacks or
        trace-time Python state)."""
        from ..errors import NonDeterminism

        # Two independent jit wrappers => two traces, so trace-time Python
        # nondeterminism (mutable counters, random.choice in handlers) is
        # caught, not just per-execution effects.
        r1 = jax.jit(partial(self.run_batch, max_steps=max_steps))(seeds)
        r2 = jax.jit(partial(self.run_batch, max_steps=max_steps))(seeds)
        flat1 = jax.tree_util.tree_flatten_with_path(r1)[0]
        flat2 = jax.tree.leaves(r2)
        mismatches = [
            jax.tree_util.keystr(path)
            for (path, a), b in zip(flat1, flat2)
            if not bool((a == b).all())
        ]
        if mismatches:
            raise NonDeterminism(
                f"TPU engine produced different results for identical seed "
                f"batches; diverging leaves: {mismatches}"
            )
        return r1


def _check_lane_spec(machine: Machine) -> None:
    """A machine that declares role-held leaves (`Machine.lane_spec`)
    is held to its declaration: every other leaf of `init()` has the
    node axis. A leaf stored once a lane and not declared would be
    indexed by node in the generic restarts."""
    spec = machine.lane_spec()
    if spec is None:
        return
    n = machine.NUM_NODES
    shapes = jax.eval_shape(machine.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    held = jax.tree.leaves(spec)
    named = jax.tree_util.tree_flatten_with_path(shapes)[0]
    if len(held) != len(named) or not all(
        isinstance(h, (bool, RoleRows)) for h in held
    ):
        raise ValueError(
            f"{type(machine).__name__}.lane_spec() must be congruent to "
            f"init() with a python bool or a RoleRows at every leaf"
        )
    for h, (path, leaf) in zip(held, named):
        where = f"{type(machine).__name__}: leaf {jax.tree_util.keystr(path)}"
        if isinstance(h, RoleRows):
            if not (0 <= h.first and h.count >= 1 and h.first + h.count <= n):
                raise ValueError(f"{where}: {h} names nodes outside 0..{n - 1}")
            want = (h.count * h.width,) if h.width else None
            if leaf.ndim < 1 or (
                leaf.shape != want if want else leaf.shape[0] != h.count
            ):
                raise ValueError(
                    f"{where} has shape {leaf.shape}, not one row for each "
                    f"of the {h.count} nodes of {h}"
                )
        elif not h and (leaf.ndim < 1 or leaf.shape[0] != n):
            raise ValueError(
                f"{where} has shape {leaf.shape}, no node axis of {n}, and "
                f"lane_spec() does not declare it role-held"
            )


def _churn_clog(clogged, disc_bits, up_bits, down, packed: bool):
    """The clog state after a churn event: the nodes of `disc_bits`
    lose every link; a link with an end in `up_bits` carries traffic
    again iff neither end is in `down` (the disconnected set after the
    event). Bitmasks over node ids (N <= 30); with both masks 0 the
    state is returned as it was."""
    n = clogged.shape[0]
    ids = jnp.arange(n)
    if packed:
        # node j of a row is bit j of word 0 (N <= CLOG_WORD_BITS)
        own = jnp.int32(1) << ids
        full = jnp.int32((1 << n) - 1)
        w0 = clogged[:, 0]
        w0 = w0 | jnp.where((disc_bits & own) != 0, full & ~own, disc_bits)
        w0 = jnp.where(
            (down & own) != 0, w0,
            jnp.where((up_bits & own) != 0, w0 & down, w0 & ~up_bits),
        )
        return jnp.stack([w0, clogged[:, 1]], axis=1)
    in_disc = ((disc_bits >> ids) & 1) == 1
    in_up = ((up_bits >> ids) & 1) == 1
    is_down = ((down >> ids) & 1) == 1
    cut = (in_disc[:, None] | in_disc[None, :]) & ~jnp.eye(n, dtype=bool)
    back = (
        (in_up[:, None] | in_up[None, :])
        & ~is_down[:, None] & ~is_down[None, :]
    )
    return (clogged | cut) & ~back


class _Push(NamedTuple):
    """One push an event wants to make: the lane's candidate for a queue
    slot. `dup_of`, static, is the index (in the event's push list) of
    the message this one duplicates: it is wanted only if that one
    landed. `prov` is the pushed event's lineage word (the sender's
    word, plus the dup bit for duplicate copies), read only where the
    provenance gate materializes the eq["prov"] plane."""

    want: Any  # traced bool
    time: Any
    kind: Any
    node: Any
    src: Any
    payload: Any  # int32[P]
    prov: Any = None
    dup_of: Optional[int] = None


def _push_ranked(eq, pushes, next_seq, pinned=None):
    """Land all of one event's pushes in ONE pass over the queue: the
    free slots are ranked once (`ops.free_slot_ranks`) and every leaf is
    written once, by masked selects (no scatters).

    The k-th wanted push lands in the k-th lowest free slot with seq
    `next_seq + k`; once the free slots run out every later wanted push
    overflows — what a first-free scan and a whole-queue write per push,
    in sequence, arrive at, at one K-th of the passes. `pinned`, a
    `(slot, push)`, is a push into a slot of its own that is known to be
    free (the churn re-arm of the popped slot): it takes the first seq
    and its slot is ranked as taken.

    Returns (eq, landed, overflow, next_seq): `landed[k]` says whether
    `pushes[k]` found a slot, `overflow` whether any wanted one did not.
    """
    valid = eq["valid"]
    writes = []  # (one-hot slot mask, seq, push); the masks are disjoint
    if pinned is not None:
        slot, push = pinned
        mask = (jnp.arange(valid.shape[0]) == slot) & push.want
        writes.append((mask, next_seq, push))
        valid = valid | mask
        next_seq = next_seq + push.want.astype(jnp.int32)
    rank, n_free = free_slot_ranks(valid)
    taken = jnp.int32(0)
    landed = []
    overflow = jnp.bool_(False)
    for push in pushes:
        want = push.want if push.dup_of is None else push.want & landed[push.dup_of]
        fits = taken < n_free
        ok = want & fits
        overflow = overflow | (want & ~fits)
        # taken slots rank -1, so a push that lands nowhere asks for -2
        writes.append((rank == jnp.where(ok, taken, -2), next_seq + taken, push))
        landed.append(ok)
        taken = taken + ok.astype(jnp.int32)

    # the slots that were taken: the free ones ranked below `taken`
    out = dict(eq, valid=valid | ((rank >= 0) & (rank < taken)))
    for mask, seq, push in writes:
        narrow = {"time": push.time, "seq": seq, "kind": push.kind,
                  "node": push.node, "src": push.src}
        for name, value in narrow.items():
            out[name] = jnp.where(mask, jnp.int32(value), out[name])
        out["payload"] = jnp.where(mask[:, None], push.payload[None, :], out["payload"])
        if "prov" in eq:
            out["prov"] = jnp.where(mask, push.prov, out["prov"])
    return out, landed, overflow, next_seq + taken
