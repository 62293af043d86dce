"""Per-step RNG word derivation — the versioned stream contract.

Every event step consumes a block of uint32 words: handler randomness,
per-message latency draws, and (config-permitting) loss, delay-spike and
restart-key draws. Two stream versions exist; an engine's
`EngineConfig.rng_stream` picks one, and corpus entries record it so
every historical seed replays byte-identically forever (the same
versioning discipline as the v1→v2 fault-plan derivation in
`core.init_lane`).

**v2 (legacy, split-chain)** — the seed-era stream. The lane key evolves
by a 3-way `jax.random.split` every step and the block is drawn from the
step key:

    key, k_step, k_restart = split(rng_key, 3)
    words = random.bits(k_step, (W2,))        # W2 = H + (4 if delay else 2)*M

Two threefry invocations per event, and the block always carries
`2*M` latency+drop words (plus `2*M` spike words when `allow_delay`)
whether or not the config can ever use them.

**v3 (counter-based)** — one threefry invocation per event, Random123
style: the lane key is immutable and the step index IS the counter
(`LaneState.step`, already carried for termination):

    words(lane_key, step) = threefry2x32(lane_key, step*W3 + iota(W3))

`W3` is sized to what the enabled config can actually consume — drop
words only when loss is statically possible, spike words only when
delay-spike windows are statically reachable, a 2-word restart key only
when kill/restart faults are enabled. Counters are unique as long as
`step * W3 < 2**32` (~300M events/lane at W3=14 — far past any
`max_steps` in use; uniqueness degrades gracefully to reuse, never to
nondeterminism). Because `jax.random.bits(key, (n,)) ==
threefry2x32(key, iota(n))`, v3 is the natural counter-offset
generalization of the v2 block draw.

Both versions share the same block layout (`StepRngLayout`):

    [ handler H | latency M | drop M? | spike M? | spike_mag M? | restart 2? | dup 2M? | torn 1? ]

v2 always materializes the drop (and, under `allow_delay`, spike)
sections; v3 omits statically-dead sections entirely. The duplication
section (`FaultPlan.allow_dup`, PR-5: gate word + fresh-latency word per
message slot) is appended at the END of both layouts — existing section
offsets never move, so every recorded stream stays byte-stable with the
flag off. The torn-write salt section (`FaultPlan.allow_torn`, PR-6: one
word per step, folded into the torn-restart damage draw) appends after
it under the same contract. The causal-provenance gate (PR-7,
`EngineConfig.provenance`) deliberately consumes NO words in either
version — lineage words are pure dataflow over values the step already
has — so it needs no section here and provably cannot move a recorded
stream. The engine
additionally elides the *compute* that consumes a section when it is
statically inert (e.g. loss_rate==0 and no storms ⇒ the drop compare
always yields False) — that elision is result-preserving in both
versions and is independent of the stream contract.

Golden word streams for both versions are pinned as literal constants in
tests/test_golden_streams.py; any change to the functions below that
disturbs a pinned stream is a corpus-breaking event and must ship as a
new version instead.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend.random import threefry_2x32

# The stream contract also pins the PRNG *lowering*. jax's
# `jax_threefry_partitionable` flag changes the bits jax.random.split /
# jax.random.bits produce for the SAME key, and jax has flipped its
# default across releases — the PR-3 corpus-rot investigation traced
# "all 8 corpus entries and slow-seed 66531 stopped reproducing" to
# exactly this: they were recorded under partitionable=True (the
# real-chip box's newer jax) and replayed under a False-default jax,
# which silently re-derived every lane key, fault schedule and v2 step
# block. Pinned True — the value the historical corpus was recorded
# under and the one newer jax keeps — so the streams are a function of
# the seed alone, not of the installed jax version. (The raw
# threefry_2x32 kernel v3 uses is flag-independent; the lane-key
# derivation above it is not.)
jax.config.update("jax_threefry_partitionable", True)

RNG_STREAM_LEGACY = 2
RNG_STREAM_COUNTER = 3
RNG_STREAM_VERSIONS = (RNG_STREAM_LEGACY, RNG_STREAM_COUNTER)


@dataclasses.dataclass(frozen=True)
class StepRngLayout:
    """Static word-block layout for one (config, machine) pair.

    Offsets are None when the section is not materialized in this
    stream. `loss_active` / `spike_active` are the compute-elision
    flags: a section can be materialized (v2 draws it unconditionally)
    yet statically inert."""

    version: int
    handler_words: int
    max_msgs: int
    lat_off: int
    drop_off: Optional[int]
    spike_off: Optional[int]  # gate words; magnitude words follow at +max_msgs
    restart_off: Optional[int]  # v3 only; v2 takes k_restart from the split
    total_words: int
    loss_active: bool
    spike_active: bool
    restart_active: bool
    # message-duplication section (gate words; fresh-latency words follow
    # at +max_msgs). Appended at the tail of BOTH stream versions so the
    # flag-off block is bit-identical to the pre-dup layouts.
    dup_off: Optional[int] = None
    dup_active: bool = False
    # torn-write section (PR-6, `FaultPlan.allow_torn`): ONE word per
    # step that salts the torn-restart damage draw (combined with the
    # fault payload's schedule-drawn mask). Appended after the dup
    # section at the very tail of both versions — same off-bit-stability
    # contract: no existing offset ever moves.
    torn_off: Optional[int] = None
    torn_active: bool = False


def layout_for(
    version: int,
    handler_words: int,
    max_msgs: int,
    *,
    loss_possible: bool,
    spike_possible: bool,
    delay_enabled: bool,
    restart_possible: bool,
    dup_possible: bool = False,
    torn_possible: bool = False,
) -> StepRngLayout:
    """Build the block layout. `delay_enabled` is the raw
    `FaultPlan.allow_delay` flag (v2 materializes spike words on it
    alone); `spike_possible` additionally requires n_faults > 0.
    `dup_possible` (`FaultPlan.allow_dup`) appends the duplication
    section to the tail of either version — never moves an offset —
    and `torn_possible` (`FaultPlan.allow_torn`) appends the one-word
    torn-write salt section after it, under the same contract."""
    h, m = handler_words, max_msgs
    if version == RNG_STREAM_LEGACY:
        legacy_total = h + (4 if delay_enabled else 2) * m
        dup_end = legacy_total + (2 * m if dup_possible else 0)
        return StepRngLayout(
            version=version,
            handler_words=h,
            max_msgs=m,
            lat_off=h,
            drop_off=h + m,
            spike_off=h + 2 * m if delay_enabled else None,
            restart_off=None,
            total_words=dup_end + (1 if torn_possible else 0),
            loss_active=loss_possible,
            spike_active=delay_enabled and spike_possible,
            restart_active=restart_possible,
            dup_off=legacy_total if dup_possible else None,
            dup_active=dup_possible,
            torn_off=dup_end if torn_possible else None,
            torn_active=torn_possible,
        )
    if version != RNG_STREAM_COUNTER:
        raise ValueError(f"unknown rng_stream version {version!r}")
    cursor = h + m
    drop_off = None
    if loss_possible:
        drop_off = cursor
        cursor += m
    spike_off = None
    if spike_possible:
        spike_off = cursor
        cursor += 2 * m
    restart_off = None
    if restart_possible:
        restart_off = cursor
        cursor += 2
    dup_off = None
    if dup_possible:
        dup_off = cursor
        cursor += 2 * m
    torn_off = None
    if torn_possible:
        torn_off = cursor
        cursor += 1
    return StepRngLayout(
        version=version,
        handler_words=h,
        max_msgs=m,
        lat_off=h,
        drop_off=drop_off,
        spike_off=spike_off,
        restart_off=restart_off,
        total_words=cursor,
        loss_active=loss_possible,
        spike_active=spike_possible,
        restart_active=restart_possible,
        dup_off=dup_off,
        dup_active=dup_possible,
        torn_off=torn_off,
        torn_active=torn_possible,
    )


def step_words_v2(rng_key: jax.Array, layout: StepRngLayout) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Legacy split-chain step draw.

    Returns (new_key, words[total_words], k_restart). The restart key is
    its own split — never derived from a consumed key (stream-collision
    hazard)."""
    key, k_step, k_restart = jax.random.split(rng_key, 3)
    words = jax.random.bits(k_step, (layout.total_words,), jnp.uint32)
    return key, words, k_restart


def step_words_v3(rng_key: jax.Array, step: jax.Array, layout: StepRngLayout) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Counter-based step draw: one threefry invocation per event.

    Returns (new_key, words[total_words], k_restart); new_key is the
    UNCHANGED lane key (immutable by contract). The restart key, when
    materialized, is the block's trailing 2 words."""
    w = layout.total_words
    counts = step.astype(jnp.uint32) * jnp.uint32(w) + jnp.arange(w, dtype=jnp.uint32)
    words = threefry_2x32(rng_key, counts)
    if layout.restart_off is not None:
        k_restart = words[layout.restart_off : layout.restart_off + 2]
    else:
        # restart statically unreachable: the key value is dead (the
        # restart write is masked off), any constant works
        k_restart = jnp.zeros((2,), jnp.uint32)
    return rng_key, words, k_restart


def step_words(rng_key: jax.Array, step: jax.Array, layout: StepRngLayout):
    if layout.version == RNG_STREAM_COUNTER:
        return step_words_v3(rng_key, step, layout)
    return step_words_v2(rng_key, layout)
