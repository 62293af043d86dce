"""Scenario-coverage fold kernel — the AFL-style map the step kernel feeds.

FoundationDB-style simulation shops treat *explored-state coverage* as
the first-class signal for when a hunt is done (thousands more seeds
finding new interleavings vs a hunt that saturated long ago); AFL keeps
that signal cheap with a fixed-size hashed hit map on the hot path. This
module is the device half of that layer for the TPU engine: every popped
event hashes (abstract-state projection, event kind, fault context) into
one slot of a per-lane uint8 saturating-count map, updated with a single
gather + scatter per lane per step (NOT a one-hot masked select — a
2^14-wide select per step would dwarf the step itself).

Slot layout is structured, not a flat hash, so the map stays *decodable*
on the host (runtime/coverage.py). Two banded layouts exist (the band
width is a LAYOUT VERSION — maps carry it, old docs keep decoding):

    v1 (3 band bits, the PR-4 layout — every config without the PR-5
        chaos kinds, so historical maps and golden slots are unchanged):
    slot = [ band:3 | phase:3 | mix:(slots_log2-6) ]

    v2 (4 band bits — selected by the engine whenever pause/skew/dup/
        strict_restart can occur, which are new configs by definition):
    slot = [ band:4 | phase:3 | mix:(slots_log2-7) ]

  * band (top bits): the popped event's class — 0 timer, 1 message,
    2.. the fault KIND of a fault event (K_PAIR..K_SKEW). v2 adds two
    synthetic bands with no event class of their own: `dup` (a step
    that enqueued at least one Bernoulli duplicate) and `amnesia` (a
    strict-restart wipe was applied). Per-band slot counts are the
    "per-fault-kind marginal coverage" signal: which chaos vocabulary
    is still finding new abstract states.
  * phase (next 3 bits): the low 3 bits of the model's
    `coverage_projection` word — each model puts its coarsest progress
    notion there (raft: term bucket; 2pc: txn index; see the models).
    (band, phase) pairs are the 64 "cells" the CLI report ranks.
  * mix: an xor-multiply hash of the full projection word, the event
    tuple discriminants and the fault-context word.

Representation: one HIT BIT per slot, packed 32 to an int32 word (the
"bit" option of AFL's bit/count family). Counts were measured and
rejected: a `uint8[lanes, 2^14]` count map cost the flagship CPU bench
~15% — the read-modify-write scatter forced XLA to materialize a copy
of the 128 MiB operand every step — while the packed-word map (16x
smaller, 2 KiB per lane) folds for free; the hit-SET, which is all the
plateau/marginal/diff consumers read, is identical by construction.

The map is monotone (bits only set), so partial maps are always subsets
of final maps and OR-reducing lanes at *every* stream harvest is
idempotent — the global vector needs no done-mask bookkeeping.

Gate discipline matches the flight recorder: `EngineConfig.coverage`
off means the lane carries `{}` and the step adds literally no ops
(asserted bit-identical in tests/test_step_gates.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import kinds as _kinds

# Default map size: 2^14 slots = 512 packed int32 words = 2 KiB per
# lane. AFL's classic 64 KiB map tracks edge pairs of real binaries;
# the engine's abstract scenario space is far smaller, and 2 KiB keeps
# the [lanes, words] block at 16 MiB for the flagship 8192-lane batch.
COV_SLOTS_LOG2_DEFAULT = 14
COV_WORD_BITS = 32  # slots per packed map word

# Band index space (top bits of the slot): event class, with fault
# events split per FaultPlan kind. Names come from madsim_tpu/kinds.py
# (runtime/coverage.py binds the same table; no jax there).
COV_BAND_BITS = 3       # layout v1 (PR-4): 8 bands
COV_BAND_BITS_V2 = 4    # layout v2 (PR-5 chaos kinds): 16 bands
COV_PHASE_BITS = 3
COV_BANDS = 1 << COV_BAND_BITS
COV_BAND_NAMES = _kinds.COV_BAND_NAMES
COV_BAND_NAMES_V2 = _kinds.COV_BAND_NAMES_V2
# v2 synthetic bands (no popped-event class of their own; the engine
# passes them via cov_slot's `band` override)
COV_BAND_DUP = 10
COV_BAND_AMNESIA = 11
# Scheduled kinds past the synthetic bands (PR-6): fault kind k >= 8
# (K_TORN, K_HEAL_ASYM) lands at band 4 + k — the 2 + k rule would
# collide with the dup/amnesia slots. Only expressible in the 4-bit
# layout; the engine forces it whenever these kinds are enabled.
COV_KIND_BAND_SHIFT_AT = 8

# mix constants: murmur3 fmix / Weyl — odd multipliers, same family as
# core.digest_fold (any single-bit input change avalanches)
_MIX_SEED = 0x9E3779B9
_MIX_M = 0x85EBCA6B


def cov_mix(words) -> jax.Array:
    """xor-multiply-xorshift fold of a list of traced scalars into one
    uint32 hash word."""
    h = jnp.uint32(_MIX_SEED)
    for w in words:
        w = jnp.asarray(w).astype(jnp.uint32)
        h = (h ^ w) * jnp.uint32(_MIX_M)
        h = h ^ (h >> 13)
    return h


def cov_band(ev_kind, op_word, band_bits: int = COV_BAND_BITS) -> jax.Array:
    """Band index of a popped event: timer 0 / msg 1 / fault 2+kind
    (apply and undo share a kind; kinds >= COV_KIND_BAND_SHIFT_AT map to
    4+kind in the 4-bit layout — past the synthetic dup/amnesia bands).
    EV_FAULT mirrored as a literal (2): engine.core imports this
    module."""
    ev_kind = jnp.asarray(ev_kind).astype(jnp.int32)
    bands = 1 << band_bits
    kind = jnp.asarray(op_word).astype(jnp.int32) // 2
    if band_bits <= COV_BAND_BITS:
        # v1 layout: the PR-4 formula, bit-exact (golden slot constants)
        fault_band = 2 + jnp.clip(kind, 0, bands - 3)
    else:
        fault_band = jnp.where(
            kind >= COV_KIND_BAND_SHIFT_AT,
            4 + jnp.clip(kind, COV_KIND_BAND_SHIFT_AT, bands - 5),
            2 + jnp.clip(kind, 0, COV_KIND_BAND_SHIFT_AT - 1),
        )
    return jnp.where(ev_kind == 2, fault_band, jnp.clip(ev_kind, 0, 1))


def cov_slot(
    abstract,
    ev_kind,
    ev_node,
    op_word,
    fault_ctx,
    slots_log2: int,
    band_bits: int = COV_BAND_BITS,
    band=None,
) -> jax.Array:
    """Map one popped event to its slot index (int32 in [0, 2^slots_log2)).

    `abstract` is the model's projection word (uint32), `op_word` the
    event discriminant (payload[0] for msg/fault events, 0 for timers —
    timer ids are epoch-encoded and would inflate slots per restart),
    `fault_ctx` the packed fault-environment word built by the step
    kernel (killed count | clog/storm/spike flags). `band_bits` picks
    the banded layout (3 = the PR-4 layout, the default so every
    historical map and golden slot constant stays valid); `band`, when
    given, overrides the event-derived band — the engine uses it for
    the v2 synthetic bands (dup / amnesia).
    """
    ev_kind = jnp.asarray(ev_kind).astype(jnp.int32)
    if band is None:
        band = cov_band(ev_kind, op_word, band_bits)
    abstract = jnp.asarray(abstract).astype(jnp.uint32)
    phase = (abstract & jnp.uint32((1 << COV_PHASE_BITS) - 1)).astype(jnp.int32)
    mix_bits = slots_log2 - band_bits - COV_PHASE_BITS
    h = cov_mix([abstract, ev_kind, ev_node, op_word, fault_ctx])
    mix = (h & jnp.uint32((1 << mix_bits) - 1)).astype(jnp.int32)
    return (band << (slots_log2 - band_bits)) | (phase << mix_bits) | mix


def cov_fold(cov_map: jax.Array, slot, hit) -> jax.Array:
    """Set slot's hit bit when `hit` (traced bool); when not, the word
    ORs in 0 — a deterministic no-op, so frozen lanes stay
    bit-identical. One word gather + one word scatter per lane per
    step, never a map-wide select."""
    w = slot >> 5
    bit = (jnp.int32(1) << (slot & 31)) * hit.astype(jnp.int32)
    return cov_map.at[w].set(cov_map[w] | bit)


# Default per-lane slot-buffer depth for the flush-on-freeze buffered
# fold (EngineConfig.cov_buffer; 0 = the unbuffered per-event scatter
# above). Round 11 measured the per-event map RMW at -7.37% of step
# throughput on a CPU box: the scatter's operand is the whole [lanes, words] map, so
# XLA touches 2 KiB/lane every step to set one bit. Buffering the slot
# indices in a tiny int32[C] per-lane ring and folding only at the
# flush cadence / segment exit removes the map from the per-event
# program entirely — the step writes one 4-byte buffer entry instead.
# 16 entries = 64 B/lane, deep enough that the flush cadence (every
# C // slots_per_step iterations) stays a cheap segment-level event.
COV_BUFFER_DEFAULT = 16


def cov_push(buf: jax.Array, n: jax.Array, slot, hit):
    """Append `slot` to the per-lane buffer when `hit`, else write a
    masked 0 into the CURRENT tail position (same write either way —
    no divergent program). `n` counts live entries; misses don't
    advance it, so the occupied prefix [0, n) holds exactly the hit
    slots in event order. The caller guarantees n < len(buf) by
    flushing on a fixed cadence (engine.core.run_segment), so the
    clip never actually redirects a write — it is defensive bounds
    hygiene for the scatter, not an overflow policy."""
    hit_i = hit.astype(jnp.int32)
    pos = jnp.clip(n, 0, buf.shape[0] - 1)
    slot = jnp.asarray(slot).astype(jnp.int32)
    return buf.at[pos].set(slot * hit_i), n + hit_i


def cov_flush(cov_map: jax.Array, buf: jax.Array, n: jax.Array) -> jax.Array:
    """Fold the buffered slot prefix [0, n) into the packed bit map.

    An unrolled sequence of `cov_fold`s with hit = (i < n): OR is
    commutative and idempotent, so the result is bit-identical to
    having folded each slot at its original event — and a sequential
    fold (not one wide scatter) is what keeps duplicate words correct:
    a single `.at[ws].set(...)` with repeated word indices would keep
    only one of the colliding ORs. len(buf) is a small static constant
    (EngineConfig.cov_buffer), so the unroll is C tiny fused ops, paid
    once per flush instead of per event."""
    for i in range(buf.shape[0]):
        cov_map = cov_fold(cov_map, buf[i], i < n)
    return cov_map


def cov_fold_words(lane_maps: jax.Array, *, shards: int = 1) -> jax.Array:
    """OR-fold the per-lane packed maps [L, W] into the global word
    vector [W] — the `cov-map-or` collective of the stream harvest.

    `shards=1` (the unsharded path) is the plain bitwise-or reduce —
    byte-for-byte the historical fold, so single-device goldens are
    untouched by construction.

    `shards=mesh.size` (the mesh path, engine.core `_stream_fns`) is
    the same fold restructured so every CROSS-DEVICE combine uses a
    reduction computation the collective runtimes implement: an
    integer bitwise-or AllReduce is UNIMPLEMENTED on the CPU backend
    the mesh path is CI-proven on (and niche on others), while an
    int32 sum / max is universal. Step 1 reduces shard-locally (a
    split reshape keeps the lane axis's sharding on the leading factor,
    so the [shards, L/shards, W] -> [shards, W] or-reduce never crosses
    devices). Step 2 combines the per-shard partials bit-unpacked:
    [shards, W, 32] int32 0/1 `max` over the shard dim (an int32 max
    AllReduce), repacked by summing the disjoint single-bit words —
    bits are disjoint so the sum IS the or, exactly. The intermediates
    are [shards, W, 32] (64 KiB per shard at any batch size): the
    restructured fold costs O(devices * words), not O(lanes).

    The combine is deliberately NOT a boolean `any`: on a TPU v5e
    (libtpu 0.0.34) XLA packs a pred AllReduce four-to-a-u32, and the
    result kept only a quarter of the bits — the first run of the mesh
    on hardware (PR 21) streamed the right seeds and reported 1114 of
    4337 coverage slots. The CPU backend computed it correctly, so no
    virtual-device test could see it.

    OR is associative/commutative/idempotent, so both forms compute
    the identical [W] vector for any lane->shard split — the
    shard-count-invariance argument tests/test_mesh.py pins."""
    if shards <= 1:
        # madsim: collective(cov-map-or, reduce=or)
        return jax.lax.reduce(
            lane_maps, jnp.int32(0), jax.lax.bitwise_or, (0,)
        )
    lanes, words = lane_maps.shape
    # madsim: collective(cov-map-or, reduce=or) — the split reshape
    # keeps the lane sharding on the leading factor; the shard-local
    # or-reduce below it never crosses devices, the int32-max combine is
    # the actual cross-chip leg
    split = lane_maps.reshape(shards, lanes // shards, words)
    part = jax.lax.reduce(split, jnp.int32(0), jax.lax.bitwise_or, (1,))
    bits = jnp.arange(COV_WORD_BITS, dtype=jnp.int32)
    hit = ((part[:, :, None] >> bits) & 1).max(axis=0)  # [W, 32] int32 0/1
    return (hit << bits).sum(axis=-1, dtype=jnp.int32)


def empty_cov_map(slots_log2: int) -> jax.Array:
    """Zeroed per-lane hit map: int32[(2^slots_log2)/32] packed words
    (slot s lives in word s >> 5, bit s & 31)."""
    return jnp.zeros(((1 << slots_log2) // COV_WORD_BITS,), jnp.int32)
