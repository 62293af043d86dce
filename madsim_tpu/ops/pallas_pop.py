"""Pallas TPU kernels for the batched event-queue pop (+ fused step prefix).

`pop_earliest` is the per-step hot op of the TPU engine: a lexicographic
(time, seq) argmin over each lane's Q event slots. The XLA lowering is
three masked reductions; the Pallas versions fuse them into one VMEM
pass per lane block so the slot arrays are read once
(guide: /opt/skills/guides/pallas_guide.md — int32 min tile 8x128, lane
axis = slots).

Three kernels:

  * `_pop_kernel` — pop only: (idx, any_valid). The original r4 kernel.
  * `_pop_gather_kernel` — pop + the 5 follow-up gathers the step does
    with the result (`eq_time[idx]`, kind, node, src, payload[idx]) in
    the SAME VMEM pass, so the popped event tuple leaves the kernel and
    the per-lane XLA gathers disappear from the step. Payload columns
    ride as separate [L, Q] operands (restacked after the call) so every
    block stays rank-2 — Mosaic-friendly, no 3-D tiling games.
  * the STEP MEGAKERNEL (`step_megakernel`, r11) — the whole
    model-independent prefix of the step in ONE VMEM pass per lane
    block: lexicographic-argmin pop → popped-tuple gather → the
    counter-based v3 RNG word block (an in-kernel Threefry-2x32,
    bit-exact vs jax's `threefry_2x32` primitive — the stream contract)
    → when the flight recorder is on, the whole digest fold over the
    popped tuple + word block. The queue planes are read once and the
    RNG block + digest never round-trip through HBM between step
    stages. What stays in XLA: handler dispatch (machine code is
    arbitrary JAX — the Machine contract), fault-branch state writes,
    outbox pushes and the coverage slot hash (it needs the POST-step
    model projection). `Engine.use_megakernel` / `EngineConfig.
    pallas_megakernel` gates it (default-ON only on TPU, requires
    `rng_stream=3`); the XLA path remains the bit-identity oracle
    everywhere (interpreter-mode equivalence over the Q/P grid in
    tests/test_pallas.py + end-to-end in tests/test_step_gates.py).

Everything is min-reductions, one-hot sums and elementwise ARX rounds
over the lane axis (argmin is expressed as min over an index encoding;
gather as a one-hot masked sum, exact for int32) — no real gathers, no
cross-lane shuffles, so the kernels lower cleanly on Mosaic.

The engine flips the fused kernels default-ON when the backend is TPU
(`Engine.use_pallas_pop` / `Engine.use_megakernel`;
`MADSIM_TPU_PALLAS_POP=0/1` and `MADSIM_TPU_PALLAS_MEGAKERNEL=0/1`
force either way). A selected kernel runs or raises; the vmapped XLA
path is the bit-identity oracle, not a fallback. Both paths are
asserted equal in interpreter mode for queue capacities {32, 64} and
payload widths {4, 6} (tests/test_pallas.py), and `python
chip_smoke.py` asserts it on the chip: all four kernels compile through
Mosaic as written (libtpu 0.0.34, TPU v5e, PR 21) at Q = 32/96/320 and
give the XLA path's bits at 8192 lanes.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pop_earliest

LANE_BLOCK = 8  # lanes per grid step (int32 sublane tile)


def _lex_argmin(t, s, v):
    """Fused lexicographic argmin over the minor axis; shared by both
    kernels. Returns (idx[., 1], any[., 1] int32) with idx=0 for
    all-invalid rows (matching jnp.argmin over an all-sentinel row)."""
    q = t.shape[-1]
    # create the sentinel inside the kernel trace (module-level jnp
    # constants would be captured, which pallas_call rejects)
    big = jnp.int32(2**31 - 1)
    t_masked = jnp.where(v, t, big)
    tmin = jnp.min(t_masked, axis=-1, keepdims=True)
    tie = v & (t == tmin)
    s_masked = jnp.where(tie, s, big)
    smin = jnp.min(s_masked, axis=-1, keepdims=True)
    # argmin = smallest column index among exact (tmin, smin) matches
    cols = jax.lax.broadcasted_iota(jnp.int32, t.shape, dimension=t.ndim - 1)
    idx_enc = jnp.where(tie & (s == smin), cols, jnp.int32(q))
    idx = jnp.min(idx_enc, axis=-1, keepdims=True)
    idx = jnp.where(idx == q, 0, idx)
    any_v = jnp.any(v, axis=-1, keepdims=True).astype(jnp.int32)
    return idx, any_v, cols


def _pop_kernel(time_ref, seq_ref, valid_ref, idx_ref, any_ref):
    """One grid step: LANE_BLOCK lanes x Q slots, pop only."""
    t = time_ref[...]
    s = seq_ref[...]
    v = valid_ref[...] != 0
    idx, any_v, _ = _lex_argmin(t, s, v)
    # outputs are [LANE_BLOCK, 1]: Mosaic requires rank-1 block shapes to
    # be 128-multiples, so the lane-per-row result keeps a unit minor dim
    idx_ref[...] = idx
    any_ref[...] = any_v


def _make_pop_gather_kernel(n_vals: int):
    """Kernel popping + gathering `n_vals` extra [LB, Q] value planes
    (kind, node, src, payload columns) at the popped slot."""

    def kernel(*refs):
        time_ref, seq_ref, valid_ref = refs[:3]
        val_refs = refs[3 : 3 + n_vals]
        idx_ref, any_ref, time_out = refs[3 + n_vals : 6 + n_vals]
        val_outs = refs[6 + n_vals :]
        t = time_ref[...]
        s = seq_ref[...]
        v = valid_ref[...] != 0
        idx, any_v, cols = _lex_argmin(t, s, v)
        idx_ref[...] = idx
        any_ref[...] = any_v
        # gather-at-idx as a one-hot masked sum: exactly one column
        # matches (idx is always in [0, Q)), so the sum IS the element —
        # exact for int32, negatives included
        sel = cols == idx
        time_out[...] = jnp.sum(jnp.where(sel, t, 0), axis=-1, keepdims=True)
        for ref, out in zip(val_refs, val_outs):
            out[...] = jnp.sum(jnp.where(sel, ref[...], 0), axis=-1, keepdims=True)

    return kernel


def _pad_lanes(arrs, lanes, q=None):
    """Pad the lane (major) axis of each [L, *] operand to a LANE_BLOCK
    multiple with zero rows (each operand keeps its own minor width —
    the megakernel mixes [L, Q] queue planes with [L, 1] per-lane
    scalars). `q` is accepted for backward compatibility and ignored."""
    pad = (-lanes) % LANE_BLOCK
    if not pad:
        return arrs, lanes
    return [
        jnp.concatenate([a, jnp.zeros((pad, a.shape[1]), a.dtype)])
        for a in arrs
    ], lanes + pad


def pop_earliest_pallas(eq_time, eq_seq, eq_valid, interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Batched pop over [L, Q] arrays. Returns (idx[L], any_valid[L] bool).

    Input domain: seq values must be < 2**31-1 (the sentinel). The
    engine's monotone next_seq counter guarantees this by construction;
    the XLA path shares the same constraint.
    Non-multiple-of-8 lane counts are padded with invalid rows and the
    outputs sliced back, so both paths accept arbitrary L."""
    lanes, q = eq_time.shape
    (eq_time, eq_seq, eq_valid), padded = _pad_lanes(
        [eq_time, eq_seq, eq_valid.astype(jnp.int32)], lanes, q
    )
    grid = (padded // LANE_BLOCK,)
    row_spec = pl.BlockSpec((LANE_BLOCK, q), lambda i: (i, 0))
    out_spec = pl.BlockSpec((LANE_BLOCK, 1), lambda i: (i, 0))
    idx, any_valid = pl.pallas_call(
        _pop_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((padded, 1), jnp.int32),
            jax.ShapeDtypeStruct((padded, 1), jnp.int32),
        ],
        interpret=interpret,
        name="madsim_pop",
    )(eq_time, eq_seq, eq_valid)
    return idx[:lanes, 0], any_valid[:lanes, 0] != 0


def pop_gather_pallas(
    eq_time, eq_seq, eq_valid, eq_kind, eq_node, eq_src, eq_payload,
    interpret: bool = False,
):
    """Fused pop + gather over [L, Q] (+ payload [L, Q, P]) arrays.

    Returns (idx[L], any_valid[L] bool, (time[L], kind[L], node[L],
    src[L], payload[L, P])) — the popped event tuple, bit-identical to
    the XLA path's `arr[lane, idx[lane]]` gathers (all-invalid lanes
    gather slot 0 on both paths)."""
    lanes, q = eq_time.shape
    p = eq_payload.shape[-1]
    vals = [eq_kind, eq_node, eq_src] + [eq_payload[:, :, j] for j in range(p)]
    ins, padded = _pad_lanes(
        [eq_time, eq_seq, eq_valid.astype(jnp.int32)] + vals, lanes, q
    )
    grid = (padded // LANE_BLOCK,)
    row_spec = pl.BlockSpec((LANE_BLOCK, q), lambda i: (i, 0))
    out_spec = pl.BlockSpec((LANE_BLOCK, 1), lambda i: (i, 0))
    n_vals = len(vals)
    n_out = 3 + n_vals  # idx, any, time, then the value planes
    outs = pl.pallas_call(
        _make_pop_gather_kernel(n_vals),
        grid=grid,
        in_specs=[row_spec] * (3 + n_vals),
        out_specs=[out_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((padded, 1), jnp.int32)] * n_out,
        interpret=interpret,
        name="madsim_pop_gather",
    )(*ins)
    outs = [o[:lanes, 0] for o in outs]
    idx, any_valid, ev_time, ev_kind, ev_node, ev_src = outs[:6]
    ev_payload = jnp.stack(outs[6:], axis=-1)
    return idx, any_valid != 0, (ev_time, ev_kind, ev_node, ev_src, ev_payload)


# -- the whole-event step megakernel (r11) -----------------------------------

# Threefry-2x32 rotation schedule + key-schedule parity constant — the
# Random123 algorithm exactly as jax's `threefry2x32` primitive unrolls
# it, so the in-kernel word block is bit-identical to `jax.extend.
# random.threefry_2x32` (tests/test_pallas.py pins the equivalence over
# keys/counters; the golden v3 stream constants pin it transitively).
_TF_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))
_TF_PARITY = 0x1BD11BDA


def threefry2x32_pair(k0, k1, x0, x1):
    """Threefry-2x32 on paired uint32 operands (any broadcastable
    shape): 20 ARX rounds with the key schedule injected every 4.
    Elementwise only — traces inside a Pallas kernel and in plain XLA
    identically; both must (and do) match jax's fused primitive
    bit-for-bit."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_TF_PARITY))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _TF_ROT[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << r) | (x1 >> (32 - r))
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def step_rng_words_fused(k0, k1, step_u32, total_words: int):
    """The v3 counter-based word block, computed from [·, 1] per-lane
    key halves + step counters as one batched Threefry-2x32 call —
    bit-identical to `ops.step_rng.step_words_v3` (which routes through
    jax's primitive, including its odd-length pad-with-zero-then-split
    packing; replicated here exactly)."""
    w = total_words
    half = (w + 1) // 2
    wp = 2 * half
    lb = step_u32.shape[0]
    base = step_u32 * jnp.uint32(w)
    i0 = jax.lax.broadcasted_iota(jnp.uint32, (lb, half), 1)
    c0 = base + i0
    i1 = i0 + jnp.uint32(half)
    # odd block: jax pads the counter vector with one trailing zero
    # before splitting — the pad position's COUNT is 0, not step·w+w
    c1 = jnp.where(i1 < jnp.uint32(w), base + i1, jnp.uint32(0)) \
        if wp != w else base + i1
    y0, y1 = threefry2x32_pair(k0, k1, c0, c1)
    words = jnp.concatenate([y0, y1], axis=-1)
    return words[:, :w] if wp != w else words


def _make_step_kernel(n_vals: int, total_words: int, digest_fold=None):
    """The megakernel body: pop + gather `n_vals` planes + the v3 RNG
    block, plus (when `digest_fold` — the engine's fold callable — is
    given) the flight-recorder digest over exactly the words the XLA
    path folds: popped tuple, payload columns, then the word block."""

    def kernel(*refs):
        time_ref, seq_ref, valid_ref = refs[:3]
        val_refs = refs[3 : 3 + n_vals]
        pos = 3 + n_vals
        k0_ref, k1_ref, step_ref = refs[pos : pos + 3]
        pos += 3
        if digest_fold is not None:
            d0_ref, d1_ref = refs[pos : pos + 2]
            pos += 2
        outs = refs[pos:]
        idx_ref, any_ref, time_out = outs[:3]
        val_outs = outs[3 : 3 + n_vals]
        words_out = outs[3 + n_vals]
        t = time_ref[...]
        s = seq_ref[...]
        v = valid_ref[...] != 0
        idx, any_v, cols = _lex_argmin(t, s, v)
        idx_ref[...] = idx
        any_ref[...] = any_v
        sel = cols == idx
        ev_time = jnp.sum(jnp.where(sel, t, 0), axis=-1, keepdims=True)
        time_out[...] = ev_time
        vals = []
        for ref, out in zip(val_refs, val_outs):
            val = jnp.sum(jnp.where(sel, ref[...], 0), axis=-1, keepdims=True)
            out[...] = val
            vals.append(val)
        words = step_rng_words_fused(
            k0_ref[...], k1_ref[...], step_ref[...], total_words
        )
        words_out[...] = words
        if digest_fold is not None:
            nd0, nd1 = digest_fold(
                d0_ref[...],
                d1_ref[...],
                [ev_time] + vals
                + [words[:, i : i + 1] for i in range(total_words)],
            )
            outs[4 + n_vals][...] = nd0
            outs[5 + n_vals][...] = nd1

    return kernel


def step_megakernel(
    eq_time, eq_seq, eq_valid, eq_kind, eq_node, eq_src, eq_payload,
    rng_key, step, total_words: int,
    d0=None, d1=None, digest_fold=None,
    interpret: bool = False,
):
    """One VMEM pass per lane block: pop + gather + the v3 RNG word
    block (+ the digest fold when `d0`/`d1`/`digest_fold` are given).

    `rng_key` is the [L, 2] uint32 immutable v3 lane key, `step` the
    int32 step counter. Returns `(idx[L], any_valid[L] bool,
    (time, kind, node, src, payload[L, P]), words[L, W] uint32,
    digest)` where digest is `(nd0[L], nd1[L])` under the recorder and
    `()` without it — every value bit-identical to the XLA path
    (`pop_gather_batch` + `step_words_v3` + `core.digest_fold`)."""
    lanes, q = eq_time.shape
    p = eq_payload.shape[-1]
    with_digest = digest_fold is not None
    vals = [eq_kind, eq_node, eq_src] + [eq_payload[:, :, j] for j in range(p)]
    scalars = [
        rng_key[:, :1].astype(jnp.uint32),
        rng_key[:, 1:].astype(jnp.uint32),
        step[:, None].astype(jnp.uint32),
    ]
    if with_digest:
        scalars += [d0[:, None].astype(jnp.uint32), d1[:, None].astype(jnp.uint32)]
    ins, padded = _pad_lanes(
        [eq_time, eq_seq, eq_valid.astype(jnp.int32)] + vals + scalars, lanes
    )
    grid = (padded // LANE_BLOCK,)
    row_spec = pl.BlockSpec((LANE_BLOCK, q), lambda i: (i, 0))
    one_spec = pl.BlockSpec((LANE_BLOCK, 1), lambda i: (i, 0))
    words_spec = pl.BlockSpec((LANE_BLOCK, total_words), lambda i: (i, 0))
    n_vals = len(vals)
    out_specs = [one_spec] * (3 + n_vals) + [words_spec]
    out_shape = [jax.ShapeDtypeStruct((padded, 1), jnp.int32)] * (3 + n_vals) + [
        jax.ShapeDtypeStruct((padded, total_words), jnp.uint32)
    ]
    if with_digest:
        out_specs += [one_spec, one_spec]
        out_shape += [jax.ShapeDtypeStruct((padded, 1), jnp.uint32)] * 2
    in_specs = [row_spec] * (3 + n_vals) + [one_spec] * len(scalars)
    outs = pl.pallas_call(
        _make_step_kernel(n_vals, total_words, digest_fold if with_digest else None),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="madsim_step_mega",
    )(*ins)
    idx, any_valid, ev_time = (o[:lanes, 0] for o in outs[:3])
    val_cols = [o[:lanes, 0] for o in outs[3 : 3 + n_vals]]
    ev_kind, ev_node, ev_src = val_cols[:3]
    ev_payload = jnp.stack(val_cols[3:], axis=-1)
    words = outs[3 + n_vals][:lanes]
    digest = (
        (outs[4 + n_vals][:lanes, 0], outs[5 + n_vals][:lanes, 0])
        if with_digest
        else ()
    )
    return (
        idx, any_valid != 0,
        (ev_time, ev_kind, ev_node, ev_src, ev_payload),
        words, digest,
    )


# -- buffered-coverage flush kernel (r12) ------------------------------------
#
# The flush-on-freeze buffered coverage path (EngineConfig.cov_buffer)
# moved the per-event map scatter out of the step; what remains is a
# per-segment fold of each lane's int32[C] slot buffer into its
# int32[W] packed bit map. The coverage SLOT HASH still cannot join the
# megakernel (it needs the POST-step model projection — see the module
# docstring), so the Pallas treatment lands here instead: one VMEM pass
# per lane block ORing every buffered entry's one-hot word into the
# map. One-hot-over-words is the same trick the gather kernels use in
# reverse, and OR is order-independent, so the kernel is bit-identical
# to the sequential `coverage.cov_flush` oracle by construction
# (asserted over the C/W grid in tests/test_pallas.py).


def _make_cov_flush_kernel(n_entries: int):
    def kernel(map_ref, buf_ref, n_ref, out_ref):
        m = map_ref[...]
        buf = buf_ref[...]
        n = n_ref[...]  # [LB, 1] live-entry counts
        cols = jax.lax.broadcasted_iota(jnp.int32, m.shape, dimension=1)
        for i in range(n_entries):
            slot = buf[:, i : i + 1]
            hit = (jnp.int32(i) < n).astype(jnp.int32)
            bit = (jnp.int32(1) << (slot & 31)) * hit
            m = m | jnp.where(cols == (slot >> 5), bit, 0)
        out_ref[...] = m

    return kernel


def cov_flush_pallas(cov_map, buf, n, interpret: bool = False):
    """Fold [L, C] buffered slot indices (live prefix per `n[L]`) into
    the [L, W] packed bit maps in one VMEM pass per lane block."""
    lanes, w = cov_map.shape
    c = buf.shape[1]
    ins, padded = _pad_lanes(
        [cov_map, buf, n[:, None].astype(jnp.int32)], lanes
    )
    grid = (padded // LANE_BLOCK,)
    out = pl.pallas_call(
        _make_cov_flush_kernel(c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((LANE_BLOCK, w), lambda i: (i, 0)),
            pl.BlockSpec((LANE_BLOCK, c), lambda i: (i, 0)),
            pl.BlockSpec((LANE_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((LANE_BLOCK, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, w), jnp.int32),
        interpret=interpret,
        name="madsim_cov_flush",
    )(*ins)
    return out[:lanes]


def cov_flush_batch(cov_map, buf, n, use_pallas: bool = False, interpret: bool = False):
    """Batched buffer→map fold: the Pallas VMEM kernel, or the vmapped
    sequential `coverage.cov_flush` reference (the bit-identity
    oracle)."""
    if use_pallas:
        return cov_flush_pallas(cov_map, buf, n, interpret=interpret)
    from .coverage import cov_flush

    return jax.vmap(cov_flush)(cov_map, buf, n)


def pop_earliest_batch(eq_time, eq_seq, eq_valid, use_pallas: bool = False, interpret: bool = False):
    """Reference implementation (vmapped XLA) or the fused Pallas kernel."""
    if use_pallas:
        return pop_earliest_pallas(eq_time, eq_seq, eq_valid, interpret=interpret)
    return jax.vmap(pop_earliest)(eq_time, eq_seq, eq_valid)


def pop_gather_batch(
    eq_time, eq_seq, eq_valid, eq_kind, eq_node, eq_src, eq_payload,
    use_pallas: bool = False, interpret: bool = False,
):
    """Pop + gather the popped event tuple: the fused Pallas kernel, or
    the vmapped-XLA reference (pop + take_along_axis gathers). Both
    return (idx, any_valid, (time, kind, node, src, payload)) with
    bit-identical values."""
    if use_pallas:
        return pop_gather_pallas(
            eq_time, eq_seq, eq_valid, eq_kind, eq_node, eq_src, eq_payload,
            interpret=interpret,
        )
    idx, any_valid = jax.vmap(pop_earliest)(eq_time, eq_seq, eq_valid)

    def take(a):
        return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]

    ev_payload = jnp.take_along_axis(
        eq_payload, idx[:, None, None], axis=1
    )[:, 0, :]
    return idx, any_valid, (
        take(eq_time), take(eq_kind), take(eq_node), take(eq_src), ev_payload
    )
