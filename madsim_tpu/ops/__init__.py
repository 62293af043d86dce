"""Batched event-queue primitives — the hot ops of the TPU engine.

The host engine's binary timer heap (time/__init__.py) is replaced by a
fixed-capacity unsorted slot array per lane with vectorized argmin pop —
O(Q) work that maps onto the VPU as pure elementwise + reduction, which
beats a data-dependent heap on TPU by a wide margin. Lexicographic
(time, seq) ordering uses two masked reductions instead of a packed
64-bit key so everything stays in native int32.

Reference semantics being replicated: naive-timer pop-nearest
(madsim/src/sim/time/mod.rs:45-59) with FIFO tie-break on insertion seq.

Siblings: `step_rng.py` (the versioned per-step RNG word contract),
`pallas_pop.py` (fused pop+gather kernel), `coverage.py` (the
scenario-coverage fold the observability layer rides).

Input domain: times and seqs must be < 2**31-1 (INT32_MAX doubles as the
masking sentinel). The engine's int32 microsecond horizon and monotone
next_seq counter guarantee both by construction.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

INT32_MAX = jnp.int32(2**31 - 1)


def pop_earliest(eq_time, eq_seq, eq_valid) -> Tuple[jax.Array, jax.Array]:
    """Index of the earliest (time, seq) valid event and whether any exists.

    Per-lane shapes: eq_time int32[Q], eq_seq int32[Q], eq_valid bool[Q].
    Returns (idx, any_valid).
    """
    t_masked = jnp.where(eq_valid, eq_time, INT32_MAX)
    tmin = jnp.min(t_masked)
    tie = eq_valid & (eq_time == tmin)
    s_masked = jnp.where(tie, eq_seq, INT32_MAX)
    idx = jnp.argmin(s_masked)
    return idx, jnp.any(eq_valid)


def free_slot_ranks(eq_valid) -> Tuple[jax.Array, jax.Array]:
    """Rank every free slot of one lane's queue, once an event.

    Returns (rank, n_free): rank int32[Q] is the number of free slots
    below q where slot q is free and -1 where it is taken, so
    `rank == k` is the one-hot mask of the k-th lowest free slot (all
    False for k >= n_free: the lane overflow check). The k-th push of
    an event that finds a slot takes exactly that one — what K
    first-free scans, each after the write before it, arrive at.

    The exclusive prefix count is a product with a strict triangle: on
    the chip it rides the otherwise idle MXU (`[L, Q] x [Q, Q]` under
    vmap) and read no slower than `cumsum`'s reduce-window, a `[Q, Q]`
    compare-sum or log-step shifts in any cell (my chip runs, PR 34).
    Exact on every backend: 0/1 in bfloat16, sums <= Q in float32.
    """
    free = ~eq_valid
    q = free.shape[0]
    below = (jnp.arange(q)[:, None] < jnp.arange(q)[None, :]).astype(jnp.bfloat16)
    excl = jnp.dot(
        free.astype(jnp.bfloat16), below, preferred_element_type=jnp.float32
    ).astype(jnp.int32)
    # n_free is a reduction of its own, not `excl[-1] + free[-1]`: with the
    # count read off the product XLA laid the step out differently and
    # `raft5_sweep` read 2999 seeds/s for 3282 (my chip runs, PR 34;
    # PERF.md section 6: a matter of layout assignment, not of this sum)
    return jnp.where(free, excl, -1), jnp.sum(free, dtype=jnp.int32)
