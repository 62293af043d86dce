"""Protocol state-machine authoring API for the TPU engine.

The host engine runs free-form async Python (like the reference runs
arbitrary futures). Arbitrary coroutines cannot run on TPU, so the TPU
engine runs *protocol step functions*: a `Machine` is a pure, traceable
transition system over fixed-shape jax arrays (SURVEY.md §7 "hard parts"
item 3 — this authoring model is first-class).

Per-lane calling convention (the engine vmaps over lanes):

  * node state: a pytree whose every leaf has leading dim N (num nodes),
    except the leaves a machine declares role-held (`Machine.lane_spec`):
    state ONE role of the lane holds (a broker's partition logs), stored
    once a lane, without the node axis
  * handlers receive the whole pytree + a scalar node index and return
    (new pytree, Outbox). The index is traced, and so are the indices a
    message carries: read `table[i]` with `get_at(table, i)` and write
    it with `set_at` / `update_node`, both one-hot selects over the
    indexed axis — a plain `table[i]` under the engine's vmap is a
    gather, and on a TPU each gather of a step is a fusion of its own,
    ~100 us a step of 8192 lanes whatever the table's size, where the
    select-reduce is a few and fuses with its neighbours (`PERF.md` §6,
    PR 38); a `.at[i].set` is a scatter and costs the same
  * Outbox: fixed-width message/timer slots with validity masks — the
    fixed-shape equivalent of the reference's dynamic spawn/send
    (sim/net/mod.rs send path); invalid slots are ignored

Timer id 0 (`BOOT`) is reserved: the engine delivers it to every node at
t=0 and after every restart — machines schedule their initial timers in
response (the analogue of NodeBuilder.init closures,
reference: sim/runtime/mod.rs:359-375).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

BOOT = 0  # reserved timer id

# Storage-atomicity classes for torn/lost-write faults
# (`Machine.torn_spec()`, consumed by `torn_restart_if`): what a torn
# restart may do to a DURABLE leaf. Volatile leaves (durable_spec False)
# ignore their class — they are wiped like any amnesia restart.
TORN_ATOMIC = 1  # the write is atomic+fsynced: the leaf row survives intact
TORN_LOSE = 2    # all-or-nothing lost write: the whole row may revert to
#                  its fresh-init value (the write never reached the disk)
TORN_PREFIX = 3  # torn multi-element write: the row keeps only a seeded
#                  prefix along its trailing axis, the suffix reverts
#                  (1-D rows degrade to TORN_LOSE — no axis to tear)

# torn damage hash: mix a (payload ^ step-salt) seed word with the leaf's
# static flatten index — murmur3-fmix-style, same avalanche family as
# core.digest_fold / ops.coverage.cov_mix
_TORN_GOLDEN = 0x9E3779B9
_TORN_M1 = 0x85EBCA6B
_TORN_M2 = 0xC2B2AE35


def torn_hash(seed, leaf_idx: int) -> jax.Array:
    """Deterministic uint32 damage word for durable leaf `leaf_idx`
    (static flatten position) under the traced torn seed word."""
    h = jnp.asarray(seed).astype(jnp.uint32) ^ jnp.uint32(
        (_TORN_GOLDEN * (leaf_idx + 1)) & 0xFFFFFFFF
    )
    h = (h ^ (h >> 16)) * jnp.uint32(_TORN_M1)
    h = (h ^ (h >> 13)) * jnp.uint32(_TORN_M2)
    return h ^ (h >> 16)


@dataclasses.dataclass(frozen=True)
class RoleRows:
    """A `Machine.lane_spec` leaf value: the leaf is held by a role of
    SEVERAL nodes — axis 0 has one row for each of the nodes `first ..
    first + count - 1`, and none for the others (five servers' logs in a
    lane of ten are `[5, CAP]`, not `[10, CAP]`). Row `i - first` is
    node i's, so the generic restarts treat it as they treat row i of a
    leaf with the node axis: by `durable_spec` and `torn_spec`.

    `width` > 0: the rows lie end to end in ONE flat axis, `[count *
    width]`, node i's the block at `(i - first) * width` — the shape a
    TPU holds cheaply (a batch of `[5, 5]` tables is tiled to 8 x 128
    words a lane, a batch of `[25]` to 128). The generic restarts view
    such a leaf as `[count, width]`."""

    first: int
    count: int
    width: int = 0


@struct.dataclass
class Outbox:
    """Fixed-capacity per-step outputs of a handler."""

    msg_dst: jax.Array  # int32[M] destination node (-1 = invalid)
    msg_payload: jax.Array  # int32[M, P]
    msg_valid: jax.Array  # bool[M]
    timer_delay_us: jax.Array  # int32[T]
    timer_id: jax.Array  # int32[T]
    timer_valid: jax.Array  # bool[T]


def empty_outbox(max_msgs: int, max_timers: int, payload_width: int) -> Outbox:
    return Outbox(
        msg_dst=jnp.full((max_msgs,), -1, jnp.int32),
        msg_payload=jnp.zeros((max_msgs, payload_width), jnp.int32),
        msg_valid=jnp.zeros((max_msgs,), bool),
        timer_delay_us=jnp.zeros((max_timers,), jnp.int32),
        timer_id=jnp.zeros((max_timers,), jnp.int32),
        timer_valid=jnp.zeros((max_timers,), bool),
    )


# All reads and writes below are mask-based `where` selects rather than
# gathers and scatters: those with traced indices are hostile to the TPU
# vectorizer, while a masked select over a small fixed axis is pure VPU
# work.


def _slot_mask(n: int, slot) -> jax.Array:
    return jnp.arange(n) == slot


def send(outbox: Outbox, slot: int, dst, payload) -> Outbox:
    """Set message slot `slot`."""
    return send_if(outbox, slot, jnp.bool_(True), dst, payload)


def send_if(outbox: Outbox, slot: int, cond, dst, payload) -> Outbox:
    """Conditionally set message slot `slot` (traced condition)."""
    m = _slot_mask(outbox.msg_dst.shape[0], slot) & cond
    return outbox.replace(
        msg_dst=jnp.where(m, jnp.int32(dst), outbox.msg_dst),
        msg_payload=jnp.where(m[:, None], payload[None, :], outbox.msg_payload),
        msg_valid=outbox.msg_valid | m,
    )


def set_timer(outbox: Outbox, slot: int, delay_us, timer_id) -> Outbox:
    return set_timer_if(outbox, slot, jnp.bool_(True), delay_us, timer_id)


def set_timer_if(outbox: Outbox, slot: int, cond, delay_us, timer_id) -> Outbox:
    m = _slot_mask(outbox.timer_id.shape[0], slot) & cond
    return outbox.replace(
        timer_delay_us=jnp.where(m, jnp.int32(delay_us), outbox.timer_delay_us),
        timer_id=jnp.where(m, jnp.int32(timer_id), outbox.timer_id),
        timer_valid=outbox.timer_valid | m,
    )


def set_at(arr: jax.Array, i, value, cond=True) -> jax.Array:
    """`arr.at[i].set(value)` for traced i, as a masked select; `cond`
    (traced bool) gates the whole write."""
    mask = (jnp.arange(arr.shape[0]) == i) & cond
    while mask.ndim < arr.ndim:
        mask = mask[..., None]
    return jnp.where(mask, value, arr)


def get_at(arr: jax.Array, i) -> jax.Array:
    """`arr[i]` for traced i — `arr[i, j]` for `i = (i, j)` — as a
    one-hot select-reduce over the indexed axis: `set_at`'s twin on the
    read side. Returns what `arr[i]` returns, bit for bit, for an index
    out of range too (a message carries indices its sender chose): a
    negative index wraps once, then the index is clamped to the axis.
    Keeps the leaf's dtype (a `bool` leaf reduces with `any`), reads a
    row of a `[N, W]` leaf as well as a word, and an index vector reads
    one row an index. `(i, j)` reads row i and then word j of it, the
    cheaper of the two one-hot forms on a TPU."""
    if isinstance(i, tuple):
        for k in i:
            arr = get_at(arr, k)
        return arr
    n = arr.shape[0]
    i = jnp.asarray(i).astype(jnp.int32)
    i = jnp.clip(jnp.where(i < 0, i + n, i), 0, n - 1)
    hit = jnp.arange(n) == i[..., None]
    hit = hit.reshape(hit.shape + (1,) * (arr.ndim - 1))
    if arr.dtype == bool:
        return jnp.any(hit & arr, axis=i.ndim)
    return jnp.sum(jnp.where(hit, arr, 0), axis=i.ndim, dtype=arr.dtype)


def update_node(nodes: Any, i, **updates) -> Any:
    """Write per-field updates into node i of a state dataclass."""
    return nodes.replace(**{k: set_at(getattr(nodes, k), i, v) for k, v in updates.items()})


def make_payload(width: int, *vals) -> jax.Array:
    """Pack scalars into a fixed-width int32 payload vector."""
    parts = [jnp.asarray(v, jnp.int32) for v in vals]
    parts += [jnp.int32(0)] * (width - len(parts))
    return jnp.stack(parts)


class Machine:
    """Base class: subclass and override the handlers.

    Class attributes to set:
      NUM_NODES, PAYLOAD_WIDTH, MAX_MSGS, MAX_TIMERS
    """

    NUM_NODES: int = 1
    PAYLOAD_WIDTH: int = 4
    MAX_MSGS: int = 4
    MAX_TIMERS: int = 2
    # A machine's own totals of a stream (records appended, commits
    # fenced): the names of the per-lane vector `stream_counters`
    # returns. With the flight recorder on, the harvest adds each over
    # the lanes a stream resolves (those named in STREAM_COUNTERS_MAX
    # fold with max) and they come out beside the recorder's totals, as
    # `stats["flight_recorder"]["machine"]`. Empty: no leaf and no op.
    STREAM_COUNTERS: Tuple[str, ...] = ()
    STREAM_COUNTERS_MAX: Tuple[str, ...] = ()

    def empty_outbox(self) -> Outbox:
        return empty_outbox(self.MAX_MSGS, self.MAX_TIMERS, self.PAYLOAD_WIDTH)

    # -- required overrides --------------------------------------------------

    def init(self, rng_key) -> Any:
        """Initial node-state pytree (every leaf leading dim NUM_NODES)."""
        raise NotImplementedError

    def lane_spec(self) -> Any:
        """Optional: the leaves ONE role of the lane holds. A pytree
        CONGRUENT to `init()`'s node state whose every leaf is a python
        bool — True marks a role-held leaf: it has NO node axis (a
        broker's partition logs are stored `[P, CAP]`, not `[N, P,
        CAP]` with N - 1 rows nobody reads), so its bytes, and the
        step's write-back select over it, are paid once a lane and not
        once a node. Handlers read and write it whole. The generic
        restarts (`_wipe_node_if`, `amnesia_restart_if`,
        `torn_restart_if`) index axis 0 by node and therefore leave a
        role-held leaf alone: what a restart of the holding node does to
        it is the machine's own `restart_lane_if`, which they call
        (under `--strict-restart` and torn writes too). A leaf may also
        be a `RoleRows(first, count)`: held by a role of several nodes,
        one row each — that one the generic restarts DO reach, at row
        `i - first`, under the same contracts. Everything else
        the engine does with the node tree — the step's write-back, the
        lane freeze, replay, the mesh's lane sharding — is shape-blind;
        provenance words and the digest trail are per node and per
        event, and hold no node state.

        Default None: every leaf has the node axis. The engine checks
        the declaration against `init()`'s shapes when it is built."""
        return None

    def restart_lane_if(self, nodes: Any, i, cond, rng_key) -> Any:
        """A restart of node i (traced, under `cond`) as the role-held
        leaves see it: reset what the role keeps in memory only (a
        coordinator's member table when i is the coordinator). The
        generic restarts call it after their per-node wipe; a machine's
        own `restart_if` calls it too, so every kill of the holding
        node goes through this one hook. Default: nothing is lost."""
        return nodes

    def _map_node_leaves(self, fn, i, nodes: Any, *rest: Any) -> Any:
        """`fn(row, cur, *rest)` over the leaves that have a row for
        node i: `row` is i where the leaf has the node axis and `i -
        first` where a role of several nodes holds it (`RoleRows`: out
        of range, so no row, when i is not of the role). Leaves held
        once a lane (`lane_spec` True) pass through."""
        spec = self.lane_spec()
        if spec is None:
            return jax.tree.map(lambda cur, *r: fn(i, cur, *r), nodes, *rest)

        def one(held, cur, *r):
            if held is True:
                return cur
            if not isinstance(held, RoleRows):
                return fn(i, cur, *r)
            if not held.width:
                return fn(i - held.first, cur, *r)
            rows = (held.count, held.width)  # the flat leaf, a row a node
            r = [x.reshape(rows) if hasattr(x, "reshape") else x for x in r]
            return fn(i - held.first, cur.reshape(rows), *r).reshape(cur.shape)

        return jax.tree.map(one, spec, nodes, *rest)

    def _wipe_node_if(self, nodes: Any, i, cond, rng_key) -> Any:
        """Non-virtual building block: copy row i from a fresh init()
        under `cond` (never dispatches to overrides — safe to call from
        any subclass hook without recursion). Role-held leaves have no
        row i and are left alone."""
        fresh = self.init(rng_key)
        return self._map_node_leaves(
            lambda row, cur, f: set_at(cur, row, f, cond), i, nodes, fresh
        )

    def init_node(self, nodes: Any, i, rng_key) -> Any:
        """Reset node i to its initial state (legacy restart hook).
        Default: re-derive from init() and copy row i."""
        return self._wipe_node_if(nodes, i, jnp.bool_(True), rng_key)

    def restart_if(self, nodes: Any, i, cond, rng_key) -> Any:
        """Conditionally reset node i — the engine's restart-fault hook
        (`cond` is a traced bool). The default honors a subclass's
        `init_node` override (the older restart hook), so machines with
        durable/volatile splits written against that API keep their
        semantics; override `restart_if` directly and fold `cond` into
        your own row masks to skip the full-tree select (it cost ~30% of
        raft's eager step time)."""
        fresh = self.init_node(nodes, i, rng_key)
        return jax.tree.map(lambda c, f: jnp.where(cond, f, c), nodes, fresh)

    def durable_spec(self) -> Any:
        """Optional durable-state contract for crash-with-amnesia faults
        (`FaultPlan.strict_restart`): a pytree CONGRUENT to `init()`'s
        node state whose every leaf is a python bool — True marks a
        leaf as durable (survives restart: stable storage / WAL /
        fsynced log), False as volatile (a restarted node must lose
        it). The engine wipes volatile leaves generically from a fresh
        `init()` in `restart_node_if(..., strict=True)` — the model's
        hand-written `restart_if` is bypassed, so a machine whose
        restart code quietly keeps state its own contract calls
        volatile can no longer hide it (the classic DST finding class:
        "node restarts but illegally kept volatile state").

        Default None: no contract declared — the engine refuses
        `strict_restart` for such machines rather than guessing.
        """
        return None

    def amnesia_restart_if(self, nodes: Any, i, cond, rng_key) -> Any:
        """Crash-with-amnesia restart: reset every leaf `durable_spec()`
        marks volatile to its fresh-`init()` value for node row i (a
        masked row write per volatile leaf; durable leaves cost nothing
        — the keep is a static python branch)."""
        spec = self.durable_spec()
        if spec is None:
            raise ValueError(
                f"{type(self).__name__} declares no durable_spec(); "
                f"strict_restart (crash-with-amnesia) needs the durable-"
                f"state contract to know which leaves to wipe"
            )
        fresh = self.init(rng_key)
        nodes = self._map_node_leaves(
            lambda row, cur, durable, f: cur if durable else set_at(cur, row, f, cond),
            i, nodes, spec, fresh,
        )
        # a role-held leaf's entry in the contract is documentation: the
        # machine's own hook is what wipes it
        return self.restart_lane_if(nodes, i, cond, rng_key)

    def torn_spec(self) -> Any:
        """Optional storage-atomicity contract for torn/lost-write
        faults (`FaultPlan.allow_torn`): a pytree CONGRUENT to `init()`'s
        node state whose every leaf is one of TORN_ATOMIC / TORN_LOSE /
        TORN_PREFIX — what a torn restart may do to that DURABLE leaf
        (volatile leaves ignore their class; they are wiped like any
        amnesia restart). Default None: every durable write is atomic
        and fsynced, so a torn restart degrades to exactly the amnesia
        wipe — a machine with only a `durable_spec()` gets the K_TORN
        kind for free and survives it by construction. A machine
        modelling a non-atomic storage path (a snapshot file written
        without fsync, a multi-page WAL append) marks those leaves
        TORN_LOSE / TORN_PREFIX, and its recovery path must tolerate
        the damage or the checkers convict it — the FoundationDB
        buggify finding class ("the disk lied")."""
        return None

    def torn_restart_if(self, nodes: Any, i, cond, rng_key, torn_seed) -> Any:
        """Torn/lost-write restart (K_TORN undo op): volatile leaves
        wipe exactly as `amnesia_restart_if`; each durable leaf then
        takes its `torn_spec()` damage — TORN_LOSE rows revert whole
        under a seeded coin, TORN_PREFIX rows keep only a seeded prefix
        of their trailing axis. `torn_seed` is a traced uint32 (the
        fault payload's schedule-drawn mask xor the step's torn salt
        word); damage is a pure function of (torn_seed, leaf position),
        so replays are bit-identical."""
        spec = self.durable_spec()
        if spec is None:
            raise ValueError(
                f"{type(self).__name__} declares no durable_spec(); "
                f"allow_torn (torn/lost-write storage faults) needs the "
                f"durable-state contract to know which leaves exist"
            )
        tspec = self.torn_spec()
        if tspec is None:
            tspec = jax.tree.map(lambda _leaf: TORN_ATOMIC, spec)
        fresh = self.init(rng_key)
        leaf_idx = [0]

        def damage(i, cur, durable, cls, f):  # i: the leaf's row of the node
            li = leaf_idx[0]
            leaf_idx[0] += 1
            if not durable:
                return set_at(cur, i, f, cond)  # amnesia wipe
            if cls == TORN_ATOMIC:
                return cur
            h = torn_hash(torn_seed, li)
            if cls == TORN_LOSE or cur.ndim < 2:
                lost = (h & 1) == 1
                return set_at(cur, i, f, cond & lost)
            if cls == TORN_PREFIX:
                size = cur.shape[-1]
                cut = (h >> 1) % jnp.uint32(size + 1)
                torn_tail = jnp.arange(size) >= cut.astype(jnp.int32)
                row = (jnp.arange(cur.shape[0]) == i) & cond
                mask = row.reshape((-1,) + (1,) * (cur.ndim - 1)) & torn_tail
                return jnp.where(mask, f, cur)
            raise ValueError(
                f"{type(self).__name__}.torn_spec() leaf {li} has "
                f"unknown atomicity class {cls!r} (expected TORN_ATOMIC/"
                f"TORN_LOSE/TORN_PREFIX)"
            )

        # role-held leaves take no generic damage (the classes tear node
        # rows); their volatile part goes through the machine's hook
        nodes = self._map_node_leaves(damage, i, nodes, spec, tspec, fresh)
        return self.restart_lane_if(nodes, i, cond, rng_key)

    def restart_node_if(self, nodes: Any, i, cond, rng_key, strict: bool = False) -> Any:
        """Engine-facing restart dispatch — do NOT override. With
        `strict` (static, from `FaultPlan.strict_restart`) the generic
        crash-with-amnesia wipe runs instead of the model's own restart
        hook — the durable_spec contract, not the handler code, decides
        what survives. Otherwise picks the restart hook by MRO position
        so both authoring styles work:

          * a subclass overriding `restart_if` (the fast path) wins when
            it is at least as derived as any `init_node` override;
          * a subclass overriding only the legacy `init_node` hook gets
            the generic bridge (fresh = init_node; tree-select on cond)
            even when a base model ships a fast-path `restart_if` —
            otherwise the override would be silently ignored, and a
            guard inside each model's restart_if can mutually recurse
            with init_node shims that delegate to restart_if.
        """
        if strict:
            return self.amnesia_restart_if(nodes, i, cond, rng_key)
        mro = type(self).__mro__

        def hook_owner(name):
            return next(c for c in mro if name in c.__dict__)

        init_owner = hook_owner("init_node")
        rif_owner = hook_owner("restart_if")
        if init_owner is not Machine and mro.index(init_owner) < mro.index(rif_owner):
            # the generic bridge; naming the base class cannot recurse
            return Machine.restart_if(self, nodes, i, cond, rng_key)
        return self.restart_if(nodes, i, cond, rng_key)

    def on_timer(self, nodes: Any, node, timer_id, now_us, rand_u32) -> Tuple[Any, Outbox]:
        raise NotImplementedError

    def on_message(self, nodes: Any, node, src, payload, now_us, rand_u32) -> Tuple[Any, Outbox]:
        raise NotImplementedError

    # -- optional overrides --------------------------------------------------

    def invariant(self, nodes: Any, now_us) -> Tuple[jax.Array, jax.Array]:
        """(ok: bool, code: int32). A False freezes the lane as FAILED —
        the on-device analogue of a failing assertion in a #[madsim::test]."""
        return jnp.bool_(True), jnp.int32(0)

    def is_done(self, nodes: Any, now_us) -> jax.Array:
        """Early-success predicate (lane stops exploring)."""
        return jnp.bool_(False)

    def summary(self, nodes: Any) -> Any:
        """Small pytree gathered back to host per lane."""
        return jnp.int32(0)

    def stream_counters(self, nodes: Any) -> jax.Array:
        """int32[len(STREAM_COUNTERS)], in that order, read off one
        lane's final state."""
        return jnp.zeros((0,), jnp.int32)

    def churn_nodes(self) -> Optional[Tuple[int, ...]]:
        """Optional: the nodes a churn process of kind `kv3a` acts on
        (`ChurnPlan.kind`): the ones it repartitions, kills and
        restarts — a service's servers, where its clients are nodes of
        the lane too and reach every server. Default None: all."""
        return None

    def churn_victim(self, nodes: Any, connected):
        """Optional: the node a churn tick disconnects
        (`FaultPlan.churn`), as an int32 scalar read off the state the
        tick finds — -1 for none (no disconnect on that tick).
        `connected` is bool[N]: the nodes the process has not cut off.
        Default None: the engine draws a connected node uniformly."""
        return None

    def coverage_projection(self, nodes: Any, now_us) -> jax.Array:
        """Abstract-state word for the scenario-coverage map
        (`EngineConfig.coverage`, ops/coverage.py): project the whole
        node-state pytree down to a uint32 of coarse buckets — the
        engine hashes it with the popped event kind and fault context
        into the per-lane hit map every step.

        Contract: pure function of (nodes, now_us); put the model's
        coarsest "phase" notion (progress stage, term/txn/generation
        bucket) in the LOW 3 BITS — those become the visible phase axis
        of the (band, phase) cell report — and keep the whole word to a
        handful of small bucketed fields. Too fine a projection (raw
        counters, timestamps) saturates the map and destroys the
        plateau signal; too coarse and saturation is declared early.

        Default: constant 0. Coverage still distinguishes event kinds,
        destination nodes and fault contexts, so the map works for any
        machine — a model projection just makes it much sharper.
        """
        return jnp.uint32(0)
