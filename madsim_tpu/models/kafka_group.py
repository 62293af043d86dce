"""Kafka consumer-group machine — the rdkafka consumer-group workload
as a batched engine Machine.

The host-engine analogue lives in services/kafka (GroupCoordinator with
rebalancing + fenced commits, exercised by tests/test_services.py); the
reference's integration suite is madsim-rdkafka/tests/test.rs. This
model proves the L5-class semantics run *batched on the TPU engine*:
thousands of seeds explore kill/restart and partition faults against a
group coordinator in lockstep, and failing seeds replay bit-identically
on the host replayer.

Topology: node 0 = broker + group coordinator (Kafka's group coordinator
IS a broker); nodes 1..C = consumer-group members. The topic has P
partitions, each pre-filled with `log_len` records (record identity is
(partition, offset), so no payload storage is needed).

Protocol (pull-based, 5 message kinds):
  * members heartbeat the coordinator; an unknown member's heartbeat is
    a join. Membership changes bump the generation and recompute a
    range assignment over joined members.
  * heartbeat responses carry (generation, assignment bitmask, committed
    offsets); a member seeing a new generation adopts the assignment and
    resumes every owned partition from its committed offset — the
    resume-from-committed rule that makes rebalancing lossless.
  * members fetch their owned partitions round-robin and auto-commit
    after each consumed record, tagged with their generation.
  * the coordinator fences commits: accepted only from the current
    generation's assigned owner (Kafka's ILLEGAL_GENERATION /
    FENCED_INSTANCE_ID checks). `NoFencingGroupMachine` drops that
    check — partitioned zombies then regress committed offsets, which
    is the bug class the invariant exists to catch.
  * a session timer expires members whose heartbeats stopped
    (kill/partition faults), bumping the generation.

Durability under engine faults: the coordinator's generation + committed
offsets survive restart (Kafka persists them in __consumer_offsets);
its member table is volatile (coordinator failover forces rejoins).
Members lose everything (positions must come back from committed).

Invariants (checked on-device after every event):
  * COMMIT_REGRESS (131): an accepted commit moved a committed offset
    backwards — impossible with fencing, the zombie signature without.
  * LOST_RECORD (130): some offset below a committed offset was never
    consumed by any member (at-least-once violated). Tracked with a
    ghost consumed-bitmap — spec-only auxiliary state, written at
    consume time, never read by the protocol.

A half at toy size (pre-filled partitions, no producer): the
deployment-sized machine — this group over live partition logs fed by
`mq.py`'s idempotent producers — is `models/kafka.py` (`--machine
kafka`); size a benchmark cell on that.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..engine.machine import (
    Machine,
    Outbox,
    make_payload,
    send_if,
    set_at,
    set_timer_if,
    update_node,
)

COORD = 0

# messages
M_HB, M_HB_RESP, M_FETCH, M_FETCH_RESP, M_COMMIT = 1, 2, 3, 4, 5

# timers
T_BOOT, T_SESSION, T_HB, T_POLL = 0, 1, 2, 3

LOST_RECORD = 130
COMMIT_REGRESS = 131

HB_US = 40_000
POLL_US = 17_000
SESSION_US = 150_000
SESSION_CHECK_US = 50_000


@struct.dataclass
class GroupState:
    # coordinator (row COORD); gen doubles as each member's adopted gen
    gen: jax.Array  # int32[N]
    joined: jax.Array  # bool[N]   coordinator's member table
    last_hb: jax.Array  # int32[N] coordinator's last-heartbeat time (us)
    assign_member: jax.Array  # int32[N, P] owning node id per partition (-1 none)
    committed: jax.Array  # int32[N, P] durable committed offsets (row COORD)
    commit_gen: jax.Array  # int32[N, P] generation of the last accepted commit
    bad_regress: jax.Array  # bool[N]  spec flag (row COORD)
    # members
    my_assign: jax.Array  # bool[N, P]
    position: jax.Array  # int32[N, P] next offset to consume
    poll_rr: jax.Array  # int32[N] round-robin partition cursor
    # ghost (spec-only): which (partition, offset) was ever consumed
    consumed: jax.Array  # bool[N, P, L] (row COORD)


class KafkaGroupMachine(Machine):
    """1 coordinator/broker + (num_nodes-1) group members."""

    MAX_MSGS = 1
    MAX_TIMERS = 2

    def __init__(self, num_nodes: int = 4, partitions: int = 2, log_len: int = 12):
        self.NUM_NODES = num_nodes
        self.P = partitions
        self.L = log_len
        self.PAYLOAD_WIDTH = max(5, 3 + partitions)

    # -- state ---------------------------------------------------------------

    def init(self, rng_key) -> GroupState:
        n, p, l = self.NUM_NODES, self.P, self.L
        return GroupState(
            gen=jnp.zeros((n,), jnp.int32),
            joined=jnp.zeros((n,), bool),
            last_hb=jnp.zeros((n,), jnp.int32),
            assign_member=jnp.full((n, p), -1, jnp.int32),
            committed=jnp.zeros((n, p), jnp.int32),
            commit_gen=jnp.zeros((n, p), jnp.int32),
            bad_regress=jnp.zeros((n,), bool),
            my_assign=jnp.zeros((n, p), bool),
            position=jnp.zeros((n, p), jnp.int32),
            poll_rr=jnp.zeros((n,), jnp.int32),
            consumed=jnp.zeros((n, p, l), bool),
        )

    def restart_if(self, nodes: GroupState, i, cond, rng_key) -> GroupState:
        n = self.NUM_NODES
        row = (jnp.arange(n) == i) & cond
        # coordinator restart: member table is volatile (all must rejoin);
        # gen/committed/ghost are durable. Member restart: session state
        # (adopted gen, assignment, positions) is volatile.
        member_row = row & (jnp.arange(n) != COORD)
        # the member table lives in the coordinator's row-space, so a
        # coordinator restart wipes the whole joined/last_hb vectors
        any_coord = cond & (i == COORD)
        joined = jnp.where(any_coord, False, nodes.joined)
        last_hb = jnp.where(any_coord, 0, nodes.last_hb)
        return nodes.replace(
            joined=joined,
            last_hb=last_hb,
            gen=jnp.where(member_row, 0, nodes.gen),
            my_assign=jnp.where(member_row[:, None], False, nodes.my_assign),
            position=jnp.where(member_row[:, None], 0, nodes.position),
            poll_rr=jnp.where(member_row, 0, nodes.poll_rr),
        )

    # -- coordinator helpers --------------------------------------------------

    def _rebalance_if(self, nodes: GroupState, cond) -> GroupState:
        """Bump generation + recompute the range assignment over joined
        members (node ids 1..N-1), under traced `cond`."""
        n, p = self.NUM_NODES, self.P
        joined = nodes.joined
        k = joined.sum(dtype=jnp.int32)
        ranks = jnp.cumsum(joined.astype(jnp.int32)) - 1  # rank among joined
        targets = jnp.mod(jnp.arange(p, dtype=jnp.int32), jnp.maximum(k, 1))
        match = joined[None, :] & (ranks[None, :] == targets[:, None])  # [P, N]
        assignment = jnp.where(k > 0, jnp.argmax(match, axis=1).astype(jnp.int32), -1)
        new_row = jnp.where(cond, assignment, nodes.assign_member[COORD])
        return nodes.replace(
            gen=set_at(nodes.gen, COORD, nodes.gen[COORD] + 1, cond),
            assign_member=set_at(nodes.assign_member, COORD, new_row),
        )

    def _commit_accepts(self, nodes: GroupState, src, c_gen, c_part) -> jax.Array:
        """Fencing predicate — the line NoFencingGroupMachine removes."""
        return (
            (c_gen == nodes.gen[COORD])
            & nodes.joined[src]
            & (nodes.assign_member[COORD, c_part] == src)
        )

    # -- timers ---------------------------------------------------------------

    def on_timer(self, nodes: GroupState, node, timer_id, now_us, rand_u32) -> Tuple[GroupState, Outbox]:
        outbox = self.empty_outbox()
        is_coord = node == COORD
        is_member = ~is_coord
        is_boot = timer_id == T_BOOT

        outbox = set_timer_if(outbox, 0, is_boot & is_coord, SESSION_CHECK_US, T_SESSION)
        outbox = set_timer_if(outbox, 0, is_boot & is_member, HB_US, T_HB)
        outbox = set_timer_if(outbox, 1, is_boot & is_member, POLL_US, T_POLL)

        # coordinator: expire silent members, rebalance if any left
        tick = (timer_id == T_SESSION) & is_coord
        expired = nodes.joined & (nodes.last_hb + SESSION_US < now_us)
        any_expired = tick & jnp.any(expired)
        nodes = nodes.replace(joined=jnp.where(any_expired, nodes.joined & ~expired, nodes.joined))
        nodes = self._rebalance_if(nodes, any_expired)
        outbox = set_timer_if(outbox, 0, tick, SESSION_CHECK_US, T_SESSION)

        # member: heartbeat (doubles as join)
        hb = (timer_id == T_HB) & is_member
        outbox = send_if(outbox, 0, hb, COORD, make_payload(self.PAYLOAD_WIDTH, M_HB))
        outbox = set_timer_if(outbox, 0, hb, HB_US, T_HB)

        # member: fetch the next owned partition (round-robin cursor)
        poll = (timer_id == T_POLL) & is_member
        rr = nodes.poll_rr[node]
        owned = nodes.my_assign[node]  # bool[P]
        # first owned partition at cursor >= rr (wrapping): rotate indices
        order = jnp.mod(rr + jnp.arange(self.P, dtype=jnp.int32), self.P)
        owned_rot = owned[order]
        pick = order[jnp.argmax(owned_rot)]
        has = jnp.any(owned)
        want = poll & has & (nodes.position[node, pick] < self.L)
        fetch = make_payload(self.PAYLOAD_WIDTH, M_FETCH, pick, nodes.position[node, pick])
        outbox = send_if(outbox, 0, want, COORD, fetch)
        nodes = update_node(nodes, node, poll_rr=jnp.where(poll, jnp.mod(pick + 1, self.P), rr))
        outbox = set_timer_if(outbox, 0, poll, POLL_US, T_POLL)
        return nodes, outbox

    # -- messages -------------------------------------------------------------

    def on_message(self, nodes: GroupState, node, src, payload, now_us, rand_u32) -> Tuple[GroupState, Outbox]:
        outbox = self.empty_outbox()
        mtype = payload[0]
        is_coord = node == COORD

        # coordinator: heartbeat / join
        hb = is_coord & (mtype == M_HB)
        new_member = hb & ~nodes.joined[src]
        nodes = nodes.replace(
            joined=set_at(nodes.joined, src, True, hb),
            last_hb=set_at(nodes.last_hb, src, now_us, hb),
        )
        nodes = self._rebalance_if(nodes, new_member)
        mask_bits = (
            (nodes.assign_member[COORD] == src).astype(jnp.int32)
            * (1 << jnp.arange(self.P, dtype=jnp.int32))
        ).sum()
        resp = make_payload(
            self.PAYLOAD_WIDTH, M_HB_RESP, nodes.gen[COORD], mask_bits,
            *[nodes.committed[COORD, p] for p in range(self.P)],
        )
        outbox = send_if(outbox, 0, hb, src, resp)

        # coordinator: fetch -> serve record identity if it exists
        fetch = is_coord & (mtype == M_FETCH)
        f_part, f_off = payload[1], payload[2]
        have = (f_off >= 0) & (f_off < self.L)
        resp_f = make_payload(self.PAYLOAD_WIDTH, M_FETCH_RESP, f_part, f_off)
        outbox = send_if(outbox, 0, fetch & have, src, resp_f)

        # coordinator: commit (fenced). Within one generation the owner's
        # commits are cumulative, so a lower offset is just a reordered
        # datagram (the real protocol rides ordered TCP) and is absorbed
        # with max(); a commit from a *different* generation starts a new
        # regime and overwrites — which is where an unfenced zombie's
        # stale offset regresses the partition.
        commit = is_coord & (mtype == M_COMMIT)
        c_gen, c_part, c_off = payload[1], payload[2], payload[3]
        accept = commit & self._commit_accepts(nodes, src, c_gen, c_part)
        part_clip = jnp.clip(c_part, 0, self.P - 1)
        same_regime = c_gen == nodes.commit_gen[COORD, part_clip]
        apply = accept & (~same_regime | (c_off > nodes.committed[COORD, part_clip]))
        regress = apply & (c_off < nodes.committed[COORD, part_clip])
        new_committed_row = set_at(nodes.committed[COORD], part_clip, c_off, apply)
        new_cgen_row = set_at(nodes.commit_gen[COORD], part_clip, c_gen, apply)
        nodes = nodes.replace(
            committed=set_at(nodes.committed, COORD, new_committed_row),
            commit_gen=set_at(nodes.commit_gen, COORD, new_cgen_row),
            bad_regress=set_at(nodes.bad_regress, COORD, nodes.bad_regress[COORD] | regress, commit),
        )

        # member: heartbeat response -> adopt new generation + resume
        is_member = node != COORD
        hb_resp = is_member & (mtype == M_HB_RESP)
        r_gen, r_mask = payload[1], payload[2]
        adopt = hb_resp & (r_gen != nodes.gen[node])
        new_assign = ((r_mask >> jnp.arange(self.P, dtype=jnp.int32)) & 1) != 0
        resume = jnp.stack([payload[3 + p] for p in range(self.P)])
        nodes = update_node(
            nodes, node,
            gen=jnp.where(adopt, r_gen, nodes.gen[node]),
            my_assign=jnp.where(adopt, new_assign, nodes.my_assign[node]),
            position=jnp.where(adopt, resume, nodes.position[node]),
        )

        # member: fetched record -> consume (ghost) + auto-commit
        fr = is_member & (mtype == M_FETCH_RESP)
        g_part, g_off = payload[1], payload[2]
        g_part_c = jnp.clip(g_part, 0, self.P - 1)
        take = fr & nodes.my_assign[node, g_part_c] & (g_off == nodes.position[node, g_part_c])
        # ghost consumed bitmap lives on the COORD row (spec-only)
        off_mask = jnp.arange(self.L) == jnp.clip(g_off, 0, self.L - 1)
        part_mask = jnp.arange(self.P) == g_part_c
        node_mask = jnp.arange(self.NUM_NODES) == COORD
        ghost_write = take & node_mask[:, None, None] & part_mask[None, :, None] & off_mask[None, None, :]
        consumed = nodes.consumed | ghost_write
        new_pos_row = set_at(nodes.position[node], g_part_c, g_off + 1, take)
        nodes = nodes.replace(
            consumed=consumed,
            position=set_at(nodes.position, node, new_pos_row),
        )
        commit_msg = make_payload(
            self.PAYLOAD_WIDTH, M_COMMIT, nodes.gen[node], g_part_c, g_off + 1
        )
        outbox = send_if(outbox, 0, take, COORD, commit_msg)
        return nodes, outbox

    # -- invariants / results --------------------------------------------------

    def invariant(self, nodes: GroupState, now_us):
        committed = nodes.committed[COORD]  # [P]
        in_range = jnp.all((committed >= 0) & (committed <= self.L))
        below = jnp.arange(self.L)[None, :] < committed[:, None]  # [P, L]
        all_consumed = jnp.all(jnp.where(below, nodes.consumed[COORD], True))
        lost = ~(in_range & all_consumed)
        regress = nodes.bad_regress[COORD]
        ok = ~(lost | regress)
        code = jnp.where(regress, COMMIT_REGRESS, jnp.where(lost, LOST_RECORD, 0))
        return ok, code.astype(jnp.int32)

    def is_done(self, nodes: GroupState, now_us):
        return jnp.all(nodes.committed[COORD] >= self.L)

    def summary(self, nodes: GroupState):
        return {
            "committed": nodes.committed[COORD],
            "generation": nodes.gen[COORD],
            "members": nodes.joined.sum(dtype=jnp.int32),
        }


class NoFencingGroupMachine(KafkaGroupMachine):
    """Bug variant: the coordinator accepts commits from any generation —
    the zombie-commit class that consumer-group fencing exists to stop.
    The engine finds seeds where a partitioned member's stale commit
    regresses a committed offset (COMMIT_REGRESS)."""

    def _commit_accepts(self, nodes: GroupState, src, c_gen, c_part) -> jax.Array:
        return jnp.bool_(True)
