"""Quorum-committed epidemic broadcast at gossip scale — the round-5
larger-n machine (VERDICT r4 directive 6: every previous machine is 4-5
nodes; this one runs 16-60 nodes and exercises the two-word group-mask
path lifted in engine/core.py).

Protocol: R rumors, rumor r originated by node r % N. The origin seeds
its rumor at a staggered inject time and every node runs an anti-entropy
tick (push one random held rumor to one random peer). First receipt of a
rumor stores it, acks the ORIGIN, and forwards to FANOUT random peers
with a hop budget; duplicate receipts re-ack (at-least-once acks — the
duplicate-ack source the counting bug mishandles). The origin commits
the rumor once DISTINCT ackers reach a majority quorum.

Invariant (checked on-device after every event):
  * COMMIT_BELOW_QUORUM (160) — a committed rumor is held by fewer than
    quorum nodes. The rumor store is durable (restart keeps it), so
    holder counts are monotone and the check is sound: an honest origin
    commits only on distinct acks, and an ack implies a stored copy.

Seeded bug variant:
  * DUP_ACK_COUNT — the origin counts every ack instead of deduping by
    acker (the classic quorum-counting bug: retransmitted/duplicate
    acks inflate the tally), committing below quorum; found by any
    vocabulary that makes duplicate acks (partitions recover + re-ack,
    storms force re-receipt, delay spikes reorder), and caught at the
    exact commit event by the ghost holder count.

Scale notes (the SoA design's stress points this machine probes):
queue capacity must absorb fanout bursts (FANOUT forwards + ack per
receipt at 33+ nodes), and group-fault masks need > 30 bits — the
two-word encoding (payload args 1+2).
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
    set_timer_if,
    update_node,
)

M_RUMOR = 1
M_ACK = 2

COMMIT_BELOW_QUORUM = 160

GOSSIP_US = 50_000   # anti-entropy tick
INJECT_US = 150_000  # stagger between rumor injections
HOP_BUDGET = 4       # forward TTL on first receipt


@struct.dataclass
class GossipState:
    holds: jax.Array      # bool[N, R] durable rumor store
    committed: jax.Array  # bool[N, R] origin's commit flag (row = origin)
    ack_cnt: jax.Array    # int32[N, R] origin's ack tally (the bug target)
    acked_by: jax.Array   # bool[N, R, N] origin's distinct-acker table
    epoch: jax.Array      # int32[N] timer epoch


class GossipMachine(Machine):
    """N-node quorum broadcast (N defaults to 33 — past the old mask cap)."""

    PAYLOAD_WIDTH = 4
    MAX_MSGS = 4  # FANOUT forwards + 1 ack
    MAX_TIMERS = 1
    FANOUT = 3

    # seeded bug variant (module docstring)
    DUP_ACK_COUNT = False

    def __init__(self, num_nodes: int = 33, rumors: int = 6):
        self.NUM_NODES = num_nodes
        self.R = rumors
        self.QUORUM = num_nodes // 2 + 1

    # -- state ----------------------------------------------------------------

    def init(self, rng_key) -> GossipState:
        n, r = self.NUM_NODES, self.R
        return GossipState(
            holds=jnp.zeros((n, r), bool),
            committed=jnp.zeros((n, r), bool),
            ack_cnt=jnp.zeros((n, r), jnp.int32),
            acked_by=jnp.zeros((n, r, n), bool),
            epoch=jnp.zeros((n,), jnp.int32),
        )

    def restart_if(self, nodes: GossipState, i, cond, rng_key) -> GossipState:
        # everything durable (the rumor store persists — required for the
        # quorum invariant's monotone holder count); restart re-fires
        # BOOT, which bumps the epoch and re-arms the gossip tick
        return nodes

    def _origin(self, r):
        return jnp.mod(r, jnp.int32(self.NUM_NODES))

    # -- timers ---------------------------------------------------------------

    def on_timer(self, nodes: GossipState, node, timer_id, now_us, rand_u32) -> Tuple[GossipState, Outbox]:
        outbox = self.empty_outbox()
        is_boot = timer_id == 0
        t_epoch = (timer_id - 1) // 2
        live = is_boot | (t_epoch == nodes.epoch[node])

        new_epoch = jnp.where(is_boot & live, nodes.epoch[node] + 1, nodes.epoch[node])
        nodes = update_node(nodes, node, epoch=new_epoch)
        tid = jnp.int32(1) + 2 * nodes.epoch[node]

        n, R = self.NUM_NODES, self.R

        # inject: the earliest owned, due, not-yet-held rumor (origin
        # stores + fans out; its own copy counts toward quorum)
        rumors = jnp.arange(R, dtype=jnp.int32)
        owned = self._origin(rumors) == node
        due = now_us >= rumors * INJECT_US
        pending = owned & due & ~nodes.holds[node]
        inject = live & jnp.any(pending)
        rumor_inj = jnp.argmax(pending).astype(jnp.int32)

        # anti-entropy: push one random held rumor to one random peer
        held = nodes.holds[node]
        n_held = held.sum(dtype=jnp.int32)
        pick_rank = (
            rand_u32[0] % jnp.maximum(n_held, 1).astype(jnp.uint32)
        ).astype(jnp.int32)
        ranks = jnp.cumsum(held.astype(jnp.int32)) - 1
        rumor_push = jnp.argmax(held & (ranks == pick_rank)).astype(jnp.int32)
        push = live & ~inject & (n_held > 0)

        peer_off = 1 + (rand_u32[1] % jnp.uint32(n - 1)).astype(jnp.int32)
        peer = jnp.mod(node + peer_off, n)

        rumor_out = jnp.where(inject, rumor_inj, rumor_push)
        hop = jnp.where(inject, HOP_BUDGET, 1)
        inj_row = (
            (jnp.arange(n) == node)[:, None]
            & (jnp.arange(R) == rumor_inj)[None, :]
            & inject
        )
        # the origin's own stored copy is the tally's first member —
        # recorded in the acker table so a self-ack cannot double-count
        inj_cell = inj_row[:, :, None] & (jnp.arange(n) == node)[None, None, :]
        nodes = nodes.replace(
            holds=jnp.where(inj_row, True, nodes.holds),
            ack_cnt=jnp.where(inj_row, 1, nodes.ack_cnt),
            acked_by=jnp.where(inj_cell, True, nodes.acked_by),
        )
        # inject fans out to FANOUT peers; a plain tick pushes to one
        for s in range(self.FANOUT):
            mix = rand_u32[2] + jnp.uint32((s * 0x9E3779B9) & 0xFFFFFFFF)
            off = 1 + (mix % jnp.uint32(n - 1)).astype(jnp.int32)
            dst = jnp.mod(node + off, n)
            want = inject if s > 0 else (inject | push)
            dst = jnp.where(inject, dst, peer)
            outbox = send_if(
                outbox, s, want, dst,
                make_payload(self.PAYLOAD_WIDTH, M_RUMOR, rumor_out, hop),
            )
        jitter = (rand_u32[3] % jnp.uint32(GOSSIP_US // 4)).astype(jnp.int32)
        outbox = set_timer_if(
            outbox, 0, live, jnp.int32(GOSSIP_US) + jitter, tid
        )
        return nodes, outbox

    # -- messages -------------------------------------------------------------

    def on_message(self, nodes: GossipState, node, src, payload, now_us, rand_u32) -> Tuple[GossipState, Outbox]:
        outbox = self.empty_outbox()
        mtype, rumor, hop = payload[0], payload[1], payload[2]
        n, R = self.NUM_NODES, self.R
        rumor_c = jnp.clip(rumor, 0, R - 1)

        # ---- rumor receipt: store on first sight, ALWAYS ack the origin
        is_rumor = mtype == M_RUMOR
        first = is_rumor & ~nodes.holds[node, rumor_c]
        nodes = nodes.replace(
            holds=jnp.where(
                ((jnp.arange(n) == node)[:, None]
                 & (jnp.arange(R) == rumor_c)[None, :] & is_rumor),
                True, nodes.holds,
            )
        )
        origin = self._origin(rumor_c)
        outbox = send_if(
            outbox, 3, is_rumor, origin,
            make_payload(self.PAYLOAD_WIDTH, M_ACK, rumor_c, 0),
        )
        # forward on first receipt while hop budget remains
        fwd = first & (hop > 0)
        for s in range(self.FANOUT):
            off = 1 + ((rand_u32[s] ) % jnp.uint32(n - 1)).astype(jnp.int32)
            dst = jnp.mod(node + off, n)
            outbox = send_if(
                outbox, s, fwd, dst,
                make_payload(self.PAYLOAD_WIDTH, M_RUMOR, rumor_c, hop - 1),
            )

        # ---- ack receipt at the origin: dedup by acker, tally, commit
        is_ack = (mtype == M_ACK) & (self._origin(rumor_c) == node)
        known = nodes.acked_by[node, rumor_c, jnp.clip(src, 0, n - 1)]
        count_it = is_ack & (jnp.bool_(self.DUP_ACK_COUNT) | ~known)
        row = (jnp.arange(n) == node)[:, None] & (jnp.arange(R) == rumor_c)[None, :]
        cell = row[:, :, None] & (jnp.arange(n) == src)[None, None, :]
        new_cnt = nodes.ack_cnt[node, rumor_c] + 1
        # the tally already includes the origin's own copy (set at inject)
        commit_now = count_it & (new_cnt >= self.QUORUM)
        nodes = nodes.replace(
            acked_by=jnp.where(cell & is_ack, True, nodes.acked_by),
            ack_cnt=jnp.where(row & count_it, new_cnt, nodes.ack_cnt),
            committed=jnp.where(row & commit_now, True, nodes.committed),
        )
        return nodes, outbox

    # -- invariants / results --------------------------------------------------

    def invariant(self, nodes: GossipState, now_us):
        # a committed rumor must be held by >= quorum nodes, NOW (holds
        # are durable, so the count is monotone and the check is exact
        # at the commit event)
        holders = nodes.holds.sum(axis=0)  # [R] global truth
        origins = self._origin(jnp.arange(self.R, dtype=jnp.int32))
        committed = nodes.committed[origins, jnp.arange(self.R)]
        below = jnp.any(committed & (holders < self.QUORUM))
        return ~below, jnp.where(below, COMMIT_BELOW_QUORUM, 0).astype(jnp.int32)

    def is_done(self, nodes: GossipState, now_us):
        origins = self._origin(jnp.arange(self.R, dtype=jnp.int32))
        all_committed = jnp.all(nodes.committed[origins, jnp.arange(self.R)])
        return all_committed & jnp.all(nodes.holds)

    def summary(self, nodes: GossipState):
        origins = self._origin(jnp.arange(self.R, dtype=jnp.int32))
        return {
            "committed": nodes.committed[origins, jnp.arange(self.R)].sum(
                dtype=jnp.int32
            ),
            "coverage": nodes.holds.sum(dtype=jnp.int32),
            "acks": nodes.ack_cnt[origins, jnp.arange(self.R)].sum(dtype=jnp.int32),
        }


class DupAckGossip(GossipMachine):
    """Bug variant (`demo-dupack-gossip`): the quorum tally counts
    duplicate acks."""

    DUP_ACK_COUNT = True
