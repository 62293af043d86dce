"""Kafka producer/consumer pipeline — madsim-rdkafka's deployment as one
batched engine Machine.

`models/mq.py` (idempotent produce over one toy partition) and
`models/kafka_group.py` (a rebalancing group over pre-filled partitions)
are the two halves of what a madsim-rdkafka user runs at once
(madsim-rdkafka `tests/test.rs`: one `SimBroker`, a topic of 3
partitions, two producers, two consumers). This machine unites them
over LIVE partition logs: records arrive while the group rebalances.

Topology, three roles in one lane (`num_nodes` = 5): node 0 is the
broker AND the group coordinator (Kafka's coordinator is a broker),
nodes 1-2 are producers, nodes 3-4 the members of one consumer group.
The topic has P = 3 partitions of `log_capacity` slots each.

Producers. Every `PRODUCE_US` an idle producer draws a key from the
`rand_u32` its handler is given; partition = key mod P (Kafka's default
partitioner on a keyed record); sequence numbers run per (producer,
partition). One record is in flight a producer and is sent again every
`RETRY_US` until acknowledged (at-least-once sends). Producers start no
record at or after `produce_until_us` (the registry's 1.5 virtual
seconds: a run's `--horizon` is that plus the half second the members
drain in — `kafka_pc5` runs 2). The window is the machine's, not read
off the horizon: one machine object serves engines of several horizons
(shrink's horizon stage), and a handler sees `now_us` only.

Broker. A record is `(producer, seq)`; the high watermark of a partition
is its length. Idempotence: a record is appended only where `seq ==
expected[partition, producer]` (`_accepts`, the line
`NoDedupKafkaMachine` removes: a retried record is appended again).
Every PRODUCE is answered with the cursor, so a stale or duplicate one
still gets an informative ack. A full log appends nothing and counts
`log_full`; that is no failure code, and a configuration sizes the log
so that it reads 0.

What a restarted producer stands for. In Kafka an idempotent producer
that restarts asks for a new producer id (or, with a `transactional.id`,
keeps the id under a bumped epoch that fences the old incarnation) and
starts its sequences at 0 under it. Here the node id is the producer id
for life: a restarted producer starts at 0, its first record to a
partition is refused as a duplicate, and the ack's cursor tells it where
the broker stands (`mq.py`'s rule) — a stable identity whose sequence is
recovered from the broker. No epoch is modelled because no zombie
producer can exist: a kill is the only way a producer restarts, and the
old incarnation's requests still in the network carry sequences the
cursor has passed or will accept once.

Members. `kafka_group.py`'s protocol as it stands: a heartbeat is a
join; a membership change bumps the generation and re-deals the
partitions over the joined members by rank (2 + 1 with both up, 3 with
one); a member that sees a new generation adopts its assignment and
resumes each owned partition from the committed offset; sessions expire
on the coordinator's tick. One difference: a fetch is answered with a
RANGE `[position, min(high watermark, position + FETCH_MAX))`, and the
member commits once a response, tagged with its generation (a consumer
commits a poll's batch, not each record). An empty range is answered by
silence; the next poll asks again. Commits are fenced as today
(`_commit_accepts`): current generation, a joined member, the owner.

Durability (`restart_lane_if`): logs, cursors, generation, assignment
and committed offsets survive a restart of node 0 (Kafka persists
partitions and `__consumer_offsets`); its member table does not (every
member rejoins). Producers and members lose everything they hold.

Role-held state (`lane_spec`): the logs, cursors, ghost state, group
state and counters belong to node 0's role and are stored ONCE a lane,
`[P, CAP]` and not `[N, P, CAP]`.

Invariants, checked after every event:
  * DUP_OR_GAP (120): in every partition log each producer's sequences
    are 0, 1, 2, ... once each, in order — checked on the appended
    record against a ghost cursor, never by rescanning the log.
  * COMMIT_REGRESS (131): an accepted commit moved a committed offset
    backwards.
  * LOST_RECORD (130): an offset under a committed offset was never
    consumed (ghost consumed bitmap `[P, CAP]`, written at consume time,
    never read by the protocol).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..engine.machine import (
    Machine, Outbox, get_at, make_payload, send_if, set_at, set_timer_if, update_node,
)
from .kafka_group import (
    COMMIT_REGRESS, HB_US, LOST_RECORD, POLL_US, SESSION_CHECK_US, SESSION_US,
)
from .mq import DUP_OR_GAP, M_ACK, M_PRODUCE, PRODUCE_US, RETRY_US

BROKER = 0
COORD = BROKER  # the group coordinator is the broker (`kafka_group.py`'s name for it)

# messages (1, 2 are mq.py's produce / ack)
M_HB, M_HB_RESP, M_FETCH, M_FETCH_RESP, M_COMMIT = 3, 4, 5, 6, 7

# timers
T_BOOT, T_PRODUCE, T_RETRY, T_SESSION, T_HB, T_POLL = 0, 1, 2, 3, 4, 5

FETCH_MAX = 8  # records a fetch response may carry
COUNTERS = (
    "produced", "appended", "dup_refused", "consumed", "rebalances",
    "commits_fenced", "log_full",
)
_C = {name: i for i, name in enumerate(COUNTERS)}


@struct.dataclass
class KafkaState:
    # -- role-held: node 0's, stored once a lane (no node axis) --
    log_producer: jax.Array  # int32[P, CAP] producer id per log slot
    log_seq: jax.Array  # int32[P, CAP]
    log_len: jax.Array  # int32[P] high watermark
    expected: jax.Array  # int32[P, N] dedup cursor per (partition, producer)
    gen: jax.Array  # int32[] group generation
    joined: jax.Array  # bool[N] member table (volatile)
    last_hb: jax.Array  # int32[N] last heartbeat, us (volatile)
    assign_member: jax.Array  # int32[P] owning node per partition (-1 none)
    committed: jax.Array  # int32[P]
    commit_gen: jax.Array  # int32[P] generation of the last accepted commit
    # ghost (spec-only) and totals
    ghost_next: jax.Array  # int32[P, N] next sequence the log may hold
    consumed: jax.Array  # bool[P, CAP] ever consumed by a member
    bad_dup: jax.Array  # bool[]
    bad_regress: jax.Array  # bool[]
    counters: jax.Array  # int32[len(COUNTERS)]
    # -- producers (per node) --
    next_seq: jax.Array  # int32[N, P] next sequence per partition
    inflight: jax.Array  # bool[N] waiting for an ack
    pend_part: jax.Array  # int32[N] the record in flight
    pend_seq: jax.Array  # int32[N]
    retry_at: jax.Array  # int32[N] when the record in flight is sent again
    # -- members (per node) --
    m_gen: jax.Array  # int32[N] adopted generation
    my_assign: jax.Array  # bool[N, P]
    position: jax.Array  # int32[N, P] next offset to consume
    poll_rr: jax.Array  # int32[N] round-robin partition cursor


_ROLE_HELD = (
    "log_producer", "log_seq", "log_len", "expected", "gen", "joined",
    "last_hb", "assign_member", "committed", "commit_gen", "ghost_next",
    "consumed", "bad_dup", "bad_regress", "counters",
)
_VOLATILE_ROLE_HELD = ("joined", "last_hb")


class KafkaMachine(Machine):
    """1 broker/coordinator + 2 producers + the rest members."""

    MAX_MSGS = 1
    MAX_TIMERS = 2
    P = 3
    producers = 2  # nodes 1..producers; the rest are the group's members
    STREAM_COUNTERS = COUNTERS + ("log_high_water",)
    STREAM_COUNTERS_MAX = ("log_high_water",)

    def __init__(self, num_nodes: int = 5, log_capacity: int = 64,
                 produce_until_us: int = 1_500_000):
        if num_nodes < self.producers + 2:
            raise ValueError(
                f"kafka needs a broker, {self.producers} producers and at least "
                f"one member: num_nodes >= {self.producers + 2}, got {num_nodes}"
            )
        self.NUM_NODES = num_nodes
        self.log_capacity = log_capacity
        self.produce_until_us = produce_until_us
        self.PAYLOAD_WIDTH = 3 + self.P

    # -- state ---------------------------------------------------------------

    def init(self, rng_key) -> KafkaState:
        n, p, cap = self.NUM_NODES, self.P, self.log_capacity
        zn = jnp.zeros((n,), jnp.int32)
        return KafkaState(
            log_producer=jnp.zeros((p, cap), jnp.int32),
            log_seq=jnp.zeros((p, cap), jnp.int32),
            log_len=jnp.zeros((p,), jnp.int32),
            expected=jnp.zeros((p, n), jnp.int32),
            gen=jnp.int32(0),
            joined=jnp.zeros((n,), bool),
            last_hb=zn,
            assign_member=jnp.full((p,), -1, jnp.int32),
            committed=jnp.zeros((p,), jnp.int32),
            commit_gen=jnp.zeros((p,), jnp.int32),
            ghost_next=jnp.zeros((p, n), jnp.int32),
            consumed=jnp.zeros((p, cap), bool),
            bad_dup=jnp.bool_(False),
            bad_regress=jnp.bool_(False),
            counters=jnp.zeros((len(COUNTERS),), jnp.int32),
            next_seq=jnp.zeros((n, p), jnp.int32),
            inflight=jnp.zeros((n,), bool),
            pend_part=zn,
            pend_seq=zn,
            retry_at=zn,
            m_gen=zn,
            my_assign=jnp.zeros((n, p), bool),
            position=jnp.zeros((n, p), jnp.int32),
            poll_rr=zn,
        )

    def _spec(self, true_fields) -> KafkaState:
        return KafkaState(**{
            f: f in true_fields for f in KafkaState.__dataclass_fields__
        })

    def lane_spec(self) -> KafkaState:
        return self._spec(_ROLE_HELD)

    def durable_spec(self) -> KafkaState:
        """The broker's disk; what a client holds is memory. (For the
        role-held leaves the entry is documentation: `restart_lane_if`
        is what a restart does to them.)"""
        return self._spec(set(_ROLE_HELD) - set(_VOLATILE_ROLE_HELD))

    def restart_lane_if(self, nodes: KafkaState, i, cond, rng_key) -> KafkaState:
        down = cond & (i == BROKER)
        return nodes.replace(
            joined=jnp.where(down, False, nodes.joined),
            last_hb=jnp.where(down, 0, nodes.last_hb),
        )

    def restart_if(self, nodes: KafkaState, i, cond, rng_key) -> KafkaState:
        # a client loses what it holds; nothing per node is the broker's
        row = (jnp.arange(self.NUM_NODES) == i) & cond
        nodes = nodes.replace(
            next_seq=jnp.where(row[:, None], 0, nodes.next_seq),
            inflight=nodes.inflight & ~row,
            pend_part=jnp.where(row, 0, nodes.pend_part),
            pend_seq=jnp.where(row, 0, nodes.pend_seq),
            retry_at=jnp.where(row, 0, nodes.retry_at),
            m_gen=jnp.where(row, 0, nodes.m_gen),
            my_assign=nodes.my_assign & ~row[:, None],
            position=jnp.where(row[:, None], 0, nodes.position),
            poll_rr=jnp.where(row, 0, nodes.poll_rr),
        )
        return self.restart_lane_if(nodes, i, cond, rng_key)

    def _is_producer(self, node):
        return (node >= 1) & (node <= self.producers)

    def _is_member(self, node):
        return node > self.producers

    def _count(self, nodes: KafkaState, **events) -> KafkaState:
        """counters[name] += n for each named event (a traced bool counts 1)."""
        add = jnp.zeros_like(nodes.counters)
        for name, n in events.items():
            add = add.at[_C[name]].set(jnp.asarray(n, jnp.int32))
        return nodes.replace(counters=nodes.counters + add)

    # -- broker: produce ------------------------------------------------------

    def _accepts(self, nodes: KafkaState, part, producer, seq) -> jax.Array:
        """Idempotence predicate — the line the NoDedup variant removes."""
        return seq == get_at(nodes.expected, (part, producer))

    def _append(self, nodes: KafkaState, part, producer, seq, do) -> KafkaState:
        cap = self.log_capacity
        length = get_at(nodes.log_len, part)
        accepts = do & self._accepts(nodes, part, producer, seq)
        room = length < cap
        fresh = accepts & room
        cell = (
            (jnp.arange(self.P)[:, None] == part)
            & (jnp.arange(cap)[None, :] == jnp.minimum(length, cap - 1))
            & fresh
        )
        at = (jnp.arange(self.P)[:, None] == part) & (
            jnp.arange(self.NUM_NODES)[None, :] == producer) & fresh
        in_order = seq == get_at(nodes.ghost_next, (part, producer))
        nodes = nodes.replace(
            log_producer=jnp.where(cell, producer, nodes.log_producer),
            log_seq=jnp.where(cell, seq, nodes.log_seq),
            log_len=set_at(nodes.log_len, part, length + 1, fresh),
            expected=jnp.where(at, seq + 1, nodes.expected),
            ghost_next=jnp.where(at, seq + 1, nodes.ghost_next),
            bad_dup=nodes.bad_dup | (fresh & ~in_order),
        )
        return self._count(
            nodes, appended=fresh, dup_refused=do & ~accepts,
            log_full=accepts & ~room,
        )

    # -- coordinator ------------------------------------------------------------

    def _rebalance_if(self, nodes: KafkaState, cond) -> KafkaState:
        """Bump the generation and re-deal the partitions over the joined
        members by rank, under traced `cond` (`kafka_group.py`'s rule)."""
        joined = nodes.joined
        k = joined.sum(dtype=jnp.int32)
        ranks = jnp.cumsum(joined.astype(jnp.int32)) - 1
        targets = jnp.mod(jnp.arange(self.P, dtype=jnp.int32), jnp.maximum(k, 1))
        match = joined[None, :] & (ranks[None, :] == targets[:, None])  # [P, N]
        assignment = jnp.where(k > 0, jnp.argmax(match, axis=1).astype(jnp.int32), -1)
        nodes = nodes.replace(
            gen=nodes.gen + cond.astype(jnp.int32),
            assign_member=jnp.where(cond, assignment, nodes.assign_member),
        )
        return self._count(nodes, rebalances=cond)

    def _commit_accepts(self, nodes: KafkaState, src, c_gen, c_part) -> jax.Array:
        """Fencing predicate: current generation, a joined member, the owner."""
        return (
            (c_gen == nodes.gen) & get_at(nodes.joined, src)
            & (get_at(nodes.assign_member, c_part) == src)
        )

    # -- timers ---------------------------------------------------------------

    def on_timer(self, nodes: KafkaState, node, timer_id, now_us, rand_u32) -> Tuple[KafkaState, Outbox]:
        outbox = self.empty_outbox()
        w = self.PAYLOAD_WIDTH
        is_broker = node == BROKER
        is_prod = self._is_producer(node)
        is_member = self._is_member(node)
        is_boot = timer_id == T_BOOT

        outbox = set_timer_if(outbox, 0, is_boot & is_broker, SESSION_CHECK_US, T_SESSION)
        outbox = set_timer_if(outbox, 0, is_boot & is_prod, PRODUCE_US, T_PRODUCE)
        outbox = set_timer_if(outbox, 0, is_boot & is_member, HB_US, T_HB)
        outbox = set_timer_if(outbox, 1, is_boot & is_member, POLL_US, T_POLL)

        # coordinator: expire silent members, rebalance if any left
        tick = (timer_id == T_SESSION) & is_broker
        expired = nodes.joined & (nodes.last_hb + SESSION_US < now_us)
        any_expired = tick & jnp.any(expired)
        nodes = nodes.replace(joined=jnp.where(any_expired, nodes.joined & ~expired, nodes.joined))
        nodes = self._rebalance_if(nodes, any_expired)
        outbox = set_timer_if(outbox, 0, tick, SESSION_CHECK_US, T_SESSION)

        # producer: start the next record when idle, inside the window
        ptick = (timer_id == T_PRODUCE) & is_prod
        start = ptick & ~get_at(nodes.inflight, node) & (now_us < self.produce_until_us)
        part = (rand_u32[0] % jnp.uint32(self.P)).astype(jnp.int32)
        # the record in flight is sent again RETRY_US after its last send:
        # a retry timer armed for an earlier record fires before the
        # deadline, finds nothing due and dies (`mq.py` resends on it)
        retry = (
            (timer_id == T_RETRY) & is_prod & get_at(nodes.inflight, node)
            & (now_us >= get_at(nodes.retry_at, node))
        )
        nodes = update_node(
            nodes, node,
            inflight=get_at(nodes.inflight, node) | start,
            pend_part=jnp.where(start, part, get_at(nodes.pend_part, node)),
            pend_seq=jnp.where(
                start, get_at(nodes.next_seq, (node, part)), get_at(nodes.pend_seq, node)
            ),
            retry_at=jnp.where(start | retry, now_us + RETRY_US, get_at(nodes.retry_at, node)),
        )
        nodes = self._count(nodes, produced=start)
        outbox = set_timer_if(outbox, 0, ptick, PRODUCE_US, T_PRODUCE)
        record = make_payload(
            w, M_PRODUCE, get_at(nodes.pend_part, node), get_at(nodes.pend_seq, node)
        )
        outbox = send_if(outbox, 0, start | retry, BROKER, record)
        outbox = set_timer_if(outbox, 1, start | retry, RETRY_US, T_RETRY)

        # member: heartbeat (doubles as join)
        hb = (timer_id == T_HB) & is_member
        outbox = send_if(outbox, 0, hb, BROKER, make_payload(w, M_HB))
        outbox = set_timer_if(outbox, 0, hb, HB_US, T_HB)

        # member: fetch the next owned partition (round-robin cursor)
        poll = (timer_id == T_POLL) & is_member
        rr = get_at(nodes.poll_rr, node)
        owned = get_at(nodes.my_assign, node)  # bool[P]
        order = jnp.mod(rr + jnp.arange(self.P, dtype=jnp.int32), self.P)
        pick = get_at(order, jnp.argmax(get_at(owned, order)))
        want = poll & jnp.any(owned)
        fetch = make_payload(w, M_FETCH, pick, get_at(nodes.position, (node, pick)))
        outbox = send_if(outbox, 0, want, BROKER, fetch)
        nodes = update_node(nodes, node, poll_rr=jnp.where(poll, jnp.mod(pick + 1, self.P), rr))
        outbox = set_timer_if(outbox, 0, poll, POLL_US, T_POLL)
        return nodes, outbox

    # -- messages -------------------------------------------------------------

    def on_message(self, nodes: KafkaState, node, src, payload, now_us, rand_u32) -> Tuple[KafkaState, Outbox]:
        outbox = self.empty_outbox()
        w, p_max = self.PAYLOAD_WIDTH, self.P - 1
        mtype = payload[0]
        is_broker = node == BROKER
        is_member = self._is_member(node)

        # broker: PRODUCE -> append (dedup), answer with the cursor
        is_produce = is_broker & (mtype == M_PRODUCE)
        r_part, r_seq = jnp.clip(payload[1], 0, p_max), payload[2]
        nodes = self._append(nodes, r_part, src, r_seq, is_produce)
        ack = make_payload(w, M_ACK, r_part, get_at(nodes.expected, (r_part, src)))
        outbox = send_if(outbox, 0, is_produce, src, ack)

        # broker: FETCH -> the range [offset, min(high watermark, offset + 8))
        is_fetch = is_broker & (mtype == M_FETCH)
        f_part, f_off = jnp.clip(payload[1], 0, p_max), payload[2]
        f_hi = jnp.minimum(get_at(nodes.log_len, f_part), f_off + FETCH_MAX)
        have = (f_off >= 0) & (f_hi > f_off)
        resp_f = make_payload(w, M_FETCH_RESP, f_part, f_off, f_hi)
        outbox = send_if(outbox, 0, is_fetch & have, src, resp_f)

        # coordinator: heartbeat / join
        hb = is_broker & (mtype == M_HB)
        new_member = hb & ~get_at(nodes.joined, src)
        nodes = nodes.replace(
            joined=set_at(nodes.joined, src, True, hb),
            last_hb=set_at(nodes.last_hb, src, now_us, hb),
        )
        nodes = self._rebalance_if(nodes, new_member)
        mask_bits = (
            (nodes.assign_member == src).astype(jnp.int32)
            * (1 << jnp.arange(self.P, dtype=jnp.int32))
        ).sum()
        resp = make_payload(
            w, M_HB_RESP, nodes.gen, mask_bits,
            *[nodes.committed[p] for p in range(self.P)],
        )
        outbox = send_if(outbox, 0, hb, src, resp)

        # coordinator: commit (fenced). Inside one generation the owner's
        # commits are cumulative, so a lower offset is a reordered
        # datagram and max() absorbs it; a commit of another generation
        # starts a new regime and overwrites (`kafka_group.py`).
        commit = is_broker & (mtype == M_COMMIT)
        c_gen, c_part, c_off = payload[1], jnp.clip(payload[2], 0, p_max), payload[3]
        accept = commit & self._commit_accepts(nodes, src, c_gen, c_part)
        same_regime = c_gen == get_at(nodes.commit_gen, c_part)
        apply = accept & (~same_regime | (c_off > get_at(nodes.committed, c_part)))
        regress = apply & (c_off < get_at(nodes.committed, c_part))
        nodes = nodes.replace(
            committed=set_at(nodes.committed, c_part, c_off, apply),
            commit_gen=set_at(nodes.commit_gen, c_part, c_gen, apply),
            bad_regress=nodes.bad_regress | regress,
        )
        nodes = self._count(nodes, commits_fenced=commit & ~accept)

        # producer: the cursor covers the record in flight -> next one
        is_ack = self._is_producer(node) & (mtype == M_ACK)
        a_part, a_cursor = jnp.clip(payload[1], 0, p_max), payload[2]
        acked = (
            is_ack & get_at(nodes.inflight, node) & (a_part == get_at(nodes.pend_part, node))
            & (a_cursor > get_at(nodes.pend_seq, node))
        )
        nodes = update_node(
            nodes, node,
            inflight=get_at(nodes.inflight, node) & ~acked,
            next_seq=set_at(get_at(nodes.next_seq, node), a_part, a_cursor, acked),
        )

        # member: heartbeat response -> adopt a new generation, resume
        # every owned partition from its committed offset
        hb_resp = is_member & (mtype == M_HB_RESP)
        r_gen, r_mask = payload[1], payload[2]
        adopt = hb_resp & (r_gen != get_at(nodes.m_gen, node))
        new_assign = ((r_mask >> jnp.arange(self.P, dtype=jnp.int32)) & 1) != 0
        resume = jnp.stack([payload[3 + p] for p in range(self.P)])
        nodes = update_node(
            nodes, node,
            m_gen=jnp.where(adopt, r_gen, get_at(nodes.m_gen, node)),
            my_assign=jnp.where(adopt, new_assign, get_at(nodes.my_assign, node)),
            position=jnp.where(adopt, resume, get_at(nodes.position, node)),
        )

        # member: a fetched range at the position -> consume it (ghost),
        # move on, commit once
        fr = is_member & (mtype == M_FETCH_RESP)
        g_part, g_off, g_hi = jnp.clip(payload[1], 0, p_max), payload[2], payload[3]
        take = (
            fr & get_at(nodes.my_assign, (node, g_part))
            & (g_off == get_at(nodes.position, (node, g_part))) & (g_hi > g_off)
        )
        offs = jnp.arange(self.log_capacity)[None, :]
        ate = (jnp.arange(self.P)[:, None] == g_part) & (offs >= g_off) & (offs < g_hi) & take
        nodes = nodes.replace(
            consumed=nodes.consumed | ate,
            position=set_at(
                nodes.position, node, set_at(get_at(nodes.position, node), g_part, g_hi, take)
            ),
        )
        nodes = self._count(nodes, consumed=jnp.where(take, g_hi - g_off, 0))
        commit_msg = make_payload(w, M_COMMIT, get_at(nodes.m_gen, node), g_part, g_hi)
        outbox = send_if(outbox, 0, take, BROKER, commit_msg)
        return nodes, outbox

    # -- invariants / results --------------------------------------------------

    def invariant(self, nodes: KafkaState, now_us):
        cap = self.log_capacity
        committed = nodes.committed
        in_range = jnp.all((committed >= 0) & (committed <= cap))
        below = jnp.arange(cap)[None, :] < committed[:, None]  # [P, CAP]
        lost = ~(in_range & jnp.all(nodes.consumed | ~below))
        code = jnp.where(
            nodes.bad_dup, DUP_OR_GAP,
            jnp.where(nodes.bad_regress, COMMIT_REGRESS, jnp.where(lost, LOST_RECORD, 0)),
        )
        return code == 0, code.astype(jnp.int32)

    def stream_counters(self, nodes: KafkaState) -> jax.Array:
        return jnp.concatenate([nodes.counters, nodes.log_len.max()[None]])

    def summary(self, nodes: KafkaState):
        return dict(
            zip(self.STREAM_COUNTERS, self.stream_counters(nodes)),
            log_len=nodes.log_len,
            committed=nodes.committed,
            generation=nodes.gen,
            members=nodes.joined.sum(dtype=jnp.int32),
        )

    def coverage_projection(self, nodes: KafkaState, now_us) -> jax.Array:
        # low 3 bits: the group's generation (the phase axis); then how
        # many members are joined, whether a producer waits for an ack,
        # how far the slowest partition's commit lags its high watermark
        gen = jnp.minimum(nodes.gen, 7).astype(jnp.uint32)
        members = jnp.minimum(nodes.joined.sum(dtype=jnp.int32), 3).astype(jnp.uint32)
        waiting = jnp.any(nodes.inflight).astype(jnp.uint32)
        lag = jnp.max(nodes.log_len - nodes.committed)
        lag_b = ((lag > 0).astype(jnp.uint32) + (lag > 4) + (lag > 16)).astype(jnp.uint32)
        return gen | (members << 3) | (waiting << 5) | (lag_b << 6)


class NoDedupKafkaMachine(KafkaMachine):
    """Bug variant: the broker appends whatever arrives — a retried record
    whose ack was lost lands in the log twice. The engine finds the seeds
    where that happens (DUP_OR_GAP), the ordering-bug class an idempotent
    producer exists to stop."""

    def _accepts(self, nodes: KafkaState, part, producer, seq) -> jax.Array:
        return jnp.bool_(True)
