"""Message-queue ordering machine — the rdkafka-class engine workload.

BASELINE.json config: "madsim-rdkafka producer/consumer ordering, 100k
seeds sharded over ICI". Node 0 is a single-partition broker with an
idempotent-producer protocol (dedup by per-producer expected seq, like
Kafka's producer idempotence); nodes 1..P are producers appending with
at-least-once retries; the last node is a consumer polling fetches.

Checked invariant (code 120, DUP_OR_GAP): the consumed stream contains
every producer's sequence exactly once, in order — i.e. per-producer
gapless monotonic delivery. The broker's log and dedup cursors are
durable across restart faults (Kafka persists partitions), and acks
carry the broker's cumulative cursor, so the invariant holds under
packet loss, partitions AND kill/restart; the `NoDedupBroker` test
variant (retries append duplicates) violates it, which is the
ordering-bug class the reference's kafka tests exist to catch.

A half at toy size: the deployment-sized machine — this produce path over
three live partition logs, with `kafka_group.py`'s rebalancing group — is
`models/kafka.py` (`--machine kafka`); size a benchmark cell on that.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..engine.machine import Machine, Outbox, make_payload, send_if, set_at, set_timer_if, update_node

BROKER = 0

# messages
M_PRODUCE, M_ACK, M_FETCH, M_BATCH = 1, 2, 3, 4

# timers
T_BOOT, T_PRODUCE, T_POLL, T_RETRY = 0, 1, 2, 3

DUP_OR_GAP = 120

PRODUCE_US = 30_000
POLL_US = 25_000
RETRY_US = 100_000


@struct.dataclass
class MqState:
    # broker
    log_producer: jax.Array  # int32[N, CAP] producer id per log slot
    log_seq: jax.Array  # int32[N, CAP]
    log_len: jax.Array  # int32[N]
    expected: jax.Array  # int32[N, N] broker's dedup cursor per producer
    # producers
    next_seq: jax.Array  # int32[N] next seq to produce
    inflight: jax.Array  # bool[N] waiting for ack
    # consumer
    offset: jax.Array  # int32[N] next log offset to fetch
    seen: jax.Array  # int32[N, N] consumer's per-producer next expected seq
    bad: jax.Array  # bool[N]


class MqMachine(Machine):
    """num_nodes = 1 broker + (num_nodes-2) producers + 1 consumer."""

    PAYLOAD_WIDTH = 5
    MAX_MSGS = 1
    MAX_TIMERS = 2

    def __init__(self, num_nodes: int = 4, log_capacity: int = 24, max_seq: int = 10):
        self.NUM_NODES = num_nodes
        self.log_capacity = log_capacity
        self.max_seq = max_seq
        self.consumer = num_nodes - 1

    def init(self, rng_key) -> MqState:
        n, cap = self.NUM_NODES, self.log_capacity
        z = jnp.zeros((n,), jnp.int32)
        return MqState(
            log_producer=jnp.zeros((n, cap), jnp.int32),
            log_seq=jnp.zeros((n, cap), jnp.int32),
            log_len=z,
            expected=jnp.zeros((n, n), jnp.int32),
            next_seq=z,
            inflight=jnp.zeros((n,), bool),
            offset=z,
            seen=jnp.zeros((n, n), jnp.int32),
            bad=jnp.zeros((n,), bool),
        )

    def init_node(self, nodes: MqState, i, rng_key) -> MqState:
        """Restart: broker durable (log + dedup cursors persist, like
        Kafka's on-disk partitions); producers/consumer reset volatile
        session state."""
        return self.restart_if(nodes, i, jnp.bool_(True), rng_key)

    def restart_if(self, nodes: MqState, i, cond, rng_key) -> MqState:
        n = self.NUM_NODES
        mask = (jnp.arange(n) == i) & (i != BROKER) & cond
        return nodes.replace(
            next_seq=jnp.where(mask, 0, nodes.next_seq),
            inflight=jnp.where(mask, False, nodes.inflight),
            offset=jnp.where(mask, 0, nodes.offset),
            seen=jnp.where(mask[:, None], 0, nodes.seen),
        )

    def _is_producer(self, node):
        return (node != BROKER) & (node != self.consumer)

    # -- broker-side append with dedup ---------------------------------------

    def _accepts(self, nodes: MqState, producer, seq) -> jax.Array:
        """Idempotence predicate — the single line the NoDedup bug variant
        overrides."""
        return seq == nodes.expected[BROKER, producer]

    def _append(self, nodes: MqState, producer, seq, do: jax.Array) -> MqState:
        fresh = do & self._accepts(nodes, producer, seq) & (
            nodes.log_len[BROKER] < self.log_capacity
        )
        slot = jnp.minimum(nodes.log_len[BROKER], self.log_capacity - 1)
        row_p = jnp.where(
            fresh, set_at(nodes.log_producer[BROKER], slot, producer), nodes.log_producer[BROKER]
        )
        row_s = jnp.where(fresh, set_at(nodes.log_seq[BROKER], slot, seq), nodes.log_seq[BROKER])
        exp_row = jnp.where(
            fresh,
            set_at(nodes.expected[BROKER], producer, seq + 1),
            nodes.expected[BROKER],
        )
        return nodes.replace(
            log_producer=set_at(nodes.log_producer, BROKER, row_p),
            log_seq=set_at(nodes.log_seq, BROKER, row_s),
            log_len=jnp.where(fresh, set_at(nodes.log_len, BROKER, nodes.log_len[BROKER] + 1), nodes.log_len),
            expected=set_at(nodes.expected, BROKER, exp_row),
        )

    # -- timers ---------------------------------------------------------------

    def on_timer(self, nodes: MqState, node, timer_id, now_us, rand_u32) -> Tuple[MqState, Outbox]:
        outbox = self.empty_outbox()
        is_boot = timer_id == T_BOOT
        is_prod = self._is_producer(node)
        is_cons = node == self.consumer

        outbox = set_timer_if(outbox, 0, is_boot & is_prod, PRODUCE_US, T_PRODUCE)
        outbox = set_timer_if(outbox, 0, is_boot & is_cons, POLL_US, T_POLL)

        # producer: send next seq when idle
        tick = (timer_id == T_PRODUCE) & is_prod
        start = tick & ~nodes.inflight[node] & (nodes.next_seq[node] < self.max_seq)
        produce = make_payload(self.PAYLOAD_WIDTH, M_PRODUCE, node, nodes.next_seq[node])
        outbox = send_if(outbox, 0, start, BROKER, produce)
        nodes = update_node(nodes, node, inflight=nodes.inflight[node] | start)
        outbox = set_timer_if(outbox, 0, tick, PRODUCE_US, T_PRODUCE)
        outbox = set_timer_if(outbox, 1, start, RETRY_US, T_RETRY)

        # producer retry (at-least-once)
        retry = (timer_id == T_RETRY) & is_prod & nodes.inflight[node]
        outbox = send_if(outbox, 0, retry, BROKER, produce)
        outbox = set_timer_if(outbox, 1, retry, RETRY_US, T_RETRY)

        # consumer: poll for the next offset
        poll = (timer_id == T_POLL) & is_cons
        fetch = make_payload(self.PAYLOAD_WIDTH, M_FETCH, node, nodes.offset[node])
        outbox = send_if(outbox, 0, poll, BROKER, fetch)
        outbox = set_timer_if(outbox, 0, poll, POLL_US, T_POLL)
        return nodes, outbox

    # -- messages -------------------------------------------------------------

    def on_message(self, nodes: MqState, node, src, payload, now_us, rand_u32) -> Tuple[MqState, Outbox]:
        outbox = self.empty_outbox()
        mtype = payload[0]

        # broker: PRODUCE -> append (dedup) + ack
        is_produce = (node == BROKER) & (mtype == M_PRODUCE)
        producer, seq = payload[1], payload[2]
        nodes = self._append(nodes, producer, seq, is_produce)
        # cumulative ack: "I have everything below `expected`" — a stale or
        # duplicate PRODUCE still gets an informative ack
        ack = make_payload(self.PAYLOAD_WIDTH, M_ACK, nodes.expected[BROKER, producer])
        outbox = send_if(outbox, 0, is_produce, producer, ack)

        # broker: FETCH -> return entry at offset (if any)
        is_fetch = (node == BROKER) & (mtype == M_FETCH)
        consumer, offset = payload[1], payload[2]
        have = offset < nodes.log_len[BROKER]
        slot = jnp.minimum(offset, self.log_capacity - 1)
        batch = make_payload(
            self.PAYLOAD_WIDTH, M_BATCH, offset,
            nodes.log_producer[BROKER, slot], nodes.log_seq[BROKER, slot],
        )
        outbox = send_if(outbox, 0, is_fetch & have, consumer, batch)

        # producer: cumulative ack advances next_seq; an ack that does not
        # cover the outstanding record keeps it inflight (retry continues),
        # so a full log degrades to retries, never to silent loss
        is_ack = self._is_producer(node) & (mtype == M_ACK)
        covers = payload[1] > nodes.next_seq[node]
        acked = is_ack & covers & nodes.inflight[node]
        nodes = update_node(
            nodes, node,
            inflight=nodes.inflight[node] & ~acked,
            next_seq=jnp.where(acked, payload[1], nodes.next_seq[node]),
        )

        # consumer: BATCH at the expected offset advances; check per-producer order
        is_batch = (node == self.consumer) & (mtype == M_BATCH)
        b_off, b_prod, b_seq = payload[1], payload[2], payload[3]
        take = is_batch & (b_off == nodes.offset[node])
        in_order = b_seq == nodes.seen[node, b_prod]
        nodes = update_node(
            nodes, node,
            offset=jnp.where(take, nodes.offset[node] + 1, nodes.offset[node]),
            bad=nodes.bad[node] | (take & ~in_order),
            seen=jnp.where(
                take & in_order,
                set_at(nodes.seen[node], b_prod, b_seq + 1),
                nodes.seen[node],
            ),
        )
        return nodes, outbox

    # -- invariants / results ---------------------------------------------------

    def invariant(self, nodes: MqState, now_us):
        ok = ~jnp.any(nodes.bad)
        return ok, jnp.where(ok, 0, DUP_OR_GAP).astype(jnp.int32)

    def is_done(self, nodes: MqState, now_us):
        total = (self.NUM_NODES - 2) * self.max_seq
        return nodes.offset[self.consumer] >= jnp.int32(min(total, self.log_capacity))

    def summary(self, nodes: MqState):
        return {
            "log_len": nodes.log_len[BROKER],
            "consumed": nodes.offset[self.consumer],
            "produced": jnp.sum(nodes.next_seq) - nodes.next_seq[BROKER] - nodes.next_seq[self.consumer],
        }
