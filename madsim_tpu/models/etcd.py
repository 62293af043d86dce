"""Leased-KV / leader-election machine — the service-class (L5) engine
workload, batched.

Models the madsim-etcd-client scenario family
(`/root/reference/madsim-etcd-client/tests/test.rs`: campaign/leader/
lease grant/keepalive over a SimServer with an MVCC store,
`src/service.rs:191+` leases `:25-35,:398,:466`, elections `:487+`) as a
TPU-engine `Machine`, so etcd-class workloads explore thousands of seeds
per batch instead of one-at-a-time on the host engine.

Topology: node 0 is the etcd-like server (durable MVCC revision counter,
per-client leases, one election); nodes 1..N-1 are clients that grant a
lease, campaign for leadership, keep their lease alive while leading,
and write revisioned values.

Lease-safety discipline (why the invariant is exact, not probabilistic):
the server expires a lease TTL after the last keepalive *receipt*; a
client stops believing in its leadership TTL after the last acked
keepalive *send* (requests echo their send time). Since receipt >= send
under non-negative network latency, a client's local deadline never
exceeds the server's expiry — so at every instant:

    believes_leader(c)  ==>  server.cur_owner == c
                             and server.cur_gen == c.believed_gen

Violations (code 120 LEASE_SAFETY) catch exactly the etcd bug classes
the reference's tests exist for: double-granted elections (campaign
ignoring a live owner), lease resurrection (keepalive reviving an
expired lease), and a server that loses its state on restart (the
durable store is what makes the honest machine safe — see
`VolatileEtcd` in tests/test_engine_etcd.py).

Timer ids are epoch-encoded like models/raft.py (a restart bumps the
node's epoch at BOOT) so kill/restart cannot double-arm tick chains.
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

SERVER = 0

# message types (payload[0])
M_GRANT = 1       # client->server: grant/refresh my lease   [m, c, send_us]
M_GRANT_OK = 2    # server->client                            [m, c, send_us]
M_CAMPAIGN = 3    # client->server: try to become leader      [m, c, send_us]
M_WON = 4         # server->client: you own generation g      [m, c, send_us, g]
M_LOST = 5        # server->client: someone else leads
M_NO_LEASE = 6    # server->client: grant a lease first
M_KA = 7          # client->server: keepalive                 [m, c, send_us]
M_KA_OK = 8       # server->client: lease extended            [m, c, send_us]
M_KA_ERR = 9      # server->client: lease expired — stand down
M_PUT = 10        # leader->server: revisioned write          [m, c, send_us, g]
M_PUT_OK = 11     # server->client                            [m, c, send_us, rev]

# timer bases (tid = base + 4*epoch; engine-raw 0 == BOOT)
T_BOOT = 0
T_TICK = 1

LEASE_SAFETY = 120  # invariant code: two believed leaderships / stale gen

TTL_US = 300_000
TICK_US = 100_000


@struct.dataclass
class EtcdState:
    # --- server-owned (rows semantically owned by node 0; durable like
    # etcd's raft-backed store — kept across server restart) -------------
    srv_rev: jax.Array            # int32[N] MVCC revision (entry 0)
    srv_gen: jax.Array            # int32[N] election generation (entry 0)
    srv_owner: jax.Array          # int32[N] current leader client, -1 (entry 0)
    srv_lease_expiry: jax.Array   # int32[N] per-CLIENT lease expiry us (0 = none)
    # --- client-owned (volatile: reset on that client's restart) --------
    cl_has_lease: jax.Array       # bool[N] grant acked
    cl_deadline: jax.Array        # int32[N] local lease deadline (send-based)
    cl_leader: jax.Array          # bool[N] believes it leads...
    cl_gen: jax.Array             # int32[N] ...this generation
    cl_writes: jax.Array          # int32[N] acked writes
    cl_max_rev: jax.Array         # int32[N] highest revision observed
    # --- bookkeeping ----------------------------------------------------
    epoch: jax.Array              # int32[N] timer epoch (persistent)
    violated: jax.Array           # bool[N] server-detected safety breach


class EtcdMachine(Machine):
    """Honest leased-KV server + campaigning clients."""

    PAYLOAD_WIDTH = 5
    MAX_MSGS = 2   # leader tick sends keepalive + write
    MAX_TIMERS = 1

    # knobs subclassed by the buggy variants in tests
    CHECK_OWNER_ON_CAMPAIGN = True   # False: double-grant bug
    REVIVE_EXPIRED_LEASES = False    # True: resurrection bug (server-side)
    EXTEND_DEADLINE_ON_WON = False   # True: client lease-discipline bug

    def __init__(self, num_nodes: int = 4, target_gens: int = 3, target_writes: int = 10):
        self.NUM_NODES = num_nodes
        self.target_gens = target_gens
        self.target_writes = target_writes

    def init(self, rng_key) -> EtcdState:
        n = self.NUM_NODES
        z = jnp.zeros((n,), jnp.int32)
        f = jnp.zeros((n,), bool)
        return EtcdState(
            srv_rev=z, srv_gen=z, srv_owner=jnp.full((n,), -1, jnp.int32),
            srv_lease_expiry=z,
            cl_has_lease=f, cl_deadline=z, cl_leader=f, cl_gen=z,
            cl_writes=z, cl_max_rev=z,
            epoch=z, violated=f,
        )

    def init_node(self, nodes: EtcdState, i, rng_key) -> EtcdState:
        """Restart semantics: the server's store is durable (etcd persists
        revisions, leases and the election through restart —
        service.rs state lives behind raft); a client loses its session
        state. Epochs always survive (timer-chain bookkeeping)."""
        return self.restart_if(nodes, i, jnp.bool_(True), rng_key)

    def durable_spec(self) -> EtcdState:
        """Crash-with-amnesia contract: the server store (revision /
        generation / election / leases) is raft-backed and durable,
        client session state is volatile; epochs (timer bookkeeping)
        and the ghost violation flag survive."""
        return EtcdState(
            srv_rev=True, srv_gen=True, srv_owner=True,
            srv_lease_expiry=True,
            cl_has_lease=False, cl_deadline=False, cl_leader=False,
            cl_gen=False, cl_writes=False, cl_max_rev=False,
            epoch=True, violated=True,
        )

    def restart_if(self, nodes: EtcdState, i, cond, rng_key) -> EtcdState:
        n = self.NUM_NODES
        row = (jnp.arange(n) == i) & cond
        is_client = i != SERVER
        reset_i32 = lambda arr: jnp.where(row & is_client, 0, arr)  # noqa: E731
        reset_b = lambda arr: jnp.where(row & is_client, False, arr)  # noqa: E731
        return nodes.replace(
            cl_has_lease=reset_b(nodes.cl_has_lease),
            cl_deadline=reset_i32(nodes.cl_deadline),
            cl_leader=reset_b(nodes.cl_leader),
            cl_gen=reset_i32(nodes.cl_gen),
            cl_writes=reset_i32(nodes.cl_writes),
            cl_max_rev=reset_i32(nodes.cl_max_rev),
        )

    # -- helpers --------------------------------------------------------------

    def _tid(self, nodes: EtcdState, node, base):
        return jnp.int32(base) + 4 * nodes.epoch[node]

    def _lazy_expire(self, nodes: EtcdState, cond, now_us):
        """Depose the current leader if its lease lapsed (the tick task of
        service.rs:25-35 done lazily on server events — same observable
        behavior, no periodic server timer needed). `cond` gates the
        whole update (only server events expire)."""
        owner = nodes.srv_owner[SERVER]
        has_owner = owner >= 0
        safe_owner = jnp.maximum(owner, 0)
        lapsed = cond & has_owner & (nodes.srv_lease_expiry[safe_owner] <= now_us)
        return update_node(
            nodes, SERVER,
            srv_owner=jnp.where(lapsed, -1, owner),
            # key deletion is a new revision (MVCC: deletes are writes)
            srv_rev=nodes.srv_rev[SERVER] + jnp.where(lapsed, 1, 0),
        )

    # -- timers ---------------------------------------------------------------

    def on_timer(self, nodes: EtcdState, node, timer_id, now_us, rand_u32) -> Tuple[EtcdState, Outbox]:
        outbox = self.empty_outbox()
        is_boot = timer_id == T_BOOT
        t_epoch = timer_id // 4
        live = is_boot | (t_epoch == nodes.epoch[node])
        is_client = node != SERVER

        # BOOT: bump epoch, clients arm their tick chain
        new_epoch = jnp.where(is_boot & live, nodes.epoch[node] + 1, nodes.epoch[node])
        nodes = update_node(nodes, node, epoch=new_epoch)
        base = timer_id - 4 * t_epoch
        is_tick = live & ~is_boot & (base == T_TICK) & is_client

        # jittered tick keeps client phases decorrelated across a lane
        jitter = (rand_u32[0] % jnp.uint32(TICK_US // 2)).astype(jnp.int32)
        outbox = set_timer_if(
            outbox, 0, (is_boot | is_tick) & is_client,
            TICK_US + jitter, self._tid(nodes, node, T_TICK),
        )

        # local lease-safety discipline: stop believing past the deadline
        still_believes = nodes.cl_leader[node] & (now_us < nodes.cl_deadline[node])
        nodes = update_node(nodes, node, cl_leader=still_believes)

        # one request per tick (at-least-once; server ops are idempotent):
        #   no lease -> GRANT;  lease, not leader -> CAMPAIGN;
        #   leader   -> KA (+ a revisioned PUT in slot 1)
        want_grant = is_tick & ~nodes.cl_has_lease[node]
        want_campaign = is_tick & nodes.cl_has_lease[node] & ~still_believes
        want_ka = is_tick & still_believes

        pay = lambda m, *rest: make_payload(self.PAYLOAD_WIDTH, m, node, now_us, *rest)  # noqa: E731
        outbox = send_if(outbox, 0, want_grant, SERVER, pay(M_GRANT))
        outbox = send_if(outbox, 0, want_campaign, SERVER, pay(M_CAMPAIGN))
        outbox = send_if(outbox, 0, want_ka, SERVER, pay(M_KA))
        outbox = send_if(outbox, 1, want_ka, SERVER, pay(M_PUT, nodes.cl_gen[node]))
        return nodes, outbox

    # -- messages -------------------------------------------------------------

    def on_message(self, nodes: EtcdState, node, src, payload, now_us, rand_u32) -> Tuple[EtcdState, Outbox]:
        outbox = self.empty_outbox()
        mtype, client, send_us = payload[0], payload[1], payload[2]
        is_server = node == SERVER

        # ---------------- server ----------------
        srv = is_server
        nodes = self._lazy_expire(nodes, srv, now_us)

        c = jnp.clip(client, 0, self.NUM_NODES - 1)
        lease_live = nodes.srv_lease_expiry[c] > now_us

        # GRANT: (re)issue the client's lease, receipt-based expiry
        is_grant = srv & (mtype == M_GRANT)
        nodes = nodes.replace(
            srv_lease_expiry=jnp.where(
                (jnp.arange(self.NUM_NODES) == c) & is_grant,
                now_us + TTL_US,
                nodes.srv_lease_expiry,
            )
        )
        outbox = send_if(
            outbox, 0, is_grant, c,
            make_payload(self.PAYLOAD_WIDTH, M_GRANT_OK, c, send_us),
        )

        # CAMPAIGN: win iff no live owner (honest) and caller's lease lives
        is_camp = srv & (mtype == M_CAMPAIGN)
        owner = nodes.srv_owner[SERVER]
        already_owner = owner == c
        seat_free = owner < 0 if self.CHECK_OWNER_ON_CAMPAIGN else jnp.bool_(True)
        win_new = is_camp & lease_live & seat_free & ~already_owner
        # double-grant detection lives at the SERVER too: stealing a seat
        # whose owner still holds a live lease is the safety breach itself
        stolen = win_new & (owner >= 0)
        new_gen = nodes.srv_gen[SERVER] + jnp.where(win_new, 1, 0)
        nodes = update_node(
            nodes, SERVER,
            srv_gen=new_gen,
            srv_owner=jnp.where(win_new, c, owner),
            srv_rev=nodes.srv_rev[SERVER] + jnp.where(win_new, 1, 0),  # key create
            violated=nodes.violated[SERVER] | stolen,
        )
        won = is_camp & lease_live & (already_owner | win_new)
        outbox = send_if(
            outbox, 0, won, c,
            make_payload(self.PAYLOAD_WIDTH, M_WON, c, send_us, nodes.srv_gen[SERVER]),
        )
        outbox = send_if(
            outbox, 0, is_camp & lease_live & ~won, c,
            make_payload(self.PAYLOAD_WIDTH, M_LOST, c, send_us),
        )
        outbox = send_if(
            outbox, 0, is_camp & ~lease_live, c,
            make_payload(self.PAYLOAD_WIDTH, M_NO_LEASE, c, send_us),
        )

        # KEEPALIVE: extend live leases; expired ones answer KA_ERR
        # (REVIVE_EXPIRED_LEASES models the resurrection bug)
        is_ka = srv & (mtype == M_KA)
        may_extend = lease_live | jnp.bool_(self.REVIVE_EXPIRED_LEASES)
        nodes = nodes.replace(
            srv_lease_expiry=jnp.where(
                (jnp.arange(self.NUM_NODES) == c) & is_ka & may_extend,
                now_us + TTL_US,
                nodes.srv_lease_expiry,
            )
        )
        outbox = send_if(
            outbox, 0, is_ka & may_extend, c,
            make_payload(self.PAYLOAD_WIDTH, M_KA_OK, c, send_us),
        )
        outbox = send_if(
            outbox, 0, is_ka & ~may_extend, c,
            make_payload(self.PAYLOAD_WIDTH, M_KA_ERR, c, send_us),
        )

        # PUT: a revisioned write, accepted only from the current leader
        # at the current generation
        is_put = srv & (mtype == M_PUT)
        put_gen = payload[3]
        accept = is_put & (nodes.srv_owner[SERVER] == c) & (put_gen == nodes.srv_gen[SERVER])
        put_rev = nodes.srv_rev[SERVER] + jnp.where(accept, 1, 0)
        nodes = update_node(nodes, SERVER, srv_rev=put_rev)
        outbox = send_if(
            outbox, 0, accept, c,
            make_payload(self.PAYLOAD_WIDTH, M_PUT_OK, c, send_us, put_rev),
        )

        # ---------------- client ----------------
        cl = node != SERVER
        # lease liveness discipline first (see on_timer)
        believes = nodes.cl_leader[node] & (now_us < nodes.cl_deadline[node])

        got_grant = cl & (mtype == M_GRANT_OK)
        got_won = cl & (mtype == M_WON)
        got_ka_ok = cl & (mtype == M_KA_OK)
        got_ka_err = cl & (mtype == M_KA_ERR)
        got_no_lease = cl & (mtype == M_NO_LEASE)
        got_put_ok = cl & (mtype == M_PUT_OK)

        # send-based local deadline: the ack proves the server extended the
        # lease no earlier than send_us, so send_us + TTL is a safe lower
        # bound. ONLY lease operations (grant/keepalive) extend it — an
        # M_WON must not: campaigning doesn't refresh the lease server-side,
        # so extending on it lets belief outlive the server's expiry (a real
        # window this machine's own invariant caught during development —
        # kept as the EXTEND_DEADLINE_ON_WON bug variant).
        extend = got_grant | got_ka_ok | (
            got_won if self.EXTEND_DEADLINE_ON_WON else jnp.bool_(False)
        )
        new_deadline = jnp.maximum(nodes.cl_deadline[node], send_us + TTL_US)
        nodes = update_node(
            nodes, node,
            cl_has_lease=jnp.where(
                got_grant, True,
                jnp.where(got_ka_err | got_no_lease, False, nodes.cl_has_lease[node]),
            ),
            cl_deadline=jnp.where(extend, new_deadline, nodes.cl_deadline[node]),
            cl_leader=jnp.where(
                got_won, True,
                jnp.where(got_ka_err, False, believes),
            ),
            cl_gen=jnp.where(got_won, payload[3], nodes.cl_gen[node]),
            cl_writes=nodes.cl_writes[node] + jnp.where(got_put_ok, 1, 0),
            cl_max_rev=jnp.where(
                got_put_ok, jnp.maximum(nodes.cl_max_rev[node], payload[3]), nodes.cl_max_rev[node]
            ),
        )
        return nodes, outbox

    # -- invariants / termination ---------------------------------------------

    def invariant(self, nodes: EtcdState, now_us):
        """Lease safety: every believed leadership is the server's current
        one, and the server never observed a double grant."""
        idx = jnp.arange(self.NUM_NODES)
        believes = nodes.cl_leader & (now_us < nodes.cl_deadline) & (idx != SERVER)
        owner_ok = believes & (nodes.srv_owner[SERVER] == idx) & (nodes.srv_gen[SERVER] == nodes.cl_gen)
        bad = jnp.any(believes & ~owner_ok) | nodes.violated[SERVER]
        return ~bad, jnp.where(bad, LEASE_SAFETY, 0).astype(jnp.int32)

    def is_done(self, nodes: EtcdState, now_us):
        return (nodes.srv_gen[SERVER] >= self.target_gens) & (
            jnp.sum(nodes.cl_writes) >= self.target_writes
        )

    def summary(self, nodes: EtcdState):
        return {
            "generations": nodes.srv_gen[SERVER],
            "revision": nodes.srv_rev[SERVER],
            "writes_acked": jnp.sum(nodes.cl_writes),
        }

    def coverage_projection(self, nodes: EtcdState, now_us):
        """Scenario projection: election generation bucket (phase) x
        ownership/lease occupancy x believed-leader count x write
        progress — the lease-safety interleaving axes (handovers seen,
        split brain pressure, workload depth)."""
        gen_b = jnp.clip(nodes.srv_gen[SERVER], 0, 7)
        owner_set = (nodes.srv_owner[SERVER] >= 0).astype(jnp.int32)
        believers = jnp.clip(jnp.sum(nodes.cl_leader.astype(jnp.int32)), 0, 3)
        leases = jnp.clip(jnp.sum(nodes.cl_has_lease.astype(jnp.int32)), 0, 3)
        writes_b = jnp.clip(jnp.max(nodes.cl_writes), 0, 7)
        return (
            gen_b
            | (owner_set << 3)
            | (believers << 4)
            | (leases << 6)
            | (writes_b << 8)
        ).astype(jnp.uint32)


class DoubleGrantEtcd(EtcdMachine):
    """Bug variant (`demo-doublegrant-etcd`): a non-atomic election txn."""

    CHECK_OWNER_ON_CAMPAIGN = False
