"""MVCC etcd machine — the revision/txn/lease semantics of the L5 etcd
service (`services/etcd/service.py`, reference:
madsim-etcd-client/src/service.rs:191+) lifted into a TPU-engine
`Machine`, so the 10^3-seeds/s chip can hunt bugs in the *MVCC* logic,
not just leased-KV leader election (`models/etcd.py`).

Topology: node 0 is the MVCC server (fixed-capacity key table, revision
counter, lease slots); nodes 1..N-1 are clients, each running a
seed-derived program of ops — put / delete / txn-on-a-key-pair /
lease-grant / leased-put / keepalive — with at-least-once retry and a
monotone per-client request sequence the server dedups on (exactly-once
application, like etcd's revision-fenced retries).

MVCC semantics mirrored from `services/etcd/service.py`:
  * every applied write bumps `revision` by one (txn = one bump per
    write op, the sequential-`put` semantics of service.py `txn`)
  * `create_revision` sticks from the creating put; a put after delete
    re-creates (service.py put: `old.create_revision if old else rev`)
  * plain put detaches any lease; leased put attaches the client's slot
  * lease expiry sweeps lazily on server events (the observable
    behavior of service.rs:25-35's 1 s tick — any client-visible read
    is itself a server event, so laziness is invisible); expiry deletes
    attached keys, one revision bump per key (service.py lease_revoke
    calls delete(key) per key)

Invariants (fail codes):
  * REV_SKEW       — revision != 1 + applied mutations (monotonicity +
                     exactly-one-bump-per-write accounting)
  * TXN_ATOMICITY  — the txn key pair diverged: a txn applied half its
                     write set (both branches write BOTH pair keys)
  * LEASE_EARLY    — ghost-variable check: the sweep expired a lease
                     before its true (refresh-based) expiry time
  * DUP_APPLY      — server applied more puts to a client's key than
                     the client ever issued (retry applied twice)
  * MVCC_ORDER     — a live key's create_revision/mod_revision ordering
                     or mod_revision <= revision broke

Seeded bug variants (class flags, each a real etcd-class defect):
  * NO_DEDUP          — the server applies retransmits instead of
                        re-acking them: a retried put double-applies.
                        Needs an ack to vanish while its request
                        arrived, so it hides from the legacy fault
                        vocabulary at loss_rate=0 and surfaces under
                        loss storms / directional clogs (FaultPlan v2).
  * KEEPALIVE_NO_EXTEND — keepalive refreshes the bookkeeping TTL but
                        not the expiry the sweep consults (classic
                        lease bug); caught by LEASE_EARLY's ghost
                        `real_expire` the moment the sweep fires early.
  * PREMATURE_GIVEUP  — deadline-RPC client against a token-dedup
                        server: each op is sent ONCE with a 300 ms
                        deadline; on timeout the client reports FAILURE
                        to the application and moves on (timeout
                        mishandling), and the server dedups by
                        idempotency token (per-seq bitmap — exactly-once
                        per token, so a late DISTINCT token still
                        applies). The safety breach is an abandoned op
                        applying AFTER its failure was reported — a
                        write the application compensated for becomes
                        visible. The in-flight request must OUTLIVE the
                        give-up moment: loss destroys it, clogs/kills
                        block it at the link, so the class is reachable
                        ONLY by the K_DELAY spike (late but delivered) —
                        the delay vocabulary's exclusive find.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..engine.machine import (
    Machine,
    Outbox,
    get_at,
    make_payload,
    send_if,
    set_timer_if,
    update_node,
)
from ..utils import set2d

SERVER = 0

# message types
M_REQ = 1
M_ACK = 2

# op kinds (client programs draw uniformly)
OP_PUT = 0
OP_DEL = 1
OP_TXN = 2
OP_GRANT = 3
OP_PUT_LEASED = 4
OP_KA = 5
N_OPS = 6

# fail codes
REV_SKEW = 201
TXN_ATOMICITY = 202
LEASE_EARLY = 203
DUP_APPLY = 204
MVCC_ORDER = 205
ABANDONED_WRITE = 206  # an op the client abandoned (reported failed) applied

RETRY_US = 100_000  # client retry/op-issue tick
GIVEUP_US = 300_000  # PREMATURE_GIVEUP variant: report failure after this
TTL_MIN_US = 300_000  # granted lease TTLs
TTL_SPAN_US = 500_000

# ack statuses
ST_OK = 0
ST_ERR = 1  # lease not found etc.


@struct.dataclass
class MvccState:
    # --- server row 0 (durable: etcd's store is raft-backed) -----------
    rev: jax.Array            # int32[N] MVCC revision (init 1)
    applied: jax.Array        # int32[N] mutations applied (ghost counter)
    val: jax.Array            # int32[N, K]
    ver: jax.Array            # int32[N, K] version; 0 = absent
    mod_rev: jax.Array        # int32[N, K]
    create_rev: jax.Array     # int32[N, K]
    key_lease: jax.Array      # int32[N, K] lease slot + 1; 0 = none
    puts_applied: jax.Array   # int32[N, K] ghost: puts ever applied per key
    lease_used: jax.Array     # int32[N, L] expiry the sweep consults; -1 = invalid
    lease_real: jax.Array     # int32[N, L] ghost: true refresh-based expiry
    lease_ttl: jax.Array      # int32[N, L] granted TTL us
    last_req: jax.Array       # int32[N, L] dedup: highest applied seq per client
    early_expiry: jax.Array   # bool[N] ghost flag: sweep fired before real expiry
    # --- client rows 1.. (durable journal: restart resumes the program)
    seq: jax.Array            # int32[N] current op seq (0 = none issued)
    acked: jax.Array          # int32[N] highest acked seq
    opk: jax.Array            # int32[N] current op kind
    oparg: jax.Array          # int32[N] current op arg (ttl for grant)
    issued_at: jax.Array      # int32[N] when the in-flight op was issued
    abandoned_seq: jax.Array  # int32[N] ghost: highest seq reported FAILED
    dirty_abandoned: jax.Array  # bool[N] ghost flag (server row): an
    #                             abandoned op applied post-abandonment
    applied_bits: jax.Array   # int32[N, 4] server token-dedup bitmap
    #                           (PREMATURE_GIVEUP's exactly-once-per-
    #                           token server; 128 seqs per client)
    puts_sent: jax.Array      # int32[N, K] ghost: unique put ops issued per key
    # --- bookkeeping ---------------------------------------------------
    epoch: jax.Array          # int32[N] timer epoch (invalidates stale timers)


class EtcdMvccMachine(Machine):
    """1 MVCC server + (N-1) clients; K = (N-1) client keys + a txn pair."""

    PAYLOAD_WIDTH = 5
    MAX_MSGS = 1
    MAX_TIMERS = 1

    # seeded bug variants (see module docstring)
    NO_DEDUP = False
    KEEPALIVE_NO_EXTEND = False
    PREMATURE_GIVEUP = False

    def __init__(self, num_nodes: int = 4, target_ops: int = 6):
        self.NUM_NODES = num_nodes
        self.n_clients = num_nodes - 1
        self.K = self.n_clients + 2  # per-client keys + txn pair
        self.L = self.n_clients
        self.target_ops = target_ops

    # -- state ----------------------------------------------------------------

    def init(self, rng_key) -> MvccState:
        n, k, l = self.NUM_NODES, self.K, self.L
        zn = jnp.zeros((n,), jnp.int32)
        zk = jnp.zeros((n, k), jnp.int32)
        zl = jnp.zeros((n, l), jnp.int32)
        return MvccState(
            rev=zn + 1,
            applied=zn,
            val=zk, ver=zk, mod_rev=zk, create_rev=zk, key_lease=zk,
            puts_applied=zk,
            lease_used=zl - 1, lease_real=zl - 1, lease_ttl=zl,
            last_req=zl,
            early_expiry=jnp.zeros((n,), bool),
            seq=zn, acked=zn, opk=zn, oparg=zn,
            issued_at=zn, abandoned_seq=zn,
            dirty_abandoned=jnp.zeros((n,), bool),
            applied_bits=jnp.zeros((n, 4), jnp.int32),
            puts_sent=zk,
            epoch=zn,
        )

    def restart_if(self, nodes: MvccState, i, cond, rng_key) -> MvccState:
        # Everything is durable: the server store is raft-backed (like
        # service.rs behind the sim fabric) and clients resume their
        # journaled program position. Restart only re-fires BOOT, which
        # bumps the epoch and re-arms the retry chain.
        return nodes

    # -- timers (clients only) -------------------------------------------------

    def _tid(self, nodes: MvccState, node):
        return jnp.int32(1) + 2 * get_at(nodes.epoch, node)

    def on_timer(self, nodes: MvccState, node, timer_id, now_us, rand_u32) -> Tuple[MvccState, Outbox]:
        outbox = self.empty_outbox()
        is_boot = timer_id == 0
        t_epoch = (timer_id - 1) // 2
        epoch = get_at(nodes.epoch, node)
        live = is_boot | (t_epoch == epoch)
        is_client = node != SERVER

        new_epoch = jnp.where(is_boot & live, epoch + 1, epoch)
        nodes = update_node(nodes, node, epoch=new_epoch)

        done_c = get_at(nodes.acked, node) >= self.target_ops
        act = live & is_client & ~done_c

        # PREMATURE_GIVEUP variant (timeout mishandling): after GIVEUP_US
        # without an ack the client reports the op FAILED and moves on.
        # The ghost records the abandoned seq; the server flags any
        # post-abandonment apply of it (ABANDONED_WRITE).
        give_up = (
            jnp.bool_(self.PREMATURE_GIVEUP)
            & act
            & (get_at(nodes.seq, node) > get_at(nodes.acked, node))
            & (now_us - get_at(nodes.issued_at, node) >= GIVEUP_US)
        )
        nodes = update_node(
            nodes, node,
            abandoned_seq=jnp.where(
                give_up, get_at(nodes.seq, node), get_at(nodes.abandoned_seq, node)
            ),
        )

        # issue the next op once the current one is acked (or abandoned)
        need_new = act & ((get_at(nodes.acked, node) == get_at(nodes.seq, node)) | give_up)
        new_seq = get_at(nodes.seq, node) + 1
        kind = (rand_u32[0] % jnp.uint32(N_OPS)).astype(jnp.int32)
        ttl = jnp.int32(TTL_MIN_US) + (rand_u32[1] % jnp.uint32(TTL_SPAN_US)).astype(jnp.int32)
        seq_p = jnp.where(need_new, new_seq, get_at(nodes.seq, node))
        opk_p = jnp.where(need_new, kind, get_at(nodes.opk, node))
        arg_p = jnp.where(need_new, ttl, get_at(nodes.oparg, node))
        own_key = node - 1
        is_put_kind = (opk_p == OP_PUT) | (opk_p == OP_PUT_LEASED)
        puts_sent = jnp.where(
            need_new & is_put_kind,
            set2d(nodes.puts_sent, node, own_key, get_at(nodes.puts_sent, (node, own_key)) + 1),
            nodes.puts_sent,
        )
        nodes = nodes.replace(puts_sent=puts_sent)
        nodes = update_node(
            nodes, node, seq=seq_p, opk=opk_p, oparg=arg_p,
            issued_at=jnp.where(need_new, now_us, get_at(nodes.issued_at, node)),
        )

        # (re)send the in-flight op; re-arm the retry chain. The
        # PREMATURE_GIVEUP variant is a deadline-RPC client: each op is
        # sent exactly once at issue (no retransmits — the deadline,
        # not the retry loop, handles "failure").
        send = act & (seq_p > get_at(nodes.acked, node))
        if self.PREMATURE_GIVEUP:
            send = send & need_new
        outbox = send_if(
            outbox, 0, send, SERVER,
            make_payload(self.PAYLOAD_WIDTH, M_REQ, seq_p, opk_p, arg_p),
        )
        jitter = (rand_u32[2] % jnp.uint32(RETRY_US // 4)).astype(jnp.int32)
        delay = jnp.where(is_boot, jitter, jnp.int32(RETRY_US) + jitter)
        outbox = set_timer_if(
            outbox, 0, live & is_client & ~done_c, delay, self._tid(nodes, node)
        )
        return nodes, outbox

    # -- server ----------------------------------------------------------------

    def _sweep(self, nodes: MvccState, now_us) -> MvccState:
        """Lazy lease-expiry sweep (server row): invalidate expired
        leases and tombstone their attached keys, one revision bump per
        deleted key. Ghost check: firing before `lease_real` is the
        LEASE_EARLY bug."""
        used = nodes.lease_used[SERVER]
        expired = (used >= 0) & (used < now_us)
        early = expired & (nodes.lease_real[SERVER] > now_us)

        lease_of_key = nodes.key_lease[SERVER]  # [K], slot+1
        safe_slot = jnp.clip(lease_of_key - 1, 0, self.L - 1)
        kill = (nodes.ver[SERVER] > 0) & (lease_of_key > 0) & get_at(expired, safe_slot)
        n_del = jnp.sum(kill.astype(jnp.int32))
        new_rev = nodes.rev[SERVER] + n_del

        srow = jnp.arange(self.NUM_NODES) == SERVER
        krow = srow[:, None] & kill[None, :]
        lrow = srow[:, None] & expired[None, :]
        return nodes.replace(
            rev=jnp.where(srow, new_rev, nodes.rev),
            applied=jnp.where(srow, nodes.applied[SERVER] + n_del, nodes.applied),
            ver=jnp.where(krow, 0, nodes.ver),
            val=jnp.where(krow, 0, nodes.val),
            key_lease=jnp.where(krow, 0, nodes.key_lease),
            mod_rev=jnp.where(krow, new_rev, nodes.mod_rev),
            lease_used=jnp.where(lrow, -1, nodes.lease_used),
            lease_real=jnp.where(lrow, -1, nodes.lease_real),
            early_expiry=nodes.early_expiry | (srow & jnp.any(early)),
        )

    def _apply(self, nodes: MvccState, c, seq, kind, arg, now_us) -> Tuple[MvccState, jax.Array]:
        """Apply one deduped client op to the server row. Returns
        (state, status)."""
        n, K = self.NUM_NODES, self.K
        srow = jnp.arange(n) == SERVER
        ks = jnp.arange(K)
        own = ks == (c - 1)
        p0 = ks == (K - 2)
        p1 = ks == (K - 1)
        slot = c - 1  # the client's lease slot
        lease_ok = get_at(nodes.lease_used[SERVER], slot) >= 0

        rev0 = nodes.rev[SERVER]
        ver = nodes.ver[SERVER]
        live = ver > 0

        # which keys does this op write, and with what?
        is_put = kind == OP_PUT
        is_del = kind == OP_DEL
        is_txn = kind == OP_TXN
        is_pl = (kind == OP_PUT_LEASED) & lease_ok
        txn_then = (nodes.ver[SERVER, K - 2] % 2) == 0
        txn_val = jnp.where(txn_then, seq, -seq)

        put_mask = own & (is_put | is_pl)
        del_mask = own & is_del & live
        txn_mask = (p0 | p1) & is_txn

        # revision bumps: put 1, effective delete 1, txn 2 (sequential
        # puts, service.py txn); per-key mod_rev gets its own bump
        bump_at = jnp.where(
            put_mask | del_mask, 1, jnp.where(txn_mask, jnp.where(p0, 1, 2), 0)
        ).astype(jnp.int32)
        # total mutations this op applies:
        n_mut = (
            jnp.sum(put_mask.astype(jnp.int32))
            + jnp.sum(del_mask.astype(jnp.int32))
            + 2 * is_txn.astype(jnp.int32)
        )
        new_rev = rev0 + n_mut
        key_rev = rev0 + bump_at  # per-key assigned revision

        write_mask = put_mask | txn_mask
        was_absent = ~live
        new_val = jnp.where(txn_mask, txn_val, seq)

        vrow = srow[:, None]
        wm = vrow & write_mask[None, :]
        dm = vrow & del_mask[None, :]
        nodes = nodes.replace(
            val=jnp.where(wm, new_val[None, :], jnp.where(dm, 0, nodes.val)),
            ver=jnp.where(wm, (ver + 1)[None, :], jnp.where(dm, 0, nodes.ver)),
            mod_rev=jnp.where(wm | dm, key_rev[None, :], nodes.mod_rev),
            create_rev=jnp.where(
                wm & was_absent[None, :], key_rev[None, :], nodes.create_rev
            ),
            key_lease=jnp.where(
                wm, jnp.where(own & is_pl, slot + 1, 0)[None, :],
                jnp.where(dm, 0, nodes.key_lease),
            ),
            puts_applied=jnp.where(wm, nodes.puts_applied + 1, nodes.puts_applied),
            rev=jnp.where(srow, new_rev, nodes.rev),
            applied=jnp.where(srow, nodes.applied[SERVER] + n_mut, nodes.applied),
        )

        # lease ops
        is_grant = kind == OP_GRANT
        is_ka = (kind == OP_KA) & lease_ok
        ls = jnp.arange(self.L) == slot
        lrow = srow[:, None] & ls[None, :]
        expire = now_us + jnp.where(is_grant, arg, get_at(nodes.lease_ttl[SERVER], slot))
        set_used = is_grant | (is_ka & ~jnp.bool_(self.KEEPALIVE_NO_EXTEND))
        set_real = is_grant | is_ka
        nodes = nodes.replace(
            lease_used=jnp.where(lrow & set_used, expire, nodes.lease_used),
            lease_real=jnp.where(lrow & set_real, expire, nodes.lease_real),
            lease_ttl=jnp.where(lrow & is_grant, arg, nodes.lease_ttl),
        )

        err = ((kind == OP_PUT_LEASED) | (kind == OP_KA)) & ~lease_ok
        return nodes, jnp.where(err, ST_ERR, ST_OK).astype(jnp.int32)

    # -- messages --------------------------------------------------------------

    def on_message(self, nodes: MvccState, node, src, payload, now_us, rand_u32) -> Tuple[MvccState, Outbox]:
        outbox = self.empty_outbox()
        mtype, seq = payload[0], payload[1]

        # ---- server: REQ -------------------------------------------------
        is_req = (node == SERVER) & (mtype == M_REQ)
        swept = self._sweep(nodes, now_us)
        slot = jnp.clip(src - 1, 0, self.L - 1)
        if self.PREMATURE_GIVEUP:
            # token-dedup server (exactly-once per idempotency token): a
            # late DISTINCT seq still applies — which is precisely what
            # lets an abandoned op land after its failure was reported.
            # Deadline-RPC clients send each token exactly once, so a
            # seq past the 128-bit window is simply never a duplicate
            # (no clip-aliasing: out-of-window tokens apply unmarked).
            in_window = seq < 128
            word = jnp.clip(seq // 32, 0, 3)
            bit = jnp.int32(1) << jnp.clip(seq % 32, 0, 31)
            is_dup = in_window & ((get_at(swept.applied_bits, (src, word)) & bit) != 0)
        else:
            is_dup = jnp.where(
                jnp.bool_(self.NO_DEDUP), jnp.bool_(False),
                seq <= get_at(swept.last_req[SERVER], slot),
            )
        applied, status = self._apply(swept, src, seq, payload[2], payload[3], now_us)
        applied = applied.replace(
            last_req=set2d(
                applied.last_req, SERVER, slot,
                jnp.maximum(get_at(applied.last_req[SERVER], slot), seq),
            )
        )
        if self.PREMATURE_GIVEUP:
            token_row = (
                (jnp.arange(self.NUM_NODES)[:, None] == src)
                & (jnp.arange(4)[None, :] == word)
                & in_window
            )
            applied = applied.replace(
                applied_bits=jnp.where(
                    token_row, applied.applied_bits | bit, applied.applied_bits
                )
            )
        # ghost: applying an op its client already reported as FAILED is
        # the PREMATURE_GIVEUP safety breach (a compensated-for write
        # becoming visible) — only reachable by a late-but-delivered
        # request, i.e. the delay-spike fault kind
        late_abandoned = seq <= get_at(applied.abandoned_seq, src)
        applied = applied.replace(
            dirty_abandoned=jnp.where(
                (jnp.arange(self.NUM_NODES) == SERVER) & late_abandoned,
                True,
                applied.dirty_abandoned,
            ),
        )
        # select: request => swept(+applied unless dup); else untouched
        do_apply = is_req & ~is_dup
        pick = lambda ap, sw, old: jax.tree.map(  # noqa: E731
            lambda a, s, o: jnp.where(do_apply, a, jnp.where(is_req, s, o)), ap, sw, old
        )
        nodes = pick(applied, swept.replace(last_req=applied.last_req), nodes)
        outbox = send_if(
            outbox, 0, is_req, src,
            make_payload(
                self.PAYLOAD_WIDTH, M_ACK, seq,
                jnp.where(is_dup, ST_OK, status), nodes.rev[SERVER],
            ),
        )

        # ---- client: ACK -------------------------------------------------
        is_ack = (node != SERVER) & (mtype == M_ACK)
        acked = get_at(nodes.acked, node)
        nodes = update_node(
            nodes, node,
            acked=jnp.where(
                is_ack, jnp.maximum(acked, jnp.minimum(seq, get_at(nodes.seq, node))), acked
            ),
        )
        return nodes, outbox

    # -- invariants / results --------------------------------------------------

    def invariant(self, nodes: MvccState, now_us):
        K = self.K
        rev = nodes.rev[SERVER]
        rev_skew = rev != 1 + nodes.applied[SERVER]

        txn_div = (nodes.val[SERVER, K - 2] != nodes.val[SERVER, K - 1]) | (
            nodes.ver[SERVER, K - 2] != nodes.ver[SERVER, K - 1]
        )

        early = nodes.early_expiry[SERVER]

        # server never applied more puts to a client key than issued
        # (client c's own key is c - 1: the diagonal of the clients' rows,
        # read by a static mask — an index vector would be a gather)
        nc = self.n_clients
        own = jnp.eye(nc, dtype=bool)
        sent = jnp.sum(jnp.where(own, nodes.puts_sent[1:, :nc], 0), axis=1)
        appl = nodes.puts_applied[SERVER, :nc]
        dup = jnp.any(appl > sent)

        live = nodes.ver[SERVER] > 0
        order = jnp.any(
            live
            & (
                (nodes.mod_rev[SERVER] > rev)
                | (nodes.create_rev[SERVER] > nodes.mod_rev[SERVER])
                | (nodes.mod_rev[SERVER] < 1)
            )
        )

        dirty = nodes.dirty_abandoned[SERVER]

        ok = ~(rev_skew | txn_div | early | dup | order | dirty)
        code = jnp.where(
            rev_skew, REV_SKEW,
            jnp.where(txn_div, TXN_ATOMICITY,
                      jnp.where(early, LEASE_EARLY,
                                jnp.where(dup, DUP_APPLY,
                                          jnp.where(order, MVCC_ORDER,
                                                    jnp.where(dirty, ABANDONED_WRITE, 0))))),
        )
        return ok, code.astype(jnp.int32)

    def is_done(self, nodes: MvccState, now_us):
        base = jnp.all(nodes.acked[1:] >= self.target_ops)
        if self.PREMATURE_GIVEUP:
            # deadline-RPC semantics: an abandoned request can still be
            # in flight (spiked up to 5 s); hold the lane open so the
            # late arrival is observed — once the event queue drains the
            # engine completes the lane anyway (done |= ~any_valid)
            return base & (now_us >= jnp.int32(7_000_000))
        return base

    def summary(self, nodes: MvccState):
        return {
            "revision": nodes.rev[SERVER],
            "applied": nodes.applied[SERVER],
            "ops_acked": jnp.sum(nodes.acked[1:]),
        }


class NoDedupMvcc(EtcdMvccMachine):
    """Bug variant (`demo-nodedup-mvcc`): retransmits double-apply
    (needs storms / directional clogs)."""

    NO_DEDUP = True


class PrematureGiveupMvcc(EtcdMvccMachine):
    """Bug variant (`demo-giveup-mvcc`): deadline-RPC timeout
    mishandling (reachable only by the delay kind)."""

    PREMATURE_GIVEUP = True
