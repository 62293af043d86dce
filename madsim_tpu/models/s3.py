"""S3 object-store machine — the multipart + lifecycle semantics of the
L5 S3 service (`services/s3/__init__.py`, reference:
madsim-aws-sdk-s3/src/server/service.rs:27-60+) lifted into a TPU-engine
`Machine`, completing the service-differential program (etcd-mvcc and
kafka-group shipped in round 4; VERDICT r4 directive 4).

Topology: node 0 is the S3 server; nodes 1..N-1 are clients, each
working a seed-derived program against its OWN object key — put /
delete / create-multipart / upload-part / complete / abort — with
at-least-once retry and a monotone per-client request sequence the
server dedups on.

Service semantics mirrored from `services/s3/__init__.py`:
  * `complete_multipart_upload` concatenates the uploaded parts in
    PART-NUMBER order (service: `b"".join(parts[n] for n in sorted(parts))`)
    and the session disappears; object content is modeled as an int32
    fold (h = h*31 + part_val in part order) the differential recomputes
    from the real service's bytes
  * `abort_multipart_upload` discards the session AND its parts
  * lifecycle: objects expire `OBJ_AGE_US` after last_modified
    (service `apply_lifecycle`: `last_modified <= now - days*86400`);
    incomplete multipart sessions abort `MPU_AGE_US` after creation
    (`abort_multipart_days`); the sweep runs lazily on server events —
    any client-visible observation is itself a server event, so the
    laziness is invisible (same argument as the etcd-mvcc machine)

Invariants (fail codes):
  * MPU_CONCAT  — a live object's content diverged from the ghost
                  expectation (completed object == concat of the parts
                  that were uploaded, in part-number order)
  * MPU_ORPHAN  — part storage non-empty with no active session
                  (abort/complete must not leak parts)
  * LC_EARLY    — ghost-variable check: lifecycle expired an object
                  before last_modified + OBJ_AGE_US
  * LC_PARTIAL  — an absent object still carries content (expiry or
                  delete tore the object down only partially)
  * DUP_APPLY   — the server applied more content-writing ops (put /
                  complete) to a client's key than the client issued

Seeded bug variants (one per invariant class, each a real S3-class
defect):
  * CONCAT_ARRIVAL_ORDER — complete concatenates parts in upload-arrival
                  order instead of part-number order; surfaces whenever
                  a client uploads parts out of order (MPU_CONCAT)
  * ABORT_KEEPS_PARTS — abort ends the session but leaks its parts
                  (MPU_ORPHAN)
  * LC_EARLY_HALF — the lifecycle sweep expires at half the configured
                  age (LC_EARLY, via the ghost expiry)
  * LC_TOMBSTONE_LEAK — expiry clears existence but not content
                  (LC_PARTIAL)
  * NO_DEDUP    — retransmitted puts double-apply (DUP_APPLY; needs an
                  ack to vanish while its request arrived — storms /
                  directional clogs)
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
from ..utils import set2d

SERVER = 0

M_REQ = 1
M_ACK = 2

# op kinds (client programs draw uniformly)
OP_PUT = 0
OP_DEL = 1
OP_CREATE = 2
OP_PART = 3
OP_COMPLETE = 4
OP_ABORT = 5
N_OPS = 6

# fail codes
MPU_CONCAT = 211
MPU_ORPHAN = 212
LC_EARLY = 213
LC_PARTIAL = 214
DUP_APPLY = 215

RETRY_US = 100_000
OBJ_AGE_US = 2_500_000   # lifecycle object expiration
MPU_AGE_US = 1_500_000   # lifecycle incomplete-multipart abort
LC_TICK_US = 500_000     # server lifecycle ticker (SimServer.lifecycle_interval)
OBSERVE_US = 4_000_000   # lanes watch the lifecycle phase before early-done

ST_OK = 0
ST_ERR = 1


@struct.dataclass
class S3State:
    # --- server row 0 (durable object store) ---------------------------
    obj_ver: jax.Array       # int32[N, K] write counter; 0 = absent
    obj_val: jax.Array       # int32[N, K] content fold (what the server built)
    obj_expected: jax.Array  # int32[N, K] ghost: honestly-computed content
    obj_mtime: jax.Array     # int32[N, K] last_modified (us)
    mpu_active: jax.Array    # int32[N, K] 1 = session open
    mpu_created: jax.Array   # int32[N, K] session creation time (us)
    mpu_mask: jax.Array      # int32[N, K] bitmask of uploaded part numbers
    part_val: jax.Array      # int32[N, K, P] uploaded part contents
    part_arr: jax.Array      # int32[N, K, P] arrival order of each part
    mpu_arrcnt: jax.Array    # int32[N, K] arrival counter
    last_req: jax.Array      # int32[N, K] dedup: highest applied seq per client
    writes_applied: jax.Array  # int32[N, K] ghost: content writes applied
    lc_early: jax.Array      # bool[N] ghost flag: sweep fired early
    # --- client rows 1.. (durable journal) -----------------------------
    seq: jax.Array           # int32[N]
    acked: jax.Array         # int32[N]
    opk: jax.Array           # int32[N]
    oparg: jax.Array         # int32[N]
    writes_sent: jax.Array   # int32[N, K] ghost: put/complete ops issued
    epoch: jax.Array         # int32[N]


class S3Machine(Machine):
    """1 S3 server + (N-1) clients, one object key per client."""

    PAYLOAD_WIDTH = 5
    MAX_MSGS = 1
    MAX_TIMERS = 1
    P = 4  # part slots per multipart session

    # seeded bug variants (module docstring)
    CONCAT_ARRIVAL_ORDER = False
    ABORT_KEEPS_PARTS = False
    LC_EARLY_HALF = False
    LC_TOMBSTONE_LEAK = False
    NO_DEDUP = False

    def __init__(self, num_nodes: int = 4, target_ops: int = 6):
        self.NUM_NODES = num_nodes
        self.n_clients = num_nodes - 1
        self.K = self.n_clients
        self.target_ops = target_ops

    # -- state ----------------------------------------------------------------

    def init(self, rng_key) -> S3State:
        n, k, p = self.NUM_NODES, self.K, self.P
        zn = jnp.zeros((n,), jnp.int32)
        zk = jnp.zeros((n, k), jnp.int32)
        zp = jnp.zeros((n, k, p), jnp.int32)
        return S3State(
            obj_ver=zk, obj_val=zk, obj_expected=zk, obj_mtime=zk,
            mpu_active=zk, mpu_created=zk, mpu_mask=zk,
            part_val=zp, part_arr=zp, mpu_arrcnt=zk,
            last_req=zk, writes_applied=zk,
            lc_early=jnp.zeros((n,), bool),
            seq=zn, acked=zn, opk=zn, oparg=zn,
            writes_sent=zk,
            epoch=zn,
        )

    def restart_if(self, nodes: S3State, i, cond, rng_key) -> S3State:
        # Durable on both sides: the store is the service's persistent
        # state; clients journal their program position. Restart re-fires
        # BOOT, which bumps the epoch and re-arms the retry chain.
        return nodes

    # -- timers (clients only) -------------------------------------------------

    def _tid(self, nodes: S3State, node):
        return jnp.int32(1) + 2 * nodes.epoch[node]

    def on_timer(self, nodes: S3State, node, timer_id, now_us, rand_u32) -> Tuple[S3State, Outbox]:
        outbox = self.empty_outbox()
        is_boot = timer_id == 0
        t_epoch = (timer_id - 1) // 2
        live = is_boot | (t_epoch == nodes.epoch[node])
        is_client = node != SERVER
        is_server = node == SERVER

        new_epoch = jnp.where(is_boot & live, nodes.epoch[node] + 1, nodes.epoch[node])
        nodes = update_node(nodes, node, epoch=new_epoch)

        # server: the lifecycle ticker (the on-device analogue of
        # SimServer's apply_lifecycle job) — sweep and re-arm. Without
        # it, full-age expiry after clients go quiet would be
        # unobservable (the lazy request-path sweep needs traffic).
        swept = self._sweep(nodes, now_us)
        nodes = jax.tree.map(
            lambda s, o: jnp.where(live & is_server & ~is_boot, s, o), swept, nodes
        )
        outbox = set_timer_if(
            outbox, 0, live & is_server, LC_TICK_US, self._tid(nodes, node)
        )

        done_c = nodes.acked[node] >= self.target_ops
        act = live & is_client & ~done_c

        # issue the next op once the current one is acked. The kind draw
        # is weighted like a real multipart workload (a session uploads
        # several parts per create/complete): PART 3/8, others 1/8.
        need_new = act & (nodes.acked[node] == nodes.seq[node])
        new_seq = nodes.seq[node] + 1
        kind_table = jnp.asarray(
            [OP_PUT, OP_DEL, OP_CREATE, OP_PART, OP_PART, OP_PART,
             OP_COMPLETE, OP_ABORT], jnp.int32,
        )
        kind = kind_table[rand_u32[0] % jnp.uint32(8)]
        part_ix = (rand_u32[1] % jnp.uint32(self.P)).astype(jnp.int32)
        seq_p = jnp.where(need_new, new_seq, nodes.seq[node])
        opk_p = jnp.where(need_new, kind, nodes.opk[node])
        arg_p = jnp.where(need_new, part_ix, nodes.oparg[node])
        own_key = node - 1
        is_write_kind = (opk_p == OP_PUT) | (opk_p == OP_COMPLETE)
        writes_sent = jnp.where(
            need_new & is_write_kind,
            set2d(nodes.writes_sent, node, own_key,
                  nodes.writes_sent[node, own_key] + 1),
            nodes.writes_sent,
        )
        nodes = nodes.replace(writes_sent=writes_sent)
        nodes = update_node(nodes, node, seq=seq_p, opk=opk_p, oparg=arg_p)

        # (re)send the in-flight op; re-arm the retry chain
        send = act & (seq_p > nodes.acked[node])
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

    def _fold_parts(self, vals, mask_bits, order) -> jax.Array:
        """h = fold(h*31 + val) over present parts in `order` (an [P]
        permutation); absent parts are skipped without consuming a fold
        step."""
        h = jnp.int32(0)
        for r in range(self.P):
            ix = order[r]
            present = ((mask_bits >> ix) & 1) > 0
            h = jnp.where(present, h * 31 + vals[ix], h)
        return h

    def _sweep(self, nodes: S3State, now_us) -> S3State:
        """Lazy lifecycle sweep (server row): expire old objects, abort
        stale multipart sessions. Ghost check: an expiry firing before
        last_modified + OBJ_AGE_US is the LC_EARLY bug."""
        age = OBJ_AGE_US // 2 if self.LC_EARLY_HALF else OBJ_AGE_US
        ver = nodes.obj_ver[SERVER]
        mtime = nodes.obj_mtime[SERVER]
        expire = (ver > 0) & (now_us >= mtime + age)
        early = expire & (now_us < mtime + OBJ_AGE_US)

        mpu_stale = (nodes.mpu_active[SERVER] > 0) & (
            now_us >= nodes.mpu_created[SERVER] + MPU_AGE_US
        )

        srow = jnp.arange(self.NUM_NODES) == SERVER
        em = srow[:, None] & expire[None, :]
        am = srow[:, None] & mpu_stale[None, :]
        return nodes.replace(
            obj_ver=jnp.where(em, 0, nodes.obj_ver),
            obj_val=(
                nodes.obj_val
                if self.LC_TOMBSTONE_LEAK
                else jnp.where(em, 0, nodes.obj_val)
            ),
            obj_expected=jnp.where(em, 0, nodes.obj_expected),
            mpu_active=jnp.where(am, 0, nodes.mpu_active),
            mpu_mask=jnp.where(am, 0, nodes.mpu_mask),
            part_val=jnp.where(am[:, :, None], 0, nodes.part_val),
            part_arr=jnp.where(am[:, :, None], 0, nodes.part_arr),
            lc_early=nodes.lc_early | (srow & jnp.any(early)),
        )

    def _apply(self, nodes: S3State, c, seq, kind, arg, now_us) -> Tuple[S3State, jax.Array]:
        """Apply one deduped client op to the server row."""
        n, K, P = self.NUM_NODES, self.K, self.P
        srow = jnp.arange(n) == SERVER
        key = jnp.clip(c - 1, 0, K - 1)
        km = jnp.arange(K) == key
        row_key = srow[:, None] & km[None, :]

        active = nodes.mpu_active[SERVER, key] > 0
        mask_bits = nodes.mpu_mask[SERVER, key]

        is_put = kind == OP_PUT
        is_del = kind == OP_DEL
        is_create = kind == OP_CREATE
        is_part = (kind == OP_PART) & active
        is_complete = (kind == OP_COMPLETE) & active & (mask_bits != 0)
        is_abort = (kind == OP_ABORT) & active
        err = (
            ((kind == OP_PART) & ~active)
            | ((kind == OP_COMPLETE) & (~active | (mask_bits == 0)))
            | ((kind == OP_ABORT) & ~active)
        )

        # content of a completed object: part-number order (the service's
        # sorted() join). The ghost is ALWAYS the honest fold; the buggy
        # variant folds in arrival order instead.
        vals = nodes.part_val[SERVER, key]
        arrs = nodes.part_arr[SERVER, key]
        index_order = jnp.arange(P, dtype=jnp.int32)
        # absent parts sort last: arrival key pushed past any real counter
        arrival_order = jnp.argsort(
            jnp.where(((mask_bits >> index_order) & 1) > 0, arrs, jnp.int32(2**30))
        ).astype(jnp.int32)
        honest = self._fold_parts(vals, mask_bits, index_order)
        built = (
            self._fold_parts(vals, mask_bits, arrival_order)
            if self.CONCAT_ARRIVAL_ORDER
            else honest
        )

        # object writes: put stores `seq`; complete stores the fold
        writes = is_put | is_complete
        new_val = jnp.where(is_put, seq, built)
        new_expected = jnp.where(is_put, seq, honest)
        dels = is_del
        nodes = nodes.replace(
            obj_ver=jnp.where(
                row_key,
                jnp.where(writes, nodes.obj_ver[SERVER, key] + 1,
                          jnp.where(dels, 0, nodes.obj_ver[SERVER, key])),
                nodes.obj_ver,
            ),
            obj_val=jnp.where(
                row_key,
                jnp.where(writes, new_val, jnp.where(dels, 0, nodes.obj_val[SERVER, key])),
                nodes.obj_val,
            ),
            obj_expected=jnp.where(
                row_key,
                jnp.where(writes, new_expected,
                          jnp.where(dels, 0, nodes.obj_expected[SERVER, key])),
                nodes.obj_expected,
            ),
            obj_mtime=jnp.where(
                row_key & writes, now_us, nodes.obj_mtime
            ),
            writes_applied=jnp.where(
                row_key & writes, nodes.writes_applied + 1, nodes.writes_applied
            ),
        )

        # session lifecycle: create opens (replacing any session, parts
        # cleared — the service keys sessions by a fresh upload_id, so a
        # new session never sees old parts); complete/abort close.
        clears = is_create | is_complete | (is_abort & ~jnp.bool_(self.ABORT_KEEPS_PARTS))
        closes = is_complete | is_abort
        part_clear = row_key[:, :, None] & clears[None, None, None]
        nodes = nodes.replace(
            mpu_active=jnp.where(
                row_key,
                jnp.where(is_create, 1, jnp.where(closes, 0, nodes.mpu_active[SERVER, key])),
                nodes.mpu_active,
            ),
            mpu_created=jnp.where(row_key & is_create, now_us, nodes.mpu_created),
            mpu_mask=jnp.where(
                row_key & clears, 0, nodes.mpu_mask
            ),
            mpu_arrcnt=jnp.where(row_key & is_create, 0, nodes.mpu_arrcnt),
            part_val=jnp.where(part_clear, 0, nodes.part_val),
            part_arr=jnp.where(part_clear, 0, nodes.part_arr),
        )

        # part upload: store content `seq` at slot `arg`, stamp arrival
        slot = jnp.clip(arg, 0, P - 1)
        pm = row_key[:, :, None] & (jnp.arange(P) == slot)[None, None, :] & is_part
        arrcnt = nodes.mpu_arrcnt[SERVER, key]
        nodes = nodes.replace(
            part_val=jnp.where(pm, seq, nodes.part_val),
            part_arr=jnp.where(pm, arrcnt, nodes.part_arr),
            mpu_mask=jnp.where(
                row_key & is_part,
                nodes.mpu_mask[SERVER, key] | (1 << slot),
                nodes.mpu_mask,
            ),
            mpu_arrcnt=jnp.where(row_key & is_part, arrcnt + 1, nodes.mpu_arrcnt),
        )

        return nodes, jnp.where(err, ST_ERR, ST_OK).astype(jnp.int32)

    # -- messages --------------------------------------------------------------

    def on_message(self, nodes: S3State, node, src, payload, now_us, rand_u32) -> Tuple[S3State, Outbox]:
        outbox = self.empty_outbox()
        mtype, seq = payload[0], payload[1]

        # ---- server: REQ -------------------------------------------------
        is_req = (node == SERVER) & (mtype == M_REQ)
        swept = self._sweep(nodes, now_us)
        key = jnp.clip(src - 1, 0, self.K - 1)
        is_dup = jnp.where(
            jnp.bool_(self.NO_DEDUP), jnp.bool_(False),
            seq <= swept.last_req[SERVER, key],
        )
        applied, status = self._apply(swept, src, seq, payload[2], payload[3], now_us)
        applied = applied.replace(
            last_req=set2d(
                applied.last_req, SERVER, key,
                jnp.maximum(applied.last_req[SERVER, key], seq),
            )
        )
        do_apply = is_req & ~is_dup
        pick = lambda ap, sw, old: jax.tree.map(  # noqa: E731
            lambda a, s, o: jnp.where(do_apply, a, jnp.where(is_req, s, o)), ap, sw, old
        )
        nodes = pick(applied, swept.replace(last_req=applied.last_req), nodes)
        outbox = send_if(
            outbox, 0, is_req, src,
            make_payload(
                self.PAYLOAD_WIDTH, M_ACK, seq,
                jnp.where(is_dup, ST_OK, status), 0,
            ),
        )

        # ---- client: ACK -------------------------------------------------
        is_ack = (node != SERVER) & (mtype == M_ACK)
        nodes = update_node(
            nodes, node,
            acked=jnp.where(
                is_ack, jnp.maximum(nodes.acked[node], jnp.minimum(seq, nodes.seq[node])),
                nodes.acked[node],
            ),
        )
        return nodes, outbox

    # -- invariants / results --------------------------------------------------

    def invariant(self, nodes: S3State, now_us):
        ver = nodes.obj_ver[SERVER]
        concat = jnp.any((ver > 0) & (nodes.obj_val[SERVER] != nodes.obj_expected[SERVER]))
        orphan = jnp.any((nodes.mpu_active[SERVER] == 0) & (nodes.mpu_mask[SERVER] != 0))
        early = nodes.lc_early[SERVER]
        partial = jnp.any((ver == 0) & (nodes.obj_val[SERVER] != 0))

        client_keys = jnp.arange(self.n_clients)
        sent = nodes.writes_sent[client_keys + 1, client_keys]
        appl = nodes.writes_applied[SERVER, client_keys]
        dup = jnp.any(appl > sent)

        ok = ~(concat | orphan | early | partial | dup)
        code = jnp.where(
            concat, MPU_CONCAT,
            jnp.where(orphan, MPU_ORPHAN,
                      jnp.where(early, LC_EARLY,
                                jnp.where(partial, LC_PARTIAL,
                                          jnp.where(dup, DUP_APPLY, 0)))),
        )
        return ok, code.astype(jnp.int32)

    def is_done(self, nodes: S3State, now_us):
        # hold the lane through the lifecycle-observation window: expiry
        # and multipart-abort behavior AFTER the clients go quiet is
        # exactly what the lifecycle invariants watch
        return jnp.all(nodes.acked[1:] >= self.target_ops) & (now_us >= OBSERVE_US)

    def summary(self, nodes: S3State):
        return {
            "objects_live": jnp.sum((nodes.obj_ver[SERVER] > 0).astype(jnp.int32)),
            "sessions_open": jnp.sum(nodes.mpu_active[SERVER]),
            "writes_applied": jnp.sum(nodes.writes_applied[SERVER]),
            "ops_acked": jnp.sum(nodes.acked[1:]),
        }


class ArrivalOrderS3(S3Machine):
    """Bug variant (`demo-arrivalorder-s3`): complete concatenates parts
    in upload order."""

    CONCAT_ARRIVAL_ORDER = True


class AbortLeakS3(S3Machine):
    """Bug variant (`demo-abortleak-s3`): abort leaks the session's
    parts."""

    ABORT_KEEPS_PARTS = True


class EarlyExpiryS3(S3Machine):
    """Bug variant (`demo-earlyexpiry-s3`): lifecycle expires at half
    the configured age."""

    LC_EARLY_HALF = True


class TombstoneLeakS3(S3Machine):
    """Bug variant (`demo-tombstoneleak-s3`): expiry clears existence
    but not content."""

    LC_TOMBSTONE_LEAK = True


class NoDedupS3(S3Machine):
    """Bug variant (`demo-nodedup-s3`): retried puts double-apply."""

    NO_DEDUP = True
