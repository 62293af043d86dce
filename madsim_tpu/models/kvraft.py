"""Key/value service on Raft — MadRaft's lab 3 (MIT 6.824 lab 3A) as one
batched engine Machine: a service layered on `models/raft.py`'s log, and
its clerks as nodes of the same lane.

Topology, two roles in one lane (`num_nodes` = 10): nodes `0 .. S-1` are
the KV servers, each a Raft peer; nodes `S .. S+C-1` are the clerks (the
tester's `makeClient(cfg.All())`: a clerk reaches every server). Clerk c
owns key c — one writer a key, as the source's 3A tests have it.

Raft layer. `models/raft.py`'s handlers, not a copy: a `RaftMachine` over
the S peers with `LOG_COMMANDS` (an entry is a term and a command word,
carried by AppendEntries) and without its leader-side client timer. Its
state is one sub-tree, `KvRaftState.raft`, `[S, ...]`. Replication keeps
the model's pace: one entry an AppendEntries, sent on the 50 ms
heartbeat, so a lane commits about 20 entries a virtual second where a
lab solution sends at once.

Service layer. A command is `(op, clerk, seq, j)` packed in one word
(`pack_cmd`). A server that receives a clerk's request while it believes
itself leader appends it (`RaftMachine.propose_if`) unless that very
request is already in its log under its current term (`pend_*`) or
already applied (the session table answers). `Get` goes through the log
like a write (lab 3's rule; `LocalGetKvRaft` breaks it). Apply: in every
message event of a server, and on its T_APPLY timer, at most
`APPLY_PER_EVENT` = 2 entries between `last_applied` and `commit` are
applied; while a backlog is left the T_APPLY timer (1 us) stays armed —
the replay of the whole log after a restart runs on it. The session
table `last_seq[server, clerk]` with the last reply makes a retried
request apply once: a duplicate is refused and answered from the table.
A key's value is (length, rolling hash of the appended j's): the
source's string `"x c j y"...` at fixed shape. The server that holds a
request (`pend_seq`) answers its clerk when the entry is applied.

Durable (Raft's stable storage): term, vote, log — terms and commands.
Volatile, rebuilt by replaying the log after a restart: `last_applied`,
the values, the session table, the pending-request table (the source
runs `maxraftstate -1`: no snapshot).

Clerks. One operation in flight; `Append(key, j)` or `Get(key)` by a coin
from the handler's `rand_u32` (500 / 1000); sent to the remembered
leader; on "wrong leader" from that server the next server is tried at
once, and after a fruitless round of all S the clerk pauses
`CYCLE_PAUSE_US`; on a timeout (`CLERK_TIMEOUT_US` after the last send
without an answer: a packet lost, or an entry slow to commit) the
request goes to the same server again, and after `STRIKES_TO_MOVE`
timeouts in a row to the next; the same `(clerk, seq)` until acknowledged. No new
operation starts at or after `load_until_us` (the machine's, not read
off the horizon: `KafkaMachine.produce_until_us`'s reason); the first
acknowledgement at or after it is followed by one closing `Get`. The
tester never crashes a clerk: what a clerk holds survives a kill (a
scheduled `--faults` kill only stops its timers for a while).

Role-held state (`lane_spec`): the servers' leaves are `[S, ...]`, the
clerks' `[C, ...]` (`RoleRows`: one row a node of the role), the ghosts
and totals are held once a lane. No leaf has the lane's node axis. The
service's tables and the clerks' records are FLAT (`RoleRows.width`):
`svc` is `[S * C * 8]`, the eight words of cell (server, clerk) end to
end, and `clk` `[C * 16]`, a clerk's record — a lane's batch of `[5, 5]`
tables is tiled to 8 x 128 words a lane on a TPU and a step's cost
follows the padded bytes (a first form with seven `[5, 5]` tables and
thirteen `[5]` leaves ran 16.3 ms a step of 8192 lanes, `PERF.md` §6);
a handler reads a cell's or a record's words one-hot (`get_at`) and
writes the cell or the record with ONE masked pass. `KvRaftState`'s properties give the tables by name.

Invariants, checked after every event:
  * Raft's ElectionSafety (101) and LogMatching (102), 102 over commands
    as well as terms.
  * APPEND_ORDER (171): every server's applied sequence for a key is
    j = 0, 1, 2, ... once each, in order (the source's `checkClntAppends`)
    — checked at the apply against the key's length.
  * STALE_GET (172): an acknowledged `Get` returns exactly what that
    clerk has had acknowledged (the source's "get wrong value"). With
    one writer a key and one operation in flight this IS linearizability
    of the key.
  * APPLY_DIVERGED (173): two servers that applied index i applied the
    same command (a ghost of the first command applied at each index).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..engine.machine import (
    Machine, Outbox, RoleRows, get_at, make_payload, send_if, set_at, set_timer_if,
)
from .raft import LEADER, M_AER, RaftCmdState, RaftMachine

# messages (1-4 are Raft's)
M_REQ, M_REPLY = 5, 6
ST_OK, ST_WRONG_LEADER = 1, 0
OP_GET, OP_APPEND = 0, 1

# timer bases (`tid = base + 4 * epoch`, Raft's scheme): a server's are
# Raft's, base 3 — its client timer, off here — is the apply re-arm; a
# clerk has two
T_APPLY = 3
T_RETRY, T_PAUSE = 1, 2

APPEND_ORDER = 171
STALE_GET = 172
APPLY_DIVERGED = 173

APPLY_PER_EVENT = 2
APPLY_DELAY_US = 1
CLERK_TIMEOUT_US = 300_000
STRIKES_TO_MOVE = 2
CYCLE_PAUSE_US = 100_000
APPEND_PERMILLE = 500

COUNTERS = (
    "ops_acked", "appends_applied", "gets_acked", "dup_refused",
    "wrong_leader", "clerk_timeouts", "closing_gets_acked", "log_full",
)
_C = {name: i for i, name in enumerate(COUNTERS)}

# clerk phases
LOAD, CLOSING, FINISHED = 0, 1, 2

_HASH_MUL = 1_000_003
_HASH_MASK = 0x7FFFFFFF


def pack_cmd(op, clerk, seq, j):
    """One log word: op in bit 0, clerk in bits 1-4, seq in bits 5-17,
    j (the append's ordinal for its clerk) in bits 18-30."""
    return op | (clerk << 1) | (seq << 5) | (j << 18)


def unpack_cmd(cmd):
    return cmd & 1, (cmd >> 1) & 0xF, (cmd >> 5) & 0x1FFF, (cmd >> 18) & 0x1FFF


def hash_step(h, j):
    """The value's rolling hash after appending `j` (python ints or int32
    arrays: the mask keeps the low 31 bits either way)."""
    return (h * _HASH_MUL + j + 1) & _HASH_MASK


class _Core(RaftMachine):
    """The Raft peers of a kvraft lane (see `raft.py`, "Embedding")."""

    PAYLOAD_WIDTH = 7
    LOG_COMMANDS = True
    CLIENT_TIMER = False


# a cell of the service's table, (server, clerk): 8 words
CELL = 8
KV_LEN, KV_HASH, LAST_SEQ, SESS_LEN, SESS_HASH, PEND_SEQ, PEND_TERM = range(7)
# a clerk's record: 16 words
REC = 16
CLERK_FIELDS = (
    "c_epoch",  # timer epoch, bumped at BOOT
    "seq",  # the operation in flight, or the last (0 none)
    "op",
    "inflight",
    "leader",  # the server to ask next
    "tries",  # "wrong leader" answers to this operation
    "strikes",  # timeouts in a row on the server asked
    "paused",  # waiting out a fruitless round
    "retry_at",  # when the operation in flight is due again
    "armed",  # the retry timer's one chain is alive
    "acked_len",  # appends acknowledged to this clerk
    "acked_hash",  # rolling hash of their j's
    "phase",  # LOAD / CLOSING / FINISHED
)
_BOOL_FIELDS = ("inflight", "paused", "armed")


@struct.dataclass
class KvRaftState:
    raft: RaftCmdState  # [S, ...]
    # -- service (volatile: rebuilt by replaying the log) --
    last_applied: jax.Array  # int32[S]
    # int32[S * C * CELL]: cell (server, clerk) holds the key's value on
    # that server (KV_LEN appends, KV_HASH of their j's), the session
    # table (LAST_SEQ applied, the reply it was given: SESS_LEN,
    # SESS_HASH) and the request the server holds for the clerk
    # (PEND_SEQ, 0 none; PEND_TERM, the term it appended it under)
    svc: jax.Array
    # -- clerks (never lost: the tester crashes no clerk) --
    clk: jax.Array  # int32[C * REC]: CLERK_FIELDS, a record a clerk
    # -- ghosts and totals, once a lane --
    ghost_cmd: jax.Array  # int32[CAP+1] first command applied at an index
    applied_hi: jax.Array  # int32[] highest index any server has applied
    bad: jax.Array  # int32[] the first service invariant broken (0 none)
    counters: jax.Array  # int32[len(COUNTERS)]
    backlog_hwm: jax.Array  # int32[] most entries committed and not applied

    # the tables by name, for tests, the reference and the summary (a
    # handler reads cells and records, not these)
    def _cells(self):
        s = self.last_applied.shape[-1]
        return self.svc.reshape(self.svc.shape[:-1] + (s, -1, CELL))

    def _records(self):
        return self.clk.reshape(self.clk.shape[:-1] + (-1, REC))


for _i, _name in enumerate(
        ("kv_len", "kv_hash", "last_seq", "sess_len", "sess_hash", "pend_seq", "pend_term")):
    setattr(KvRaftState, _name, property(lambda self, i=_i: self._cells()[..., i]))
for _i, _name in enumerate(CLERK_FIELDS):
    setattr(KvRaftState, _name, property(
        (lambda self, i=_i: self._records()[..., i] != 0) if _name in _BOOL_FIELDS
        else (lambda self, i=_i: self._records()[..., i])))


class KvRaftMachine(Machine):
    """`servers` KV servers on Raft + the rest clerks, one key a clerk."""

    MAX_TIMERS = 2
    PAYLOAD_WIDTH = 7
    STREAM_COUNTERS = COUNTERS + ("log_high_water", "apply_backlog")
    STREAM_COUNTERS_MAX = ("log_high_water", "apply_backlog")

    # Get through the log (lab 3's rule). False (`LocalGetKvRaft`): a
    # server that believes itself leader answers a Get from its applied
    # state at once.
    GET_THROUGH_LOG = True
    # The session table decides whether a committed entry is applied.
    # False (a test's twin): every committed entry is.
    SESSION_DEDUP = True

    def __init__(self, num_nodes: int = 10, log_capacity: int = 64,
                 load_until_us: int = 1_500_000, servers: int = 0):
        servers = servers or num_nodes // 2
        clerks = num_nodes - servers
        if servers < 1 or not 1 <= clerks <= 16:
            raise ValueError(
                f"kvraft needs at least one server and 1-16 clerks (a command "
                f"word holds the clerk in 4 bits): got {servers} + {clerks}"
            )
        self.NUM_NODES = num_nodes
        self.servers, self.clerks = servers, clerks
        self.log_capacity = log_capacity
        self.load_until_us = load_until_us
        self.raft = _Core(num_nodes=servers, log_capacity=log_capacity)
        # Raft's sends to its peers, or a Raft reply and the applies' answers
        self.MAX_MSGS = max(self.raft.MAX_MSGS, 1 + APPLY_PER_EVENT)

    # -- state ---------------------------------------------------------------

    def init(self, rng_key) -> KvRaftState:
        s, c, cap = self.servers, self.clerks, self.log_capacity
        # clerk k starts at server k mod S
        leader = jnp.arange(c, dtype=jnp.int32) % s
        records = jnp.zeros((c, REC), jnp.int32).at[:, CLERK_FIELDS.index("leader")].set(leader)
        return KvRaftState(
            raft=self.raft.init(rng_key),
            last_applied=jnp.zeros((s,), jnp.int32),
            svc=jnp.zeros((s * c * CELL,), jnp.int32),
            clk=records.reshape(-1),
            ghost_cmd=jnp.zeros((cap + 1,), jnp.int32),
            applied_hi=jnp.int32(0),
            bad=jnp.int32(0),
            counters=jnp.zeros((len(COUNTERS),), jnp.int32),
            backlog_hwm=jnp.int32(0),
        )

    def _spec(self, raft, service, clerk, lane) -> KvRaftState:
        return KvRaftState(
            raft=raft, last_applied=service[0], svc=service[1], clk=clerk,
            ghost_cmd=lane, applied_hi=lane, bad=lane, counters=lane, backlog_hwm=lane,
        )

    def lane_spec(self) -> KvRaftState:
        s, c = self.servers, self.clerks
        servers = RoleRows(0, s)
        return self._spec(
            jax.tree.map(lambda _: servers, self.raft.durable_spec()),
            (servers, RoleRows(0, s, c * CELL)), RoleRows(s, c, REC), True,
        )

    def durable_spec(self) -> KvRaftState:
        """Raft's stable storage and what the clerks hold; the service's
        state is memory, rebuilt from the log."""
        return self._spec(self.raft.durable_spec(), (False, False), True, True)

    def restart_if(self, nodes: KvRaftState, i, cond, rng_key) -> KvRaftState:
        # a clerk's row index is never < S, so a clerk loses nothing
        s, width = self.servers, self.clerks * CELL
        return nodes.replace(
            raft=self.raft.restart_if(nodes.raft, i, cond, rng_key),
            last_applied=set_at(nodes.last_applied, i, 0, cond),
            svc=jnp.where((jnp.arange(s * width) // width == i) & cond, 0, nodes.svc),
        )

    def churn_nodes(self):
        """The tester partitions and crashes the servers."""
        return tuple(range(self.servers))

    # -- helpers -------------------------------------------------------------

    def _pay(self, *vals):
        return make_payload(self.PAYLOAD_WIDTH, *vals)

    def _count(self, nodes: KvRaftState, **events) -> KvRaftState:
        """counters[name] += n for each named event (a traced bool counts 1)."""
        add = jnp.zeros_like(nodes.counters)
        for name, n in events.items():
            add = add.at[_C[name]].set(jnp.asarray(n, jnp.int32))
        return nodes.replace(counters=nodes.counters + add)

    def _flag(self, nodes: KvRaftState, code: int, cond) -> KvRaftState:
        """The first service invariant broken stays the lane's verdict."""
        return nodes.replace(bad=jnp.where((nodes.bad == 0) & cond, code, nodes.bad))

    def _raft_out(self, out: Outbox, on) -> Outbox:
        """Raft's outbox as this machine's: valid under `on`, widened to
        MAX_MSGS where the applies' answers need more slots than Raft."""
        extra = self.MAX_MSGS - self.raft.MAX_MSGS
        if extra:
            out = out.replace(
                msg_dst=jnp.concatenate([out.msg_dst, jnp.full((extra,), -1, jnp.int32)]),
                msg_payload=jnp.concatenate(
                    [out.msg_payload, jnp.zeros((extra, self.PAYLOAD_WIDTH), jnp.int32)]),
                msg_valid=jnp.concatenate([out.msg_valid, jnp.zeros((extra,), bool)]),
            )
        return out.replace(
            msg_valid=out.msg_valid & on, timer_valid=out.timer_valid & on
        )

    def _cell(self, nodes: KvRaftState, srv, c):
        """The eight words of cell (srv, c), each a one-hot read."""
        base = (srv * self.clerks + c) * CELL
        return jnp.stack([get_at(nodes.svc, base + k) for k in range(CELL)])

    def _set_cell(self, nodes: KvRaftState, srv, c, cond, cell, fields: dict) -> KvRaftState:
        """Cell (srv, c) <- `cell` with `fields` ({word: value}) replaced,
        under `cond`: one masked pass over the table."""
        for word, value in fields.items():
            cell = set_at(cell, word, value)
        n = self.servers * self.clerks
        here = (jnp.arange(n * CELL) // CELL == srv * self.clerks + c) & cond
        return nodes.replace(svc=jnp.where(here, jnp.tile(cell, n), nodes.svc))

    # -- service: apply --------------------------------------------------------

    def _apply(self, nodes: KvRaftState, srv, do, outbox: Outbox):
        """Apply up to APPLY_PER_EVENT committed entries on server `srv`
        under `do`; answers go out in message slots 1.."""
        cap = self.log_capacity
        commit = get_at(nodes.raft.commit, srv)
        applied = get_at(nodes.last_applied, srv)
        for k in range(APPLY_PER_EVENT):
            idx = applied + 1
            can = do & (idx <= commit)
            at = jnp.minimum(idx, cap)
            cmd = get_at(nodes.raft.log_cmd, (srv, at))
            op, c, seq, j = unpack_cmd(cmd)
            c = jnp.minimum(c, self.clerks - 1)
            cell = self._cell(nodes, srv, c)
            fresh = seq > cell[LAST_SEQ] if self.SESSION_DEDUP else jnp.bool_(True)
            grow = can & fresh & (op == OP_APPEND)
            old_len = cell[KV_LEN]
            new_len = old_len + grow.astype(jnp.int32)
            new_hash = jnp.where(grow, hash_step(cell[KV_HASH], j), cell[KV_HASH])
            note = can & fresh
            # a duplicate is answered with the reply its first apply was given
            r_len = jnp.where(fresh, new_len, cell[SESS_LEN])
            r_hash = jnp.where(fresh, new_hash, cell[SESS_HASH])
            waiting = can & (cell[PEND_SEQ] == seq)
            outbox = send_if(
                outbox, 1 + k, waiting, self.servers + c,
                self._pay(M_REPLY, seq, ST_OK, r_len, r_hash),
            )
            nodes = self._set_cell(
                nodes, srv, c, can, cell,
                {KV_LEN: new_len, KV_HASH: new_hash,
                 LAST_SEQ: jnp.where(note, seq, cell[LAST_SEQ]),
                 SESS_LEN: r_len, SESS_HASH: r_hash,
                 PEND_SEQ: jnp.where(waiting, 0, cell[PEND_SEQ])},
            )
            first = can & (idx > nodes.applied_hi)
            nodes = self._flag(nodes, APPEND_ORDER, grow & (j != old_len))
            nodes = self._flag(
                nodes, APPLY_DIVERGED, can & ~first & (get_at(nodes.ghost_cmd, at) != cmd))
            nodes = nodes.replace(
                ghost_cmd=set_at(nodes.ghost_cmd, at, cmd, first),
                applied_hi=jnp.where(first, idx, nodes.applied_hi),
            )
            nodes = self._count(
                nodes, appends_applied=first & grow, dup_refused=first & ~fresh)
            applied = applied + can.astype(jnp.int32)
        return nodes.replace(
            last_applied=set_at(nodes.last_applied, srv, applied, do)
        ), applied, commit, outbox

    def _serve(self, nodes: KvRaftState, srv, on, was_behind, chain_fired, outbox):
        """The service's part of a server event (`on`): apply, keep the
        T_APPLY chain armed while a backlog is left (`was_behind`: one
        was already armed before this event, unless this event IS it:
        `chain_fired`), note the backlog's high-water mark."""
        # (a vote that wins sends Raft's heartbeats in every slot: that
        # event applies nothing, and a backlog's chain is armed already)
        nodes, applied, commit, outbox = self._apply(
            nodes, srv, on & ~outbox.msg_valid[1:].any(), outbox)
        behind = commit - applied
        arm = on & (behind > 0) & (~was_behind | chain_fired)
        outbox = set_timer_if(
            outbox, 1, arm, APPLY_DELAY_US,
            jnp.int32(T_APPLY) + 4 * get_at(nodes.raft.epoch, srv),
        )
        return nodes.replace(
            backlog_hwm=jnp.maximum(nodes.backlog_hwm, jnp.where(on, behind, 0))
        ), outbox

    # -- clerks ---------------------------------------------------------------------

    def _record(self, nodes: KvRaftState, clk):
        """Clerk `clk`'s record as a dict of its fields, each a one-hot read."""
        rec = {
            name: get_at(nodes.clk, clk * REC + i) for i, name in enumerate(CLERK_FIELDS)
        }
        for name in _BOOL_FIELDS:
            rec[name] = rec[name] != 0
        return rec

    def _set_record(self, nodes: KvRaftState, clk, cond, rec) -> KvRaftState:
        """Clerk `clk`'s record <- `rec` under `cond`: one masked pass."""
        words = jnp.stack(
            [jnp.asarray(rec[name], jnp.int32) for name in CLERK_FIELDS]
            + [jnp.int32(0)] * (REC - len(CLERK_FIELDS)))
        here = (jnp.arange(self.clerks * REC) // REC == clk) & cond
        return nodes.replace(clk=jnp.where(here, jnp.tile(words, self.clerks), nodes.clk))

    def _clerk_move(self, rec, start, resend, rotate, pause, now_us, rand_word,
                    outbox, fired=False):
        """A clerk's move, on its record. `start`: begin the next
        operation (the closing Get at or after the load window);
        `resend`: the operation in flight goes out again; `rotate`: to
        the next server; `pause`: not now — a fruitless round of all S
        ends here, and the operation goes out again CYCLE_PAUSE_US
        later, on T_PAUSE (one alive at most: a paused clerk sends
        nothing until it fires). The retry timer is ONE chain a clerk
        (Raft's election timer's way, so a clerk never holds more than
        two queue slots): `fired` says this event is its firing; it
        re-arms at the deadline — CLERK_TIMEOUT_US after the last send —
        while an operation is in flight and dies otherwise, and a send
        arms it only where no chain is alive."""
        closing = now_us >= self.load_until_us
        append = ~closing & ((rand_word % jnp.uint32(1000)) < jnp.uint32(APPEND_PERMILLE))
        target = jnp.where(rotate, (rec["leader"] + 1) % self.servers, rec["leader"])
        move = start | resend
        send = move & ~pause
        # (a pause pushes the deadline past itself: the chain must not
        # come due while the clerk sends nothing)
        deadline = jnp.where(
            send, now_us + CLERK_TIMEOUT_US,
            jnp.where(move, now_us + CYCLE_PAUSE_US + CLERK_TIMEOUT_US, rec["retry_at"]),
        )
        inflight = rec["inflight"] | start
        arm = inflight & (fired | (send & ~rec["armed"]))
        rec = dict(
            rec,
            seq=rec["seq"] + start.astype(jnp.int32),
            op=jnp.where(start, append.astype(jnp.int32), rec["op"]),
            inflight=inflight,
            leader=target,
            tries=jnp.where(start, 0, rec["tries"]),
            strikes=jnp.where(start | rotate, 0, rec["strikes"]),
            paused=jnp.where(move, pause, rec["paused"]),
            retry_at=deadline,
            armed=jnp.where(fired, inflight, rec["armed"] | arm),
            phase=jnp.where(start & closing, CLOSING, rec["phase"]),
        )
        req = self._pay(M_REQ, rec["seq"], rec["op"], rec["acked_len"])
        outbox = send_if(outbox, 0, send, target, req)
        outbox = set_timer_if(
            outbox, 0, arm, jnp.maximum(deadline - now_us, 1),
            jnp.int32(T_RETRY) + 4 * rec["c_epoch"],
        )
        outbox = set_timer_if(
            outbox, 1, move & pause, CYCLE_PAUSE_US,
            jnp.int32(T_PAUSE) + 4 * rec["c_epoch"],
        )
        return rec, outbox

    # -- timers ---------------------------------------------------------------------

    def on_timer(self, nodes: KvRaftState, node, timer_id, now_us, rand_u32) -> Tuple[KvRaftState, Outbox]:
        s = self.servers
        is_server = node < s
        srv = jnp.minimum(node, s - 1)
        clk = jnp.clip(node - s, 0, self.clerks - 1)
        is_boot = timer_id == 0

        # a server's timers are Raft's, but for the apply re-arm. A clerk's
        # event goes to Raft under a node outside the peer set: every write
        # of Raft's handlers is a row mask, so it writes nothing
        was_behind = get_at(nodes.raft.commit, srv) > get_at(nodes.last_applied, srv)
        chain_fired = (
            is_server & ~is_boot & (timer_id % 4 == T_APPLY)
            & (timer_id // 4 == get_at(nodes.raft.epoch, srv))
        )
        raft, r_out = self.raft.on_timer(
            nodes.raft, jnp.where(is_server, node, s), timer_id, now_us, rand_u32)
        outbox = self._raft_out(r_out, is_server)
        nodes, outbox = self._serve(
            nodes.replace(raft=raft), srv, chain_fired, was_behind, chain_fired, outbox)

        # a clerk: BOOT (re)starts its loop (a new epoch: no chain is
        # alive), the retry timer re-sends what is due
        is_clerk = ~is_server
        boot = is_clerk & is_boot
        rec = self._record(nodes, clk)
        rec = dict(
            rec, c_epoch=rec["c_epoch"] + boot.astype(jnp.int32),
            armed=rec["armed"] & ~boot,
        )
        mine = is_clerk & ~is_boot & (timer_id // 4 == rec["c_epoch"])
        fired = mine & (timer_id % 4 == T_RETRY)
        timeout = (
            fired & rec["inflight"] & (now_us >= rec["retry_at"]) & ~rec["paused"]
        )
        unpause = mine & (timer_id % 4 == T_PAUSE) & rec["inflight"] & rec["paused"]
        strikes = rec["strikes"] + timeout.astype(jnp.int32)
        rec, outbox = self._clerk_move(
            dict(rec, strikes=strikes),
            start=boot & ~rec["inflight"] & (rec["phase"] != FINISHED),
            resend=timeout | unpause | (boot & rec["inflight"]),
            rotate=timeout & (strikes >= STRIKES_TO_MOVE), pause=jnp.bool_(False),
            now_us=now_us, rand_word=rand_u32[0], outbox=outbox, fired=fired,
        )
        nodes = self._set_record(nodes, clk, is_clerk, rec)
        return self._count(nodes, clerk_timeouts=timeout), outbox

    # -- messages ---------------------------------------------------------------------

    def on_message(self, nodes: KvRaftState, node, src, payload, now_us, rand_u32) -> Tuple[KvRaftState, Outbox]:
        s = self.servers
        mtype = payload[0]
        is_server = node < s
        srv = jnp.minimum(node, s - 1)
        clk = jnp.clip(node - s, 0, self.clerks - 1)

        # ---- server: Raft's messages (another event: a node outside the
        # peer set, which writes nothing) ----
        was_behind = get_at(nodes.raft.commit, srv) > get_at(nodes.last_applied, srv)
        is_raft = is_server & (mtype <= M_AER)
        raft, r_out = self.raft.on_message(
            nodes.raft, jnp.where(is_raft, node, s), src, payload, now_us, rand_u32)
        outbox = self._raft_out(r_out, is_raft)

        # ---- server: a clerk's request ----
        is_req = is_server & (mtype == M_REQ)
        c = jnp.clip(src - s, 0, self.clerks - 1)
        seq, op, j = payload[1], payload[2], payload[3]
        cell = self._cell(nodes, srv, c)
        term = get_at(raft.term, srv)
        leads = is_req & (get_at(raft.role, srv) == LEADER)
        applied = leads & (cell[LAST_SEQ] == seq)
        local = leads & (op == OP_GET) & (not self.GET_THROUGH_LOG)
        held = (cell[PEND_SEQ] == seq) & (cell[PEND_TERM] == term)
        want = leads & ~applied & ~local & ~held & (seq > cell[LAST_SEQ])
        raft, took = self.raft.propose_if(raft, srv, want, pack_cmd(op, c, seq, j))
        nodes = self._set_cell(
            nodes.replace(raft=raft), srv, c, took, cell,
            {PEND_SEQ: seq, PEND_TERM: term})
        nodes = self._count(nodes, log_full=want & ~took)
        answer = jnp.where(
            local,
            self._pay(M_REPLY, seq, ST_OK, cell[KV_LEN], cell[KV_HASH]),
            jnp.where(
                applied,
                self._pay(M_REPLY, seq, ST_OK, cell[SESS_LEN], cell[SESS_HASH]),
                self._pay(M_REPLY, seq, ST_WRONG_LEADER),
            ),
        )
        outbox = send_if(outbox, 0, (is_req & ~leads) | applied | local, src, answer)

        # ---- server: apply what is committed ----
        nodes, outbox = self._serve(
            nodes, srv, is_server, was_behind, jnp.bool_(False), outbox)

        # ---- clerk: an answer to the operation in flight ----
        rec = self._record(nodes, clk)
        is_reply = ~is_server & (mtype == M_REPLY)
        mine = is_reply & rec["inflight"] & (payload[1] == rec["seq"])
        acked = mine & (payload[2] == ST_OK)
        wrong = mine & (payload[2] == ST_WRONG_LEADER) & (src == rec["leader"])
        was_get = rec["op"] == OP_GET
        was_closing = rec["phase"] == CLOSING
        grew = acked & ~was_get
        stale = acked & was_get & (
            (payload[3] != rec["acked_len"]) | (payload[4] != rec["acked_hash"]))
        tries = rec["tries"] + wrong.astype(jnp.int32)
        rec = dict(
            rec,
            inflight=rec["inflight"] & ~acked,
            # the answering server is the leader to remember
            leader=jnp.where(acked, jnp.minimum(src, s - 1), rec["leader"]),
            acked_len=rec["acked_len"] + grew.astype(jnp.int32),
            acked_hash=jnp.where(
                grew, hash_step(rec["acked_hash"], rec["acked_len"]), rec["acked_hash"]),
            tries=tries,
            phase=jnp.where(acked & was_closing, FINISHED, rec["phase"]),
        )
        rec, outbox = self._clerk_move(
            rec, start=acked & ~was_closing, resend=wrong, rotate=wrong,
            pause=wrong & (tries % s == 0),
            now_us=now_us, rand_word=rand_u32[0], outbox=outbox,
        )
        nodes = self._set_record(nodes, clk, is_reply, rec)
        nodes = self._flag(nodes, STALE_GET, stale)
        return self._count(
            nodes, ops_acked=acked, gets_acked=acked & was_get, wrong_leader=wrong,
            closing_gets_acked=acked & was_closing,
        ), outbox

    # -- invariants / results -------------------------------------------------------

    def invariant(self, nodes: KvRaftState, now_us):
        ok, code = self.raft.invariant(nodes.raft, now_us)
        code = jnp.where(ok, nodes.bad, code).astype(jnp.int32)
        return code == 0, code

    def stream_counters(self, nodes: KvRaftState) -> jax.Array:
        return jnp.concatenate([
            nodes.counters, nodes.raft.log_len.max()[None], nodes.backlog_hwm[None],
        ])

    def summary(self, nodes: KvRaftState):
        return dict(
            zip(self.STREAM_COUNTERS, self.stream_counters(nodes)),
            max_term=jnp.max(nodes.raft.term),
            min_commit=jnp.min(nodes.raft.commit),
            applied_hi=nodes.applied_hi,
            finished=(nodes.phase == FINISHED).sum(dtype=jnp.int32),
            acked_len=nodes.acked_len,
        )

    def coverage_projection(self, nodes: KvRaftState, now_us) -> jax.Array:
        # Raft's word (term bucket in the low 3 bits: the phase axis), then
        # how many clerks wait for an answer, whether a server lags its
        # commit, how many clerks have closed
        waiting = jnp.minimum(nodes.inflight.sum(dtype=jnp.int32), 3).astype(jnp.uint32)
        behind = jnp.any(nodes.raft.commit > nodes.last_applied).astype(jnp.uint32)
        closed = jnp.minimum(
            (nodes.phase == FINISHED).sum(dtype=jnp.int32), 3).astype(jnp.uint32)
        return (
            self.raft.coverage_projection(nodes.raft, now_us)
            | (waiting << 12) | (behind << 14) | (closed << 15)
        )


class LocalGetKvRaft(KvRaftMachine):
    """Bug variant (`demo-localget-kvraft`): a server that believes
    itself leader answers a Get from its applied state without
    committing it — a deposed leader on the minority side of a split, or
    a new one that has not yet applied what the old one acknowledged,
    returns a value that lacks what the clerk has since had acknowledged
    (STALE_GET; found under `--churn kv3a`)."""

    GET_THROUGH_LOG = False
