"""Raft leader election + log replication as a TPU-engine Machine.

The MadRaft-class flagship workload (BASELINE.json: "MadRaft 3-node
leader election" / "5-node log replication + partition injection").
Single-entry AppendEntries, randomized election timeouts, heartbeats,
client appends modeled as a leader-side timer. Safe under partition AND
kill/restart chaos: term/votedFor/log survive restarts (stable storage),
volatile state resets — so `FaultPlan(allow_kill=True)` exercises true
crash-recovery.

On-device invariants (checked after every event):
  * ElectionSafety (code 101): at most one leader per term
  * LogMatching on committed prefixes (code 102)
  * CommitMonotonicity is implied by construction (commit only grows)

Timer ids are epoch-encoded (`tid = base + 4*epoch[node]`): a restart
bumps the node's epoch at BOOT so timer chains from a previous
incarnation die instead of double-arming — the fixed-shape analogue of
the reference dropping a killed node's timers with its futures
(madsim/src/sim/task/mod.rs:133-140).

Embedding (`models/kvraft.py`). A machine that layers a service on the
log builds a `RaftMachine` over the PEERS only — `num_nodes` is the
size of the peer set, nodes `0 .. num_nodes - 1` of a lane that may
hold more (clients) — keeps its state `[peers, ...]` as one sub-tree of
its own, and calls `on_timer` / `on_message` for the events that are
Raft's. Two class switches serve it, both off here so that `raft` and
its demo twins trace the program they always traced: `LOG_COMMANDS`
(an entry is a term AND a command word: `log_cmd`, carried by
AppendEntries in payload word 6, compared by LogMatching) and
`CLIENT_TIMER` (False: no leader-side client timer; the embedding
machine appends through `propose_if`). It learns that `commit` moved
from the state the handlers return: `commit[node]` against its own
`last_applied[node]`. An event that is not Raft's is handed over under
a node index OUTSIDE the peer set (`num_nodes`): every write of these
handlers is a row mask over the peers, so such a call writes nothing,
and the embedding machine drops its outbox — no select over the whole
Raft state is needed to keep it.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from ..engine.machine import (
    Machine, Outbox, get_at, make_payload, send_if, set_at, set_timer_if, update_node,
)
from ..utils import set2d

# roles
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2

# message types (payload[0])
M_RV, M_VOTE, M_AE, M_AER = 1, 2, 3, 4

# timer bases (payload[0] = base + 4*epoch; base 0 = engine BOOT)
T_BOOT, T_ELECTION, T_HEARTBEAT, T_CLIENT = 0, 1, 2, 3

# invariant failure codes
ELECTION_SAFETY = 101
LOG_MATCHING = 102

ELECTION_MIN_US = 150_000
ELECTION_MAX_US = 300_000
HEARTBEAT_US = 50_000
CLIENT_APPEND_US = 30_000


@struct.dataclass
class RaftState:
    # persistent (survives restart — stable storage)
    term: jax.Array  # int32[N]
    voted_for: jax.Array  # int32[N], -1 = none
    log_term: jax.Array  # int32[N, CAP+1]; slot 0 is the 0-sentinel
    log_len: jax.Array  # int32[N]
    epoch: jax.Array  # int32[N] timer epoch (persistent, bumped at BOOT)
    # volatile
    role: jax.Array  # int32[N]
    votes: jax.Array  # int32[N]
    elec_deadline: jax.Array  # int32[N] us
    commit: jax.Array  # int32[N]
    next_idx: jax.Array  # int32[N, N]
    match_idx: jax.Array  # int32[N, N]


@struct.dataclass
class RaftCmdState(RaftState):
    """`RaftState` of a machine with `LOG_COMMANDS`: a command word
    beside each entry's term (persistent, as the log is)."""

    log_cmd: jax.Array  # int32[N, CAP+1]; slot 0 unused


class RaftMachine(Machine):
    PAYLOAD_WIDTH = 6
    MAX_TIMERS = 2

    # An entry holds a command word beside its term (see "Embedding"
    # above): state is `RaftCmdState`, AppendEntries carries the word in
    # payload[6] (so PAYLOAD_WIDTH >= 7), LogMatching compares it too.
    LOG_COMMANDS = False
    # The leader-side client timer (T_CLIENT: one entry every 30 ms).
    # False: nothing appends but `propose_if`, and timer base 3 is the
    # embedding machine's.
    CLIENT_TIMER = True

    # Follower commit bound on AppendEntries. False (correct, Raft §5.3
    # "index of last new entry"): commit caps at prev_idx(+1 with an
    # entry). True reproduces the classic overcommit bug — capping at
    # the follower's whole log length lets a stale divergent tail that
    # extends past the match point be committed. The engine found this
    # at seed 66531 of an 88k-seed sweep (LOG_MATCHING violated: one
    # node committed term-1 entries 6-8 where the cluster committed
    # term-2 ones); kept as a flag so the bug class stays testable.
    COMMIT_TO_LOG_LEN = False

    # Leader commit quorum. False (correct): an entry commits when
    # replicated on a strict majority. True reproduces a
    # quorum-off-by-one bug (commit at majority-1 acks, i.e. leader +
    # one follower on a 5-node cluster). Triggering a *safety* violation
    # needs the leader plus its one follower sustained-isolated from a
    # majority that elects and commits divergently — a 2/3 group split
    # clogs 6 links at once, unreachable for the legacy two-pair-clog
    # fault vocabulary; FaultPlan(allow_group=True) finds it (the
    # round-3 new-fault-kinds demo, see tests/test_engine.py).
    QUORUM_OFF_BY_ONE = False

    # Durable-state contract bug (the crash-with-amnesia demo). False
    # (correct, Raft §5.1): term/votedFor/log live in stable storage,
    # commitIndex is volatile. True flips the log and the commit index:
    # the node persists its commitIndex but NOT the log backing it —
    # the classic "fsync the metadata, forget the data" storage bug. A
    # plain kill/restart can't see it (the model's restart_if still
    # hand-resets the right fields); FaultPlan(strict_restart=True)
    # makes the CONTRACT the restart semantics, so the first restart
    # after any commit leaves commit pointing at a wiped log — caught
    # by the existing LogMatching checker (code 102), no new invariant
    # needed.
    PERSIST_COMMIT_NOT_LOG = False

    # Vote tally semantics. False (correct, Raft §5.2: a candidate wins
    # when a majority of SERVERS grant — distinct voters): `votes` holds
    # a bitmask of granting node ids (self-vote included) and the win
    # check popcounts it, so a re-delivered grant is idempotent. True
    # reproduces the duplicate-vote tally bug this model silently had
    # until PR-5's message-duplication chaos (FaultPlan.allow_dup) found
    # it: `votes` is a plain per-message counter, an at-least-once
    # network delivers one grant twice, and two leaders share a term
    # (ELECTION_SAFETY, code 101). Identical behavior on exactly-once
    # networks either way — every recorded no-dup seed replays unchanged.
    DUP_VOTE_COUNT = False

    # Commit rule for entries of EARLIER terms. False (correct, Raft
    # §5.4.2): a leader commits by counting replicas only entries of its
    # own term; older ones commit with them (Log Matching). True is the
    # bug the paper's Figure 8 is drawn for: an old-term entry that has
    # reached a majority is committed by count, and a later leader whose
    # log never held it overwrites it — caught by the LogMatching check
    # (code 102). Reaching it takes leaders that are cut off with
    # entries half replicated and come back terms later: the churn
    # process (`FaultPlan.churn`, `--churn fig8`) finds it, and so do
    # two scheduled kills or partitions on a lossy net (6 of 65,536
    # seeds against 1 with the churn, my chip run, PR 27).
    COMMIT_OLD_TERM_BY_COUNT = False

    def __init__(self, num_nodes: int = 5, log_capacity: int = 8):
        if num_nodes > 31:
            raise ValueError(
                "RaftMachine tracks granting voters as an int32 bitmask "
                "(dup-safe tally, Raft §5.2); num_nodes must be <= 31"
            )
        self.NUM_NODES = num_nodes
        self.MAX_MSGS = num_nodes - 1
        self.log_capacity = log_capacity
        self.majority = num_nodes // 2 + 1

    # -- state ---------------------------------------------------------------

    def init(self, rng_key) -> RaftState:
        n, cap = self.NUM_NODES, self.log_capacity
        z = jnp.zeros((n,), jnp.int32)
        state = RaftState(
            term=z,
            voted_for=jnp.full((n,), -1, jnp.int32),
            log_term=jnp.zeros((n, cap + 1), jnp.int32),
            log_len=z,
            epoch=z,
            role=z,
            votes=z,
            elec_deadline=z,
            commit=z,
            next_idx=jnp.ones((n, n), jnp.int32),
            match_idx=jnp.zeros((n, n), jnp.int32),
        )
        return self._with_cmd(state, jnp.zeros((n, cap + 1), jnp.int32))

    def _with_cmd(self, state: RaftState, log_cmd) -> RaftState:
        """`state` as this machine holds it: with the command leaf where
        entries have commands (`log_cmd`: an array, or the leaf's
        durable / torn class for a spec)."""
        if not self.LOG_COMMANDS:
            return state
        return RaftCmdState(
            **{f: getattr(state, f) for f in RaftState.__dataclass_fields__},
            log_cmd=log_cmd,
        )

    def init_node(self, nodes: RaftState, i, rng_key) -> RaftState:
        """Restart: persistent state survives, volatile resets
        (Raft §5.1 stable storage semantics)."""
        return self.restart_if(nodes, i, jnp.bool_(True), rng_key)

    def durable_spec(self) -> RaftState:
        """Crash-with-amnesia contract (`FaultPlan.strict_restart`):
        term/votedFor/log are stable storage, the timer epoch is
        bookkeeping that must survive (it dies with the node's timers
        otherwise), everything else is volatile. The generic wipe under
        this spec is leaf-for-leaf identical to `restart_if` — strict
        ON/OFF is bit-identical for the honest machine (tests assert)."""
        log_durable = not self.PERSIST_COMMIT_NOT_LOG
        return self._with_cmd(RaftState(
            term=True,
            voted_for=True,
            log_term=log_durable,
            log_len=log_durable,
            epoch=True,
            role=False,
            votes=False,
            elec_deadline=False,
            commit=bool(self.PERSIST_COMMIT_NOT_LOG),
            next_idx=False,
            match_idx=False,
        ), log_durable)

    def restart_if(self, nodes: RaftState, i, cond, rng_key) -> RaftState:
        """Masked restart: cond folds into the row mask, so the engine's
        per-step fault branch costs row writes, not a full-tree select."""
        n = self.NUM_NODES
        row = (jnp.arange(n) == i) & cond
        set_row = lambda arr, v: jnp.where(row, v, arr)  # noqa: E731
        return nodes.replace(
            role=set_row(nodes.role, FOLLOWER),
            votes=set_row(nodes.votes, 0),
            elec_deadline=set_row(nodes.elec_deadline, 0),
            commit=set_row(nodes.commit, 0),
            next_idx=jnp.where(row[:, None], 1, nodes.next_idx),
            match_idx=jnp.where(row[:, None], 0, nodes.match_idx),
        )

    # -- helpers -------------------------------------------------------------

    def _peers(self, node):
        """The NUM_NODES-1 other node ids, as a static-shape vector."""
        n = self.NUM_NODES
        offs = jnp.arange(1, n, dtype=jnp.int32)
        return (node + offs) % n

    def _rand_timeout(self, rand_word):
        span = jnp.uint32(ELECTION_MAX_US - ELECTION_MIN_US)
        return jnp.int32(ELECTION_MIN_US) + (rand_word % span).astype(jnp.int32)

    def _pay(self, *vals):
        return make_payload(self.PAYLOAD_WIDTH, *vals)

    def _tid(self, nodes, node, base):
        return jnp.int32(base) + 4 * get_at(nodes.epoch, node)

    # vote-tally representation (see DUP_VOTE_COUNT): bitmask of voter
    # ids by default, plain counter for the seeded buggy variant

    def _vote_init(self, node):
        if self.DUP_VOTE_COUNT:
            return jnp.int32(1)
        return jnp.int32(1) << node

    def _vote_add(self, votes, src, counts):
        if self.DUP_VOTE_COUNT:
            return votes + jnp.where(counts, 1, 0)
        return jnp.where(counts, votes | (jnp.int32(1) << src), votes)

    def _vote_count(self, votes):
        if self.DUP_VOTE_COUNT:
            return votes
        return lax.population_count(votes.astype(jnp.uint32)).astype(jnp.int32)

    # -- timers --------------------------------------------------------------

    def on_timer(self, nodes: RaftState, node, timer_id, now_us, rand_u32) -> Tuple[RaftState, Outbox]:
        outbox = self.empty_outbox()
        base = timer_id % 4
        t_epoch = timer_id // 4
        # BOOT (engine-raw id 0) always valid; others require current epoch.
        is_boot = timer_id == T_BOOT
        epoch = get_at(nodes.epoch, node)
        live = is_boot | (t_epoch == epoch)

        # ---- BOOT: bump epoch, arm election + client timers ----
        new_epoch = jnp.where(is_boot & live, epoch + 1, epoch)
        nodes = update_node(nodes, node, epoch=new_epoch)
        timeout = self._rand_timeout(rand_u32[0])
        boot_deadline = now_us + timeout
        nodes = update_node(
            nodes, node,
            elec_deadline=jnp.where(
                is_boot & live, boot_deadline, get_at(nodes.elec_deadline, node)
            ),
        )
        outbox = set_timer_if(outbox, 0, is_boot & live, timeout, self._tid(nodes, node, T_ELECTION))
        if self.CLIENT_TIMER:
            outbox = set_timer_if(outbox, 1, is_boot & live, CLIENT_APPEND_US, self._tid(nodes, node, T_CLIENT))

        # ---- ELECTION ----
        is_elec = live & (base == T_ELECTION) & ~is_boot
        not_yet = now_us < get_at(nodes.elec_deadline, node)
        # re-arm at the postponed deadline (heartbeats push it forward)
        rearm_delay = jnp.maximum(get_at(nodes.elec_deadline, node) - now_us, 1)
        outbox = set_timer_if(outbox, 0, is_elec & not_yet, rearm_delay, self._tid(nodes, node, T_ELECTION))

        start = is_elec & ~not_yet & (get_at(nodes.role, node) != LEADER)
        new_term = get_at(nodes.term, node) + 1
        timeout2 = self._rand_timeout(rand_u32[1])
        nodes = update_node(
            nodes, node,
            term=jnp.where(start, new_term, get_at(nodes.term, node)),
            role=jnp.where(start, CANDIDATE, get_at(nodes.role, node)),
            voted_for=jnp.where(start, node, get_at(nodes.voted_for, node)),
            votes=jnp.where(start, self._vote_init(node), get_at(nodes.votes, node)),
            elec_deadline=jnp.where(start, now_us + timeout2, get_at(nodes.elec_deadline, node)),
        )
        outbox = set_timer_if(
            outbox, 0, is_elec & ~not_yet, timeout2, self._tid(nodes, node, T_ELECTION)
        )
        last_idx = get_at(nodes.log_len, node)
        last_term = get_at(nodes.log_term, (node, last_idx))
        rv = self._pay(M_RV, get_at(nodes.term, node), node, last_idx, last_term)
        peers = self._peers(node)
        for s in range(self.MAX_MSGS):
            outbox = send_if(outbox, s, start, peers[s], rv)

        # ---- HEARTBEAT (leader replicates) ----
        is_hb = live & (base == T_HEARTBEAT) & ~is_boot
        is_leader = get_at(nodes.role, node) == LEADER
        do_hb = is_hb & is_leader
        outbox = set_timer_if(outbox, 1, do_hb, HEARTBEAT_US, self._tid(nodes, node, T_HEARTBEAT))
        # the node's rows, read once; each peer's words come from them
        my_next = get_at(nodes.next_idx, node)  # [N]
        my_log = get_at(nodes.log_term, node)  # [CAP+1]
        my_len, my_term = get_at(nodes.log_len, node), get_at(nodes.term, node)
        my_commit = get_at(nodes.commit, node)
        if self.LOG_COMMANDS:
            my_cmds = get_at(nodes.log_cmd, node)
        for s in range(self.MAX_MSGS):
            peer = peers[s]
            ni = get_at(my_next, peer)
            prev_idx = ni - 1
            prev_term = get_at(my_log, prev_idx)
            has_entry = ni <= my_len
            at = jnp.minimum(ni, self.log_capacity)
            entry_term = jnp.where(has_entry, get_at(my_log, at), 0)
            ae = (M_AE, my_term, prev_idx, prev_term, entry_term, my_commit)
            if self.LOG_COMMANDS:
                ae += (get_at(my_cmds, at),)
            outbox = send_if(outbox, s, do_hb, peer, self._pay(*ae))

        # ---- CLIENT (leader appends an entry) ----
        if self.CLIENT_TIMER:
            is_client = live & (base == T_CLIENT) & ~is_boot
            outbox = set_timer_if(outbox, 1, is_client & ~do_hb, CLIENT_APPEND_US, self._tid(nodes, node, T_CLIENT))
            nodes, _ = self.propose_if(nodes, node, is_client & is_leader)
        return nodes, outbox

    def propose_if(self, nodes: RaftState, node, want, cmd=0):
        """Append one entry of the node's current term to its own log
        where `want` (traced; the caller has checked that the node is
        the leader) and the log has room. Returns (nodes, appended)."""
        can_append = want & (get_at(nodes.log_len, node) < self.log_capacity)
        new_len = get_at(nodes.log_len, node) + 1
        nodes = update_node(
            nodes, node,
            log_len=jnp.where(can_append, new_len, get_at(nodes.log_len, node)),
            log_term=jnp.where(
                can_append,
                set_at(
                    get_at(nodes.log_term, node),
                    jnp.minimum(new_len, self.log_capacity),
                    get_at(nodes.term, node),
                ),
                get_at(nodes.log_term, node),
            ),
        )
        nodes = nodes.replace(
            match_idx=jnp.where(
                can_append,
                set2d(nodes.match_idx, node, node, new_len),
                nodes.match_idx,
            )
        )
        if self.LOG_COMMANDS:
            nodes = nodes.replace(log_cmd=jnp.where(
                can_append,
                set2d(nodes.log_cmd, node, jnp.minimum(new_len, self.log_capacity), cmd),
                nodes.log_cmd,
            ))
        return nodes, can_append

    # -- messages ------------------------------------------------------------

    def on_message(self, nodes: RaftState, node, src, payload, now_us, rand_u32) -> Tuple[RaftState, Outbox]:
        mtype = payload[0]
        branch = jnp.clip(mtype - 1, 0, 3)

        def rv_branch(args):
            nodes, = args
            outbox = self.empty_outbox()
            t, cand, last_idx, last_term = payload[1], payload[2], payload[3], payload[4]
            # step down on newer term
            newer = t > get_at(nodes.term, node)
            nodes = update_node(
                nodes, node,
                term=jnp.where(newer, t, get_at(nodes.term, node)),
                role=jnp.where(newer, FOLLOWER, get_at(nodes.role, node)),
                voted_for=jnp.where(newer, -1, get_at(nodes.voted_for, node)),
            )
            my_last = get_at(nodes.log_len, node)
            my_last_term = get_at(nodes.log_term, (node, my_last))
            log_ok = (last_term > my_last_term) | ((last_term == my_last_term) & (last_idx >= my_last))
            voted_for = get_at(nodes.voted_for, node)
            can_vote = (voted_for == -1) | (voted_for == cand)
            grant = (t == get_at(nodes.term, node)) & can_vote & log_ok
            nodes = update_node(
                nodes, node,
                voted_for=jnp.where(grant, cand, get_at(nodes.voted_for, node)),
                elec_deadline=jnp.where(
                    grant, now_us + self._rand_timeout(rand_u32[0]),
                    get_at(nodes.elec_deadline, node),
                ),
            )
            vote = self._pay(M_VOTE, get_at(nodes.term, node), grant.astype(jnp.int32))
            outbox = send_if(outbox, 0, jnp.bool_(True), src, vote)
            return nodes, outbox

        def vote_branch(args):
            nodes, = args
            outbox = self.empty_outbox()
            t, granted = payload[1], payload[2]
            newer = t > get_at(nodes.term, node)
            nodes = update_node(
                nodes, node,
                term=jnp.where(newer, t, get_at(nodes.term, node)),
                role=jnp.where(newer, FOLLOWER, get_at(nodes.role, node)),
                voted_for=jnp.where(newer, -1, get_at(nodes.voted_for, node)),
            )
            role = get_at(nodes.role, node)
            counts = (t == get_at(nodes.term, node)) & (role == CANDIDATE) & (granted == 1)
            new_votes = self._vote_add(get_at(nodes.votes, node), src, counts)
            win = (
                counts
                & (self._vote_count(new_votes) >= self.majority)
                & (role == CANDIDATE)
            )
            n = self.NUM_NODES
            nodes = update_node(nodes, node, votes=new_votes, role=jnp.where(win, LEADER, role))
            # leader volatile state
            last = get_at(nodes.log_len, node)
            nodes = nodes.replace(
                next_idx=jnp.where(
                    win,
                    set_at(nodes.next_idx, node, jnp.full((n,), last + 1, jnp.int32)),
                    nodes.next_idx,
                ),
                match_idx=jnp.where(
                    win,
                    set_at(nodes.match_idx, node, set_at(jnp.zeros((n,), jnp.int32), node, last)),
                    nodes.match_idx,
                ),
            )
            # announce leadership immediately with heartbeats + arm timer
            peers = self._peers(node)
            prev_idx = last
            prev_term = get_at(nodes.log_term, (node, prev_idx))
            ae = self._pay(
                M_AE, get_at(nodes.term, node), prev_idx, prev_term, 0, get_at(nodes.commit, node)
            )
            for s in range(self.MAX_MSGS):
                outbox = send_if(outbox, s, win, peers[s], ae)
            outbox = set_timer_if(outbox, 0, win, HEARTBEAT_US, self._tid(nodes, node, T_HEARTBEAT))
            return nodes, outbox

        def ae_branch(args):
            nodes, = args
            outbox = self.empty_outbox()
            t, prev_idx, prev_term, entry_term, leader_commit = (
                payload[1], payload[2], payload[3], payload[4], payload[5],
            )
            stale = t < get_at(nodes.term, node)
            newer = t > get_at(nodes.term, node)
            nodes = update_node(
                nodes, node,
                term=jnp.where(newer, t, get_at(nodes.term, node)),
                role=jnp.where(~stale, FOLLOWER, get_at(nodes.role, node)),
                voted_for=jnp.where(newer, -1, get_at(nodes.voted_for, node)),
                elec_deadline=jnp.where(
                    ~stale, now_us + self._rand_timeout(rand_u32[0]),
                    get_at(nodes.elec_deadline, node),
                ),
            )
            my_len = get_at(nodes.log_len, node)
            my_log = get_at(nodes.log_term, node)  # [CAP+1]
            log_ok = (prev_idx <= my_len) & (get_at(my_log, prev_idx) == prev_term)
            ok = ~stale & log_ok
            has_entry = entry_term > 0
            slot = jnp.minimum(prev_idx + 1, self.log_capacity)
            existing_matches = (my_len >= prev_idx + 1) & (get_at(my_log, slot) == entry_term)
            append = ok & has_entry
            new_len = jnp.where(
                append,
                jnp.where(existing_matches, jnp.maximum(my_len, prev_idx + 1), prev_idx + 1),
                my_len,
            )
            # Raft §5.3: commit caps at the index of the last entry THIS
            # AE verified (prev_idx, +1 if it carried an entry) — not at
            # the follower's log length, whose tail past the match point
            # may be stale (see COMMIT_TO_LOG_LEN above).
            last_new = prev_idx + jnp.where(has_entry, 1, 0)
            commit_cap = jnp.where(
                jnp.bool_(self.COMMIT_TO_LOG_LEN), new_len, jnp.minimum(last_new, new_len)
            )
            nodes = update_node(
                nodes, node,
                log_term=jnp.where(append, set_at(my_log, slot, entry_term), my_log),
                log_len=new_len,
                commit=jnp.where(
                    ok,
                    jnp.maximum(get_at(nodes.commit, node), jnp.minimum(leader_commit, commit_cap)),
                    get_at(nodes.commit, node),
                ),
            )
            if self.LOG_COMMANDS:
                nodes = nodes.replace(log_cmd=jnp.where(
                    append, set2d(nodes.log_cmd, node, slot, payload[6]), nodes.log_cmd
                ))
            match = jnp.where(has_entry, prev_idx + 1, prev_idx)
            aer = self._pay(M_AER, get_at(nodes.term, node), ok.astype(jnp.int32), match)
            outbox = send_if(outbox, 0, jnp.bool_(True), src, aer)
            return nodes, outbox

        def aer_branch(args):
            nodes, = args
            outbox = self.empty_outbox()
            t, success, midx = payload[1], payload[2], payload[3]
            newer = t > get_at(nodes.term, node)
            nodes = update_node(
                nodes, node,
                term=jnp.where(newer, t, get_at(nodes.term, node)),
                role=jnp.where(newer, FOLLOWER, get_at(nodes.role, node)),
                voted_for=jnp.where(newer, -1, get_at(nodes.voted_for, node)),
            )
            is_lead = (get_at(nodes.role, node) == LEADER) & (t == get_at(nodes.term, node))
            good = is_lead & (success == 1)
            new_match = jnp.maximum(get_at(nodes.match_idx, (node, src)), midx)
            nodes = nodes.replace(
                match_idx=jnp.where(
                    good, set2d(nodes.match_idx, node, src, new_match), nodes.match_idx
                ),
                next_idx=jnp.where(
                    good,
                    set2d(nodes.next_idx, node, src, new_match + 1),
                    jnp.where(
                        is_lead & (success == 0),
                        set2d(
                            nodes.next_idx, node, src,
                            jnp.maximum(get_at(nodes.next_idx, (node, src)) - 1, 1),
                        ),
                        nodes.next_idx,
                    ),
                ),
            )
            # advance commit: highest idx replicated on a majority with
            # an entry from the current term (Raft §5.4.2)
            idxs = jnp.arange(self.log_capacity + 1, dtype=jnp.int32)  # [CAP+1]
            replicated = get_at(nodes.match_idx, node)[None, :] >= idxs[:, None]  # [CAP+1, N]
            cnt = jnp.sum(replicated, axis=1)
            cur_term_entry = get_at(nodes.log_term, node) == get_at(nodes.term, node)  # [CAP+1]
            quorum = self.majority - 1 if self.QUORUM_OFF_BY_ONE else self.majority
            if self.COMMIT_OLD_TERM_BY_COUNT:
                cur_term_entry = jnp.bool_(True)
            committable = (
                (cnt >= quorum) & cur_term_entry & (idxs >= 1)
                & (idxs <= get_at(nodes.log_len, node))
            )
            best = jnp.max(jnp.where(committable, idxs, 0))
            commit = get_at(nodes.commit, node)
            nodes = update_node(
                nodes, node, commit=jnp.where(good, jnp.maximum(commit, best), commit),
            )
            return nodes, outbox

        return lax.switch(branch, [rv_branch, vote_branch, ae_branch, aer_branch], (nodes,))

    # -- invariants / results ------------------------------------------------

    def invariant(self, nodes: RaftState, now_us):
        n = self.NUM_NODES
        is_lead = nodes.role == LEADER
        same_term = nodes.term[:, None] == nodes.term[None, :]
        both_lead = is_lead[:, None] & is_lead[None, :] & ~jnp.eye(n, dtype=bool)
        elec_viol = jnp.any(both_lead & same_term)

        # Committed prefixes must agree pairwise. Checked per POSITION
        # instead of per pair — O(N*CAP), not O(N^2*CAP), and exactly
        # equivalent: nodes i, j disagree at a position k both have
        # committed iff, among the nodes whose commit reaches k, the
        # min and max log term at k differ (empty/singleton sets give
        # min >= max, never a violation). The invariant runs EVERY
        # event on every lane, so this is hot-path arithmetic.
        idxs = jnp.arange(self.log_capacity + 1, dtype=jnp.int32)
        committed = (idxs[None, :] >= 1) & (idxs[None, :] <= nodes.commit[:, None])
        big = jnp.int32(2**31 - 1)
        t_min = jnp.min(jnp.where(committed, nodes.log_term, big), axis=0)
        t_max = jnp.max(jnp.where(committed, nodes.log_term, -big), axis=0)
        log_viol = jnp.any(t_max > t_min)
        if self.LOG_COMMANDS:
            c_min = jnp.min(jnp.where(committed, nodes.log_cmd, big), axis=0)
            c_max = jnp.max(jnp.where(committed, nodes.log_cmd, -big), axis=0)
            log_viol = log_viol | jnp.any(c_max > c_min)

        ok = ~(elec_viol | log_viol)
        code = jnp.where(elec_viol, ELECTION_SAFETY, jnp.where(log_viol, LOG_MATCHING, 0))
        return ok, code.astype(jnp.int32)

    def churn_victim(self, nodes: RaftState, connected):
        """Figure 8's churn disconnects THE LEADER: the connected node
        with role LEADER, the lowest index where two terms' leaders are
        both connected; -1 while there is none."""
        lead = connected & (nodes.role == LEADER)
        return jnp.where(lead.any(), jnp.argmax(lead), -1).astype(jnp.int32)

    def is_done(self, nodes: RaftState, now_us):
        # all nodes committed a full log => nothing left to explore
        return jnp.all(nodes.commit >= self.log_capacity)

    def summary(self, nodes: RaftState):
        return {
            "max_term": jnp.max(nodes.term),
            "max_commit": jnp.max(nodes.commit),
            "min_commit": jnp.min(nodes.commit),
            "num_leaders": jnp.sum((nodes.role == LEADER).astype(jnp.int32)),
        }

    def coverage_projection(self, nodes: RaftState, now_us):
        """Scenario projection (EngineConfig.coverage): term bucket
        (phase, low 3 bits) x leader count x committed-log divergence x
        cross-node term delta — the cluster-shape axes along which raft
        interleavings actually differ (which election round, split
        leadership, how far replicas disagree)."""
        term_b = jnp.clip(jnp.max(nodes.term), 0, 7)  # phase bits
        leaders = jnp.clip(
            jnp.sum((nodes.role == LEADER).astype(jnp.int32)), 0, 3
        )
        commit_div = jnp.clip(jnp.max(nodes.commit) - jnp.min(nodes.commit), 0, 7)
        term_delta = jnp.clip(jnp.max(nodes.term) - jnp.min(nodes.term), 0, 3)
        candidates = jnp.clip(
            jnp.sum((nodes.role == CANDIDATE).astype(jnp.int32)), 0, 3
        )
        return (
            term_b
            | (leaders << 3)
            | (commit_div << 5)
            | (term_delta << 8)
            | (candidates << 10)
        ).astype(jnp.uint32)


class OvercommitRaft(RaftMachine):
    """Bug variant (`demo-overcommit-raft`): the Raft §5.3 commit-bound
    bug."""

    COMMIT_TO_LOG_LEN = True


class QuorumOffByOneRaft(RaftMachine):
    """Bug variant (`demo-quorumoffbyone-raft`): commits below a
    majority (needs group faults)."""

    QUORUM_OFF_BY_ONE = True


class VolatileCommitRaft(RaftMachine):
    """Bug variant (`demo-volatilecommit-raft`): durable commitIndex,
    volatile log (caught only by --strict-restart)."""

    PERSIST_COMMIT_NOT_LOG = True


class DupVoteRaft(RaftMachine):
    """Bug variant (`demo-dupvote-raft`): a per-message vote tally
    (caught by dup chaos)."""

    DUP_VOTE_COUNT = True


class Fig8Raft(RaftMachine):
    """Bug variant (`demo-fig8-raft`): commits an earlier term's entry
    by counting replicas (Raft §5.4.2, Figure 8; found under `--churn
    fig8` and under scheduled kills alike)."""

    COMMIT_OLD_TERM_BY_COUNT = True
