"""PR-5/PR-6 chaos palette: pause/resume deferral, clock skew, message
duplication, crash-with-amnesia, torn/lost-write storage faults,
asymmetric partition healing — semantics verified against host-side
Python oracles over the bit-identical replay trace, the seeded
durable-contract bugs caught by the existing checkers, plus the
satellite machinery (shrink kind ablation, hunt checkpoint/resume,
transient-dispatch retry).

Oracle discipline: the eager replay pops the SAME events the device
pops, in the same order, so a plain Python walk of the trace that
re-implements the documented semantics (defer iff the target is paused
at pop time; timer delays scaled by the active q10 factor; horizon-hit
final events are popped but never processed) must predict the final
node state exactly. That is an independent re-derivation, not a replay
of the engine's own arithmetic.
"""

import dataclasses
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
from madsim_tpu.engine.core import (
    F_PAUSE,
    F_RESUME,
    F_SKEW,
    F_SKEW_END,
)
from madsim_tpu.engine.machine import (
    Machine,
    make_payload,
    send_if,
    set_at,
    set_timer_if,
)
from madsim_tpu.engine.replay import replay
from madsim_tpu.models.raft import RaftMachine

HORIZON_US = 1_500_000
TICK_US = 50_000
WINDOW = dict(t_min_us=200_000, t_max_us=600_000,
              dur_min_us=200_000, dur_max_us=400_000)


class TickMachine(Machine):
    """Three periodic tickers: every node counts its own ticks; node 0
    additionally reports each tick to node 2 (the message path the dup
    differential counts). No randomness, no retries — the schedule is
    fully predictable from the chaos semantics alone."""

    NUM_NODES = 3
    PAYLOAD_WIDTH = 3
    MAX_MSGS = 1
    MAX_TIMERS = 1

    def init(self, rng_key):
        z = jnp.zeros((self.NUM_NODES,), jnp.int32)
        return {"ticks": z, "rx": z}

    def on_timer(self, nodes, node, timer_id, now_us, rand_u32):
        outbox = self.empty_outbox()
        is_tick = timer_id == 1
        nodes = {**nodes, "ticks": set_at(
            nodes["ticks"], node, nodes["ticks"][node] + 1, is_tick)}
        outbox = set_timer_if(outbox, 0, jnp.bool_(True), TICK_US, 1)
        pay = make_payload(self.PAYLOAD_WIDTH, 1, nodes["ticks"][node])
        outbox = send_if(outbox, 0, is_tick & (node == 0),
                         self.NUM_NODES - 1, pay)
        return nodes, outbox

    def on_message(self, nodes, node, src, payload, now_us, rand_u32):
        nodes = {**nodes, "rx": set_at(
            nodes["rx"], node, nodes["rx"][node] + 1)}
        return nodes, self.empty_outbox()


def _only_kind(**kind_flags) -> FaultPlan:
    return FaultPlan(n_faults=1, allow_partition=False, allow_kill=False,
                     **WINDOW, **kind_flags)


# -- pause/resume: deferral semantics vs a host oracle -----------------------


def test_pause_defers_and_preserves_state():
    """Host-oracle differential: a Python walk of the replay trace that
    implements the documented pause semantics (fault events always
    apply; a handler event whose target is paused at pop time is
    deferred — skipped now, re-delivered at the resume instant; the
    horizon-hit final pop is never processed) must predict the final
    counters exactly. Seed 0 defers 9 events through its window."""
    eng = Engine(TickMachine(), EngineConfig(
        horizon_us=HORIZON_US, queue_capacity=32,
        faults=_only_kind(allow_pause=True)))
    rp = replay(eng, 0, max_steps=400)
    assert not rp.failed
    paused = {}
    expect = {"ticks": [0] * 3, "rx": [0] * 3}
    deferred = 0
    window = None
    for ev in rp.trace:
        if ev.time_us >= HORIZON_US:
            continue  # popped at the horizon: recorded but not processed
        if ev.kind == "fault":
            if ev.payload[0] == F_PAUSE:
                paused[ev.payload[1]] = ev.payload[2]
                window = (ev.time_us, ev.payload[2], ev.payload[1])
            elif ev.payload[0] == F_RESUME:
                paused[ev.payload[1]] = 0
            continue
        if paused.get(ev.node, 0) > ev.time_us:
            deferred += 1  # frozen target: nothing processed, nothing lost
            continue
        if ev.kind == "timer" and ev.payload[0] == 1:
            expect["ticks"][ev.node] += 1
        if ev.kind == "msg":
            expect["rx"][ev.node] += 1
    assert deferred > 0, "pause window deferred nothing — test is vacuous"
    assert rp.state.nodes["ticks"].tolist() == expect["ticks"]
    assert rp.state.nodes["rx"].tolist() == expect["rx"]

    # pause froze, not killed: every deferred event re-delivers AT the
    # resume instant (state survived; nothing was dropped)
    t0, resume, pn = window
    in_window = [ev for ev in rp.trace
                 if ev.kind != "fault" and ev.node == pn
                 and t0 < ev.time_us < resume]
    redelivered = [ev for ev in rp.trace
                   if ev.kind != "fault" and ev.node == pn
                   and ev.time_us == resume]
    assert in_window and redelivered


# -- clock skew: timer stretch/compress vs a host oracle ---------------------


def test_skew_scales_timer_delays_exactly():
    """Host-oracle differential: while a skew window is active on a
    node, every timer it arms lands at t + scaled(TICK) where scaled is
    the documented exact-int32 q10 arithmetic — the oracle predicts
    every timer arrival from the fault events alone."""
    eng = Engine(TickMachine(), EngineConfig(
        horizon_us=HORIZON_US, queue_capacity=32,
        faults=_only_kind(allow_skew=True)))
    rp = replay(eng, 0, max_steps=400)
    assert not rp.failed
    skew = {}
    expected_next = {}
    scaled_arms = 0
    for ev in rp.trace:
        if ev.kind == "fault":
            if ev.payload[0] == F_SKEW:
                skew[ev.payload[1]] = ev.payload[2]
            elif ev.payload[0] == F_SKEW_END:
                skew[ev.payload[1]] = 0
            continue
        if ev.kind != "timer":
            continue
        if ev.node in expected_next:
            assert ev.time_us == expected_next[ev.node], ev
        if ev.time_us >= HORIZON_US:
            continue  # horizon pop: processed nothing, armed nothing
        q = skew.get(ev.node, 0)
        d = TICK_US if q == 0 else (
            (TICK_US >> 10) * q + (((TICK_US & 1023) * q) >> 10))
        if q:
            scaled_arms += 1
        expected_next[ev.node] = ev.time_us + d
    assert scaled_arms > 0, "skew window scaled nothing — test is vacuous"


# -- message duplication: at-least-once chaos --------------------------------


def test_dup_duplicates_delivered_messages():
    """With dup on, the same seed runs the identical tick schedule (the
    dup words ride the TAIL of the RNG block — original latencies are
    untouched) plus Bernoulli duplicates: the msg_count delta vs the
    dup-off run equals the flight recorder's dup counter, and the
    receiver observes the extra deliveries."""
    fp = FaultPlan(n_faults=0, allow_partition=False, allow_kill=False)
    cfg_off = EngineConfig(horizon_us=HORIZON_US, queue_capacity=48,
                           faults=fp, flight_recorder=True)
    cfg_on = dataclasses.replace(
        cfg_off, faults=dataclasses.replace(fp, allow_dup=True))
    r_off = replay(Engine(TickMachine(), cfg_off), 0, max_steps=400, trace=False)
    r_on = replay(Engine(TickMachine(), cfg_on), 0, max_steps=400, trace=False)
    dups = int(r_on.state.fr["dup"])
    assert dups > 0
    assert int(r_on.state.msg_count) - int(r_off.state.msg_count) == dups
    # identical base schedule, strictly more deliveries at the receiver
    assert r_on.state.nodes["ticks"].tolist() == r_off.state.nodes["ticks"].tolist()
    assert int(r_on.state.nodes["rx"][2]) > int(r_off.state.nodes["rx"][2])


# -- crash-with-amnesia: the durable-state contract --------------------------


class VolatileCommitRaft(RaftMachine):
    PERSIST_COMMIT_NOT_LOG = True


class DupVoteRaft(RaftMachine):
    DUP_VOTE_COUNT = True


def test_strict_restart_catches_volatile_commit_bug():
    """The acceptance scenario: a raft whose durable_spec persists its
    commitIndex but not the log backing it. Under plain restarts the
    model's hand-written hook hides the lie; under strict_restart the
    contract IS the restart semantics, and the first restart after any
    commit leaves commit pointing at a wiped log — caught by the
    EXISTING LogMatching checker (code 102). The honest machine under
    the identical chaos stays clean."""
    cfg = EngineConfig(
        horizon_us=3_000_000, queue_capacity=64,
        faults=FaultPlan(n_faults=2, t_max_us=1_800_000,
                         dur_min_us=100_000, dur_max_us=600_000,
                         strict_restart=True))
    seeds = jnp.arange(32, dtype=jnp.uint32)
    bug = Engine(VolatileCommitRaft(num_nodes=5, log_capacity=8), cfg)
    r = jax.jit(lambda s: bug.run_batch(s, 1500))(seeds)
    codes = {int(c) for c, f in zip(r.fail_code.tolist(), r.failed.tolist()) if f}
    assert codes == {102}, codes
    honest = Engine(RaftMachine(num_nodes=5, log_capacity=8), cfg)
    rh = jax.jit(lambda s: honest.run_batch(s, 1500))(seeds)
    assert int(rh.failed.sum()) == 0


def test_strict_restart_requires_durable_spec():
    from madsim_tpu.models.echo import EchoMachine

    with pytest.raises(ValueError, match="durable_spec"):
        Engine(EchoMachine(rounds=4), EngineConfig(
            queue_capacity=32,
            faults=FaultPlan(n_faults=1, strict_restart=True)))


@pytest.mark.slow
def test_dup_chaos_catches_duplicate_vote_tally():
    """The bug dup chaos found in this repo's own raft the day it was
    turned on: a per-message vote tally (DupVoteRaft) lets a duplicated
    grant elect two leaders in one term (ELECTION_SAFETY, 101); the
    fixed tally (granted-voter bitmask) is dup-safe."""
    cfg = EngineConfig(
        horizon_us=1_000_000, queue_capacity=96,
        faults=FaultPlan(n_faults=2, t_max_us=600_000, dur_min_us=100_000,
                         dur_max_us=800_000, allow_dup=True))
    seeds = jnp.arange(64, dtype=jnp.uint32)
    buggy = Engine(DupVoteRaft(num_nodes=5, log_capacity=8), cfg)
    r = jax.jit(lambda s: buggy.run_batch(s, 600))(seeds)
    codes = {int(c) for c, f in zip(r.fail_code.tolist(), r.failed.tolist()) if f}
    assert codes == {101}, codes
    fixed = Engine(RaftMachine(num_nodes=5, log_capacity=8), cfg)
    rf = jax.jit(lambda s: fixed.run_batch(s, 600))(seeds)
    assert int(rf.failed.sum()) == 0


# -- torn/lost-write storage faults (PR-6) -----------------------------------


class TornToy(Machine):
    """Four-leaf machine exercising every torn atomicity class."""

    NUM_NODES = 3
    PAYLOAD_WIDTH = 3

    def init(self, rng_key):
        n = self.NUM_NODES
        return {
            "atomic": jnp.zeros((n,), jnp.int32),
            "lost": jnp.zeros((n,), jnp.int32),
            "ring": jnp.zeros((n, 4), jnp.int32),
            "vol": jnp.zeros((n,), jnp.int32),
        }

    def durable_spec(self):
        return {"atomic": True, "lost": True, "ring": True, "vol": False}

    def torn_spec(self):
        from madsim_tpu.engine.machine import TORN_ATOMIC, TORN_LOSE, TORN_PREFIX

        return {"atomic": TORN_ATOMIC, "lost": TORN_LOSE,
                "ring": TORN_PREFIX, "vol": TORN_ATOMIC}

    def on_timer(self, nodes, node, timer_id, now_us, rand_u32):
        return nodes, self.empty_outbox()

    def on_message(self, nodes, node, src, payload, now_us, rand_u32):
        return nodes, self.empty_outbox()


def test_torn_restart_damages_by_contract():
    """torn_restart_if unit: volatile leaves wipe (amnesia), TORN_ATOMIC
    rows survive, TORN_LOSE rows revert whole iff the seeded coin says
    so, TORN_PREFIX rows keep exactly the seeded prefix of the trailing
    axis — all damage a pure function of (torn_seed, leaf position),
    untouched rows bit-identical."""
    from madsim_tpu.engine.machine import torn_hash

    m = TornToy()
    key = jax.random.PRNGKey(0)
    nodes = {
        "atomic": jnp.asarray([11, 12, 13], jnp.int32),
        "lost": jnp.asarray([21, 22, 23], jnp.int32),
        "ring": jnp.arange(1, 13, dtype=jnp.int32).reshape(3, 4),
        "vol": jnp.asarray([31, 32, 33], jnp.int32),
    }
    seed = jnp.uint32(0xDEADBEEF)
    out = m.torn_restart_if(nodes, jnp.int32(1), jnp.bool_(True), key, seed)
    # dict flatten order: atomic=0, lost=1, ring=2, vol=3
    h_lost = int(torn_hash(seed, 1))
    h_ring = int(torn_hash(seed, 2))
    lost_expect = 0 if (h_lost & 1) == 1 else 22
    cut = (h_ring >> 1) % 5  # keep ring[1, :cut], lose the suffix
    assert out["atomic"].tolist() == [11, 12, 13]  # atomic survives
    assert out["vol"].tolist() == [31, 0, 33]  # volatile wiped
    assert out["lost"].tolist() == [21, lost_expect, 23]
    expect_ring = [5, 6, 7, 8]
    for k in range(cut, 4):
        expect_ring[k] = 0
    assert out["ring"][1].tolist() == expect_ring, (cut, out["ring"].tolist())
    assert out["ring"][0].tolist() == [1, 2, 3, 4]  # other rows untouched
    assert out["ring"][2].tolist() == [9, 10, 11, 12]
    # deterministic: same inputs, same damage
    out2 = m.torn_restart_if(nodes, jnp.int32(1), jnp.bool_(True), key, seed)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()), out, out2))
    # cond off: bit-identical passthrough
    out3 = m.torn_restart_if(nodes, jnp.int32(1), jnp.bool_(False), key, seed)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()), nodes, out3))


def test_torn_requires_durable_spec_and_valid_torn_spec():
    from madsim_tpu.models.echo import EchoMachine

    with pytest.raises(ValueError, match="durable_spec"):
        Engine(EchoMachine(rounds=4), EngineConfig(
            queue_capacity=32,
            faults=FaultPlan(n_faults=1, allow_torn=True)))

    class BadTornSpec(TornToy):
        def torn_spec(self):
            return {"atomic": 1, "lost": 99, "ring": 1, "vol": 1}

    with pytest.raises(ValueError, match="torn_spec"):
        Engine(BadTornSpec(), EngineConfig(
            queue_capacity=32,
            faults=FaultPlan(n_faults=1, allow_torn=True)))


def test_torn_catches_tornsnapshot_raft():
    """The acceptance scenario: a raft-with-compaction whose snapshot
    file write is not fsynced (TornSnapshotRaftCompact.torn_spec marks
    snap_idx/snap_term TORN_LOSE). A torn restart keeps the trimmed log
    but loses the snapshot; the node's first re-commit stands on
    positions neither stored nor attested — caught by the
    compaction-aware LogMatching checker (code 102), and a flagged seed
    replays bit-identically on the host path. (The honest machine's
    clean run under the identical — and wider — chaos is asserted in
    test_new_chaos_kinds_live_and_observable and in the slow soak,
    keeping tier-1 to one compile here.)"""
    from madsim_tpu.models.raft_compact import TornSnapshotRaftCompact

    cfg = EngineConfig(
        horizon_us=4_000_000, queue_capacity=64,
        faults=FaultPlan(n_faults=3, t_max_us=1_800_000,
                         dur_min_us=100_000, dur_max_us=600_000,
                         allow_partition=False, allow_kill=False,
                         allow_torn=True, strict_restart=True))
    seeds = jnp.arange(48, dtype=jnp.uint32)
    bug = Engine(TornSnapshotRaftCompact(num_nodes=5, log_capacity=8), cfg)
    r = jax.jit(lambda s: bug.run_batch(s, 4000))(seeds)
    fails = [int(s) for s, f in zip(r.seeds.tolist(), r.failed.tolist()) if f]
    codes = {int(c) for c, f in zip(r.fail_code.tolist(), r.failed.tolist()) if f}
    assert fails and codes == {102}, (fails, codes)
    rp = replay(bug, fails[0], max_steps=4000, trace=False)
    assert rp.failed and rp.fail_code == 102


def test_raft_bitmask_node_cap_is_loud():
    """The granted-voter bitmask (int32) silently wraps past 31 nodes;
    both raft variants must refuse loudly instead."""
    from madsim_tpu.models.raft import RaftMachine
    from madsim_tpu.models.raft_compact import RaftCompactMachine

    with pytest.raises(ValueError, match="<= 31"):
        RaftMachine(num_nodes=32)
    with pytest.raises(ValueError, match="<= 31"):
        RaftCompactMachine(num_nodes=32)
    RaftMachine(num_nodes=31)  # the boundary itself is fine
    with pytest.raises(ValueError, match="compact_lag"):
        RaftCompactMachine(num_nodes=5, log_capacity=8, compact_lag=9)


@pytest.mark.slow
def test_torn_hunt_shrinks_to_minimal_kinds_and_honest_soaks_clean():
    """Acceptance end-to-end: a torn-vocabulary hunt finds
    demo-tornsnapshot-raft, the shrunk minimal kind set still includes
    `torn` (ablating strict_restart is fine — the torn restart IS the
    contract wipe), and the honest raft_compact survives a full
    11-kind chaos-palette soak clean."""
    import importlib

    from madsim_tpu.models.raft_compact import (
        RaftCompactMachine,
        TornSnapshotRaftCompact,
    )

    shrink_mod = importlib.import_module("madsim_tpu.engine.shrink")
    cfg = EngineConfig(
        horizon_us=4_000_000, queue_capacity=64,
        faults=FaultPlan(n_faults=3, t_max_us=1_800_000,
                         dur_min_us=100_000, dur_max_us=600_000,
                         allow_partition=False, allow_kill=False,
                         allow_torn=True, strict_restart=True))
    bug = Engine(TornSnapshotRaftCompact(num_nodes=5, log_capacity=8), cfg)
    seeds = jnp.arange(64, dtype=jnp.uint32)
    r = jax.jit(lambda s: bug.run_batch(s, 4000))(seeds)
    fails = [int(s) for s, f in zip(r.seeds.tolist(), r.failed.tolist()) if f]
    assert fails
    sr = shrink_mod.shrink(bug, fails[0], max_steps=4000)
    assert sr.fail_code == 102
    assert sr.shrunk.faults.allow_torn, "shrink ablated the load-bearing kind"
    assert "torn" not in sr.kinds_removed

    soak = EngineConfig(
        horizon_us=4_000_000, queue_capacity=96, packet_loss_rate=0.01,
        faults=FaultPlan(
            n_faults=3, t_max_us=2_400_000, dur_min_us=100_000,
            dur_max_us=600_000, allow_dir_clog=True, allow_group=True,
            allow_storm=True, allow_delay=True, allow_pause=True,
            allow_skew=True, allow_dup=True, allow_torn=True,
            allow_heal_asym=True, strict_restart=True))
    honest = Engine(RaftCompactMachine(num_nodes=5, log_capacity=8), soak)
    rh = jax.jit(lambda s: honest.run_batch(s, 4000))(
        jnp.arange(128, dtype=jnp.uint32))
    assert int(rh.failed.sum()) == 0, set(
        int(c) for c, f in zip(rh.fail_code.tolist(), rh.failed.tolist()) if f)


# -- asymmetric partition healing (PR-6) -------------------------------------


class BidiTickMachine(TickMachine):
    """TickMachine with traffic in BOTH directions between nodes 0 and
    2, so one-way clog windows are observable from the delivery trace."""

    def on_timer(self, nodes, node, timer_id, now_us, rand_u32):
        outbox = self.empty_outbox()
        is_tick = timer_id == 1
        nodes = {**nodes, "ticks": set_at(
            nodes["ticks"], node, nodes["ticks"][node] + 1, is_tick)}
        outbox = set_timer_if(outbox, 0, jnp.bool_(True), TICK_US, 1)
        pay = make_payload(self.PAYLOAD_WIDTH, 1, nodes["ticks"][node])
        peer = jnp.where(node == 0, self.NUM_NODES - 1, 0)
        outbox = send_if(outbox, 0, is_tick & ((node == 0) | (node == 2)),
                         peer, pay)
        return nodes, outbox


def test_heal_asym_one_way_window():
    """Replay-trace oracle for asymmetric healing, pinned seed 4: the
    fault clogs pair (0, 2) both ways at t0, heals 2->0 at t1, then
    0->2 at t2 > t1. With the engine's latency bounds [1ms, 10ms) a
    delivery at time d was sent in (d-10ms, d-1ms], so: no 0->2
    delivery may land in [t0+10ms, t2+1ms) (sent while that direction
    was clogged), 2->0 deliveries MUST reappear inside the one-way
    window [t1+10ms, t2] while 0->2 is still dark, and both directions
    flow again after t2+10ms."""
    eng = Engine(BidiTickMachine(), EngineConfig(
        horizon_us=HORIZON_US, queue_capacity=32,
        faults=_only_kind(allow_heal_asym=True)))
    rp = replay(eng, 4, max_steps=600)
    assert not rp.failed
    from madsim_tpu.engine.core import F_HASYM, F_HASYM_HEAL

    fault_ops = [(e.time_us, e.payload[0], e.payload[1], e.payload[2])
                 for e in rp.trace if e.kind == "fault"]
    assert len(fault_ops) == 3
    (t0, op0, a, b), (t1, op1, h1a, h1b), (t2, op2, h2a, h2b) = sorted(fault_ops)
    assert op0 == F_HASYM and {op1, op2} == {F_HASYM_HEAL}
    assert (a, b) == (0, 2)
    # the two one-way heals cover both directions, at distinct times
    assert {(h1a, h1b), (h2a, h2b)} == {(0, 2), (2, 0)}
    assert t0 < t1 < t2
    first_heal_dir = (h1a, h1b)
    assert first_heal_dir == (2, 0)  # seed 4: b->a heals first

    lat_min, lat_max = 1_000, 10_000
    msgs = [(e.time_us, e.src, e.node) for e in rp.trace
            if e.kind == "msg" and e.time_us < HORIZON_US]
    send_02 = [t for t, s, n in msgs if (s, n) == (0, 2)]
    send_20 = [t for t, s, n in msgs if (s, n) == (2, 0)]
    # 0->2 stays dark until its own heal at t2 — even through the
    # one-way window where 2->0 is already flowing
    assert not [t for t in send_02 if t0 + lat_max <= t < t2 + lat_min]
    # 2->0 resumes INSIDE the one-way window (the asymmetric signature)
    assert [t for t in send_20 if t1 + lat_max <= t <= t2]
    # and both directions flow again after the second heal
    assert [t for t in send_02 if t > t2 + lat_max]
    assert [t for t in send_20 if t > t2 + lat_max]
    # liveness before the fault, both ways
    assert [t for t in send_02 if t < t0] and [t for t in send_20 if t < t0]


# -- kafka group rebalance under the PR-5 window/dup kinds -------------------


def test_group_rebalance_under_pause_skew_dup():
    """The consumer-group model under the pause/skew/dup vocabulary
    (ROADMAP [scenarios]: kafka_group barely exercised the PR-5 kinds):
    pause windows outlast the session timeout, so members get expired
    and rejoin — rebalances beyond the three joins — while fencing plus
    cumulative commits keep every lane clean; the injection counters
    and the pause/skew/dup coverage bands must all go live."""
    import numpy as np

    from madsim_tpu.engine.core import K_PAUSE, K_SKEW
    from madsim_tpu.models.kafka_group import KafkaGroupMachine
    from madsim_tpu.runtime.coverage import coverage_dict, unpack_map

    cfg = EngineConfig(
        # a paused coordinator defers every heartbeat/fetch targeting it
        # until resume, each parked in its own slot — size the queue for
        # a 500ms window of member traffic
        horizon_us=3_000_000, queue_capacity=192,
        flight_recorder=True, coverage=True, cov_slots_log2=12,
        faults=FaultPlan(
            n_faults=3, t_max_us=2_000_000, dur_min_us=200_000,
            dur_max_us=500_000, allow_partition=False, allow_kill=False,
            allow_pause=True, allow_skew=True, allow_dup=True))
    eng = Engine(KafkaGroupMachine(num_nodes=4, partitions=2, log_len=12), cfg)
    seeds = jnp.arange(32, dtype=jnp.uint32)
    res = jax.jit(lambda s: eng.run_batch(s, 3500))(seeds)
    assert not bool(res.failed.any()), set(res.fail_code.tolist())
    inj = res.fr["inj"].sum(axis=0)
    assert int(inj[K_PAUSE]) > 0 and int(inj[K_SKEW]) > 0, inj.tolist()
    assert int(res.fr["dup"].sum()) > 0
    # pause-expired members force rebalances beyond the three joins
    gens = res.summary["generation"].tolist()
    assert any(g > 3 for g in gens), gens
    m = unpack_map(np.bitwise_or.reduce(np.asarray(res.cov["map"]), axis=0), 12)
    bands = coverage_dict(m, 12, band_bits=4)["by_band"]
    for band in ("pause", "skew", "dup"):
        assert bands[band] > 0, (band, bands)


# -- shrink: fault-kind ablation ---------------------------------------------


def test_shrink_ablates_fault_kinds_to_minimal_set(monkeypatch):
    """The ablation loop (unit, replay stubbed): a failure that needs
    exactly {storm, strict_restart, >=1 fault} should shed dup, kill and
    pair, keep storm and strict, and report the removals."""
    import importlib

    # the engine package re-exports the shrink FUNCTION under the same
    # name as its module — resolve the module explicitly
    shrink_mod = importlib.import_module("madsim_tpu.engine.shrink")

    def fake_replay(engine, seed, max_steps=10_000, trace=True):
        fp = engine.config.faults
        fails = fp.n_faults >= 1 and fp.allow_storm and fp.strict_restart
        st = SimpleNamespace(failed=fails, fail_code=7 if fails else 0,
                             now_us=123_000, step=57)
        return SimpleNamespace(failed=bool(fails),
                               fail_code=7 if fails else 0, state=st)

    monkeypatch.setattr(shrink_mod, "replay", fake_replay)
    eng = Engine(RaftMachine(num_nodes=5, log_capacity=8), EngineConfig(
        queue_capacity=64,
        faults=FaultPlan(n_faults=2, allow_storm=True, allow_dup=True,
                         strict_restart=True)))
    sr = shrink_mod.shrink(eng, seed=5)
    f = sr.shrunk.faults
    assert sr.fail_code == 7 and sr.steps == 57
    assert f.n_faults == 1  # prefix bisect still ran first
    assert f.allow_storm and f.strict_restart  # load-bearing: kept
    assert not (f.allow_dup or f.allow_kill or f.allow_partition)
    assert sr.kinds_removed == ("dup", "kill", "pair")
    assert "kinds -dup,-kill,-pair" in sr.summary()
    assert sr.shrunk.horizon_us == 123_001  # horizon cut still ran after


# -- hunt checkpoint/resume ---------------------------------------------------


def test_checkpoint_roundtrip_and_fingerprint(tmp_path):
    from madsim_tpu.runtime import checkpoint as ck

    args = SimpleNamespace(machine="echo", nodes=0, seed=0, seeds=96,
                           batch=32, max_steps=300, horizon=1.0, loss=0.0,
                           faults=0, fault_tmax=0, fault_kinds="pair,kill",
                           rng_stream=2, strict_restart=False,
                           coverage=False, stop_on_plateau=0)
    path = str(tmp_path / "ck.json")
    assert ck.load_checkpoint(path) is None
    ck.save_checkpoint(path, {
        "fingerprint": ck.fingerprint_from_args(args),
        "batch": 1, "planned": 3, "cursor": 32, "completed": 32,
        "seeds_consumed": 32, "failing": [], "infra": [], "abandoned": [],
        "cov_b64": None, "detector": None, "plateau": False, "done": False,
    })
    loaded = ck.load_checkpoint(path)
    assert loaded["batch"] == 1 and loaded["version"] == ck.CKPT_VERSION
    assert ck.check_fingerprint(loaded, args) is None
    args2 = SimpleNamespace(**{**vars(args), "seeds": 128})
    assert "seeds" in ck.check_fingerprint(loaded, args2)


@pytest.fixture(scope="module")
def echo_engine():
    from madsim_tpu.models.echo import EchoMachine

    return Engine(EchoMachine(rounds=10), EngineConfig(
        horizon_us=1_000_000, queue_capacity=32,
        faults=FaultPlan(n_faults=0)))


def _stream_args(tmp_path, **over):
    d = dict(machine="echo", nodes=0, seed=0, seeds=96, batch=32,
             max_steps=300, horizon=1.0, loss=0.0, faults=0, fault_tmax=0,
             fault_kinds="pair,kill", rng_stream=2, strict_restart=False,
             coverage=False, stop_on_plateau=0, stats=None, stream=True,
             checkpoint=str(tmp_path / "hunt_ck.json"),
             stop_after_batches=0)
    d.update(over)
    return SimpleNamespace(**d)


@pytest.mark.slow
def test_checkpoint_resume_matches_uninterrupted(
        tmp_path, monkeypatch, capsys, echo_engine):
    """Interrupt-after-batch-1 + resume must reproduce the
    uninterrupted run's aggregates exactly, and announce
    'resumed at batch 2/3'. (slow tier: one run_stream compile; the CI
    checkpoint smoke exercises the same path end to end via the CLI —
    tier-1 keeps the pure-host checkpoint units.)"""
    monkeypatch.delenv("MADSIM_TPU_STATS", raising=False)
    from madsim_tpu.__main__ import _stream_batches

    full = _stream_batches(echo_engine, _stream_args(tmp_path, checkpoint=None))
    assert full["batches_run"] >= 2 and full["completed"] >= 96

    part = _stream_batches(
        echo_engine, _stream_args(tmp_path, stop_after_batches=1))
    assert part["batches_run"] == 1
    ckpt = json.load(open(str(tmp_path / "hunt_ck.json")))
    assert ckpt["batch"] == 1 and ckpt["done"] is False

    capsys.readouterr()
    resumed = _stream_batches(echo_engine, _stream_args(tmp_path))
    assert "resumed at batch 2/3" in capsys.readouterr().out
    for key in ("completed", "seeds_consumed", "batches_run",
                "batches_planned"):
        assert resumed[key] == full[key], key
    assert sorted(map(tuple, resumed["failing"])) == sorted(map(tuple, full["failing"]))
    assert resumed["abandoned"] == full["abandoned"]
    ckpt = json.load(open(str(tmp_path / "hunt_ck.json")))
    # streaming refill can overshoot the seed budget: the contract is
    # done=True, not a specific final batch index
    assert ckpt["done"] is True


@pytest.mark.slow
def test_checkpoint_refuses_mismatched_args(tmp_path, monkeypatch, echo_engine):
    monkeypatch.delenv("MADSIM_TPU_STATS", raising=False)
    from madsim_tpu.__main__ import _stream_batches

    _stream_batches(
        echo_engine, _stream_args(tmp_path, stop_after_batches=1))
    with pytest.raises(SystemExit, match="seeds"):
        _stream_batches(echo_engine, _stream_args(tmp_path, seeds=128))


# -- transient-dispatch retry -------------------------------------------------


def test_retry_transient_unit():
    from madsim_tpu._dispatch_retry import retry_transient

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("UNAVAILABLE: fake backend blip")
        return 42

    sleeps = []
    assert retry_transient(flaky, attempts=3, sleep=sleeps.append) == 42
    assert len(calls) == 3
    assert sleeps == [0.25, 0.5]  # exponential backoff

    def wrong():
        raise ValueError("INVALID_ARGUMENT: not transient")

    with pytest.raises(ValueError):  # propagates immediately, no retry
        retry_transient(wrong, sleep=lambda s: None)

    def always():
        raise RuntimeError("DEADLINE_EXCEEDED: poll")

    with pytest.raises(RuntimeError, match="failed after 2 attempts"):
        retry_transient(always, attempts=2, sleep=lambda s: None)


@pytest.mark.slow
def test_run_stream_retries_transient_dispatch(monkeypatch, echo_engine):
    """A one-shot fake transient error on a supersegment dispatch must
    be retried (counted in stats) and the stream still completes. The
    fake raises BEFORE touching the donated carry — the retry-able
    shape; a post-consumption failure propagates (not retried), which
    the donation caveat in _dispatch_retry documents."""
    orig = Engine._stream_executables
    state = {"tripped": False}

    def wrapped(self, quartet, batch, pipelined):
        held, made = orig(self, quartet, batch, pipelined)
        supersegment = held[quartet[2]]  # the executable run_stream calls

        def flaky_super(c, need):
            if not state["tripped"]:
                state["tripped"] = True
                raise RuntimeError("UNAVAILABLE: injected backend blip")
            return supersegment(c, need)

        return {**held, quartet[2]: flaky_super}, made

    monkeypatch.setattr(Engine, "_stream_executables", wrapped)
    out = echo_engine.run_stream(32, batch=32, segment_steps=384, max_steps=300)
    assert out["completed"] >= 32
    assert out["stats"]["dispatch_retries"] == 1
