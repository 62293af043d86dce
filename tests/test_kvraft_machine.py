"""The key/value service on Raft (`models/kvraft.py`, `--machine kvraft`):
MadRaft's lab 3A — five servers on `models/raft.py`'s handlers, five
clerks as nodes of the same lane — under the tester's fault process
(`--churn kv3a`: random repartitions of the servers, then a kill of all
of them at once). Held against its plain reference
(`differential_kvraft`, `churn_reference`), through the engine's part of
it (role-held `[5, ...]` leaves: `RoleRows`), through stream, hunt,
shrink and the corpus. All at a small size on the CPU."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.differential import (
    CHURN_HEAL, CHURN_KILL, CHURN_PARTITION, CHURN_RESTART,
    applied_churn_faults, check_clnt_appends, churn_reference,
    differential_kvraft, kv_value_words,
)
from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
from madsim_tpu.engine.core import CHURN_PRESETS, FR_METRICS_LEN
from madsim_tpu.engine.machine import TORN_LOSE, RoleRows
from madsim_tpu.engine.replay import replay
from madsim_tpu.models import kvraft as K
from madsim_tpu.models.kvraft import KvRaftMachine, LocalGetKvRaft
from madsim_tpu.models.raft import Fig8Raft, RaftMachine, RaftState

GATES = dict(flight_recorder=True, fr_digest_every=32, fr_digest_ring=8,
             coverage=True, cov_slots_log2=12)
UNTIL_US = 1_100_000  # the load window and the kill, at the small size


class NoSessionKvRaft(KvRaftMachine):
    """A test's twin: every committed entry is applied, a retried Append
    that committed twice included."""

    SESSION_DEDUP = False


def _cfg(n_faults=0, churn="kv3a", loss=0.1, horizon_us=3_200_000, **kw):
    """`kvraft5`'s flags at a small size: splits at 0 and ~1.05 s, the
    kill of all five servers at 1.1 s, the lane ends at 3.2 s."""
    gates = {k: kw.pop(k) for k in list(kw) if k in GATES}
    faults = dict(n_faults=n_faults, t_max_us=900_000, dur_min_us=100_000,
                  dur_max_us=400_000, **kw)
    if churn:
        faults.update(churn=CHURN_PRESETS[churn], churn_until_us=UNTIL_US)
    return EngineConfig(
        horizon_us=horizon_us, queue_capacity=48, rng_stream=3,
        packet_loss_rate=loss, latency_min_us=1000, latency_max_us=27000,
        faults=FaultPlan(**faults), **gates,
    )


def _small(cls=KvRaftMachine):
    return cls(log_capacity=32, load_until_us=UNTIL_US)


def _batch(eng, n, max_steps=4000, start=0):
    return eng.make_runner(max_steps=max_steps)(
        jnp.arange(start, start + n, dtype=jnp.uint32))


@pytest.fixture(scope="module")
def sweep():
    """2,048 seeds of the honest machine, 512 of each buggy one."""
    return {
        cls: _batch(Engine.on_xla_step_path(_small(cls), _cfg(**GATES)), n)
        for cls, n in ((KvRaftMachine, 2048), (LocalGetKvRaft, 512),
                       (NoSessionKvRaft, 512))
    }


# -- invariants -----------------------------------------------------------------


def test_the_invariants_hold_over_two_thousand_seeds_under_kv3a(sweep):
    res = sweep[KvRaftMachine]
    assert not bool(res.failed.any()), set(res.fail_code.tolist())
    assert bool(res.done.all()) and bool((res.steps < 4000).all())
    s = res.summary
    assert int(s["log_full"].sum()) == 0 and int(s["log_high_water"].max()) <= 32
    # the load is real: operations acknowledged, appends and gets, through
    # leader search, timeouts and retried requests the session table refused
    assert float(s["ops_acked"].mean()) > 10
    assert int(s["appends_applied"].sum()) > 5000 and int(s["gets_acked"].sum()) > 5000
    assert int(s["dup_refused"].sum()) > 500 and int(s["wrong_leader"].sum()) > 10_000
    assert int(s["clerk_timeouts"].sum()) > 10_000
    # a clerk's acknowledged appends are on every server that applied them
    assert bool((s["acked_len"].sum(axis=1) <= s["appends_applied"]).all())
    # every server replayed its log after the kill: the backlog is the log
    assert int(s["apply_backlog"].max()) >= 10
    # at this size 3.2 s is not enough for every lane's election after the
    # kill; the share the configuration guarantees (99%) is its own
    closed = int(s["closing_gets_acked"].sum())
    assert closed == int(s["finished"].sum()) and closed > 0.9 * 5 * 2048


def test_every_closing_get_is_acknowledged_on_a_quiet_net():
    """No loss, no fault process: every clerk runs its loop, stops at the
    load window and has its closing Get acknowledged."""
    cfg = _cfg(churn=None, loss=0.0, horizon_us=2_500_000)
    res = _batch(Engine.on_xla_step_path(_small(), cfg), 128)
    s = res.summary
    assert not bool(res.failed.any())
    assert bool((s["closing_gets_acked"] == 5).all()) and bool((s["finished"] == 5).all())
    assert int(s["dup_refused"].sum()) == 0 == int(s["log_full"].sum())
    # one entry a heartbeat: some 10-20 operations a lane-second
    assert 10 < float(s["ops_acked"].mean()) < 45


def test_localget_is_convicted_by_stale_get_and_replays(sweep):
    res = sweep[LocalGetKvRaft]
    failed = np.asarray(res.failed)
    assert 50 < int(failed.sum()) < 512
    assert set(np.asarray(res.fail_code)[failed].tolist()) == {K.STALE_GET}
    assert not bool(sweep[KvRaftMachine].failed[:512].any())  # the same seeds
    seed = int(res.seeds[failed][0])
    eng = Engine.on_xla_step_path(_small(LocalGetKvRaft), _cfg(**GATES))
    rp = replay(eng, seed, max_steps=4000, trace=False)
    assert rp.failed and rp.fail_code == K.STALE_GET
    assert int(rp.state.step) == int(res.steps[failed][0])


def test_no_session_table_is_convicted_by_append_order(sweep):
    """Without the session table a retried Append that committed twice is
    applied twice: the key's applied sequence is not 0, 1, 2, ... (171)."""
    res = sweep[NoSessionKvRaft]
    failed = np.asarray(res.failed)
    assert 20 < int(failed.sum()) < 512
    assert set(np.asarray(res.fail_code)[failed].tolist()) == {K.APPEND_ORDER}
    # where the honest machine refused a duplicate Append, the twin fails
    assert int(sweep[KvRaftMachine].summary["dup_refused"][:512].sum()) >= int(failed.sum())


# -- the plain reference ----------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_differential_agrees_event_for_event_without_faults(seed):
    eng = Engine.on_xla_step_path(
        _small(), _cfg(churn=None, loss=0.0, horizon_us=2_000_000))
    out = differential_kvraft(eng, seed, max_steps=4000)
    assert out["ok"], out["mismatches"]
    assert not out["replay_failed"] and out["refused"] == 0
    # five servers applied every command, every clerk closed
    assert out["applies"] >= 5 * out["replies"] > 50
    assert out["closing_values"] == 5 == out["counters"]["closing_gets_acked"]
    assert sum(out["acked_appends"]) == out["counters"]["appends_applied"]


def test_differential_agrees_under_splits_and_the_whole_cluster_kill():
    """Seeds under loss, two splits and the kill of all five servers: every
    apply (the replay of the log after the restart among them), every
    answer a clerk accepted and the closing values are the plain store's."""
    eng = Engine.on_xla_step_path(_small(), _cfg())
    refused = closed = 0
    for seed in (3, 4, 5):
        out = differential_kvraft(eng, seed, max_steps=4000)
        assert out["ok"], (seed, out["mismatches"])
        assert out["replies"] >= 5 and out["applies"] > 5 * out["replies"]
        refused += out["refused"]
        closed += out["closing_values"]
    assert refused > 0 and closed > 0


def test_differential_convicts_the_local_get(sweep):
    res = sweep[LocalGetKvRaft]
    seed = int(res.seeds[np.asarray(res.failed)][0])
    eng = Engine.on_xla_step_path(_small(LocalGetKvRaft), _cfg())
    out = differential_kvraft(eng, seed, max_steps=4000)
    assert out["replay_failed"] and out["fail_code"] == K.STALE_GET and not out["ok"]
    assert any("applied no such command" in m for m in out["mismatches"]), out["mismatches"]


def test_the_strings_and_the_words_agree():
    value = "x 3 0 yx 3 1 yx 3 2 y"
    h = 0
    for j in range(3):
        h = K.hash_step(h, j)
    assert kv_value_words(value) == (3, h) and kv_value_words("") == (0, 0)
    assert check_clnt_appends(3, value, 3) == []
    assert "missing" in check_clnt_appends(3, value, 4)[0]
    assert "duplicate" in check_clnt_appends(3, value + "x 3 1 y", 3)[0]
    assert "wrong order" in check_clnt_appends(3, "x 3 1 yx 3 0 y", 2)[0]
    cmd = K.pack_cmd(K.OP_APPEND, 4, 17, 9)
    assert K.unpack_cmd(cmd) == (K.OP_APPEND, 4, 17, 9) and 0 < cmd < 2**31


# -- the fault process ------------------------------------------------------------


def test_churn_reference_equals_the_lanes_applied_faults_for_kv3a():
    m = _small()
    eng = Engine.on_xla_step_path(m, _cfg())
    plan = eng.config.faults.churn
    for seed in (11, 12):
        ref = churn_reference(
            seed, plan, None, n=10, until_us=UNTIL_US,
            horizon_us=eng.config.horizon_us, nodes=m.churn_nodes())
        assert ref == applied_churn_faults(eng, seed, max_steps=4000)
        ops = [op for _t, op, _x in ref]
        # tick 0 at t = 0, one more before the end, then heal, five kills
        # at that instant and five restarts 150 ms later
        assert ops == [CHURN_PARTITION] * 2 + [CHURN_HEAL] + [CHURN_KILL] * 5 \
            + [CHURN_RESTART] * 5
        assert ref[0][0] == 0 and 1_000_000 <= ref[1][0] < 1_200_000
        assert {t for t, op, _x in ref if op == CHURN_KILL} == {UNTIL_US}
        assert {t for t, op, _x in ref if op == CHURN_RESTART} == {UNTIL_US + 150_000}
        # a split is over the servers alone: the clerks are on no side
        assert all(x < 32 for _t, op, x in ref if op == CHURN_PARTITION)


def test_churn_reference_still_equals_the_lanes_applied_faults_for_fig8():
    cfg = dataclasses.replace(
        _cfg(churn="fig8", horizon_us=1_500_000), queue_capacity=40)
    eng = Engine.on_xla_step_path(RaftMachine(5, 64), cfg)
    leaders = {}
    applied = applied_churn_faults(
        eng, 21, max_steps=3000,
        on_tick=lambda tick, _t, before: leaders.__setitem__(tick, before))

    def leader_at(_t, tick, connected):
        lead = np.asarray(connected) & (np.asarray(leaders[tick].nodes.role) == 2)
        return int(np.argmax(lead)) if lead.any() else -1

    ref = churn_reference(
        21, eng.config.faults.churn, leader_at, n=5, until_us=UNTIL_US,
        horizon_us=cfg.horizon_us)
    assert ref == applied and len(ref) >= 4


def test_a_split_clogs_server_links_only_and_the_kill_takes_all_five():
    from madsim_tpu.engine.core import F_CHURN_HEAL, F_CHURN_RESTART, F_CHURN_TICK

    eng = Engine.on_xla_step_path(_small(), _cfg(**GATES))
    seen = {"ticks": 0, "restarts": []}

    def hook(ev, state):
        if ev.kind != "fault":
            return
        rows = np.asarray(state.clogged)[:, 0]
        killed = np.asarray(state.killed)
        if ev.payload[0] == F_CHURN_TICK:
            seen["ticks"] += 1
            sides = int(state.churn["last"][0])
            for i in range(10):
                want = 0 if i >= 5 else (
                    (~sides & 31) if (sides >> i) & 1 else sides)
                assert int(rows[i]) == want, (i, rows)
            assert not killed.any()
        elif ev.payload[0] == F_CHURN_HEAL:
            assert not rows.any() and killed.tolist() == [True] * 5 + [False] * 5
        elif ev.payload[0] == F_CHURN_RESTART:
            seen["restarts"].append((ev.time_us, ev.payload[1]))
            assert not killed[ev.payload[1]]

    rp = replay(eng, 31, max_steps=4000, on_step=hook, trace=False)
    assert seen["ticks"] == 2
    assert seen["restarts"] == [(UNTIL_US + 150_000, i) for i in range(5)]
    book = rp.state.churn
    assert (int(book["partitions"]), int(book["crashes"]), int(book["ticks"])) == (2, 1, 2)
    assert not rp.failed and not np.asarray(rp.state.killed).any()


# -- role-held leaves: RoleRows ------------------------------------------------------


def _mid_run_state(m, cfg, seed=2, steps=500):
    eng = Engine.on_xla_step_path(m, cfg)
    return eng, replay(eng, seed, max_steps=steps, trace=False).state.nodes


def test_no_leaf_has_the_lanes_node_axis():
    m = _small()
    spec = jax.tree.leaves(m.lane_spec())
    shapes = jax.tree.leaves(jax.eval_shape(m.init, jnp.zeros((2,), jnp.uint32)))
    assert len(spec) == len(shapes)
    for held, leaf in zip(spec, shapes):
        if isinstance(held, RoleRows):
            assert held.count == 5 and held.first in (0, 5)
            # a row a node of the role, or the rows end to end in one flat axis
            assert leaf.shape == (5 * held.width,) if held.width else leaf.shape[0] == 5
        else:
            assert held is True
        assert leaf.shape[:1] != (10,)
    # the service's tables and the clerks' records are flat: 2-D a batch
    flat = {f: getattr(m.lane_spec(), f).width for f in ("svc", "clk")}
    assert flat == {"svc": 5 * K.CELL, "clk": K.REC}
    assert m.raft.NUM_NODES == 5 and m.NUM_NODES == 10 and m.churn_nodes() == (0, 1, 2, 3, 4)


def test_the_generic_restarts_reach_a_roles_rows():
    """`_wipe_node_if`, the amnesia wipe and the torn restart treat row
    `i - first` of a RoleRows leaf as they treat row i of a node leaf."""
    m = _small()
    _eng, nodes = _mid_run_state(m, _cfg(churn=None, loss=0.0))
    key, yes = jnp.zeros((2,), jnp.uint32), jnp.bool_(True)
    assert int(nodes.last_applied[1]) > 0 and int(nodes.seq[1]) > 0
    assert int(nodes.raft.log_len[1]) > 0 and int(nodes.kv_len[1].sum()) > 0

    strict = m.amnesia_restart_if(nodes, 1, yes, key)  # server 1
    plain = m.restart_if(nodes, 1, yes, key)
    for a, b in zip(jax.tree.leaves(strict), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(strict.last_applied[1]) == 0 == int(strict.kv_len[1].sum())
    assert int(strict.raft.commit[1]) == 0
    # durable: the log, terms and commands; and the other servers' rows
    assert np.array_equal(np.asarray(strict.raft.log_cmd), np.asarray(nodes.raft.log_cmd))
    assert np.array_equal(np.asarray(strict.last_applied[2:]), np.asarray(nodes.last_applied[2:]))
    # a clerk (node 6 = row 1 of the clerks' leaves) loses nothing
    clerk = m.amnesia_restart_if(nodes, 6, yes, key)
    for a, b in zip(jax.tree.leaves(clerk), jax.tree.leaves(nodes)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the plain wipe copies a fresh row: server 1's durable log goes too
    wiped = m._wipe_node_if(nodes, 1, yes, key)
    assert int(wiped.raft.log_len[1]) == 0 and int(wiped.raft.log_len[2]) > 0
    assert np.array_equal(np.asarray(wiped.seq), np.asarray(nodes.seq))
    wiped = m._wipe_node_if(nodes, 6, yes, key)
    assert int(wiped.seq[1]) == 0 and int(wiped.seq[0]) == int(nodes.seq[0])
    assert np.array_equal(np.asarray(wiped.raft.log_len), np.asarray(nodes.raft.log_len))

    class LossyVote(KvRaftMachine):
        def torn_spec(self):
            spec = jax.tree.map(lambda _d: 1, self.durable_spec())
            return spec.replace(raft=spec.raft.replace(voted_for=TORN_LOSE))

    t = LossyVote(log_capacity=32, load_until_us=UNTIL_US)
    lost = {int(t.torn_restart_if(nodes, 1, yes, key, jnp.uint32(s)).raft.voted_for[1])
            for s in range(8)}
    assert lost == {-1, int(nodes.raft.voted_for[1])} or int(nodes.raft.voted_for[1]) == -1
    torn = t.torn_restart_if(nodes, 1, yes, key, jnp.uint32(3))
    assert int(torn.last_applied[1]) == 0  # the volatile part goes as under amnesia
    assert np.array_equal(np.asarray(torn.raft.voted_for[2:]), np.asarray(nodes.raft.voted_for[2:]))


def test_kills_strict_restarts_and_torn_restarts_keep_the_invariants():
    """Scheduled kills of servers and of clerks beside the fault process;
    `--strict-restart` is bit-identical to the machine's own restart."""
    runs = {}
    for name, kw in (("plain", {}), ("strict", {"strict_restart": True}),
                     ("torn", {"allow_torn": True})):
        cfg = _cfg(n_faults=2, allow_kill=True, allow_partition=False, **kw)
        runs[name] = _batch(Engine.on_xla_step_path(_small(), cfg), 128)
        assert not bool(runs[name].failed.any()), (name, set(runs[name].fail_code.tolist()))
        assert int(runs[name].summary["ops_acked"].sum()) > 500
    for field in ("steps", "now_us", "msg_count"):
        assert bool((getattr(runs["plain"], field) == getattr(runs["strict"], field)).all())
    for k, v in runs["plain"].summary.items():
        assert np.array_equal(np.asarray(v), np.asarray(runs["strict"].summary[k])), k


def test_the_engine_holds_a_machine_to_its_role_rows():
    class Short(KvRaftMachine):
        def lane_spec(self):
            return super().lane_spec().replace(clk=RoleRows(5, 4, K.REC))

    with pytest.raises(ValueError, match="clk"):
        Engine.on_xla_step_path(Short(), _cfg())

    class Rows(KvRaftMachine):  # a flat leaf declared as rows
        def lane_spec(self):
            return super().lane_spec().replace(svc=RoleRows(0, 5))

    with pytest.raises(ValueError, match="svc"):
        Engine.on_xla_step_path(Rows(), _cfg())

    class Outside(KvRaftMachine):
        def lane_spec(self):
            return super().lane_spec().replace(clk=RoleRows(6, 5, K.REC))

    with pytest.raises(ValueError, match="outside"):
        Engine.on_xla_step_path(Outside(), _cfg())

    class Unheld(KvRaftMachine):
        def lane_spec(self):
            return super().lane_spec().replace(counters=False)

    with pytest.raises(ValueError, match="counters"):
        Engine.on_xla_step_path(Unheld(), _cfg())


# -- models/raft.py keeps its behaviour ------------------------------------------------


def _pin(machine, cfg, n=64):
    res = _batch(Engine.on_xla_step_path(machine, cfg), n, max_steps=1500)
    h = hashlib.sha256()
    for name in ("steps", "now_us", "msg_count", "fail_code", "failed"):
        h.update(np.asarray(getattr(res, name)).astype(np.int64).tobytes())
    for k in sorted(res.summary):
        h.update(np.asarray(res.summary[k]).astype(np.int64).tobytes())
    return h.hexdigest()[:16]


# the same three lines read the same on the parent commit (my CPU runs, PR 35)
@pytest.mark.parametrize("name, machine, pin", [
    ("raft", RaftMachine(5, 8), "7e66293de2714ca7"),
    ("raft5_fig8", RaftMachine(5, 64), "8299018f6c81dea9"),
    ("demo-fig8-raft", Fig8Raft(5, 64), "d5eb6580dd6e7316"),
])
def test_the_peer_set_and_command_word_refactor_moves_no_bit_of_raft(name, machine, pin):
    gates = dict(flight_recorder=True, coverage=True)
    if name == "raft":
        cfg = EngineConfig(
            horizon_us=2_000_000, queue_capacity=40, rng_stream=3,
            faults=FaultPlan(n_faults=2, t_max_us=1_200_000, dur_min_us=100_000,
                             dur_max_us=800_000, allow_partition=True, allow_kill=True),
            **gates)
    else:
        cfg = EngineConfig(
            horizon_us=1_500_000, queue_capacity=40, rng_stream=3,
            packet_loss_rate=0.1, latency_min_us=1000, latency_max_us=27000,
            faults=FaultPlan(n_faults=0, churn=CHURN_PRESETS["fig8"],
                             churn_until_us=1_200_000), **gates)
    assert _pin(machine, cfg) == pin
    state = machine.init(jnp.zeros((2,), jnp.uint32))
    assert type(state) is RaftState and not hasattr(state, "log_cmd")
    assert machine.PAYLOAD_WIDTH == 6 and machine.CLIENT_TIMER and not machine.LOG_COMMANDS


def test_the_service_runs_rafts_handlers_with_commands_in_the_log():
    m = _small()
    assert isinstance(m.raft, RaftMachine) and m.raft.LOG_COMMANDS and not m.raft.CLIENT_TIMER
    for name in ("on_timer", "on_message", "invariant", "restart_if"):
        assert getattr(type(m.raft), name) is getattr(RaftMachine, name)
    _eng, nodes = _mid_run_state(m, _cfg(churn=None, loss=0.0), steps=800)
    top = int(nodes.raft.commit.min())
    assert top >= 5
    # LogMatching over commands: the committed prefixes agree word for word
    cmds = np.asarray(nodes.raft.log_cmd)[:, 1:top + 1]
    assert (cmds == cmds[0]).all() and (cmds > 0).all()
    ops, clerks, seqs, _js = K.unpack_cmd(cmds[0])
    assert set(clerks.tolist()) <= set(range(5)) and set(ops.tolist()) <= {0, 1}
    assert (seqs >= 1).all()


def test_a_node_outside_the_peer_set_writes_nothing_in_rafts_handlers():
    """What `kvraft` leans on to hand Raft the events that are not
    Raft's: every write is a row mask over the peers."""
    m = _small()
    _eng, nodes = _mid_run_state(m, _cfg(churn=None, loss=0.0), steps=600)
    raft, rand = nodes.raft, jnp.arange(4, dtype=jnp.uint32) + 7
    outside = jnp.int32(m.servers)
    for mtype in (1, 2, 3, 4):
        payload = jnp.array([mtype, 99, 1, 1, 99, 5, 3], jnp.int32)
        got, _out = jax.jit(m.raft.on_message)(  # traced, as in the step: reads clamp
            raft, outside, jnp.int32(1), payload, jnp.int32(10**6), rand)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(raft)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), mtype
    for tid in (0, 1 + 4 * 1, 2 + 4 * 1, 3 + 4 * 1):
        got, _out = jax.jit(m.raft.on_timer)(
            raft, outside, jnp.int32(tid), jnp.int32(10**6), rand)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(raft)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), tid


# -- stream, totals, entry points -------------------------------------------------------


def test_the_stream_carries_the_machines_and_the_process_totals():
    from madsim_tpu.kinds import FR_CHURN_KV3A_NAMES, FR_CHURN_NAMES
    from madsim_tpu.runtime.metrics import fr_metrics_dict

    eng = Engine.on_xla_step_path(_small(), _cfg(**GATES))
    assert eng._fr_metrics_len == FR_METRICS_LEN + 5 + len(KvRaftMachine.STREAM_COUNTERS)
    out = eng.run_stream(64, batch=32, seed_start=0, max_steps=4000)
    assert out["completed"] == 64 and not out["abandoned"] and not out["failing"]
    fr = out["stats"]["flight_recorder"]
    # tick 0 on every lane, the second (1.0-1.2 s) where it fires before the end
    assert list(fr["churn"]) == list(FR_CHURN_NAMES + FR_CHURN_KV3A_NAMES)
    assert 64 < fr["churn"]["ticks"] == fr["churn"]["partitions"] < 128
    assert fr["churn"]["crashes"] == 64
    assert 0 < fr["churn"]["reconnects"] <= fr["churn"]["disconnects"] <= 2 * 128
    mine = fr["machine"]
    assert tuple(mine) == KvRaftMachine.STREAM_COUNTERS
    assert mine["ops_acked"] > 500 and mine["log_full"] == 0
    assert mine["ops_acked"] == mine["gets_acked"] + sum(
        int(x) for x in _batch(eng, 64).summary["acked_len"].sum(axis=1))
    assert 10 <= mine["log_high_water"] <= 32 and mine["apply_backlog"] >= 8
    assert fr["killed_hwm"] == 5
    # the three counters every kind has keep their places in the vector
    base = list(range(FR_METRICS_LEN))
    out = fr_metrics_dict(base + [1, 2, 3, 4, 5] + [7], ("ops_acked",))
    assert out["churn"] == dict(zip(FR_CHURN_NAMES + FR_CHURN_KV3A_NAMES, (1, 2, 3, 4, 5)))
    assert fr_metrics_dict(base + [1, 2, 3])["churn"] == dict(zip(FR_CHURN_NAMES, (1, 2, 3)))


PIN_SEED = 7006  # my CPU hunt, PR 35: the first find of [7000, 7064)
PIN_FLAGS = [
    "--horizon", "2.5", "--log-capacity", "32", "--churn", "kv3a",
    "--churn-until", "1.2", "--loss", "0.1", "--latency", "1000,27000",
    "--faults", "0", "--rng-stream", "3", "--queue", "48", "--max-steps", "3000",
]


def test_pinned_seed_goes_hunt_shrink_corpus_regress_audit(tmp_path, capsys):
    from madsim_tpu.__main__ import main

    path = str(tmp_path / "corpus.json")
    rc = main(["hunt", "--machine", "demo-localget-kvraft", "--stream",
               "--seed", "7000", "--seeds", "16", "--batch", "16",
               "--limit", "1", "--corpus", path] + PIN_FLAGS)
    out = capsys.readouterr().out
    assert rc == 1 and "1 new entries" in out, out
    doc = json.load(open(path))["entries"][0]
    assert doc["seed"] == PIN_SEED and doc["fail_code"] == K.STALE_GET
    assert doc["log_capacity"] == 32
    assert doc["config"]["faults"]["churn"]["kind"] == "kv3a"
    assert doc["config"]["horizon_us"] < 2_500_000  # shrunk
    assert main(["regress", "--corpus", path]) == 0
    assert main(["audit", "--corpus", path]) == 0
    out = capsys.readouterr().out
    assert "still open" in out and "digest trail matches" in out
    rc = main(["replay", "--machine", "demo-localget-kvraft", "--seed",
               str(PIN_SEED), "--tail", "1"] + PIN_FLAGS)
    assert rc == 1 and "FAILED (code 172)" in capsys.readouterr().out


def test_explore_takes_the_machine_and_prints_its_totals(capsys):
    from madsim_tpu.__main__ import main

    rc = main(["explore", "--machine", "kvraft", "--stream", "--seeds", "32",
               "--batch", "32", "--flight-recorder"] + PIN_FLAGS)
    out = capsys.readouterr().out
    assert rc == 0 and "0 failing, 0 abandoned" in out, out
    assert "64 partitions / 32 crashes" in out and "ops_acked=" in out
    assert "closing_gets_acked=" in out and "log_full=0" in out


def test_registry_fleet_spec_and_the_lines_that_carry_the_deployment():
    from types import SimpleNamespace

    from madsim_tpu.__main__ import _repro_line, build_machine
    from madsim_tpu.engine import corpus
    from madsim_tpu.fleet import store

    a, b = build_machine("kvraft", 0, 96), build_machine("kvraft")
    assert (a.log_capacity, b.log_capacity, a.NUM_NODES, a.servers) == (96, 64, 10, 5)
    assert a is build_machine("kvraft", 0, 96) and b.load_until_us == 1_500_000
    assert isinstance(build_machine("demo-localget-kvraft"), LocalGetKvRaft)
    args = SimpleNamespace(
        machine="kvraft", nodes=0, horizon=5.0, queue=40, faults=0, loss=0.1,
        fault_tmax=0, max_steps=4000, rng_stream=3, churn="kv3a",
        churn_until=1.5, log_capacity=64, latency="1000,27000")
    line = _repro_line(args, 7)
    assert "--churn kv3a --churn-until 1.5" in line and "--log-capacity 64" in line
    spec = store.normalize_spec({
        "machine": "demo-localget-kvraft", "churn": "kv3a", "churn_until": 1.5,
        "log_capacity": 64, "faults": 0})
    cmd = store.repro_cmd(spec)
    assert "--machine demo-localget-kvraft" in cmd and "--churn kv3a" in cmd
    assert store.job_fingerprint(spec)["churn"] is not None
    assert store.engine_key(spec) != store.engine_key(dict(spec, churn="fig8"))
    # a corpus entry records the kind; a fig8 entry is written as it always was
    d = corpus.config_to_dict(_cfg())
    assert d["faults"]["churn"]["kind"] == "kv3a"
    assert corpus.config_from_dict(d).faults == _cfg().faults
    fig8 = corpus.config_to_dict(_cfg(churn="fig8"))["faults"]["churn"]
    assert "kind" not in fig8 and "period_us" not in fig8
    with pytest.raises(ValueError, match="kind"):
        Engine.on_xla_step_path(_small(), dataclasses.replace(
            _cfg(), faults=dataclasses.replace(
                _cfg().faults, churn=dataclasses.replace(CHURN_PRESETS["kv3a"], kind="x"))))
