"""The traceless replay stops where its lane stops (ISSUE 29).

`replay_outcome`'s compiled loop is a `while_loop` on `done | failed`
and `max_steps`: it leaves the state the old loop left (a `fori_loop`
over `max_steps` whose body passed a finished lane through a `cond`
untouched, rebuilt here as the reference) and the state the eager traced
replay stops at, leaf for leaf, and its trip count is the lane's event
count."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from madsim_tpu.__main__ import build_machine
from madsim_tpu.engine import ChurnPlan, Engine, EngineConfig, FaultPlan, replay
from madsim_tpu.engine.replay import cpu_device, replay_outcome
from madsim_tpu.models.raft import RaftMachine
from madsim_tpu.perf.recorder import PerfRecorder


def _raft():
    return Engine(RaftMachine(5, 8), EngineConfig(
        horizon_us=1_000_000, queue_capacity=32,
        faults=FaultPlan(n_faults=2, t_max_us=600_000)))


def _nodedup_mvcc():
    """`etcd_mvcc4`'s hunt machine under the cell's flags."""
    return Engine(build_machine("demo-nodedup-mvcc", 0), EngineConfig(
        horizon_us=8_000_000, queue_capacity=48,
        faults=FaultPlan(
            n_faults=3, t_max_us=3_000_000, allow_partition=True,
            allow_kill=True, allow_dir_clog=True, allow_group=True,
            allow_storm=True)))


def _churn3():
    """`raft5_fig8`'s plan at three nodes and one virtual second."""
    return Engine(RaftMachine(num_nodes=3, log_capacity=16), EngineConfig(
        horizon_us=1_000_000, queue_capacity=40, latency_min_us=1_000,
        latency_max_us=27_000, packet_loss_rate=0.1, rng_stream=3,
        faults=FaultPlan(n_faults=0, churn=ChurnPlan(),
                         churn_until_us=900_000)))


_BUILD = {"raft": _raft, "nodedup-mvcc": _nodedup_mvcc, "churn3": _churn3}


class _Rig:
    """An engine and the loop `_fast_outcome_fn` ran before this PR."""

    def __init__(self, engine):
        self.engine = engine

        def old_run(state, horizon_us, n_steps):
            def body(_i, s):
                return lax.cond(
                    s.done | s.failed,
                    lambda x: x,
                    lambda x: engine.lane_step(x, horizon_us=horizon_us),
                    s,
                )

            return lax.fori_loop(0, n_steps, body, state)

        self._old = jax.jit(old_run)

    def old_loop(self, seed, max_steps):
        with jax.default_device(cpu_device()):
            return jax.device_get(self._old(
                self.engine.init_lane(seed),
                jnp.int32(self.engine.config.horizon_us),
                jnp.int32(max_steps)))


@pytest.fixture(scope="module")
def rigs():
    made = {}

    def get(name):
        if name not in made:
            made[name] = _Rig(_BUILD[name]())
        return made[name]

    return get


def _leaves_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape and (x == y).all()


#: seed 5 of the NO_DEDUP store fails with code 204 on its 10th event
FAILS_AT = 10

#: name: (machine, seed, max_steps, how the lane ends, its trips where
#: the case pins them — else the eager trace's length, under max_steps)
CASES = {
    "raft": ("raft", 3, 4000, "done", None),
    "demo-nodedup-mvcc": ("nodedup-mvcc", 0, 4000, "done", None),
    "churn-at-3-nodes": ("churn3", 8, 4000, "done", None),
    "fails-early": ("nodedup-mvcc", 5, 4000, "failed", FAILS_AT),
    "ends-done": ("raft", 29, 4000, "done", None),
    "cut-below-its-length": ("raft", 3, 100, "cut", 100),
    "cut-one-short-of-the-failure": (
        "nodedup-mvcc", 5, FAILS_AT - 1, "cut", FAILS_AT - 1),
    "cap-equals-the-length": ("nodedup-mvcc", 5, FAILS_AT, "failed", FAILS_AT),
    "max-steps-0": ("nodedup-mvcc", 5, 0, "cut", 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_while_loop_leaves_the_state_the_old_loop_and_the_eager_replay_leave(
        rigs, case):
    name, seed, max_steps, end, trips = CASES[case]
    rig = rigs(name)
    rec = PerfRecorder()
    with rec:
        state = replay_outcome(rig.engine, seed, max_steps=max_steps).state
    eager = replay(rig.engine, seed, max_steps=max_steps, trace=True)
    if trips is None:
        trips = len(eager.trace)
        assert 0 < trips < max_steps
    assert (int(state.step), bool(state.done), bool(state.failed)) == (
        trips, end == "done", end == "failed")
    _leaves_equal(state, jax.device_get(eager.state))
    _leaves_equal(state, rig.old_loop(seed, max_steps))
    # the loop ran one iteration an event, not one a `max_steps`
    [span] = [s for s in rec.spans if s["name"] == "replay"]
    assert span["args"]["trips"] == span["args"]["steps"] == trips
    assert rec.counters["replay.loop_trips"] == trips


def test_trips_sum_in_the_counter_and_the_program_is_shared():
    """Under a recorder, on a machine object that holds no program yet:
    a lane that fails at step k with `max_steps` 4000 notes `trips ==
    steps == k`, a lane cut at m notes m, the counter holds their sum —
    and a second engine over the same machine object (a shrink
    candidate: another horizon, fewer scheduled faults) asks for the
    program the first one made: the key kept its shape."""
    engine = _nodedup_mvcc()
    cand = Engine(engine.machine, dataclasses.replace(
        engine.config, horizon_us=2_000_000,
        faults=dataclasses.replace(engine.config.faults, n_faults=1)))
    rec = PerfRecorder()
    with rec:
        assert replay_outcome(engine, 5, max_steps=4000).fail_code == 204
        replay_outcome(engine, 0, max_steps=17)
        replay_outcome(cand, 5, max_steps=4000)
    spans = [s["args"] for s in rec.spans if s["name"] == "replay"]
    assert [(a["trips"], a["steps"]) for a in spans[:2]] == [
        (FAILS_AT, FAILS_AT), (17, 17)]
    assert spans[2]["trips"] == spans[2]["steps"] < 4000
    assert rec.counters["replay.loop_trips"] == sum(a["trips"] for a in spans)
    assert [a["program_hit"] for a in spans] == [False, True, True]
    assert rec.counters["replay.program_miss"] == 1
    assert rec.counters["replay.program_hit"] == 2
    assert [s["args"]["program"] for s in rec.spans
            if s["name"] == "compile"] == ["replay.run"]
