"""The Kafka pipeline machine (`models/kafka.py`, `--machine kafka`): two
idempotent producers, a broker with three live partition logs, a
two-member rebalancing group. Held against its plain reference (the L5
`services.kafka.Broker`, through `differential_kafka`), across the step
paths, through stream, mesh and replay with its counters, through hunt,
shrink and the corpus — and the engine's part of it: state one role
holds (`Machine.lane_spec`) and a machine's own totals of a stream
(`Machine.STREAM_COUNTERS`). All at a small size on the CPU."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.differential_services import differential_kafka
from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
from madsim_tpu.engine.core import FR_METRICS_LEN
from madsim_tpu.engine.replay import replay
from madsim_tpu.models import kafka as K
from madsim_tpu.models.kafka import KafkaMachine, NoDedupKafkaMachine
from madsim_tpu.models.mq import DUP_OR_GAP

GATES = dict(flight_recorder=True, fr_digest_every=32, fr_digest_ring=8,
             coverage=True, cov_slots_log2=12)
FIVE_KINDS = dict(allow_partition=True, allow_kill=True, allow_dir_clog=True,
                  allow_group=True, allow_storm=True)


def _cfg(n_faults=3, **kw):
    """`kafka_pc5`'s flags at a small size: producers stop at 0.4 virtual
    seconds, the lane at 0.7."""
    return EngineConfig(
        horizon_us=700_000, queue_capacity=40, rng_stream=3,
        faults=FaultPlan(n_faults=n_faults, t_max_us=450_000,
                         dur_min_us=100_000, dur_max_us=800_000, **FIVE_KINDS),
        **kw,
    )


def _small(cls=KafkaMachine):
    return cls(log_capacity=32, produce_until_us=400_000)


def _batch(eng, n, max_steps=2500, start=0):
    return eng.make_runner(max_steps=max_steps)(
        jnp.arange(start, start + n, dtype=jnp.uint32))


@pytest.fixture(scope="module")
def sweep():
    """2,048 seeds of the small deployment, correct and NoDedup."""
    return {
        cls: _batch(Engine.on_xla_step_path(_small(cls), _cfg(**GATES)), 2048)
        for cls in (KafkaMachine, NoDedupKafkaMachine)
    }


# -- the plain reference ------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_differential_agrees_event_for_event_without_faults(seed):
    eng = Engine.on_xla_step_path(_small(), _cfg(n_faults=0))
    out = differential_kafka(eng, seed, max_steps=2500)
    assert out["ok"], out["mismatches"]
    assert not out["had_fault"] and not out["replay_failed"]
    # both producers wrote, both members joined, fetched and committed
    assert out["records"] >= 20 and out["machine_gen"] == 2
    assert out["fetch_responses"] >= 10 and out["commits"] >= 10
    assert out["counters"]["dup_refused"] == 0 == out["counters"]["log_full"]


def test_differential_converges_under_kills():
    """Seeds whose schedules kill and restart node 0 and clients: the logs,
    high watermarks, fetch responses, members, generation, assignment and
    committed offsets are the service's."""
    from madsim_tpu.engine.core import F_KILL

    eng = Engine.on_xla_step_path(_small(), _cfg())
    killed = set()
    for seed in range(3, 9):
        out = differential_kafka(eng, seed, max_steps=2500)
        assert out["ok"], (seed, out["mismatches"])
        assert out["had_fault"]
        rp = replay(eng, seed, max_steps=2500)
        killed |= {ev.payload[1] for ev in rp.trace
                   if ev.kind == "fault" and ev.payload[0] == F_KILL}
    assert K.BROKER in killed and killed - {K.BROKER}, killed


def test_differential_convicts_the_nodedup_log(sweep):
    res = sweep[NoDedupKafkaMachine]
    seed = int(res.seeds[np.asarray(res.failed)][0])
    eng = Engine.on_xla_step_path(_small(NoDedupKafkaMachine), _cfg())
    out = differential_kafka(eng, seed, max_steps=2500)
    assert out["replay_failed"] and not out["ok"]
    assert any(m.startswith(("log[", "high watermark[", "appended"))
               for m in out["mismatches"]), out["mismatches"]


# -- invariants -----------------------------------------------------------------


def test_the_three_invariants_hold_over_two_thousand_seeds(sweep):
    res = sweep[KafkaMachine]
    assert not bool(res.failed.any()), set(res.fail_code.tolist())
    assert bool(res.done.all()) and bool((res.steps < 2500).all())
    s = res.summary
    assert int(s["log_full"].sum()) == 0 and int(s["log_high_water"].max()) <= 32
    # the load is real and the faults bite: records, rebalances past the
    # two joins, duplicates refused, commits fenced
    assert float(s["appended"].mean()) > 15
    assert int((s["generation"] > 2).sum()) > 200
    assert int(s["dup_refused"].sum()) > 100 and int(s["commits_fenced"].sum()) > 100
    assert bool((s["consumed"] >= s["committed"].sum(axis=1)).all())


def test_nodedup_fails_with_120_and_replays(sweep):
    res = sweep[NoDedupKafkaMachine]
    failed = np.asarray(res.failed)
    assert 100 < int(failed.sum()) < 2048
    assert set(np.asarray(res.fail_code)[failed].tolist()) == {DUP_OR_GAP}
    # the same seeds pass with idempotence on
    assert not bool(sweep[KafkaMachine].failed.any())
    seed = int(res.seeds[failed][0])
    eng = Engine.on_xla_step_path(_small(NoDedupKafkaMachine), _cfg(**GATES))
    rp = replay(eng, seed, max_steps=2500, trace=False)
    assert rp.failed and rp.fail_code == DUP_OR_GAP
    assert int(rp.state.step) == int(res.steps[failed][0])


# -- step paths, stream, mesh, replay --------------------------------------------


def _same(ra, rb):
    for name in ("done", "failed", "fail_code", "now_us", "steps", "msg_count"):
        assert bool((getattr(ra, name) == getattr(rb, name)).all()), name
    for k in rb.fr:
        assert bool((ra.fr[k] == rb.fr[k]).all()), k
    assert bool((ra.cov["map"] == rb.cov["map"]).all())
    for k in rb.summary:
        assert bool((ra.summary[k] == rb.summary[k]).all()), k


@pytest.mark.parametrize("path", ["megakernel", "pallas_pop"])
def test_step_paths_bit_identical(path):
    """Fail codes, times, steps, digest trails, coverage maps and the
    machine's totals: the Pallas paths (interpreter mode off the TPU)
    against the XLA step path."""
    m, cfg = _small(NoDedupKafkaMachine), _cfg(**GATES)
    ref = _batch(Engine.on_xla_step_path(m, cfg), 16, 600)
    if path == "megakernel":
        eng = Engine(m, dataclasses.replace(cfg, pallas_megakernel=True))
        assert eng.use_megakernel
    else:
        eng = Engine(m, dataclasses.replace(cfg, pallas_megakernel=False),
                     use_pallas_pop=True)
    _same(_batch(eng, 16, 600), ref)
    assert int(ref.steps.max()) > 400 and bool(ref.failed.any())


def test_batch_stream_mesh_and_cpu_replay_agree_counters_included():
    """One stream of 64 seeds, unsharded and on a 4-way virtual mesh, gives
    the batch runner's outcomes, recorder totals and machine totals; the
    CPU replay reproduces a lane, its totals included."""
    from madsim_tpu.parallel import make_mesh

    m = _small()
    eng = Engine.on_xla_step_path(m, _cfg(**GATES))
    res = _batch(eng, 64)
    outs = [
        eng.run_stream(64, batch=64, segment_steps=1024, seed_start=0,
                       max_steps=2500, **kw)
        for kw in ({}, {"mesh": make_mesh(jax.devices()[:4])})
    ]
    for out in outs:
        assert out["completed"] == 64 and not out["abandoned"]
        assert not out["failing"] and not out["infra"]
    fr = [o["stats"]["flight_recorder"] for o in outs]
    assert fr[0] == fr[1]
    mine = fr[0]["machine"]
    assert list(mine) == list(m.STREAM_COUNTERS)
    for name in K.COUNTERS:
        assert mine[name] == int(res.summary[name].sum()), name
    assert mine["log_high_water"] == int(res.summary["log_high_water"].max())
    assert mine["appended"] > 64 * 15 and mine["rebalances"] >= 64 * 2
    assert np.array_equal(outs[0]["coverage_map"], outs[1]["coverage_map"])
    rp = replay(eng, 5, max_steps=2500, trace=False)
    assert int(rp.state.step) == int(res.steps[5])
    assert (int(rp.state.fr["d0"]), int(rp.state.fr["d1"])) == (
        int(res.fr["d0"][5]), int(res.fr["d1"][5]))
    for name, v in zip(m.STREAM_COUNTERS, m.stream_counters(rp.state.nodes)):
        assert int(v) == int(res.summary[name][5]), name


@pytest.mark.parametrize("name", ["raft", "etcd-mvcc"])
def test_a_machine_without_counters_or_role_held_leaves_is_as_it_was(name):
    """No leaf and no op: the recorder's vector keeps its length, the
    stats carry no machine totals, and the restart hook the generic
    wipes now call is the identity."""
    from madsim_tpu.__main__ import build_machine

    m = build_machine(name)
    assert m.STREAM_COUNTERS == () and m.lane_spec() is None
    assert m.stream_counters(None).shape == (0,)
    cfg = EngineConfig(queue_capacity=48, rng_stream=3, flight_recorder=True,
                       faults=FaultPlan(n_faults=2))
    eng = Engine.on_xla_step_path(m, cfg)
    init_carry = eng._stream_fns(128, 2000, 64, 32)[0]
    carry = jax.eval_shape(init_carry, jnp.arange(32, dtype=jnp.uint32))
    assert carry.fr_metrics.shape == (FR_METRICS_LEN,)
    nodes = eng.init_lane(1).nodes
    assert m.restart_lane_if(nodes, 0, True, None) is nodes
    text = str(jax.make_jaxpr(
        lambda n, k: m.restart_node_if(n, 1, jnp.bool_(True), k))(
            nodes, jnp.zeros((2,), jnp.uint32)))
    kafka = Engine.on_xla_step_path(_small(), cfg)
    carry = jax.eval_shape(kafka._stream_fns(128, 2000, 64, 32)[0],
                           jnp.arange(32, dtype=jnp.uint32))
    assert carry.fr_metrics.shape == (FR_METRICS_LEN + len(K.COUNTERS) + 1,)
    assert "while" not in text  # a handful of selects, no loop


def test_fr_metrics_dict_names_the_machines_totals():
    from madsim_tpu.kinds import FR_CHURN_NAMES
    from madsim_tpu.runtime.metrics import fr_metrics_dict

    base = list(range(FR_METRICS_LEN))
    assert "machine" not in fr_metrics_dict(base)
    out = fr_metrics_dict(base + [7, 9], ("appended", "log_high_water"))
    assert out["machine"] == {"appended": 7, "log_high_water": 9}
    assert "churn" not in out and out["killed_hwm"] == FR_METRICS_LEN - 1
    churn = [1] * len(FR_CHURN_NAMES)
    out = fr_metrics_dict(base + churn + [7], ("appended",))
    assert out["churn"]["ticks"] == 1 and out["machine"] == {"appended": 7}
    with pytest.raises(ValueError):
        fr_metrics_dict(base + [7], ("appended", "log_high_water"))


# -- role-held state --------------------------------------------------------------


@pytest.fixture(scope="module")
def mid_lane():
    """(machine, one lane's node state after a fault-free run)."""
    m = _small()
    eng = Engine.on_xla_step_path(m, _cfg(n_faults=0))
    nodes = replay(eng, 1, max_steps=2500, trace=False).state.nodes
    assert int(nodes.log_len.sum()) > 20 and bool(nodes.joined[3] & nodes.joined[4])
    return m, nodes


def _leaves(nodes, names):
    return {f: np.asarray(getattr(nodes, f)) for f in names}


@pytest.mark.parametrize("hook", ["own", "strict", "torn"])
def test_role_held_leaves_survive_a_restart_of_node_0(mid_lane, hook):
    """Logs, cursors, generation, assignment, committed offsets, ghost
    state and totals stay; the member table goes — through the machine's
    own restart, the crash-with-amnesia wipe and the torn-write restart
    alike. A restart of any other node touches no role-held leaf."""
    m, nodes = mid_lane
    key = jnp.zeros((2,), jnp.uint32)

    def restart(i):
        if hook == "torn":
            return m.torn_restart_if(nodes, i, jnp.bool_(True), key, jnp.uint32(5))
        return m.restart_node_if(nodes, i, jnp.bool_(True), key,
                                 strict=(hook == "strict"))

    durable = [f for f in K._ROLE_HELD if f not in K._VOLATILE_ROLE_HELD]
    after = restart(K.BROKER)
    for f, v in _leaves(nodes, durable).items():
        assert np.array_equal(np.asarray(getattr(after, f)), v), f
    assert not bool(after.joined.any()) and not bool(after.last_hb.any())
    # the broker's restart takes nothing from a client
    for f in ("next_seq", "position", "m_gen", "my_assign"):
        assert np.array_equal(np.asarray(getattr(after, f)),
                              np.asarray(getattr(nodes, f))), f
    member = restart(3)
    for f, v in _leaves(nodes, K._ROLE_HELD).items():
        assert np.array_equal(np.asarray(getattr(member, f)), v), f
    assert int(member.m_gen[3]) == 0 and not bool(member.my_assign[3].any())
    assert int(member.m_gen[4]) == int(nodes.m_gen[4]) > 0
    producer = restart(1)
    assert not bool(producer.next_seq[1].any()) and bool(nodes.next_seq[1].any())
    assert np.array_equal(np.asarray(producer.next_seq[2]), np.asarray(nodes.next_seq[2]))


def test_the_generic_wipe_skips_role_held_leaves(mid_lane):
    m, nodes = mid_lane
    wiped = m._wipe_node_if(nodes, 4, jnp.bool_(True), jnp.zeros((2,), jnp.uint32))
    for f, v in _leaves(nodes, K._ROLE_HELD).items():
        assert np.array_equal(np.asarray(getattr(wiped, f)), v), f
    assert int(wiped.m_gen[4]) == 0 and int(wiped.m_gen[3]) == int(nodes.m_gen[3])


def test_the_engine_holds_a_machine_to_its_lane_spec():
    class Forgetful(KafkaMachine):
        def lane_spec(self):
            return super().lane_spec().replace(log_len=False)

    with pytest.raises(ValueError, match="log_len"):
        Engine.on_xla_step_path(Forgetful(), _cfg())

    class Crooked(KafkaMachine):
        def lane_spec(self):
            return {"log_len": True}

    with pytest.raises(ValueError, match="congruent"):
        Engine.on_xla_step_path(Crooked(), _cfg())


def test_strict_restart_runs_and_keeps_the_invariants():
    """`--strict-restart` on a machine with role-held leaves: the generic
    wipe handles the per-node leaves, the machine's hook the member table."""
    cfg = _cfg()
    cfg = dataclasses.replace(
        cfg, faults=dataclasses.replace(cfg.faults, strict_restart=True))
    res = _batch(Engine.on_xla_step_path(_small(), cfg), 128)
    assert not bool(res.failed.any()) and bool(res.done.all())
    assert int((res.summary["generation"] > 2).sum()) > 10


# -- the entry points ---------------------------------------------------------------

PIN_SEED = 7002  # my CPU hunt, PR 33: the first find of [7000, 7064)
PIN_FLAGS = [
    "--horizon", "1", "--log-capacity", "32", "--faults", "3",
    "--fault-kinds", "pair,kill,dir,group,storm", "--fault-tmax", "600000",
    "--rng-stream", "3", "--queue", "40", "--max-steps", "2500",
]


def test_pinned_seed_goes_hunt_shrink_corpus_regress_audit(tmp_path, capsys):
    from madsim_tpu.__main__ import main

    path = str(tmp_path / "corpus.json")
    rc = main(["hunt", "--machine", "demo-nodedup-kafka", "--stream",
               "--seed", "7000", "--seeds", "64", "--batch", "64",
               "--limit", "1", "--corpus", path] + PIN_FLAGS)
    out = capsys.readouterr().out
    assert rc == 1 and "1 new entries" in out, out
    doc = json.load(open(path))["entries"][0]
    assert doc["seed"] == PIN_SEED and doc["fail_code"] == DUP_OR_GAP
    assert doc["log_capacity"] == 32
    assert doc["config"]["faults"]["n_faults"] < 3  # shrunk
    assert doc["config"]["horizon_us"] < 1_000_000
    assert main(["regress", "--corpus", path]) == 0
    assert main(["audit", "--corpus", path]) == 0
    out = capsys.readouterr().out
    assert "still open" in out and "digest trail matches" in out
    # the replay line a user would copy reproduces it
    rc = main(["replay", "--machine", "demo-nodedup-kafka", "--seed",
               str(PIN_SEED), "--tail", "1"] + PIN_FLAGS)
    assert rc == 1 and "FAILED (code 120)" in capsys.readouterr().out


def test_registry_log_capacity_and_the_lines_that_carry_it():
    from types import SimpleNamespace

    from madsim_tpu.__main__ import _repro_line, build_machine
    from madsim_tpu.fleet import store

    a, b = build_machine("kafka", 0, 96), build_machine("kafka")
    assert (a.log_capacity, b.log_capacity, a.NUM_NODES) == (96, 64, 5)
    assert a is build_machine("kafka", 0, 96) and a is not b
    assert isinstance(build_machine("demo-nodedup-kafka", 0, 96), NoDedupKafkaMachine)
    for name in ("mq", "group", "etcd-mvcc"):  # machines with no log to size
        with pytest.raises(SystemExit):
            build_machine(name, 0, 96)
    args = SimpleNamespace(
        machine="kafka", nodes=0, horizon=2.0, queue=40, faults=3, loss=0.0,
        fault_tmax=1_500_000, max_steps=4000, rng_stream=3, churn=None,
        churn_until=None, log_capacity=96, latency=None)
    assert "--log-capacity 96" in _repro_line(args, 7)
    spec = store.normalize_spec({
        "machine": "demo-nodedup-kafka", "log_capacity": 96, "faults": 3,
        "fault_kinds": "pair,kill,dir,group,storm"})
    assert "--machine demo-nodedup-kafka" in store.repro_cmd(spec)
    assert "--log-capacity 96" in store.repro_cmd(spec)
    assert store.engine_key(spec) != store.engine_key(dict(spec, log_capacity=64))
    assert store.job_fingerprint(spec)["log_capacity"] == 96
