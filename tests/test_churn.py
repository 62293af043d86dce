"""The churn process (`FaultPlan.churn`, `--churn fig8`): faults drawn as
they fire. Held against its plain reference (`differential.churn_reference`),
across the step paths, through provenance, shrink and the corpus, and on
the host engine — all at a small size on the CPU."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import differential as D
from madsim_tpu.engine import ChurnPlan, Engine, EngineConfig, FaultPlan
from madsim_tpu.engine.core import F_CHURN_HEAL, F_CHURN_TICK, OVERFLOW
from madsim_tpu.engine.replay import replay
from madsim_tpu.models.raft import LEADER, LOG_MATCHING, Fig8Raft, RaftMachine

FIG8 = ChurnPlan()


def _cfg(horizon_s=1.0, until_s=0.9, queue=40, **kw):
    """The `raft5_fig8` flags at a small size."""
    return EngineConfig(
        horizon_us=int(horizon_s * 1e6), queue_capacity=queue,
        latency_min_us=1_000, latency_max_us=27_000, packet_loss_rate=0.1,
        rng_stream=3,
        faults=FaultPlan(n_faults=0, churn=FIG8,
                         churn_until_us=int(until_s * 1e6)),
        **kw,
    )


# the pinned demo-fig8-raft find (my CPU search, PR 27: one of 81,920
# seeds at log_capacity 32, 2 virtual seconds, the process ending at 1.8)
PIN_SEED, PIN_CAP = 40785, 32
PIN_CFG = _cfg(horizon_s=2.0, until_s=1.8)


def _leader_book(engine, seed, max_steps=3000):
    """(applied faults of the lane, {tick: the leader hook's answer on
    the state that tick found}) from the CPU replay's state trail."""
    leaders = {}

    def on_tick(tick, _t_us, before):
        up = [not (int(before.churn["down"]) >> i) & 1
              for i in range(engine.machine.NUM_NODES)]
        role = np.asarray(before.nodes.role)
        leaders[tick] = next(
            (i for i, u in enumerate(up) if u and role[i] == LEADER), -1)

    return D.applied_churn_faults(engine, seed, max_steps, on_tick), leaders


@pytest.mark.parametrize("n", [3, 5])
def test_device_applied_faults_equal_the_reference(n):
    eng = Engine(RaftMachine(num_nodes=n, log_capacity=16), _cfg())
    total = 0
    for seed in (3, 11, 2_500_000_001):
        applied, leaders = _leader_book(eng, seed)
        ref = D.churn_reference(
            seed, FIG8, lambda _t, tick, _up: leaders[tick], n=n,
            until_us=900_000, horizon_us=1_000_000)
        assert applied == ref, (seed, applied[:6], ref[:6])
        total += len(applied)
    assert total >= 8, total  # the process does apply faults


def test_reference_draws_the_victim_for_a_machine_without_the_hook():
    class NoHook(RaftMachine):
        def churn_victim(self, nodes, connected):
            return None

    eng = Engine(NoHook(num_nodes=5, log_capacity=16), _cfg())
    applied = D.applied_churn_faults(eng, 7, 3000)
    ref = D.churn_reference(7, FIG8, lambda *_: None, n=5,
                            until_us=900_000, horizon_us=1_000_000)
    assert applied == ref and len(applied) >= 10
    # every tick finds a connected node to cut: more disconnects than
    # the leader-only hook gives
    assert sum(op == D.CHURN_DISCONNECT for _t, op, _n in applied) >= 10


def test_threefry_reference_equals_jax():
    from madsim_tpu.engine.core import churn_key, churn_words

    for seed, draw in ((0, 0), (40785, 17), (4_000_000_000, 123_456)):
        got = [int(x) for x in churn_words(churn_key(seed), draw)]
        assert got == D.churn_draw(seed, draw)


def test_queue_holds_one_slot_whatever_the_number_of_faults():
    m = RaftMachine(num_nodes=5, log_capacity=16)
    floor = 5 + 1 + m.MAX_MSGS + m.MAX_TIMERS
    Engine(m, _cfg(queue=floor))  # accepted: Q does not count faults
    with pytest.raises(ValueError, match="queue_capacity"):
        Engine(m, _cfg(queue=floor - 1))
    eng = Engine(m, _cfg(horizon_s=2.0, until_s=1.9, flight_recorder=True))
    res = jax.jit(lambda s: eng.run_batch(s, 3000))(jnp.arange(64, dtype=jnp.uint32))
    assert not bool((res.fail_code == OVERFLOW).any())
    st = jax.vmap(eng.init_lane)(jnp.arange(4, dtype=jnp.uint32))
    assert int(st.eq_valid.sum(axis=1).max()) == 6  # 5 boots + the tick


def test_churn_needs_an_end_and_fits_one_mask():
    m = RaftMachine(num_nodes=5, log_capacity=8)
    with pytest.raises(ValueError, match="churn_until_us"):
        Engine(m, dataclasses.replace(
            _cfg(), faults=FaultPlan(n_faults=0, churn=FIG8)))


def _batch(engine, n=64, max_steps=1500):
    return jax.jit(lambda s: engine.run_batch(s, max_steps))(
        jnp.arange(n, dtype=jnp.uint32))


def _same(ra, rb):
    for name in ("done", "failed", "fail_code", "now_us", "steps", "msg_count"):
        assert bool((getattr(ra, name) == getattr(rb, name)).all()), name
    for k in rb.fr:
        assert bool((ra.fr[k] == rb.fr[k]).all()), k
    if rb.cov:
        assert bool((ra.cov["map"] == rb.cov["map"]).all())


GATES = dict(flight_recorder=True, fr_digest_every=32, fr_digest_ring=8,
             coverage=True, cov_slots_log2=12)


@pytest.mark.parametrize("path", ["megakernel", "pallas_pop", "bool_clog"])
def test_step_paths_bit_identical_with_churn_on(path):
    """Fail codes, times, steps, digest trails and coverage maps: the
    Pallas paths (interpreter mode off the TPU) and the bool-matrix clog
    oracle against the XLA step path."""
    m = RaftMachine(num_nodes=5, log_capacity=16)
    cfg = _cfg(**GATES)
    n, steps = (16, 400) if path != "bool_clog" else (64, 1500)
    ref = _batch(Engine.on_xla_step_path(m, cfg), n, steps)
    if path == "megakernel":
        eng = Engine(m, dataclasses.replace(cfg, pallas_megakernel=True))
        assert eng.use_megakernel
    elif path == "pallas_pop":
        eng = Engine(m, dataclasses.replace(cfg, pallas_megakernel=False),
                     use_pallas_pop=True)
    else:
        eng = Engine.on_xla_step_path(
            m, dataclasses.replace(cfg, clog_packed=False))
    _same(_batch(eng, n, steps), ref)
    assert int(ref.steps.max()) > 300


def test_batch_stream_mesh_and_cpu_replay_agree_with_churn_on():
    """One stream of 64 seeds, unsharded and on a 4-way virtual mesh,
    gives the batch runner's outcomes and recorder totals (the churn
    counters among them); the CPU replay reproduces a lane bit for bit."""
    from madsim_tpu.parallel import make_mesh

    m = Fig8Raft(num_nodes=5, log_capacity=16)
    cfg = _cfg(**GATES)
    eng = Engine.on_xla_step_path(m, cfg)
    res = _batch(eng, 64, 1500)
    outs = [
        eng.run_stream(64, batch=64, segment_steps=128, seed_start=0,
                       max_steps=1500, **kw)
        for kw in ({}, {"mesh": make_mesh(jax.devices()[:4])})
    ]
    for out in outs:
        assert out["completed"] == 64 and not out["abandoned"]
        assert sorted(out["failing"]) == sorted(
            (int(s), int(c)) for s, c, f in
            zip(res.seeds, res.fail_code, res.failed) if f)
    fr = [o["stats"]["flight_recorder"] for o in outs]
    assert fr[0] == fr[1]
    churn = fr[0]["churn"]
    assert churn["ticks"] > 64 * 10 and churn["disconnects"] > 64
    assert churn["reconnects"] >= churn["disconnects"] - 64 * 5
    assert np.array_equal(outs[0]["coverage_map"], outs[1]["coverage_map"])
    rp = replay(eng, 5, max_steps=1500, trace=False)
    assert int(rp.state.step) == int(res.steps[5])
    assert int(rp.state.now_us) == int(res.now_us[5])
    assert (int(rp.state.fr["d0"]), int(rp.state.fr["d1"])) == (
        int(res.fr["d0"][5]), int(res.fr["d1"][5]))


def test_churn_leaves_a_plan_without_it_as_it_was():
    """Off, the process adds no leaf and no op: the state tree and the
    step's jaxpr are those of a FaultPlan that never heard of it."""
    m = RaftMachine(num_nodes=5, log_capacity=8)
    cfg = EngineConfig(queue_capacity=32, rng_stream=3,
                       faults=FaultPlan(n_faults=2))
    eng = Engine.on_xla_step_path(m, cfg)
    st = eng.init_lane(1)
    assert st.churn == {} and jax.tree.leaves(st.churn) == []
    text = str(jax.make_jaxpr(eng.lane_step)(st))
    assert "threefry" in text and "churn" not in text
    on = Engine.on_xla_step_path(m, dataclasses.replace(
        cfg, faults=dataclasses.replace(
            cfg.faults, churn=FIG8, churn_until_us=500_000)))
    assert len(jax.tree.leaves(on.init_lane(1).churn)) == 7


def test_provenance_names_the_nodes_the_process_cut():
    """A generated fault sets the bit of its node. Sound as an OR: a
    node's word holds a bit only if that node was cut off or brought
    back before, and after the first applied fault every node holds its
    bit (each is an end of a link it moved)."""
    m = RaftMachine(num_nodes=5, log_capacity=16)
    eng = Engine.on_xla_step_path(m, _cfg(provenance=True))
    seen, first = set(), {}

    def hook(ev, state):
        if ev.kind == "fault" and ev.payload[0] in (F_CHURN_TICK, F_CHURN_HEAL):
            cut, back = (int(x) for x in state.churn["last"])
            seen.update(i for i in range(5) if ((cut | back) >> i) & 1)
            if (cut | back) and not first:
                first["bits"] = cut | back
                first["words"] = [int(w) for w in state.node_prov]
        words = [int(w) for w in state.node_prov]
        allowed = sum(1 << i for i in seen)
        assert all(w & ~allowed == 0 for w in words), (ev, words, seen)

    replay(eng, 3, max_steps=2000, on_step=hook, trace=False)
    assert first and all(w & first["bits"] == first["bits"]
                         for w in first["words"])
    assert len(seen) >= 2


def test_why_decodes_churn_bits_to_nodes():
    from madsim_tpu.engine.provenance import KIND_CHURN, implicated

    eng = Engine.on_xla_step_path(
        Fig8Raft(num_nodes=5, log_capacity=PIN_CAP),
        dataclasses.replace(PIN_CFG, provenance=True))
    rp = replay(eng, PIN_SEED, max_steps=3000, trace=False)
    assert rp.failed and rp.fail_code == LOG_MATCHING
    att = implicated(eng, PIN_SEED, int(rp.state.fail_prov))
    assert KIND_CHURN in att.kinds and att.churn_nodes and not att.faults
    assert any("churn" in line for line in att.describe())


def test_shrink_churn_stage_ends_with_fewer_applied_faults():
    from madsim_tpu.engine.shrink import shrink

    # a second find of my CPU search, PR 27 (one of 32,768 seeds at
    # log_capacity 64, 3 virtual seconds): PIN_SEED needs all ten of its
    # faults, this one's violation is complete a second before the end
    seed, cfg = 181, _cfg(horizon_s=3.0, until_s=2.7)
    eng = Engine(Fig8Raft(num_nodes=5, log_capacity=64), cfg)
    base = replay(eng, seed, max_steps=4000, trace=False)
    assert base.failed and base.fail_code == LOG_MATCHING
    sr = shrink(eng, seed, max_steps=4000)
    assert sr.fail_code == LOG_MATCHING
    assert sr.shrunk.faults.churn_until_us < 2_000_000
    assert "churn until" in sr.summary()
    small = replay(Engine(eng.machine, sr.shrunk), seed,
                   max_steps=sr.steps + 1, trace=False)
    assert small.failed and small.fail_code == LOG_MATCHING

    def applied(state):
        return int(state.churn["disconnects"]) + int(state.churn["reconnects"])

    assert applied(small.state) < applied(base.state)  # 30 of 40
    # the correct machine survives the same lane
    ok = replay(Engine(RaftMachine(5, 64), cfg), seed, max_steps=4000,
                trace=False)
    assert not ok.failed


PIN_FLAGS = [
    "--churn", "fig8", "--churn-until", "1.8", "--horizon", "2",
    "--log-capacity", str(PIN_CAP), "--loss", "0.1",
    "--latency", "1000,27000", "--faults", "0", "--rng-stream", "3",
    "--queue", "40", "--max-steps", "3000",
]


def test_pinned_seed_goes_hunt_shrink_corpus_regress_audit(tmp_path, capsys):
    from madsim_tpu.__main__ import main

    path = str(tmp_path / "corpus.json")
    rc = main(["hunt", "--machine", "demo-fig8-raft", "--stream",
               "--seed", str(PIN_SEED - 21), "--seeds", "64", "--batch", "64",
               "--corpus", path] + PIN_FLAGS)
    out = capsys.readouterr().out
    assert rc == 1 and "1 new entries" in out, out
    doc = json.load(open(path))["entries"][0]
    assert doc["seed"] == PIN_SEED and doc["fail_code"] == LOG_MATCHING
    assert doc["log_capacity"] == PIN_CAP
    faults = doc["config"]["faults"]
    assert faults["churn"]["disconnect_permille"] == 500
    assert 0 < faults["churn_until_us"] < 1_800_000
    assert main(["regress", "--corpus", path]) == 0
    assert main(["audit", "--corpus", path]) == 0
    out = capsys.readouterr().out
    assert "still open" in out and "digest trail matches" in out
    # the replay line a user would copy reproduces it
    rc = main(["replay", "--machine", "demo-fig8-raft", "--seed", str(PIN_SEED),
               "--tail", "1"] + PIN_FLAGS)
    assert rc == 1 and "FAILED (code 102)" in capsys.readouterr().out


def test_registry_keys_on_the_log_capacity_and_lines_carry_the_flags():
    from types import SimpleNamespace

    from madsim_tpu.__main__ import _repro_line, build_machine
    from madsim_tpu.engine import corpus

    a, b = build_machine("raft", 5, 32), build_machine("raft", 5)
    assert a is build_machine("raft", 5, 32) and a is not b
    assert (a.log_capacity, b.log_capacity) == (32, 8)
    assert b is build_machine("raft", 5, 0)
    with pytest.raises(SystemExit):
        build_machine("echo", 0, 32)
    args = SimpleNamespace(
        machine="raft", nodes=5, horizon=2.0, queue=40, faults=0, loss=0.1,
        fault_tmax=0, max_steps=3000, rng_stream=3, churn="fig8",
        churn_until=1.8, log_capacity=32, latency="1000,27000")
    line = _repro_line(args, 7)
    for part in ("--churn fig8 --churn-until 1.8", "--log-capacity 32",
                 "--latency 1000,27000"):
        assert part in line, line
    entry = corpus.CorpusEntry(
        machine="raft", seed=7, fail_code=102, status=corpus.STATUS_OPEN,
        config=PIN_CFG, max_steps=10, nodes=5, log_capacity=32)
    back = corpus.CorpusEntry.from_dict(json.loads(json.dumps(entry.to_dict())))
    assert back.config == PIN_CFG and back.log_capacity == 32
    assert corpus.entry_machine(back, build_machine) is a
    # an entry without a process is written as it always was
    plain = corpus.config_to_dict(EngineConfig())
    assert "churn" not in plain["faults"] and "churn_until_us" not in plain["faults"]


def test_fleet_job_specs_carry_the_flags():
    from madsim_tpu.fleet import store

    spec = store.normalize_spec({
        "machine": "demo-fig8-raft", "churn": "fig8", "churn_until": 1.8,
        "log_capacity": 32, "latency": "1000,27000", "faults": 0})
    cmd = store.repro_cmd(spec)
    assert "--churn fig8 --churn-until 1.8 --log-capacity 32 --latency 1000,27000" in cmd
    plain = store.normalize_spec({"machine": "raft"})
    assert plain["churn"] == "" and "--churn" not in store.repro_cmd(plain)
    assert store.engine_key(spec) != store.engine_key(
        dict(spec, log_capacity=64))
    assert store.job_fingerprint(plain)["churn"] is None
    assert store.job_fingerprint(spec)["log_capacity"] == 32


def test_host_raft_under_the_lanes_applied_faults():
    """differential_raft with a churn process: the host-engine Raft,
    given what the device lane applied at the same virtual times,
    upholds the same verdicts, and once the process has ended one more
    entry commits on all five (the test's closing `one(cmd, servers)`)."""
    eng = Engine.on_xla_step_path(
        RaftMachine(num_nodes=5, log_capacity=32), _cfg(horizon_s=2.0, until_s=1.8))
    rep = D.differential_raft(eng, [3, 11], closing_commit_s=10.0)
    assert rep["schedule_mismatches"] == 0
    assert rep["device_violations"] == 0 and rep["host_violations"] == 0
    for row in rep["rows"]:
        assert len(row["host"]["churn_applied"]) >= 6
        assert row["host"]["closing_committed"] is True, row["seed"]


def test_device_commits_on_all_five_after_the_process_ends():
    eng = Engine.on_xla_step_path(
        RaftMachine(num_nodes=5, log_capacity=32), _cfg(horizon_s=3.0, until_s=0.9))
    marks = {}

    def hook(ev, state):
        if ev.kind == "fault" and ev.payload[0] == F_CHURN_HEAL:
            marks["at_heal"] = int(np.max(np.asarray(state.nodes.commit)))

    rp = replay(eng, 3, max_steps=6000, on_step=hook, trace=False)
    assert not rp.failed and "at_heal" in marks
    assert int(np.min(np.asarray(rp.state.nodes.commit))) > marks["at_heal"]


def test_host_raft_under_its_own_churn_process():
    """The process itself against the host engine (`ChurnReference`): the
    ticks, coins and picks of the seed, the victim the host's own
    connected leader. The example Raft stays safe, what it applied is
    the reference's stream for the leaders it had, and it commits on all
    five once the process is over."""
    for seed in (1, 3):  # seed 2 misses the closing commit: CHANGES.md, PR 27
        leaders = {}
        host = D.run_host_raft(
            seed, [], n=5, horizon_us=2_000_000, base_loss=0.1,
            latency_us=(1_000, 27_000), churn=(FIG8, 1_800_000),
            closing_commit_s=10.0)
        assert host["violation"] is None and host["closing_committed"] is True
        applied = host["churn_applied"]
        assert len(applied) >= 2 and applied[-1][0] == 1_800_000
        cut = [(t, n) for t, op, n in applied if op == D.CHURN_DISCONNECT]
        leaders.update({t: n for t, n in cut})
        ref = D.churn_reference(
            seed, FIG8, lambda t, _i, up: leaders.get(t, -1), n=5,
            until_us=1_800_000, horizon_us=2_000_000)
        # a leader the coin spared is not in the book: the reference, told
        # of the leaders that were cut, applies the same stream
        assert [e for e in ref if e[1] == D.CHURN_DISCONNECT] == \
            [e for e in applied if e[1] == D.CHURN_DISCONNECT]
