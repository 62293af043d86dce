"""The identity of a replay program (ISSUE 26): a replay program is asked
for again exactly when nothing it was traced from differs.

Three parts, each with its test: one machine object per registry name
per process (so `record_entry`, `regress` and `audit` find the programs
shrink lowered), a `_trace_affecting_key` that names every field of
`EngineConfig` / `FaultPlan`, and a recorder ring sized in buckets (so
the audit's program does not depend on the seed shrunk)."""

import dataclasses
import importlib
import json
import os

import jax
import numpy as np
import pytest

from madsim_tpu.__main__ import build_machine, main
from madsim_tpu.engine import Engine, EngineConfig, FaultPlan, audit, corpus
from madsim_tpu.engine.replay import replay_outcome
from madsim_tpu.models.echo import EchoMachine
from madsim_tpu.perf.recorder import PerfRecorder

# `madsim_tpu.engine.replay` the attribute is the function of that name
replay_mod = importlib.import_module("madsim_tpu.engine.replay")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the benchmark's `etcd_mvcc4` flags at 64 lanes (tests/test_perf.py's
#: hunt uses the same, so the persistent cache serves both)
HUNT_FLAGS = ["--horizon", "8", "--queue", "48", "--faults", "3",
              "--fault-kinds", "pair,kill,dir,group,storm",
              "--fault-tmax", "3000000", "--max-steps", "4000",
              "--batch", "64", "--seeds", "64", "--limit", "1"]


def _hunt_regress_audit(corpus_path, seed_start):
    """One `hunt --limit 1` -> `regress` -> `audit` under a recorder;
    returns (recorder, the filed entry)."""
    rec = PerfRecorder()
    with rec:
        assert main(["hunt", "--machine", "demo-nodedup-mvcc", "--stream",
                     "--seed", str(seed_start), "--corpus", corpus_path,
                     *HUNT_FLAGS]) == 1  # found and filed
        assert main(["regress", "--corpus", corpus_path]) == 0
        assert main(["audit", "--corpus", corpus_path]) == 0
    [entry] = corpus.load(corpus_path)
    return rec, entry


def _replay_compiles(rec):
    """(`compile` spans with `program: replay.run`, by the name of the
    span two levels up: which command asked)."""
    by_id = {s["id"]: s for s in rec.spans}
    return [by_id[by_id[s["parent"]]["parent"]]["name"] for s in rec.spans
            if s["name"] == "compile" and s["args"]["program"] == "replay.run"]


def test_a_second_hunt_lowers_no_replay_program(tmp_path):
    """Two hunts over two seed ranges in one process, each followed by
    `regress` and `audit`: the first lowers what shrink and the recorded
    replay need and nothing for `regress` / `audit` (they ask for the
    program shrink's last candidate and `record_entry` lowered); the
    second, whose ring bucket repeats, lowers no replay program at all.
    Hits and misses add up to the `replay` spans."""
    rec1, e1 = _hunt_regress_audit(str(tmp_path / "c1.json"), 0)
    rec2, e2 = _hunt_regress_audit(str(tmp_path / "c2.json"), 131072)
    assert e1.seed != e2.seed and e1.max_steps != e2.max_steps
    assert audit.trail_ring(e1.max_steps, e1.digest_every) == \
        audit.trail_ring(e2.max_steps, e2.digest_every)

    first = _replay_compiles(rec1)
    assert "shrink_candidate" in first and first.count("corpus_record") == 1
    assert "regress_entry" not in first and "audit_entry" not in first
    assert _replay_compiles(rec2) == []

    for rec, misses in ((rec1, len(first)), (rec2, 0)):
        replays = [s for s in rec.spans if s["name"] == "replay"]
        hits = rec.counters.get("replay.program_hit", 0)
        assert rec.counters.get("replay.program_miss", 0) == misses
        assert hits + misses == len(replays)
        assert [s["args"]["program_hit"] for s in replays].count(True) == hits


# -- the key is complete ---------------------------------------------------------


def test_every_config_field_is_placed():
    """Each field of `EngineConfig` and `FaultPlan` is in the key (by
    value, or through the attribute `Engine` derives from it) or in
    `_INIT_ONLY`, and in one place only: a field added later fails here
    until someone decides whether the step program reads it."""
    key_lists = [replay_mod._KEY_CONFIG, replay_mod._KEY_IF_RECORDER,
                 replay_mod._KEY_IF_COVERAGE, replay_mod._KEY_FAULTS,
                 tuple(replay_mod._KEY_DERIVED), tuple(replay_mod._INIT_ONLY)]
    placed = [f for group in key_lists for f in group]
    assert len(placed) == len(set(placed)), sorted(
        f for f in placed if placed.count(f) > 1)
    fields = {f.name for f in dataclasses.fields(EngineConfig)} | {
        f.name for f in dataclasses.fields(FaultPlan)}
    assert fields - set(placed) == set(), "unplaced: decide key or _INIT_ONLY"
    assert set(placed) - fields == set(), "names no field"
    # a keyed FaultPlan field is a FaultPlan field, and the derived
    # attributes exist on an Engine
    fault_fields = {f.name for f in dataclasses.fields(FaultPlan)}
    assert set(replay_mod._KEY_FAULTS) <= fault_fields
    eng = Engine(EchoMachine(rounds=2), EngineConfig(queue_capacity=16))
    for attr in set(replay_mod._KEY_DERIVED.values()):
        assert hasattr(eng, attr), attr


def _leaves_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape and (x == y).all()


#: pairs of configurations that one machine object must keep apart:
#: each differs from the base in fields the old key did not hold
#: (PERF.md §6 PR 26, finding 4: `regress` loads an entry with the
#: coverage knobs dropped where the hunt had them on)
_BASE = dict(horizon_us=400_000, queue_capacity=16, rng_stream=3)
KEY_SPLITS = {
    "coverage": (dict(coverage=True), dict(coverage=False)),
    "cov_slots": (dict(coverage=True, cov_slots_log2=10),
                  dict(coverage=True, cov_slots_log2=12)),
    "cov_buffer": (dict(coverage=True, cov_buffer=0),
                   dict(coverage=True, cov_buffer=8)),
    "cov_band_bits": (dict(coverage=True, cov_band_bits_min=0),
                      dict(coverage=True, cov_band_bits_min=4)),
}


@pytest.mark.parametrize("order", ["forward", "reverse"])
@pytest.mark.parametrize("split", sorted(KEY_SPLITS))
def test_one_machine_serves_both_sides_of_a_gate(split, order):
    """On ONE machine object, `replay_outcome` under one configuration
    and then under its twin equals a replay on a fresh machine, leaf for
    leaf: the second asker never gets the program traced from the
    first."""
    sides = KEY_SPLITS[split]
    if order == "reverse":
        sides = sides[::-1]
    shared = EchoMachine(rounds=3)
    for extra in sides:
        cfg = EngineConfig(**_BASE, **extra)
        got = replay_outcome(Engine(shared, cfg), 7, max_steps=200)
        want = replay_outcome(Engine(EchoMachine(rounds=3), cfg), 7,
                              max_steps=200)
        _leaves_equal(got.state, want.state)
    assert len(replay_mod._replay_cache(Engine(shared, cfg))) == 2


def test_a_gate_that_is_off_keys_none_of_its_sizes():
    """With the recorder and coverage off, their sizes are not traced,
    so they do not split the cache (a corpus entry's config has both
    off and every size at its default)."""
    m = EchoMachine(rounds=3)
    base = EngineConfig(**_BASE)
    k = replay_mod._trace_affecting_key
    assert k(Engine(m, base)) == k(Engine(m, dataclasses.replace(
        base, fr_digest_ring=128, fr_digest_every=16, cov_slots_log2=12,
        cov_buffer=0, cov_band_bits_min=4, horizon_us=1, compile_cache_dir=None,
        faults=FaultPlan(n_faults=0, t_max_us=5, allow_group=True))))
    on = dataclasses.replace(base, flight_recorder=True)
    assert k(Engine(m, on)) != k(Engine(m, dataclasses.replace(
        on, fr_digest_ring=128)))


# -- one machine per registry name ------------------------------------------------


def test_registry_hands_out_one_machine_per_name():
    a = build_machine("demo-nodedup-mvcc", 0)
    assert build_machine("demo-nodedup-mvcc") is a
    assert build_machine("demo-nodedup-mvcc", 0) is a
    b = build_machine("etcd-mvcc", 0)
    assert b is not a and type(b) is not type(a)
    cfg = EngineConfig(horizon_us=8_000_000, queue_capacity=48)
    # two registry names never share a cache, though they differ by one
    # class attribute
    assert replay_mod._replay_cache(Engine(a, cfg)) is not \
        replay_mod._replay_cache(Engine(b, cfg))
    assert replay_mod._replay_cache(Engine(a, cfg)) is \
        replay_mod._replay_cache(Engine(build_machine("demo-nodedup-mvcc"), cfg))
    assert build_machine("raft", 3) is not build_machine("raft", 5)
    with pytest.raises(SystemExit):
        build_machine("no-such-machine")


# -- the ring's bucket is invisible -------------------------------------------------


@pytest.mark.parametrize("max_steps, every, ring", [
    (0, 64, 8), (12, 64, 8), (447, 64, 8), (448, 64, 16), (959, 64, 16),
    (960, 64, 32), (4000, 64, 64), (4032, 64, 128), (300, 16, 32),
])
def test_trail_ring_is_a_power_of_two_that_never_wraps(max_steps, every, ring):
    assert audit.trail_ring(max_steps, every) == ring
    assert ring >= max_steps // every + 2 and ring & (ring - 1) == 0


@pytest.mark.parametrize("name, cfg, seeds", [
    ("raft", EngineConfig(
        horizon_us=5_000_000, queue_capacity=96,
        faults=FaultPlan(n_faults=2, t_max_us=3_000_000,
                         dur_min_us=200_000, dur_max_us=800_000)), (0, 3, 5)),
    ("demo-nodedup-mvcc", EngineConfig(
        horizon_us=8_000_000, queue_capacity=48,
        faults=FaultPlan(n_faults=3, t_max_us=3_000_000, allow_dir_clog=True,
                         allow_group=True, allow_storm=True,
                         dur_min_us=100_000, dur_max_us=800_000)), (1, 2, 3)),
])
def test_bucketed_ring_records_the_exact_rings_trail(name, cfg, seeds):
    """`collect_trail` with the ring in buckets gives the trail the
    exact-size ring gives, checkpoint for checkpoint."""
    max_steps, every = 300, 16
    eng = Engine(build_machine(name, 0), cfg)
    exact = audit.fr_variant(eng, every, max_steps // every + 2)
    assert exact.config.fr_digest_ring < audit.trail_ring(max_steps, every)
    n_checkpoints = 0
    for seed in seeds:
        bucketed = audit.collect_trail(eng, seed, max_steps, every=every)
        plain = audit.collect_trail(exact, seed, max_steps, every=every)
        assert bucketed == plain
        assert bucketed.to_lists() == plain.to_lists()
        n_checkpoints += len(bucketed.checkpoints)
    assert n_checkpoints >= len(seeds)  # the trails are not empty


def _repo_corpus_by_machine():
    with open(os.path.join(REPO, "corpus.json")) as f:
        entries = json.load(f)["entries"]
    return sorted({e["machine"] for e in entries})


@pytest.mark.parametrize("machine", _repo_corpus_by_machine())
def test_repo_corpus_passes_regress_and_audit_unedited(machine):
    """Every entry of the repo's `corpus.json` still reproduces its
    fail code and still matches its recorded digest trail, and the
    entries of one machine share their two programs."""
    entries = [e for e in corpus.load(os.path.join(REPO, "corpus.json"))
               if e.machine == machine]
    rec = PerfRecorder()
    with rec:
        for e in entries:
            out = corpus.check(e, build_machine)
            assert out.ok, (e.seed, out.verdict)
            got = audit.audit_entry(e, build_machine)
            assert got.status == "match", (e.seed, got.verdict)
    rings = {audit.trail_ring(e.max_steps, e.digest_every) for e in entries}
    assert rec.counters["replay.program_miss"] <= 1 + len(rings)
    assert rec.counters.get("replay.program_hit", 0) == \
        2 * len(entries) - rec.counters["replay.program_miss"]
