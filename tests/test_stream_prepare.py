"""A campaign runs no throw-away batch (ISSUE 32).

`_stream_batches` makes the stream's programs READY — traced, lowered,
compiled or read from the cache — instead of running a warm-up batch,
and does nothing at all on an engine that already holds them
(`Engine.prepare_stream`). Held to it here, for the three shapes of
quartet `_stream_fns` builds (plain jit, meshed over host devices,
exported-call jits of the AOT path):

* a campaign makes exactly `batches_planned` `run_stream` calls, none
  for one seed, and its aggregate is that of direct `run_stream` calls
  over the same ranges;
* on a fresh engine every compile stage of the stream's programs falls
  inside `warmup_dispatch`, none after it: the first timed batch
  compiles nothing, and runs the executables that were made;
* on a ready engine `warmup_dispatch` has no child and nothing reaches
  the device before the first batch's `init`;
* a `--checkpoint` file marked done makes and dispatches nothing.

Tiny shapes (3-node Raft, 16 lanes): one fresh engine a mode.
"""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
from madsim_tpu.engine.core import STREAM_PROGRAMS
from madsim_tpu.models.raft import RaftMachine
from madsim_tpu.perf import compile_log
from madsim_tpu.perf.recorder import PerfRecorder

MODES = ("plain", "meshed", "aot")
BATCH, SEEDS, SEED0, MAX_STEPS, DEVICES = 16, 48, 7, 150, 4
STAGES = ("trace", "lower", "backend")
#: what the executor puts on the device
DEVICE_SPANS = {"init", "dispatch", "counters_poll", "ring_drain", "harvest"}


def _engine():
    return Engine(
        RaftMachine(num_nodes=3, log_capacity=4),
        EngineConfig(
            horizon_us=2_000_000, queue_capacity=64,
            faults=FaultPlan(n_faults=1, t_max_us=1_000_000),
            coverage=True, flight_recorder=True,
        ),
    )


def _args(mode, **over):
    d = dict(machine="raft", nodes=3, seed=SEED0, seeds=SEEDS, batch=BATCH,
             max_steps=MAX_STEPS, horizon=2.0, loss=0.0, faults=1,
             fault_tmax=1_000_000, fault_kinds="pair,kill", rng_stream=2,
             strict_restart=False, coverage=True, stop_on_plateau=0,
             stats=None, stream=True, checkpoint=None, stop_after_batches=0,
             devices=DEVICES if mode == "meshed" else 0)
    d.update(over)
    return SimpleNamespace(**d)


def _spy(eng):
    """Record every `run_stream` call of this engine: (kwargs, result)."""
    calls = []
    inner = eng.run_stream

    def run_stream(n_seeds, **kw):
        out = inner(n_seeds, **kw)
        calls.append((dict(kw, n_seeds=n_seeds), out))
        return out

    eng.run_stream = run_stream
    return calls


def _campaign(eng, args):
    """One `_stream_batches` under a recorder: the aggregate, the
    recorder, and the compile-stage events that ended after the
    `warmup_dispatch` span did (all of them where there is no span)."""
    from madsim_tpu.__main__ import _stream_batches

    log = compile_log.install()
    n0 = len(log.events)
    rec = PerfRecorder()
    with rec:
        agg = _stream_batches(eng, args)
    warm = [s for s in rec.spans if s["name"] == "warmup_dispatch"]
    t_ready = rec._t0 + (warm[0]["ts"] + warm[0]["dur"]) / 1e6 if warm else 0.0
    late = [e for e in log.events[n0:] if e[0] > t_ready]
    return SimpleNamespace(agg=agg, rec=rec, warm=warm, late=late,
                           events=log.events[n0:])


@pytest.fixture(scope="module", params=MODES)
def runs(request, tmp_path_factory):
    """Two campaigns on one fresh engine, and the same ranges run by
    direct `run_stream` calls on another."""
    mode = request.param
    if mode == "meshed" and len(jax.devices()) < DEVICES:
        pytest.skip(f"needs {DEVICES} host devices")
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("MADSIM_TPU_STATS", raising=False)
        if mode == "aot":
            mp.setenv("MADSIM_TPU_AOT_CACHE",
                      str(tmp_path_factory.mktemp("aot")))
        else:
            mp.delenv("MADSIM_TPU_AOT_CACHE", raising=False)
        eng = _engine()
        calls = _spy(eng)
        first = _campaign(eng, _args(mode))
        first_calls = list(calls)
        held = dict(eng._stream_compiled)
        second = _campaign(eng, _args(mode))

        # the reference: what the campaign's batches are, asked directly
        from madsim_tpu.__main__ import _stream_kwargs

        ref_eng, direct, cursor, done = _engine(), [], SEED0, 0
        while done < SEEDS:
            out = ref_eng.run_stream(
                min(BATCH, SEEDS - done), batch=BATCH, segment_steps=384,
                seed_start=cursor, max_steps=MAX_STEPS,
                **_stream_kwargs(_args(mode)))
            direct.append(out)
            cursor += out["seeds_consumed"]
            done += out["completed"]
        yield SimpleNamespace(
            mode=mode, eng=eng, first=first, second=second,
            first_calls=first_calls, second_calls=calls[len(first_calls):],
            held=held, direct=direct)


def test_campaign_is_its_batches_and_nothing_else(runs):
    """(a) `batches_planned` calls, none for one seed, and the aggregate
    of direct calls over the same ranges."""
    agg, direct = runs.first.agg, runs.direct
    assert agg["batches_planned"] == -(-SEEDS // BATCH)
    assert len(runs.first_calls) == agg["batches_run"] == len(direct)
    assert agg["batches_run"] <= agg["batches_planned"]
    assert all(kw["n_seeds"] > 1 for kw, _ in runs.first_calls)
    starts = [kw["seed_start"] for kw, _ in runs.first_calls]
    assert starts[0] == SEED0
    assert starts == [SEED0 + sum(d["seeds_consumed"] for d in direct[:i])
                      for i in range(len(direct))]
    assert agg["completed"] == sum(d["completed"] for d in direct) >= SEEDS
    assert agg["seeds_consumed"] == sum(d["seeds_consumed"] for d in direct)
    for key in ("failing", "infra", "abandoned"):
        assert agg[key] == [x for d in direct for x in d[key]], key
    assert agg["abandoned"], "the shape should abandon some lanes"
    assert np.array_equal(
        agg["coverage_map"],
        np.logical_or.reduce([d["coverage_map"] for d in direct]))
    assert agg["stats"]["flight_recorder"] == \
        direct[-1]["stats"]["flight_recorder"]
    # and a second campaign on the same engine is the same campaign
    again = runs.second.agg
    for key in ("completed", "seeds_consumed", "failing", "infra",
                "abandoned", "batches_run"):
        assert again[key] == agg[key], key
    assert np.array_equal(again["coverage_map"], agg["coverage_map"])


def test_fresh_engine_compiles_inside_warmup_only(runs):
    """(b) every stage of the three programs falls inside the
    `warmup_dispatch` span; nothing — not an `iota` — compiles after it,
    so the first timed batch compiles nothing; one miss is counted."""
    first = runs.first
    [warm] = first.warm
    assert warm["args"] == {"ready": False, "programs": 3}
    assert first.rec.counters["stream.programs_ready_miss"] == 1
    assert "stream.programs_ready_hit" not in first.rec.counters
    assert first.late == [], first.late
    staged = {(e[2], e[1]) for e in first.events}
    for name in ("init_carry", "supersegment", "reset_rings"):
        assert {(name, s) for s in STAGES} <= staged, (name, staged)
    assert ("segment", "backend") not in staged  # pipelined: never made
    compiles = [s for s in first.rec.spans if s["name"] == "compile"]
    assert [s["args"]["program"] for s in compiles] == [
        "init_carry", "supersegment", "reset_rings"]
    assert all(s["parent"] == warm["id"] for s in compiles)
    # the batches ran the executables that were made, and made no other
    assert set(runs.held.values()) == set(runs.eng._stream_compiled.values())
    assert len(runs.held) == 3
    by_name = dict(zip(STREAM_PROGRAMS, runs.eng._stream_fns(
        384, MAX_STEPS, 2 * BATCH, BATCH, aot=runs.mode == "aot",
        mesh=runs.first_calls[0][0].get("mesh"))))
    assert set(runs.held) == {by_name[n] for n in (
        "init_carry", "supersegment", "reset_rings")}


def test_ready_engine_makes_and_dispatches_nothing_before_first_batch(runs):
    """(c) the second campaign: `warmup_dispatch` has no child, says
    `ready`, counts a hit; no compile stage of any program anywhere in
    it; nothing on the device before the first batch's `init`."""
    second = runs.second
    [warm] = second.warm
    assert warm["args"] == {"ready": True, "programs": 0}
    assert second.rec.counters["stream.programs_ready_hit"] == 1
    assert "stream.programs_ready_miss" not in second.rec.counters
    assert not [s for s in second.rec.spans if s["parent"] == warm["id"]]
    assert second.events == [] and second.late == []
    assert not [s for s in second.rec.spans if s["name"] == "compile"]
    by_start = sorted(second.rec.spans, key=lambda s: s["ts"])
    first_batch = next(s for s in by_start if s["name"] == "run_stream")
    before = [s["name"] for s in by_start if s["ts"] < first_batch["ts"]]
    assert not DEVICE_SPANS & set(before), before
    inside = [s for s in by_start if s["parent"] == first_batch["id"]]
    assert inside[0]["name"] == "init"
    assert len(runs.second_calls) == second.agg["batches_run"]
    assert all(kw["n_seeds"] > 1 for kw, _ in runs.second_calls)


def test_done_checkpoint_makes_and_dispatches_nothing(runs, tmp_path):
    """(d) a `--checkpoint` file marked done: a fresh engine resumes it
    to the same aggregate with no program made, no `warmup_dispatch`,
    no `run_stream` call."""
    ck = str(tmp_path / "ck.json")
    full = _campaign(runs.eng, _args(runs.mode, checkpoint=ck))
    assert full.agg["completed"] == runs.first.agg["completed"]

    fresh = _engine()
    calls = _spy(fresh)
    resumed = _campaign(fresh, _args(runs.mode, checkpoint=ck))
    assert calls == [] and resumed.warm == [] and resumed.events == []
    assert "_stream_compiled" not in fresh.__dict__
    assert not DEVICE_SPANS & {s["name"] for s in resumed.rec.spans}
    for key in ("completed", "seeds_consumed", "failing", "abandoned"):
        assert resumed.agg[key] == full.agg[key], key
    assert np.array_equal(resumed.agg["coverage_map"],
                          full.agg["coverage_map"])


def test_prepare_stream_holds_what_run_stream_calls():
    """The unit under it: `prepare_stream` returns how many programs it
    made and dispatches nothing; a `run_stream` after it makes none and
    compiles nothing; the per-segment executor's `segment` is a program
    of its own, made on demand and once."""
    eng = _engine()
    kw = dict(batch=BATCH, segment_steps=96, max_steps=MAX_STEPS)
    log = compile_log.install()
    with PerfRecorder() as rec:
        assert eng.prepare_stream(**kw) == 3
        assert not DEVICE_SPANS & {s["name"] for s in rec.spans}
        assert eng.prepare_stream(**kw) == 0
        n0 = len(log.events)
        out = eng.run_stream(BATCH, **kw)
        assert log.events[n0:] == []
        assert eng.prepare_stream(pipelined=False, **kw) == 1  # `segment`
        ref = eng.run_stream(BATCH, pipelined=False, **kw)
        assert log.events[-1][2] == "segment"
    assert rec.counters["stream.programs_ready_miss"] == 2
    assert rec.counters["stream.programs_ready_hit"] == 1
    assert [s["args"]["program"] for s in rec.spans
            if s["name"] == "compile"] == [
        "init_carry", "supersegment", "reset_rings", "segment"]
    for key in ("completed", "seeds_consumed", "failing", "abandoned"):
        assert out[key] == ref[key], key


def test_prepare_stream_refuses_what_run_stream_refuses():
    """A batch the mesh does not divide is refused with the clear error
    of seed placement before anything is traced."""
    from madsim_tpu.parallel import make_mesh

    if len(jax.devices()) < 3:
        pytest.skip("needs 3 host devices")
    eng = _engine()
    with pytest.raises(ValueError, match="multiple of"):
        eng.prepare_stream(batch=BATCH, mesh=make_mesh(jax.devices()[:3]))
    assert "_stream_compiled" not in eng.__dict__
