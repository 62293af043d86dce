"""The perf observatory (madsim_tpu/perf): host-timeline recorder span
semantics + Perfetto schema pin, the run_stream --perf-timeline
end-to-end accounting (spans must explain the wall), and the compile
cache's placement and warm start.

Everything except the e2e half is jax-free host math — deterministic
fake clocks, no device work.
"""

import json
import math
import os

import pytest

from madsim_tpu.perf.recorder import (
    PerfRecorder,
    current_recorder,
    maybe_count,
    maybe_note,
    maybe_span,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, s):
        self.t += s


# -- PerfRecorder ------------------------------------------------------------


def test_recorder_span_nesting_and_totals():
    clk = FakeClock()
    rec = PerfRecorder(clock=clk)
    with rec:
        with rec.span("outer"):
            clk.tick(1.0)
            with rec.span("inner"):
                clk.tick(0.25)
            clk.tick(0.5)
        clk.tick(0.1)  # gap between top-level spans
        with rec.span("outer"):
            clk.tick(0.4)
    s = rec.summary()
    assert s["wall_s"] == pytest.approx(2.25)
    # per-name totals include every depth; outer ran twice
    assert s["spans"]["outer"]["total_s"] == pytest.approx(2.15)
    assert s["spans"]["outer"]["count"] == 2
    assert s["spans"]["inner"]["total_s"] == pytest.approx(0.25)
    # nested spans record parent depth correctly: inner is not top-level,
    # so coverage (union of top spans) is wall minus the gap
    assert s["dispatch_gap_s"] == pytest.approx(0.1)
    assert s["span_coverage"] == pytest.approx(2.15 / 2.25, abs=1e-4)


def test_recorder_device_wait_scoped_to_run_stream():
    """Uncovered interior of a run_stream span is device_wait (the
    shared-core starvation signal); uncovered interior of any OTHER
    span is that span's own host work — never device_wait."""
    clk = FakeClock()
    rec = PerfRecorder(clock=clk)
    with rec:
        with rec.span("engine_build"):
            clk.tick(0.4)  # childless top span: NOT device_wait
        with rec.span("run_stream"):
            with rec.span("compile"):
                clk.tick(2.0)
            clk.tick(0.7)  # starved interior: device_wait
            with rec.span("counters_poll"):
                clk.tick(0.05)
    s = rec.summary()
    assert s["device_wait_s"] == pytest.approx(0.7)
    assert s["spans"]["run_stream"]["total_s"] == pytest.approx(2.75)
    assert "compile-bound" in rec.verdict()


def test_recorder_spans_name_their_parent_and_trace():
    """Every span carries its id, the id of the span that encloses it
    (-1 at the top) and the recorder's trace_id, so self time is
    duration minus children with no guessing from depth; `run_stream`'s
    device_wait is found through the parent index at any depth (the
    warm-up's run_stream sits under `warmup_dispatch`)."""
    clk = FakeClock()
    rec = PerfRecorder(clock=clk, trace_id="job-7")
    with rec:
        with rec.span("warmup_dispatch"):
            with rec.span("run_stream"):
                with rec.span("dispatch"):
                    clk.tick(0.1)
                clk.tick(0.6)  # device executing: device_wait
            clk.tick(0.05)  # warmup_dispatch's own host work
        rec.instant("mark")
        with maybe_span("hunt_report") as got:
            assert got is rec  # a span yields its recorder (subclasses rely on it)
            maybe_note(lines=3)  # what a span learns inside goes to its args
            clk.tick(0.2)
        maybe_note(lost=1)  # no span open: dropped, not an error
    by_name = {s["name"]: s for s in rec.spans}
    assert by_name["warmup_dispatch"]["parent"] == -1
    assert by_name["run_stream"]["parent"] == by_name["warmup_dispatch"]["id"]
    assert by_name["dispatch"]["parent"] == by_name["run_stream"]["id"]
    assert by_name["mark"]["parent"] == -1 and by_name["mark"]["dur"] is None
    assert by_name["hunt_report"]["args"] == {"lines": 3}
    assert len({s["id"] for s in rec.spans}) == len(rec.spans)
    assert {s["trace_id"] for s in rec.spans} == {"job-7"}
    assert PerfRecorder().trace_id != PerfRecorder().trace_id
    s = rec.summary()
    assert s["device_wait_s"] == pytest.approx(0.6)
    # absorbed spans keep a valid tree under fresh ids
    outer = PerfRecorder(clock=clk)
    with outer:
        with outer.span("fleet_unit"):
            clk.tick(0.1)
        outer.absorb(rec, ts_offset_us=5.0)
    ids = {s["id"]: s for s in outer.spans}
    assert len(ids) == len(outer.spans)
    moved = next(s for s in outer.spans if s["name"] == "dispatch")
    assert ids[moved["parent"]]["name"] == "run_stream"


@pytest.mark.parametrize("annotate", [True, False])
def test_recorder_annotates_each_span_once_or_never(annotate, monkeypatch):
    """`PerfRecorder(annotate=True)` writes every span into a running
    profiler capture as ONE `madsim.<name>` TraceAnnotation, entered and
    left with the span; the default writes none (the benchmark's
    recorder wraps spans itself and must not get them twice)."""
    import jax

    seen = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    rec = PerfRecorder(clock=FakeClock(), annotate=annotate)
    with rec:
        with rec.span("shrink_candidate", stage="base"):
            with rec.span("replay"):
                pass
        maybe_count("compile.trace")
    assert [s["name"] for s in rec.spans] == ["replay", "shrink_candidate"]
    assert seen == ([
        ("enter", "madsim.shrink_candidate"), ("enter", "madsim.replay"),
        ("exit", "madsim.replay"), ("exit", "madsim.shrink_candidate"),
    ] if annotate else [])


def test_recorder_contextvar_scoping():
    assert current_recorder() is None
    # no recorder: maybe_span is a no-op context, maybe_count a no-op
    with maybe_span("anything"):
        maybe_count("x")
    rec = PerfRecorder(clock=FakeClock())
    with rec:
        assert current_recorder() is rec
        maybe_count("x", 3)
        with maybe_span("spanned"):
            pass
    assert current_recorder() is None
    assert rec.counters == {"x": 3}
    assert [s["name"] for s in rec.spans] == ["spanned"]


def test_recorder_open_spans_crash_flush_view():
    """open_spans materializes the still-open stack mid-run — the
    crash-flush path (fleet worker SIGTERM/atexit) dumps these so a
    killed unit's timeline is never empty. Durations run to `now`,
    depths are the live nesting, and every span is tagged partial."""
    clk = FakeClock()
    rec = PerfRecorder(clock=clk)
    assert rec.open_spans() == []  # before entry: nothing to flush
    with rec:
        with rec.span("unit", batch=32):
            clk.tick(1.0)
            with rec.span("dispatch"):
                clk.tick(0.25)
                got = rec.open_spans()
    assert [s["name"] for s in got] == ["unit", "dispatch"]
    assert [s["depth"] for s in got] == [0, 1]
    assert got[0]["dur"] == pytest.approx(1.25e6)  # µs, runs to now
    assert got[1]["dur"] == pytest.approx(0.25e6)
    assert got[0]["args"] == {"batch": 32, "partial": True}
    assert got[1]["args"] == {"partial": True}
    # after clean exit the stack is empty — nothing double-reports
    assert rec.open_spans() == []
    assert [s["name"] for s in rec.spans] == ["dispatch", "unit"]


def test_recorder_not_reenterable():
    rec = PerfRecorder(clock=FakeClock())
    with rec:
        pass
    with pytest.raises(RuntimeError):
        rec.__enter__()


def test_chrome_trace_schema_pin(tmp_path):
    """The Perfetto export schema is a contract (CI uploads these
    artifacts; external tooling reads them): pin the envelope keys, the
    metadata records, and the slice/instant shapes."""
    clk = FakeClock()
    rec = PerfRecorder(meta={"cmd": "test"}, clock=clk)
    with rec:
        with rec.span("dispatch", batch=8):
            clk.tick(0.002)
        rec.instant("marker", note="hi")
    path = tmp_path / "t.json"
    n = rec.write(str(path))
    doc = json.loads(path.read_text())
    assert sorted(doc.keys()) == [
        "displayTimeUnit", "madsim_perf_meta", "madsim_perf_summary",
        "traceEvents",
    ]
    assert doc["displayTimeUnit"] == "ms"
    assert doc["madsim_perf_meta"] == {"cmd": "test"}
    evs = doc["traceEvents"]
    assert n == len(evs) - 2
    # two metadata records first: process + thread names
    assert [e["ph"] for e in evs[:2]] == ["M", "M"]
    assert evs[0]["args"]["name"] == "madsim_tpu host"
    [slice_ev] = [e for e in evs if e["ph"] == "X"]
    assert slice_ev["name"] == "dispatch"
    assert slice_ev["pid"] == 0 and slice_ev["tid"] == 0
    assert slice_ev["ts"] == 0.0 and slice_ev["dur"] == pytest.approx(2000.0)
    assert slice_ev["args"] == {"batch": 8}
    [inst] = [e for e in evs if e["ph"] == "i"]
    assert inst["name"] == "marker" and inst["s"] == "t"
    assert doc["madsim_perf_summary"]["spans"]["dispatch"]["count"] == 1


# -- end to end: --perf-timeline over a real streaming run -------------------


def test_perf_timeline_e2e_explore_stream(tmp_path):
    """`explore --stream --perf-timeline` writes a Perfetto file whose
    spans explain the run: compile/dispatch/counters_poll/ring_drain
    all present, and the union of spans accounts for >= 90% of the
    recorder wall (the acceptance bar — on the 1-core box the starved
    interior is captured by the run_stream outer span and reported as
    device_wait)."""
    from madsim_tpu.__main__ import main

    out = tmp_path / "host.perfetto.json"
    rc = main([
        "explore", "--machine", "echo", "--seeds", "64", "--batch", "32",
        "--stream", "--faults", "0", "--horizon", "1.0",
        "--max-steps", "400", "--queue", "16",
        "--perf-timeline", str(out),
    ])
    assert rc == 0
    doc = json.loads(out.read_text())
    s = doc["madsim_perf_summary"]
    names = set(s["spans"])
    assert {"compile", "dispatch", "counters_poll",
            "ring_drain", "run_stream", "engine_build"} <= names, names
    assert s["span_coverage"] >= 0.9, s
    # the named spans + device_wait explain (almost) everything the
    # gaps don't: accounted wall >= 90%
    accounted = (
        sum(v["total_s"] for k, v in s["spans"].items() if k != "run_stream")
        + s["device_wait_s"]
    )
    assert accounted >= 0.9 * s["wall_s"], s
    # dur values are microseconds from recorder entry, monotone start order
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs == sorted(xs, key=lambda e: e["ts"])
    assert math.isfinite(sum(e["dur"] for e in xs))


#: the host taxonomy a hunt -> regress -> audit must show (ISSUE 25,
#: table A), each with the args a reader keys on
HUNT_TREE = {
    "warmup_dispatch": (), "run_stream": ("n_seeds",), "hunt_report": (),
    "shrink_candidate": ("stage", "accepted"),
    "replay": ("seed", "traced", "steps"), "compile": ("program",),
    "replay_run": (), "corpus_record": (), "regress_entry": ("seed",),
    "audit_entry": ("seed",),
}


def test_hunt_span_tree_reaches_one_replay(tmp_path):
    """A tiny `hunt --stream --limit 1` + `regress` + `audit` under one
    recorder: every span of the taxonomy is there with its args and a
    valid parent, each shrink attempt is one `shrink_candidate`, each
    replay program is compiled under a `compile` span naming it, and the
    named spans cover >= 95% of the commands' wall."""
    import time

    import jax  # noqa: F401 — a process's first jax import is not the command's

    from madsim_tpu.__main__ import main

    corpus = str(tmp_path / "corpus.json")
    hunt = ["hunt", "--machine", "demo-nodedup-mvcc", "--stream",
            "--seeds", "64", "--seed", "0", "--limit", "1",
            "--corpus", corpus, "--horizon", "8", "--queue", "48",
            "--faults", "3", "--fault-kinds", "pair,kill,dir,group,storm",
            "--fault-tmax", "3000000", "--max-steps", "4000", "--batch", "64"]
    rec = PerfRecorder()
    with rec:
        t0 = time.perf_counter()
        assert main(hunt) == 1  # found and filed
        assert main(["regress", "--corpus", corpus]) == 0
        assert main(["audit", "--corpus", corpus]) == 0
        wall_us = (time.perf_counter() - t0) * 1e6
    by_id = {s["id"]: s for s in rec.spans}
    names = {s["name"] for s in rec.spans}
    assert set(HUNT_TREE) <= names, sorted(set(HUNT_TREE) - names)
    for s in rec.spans:
        assert s["parent"] == -1 or s["parent"] in by_id, s
        assert set(HUNT_TREE.get(s["name"], ())) <= set(s["args"]), s
        if s["parent"] >= 0:  # a child lies inside its parent
            p = by_id[s["parent"]]
            assert p["ts"] <= s["ts"] and \
                s["ts"] + s["dur"] <= p["ts"] + p["dur"] + 1.0, (s, p)
    parent_of = lambda s: by_id[s["parent"]]["name"] if s["parent"] >= 0 else None
    cands = [s for s in rec.spans if s["name"] == "shrink_candidate"]
    assert cands[0]["args"]["stage"] == "base"
    assert {c["args"]["stage"] for c in cands} <= {
        "base", "faults", "loss", "kinds", "horizon"}
    replays = [s for s in rec.spans if s["name"] == "replay"]
    assert {parent_of(r) for r in replays} == {
        "shrink_candidate", "corpus_record", "regress_entry", "audit_entry"}
    assert len([r for r in replays if parent_of(r) == "shrink_candidate"]) \
        == len(cands)  # `attempts` is a count of spans
    assert {s["args"]["program"] for s in rec.spans if s["name"] == "compile"} \
        >= {"supersegment", "init_carry", "replay.run"}
    assert all(parent_of(s) == "replay" for s in rec.spans
               if s["name"] in ("replay_run",))
    top = sorted((s for s in rec.spans if s["parent"] == -1 and s["dur"]),
                 key=lambda s: s["ts"])
    assert PerfRecorder._union_us(top) >= 0.95 * wall_us, (
        PerfRecorder._union_us(top), wall_us)
    # compile stages while a recorder is active land as counters too
    assert rec.counters.get("compile.backend", 0) >= 1
    assert rec.counters.get("compile.trace", 0) >= 1


def test_compile_log_counts_stages_by_program():
    """One trace + one lower + one backend event per new program, filed
    under the site's name inside `program(...)` and under jax's function
    name outside; a replay's compile goes to `replay.run`; a snapshot is
    bounded by its window and a stage's total is a union (a nested jit
    is not counted twice)."""
    import time

    import jax
    import jax.numpy as jnp

    from madsim_tpu.perf import compile_log

    log = compile_log.install()
    assert compile_log.install() is log  # idempotent: one listener

    def madsim_test_only_fn(x):  # no nested jit: primitives only
        return x * 3 + 1

    t0 = time.perf_counter()
    n0 = len(log.events)
    jax.jit(madsim_test_only_fn)(jnp.arange(7, dtype=jnp.int32)).block_until_ready()
    mine = [e for e in log.events[n0:] if e[2] == "madsim_test_only_fn"]
    assert sorted(e[1] for e in mine) == ["backend", "lower", "trace"]
    snap = compile_log.snapshot(t0, time.perf_counter())
    assert snap["by_program"]["madsim_test_only_fn"]["requests"] == 1
    assert snap["requests"] >= 1
    assert snap["trace_s"] > 0 and snap["backend_s"] > 0
    assert compile_log.snapshot(0.0, t0 - 1.0)["by_program"].get(
        "madsim_test_only_fn") is None

    def other_fn(x):
        return jnp.sin(x) + jnp.cumsum(x)  # cumsum is a nested jit

    ones = jnp.ones((5,))  # made outside: an eager op is a program too
    t1 = time.perf_counter()
    with compile_log.program("site.name"):
        jax.jit(other_fn)(ones).block_until_ready()
    snap = compile_log.snapshot(t1, time.perf_counter())
    site = snap["by_program"]["site.name"]
    assert site["requests"] == 1 and "other_fn" not in snap["by_program"]
    assert site["trace_s"] <= time.perf_counter() - t1  # a union, not a sum
    assert compile_log.slowest(snap).split(" of ")[1].startswith("site.name")

    # the replay's program, by the name of the site that first calls it
    from madsim_tpu.__main__ import build_machine
    from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
    from madsim_tpu.engine.replay import replay_outcome

    eng = Engine(build_machine("echo", 0), EngineConfig(
        horizon_us=500_000, queue_capacity=16, faults=FaultPlan(n_faults=0)))
    t2 = time.perf_counter()
    replay_outcome(eng, 3, max_steps=50)
    first = compile_log.snapshot(t2, time.perf_counter())["by_program"]
    assert first["replay.run"]["requests"] == 1
    t3 = time.perf_counter()
    replay_outcome(eng, 4, max_steps=50)  # same machine: no new program
    assert "replay.run" not in compile_log.snapshot(
        t3, time.perf_counter())["by_program"]


def test_perf_timeline_written_on_failure(tmp_path):
    """A failing run still writes its timeline — a failing run's wall
    profile is exactly what one wants to inspect."""
    from madsim_tpu.__main__ import _perf_session

    class A:
        perf_timeline = str(tmp_path / "fail.json")
        xla_profile = None
        cmd = "explore"

    with pytest.raises(RuntimeError):
        with _perf_session(A()) as rec:
            with rec.span("doomed"):
                raise RuntimeError("boom")
    doc = json.loads((tmp_path / "fail.json").read_text())
    assert any(e.get("name") == "doomed" for e in doc["traceEvents"])


# -- the compile cache: placement and warm start ------------------------------


def test_compile_cache_subkey_shape():
    """cache_subkey renders the warm-start tuple — (jax version, gate
    tuple, stream version, shape) — as one directory-name-safe string,
    deterministically."""
    from madsim_tpu.compile_cache import cache_subkey

    k = cache_subkey(
        gates={"coverage": True, "flight_recorder": False},
        rng_stream=3, lanes=8192, segment_steps=384,
    )
    assert k == cache_subkey(
        gates={"flight_recorder": False, "coverage": True},  # order-free
        rng_stream=3, lanes=8192, segment_steps=384,
    )
    assert "rng3" in k and "l8192x384" in k
    import re

    assert re.fullmatch(r"[A-Za-z0-9._-]+", k), k
    # jax/jaxlib versions discriminate upgrades
    import jax

    assert jax.__version__.replace("+", "_") in k or jax.__version__ in k


def test_compile_cache_unwritable_fails_loud(tmp_path, monkeypatch):
    """enable_compile_cache on an uncreatable directory: strict raises,
    the default warns and leaves the cache OFF — never the old silent
    degrade (a fleet that believes it is warm while every worker
    recompiles). Probing is by actual write, not os.access (CI and the
    reference box run as root, where access() lies)."""
    from madsim_tpu import compile_cache as cc

    blocker = tmp_path / "blocker"
    blocker.write_text("a file where a directory must go")
    bad = str(blocker / "cache")
    monkeypatch.setattr(cc, "_active_dir", None)
    monkeypatch.delenv("MADSIM_TPU_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with pytest.raises(RuntimeError, match="not writable"):
        cc.enable_compile_cache(bad, strict=True)
    # non-strict: warns, returns None, cache stays off
    assert cc.enable_compile_cache(bad) is None
    assert cc._active_dir is None
    # a directory placed from outside is probed the same way
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", bad)
    with pytest.raises(RuntimeError, match="not writable"):
        cc.enable_compile_cache(strict=True)


def _recorded_cache_wiring(monkeypatch):
    """compile_cache with jax's config updates and cache reset recorded
    instead of applied: the cache is process-global, and a test must
    not rebind it under the rest of the suite."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jcc

    from madsim_tpu import compile_cache as cc

    updates = {}
    monkeypatch.setattr(cc, "_active_dir", None)
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.setattr(jcc, "reset_cache", lambda: None)
    return cc, updates


def test_compile_cache_placed_from_outside_wins(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: that directory is the active one
    whatever --compile-cache / $MADSIM_TPU_COMPILE_CACHE /
    EngineConfig.compile_cache_dir say, nothing is nested under it, and
    the code never sets `jax_compilation_cache_dir` — only the
    cache-everything thresholds."""
    cc, updates = _recorded_cache_wiring(monkeypatch)
    outside = tmp_path / "placed-by-the-driver"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outside))
    monkeypatch.setenv("MADSIM_TPU_COMPILE_CACHE", str(tmp_path / "env"))
    assert cc.enable_compile_cache(str(tmp_path / "flag")) == str(outside)
    assert cc.active_compile_cache() == str(outside)
    assert "jax_compilation_cache_dir" not in updates
    assert updates == {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }
    assert sorted(os.listdir(tmp_path)) == ["placed-by-the-driver"]
    assert os.listdir(outside) == []  # no subdirectory, no probe left
    assert cc.cache_entry_count() == 0
    # first directory wins: a later Engine(compile_cache_dir=...) is ignored
    assert cc.enable_compile_cache(str(tmp_path / "later")) == str(outside)


def test_compile_cache_default_is_the_checkout_dir(tmp_path, monkeypatch):
    """Nothing set: the cache is ON at the fixed <checkout>/
    .madsim-jit-cache (never a temporary name); $MADSIM_TPU_COMPILE_CACHE
    or an explicit path moves it."""
    cc, updates = _recorded_cache_wiring(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("MADSIM_TPU_COMPILE_CACHE", raising=False)
    default = os.path.join(REPO, ".madsim-jit-cache")
    assert cc.DEFAULT_CACHE_DIR == default
    assert cc.enable_compile_cache() == default
    assert updates["jax_compilation_cache_dir"] == default
    monkeypatch.setattr(cc, "_active_dir", None)
    monkeypatch.setenv("MADSIM_TPU_COMPILE_CACHE", str(tmp_path / "env"))
    assert cc.enable_compile_cache() == str(tmp_path / "env")
    monkeypatch.setattr(cc, "_active_dir", None)
    assert cc.enable_compile_cache(str(tmp_path / "flag")) == str(tmp_path / "flag")
    assert updates["jax_compilation_cache_dir"] == str(tmp_path / "flag")


def test_aot_warm_start_beats_cold_trace(tmp_path, monkeypatch):
    """The AOT supersegment artifacts pay off: a rebuilt engine whose
    stream fns DESERIALIZE (warm, artifacts allowed) must start faster
    than the same rebuild forced to re-trace everything
    (measure_warm_compile(cold_trace=True) suspends the artifact
    cache). The persistent XLA executable cache backs BOTH rebuilds,
    so the delta isolates exactly the trace-vs-deserialize gap the
    flagship's sub-5s warm-start target rests on. Small echo shape:
    the gap is structural, not scale-dependent."""
    import jax

    from madsim_tpu import compile_cache as cc
    from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
    from madsim_tpu.models.echo import EchoMachine

    monkeypatch.setenv("MADSIM_TPU_AOT_CACHE", str(tmp_path / "aot"))
    if cc.active_compile_cache() is None:
        cc.enable_compile_cache(str(tmp_path / "xla"))
    cfg = EngineConfig(
        horizon_us=1_000_000, queue_capacity=16,
        faults=FaultPlan(n_faults=0, t_max_us=1),
    )
    built = []

    def build_and_run():
        eng = Engine(EchoMachine(), cfg)
        eng.run_stream(8, batch=16, segment_steps=64, max_steps=256)
        built.append(eng)

    build_and_run()  # cold: traces, exports, persists the artifacts
    arts = [f for _, _, fs in os.walk(str(tmp_path / "aot")) for f in fs]
    assert any(f.endswith(".jaxexp") for f in arts), arts
    cold_timings = built[-1].compile_timings
    assert cold_timings["aot_misses"] and cold_timings["trace_s"] > 0

    warm_aot = cc.measure_warm_compile(build_and_run)
    aot_timings = built[-1].compile_timings
    warm_trace = cc.measure_warm_compile(build_and_run, cold_trace=True)
    assert warm_aot is not None and warm_trace is not None
    # structural receipts first (timing asserts alone flake on a busy
    # 1-core box): the warm rebuild hit every artifact and re-traced
    # nothing; the cold_trace rebuild never even engaged the AOT layer
    assert set(aot_timings["aot_hits"]) == {
        "init_carry", "segment", "supersegment", "reset_rings"
    }
    assert not aot_timings["aot_misses"] and aot_timings["trace_s"] == 0.0
    # the suspended rebuild bypassed the AOT layer entirely
    assert getattr(built[-1], "compile_timings", None) is None
    # and the payoff itself: deserialize beats re-trace
    assert warm_aot < warm_trace, (warm_aot, warm_trace)
    jax.clear_caches()
