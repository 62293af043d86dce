"""The three-clock profiler (madsim_tpu/perf/xprof): always-on phase
scopes that move no cache key, a gate that only captures, device-trace
parsing, the compile autopsy, the golden
clock-alignment fixture for merge_plane, and the fleet /profile
endpoint's degraded/full paths.

The merge/parse half is jax-free host math — hand-built trace documents
with known clock offsets; the scope tests lower a tiny engine on the CPU.
"""

import gzip
import json
import os

import pytest

from madsim_tpu.perf import xprof

# -- the gate captures; it selects no program ---------------------------------

ENGINE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "madsim_tpu", "engine")

STEP_SCOPES = ("step.pop", "step.rng", "step.handlers", "step.outbox",
               "step.timers", "step.recorder", "step.coverage",
               "step.invariants")


def _tiny_engine(megakernel=False, provenance=False):
    from madsim_tpu.__main__ import build_machine
    from madsim_tpu.engine import Engine, EngineConfig, FaultPlan

    cfg = EngineConfig(
        horizon_us=1_000_000, queue_capacity=16, faults=FaultPlan(n_faults=1),
        rng_stream=3, flight_recorder=True, coverage=True,
        provenance=provenance, pallas_megakernel=megakernel,
    )
    return Engine(build_machine("echo", 0), cfg,
                  use_pallas_pop=True if megakernel else None)


def _lowered_supersegment(eng, debug_info=True) -> str:
    import jax
    import jax.numpy as jnp

    init_carry, _seg, supersegment, _reset = eng._stream_fns(32, 200, 16, 8)
    carry = jax.eval_shape(init_carry, jax.ShapeDtypeStruct((8,), jnp.uint32))
    need = jax.ShapeDtypeStruct((), jnp.int32)
    return supersegment.lower(carry, need).as_text(debug_info=debug_info)


def test_gate_captures_and_selects_no_program(monkeypatch):
    """MADSIM_TPU_XPROF means "capture a device profile" and nothing
    else: set or unset, `scope` is a real named scope, the engine serves
    the same `_stream_fns` cache entry and lowers the same text. Only
    the capture side (sync markers) reads it."""
    monkeypatch.delenv(xprof.ENV_GATE, raising=False)
    assert not xprof.enabled()
    assert xprof.sync_marker("anywhere") is None
    eng = _tiny_engine()
    fns_off = eng._stream_fns(32, 200, 16, 8)
    text_off = _lowered_supersegment(eng, debug_info=False)
    monkeypatch.setenv(xprof.ENV_GATE, "1")
    assert xprof.enabled()
    assert eng._stream_fns(32, 200, 16, 8) is fns_off
    assert len(eng._stream_cache) == 1
    assert _lowered_supersegment(_tiny_engine(), debug_info=False) == text_off
    assert "madsim.step.pop" in _lowered_supersegment(eng)
    monkeypatch.setenv(xprof.ENV_GATE, "0")
    assert not xprof.enabled()


def test_engine_reads_no_measurement_gate():
    """Source pin: nothing under madsim_tpu/engine reads the gate or
    annotates beside a recorder span (one name per site: the span,
    which an annotating recorder writes into the capture)."""
    for name in os.listdir(ENGINE_DIR):
        if name.endswith(".py"):
            src = open(os.path.join(ENGINE_DIR, name)).read()
            assert "xprof.enabled()" not in src, name
            assert "_xprof.annotation" not in src, name


@pytest.mark.parametrize("megakernel", [False, True],
                         ids=["xla-step", "megakernel"])
def test_lowered_stream_names_every_step_scope(megakernel):
    """Every cost-model row of the step is a `madsim.step.*` scope in the
    lowered supersegment, on both step paths; every name the program
    emits is listed in DEVICE_PHASES (collectives apart)."""
    import re

    text = _lowered_supersegment(_tiny_engine(megakernel, provenance=True))
    found = set(re.findall(r"madsim\.([A-Za-z0-9_.\-]+)", text))
    for name in STEP_SCOPES + ("step.provenance", "step", "refill",
                               "harvest", "cov_flush", "counters"):
        assert name in found, (name, sorted(found))
    assert {n for n in found if not n.startswith("collective.")} \
        <= set(xprof.DEVICE_PHASES), sorted(found)


def test_persistent_cache_key_does_not_move_with_scopes(tmp_path, monkeypatch):
    """THE reason the scopes can be on always: a name stack lives in MLIR
    locations, and jax strips debug info before hashing a module for the
    persistent cache. Fill a fresh cache with `jax.named_scope` patched
    to a null context, then run the real program: zero cache misses."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    misses, hits = [], []

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            misses.append(event)
        elif event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(on_event)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def stream_once():
        jax.clear_caches()
        return _tiny_engine().run_stream(
            8, batch=8, segment_steps=32, max_steps=200)

    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda _name: contextlib.nullcontext())
            bare = stream_once()
        assert misses, "the bare run must have filled the cache"
        del misses[:], hits[:]
        scoped = stream_once()
        assert not misses and hits, (len(misses), len(hits))
        assert scoped["completed"] == bare["completed"]
        assert scoped["failing"] == bare["failing"]
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()


# -- device-trace parsing ----------------------------------------------------


def test_load_device_events_parses_and_filters(tmp_path):
    events = [
        {"ph": "X", "name": "madsim.step", "ts": 10, "dur": 5, "pid": 7},
        {"ph": "X", "name": "$profiler.py:120", "ts": 0, "dur": 99},
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "dev"}},
        "not-a-dict",
    ]
    gz = tmp_path / "t.trace.json.gz"
    with gzip.open(gz, "wt") as f:
        json.dump({"traceEvents": events}, f)
    got = xprof.load_device_events(str(gz))
    assert [e.get("name") for e in got] == ["madsim.step", "process_name"]
    # python-tracer frames kept on request
    assert len(xprof.load_device_events(str(gz), keep_python=True)) == 3
    # degraded inputs never raise: missing, torn, wrong shape -> []
    assert xprof.load_device_events(str(tmp_path / "nope.json")) == []
    torn = tmp_path / "torn.json"
    torn.write_text('{"traceEvents": [')
    assert xprof.load_device_events(str(torn)) == []
    scalar = tmp_path / "scalar.json"
    scalar.write_text('{"traceEvents": 42}')
    assert xprof.load_device_events(str(scalar)) == []


def test_find_device_trace_prefers_perfetto(tmp_path):
    assert xprof.find_device_trace(str(tmp_path)) is None
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.trace.json.gz").write_bytes(b"x")
    assert xprof.find_device_trace(str(tmp_path)).endswith(
        "host.trace.json.gz")
    (run / "perfetto_trace.json.gz").write_bytes(b"x")
    assert xprof.find_device_trace(str(tmp_path)).endswith(
        "perfetto_trace.json.gz")


# -- the golden clock-alignment fixture --------------------------------------


def _host_doc():
    """A hand-built host plane: two executor spans (dispatch 1000–1500,
    counters_poll 2000–2300 host µs) and two sync instants, seqs 0/1."""
    return {
        "traceEvents": [
            {"ph": "M", "pid": 0, "name": "process_name",
             "args": {"name": "madsim_tpu host"}},
            {"ph": "X", "pid": 0, "tid": 0, "name": "dispatch",
             "ts": 1000.0, "dur": 500.0, "args": {}},
            {"ph": "X", "pid": 0, "tid": 0, "name": "counters_poll",
             "ts": 2000.0, "dur": 300.0, "args": {}},
            {"ph": "i", "s": "t", "pid": 0, "tid": 0,
             "name": "madsim.sync", "ts": 1000.0,
             "args": {"point": "a", "seq": 0}},
            {"ph": "i", "s": "t", "pid": 0, "tid": 0,
             "name": "madsim.sync", "ts": 2300.0,
             "args": {"point": "b", "seq": 1}},
        ],
    }


def _device_events():
    """The same run on the device clock, which started 900 µs earlier:
    sync slices at 100/1400 device µs match host 1000/2300 exactly, so
    the true offset is +900; both phase slices must land INSIDE their
    enclosing host spans after the shift."""
    return [
        {"ph": "X", "pid": 3, "tid": 0, "name": "madsim.sync:0",
         "ts": 100.0, "dur": 0.0},
        {"ph": "X", "pid": 3, "tid": 0, "name": "madsim.sync:1",
         "ts": 1400.0, "dur": 0.0},
        {"ph": "X", "pid": 3, "tid": 0, "name": "madsim.step",
         "ts": 150.0, "dur": 200.0},
        {"ph": "X", "pid": 3, "tid": 0, "name": "madsim.counters",
         "ts": 1150.0, "dur": 100.0},
        # anonymous XLA fusion: merged in, but never counted as a
        # madsim phase for attribution
        {"ph": "X", "pid": 3, "tid": 0, "name": "fusion.42",
         "ts": 500.0, "dur": 50.0},
    ]


def _virtual_doc():
    return {
        "traceEvents": [
            {"ph": "M", "pid": 0, "name": "process_name",
             "args": {"name": "node timelines"}},
            {"ph": "X", "pid": 0, "tid": 2, "name": "elect",
             "ts": 123456.0, "dur": 10.0, "args": {}},
        ],
    }


def test_merge_plane_golden_clock_alignment():
    """THE alignment golden: device time shifts by the median host−
    device sync delta (+900 µs here) so each device phase lands inside
    the host span that dispatched it; virtual timestamps are NEVER
    shifted — simulated µs stay simulated µs, renamed as such."""
    doc = xprof.merge_plane(
        _host_doc(), _device_events(), _virtual_doc(),
        meta={"trace_id": "golden"})
    s = doc["madsim_xprof_summary"]
    assert s["clock_offset_us"] == pytest.approx(900.0)
    assert s["sync_points"] == 2
    assert s["tracks"] == {"host": True, "device": True, "virtual": True}

    by_name = {}
    for e in doc["traceEvents"]:
        by_name.setdefault(e.get("name"), []).append(e)
    # device phases, host-aligned: step 1050–1250 ⊂ dispatch 1000–1500,
    # counters 2050–2150 ⊂ counters_poll 2000–2300
    [step] = by_name["madsim.step"]
    assert step["ts"] == pytest.approx(1050.0)
    [dispatch] = by_name["dispatch"]
    assert (dispatch["ts"] <= step["ts"]
            and step["ts"] + step["dur"] <= dispatch["ts"] + dispatch["dur"])
    [counters] = by_name["madsim.counters"]
    [poll] = by_name["counters_poll"]
    assert (poll["ts"] <= counters["ts"]
            and counters["ts"] + counters["dur"] <= poll["ts"] + poll["dur"])
    # virtual stays virtual: ts untouched, pid its own, label says so
    [velect] = by_name["elect"]
    assert velect["ts"] == 123456.0
    host_dev_pids = {e["pid"] for e in _host_doc()["traceEvents"]} | {
        e["pid"] for e in by_name["madsim.step"]}
    assert velect["pid"] not in host_dev_pids
    vmeta = [e for e in by_name["process_name"]
             if "VIRTUAL" in (e.get("args") or {}).get("name", "")]
    assert len(vmeta) == 1 and "simulated" in vmeta[0]["args"]["name"]
    # attribution golden: host union [1000,1500]∪[2000,2300] = 800 µs
    # over the 1300 µs host window (device phases add nothing new —
    # they sit inside host spans; the anonymous fusion never counts)
    assert s["host_wall_us"] == pytest.approx(1300.0)
    assert s["attribution"] == pytest.approx(800.0 / 1300.0, abs=1e-3)


def test_merge_plane_without_sync_markers_anchors_at_host_start():
    """A capture with no matched sync markers still merges — anchored
    so the earliest device slice lands at the host window start, and
    honestly flagged with sync_points 0."""
    devs = [e for e in _device_events()
            if not e["name"].startswith("madsim.sync")]
    doc = xprof.merge_plane(_host_doc(), devs, None)
    s = doc["madsim_xprof_summary"]
    assert s["sync_points"] == 0
    assert s["tracks"]["device"] is True and s["tracks"]["virtual"] is False
    assert s["clock_offset_us"] == pytest.approx(1000.0 - 150.0)
    [step] = [e for e in doc["traceEvents"]
              if e.get("name") == "madsim.step"]
    assert step["ts"] == pytest.approx(1000.0)


def test_merge_plane_degrades_to_host_only():
    doc = xprof.merge_plane(_host_doc(), None, None)
    s = doc["madsim_xprof_summary"]
    assert s["tracks"] == {"host": True, "device": False, "virtual": False}
    assert s["sync_points"] == 0 and s["clock_offset_us"] == 0.0
    assert s["attribution"] == pytest.approx(800.0 / 1300.0, abs=1e-3)
    # write_doc round-trips, gzipped and plain
    import tempfile

    d = tempfile.mkdtemp()
    for name in ("m.json", "m.json.gz"):
        path = os.path.join(d, name)
        n = xprof.write_doc(doc, path)
        opener = gzip.open if name.endswith(".gz") else open
        with opener(path, "rt") as f:
            back = json.load(f)
        assert len(back["traceEvents"]) == n
        assert back["madsim_xprof_summary"] == s


# -- compile autopsy ---------------------------------------------------------


def test_compile_autopsy_stages_and_cost():
    """The AOT-stages split on a real (tiny) jitted fn: stages
    non-negative and summing to total, cost_analysis flops reported on
    CPU, metrics never fabricated."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: jnp.sin(x) @ x.T)
    aval = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    out = xprof.compile_autopsy(fn, [aval], label="tiny")
    assert out["label"] == "tiny"
    for k in ("trace_s", "lower_s", "backend_s"):
        assert out[k] >= 0.0
    assert out["total_s"] == pytest.approx(
        out["trace_s"] + out["lower_s"] + out["backend_s"], abs=1e-3)
    assert out["flops"] and out["flops"] > 0
    assert out["bytes_accessed"] and out["bytes_accessed"] > 0


# -- the fleet /profile endpoint ---------------------------------------------


def test_fleet_profile_endpoint_degraded_and_full(tmp_path):
    """/jobs/{id}/profile merges whatever planes exist: with no xprof
    artifacts it degrades to the host plane (the cross-process
    timeline); once the worker's device capture and failing-lane
    virtual trace are on disk they merge in, and fsck recognizes both
    artifact shapes. Jax-free throughout."""
    from madsim_tpu.fleet import fsck as fsck_mod
    from madsim_tpu.fleet.api import FleetAPI
    from madsim_tpu.fleet.chaos import synthetic_driver
    from madsim_tpu.fleet.store import JobStore
    from madsim_tpu.fleet.worker import FleetWorker

    root = str(tmp_path)
    st = JobStore(root)
    job = st.submit({"machine": "chaos-echo", "seeds": 96, "batch": 32,
                     "faults": 0})
    FleetWorker(root, worker_id="w1", driver=synthetic_driver,
                poll_s=0.01).run(drain=True)
    api = FleetAPI(st)

    status, _, body = api.handle("GET", "/jobs/nope/profile")
    assert status == 404

    status, _, body = api.handle("GET", f"/jobs/{job.id}/profile")
    doc = json.loads(body)
    assert status == 200
    assert doc["madsim_xprof_summary"]["tracks"] == {
        "host": True, "device": False, "virtual": False}
    assert doc["madsim_xprof_meta"]["trace_id"] == job.id

    # the worker's xprof artifacts appear -> the planes merge in
    with gzip.open(st.device_trace_path(job.id), "wt") as f:
        json.dump({"traceEvents": [
            {"ph": "X", "pid": 0, "tid": 0, "name": "madsim.step",
             "ts": 5.0, "dur": 2.0},
        ]}, f)
    with open(st.vtrace_path(job.id), "w") as f:
        json.dump(_virtual_doc(), f)
    status, _, body = api.handle("GET", f"/jobs/{job.id}/profile")
    doc = json.loads(body)
    assert status == 200
    s = doc["madsim_xprof_summary"]
    assert s["tracks"] == {"host": True, "device": True, "virtual": True}
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "madsim.step" in names and "elect" in names
    # a torn vtrace degrades (no virtual track), never 500s
    with open(st.vtrace_path(job.id), "w") as f:
        f.write('{"traceEvents": [')
    status, _, body = api.handle("GET", f"/jobs/{job.id}/profile")
    assert status == 200
    assert json.loads(body)["madsim_xprof_summary"]["tracks"][
        "virtual"] is False
    with open(st.vtrace_path(job.id), "w") as f:
        json.dump(_virtual_doc(), f)

    # fsck knows both artifact shapes: the gz capture is opaque-but-
    # expected, the vtrace is JSON-checked without being read as a job
    rep = fsck_mod.scan(st)
    flagged = {x["path"] for x in rep["findings"]}
    assert st.device_trace_path(job.id) not in flagged
    assert st.vtrace_path(job.id) not in flagged
    with open(st.vtrace_path(job.id), "w") as f:
        f.write('{"torn')
    rep = fsck_mod.scan(st)
    [finding] = [x for x in rep["findings"]
                 if x["path"] == st.vtrace_path(job.id)]
    assert finding["verdict"] in ("truncated", "unparseable")
