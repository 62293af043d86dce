"""The readers PR 25 added: device self time by `madsim.*` phase
(benchmark/phase_reduce.py, on a small recorded trace and on an xplane
file written here byte by byte), the hunt readers on a hand-made span
tree, and the set-up readers on a hand-filled compile log."""

import json
import os
import types

import pytest

from benchmark import cells, drive, hunt_spans, phase_reduce
from benchmark import trace_reduce as tr
from madsim_tpu.perf import compile_log
from madsim_tpu.perf.recorder import PerfRecorder

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
BENCH = cells.load_benchmark()
DEVICE_METRICS = sorted(phase_reduce.STEP_METRICS) + [
    "segment_overhead_share", "device_unscoped_share"]


def scoped_trace():
    with open(os.path.join(FIXTURES, "raft5_scoped.trace.json")) as f:
        return json.load(f)


def reader(metric):
    cell = next(w["name"] for w in BENCH["workloads"] if any(
        m["name"] == metric and cells.reports(m, w["name"])
        for m in BENCH["per_layer"]))
    return cells.load_reader(cells.load_cell(cell, BENCH), metric)


# -- an xplane file, written by hand -------------------------------------------


def varint(n):
    out = b""
    while True:
        byte, n = n & 0x7F, n >> 7
        out += bytes([byte | (0x80 if n else 0)])
        if not n:
            return out


def ld(field, payload):  # a length-delimited field
    return varint(field << 3 | 2) + varint(len(payload)) + payload


def vi(field, n):
    return varint(field << 3) + varint(n)


def xplane_bytes(planes):
    """planes: [(plane name, [(full HLO text of the op, op_name or None)])].
    The op_name is filed as the `tf_op` stat of the op's event metadata,
    as a string for even ops and as a reference to a stat-metadata name
    for odd ones: the profiler uses both."""
    space = b""
    for name, ops in planes:
        stat_meta = {1: "tf_op", 2: "flops"}
        events = b""
        for k, (text, op_name) in enumerate(ops):
            stats = ld(5, vi(1, 2) + vi(3, 12288))  # flops, uint64
            stats += ld(5, vi(1, 2) + varint(2 << 3 | 1) + b"\0" * 8)  # a double
            if op_name is not None and k % 2 == 0:
                stats += ld(5, vi(1, 1) + ld(5, op_name.encode()))
            elif op_name is not None:
                ref = 10 + k
                stat_meta[ref] = op_name
                stats += ld(5, vi(1, 1) + vi(7, ref))
            meta = vi(1, k + 1) + ld(2, text.encode()) + stats
            events += ld(4, vi(1, k + 1) + ld(2, meta))
        stat_names = b"".join(
            ld(5, vi(1, i) + ld(2, vi(1, i) + ld(2, n.encode())))
            for i, n in stat_meta.items())
        line = ld(3, vi(1, 7) + ld(2, b"XLA Ops"))
        space += ld(1, vi(1, 3) + ld(2, name.encode()) + line + events + stat_names)
    return space


def write_xplane(workdir, scopes):
    """The file where the harness leaves it, holding `scopes`
    {short op name: op_name or None}."""
    run = os.path.join(workdir, "trace", "plugins", "profile", "run1")
    os.makedirs(run)
    ops = [(f"%{op} = s32[8]{{0}} fusion(s32[8]{{0}} %p), kind=kLoop", name)
           for op, name in scopes.items()]
    with open(os.path.join(run, "host.xplane.pb"), "wb") as f:
        f.write(xplane_bytes([
            ("/device:TPU:0", ops),
            ("/host:CPU", [("%not_a_device_op = s32[] add()", "madsim.step.pop/add")]),
        ]))


def device_obs(workdir, trace):
    return types.SimpleNamespace(
        trace=tr.reduce(trace), session=types.SimpleNamespace(workdir=workdir))


# -- phase_reduce --------------------------------------------------------------


def slow_phase(op_name):
    """The innermost madsim component, the slow way: walk the path."""
    for part in reversed((op_name or "").split("/")):
        part = part.removeprefix("vmap(").rstrip(")")
        if part.startswith("madsim."):
            return part[len("madsim."):]
    return None


def test_phases_partition_busy_time():
    """On the recorded slice: phase self times and the unscoped rest sum
    to trace_reduce's busy time, the nine shares sum to 100% of it, each
    phase equals a slow walk's, and both kinds of op are there."""
    trace = scoped_trace()
    got = phase_reduce.reduce(trace)
    busy = tr.reduce(trace)["busy_s"]
    assert got["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert sum(got["phases"].values()) + got["unscoped_s"] == pytest.approx(busy)
    assert got["scoped"] and got["unscoped_s"] > 0
    slow = {}
    for op, seconds in tr.reduce(trace)["self_s"].items():
        phase = slow_phase(trace["scopes"].get(op))
        slow[phase] = slow.get(phase, 0.0) + seconds
    assert got["unscoped_s"] == pytest.approx(slow.pop(None))
    assert got["phases"] == pytest.approx(slow)
    assert {"step.pop", "step.handlers", "step.outbox", "step.timers",
            "step.invariants", "step"} <= set(got["phases"])
    shares = phase_reduce.shares(got)
    assert sorted(shares) == sorted(DEVICE_METRICS)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["step_push_share"] == pytest.approx(100.0 * (
        got["phases"]["step.outbox"] + got["phases"]["step.timers"]) / busy)
    # the loop's own control ops are scoped `step`: segment overhead
    assert shares["segment_overhead_share"] == pytest.approx(
        100.0 * got["phases"]["step"] / busy)


@pytest.mark.parametrize("op_name, phase", [
    ("jit(supersegment)/while/body/closed_call/cond/branch_1_fun/madsim.step"
     "/while/body/vmap(madsim.step.handlers)/and:", "step.handlers"),
    ("jit(supersegment)/madsim.harvest/madsim.ring_append"
     "/madsim.collective.ring-append-gather/gather:", "collective.ring-append-gather"),
    ("jit(supersegment)/madsim.step/while/body/madsim.step.pop/vmap()", "step.pop"),
    ("jit(supersegment)/while/body/closed_call:", None),
    ("", None),
])
def test_an_ops_phase_is_its_innermost_scope(op_name, phase):
    assert phase_reduce.phase_of(op_name) == phase == slow_phase(op_name)


def test_op_scopes_reads_the_profilers_file(tmp_path):
    """The op_name sits in the event METADATA's stats, which jax's
    ProfileData does not show: read from the file itself, as a string or
    as a reference, device planes only, ops without one left out."""
    scopes = {"fusion.1": "jit(f)/madsim.step/while/body/vmap(madsim.step.rng)/xor",
              "fusion.2": "jit(f)/madsim.refill/add",
              "copy.3": None,
              "madsim_step_mega.11": "jit(f)/madsim.step/while/body/madsim.step.pop/pallas_call"}
    write_xplane(str(tmp_path), scopes)
    [path] = [os.path.join(d, f) for d, _s, fs in os.walk(tmp_path) for f in fs]
    assert phase_reduce.op_scopes(path) == {
        k: v for k, v in scopes.items() if v is not None}


@pytest.mark.parametrize("metric", DEVICE_METRICS)
def test_device_reader_reads_its_share(metric, tmp_path):
    trace = scoped_trace()
    write_xplane(str(tmp_path), {
        op: trace["scopes"].get(op) for op in tr.reduce(trace)["self_s"]})
    got = reader(metric).read(device_obs(str(tmp_path), trace))
    assert got == pytest.approx(
        phase_reduce.shares(phase_reduce.reduce(trace))[metric])
    assert 0.0 <= got <= 100.0


def test_no_scoped_op_silences_every_device_reader(tmp_path, capsys):
    """A program from before the scopes, or an executable the compile
    cache kept from then: where the step loop carries no scope every
    reader returns None (the metric is left out, with one line saying
    why), never 0% or 100% unscoped; so does an untraced run and a run
    whose xplane file is gone."""
    trace = scoped_trace()
    bare = {op: "jit(supersegment)/while/body/closed_call:"
            for op in tr.reduce(trace)["self_s"]}
    write_xplane(str(tmp_path), bare)
    obs = device_obs(str(tmp_path), trace)
    assert [reader(m).read(obs) for m in DEVICE_METRICS] == [None] * 9
    assert capsys.readouterr().out.count("under a madsim.step scope") == 1
    assert phase_reduce.shares(phase_reduce.phases(obs.trace["self_s"], bare)) is None
    # a mixed cache (my chip run, PR 25): the parent's capture held a
    # few ops of a kernel-less program the change had compiled, scoped —
    # the step loop's were not, so there is still nothing to read
    mixed = dict(bare)
    mixed[next(iter(mixed))] = "jit(reset_rings)/madsim.counters/stack:"
    reduced = phase_reduce.phases(obs.trace["self_s"], mixed)
    assert reduced["phases"] and not reduced["scoped"]
    assert phase_reduce.shares(reduced) is None
    gone = device_obs(str(tmp_path / "nowhere"), trace)
    untraced = types.SimpleNamespace(trace=None, session=gone.session)
    for o in (gone, untraced):
        assert [reader(m).read(o) for m in DEVICE_METRICS] == [None] * 9


# -- the hunt readers, on a hand-made span tree --------------------------------


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, s):
        self.t += s


def one_hunt(rec, clk, compile_s, run_s):
    """One hunt's spans as the program emits them; (t0, t1) of it on the
    recorder's clock. 0.3 s of it lie under no span."""
    t0 = clk.t
    clk.tick(0.1)  # argparse, the CLI's own prologue: unattributed
    with rec.span("campaign"):  # the benchmark's own span: left out
        with rec.span("warmup_dispatch"):
            with rec.span("run_stream", n_seeds=1):
                clk.tick(0.5)
        with rec.span("run_stream", n_seeds=16384):
            clk.tick(1.0)
        with rec.span("hunt_report"):
            clk.tick(0.02)
        with rec.span("shrink"):  # the benchmark's
            for k, stage in enumerate(("base", "faults", "kinds")):
                with rec.span("shrink_candidate", stage=stage):
                    clk.tick(0.01)  # the candidate Engine
                    with rec.span("replay", seed=3, traced=False):
                        clk.tick(0.02)  # init_lane
                        if k != 1:
                            with rec.span("compile", program="replay.run"):
                                clk.tick(compile_s)
                        with rec.span("replay_run"):
                            clk.tick(run_s)
        with rec.span("corpus_record", seed=3):
            with rec.span("replay", seed=3, traced=False):
                with rec.span("replay_run"):
                    with rec.span("compile", program="replay.step"):
                        clk.tick(0.25)  # a traced replay nests it here
                    clk.tick(0.05)
        clk.tick(0.2)  # between the commands: unattributed
        with rec.span("verify"):  # the benchmark's
            with rec.span("regress_entry", seed=3):
                clk.tick(0.4)
            with rec.span("audit_entry", seed=3):
                clk.tick(0.6)
    return t0, clk.t


@pytest.fixture
def hunt_obs():
    clk = Clock()
    rec = PerfRecorder(clock=clk)
    session = drive.Session("unused", traced=True)
    session.recorder, session._recorder_t0 = rec, 100.0
    with rec:
        spans = [one_hunt(rec, clk, c, r) for c, r in
                 ((0.30, 0.10), (0.50, 0.20), (0.40, 0.40))]
        with rec.span("compile", program="supersegment"):  # outside any hunt
            clk.tick(9.0)
    records = [{"t0": 100.0 + a, "t1": 100.0 + b} for a, b in spans]
    return types.SimpleNamespace(session=session, records=records)


def hunt_wall(compile_s, run_s):
    return (0.1 + 0.5 + 1.0 + 0.02 + 3 * 0.03 + 2 * compile_s + 3 * run_s
            + 0.30 + 0.2 + 1.0)


#: per hunt: what each reader sums, by hand; the metric is the median
HUNT_EXPECTED = {
    "hunt_warmup_dispatch_s": lambda c, r: 0.5,
    "replay_compile_s": lambda c, r: 2 * c + 0.25,
    "replay_run_s": lambda c, r: 3 * r + 0.05,
    "hunt_file_s": lambda c, r: 0.02 + 0.30,
    "verify_regress_s": lambda c, r: 0.4,
    "verify_audit_s": lambda c, r: 0.6,
    "hunt_unattributed_share": lambda c, r: 100.0 * 0.3 / hunt_wall(c, r),
}


@pytest.mark.parametrize("metric", sorted(HUNT_EXPECTED))
def test_hunt_reader_reads_the_median_hunt(metric, hunt_obs):
    per_hunt = sorted(HUNT_EXPECTED[metric](c, r) for c, r in
                      ((0.30, 0.10), (0.50, 0.20), (0.40, 0.40)))
    assert reader(metric).read(hunt_obs) == pytest.approx(per_hunt[1])


def test_hunt_readers_are_silent_without_the_tree(hunt_obs):
    """Untraced: no recorder. A program from before the tree: spans with
    no `parent`, and none of the new names. Either way every reader
    returns None and none raises."""
    untraced = types.SimpleNamespace(
        session=drive.Session("unused"), records=hunt_obs.records)
    old = hunt_obs.session.recorder
    for s in old.spans:
        del s["parent"], s["id"], s["trace_id"]
    for obs in (untraced, hunt_obs):
        assert [reader(m).read(obs) for m in sorted(HUNT_EXPECTED)] == [None] * 7
    assert hunt_spans.program_spans(untraced) == []


def test_an_old_programs_spans_give_no_unattributed_share(hunt_obs):
    """With a parent index but none of the new spans (only the
    executor's), `hunt_unattributed_share` has nothing to stand on."""
    rec = hunt_obs.session.recorder
    rec.spans[:] = [s for s in rec.spans if s["name"] in ("run_stream", "campaign")]
    assert reader("hunt_unattributed_share").read(hunt_obs) is None
    assert reader("replay_run_s").read(hunt_obs) is None
    assert reader("replay_compile_s").read(hunt_obs) is None


def test_the_programs_spans_survive_the_benchmarks_recorder():
    """The benchmark's recorder subclasses `PerfRecorder.span` and yields
    itself; the program must not expect a span's record from `with`
    (a traced hunt on the chip crashed on exactly that, PR 25): what a
    span learns inside reaches its args through `maybe_note`."""
    from madsim_tpu.__main__ import build_machine
    from madsim_tpu.engine import Engine, EngineConfig, FaultPlan, shrink

    eng = Engine(build_machine("demo-nodedup-mvcc", 0), EngineConfig(
        horizon_us=8_000_000, queue_capacity=48, faults=FaultPlan(n_faults=0)))
    rec = drive.annotated_recorder()
    with rec:
        sr = shrink(eng, 3, max_steps=4000)
    cands = [s for s in rec.spans if s["name"] == "shrink_candidate"]
    assert len(cands) == sr.attempts and cands[0]["args"] == {
        "stage": "base", "accepted": True}
    replays = [s for s in rec.spans if s["name"] == "replay"]
    assert len(replays) == sr.attempts
    assert all(s["args"]["steps"] == sr.steps for s in replays)


# -- the set-up readers, on a hand-filled compile log ---------------------------


@pytest.mark.parametrize("metric, expected", [
    ("setup_trace_s", 3.0),      # [10, 12] and [11, 13] overlap: a union
    ("setup_lower_s", 0.5),
    ("setup_backend_s", 4.0),    # [20, 24]; the one ending at 31 is clipped out
    ("setup_cache_misses", 2.0),
])
def test_setup_reader_reads_the_warmups_compile_stages(metric, expected, monkeypatch):
    log = compile_log.CompileLog()
    log.events += [
        (12.0, "trace", "supersegment", 2.0), (13.0, "trace", "cumsum", 2.0),
        (14.0, "lower", "supersegment", 0.5), (24.0, "backend", "supersegment", 4.0),
        (31.0, "backend", "replay.run", 0.5),  # after the warm-up
        (5.0, "trace", "init_carry", 1.0),     # before it
    ]
    log.cache_misses += [4.0, 21.0, 23.0, 30.5]
    monkeypatch.setattr(compile_log, "_LOG", log)
    obs = types.SimpleNamespace(warmup={"t0": 10.0, "t1": 30.0})
    assert reader(metric).read(obs) == pytest.approx(expected)
    snap = compile_log.snapshot(10.0, 30.0)
    assert snap["trace_s"] + snap["lower_s"] + snap["backend_s"] <= 20.0
    assert list(snap["by_program"])[0] == "supersegment"


def test_new_metrics_name_a_layer_the_benchmark_had():
    """PR 25 adds 20 entries at the end of `per_layer`; each names a
    layer that an older entry names, and a reader file exists."""
    old, new = BENCH["per_layer"][:19], BENCH["per_layer"][19:]
    assert len(new) >= 20
    layers = {m["layer"] for m in old} | {"step", "segment"}
    for m in new[:20]:
        assert m["layer"] in layers, m
        assert os.path.isfile(os.path.join(
            cells.DATA_ROOT, "layer_metrics", m["name"] + ".py")), m
