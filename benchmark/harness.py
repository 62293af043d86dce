"""One cell, once: set-up, the window, the checks, the result line.

    set-up   import, engine build (the CLI's own `_build_engine`), ONE
             whole unmeasured campaign: every program the window runs is
             then compiled, or read from the persistent cache
    window   whole campaigns through the CLI until --seconds have
             passed; the campaign under way is finished
    after    memory peak; the lane sample against the XLA step path and
             the CPU backend (and, on a mesh, against one device); in a
             traced run the reduction of the profiler's trace
    line     one JSON object, last on stdout

A traced run (--trace 1) is a run of its own: the profiler covers the
window's first `trace_campaigns` campaigns, the program's host spans are
recorded, and the line carries the cell's per-layer metrics and
`breakdown` instead of its end-to-end metrics.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import random
import shutil
import statistics
import sys
import tempfile
import time

from benchmark import cells, checks, drive, kernel_bytes, trace_reduce

#: the platform a number may come from. Tests patch this to drive the
#: same code at a tiny size on the CPU; nothing else may.
PLATFORM = "tpu"


class Refusal(Exception):
    """This machine cannot give the cell's numbers; no result is printed."""


say = drive.say


def process_age_s() -> float:
    """Seconds since this process was started (the kernel's record of
    it), so that `setup_s` counts the interpreter's own start too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        boot_now = time.clock_gettime(time.CLOCK_BOOTTIME)
        return max(boot_now - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


def campaign_seeds(traffic: dict, seed: int):
    """The one traffic generator: where each campaign of a run starts in
    the seed space. Every --seed runs the same pool of `pool` campaigns,
    in an order drawn from the seed, so runs with different seeds do the
    same work; past the pool the campaigns go on to fresh seeds. A file
    without `slots` never comes back to a range: a hunt's replay
    programs are keyed by the seed shrunk, so a second visit would find
    compiled what the first one paid for.

    Where the traffic file names its ranges (`slots`: slot numbers that
    `pool_check.py` has run whole and found to lose no lane), position k
    is the k-th of that list: the first `pool` of it are the pool, the
    rest the fresh ranges in list order. Past the list's end the
    campaigns go round the fresh ranges again, in list order (round the
    pool, in this seed's order, where the list is the pool alone): a
    run never leaves the checked ranges, at whatever rate the program
    runs, and one `benchmark:` line says when it first wraps. A second
    visit is the same work as the first: a lane's result depends on its
    seed alone, a sweep campaign is one CLI call with a carry of its
    own, and nothing of a sweep is keyed by seed between calls (PR 37
    on the chip: a range's second, third and fourth visits resolved its
    first one's seeds in its first one's seconds to 0.02%, PERF.md
    section 6)."""
    pool = int(traffic["pool"])
    order = list(range(pool))
    random.Random(int(seed)).shuffle(order)
    slots = traffic.get("slots")
    fresh = len(slots) - pool if slots is not None else 0
    k = 0
    while True:
        slot = order[k] if k < pool else k
        if slots is not None:
            if k == len(slots):
                say(f"benchmark: campaign {k} wraps: the traffic file names "
                    f"{len(slots)} checked ranges, and a run never leaves them")
            if k >= len(slots):
                slot = pool + (k - pool) % fresh if fresh else order[k % pool]
            slot = int(slots[slot])
        yield int(traffic["base_seed"]) + slot * int(traffic["stride"])
        k += 1


def sample_seed_start(traffic: dict, seed: int, lanes: int) -> int:
    """Where the lane sample starts: below the campaigns' seeds, drawn
    from --seed, inside uint32 whatever the seed."""
    slots = max(1, int(traffic["warmup_seed"]) // max(lanes, 1) - 1)
    return (int(seed) % slots) * lanes


@dataclasses.dataclass
class Observation:
    """What a per-layer reader may read. A reader that finds nothing to
    read returns None and its metric is left out of the line."""

    cell: cells.Cell
    records: list  # the window's campaign records (campaigns/<kind>.py)
    warmup: dict  # the unmeasured first campaign's record
    session: drive.Session
    window: tuple  # (t0, t1) on perf_counter
    trace: dict | None  # trace_reduce.reduce(...) of the traced campaigns
    traced_records: list  # the records the profiler covered
    sample: dict  # facts of the lane sample (lanes, events, seconds, ...)
    peaks: dict  # peaks.json entry of this device kind
    kernel_shapes: dict  # kernel_bytes.shapes_of(engine, lanes)
    memory_peak_bytes: int | None

    def stream_calls(self) -> list:
        """The timed `run_stream` calls (the warm-up dispatch of each
        campaign asks for one seed and is left out)."""
        return [c for r in self.records for c in r["calls"] if c["n_seeds"] > 1]

    def campaign_median(self, key: str) -> float:
        """The median over the window's campaigns of one record field."""
        return statistics.median(r[key] for r in self.records)

    def spans_inside(self, name: str, intervals: list) -> list:
        """The program's host spans of that name lying inside any of
        `intervals` [(t0, t1)] (traced run only: else there are none)."""
        return [
            (t0, t1) for n, t0, t1 in self.session.spans()
            if n == name and any(a <= t0 and t1 <= b for a, b in intervals)
        ]


def check_device(cell: cells.Cell) -> dict:
    device = drive.device_info()
    if device["platform"] != PLATFORM:
        raise Refusal(
            f"jax's default platform is {device['platform']!r} "
            f"({device['kind']}), not {PLATFORM!r}: no chip, no number"
        )
    if device["count"] < cell.chips:
        raise Refusal(
            f"cell {cell.name} needs {cell.chips} chip(s), jax sees "
            f"{device['count']}"
        )
    return device


def start_profiler(trace_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer slows the host loop
    opts.host_tracer_level = 2  # TraceAnnotation: the bench:<span> events
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop_profiler(trace_dir: str) -> str:
    import jax

    jax.profiler.stop_trace()
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler left no xplane file under {trace_dir}")
    return found[-1]


def set_up(cell, campaign, workdir: str, traced: bool) -> tuple:
    """(session, the warm-up campaign's record, every unmeasured record).

    One whole unmeasured campaign compiles, or reads from the persistent
    cache, every program a campaign runs. Where campaigns of one mix
    differ in their programs (`prewarm_pool`: a hunt's shrink and verify
    programs depend on the seed it shrinks) and this checkout's cache
    was cold, the whole pool is run once as well, so that nothing
    compiles cold inside any window; jax's in-process caches are then
    dropped and the set-up made again, so that this run's window starts
    where every later run's does: a new engine, a warm persistent cache."""
    session = drive.Session(workdir, traced=traced)
    session.listen_for_compiles()
    warmup_seed = int(cell.traffic["warmup_seed"])
    warmup = campaign.run_campaign(session, cell, warmup_seed, -1)
    unmeasured = [warmup]
    if cell.traffic.get("prewarm_pool") and session.cache_misses:
        import itertools

        import jax

        pool = itertools.islice(
            campaign_seeds(cell.traffic, 0), int(cell.traffic["pool"]))
        say(f"benchmark: cold cache ({len(session.cache_misses)} misses): "
            f"running the pool of {cell.traffic['pool']} once, unmeasured")
        unmeasured += [campaign.run_campaign(session, cell, start, -2 - k)
                       for k, start in enumerate(pool)]
        jax.clear_caches()
        session = drive.Session(workdir, traced=traced)
        session.listen_for_compiles()
        warmup = campaign.run_campaign(session, cell, warmup_seed, -1)
        unmeasured.append(warmup)
    return session, warmup, unmeasured


def run_window(cell, campaign, session, seed: int, seconds: float,
               trace_dir: str | None) -> tuple:
    """(records, traced_records, (t0, t1), seconds the profiler's stop
    took inside it, xplane path or None)."""
    records: list = []
    traced: list = []
    xplane = None
    n_traced = int(cell.traffic.get("trace_campaigns", 1)) if trace_dir else 0
    paused = 0.0  # the profiler's stop: not part of the window
    with session.recording():
        if n_traced:
            start_profiler(trace_dir)
        t0 = time.perf_counter()
        for k, seed_start in enumerate(campaign_seeds(cell.traffic, seed)):
            records.append(campaign.run_campaign(session, cell, seed_start, k))
            if k + 1 == n_traced:
                traced = list(records)
                t = time.perf_counter()
                xplane = stop_profiler(trace_dir)
                paused += time.perf_counter() - t
            if time.perf_counter() - t0 - paused >= seconds and k + 1 >= n_traced:
                break
        t1 = time.perf_counter()
    return records, traced, (t0, t1), paused, xplane


def after_window(cell, session, seed: int) -> tuple:
    """(problems, sample facts) of the checks made after the window."""
    eng = session.eng
    lanes = int(cell.config["check"]["sample_lanes"])
    max_steps = session.stream_args.max_steps  # as the CLI parsed it
    start = sample_seed_start(cell.traffic, seed, lanes)
    bad, facts = checks.sample_problems(eng, start, lanes, max_steps)
    say(f"benchmark: lane sample [{start}, {start + lanes}) checked in "
        f"{facts['seconds']:.1f}s: {facts['failing_lanes']} failing, "
        f"{facts['events']} events, kernels compared: "
        f"{facts['kernels_compared']}, problems: {len(bad)}")
    if cell.chips > 1:
        import jax

        from madsim_tpu.parallel import make_mesh

        mesh_bad, mesh_facts = checks.mesh_problems(
            eng, make_mesh(jax.devices()[:cell.chips]), start,
            session.stream_args.batch, max_steps)
        say(f"benchmark: mesh batch vs one device checked in "
            f"{mesh_facts['seconds']:.1f}s: {mesh_facts['seeds']} seeds, "
            f"{mesh_facts['coverage_slots']} coverage slots, problems: "
            f"{len(mesh_bad)}")
        bad += mesh_bad
        facts["mesh"] = mesh_facts
    return bad, facts


def layer_metrics(cell, obs: Observation) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cells.load_reader(cell, m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             benchmark_json: str | None = None,
             data_root: str | None = None) -> dict:
    """Run one cell once and return the result object (also printed as
    the last line of stdout). Raises `Refusal` / `BenchmarkError` before
    any work where no result may be given."""
    age0, t_entry = process_age_s(), time.perf_counter()
    cell = cells.load_cell(
        workload, cells.load_benchmark(benchmark_json), data_root)
    campaign = cells.load_campaign(cell)
    drive.list_cpu_backend()

    from madsim_tpu.compile_cache import enable_compile_cache

    device = check_device(cell)
    peaks = cells.load_peaks(cell, device["kind"]) if PLATFORM == "tpu" else {}
    cache_dir = enable_compile_cache(strict=True)
    say(f"benchmark: cell {cell.name} ({cell.config_name} x {cell.traffic_name}, "
        f"{cell.chips} chip(s)) seed {seed} seconds {seconds} trace {int(trace)} "
        f"on {device}; compile cache {cache_dir}")

    workdir = tempfile.mkdtemp(prefix="madsim-bench-")
    try:
        session, warmup, unmeasured = set_up(cell, campaign, workdir, trace)
        setup_s = age0 + (time.perf_counter() - t_entry)
        say(f"benchmark: set-up {setup_s:.1f}s (engine build "
            f"{session.engine_build_s:.1f}s, first campaign "
            f"{warmup['t1'] - warmup['t0']:.1f}s, {len(session.compiles)} compile "
            f"requests, {len(session.cache_misses)} cache misses)")

        trace_dir = os.path.join(workdir, "trace") if trace else None
        records, traced_records, window, paused, xplane = run_window(
            cell, campaign, session, seed, seconds, trace_dir)
        memory_peak = drive.memory_peak_bytes(cell.chips)
        in_window = session.compiles_between(*window)
        say(f"benchmark: window {window[1] - window[0] - paused:.2f}s, {len(records)} "
            f"campaigns, {len(in_window)} compile requests in it"
            + (f": {sorted({c[1] for c in in_window})}" if in_window else ""))

        attempted, failed = campaign.counts(records)
        problems = [p for r in unmeasured + records for p in r["problems"]]
        check_bad, sample = after_window(cell, session, seed)
        problems += check_bad
        for p in problems:
            say(f"benchmark: WRONG: {p}")

        end_to_end = dict(campaign.end_to_end(records), setup_s=setup_s)
        if not trace:  # a traced window holds the profiler's stop
            say(f"benchmark: end to end {json.dumps(end_to_end)}")
        result = {
            "correct": not problems, "attempted": int(attempted),
            "failed": int(failed), "metrics": {},
            "device": dict(device, memory_peak_bytes=memory_peak),
        }
        if trace:
            t = time.perf_counter()
            reduced = trace_reduce.reduce(trace_reduce.load_xplane(xplane))
            say(f"benchmark: trace {os.path.getsize(xplane)} bytes, "
                f"{reduced['op_events']} device op events on "
                f"{reduced['devices']} device(s), reduced in "
                f"{time.perf_counter() - t:.1f}s")
            obs = Observation(
                cell=cell, records=records, warmup=warmup, session=session,
                window=window, trace=reduced, traced_records=traced_records,
                sample=sample, peaks=peaks,
                kernel_shapes=kernel_bytes.shapes_of(
                    session.eng, session.stream_args.batch),
                memory_peak_bytes=memory_peak,
            )
            result["metrics"] = layer_metrics(cell, obs)
            result["device"].update(
                busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
        else:
            for m in cell.end_to_end:
                if m["name"] not in end_to_end:
                    raise cells.BenchmarkError(
                        f"cell {cell.name} lists {m['name']}, which campaign "
                        f"kind {cell.kind} does not measure")
                result["metrics"][m["name"]] = {
                    "value": float(end_to_end[m["name"]]), "unit": m["unit"]}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return result
