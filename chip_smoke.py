#!/usr/bin/env python3
"""chip_smoke.py — the hunt path, once, on the chip.

    python chip_smoke.py          # from the root of a checkout, on a TPU host

The quickest proof that the system still starts where it says it runs.
ONE process (a chip belongs to one process at a time) drives the main
path through the entry points a user calls — `python -m madsim_tpu
explore|hunt --stream`, in-process — at the sizes the upstream suite
calls real (8192 lanes x 384-step segments, 16,384 seeds per stage),
and holds what comes out to the repo's own oracles:

  flagship  explore raft, stream v3, recorder + coverage: the step
            megakernel + the coverage-flush kernel, compiled by Mosaic
  oracle    the same seeds on the XLA step path on the chip (stream
            results, then per-lane digest trails), and 1,024 seeds on
            the chip against jax's CPU backend in this same process
  hunt      a high-find-rate hunt on stream v2 (the fused pop+gather
            kernel) -> shrink -> corpus entry -> CPU replay of the entry
            to the device's fail code and digest trail
  widest    gossip-33, queue 320: the largest per-lane state there is
  mesh      the flagship over 4 devices (only where there are 4)

It exits non-zero, and prints no result line, unless jax's default
platform is a TPU: no number from any other backend may be read as the
chip's. On success the LAST line of stdout is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}. Any seeds/s it
prints is an observation on one run — not a baseline and not a claim.

Sizes are the constants in FULL and are never lowered to make it pass;
the stage functions take them as arguments so tests/test_chip_smoke.py
can drive the same code on the CPU at a tiny size.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import time
import traceback
from importlib import metadata
from unittest import mock

#: the sizes a run on the chip uses (ISSUE 21; `BASELINE.json` config 3)
FULL = {
    "lanes": 8192,
    "flagship_seeds": 16384,
    "cpu_oracle_seeds": 1024,
    "hunt_seeds": 16384,
    "widest_seeds": 8192,
    "widest_nodes": 33,
    "widest_queue": 320,
    "widest_cpu_seeds": 256,
    "mesh_devices": 4,
}

SEGMENT_STEPS = 384  # what `_stream_batches` dispatches with

KERNELS_OFF = {"MADSIM_TPU_PALLAS_POP": "0", "MADSIM_TPU_PALLAS_MEGAKERNEL": "0"}


class SmokeFailure(Exception):
    """A stage's result was wrong (as opposed to the stage crashing)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- driving the CLI in-process ------------------------------------------------


@dataclasses.dataclass
class CliRun:
    rc: int
    wall_s: float
    eng: object  # the Engine the CLI built
    args: object  # its parsed argparse namespace
    agg: dict  # `_stream_batches`' aggregate (failing/infra/abandoned/...)

    @property
    def setup_s(self) -> float:
        """Everything before the timed stream loop: engine build and
        the stream's programs made ready (trace, compile or cache hit;
        nothing dispatched)."""
        return self.wall_s - self.agg["elapsed_s"]


def run_cli(argv, env=None) -> CliRun:
    """`python -m madsim_tpu <argv>` in this process, with the engine it
    built and the stream driver's full aggregate kept for inspection
    (the CLI itself prints only the first 20 failing seeds). `env`
    entries are set for the call and restored after."""
    import madsim_tpu.__main__ as cli

    seen: dict = {}
    orig = cli._stream_batches

    def spy(eng, args, purpose="explore"):
        agg = orig(eng, args, purpose=purpose)
        seen.update(eng=eng, args=args, agg=agg)
        return agg

    say(f"$ python -m madsim_tpu {' '.join(argv)}"
        + (f"   # {env}" if env else ""))
    t0 = time.perf_counter()
    with mock.patch.dict(os.environ, env or {}), \
            mock.patch.object(cli, "_stream_batches", spy):
        rc = cli.main(list(argv))
    wall = time.perf_counter() - t0
    check("agg" in seen, f"{argv[0]} never reached the stream driver")
    return CliRun(int(rc or 0), wall, seen["eng"], seen["args"], seen["agg"])


def read_stats(base: str) -> list:
    """The `--stats BASE` JSONL records of one CLI run."""
    with open(base + ".jsonl") as f:
        return [json.loads(line) for line in f if line.strip()]


def check_stream_resolved(run: CliRun, seeds: int, what: str) -> dict:
    """Every requested seed resolved, gaplessly, with no dispatch
    retried; returns the counts."""
    agg = run.agg
    reported = (
        [s for s, _c in agg["failing"]] + [s for s, _c in agg["infra"]]
        + list(agg["abandoned"])
    )
    first = run.args.seed
    in_flight = agg["seeds_consumed"] - agg["completed"]
    check(agg["completed"] >= seeds,
          f"{what}: {agg['completed']} of {seeds} seeds resolved")
    check(len(set(reported)) == len(reported) <= agg["completed"],
          f"{what}: a seed was reported twice")
    check(all(first <= s < first + agg["seeds_consumed"] for s in reported),
          f"{what}: a reported seed never entered a lane")
    # what entered lanes and did not resolve was still in flight when a
    # batch reached its budget: at most one lane-load per batch
    check(0 <= in_flight <= agg["batches_run"] * run.args.batch,
          f"{what}: {in_flight} seeds consumed but unresolved (gap)")
    check(agg["stats"]["dispatch_retries"] == 0,
          f"{what}: {agg['stats']['dispatch_retries']} dispatches retried")
    return {
        "completed": agg["completed"],
        "failing": len(agg["failing"]),
        "infra": len(agg["infra"]),
        "abandoned": len(agg["abandoned"]),
        "seeds_consumed": agg["seeds_consumed"],
        "setup_s": round(run.setup_s, 1),
        "stream_s": round(agg["elapsed_s"], 2),
        # one run, one observation — not a baseline, not a claim
        "observed_seeds_per_s": round(agg["completed"] / agg["elapsed_s"], 1),
    }


def check_same_stream(a: CliRun, b: CliRun, stats_a: str, stats_b: str,
                      what: str) -> None:
    """Two stream runs over the same seeds agree in everything the
    simulation decides: failing set and codes, infra, abandoned, the
    counts, the coverage map, and the per-batch recorder totals."""
    import numpy as np

    for key in ("failing", "infra", "abandoned"):
        check(sorted(a.agg[key]) == sorted(b.agg[key]),
              f"{what}: {key} differ "
              f"({len(a.agg[key])} vs {len(b.agg[key])} entries)")
    for key in ("completed", "seeds_consumed"):
        check(a.agg[key] == b.agg[key],
              f"{what}: {key} {a.agg[key]} vs {b.agg[key]}")
    check(("coverage_map" in a.agg) == ("coverage_map" in b.agg),
          f"{what}: one run has no coverage map")
    if "coverage_map" in a.agg:
        check(np.array_equal(a.agg["coverage_map"], b.agg["coverage_map"]),
              f"{what}: coverage maps differ")

    def decided(rec):
        return {k: rec.get(k) for k in (
            "kind", "batch", "completed", "failing", "infra", "abandoned",
            "coverage", "flight_recorder",
        )}

    check([decided(r) for r in read_stats(stats_a)]
          == [decided(r) for r in read_stats(stats_b)],
          f"{what}: per-batch stats records differ")


# -- per-lane results: fail codes, digest trails, coverage ---------------------


def xla_twin(eng):
    """`eng`'s machine and config on the XLA step path — the oracle."""
    return type(eng).on_xla_step_path(eng.machine, eng.config)


def lane_results(eng, seed_start: int, n_seeds: int, lanes: int,
                 max_steps: int) -> dict:
    """Run seeds [seed_start, seed_start + n_seeds) to completion, one
    lane each, `lanes` at a time (`Engine.make_runner` — the fixed-batch
    path, whose result carries every lane's final state), and return
    what the simulation decided per lane as numpy arrays: outcome, the
    recorder's digest trail (final digest + checkpoint ring) and the
    coverage map. A lane's result depends on its seed alone, never on
    the lanes beside it. The coverage slot buffer is scratch, not a
    result (its stale tail depends on when the batch's last lane
    stopped), and is left out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    run = eng.make_runner(max_steps=max_steps)
    chunks = []
    for lo in range(seed_start, seed_start + n_seeds, lanes):
        n = min(lanes, seed_start + n_seeds - lo)
        res = run(jnp.arange(lo, lo + n, dtype=jnp.uint32))
        out = {
            k: np.asarray(getattr(res, k))
            for k in ("seeds", "done", "failed", "fail_code", "now_us",
                      "steps", "msg_count")
        }
        for path, leaf in jax.tree_util.tree_flatten_with_path(res.fr)[0]:
            out["fr" + jax.tree_util.keystr(path)] = np.asarray(leaf)
        if eng.config.coverage:
            out["cov_map"] = np.asarray(res.cov["map"])
        chunks.append(out)
    return {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}


def check_same_lanes(a: dict, b: dict, what: str) -> None:
    import numpy as np

    check(a.keys() == b.keys(), f"{what}: result leaves differ")
    bad = [k for k in a if not np.array_equal(a[k], b[k])]
    check(not bad, f"{what}: lanes differ in {bad}")


def on_cpu():
    """Context: place new arrays and jits on jax's CPU backend."""
    import jax

    from madsim_tpu.engine.replay import cpu_device

    return jax.default_device(cpu_device())


# -- stages ----------------------------------------------------------------------


def flagship_argv(out_dir: str, tag: str, seeds: int, batch: int) -> list:
    # the flagship config: `benchmark/configs/raft5.json`'s flags
    return [
        "explore", "--machine", "raft", "--stream",
        "--seeds", str(seeds), "--batch", str(batch),
        "--horizon", "5", "--queue", "32", "--faults", "2",
        "--rng-stream", "3", "--flight-recorder", "--coverage",
        "--stats", os.path.join(out_dir, tag),
    ]


def lowered_supersegment(eng, batch: int, max_steps: int) -> str:
    """The StableHLO text of the supersegment the stream dispatched."""
    import jax
    import jax.numpy as jnp

    init_carry, _seg, supersegment, _reset = eng._stream_fns(
        SEGMENT_STEPS, max_steps, 2 * batch, batch,
        donate=True, segments_per_dispatch=8,
    )
    carry = jax.eval_shape(
        init_carry, jax.ShapeDtypeStruct((batch,), jnp.uint32)
    )
    need = jax.ShapeDtypeStruct((), jnp.int32)
    return supersegment.lower(carry, need).as_text()


def stage_flagship(out_dir: str, seeds: int, batch: int,
                   on_chip: bool = True) -> dict:
    """The flagship on the default TPU step path: the megakernel."""
    run = run_cli(flagship_argv(out_dir, "flagship", seeds, batch))
    counts = check_stream_resolved(run, seeds, "flagship")
    eng = run.eng
    check(eng.use_megakernel and eng.use_pallas_pop,
          "flagship: the engine did not select the Pallas kernels "
          f"(megakernel={eng.use_megakernel}, pop={eng.use_pallas_pop})")
    if on_chip:
        check(not eng._pallas_interpret,
              "flagship: a kernel resolved to interpreter mode on the chip")
        n_mosaic = lowered_supersegment(
            eng, min(seeds, batch), run.args.max_steps
        ).count("tpu_custom_call")
        check(n_mosaic >= 1,
              "flagship: no Mosaic custom call in the lowered supersegment")
        counts["mosaic_custom_calls"] = n_mosaic
    for rec in read_stats(os.path.join(out_dir, "flagship")):
        check({"platform", "device_kind", "device_count"} <= rec.keys(),
              "flagship: a stats record does not name its device")
    counts["coverage_slots"] = int(run.agg["coverage_map"].sum())
    return {"counts": counts, "run": run}


def stage_oracle(flagship: CliRun, out_dir: str, seeds: int, batch: int,
                 cpu_seeds: int) -> dict:
    """The flagship's results against the XLA step path on the chip,
    and a prefix of its seeds against jax's CPU backend."""
    oracle = run_cli(
        flagship_argv(out_dir, "oracle", seeds, batch), env=KERNELS_OFF
    )
    check(not (oracle.eng.use_megakernel or oracle.eng.use_pallas_pop),
          "oracle: the XLA engine still has a kernel on")
    counts = check_stream_resolved(oracle, seeds, "oracle")
    check_same_stream(
        flagship, oracle, os.path.join(out_dir, "flagship"),
        os.path.join(out_dir, "oracle"), "megakernel vs XLA stream",
    )
    # digest trails are per-lane state the stream never ships to the
    # host: take them from the fixed-batch path, all seeds, both paths
    first, max_steps = flagship.args.seed, flagship.args.max_steps
    t0 = time.perf_counter()
    kernel_lanes = lane_results(flagship.eng, first, seeds, batch, max_steps)
    xla_lanes = lane_results(oracle.eng, first, seeds, batch, max_steps)
    check_same_lanes(kernel_lanes, xla_lanes,
                     f"megakernel vs XLA, {seeds} lanes on the device")
    counts["trail_lanes"] = int(kernel_lanes["seeds"].shape[0])
    counts["trail_checkpoints"] = int(
        (kernel_lanes["fr['ck_step']"] >= 0).sum()
    )
    counts["trails_s"] = round(time.perf_counter() - t0, 1)
    # the same config, the device against the CPU backend, one process:
    # the first `cpu_seeds` of the lanes the kernels just ran
    t0 = time.perf_counter()
    n = min(cpu_seeds, seeds)
    with on_cpu():
        cpu_lanes = lane_results(
            xla_twin(flagship.eng), first, n, n, max_steps
        )
    check_same_lanes({k: v[:n] for k, v in kernel_lanes.items()}, cpu_lanes,
                     f"device kernels vs CPU backend, {n} lanes")
    counts["cpu_lanes"] = n
    counts["cpu_s"] = round(time.perf_counter() - t0, 1)
    return {"counts": counts, "run": oracle}


def device_trail(entry, build_machine):
    """One corpus entry's (checkpoints, final) digest trail as the
    DEVICE computes it: the entry's machine and shrunk config on the
    default step path, recorder on at the entry's cadence."""
    import jax.numpy as jnp

    from madsim_tpu.engine import Engine, audit, corpus

    eng = audit.fr_variant(
        Engine(corpus.entry_machine(entry, build_machine), entry.config),
        entry.digest_every, entry.max_steps // entry.digest_every + 2,
    )
    res = eng.make_runner(max_steps=entry.max_steps)(
        jnp.asarray([entry.seed], dtype=jnp.uint32)
    )
    checkpoints = [list(c) for c in eng.digest_checkpoints(res, 0)]
    final = [int(res.steps[0]), int(res.fr["d0"][0]), int(res.fr["d1"][0])]
    return checkpoints, final, int(res.fail_code[0])


def stage_hunt(out_dir: str, seeds: int, batch: int) -> dict:
    """hunt -> shrink -> corpus -> CPU replay, at a high find rate, on
    stream v2 (the CLI default): the fused pop+gather kernel."""
    import madsim_tpu.__main__ as cli
    from madsim_tpu.engine import corpus

    corpus_path = os.path.join(out_dir, "corpus.json")
    run = run_cli([
        "hunt", "--machine", "demo-nopromise-multipaxos", "--stream",
        "--seeds", str(seeds), "--batch", str(batch),
        "--horizon", "8", "--queue", "96", "--faults", "3",
        "--fault-kinds", "pair,kill,dir,group,storm",
        "--fault-tmax", "3000000", "--max-steps", "6000",
        "--limit", "1", "--corpus", corpus_path,
        "--stats", os.path.join(out_dir, "hunt"),
    ])
    counts = check_stream_resolved(run, seeds, "hunt")
    check(run.eng.use_pallas_pop and not run.eng.use_megakernel,
          "hunt: stream v2 should run the pop+gather kernel only")
    check(len(run.agg["failing"]) >= 1 and run.rc == 1,
          "hunt: no failing seed found (expected ~20% of lanes)")
    entries = corpus.load(corpus_path)
    check(len(entries) == 1,
          f"hunt: {len(entries)} corpus entries filed, expected 1 (--limit 1)")
    entry = entries[0]
    device_codes = dict(run.agg["failing"])
    check(device_codes.get(entry.seed) == entry.fail_code,
          f"hunt: entry seed {entry.seed} code {entry.fail_code} is not "
          f"what the device reported ({device_codes.get(entry.seed)})")
    # the CPU replays the filed entry to the device's fail code ...
    check(cli.main(["regress", "--corpus", corpus_path]) == 0,
          "hunt: the filed entry does not reproduce on the CPU replay")
    # ... and to the digest trail recorded at birth ...
    check(cli.main(["audit", "--corpus", corpus_path]) == 0,
          "hunt: the CPU replay diverges from the entry's digest trail")
    # ... which is also the trail the device computes for that seed
    checkpoints, final, code = device_trail(entry, cli.build_machine)
    check(code == entry.fail_code,
          f"hunt: device re-run of the entry fails with {code}")
    check(checkpoints == [list(c) for c in entry.digests]
          and final == list(entry.digest_final),
          "hunt: the device's digest trail for the filed seed differs "
          "from the CPU replay's")
    counts.update(
        find_rate=round(len(run.agg["failing"]) / run.agg["completed"], 4),
        corpus_seed=entry.seed, corpus_code=entry.fail_code,
        corpus_steps=entry.max_steps - 1,
        trail_checkpoints=len(entry.digests),
    )
    return {"counts": counts, "run": run}


def stage_widest(out_dir: str, seeds: int, batch: int, nodes: int,
                 queue: int, cpu_seeds: int) -> dict:
    """The largest per-lane state the repo supports compiles and
    completes; a prefix of its seeds agrees with the CPU backend."""
    import jax

    run = run_cli([
        "explore", "--machine", "gossip", "--nodes", str(nodes), "--stream",
        "--seeds", str(seeds), "--batch", str(batch),
        "--horizon", "5", "--queue", str(queue), "--faults", "3",
        "--fault-kinds", "pair,kill,dir,group,storm,delay",
        "--fault-tmax", "3000000", "--max-steps", "9000",
        "--stats", os.path.join(out_dir, "widest"),
    ])
    counts = check_stream_resolved(run, seeds, "widest")
    check(run.eng.use_pallas_pop, "widest: the pop+gather kernel is off")
    n = min(cpu_seeds, seeds)
    first, max_steps = run.args.seed, run.args.max_steps
    t0 = time.perf_counter()
    dev_lanes = lane_results(run.eng, first, n, n, max_steps)
    with on_cpu():
        cpu_lanes = lane_results(xla_twin(run.eng), first, n, n, max_steps)
    check_same_lanes(dev_lanes, cpu_lanes,
                     f"widest: device vs CPU backend, {n} lanes")
    counts["cpu_lanes"] = n
    counts["cpu_s"] = round(time.perf_counter() - t0, 1)
    mem = jax.devices()[0].memory_stats() or {}
    counts["peak_device_bytes"] = mem.get("peak_bytes_in_use")
    return {"counts": counts, "run": run}


def stage_mesh(flagship: CliRun, out_dir: str, seeds: int, batch: int,
               devices: int) -> dict:
    """The flagship as one SPMD program over `devices` devices: same
    results, and the lane leaves of the carry really spread out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from madsim_tpu.parallel import (
        LANE_AXIS, carry_shardings, make_mesh, shard_seeds,
    )

    run = run_cli(
        flagship_argv(out_dir, "mesh", seeds, batch)
        + ["--devices", str(devices)]
    )
    check(not (run.eng.use_megakernel or run.eng.use_pallas_pop),
          "mesh: a meshed engine must take the XLA step path")
    counts = check_stream_resolved(run, seeds, "mesh")
    check_same_stream(
        flagship, run, os.path.join(out_dir, "flagship"),
        os.path.join(out_dir, "mesh"), f"1 device vs {devices}-device mesh",
    )
    # where the carry lives: build it as the stream did (the engine's
    # cached init_carry of the same mesh; the stream ran the executable
    # made from it, so this call is a persistent-cache read) and look
    lanes = min(seeds, batch)
    mesh = make_mesh(jax.devices()[:devices])
    init_carry = run.eng._stream_fns(
        SEGMENT_STEPS, run.args.max_steps, 2 * lanes, lanes,
        donate=True, segments_per_dispatch=8, mesh=mesh,
    )[0]
    carry = init_carry(
        shard_seeds(jnp.arange(lanes, dtype=jnp.uint32), mesh)
    )
    placed = jax.tree_util.tree_flatten_with_path(carry)[0]
    declared = jax.tree.leaves(carry_shardings(mesh, carry))
    lane_leaves = 0
    for (path, leaf), want in zip(placed, declared):
        if want.spec != P(LANE_AXIS) or leaf.size == 0:
            continue
        shards = leaf.addressable_shards
        name = jax.tree_util.keystr(path)
        check(len({s.device for s in shards}) == devices,
              f"mesh: carry leaf {name} sits on "
              f"{len({s.device for s in shards})} device(s)")
        check({s.data.shape[0] for s in shards} == {lanes // devices},
              f"mesh: carry leaf {name} is not split evenly over lanes")
        lane_leaves += 1
    check(lane_leaves > 0, "mesh: no lane-axis leaf found in the carry")
    counts["lane_leaves_on_all_devices"] = lane_leaves
    counts["lanes_per_device"] = lanes // devices
    counts["devices"] = [str(d) for d in mesh.devices.flat]
    return {"counts": counts, "run": run}


# -- the run ---------------------------------------------------------------------


def device_header() -> dict:
    """Print where this runs; returns the contract's device object."""
    import jax
    import jaxlib

    from madsim_tpu.compile_cache import cache_entry_count, enable_compile_cache

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    cache_dir = enable_compile_cache(strict=True)
    say(
        f"chip_smoke: platform: {device['platform']}, device_kind: "
        f"{device['kind']}, devices: {device['count']}, jax "
        f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}, "
        f"compile cache: {cache_dir} ({cache_entry_count()} entries, placed "
        f"by {'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'the checkout default'})"
    )
    return device


def count_cache_events() -> dict:
    """Persistent-cache hits and misses from here on, as jax counts
    them (one event per compile request that consulted the cache)."""
    import jax

    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def list_cpu_backend() -> None:
    """The smoke compares the chip against jax's CPU backend in one
    process (and every replay runs there), so the CPU must be
    initialised beside whatever platform the environment names. The
    named platform stays first — still the default, and jax still fails
    at start-up if it cannot be initialised. Before jax is imported."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"


def main() -> int:
    t_start = time.perf_counter()
    list_cpu_backend()
    device = device_header()
    if device["platform"] != "tpu":
        say(
            f"chip_smoke: jax's default platform is {device['platform']!r} "
            f"({device['kind']}), not a TPU — nothing here may be read as "
            f"a chip result. Refusing to run."
        )
        return 2

    import jax

    from madsim_tpu.compile_cache import cache_entry_count

    entries_before = cache_entry_count()
    cache = count_cache_events()
    out_dir = os.path.join("chiprun_out", "chip_smoke")
    shutil.rmtree(out_dir, ignore_errors=True)  # stats append, the corpus dedups
    os.makedirs(out_dir)

    results: dict = {}
    failed: list = []

    def stage(name: str, fn, *args):
        say(f"\n=== stage {name} ===")
        t0 = time.perf_counter()
        hits0, miss0 = cache["hits"], cache["misses"]
        try:
            res = fn(*args)
        except Exception as exc:  # noqa: BLE001 — report, then fail the run
            failed.append(name)
            kind = "WRONG" if isinstance(exc, SmokeFailure) else "CRASHED"
            say(f"[stage {name}] {kind}: {exc}")
            if not isinstance(exc, SmokeFailure):
                traceback.print_exc(file=sys.stdout)
            return None
        res["counts"].update(
            stage_s=round(time.perf_counter() - t0, 1),
            cache_hits=cache["hits"] - hits0,
            cache_misses=cache["misses"] - miss0,
        )
        results[name] = res["counts"]
        say(f"[stage {name}] OK {json.dumps(res['counts'])}")
        return res

    lanes = FULL["lanes"]
    flagship = stage("flagship", stage_flagship, out_dir,
                     FULL["flagship_seeds"], lanes)
    if flagship is None:
        failed.append("oracle")
        say("[stage oracle] NOT RUN: it compares against the flagship stage")
    else:
        stage("oracle", stage_oracle, flagship["run"], out_dir,
              FULL["flagship_seeds"], lanes, FULL["cpu_oracle_seeds"])
    stage("hunt", stage_hunt, out_dir, FULL["hunt_seeds"], lanes)
    stage("widest", stage_widest, out_dir, FULL["widest_seeds"], lanes,
          FULL["widest_nodes"], FULL["widest_queue"],
          FULL["widest_cpu_seeds"])
    n_mesh = FULL["mesh_devices"]
    if jax.device_count() < n_mesh:
        say(f"\n=== stage mesh ===\n[stage mesh] DID NOT RUN: "
            f"{jax.device_count()} device(s) here, the stage needs {n_mesh} "
            f"(run this script on the four-chip host). Not a pass.")
        results["mesh"] = "did not run"
    elif flagship is None:
        failed.append("mesh")
        say("[stage mesh] NOT RUN: it compares against the flagship stage")
    else:
        stage("mesh", stage_mesh, flagship["run"], out_dir,
              FULL["flagship_seeds"], lanes, n_mesh)

    summary = {
        "total_s": round(time.perf_counter() - t_start, 1),
        "cache_entries_before": entries_before,
        "cache_entries_after": cache_entry_count(),
        "cache_hits": cache["hits"],
        "cache_misses": cache["misses"],
        "stages": results,
    }
    say(f"\nchip_smoke summary: {json.dumps(summary)}")
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump({"device": device, **summary, "failed": failed}, f, indent=1)
    if failed:
        say(f"chip_smoke: FAILED stages: {failed}")
        print(json.dumps({"ok": False, "device": device, "failed": failed}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
