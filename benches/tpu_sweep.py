"""Parameter sweep for the TPU-engine streaming path on a live chip.

Sweeps batch (lanes) x segment_steps on the flagship MadRaft bench
workload and prints one JSON line per point. Run:

    python benches/tpu_sweep.py                # default grid
    python benches/tpu_sweep.py 8192 192       # single point
    MADSIM_TPU_PALLAS_POP=0 python benches/tpu_sweep.py 8192 192   # A/B: XLA pop
    MADSIM_TPU_RNG_STREAM=2 MADSIM_TPU_CLOG_PACKED=0 ...           # A/B: legacy step path

The timed region matches bench.py (3*batch seeds streamed, warmed up).

`--mesh` runs the MULTICHIP capture instead: the same workload spanned
over a 1-D "batch" mesh at 1/2/4/8 devices (one jitted SPMD program per
topology, `run_stream(mesh=...)`), seeds/s per point plus the scaling
ratio vs the 1-device rate, written to MULTICHIP_r06.json and appended
to BENCH_HISTORY with `device_count` in the fingerprint. On a box with
no accelerator it forces 8 virtual CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8, set before jax
imports) — the CI-provable stand-in; virtual devices share the host's
cores, so the CPU ratio is a correctness/plumbing capture, not the
near-linear claim (that is reserved for real multi-chip hardware).
"""

import json
import os
import sys
import time

# --mesh needs the multi-device backend decided BEFORE anything imports
# jax: XLA reads XLA_FLAGS once at backend init. The flag only shapes
# the host (CPU) platform, so on a real TPU box the sweep still spans
# the actual chips.
MESH_MODE = "--mesh" in sys.argv
if MESH_MODE and "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def run_point(batch: int, segment_steps: int) -> dict:
    from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
    from madsim_tpu.models.raft import RaftMachine

    cfg = EngineConfig(
        horizon_us=5_000_000,
        queue_capacity=96,
        faults=FaultPlan(n_faults=2, t_max_us=3_000_000, dur_min_us=200_000, dur_max_us=800_000),
        # step-path gates (same env overrides as bench.py; defaults = on)
        rng_stream=int(os.environ.get("MADSIM_TPU_RNG_STREAM", "3")),
        clog_packed=os.environ.get("MADSIM_TPU_CLOG_PACKED", "1") not in ("", "0"),
        # observability gates ride the sweep like the flagship bench
        flight_recorder=os.environ.get("MADSIM_TPU_FLIGHT_RECORDER", "1")
        not in ("", "0"),
        coverage=os.environ.get("MADSIM_TPU_COVERAGE", "1") not in ("", "0"),
    )
    eng = Engine(RaftMachine(num_nodes=5, log_capacity=8), cfg)
    # pipelined-executor knobs (round-6), env-tunable for A/B sweeps:
    # MADSIM_TPU_STREAM_PIPELINE=0 restores the r5 per-segment driver
    run = eng.make_stream_runner(
        batch=batch,
        segment_steps=segment_steps,
        pipelined=os.environ.get("MADSIM_TPU_STREAM_PIPELINE", "1") not in ("", "0"),
        segments_per_dispatch=int(os.environ.get("MADSIM_TPU_STREAM_SUPERSEG", "8")),
        dispatch_depth=int(os.environ.get("MADSIM_TPU_STREAM_DEPTH", "4")),
    )
    t_c0 = time.perf_counter()
    run(1)
    compile_s = time.perf_counter() - t_c0
    t0 = time.perf_counter()
    out = run(3 * batch, seed_start=1_000_000)
    elapsed = time.perf_counter() - t0
    st = out["stats"]
    return {
        "batch": batch,
        "segment_steps": segment_steps,
        # resolved gate, not the env echo: pallas defaults ON on TPU now
        "pallas_pop": eng.use_pallas_pop,
        "rng_stream": cfg.rng_stream,
        "clog_packed": cfg.clog_packed,
        "seeds_per_sec": round(out["completed"] / elapsed, 1),
        "completed": out["completed"],
        "elapsed_s": round(elapsed, 2),
        "compile_s": round(compile_s, 1),
        "platform": jax.devices()[0].platform,
        "host_syncs": st["host_syncs"],
        "device_segments": st["device_segments"],
        "pipelined": st["pipelined"],
        "donation": st["donation"],
        "flight_recorder": cfg.flight_recorder,
        **(
            {
                "coverage": {
                    k: v for k, v in st["coverage"].items() if k != "curve"
                }
            }
            if "coverage" in st else {}
        ),
    }


def run_mesh_sweep(out_path: str, batch: int = 1024, segment_steps: int = 192) -> None:
    """The MULTICHIP capture: one hunt spanned over 1/2/4/8 devices as
    a single jitted SPMD program per topology. Every point runs the
    identical seed range (byte-identical results by the shard-invariance
    contract, tests/test_mesh.py), so the ONLY variable is the mesh."""
    from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
    from madsim_tpu.models.raft import RaftMachine
    from madsim_tpu.parallel import make_mesh
    from madsim_tpu.perf import history as bench_history

    devs = jax.devices()
    counts = [k for k in (1, 2, 4, 8) if k <= len(devs)]
    cfg = EngineConfig(
        horizon_us=5_000_000,
        queue_capacity=96,
        faults=FaultPlan(
            n_faults=2, t_max_us=3_000_000,
            dur_min_us=200_000, dur_max_us=800_000,
        ),
        rng_stream=int(os.environ.get("MADSIM_TPU_RNG_STREAM", "3")),
        clog_packed=os.environ.get("MADSIM_TPU_CLOG_PACKED", "1") not in ("", "0"),
        flight_recorder=os.environ.get("MADSIM_TPU_FLIGHT_RECORDER", "1")
        not in ("", "0"),
        coverage=os.environ.get("MADSIM_TPU_COVERAGE", "1") not in ("", "0"),
    )
    eng = Engine.on_xla_step_path(RaftMachine(num_nodes=5, log_capacity=8), cfg)
    gates = {
        "rng_stream": cfg.rng_stream,
        "clog_packed": cfg.clog_packed,
        "pallas_pop": eng.use_pallas_pop,
        "flight_recorder": cfg.flight_recorder,
        "coverage": cfg.coverage,
        "provenance": False,
    }
    repo_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hist_path = os.environ.get("MADSIM_TPU_BENCH_HISTORY") or os.path.join(
        repo_dir, bench_history.DEFAULT_BASENAME
    )
    points = []
    for k in counts:
        run = eng.make_stream_runner(
            batch=batch, segment_steps=segment_steps,
            mesh=make_mesh(devs[:k]),
        )
        t_c0 = time.perf_counter()
        run(1)
        compile_s = time.perf_counter() - t_c0
        t0 = time.perf_counter()
        out = run(3 * batch, seed_start=1_000_000)
        elapsed = time.perf_counter() - t0
        point = {
            "devices": k,
            "seeds_per_sec": round(out["completed"] / elapsed, 1),
            "completed": out["completed"],
            "elapsed_s": round(elapsed, 2),
            "compile_s": round(compile_s, 1),
            "host_syncs": out["stats"]["host_syncs"],
        }
        points.append(point)
        print(json.dumps(point), flush=True)
        bench_history.append(hist_path, bench_history.make_record(
            f"mesh_d{k}", point["seeds_per_sec"],
            bench_history.env_fingerprint(
                backend_platform=devs[0].platform,
                lanes=batch, reps=1, segment_steps=segment_steps,
                gates=gates, device_count=k,
            ),
            compile_s=compile_s, source="benches/tpu_sweep.py --mesh",
        ))
    base = points[0]["seeds_per_sec"]
    doc = {
        "batch": batch,
        "segment_steps": segment_steps,
        "platform": devs[0].platform,
        "forced_host_devices": "xla_force_host_platform_device_count"
        in os.environ.get("XLA_FLAGS", ""),
        "points": points,
        # per-device scaling vs the 1-device rate, reported honestly:
        # on the forced-host-device CPU backend all "devices" share the
        # box's cores, so ~1.0x total (NOT k-x) is the expected shape —
        # this capture proves the SPMD plumbing and its overhead bound;
        # the near-linear claim is reserved for real multi-chip runs
        "scaling_vs_1dev": {
            str(p["devices"]): round(p["seeds_per_sec"] / base, 3)
            for p in points
        },
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"wrote {out_path}", flush=True)


def main() -> None:
    if MESH_MODE:
        argv = [a for a in sys.argv[1:] if a != "--mesh"]
        repo_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = argv[0] if argv else os.path.join(repo_dir, "MULTICHIP_r06.json")
        run_mesh_sweep(out)
        return
    if len(sys.argv) >= 3:
        grid = [(int(sys.argv[1]), int(sys.argv[2]))]
    else:
        grid = [
            (4096, 192),
            (8192, 192),
            (16384, 192),
            (32768, 192),
            (8192, 384),
            (16384, 384),
        ]
    # long sweeps are observable from outside the process: with
    # MADSIM_TPU_STATS=base set, every point also lands in base.jsonl +
    # the base.prom / base.json snapshots (`serve --service stats`)
    emitter = None
    if os.environ.get("MADSIM_TPU_STATS"):
        from madsim_tpu.tracing import StatsEmitter

        emitter = StatsEmitter(os.environ["MADSIM_TPU_STATS"])
    for batch, seg in grid:
        point = run_point(batch, seg)
        print(json.dumps(point), flush=True)
        if emitter is not None:
            emitter.emit({"kind": "sweep_point", **point})
    if emitter is not None:
        emitter.close()


if __name__ == "__main__":
    main()
