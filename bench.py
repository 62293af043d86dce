"""Flagship benchmark: MadRaft 5-node log replication + partition injection.

Measures seeds/sec on the TPU engine (the BASELINE.json north-star
metric: >= 10,000 MadRaft 5-node simulations/sec on a v5e-8; this
machine has ONE chip, so vs_baseline compares against the per-chip share
of the target, 10_000/8 = 1250 seeds/sec/chip).

Each "simulation" = one seed run to completion: boot 5 nodes, elect,
replicate an 8-entry log under 2 random partition/kill faults, verify
election + log-matching invariants on every event, horizon 5 virtual
seconds (a lane typically processes ~200-400 events).

Statistical discipline (round-3): never single-shot. After a compile +
chip-warm run, we time N repetitions and report the MEDIAN rate (the
reference's criterion benches never single-shot either,
madsim/benches/rpc.rs:11-26). Per-rep rates, min/max, spread, and host
load go into a "diagnostics" key so a depressed capture is explainable
(round-2's driver capture was 2x below the builder's sweep at the same
config; an idle-box rerun reproduced the sweep, implicating host
contention — this box has ONE CPU core, so any concurrent process
halves the host-side segment loop).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
the device the run was placed on, as jax reports it ("platform",
"device_kind", "device_count"), and "diagnostics". The platform is
whatever jax resolves: nothing here probes, retries or falls back, and
the one process that runs the bench is the one that holds the chip.
"""

import json
import os
import statistics
import sys
import time

import jax


def main() -> None:
    """Entry point: `MADSIM_TPU_PERF_TIMELINE=path` wraps the whole
    bench in a PerfRecorder (madsim_tpu/perf) so the capture ships with
    its host timeline — where the 8 minutes actually went (compile vs
    blocked-on-device vs host Python). The JSON-line stdout contract is
    untouched; the timeline summary prints to stderr. (Via `python -m
    madsim_tpu bench --perf-timeline`, the CLI's recorder is already
    active in-process and this env path is not needed.)"""
    path = os.environ.get("MADSIM_TPU_PERF_TIMELINE")
    if not path:
        return _main_impl()
    from madsim_tpu.perf.recorder import PerfRecorder

    rec = PerfRecorder(meta={"source": "bench.py"})
    try:
        with rec:
            return _main_impl()
    finally:
        n = rec.write(path)
        s = rec.summary()
        print(
            f"bench: host timeline {n} spans, "
            f"{100 * s['span_coverage']:.0f}% of {s['wall_s']:.1f}s wall "
            f"attributed -> {path}",
            file=sys.stderr, flush=True,
        )


def _main_impl() -> None:
    import dataclasses

    # the engine/flax import chain is seconds of real wall time — put
    # it on the host timeline rather than leaving it unattributed
    from madsim_tpu.perf.recorder import maybe_span

    with maybe_span("engine_build"):
        from madsim_tpu.compile_cache import (
            active_compile_cache,
            aot_cache_dir,
            aot_enabled,
            enable_compile_cache,
            measure_warm_compile,
        )
        from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
        from madsim_tpu.models.raft import RaftMachine
        from madsim_tpu.utils import device_info

    # default = the real-chip sweep's max (benches/tpu_sweep.py, r2:
    # 8192x384 -> 2825 seeds/s vs 2214 at the old 4096x192)
    lanes = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    if lanes < 1 or reps < 1:
        sys.exit("usage: bench.py [lanes>=1] [reps>=1]")
    segment_steps = 384
    # Step-path gates (this PR): counter-based per-event RNG (stream v3)
    # and bit-packed clog rows, both default-ON for the bench; the fused
    # Pallas pop+gather engages by backend (TPU). Each is individually
    # toggleable for A/B attribution (MADSIM_TPU_RNG_STREAM=2,
    # MADSIM_TPU_CLOG_PACKED=0, MADSIM_TPU_PALLAS_POP=0) and the active
    # gates land in the output JSON so BENCH_r* files are self-describing.
    rng_stream = int(os.environ.get("MADSIM_TPU_RNG_STREAM", "3"))
    clog_packed = os.environ.get("MADSIM_TPU_CLOG_PACKED", "1") not in ("", "0")
    # Flight recorder (PR-3 observability gate): default ON so the
    # flagship number is captured WITH digests + metrics riding the
    # step (the acceptance bar: < 5% vs the recorder-off r6 capture);
    # =0 for an A/B.
    flight_recorder = os.environ.get("MADSIM_TPU_FLIGHT_RECORDER", "1") not in ("", "0")
    # Scenario coverage (PR-4 observability gate): default ON for the
    # same reason — the flagship number is captured with the full
    # observability stack riding the step (budget: recorder+coverage ON
    # within 5% of the r08 capture; the vs_r08 field below is the
    # receipt). =0 for an A/B.
    coverage = os.environ.get("MADSIM_TPU_COVERAGE", "1") not in ("", "0")
    # Causal provenance (PR-7 observability gate): default OFF in the
    # flagship capture — the r09 budget receipt (recorder+coverage ON)
    # stays the comparable configuration. MADSIM_TPU_PROVENANCE=1 turns
    # it on for an A/B; with MADSIM_TPU_BENCH_STEP_COST=1 the breakdown
    # then carries a `provenance_off` line (acceptance: the lineage
    # dataflow costs <= 5% of the step).
    provenance = os.environ.get("MADSIM_TPU_PROVENANCE", "0") not in ("", "0")
    # Buffered coverage (r12): default = the engine's buffered fold
    # (flush-on-freeze slot buffer); MADSIM_TPU_COV_BUFFER=0 restores
    # the per-event map scatter for an A/B (maps bit-identical).
    cov_buffer_env = os.environ.get("MADSIM_TPU_COV_BUFFER", "")
    cov_buffer_kw = (
        {} if cov_buffer_env == "" else {"cov_buffer": int(cov_buffer_env)}
    )
    cfg = EngineConfig(
        horizon_us=5_000_000,
        # 32 slots: the round-2 real-chip queue sweep (ROADMAP S6) — the [L, Q]
        # queue arrays dominate HBM traffic, and 32 runs this workload
        # with ZERO overflows over 263k validation seeds (overflow would
        # surface as failing lanes with code 1, never as silent loss)
        queue_capacity=32,
        faults=FaultPlan(n_faults=2, t_max_us=3_000_000, dur_min_us=200_000, dur_max_us=800_000),
        rng_stream=rng_stream,
        clog_packed=clog_packed,
        flight_recorder=flight_recorder,
        coverage=coverage,
        provenance=provenance,
        **cov_buffer_kw,
    )
    # Persistent compilation cache (compile_cache.enable_compile_cache:
    # $JAX_COMPILATION_CACHE_DIR where set, else
    # $MADSIM_TPU_COMPILE_CACHE, else the checkout default): sweeps and
    # repeated bench captures pay the multi-second streaming compile
    # once per machine, not once per process. Enabled BEFORE the first
    # jit (Engine construction) so the warmup compile itself can hit,
    # and STRICT: a bench that silently recompiled while claiming warm
    # numbers would poison every compile_s_warm it reports.
    enable_compile_cache(strict=True)

    with maybe_span("engine_build"):
        eng = Engine(RaftMachine(num_nodes=5, log_capacity=8), cfg)

    # Pipelined executor (round-6): device-side supersegments + donated
    # StreamCarry + K-deep async dispatch. MADSIM_TPU_STREAM_PIPELINE=0
    # restores the r5 per-segment driver (bit-identical results) for
    # A/B measurement.
    pipelined = os.environ.get("MADSIM_TPU_STREAM_PIPELINE", "1") not in ("", "0")
    run = eng.make_stream_runner(
        batch=lanes, segment_steps=segment_steps, pipelined=pipelined,
    )

    # Compile timing (r12: COMPILE-ONLY, via Engine.compile_stream's
    # .lower().compile() forcing — no stream execution in the timed
    # window). `compile_s_cold` is what the FIRST process of this
    # (jax, gates, shape) tuple pays before it can dispatch; when a
    # persistent cache is active the warm path is then measured the
    # same way against the entries the cold compile just wrote —
    # `compile_s_warm` is what every SUBSEQUENT worker/restart pays
    # (trace or AOT deserialize + XLA cache hit). Through r11 these
    # keys timed a full run(1), which CONFLATED the start cost with
    # the first dispatch's fixed-shape execution (~17 s of the r11
    # flagship "warm 18.2 s" was the 8192-wide dispatch itself running
    # on the 1-core box, not compile); rows with a `trace_s` key carry
    # the honest split.
    t0 = time.perf_counter()
    eng.compile_stream(batch=lanes, segment_steps=segment_steps)
    compile_s = time.perf_counter() - t0

    # Compile autopsy (r13, supersedes r12's trace-only re-lower): the
    # AOT stages API re-runs trace -> lower -> backend per quartet fn
    # AFTER the timed cold run, so the "TRACE-dominated" claim becomes
    # three tracked numbers instead of one. trace_s keeps its r12
    # meaning (the abstract-trace floor a warm worker pays even when
    # every XLA executable deserializes; what MADSIM_TPU_AOT_CACHE
    # removes), now summed over the whole quartet; lower_s and
    # backend_s split the remainder. cost_analysis flops/bytes are
    # normalized to ONE seed-step (the supersegment runs lanes x
    # segment_steps x segments_per_dispatch of them) so the numbers
    # compare across shapes; backend_s here may ride the persistent
    # cache — the honest cold total stays compile_s.
    segments_per_dispatch = 8  # run_stream's default dispatch grain
    with maybe_span("trace_measure"):
        autopsy = eng.stream_compile_autopsy(
            batch=lanes, segment_steps=segment_steps,
            segments_per_dispatch=segments_per_dispatch,
        )
    trace_s = sum(r["trace_s"] for r in autopsy)
    lower_s = sum(r["lower_s"] for r in autopsy)
    backend_s = sum(r["backend_s"] for r in autopsy)
    super_row = next(
        (r for r in autopsy if r["label"] == "supersegment"), {})
    seed_steps = lanes * segment_steps * segments_per_dispatch
    flops_per_seed_step = (
        round(super_row["flops"] / seed_steps, 3)
        if super_row.get("flops") is not None else None
    )
    bytes_per_seed_step = (
        round(super_row["bytes_accessed"] / seed_steps, 3)
        if super_row.get("bytes_accessed") is not None else None
    )

    def _warm_build_and_run():
        fresh = Engine(RaftMachine(num_nodes=5, log_capacity=8), cfg)
        fresh.compile_stream(batch=lanes, segment_steps=segment_steps)

    # MADSIM_TPU_BENCH_COLD_TRACE=1: measure the warm rebuild with the
    # AOT artifact cache dropped too — "warm" then means persistent XLA
    # cache only (trace + deserialize), the honest pre-AOT warm number
    cold_trace = (
        os.environ.get("MADSIM_TPU_BENCH_COLD_TRACE", "0") not in ("", "0")
    )
    with maybe_span("compile_warm"):
        compile_s_warm = measure_warm_compile(
            _warm_build_and_run, cold_trace=cold_trace
        )
    run(2 * lanes, seed_start=500_000)

    # Timed: `reps` independent repetitions over disjoint seed ranges;
    # seed streaming keeps every lane busy (finished lanes refill with
    # fresh seeds each segment, so stragglers never idle the batch).
    rates = []
    out = None
    for r in range(reps):
        t0 = time.perf_counter()
        out = run(2 * lanes, seed_start=1_000_000 + r * 4 * lanes)
        elapsed = time.perf_counter() - t0
        rates.append(out["completed"] / elapsed)
    stream_stats = out["stats"]

    seeds_per_sec = statistics.median(rates)
    per_chip_target = 10_000 / 8  # north star is for a v5e-8; we have 1 chip
    try:
        load1 = round(os.getloadavg()[0], 2)
    except OSError:
        load1 = None

    # Optional per-gate attribution (MADSIM_TPU_BENCH_STEP_COST): the
    # old protocol timed ONE rep per gate against the early-run median
    # — on a host that drifts ±10% across the bench that misread the
    # provenance gate by 13x (PR-7 receipt: 8% single-rep vs 0.61%
    # hand-interleaved). Each gate now runs through the interleaved A/B
    # harness (madsim_tpu/perf/ab.py): ABAB… alternating reps against
    # the flagship runner over identical seed ranges, median of PAIRED
    # deltas + bootstrap 95% CI + sign test. Still one compile + one
    # warm rep per gate; MADSIM_TPU_BENCH_AB_PAIRS (default 2) sets the
    # pair count. Old key names preserved (step_cost[<key>] is still
    # "rate with the gate toggled", now a median of interleaved reps);
    # the paired detail lands under step_cost["ab"][<key>].
    # Values: 1/all = every applicable gate; obs = the observability
    # gates only; or an explicit comma list of keys.
    step_cost = None
    sc_env = os.environ.get("MADSIM_TPU_BENCH_STEP_COST", "")
    if sc_env not in ("", "0"):
        from madsim_tpu.perf.ab import DEFAULT_BENCH_AB_PAIRS, interleaved_ab

        # default widened 2 -> DEFAULT_BENCH_AB_PAIRS (r11): two paired
        # deltas bootstrap to a degenerate CI that straddles zero for
        # any sub-percent gate (r10's coverage line: -0.95% [CI -3.53,
        # +8.63] — unactionable); the pinned default buys a CI narrow
        # enough to judge the <1.5% per-gate budget against.
        ab_pairs = int(
            os.environ.get(
                "MADSIM_TPU_BENCH_AB_PAIRS", str(DEFAULT_BENCH_AB_PAIRS)
            )
        )
        menu = []
        if cfg.rng_stream != 2:
            menu.append(("rng_stream_v2", dataclasses.replace(cfg, rng_stream=2), {}))
        if cfg.clog_packed:
            menu.append(("clog_unpacked", dataclasses.replace(cfg, clog_packed=False), {}))
        if eng.use_pallas_pop:
            menu.append(("pallas_pop_off", cfg, {"use_pallas_pop": False}))
        if cfg.flight_recorder:
            menu.append(("flight_recorder_off",
                         dataclasses.replace(cfg, flight_recorder=False), {}))
        if cfg.coverage:
            menu.append(("coverage_off", dataclasses.replace(cfg, coverage=False), {}))
        if cfg.coverage and cfg.cov_buffer:
            # the r12 escape hatch: coverage ON but the pre-buffer
            # per-event map scatter (cov_buffer=0) — the delta is what
            # the flush-on-freeze buffered fold pays off
            menu.append(("coverage_unbuffered",
                         dataclasses.replace(cfg, cov_buffer=0), {}))
        if cfg.provenance:
            menu.append(("provenance_off",
                         dataclasses.replace(cfg, provenance=False), {}))
        else:
            # flagship runs provenance OFF (r09 receipt convention);
            # the A/B then answers "what would turning it ON cost" —
            # a POSITIVE delta here means the gate costs throughput
            menu.append(("provenance_on",
                         dataclasses.replace(cfg, provenance=True), {}))
        if sc_env not in ("1", "all"):
            want = (
                {"flight_recorder_off", "coverage_off",
                 "provenance_off", "provenance_on"}
                if sc_env == "obs"
                else {k.strip() for k in sc_env.split(",") if k.strip()}
            )
            menu = [m for m in menu if m[0] in want]

        step_cost = {"all_gates_on": round(seeds_per_sec, 1), "ab": {}}
        for key, vcfg, ekw in menu:
            vrun = Engine(eng.machine, vcfg, **ekw).make_stream_runner(
                batch=lanes, segment_steps=segment_steps, pipelined=pipelined
            )
            vrun(1)  # one compile per gate, as before
            vrun(2 * lanes, seed_start=600_000)  # steady-state warm
            res = interleaved_ab(
                lambda s: run(2 * lanes, seed_start=s)["completed"],
                lambda s, _v=vrun: _v(2 * lanes, seed_start=s)["completed"],
                pairs=ab_pairs,
                seed_start=3_000_000,
                seeds_per_rep=4 * lanes,
                label_a="all_gates_on",
                label_b=key,
            )
            # the variant's rate under the OLD key name (consumers keep
            # working), now a median of interleaved reps
            step_cost[key] = round(res.median_b, 1)
            step_cost["ab"][key] = res.to_dict()
            print(f"bench step_cost: {res.summary()}", file=sys.stderr, flush=True)

    # Drift-aware budget receipt (madsim_tpu/perf/history.py): the old
    # check compared every capture against ONE absolute file (vs_r08),
    # which conflates code regressions with box drift across eras. The
    # baseline is now the NEWEST comparable history row — same
    # platform, lanes and gate tuple (and host, when both recorded):
    # the closest same-box/same-config capture in time. First capture
    # of a config has no honest baseline -> budget None (CI's tiny
    # 512-lane run never false-alarms by construction).
    # MADSIM_TPU_BENCH_ENFORCE_BUDGET=1 still turns a violation into a
    # nonzero exit for gating jobs.
    from madsim_tpu.perf import history as bench_history

    gates = {
        "rng_stream": cfg.rng_stream,
        "clog_packed": cfg.clog_packed,
        "pallas_pop": eng.use_pallas_pop,
        "pallas_megakernel": eng.use_megakernel,
        "flight_recorder": cfg.flight_recorder,
        "coverage": cfg.coverage,
        "cov_buffer": cfg.cov_buffer,
        "provenance": cfg.provenance,
        "compile_cache": active_compile_cache(),
        # AOT supersegment artifacts (jax.export): when set, warm
        # workers deserialize the traced program instead of re-tracing
        "aot_cache": aot_cache_dir() if aot_enabled() else None,
    }
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    hist_path = os.environ.get("MADSIM_TPU_BENCH_HISTORY") or os.path.join(
        repo_dir, bench_history.DEFAULT_BASENAME
    )
    # first use seeds the history from the legacy BENCH_r*.json series,
    # so the neighbor search starts with the whole recorded trajectory
    hist_rows = bench_history.load_or_seed(hist_path, repo_dir=repo_dir)
    fingerprint = bench_history.env_fingerprint(
        backend_platform=jax.devices()[0].platform,
        lanes=lanes,
        reps=reps,
        segment_steps=segment_steps,
        gates=gates,
        # cache state rides the fingerprint (was this capture's compile
        # cold-built or persistent-cache-backed?) — recorded, NOT part
        # of the comparability key: cache state never changes
        # steady-state throughput, only compile_s
        compile_cache=active_compile_cache() is not None,
        # this harness drives the unsharded single-device stream; the
        # mesh captures (benches/tpu_sweep.py --mesh) record their own
        # device_count so neighbor search never crosses topologies
        device_count=1,
    )
    budget = bench_history.neighbor_budget(hist_rows, seeds_per_sec, fingerprint)
    if budget is not None and not budget["within_5pct"]:
        print(
            f"bench: BUDGET VIOLATION — {seeds_per_sec:.1f} seeds/s is "
            f"{100 * (1 - budget['vs_neighbor']):.1f}% below the "
            f"{budget['neighbor']} capture ({budget['neighbor_value']}), "
            f"the newest same-box/same-config neighbor",
            file=sys.stderr, flush=True,
        )

    # every capture appends to the history (the bench trajectory is an
    # artifact, not archaeology); MADSIM_TPU_BENCH_TAG overrides the
    # auto-continued rNN tag
    bench_tag = (
        os.environ.get("MADSIM_TPU_BENCH_TAG") or bench_history.next_tag(hist_rows)
    )
    bench_history.append(
        hist_path,
        bench_history.make_record(
            bench_tag,
            round(seeds_per_sec, 1),
            fingerprint,
            reps=[round(x, 1) for x in rates],
            compile_s=round(compile_s, 2),
            compile_s_warm=(
                round(compile_s_warm, 2) if compile_s_warm is not None else None
            ),
            trace_s=round(trace_s, 2),
            lower_s=round(lower_s, 3),
            backend_s=round(backend_s, 3),
            flops_per_seed_step=flops_per_seed_step,
            bytes_per_seed_step=bytes_per_seed_step,
            spread_pct=round(100 * (max(rates) - min(rates)) / max(rates), 1),
            host_load1=load1,
            step_cost=step_cost,
            source="bench.py",
        ),
    )

    print(
        json.dumps(
            {
                "metric": "madraft5_seeds_per_sec_per_chip",
                "value": round(seeds_per_sec, 1),
                "unit": "seeds/sec",
                "vs_baseline": round(seeds_per_sec / per_chip_target, 3),
                **({"budget": budget} if budget else {}),
                # this capture's history row (BENCH_HISTORY.jsonl —
                # `python -m madsim_tpu bench report` renders the trend)
                "history": {
                    "tag": bench_tag,
                    "path": os.path.basename(hist_path),
                },
                **device_info(),
                # one-time compile vs steady state, split: cold = what
                # the first process of this (jax, gates, shape) tuple
                # pays; warm = what every later worker pays against the
                # persistent cache (null when no cache is configured —
                # there is no warm path to measure). "compile_s" stays
                # the cold number for every existing consumer.
                "compile_s": round(compile_s, 2),
                "compile_s_cold": round(compile_s, 2),
                "compile_s_warm": (
                    round(compile_s_warm, 2)
                    if compile_s_warm is not None else None
                ),
                # the compile autopsy (r13): the cold compile split by
                # AOT stage across the stream quartet. trace_s keeps
                # its r12 meaning — the abstract-trace floor a warm
                # worker pays even when every XLA executable
                # deserializes (what MADSIM_TPU_AOT_CACHE removes) —
                # lower_s/backend_s split the remainder; flops/bytes
                # come from XLA cost_analysis on the supersegment,
                # normalized to one seed-step so shapes compare
                "trace_s": round(trace_s, 2),
                "lower_s": round(lower_s, 3),
                "backend_s": round(backend_s, 3),
                "flops_per_seed_step": flops_per_seed_step,
                "bytes_per_seed_step": bytes_per_seed_step,
                "compile_autopsy": [
                    {
                        "label": r["label"],
                        "trace_s": round(r["trace_s"], 3),
                        "lower_s": round(r["lower_s"], 3),
                        "backend_s": round(r["backend_s"], 3),
                        "total_s": round(r["total_s"], 3),
                        "flops": r["flops"],
                        "bytes_accessed": r["bytes_accessed"],
                        "peak_bytes": r["peak_bytes"],
                    }
                    for r in autopsy
                ],
                "steady_seeds_per_sec": round(seeds_per_sec, 1),
                # active step-path gates: BENCH_r* files stay
                # self-describing across this PR's flags
                "gates": gates,
                "diagnostics": {
                    "reps": [round(x, 1) for x in rates],
                    "min": round(min(rates), 1),
                    "max": round(max(rates), 1),
                    "spread_pct": round(100 * (max(rates) - min(rates)) / max(rates), 1),
                    "host_load1": load1,
                    "lanes": lanes,
                    "segment_steps": segment_steps,
                    "queue_capacity": cfg.queue_capacity,
                    # pipelined-executor evidence (last rep): blocking
                    # device->host syncs vs segments the device ran
                    "host_syncs": stream_stats["host_syncs"],
                    "device_segments": stream_stats["device_segments"],
                    "dispatch_depth": stream_stats["dispatch_depth"],
                    "segments_per_dispatch": stream_stats["segments_per_dispatch"],
                    "donation": stream_stats["donation"],
                    "pipelined": stream_stats["pipelined"],
                    # on-device fault-injection / occupancy telemetry
                    # harvested by the flight recorder (last rep)
                    **(
                        {"flight_recorder": stream_stats["flight_recorder"]}
                        if "flight_recorder" in stream_stats else {}
                    ),
                    # scenario-coverage summary (last rep; curve omitted
                    # to keep the JSON line one-screen)
                    **(
                        {
                            "coverage": {
                                k: v
                                for k, v in stream_stats["coverage"].items()
                                if k != "curve"
                            }
                        }
                        if "coverage" in stream_stats else {}
                    ),
                    **({"step_cost": step_cost} if step_cost else {}),
                },
            }
        )
    )
    if (
        budget is not None
        and not budget["within_5pct"]
        and os.environ.get("MADSIM_TPU_BENCH_ENFORCE_BUDGET", "") not in ("", "0")
    ):
        sys.exit(1)


if __name__ == "__main__":
    main()
