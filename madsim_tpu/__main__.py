"""CLI harness — seed exploration, replay, determinism checking.

Build-plan step 7 (SURVEY.md §7): the env-driven multi-seed runner +
determinism-check mode, as a command line:

  python -m madsim_tpu explore --machine raft --seeds 4096 [--faults 2]
  python -m madsim_tpu replay  --machine raft --seed 1234 [--tail 30]
  python -m madsim_tpu check   --machine kv   --seeds 64

`explore` prints failing seeds (the reference prints
`MADSIM_TEST_SEED=...` repro hints; here the seed IS the repro:
`replay --seed N` shows the full event trace).
"""

from __future__ import annotations

# madsim: allow-file(D001) — every wall-clock read in this module goes
# through the deliberately named `import time as wall` alias and only
# measures host throughput (seeds/s, elapsed_s) or stamps report
# metadata; nothing feeds simulation state. Virtual time lives in the
# engine.
import argparse
import contextlib
import dataclasses
import functools
import json
import logging
import os
import sys


def build_machine(name: str, nodes: int = 0, log_capacity: int = 0):
    """CLI machine registry — also the resolver corpus entries use to
    rebuild their machine from (name, nodes). The demo-* entries are
    deliberately buggy variants (each models a classic bug class, and
    lives beside its model) so the hunt -> shrink -> replay -> corpus
    workflow is demonstrable without writing a protocol first.

    `log_capacity` (0 = the registry's 8, for kafka and kvraft its 64)
    sizes the raft and kvraft machines' log and the kafka machines'
    partition logs
    (`--log-capacity`; a corpus entry records it). One object per
    (name, nodes, log_capacity) per process: the compiled-replay cache
    hangs on the machine object (engine/replay.py `_replay_cache`), so
    `hunt`, `regress`, `audit` and the fleet worker's jobs ask for a
    replay program their process already lowered instead of lowering it
    again per call. Sound because a machine is never mutated after
    construction, apart from the caches it carries (the engine reads
    its constants and calls its handlers; nothing writes to it)."""
    return _registry_machine(name, int(nodes or 0), int(log_capacity or 0))


@functools.lru_cache(maxsize=None)
def _registry_machine(name: str, nodes: int, log_capacity: int = 0):
    from .models.echo import EchoMachine
    from .models.etcd import DoubleGrantEtcd, EtcdMachine
    from .models.etcd_mvcc import (
        EtcdMvccMachine, NoDedupMvcc, PrematureGiveupMvcc,
    )
    from .models.gossip import DupAckGossip, GossipMachine
    from .models.kafka import KafkaMachine, NoDedupKafkaMachine
    from .models.kafka_group import KafkaGroupMachine, NoFencingGroupMachine
    from .models.kv import KvMachine
    from .models.kvraft import KvRaftMachine, LocalGetKvRaft
    from .models.mq import MqMachine
    from .models.multipaxos import MultiPaxosMachine, NoPromiseCheckMultiPaxos
    from .models.paxos import NoPromiseCheckPaxos, PaxosMachine
    from .models.raft import (
        DupVoteRaft, Fig8Raft, OvercommitRaft, QuorumOffByOneRaft,
        RaftMachine, VolatileCommitRaft,
    )
    from .models.raft_compact import RaftCompactMachine, TornSnapshotRaftCompact
    from .models.s3 import (
        AbortLeakS3, ArrivalOrderS3, EarlyExpiryS3, NoDedupS3, S3Machine,
        TombstoneLeakS3,
    )
    from .models.twopc import TwoPcMachine

    cap = log_capacity or 8
    machines = {
        "echo": lambda: EchoMachine(rounds=10),
        "raft": lambda: RaftMachine(num_nodes=nodes or 5, log_capacity=cap),
        "kv": lambda: KvMachine(num_nodes=nodes or 4),
        "mq": lambda: MqMachine(num_nodes=nodes or 4),
        "etcd": lambda: EtcdMachine(num_nodes=nodes or 4),
        "etcd-mvcc": lambda: EtcdMvccMachine(num_nodes=nodes or 4),
        "twopc": lambda: TwoPcMachine(num_nodes=nodes or 4),
        "group": lambda: KafkaGroupMachine(num_nodes=nodes or 4),
        # the partition logs' capacity: 64 where --log-capacity is unset
        "kafka": lambda: KafkaMachine(
            num_nodes=nodes or 5, log_capacity=log_capacity or 64
        ),
        "demo-nodedup-kafka": lambda: NoDedupKafkaMachine(
            num_nodes=nodes or 5, log_capacity=log_capacity or 64
        ),
        # 5 servers + 5 clerks; the servers' logs: 64 where unset
        "kvraft": lambda: KvRaftMachine(
            num_nodes=nodes or 10, log_capacity=log_capacity or 64
        ),
        "demo-localget-kvraft": lambda: LocalGetKvRaft(
            num_nodes=nodes or 10, log_capacity=log_capacity or 64
        ),
        "paxos": lambda: PaxosMachine(num_nodes=nodes or 5),
        "multipaxos": lambda: MultiPaxosMachine(num_nodes=nodes or 5),
        "demo-nopromise-paxos": lambda: NoPromiseCheckPaxos(num_nodes=nodes or 5),
        "demo-doublegrant-etcd": lambda: DoubleGrantEtcd(
            num_nodes=nodes or 4, target_gens=99, target_writes=9999
        ),
        "demo-overcommit-raft": lambda: OvercommitRaft(
            num_nodes=nodes or 5, log_capacity=cap
        ),
        "demo-nofencing-group": lambda: NoFencingGroupMachine(num_nodes=nodes or 4),
        "demo-quorumoffbyone-raft": lambda: QuorumOffByOneRaft(
            num_nodes=nodes or 5, log_capacity=cap
        ),
        "demo-volatilecommit-raft": lambda: VolatileCommitRaft(
            num_nodes=nodes or 5, log_capacity=cap
        ),
        "demo-dupvote-raft": lambda: DupVoteRaft(
            num_nodes=nodes or 5, log_capacity=cap
        ),
        "demo-fig8-raft": lambda: Fig8Raft(
            num_nodes=nodes or 5, log_capacity=cap
        ),
        "raft-compact": lambda: RaftCompactMachine(
            num_nodes=nodes or 5, log_capacity=cap
        ),
        "demo-tornsnapshot-raft": lambda: TornSnapshotRaftCompact(
            num_nodes=nodes or 5, log_capacity=cap
        ),
        "demo-nodedup-mvcc": lambda: NoDedupMvcc(num_nodes=nodes or 4),
        "demo-giveup-mvcc": lambda: PrematureGiveupMvcc(num_nodes=nodes or 4),
        "demo-nopromise-multipaxos": lambda: NoPromiseCheckMultiPaxos(
            num_nodes=nodes or 5
        ),
        "s3": lambda: S3Machine(num_nodes=nodes or 4),
        "gossip": lambda: GossipMachine(num_nodes=nodes or 33),
        "demo-dupack-gossip": lambda: DupAckGossip(num_nodes=nodes or 33),
        "demo-arrivalorder-s3": lambda: ArrivalOrderS3(num_nodes=nodes or 4),
        "demo-abortleak-s3": lambda: AbortLeakS3(num_nodes=nodes or 4),
        "demo-earlyexpiry-s3": lambda: EarlyExpiryS3(num_nodes=nodes or 4),
        "demo-tombstoneleak-s3": lambda: TombstoneLeakS3(num_nodes=nodes or 4),
        "demo-nodedup-s3": lambda: NoDedupS3(num_nodes=nodes or 4),
    }
    if name not in machines:
        sys.exit(f"unknown machine {name!r}; choose from {sorted(machines)}")
    machine = machines[name]()
    if log_capacity and getattr(machine, "log_capacity", None) != log_capacity:
        sys.exit(f"--log-capacity sizes the log of a raft, kvraft or kafka machine; "
                 f"{name!r} has none")
    return machine


def _build_engine(args):
    # engine construction (the engine/flax import chain, model init,
    # device constants, first backend touch) lands on the host
    # timeline: it is real wall time a --perf-timeline run would
    # otherwise report as unattributed
    from .perf.recorder import maybe_span

    with maybe_span("engine_build"):
        from .engine import Engine, EngineConfig, FaultPlan

        return _build_engine_inner(args, Engine, EngineConfig, FaultPlan)


def _build_engine_inner(args, Engine, EngineConfig, FaultPlan):
    machine = build_machine(
        args.machine, args.nodes, getattr(args, "log_capacity", None) or 0
    )
    cfg = EngineConfig(
        **_latency_fields(args),
        # guided hunts pin the 4-bit coverage band layout so the slot
        # space stays identical across fault-vocabulary escalations
        # (madsim_tpu/search); 0 keeps the derived layout — bit-for-bit
        # the HEAD behavior — for every unguided run
        cov_band_bits_min=4 if getattr(args, "guided", False) else 0,
        # round, not truncate: a shrunk repro prints horizon_us/1e6 and
        # float truncation would shave the failing event off the horizon
        horizon_us=round(args.horizon * 1e6),
        queue_capacity=args.queue,
        packet_loss_rate=args.loss,
        rng_stream=getattr(args, "rng_stream", 2),
        flight_recorder=bool(getattr(args, "flight_recorder", False)),
        coverage=bool(getattr(args, "coverage", False)),
        # None = keep the engine default (buffered); 0 = the unbuffered
        # escape hatch (per-event map scatter); maps bit-identical either way
        **({} if getattr(args, "cov_buffer", None) is None
           else {"cov_buffer": int(args.cov_buffer)}),
        provenance=bool(getattr(args, "provenance", False)),
        compile_cache_dir=getattr(args, "compile_cache", None),
        faults=FaultPlan(
            n_faults=args.faults,
            # explicit --fault-tmax keeps fault draws stable when a shrunk
            # repro command passes a smaller --horizon
            t_max_us=args.fault_tmax or int(args.horizon * 0.6e6) or 1,
            dur_min_us=100_000,
            dur_max_us=800_000,
            strict_restart=bool(getattr(args, "strict_restart", False)),
            **_fault_kind_flags(args),
            **_churn_fields(args),
        ),
    )
    if _device_count(args) > 1:
        # the code, not an environment variable, picks the step path a
        # meshed run can take (run_stream refuses a kernel a library
        # caller forces onto a mesh)
        return Engine.on_xla_step_path(machine, cfg)
    return Engine(machine, cfg)


def _latency_fields(args) -> dict:
    """`--latency MIN_US,MAX_US`: the send latency's uniform range
    (unset: the engine's 1-10 ms)."""
    raw = getattr(args, "latency", None)
    if not raw:
        return {}
    try:
        lo, hi = (int(x) for x in raw.split(","))
    except ValueError:
        sys.exit(f"--latency {raw!r}: expected MIN_US,MAX_US")
    if not 0 <= lo < hi:
        sys.exit(f"--latency {raw!r}: need 0 <= MIN_US < MAX_US")
    return {"latency_min_us": lo, "latency_max_us": hi}


def _churn_fields(args) -> dict:
    """`--churn NAME --churn-until S`: the fault process beside the
    schedule (engine/core.py `ChurnPlan`; unset: none)."""
    name = getattr(args, "churn", None)
    if not name:
        return {}
    from .engine.core import CHURN_PRESETS

    if name not in CHURN_PRESETS:
        sys.exit(f"unknown --churn {name!r}; choose from {sorted(CHURN_PRESETS)}")
    until = getattr(args, "churn_until", None)
    if not until or until <= 0:
        sys.exit("--churn needs --churn-until S > 0: the virtual second at "
                 "which every node is reconnected")
    return {"churn": CHURN_PRESETS[name], "churn_until_us": round(until * 1e6)}


def deployment_flags_str(churn, churn_until, log_capacity, latency) -> str:
    """The flags of `_latency_fields` / `_churn_fields` / the log size,
    as a repro line carries them ('' for each that is unset; ends in a
    space otherwise)."""
    return (
        (f"--churn {churn} --churn-until {churn_until} " if churn else "")
        + (f"--log-capacity {log_capacity} " if log_capacity else "")
        + (f"--latency {latency} " if latency else "")
    )


def config_deployment_flags_str(cfg, log_capacity: int = 0) -> str:
    """`deployment_flags_str` of an EngineConfig (a shrunk one)."""
    from .engine.core import CHURN_PRESETS, EngineConfig

    f = cfg.faults
    churn = next((k for k, v in CHURN_PRESETS.items() if v == f.churn), None)
    base = EngineConfig()
    lat = (cfg.latency_min_us, cfg.latency_max_us)
    return deployment_flags_str(
        churn, f.churn_until_us / 1e6, log_capacity,
        None if lat == (base.latency_min_us, base.latency_max_us)
        else f"{lat[0]},{lat[1]}",
    )


def _fault_kind_flags(args) -> dict:
    # default-tolerant: programmatic callers and pre-round-3 recorded
    # argsets may lack the flag; absent == legacy pair,kill. The
    # vocabulary is the shared madsim_tpu/kinds.py table (lint rule
    # G004 asserts this parser binds it rather than a drifting copy).
    from .kinds import CLI_KIND_TO_FLAG

    raw = getattr(args, "fault_kinds", "pair,kill")
    kinds = {k.strip() for k in raw.split(",") if k.strip()}
    known = {name for name, _field in CLI_KIND_TO_FLAG}
    if not kinds <= known:
        sys.exit(f"unknown fault kinds {sorted(kinds - known)}; choose from {sorted(known)}")
    if kinds == {"dup"} and args.faults > 0:
        sys.exit(
            "dup is per-delivery chaos, not a scheduled fault: with "
            "--faults > 0 pick at least one scheduled kind too "
            "(e.g. --fault-kinds pair,kill,dup), or pass --faults 0"
        )
    return {field: name in kinds for name, field in CLI_KIND_TO_FLAG}


def fault_kinds_str(fp) -> str:
    """The --fault-kinds value that reproduces a FaultPlan's vocabulary
    (the inverse of _fault_kind_flags; shrink prints it after kind
    ablation so the repro line matches the MINIMIZED plan)."""
    from .kinds import CLI_KIND_TO_FLAG

    return ",".join(
        name for name, field in CLI_KIND_TO_FLAG if getattr(fp, field)
    ) or "pair"


def _repro_line(args, seed) -> str:
    """A replay command that reproduces `seed` exactly — including the
    resolved --fault-tmax, which is load-bearing: without it a replay
    with a different --horizon would draw a different fault schedule."""
    tmax = args.fault_tmax or int(args.horizon * 0.6e6) or 1
    return (
        f"reproduce: python -m madsim_tpu replay --machine {args.machine} "
        f"--seed {seed} --nodes {args.nodes} --horizon {args.horizon} "
        f"--queue {args.queue} --faults {args.faults} --loss {args.loss} "
        f"--fault-tmax {tmax} "
        f"--fault-kinds {getattr(args, 'fault_kinds', 'pair,kill')} "
        f"--rng-stream {getattr(args, 'rng_stream', 2)} "
        + ("--strict-restart " if getattr(args, "strict_restart", False) else "")
        + deployment_flags_str(
            getattr(args, "churn", None), getattr(args, "churn_until", None),
            getattr(args, "log_capacity", None), getattr(args, "latency", None),
        )
        + (
            f"--devices {args.devices} "
            if getattr(args, "devices", 0)
            else ""
        )
        + f"--max-steps {args.max_steps}"
    )


@contextlib.contextmanager
def _perf_session(args):
    """`--perf-timeline PATH` / `--xla-profile DIR` wrapper around a
    whole subcommand: a PerfRecorder publishes itself for the program's
    span instrumentation (madsim_tpu/perf/recorder.py) and the Chrome/
    Perfetto host timeline + summary land AFTER the command's own
    output; `--xla-profile` additionally wraps the run in
    `jax.profiler.trace` (device/XLA-level profile for tensorboard),
    and with both the recorder annotates: every span is also a
    `madsim.<name>` slice in that profile, on the device ops' clock.
    The timeline is written even when the command fails — a failing
    run's wall-clock profile is exactly what you want to look at."""
    path = getattr(args, "perf_timeline", None)
    xla_dir = getattr(args, "xla_profile", None)
    if not path and not xla_dir:
        yield None
        return
    rec = None
    try:
        with contextlib.ExitStack() as stack:
            if xla_dir:
                import jax

                stack.enter_context(jax.profiler.trace(xla_dir))
            if path:
                from .perf.recorder import PerfRecorder

                # with --xla-profile the spans are written into the
                # capture too: host and device on one clock
                rec = stack.enter_context(PerfRecorder(
                    meta={"cmd": getattr(args, "cmd", None)},
                    annotate=bool(xla_dir),
                ))
            yield rec
    finally:
        if rec is not None and rec.wall_us:
            n = rec.write(path)
            s = rec.summary()
            print(
                f"host timeline: {n} spans, "
                f"{100 * s['span_coverage']:.0f}% of {s['wall_s']:.1f}s "
                f"wall attributed -> {path} (open in https://ui.perfetto.dev)"
            )
            print(f"host verdict: {rec.verdict()}")
        if xla_dir:
            print(f"xla profile -> {xla_dir} (tensorboard --logdir {xla_dir})")


def _stream_kwargs(args) -> dict:
    """What `--devices N` adds to a run_stream call: the mesh."""
    kw = {}
    n = getattr(args, "devices", 0)
    if n:
        import jax

        from .parallel import make_mesh

        devs = jax.devices()
        if n > len(devs):
            raise SystemExit(
                f"--devices {n}: only {len(devs)} devices visible (on CPU, "
                f"set XLA_FLAGS=--xla_force_host_platform_device_count={n})"
            )
        kw["mesh"] = make_mesh(devs[:n])
    return kw


def _print_fr_stats(stats) -> None:
    """One metrics line when the flight recorder rode the stream."""
    fr = stats.get("flight_recorder")
    if not fr:
        return
    inj = ", ".join(f"{k}={v}" for k, v in fr["faults_injected"].items() if v)
    extra = "".join(
        f", {label} {fr[key]}"
        for key, label in (
            ("dup_injected", "dups"), ("amnesia_restarts", "amnesia restarts"),
        )
        if fr.get(key)
    )
    if fr.get("churn"):
        c = fr["churn"]
        extra += (f", churn {c['ticks']} ticks / {c['disconnects']} "
                  f"disconnects / {c['reconnects']} reconnects")
        if "partitions" in c:  # kind kv3a
            extra += f" / {c['partitions']} partitions / {c['crashes']} crashes"
    if fr.get("machine"):
        extra += ", machine [" + ", ".join(
            f"{k}={v}" for k, v in fr["machine"].items()) + "]"
    print(
        f"flight recorder: faults injected [{inj or 'none'}]{extra}, "
        f"queue hwm {fr['queue_hwm']}, clogged-links hwm {fr['clog_links_hwm']}, "
        f"killed hwm {fr['killed_hwm']}"
    )


def _make_emitter(args):
    """StatsEmitter bound to --stats BASE (also $MADSIM_TPU_STATS):
    BASE.jsonl (history), BASE.prom (Prometheus textfile), BASE.json
    (latest snapshot — what `serve --service stats` exposes).
    `args.stats_labels` (set by the fleet worker, not a CLI flag)
    namespaces the Prometheus gauges per job."""
    base = getattr(args, "stats", None) or os.environ.get("MADSIM_TPU_STATS")
    if not base:
        return None
    from .tracing import StatsEmitter
    from .utils import device_info

    return StatsEmitter(
        base, labels=getattr(args, "stats_labels", None),
        common=device_info(),
    )


def _print_device() -> None:
    """The device the run was placed on, as jax reports it — printed
    with every explore/hunt summary so no count or rate is read
    without it."""
    from .utils import device_info

    d = device_info()
    print(
        f"device: platform={d['platform']} kind={d['device_kind']!r} "
        f"count={d['device_count']}"
    )


def _print_cov_stats(stats) -> None:
    """One coverage line when the map rode the stream."""
    cov = stats.get("coverage")
    if not cov:
        return
    bands = ", ".join(f"{k}={v}" for k, v in cov["by_band"].items() if v)
    print(
        f"coverage: {cov['slots_hit']}/{cov['slots_total']} slots "
        f"({100 * cov['fraction']:.2f}%) [{bands or 'none'}]"
    )


def _print_attribution(stats) -> None:
    """One fault-attribution line when provenance rode the run: how many
    failures causally implicate each chaos kind."""
    att = stats.get("fault_attribution")
    if att is None:
        return
    kinds = ", ".join(f"{k}={v}" for k, v in att.items())
    print(f"fault attribution: [{kinds or 'no failures'}]")


def _batch_heartbeat(bi, planned, completed, el, failing, infra, abandoned,
                     device_count=1, escalation=None, cov_txt=""):
    """The per-batch heartbeat line (format pinned in tests): batch
    index, throughput, the device count the unit spanned (meshed hunts
    read differently from single-device ones in the same log), failure
    tallies, the guided escalation rung when one exists, and the
    coverage delta."""
    esc_txt = f", escalation {escalation}" if escalation is not None else ""
    return (
        f"batch {bi}/{planned}: {completed} seeds in {el:.1f}s "
        f"({completed / el:.0f} seeds/s) on {device_count} device(s), "
        f"{failing} failing so far, {infra} infra, {abandoned} abandoned"
        f"{esc_txt}{cov_txt}"
    )


def _device_count(args) -> int:
    """Devices a streaming unit spans: `--devices N` meshes over N, 0
    means the classic unsharded single-device path."""
    return int(getattr(args, "devices", 0) or 0) or 1


def _stream_batches(eng, args, purpose="explore"):
    """Chunked streaming driver shared by explore/hunt: run the seed
    budget as batches of `--batch` seeds (each one run_stream call), so
    long hunts are observable — a heartbeat log line per batch (at
    --log-level info), a StatsEmitter record per batch (--stats), a
    cumulative coverage map, and the `--stop-on-plateau N` early exit
    when N consecutive batches add zero new coverage slots.

    Returns an aggregate dict shaped like run_stream's result, plus
    "batches_run"/"batches_planned"/"plateau"/"elapsed_s" (and
    "coverage_map" when the engine's coverage gate is on).
    """
    import numpy as np
    import time as wall

    from .perf.recorder import maybe_note, maybe_span

    if getattr(args, "guided", False):
        # coverage-feedback search (madsim_tpu/search): same aggregate
        # shape, same checkpoint file, same stats feed — but every
        # batch's seed vector is chosen by the bias state instead of
        # streamed sequentially. Guidance OFF never reaches this
        # import, so the streaming path below stays byte-identical to
        # HEAD by construction.
        from .search.guided import run_guided

        return run_guided(eng, args, purpose=purpose)

    log = logging.getLogger(f"madsim_tpu.{purpose}")
    emitter = _make_emitter(args)
    plateau_n = int(getattr(args, "stop_on_plateau", 0) or 0)
    detector = None
    if plateau_n:
        if not getattr(args, "coverage", False):
            sys.exit(
                "--stop-on-plateau needs --coverage: the plateau signal "
                "IS the coverage curve"
            )
        from .runtime.coverage import PlateauDetector

        detector = PlateauDetector(plateau_n)

    sk = _stream_kwargs(args)
    batch = min(args.seeds, args.batch)
    planned = -(-args.seeds // batch)  # ceil

    agg = {
        "completed": 0,
        "failing": [],
        "infra": [],
        "abandoned": [],
        "seeds_consumed": 0,
        "stats": {},
        # seed -> violation provenance word (--provenance; stays empty
        # otherwise)
        "provenance": {},
    }
    cov_map = None
    cursor = args.seed
    plateaued = False
    start_bi = 0

    # --checkpoint PATH: restore per-batch progress recorded by an
    # interrupted run (atomic JSON, runtime/checkpoint.py). Batch i
    # always consumes the same seed range, so cursor + aggregates are
    # the whole resumable state — the finished report is identical to
    # the uninterrupted run's.
    ckpt_path = getattr(args, "checkpoint", None)
    stop_after = int(getattr(args, "stop_after_batches", 0) or 0)
    if ckpt_path:
        from .runtime.checkpoint import check_fingerprint, load_checkpoint

        ck = load_checkpoint(ckpt_path)
        if ck is not None:
            err = check_fingerprint(ck, args)
            if err:
                sys.exit(f"--checkpoint {ckpt_path}: {err}")
            agg["completed"] = int(ck["completed"])
            agg["seeds_consumed"] = int(ck["seeds_consumed"])
            agg["failing"] = [tuple(x) for x in ck["failing"]]
            agg["infra"] = [tuple(x) for x in ck["infra"]]
            agg["abandoned"] = list(ck["abandoned"])
            agg["provenance"] = {
                int(k): int(v) for k, v in (ck.get("prov") or {}).items()
            }
            cursor = int(ck["cursor"])
            start_bi = int(ck["batch"])
            plateaued = bool(ck.get("plateau", False))
            if ck.get("cov_b64"):
                from .runtime.coverage import decode_map

                cov_map = decode_map(ck["cov_b64"], eng.config.cov_slots_log2)
            if detector is not None and ck.get("detector"):
                d = ck["detector"]
                detector.best = int(d["best"])
                detector.streak = int(d["streak"])
                detector.batches = int(d["batches"])
            if ck.get("done"):
                print(
                    f"checkpoint {ckpt_path}: run already complete "
                    f"({start_bi}/{planned} batches, "
                    f"{agg['completed']} seeds) — nothing to resume"
                )
            else:
                print(f"resumed at batch {start_bi + 1}/{planned} "
                      f"({agg['completed']} seeds already completed)")
                log.info(
                    "checkpoint %s: resumed at batch %d/%d",
                    ckpt_path, start_bi + 1, planned,
                )

    def _save_ckpt(bi_done: int, done_flag: bool) -> None:
        if not ckpt_path:
            return
        from .runtime.checkpoint import fingerprint_from_args, save_checkpoint
        from .runtime.coverage import encode_map

        save_checkpoint(
            ckpt_path,
            {
                "fingerprint": fingerprint_from_args(args),
                "batch": bi_done,
                "planned": planned,
                "cursor": cursor,
                "completed": agg["completed"],
                "seeds_consumed": agg["seeds_consumed"],
                "failing": [list(x) for x in agg["failing"]],
                "infra": [list(x) for x in agg["infra"]],
                "abandoned": list(agg["abandoned"]),
                "prov": {str(k): v for k, v in agg["provenance"].items()},
                "cov_b64": encode_map(cov_map) if cov_map is not None else None,
                "detector": (
                    {
                        "best": detector.best,
                        "streak": detector.streak,
                        "batches": detector.batches,
                    }
                    if detector is not None else None
                ),
                "plateau": plateaued,
                "done": done_flag,
            },
        )

    # compile outside the timed loop, and run nothing: the stream's
    # programs are made ready (traced, lowered, compiled or read from
    # the cache) without a dispatch, and an engine that already holds
    # them — a second campaign on a process, a fleet worker's next
    # unit — does nothing here. Nothing left to run, nothing to make.
    if start_bi < planned and agg["completed"] < args.seeds:
        with maybe_span("warmup_dispatch"):
            made = eng.prepare_stream(
                batch=batch, segment_steps=384, max_steps=args.max_steps, **sk
            )
            maybe_note(ready=not made, programs=made)

    t_start = wall.perf_counter()
    bi = start_bi - 1
    for bi in range(start_bi, planned):
        chunk = min(batch, args.seeds - agg["completed"])
        if chunk <= 0:
            _save_ckpt(bi, True)  # seed budget already consumed: complete
            break
        t0 = wall.perf_counter()
        # every batch runs the full lane count, the tail included: a
        # narrower tail would be a new shape — a fresh compile, which
        # on the chip costs more than the lanes it saves — and a lane
        # count the mesh may not divide
        out = eng.run_stream(
            chunk, batch=batch, segment_steps=384,
            seed_start=cursor, max_steps=args.max_steps, **sk,
        )
        el = max(wall.perf_counter() - t0, 1e-9)
        cursor += out["seeds_consumed"]
        agg["completed"] += out["completed"]
        agg["seeds_consumed"] += out["seeds_consumed"]
        agg["failing"].extend(out["failing"])
        agg["infra"].extend(out["infra"])
        agg["abandoned"].extend(out["abandoned"])
        agg["provenance"].update(out.get("provenance", {}))
        agg["stats"] = out["stats"]
        new_slots = 0
        slots_hit = 0
        if "coverage_map" in out:
            m = np.asarray(out["coverage_map"])
            prev = 0 if cov_map is None else int(cov_map.sum())
            cov_map = m if cov_map is None else (cov_map | m)
            slots_hit = int(cov_map.sum())
            new_slots = slots_hit - prev
        cov_txt = (
            f", coverage {slots_hit} slots (+{new_slots})"
            if cov_map is not None else ""
        )
        log.info("%s", _batch_heartbeat(
            bi + 1, planned, out["completed"], el,
            len(agg["failing"]), len(agg["infra"]), len(agg["abandoned"]),
            device_count=_device_count(args), cov_txt=cov_txt,
        ))
        if emitter is not None:
            rec = {
                "kind": f"{purpose}_batch",
                "machine": args.machine,
                "batch": bi + 1,
                "batches": planned,
                "completed": agg["completed"],
                "batch_completed": out["completed"],
                "seeds_per_sec": round(out["completed"] / el, 1),
                "failing": len(agg["failing"]),
                "infra": len(agg["infra"]),
                "abandoned": len(agg["abandoned"]),
            }
            if cov_map is not None:
                rec["coverage"] = {
                    "slots_hit": slots_hit, "new_slots": new_slots,
                }
            if "flight_recorder" in out["stats"]:
                rec["flight_recorder"] = out["stats"]["flight_recorder"]
            emitter.emit(rec)
        if detector is not None and detector.update(slots_hit):
            plateaued = True
        _save_ckpt(bi + 1, plateaued)
        if plateaued:
            log.info(
                "coverage plateau: no new slots for %d consecutive "
                "batches — stopping after batch %d/%d",
                plateau_n, bi + 1, planned,
            )
            break
        if stop_after and bi + 1 >= stop_after:
            # deliberate early stop (CI checkpoint smoke / operational
            # "hunt in slices"): the checkpoint above has done=False,
            # so the next --checkpoint run resumes at batch bi+2
            log.info(
                "stopping after batch %d/%d (--stop-after-batches %d; "
                "resumable via --checkpoint)", bi + 1, planned, stop_after,
            )
            break
    else:
        _save_ckpt(planned, True)

    agg["elapsed_s"] = wall.perf_counter() - t_start
    agg["batches_run"] = bi + 1
    agg["batches_planned"] = planned
    agg["plateau"] = plateaued
    if cov_map is not None:
        agg["coverage_map"] = cov_map
        from .runtime.coverage import coverage_dict

        agg["stats"] = dict(agg["stats"])
        agg["stats"]["coverage"] = {
            **coverage_dict(
                cov_map, eng.config.cov_slots_log2,
                band_bits=eng.cov_band_bits,
            ),
            "plateau": plateaued,
            "plateau_patience": plateau_n,
        }
    if agg["provenance"]:
        # per-kind fault attribution over the finds: how many failures
        # causally implicate each chaos kind — the machine-readable
        # "why" marginal the stats JSONL and `/stats` service expose
        from .engine.provenance import kind_counts

        agg["stats"] = dict(agg["stats"])
        agg["stats"]["fault_attribution"] = kind_counts(eng, agg["provenance"])
    if emitter is not None:
        emitter.emit(
            {
                "kind": f"{purpose}_summary",
                "machine": args.machine,
                "completed": agg["completed"],
                "failing": len(agg["failing"]),
                "infra": len(agg["infra"]),
                "abandoned": len(agg["abandoned"]),
                "batches_run": agg["batches_run"],
                "batches_planned": planned,
                "plateau": plateaued,
                "elapsed_s": round(agg["elapsed_s"], 2),
                **(
                    {"coverage": agg["stats"]["coverage"]}
                    if cov_map is not None else {}
                ),
                **(
                    {"fault_attribution": agg["stats"]["fault_attribution"]}
                    if "fault_attribution" in agg["stats"] else {}
                ),
            }
        )
        emitter.close()
    return agg


def _write_coverage_out(eng, args, agg) -> None:
    """`hunt --coverage-out PATH`: persist the cumulative map for
    cross-run diffing (`madsim_tpu coverage PATH --diff OLD`)."""
    path = getattr(args, "coverage_out", None)
    if not path:
        return
    if "coverage_map" not in agg:
        sys.exit("--coverage-out needs --coverage and --stream")
    import time as wall

    from .runtime.coverage import make_coverage_doc, save_coverage_doc

    doc = make_coverage_doc(
        {args.machine: agg["coverage_map"]},
        eng.config.cov_slots_log2,
        band_bits=eng.cov_band_bits,
        meta={
            "seeds": args.seeds,
            "seed_start": args.seed,
            "completed": agg["completed"],
            "fault_kinds": getattr(args, "fault_kinds", "pair,kill"),
            "ts": round(wall.time(), 3),
        },
    )
    save_coverage_doc(path, doc)
    cov = agg["stats"]["coverage"]
    print(
        f"coverage map: {cov['slots_hit']}/{cov['slots_total']} slots "
        f"-> {path}"
    )


def _split_infra(failing):
    """Partition (seed, code) pairs into (findings, infra): OVERFLOW is
    a fixed-shape capacity abort — an infrastructure artifact that says
    "rerun with a bigger --queue", never a protocol finding."""
    from .engine import OVERFLOW

    pairs = list(failing)
    findings = [(s, c) for s, c in pairs if c != OVERFLOW]
    infra = [(s, c) for s, c in pairs if c == OVERFLOW]
    return findings, infra


def _find_failing(eng, args, purpose="hunt"):
    """Run the seed batch (streaming or fixed) and return
    (failing [(seed, code), ...], infra [(seed, code), ...],
    abandoned_count, aggregate) where aggregate is _stream_batches'
    result dict (empty for the fixed path)."""
    if args.stream:
        agg = _stream_batches(eng, args, purpose=purpose)
        return agg["failing"], agg["infra"], len(agg["abandoned"]), agg
    import jax.numpy as jnp

    seeds = jnp.arange(args.seed, args.seed + args.seeds, dtype=jnp.uint32)
    res = eng.make_runner(max_steps=args.max_steps)(seeds)
    failing, infra = _split_infra(
        (int(s), int(c))
        for s, c in zip(
            eng.failing_seeds(res).tolist(), res.fail_code[res.failed].tolist()
        )
    )
    agg = {"stats": {}, "provenance": {}}
    if eng.config.provenance:
        agg["provenance"] = {
            int(s): int(p)
            for s, p in zip(
                eng.failing_seeds(res).tolist(),
                res.fail_prov[res.failed].tolist(),
            )
        }
        from .engine.provenance import kind_counts

        agg["stats"]["fault_attribution"] = kind_counts(eng, agg["provenance"])
    return failing, infra, 0, agg


def cmd_explore(args) -> int:
    import jax.numpy as jnp

    if getattr(args, "multihost", False):
        # join the jax.distributed job (MADSIM_TPU_COORDINATOR/NUM_PROCS/
        # PROC_ID, or pod auto-detect) and shard the batch globally
        from .parallel import multihost, pad_to_multiple

        multihost.initialize()
        import jax as _jax

        eng = _build_engine(args)
        n = pad_to_multiple(args.seeds, _jax.device_count())
        out = multihost.run_batch_global(
            eng, n, seed_start=args.seed, max_steps=args.max_steps
        )
        # results are replicated on every process — only rank 0 reports
        if _jax.process_index() == 0:
            print(
                f"explored {n} seeds over {out['processes']} processes / "
                f"{out['global_devices']} devices ({out['completed']} completed), "
                f"{out['failed']} failing"
            )
            if out["failing"]:
                print(f"failing seeds: {out['failing'][:20]}"
                      f"{' ...' if out['truncated'] else ''}")
        return 1 if out["failing"] else 0

    eng = _build_engine(args)
    if args.stream:
        # seed streaming: finished lanes refill with fresh seeds — the
        # high-throughput path for large batches,
        # chunked into --batch-seed batches so long runs heartbeat,
        # emit stats and can stop on a coverage plateau
        out = _stream_batches(eng, args, purpose="explore")
        el = out["elapsed_s"]
        failing = out["failing"]
        st = out["stats"]
        plateau_txt = (
            f" [stopped early: coverage plateau after batch "
            f"{out['batches_run']}/{out['batches_planned']}]"
            if out["plateau"] else ""
        )
        print(
            f"streamed {out['completed']} seeds in {el:.1f}s "
            f"({out['completed']/max(el, 1e-9):.0f} seeds/s), {len(failing)} failing, "
            f"{len(out['abandoned'])} abandoned"
            + (f", {len(out['infra'])} infra (queue overflow)" if out["infra"] else "")
            + plateau_txt
        )
        print(
            f"executor: {st['device_segments']} segments, "
            f"{st['host_syncs']} host syncs, {st['drains']} drains "
            f"(pipelined={st['pipelined']}, donation={st['donation']}, "
            f"depth={st['dispatch_depth']}x{st['segments_per_dispatch']})"
        )
        _print_device()
        _print_fr_stats(st)
        _print_cov_stats(st)
        _print_attribution(st)
        if failing:
            codes = sorted({c for _s, c in failing})
            print(f"failure codes: {codes}")
            print(f"failing seeds: {[s for s, _ in failing[:20]]}"
                  f"{' ...' if len(failing) > 20 else ''}")
            print(_repro_line(args, failing[0][0]))
            return 1
        return 0

    seeds = jnp.arange(args.seed, args.seed + args.seeds, dtype=jnp.uint32)
    res = eng.make_runner(max_steps=args.max_steps)(seeds)
    failing = eng.failing_seeds(res).tolist()
    n_done = int(res.done.sum())
    print(f"explored {len(seeds.tolist())} seeds ({n_done} completed), "
          f"{len(failing)} failing")
    _print_device()
    if getattr(args, "coverage", False):
        import numpy as np

        from .runtime.coverage import coverage_dict, unpack_map

        m = unpack_map(
            np.bitwise_or.reduce(np.asarray(res.cov["map"]), axis=0),
            eng.config.cov_slots_log2,
        )
        _print_cov_stats(
            {"coverage": coverage_dict(
                m, eng.config.cov_slots_log2, band_bits=eng.cov_band_bits
            )}
        )
    if failing:
        codes = sorted({int(c) for c in res.fail_code.tolist() if c != 0})
        print(f"failure codes: {codes}")
        print(f"failing seeds: {failing[:20]}{' ...' if len(failing) > 20 else ''}")
        print(_repro_line(args, failing[0]))
        return 1
    return 0


def cmd_hunt(args) -> int:
    """explore -> shrink -> corpus: every found failing seed becomes a
    durable "open" regression entry with its minimized config."""
    from .perf.recorder import maybe_span

    if getattr(args, "guided", False):
        if not args.stream:
            sys.exit("--guided needs --stream (the chunked batch loop "
                     "is where the feedback lives)")
        if not getattr(args, "coverage", False):
            sys.exit("--guided needs --coverage: the bias signal IS the "
                     "live coverage map")
    eng = _build_engine(args)
    # after the build, whose span holds the engine's import chain
    from .engine import audit, corpus, shrink

    failing, infra, abandoned, agg = _find_failing(eng, args, purpose="hunt")
    # the stream's return to the first shrink: prints, the coverage
    # file, the corpus load
    with maybe_span("hunt_report"):
        stream_stats = agg.get("stats", {})
        hunted = agg.get("completed", args.seeds)
        plateau_txt = ""
        if agg.get("plateau"):
            # honest reporting: a plateaued hunt ran FEWER seeds than asked
            plateau_txt = (
                f" [coverage plateau: stopped after batch "
                f"{agg['batches_run']}/{agg['batches_planned']} — "
                f"{max(0, args.seeds - hunted)} budgeted seeds not run]"
            )
        print(
            f"hunted {hunted} seeds: {len(failing)} failing"
            + (f", {abandoned} abandoned (over --max-steps)" if abandoned else "")
            + (
                f", {len(infra)} infra artifacts (queue overflow — rerun "
                f"with a bigger --queue; not recorded as findings)"
                if infra else ""
            )
            + plateau_txt
        )
        _print_device()
        _print_fr_stats(stream_stats)
        _print_cov_stats(stream_stats)
        _print_attribution(stream_stats)
        guided_rec = agg.get("guided") or {}
        if guided_rec:
            g = stream_stats.get("guided", {})
            print(
                f"guided: escalation step {g.get('escalation', 0)}, "
                f"{g.get('parents', 0)} corpus parents, "
                f"{g.get('mutants', 0)} mutants over {g.get('batches', 0)} "
                f"batches (trail recorded"
                + (" in checkpoint)" if getattr(args, "checkpoint", None)
                   else ")")
            )
        _write_coverage_out(eng, args, agg)
        entries = corpus.load(args.corpus)
        known = {e.key for e in entries}
        added = 0
        # Shrink one representative per distinct fail code (high-find-rate
        # hunts surface thousands of seeds of the SAME bug; shrinking five
        # copies of one code is pure waste). --all-seeds restores the
        # first-N behavior for deliberately sampling one code's seeds.
        if getattr(args, "all_seeds", False):
            to_shrink = failing[: args.limit]
        else:
            by_code: dict = {}
            for seed, code in failing:
                by_code.setdefault(code, []).append(seed)
            to_shrink = [(s[0], c) for c, s in sorted(by_code.items())][: args.limit]
            shrinking = {c for _s, c in to_shrink}
            for code, seeds_of in sorted(by_code.items()):
                verb = (
                    f"shrinking seed {seeds_of[0]}" if code in shrinking
                    else "beyond --limit, not shrunk"
                )
                print(f"  code {code}: {len(seeds_of)} seeds ({verb})")
        esc_by_seed = {
            int(k): int(v)
            for k, v in (guided_rec.get("failing_escalation") or {}).items()
        } if guided_rec else {}
    for seed, code in to_shrink:
        # a guided find made under an escalated vocabulary only
        # reproduces under that vocabulary: shrink (and the corpus
        # entry's config) start from the escalation step's engine, and
        # kind ablation then minimizes it honestly
        shrink_eng = eng
        if esc_by_seed.get(seed):
            from .search.guided import engine_for_escalation

            shrink_eng = engine_for_escalation(eng, esc_by_seed[seed])
        try:
            # the device-harvested provenance word (when the gate rode
            # the hunt) seeds the guided candidate order; shrink still
            # verifies every candidate by honest replay
            sr = shrink(
                shrink_eng, seed, max_steps=args.max_steps,
                prov_word=agg.get("provenance", {}).get(seed),
            )
        except ValueError as exc:
            # device-flagged but not reproducing on the host replay —
            # report it (that drift is itself a finding) and keep going
            print(f"  ! seed {seed} code {code}: {exc}")
            continue
        entry = corpus.CorpusEntry(
            machine=args.machine,
            nodes=args.nodes,
            log_capacity=getattr(args, "log_capacity", None) or 0,
            seed=seed,
            fail_code=code,
            status=corpus.STATUS_OPEN,
            config=sr.shrunk,
            max_steps=sr.steps + 1,
            note=sr.summary(),
        )
        if entry.key in known:
            print(f"  = corpus: seed {seed} code {code} already recorded")
            continue
        # every new entry carries its digest trail + environment
        # fingerprint from birth, so future rot is auditable
        with maybe_span("corpus_record", seed=int(seed)):
            entry, _trail = audit.record_entry(entry, build_machine)
        known.add(entry.key)
        entries.append(entry)
        added += 1
        print(f"  + corpus: {sr.summary()}")
    if added:
        with maybe_span("corpus_record"):
            corpus.save(args.corpus, entries)
    if len(to_shrink) < (len(failing) if getattr(args, "all_seeds", False)
                         else len({c for _s, c in failing})):
        print(f"  (further failing codes/seeds not shrunk; raise --limit)")
    print(f"{added} new entries in {args.corpus}")
    return 1 if failing else 0


def cmd_regress(args) -> int:
    """Re-verify every corpus entry against its status contract: open
    entries must still reproduce their exact failure; fixed entries must
    keep passing. `--promote` flips open entries that no longer fail."""
    from .engine import corpus
    from .perf.recorder import maybe_span

    entries = corpus.load(args.corpus)
    if not entries:
        print(f"corpus {args.corpus} is empty")
        return 0
    bad = 0
    changed = False
    for i, e in enumerate(entries):
        try:
            with maybe_span("regress_entry", seed=int(e.seed)):
                out = corpus.check(e, build_machine)
        except SystemExit:
            # unknown machine name (renamed registry entry / foreign
            # corpus) must not kill the run — later entries still get
            # checked and pending --promote updates still get saved
            print(f"[FAIL] {e.machine} seed {e.seed}: unknown machine in registry")
            bad += 1
            continue
        tag = "ok " if out.ok else "FAIL"
        print(f"[{tag}] {e.machine} seed {e.seed} code {e.fail_code} ({e.status}): {out.verdict}")
        if not out.ok:
            if args.promote and e.status == corpus.STATUS_OPEN and not out.failed:
                entries[i] = dataclasses.replace(e, status=corpus.STATUS_FIXED)
                changed = True
                print(f"       promoted to {corpus.STATUS_FIXED}")
            else:
                bad += 1
    if changed:
        corpus.save(args.corpus, entries)
        print(f"corpus updated: {args.corpus}")
    print(f"{len(entries) - bad}/{len(entries)} entries satisfied")
    return 1 if bad else 0


def cmd_replay(args) -> int:
    from .engine import replay

    eng = _build_engine(args)
    if getattr(args, "diff_seed", None) is not None:
        # schedule-fork debugger: replay both seeds, print the first
        # diverging step with context (typical use: a failing seed vs
        # its nearest passing neighbor)
        from .engine.replay import replay_diff

        replay_diff(
            eng, args.seed, args.diff_seed, max_steps=args.max_steps,
            context=args.diff_context,
        )
        return 0
    rp = replay(eng, args.seed, max_steps=args.max_steps)
    events = rp.trace[-args.tail :] if args.tail else rp.trace
    for ev in events:
        print(ev)
    status = f"FAILED (code {rp.fail_code})" if rp.failed else "ok"
    print(f"seed {args.seed}: {status}, {len(rp.trace)} events, "
          f"t={int(rp.state.now_us)}us")
    return 1 if rp.failed else 0


def cmd_trace(args) -> int:
    """Replay one seed and export its virtual-time event timeline:
    Chrome/Perfetto trace_event JSON (--perfetto, opens in
    ui.perfetto.dev / chrome://tracing with one row per node) and/or
    structured JSONL (--jsonl, one object per event)."""
    from .engine import replay
    from .engine.trace_export import write_jsonl, write_perfetto

    if not args.perfetto and not args.jsonl:
        sys.exit("trace needs at least one of --perfetto PATH / --jsonl PATH")
    eng = _build_engine(args)
    n_nodes = eng.machine.NUM_NODES
    if args.perfetto:
        # lineage-capturing replay: the queue sequence numbers plus the
        # per-step push watermarks reconstruct every send->delivery
        # edge, so the export draws flow arrows (works with the
        # provenance gate off — message causality is free)
        from .engine.provenance import replay_with_lineage

        rp, lineage = replay_with_lineage(eng, args.seed, max_steps=args.max_steps)
        flows = [
            (lineage.trace[i], lineage.trace[j])
            for i, j in lineage.message_flows()
        ]
        n = write_perfetto(
            args.perfetto, rp.trace,
            machine=args.machine, seed=args.seed, num_nodes=n_nodes,
            flows=flows,
        )
        print(f"wrote {n} events ({len(flows)} message flows) to "
              f"{args.perfetto} (perfetto trace_event; "
              f"open in https://ui.perfetto.dev)")
    else:
        rp = replay(eng, args.seed, max_steps=args.max_steps)
    if args.jsonl:
        n = write_jsonl(args.jsonl, rp.trace, machine=args.machine, seed=args.seed)
        print(f"wrote {n} events to {args.jsonl} (JSONL)")
    status = f"FAILED (code {rp.fail_code})" if rp.failed else "ok"
    print(f"seed {args.seed}: {status}, {len(rp.trace)} events, "
          f"t={int(rp.state.now_us)}us")
    return 1 if rp.failed else 0


def cmd_why(args) -> int:
    """Answer "why did this seed fail?": replay with causal provenance +
    lineage reconstruction, decode the violation's provenance word to
    the implicated scheduled faults (kind, virtual time, target), cut
    the trace to the violation's past cone, and render the causal chain
    as text (stdout / --out), machine-readable JSON (--json), and a
    Perfetto timeline with flow arrows + the cone highlighted
    (--perfetto)."""
    from .engine.provenance import implicated, render_why, replay_with_lineage
    from .engine.trace_export import write_perfetto

    args.provenance = True  # the whole point of `why`
    if getattr(args, "seed_pos", None) is not None:
        args.seed = args.seed_pos
    eng = _build_engine(args)
    rp, lineage = replay_with_lineage(eng, args.seed, max_steps=args.max_steps)
    if not rp.failed:
        print(
            f"seed {args.seed} does not fail under this config (within "
            f"{args.max_steps} steps) — nothing to explain; pass the "
            f"repro line's exact flags"
        )
        return 2
    word = int(rp.state.fail_prov)
    att = implicated(eng, args.seed, word)
    cone = lineage.past_cone(len(lineage.trace) - 1)
    text = render_why(
        eng, args.seed, rp, lineage, cone, att, max_events=args.tail
    )
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"causal chain -> {args.out}")
    if args.json:
        doc = {
            "machine": args.machine,
            "seed": args.seed,
            "fail_code": rp.fail_code,
            "fail_time_us": int(rp.state.now_us),
            "prov_word": word,
            "implicated_kinds": list(att.kinds),
            "implicated_faults": [
                {
                    "index": f.index,
                    "kind": f.kind_name,
                    "t_apply_us": f.t_apply_us,
                    "t_undo_us": f.t_undo_us,
                    "target": f.target,
                }
                for f in att.faults
            ],
            "cone_events": len(cone),
            "trace_events": len(lineage.trace),
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"attribution JSON -> {args.json}")
    if args.perfetto:
        cone_idx = set(cone)
        cone_steps = {lineage.trace[i].step for i in cone}
        flows = [
            (lineage.trace[i], lineage.trace[j])
            for i, j in lineage.message_flows()
            if j in cone_idx
        ]
        n = write_perfetto(
            args.perfetto, rp.trace,
            machine=args.machine, seed=args.seed,
            num_nodes=eng.machine.NUM_NODES,
            flows=flows, highlight=cone_steps,
        )
        print(
            f"wrote {n} events ({len(flows)} causal flows, cone "
            f"highlighted) to {args.perfetto} (open in "
            f"https://ui.perfetto.dev)"
        )
    return 0


def cmd_audit(args) -> int:
    """Replay every corpus entry and bisect its recorded digest trail to
    the first divergent checkpoint (the corpus-rot diagnosis). With
    --record, re-record trails + environment metadata at HEAD instead —
    refusing entries whose behavioral outcome no longer matches their
    status contract (recording those would bake the rot in)."""
    from .engine import audit, corpus
    from .perf.recorder import maybe_span

    entries = corpus.load(args.corpus)
    if not entries:
        print(f"corpus {args.corpus} is empty")
        return 0
    bad = 0
    changed = False
    for i, e in enumerate(entries):
        try:
            if args.record:
                new, trail = audit.record_entry(
                    e, build_machine, every=args.digest_every
                )
                if e.status == corpus.STATUS_OPEN:
                    contract_ok = trail.failed and trail.fail_code == e.fail_code
                else:  # STATUS_FIXED must pass
                    contract_ok = not trail.failed
                if not contract_ok:
                    got = (
                        f"fails with code {trail.fail_code}"
                        if trail.failed else "passes"
                    )
                    print(f"[FAIL] {e.machine} seed {e.seed}: replay {got}, "
                          f"which breaks its {e.status!r} contract — NOT "
                          f"recording (fix or re-hunt the entry first)")
                    bad += 1
                    continue
                entries[i] = new
                changed = True
                print(f"[rec ] {e.machine} seed {e.seed} code {e.fail_code}: "
                      f"{len(new.digests)} checkpoints every {new.digest_every} "
                      f"steps, final step {new.digest_final[0]}")
                continue
            with maybe_span("audit_entry", seed=int(e.seed)):
                out = audit.audit_entry(e, build_machine)
        except SystemExit:
            print(f"[FAIL] {e.machine} seed {e.seed}: unknown machine in registry")
            bad += 1
            continue
        tag = {"match": "ok  ", "no-digests": "??  ", "diverged": "DIVG"}[out.status]
        print(f"[{tag}] {e.machine} seed {e.seed} code {e.fail_code}: {out.verdict}")
        if not out.ok:
            bad += 1
    if changed:
        corpus.save(args.corpus, entries)
        print(f"corpus updated: {args.corpus}")
    print(f"{len(entries) - bad}/{len(entries)} entries satisfied")
    return 1 if bad else 0


def cmd_shrink(args) -> int:
    from .engine import shrink

    eng = _build_engine(args)
    try:
        sr = shrink(eng, args.seed, max_steps=args.max_steps)
    except ValueError as exc:
        print(exc)
        return 2
    print(sr.summary())
    f = sr.shrunk.faults
    print(
        f"minimal repro: python -m madsim_tpu replay --machine {args.machine} "
        f"--seed {args.seed} --nodes {args.nodes} "
        f"--horizon {sr.shrunk.horizon_us / 1e6} --queue {sr.shrunk.queue_capacity} "
        f"--faults {f.n_faults} --fault-tmax {f.t_max_us} "
        f"--loss {sr.shrunk.packet_loss_rate} --max-steps {sr.steps} "
        # kinds from the SHRUNK plan — ablation may have dropped some
        f"--fault-kinds {fault_kinds_str(f)} "
        + ("--strict-restart " if f.strict_restart else "")
        + config_deployment_flags_str(
            sr.shrunk, getattr(args, "log_capacity", None) or 0)
        + f"--rng-stream {sr.shrunk.rng_stream}"
    )
    return 0


def cmd_check(args) -> int:
    import jax.numpy as jnp

    from .errors import NonDeterminism

    eng = _build_engine(args)
    seeds = jnp.arange(args.seed, args.seed + args.seeds, dtype=jnp.uint32)
    try:
        eng.check_determinism(seeds, max_steps=args.max_steps)
    except NonDeterminism as exc:
        print(f"FAIL: {exc}")
        return 1
    print(f"determinism check passed for {args.seeds} seeds")
    return 0


def cmd_coverage(args) -> int:
    """Render a persisted coverage map (`hunt --coverage-out`): total
    slots hit, per-band (event class / fault kind) marginals, the
    thinnest (band x model-phase) cells — the steer-here signal — and,
    with --diff, what a second run added over the first. Pure host-side
    numpy: works without an accelerator stack. `--json` emits the same
    tables machine-readably — the thinnest-cell list there is the
    EXACT artifact the guided-search bias layer consumes
    (runtime/coverage.top_uncovered), so operators and the bias state
    read one truth."""
    from .runtime.coverage import load_coverage_doc, render_report

    try:
        doc = load_coverage_doc(args.doc)
        diff_doc = load_coverage_doc(args.diff) if args.diff else None
    except (OSError, ValueError, KeyError) as exc:
        sys.exit(f"coverage: {exc}")
    if getattr(args, "json", False):
        from .runtime.coverage import (
            coverage_dict, diff_maps, doc_band_bits, doc_maps, top_uncovered,
        )

        L = doc["slots_log2"]
        bb = doc_band_bits(doc)
        other = doc_maps(diff_doc) if diff_doc is not None else {}
        out = {"slots_log2": L, "band_bits": bb, "maps": {}}
        for name, m in doc_maps(doc).items():
            entry = {
                **coverage_dict(m, L, band_bits=bb),
                "thinnest": top_uncovered(m, L, top=args.top, band_bits=bb),
            }
            if name in other:
                dd = diff_maps(other[name], m)
                entry["diff"] = {
                    "new": dd["only_b"], "lost": dd["only_a"],
                    "shared": dd["both"],
                }
            out["maps"][name] = entry
        print(json.dumps(out, indent=1, sort_keys=True))
        return 0
    print(render_report(doc, top=args.top, diff_doc=diff_doc))
    return 0


def _serve_stats(args) -> int:
    """`serve --service stats`: a tiny HTTP endpoint over the
    StatsEmitter's files — GET /stats returns the latest run snapshot
    (BASE.json), GET /metrics the Prometheus textfile (BASE.prom) — so
    dashboards poll an endpoint instead of parsing logs. Plain stdlib
    http.server; read-only; no sim/jax imports."""
    import http.server

    base = args.stats or os.environ.get("MADSIM_TPU_STATS") or "madsim_stats"
    routes = {
        "/stats": (base + ".json", "application/json"),
        "/metrics": (base + ".prom", "text/plain; version=0.0.4"),
    }

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (stdlib API name)
            path = self.path.split("?", 1)[0].rstrip("/") or "/stats"
            if path == "/healthz":
                body, ctype = b"ok\n", "text/plain"
            elif path in routes:
                fname, ctype = routes[path]
                try:
                    with open(fname, "rb") as f:
                        body = f.read()
                except OSError:
                    self.send_error(
                        404, f"no stats recorded yet ({fname} missing)"
                    )
                    return
            else:
                self.send_error(404, "routes: /stats /metrics /healthz")
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *a):  # route access logs to logging
            logging.getLogger("madsim_tpu.serve").debug(fmt, *a)

    # shared daemon glue (fleet/httpd.py): --port-file writes the
    # realized port atomically so tests/workers discover a host:0 bind
    # without racing, and SIGTERM now closes the server as gracefully
    # as Ctrl-C always did
    from .fleet import httpd

    srv, host, port = httpd.bind(args.addr, Handler)
    print(
        f"stats serving on {host}:{port} "
        f"(GET /stats /metrics /healthz; files {base}.json/.prom)",
        flush=True,
    )
    return httpd.run_http_server(
        srv, port_file=getattr(args, "port_file", None)
    )


def cmd_lint(args) -> int:
    """Static determinism & contract analysis (madsim_tpu/analysis/).
    Runs jax-free except the C-rule import half (--no-import-check
    disables it)."""
    from .analysis.cli import main as lint_main

    return lint_main(args)


def cmd_serve(args) -> int:
    """Run an L5 service server over real TCP (production mode) — the
    counterpart of the reference's real etcd/kafka/S3 endpoints. Apps
    written against `services.*` clients connect unmodified.

    SECURITY: the wire format is pickle (like the reference real-mode
    Endpoint uses bincode, but pickle can execute code on load) — bind
    only on trusted networks / localhost."""
    if args.service == "stats":
        # observability endpoint over StatsEmitter files: no sim
        # networking involved, so no real-mode requirement
        return _serve_stats(args)
    from . import dual

    if dual.MODE != "real":
        sys.exit(
            "serve needs production networking: re-run as\n"
            f"  MADSIM_TPU_MODE=real python -m madsim_tpu serve "
            f"--service {args.service} --addr {args.addr}"
        )
    import asyncio

    async def run_server() -> None:
        if getattr(args, "grpc", False):
            if args.service != "etcd":
                sys.exit("--grpc is only available for --service etcd")
            from .services.etcd.real_gateway import EtcdGrpcGateway

            gw = EtcdGrpcGateway()
            port = await gw.start(args.addr)
            host = args.addr.rsplit(":", 1)[0]
            print(f"etcd serving on {host}:{port} (genuine gRPC wire)", flush=True)
            await gw.wait()
            return
        if getattr(args, "http", False):
            if args.service != "s3":
                sys.exit("--http is only available for --service s3")
            from .services.s3.real_gateway import S3HttpGateway

            gw = S3HttpGateway()
            port = await gw.start(args.addr)
            host = args.addr.rsplit(":", 1)[0]
            print(f"s3 serving on {host}:{port} (genuine S3 REST wire)", flush=True)
            await gw.wait()
            return
        if getattr(args, "wire", False):
            if args.service != "kafka":
                sys.exit("--wire is only available for --service kafka")
            from .services.kafka.wire_gateway import KafkaWireGateway

            host = args.addr.rsplit(":", 1)[0]
            # Metadata/FindCoordinator responses must name an address
            # clients can CONNECT to — a 0.0.0.0 bind is not one (real
            # brokers split listeners from advertised.listeners too)
            advertise = getattr(args, "advertise", None) or (
                host if host and host != "0.0.0.0" else "127.0.0.1"
            )
            gw = KafkaWireGateway(advertised_host=advertise)
            port = await gw.start(args.addr)
            gw.advertised_port = port
            print(
                f"kafka serving on {host or '127.0.0.1'}:{port} "
                f"(genuine Kafka wire, advertising {advertise}:{port})",
                flush=True,
            )
            await gw.wait()
            return
        if args.service == "etcd":
            from .services.etcd import SimServer

            server = SimServer()
        elif args.service == "kafka":
            from .services.kafka import SimBroker

            server = SimBroker()
        elif args.service == "s3":
            from .services.s3 import SimServer as S3Server

            server = S3Server()
        else:
            sys.exit(f"unknown service {args.service!r}")

        def on_bound(ep) -> None:
            # the ready line prints the ACTUAL bound address (supports
            # --addr host:0) and only after the socket exists
            host, port = ep.local_addr
            print(f"{args.service} serving on {host}:{port} (real TCP)", flush=True)

        await server.serve(args.addr, on_bound=on_bound)

    try:
        asyncio.run(run_server())
    except KeyboardInterrupt:
        pass
    return 0


def _format_fleet_event(ev: dict, t0: float) -> str:
    """One `fleet watch` line per event: relative seconds (wall deltas
    between recorded timestamps — no clock is read here), the event
    type, and the payload fields that aren't already in the prefix."""
    ts = float(ev.get("ts") or t0)
    skip = {"seq", "ts", "type", "job"}
    detail = " ".join(
        f"{k}={ev[k]}" for k in sorted(ev) if k not in skip
        and ev[k] is not None
    )
    return f"+{ts - t0:9.2f}s  {ev.get('type', '?'):<16} {detail}".rstrip()


def _fleet_watch(client, addr: str, args) -> int:
    """`fleet watch JOB`: tail the job's SSE event stream and print one
    line per event, exiting 0 once the stream's `end` frame reports a
    terminal state. Push, not poll — the server parks between events."""
    t0 = None
    for frame in client.iter_events(addr, args.job, since=args.since):
        data = frame.get("data")
        if frame.get("event") == "end":
            state = (data or {}).get("state") if isinstance(data, dict) else "?"
            print(f"-- job {args.job} reached terminal state "
                  f"{state!r} --")
            return 0
        if not isinstance(data, dict):
            continue
        if t0 is None:
            t0 = float(data.get("ts") or 0.0)
        print(_format_fleet_event(data, t0), flush=True)
    # stream generator returned without an end frame (server gone mid-
    # tail after retries) — surface it
    print(f"fleet watch: stream for {args.job} closed before a "
          f"terminal state", file=sys.stderr)
    return 1


def _fleet_timeline(client, addr: str, args, retries: int) -> int:
    """`fleet timeline JOB`: fetch the merged control-plane + worker
    Perfetto timeline and write it next to the invoker."""
    doc = client.timeline(addr, args.job, retries=retries)
    out_path = args.out or f"{args.job}.timeline.perfetto.json"
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    summary = doc.get("madsim_fleet_timeline_summary", {})
    n_ev = len(doc.get("traceEvents", []))
    frac = float(summary.get("attribution") or 0.0)
    print(f"timeline: {n_ev} trace events "
          f"({summary.get('events', 0)} lifecycle events, "
          f"{summary.get('worker_spans', 0)} worker spans), "
          f"{frac * 100.0:.0f}% of job wall clock attributed "
          f"-> {out_path} (open in https://ui.perfetto.dev)")
    return 0


def _fleet_profile(client, addr: str, args, retries: int) -> int:
    """`fleet profile JOB`: fetch the three-clock merge — the
    timeline's host plane joined with the worker's device-profile
    capture and failing-lane virtual trace (whichever the store has;
    the worker records them when run under MADSIM_TPU_XPROF=1)."""
    doc = client.profile(addr, args.job, retries=retries)
    out_path = args.out or f"{args.job}.profile.perfetto.json"
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    summary = doc.get("madsim_xprof_summary", {})
    tracks = summary.get("tracks", {})
    present = ", ".join(k for k in ("host", "device", "virtual")
                        if tracks.get(k)) or "none"
    print(f"profile: {len(doc.get('traceEvents', []))} trace events, "
          f"tracks present: {present}, "
          f"{summary.get('sync_points', 0)} sync points, "
          f"{float(summary.get('attribution') or 0.0) * 100.0:.0f}% "
          f"attributed -> {out_path} (open in https://ui.perfetto.dev)")
    if not (tracks.get("device") or tracks.get("virtual")):
        print("hint: run the worker with MADSIM_TPU_XPROF=1 to record "
              "the device profile and the failing lane's virtual trace")
    return 0


def _fleet_top_render(doc: dict) -> str:
    """One screenful of farm state from a /queue document. Pure
    formatting — jax-free, storeless, testable."""
    counts = doc.get("counts", {})
    head = "fleet top — " + " ".join(
        f"{k}:{counts[k]}" for k in sorted(counts) if counts[k]
    ) if counts else "fleet top — queue empty"
    if doc.get("degraded"):
        head += "  [DEGRADED: index-served while load-shedding]"
    cols = (f"{'JOB':<14} {'STATE':<11} {'MACHINE':<18} {'BATCH':>7} "
            f"{'FAIL':>4} {'SLOTS':>6} {'RUNG':>4} {'MOM':>3} "
            f"{'WORKER':<10} LAST EVENT")
    jobs = doc.get("jobs", [])
    lines = [head]
    farm = doc.get("farm")
    if farm:
        # the contention plane: shed state, index honesty, and each
        # worker's lost claim races / refused zombie writes
        bits = [f"shed:{'YES' if farm.get('shed') else 'no'}"]
        if farm.get("queue_log_lag") is not None:
            bits.append(f"lag:{farm['queue_log_lag']}")
        for wid, ws in sorted((farm.get("workers") or {}).items()):
            bits.append(
                f"{wid}[units:{ws.get('units_done', 0)} "
                f"conflicts:{ws.get('claim_conflicts', 0)} "
                f"fenced:{ws.get('fenced_writes', 0)}]"
            )
        lines.append("farm — " + " ".join(bits))
    lines += [cols] if jobs else []
    for s in jobs:
        mom = s.get("momentum") or {}
        last = s.get("last_event") or {}
        planned = s.get("batches_planned")
        batch = (f"{s.get('batches_run', 0)}/{planned}" if planned
                 else str(s.get("batches_run", 0)))
        lines.append(
            f"{s.get('id', '?'):<14} {s.get('state', '?'):<11} "
            f"{str(s.get('machine', '?'))[:18]:<18} "
            f"{batch:>7} "
            f"{s.get('failing') or 0:>4} "
            f"{s.get('coverage_slots') or 0:>6} "
            f"{s.get('escalation') or 0:>4} "
            f"{'*' if mom.get('active') else '.':>3} "
            f"{str(s.get('worker') or '-')[:10]:<10} "
            f"{last.get('type', '-')}"
        )
    return "\n".join(lines)


def _fleet_top(client, addr: str, args, retries: int) -> int:
    """`fleet top`: a one-screen live farm view rendered purely from
    /queue (momentum and last-event are attached server-side, so this
    verb needs no store access and stays jax-free). `--once` prints a
    single frame for scripts/CI; otherwise redraws every --interval."""
    import time as wall

    while True:
        print(_fleet_top_render(client.queue(addr, retries=retries)),
              flush=True)
        if args.once:
            return 0
        wall.sleep(max(0.2, args.interval))
        print()


def cmd_fleet(args) -> int:
    """The hunt-farm service (madsim_tpu/fleet): a durable job store +
    queue, a lease-based worker that slices jobs into checkpointed
    batch units, and a jax-free HTTP control plane + client verbs.
    Only `fleet worker` touches jax; serve/submit/status/result/cancel/
    queue/watch/timeline/profile/top run on boxes with no accelerator
    stack."""
    sub = args.fleet_cmd
    if sub == "serve":
        from .fleet import api

        return api.serve(args.root, args.addr, port_file=args.port_file,
                         sweep_interval_s=args.sweep_interval)
    if sub == "worker":
        from .fleet.worker import FleetWorker

        driver = None
        if args.driver == "synthetic":
            from .fleet.chaos import synthetic_driver as driver
        worker = FleetWorker(
            args.root,
            worker_id=args.worker_id or f"w{os.getpid()}",
            lease_ttl_s=args.lease_ttl,
            poll_s=args.poll,
            max_attempts=args.max_attempts,
            backoff_base_s=args.backoff_base,
            driver=driver,
            reclaim=not args.no_reclaim,
        )
        return worker.run(drain=args.drain, max_units=args.max_units)
    if sub == "fsck":
        from .fleet import fsck as fsck_mod

        rep = fsck_mod.fsck(
            args.root,
            fix=not args.dry_run,
            reclaim=args.reclaim,
            release_quarantined=args.release_quarantined,
        )
        if args.json:
            print(json.dumps(rep, indent=1, sort_keys=True))
        else:
            print(fsck_mod.render(rep))
        # lint-style exit: 0 clean, 1 when corruption was found (even
        # if a fixing run just quarantined it — the operator should
        # look at the .corrupt files)
        return 1 if rep["corrupt"] else 0
    if sub == "chaos":
        from .fleet import chaos as chaos_mod

        failures = []
        for chaos_seed in range(args.seed, args.seed + max(1, args.sweep)):
            res = chaos_mod.run_chaos(
                chaos_seed,
                profile=args.profile,
                out_dir=args.out,
                real=args.real,
                rounds=args.rounds or None,
                jobs=args.jobs or None,
                keep=args.keep,
                workers=getattr(args, "workers", 1),
            )
            if not res["ok"]:
                failures.append(res)
        if failures:
            for res in failures:
                print(f"chaos seed {res['seed']}: "
                      f"{len(res['violations'])} violation(s)")
            return 1
        n = max(1, args.sweep)
        print(f"fleet chaos: {n} seed(s) ok "
              f"(profile {args.profile}, first seed {args.seed})")
        return 0
    from .fleet import client

    try:
        addr = client.resolve_addr(args.addr, getattr(args, "port_file", None))
        retries = 0 if getattr(args, "no_retry", False) else client.DEFAULT_RETRIES
        if sub == "submit":
            from .fleet.store import SPEC_FIELDS

            spec = {k: getattr(args, k) for k in SPEC_FIELDS}
            out = client.submit(
                addr, spec, priority=args.priority,
                deadline_s=args.deadline,
                tenant=getattr(args, "tenant", None), retries=retries,
            )
            # stdout is exactly the job id — script-composable
            # (`JOB=$(python -m madsim_tpu fleet submit ...)`)
            print(out["id"])
            return 0
        if sub == "status":
            print(json.dumps(
                client.status(addr, args.job, feed=args.feed,
                              wait=getattr(args, "wait", 0) or 0,
                              retries=retries),
                indent=1, sort_keys=True))
            return 0
        if sub == "result":
            doc = client.result(addr, args.job, retries=retries)
            print(json.dumps(doc, indent=1, sort_keys=True))
            return 0 if doc.get("state") != "failed" else 1
        if sub == "cancel":
            print(json.dumps(client.cancel(addr, args.job, retries=retries),
                             indent=1, sort_keys=True))
            return 0
        if sub == "queue":
            print(json.dumps(client.queue(addr, retries=retries),
                             indent=1, sort_keys=True))
            return 0
        if sub == "watch":
            return _fleet_watch(client, addr, args)
        if sub == "timeline":
            return _fleet_timeline(client, addr, args, retries)
        if sub == "profile":
            return _fleet_profile(client, addr, args, retries)
        if sub == "top":
            return _fleet_top(client, addr, args, retries)
        raise AssertionError(f"unhandled fleet verb {sub!r}")
    except (client.FleetClientError, RuntimeError, OSError) as exc:
        print(f"fleet {sub}: {exc}", file=sys.stderr)
        return 1


def cmd_perf(args) -> int:
    """Host wall-clock observatory: run a streaming workload with the
    PerfRecorder active (main() wires `args.perf_timeline = args.out`
    before the command runs) and report what the wall clock went to —
    compile vs blocked-on-device (counters_poll/ring_drain) vs the
    host-side Python between dispatches. The Perfetto timeline +
    verdict print via the shared --perf-timeline epilogue."""
    eng = _build_engine(args)
    agg = _stream_batches(eng, args, purpose="perf")
    st = agg["stats"]
    el = agg["elapsed_s"]
    print(
        f"streamed {agg['completed']} seeds in {el:.1f}s "
        f"({agg['completed'] / max(el, 1e-9):.0f} seeds/s), "
        f"{len(agg['failing'])} failing"
    )
    print(
        f"executor: {st['device_segments']} segments, "
        f"{st['host_syncs']} host syncs, {st['drains']} drains "
        f"(pipelined={st['pipelined']}, donation={st['donation']})"
    )
    if "device_memory" in st:
        mem = st["device_memory"]
        print(
            "device memory: "
            + ", ".join(f"{k}={v}" for k, v in sorted(mem.items()))
        )
    return 0


def _cmd_prof_compile(args) -> int:
    """`prof compile`: the compile autopsy — trace_s / lower_s /
    backend_s per streaming fn at this shape, plus cost_analysis
    flops/bytes and memory_analysis peak bytes, keyed by this shape's
    `cache_subkey`. One JSON line + a table."""
    from .compile_cache import cache_subkey
    from .utils import device_info

    eng = _build_engine(args)
    sk = _stream_kwargs(args)
    rows = eng.stream_compile_autopsy(
        batch=args.batch,
        segment_steps=384,
        max_steps=args.max_steps,
        mesh=sk.get("mesh"),
    )
    subkey = cache_subkey(
        gates={
            "rng_stream": eng.config.rng_stream,
            "flight_recorder": eng.config.flight_recorder,
            "coverage": eng.config.coverage,
            "provenance": eng.config.provenance,
        },
        lanes=args.batch,
        segment_steps=384,
        devices=sk["mesh"].size if sk.get("mesh") else 1,
    )
    print(json.dumps({
        "metric": "prof_compile_autopsy",
        "machine": args.machine,
        **device_info(),
        "cache_subkey": subkey,
        "lanes": args.batch,
        "fns": rows,
    }))
    hdr = f"{'fn':<14}{'trace_s':>9}{'lower_s':>9}{'backend_s':>11}{'flops':>14}{'bytes':>14}{'peak_bytes':>12}"
    print(hdr)
    for r in rows:
        print(
            f"{r['label']:<14}{r['trace_s']:>9.3f}{r['lower_s']:>9.3f}"
            f"{r['backend_s']:>11.3f}"
            f"{(r['flops'] if r['flops'] is not None else float('nan')):>14.3g}"
            f"{(r['bytes_accessed'] if r['bytes_accessed'] is not None else float('nan')):>14.3g}"
            f"{(r['peak_bytes'] if r['peak_bytes'] is not None else 0):>12}"
        )
    tot = {k: sum(r[k] for r in rows) for k in ("trace_s", "lower_s", "backend_s")}
    bound = max(tot, key=lambda k: tot[k])
    print(
        f"total: trace {tot['trace_s']:.3f}s, lower {tot['lower_s']:.3f}s, "
        f"backend {tot['backend_s']:.3f}s -> {bound.split('_')[0]}-dominated "
        f"(subkey {subkey})"
    )
    return 0


def cmd_prof(args) -> int:
    """The three-clock profiler (madsim_tpu/perf/xprof.py): stream a
    hunt batch under a jax.profiler device capture — the program's
    `madsim.*` phase scopes name the device ops, an annotating recorder
    writes the host spans into the same capture, clock-sync markers sit
    at dispatch/poll boundaries — and, with --merge, align host wall-clock spans,
    the device profile and the failing lane's virtual-time trace into
    ONE Perfetto session. `prof compile` prints the per-stage compile
    autopsy instead."""
    import tempfile

    from .perf import xprof
    from .perf.recorder import PerfRecorder

    if getattr(args, "action", None) == "compile":
        return _cmd_prof_compile(args)

    # the gate means "capture a device profile"; the phase scopes are
    # in every program anyway, so nothing is re-traced for it
    os.environ[xprof.ENV_GATE] = "1"
    eng = _build_engine(args)
    sk = _stream_kwargs(args)
    logdir = args.profile_dir or tempfile.mkdtemp(prefix="madsim-xprof-")
    # annotate: every host span is also a `madsim.<name>` slice in the
    # capture, on the device ops' clock
    rec = PerfRecorder(annotate=True, meta={
        "cmd": "prof", "machine": args.machine, "seeds": args.seeds,
        "batch": args.batch,
    })
    # recorder INSIDE the capture: the profiler's stop/export cost (a
    # multi-MB artifact parse+write) stays off the hunt's wall clock,
    # so the attribution fraction measures the hunt, not the profiler
    with xprof.device_trace(logdir):
        with rec:
            out = eng.run_stream(
                args.seeds, batch=args.batch, seed_start=args.seed,
                max_steps=args.max_steps, **sk,
            )
    wall_s = rec.wall_us / 1e6
    print(
        f"streamed {out['completed']} seeds in {wall_s:.1f}s "
        f"({out['completed'] / max(wall_s, 1e-9):.0f} seeds/s), "
        f"{len(out['failing'])} failing"
    )
    artifact = xprof.find_device_trace(logdir)
    dev = xprof.load_device_events(artifact) if artifact else []
    if dev:
        print(f"device profile: {len(dev)} events ({artifact})")
    else:
        print("device profile: no artifact (backend without profiler export)")

    if not args.merge:
        n = rec.write(args.out)
        print(
            f"host timeline: {n} spans -> {args.out} "
            f"(pass --merge for the three-clock plane)"
        )
        print(f"host verdict: {rec.verdict()}")
        return 0

    # virtual-time track: the failing lane when the hunt surfaced one,
    # else the batch's first seed — timestamps stay in VIRTUAL µs
    vseed = args.trace_seed
    if vseed is None:
        vseed = out["failing"][0][0] if out["failing"] else args.seed
    from .engine import replay
    from .engine.trace_export import trace_event_dict

    rp = replay(eng, int(vseed), max_steps=args.max_steps)
    vdoc = trace_event_dict(
        rp.trace, machine=args.machine, seed=int(vseed),
        num_nodes=eng.machine.NUM_NODES,
    )
    doc = xprof.merge_plane(
        rec.chrome_trace(), dev, vdoc,
        meta={"machine": args.machine, "virtual_seed": int(vseed)},
    )
    n = xprof.write_doc(doc, args.out)
    s = doc["madsim_xprof_summary"]
    print(json.dumps({"metric": "prof_merge", **s}))
    tracks = "+".join(k for k, v in s["tracks"].items() if v)
    print(
        f"merged plane: {n} events ({tracks}), "
        f"{100 * s['attribution']:.0f}% of {s['host_wall_us'] / 1e6:.1f}s "
        f"wall attributed across {s['sync_points']} sync points "
        f"-> {args.out} (open in https://ui.perfetto.dev)"
    )
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="madsim_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    def obs_flags(p):
        """Observability flags (every subcommand): logging + recorder."""
        p.add_argument(
            "--log-level", default=os.environ.get("MADSIM_TPU_LOG"),
            help="wire init_tracing at this level (DEBUG/INFO/...; also "
            "$MADSIM_TPU_LOG) — log lines carry the sim span context",
        )
        p.add_argument(
            "--log-jsonl", default=None, metavar="PATH",
            help="also sink logs as structured JSONL to PATH",
        )

    def common(p):
        obs_flags(p)
        p.add_argument("--machine", default="raft")
        p.add_argument("--nodes", type=int, default=0)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--horizon", type=float, default=5.0, help="virtual seconds")
        p.add_argument("--queue", type=int, default=96)
        p.add_argument("--faults", type=int, default=2)
        p.add_argument("--loss", type=float, default=0.0)
        p.add_argument("--max-steps", type=int, default=3000)
        p.add_argument(
            "--fault-tmax", type=int, default=0,
            help="fault injection window in us (0 = 60%% of horizon)",
        )
        p.add_argument(
            "--fault-kinds", default="pair,kill",
            help="comma list of fault kinds to draw from: "
            "pair,kill,dir,group,storm,delay,pause,skew,dup,torn,"
            "heal-asym (default pair,kill; any other kind switches to "
            "the v2 schedule derivation; dup is per-delivery Bernoulli "
            "duplication, not a scheduled window; torn restarts damage "
            "durable state per Machine.torn_spec(); heal-asym "
            "partitions heal one direction at a time)",
        )
        p.add_argument(
            "--strict-restart", action="store_true",
            help="crash-with-amnesia restarts: a restarted node keeps "
            "ONLY the leaves its Machine.durable_spec() contract marks "
            "durable — the engine wipes the rest generically, so "
            "illegally-kept volatile state becomes findable",
        )
        p.add_argument(
            "--churn", default=None, metavar="NAME",
            help="a fault PROCESS beside the scheduled faults: ticks that "
            "draw their faults as they fire, one queue slot however many. "
            "fig8 = 6.824 TestFigure8Unreliable2C's loop: every U[0,13) ms "
            "(10%% of ticks U[0,500) ms) disconnect the leader w.p. 1/2, "
            "reconnect a random node while under a majority is connected. "
            "kv3a = 6.824 lab 3A's partitioner and crash "
            "(TestPersistPartitionUnreliable3A): every 1 s + U[0,200) ms "
            "re-draw a random two-way split of the nodes the machine names "
            "(kvraft: its servers); at --churn-until heal, kill them all at "
            "once and restart them 150 ms later. Needs --churn-until",
        )
        p.add_argument(
            "--churn-until", type=float, default=None, metavar="S",
            help="virtual second at which --churn reconnects every node "
            "and stops (kv3a: and kills and restarts the named nodes)",
        )
        p.add_argument(
            "--log-capacity", type=int, default=None, metavar="N",
            help="log entries a node of a raft machine can hold (default "
            "8: a lane ends when every node has committed a full log), or "
            "records a partition log of a kafka machine, or entries a "
            "server's log of a kvraft machine, can hold (default 64: a full "
            "log refuses appends and counts log_full)",
        )
        p.add_argument(
            "--latency", default=None, metavar="MIN_US,MAX_US",
            help="uniform send latency in virtual us (default 1000,10000)",
        )
        p.add_argument(
            "--rng-stream", type=int, default=2, choices=(2, 3),
            help="per-step RNG stream version: 2 = legacy split-chain "
            "(default; replays every recorded seed), 3 = counter-based "
            "(one threefry per event — faster; new hunts should use it; "
            "corpus entries record the version either way)",
        )
        p.add_argument(
            "--compile-cache", default=os.environ.get("MADSIM_TPU_COMPILE_CACHE"),
            help="JAX persistent compilation cache directory (also "
            "$MADSIM_TPU_COMPILE_CACHE; default <checkout>/"
            ".madsim-jit-cache): pay each compile once per machine, "
            "not once per process. $JAX_COMPILATION_CACHE_DIR, where "
            "set, wins over all of these",
        )
        p.add_argument(
            "--flight-recorder", action="store_true",
            help="engine flight recorder: rolling per-lane trace digests "
            "+ checkpoint ring + on-device fault/queue metrics (results "
            "are bit-identical either way; see `audit`)",
        )
        p.add_argument(
            "--coverage", action="store_true",
            help="scenario-coverage telemetry: per-lane AFL-style hit "
            "maps over (model abstract state, event kind, fault "
            "context), OR-reduced on device at stream harvest (results "
            "are bit-identical either way; enables --stop-on-plateau "
            "and `coverage` reports)",
        )
        p.add_argument(
            "--cov-buffer", type=int, default=None, metavar="N",
            help="coverage slot-buffer depth per lane (default: engine "
            "default; 0 = unbuffered escape hatch, the per-event map "
            "scatter — final maps are bit-identical either way)",
        )
        p.add_argument(
            "--provenance", action="store_true",
            help="causal provenance: every queued event and node "
            "carries a 32-bit lineage word (one bit per scheduled "
            "fault, ORed along deliveries); failures decode to the "
            "implicated faults in hunt reports, shrink uses attribution "
            "to order its candidates, and `why` renders the causal "
            "chain (results are bit-identical either way)",
        )
        p.add_argument(
            "--stats", default=None, metavar="BASE",
            help="StatsEmitter base path (also $MADSIM_TPU_STATS): "
            "stream per-batch stats to BASE.jsonl + Prometheus textfile "
            "BASE.prom + latest-snapshot BASE.json (what `serve "
            "--service stats` exposes)",
        )

    def stream_flags(p):
        """Streaming-run flags (explore/hunt/perf)."""
        p.add_argument(
            "--devices", type=int, default=0, metavar="N",
            help="span the hunt over the first N devices as one jitted "
            "SPMD program (a 1-D 'batch' mesh; lane leaves sharded, "
            "global leaves replicated). Results are byte-identical at "
            "any N; batch must be a multiple of N. 0 = unsharded "
            "single-device path (the default). On a CPU-only box, "
            "force virtual devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N",
        )
        p.add_argument(
            "--stop-on-plateau", type=int, default=0, metavar="N",
            help="with --coverage: stop the run early when N consecutive "
            "seed batches add zero new coverage slots (the saturation "
            "signal — more seeds are no longer finding new scenarios); "
            "reported honestly in the summary",
        )
        p.add_argument(
            "--stop-after-batches", type=int, default=0, metavar="N",
            help="deliberately stop after N seed batches (the run stays "
            "resumable via --checkpoint; CI's interrupt/resume smoke and "
            "'hunt in slices' both use this)",
        )
        p.add_argument(
            "--perf-timeline", default=None, metavar="PATH",
            help="record the HOST wall-clock timeline of this run "
            "(compile/dispatch/counters_poll/ring_drain/checkpoint/"
            "stats spans + dispatch-gap idle accounting) as Chrome/"
            "Perfetto trace_event JSON at PATH, with a bound verdict "
            "(compile- vs device- vs dispatch-gap-bound) printed after "
            "the run — the real-time complement of `trace`'s "
            "virtual-time view",
        )
        p.add_argument(
            "--xla-profile", default=None, metavar="DIR",
            help="additionally wrap the run in jax.profiler.trace(DIR) "
            "— a device/XLA-level profile for tensorboard/xprof "
            "(heavier than --perf-timeline; opt-in)",
        )

    p = sub.add_parser("explore", help="run a seed batch, report failing seeds")
    common(p)
    p.add_argument("--seeds", type=int, default=1024)
    p.add_argument(
        "--stream", action="store_true",
        help="seed-streaming path (refill finished lanes; for large batches)",
    )
    p.add_argument("--batch", type=int, default=8192, help="lanes per streaming batch")
    stream_flags(p)
    p.add_argument(
        "--multihost", action="store_true",
        help="shard the batch over a jax.distributed job "
             "(MADSIM_TPU_COORDINATOR/NUM_PROCS/PROC_ID env vars)",
    )
    p.set_defaults(fn=cmd_explore)

    p = sub.add_parser("replay", help="bit-identical replay of one seed with trace")
    common(p)
    p.add_argument("--tail", type=int, default=30, help="print last N events (0=all)")
    p.add_argument(
        "--devices", type=int, default=0,
        help="accepted for repro-line fidelity (hunts record the mesh "
        "size they ran at); replay is single-lane and byte-identical "
        "at any device count, so the value is recorded but unused",
    )
    p.add_argument(
        "--diff-seed", type=int, default=None,
        help="also replay this seed and print where the two event "
        "schedules first diverge (debugging: failing seed vs its "
        "nearest passing neighbor)",
    )
    p.add_argument("--diff-context", type=int, default=3,
                   help="events of context around the divergence")
    p.set_defaults(fn=cmd_replay)

    p = sub.add_parser(
        "trace",
        help="replay one seed and export its virtual-time event timeline "
        "(Perfetto trace_event JSON / structured JSONL)",
    )
    common(p)
    p.add_argument(
        "--perfetto", default=None, metavar="PATH",
        help="write Chrome/Perfetto trace_event JSON (one thread row per "
        "node, instants at virtual microseconds; open in ui.perfetto.dev)",
    )
    p.add_argument(
        "--jsonl", default=None, metavar="PATH",
        help="write one JSON object per event (grep/jq-able)",
    )
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("shrink", help="minimize a failing seed's config")
    common(p)
    p.set_defaults(fn=cmd_shrink)

    p = sub.add_parser(
        "why",
        help="explain a failing seed: replay with causal provenance, "
        "name the implicated faults (kind, time, target), and render "
        "the violation's past cone as text / JSON / Perfetto flows",
    )
    common(p)
    p.add_argument(
        "seed_pos", nargs="?", type=int, default=None, metavar="SEED",
        help="the failing seed (equivalent to --seed; pass the repro "
        "line's remaining flags so the schedule matches)",
    )
    p.add_argument(
        "--tail", type=int, default=30,
        help="cone events to print (0 = the whole cone)",
    )
    p.add_argument(
        "--out", default=None, metavar="PATH",
        help="also write the rendered causal chain to PATH",
    )
    p.add_argument(
        "--json", default=None, metavar="PATH",
        help="write machine-readable attribution JSON "
        "(implicated kinds/faults, prov word, cone size)",
    )
    p.add_argument(
        "--perfetto", default=None, metavar="PATH",
        help="write the timeline with causal flow arrows and the past "
        "cone highlighted (args.cone=true; open in ui.perfetto.dev)",
    )
    p.set_defaults(fn=cmd_why)

    p = sub.add_parser(
        "hunt", help="explore + shrink + record failing seeds in the corpus"
    )
    common(p)
    p.add_argument("--seeds", type=int, default=1024)
    p.add_argument("--stream", action="store_true", help="seed-streaming hunt")
    p.add_argument("--batch", type=int, default=8192, help="lanes per streaming batch")
    stream_flags(p)
    p.add_argument("--corpus", default="corpus.json")
    p.add_argument("--limit", type=int, default=5, help="max seeds to shrink+record")
    p.add_argument(
        "--checkpoint", default=None, metavar="PATH",
        help="with --stream: persist per-batch progress (seed cursor, "
        "failures, coverage map, plateau state) to PATH after every "
        "batch; an interrupted hunt re-run with the same arguments "
        "resumes exactly where it stopped ('resumed at batch k/n')",
    )
    p.add_argument(
        "--coverage-out", default=None, metavar="PATH",
        help="with --coverage --stream: persist the hunt's cumulative "
        "coverage map as JSON for cross-run diffing "
        "(`madsim_tpu coverage PATH --diff OLD`)",
    )
    p.add_argument(
        "--all-seeds",
        action="store_true",
        help="shrink the first --limit failing seeds even when they share "
        "a fail code (default: one representative per distinct code)",
    )
    p.add_argument(
        "--guided", action="store_true",
        help="coverage-feedback search (needs --stream --coverage): "
        "every batch's seed vector is chosen — half mutated children "
        "of seeds that hit new coverage slots (candidates scored by a "
        "bias state fed from the live map's thin bands and, with "
        "--provenance, the fault kinds in failure lineages), half "
        "fresh sequential exploration; with --stop-on-plateau N a "
        "plateau escalates the fault vocabulary along the recorded "
        "ladder instead of stopping. The (seed schedule, bias state) "
        "trail is recorded in the checkpoint and stats feed, so a "
        "guided hunt resumes and replays byte-identically; guidance "
        "off is bit-identical to the unguided streaming path",
    )
    p.set_defaults(fn=cmd_hunt)

    p = sub.add_parser(
        "regress",
        help="re-verify every corpus entry (open must reproduce, fixed must pass)",
    )
    obs_flags(p)
    p.add_argument("--corpus", default="corpus.json")
    p.add_argument(
        "--promote", action="store_true",
        help="flip open entries that no longer fail to fixed",
    )
    p.set_defaults(fn=cmd_regress)

    p = sub.add_parser(
        "audit",
        help="bisect every corpus entry's recorded digest trail to the "
        "first divergent checkpoint (corpus-rot diagnosis); --record "
        "re-records trails + env metadata at HEAD",
    )
    obs_flags(p)
    p.add_argument("--corpus", default="corpus.json")
    p.add_argument(
        "--record", action="store_true",
        help="re-record digest trails (refuses entries whose outcome "
        "broke their status contract)",
    )
    p.add_argument(
        "--digest-every", type=int, default=64,
        help="checkpoint cadence in steps when recording",
    )
    p.set_defaults(fn=cmd_audit)

    p = sub.add_parser("check", help="engine determinism self-check")
    common(p)
    p.add_argument("--seeds", type=int, default=64)
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser(
        "perf",
        help="host wall-clock observatory: stream a workload with the "
        "PerfRecorder on and write the Perfetto host timeline "
        "(compile/dispatch/poll/drain spans + dispatch-gap idle), with "
        "a compile- vs device- vs dispatch-gap-bound verdict",
    )
    common(p)
    p.add_argument("out", help="host-timeline Perfetto JSON output path")
    p.add_argument("--seeds", type=int, default=2048)
    p.add_argument("--batch", type=int, default=512, help="lanes per streaming batch")
    stream_flags(p)
    p.set_defaults(fn=cmd_perf)

    p = sub.add_parser(
        "prof",
        help="the three-clock profiler: stream a hunt batch with "
        "device-phase annotations + a jax.profiler capture on "
        "(MADSIM_TPU_XPROF), and with --merge align host spans, the "
        "device profile and a failing lane's virtual-time trace into "
        "one Perfetto session; `prof compile` prints the per-stage "
        "compile autopsy (trace/lower/backend + flops/bytes)",
    )
    common(p)
    p.add_argument(
        "action", nargs="?", choices=("compile",), default=None,
        help="compile: autopsy the streaming quartet's compile at this "
        "shape instead of running a profiled stream",
    )
    p.add_argument(
        "--out", default="prof.perfetto.json",
        help="output trace path (host timeline, or the merged "
        "three-clock plane with --merge; .gz compresses)",
    )
    p.add_argument(
        "--merge", action="store_true",
        help="write ONE merged Perfetto session: host + device + "
        "virtual tracks, clock-sync aligned",
    )
    p.add_argument("--seeds", type=int, default=2048)
    p.add_argument("--batch", type=int, default=512, help="lanes per streaming batch")
    p.add_argument(
        "--trace-seed", type=int, default=None,
        help="seed for the virtual-time track (default: first failing "
        "seed of the profiled batch, else --seed)",
    )
    p.add_argument(
        "--profile-dir", default=None, metavar="DIR",
        help="keep the raw jax.profiler logdir here (default: a "
        "throwaway tempdir)",
    )
    stream_flags(p)
    p.set_defaults(fn=cmd_prof)

    p = sub.add_parser(
        "coverage",
        help="render a persisted scenario-coverage map (total %%, "
        "per-band marginals, thinnest fault x phase cells, per-model "
        "breakdown); --diff OLD shows what a run added over another",
    )
    p.add_argument("doc", help="coverage JSON written by `hunt --coverage-out`")
    p.add_argument(
        "--diff", default=None, metavar="OLD",
        help="baseline coverage doc to diff against (new/lost/shared slots)",
    )
    p.add_argument("--top", type=int, default=8,
                   help="thinnest band x phase cells to list")
    p.add_argument(
        "--json", action="store_true",
        help="machine-readable output: per-map slots/by-band summary "
        "plus the thinnest-cell table (the same "
        "runtime/coverage.top_uncovered artifact the guided-search "
        "bias layer reads)",
    )
    p.set_defaults(fn=cmd_coverage)

    p = sub.add_parser(
        "serve",
        help="run an L5 service over real TCP (MADSIM_TPU_MODE=real); "
        "pickle wire format — trusted networks only. `--service stats` "
        "serves the last run's StatsEmitter snapshot over HTTP instead "
        "(/stats JSON + /metrics Prometheus; any mode)",
    )
    p.add_argument("--service", default="etcd",
                   choices=["etcd", "kafka", "s3", "stats"])
    p.add_argument("--addr", default="127.0.0.1:23790", help="host:port (port 0 = ephemeral)")
    p.add_argument(
        "--stats", default=None, metavar="BASE",
        help="stats service only: StatsEmitter base path to serve "
        "(default $MADSIM_TPU_STATS or ./madsim_stats)",
    )
    p.add_argument(
        "--port-file", default=None, metavar="PATH",
        help="stats service only: atomically write the realized port to "
        "PATH after binding (with --addr host:0, tests and fleet "
        "workers discover the daemon without racing its stdout)",
    )
    p.add_argument(
        "--grpc",
        action="store_true",
        help="etcd only: serve the genuine etcd v3 gRPC wire protocol "
        "(etcdserverpb over grpc.aio) instead of the pickle sim protocol",
    )
    p.add_argument(
        "--http",
        action="store_true",
        help="s3 only: serve the genuine S3 REST wire protocol "
        "instead of the pickle sim protocol",
    )
    p.add_argument(
        "--wire",
        action="store_true",
        help="kafka only: serve the genuine Kafka wire protocol "
        "(ApiVersions/Metadata/Produce/Fetch/group APIs) instead of the "
        "pickle sim protocol",
    )
    p.add_argument(
        "--advertise",
        default=None,
        help="kafka --wire only: hostname to advertise in Metadata/"
        "FindCoordinator responses (defaults to the bind host, or "
        "127.0.0.1 when binding 0.0.0.0)",
    )
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser(
        "fleet",
        help="the hunt farm: DST as a continuously operating service — "
        "a durable job store + queue (JSON-on-disk, atomic, "
        "fingerprinted), `worker` (leases jobs, runs checkpointed "
        "batch units packed by warm-compile subkey, shrinks + files "
        "finds), `serve` (jax-free HTTP control plane: POST /jobs, "
        "GET /jobs/{id}[/result|/events|/timeline], DELETE /jobs/{id}, "
        "/queue /metrics /healthz) and thin client verbs, including "
        "the observatory (`watch` SSE tail, `timeline` Perfetto "
        "merge, `top` farm view)",
    )
    fl = p.add_subparsers(dest="fleet_cmd", required=True)

    def fleet_root(q):
        q.add_argument(
            "--root", default=os.environ.get("MADSIM_TPU_FLEET_ROOT", "fleet"),
            help="fleet state directory (jobs/, corpus.json; also "
            "$MADSIM_TPU_FLEET_ROOT)",
        )

    def fleet_client_flags(q):
        q.add_argument(
            "--addr", default=None,
            help="control-plane host:port (default $MADSIM_TPU_FLEET_ADDR "
            "or 127.0.0.1:8142)",
        )
        q.add_argument(
            "--port-file", default=None, metavar="PATH",
            help="resolve the daemon as 127.0.0.1:<port read from PATH> "
            "(the file `fleet serve --port-file` writes atomically)",
        )
        q.add_argument(
            "--no-retry", action="store_true",
            help="fail fast instead of retrying transient HTTP errors "
            "(connection refused during a server restart, 502/503/504) "
            "with seeded-jitter backoff",
        )

    q = fl.add_parser("serve", help="jax-free HTTP control plane over a fleet root")
    obs_flags(q)
    fleet_root(q)
    q.add_argument("--addr", default="127.0.0.1:8142",
                   help="bind host:port (port 0 = ephemeral)")
    q.add_argument(
        "--port-file", default=None, metavar="PATH",
        help="atomically write the realized port to PATH after binding",
    )
    q.add_argument(
        "--sweep-interval", type=float, default=5.0,
        help="seconds between lease-reclamation supervisor sweeps "
        "(expired worker leases requeue their jobs with backoff, or "
        "quarantine at the attempt cap; 0 disables)",
    )
    q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser(
        "worker",
        help="lease jobs and run them one checkpointed batch unit at a "
        "time (kill -9 loses at most one batch; jobs sharing a "
        "cache_subkey run back-to-back on the warm jit)",
    )
    obs_flags(q)
    fleet_root(q)
    q.add_argument("--worker-id", default=None,
                   help="stable lease identity (default w<pid>; reusing an "
                   "id reclaims its own leases immediately after a crash)")
    q.add_argument("--lease-ttl", type=float, default=60.0,
                   help="seconds before a dead worker's jobs become "
                   "reclaimable")
    q.add_argument("--poll", type=float, default=0.5,
                   help="idle store-poll interval in seconds")
    q.add_argument("--drain", action="store_true",
                   help="exit once every job is terminal (CI/batch mode) "
                   "instead of serving forever")
    q.add_argument("--max-units", type=int, default=0,
                   help="exit after N work units (deterministic "
                   "interruption for tests; 0 = unlimited)")
    q.add_argument(
        "--compile-cache", default=os.environ.get("MADSIM_TPU_COMPILE_CACHE"),
        help="JAX persistent compilation cache directory (also "
        "$MADSIM_TPU_COMPILE_CACHE) — a warm cache makes a fresh "
        "worker productive in seconds",
    )
    q.add_argument(
        "--perf-timeline", default=None, metavar="PATH",
        help="record the worker's host timeline (per-unit fleet_unit "
        "spans with job ids wrapping the usual compile/dispatch/poll "
        "spans) as Perfetto trace_event JSON",
    )
    q.add_argument(
        "--max-attempts", type=int, default=3,
        help="consecutive deaths/hard failures before a job is "
        "quarantined as poison (exception + batch index + repro "
        "recorded on the job)",
    )
    q.add_argument(
        "--backoff-base", type=float, default=2.0,
        help="requeue backoff base: a job that died attempt k waits "
        "base * 2^(k-1) seconds before it can be leased again",
    )
    q.add_argument(
        "--no-reclaim", action="store_true",
        help="skip the lease-reclamation sweep at each poll (rely on "
        "`fleet serve`'s supervisor thread / `fleet fsck --reclaim`)",
    )
    q.add_argument(
        "--driver", choices=("real", "synthetic"), default="real",
        help="'synthetic' replaces the jitted streaming path with the "
        "jax-free deterministic stand-in (chaos harness / farm tests "
        "only: same checkpoint+stats machinery, no engine)",
    )
    q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser("submit", help="submit a hunt job; prints the job id")
    obs_flags(q)
    fleet_client_flags(q)
    q.add_argument("--machine", required=True)
    q.add_argument("--nodes", type=int, default=0)
    q.add_argument("--seed", type=int, default=0, help="seed-range start")
    q.add_argument("--seeds", type=int, default=1024, help="seed budget")
    q.add_argument("--batch", type=int, default=256,
                   help="lanes per batch unit (the checkpoint granularity)")
    q.add_argument("--horizon", type=float, default=5.0)
    q.add_argument("--max-steps", type=int, default=3000)
    q.add_argument("--queue", type=int, default=96)
    q.add_argument("--faults", type=int, default=2)
    q.add_argument("--loss", type=float, default=0.0)
    q.add_argument("--fault-tmax", type=int, default=0)
    q.add_argument("--fault-kinds", default="pair,kill")
    q.add_argument("--rng-stream", type=int, default=2, choices=(2, 3))
    q.add_argument("--strict-restart", action="store_true")
    q.add_argument("--churn", default="", metavar="NAME")
    q.add_argument("--churn-until", type=float, default=0.0, metavar="S")
    q.add_argument("--log-capacity", type=int, default=0, metavar="N")
    q.add_argument("--latency", default="", metavar="MIN_US,MAX_US")
    q.add_argument("--coverage", action="store_true")
    q.add_argument("--provenance", action="store_true")
    q.add_argument("--flight-recorder", action="store_true")
    q.add_argument("--stop-on-plateau", type=int, default=0)
    q.add_argument(
        "--guided", action="store_true",
        help="coverage-feedback search (needs --coverage): the worker "
        "evolves this job's seed corpus AFL-style, biases fault draws "
        "toward thin coverage cells / implicated kinds, and escalates "
        "the vocabulary on plateau; the (seed schedule, bias state) "
        "trail rides the job checkpoint, so interrupt/resume and "
        "worker replacement reproduce byte-identically",
    )
    q.add_argument("--shrink-limit", type=int, default=5,
                   help="max distinct-code finds to shrink + file")
    q.add_argument(
        "--devices", type=int, default=0, metavar="N",
        help="span each batch unit over the first N devices as one "
        "jitted SPMD program (the lane-axis mesh; 0 = unsharded). "
        "Part of the warm-compile grouping key",
    )
    q.add_argument("--priority", type=int, default=0,
                   help="higher runs earlier (and may pay a compile switch)")
    q.add_argument("--deadline", type=float, default=None,
                   help="relative deadline in wall seconds; the worker "
                   "stops the job when it passes")
    q.add_argument(
        "--tenant", default=None,
        help="admission-accounting identity: the server's per-tenant "
        "token bucket ($MADSIM_TPU_FLEET_RATE_LIMIT) charges this name; "
        "a 429 refusal names it and the client retries after the "
        "server's Retry-After",
    )
    q.set_defaults(fn=cmd_fleet)

    for verb, hlp in (
        ("status", "job document + live per-batch feed"),
        ("result", "find + shrunk repro + why attribution (terminal jobs)"),
        ("cancel", "cancel a job (queued dies now; running at the next "
                   "unit boundary)"),
    ):
        q = fl.add_parser(verb, help=hlp)
        obs_flags(q)
        fleet_client_flags(q)
        q.add_argument("job", help="job id (from `fleet submit`)")
        if verb == "status":
            q.add_argument("--feed", type=int, default=20,
                           help="live-feed rows to include")
            q.add_argument(
                "--wait", type=float, default=0, metavar="S",
                help="long-poll: the server holds the request up to S "
                "seconds (capped server-side) and answers as soon as "
                "the job document or its stats feed changes — clients "
                "stop busy-polling GET /jobs/{id}",
            )
        q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser("queue", help="state counts + per-job summaries")
    obs_flags(q)
    fleet_client_flags(q)
    q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser(
        "watch",
        help="tail a job's lifecycle event stream over SSE (push, not "
        "poll: the server parks between events), one line per event; "
        "exits 0 when the job reaches a terminal state",
    )
    obs_flags(q)
    fleet_client_flags(q)
    q.add_argument("job", help="job id (from `fleet submit`)")
    q.add_argument("--since", type=int, default=0, metavar="SEQ",
                   help="resume the tail after event SEQ (0 = replay "
                   "the full event log first)")
    q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser(
        "timeline",
        help="merge the job's lifecycle events with every worker's "
        "span dump (correlated by job id as trace id) into one "
        "Perfetto timeline: queue-wait, per-batch progress and worker "
        "internals on a shared wall clock",
    )
    obs_flags(q)
    fleet_client_flags(q)
    q.add_argument("job", help="job id (from `fleet submit`)")
    q.add_argument("--out", default=None, metavar="PATH",
                   help="output trace path (default "
                   "<job>.timeline.perfetto.json)")
    q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser(
        "profile",
        help="the three-clock merge for a job: host timeline + the "
        "worker's device-profile capture + the failing lane's "
        "virtual-time trace (recorded when the worker runs under "
        "MADSIM_TPU_XPROF=1), aligned by xprof clock-sync markers "
        "into one Perfetto session",
    )
    obs_flags(q)
    fleet_client_flags(q)
    q.add_argument("job", help="job id (from `fleet submit`)")
    q.add_argument("--out", default=None, metavar="PATH",
                   help="output trace path (default "
                   "<job>.profile.perfetto.json)")
    q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser(
        "top",
        help="one-screen farm view rendered from /queue (state counts, "
        "per-job batch/find/coverage/escalation progress, momentum, "
        "lease holder, last event) — jax-free, needs only the HTTP "
        "control plane",
    )
    obs_flags(q)
    fleet_client_flags(q)
    q.add_argument("--interval", type=float, default=2.0, metavar="S",
                   help="seconds between redraws")
    q.add_argument("--once", action="store_true",
                   help="print a single frame and exit (scripts/CI)")
    q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser(
        "fsck",
        help="scan the job store + fleet corpus for truncated/"
        "unparseable/fingerprint-inconsistent files, quarantine them "
        "to *.corrupt with a per-file verdict, remove stale atomic-"
        "write tmp files, and rebuild the queue counts; exit 0 clean "
        "/ 1 corruption found",
    )
    obs_flags(q)
    fleet_root(q)
    q.add_argument("--dry-run", action="store_true",
                   help="scan + report only; quarantine/remove nothing")
    q.add_argument("--reclaim", action="store_true",
                   help="also run the lease-reclamation sweep (requeue "
                   "jobs whose worker lease expired, or quarantine at "
                   "the attempt cap)")
    q.add_argument("--release-quarantined", action="store_true",
                   help="re-queue quarantined jobs (attempt counter "
                   "reset; the quarantine post-mortem stays on the "
                   "doc)")
    q.add_argument("--json", action="store_true",
                   help="machine-readable report instead of text")
    q.set_defaults(fn=cmd_fleet)

    q = fl.add_parser(
        "chaos",
        help="attack a scratch farm with a seeded schedule of process-"
        "level faults (SIGKILL worker/server at the k-th store write, "
        "torn in-flight writes, lease-clock jumps, client calls "
        "through a bounced server) and assert the recovery "
        "invariants: no accepted job lost, every resumed job's final "
        "report byte-identical to an unperturbed oracle run; a "
        "failing seed reproduces from its printed line forever",
    )
    obs_flags(q)
    q.add_argument("--seed", type=int, default=0,
                   help="chaos schedule seed (the repro key)")
    q.add_argument("--sweep", type=int, default=1,
                   help="run N consecutive seeds starting at --seed")
    q.add_argument("--profile",
                   choices=("kill", "torn", "mixed", "spans", "claims"),
                   default="mixed",
                   help="fault-mix weighting of the schedule ('claims' "
                   "weights the contention plane: claim races, zombie "
                   "resumes, single-victim lease jumps, torn queue.log "
                   "appends)")
    q.add_argument("--workers", type=int, default=1,
                   help="race N workers against the one store every "
                   "worker round (adds the contention invariants: no "
                   "(job, batch, gen) executed twice, no find filed "
                   "twice, reports still byte-identical to the "
                   "1-worker oracle)")
    q.add_argument("--rounds", type=int, default=0,
                   help="override the schedule's round count (0 = from "
                   "the seed)")
    q.add_argument("--jobs", type=int, default=0,
                   help="override the number of tenant jobs (0 = from "
                   "the seed)")
    q.add_argument("--real", action="store_true",
                   help="drive real echo-machine engines instead of "
                   "the jax-free synthetic driver (slow: each worker "
                   "restart pays a jax import; finds are filed and "
                   "regress-replayed)")
    q.add_argument("--out", default=None, metavar="DIR",
                   help="keep the farm, schedule.json and fsck.json "
                   "under DIR (default: a temp dir, removed when the "
                   "seed passes)")
    q.add_argument("--keep", action="store_true",
                   help="keep the scratch farm even on success")
    q.set_defaults(fn=cmd_fleet)

    p = sub.add_parser(
        "lint",
        help="static determinism & contract analysis: D-rules "
        "(wall-clock/entropy/set-order/callback hazards, AST-only), "
        "C-rules (Machine contract: handler purity, durable/torn spec "
        "congruence, coverage projection), G-rules (fault-kind mirror "
        "and RNG-layout cross-checks), and the whole-program families "
        "— L (jax-free layer map), T (traced-value taint/donation), "
        "R (static RNG ledger), S (sharding readiness: lane-axis "
        "dataflow vs the collective registry). Exit 0 clean / "
        "1 findings / 2 usage error — pre-commit friendly",
    )
    from .analysis.cli import add_lint_args

    add_lint_args(p)
    p.set_defaults(fn=cmd_lint)

    args = parser.parse_args(argv)
    if getattr(args, "log_level", None) or getattr(args, "log_jsonl", None):
        from .tracing import init_tracing

        init_tracing(
            getattr(args, "log_level", None) or "INFO",
            jsonl_path=getattr(args, "log_jsonl", None),
        )
    if args.cmd == "perf":
        # the out positional IS the host timeline: cmd_perf runs under
        # the same --perf-timeline session as explore/hunt
        args.perf_timeline = args.out
    jax_free = args.cmd in ("serve", "coverage", "lint") or (
        # the whole fleet control plane (serve + client verbs + fsck +
        # chaos orchestration) is jax-free by contract; only a worker
        # with the real driver runs engines — the chaos harness's
        # synthetic-driver workers stay jax-free so a fleet-chaos round
        # costs milliseconds, not a jax import per incarnation
        args.cmd == "fleet" and (
            args.fleet_cmd != "worker"
            or getattr(args, "driver", "real") == "synthetic"
        )
    )
    if getattr(args, "multihost", False):
        # distributed init must precede ANY backend access, which
        # would pin a single-process backend
        from .parallel import multihost

        multihost.initialize()
    if not jax_free:
        # Warm-start priming: wire the persistent compilation cache
        # (compile_cache.enable_compile_cache: $JAX_COMPILATION_CACHE_DIR,
        # else --compile-cache / $MADSIM_TPU_COMPILE_CACHE, else the
        # checkout default) BEFORE the
        # subcommand's first jit, so hunt/explore warmups
        # read and write the cache from their very first compile —
        # enabling is first-directory-wins per process, and an engine
        # constructed before the cache was bound would pay a full
        # cold build that the fleet then never reuses.
        from .compile_cache import enable_compile_cache

        enable_compile_cache(getattr(args, "compile_cache", None))
    with _perf_session(args):
        return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
