"""The cell PR 35 added, `kvraft5_sweep` (MadRaft's lab 3A: a key/value
service on Raft, five servers and five clerks in a lane of ten, under the
tester's repartitions and whole-cluster crash). BENCHMARK.json validates with
it, the cell loads and its flags parse through the CLI's own parser, the
three new readers read a fixture, and a tiny twin of the configuration runs
through the real harness on the CPU backend with the machine's totals on its
line. A time taken here is no device number."""

import json
import os
import shutil
from types import SimpleNamespace

import pytest

from benchmark import cells, harness

NEW_READERS = ("ops_per_seed", "wrong_leader_per_seed", "partitions_per_seed")
SETUP_READERS = ("setup_warmup_s", "setup_trace_s", "setup_lower_s",
                 "setup_backend_s", "setup_cache_misses")


def test_benchmark_json_validates_with_the_new_cell():
    bench = cells.load_benchmark()
    assert cells.validate(bench) == []
    assert [w["name"] for w in bench["workloads"]][-1] == "kvraft5_sweep"
    entry = bench["configs"][-1]
    assert entry["name"] == "kvraft5" and entry["reduced"] == ["horizon", "iterations"]
    assert len(entry["source"]) <= 200 and "persist_partition_unreliable_3a" in entry["source"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in NEW_READERS:
        m = by_name[name]
        assert m["workloads"] == ["kvraft5_sweep"] and m["moves"] == "seeds_per_s"
        assert (m["source"], m["layer"]) == ("program_counter", "step")
    # the cell is in every list kafka_pc5_sweep's sweep readers are in, last
    for m in bench["end_to_end"] + bench["per_layer"]:
        wl = m.get("workloads", [])
        if "kafka_pc5_sweep" in wl and m["name"] not in SETUP_READERS + (
                "records_per_seed", "rebalances_per_seed"):
            assert "kvraft5_sweep" in wl, m["name"]
    for name in ("step_churn_share", "faults_per_seed"):
        assert by_name[name]["workloads"] == ["raft5_fig8_sweep", "kvraft5_sweep"]
    for name in SETUP_READERS:
        assert "kvraft5_sweep" not in by_name[name]["workloads"]


def test_new_cell_loads_and_its_argv_parses():
    cell = cells.load_cell("kvraft5_sweep")
    assert (cell.chips, cell.kind, cell.traffic_name) == (1, "sweep", "sweep_8k")
    assert {m["name"] for m in cell.end_to_end} == {"seeds_per_s", "setup_s"}
    listed = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) | {
        "step_churn_share", "faults_per_seed", "events_per_seed",
        "step_handler_share", "step_kernel_share", "step_kernel_roofline",
        "device_idle_share", "peak_device_bytes", "compiles_in_window",
        "host_syncs_per_batch"} <= listed
    assert not {"records_per_seed", "rebalances_per_seed"} & listed
    argv = cells.load_campaign(cell).argv(cell, 1_000_000)
    import madsim_tpu.__main__ as cli

    seen = {}

    def build(args):
        seen["args"] = args
        raise SystemExit(0)  # parsed: nothing is built here

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cli, "_build_engine", build)
        with pytest.raises(SystemExit):
            cli.main(list(argv))
    args = seen["args"]
    assert (args.machine, args.seeds, args.batch) == ("kvraft", 8192, 8192)
    assert (args.churn, args.churn_until, args.horizon, args.loss, args.latency,
            args.faults, args.rng_stream) == (
        "kv3a", 1.5, 4.8, 0.1, "1000,27000", 0, 3)
    assert args.flight_recorder and args.coverage
    assert args.log_capacity == cell.config["flags"]["log-capacity"]
    machine = cli.build_machine(args.machine, args.nodes, args.log_capacity)
    assert (machine.NUM_NODES, machine.servers, machine.clerks) == (10, 5, 5)
    assert machine.log_capacity % 32 == 0 and machine.churn_nodes() == (0, 1, 2, 3, 4)
    # the load window ends where the fault process kills the servers
    assert machine.load_until_us == args.churn_until * 1e6
    assert args.queue % 8 == 0 and args.queue >= 32
    doc = cell.config
    assert doc["reduced"] == ["horizon", "iterations"]
    assert doc["hunt_machine"] == "demo-localget-kvraft"
    assert 64 <= doc["check"]["sample_lanes"] <= 256
    assert {"recalled", "log-capacity", "queue", "max-steps", "clerk", "replication",
            "partitioner", "crash", "apply"} <= set(doc["assumed"])
    assert any("172" in g for g in doc["guarantees"])
    assert any("NOT covered" in g for g in doc["guarantees"])


def _obs(records):
    return SimpleNamespace(records=records)


def _record(machine=None, churn=None, completed=100):
    fr = {}
    if machine is not None:
        fr["machine"] = machine
    if churn is not None:
        fr["churn"] = churn
    return {"agg": {"stats": {"flight_recorder": fr}},
            "calls": [{"n_seeds": 1, "completed": 1},
                      {"n_seeds": 128, "completed": completed}]}


def test_the_three_new_readers_on_a_fixture():
    cell = cells.load_cell("kvraft5_sweep")
    ops, wrong, parts = (cells.load_reader(cell, n).read for n in NEW_READERS)
    kv = {"ops_acked": 2000, "wrong_leader": 6000}
    churn = {"ticks": 200, "disconnects": 310, "reconnects": 150,
             "partitions": 200, "crashes": 100}
    obs = _obs([_record(kv, churn), _record(kv, churn)])
    assert ops(obs) == 20.0 and wrong(obs) == 60.0 and parts(obs) == 3.0
    # a program without the counters (another machine, a fig8 process, a
    # parent from before the kind) reads nothing and does not raise
    fig8 = {"ticks": 9, "disconnects": 4, "reconnects": 4}
    old = _obs([_record({"appended": 7}, fig8), _record(None, None)])
    assert ops(old) is None and wrong(old) is None and parts(old) is None
    assert parts(_obs([])) is None
    # the accepted reader of the process's faults reads the new kind by name
    faults = cells.load_reader(cell, "faults_per_seed").read
    assert faults(obs) == (310 + 150) / 100


TINY_KV = {
    "name": "kvraft_tiny", "machine": "kvraft", "hunt_machine": "demo-localget-kvraft",
    "flags": {"churn": "kv3a", "churn-until": 0.6, "horizon": 1.2,
              "log-capacity": 32, "loss": 0.1, "latency": "1000,27000",
              "faults": 0, "rng-stream": 3, "flight-recorder": True,
              "coverage": True, "batch": 16, "max-steps": 2000, "queue": 48},
    "mesh_flags": {"devices": 4},
    "check": {"sample_lanes": 8},
}
TINY_SWEEP = {"name": "sweep_tiny", "kind": "sweep", "seeds": 16, "stride": 256,
              "pool": 2, "base_seed": 4096, "warmup_seed": 1024,
              "trace_campaigns": 1, "slots": [0, 1],
              "slots_checked": {"config": "kvraft_tiny", "by": "this test's window"}}


def test_tiny_kvraft_cell_through_the_harness_on_the_cpu(tmp_path, monkeypatch,
                                                         capsys):
    root = tmp_path / "benchmark"
    for group in ("configs", "traffic", "campaigns", "layer_metrics"):
        shutil.copytree(os.path.join(cells.DATA_ROOT, group), root / group)
    shutil.copy(os.path.join(cells.DATA_ROOT, "peaks.json"), root / "peaks.json")
    (root / "configs" / "kvraft_tiny.json").write_text(json.dumps(TINY_KV))
    (root / "traffic" / "sweep_tiny.json").write_text(json.dumps(TINY_SWEEP))
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "kvraft_tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/kvraft_tiny.json", "why": "t"})
    bench["workloads"].append({"name": "tiny_kvraft", "config": "kvraft_tiny",
                               "traffic": "sweep_tiny", "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "kvraft5_sweep" in m.get("workloads", []):
            m["workloads"].append("tiny_kvraft")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    assert cells.validate(bench, str(root)) == []
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    harness.run_cell("tiny_kvraft", 2**31 + 11, 0.2, True, str(path), str(root))
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0, lines[-12:]
    assert line["attempted"] >= 16
    metrics = line["metrics"]
    # the machine's totals and the process's are on the line: tick 0 and the
    # kill on every lane, operations acknowledged, leaders searched for
    assert metrics["partitions_per_seed"]["value"] == 2.0
    assert metrics["ops_per_seed"]["value"] > 1
    assert metrics["wrong_leader_per_seed"]["value"] > 5
    assert metrics["faults_per_seed"]["value"] > 0
    assert metrics["events_per_seed"]["value"] > 150
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["host_syncs_per_batch"]["value"] == 3
    assert "records_per_seed" not in metrics and "rebalances_per_seed" not in metrics
