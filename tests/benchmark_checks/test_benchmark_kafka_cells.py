"""The cell PR 33 added, `kafka_pc5_sweep` (madsim-rdkafka's producer /
consumer pipeline), and the four-chip cell `raft5_sweep_x4` with its traffic
file `sweep_16k`. The new cell loads, its flags parse through the CLI's own
parser, a tiny twin of the configuration runs through the real harness on
the CPU backend with the two new readers on its line, `sweep_16k` names
`sweep_10k`'s ranges, BENCHMARK.json validates with one and with two
new cells, and `validate` refuses a sweep cell whose traffic file names no
ranges checked under the cell's configuration (PR 37). A time taken here is no device number."""

import itertools
import json
import os
import shutil

import pytest

from benchmark import cells, harness

X4 = {"name": "raft5_sweep_x4", "config": "raft5", "traffic": "sweep_16k",
      "chips": 4, "why": "the lane-sharded mesh and its collectives"}
MESH_READERS = ("collective_share", "collective_exposed_share")
KERNEL_PAIR = ("step_kernel_share", "step_kernel_roofline")


def _bench(with_x4: bool) -> dict:
    """BENCHMARK.json with `raft5_sweep_x4` listed or not, whichever way
    the file stands."""
    bench = cells.load_benchmark()
    listed = any(w["name"] == X4["name"] for w in bench["workloads"])
    if with_x4 and not listed:
        bench["workloads"].append(dict(X4))
        for m in bench["end_to_end"] + bench["per_layer"]:
            # the sweep readers; the set-up metrics' lists are pinned by an
            # accepted test and the kernel pair has no Mosaic call to read
            if ("raft5_sweep" in m.get("workloads", [])
                    and m.get("moves") != "setup_s"
                    and m["name"] not in KERNEL_PAIR):
                m["workloads"].append(X4["name"])
        for name in MESH_READERS:
            bench["per_layer"].append({
                "name": name, "unit": "%", "better": "lower",
                "source": "device_trace", "layer": "mesh",
                "moves": "seeds_per_s", "workloads": [X4["name"]]})
    if not with_x4 and listed:
        bench["workloads"] = [w for w in bench["workloads"] if w["name"] != X4["name"]]
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m.get("workloads") != [X4["name"]]]
        for m in bench["end_to_end"] + bench["per_layer"]:
            if X4["name"] in m.get("workloads", []):
                m["workloads"].remove(X4["name"])
    return bench


@pytest.mark.parametrize("with_x4", [False, True])
def test_benchmark_json_validates_with_one_and_with_two_new_cells(with_x4):
    bench = _bench(with_x4)
    assert cells.validate(bench) == []
    names = [w["name"] for w in bench["workloads"]]
    assert "kafka_pc5_sweep" in names and (X4["name"] in names) == with_x4
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == int(with_x4)
    if with_x4:
        cell = cells.load_cell(X4["name"], bench)
        assert cell.chips == 4 and cell.config["mesh_flags"] == {"devices": 4}
        assert set(MESH_READERS) <= {m["name"] for m in cell.per_layer}
        argv = cells.load_campaign(cell).argv(cell, 1_000_000)
        assert argv[-2:] == ["--devices", "4"] and "16384" in argv


def test_new_cell_loads_and_its_argv_parses():
    bench = cells.load_benchmark()
    cell = cells.load_cell("kafka_pc5_sweep", bench)
    assert (cell.chips, cell.kind, cell.traffic_name) == (1, "sweep", "sweep_8k")
    assert {m["name"] for m in cell.end_to_end} == {"seeds_per_s", "setup_s"}
    listed = {m["name"] for m in cell.per_layer}
    assert {"records_per_seed", "rebalances_per_seed", "events_per_seed",
            "step_handler_share", "step_invariant_share", "step_kernel_roofline",
            "device_idle_share", "peak_device_bytes", "compiles_in_window",
            "host_syncs_per_batch"} <= listed
    assert not {"step_churn_share", "faults_per_seed"} & listed  # no churn process
    for m in bench["per_layer"]:
        if m["name"] in ("records_per_seed", "rebalances_per_seed"):
            assert m["workloads"] == ["kafka_pc5_sweep"] and m["moves"] == "seeds_per_s"
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
    assert (args.machine, args.seeds, args.batch) == ("kafka", 8192, 8192)
    assert (args.horizon, args.log_capacity, args.faults, args.fault_tmax,
            args.fault_kinds, args.rng_stream) == (
        2.0, cell.config["flags"]["log-capacity"], 3, 1_500_000,
        "pair,kill,dir,group,storm", 3)
    assert args.flight_recorder and args.coverage
    machine = cli.build_machine(args.machine, args.nodes, args.log_capacity)
    assert (machine.NUM_NODES, machine.P, machine.producers) == (5, 3, 2)
    assert machine.log_capacity % 32 == 0
    # the produce window ends half a second before the configuration's horizon
    assert machine.produce_until_us == (args.horizon - 0.5) * 1e6
    assert args.queue % 8 == 0 and args.queue >= 32
    doc = cell.config
    assert doc["reduced"] == ["horizon"] and doc["hunt_machine"] == "demo-nodedup-kafka"
    assert 64 <= doc["check"]["sample_lanes"] <= 256
    assert {"recalled", "log-capacity", "queue", "max-steps", "timers"} <= set(doc["assumed"])


def test_sweep_16k_names_sweep_10ks_checked_ranges():
    t16 = cells.load_json(os.path.join(cells.DATA_ROOT, "traffic", "sweep_16k.json"))
    t10 = cells.load_json(os.path.join(cells.DATA_ROOT, "traffic", "sweep_10k.json"))
    for key in ("kind", "seeds", "stride", "pool", "base_seed", "warmup_seed",
                "slots", "slots_checked"):
        assert t16[key] == t10[key], key
    assert t16["seeds"] == 16384
    # a campaign consumes ~23.7k seeds of its slot: inside what was checked
    assert t16["slots_checked"]["seeds_per_slot"] == 32768 >= 2 * t16["seeds"]
    n = len(t10["slots"])
    assert n >= 96 and t16["slots"][:39] == [s for s in range(42) if s not in (7, 15, 16)]
    for seed in (0, 7, 2**31 + 11):
        a = list(itertools.islice(harness.campaign_seeds(t16, seed), 2 * n))
        b = list(itertools.islice(harness.campaign_seeds(t10, seed), 2 * n))
        assert a == b
        assert sorted(a[:8]) == [1_000_000 + s * 65536 for s in t10["slots"][:8]]
        assert 1_000_000 + 7 * 65536 not in a  # the overflowing lane's slot
        assert set(a) == {1_000_000 + s * 65536 for s in t16["slots"]}  # wrapped, not beyond


def _doctored_sweep_8k(fault: str) -> dict:
    t = cells.load_json(os.path.join(cells.DATA_ROOT, "traffic", "sweep_8k.json"))
    if fault == "no_slots":  # the file as it stood before PR 37
        del t["slots"], t["slots_checked"]
    elif fault == "slots_shorter_than_the_pool":
        t["slots"] = t["slots"][:1]
    elif fault == "checked_under_other_configurations":
        t["slots_checked"]["config"] = [
            c for c in t["slots_checked"]["config"] if c != "kafka_pc5"]
    elif fault == "checked_under_one_other_configuration":
        t["slots_checked"]["config"] = "raft5_fig8"
    return t


@pytest.mark.parametrize("fault, refused, words", [
    ("none", [], ""),
    ("no_slots", ["raft5_fig8_sweep", "kafka_pc5_sweep", "kvraft5_sweep"],
     "traffic sweep_8k names no checked seed ranges"),
    ("slots_shorter_than_the_pool",
     ["raft5_fig8_sweep", "kafka_pc5_sweep", "kvraft5_sweep"],
     "traffic sweep_8k names no checked seed ranges"),
    ("checked_under_other_configurations", ["kafka_pc5_sweep"],
     "['raft5_fig8', 'kvraft5'], not under kafka_pc5"),
    ("checked_under_one_other_configuration", ["kafka_pc5_sweep", "kvraft5_sweep"],
     "['raft5_fig8'], not under k"),
])
def test_validate_refuses_a_sweep_cell_without_ranges_checked_under_its_configuration(
        tmp_path, fault, refused, words):
    root = tmp_path / "benchmark"
    for group in ("configs", "traffic", "campaigns", "layer_metrics"):
        shutil.copytree(os.path.join(cells.DATA_ROOT, group), root / group)
    (root / "traffic" / "sweep_8k.json").write_text(
        json.dumps(_doctored_sweep_8k(fault)))
    bench = cells.load_benchmark()
    assert cells.validate(bench) == []  # the accepted file, as it stands
    bad = cells.validate(bench, str(root))
    assert [b.split(":")[0] for b in bad] == refused
    assert all(words in b for b in bad)
    # a hunt's file names no ranges and needs none: it counts no lost lane
    assert "slots" not in cells.load_cell("etcd_mvcc4_hunt", bench, str(root)).traffic


TINY_KAFKA = {
    "name": "kafka_tiny", "machine": "kafka", "hunt_machine": "demo-nodedup-kafka",
    "flags": {"horizon": 0.5, "log-capacity": 32, "faults": 3,
              "fault-kinds": "pair,kill,dir,group,storm", "fault-tmax": 400000,
              "rng-stream": 3, "flight-recorder": True, "coverage": True,
              "batch": 16, "max-steps": 2000, "queue": 40},
    "mesh_flags": {"devices": 4},
    "check": {"sample_lanes": 8},
}
TINY_SWEEP = {"name": "sweep_tiny", "kind": "sweep", "seeds": 16, "stride": 256,
              "pool": 2, "base_seed": 4096, "warmup_seed": 1024,
              "trace_campaigns": 1, "slots": [0, 1],
              "slots_checked": {"config": "kafka_tiny", "by": "this test's window"}}


def test_tiny_kafka_cell_through_the_harness_on_the_cpu(tmp_path, monkeypatch,
                                                        capsys):
    root = tmp_path / "benchmark"
    for group in ("configs", "traffic", "campaigns", "layer_metrics"):
        shutil.copytree(os.path.join(cells.DATA_ROOT, group), root / group)
    shutil.copy(os.path.join(cells.DATA_ROOT, "peaks.json"), root / "peaks.json")
    (root / "configs" / "kafka_tiny.json").write_text(json.dumps(TINY_KAFKA))
    (root / "traffic" / "sweep_tiny.json").write_text(json.dumps(TINY_SWEEP))
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "kafka_tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/kafka_tiny.json", "why": "t"})
    bench["workloads"].append({"name": "tiny_kafka", "config": "kafka_tiny",
                               "traffic": "sweep_tiny", "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "kafka_pc5_sweep" in m.get("workloads", []):
            m["workloads"].append("tiny_kafka")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    assert cells.validate(bench, str(root)) == []
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    harness.run_cell("tiny_kafka", 2**31 + 11, 0.2, True, str(path), str(root))
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0, lines[-12:]
    assert line["attempted"] >= 16
    metrics = line["metrics"]
    # the machine's totals are there: half a virtual second of two producers,
    # and at least the two joins a lane
    assert 10 < metrics["records_per_seed"]["value"] < 40
    assert metrics["rebalances_per_seed"]["value"] >= 1.5
    assert metrics["events_per_seed"]["value"] > 200
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["host_syncs_per_batch"]["value"] == 3
    assert "step_churn_share" not in metrics and "faults_per_seed" not in metrics
