"""The cell PR 27 added, `raft5_fig8_sweep` (Figure 8's churn), and the
waiting cell `etcd_mvcc4_sweep`, whose files stay in place but which is not
in BENCHMARK.json (its `seeds_per_s` spread 2.9-4.2% over six runs a side
on the chip, PERF.md §7). The new cell loads, its flags parse through the
CLI's own parser, the waiting cell's files would load the day it is listed
(`validate` asks it for checked seed ranges first, PR 37),
and a tiny twin of the new configuration runs through the real harness on
the CPU backend with the two new readers on its line. A time taken here is
no device number."""

import json
import os
import shutil

import pytest

from benchmark import cells, harness

NEW_CELLS = ("raft5_fig8_sweep",)
WAITING = {"name": "etcd_mvcc4_sweep", "config": "etcd_mvcc4",
           "traffic": "sweep_100k", "chips": 1, "why": "waiting (PERF.md §7)"}


def _bench_with_waiting_cell() -> dict:
    """BENCHMARK.json with the waiting cell listed as a sweep cell."""
    bench = cells.load_benchmark()
    bench["workloads"].append(dict(WAITING))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "raft5_sweep" in m.get("workloads", []):
            m["workloads"].append(WAITING["name"])
    return bench


@pytest.mark.parametrize("name", NEW_CELLS + (WAITING["name"],))
def test_new_cell_loads_and_its_argv_parses(name):
    bench = _bench_with_waiting_cell()
    # the waiting cell's files load, but `sweep_100k` names no checked
    # ranges (PR 37): the day it is listed it brings a checked file
    (refusal,) = cells.validate(bench)
    assert refusal.startswith("etcd_mvcc4_sweep: traffic sweep_100k names no "
                              "checked seed ranges")
    cell = cells.load_cell(name, bench)
    assert cell.chips == 1 and cell.kind == "sweep"
    assert {m["name"] for m in cell.end_to_end} == {"seeds_per_s", "setup_s"}
    listed = {m["name"] for m in cell.per_layer}
    assert {"driver_self_share", "device_idle_share", "events_per_seed",
            "step_handler_share", "step_kernel_roofline", "setup_warmup_s",
            "setup_cache_misses"} <= listed
    assert ("step_churn_share" in listed) == (name == "raft5_fig8_sweep")
    assert ("faults_per_seed" in listed) == (name == "raft5_fig8_sweep")
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
    assert args.seeds == cell.traffic["seeds"] and args.batch == 8192
    if name == "raft5_fig8_sweep":
        assert (args.churn, args.churn_until, args.log_capacity, args.latency,
                args.faults, args.loss) == ("fig8", 4.5, 256, "1000,27000", 0, 0.1)
        assert cell.traffic["seeds"] == 8192 and cell.traffic["pool"] == 2
        assert cell.config["check"]["sample_lanes"] == 64
        assert cell.config["reduced"] == ["horizon"]


def test_the_accepted_setup_metrics_keep_their_accepted_cells():
    bench = cells.load_benchmark()
    for m in bench["per_layer"]:
        if m["moves"] == "setup_s":
            assert m["workloads"] == ["raft5_sweep", "etcd_mvcc4_hunt",
                                      *NEW_CELLS]


TINY_FIG8 = {
    "name": "fig8_tiny", "machine": "raft", "hunt_machine": "demo-fig8-raft",
    "flags": {"churn": "fig8", "churn-until": 0.9, "horizon": 1,
              "log-capacity": 16, "loss": 0.1, "latency": "1000,27000",
              "faults": 0, "rng-stream": 3, "flight-recorder": True,
              "coverage": True, "batch": 16, "max-steps": 2000, "queue": 32},
    "mesh_flags": {"devices": 4},
    "check": {"sample_lanes": 8},
}
TINY_SWEEP = {"name": "sweep_tiny", "kind": "sweep", "seeds": 16, "stride": 256,
              "pool": 2, "base_seed": 4096, "warmup_seed": 1024,
              "trace_campaigns": 1, "slots": [0, 1],
              "slots_checked": {"config": "fig8_tiny", "by": "this test's window"}}


def test_tiny_fig8_cell_through_the_harness_on_the_cpu(tmp_path, monkeypatch,
                                                       capsys):
    root = tmp_path / "benchmark"
    for group in ("configs", "traffic", "campaigns", "layer_metrics"):
        shutil.copytree(os.path.join(cells.DATA_ROOT, group), root / group)
    shutil.copy(os.path.join(cells.DATA_ROOT, "peaks.json"), root / "peaks.json")
    (root / "configs" / "fig8_tiny.json").write_text(json.dumps(TINY_FIG8))
    (root / "traffic" / "sweep_tiny.json").write_text(json.dumps(TINY_SWEEP))
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "fig8_tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/fig8_tiny.json", "why": "t"})
    bench["workloads"].append({"name": "tiny_fig8", "config": "fig8_tiny",
                               "traffic": "sweep_tiny", "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "raft5_fig8_sweep" in m.get("workloads", []):
            m["workloads"].append("tiny_fig8")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    assert cells.validate(bench, str(root)) == []
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    harness.run_cell("tiny_fig8", 2**31 + 11, 0.2, True, str(path), str(root))
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0, lines[-12:]
    assert line["attempted"] >= 16
    metrics = line["metrics"]
    # the counters are there; the CPU backend has no device plane, so the
    # share of the trace is left out, never read as 0
    assert metrics["faults_per_seed"]["value"] > 1.0
    assert metrics["events_per_seed"]["value"] > 300
    assert "step_churn_share" not in metrics
