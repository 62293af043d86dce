"""Each campaign kind end to end at a tiny size on the CPU backend, in a
throwaway data root: a new config, a new traffic mix, a new cell and a
new per-layer metric are added as files and BENCHMARK.json entries only
— no file of benchmark/ is edited — and run through the real harness
with the platform check patched. A time taken here is no device number;
only the shape of the result line and the checks are looked at."""

import json
import os
import shutil

import pytest

from benchmark import cells, checks, harness

TINY_RAFT = {
    "name": "raft_tiny", "machine": "raft", "hunt_machine": None,
    "flags": {"horizon": 5, "queue": 32, "faults": 2, "rng-stream": 3,
              "flight-recorder": True, "coverage": True, "batch": 16},
    "mesh_flags": {"devices": 4},
    "check": {"sample_lanes": 8},
}
TINY_MVCC = {
    "name": "mvcc_tiny", "machine": "etcd-mvcc", "hunt_machine": "demo-nodedup-mvcc",
    "flags": {"horizon": 8, "queue": 48, "faults": 3,
              "fault-kinds": "pair,kill,dir,group,storm", "fault-tmax": 3000000,
              "max-steps": 4000, "batch": 16},
    "mesh_flags": {"devices": 4},
    "check": {"sample_lanes": 8},
}
TINY_SWEEP = {"name": "sweep_tiny", "kind": "sweep", "seeds": 32, "stride": 256,
              "pool": 3, "base_seed": 4096, "warmup_seed": 1024,
              "trace_campaigns": 1, "slots": [0, 1, 2],
              "slots_checked": {"config": "raft_tiny", "by": "this test's window"}}
TINY_HUNT = {"name": "hunt_tiny", "kind": "hunt", "seeds": 32, "stride": 256,
             "limit": 1, "pool": 3, "base_seed": 4096, "warmup_seed": 1024,
             "trace_campaigns": 1}
NEW_READER = '''"""A throwaway per-layer metric: campaigns in the window."""


def read(obs):
    return len(obs.records)
'''


@pytest.fixture
def throwaway_root(tmp_path):
    """A copy of the benchmark's data with a tiny config, mix, cell and
    metric of its own ADDED; returns (BENCHMARK.json path, data root)."""
    root = tmp_path / "benchmark"
    for group in ("configs", "traffic", "campaigns", "layer_metrics"):
        shutil.copytree(os.path.join(cells.DATA_ROOT, group), root / group)
    shutil.copy(os.path.join(cells.DATA_ROOT, "peaks.json"), root / "peaks.json")
    for group, doc in (("configs", TINY_RAFT), ("configs", TINY_MVCC),
                       ("traffic", TINY_SWEEP), ("traffic", TINY_HUNT)):
        (root / group / f"{doc['name']}.json").write_text(json.dumps(doc))
    (root / "layer_metrics" / "campaigns_in_window.py").write_text(NEW_READER)
    bench = cells.load_benchmark()
    tiny_cells = [("tiny_sweep", "raft_tiny", "sweep_tiny", 1),
                  ("tiny_hunt", "mvcc_tiny", "hunt_tiny", 1),
                  ("tiny_sweep_x4", "raft_tiny", "sweep_tiny2", 4)]
    for name, config, traffic, chips in tiny_cells:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "throwaway"})
    (root / "traffic" / "sweep_tiny2.json").write_text(
        json.dumps(dict(TINY_SWEEP, name="sweep_tiny2")))
    for c in ("raft_tiny", "mvcc_tiny"):
        bench["configs"].append({"name": c, "source": "test", "reduced": [],
                                 "file": f"benchmark/configs/{c}.json", "why": "t"})
    # a tiny cell reports what the real cells of its campaign kind and
    # chip count report — read off the data, not off any name
    real = {w["name"]: cells.load_cell(w["name"]) for w in cells.load_benchmark()["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = [real[w] for w in m.get("workloads", [])]
        for name, _config, traffic, chips in tiny_cells:
            kind = "hunt" if traffic == "hunt_tiny" else "sweep"
            if any(c.kind == kind and (c.chips > 1) <= (chips > 1) for c in listed) \
                    and not (chips == 1 and all(c.chips > 1 for c in listed)):
                m["workloads"].append(name)
    bench["per_layer"].append({
        "name": "campaigns_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "stream driver",
        "moves": "setup_s"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    assert cells.validate(bench, str(root)) == []
    return str(path), str(root)


def last_line(capsys) -> tuple:
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), lines[:-1]


def assert_contract(line: dict, metrics: set, traced: bool,
                    correct: bool = True) -> None:
    assert {"correct", "attempted", "failed", "metrics", "device"} <= line.keys()
    assert line["correct"] is correct and line["failed"] == 0
    assert line["attempted"] > 0
    assert metrics <= line["metrics"].keys()
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= line["device"].keys()
    assert ("breakdown" in line) == traced
    if traced:
        assert {"busy_s", "window_s"} <= line["device"].keys()
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_sweep_kind_traced_and_a_disagreeing_sample(throwaway_root,
                                                    monkeypatch, capsys):
    """The sweep kind in a traced run, with the window engine's lane
    sample skewed by one fail code: every per-layer metric a CPU run can
    give is on the line, and the line says `correct: false`."""
    bench_json, root = throwaway_root
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    real = checks.lane_results
    calls = []

    def skewed(eng, *a, **kw):
        out = real(eng, *a, **kw)
        calls.append(eng)
        if len(calls) == 1:  # the window engine's own lanes
            out["fail_code"] = out["fail_code"] + 1
        return out

    monkeypatch.setattr(checks, "lane_results", skewed)
    harness.run_cell("tiny_sweep", 3, 0.2, True, bench_json, root)
    line, said = last_line(capsys)
    # the CPU backend has no device plane: readers of the trace return
    # nothing and their metrics are left out; the counters are there
    assert_contract(line, {"driver_self_share", "host_syncs_per_batch",
                           "discarded_seed_share", "events_per_seed",
                           "compiles_in_window", "setup_warmup_s",
                           "campaigns_in_window"}, traced=True, correct=False)
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
    assert "device_idle_share" not in line["metrics"]
    assert "seeds_per_s" not in line["metrics"]  # a traced run: per-layer only
    wrong = [ln for ln in said if "WRONG" in ln]
    assert len(wrong) == 1 and "device vs CPU backend" in wrong[0]


def test_hunt_kind_end_to_end(throwaway_root, monkeypatch, capsys):
    bench_json, root = throwaway_root
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    harness.run_cell("tiny_hunt", 2**31 + 5, 0.5, False, bench_json, root)
    line, _said = last_line(capsys)
    assert_contract(line, {"find_s", "setup_s"}, traced=False)
    assert "seeds_per_s" not in line["metrics"]  # the hunt cell never reports it
    assert line["attempted"] >= 1  # whole hunts; the one under way is finished


def test_mesh_cell_checks_the_mesh_against_one_device(throwaway_root,
                                                      monkeypatch, capsys):
    bench_json, root = throwaway_root
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    seen = {}
    real = checks.mesh_problems

    def spy(*a, **kw):
        seen["out"] = real(*a, **kw)
        return seen["out"]

    monkeypatch.setattr(checks, "mesh_problems", spy)
    harness.run_cell("tiny_sweep_x4", 1, 0.1, False, bench_json, root)
    line, _said = last_line(capsys)
    assert_contract(line, {"seeds_per_s", "setup_s"}, traced=False)
    assert "find_s" not in line["metrics"]
    bad, facts = seen["out"]
    assert bad == [] and facts["coverage_slots"] > 0
