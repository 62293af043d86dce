"""A sweep's named seed ranges (`slots`): what `harness.campaign_seeds`
makes of them, that a traffic file without them yields what it always
did, what `sweep_10k.json` itself promises, and `pool_check.py` — the
check a range has to pass before a traffic file may list it — at a tiny
size on the CPU backend."""

import itertools
import json
import os

import pytest

from benchmark import cells, harness, pool_check

SEEDS = (0, 7, 2**31 + 11)
TAIL = [1524288, 1589824, 1655360, 1720896]
#: the first 12 campaign seeds of a run at the parent of the PR that
#: added `slots` (eb1323c), for --seed 0, 7 and 2**31 + 11
GOLDEN = {
    "sweep_8k": {
        0: [1000000, 1065536, 1131072, 1196608, 1262144, 1327680, 1393216,
            1458752] + TAIL,
        7: [1000000, 1065536, 1131072, 1196608, 1262144, 1327680, 1393216,
            1458752] + TAIL,
        2**31 + 11: [1065536, 1000000, 1131072, 1196608, 1262144, 1327680,
                     1393216, 1458752] + TAIL,
    },
    "hunt_highfind": {
        0: [1262144, 1065536, 1327680, 1131072, 1000000, 1196608, 1458752,
            1393216] + TAIL,
        7: [1393216, 1458752, 1131072, 1262144, 1000000, 1196608, 1065536,
            1327680] + TAIL,
        2**31 + 11: [1393216, 1000000, 1262144, 1131072, 1065536, 1327680,
                     1458752, 1196608] + TAIL,
    },
    "sweep_100k": {
        0: [2048576, 1262144, 2310720, 1524288, 1000000, 1786432, 2835008,
            2572864, 3097152, 3359296, 3621440, 3883584],
        7: [2572864, 2835008, 1524288, 2048576, 1000000, 1786432, 1262144,
            2310720, 3097152, 3359296, 3621440, 3883584],
        2**31 + 11: [2572864, 1000000, 2048576, 1524288, 1262144, 2310720,
                     2835008, 1786432, 3097152, 3359296, 3621440, 3883584],
    },
}


def traffic_file(name: str) -> dict:
    return cells.load_json(os.path.join(cells.DATA_ROOT, "traffic", name + ".json"))


def first(traffic: dict, seed: int, n: int) -> list:
    return list(itertools.islice(harness.campaign_seeds(traffic, seed), n))


def test_named_slots_same_pool_another_order_then_the_list_then_beyond(capsys):
    traffic = {"pool": 3, "base_seed": 1000, "stride": 100, "warmup_seed": 500,
               "slots": [0, 2, 5, 6, 9]}
    runs = {seed: first(traffic, seed, 8) for seed in SEEDS + (1, 2)}
    said = capsys.readouterr().out.splitlines()
    for r in runs.values():
        assert sorted(r[:3]) == [1000, 1200, 1500]  # the same pool
        assert r[3:5] == [1600, 1900]  # then the fresh ranges, in list order
        assert r[5:] == [2000, 2100, 2200]  # then the slots after the last
        assert len(set(r)) == len(r)  # never a repeat
    assert len({tuple(r[:3]) for r in runs.values()}) > 1  # another order
    assert runs[7] == first(traffic, 7, 8)
    # every campaign past the list is announced, and no other
    assert len(said) == 3 * len(runs)
    assert all(ln.startswith("benchmark: campaign ") and "unchecked" in ln
               for ln in said)
    capsys.readouterr()
    first(traffic, 0, 5)
    assert capsys.readouterr().out == ""
    # the shuffle is the one a file without `slots` gets: `slots` only
    # renames the positions
    plain = {k: v for k, v in traffic.items() if k != "slots"}
    for seed in SEEDS:
        assert [traffic["slots"][(s - 1000) // 100] for s in first(plain, seed, 5)] \
            == [(s - 1000) // 100 for s in runs[seed][:5]]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_a_traffic_file_without_slots_yields_the_parents_sequences(name, capsys):
    traffic = traffic_file(name)
    assert "slots" not in traffic
    for seed, golden in GOLDEN[name].items():
        assert first(traffic, seed, 12) == golden
    assert capsys.readouterr().out == ""


def test_sweep_10k_names_checked_disjoint_ranges_and_keeps_seven_of_its_pool():
    t = traffic_file("sweep_10k")
    slots, checked = t["slots"], t["slots_checked"]
    assert len(slots) >= 32 and len(set(slots)) == len(slots)
    assert all(isinstance(s, int) and s >= 0 for s in slots)
    assert slots == sorted(slots)  # the lowest clean slots, in order
    dropped = {int(s): lanes for s, lanes in checked["dropped"].items()}
    assert not set(slots) & set(dropped) and dropped[7] == [1471132]
    # every slot below the last listed one was either passed or dropped
    assert set(slots) | set(dropped) >= set(range(slots[-1] + 1))
    for slot, lanes in dropped.items():
        start = t["base_seed"] + slot * t["stride"]
        assert lanes and all(start <= s < start + checked["seeds_per_slot"]
                             for s in lanes)
    # what was checked is the most a campaign may consume (checks.py's
    # gap rule), ranges do not overlap, sit above the warm-up's and the
    # lane sample's seeds, and fit the engine's uint32
    batch = cells.load_cell("raft5_sweep").config["flags"]["batch"]
    assert checked["config"] == "raft5"
    assert checked["seeds_per_slot"] == t["seeds"] + -(-t["seeds"] // batch) * batch
    assert checked["seeds_per_slot"] <= t["stride"]
    assert t["warmup_seed"] + t["stride"] <= t["base_seed"]
    assert t["base_seed"] + (slots[-1] + 1) * t["stride"] < 2**32
    # seven eighths of the pool's work is what every ledger line measured
    assert t["pool"] == 8 and slots[:7] == list(range(7)) and slots[7] > 7
    pools = {seed: sorted(first(t, seed, 8)) for seed in SEEDS}
    assert len({tuple(p) for p in pools.values()}) == 1
    assert 1458752 not in first(t, 7, len(slots))  # slot 7 is never run


TIGHT_RAFT = {
    "name": "raft_tight", "machine": "raft", "hunt_machine": None,
    "flags": {"horizon": 5, "queue": 26, "faults": 2, "rng-stream": 3,
              "flight-recorder": True, "coverage": True, "batch": 16,
              "max-steps": 900},
    "check": {"sample_lanes": 8},
}
TIGHT_SWEEP = {"name": "sweep_tight", "kind": "sweep", "seeds": 16, "stride": 32,
               "pool": 2, "base_seed": 4096, "warmup_seed": 1024,
               "slots": [0, 1]}
#: Q 26 is too small for this lane of the tiny raft (its queue overflows);
#: it sits in slot 11 of the mix above, [4448, 4480)
LOST_LANE, LOST_SLOT = 4474, 11


def test_pool_check_passes_a_clean_range_and_names_the_lane_a_range_loses(
        tmp_path, capsys):
    root = tmp_path / "benchmark"
    for group, doc in (("configs", TIGHT_RAFT), ("traffic", TIGHT_SWEEP)):
        (root / group).mkdir(parents=True)
        (root / group / f"{doc['name']}.json").write_text(json.dumps(doc))
    os.symlink(os.path.join(cells.DATA_ROOT, "campaigns"), root / "campaigns")
    argv = ["--config", "raft_tight", "--traffic", "sweep_tight",
            "--data-root", str(root)]

    assert pool_check.main(argv) == 0  # the file's own slots: clean
    out = capsys.readouterr()
    table = json.loads(out.out.splitlines()[-1])
    assert table["clean"] == [0, 1] and table["dropped"] == {}
    assert table["seeds_per_slot"] == 32  # 16 + one 16-lane batch
    assert "LOSES" not in out.err
    for row in table["rows"]:
        assert row["completed"] >= 32 and row["seeds_consumed"] >= row["completed"]
        assert row["lost"] == [] and row["queue_hwm"] <= 26
    # two lanes of slot 0 run past --max-steps 900 (and past the lanes
    # pass's first cap: they were run again). The stream, which throws
    # its longest lanes away in flight, never reports them: listed, not lost
    assert table["rows"][0]["over_max_steps"] == [4099, 4123]
    assert table["rows"][0]["stream_abandoned"] == []
    assert table["rows"][1]["over_max_steps"] == []

    assert pool_check.main(argv + ["--slots", f"0,{LOST_SLOT}"]) == 1
    out = capsys.readouterr()
    table = json.loads(out.out.splitlines()[-1])
    assert table["clean"] == [0]
    assert table["dropped"] == {str(LOST_SLOT): [LOST_LANE]}
    assert f"slot {LOST_SLOT} LOSES lanes [{LOST_LANE}]" in out.err
    row = table["rows"][1]
    assert row["infra"] == [LOST_LANE] and row["stream_infra"] == [LOST_LANE]
    assert row["queue_hwm"] == 26  # a lane that overflows Q marks Q

    # not a sweep, not a file: refused before any engine is built
    (root / "traffic" / "hunt_tight.json").write_text(
        json.dumps(dict(TIGHT_SWEEP, name="hunt_tight", kind="hunt")))
    assert pool_check.main(["--config", "raft_tight", "--traffic", "hunt_tight",
                            "--data-root", str(root)]) == 2
    assert pool_check.main(["--config", "raft_tight", "--traffic", "nope",
                            "--data-root", str(root)]) == 2
    assert "refusing to run" in capsys.readouterr().err
