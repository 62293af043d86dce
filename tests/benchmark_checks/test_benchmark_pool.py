"""A sweep's named seed ranges (`slots`): what `harness.campaign_seeds`
makes of them (past the list's end it goes round the fresh ranges again,
never to a range nobody checked), that a traffic file without them
yields what it always did, what `sweep_10k.json` and `sweep_8k.json`
themselves promise, and `pool_check.py` — the
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


def test_named_slots_same_pool_another_order_then_the_list_then_round_the_fresh_ranges_again(
        capsys):
    traffic = {"pool": 3, "base_seed": 1000, "stride": 100, "warmup_seed": 500,
               "slots": [0, 2, 5, 6, 9]}
    n = 10 * len(traffic["slots"])
    runs = {seed: first(traffic, seed, n) for seed in SEEDS + (1, 2)}
    said = capsys.readouterr().out.splitlines()
    for r in runs.values():
        assert sorted(r[:3]) == [1000, 1200, 1500]  # the same pool
        assert r[3:5] == [1600, 1900]  # then the fresh ranges, in list order
        assert r[5:] == [1600, 1900] * ((n - 5) // 2) + [1600]  # and again
        assert set(r) == {1000, 1200, 1500, 1600, 1900}  # never off the list
    assert len({tuple(r[:3]) for r in runs.values()}) > 1  # another order
    # ONE announcement a run, whatever its length, and it says what the list held
    assert len(said) == len(runs)
    assert all(ln.startswith("benchmark: campaign 5 wraps") and " 5 checked ranges" in ln
               and "unchecked" not in ln for ln in said)
    first(traffic, 0, 5)  # none before the wrap
    assert capsys.readouterr().out == ""
    assert runs[7] == first(traffic, 7, n)
    # the shuffle is the one a file without `slots` gets: `slots` only
    # renames the positions
    plain = {k: v for k, v in traffic.items() if k != "slots"}
    for seed in SEEDS:
        assert [traffic["slots"][(s - 1000) // 100] for s in first(plain, seed, 5)] \
            == [(s - 1000) // 100 for s in runs[seed][:5]]


def test_a_list_that_is_the_pool_alone_wraps_over_the_pool_in_the_seeds_order(capsys):
    traffic = {"pool": 3, "base_seed": 1000, "stride": 100, "warmup_seed": 500,
               "slots": [0, 2, 5]}
    for seed in SEEDS:
        r = first(traffic, seed, 30)
        assert sorted(r[:3]) == [1000, 1200, 1500]
        assert r == r[:3] * 10
    said = capsys.readouterr().out.splitlines()
    assert len(said) == len(SEEDS)
    assert all(ln.startswith("benchmark: campaign 3 wraps") for ln in said)


SWEEP_CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]
               if traffic_file(w["traffic"])["kind"] == "sweep"]


@pytest.mark.parametrize("name", SWEEP_CELLS)
def test_a_sweep_cell_never_leaves_the_ranges_checked_under_its_configuration(
        name, capsys):
    cell = cells.load_cell(name)
    t = cell.traffic
    assert cells.unchecked_ranges(cell) is None
    assert cell.config_name in cells.checked_configs(t)
    named = {t["base_seed"] + s * t["stride"] for s in t["slots"]}
    length, pool = len(t["slots"]), t["pool"]
    for seed in SEEDS + (1, 2**32 + 5):
        r = first(t, seed, 10 * length)
        assert set(r) == named  # every range named is run, and no other
        assert sorted(r[:length]) == sorted(named)  # each once before any twice
        again = itertools.cycle(r[pool:length])  # the fresh ranges, in list order
        assert r[length:] == list(itertools.islice(again, 9 * length))
    said = capsys.readouterr().out.splitlines()
    assert len(said) == len(SEEDS) + 2 and all(f" {length} checked" in ln for ln in said)


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_the_first_twelve_campaigns_are_the_parents_sequences(name, capsys):
    """`hunt_highfind` and `sweep_100k` name no ranges and yield what they
    always did; `sweep_8k` names its own since PR 37, and its first twelve
    positions are the ones every ledger line was measured on."""
    traffic = traffic_file(name)
    assert ("slots" in traffic) == (name == "sweep_8k")
    for seed, golden in GOLDEN[name].items():
        assert first(traffic, seed, 12) == golden
    assert capsys.readouterr().out == ""


def assert_ranges_sound(t: dict, batch: int) -> None:
    """What every file that names its ranges promises, whatever its sizes."""
    slots, checked = t["slots"], t["slots_checked"]
    assert len(set(slots)) == len(slots)
    assert all(isinstance(s, int) and s >= 0 for s in slots)
    assert slots == sorted(slots)  # the lowest clean slots, in order
    # what was checked is the most a campaign may consume (checks.py's
    # gap rule), ranges do not overlap, sit above the warm-up's and the
    # lane sample's seeds, and fit the engine's uint32
    assert checked["seeds_per_slot"] == t["seeds"] + -(-t["seeds"] // batch) * batch
    assert checked["seeds_per_slot"] <= t["stride"]
    assert t["warmup_seed"] + t["stride"] <= t["base_seed"]
    assert t["base_seed"] + (slots[-1] + 1) * t["stride"] < 2**32


def dropped_by_slot(t: dict, dropped: dict) -> dict:
    """{slot: lanes} of a `dropped` table, each lane inside its slot and
    no dropped slot listed."""
    dropped = {int(s): lanes for s, lanes in dropped.items()}
    assert not set(t["slots"]) & set(dropped)
    # a lane lies in its slot: in the seeds the lanes pass ran, or past
    # them where pool_check's stream (twice a campaign's budget) reached it
    for slot, lanes in dropped.items():
        start = t["base_seed"] + slot * t["stride"]
        assert lanes and all(start <= s < start + t["stride"] for s in lanes)
    return dropped


def test_sweep_10k_names_checked_disjoint_ranges_and_keeps_seven_of_its_pool():
    t = traffic_file("sweep_10k")
    slots, checked = t["slots"], t["slots_checked"]
    # a 77-campaign window (PR 36 on four chips) and one a quarter faster
    assert len(slots) >= 96
    assert_ranges_sound(t, cells.load_cell("raft5_sweep").config["flags"]["batch"])
    dropped = dropped_by_slot(t, checked["dropped"])
    assert dropped[7] == [1471132]
    assert dropped[45] == [3977016] and dropped[48] == [4183821]  # ISSUE 37's leads
    # every slot below the last listed one was either passed or dropped
    assert set(slots) | set(dropped) == set(range(slots[-1] + 1))
    assert checked["config"] == "raft5" and checked["seeds_per_slot"] == 32768
    # seven eighths of the pool's work is what every ledger line measured
    assert t["pool"] == 8 and slots[:7] == list(range(7)) and slots[7] > 7
    pools = {seed: sorted(first(t, seed, 8)) for seed in SEEDS}
    assert len({tuple(p) for p in pools.values()}) == 1
    assert 1458752 not in first(t, 7, 3 * len(slots))  # slot 7 is never run
    # the 39 positions every line to PR 36 could reach are PR 31's
    assert slots[:39] == [s for s in range(42) if s not in (7, 15, 16)]


@pytest.mark.parametrize("config", ["raft5_fig8", "kafka_pc5", "kvraft5"])
def test_sweep_8k_names_ranges_checked_under_each_configuration_that_sweeps_with_it(
        config):
    t = traffic_file("sweep_8k")
    slots, checked = t["slots"], t["slots_checked"]
    assert len(slots) >= 12  # 1.5x a window of PR 36's program (5-8 campaigns)
    assert config in checked["config"] == cells.checked_configs(t)
    users = {w["config"] for w in cells.load_benchmark()["workloads"]
             if w["traffic"] == "sweep_8k"}
    assert users == set(checked["config"])
    doc = cells.load_json(os.path.join(cells.DATA_ROOT, "configs", config + ".json"))
    assert_ranges_sound(t, doc["flags"]["batch"])
    assert checked["seeds_per_slot"] == 16384
    # dropped: per configuration, the slots that lose a lane under it
    assert set(checked["dropped"]) == set(checked["config"])
    dropped_by_slot(t, checked["dropped"][config])
    lossy = {int(s) for by in checked["dropped"].values() for s in by}
    assert set(slots) | lossy == set(range(slots[-1] + 1)) and not set(slots) & lossy
    # the pool is what every ledger line measured: slots 0 and 1
    assert t["pool"] == 2 and slots[:2] == [0, 1]
    assert {tuple(sorted(first(t, seed, 2))) for seed in SEEDS} == {(1000000, 1065536)}


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
