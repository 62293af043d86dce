"""`correct` is false when a seed is lost or reported twice, when a
dispatch was retried, when the lane sample disagrees, or when a filed
entry does not reproduce on the CPU — each shown on doctored inputs —
and the traffic generator gives every --seed the same work."""

import dataclasses
import itertools

import numpy as np

from benchmark import cells, checks, harness


def good_agg() -> dict:
    return {
        "completed": 40, "seeds_consumed": 48, "batches_run": 2,
        "failing": [(101, 7), (105, 7)], "infra": [(110, 3)],
        "abandoned": [120], "stats": {"dispatch_retries": 0},
    }


def test_a_whole_stream_has_no_problem():
    assert checks.stream_problems(good_agg(), 100, 32, 16, "t") == []


def test_a_lost_seed_is_a_problem():
    agg = dict(good_agg(), completed=30)
    assert any("30 of 32" in p for p in checks.stream_problems(agg, 100, 32, 16, "t"))
    agg = dict(good_agg(), seeds_consumed=400)  # a gap: consumed, never resolved
    assert any("gap" in p for p in checks.stream_problems(agg, 100, 32, 16, "t"))


def test_a_seed_reported_twice_or_from_nowhere_is_a_problem():
    agg = dict(good_agg(), abandoned=[101])
    assert any("twice" in p for p in checks.stream_problems(agg, 100, 32, 16, "t"))
    agg = dict(good_agg(), abandoned=[99])
    assert any("never entered" in p
               for p in checks.stream_problems(agg, 100, 32, 16, "t"))


def test_a_retried_dispatch_is_a_problem():
    agg = dict(good_agg(), stats={"dispatch_retries": 1})
    assert any("retried" in p for p in checks.stream_problems(agg, 100, 32, 16, "t"))


def test_lanes_that_differ_in_any_leaf_are_a_problem():
    a = {"fail_code": np.array([0, 7, 0]), "fr['d0']": np.array([1, 2, 3])}
    assert checks.lanes_differ(a, {k: v.copy() for k, v in a.items()}, "t") == []
    b = dict(a, **{"fr['d0']": np.array([1, 2, 4])})
    assert "fr['d0']" in checks.lanes_differ(a, b, "t")[0]
    assert checks.lanes_differ(a, {"fail_code": a["fail_code"]}, "t")


@dataclasses.dataclass
class Entry:
    seed: int
    fail_code: int


def hunt_kind():
    cell = next(c for c in (cells.load_cell(w["name"])
                            for w in cells.load_benchmark()["workloads"])
                if c.kind == "hunt")
    return cells.load_campaign(cell)


def test_a_hunt_that_files_nothing_or_does_not_reproduce_is_a_problem():
    hunt = hunt_kind()
    failing = [(101, 204), (105, 204)]
    ok = dict(entries=[Entry(101, 204)], failing=failing, rc_hunt=1,
              rc_regress=0, rc_audit=0, limit=1, what="t")
    assert hunt.entry_problems(**ok) == []
    assert hunt.entry_problems(**dict(ok, rc_regress=1))  # CPU replay differs
    assert hunt.entry_problems(**dict(ok, rc_audit=1))  # digest trail differs
    assert hunt.entry_problems(**dict(ok, entries=[]))  # filed nothing
    assert hunt.entry_problems(**dict(ok, entries=[Entry(101, 205)]))  # other code
    assert hunt.entry_problems(**dict(ok, failing=[], rc_hunt=0))  # found nothing


def test_find_s_is_the_median_of_whole_hunts_and_a_bad_hunt_counts_failed():
    hunt = hunt_kind()
    records = [{"find_s": s, "problems": p} for s, p in
               ((7.0, []), (6.0, []), (30.0, ["x"]), (6.5, []), (7.5, []))]
    assert hunt.end_to_end(records) == {"find_s": 7.0}
    assert hunt.counts(records) == (5, 1)


def test_every_seed_runs_the_same_pool_in_another_order():
    traffic = {"pool": 6, "base_seed": 1000, "stride": 100, "warmup_seed": 500}
    runs = {seed: list(itertools.islice(harness.campaign_seeds(traffic, seed), 9))
            for seed in (0, 1, 2**31 + 11)}
    pools = {seed: sorted(r[:6]) for seed, r in runs.items()}
    assert len({tuple(p) for p in pools.values()}) == 1  # the same work
    assert len({tuple(r[:6]) for r in runs.values()}) > 1  # another order
    for r in runs.values():  # past the pool: fresh seeds, never a repeat
        assert len(set(r)) == len(r) and r[6:] == [1600, 1700, 1800]
    assert runs[1] == list(itertools.islice(harness.campaign_seeds(traffic, 1), 9))
    for seed in (0, 2**31 + 11):
        start = harness.sample_seed_start(traffic, seed, 64)
        assert 0 <= start and start + 64 <= traffic["warmup_seed"]


class FakeSession:
    """Stands in for drive.Session: the first one saw a cold cache."""

    made = 0

    def __init__(self, workdir, traced=False):
        type(self).made += 1
        self.cache_misses = [1.0] if type(self).made == 1 else []

    def listen_for_compiles(self):
        pass


class FakeCampaign:
    def __init__(self):
        self.ran = []

    def run_campaign(self, session, cell, seed_start, index):
        self.ran.append((session, seed_start, index))
        return {"problems": [], "t0": 0.0, "t1": 1.0}


def test_a_cold_cache_runs_the_pool_unmeasured_and_sets_up_again(monkeypatch):
    traffic = {"pool": 3, "base_seed": 1000, "stride": 100, "warmup_seed": 500,
               "prewarm_pool": True}
    cell = cells.Cell("c", 1, "cfg", "mix", {}, traffic, (), (), "")
    monkeypatch.setattr(harness.drive, "Session", FakeSession)
    FakeSession.made = 0
    camp = FakeCampaign()
    session, warmup, unmeasured = harness.set_up(cell, camp, "/nowhere", False)
    starts = [s for _sess, s, _i in camp.ran]
    assert starts[0] == 500 and starts[-1] == 500  # set up, then set up again
    assert sorted(starts[1:-1]) == [1000, 1100, 1200]  # the whole pool between
    assert len(unmeasured) == 5 and warmup is unmeasured[-1]
    assert camp.ran[-1][0] is session and camp.ran[0][0] is not session
    # a warm cache, or a mix whose campaigns share their programs: once
    camp = FakeCampaign()
    harness.set_up(cell, camp, "/nowhere", False)
    assert [s for _sess, s, _i in camp.ran] == [500]
    FakeSession.made = 0
    camp = FakeCampaign()
    sweep = cells.Cell("c", 1, "cfg", "mix", {}, dict(traffic, prewarm_pool=False),
                       (), (), "")
    harness.set_up(sweep, camp, "/nowhere", False)
    assert [s for _sess, s, _i in camp.ran] == [500]
