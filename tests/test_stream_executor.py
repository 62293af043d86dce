"""Pipelined, donation-aware streaming executor (round 6).

The contract under test: the pipelined executor (device-side
supersegments + donated StreamCarry + K-deep async dispatch) runs the
BIT-IDENTICAL segment sequence as the r5 per-segment driver — same
completions, same failing-seed ring contents in the same order, same
seeds consumed — while its blocking host syncs drop from one-per-segment
to one-per-poll-cycle plus ring drains. Deliberately NOT marked slow:
this is the tier-1 fast gate's coverage of the streaming hot path, so
the configs are tiny (3-node machines, 16-lane batches).
"""

import jax
import jax.numpy as jnp
import pytest

from madsim_tpu.engine import Engine, EngineConfig, FaultPlan, OVERFLOW
from madsim_tpu.models.raft import RaftMachine
from madsim_tpu.parallel import make_mesh


class AlwaysFails(RaftMachine):
    """Every processed event violates the invariant: maximal pressure on
    the failing-seed rings (every lane fails every segment, so drains
    trigger constantly)."""

    def invariant(self, nodes, now_us):
        return jnp.bool_(False), jnp.int32(99)


@pytest.fixture(scope="module")
def raft_engine():
    return Engine(
        RaftMachine(num_nodes=3, log_capacity=4),
        EngineConfig(
            horizon_us=2_000_000,
            queue_capacity=48,
            faults=FaultPlan(n_faults=1, t_max_us=1_000_000),
        ),
    )


@pytest.fixture(scope="module")
def failing_engine():
    return Engine(
        AlwaysFails(3, 4), EngineConfig(horizon_us=1_000_000, queue_capacity=48)
    )


def _strip(out):
    """Everything but the executor telemetry (which legitimately differs
    between executors)."""
    return {k: v for k, v in out.items() if k != "stats"}


def test_pipelined_identical_to_r5_executor(failing_engine):
    """Ring-heavy workload (every lane fails every segment → multiple
    drains): the pipelined executor's findings, order included, match
    the r5 driver exactly."""
    kw = dict(batch=16, segment_steps=64, seed_start=100)
    new = failing_engine.run_stream(40, **kw)
    old = failing_engine.run_stream(40, pipelined=False, **kw)
    assert _strip(new) == _strip(old)
    assert new["stats"]["device_segments"] == old["stats"]["device_segments"]
    # gapless coverage survives the rewrite
    assert sorted(s for s, _ in new["failing"]) == list(
        range(100, 100 + new["seeds_consumed"])
    )
    assert new["stats"]["drains"] >= 2  # the drain path really ran


def test_donation_is_bit_identical(raft_engine):
    """Buffer donation is a pure aliasing optimization: same failing
    rings, same counters, with and without."""
    kw = dict(batch=16, segment_steps=64, seed_start=500)
    donated = raft_engine.run_stream(48, donate=True, **kw)
    copied = raft_engine.run_stream(48, donate=False, **kw)
    assert _strip(donated) == _strip(copied)
    assert donated["stats"]["donation"] and not copied["stats"]["donation"]


def test_dispatch_knobs_never_change_results(raft_engine):
    """The executed segment sequence is pinned by the on-device
    termination check, so supersegment size and dispatch depth are pure
    scheduling knobs — any combination yields bit-identical results."""
    kw = dict(batch=16, segment_steps=64, seed_start=900)
    outs = [
        raft_engine.run_stream(
            48, segments_per_dispatch=spd, dispatch_depth=dd, **kw
        )
        for spd, dd in [(1, 1), (4, 2), (8, 4)]
    ]
    assert _strip(outs[0]) == _strip(outs[1]) == _strip(outs[2])


def test_steady_state_host_syncs_drop(raft_engine):
    """The headline perf property: the r5 driver blocks once per
    segment; the pipelined executor blocks once per
    dispatch_depth * segments_per_dispatch segments (plus drains and the
    O(1) tail)."""
    kw = dict(batch=16, segment_steps=32, seed_start=2_000, max_steps=4_000)
    new = raft_engine.run_stream(64, segments_per_dispatch=8, dispatch_depth=4, **kw)
    old = raft_engine.run_stream(64, pipelined=False, **kw)
    segs = old["stats"]["device_segments"]
    assert segs > 4  # the workload actually streams multiple segments
    # r5: one blocking sync per segment + final poll + final drain
    assert old["stats"]["host_syncs"] == segs + 2
    # pipelined: one per poll cycle (32 segments) + drains + tail
    budget = -(-segs // 32) + new["stats"]["drains"] + 2
    assert new["stats"]["host_syncs"] <= budget
    assert new["stats"]["host_syncs"] < old["stats"]["host_syncs"]


def test_overflow_lands_in_infra_bucket_not_findings():
    """OVERFLOW lanes are fixed-shape capacity aborts (infrastructure
    artifacts), not protocol findings: run_stream reports them in a
    separate bucket so hunt output never interleaves them with invariant
    violations."""
    eng = Engine(
        RaftMachine(5, 8), EngineConfig(horizon_us=5_000_000, queue_capacity=16)
    )
    out = eng.run_stream(32, batch=16, segment_steps=64, max_steps=400)
    assert out["failing"] == []
    assert len(out["infra"]) >= 32
    assert all(code == OVERFLOW for _seed, code in out["infra"])


def test_repeated_run_stream_reuses_fns_and_is_deterministic(raft_engine):
    """Repeated run_stream calls at one shape reuse the cached jitted
    quartet and stay deterministic; the stats name the knobs that ran."""
    kw = dict(batch=16, segment_steps=64, segments_per_dispatch=4,
              dispatch_depth=2, seed_start=700)
    out1 = raft_engine.run_stream(32, **kw)
    n_fns = len(raft_engine._stream_cache)
    out2 = raft_engine.run_stream(32, **kw)
    assert len(raft_engine._stream_cache) == n_fns
    assert out1 == out2
    assert out1["completed"] >= 32
    assert out1["stats"]["pipelined"] and out1["stats"]["segments_per_dispatch"] == 4


def test_pipelined_sharded_matches_unsharded(raft_engine):
    """Mesh sharding composes with donation + supersegments: identical
    results, lane axis sharded."""
    cpus = jax.devices("cpu")
    if len(cpus) < 2:
        pytest.skip("no multi-device CPU backend")
    mesh = make_mesh(cpus)
    kw = dict(batch=8 * len(cpus), segment_steps=64, seed_start=3_000)
    sharded = raft_engine.run_stream(32, mesh=mesh, **kw)
    unsharded = raft_engine.run_stream(32, **kw)
    assert sharded == unsharded
