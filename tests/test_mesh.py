"""The lane-axis mesh rebuild: shard-count invariance and topology
refusal.

The contract under test: `run_stream(mesh=...)` executes one hunt as a
single jitted SPMD program over a 1-D "batch" mesh, with every
StreamCarry leaf pinned per its declared `analysis.srules.CARRY_AXES`
axis — and because lane key derivation is shard-independent and every
cross-lane fold is computed over the full logical [L] axis under GSPMD,
the results are BYTE-IDENTICAL at any device count, including the
unsharded (mesh=None) golden. conftest forces 8 virtual CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8) for the whole
suite, so 1/2/4/8-device meshes all run in-process. Deliberately NOT
marked slow: shard invariance is the correctness spine of the mesh
path and belongs in the tier-1 fast gate, so the shapes are tiny.
"""

import jax
import numpy as np
import pytest

from madsim_tpu import compile_cache
from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
from madsim_tpu.models.raft import RaftMachine
from madsim_tpu.parallel import make_mesh, shard_seeds


@pytest.fixture(scope="module")
def full_engine():
    """Every harvest surface on: coverage (map OR + buffered fold),
    flight recorder (fr folds/hwm), provenance — so the invariance
    check exercises all 17 registered collectives, not just the happy
    path."""
    return Engine(
        RaftMachine(num_nodes=3, log_capacity=4),
        EngineConfig(
            horizon_us=2_000_000,
            queue_capacity=64,
            faults=FaultPlan(n_faults=1, t_max_us=1_000_000),
            coverage=True,
            flight_recorder=True,
            provenance=True,
            rng_stream=3,
        ),
    )


STREAM_KW = dict(
    batch=16,
    segment_steps=48,
    seed_start=100,
    max_steps=400,
    segments_per_dispatch=4,
    dispatch_depth=2,
)


def _devices_or_skip(k):
    devs = jax.devices()
    if len(devs) < k:
        pytest.skip(f"needs {k} devices (conftest forces 8 on CPU)")
    return devs[:k]


def test_stream_shard_invariance(full_engine):
    """The golden: the same 32-seed hunt at 1, 2, 4, and 8 devices is
    byte-identical to the unsharded run — streams, final coverage map,
    failure rings, fr metrics, stats (incl. host_syncs) all equal."""
    golden = full_engine.run_stream(32, **STREAM_KW)
    gmap = golden.pop("coverage_map")
    for k in (1, 2, 4, 8):
        mesh = make_mesh(_devices_or_skip(k))
        out = full_engine.run_stream(32, mesh=mesh, **STREAM_KW)
        omap = out.pop("coverage_map")
        assert np.array_equal(omap, gmap), f"coverage map diverged at {k} devices"
        assert out == golden, f"stream results diverged at {k} devices"


def test_mesh_batch_divisibility():
    """A batch that doesn't split evenly over the mesh axis is refused
    with a clear error at seed placement, not a raw XLA one."""
    mesh = make_mesh(_devices_or_skip(8))
    import jax.numpy as jnp

    with pytest.raises(ValueError, match="multiple of"):
        shard_seeds(jnp.arange(12, dtype=jnp.uint32), mesh)


def test_aot_export_refuses_mesh(full_engine):
    """PR-16's serialized exports are traced unsharded; a mesh run must
    never produce or consume one. Belt: `_stream_fns(aot=True, mesh=..)`
    raises. Braces: the AOT cache subkey carries the device topology,
    so even artifacts on disk can't cross topologies."""
    mesh = make_mesh(_devices_or_skip(2))
    with pytest.raises(ValueError, match="mesh"):
        full_engine._stream_fns(
            segment_steps=48,
            max_steps=400,
            ring_capacity=64,
            batch=16,
            aot=True,
            mesh=mesh,
        )


def test_cache_subkey_discriminates_devices():
    """The warm-start subkey separates topologies: d1 vs d8 never share
    a directory (AOT refusal + fleet warm-compile grouping), and the
    devices part is omitted when unspecified (legacy keys unchanged)."""
    k1 = compile_cache.cache_subkey(rng_stream=3, lanes=16, devices=1)
    k8 = compile_cache.cache_subkey(rng_stream=3, lanes=16, devices=8)
    legacy = compile_cache.cache_subkey(rng_stream=3, lanes=16)
    assert k1 != k8
    assert "d1" in k1 and "d8" in k8
    assert "d1" not in legacy and "d8" not in legacy
    # jax-free rendering (the fleet control plane's mode) discriminates
    # the same way
    f1 = compile_cache.cache_subkey(rng_stream=3, lanes=16, devices=1, import_jax=False)
    f8 = compile_cache.cache_subkey(rng_stream=3, lanes=16, devices=8, import_jax=False)
    assert f1 != f8 and f1.startswith("jax-unknown")


def test_mesh_refuses_pallas_kernels():
    """pallas_call blocks GSPMD sharding propagation, so the lane-pinned
    layout can't cross it: a meshed run with the Pallas pop/megakernel
    on must refuse up front (CPU default is off, so this is opt-in
    misconfiguration)."""
    eng = Engine(
        RaftMachine(num_nodes=3, log_capacity=4),
        EngineConfig(horizon_us=2_000_000, queue_capacity=64),
        use_pallas_pop=True,
    )
    mesh = make_mesh(_devices_or_skip(2))
    with pytest.raises(ValueError, match="[Pp]allas"):
        eng.run_stream(32, mesh=mesh, **STREAM_KW)


def test_meshed_cov_fold_combines_shards_with_an_int32_max():
    """The cross-device leg of the coverage fold must not be a boolean
    reduce: on a TPU v5e (libtpu 0.0.34) the pred AllReduce it became
    kept one bit in four inside the full supersegment (PR 21, the first
    mesh run on hardware: 1,114 coverage slots for 4,337), while every
    CPU device count computes it correctly — so this pins the
    formulation, and `chip_smoke.py` on four chips pins the result."""
    import jax.numpy as jnp

    from madsim_tpu.ops.coverage import cov_fold_words

    rng = np.random.default_rng(7)
    maps = rng.integers(-(2**31), 2**31, size=(16, 8), dtype=np.int64)
    maps = (maps & rng.integers(-(2**31), 2**31, size=(16, 8))).astype(np.int32)
    want = np.bitwise_or.reduce(maps, axis=0)
    for shards in (1, 2, 4, 8):
        got = np.asarray(cov_fold_words(jnp.asarray(maps), shards=shards))
        assert np.array_equal(got, want), shards
    jaxpr = str(jax.make_jaxpr(lambda m: cov_fold_words(m, shards=4))(maps))
    assert "reduce_max" in jaxpr
    assert "bool" not in jaxpr and "reduce_or" not in jaxpr
