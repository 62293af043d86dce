"""The three step-path gates (rng_stream / clog_packed / pallas pop) are
result-preserving under their gates — each toggled OFF individually must
leave run results bit-identical (clog_packed, pallas_pop: identical to
the gate-ON run; rng_stream: v2 identical to the seed-era stream, pinned
separately in test_golden_streams.py, and v3 self-consistent across
executors and the replay path).

Also covers the persistent-compilation-cache wiring (satellite)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
from madsim_tpu.engine.replay import replay
from madsim_tpu.models.raft import RaftMachine

# all six fault kinds + real packet loss: every clog representation and
# every chaos-draw section of the RNG block is exercised
FULL_CHAOS = EngineConfig(
    horizon_us=2_000_000,
    queue_capacity=64,
    packet_loss_rate=0.01,
    faults=FaultPlan(
        n_faults=3, t_max_us=1_500_000, dur_min_us=100_000, dur_max_us=600_000,
        allow_dir_clog=True, allow_group=True, allow_storm=True, allow_delay=True,
    ),
)
BENCH_LIKE = EngineConfig(
    horizon_us=2_000_000,
    queue_capacity=32,
    faults=FaultPlan(n_faults=2, t_max_us=1_500_000, dur_min_us=100_000, dur_max_us=600_000),
)


def _machine():
    return RaftMachine(num_nodes=5, log_capacity=8)


# Gate-matrix parametrization: the FULL_CHAOS rows stay tier-1 (every
# chaos-draw section + both clog representations exercised, both stream
# versions); the BENCH_LIKE rows are the weaker half of the matrix —
# same gates over a strict subset of the chaos paths — and each costs a
# fresh ~15-20 s engine compile on the 1-core reference box, so they
# ride the slow tier (PR-7: the tier-1 wall time sat at the 870 s cap).
CFG_PARAMS = [
    pytest.param(FULL_CHAOS, id="full-chaos"),
    pytest.param(BENCH_LIKE, id="bench-like", marks=pytest.mark.slow),
]


def _run(engine, n=48, max_steps=1200):
    seeds = jnp.arange(n, dtype=jnp.uint32)
    return jax.jit(lambda s: engine.run_batch(s, max_steps))(seeds)


def _assert_results_equal(ra, rb):
    for name in ("done", "failed", "fail_code", "now_us", "steps", "msg_count"):
        a, b = getattr(ra, name), getattr(rb, name)
        assert bool((a == b).all()), f"{name} diverged"
    assert jax.tree.all(
        jax.tree.map(lambda a, b: bool((a == b).all()), ra.summary, rb.summary)
    )


@pytest.mark.parametrize("cfg", CFG_PARAMS)
@pytest.mark.parametrize("rng_stream", [2, 3], ids=["rng-v2", "rng-v3"])
def test_clog_packed_gate_bit_identical(cfg, rng_stream):
    cfg = dataclasses.replace(cfg, rng_stream=rng_stream)
    r_packed = _run(Engine(_machine(), cfg))
    r_bool = _run(Engine(_machine(), dataclasses.replace(cfg, clog_packed=False)))
    _assert_results_equal(r_packed, r_bool)


def test_pallas_pop_gate_bit_identical():
    # fused pop+gather (interpreter mode off-TPU) vs the XLA oracle
    cfg = dataclasses.replace(FULL_CHAOS, rng_stream=3)
    r_fused = _run(Engine(_machine(), cfg, use_pallas_pop=True), n=16, max_steps=300)
    r_xla = _run(Engine(_machine(), cfg, use_pallas_pop=False), n=16, max_steps=300)
    _assert_results_equal(r_fused, r_xla)


@pytest.mark.parametrize("cfg", CFG_PARAMS)
@pytest.mark.parametrize("rng_stream", [2, 3], ids=["rng-v2", "rng-v3"])
def test_flight_recorder_gate_off_bit_identical(cfg, rng_stream):
    """The PR-3 flight recorder (digest fold + checkpoint ring + metric
    counters in the step) must leave every simulation result bit-exactly
    unchanged — recorder ON vs OFF, across both stream versions. The
    gate-off path adds literally no ops (fr == {})."""
    cfg = dataclasses.replace(cfg, rng_stream=rng_stream)
    r_off = _run(Engine(_machine(), cfg))
    r_on = _run(
        Engine(
            _machine(),
            dataclasses.replace(
                cfg, flight_recorder=True, fr_digest_every=32, fr_digest_ring=8
            ),
        )
    )
    _assert_results_equal(r_off, r_on)
    assert r_off.fr == {} and r_on.fr  # recorder state only when gated on


def test_coverage_gate_off_bit_identical():
    """The PR-4 scenario-coverage gate (projection hash + per-lane map
    scatter in the step) must leave every simulation result bit-exactly
    unchanged — coverage ON vs OFF under the full chaos vocabulary. The
    map consumes no RNG words (stream-version independence is by
    construction; tests/test_coverage.py exercises the v2 default) and
    writes only its own state; gate-off carries cov == {} (literally no
    added ops). One config pair, not a matrix: tier-1 compile budget."""
    cfg = dataclasses.replace(FULL_CHAOS, rng_stream=3)
    r_off = _run(Engine(_machine(), cfg))
    r_on = _run(
        Engine(
            _machine(),
            dataclasses.replace(cfg, coverage=True, cov_slots_log2=12),
        )
    )
    _assert_results_equal(r_off, r_on)
    assert r_off.cov == {} and r_on.cov  # map state only when gated on


@pytest.mark.parametrize("rng_stream", [2, 3], ids=["rng-v2", "rng-v3"])
def test_provenance_gate_off_bit_identical(rng_stream):
    """The PR-7 causal-provenance gate (lineage words on every queued
    event/node + the violation-word capture) must leave every simulation
    result bit-exactly unchanged — provenance ON vs OFF, under both
    stream versions (it consumes no RNG words by construction; this
    asserts the dataflow adds no result-affecting ops either). Gate-off
    carries empty provenance leaves (literally no added ops). Small
    n/max_steps: compile cost dominates, the assertion doesn't need
    depth (tier-1 budget)."""
    cfg = dataclasses.replace(FULL_CHAOS, rng_stream=rng_stream)
    r_off = _run(Engine(_machine(), cfg), n=24, max_steps=600)
    r_on = _run(
        Engine(_machine(), dataclasses.replace(cfg, provenance=True)),
        n=24, max_steps=600,
    )
    _assert_results_equal(r_off, r_on)
    # lineage state materializes only under the gate
    assert r_off.fail_prov.shape == (24, 0) and r_on.fail_prov.shape == (24,)


def test_coverage_rejects_bad_slot_budget():
    with pytest.raises(ValueError, match="cov_slots_log2"):
        Engine(
            _machine(),
            dataclasses.replace(BENCH_LIKE, coverage=True, cov_slots_log2=5),
        )


@pytest.mark.slow
def test_rng_v3_stream_executor_and_replay_agree():
    """v3 results are executor-independent (batch vs stream) and the
    host replay reproduces a v3 device finding bit-identically — the
    same cross-engine contract v2 has. Slow tier (PR-7): compiles the
    whole streaming executor (~20 s on the reference box); tier-1 keeps
    the batch/replay v3 coverage via the golden pins + gate tests, and
    test_provenance's slow stream-harvest check exercises the same
    stream-vs-replay contract."""
    cfg = dataclasses.replace(FULL_CHAOS, rng_stream=3)
    eng = Engine(_machine(), cfg)
    out = eng.run_stream(96, batch=32, segment_steps=128, seed_start=0, max_steps=2500)
    assert out["completed"] >= 96
    res = _run(eng, n=96, max_steps=2500)
    stream_codes = dict(out["failing"] + out["infra"])
    batch_codes = {
        int(s): int(c)
        for s, c in zip(res.seeds.tolist(), res.fail_code.tolist())
        if bool(res.failed[int(s)])
    }
    assert stream_codes == batch_codes
    for seed, code in list(stream_codes.items())[:2]:
        rp = replay(eng, seed, max_steps=2500, trace=False)
        assert rp.failed and rp.fail_code == code


def test_rng_v3_changes_the_stream():
    """Sanity: v3 is a genuinely different stream (the gate is a
    VERSION, not a no-op) — the two versions must not accidentally
    alias, or the speedup would be fictional."""
    eng2 = Engine(_machine(), BENCH_LIKE)
    eng3 = Engine(_machine(), dataclasses.replace(BENCH_LIKE, rng_stream=3))
    r2, r3 = _run(eng2, n=64), _run(eng3, n=64)
    assert not bool((r2.now_us == r3.now_us).all())


def test_v3_word_budget_shrinks_with_config():
    """v3 sizes the block to what the config's fault-kind FLAGS can
    consume; v2 never changes shape (that IS the legacy contract). The
    layout is deliberately n_faults-independent — shrink bisects
    n_faults, and the stream + compiled replay must survive that."""
    m = _machine()  # MAX_MSGS = 4
    no_chaos = EngineConfig(
        queue_capacity=32, faults=FaultPlan(n_faults=0, allow_kill=False)
    )
    assert Engine(m, dataclasses.replace(no_chaos, rng_stream=3))._rng_layout.total_words == 8
    assert Engine(m, no_chaos)._rng_layout.total_words == 12
    full = dataclasses.replace(FULL_CHAOS, rng_stream=3)
    # handler 4 + lat 4 + drop 4 + spike 8 + restart 2
    assert Engine(m, full)._rng_layout.total_words == 22
    # n_faults-independence: same layout (and jit-cache key) for every
    # shrink candidate
    import dataclasses as dc

    shrunk = dc.replace(full, faults=dc.replace(full.faults, n_faults=0))
    assert Engine(m, shrunk)._rng_layout == Engine(m, full)._rng_layout


def test_corpus_roundtrip_records_gates():
    from madsim_tpu.engine import corpus

    cfg = dataclasses.replace(BENCH_LIKE, rng_stream=3, clog_packed=False)
    d = corpus.config_to_dict(cfg)
    assert d["rng_stream"] == 3 and d["clog_packed"] is False
    assert "compile_cache_dir" not in d  # host-side knob, never recorded
    # the megakernel is the same class: a perf knob the recording box
    # resolved, asserted bit-identical — entries must replay anywhere
    assert "pallas_megakernel" not in d
    back = corpus.config_from_dict(d)
    assert back.rng_stream == 3 and back.clog_packed is False
    # entries predating the gates decode to the legacy stream
    legacy = {k: v for k, v in d.items() if k not in ("rng_stream", "clog_packed")}
    assert corpus.config_from_dict(legacy).rng_stream == 2


def test_clog_packed_rejects_oversized_machines():
    class Wide(RaftMachine):
        pass

    m = Wide(num_nodes=5, log_capacity=8)
    m.NUM_NODES = 61
    with pytest.raises(ValueError, match="clog_packed"):
        Engine(m, EngineConfig(queue_capacity=256, faults=FaultPlan(n_faults=0)))


def test_strict_restart_gate_bit_identical():
    """Crash-with-amnesia for a machine whose durable_spec matches its
    hand-written restart hook (every honest shipped model): strict
    on/off must be bit-identical under kill/restart chaos — the generic
    wipe IS the model's own semantics, just contract-driven. (The
    divergence case — a model whose spec lies — is the bug detector,
    exercised in tests/test_chaos_palette.py.)"""
    r_off = _run(Engine(_machine(), BENCH_LIKE))
    r_on = _run(
        Engine(
            _machine(),
            dataclasses.replace(
                BENCH_LIKE,
                faults=dataclasses.replace(
                    BENCH_LIKE.faults, strict_restart=True
                ),
            ),
        )
    )
    _assert_results_equal(r_off, r_on)


def test_new_chaos_kinds_live_and_observable():
    """The whole 11-kind palette on at once (PR-5 pause + skew + dup +
    strict_restart and PR-6 torn + heal-asym, on top of FULL_CHAOS)
    with recorder + coverage: every new capability must show nonzero
    injection counters AND nonzero coverage in its own 4-bit-layout
    band — the 'is this chaos actually reachable' assertion. One engine
    covers all six (tier-1 compile budget); raft's durable_spec with no
    torn_spec means torn restarts degrade to the amnesia wipe, so the
    honest machine must also stay conviction-free."""
    import numpy as np

    from madsim_tpu.engine.core import K_HEAL_ASYM, K_PAUSE, K_SKEW, K_TORN
    from madsim_tpu.runtime.coverage import coverage_dict, unpack_map

    cfg = dataclasses.replace(
        FULL_CHAOS,
        rng_stream=3,
        # headroom for pause-window deferral pressure: deliveries to a
        # paused node park in their slots until resume
        queue_capacity=96,
        flight_recorder=True,
        fr_digest_every=64,
        fr_digest_ring=4,
        coverage=True,
        cov_slots_log2=12,
        faults=dataclasses.replace(
            FULL_CHAOS.faults,
            allow_pause=True,
            allow_skew=True,
            allow_dup=True,
            allow_torn=True,
            allow_heal_asym=True,
            strict_restart=True,
        ),
    )
    eng = Engine(_machine(), cfg)
    assert eng.cov_band_bits == 4
    res = _run(eng, n=48, max_steps=1200)
    assert not bool(res.failed.any()), set(res.fail_code.tolist())
    inj = res.fr["inj"].sum(axis=0).tolist()
    assert inj[K_PAUSE] > 0 and inj[K_SKEW] > 0, inj
    assert inj[K_TORN] > 0 and inj[K_HEAL_ASYM] > 0, inj
    assert int(res.fr["dup"].sum()) > 0
    assert int(res.fr["amnesia"].sum()) > 0
    m = unpack_map(
        np.bitwise_or.reduce(np.asarray(res.cov["map"]), axis=0), 12
    )
    bands = coverage_dict(m, 12, band_bits=4)["by_band"]
    for band in ("pause", "skew", "dup", "amnesia", "torn", "heal_asym"):
        assert bands[band] > 0, (band, bands)


def test_coverage_band4_needs_one_more_slot_bit():
    """The 4-bit banded layout (any PR-5 capability on) steals one mix
    bit, so the minimum map size rises from 2^7 to 2^8."""
    faults = dataclasses.replace(BENCH_LIKE.faults, allow_dup=True)
    with pytest.raises(ValueError, match="cov_slots_log2"):
        Engine(
            _machine(),
            dataclasses.replace(
                BENCH_LIKE, coverage=True, cov_slots_log2=7, faults=faults
            ),
        )
    # 2^7 stays legal for the legacy 3-bit layout
    Engine(
        _machine(),
        dataclasses.replace(BENCH_LIKE, coverage=True, cov_slots_log2=7),
    )


def test_compile_cache_wiring(tmp_path, monkeypatch):
    """Engine(config.compile_cache_dir) enables the persistent cache and
    compiles land in the directory. Process-global and first-dir-wins,
    so the test tolerates a cache already enabled by another test."""
    from madsim_tpu import compile_cache

    target = str(tmp_path / "jit-cache")
    monkeypatch.delenv("MADSIM_TPU_COMPILE_CACHE", raising=False)
    eng = Engine(
        _machine(),
        dataclasses.replace(BENCH_LIKE, compile_cache_dir=target),
    )
    active = compile_cache.active_compile_cache()
    assert active is not None
    _run(eng, n=8, max_steps=64)
    import os

    assert os.path.isdir(active)
    if active == os.path.abspath(target):  # first enabler in this process
        assert os.listdir(active), "no cache entries written"


def test_megakernel_gate_bit_identical():
    """The whole-event step megakernel (pop + gather + v3 RNG block +
    digest fold in one fused pass, interpreter mode off-TPU) vs the XLA
    oracle, end to end with the FULL 11-kind chaos palette plus
    recorder + coverage + provenance riding the step — every result
    leaf, every digest, every metric bit-identical. One engine pair
    (tier-1 compile budget); the per-kernel Q/P grid lives in
    tests/test_pallas.py."""
    cfg = dataclasses.replace(
        FULL_CHAOS,
        rng_stream=3,
        queue_capacity=96,
        flight_recorder=True,
        fr_digest_every=32,
        fr_digest_ring=4,
        coverage=True,
        cov_slots_log2=12,
        provenance=True,
        faults=dataclasses.replace(
            FULL_CHAOS.faults,
            allow_pause=True,
            allow_skew=True,
            allow_dup=True,
            allow_torn=True,
            allow_heal_asym=True,
            strict_restart=True,
        ),
    )
    eng_mk = Engine(_machine(), dataclasses.replace(cfg, pallas_megakernel=True))
    assert eng_mk.use_megakernel
    r_mk = _run(eng_mk, n=16, max_steps=300)
    eng_x = Engine(_machine(), dataclasses.replace(cfg, pallas_megakernel=False))
    assert not eng_x.use_megakernel
    r_x = _run(eng_x, n=16, max_steps=300)
    _assert_results_equal(r_mk, r_x)
    assert bool((r_mk.fail_prov == r_x.fail_prov).all())
    for k in r_x.fr:
        assert bool((r_mk.fr[k] == r_x.fr[k]).all()), k
    assert bool((r_mk.cov["map"] == r_x.cov["map"]).all())


def test_megakernel_requires_v3_stream():
    """Explicitly requesting the megakernel on a v2 engine is a config
    error (the kernel computes the counter-based block; v2's split
    chain cannot be); auto/env resolution instead degrades to OFF so
    legacy replays and shrink candidates keep working."""
    with pytest.raises(ValueError, match="pallas_megakernel"):
        Engine(
            _machine(),
            dataclasses.replace(BENCH_LIKE, rng_stream=2, pallas_megakernel=True),
        )
    eng = Engine(_machine(), dataclasses.replace(BENCH_LIKE, rng_stream=2))
    assert not eng.use_megakernel


def test_gate_off_segment_is_specialized():
    """The observability bargain, pinned at the HLO level: with every
    observability gate OFF the lowered streaming segment contains no
    digest arithmetic (the fold multipliers), no coverage popcount and
    no recorder/coverage/provenance operands — the gates compile to
    NOTHING, not to dead data movement. With the gates ON the same
    probes must appear (so the string-match is proven meaningful)."""
    import jax

    def lowered_segment_text(cfg):
        eng = Engine(_machine(), cfg)
        init_carry, segment, _, _ = eng._stream_fns(128, 2000, 64, 32)
        seeds = jnp.arange(32, dtype=jnp.uint32)
        carry_shape = jax.eval_shape(init_carry, seeds)
        return eng, segment.lower(carry_shape).as_text()

    off_cfg = dataclasses.replace(FULL_CHAOS, rng_stream=3)
    eng_off, off_txt = lowered_segment_text(off_cfg)
    # digest fold multipliers (core._DIGEST_M0/M1) — M1 doubles as the
    # coverage mix multiplier, so its absence also proves no slot hash;
    # the coverage mix SEED (0x9E3779B9) is the third probe. (popcnt is
    # deliberately not probed: the raft model's own vote-bitmask tally
    # legitimately popcounts inside the handler.)
    assert "2654435761" not in off_txt  # 0x9E3779B1 digest M0
    assert "2245273453" not in off_txt  # 0x85EBCA6B digest M1 / cov mix mult
    assert "2654435769" not in off_txt  # 0x9E3779B9 cov mix seed
    # dead operands pruned from the carry, not threaded as zeros
    carry = jax.eval_shape(
        eng_off._stream_fns(128, 2000, 64, 32)[0],
        jnp.arange(32, dtype=jnp.uint32),
    )
    assert carry.fr_metrics.shape == (0,)
    assert carry.cov_map.shape == (0,)
    assert carry.fail_provs.shape == (0,)
    assert carry.state.eq_prov.shape == (32, 0)
    assert carry.state.fr == {} and carry.state.cov == {}

    on_cfg = dataclasses.replace(
        FULL_CHAOS, rng_stream=3, flight_recorder=True, fr_digest_every=32,
        fr_digest_ring=4, coverage=True, cov_slots_log2=12, provenance=True,
    )
    eng_on, on_txt = lowered_segment_text(on_cfg)
    assert "2654435761" in on_txt and "2654435769" in on_txt
    carry_on = jax.eval_shape(
        eng_on._stream_fns(128, 2000, 64, 32)[0],
        jnp.arange(32, dtype=jnp.uint32),
    )
    assert carry_on.fr_metrics.shape != (0,)
    assert carry_on.state.eq_prov.shape == (32, on_cfg.queue_capacity)
