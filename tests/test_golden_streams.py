"""Golden RNG word streams + fault schedules, pinned as literal constants.

Behavioral replay (corpus regress, pinned-seed tests) guards legacy
seeds indirectly; these constants guard them DIRECTLY: the v2 step-word
stream, the v1/v2 fault-schedule derivations, and the v3 counter stream
are each pinned bit-for-bit. If any engine change disturbs a pinned
stream, this file fails before a single corpus entry gets a chance to
drift — the rng_stream=3 gate (and anything after it) provably cannot
touch the legacy streams.

History (PR-3, the corpus-rot incident): the constants here were
originally captured at PR-1 HEAD (e0405fb) — in an environment where
jax's `jax_threefry_partitionable` flag defaulted FALSE. The corpus and
slow-seed 66531 were recorded earlier, on a box whose newer jax
defaulted it TRUE, producing different split/bits streams for the same
seed; the flag gap — not any engine edit — was the whole "corpus rot"
(found by bisection in PR 3). The engine now pins
partitionable=True in ops/step_rng.py (the recording-era value and the
one modern jax keeps), and the constants below are the re-capture under
that pinned lowering — i.e. the restored ORIGINAL seed-era streams.
With the lowering pinned, a deliberate stream change must ship as a new
version, never as an edit to these numbers.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from madsim_tpu.engine import Engine, EngineConfig, FaultPlan
from madsim_tpu.models.raft import RaftMachine
from madsim_tpu.ops.step_rng import (
    RNG_STREAM_COUNTER,
    RNG_STREAM_LEGACY,
    layout_for,
    step_words,
    step_words_v3,
)

# --- pinned constants ------------------------------------------------------

# v2 step words: handler_rand_words=4, MAX_MSGS=4, allow_delay off
# => 12-word block; key chain PRNGKey(seed) -> split(3) -> per-step
# split(3)+bits. Re-captured under the pinned partitionable lowering
# (PR-3) — the restored seed-era stream.
V2_WORDS = {
    7: [
        [4241556475, 84765514, 193814917, 4022430017, 1899920453, 4270662650,
         3438644710, 482149783, 3504413964, 2380566562, 1683184507, 3477902931],
        [3620214620, 1532762980, 674263535, 631928992, 612896602, 2081840896,
         2783207604, 1313509888, 732748563, 922991306, 564573486, 2599884155],
    ],
    123: [
        [135492065, 1353318086, 2088731245, 1196048, 2557717920, 1222849717,
         567684486, 2729488727, 654290142, 1887700272, 3147832536, 3759350190],
        [994083955, 2970041183, 540460582, 1847628849, 842695244, 4247492917,
         2100597832, 894227792, 1875384957, 1343808822, 2415306344, 1404810419],
    ],
}
V2_K_RESTART = {
    7: [[2068379011, 934402480], [691513977, 469030390]],
    123: [[2948281090, 2785986219], [3753851117, 1392532467]],
}

# Fault schedules for RaftMachine(5), queue_capacity=32,
# FaultPlan(n_faults=2, t_max_us=3_000_000, dur 200_000..800_000):
# event-queue rows [5, 9) of init_lane. Re-captured under the pinned
# partitionable lowering (PR-3).
V1_FAULTS = FaultPlan(n_faults=2, t_max_us=3_000_000, dur_min_us=200_000, dur_max_us=800_000)
V2_FAULTS = dataclasses.replace(
    V1_FAULTS, allow_dir_clog=True, allow_group=True, allow_storm=True
)
V1_SCHED = {
    7: {
        "time": [2359908, 2901252, 2321832, 2529284],
        "seq": [5, 6, 7, 8],
        "node": [2, 2, 2, 2],
        "pay": [[2, 2, 0, 0, 0, 0], [3, 2, 0, 0, 0, 0],
                [0, 2, 3, 0, 0, 0], [1, 2, 3, 0, 0, 0]],
    },
    123: {
        "time": [2025571, 2552840, 2104602, 2529175],
        "seq": [5, 6, 7, 8],
        "node": [1, 1, 3, 3],
        "pay": [[2, 1, 2, 0, 0, 0], [3, 1, 2, 0, 0, 0],
                [2, 3, 2, 0, 0, 0], [3, 3, 2, 0, 0, 0]],
    },
}
V2_SCHED = {
    7: {
        "time": [2359908, 2901252, 2321832, 2529284],
        "seq": [5, 6, 7, 8],
        "node": [2, 2, 2, 2],
        "pay": [[6, 17, 0, 0, 0, 0], [7, 17, 0, 0, 0, 0],
                [0, 2, 3, 0, 0, 0], [1, 2, 3, 0, 0, 0]],
    },
    123: {
        "time": [2025571, 2552840, 2104602, 2529175],
        "seq": [5, 6, 7, 8],
        "node": [1, 1, 3, 3],
        "pay": [[4, 1, 2, 0, 0, 0], [5, 1, 2, 0, 0, 0],
                [8, 52428, 2, 0, 0, 0], [9, 52428, 2, 0, 0, 0]],
    },
}

# v3 counter stream: same (4, 4, no-delay) config with kill enabled
# => 10-word block [handler 4 | lat 4 | restart 2];
# words(key, step) = threefry2x32(key, step*10 + iota(10)). The raw
# threefry kernel is partitionable-independent, but the lane key above
# it is not — re-captured with the pinned lowering (PR-3).
V3_WORDS = {
    7: [
        [3728983260, 26083367, 2944131905, 213569972, 1554746844, 3940825189,
         4057694018, 4138724339, 1091535129, 937531743],
        [175129385, 3377294044, 3814277806, 394252965, 140491592, 1901111588,
         1746438459, 257038357, 1010648607, 2318744050],
    ],
    123: [
        [1663137049, 960457938, 1916282871, 736501441, 3805247166, 785596073,
         1835670850, 3822876231, 582579697, 3441787572],
        [2546113118, 3690581579, 3432516389, 4176221090, 321841896, 129854500,
         3465149680, 1630024501, 952624321, 80431547],
    ],
}


# v3 counter stream WITH the PR-5 duplication section: same (4, 4,
# no-delay, kill) config plus allow_dup => 18-word block
# [handler 4 | lat 4 | restart 2 | dup 8]. W changes, so this is a NEW
# pinned stream (counter = step*18 + iota); the dup-OFF block above is
# untouched — that is the byte-stability contract.
V3_DUP_WORDS = {
    7: [
        [651372970, 1641003165, 4259759113, 830191501, 2543082826, 1701606646,
         1850397451, 383445794, 1466414099, 558659640, 2668535539, 2285691388,
         720074552, 4243045693, 1742119742, 4243794367, 2215412076, 155270363],
        [1777434092, 644396529, 3913584264, 469921086, 3716644114, 2027927174,
         4258361963, 3767944336, 736985225, 2140010, 3143326239, 3257841404,
         2379367988, 4092191589, 4100656410, 3831774530, 914001907, 2578195557],
    ],
    123: [
        [1061889091, 2343006490, 3997153370, 3747912777, 2645534252, 3709234104,
         2208487181, 1968141284, 3608368773, 3262677698, 2978737244, 3737086252,
         3332214997, 3984418987, 3686978842, 325655645, 258537910, 848770202],
        [1345064064, 818209895, 3795277425, 1191277824, 3307115550, 1697939720,
         2348577852, 3986674684, 1162353679, 3478757770, 2153672204, 713638025,
         3377012704, 2482713552, 2442345633, 3869989311, 2766960863, 2487333485],
    ],
}

# v2 + dup, step 0, seed 7: the first 12 words must BE V2_WORDS[7][0]
# (the dup section rides the tail; jax.random.bits extends the counter,
# so the legacy prefix is untouched) — pinned tail words follow.
V2_DUP_TAIL_7 = [1537568898, 988553731, 2699239489, 3125584811,
                 2504740702, 1895120738, 2569829754, 4011237394]

# Window-kind (pause/skew) fault schedules. The extra per-fault draw
# (the skew q10 factor) shifts the k_faults chain, so schedules with
# window kinds enabled are a NEW pinned derivation; V1_SCHED/V2_SCHED
# above must keep passing untouched — that is the off-bit-stability
# proof. PAUSE rows pin arg2 = resume time (t + dur); SKEW rows pin
# arg2 = the drawn q10 factor.
WINDOW_FAULTS = dataclasses.replace(
    V2_FAULTS, allow_pause=True, allow_skew=True
)
WINDOW_SCHED = {
    7: {
        "time": [2359908, 2901252, 1011953, 1349725],
        "seq": [5, 6, 7, 8],
        "node": [2, 2, 0, 0],
        "pay": [[8, 52428, 0, 0, 0, 0], [9, 52428, 0, 0, 0, 0],
                [2, 0, 3, 0, 0, 0], [3, 0, 3, 0, 0, 0]],
    },
    123: {
        "time": [2025571, 2552840, 1046676, 1496377],
        "seq": [5, 6, 7, 8],
        "node": [1, 1, 3, 3],
        "pay": [[2, 1, 2, 0, 0, 0], [3, 1, 2, 0, 0, 0],
                [2, 3, 2, 0, 0, 0], [3, 3, 2, 0, 0, 0]],
    },
}
PAUSE_ONLY_ROWS_7 = {
    "time": [359908, 701252], "node": [2, 2],
    "pay": [[12, 2, 701252, 0, 0, 0], [13, 2, 701252, 0, 0, 0]],
}
SKEW_ONLY_ROWS_7 = {
    "time": [359908, 701252], "node": [2, 2],
    "pay": [[14, 2, 680, 0, 0, 0], [15, 2, 680, 0, 0, 0]],
}


# v3 counter stream WITH the PR-6 torn-write salt section: (4, 4,
# no-delay, kill) plus allow_torn => 11-word block
# [handler 4 | lat 4 | restart 2 | torn 1]. New W, new pinned stream;
# the torn-OFF block (V3_WORDS) is untouched — the byte-stability
# contract, again.
V3_TORN_WORDS = {
    7: [
        [2686112139, 1920907495, 3117116237, 1839934677, 1453259340, 1192845063,
         3456765616, 1606147535, 3603694514, 2566954649, 584178859],
        [1281725469, 2899835270, 3407625762, 1157853032, 3943749771, 3821801872,
         720138553, 690176044, 108529684, 1925277224, 876130989],
    ],
    123: [
        [1497626296, 220333688, 3958732928, 105686110, 3354259625, 897652912,
         407698561, 1257635799, 1854429325, 2521537040, 3730749344],
        [4270409091, 535029018, 814983135, 2487286935, 4015632930, 797900295,
         1741178096, 1288928074, 3262815166, 1673231734, 299123086],
    ],
}

# v2 + torn, steps 0-1: the first 12 words must BE V2_WORDS (the torn
# salt rides the tail; jax.random.bits extends the counter, so the
# legacy prefix is untouched) — the pinned tail words follow. Note
# V2_TORN_TAIL[7][0] == V2_DUP_TAIL_7[0]: with dup off the torn section
# claims block word 12, and the counter-extension property makes word 12
# the same bits no matter which section owns it.
V2_TORN_TAIL = {
    7: [1537568898, 2579175849],
    123: [4199490399, 379683286],
}

# Storage-kind (torn/heal-asym) fault schedules. The extra per-fault
# draw (the torn damage mask / heal-asym second duration) shifts the
# k_faults chain, and heal-asym gives every fault a THIRD slot (invalid
# for other kinds), so schedules with storage kinds enabled are a NEW
# pinned derivation; V1_SCHED/V2_SCHED/WINDOW_SCHED passing untouched is
# the off-bit-stability proof. TORN rows pin arg2 = the damage mask;
# HASYM rows pin the op-18 both-way clog plus the two op-19 one-way
# heals at independently drawn times.
STORAGE_FAULTS = dataclasses.replace(
    WINDOW_FAULTS, allow_torn=True, allow_heal_asym=True
)
STORAGE_SCHED = {
    7: {
        "time": [2359908, 2901252, 2971861, 1434940, 1923642, 1955941],
        "seq": [5, 6, 7, 8, 9, 10],
        "node": [2, 2, 2, 0, 0, 0],
        "valid": [True, True, False, True, True, True],
        "pay": [[12, 2, 2901252, 0, 0, 0], [13, 2, 2901252, 0, 0, 0],
                [19, 0, 2, 0, 0, 0], [18, 0, 1, 0, 0, 0],
                [19, 0, 1, 0, 0, 0], [19, 1, 0, 0, 0, 0]],
    },
    123: {
        "time": [2025571, 2552840, 2672247, 1484037, 2082825, 1881822],
        "seq": [5, 6, 7, 8, 9, 10],
        "node": [1, 1, 1, 1, 1, 1],
        "valid": [True, True, False, True, True, False],
        "pay": [[12, 1, 2552840, 0, 0, 0], [13, 1, 2552840, 0, 0, 0],
                [19, 2, 1, 0, 0, 0], [2, 1, 2, 0, 0, 0],
                [3, 1, 2, 0, 0, 0], [19, 2, 1, 0, 0, 0]],
    },
}
TORN_ONLY_ROWS_7 = {
    "time": [359908, 701252], "node": [2, 2], "valid": [True, True],
    "pay": [[16, 2, 1754838184, 0, 0, 0], [17, 2, 1754838184, 0, 0, 0]],
}
HASYM_ONLY_ROWS_7 = {
    "time": [359908, 701252, 681740], "node": [2, 2, 2],
    "valid": [True, True, True],
    "pay": [[18, 2, 0, 0, 0, 0], [19, 2, 0, 0, 0, 0], [19, 0, 2, 0, 0, 0]],
}


def _lane_key(seed):
    key = jax.random.PRNGKey(seed)
    key, _k_init, _k_faults = jax.random.split(key, 3)
    return key


def _v2_layout():
    return layout_for(
        RNG_STREAM_LEGACY, 4, 4,
        loss_possible=False, spike_possible=False, delay_enabled=False,
        restart_possible=True,
    )


def _v3_layout():
    return layout_for(
        RNG_STREAM_COUNTER, 4, 4,
        loss_possible=False, spike_possible=False, delay_enabled=False,
        restart_possible=True,
    )


def test_v2_step_words_pinned():
    layout = _v2_layout()
    assert layout.total_words == 12
    for seed, expect in V2_WORDS.items():
        key = _lane_key(seed)
        for step in range(2):
            key, words, k_restart = step_words(key, jnp.int32(step), layout)
            assert words.tolist() == expect[step], (seed, step)
            assert k_restart.tolist() == V2_K_RESTART[seed][step], (seed, step)


def test_v3_step_words_pinned():
    layout = _v3_layout()
    assert layout.total_words == 10
    assert layout.restart_off == 8
    for seed, expect in V3_WORDS.items():
        key = _lane_key(seed)
        for step in range(2):
            new_key, words, k_restart = step_words_v3(key, jnp.int32(step), layout)
            assert words.tolist() == expect[step], (seed, step)
            # immutable lane key + restart key = trailing block words
            assert new_key.tolist() == key.tolist()
            assert k_restart.tolist() == words[8:10].tolist()


@pytest.mark.parametrize(
    "faults,sched", [(V1_FAULTS, V1_SCHED), (V2_FAULTS, V2_SCHED)],
    ids=["v1-derivation", "v2-derivation"],
)
@pytest.mark.parametrize("rng_stream", [2, 3], ids=["rng-v2", "rng-v3"])
def test_fault_schedules_pinned(faults, sched, rng_stream):
    """The fault-plan derivation is pinned AND independent of the step
    stream version: flipping rng_stream=3 provably cannot disturb a
    recorded schedule (both versions must reproduce the PR-1 constants)."""
    eng = Engine(
        RaftMachine(num_nodes=5, log_capacity=8),
        EngineConfig(
            horizon_us=5_000_000, queue_capacity=32, faults=faults,
            rng_stream=rng_stream,
        ),
    )
    for seed, expect in sched.items():
        s = eng.init_lane(seed)
        rows = slice(5, 5 + 2 * faults.n_faults)
        assert s.eq_time[rows].tolist() == expect["time"], seed
        assert s.eq_seq[rows].tolist() == expect["seq"], seed
        assert s.eq_node[rows].tolist() == expect["node"], seed
        assert s.eq_payload[rows].tolist() == expect["pay"], seed
        assert bool(s.eq_valid[rows].all())


def test_dup_section_rides_the_tail():
    """The duplication section appends to BOTH layouts without moving an
    existing offset — the off-bit-stability proof at the layout level."""
    base3, dup3 = _v3_layout(), layout_for(
        RNG_STREAM_COUNTER, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, dup_possible=True,
    )
    assert (dup3.lat_off, dup3.restart_off) == (base3.lat_off, base3.restart_off)
    assert dup3.dup_off == base3.total_words == 10
    assert dup3.total_words == 18
    base2, dup2 = _v2_layout(), layout_for(
        RNG_STREAM_LEGACY, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, dup_possible=True,
    )
    assert (dup2.lat_off, dup2.drop_off) == (base2.lat_off, base2.drop_off)
    assert dup2.dup_off == base2.total_words == 12
    assert dup2.total_words == 20


def test_v3_dup_step_words_pinned():
    layout = layout_for(
        RNG_STREAM_COUNTER, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, dup_possible=True,
    )
    for seed, expect in V3_DUP_WORDS.items():
        key = _lane_key(seed)
        for step in range(2):
            _k, words, k_restart = step_words_v3(key, jnp.int32(step), layout)
            assert words.tolist() == expect[step], (seed, step)
            # restart key still reads from offset 8 — dup is pure tail
            assert k_restart.tolist() == words[8:10].tolist()


def test_v2_dup_prefix_is_the_legacy_stream():
    """v2 + dup: the first 12 words of the 20-word block are bit-exactly
    the pinned legacy block (same key chain, counter extended), and the
    restart key is untouched — recorded v2 seeds cannot notice the dup
    section existing."""
    layout = layout_for(
        RNG_STREAM_LEGACY, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, dup_possible=True,
    )
    key = _lane_key(7)
    _k, words, k_restart = step_words(key, jnp.int32(0), layout)
    assert words.tolist()[:12] == V2_WORDS[7][0]
    assert words.tolist()[12:] == V2_DUP_TAIL_7
    assert k_restart.tolist() == V2_K_RESTART[7][0]


def test_window_kind_fault_schedules_pinned():
    """The pause/skew derivation (one extra per-fault draw) is pinned:
    the mixed-vocabulary schedule, plus pause-only rows (arg2 = resume
    time) and skew-only rows (arg2 = q10 factor). V1_SCHED/V2_SCHED
    passing above is the proof the extra draw is invisible with the
    window kinds off."""
    eng = Engine(
        RaftMachine(num_nodes=5, log_capacity=8),
        EngineConfig(
            horizon_us=5_000_000, queue_capacity=32, faults=WINDOW_FAULTS
        ),
    )
    for seed, expect in WINDOW_SCHED.items():
        s = eng.init_lane(seed)
        rows = slice(5, 9)
        assert s.eq_time[rows].tolist() == expect["time"], seed
        assert s.eq_seq[rows].tolist() == expect["seq"], seed
        assert s.eq_node[rows].tolist() == expect["node"], seed
        assert s.eq_payload[rows].tolist() == expect["pay"], seed
    window = dict(
        n_faults=1, allow_partition=False, allow_kill=False,
        t_min_us=200_000, t_max_us=600_000,
        dur_min_us=200_000, dur_max_us=400_000,
    )
    for kind_flags, expect in (
        (dict(allow_pause=True), PAUSE_ONLY_ROWS_7),
        (dict(allow_skew=True), SKEW_ONLY_ROWS_7),
    ):
        eng = Engine(
            RaftMachine(num_nodes=5, log_capacity=8),
            EngineConfig(
                horizon_us=2_000_000, queue_capacity=32,
                faults=FaultPlan(**window, **kind_flags),
            ),
        )
        s = eng.init_lane(7)
        rows = slice(5, 7)
        assert s.eq_time[rows].tolist() == expect["time"], kind_flags
        assert s.eq_node[rows].tolist() == expect["node"], kind_flags
        assert s.eq_payload[rows].tolist() == expect["pay"], kind_flags


def test_torn_section_rides_the_tail():
    """The torn salt section appends AFTER the dup section at the very
    tail of both layouts without moving an existing offset — the
    off-bit-stability proof at the layout level."""
    base3 = _v3_layout()
    torn3 = layout_for(
        RNG_STREAM_COUNTER, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, torn_possible=True,
    )
    assert (torn3.lat_off, torn3.restart_off) == (base3.lat_off, base3.restart_off)
    assert torn3.torn_off == base3.total_words == 10
    assert torn3.total_words == 11
    both3 = layout_for(
        RNG_STREAM_COUNTER, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, dup_possible=True,
        torn_possible=True,
    )
    assert (both3.dup_off, both3.torn_off, both3.total_words) == (10, 18, 19)
    base2 = _v2_layout()
    torn2 = layout_for(
        RNG_STREAM_LEGACY, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, torn_possible=True,
    )
    assert (torn2.lat_off, torn2.drop_off) == (base2.lat_off, base2.drop_off)
    assert torn2.torn_off == base2.total_words == 12
    assert torn2.total_words == 13
    both2 = layout_for(
        RNG_STREAM_LEGACY, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, dup_possible=True,
        torn_possible=True,
    )
    assert (both2.dup_off, both2.torn_off, both2.total_words) == (12, 20, 21)


def test_v3_torn_step_words_pinned():
    layout = layout_for(
        RNG_STREAM_COUNTER, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, torn_possible=True,
    )
    for seed, expect in V3_TORN_WORDS.items():
        key = _lane_key(seed)
        for step in range(2):
            _k, words, k_restart = step_words_v3(key, jnp.int32(step), layout)
            assert words.tolist() == expect[step], (seed, step)
            # restart key still reads from offset 8 — torn is pure tail
            assert k_restart.tolist() == words[8:10].tolist()


def test_v2_torn_prefix_is_the_legacy_stream():
    """v2 + torn: the first 12 words of the 13-word block are bit-exactly
    the pinned legacy block and the restart key is untouched — recorded
    v2 seeds cannot notice the torn section existing."""
    layout = layout_for(
        RNG_STREAM_LEGACY, 4, 4, loss_possible=False, spike_possible=False,
        delay_enabled=False, restart_possible=True, torn_possible=True,
    )
    for seed, tails in V2_TORN_TAIL.items():
        key = _lane_key(seed)
        for step in range(2):
            key, words, k_restart = step_words(key, jnp.int32(step), layout)
            assert words.tolist()[:12] == V2_WORDS[seed][step], (seed, step)
            assert int(words[12]) == tails[step], (seed, step)
            assert k_restart.tolist() == V2_K_RESTART[seed][step], (seed, step)


def test_storage_kind_fault_schedules_pinned():
    """The torn/heal-asym derivation (one extra per-fault draw + the
    heal-asym third slot) is pinned: the mixed-vocabulary schedule (note
    the third slot is VALID only for heal-asym faults), plus torn-only
    rows (arg2 = the damage mask on both apply and undo) and
    heal-asym-only rows (op 18 both-way clog, then op 19 heals a->b and
    b->a at independently drawn times). V1/V2/WINDOW schedules passing
    above is the proof the extra draw and slot are invisible with the
    storage kinds off."""
    eng = Engine(
        RaftMachine(num_nodes=5, log_capacity=8),
        EngineConfig(
            horizon_us=5_000_000, queue_capacity=32, faults=STORAGE_FAULTS
        ),
    )
    for seed, expect in STORAGE_SCHED.items():
        s = eng.init_lane(seed)
        rows = slice(5, 5 + 3 * STORAGE_FAULTS.n_faults)
        assert s.eq_time[rows].tolist() == expect["time"], seed
        assert s.eq_seq[rows].tolist() == expect["seq"], seed
        assert s.eq_node[rows].tolist() == expect["node"], seed
        assert s.eq_valid[rows].tolist() == expect["valid"], seed
        assert s.eq_payload[rows].tolist() == expect["pay"], seed
    single = dict(
        n_faults=1, allow_partition=False, allow_kill=False,
        t_min_us=200_000, t_max_us=600_000,
        dur_min_us=200_000, dur_max_us=400_000,
    )
    for kind_flags, nrows, expect in (
        (dict(allow_torn=True), 2, TORN_ONLY_ROWS_7),
        (dict(allow_heal_asym=True), 3, HASYM_ONLY_ROWS_7),
    ):
        eng = Engine(
            RaftMachine(num_nodes=5, log_capacity=8),
            EngineConfig(
                horizon_us=2_000_000, queue_capacity=32,
                faults=FaultPlan(**single, **kind_flags),
            ),
        )
        s = eng.init_lane(7)
        rows = slice(5, 5 + nrows)
        assert s.eq_time[rows].tolist() == expect["time"], kind_flags
        assert s.eq_node[rows].tolist() == expect["node"], kind_flags
        assert s.eq_valid[rows].tolist() == expect["valid"], kind_flags
        assert s.eq_payload[rows].tolist() == expect["pay"], kind_flags


def test_engine_v2_block_matches_module():
    """The engine's own layout for the bench config must agree with the
    module-level layout the golden words pin (guards against the engine
    silently re-sizing the legacy block)."""
    eng = Engine(
        RaftMachine(num_nodes=5, log_capacity=8),
        EngineConfig(horizon_us=5_000_000, queue_capacity=32, faults=V1_FAULTS),
    )
    assert eng._rng_layout == _v2_layout()
    eng3 = Engine(
        RaftMachine(num_nodes=5, log_capacity=8),
        EngineConfig(
            horizon_us=5_000_000, queue_capacity=32, faults=V1_FAULTS, rng_stream=3
        ),
    )
    assert eng3._rng_layout == _v3_layout()


# -- causal provenance (PR-7) ------------------------------------------------

# End-to-end golden violation provenance words: demo-volatilecommit-raft
# under the default CLI-shaped chaos config, one pinned failing seed per
# stream version. The word is a pure function of the seed and the
# documented OR-along-delivery dataflow — any engine change that moves
# it is a provenance-layout-breaking event (ship a new layout, don't
# edit the constants). 0x40000002 = scheduled fault #1 (the kill) +
# bit 30 (the crash-with-amnesia wipe); 0x40000001 = fault #0 + bit 30.
PROV_PINNED = {
    2: (5, 102, 0x40000002),
    3: (8, 102, 0x40000001),
}


def _volatile_prov_engine(rng_stream):
    from madsim_tpu.__main__ import build_machine

    return Engine(
        build_machine("demo-volatilecommit-raft", 0),
        EngineConfig(
            horizon_us=5_000_000,
            queue_capacity=96,
            rng_stream=rng_stream,
            faults=FaultPlan(
                n_faults=2, t_max_us=3_000_000, dur_min_us=100_000,
                dur_max_us=800_000, strict_restart=True,
            ),
            provenance=True,
        ),
    )


def test_provenance_word_layout_pinned():
    """The provenance word layout contract: scheduled fault f owns bit
    min(f, 29), bits 30/31 are the amnesia/dup channels, and init_lane's
    eq_prov plane carries exactly the slot bits (boot timers are causal
    roots) — under BOTH fault-schedule derivations, so the layout can
    never drift with the vocabulary."""
    from madsim_tpu.engine.core import (
        PROV_BIT_AMNESIA,
        PROV_BIT_DUP,
        PROV_FAULT_BITS,
        prov_fault_bit,
    )

    assert (PROV_FAULT_BITS, PROV_BIT_AMNESIA, PROV_BIT_DUP) == (30, 30, 31)
    assert prov_fault_bit(0) == 1
    assert prov_fault_bit(29) == prov_fault_bit(40) == 2 ** 29  # tail aliases
    for faults in (V1_FAULTS, V2_FAULTS):
        eng = Engine(
            RaftMachine(num_nodes=5, log_capacity=8),
            EngineConfig(
                horizon_us=5_000_000, queue_capacity=32, faults=faults,
                provenance=True,
            ),
        )
        s = eng.init_lane(7)
        prov = s.eq_prov.tolist()
        assert prov[:5] == [0] * 5, faults  # boot timers: roots
        assert prov[5:9] == [1, 1, 2, 2], faults  # fault slots own their bit
        assert not any(prov[9:]), faults


@pytest.mark.parametrize("rng_stream", [2, 3], ids=["rng-v2", "rng-v3"])
def test_provenance_violation_word_pinned(rng_stream):
    """Golden end-to-end words, one per stream version: the pinned seed
    must fail with the pinned code AND the exact pinned provenance word
    on the host replay path (the same lane_step ops the device runs)."""
    from madsim_tpu.engine.replay import replay

    seed, code, word = PROV_PINNED[rng_stream]
    rp = replay(_volatile_prov_engine(rng_stream), seed, max_steps=3000, trace=False)
    assert rp.failed and rp.fail_code == code
    assert int(rp.state.fail_prov) == word, hex(int(rp.state.fail_prov))
