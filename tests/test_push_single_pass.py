"""An event's pushes land in one pass over the queue (`core._push_ranked`:
one free-slot ranking, one write of every queue leaf) — and every lane
stays bit for bit what the loop it replaced made of it.

The oracle is that loop, kept here and nowhere else: a first-free scan
(`find_free_slot`) and a whole-queue masked write (`_push`) per push, in
sequence, each scan reading the `valid` plane the write before it left.

Three layers: the pass against the loop on made-up queues (every want
pattern x 0, 1, 2, ... free slots and a full queue; K 3, 4 and 7, with
and without duplicates, the provenance plane, the churn re-arm); whole
engines traced once with each (a Raft whose queue overflows, `kafka`,
`etcd-mvcc` under dup + pause + skew, Raft under `--churn fig8`), every
leaf of the final state equal; and the structure the gain rests on: the
step's reductions over the queue axis do not grow with `MAX_MSGS`."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.engine import ChurnPlan, Engine, EngineConfig, FaultPlan
from madsim_tpu.engine import core
from madsim_tpu.engine.core import EV_FAULT, EV_MSG, EV_TIMER, OVERFLOW, _Push
from madsim_tpu.models.etcd_mvcc import EtcdMvccMachine
from madsim_tpu.models.kafka import KafkaMachine
from madsim_tpu.models.raft import RaftMachine

# -- the oracle: the loop `_push_ranked` replaced (core.py at PR 33) ------------


def find_free_slot(eq_valid):
    """First free slot index and whether one exists (lane overflow check)."""
    free = ~eq_valid
    idx = jnp.argmax(free)  # first True
    return idx, jnp.any(free)


def _push(eq, idx, do_push, time, seq, kind, node, src, payload, prov=None):
    """Masked-select write of one event into slot `idx` (no scatters)."""
    m = (jnp.arange(eq["valid"].shape[0]) == idx) & do_push

    def upd(arr, value):
        return jnp.where(m, jnp.int32(value), arr)

    out = {
        "time": upd(eq["time"], time),
        "seq": upd(eq["seq"], seq),
        "kind": upd(eq["kind"], kind),
        "node": upd(eq["node"], node),
        "src": upd(eq["src"], src),
        "payload": jnp.where(m[:, None], payload[None, :], eq["payload"]),
        "valid": eq["valid"] | m,
    }
    if "prov" in eq:
        out["prov"] = (
            jnp.where(m, prov, eq["prov"]) if prov is not None else eq["prov"]
        )
    return out


def _push_sequence(eq, pushes, next_seq, pinned=None):
    """`_push_ranked`'s contract by the old means: one scan and one
    whole-queue write a push."""
    if pinned is not None:
        slot, p = pinned
        eq = _push(eq, slot, p.want, p.time, next_seq, p.kind, p.node, p.src,
                   p.payload, prov=p.prov)
        next_seq = next_seq + p.want.astype(jnp.int32)
    landed = []
    overflow = jnp.bool_(False)
    for p in pushes:
        do_push = p.want if p.dup_of is None else p.want & landed[p.dup_of]
        slot, has_free = find_free_slot(eq["valid"])
        overflow = overflow | (do_push & ~has_free)
        do_push = do_push & has_free
        eq = _push(eq, slot, do_push, p.time, next_seq, p.kind, p.node, p.src,
                   p.payload, prov=p.prov)
        next_seq = next_seq + jnp.where(do_push, 1, 0)
        landed.append(do_push)
    return eq, landed, overflow, next_seq


# -- the pass against the loop, on made-up queues -------------------------------

Q, P = 12, 3
#: (MAX_MSGS, MAX_TIMERS) whose pushes an event number K: etcd-mvcc, kafka, raft5
MACHINES = {3: (1, 1), 4: (1, 2), 7: (4, 2)}


def _pushes(n_msgs, n_timers, dup, prov, wants, fields):
    """An event's push list as the step builds it: message 0, (its
    duplicate), ..., the timers, the boot."""
    out = []

    def one(kind, dup_of=None):
        k = len(out)
        out.append(_Push(
            wants[k], fields["time"][k], kind, fields["node"][k],
            fields["src"][k], fields["payload"][k],
            fields["prov"][k] if prov else None, dup_of=dup_of,
        ))

    for _ in range(n_msgs):
        one(EV_MSG)
        if dup:
            one(EV_MSG, dup_of=len(out) - 1)
    for _ in range(n_timers + 1):
        one(EV_TIMER)
    return out


def _lanes(n_pushes, rearm, rng):
    """Every want pattern x queues with 0, 1, 2, 3, half and all slots
    free (the popped slot among them where a re-arm wants it)."""
    patterns = np.array(list(itertools.product([False, True], repeat=n_pushes)))
    frees = [0, 1, 2, 3, Q // 2, Q]
    wants = np.repeat(patterns, len(frees), axis=0)
    n = len(wants)
    valid = np.ones((n, Q), bool)
    for i in range(n):
        valid[i, rng.choice(Q, size=frees[i % len(frees)], replace=False)] = False
    idx = rng.integers(0, Q, size=n)
    want_rearm = rng.random(n) < 0.5
    if rearm:
        # a tick that re-arms was popped: its slot is free
        valid[np.arange(n), idx] &= ~want_rearm
    ints = lambda *shape: rng.integers(-5, 1 << 20, size=shape).astype(np.int32)
    lane = {
        "eq": {
            "time": ints(n, Q), "seq": ints(n, Q), "kind": ints(n, Q),
            "node": ints(n, Q), "src": ints(n, Q), "payload": ints(n, Q, P),
            "valid": valid, "prov": ints(n, Q).astype(np.uint32),
        },
        "next_seq": ints(n),
        "wants": wants,
        "fields": {
            "time": ints(n, n_pushes), "node": ints(n, n_pushes),
            "src": ints(n, n_pushes), "payload": ints(n, n_pushes, P),
            "prov": ints(n, n_pushes).astype(np.uint32),
        },
        "pin": {"slot": idx.astype(np.int32), "want": want_rearm,
                "time": ints(n), "payload": ints(n, P)},
    }
    return jax.tree.map(jnp.asarray, lane)


def _event(push_fn, n_msgs, n_timers, dup, prov, rearm):
    """One lane's event through `push_fn`, with the step's own accounting
    of what landed."""

    def run(lane):
        eq = dict(lane["eq"])
        if not prov:
            del eq["prov"]
        pushes = _pushes(n_msgs, n_timers, dup, prov, lane["wants"], lane["fields"])
        pin = lane["pin"]
        pinned = (pin["slot"], _Push(
            pin["want"], pin["time"], EV_FAULT, jnp.int32(0), jnp.int32(-1),
            pin["payload"], jnp.uint32(0),
        )) if rearm else None
        eq, landed, overflow, next_seq = push_fn(eq, pushes, lane["next_seq"], pinned)
        return {
            "eq": eq,
            "landed": jnp.stack(landed),
            "next_seq": next_seq,
            "msg_count": sum(ok.astype(jnp.int32)
                             for ok, p in zip(landed, pushes) if p.kind == EV_MSG),
            "n_dups": sum(
                (ok.astype(jnp.int32) for ok, p in zip(landed, pushes)
                 if p.dup_of is not None), jnp.int32(0)),
            "failed": overflow,
            "fail_code": jnp.where(overflow, jnp.int32(OVERFLOW), jnp.int32(0)),
        }

    return jax.jit(jax.vmap(run))


@pytest.mark.parametrize("rearm", [False, True], ids=["", "rearm"])
@pytest.mark.parametrize("prov", [False, True], ids=["", "prov"])
@pytest.mark.parametrize("dup", [False, True], ids=["", "dup"])
@pytest.mark.parametrize("k", sorted(MACHINES))
def test_one_pass_lands_what_the_loop_lands(k, dup, prov, rearm):
    n_msgs, n_timers = MACHINES[k]
    n_pushes = n_msgs * (2 if dup else 1) + n_timers + 1
    lanes = _lanes(n_pushes, rearm, np.random.default_rng(1000 * k + 4 * dup + 2 * prov + rearm))
    want = _event(_push_sequence, n_msgs, n_timers, dup, prov, rearm)(lanes)
    got = _event(core._push_ranked, n_msgs, n_timers, dup, prov, rearm)(lanes)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree.leaves_with_path(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a), err_msg=str(path))
    # the lanes did exercise both ends: queues that take every push, queues
    # that overflow, and (with duplicates) copies whose message did not land
    assert bool(want["failed"].any()) and not bool(want["failed"].all())
    assert int(want["landed"].sum(axis=1).max()) == n_pushes


# -- whole engines, traced once with each ---------------------------------------

DIGESTS = dict(flight_recorder=True, fr_digest_every=32, fr_digest_ring=8)


def _raft_overflowing():
    # Q 20 is too small for five nodes and two faults: 30 of the 48 lanes
    # below overflow, the first at its 32nd event
    return RaftMachine(num_nodes=5, log_capacity=8), EngineConfig(
        horizon_us=1_000_000, queue_capacity=20, rng_stream=3, coverage=True,
        faults=FaultPlan(n_faults=2, t_max_us=800_000, dur_min_us=100_000,
                         dur_max_us=400_000),
        **DIGESTS,
    ), 400


def _kafka():
    return KafkaMachine(log_capacity=32, produce_until_us=300_000), EngineConfig(
        horizon_us=500_000, queue_capacity=40, rng_stream=3,
        faults=FaultPlan(n_faults=3, t_max_us=350_000, dur_min_us=100_000,
                         dur_max_us=400_000, allow_dir_clog=True, allow_group=True,
                         allow_storm=True),
        **DIGESTS,
    ), 700


def _etcd_mvcc_dup_pause_skew():
    return EtcdMvccMachine(4), EngineConfig(
        horizon_us=1_500_000, queue_capacity=20, provenance=True,
        faults=FaultPlan(n_faults=3, t_max_us=1_000_000, dur_min_us=100_000,
                         dur_max_us=500_000, allow_dup=True, allow_pause=True,
                         allow_skew=True, allow_delay=True),
        **DIGESTS,
    ), 600


def _raft_fig8():
    return RaftMachine(num_nodes=5, log_capacity=16), EngineConfig(
        horizon_us=600_000, queue_capacity=24, latency_min_us=1_000,
        latency_max_us=27_000, packet_loss_rate=0.1, rng_stream=3,
        faults=FaultPlan(n_faults=0, churn=ChurnPlan(), churn_until_us=500_000),
        **DIGESTS,
    ), 500


ENGINES = {
    "raft-overflowing": _raft_overflowing,
    "kafka": _kafka,
    "etcd-mvcc-dup-pause-skew": _etcd_mvcc_dup_pause_skew,
    "raft-churn-fig8": _raft_fig8,
}


def _final_state(make, seeds):
    machine, cfg, max_steps = make()
    eng = Engine.on_xla_step_path(machine, cfg)
    return jax.jit(lambda s: eng.run_segment(eng.init_batch(s), max_steps))(seeds)


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_engine_lanes_are_bit_identical_to_the_loops(name, monkeypatch):
    seeds = jnp.arange(7000, 7048, dtype=jnp.uint32)
    got = _final_state(ENGINES[name], seeds)
    monkeypatch.setattr(core, "_push_ranked", _push_sequence)
    want = _final_state(ENGINES[name], seeds)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree.leaves_with_path(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a), err_msg=str(path))
    # failing sets, fail codes, step counts, digest trails: by name, and
    # the lanes did run
    assert int(want.step.min()) > 30 and int(want.msg_count.min()) > 0
    assert len({int(d) for d in np.asarray(want.fr["d0"])}) == len(seeds)
    overflowed = np.asarray(want.failed & (want.fail_code == OVERFLOW))
    if name == "raft-overflowing":
        assert overflowed.any() and not overflowed.all()
        assert int(want.fr["q_hwm"].max()) == 20
    if name == "etcd-mvcc-dup-pause-skew":
        assert int(want.fr["dup"].sum()) > 0
    if name == "raft-churn-fig8":
        assert int(want.churn["ticks"].min()) > 0 and int(want.churn["ticks"].sum()) > 500


# -- the structure the gain rests on --------------------------------------------

QUEUE_AXIS = 37  # no other axis of the tiny Rafts below has this size


def _queue_reductions(jaxpr) -> int:
    """Equations of a jaxpr (and of the jaxprs inside it) that reduce,
    scan or contract an operand along a QUEUE_AXIS-sized axis."""
    n = 0
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _queue_reductions(sub)
        name = eqn.primitive.name
        if not (name.startswith(("reduce_", "arg", "cum")) or name == "dot_general"):
            continue
        n += any(QUEUE_AXIS in getattr(v.aval, "shape", ()) for v in eqn.invars)
    return n


def test_queue_reductions_of_a_step_do_not_grow_with_max_msgs():
    counts = {}
    for nodes in (3, 5, 7):
        machine = RaftMachine(num_nodes=nodes, log_capacity=8)
        eng = Engine.on_xla_step_path(machine, EngineConfig(
            queue_capacity=QUEUE_AXIS, rng_stream=3,
            faults=FaultPlan(n_faults=2, allow_dup=True)))
        state = eng.init_lane(jnp.uint32(3))
        counts[machine.MAX_MSGS] = _queue_reductions(
            jax.make_jaxpr(eng.lane_step)(state).jaxpr)
    assert sorted(counts) == [2, 4, 6]
    assert len(set(counts.values())) == 1, counts
    # the pop's (min over time, argmin over seq, any valid), the popped
    # event's five one-hot reads (time, kind, node, src, payload: `get_at`,
    # where the step pops for itself) and the one ranking of the free
    # slots: nothing a push
    assert counts[2] <= 10, counts
