"""Timeout-mishandling hunt with the delay fault kind — the round-5
chaos vocabulary in action.

Most fault vocabularies (loss, partitions, kills) make messages VANISH.
The `delay` kind makes them LATE: during a timed window, ~10% of sends
take +1-5 virtual seconds (the host fabric's buggify numbers,
reference sim/net/mod.rs:287-296). Late-but-delivered is the only way
to reach a whole class of real bugs: code that treats a timeout as
failure while the request is still in flight.

The demo machine is a deadline-RPC client against a token-dedup server
(models/etcd_mvcc.py PREMATURE_GIVEUP): each op is sent once with a
300 ms deadline; on expiry the client reports failure to the
application and moves on. The bug: the abandoned request can still
land — a write the application compensated for becomes visible
(ABANDONED_WRITE, code 206). Loss destroys the in-flight copy and
clogs/kills block it at the link, so every other vocabulary finds
NOTHING; only delay reaches it (measured: 21.6% vs 0.0% at 384 seeds
per vocabulary).

Run:  python examples/delay_hunt.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

from madsim_tpu.engine import Engine, EngineConfig, FaultPlan, replay, shrink
from madsim_tpu.models.etcd_mvcc import ABANDONED_WRITE, EtcdMvccMachine


class PrematureGiveup(EtcdMvccMachine):
    PREMATURE_GIVEUP = True  # the CLI ships this as demo-giveup-mvcc


def main() -> None:
    def engine(**fault_kinds):
        kinds = dict(allow_partition=False, allow_kill=False)
        kinds.update(fault_kinds)
        return Engine(
            PrematureGiveup(num_nodes=4),
            EngineConfig(
                horizon_us=8_000_000,
                queue_capacity=48,
                faults=FaultPlan(
                    n_faults=3, t_max_us=3_000_000,
                    dur_min_us=200_000, dur_max_us=800_000, **kinds,
                ),
            ),
        )

    seeds = jnp.arange(256, dtype=jnp.uint32)

    # 1. the vanishing vocabularies find nothing…
    for name, kinds in [
        ("loss storms", dict(allow_storm=True)),
        ("partitions + kills", dict(allow_partition=True, allow_kill=True)),
    ]:
        res = engine(**kinds).make_runner(max_steps=3000)(seeds)
        n = int(res.failed.sum())
        print(f"{name:>20}: {n}/256 seeds flagged")

    # 2. …the delay vocabulary finds the bug
    eng = engine(allow_delay=True)
    res = eng.make_runner(max_steps=3000)(seeds)
    failing = [int(s) for s in eng.failing_seeds(res).tolist()]
    codes = {int(c) for c in res.fail_code.tolist() if c}
    print(f"{'delay spikes':>20}: {len(failing)}/256 seeds flagged, codes {codes}")
    assert codes == {ABANDONED_WRITE}

    # 3. bit-identical replay of one find, then shrink it to a minimal repro
    seed = failing[0]
    rp = replay(eng, seed, max_steps=3000, trace=False)
    assert rp.failed and rp.fail_code == ABANDONED_WRITE
    sr = shrink(eng, seed, max_steps=3000)
    print(f"{'replay + shrink':>20}: {sr.summary()}")
    # the minimal config still carries delay windows — the late delivery
    # IS the bug's trigger, so shrink cannot remove every fault
    assert sr.shrunk.faults.n_faults >= 1


if __name__ == "__main__":
    main()
