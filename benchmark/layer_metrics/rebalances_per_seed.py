"""Step: generation bumps of the consumer group per resolved seed — how
much of the group protocol the faults exercised (two joins a lane with no
fault at all) — from the machine's own totals, as `records_per_seed`."""

from benchmark import machine_totals


def read(obs):
    return machine_totals.per_seed(obs, "rebalances")
