"""Step: operations acknowledged to the clerks per resolved seed — the load a
lane of the key/value service really carried — from the machine's own totals
(`benchmark/machine_totals.py`: the `ops_acked` of
`stats["flight_recorder"]["machine"]`). None where the program has no such
counter."""

from benchmark import machine_totals


def read(obs):
    return machine_totals.per_seed(obs, "ops_acked")
