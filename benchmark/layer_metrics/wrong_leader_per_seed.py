"""Step: "wrong leader" answers the clerks took per resolved seed — how much
leader search the faults forced on a lane of the key/value service — from the
machine's own totals (`benchmark/machine_totals.py`: the `wrong_leader` of
`stats["flight_recorder"]["machine"]`). None where the program has no such
counter."""

from benchmark import machine_totals


def read(obs):
    return machine_totals.per_seed(obs, "wrong_leader")
