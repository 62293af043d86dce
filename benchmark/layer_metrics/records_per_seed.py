"""Step: records the broker appended per resolved seed — the load a lane
really carried — from the machine's own totals (`benchmark/machine_totals.py`:
the `appended` of `stats["flight_recorder"]["machine"]`). None where the
program has no such counter."""

from benchmark import machine_totals


def read(obs):
    return machine_totals.per_seed(obs, "appended")
