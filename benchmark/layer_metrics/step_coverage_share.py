"""Step: share (%) of device busy time under `madsim.step.coverage` — the
abstract-state projection, the slot hash and the buffer append. Self time by
phase from the device trace (benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "step_coverage_share")
