"""Step: share (%) of device busy time under `madsim.step.invariants` — the
machine's invariant, termination, and the assembly of the lane's next state.
Self time by phase from the device trace (benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "step_invariant_share")
