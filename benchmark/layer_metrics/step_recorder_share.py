"""Step: share (%) of device busy time under `madsim.step.recorder` — the trace
ring and the flight recorder (digest fold, checkpoints, counters, high-water
marks). Self time by phase from the device trace
(benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "step_recorder_share")
