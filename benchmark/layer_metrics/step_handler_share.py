"""Step: share (%) of device busy time under `madsim.step.handlers` (+
`step.provenance` where that gate adds ops) — the timer / message / fault
branches and the masked write-back of their results. Self time by phase from
the device trace (benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "step_handler_share")
