"""Step: share (%) of device busy time under `madsim.step.pop` — the queue pop
and the gather of the popped event, or the Pallas kernel (step megakernel,
pop+gather) that does both. Self time by phase from the device trace
(benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "step_pop_share")
