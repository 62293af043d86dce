"""Step: share (%) of device busy time under `madsim.step.rng` — the step's RNG
word block (drawn inside the megakernel on stream v3, then only sliced
here). Self time by phase from the device trace (benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "step_rng_share")
