"""Device: share (%) of device busy time in ops under no `madsim.` scope — what
the phase metrics cannot see; keeps them honest. Self time by phase from the
device trace (benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "device_unscoped_share")
