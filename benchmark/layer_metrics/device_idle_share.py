"""Device: share (%) of the traced campaigns' wall-clock in which no
operation ran on the device (1 - union of device-op intervals / window,
averaged over the chips used). `breakdown.idle_gaps` names the gaps."""

from benchmark.trace_reduce import idle_share


def read(obs):
    return idle_share(obs.trace)
