"""Device, hunt cells: as device_idle_share, over the whole traced
hunts (stream, shrink, filing and CPU verify), not the stream alone."""

from benchmark.trace_reduce import idle_share


def read(obs):
    return idle_share(obs.trace)
