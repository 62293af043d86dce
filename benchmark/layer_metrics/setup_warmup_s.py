"""Compile + cache: engine build to the end of the unmeasured first
campaign — trace, compile or read from the cache, warm-up dispatch."""


def read(obs):
    return obs.warmup["t1"] - obs.warmup["t0"]
