"""Compile + cache: persistent-cache misses (real XLA compiles) during the
unmeasured first campaign, from jax's `/jax/compilation_cache/cache_misses`
events as `madsim_tpu/perf/compile_log.py` keeps them. 0 on a warm cache."""


def read(obs):
    try:
        from madsim_tpu.perf import compile_log
    except ImportError:  # a program from before the log
        return None
    return compile_log.snapshot(obs.warmup["t0"], obs.warmup["t1"])["cache_misses"]
