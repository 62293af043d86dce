"""Compile + cache: seconds of the unmeasured first campaign (the span
`setup_warmup_s` times) spent lowering the jaxprs to MLIR modules, by jax's own
compile-stage events as `madsim_tpu/perf/compile_log.py` keeps them (union
of the stage's intervals, so a nested jit is not counted twice)."""


def read(obs):
    try:
        from madsim_tpu.perf import compile_log
    except ImportError:  # a program from before the log
        return None
    return compile_log.snapshot(obs.warmup["t0"], obs.warmup["t1"])["lower_s"]
