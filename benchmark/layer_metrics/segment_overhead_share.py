"""Segment: share (%) of device busy time under every `madsim.*` scope that is
not a step phase — refill, harvest, the recorder and coverage folds, the
coverage flush, ring appends, counters, their collectives, and the step
loop's own control. Self time by phase from the device trace
(benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "segment_overhead_share")
