"""Step: simulated events per seed — the step counts of the lane sample
drawn from --seed, over its lanes. Repeats exactly for a --seed; a
speed-only change may not move it."""


def read(obs):
    s = obs.sample
    return s["events"] / s["lanes"] if s.get("lanes") else None
