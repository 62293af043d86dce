"""Shrink + replay, hunt cells: seconds inside `engine.shrink` per hunt
(benchmark span around the call), median."""


def read(obs):
    return obs.campaign_median("shrink_s")
