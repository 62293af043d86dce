"""Compile + cache, hunt cells: compile requests per hunt (median). A
hunt's shrink candidates and its CPU verify are new configurations, so
new programs; with the persistent cache warm each is a trace + a read."""


def read(obs):
    return obs.campaign_median("compiles")
