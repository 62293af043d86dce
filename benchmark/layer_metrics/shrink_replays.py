"""Shrink + replay, hunt cells: replays `shrink` spent per hunt
(`ShrinkResult.attempts`), median. A count."""


def read(obs):
    return obs.campaign_median("shrink_replays")
