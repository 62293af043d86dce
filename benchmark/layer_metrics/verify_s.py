"""Shrink + replay, hunt cells: seconds in `regress` + `audit` on the
filed entry per hunt (benchmark span), median."""


def read(obs):
    return obs.campaign_median("verify_s")
