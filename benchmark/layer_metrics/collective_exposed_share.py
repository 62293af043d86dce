"""Mesh: share (%) of device busy time in which a collective ran with no
compute beside it on that chip — the part of collective_share that
nothing hides."""


def read(obs):
    t = obs.trace
    if not t or not t["busy_s"] or t["devices"] < 2:
        return None
    return 100.0 * t["collective_exposed_s"] / t["busy_s"]
