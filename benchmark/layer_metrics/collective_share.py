"""Mesh: share (%) of device busy time spent in collectives (all-reduce,
collective-permute, all-gather, ...), by self time, averaged over chips."""


def read(obs):
    t = obs.trace
    if not t or not t["busy_s"] or t["devices"] < 2:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
