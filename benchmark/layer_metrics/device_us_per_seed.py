"""Segment: chip-microseconds of device busy time per seed resolved in
the traced campaigns (busy time summed over the chips used)."""


def read(obs):
    t = obs.trace
    seeds = sum(r["agg"]["completed"] for r in obs.traced_records)
    if not t or not seeds or not t["busy_s"]:
        return None
    return 1e6 * t["busy_s"] * t["devices"] / seeds
