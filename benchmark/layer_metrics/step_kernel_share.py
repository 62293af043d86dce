"""Step kernels: share (%) of device busy time spent in Mosaic (Pallas)
custom calls, by self time in the traced campaigns."""


def read(obs):
    t = obs.trace
    if not t or not t["busy_s"] or not t["mosaic_s"]:
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
