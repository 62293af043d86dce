"""Device: `memory_stats()["peak_bytes_in_use"]` on the fullest chip
after the window (live buffers — the carry; XLA's temp space for a
running program is not in it)."""


def read(obs):
    return obs.memory_peak_bytes
