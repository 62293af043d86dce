"""Executor, hunt cells: share (%) of the hunts' timed stream wall spent
in `ring_drain` spans (the program's own span): failing lanes shipped to
the host. About 0 in a clean sweep, which is why no sweep reports it."""


def read(obs):
    calls = [(c["t0"], c["t1"]) for c in obs.stream_calls()]
    wall = sum(b - a for a, b in calls)
    if not wall or not obs.session.traced:
        return None
    drains = obs.spans_inside("ring_drain", calls)
    return 100.0 * sum(b - a for a, b in drains) / wall
