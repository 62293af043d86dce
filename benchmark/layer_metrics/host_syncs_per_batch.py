"""Executor: blocking device->host reads (`stats["host_syncs"]`: counters
polls and ring drains) per timed `run_stream` call. An exact count."""


def read(obs):
    calls = obs.stream_calls()
    if not calls:
        return None
    return sum(c["stats"]["host_syncs"] for c in calls) / len(calls)
