"""Compile + cache, hunt cells: seconds per hunt in `compile` spans under
a `replay` span — the first call of a replay program for a new machine
or step configuration: trace + lower + compile-or-read. Median."""

from benchmark import hunt_spans


def read(obs):
    def measure(spans, _wall):
        by_id = {s["id"]: s for s in spans}
        if not any(s["name"] == "replay" for s in spans):
            return None
        return sum(s["t1"] - s["t0"] for s in spans
                   if s["name"] == "compile" and hunt_spans.under(s, "replay", by_id))
    return hunt_spans.per_hunt(obs, measure)
