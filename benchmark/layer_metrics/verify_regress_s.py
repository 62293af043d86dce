"""Shrink + replay, hunt cells: seconds per hunt in `regress_entry` spans
(`regress`: the CPU replays the filed entry to its fail code). Median."""

from benchmark import hunt_spans


def read(obs):
    return hunt_spans.per_hunt(obs, hunt_spans.named_total(("regress_entry",)))
