"""Shrink + replay, hunt cells: seconds per hunt in `audit_entry` spans
(`audit`: the CPU replays the filed entry to its digest trail). Median."""

from benchmark import hunt_spans


def read(obs):
    return hunt_spans.per_hunt(obs, hunt_spans.named_total(("audit_entry",)))
