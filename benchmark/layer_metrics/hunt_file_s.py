"""Shrink + replay, hunt cells: seconds per hunt in `hunt_report` (the
stream's return to the first shrink: prints, coverage file, corpus load)
and `corpus_record` (the digest-trail replay and the filing). Median."""

from benchmark import hunt_spans


def read(obs):
    return hunt_spans.per_hunt(
        obs, hunt_spans.named_total(("hunt_report", "corpus_record")))
