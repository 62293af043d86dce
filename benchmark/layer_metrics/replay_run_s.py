"""Shrink + replay, hunt cells: seconds per hunt in `replay_run` spans
(self time: dispatch to result of one CPU replay, a first call's compile
span taken out). Median over the window's hunts."""

from benchmark import hunt_spans


def read(obs):
    def measure(spans, _wall):
        runs = [s for s in spans if s["name"] == "replay_run"]
        return sum(hunt_spans.self_s(s, spans) for s in runs) if runs else None
    return hunt_spans.per_hunt(obs, measure)
