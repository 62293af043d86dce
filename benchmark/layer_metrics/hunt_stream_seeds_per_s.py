"""Stream driver + executor, hunt cells: seeds resolved over the timed
stream loop, per hunt, median. A rate over about a second: it decides
nothing (PR 22 fell on it) and is kept to be read beside hunt_stream_s."""

import statistics


def read(obs):
    return statistics.median(
        r["agg"]["completed"] / r["agg"]["elapsed_s"] for r in obs.records)
