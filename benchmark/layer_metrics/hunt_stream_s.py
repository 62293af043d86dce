"""Stream driver + executor, hunt cells: `_stream_batches`' timed loop
(`agg["elapsed_s"]`) per hunt, median."""

import statistics


def read(obs):
    return statistics.median(r["agg"]["elapsed_s"] for r in obs.records)
