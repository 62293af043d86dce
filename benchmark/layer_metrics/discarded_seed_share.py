"""Executor: share (%) of the seeds that entered lanes and were thrown
away unresolved — in flight when a `run_stream` call met its budget
((seeds_consumed - completed) / seeds_consumed). An exact count."""


def read(obs):
    consumed = sum(r["agg"]["seeds_consumed"] for r in obs.records)
    done = sum(r["agg"]["completed"] for r in obs.records)
    return 100.0 * (consumed - done) / consumed if consumed else None
