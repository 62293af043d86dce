"""Stream driver, hunt cells: seconds per hunt in the program's
`warmup_dispatch` span — `_stream_batches`' unmeasured `run_stream(1, ...)`
before the timed stream. Median over the window's hunts."""

from benchmark import hunt_spans


def read(obs):
    return hunt_spans.per_hunt(obs, hunt_spans.named_total(("warmup_dispatch",)))
