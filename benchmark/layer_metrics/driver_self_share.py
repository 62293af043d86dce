"""Stream driver: share (%) of the campaigns' wall-clock not spent inside
`Engine.run_stream` — the CLI's parsing, `_stream_batches`' own loop,
its aggregation and printing. Benchmark span around each run_stream
call, warm-up dispatch included (it is executor time, not driver time)."""


def read(obs):
    wall = sum(r["t1"] - r["t0"] for r in obs.records)
    inside = sum(c["t1"] - c["t0"] for r in obs.records for c in r["calls"])
    return 100.0 * (wall - inside) / wall if wall > 0 else None
