"""A machine's own totals of a stream, per resolved seed.

`Machine.STREAM_COUNTERS` are summed on the device over the lanes a stream
resolved and come out beside the flight recorder's totals, as
`stats["flight_recorder"]["machine"]`. A campaign's aggregate keeps its LAST
stream call's totals, so each campaign gives that call's total over that
call's seeds."""


def per_seed(obs, counter: str):
    """`counter` over the seeds resolved, summed over the window's campaigns;
    None where the program has no such counter (another machine, or a program
    from before the machine had totals)."""
    total = seeds = 0
    for record in obs.records:
        mine = (record["agg"].get("stats", {}).get("flight_recorder") or {}
                ).get("machine")
        calls = [c for c in record["calls"] if c["n_seeds"] > 1]
        if not mine or counter not in mine or not calls:
            continue
        total += mine[counter]
        seeds += calls[-1]["completed"]
    return total / seeds if seeds else None
