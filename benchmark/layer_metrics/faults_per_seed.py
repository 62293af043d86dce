"""Step: faults the churn process applied (disconnects + reconnects) per
resolved seed, from the flight recorder's totals (`fr_metrics`' churn
counters, summed on the device over the lanes a stream resolved). A
campaign's aggregate keeps its LAST stream call's totals, so each campaign
gives that call's faults over that call's seeds. None where the program has
no such counters (no process, or a program from before it)."""


def read(obs):
    faults = seeds = 0
    for record in obs.records:
        churn = (record["agg"].get("stats", {}).get("flight_recorder") or {}
                 ).get("churn")
        calls = [c for c in record["calls"] if c["n_seeds"] > 1]
        if not churn or not calls:
            continue
        faults += churn["disconnects"] + churn["reconnects"]
        seeds += calls[-1]["completed"]
    return faults / seeds if seeds else None
