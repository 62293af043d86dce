"""Step: re-draws of the servers' split plus whole-cluster kills (a kill of
every named node at one instant counts once) per resolved seed, from the
flight recorder's totals (`fr_metrics`' churn counters of a process of kind
`kv3a`: `partitions` and `crashes`, summed on the device over the lanes a
stream resolved). A campaign's aggregate keeps its LAST stream call's totals,
so each campaign gives that call's count over that call's seeds. None where
the program has no such counters (no process, another kind of process, or a
program from before the kind)."""


def read(obs):
    count = seeds = 0
    for record in obs.records:
        churn = (record["agg"].get("stats", {}).get("flight_recorder") or {}
                 ).get("churn")
        calls = [c for c in record["calls"] if c["n_seeds"] > 1]
        if not churn or "partitions" not in churn or not calls:
            continue
        count += churn["partitions"] + churn["crashes"]
        seeds += calls[-1]["completed"]
    return count / seeds if seeds else None
