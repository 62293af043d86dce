"""Campaign kind `sweep`: `explore --stream` over `seeds` seeds.

What an `explore --stream` user runs: the CLI parses the flags, takes
the live engine, makes its warm-up dispatch and streams the seed budget
in whole batches. End to end it yields `seeds_per_s`: seeds resolved in
the window's whole campaigns over the wall-clock from the first
campaign's start to the last one's end.
"""

from benchmark import checks
from benchmark.drive import flag_argv


def argv(cell, seed_start: int) -> list:
    cfg = cell.config
    return (
        ["explore", "--machine", cfg["machine"], "--stream",
         "--seeds", str(cell.traffic["seeds"]), "--seed", str(seed_start)]
        + flag_argv(cfg["flags"])
        + (flag_argv(cfg["mesh_flags"]) if cell.chips > 1 else [])
    )


def run_campaign(session, cell, seed_start: int, index: int) -> dict:
    first_call = len(session.stream_calls)
    with session.span("campaign"):
        run = session.cli(argv(cell, seed_start))
    agg = run.agg or {}
    bad = ["explore never reached the stream driver"] if run.agg is None else \
        checks.stream_problems(agg, seed_start, cell.traffic["seeds"],
                               run.args.batch, f"campaign {index}")
    return {
        "index": index, "seed_start": seed_start, "t0": run.t0, "t1": run.t1,
        "rc": run.rc, "agg": agg, "problems": bad,
        "calls": session.stream_calls[first_call:],
    }


def counts(records: list) -> tuple:
    """(attempted, failed): seeds resolved; infra + abandoned among them."""
    done = sum(r["agg"].get("completed", 0) for r in records)
    lost = sum(len(r["agg"].get("infra", ())) + len(r["agg"].get("abandoned", ()))
               for r in records)
    return done, lost


def end_to_end(records: list) -> dict:
    wall = records[-1]["t1"] - records[0]["t0"]
    return {"seeds_per_s": counts(records)[0] / wall}
