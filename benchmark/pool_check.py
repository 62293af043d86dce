#!/usr/bin/env python3
"""python3 benchmark/pool_check.py --config <config> --traffic <traffic> [--slots 7,8]

Does a sweep's traffic file name only seed ranges that lose no lane?

`campaigns/sweep.py` counts a lane the stream reports `infra` (its queue
overflowed the configuration's own Q) or `abandoned` (over --max-steps)
as a failed operation. A range that holds such a lane makes a cell's
failed share depend on whether a window reaches it, so a traffic file
lists (`slots`) only ranges this check has passed under the cell's
configuration, and `harness.campaign_seeds` never leaves the list (past
its end it goes round the fresh ranges again). A lane's result
depends on its seed alone and is bit-identical on every backend (the
guarantee `correct` holds every run to), so jax's CPU backend is enough:

    JAX_PLATFORMS=cpu python3 benchmark/pool_check.py --config raft5 --traffic sweep_10k

A range is `base_seed + slot x stride` and its first `seeds` + batches
x `batch` seeds: the most a campaign may consume before
`checks.stream_problems` calls it a gap. Each is checked
twice, in one process, on one engine built by the CLI:

    stream  the campaign kind's own `explore --stream` argv over that
            many seeds: what a campaign of the benchmark sees, and the
            table's `completed` / `seeds_consumed` / recorder mark (the
            CLI keeps the recorder totals of its last batch only);
    lanes   every seed of the range run to its end in fixed batches
            (`Engine.make_runner`). The stream throws away what is in
            flight when a batch meets its budget (~8% of what it
            consumed, the long lanes first), and WHICH seeds those are
            moves with the executor's polling, so only this pass covers
            a later, faster program.

A range LOSES a lane the stream reports `infra` or `abandoned`, and a
lane whose queue overflows in the lanes pass, at whatever step. A lane
the lanes pass finds still running at --max-steps is listed
(`over_max_steps`) and loses nothing: a stream abandons a lane only
where one `run_stream` call lets it run that long, which no call of a
16,384-seed campaign does (~500 steps), and `raft5` has one or two such
lanes in nearly every range, so no list could avoid them (PERF.md
section 7).

Without --slots it checks the file's `slots` (the first `pool` ranges
where it has none). Exit code 0: no range lost a lane; 1: one did (each
is named on stderr); 2: unknown config or traffic, or a traffic kind it
cannot run. The last line of stdout is the table as one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, drive  # noqa: E402 — after the path is set

say = drive.say

#: the lanes pass runs every lane under this cap first and only the
#: lanes it cuts to the CLI's own --max-steps: a fixed batch steps until
#: its longest lane ends, and of `raft5`'s lanes 97% end within two
#: 384-step segments while 3% run 1,800 steps and more
FIRST_CAP = 768


def lane_outcomes(eng, max_steps: int, seeds, batch: int) -> tuple:
    """`seeds` (any vector), one lane each, run to their end or to
    `max_steps` in fixed batches of `batch` lanes (the last one padded
    with its last seed: one shape): per seed (done, failed, fail_code,
    the recorder's queue high-water mark, 0 with the recorder off)."""
    import jax.numpy as jnp
    import numpy as np

    run = eng.make_runner(max_steps=max_steps)
    cols: tuple = ([], [], [], [])
    for i in range(0, len(seeds), batch):
        part = seeds[i:i + batch]
        padded = np.concatenate([part, np.full(batch - len(part), part[-1])])
        res = run(jnp.asarray(padded, dtype=jnp.uint32))
        mark = res.fr["q_hwm"] if eng.config.flight_recorder \
            else np.zeros(batch, np.int32)
        for col, leaf in zip(cols, (res.done, res.failed, res.fail_code, mark)):
            col.append(np.asarray(leaf)[:len(part)])
    return tuple(np.concatenate(c) for c in cols)


def lanes_pass(eng, starts: list, n_seeds: int, batch: int, max_steps: int) -> dict:
    """Every seed of each [start, start + n_seeds) run to its end, one
    lane each: {start: {"infra": lanes whose queue overflows, at
    whatever step; "over_max_steps": lanes still running at --max-steps
    (a stream reports one `abandoned` only if a single `run_stream` call
    lets it run that long); "queue_hwm": the largest queue occupancy any
    lane's recorder saw}}."""
    import numpy as np

    from madsim_tpu.engine import OVERFLOW

    seeds = np.concatenate(
        [np.arange(s, s + n_seeds, dtype=np.int64) for s in starts])
    first_cap = min(FIRST_CAP, max_steps)
    done, failed, code, mark = lane_outcomes(eng, first_cap, seeds, batch)
    cut = ~done & ~failed
    say(f"pool_check: {len(seeds)} lanes under a cap of {first_cap} steps: "
        f"{int(cut.sum())} still running, run again to {max_steps}")
    if first_cap < max_steps and cut.any():
        again = lane_outcomes(eng, max_steps, seeds[cut], batch)
        for col, new in zip((done, failed, code, mark), again):
            col[cut] = new
    out = {}
    for k, start in enumerate(starts):
        part = slice(k * n_seeds, (k + 1) * n_seeds)
        over = failed[part] & (code[part] == OVERFLOW)
        out[start] = {
            "infra": seeds[part][over].tolist(),
            "over_max_steps": seeds[part][~done[part] & ~failed[part]].tolist(),
            "queue_hwm": int(mark[part].max()),
        }
    return out


def check_ranges(config: dict, traffic: dict, slots: list, seeds_per_slot: int,
                 data_root: str) -> list:
    """One row a slot: the stream's aggregate, then the lanes pass."""
    cell = cells.Cell(
        name="pool_check", chips=1, config_name=config["name"],
        traffic_name=traffic["name"], config=config,
        traffic=dict(traffic, seeds=seeds_per_slot), end_to_end=(),
        per_layer=(), data_root=data_root)
    campaign = cells.load_campaign(cell)
    session = drive.Session(workdir=None)  # a sweep writes no file
    rows = []
    for slot in slots:
        start = int(traffic["base_seed"]) + slot * int(traffic["stride"])
        run = session.cli(campaign.argv(cell, start))
        if run.agg is None:
            raise cells.BenchmarkError("explore never reached the stream driver")
        agg = run.agg
        rows.append({
            "slot": slot, "start": start,
            "completed": agg["completed"],
            "seeds_consumed": agg["seeds_consumed"],
            "stream_infra": sorted(s for s, _c in agg["infra"]),
            "stream_abandoned": sorted(agg["abandoned"]),
            "stream_queue_hwm": (agg["stats"].get("flight_recorder") or {}
                                 ).get("queue_hwm"),
        })
    args = session.stream_args
    lanes = lanes_pass(session.eng, [r["start"] for r in rows], seeds_per_slot,
                       args.batch, args.max_steps)
    for row in rows:
        row.update(lanes[row["start"]])
        row["lost"] = sorted(set(
            row["stream_infra"] + row["stream_abandoned"] + row["infra"]))
        say(f"pool_check: slot {row['slot']} [{row['start']}, "
            f"{row['start'] + seeds_per_slot}): stream completed "
            f"{row['completed']}, consumed {row['seeds_consumed']}, infra "
            f"{row['stream_infra']}, abandoned {row['stream_abandoned']}, queue "
            f"hwm {row['stream_queue_hwm']} (its last batch); every lane: infra "
            f"{row['infra']}, over --max-steps {row['over_max_steps']}, queue "
            f"hwm {row['queue_hwm']}; "
            f"{'LOST ' + str(row['lost']) if row['lost'] else 'clean'}")
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--slots", default=None,
                   help="comma-separated slot numbers (the file's own when left out)")
    p.add_argument("--data-root", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    data_root = args.data_root or cells.DATA_ROOT
    try:
        config = cells.load_json(
            cells.data_file(data_root, "configs", args.config, ".json"))
        traffic = cells.load_json(
            cells.data_file(data_root, "traffic", args.traffic, ".json"))
        if traffic.get("kind") != "sweep":
            raise cells.BenchmarkError(
                f"traffic {args.traffic} is of kind {traffic.get('kind')!r}: "
                f"only a sweep counts lost lanes as failed operations")
        if args.slots is not None:
            slots = [int(s) for s in args.slots.split(",")]
        else:
            slots = list(traffic.get("slots", range(int(traffic["pool"]))))
        seeds, batch = int(traffic["seeds"]), int(config["flags"]["batch"])
        per_slot = seeds + -(-seeds // batch) * batch
        if per_slot > int(traffic["stride"]):
            raise cells.BenchmarkError(
                f"{per_slot} seeds a slot overlap the next (stride "
                f"{traffic['stride']})")
        drive.list_cpu_backend()
        say(f"pool_check: {args.config} x {args.traffic}, slots {slots}, "
            f"{per_slot} seeds a slot")
        rows = check_ranges(config, traffic, slots, per_slot, data_root)
    except cells.BenchmarkError as exc:
        print(f"pool_check: refusing to run: {exc}", file=sys.stderr, flush=True)
        return 2
    dropped = {str(r["slot"]): r["lost"] for r in rows if r["lost"]}
    for slot, lost in dropped.items():
        print(f"pool_check: slot {slot} LOSES lanes {lost}", file=sys.stderr,
              flush=True)
    print(json.dumps({
        "config": args.config, "traffic": args.traffic,
        "seeds_per_slot": per_slot,
        "device": drive.device_info(),
        "clean": [r["slot"] for r in rows if not r["lost"]],
        "dropped": dropped, "rows": rows,
    }), flush=True)
    return 1 if dropped else 0


if __name__ == "__main__":
    sys.exit(main())
