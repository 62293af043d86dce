"""Campaign kind `hunt`: `hunt --stream --limit N` to a verified find.

What a user who wants a bug runs: hunt the seed budget on the config's
`hunt_machine`, shrink the first failing seed of each code, file it in
a fresh corpus, then `regress` (the CPU replays the entry to the
device's fail code) and `audit` (and to its digest trail). End to end it
yields `find_s`: the MEDIAN over the window's whole hunts of one hunt's
wall-clock, from the call of the CLI to the return of `audit`. One hunt
is a 7 s sample of shrink's luck with its seed; the median of several
is what a user meets.
"""

import os
import statistics
import time
from unittest import mock

from benchmark import checks
from benchmark.drive import flag_argv


def argv(cell, seed_start: int, corpus_path: str) -> list:
    cfg = cell.config
    return (
        ["hunt", "--machine", cfg["hunt_machine"], "--stream",
         "--seeds", str(cell.traffic["seeds"]), "--seed", str(seed_start),
         "--limit", str(cell.traffic["limit"]), "--corpus", corpus_path]
        + flag_argv(cfg["flags"])
        + (flag_argv(cfg["mesh_flags"]) if cell.chips > 1 else [])
    )


def run_campaign(session, cell, seed_start: int, index: int) -> dict:
    import madsim_tpu.engine as engine_pkg
    from madsim_tpu.engine import corpus

    corpus_path = os.path.join(session.workdir, f"corpus_{index}_{seed_start}.json")
    if os.path.exists(corpus_path):
        os.remove(corpus_path)
    shrinks: list = []
    real_shrink = engine_pkg.shrink

    def timed_shrink(*a, **kw):
        t0 = time.perf_counter()
        with session.span("shrink"):
            sr = real_shrink(*a, **kw)
        shrinks.append((time.perf_counter() - t0, int(sr.attempts)))
        return sr

    first_call = len(session.stream_calls)
    with session.span("campaign"):
        with mock.patch.object(engine_pkg, "shrink", timed_shrink):
            run = session.cli(argv(cell, seed_start, corpus_path))
        t_verify = time.perf_counter()
        with session.span("verify"):
            rc_regress = session.cli(["regress", "--corpus", corpus_path]).rc
            rc_audit = session.cli(["audit", "--corpus", corpus_path]).rc
        t1 = time.perf_counter()

    agg = run.agg or {}
    bad = ["hunt never reached the stream driver"] if run.agg is None else \
        checks.stream_problems(agg, seed_start, cell.traffic["seeds"],
                               run.args.batch, f"hunt {index}")
    entries = corpus.load(corpus_path) if os.path.exists(corpus_path) else []
    bad += entry_problems(entries, agg.get("failing", ()), run.rc, rc_regress,
                          rc_audit, cell.traffic["limit"], f"hunt {index}")
    return {
        "index": index, "seed_start": seed_start, "t0": run.t0, "t1": t1,
        "rc": run.rc, "agg": agg, "problems": bad,
        "calls": session.stream_calls[first_call:],
        "find_s": t1 - run.t0,
        "verify_s": t1 - t_verify,
        "shrink_s": sum(s for s, _n in shrinks),
        "shrink_replays": sum(n for _s, n in shrinks),
        "compiles": len(session.compiles_between(run.t0, t1)),
        "entries": len(entries),
    }


def entry_problems(entries, failing, rc_hunt: int, rc_regress: int,
                   rc_audit: int, limit: int, what: str) -> list:
    """A hunt that filed nothing, filed something the device did not
    report, or whose entry does not reproduce on the CPU."""
    bad = []
    if not failing or rc_hunt != 1:
        bad.append(f"{what}: no failing seed found")
    if not 1 <= len(entries) <= limit:
        bad.append(f"{what}: {len(entries)} corpus entries filed (--limit {limit})")
    device_codes = dict(failing)
    for e in entries:
        if device_codes.get(e.seed) != e.fail_code:
            bad.append(f"{what}: entry seed {e.seed} code {e.fail_code} is not "
                       f"what the device reported ({device_codes.get(e.seed)})")
    if rc_regress != 0:
        bad.append(f"{what}: the filed entry does not reproduce on the CPU replay")
    if rc_audit != 0:
        bad.append(f"{what}: the CPU replay diverges from the entry's digest trail")
    return bad


def counts(records: list) -> tuple:
    """(attempted, failed): hunts started; hunts that filed nothing or
    whose entry did not reproduce."""
    return len(records), sum(bool(r["problems"]) for r in records)


def end_to_end(records: list) -> dict:
    return {"find_s": statistics.median(r["find_s"] for r in records)}
