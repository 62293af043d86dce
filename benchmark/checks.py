"""The comparisons that decide `correct`.

Copies of `chip_smoke.py`'s checks (PR 21), kept here so that a later
change to that script cannot move the benchmark's yardstick. Each
returns a list of problems as strings; `correct` is "no problem".

The guarantees held (stated in every configs/<name>.json):

* every requested seed resolves exactly once, gaplessly, and no
  dispatch was retried (`stream_problems`);
* on a sample of lanes drawn from --seed, what the simulation decided
  per lane — outcome, fail code, virtual time, step count, the
  recorder's digest trail, the coverage map — is equal on the window's
  engine, on the XLA step path on the same device, and on jax's CPU
  backend (`sample_problems`);
* on a mesh, one streamed batch equals the same batch on one device,
  coverage map and recorder totals included (`mesh_problems`).
"""

from __future__ import annotations

import time


def stream_problems(agg: dict, first_seed: int, seeds: int, batch: int,
                    what: str) -> list:
    """Problems in one `_stream_batches` aggregate."""
    bad = []
    reported = (
        [s for s, _c in agg["failing"]] + [s for s, _c in agg["infra"]]
        + list(agg["abandoned"])
    )
    in_flight = agg["seeds_consumed"] - agg["completed"]
    if agg["completed"] < seeds:
        bad.append(f"{what}: {agg['completed']} of {seeds} seeds resolved")
    if not len(set(reported)) == len(reported) <= agg["completed"]:
        bad.append(f"{what}: a seed was reported twice")
    if not all(first_seed <= s < first_seed + agg["seeds_consumed"]
               for s in reported):
        bad.append(f"{what}: a reported seed never entered a lane")
    # what entered lanes and did not resolve was still in flight when a
    # batch reached its budget: at most one lane-load per batch
    if not 0 <= in_flight <= agg["batches_run"] * batch:
        bad.append(f"{what}: {in_flight} seeds consumed but unresolved (gap)")
    if agg["stats"].get("dispatch_retries", 0) != 0:
        bad.append(f"{what}: {agg['stats']['dispatch_retries']} dispatches retried")
    return bad


def xla_twin(eng):
    """`eng`'s machine and config on the XLA step path — the oracle."""
    return type(eng).on_xla_step_path(eng.machine, eng.config)


def lane_results(eng, seed_start: int, n_seeds: int, max_steps: int) -> dict:
    """Seeds [seed_start, seed_start + n_seeds) run to completion, one
    lane each, in one fixed batch (`Engine.make_runner`, whose result
    carries every lane's final state); what the simulation decided per
    lane as numpy arrays. A lane's result depends on its seed alone.
    The coverage slot buffer is scratch, not a result, and is left out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    res = eng.make_runner(max_steps=max_steps)(
        jnp.arange(seed_start, seed_start + n_seeds, dtype=jnp.uint32)
    )
    out = {
        k: np.asarray(getattr(res, k))
        for k in ("seeds", "done", "failed", "fail_code", "now_us", "steps",
                  "msg_count")
    }
    for path, leaf in jax.tree_util.tree_flatten_with_path(res.fr)[0]:
        out["fr" + jax.tree_util.keystr(path)] = np.asarray(leaf)
    if eng.config.coverage:
        out["cov_map"] = np.asarray(res.cov["map"])
    return out


def lanes_differ(a: dict, b: dict, what: str) -> list:
    import numpy as np

    if a.keys() != b.keys():
        return [f"{what}: result leaves differ"]
    bad = [k for k in a if not np.array_equal(a[k], b[k])]
    return [f"{what}: lanes differ in {bad}"] if bad else []


def sample_problems(eng, seed_start: int, n_seeds: int, max_steps: int) -> tuple:
    """(problems, facts) for the lane sample: the window's engine against
    the XLA step path on the same device and against jax's CPU backend.
    Where the window's engine already is the XLA step path (a meshed
    cell, any engine off the TPU) the first comparison would compare a
    program with itself and is skipped."""
    import jax

    from madsim_tpu.engine.replay import cpu_device

    t0 = time.perf_counter()
    here = lane_results(eng, seed_start, n_seeds, max_steps)
    bad = []
    kernels = bool(eng.use_megakernel or eng.use_pallas_pop)
    if kernels:
        bad += lanes_differ(
            here, lane_results(xla_twin(eng), seed_start, n_seeds, max_steps),
            f"kernels vs XLA step path, {n_seeds} lanes",
        )
    with jax.default_device(cpu_device()):
        cpu = lane_results(xla_twin(eng), seed_start, n_seeds, max_steps)
    bad += lanes_differ(here, cpu, f"device vs CPU backend, {n_seeds} lanes")
    facts = {
        "lanes": int(n_seeds),
        "seed_start": int(seed_start),
        "kernels_compared": kernels,
        "failing_lanes": int(here["failed"].sum()),
        "events": int(here["steps"].sum()),
        "seconds": time.perf_counter() - t0,
    }
    return bad, facts


def mesh_problems(eng, mesh, seed_start: int, batch: int,
                  max_steps: int) -> tuple:
    """(problems, facts): one streamed batch on the mesh against the
    same seeds streamed on one device (same XLA step path): failing set
    and codes, counts, coverage map, recorder totals. The check that
    caught PR 21's lost AllReduce bits, which no CPU test can see."""
    import numpy as np

    t0 = time.perf_counter()
    outs = [
        eng.run_stream(batch, batch=batch, segment_steps=384,
                       seed_start=seed_start, max_steps=max_steps, **kw)
        for kw in ({"mesh": mesh}, {})
    ]
    a, b = outs
    bad = []
    for key in ("failing", "infra", "abandoned"):
        if sorted(a[key]) != sorted(b[key]):
            bad.append(f"mesh vs one device: {key} differ "
                       f"({len(a[key])} vs {len(b[key])} entries)")
    for key in ("completed", "seeds_consumed"):
        if a[key] != b[key]:
            bad.append(f"mesh vs one device: {key} {a[key]} vs {b[key]}")
    if ("coverage_map" in a) != ("coverage_map" in b):
        bad.append("mesh vs one device: one run has no coverage map")
    elif "coverage_map" in a and not np.array_equal(
            a["coverage_map"], b["coverage_map"]):
        bad.append(
            f"mesh vs one device: coverage maps differ "
            f"({int(a['coverage_map'].sum())} vs {int(b['coverage_map'].sum())} slots)"
        )
    if a["stats"].get("flight_recorder") != b["stats"].get("flight_recorder"):
        bad.append("mesh vs one device: recorder totals differ")
    facts = {
        "seeds": int(a["completed"]),
        "coverage_slots": int(a["coverage_map"].sum()) if "coverage_map" in a else None,
        "seconds": time.perf_counter() - t0,
    }
    return bad, facts
