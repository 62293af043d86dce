"""Drift-aware bench history — BENCH_HISTORY.jsonl.

The BENCH_r0*.json series is nine disconnected snapshots from a box
whose throughput drifts ±10% across hours; the old budget check
compared every new capture against ONE absolute file (`vs_r08
within_5pct`), so "did 505.8 -> 452.5 regress or drift?" took
archaeology (worktree reruns of old HEADs). This module makes the
series a queryable artifact:

* every bench run APPENDS one JSONL row: value, per-rep rates,
  compile_s, and an ENVIRONMENT FINGERPRINT (host, platform,
  jax/jaxlib/python versions, lanes/reps/segment_steps, the engine
  gate tuple) — the fields that decide whether two rows are comparable
  at all;
* legacy BENCH_r*.json files import once (auto, on first append)
  so the trajectory starts populated, tagged by their round;
* the budget check becomes a NEIGHBOR comparison: the newest prior row
  whose platform/lanes/gates (and host, when both recorded) match —
  same box, same config, closest in time — instead of one absolute
  snapshot from another era;
* `python -m madsim_tpu bench report` renders the trend: per-row value,
  delta vs its own comparable neighbor, and config-change annotations.

Pure stdlib (no jax, no numpy): `bench report` must render on a box
with no accelerator stack at all.
"""

from __future__ import annotations

# madsim: allow-file(D001) — history rows are stamped with host wall
# time (when was this capture taken) by design; nothing here feeds
# simulation state.
import glob
import json
import os
import platform as _platform
import re
import time
from typing import List, Optional

DEFAULT_BASENAME = "BENCH_HISTORY.jsonl"

# gate keys that make two runs comparable: a differing gate means the
# compiled step does different work, so a throughput delta is expected
GATE_KEYS = (
    "rng_stream",
    "clog_packed",
    "pallas_pop",
    "flight_recorder",
    "coverage",
    "provenance",
)


def env_fingerprint(
    *,
    backend_platform: Optional[str] = None,
    lanes: Optional[int] = None,
    reps: Optional[int] = None,
    segment_steps: Optional[int] = None,
    gates: Optional[dict] = None,
    compile_cache: Optional[bool] = None,
    device_count: Optional[int] = None,
) -> dict:
    """The comparability fingerprint for one bench capture. Versions
    are read from the installed packages; `backend_platform` is the
    jax device platform string ("cpu"/"tpu"/...), passed in so this
    module stays jax-free. `compile_cache` records whether a
    persistent compilation cache backed the capture — context for its
    compile_s numbers, deliberately NOT part of the comparability key
    (cache state never changes steady-state rate). `device_count` is
    the 1-D mesh size the stream spanned (None/1 = unsharded) and IS
    part of the comparability key: neighbor comparison must never put
    an 8-device rate next to a single-device one."""
    try:
        import jax
        import jaxlib

        jax_v, jaxlib_v = jax.__version__, jaxlib.__version__
    except Exception:  # render/report paths never need jax installed
        jax_v = jaxlib_v = None
    return {
        "host": _platform.node() or None,
        "platform": backend_platform,
        "python": _platform.python_version(),
        "jax": jax_v,
        "jaxlib": jaxlib_v,
        "lanes": lanes,
        "reps": reps,
        "segment_steps": segment_steps,
        "gates": _norm_gates(gates),
        "compile_cache": compile_cache,
        "device_count": device_count,
    }


def _norm_gates(gates: Optional[dict]) -> Optional[dict]:
    """Project a bench `gates` dict onto the comparability keys with
    plain JSON-stable values (compile_cache paths etc. dropped —
    whether a compile was cached never changes steady-state rate)."""
    if gates is None:
        return None
    out = {}
    for k in GATE_KEYS:
        v = gates.get(k)
        if isinstance(v, bool) or v is None:
            out[k] = bool(v) if v is not None else False
        else:
            out[k] = v
    return out


def make_record(
    tag: str,
    value: float,
    fingerprint: dict,
    *,
    reps: Optional[List[float]] = None,
    compile_s: Optional[float] = None,
    compile_s_warm: Optional[float] = None,
    trace_s: Optional[float] = None,
    lower_s: Optional[float] = None,
    backend_s: Optional[float] = None,
    flops_per_seed_step: Optional[float] = None,
    bytes_per_seed_step: Optional[float] = None,
    spread_pct: Optional[float] = None,
    host_load1: Optional[float] = None,
    step_cost: Optional[dict] = None,
    source: str = "bench.py",
    ts: Optional[float] = None,
) -> dict:
    # madsim: allow(D001) — capture timestamp (host metadata, not sim)
    return {
        "tag": tag,
        "ts": round(time.time(), 3) if ts is None else ts,
        "value": value,
        "reps": reps,
        # compile_s = the cold number (first process of a config);
        # compile_s_warm = the persistent-cache path (None when the
        # capture ran without a cache — no warm path existed)
        "compile_s": compile_s,
        "compile_s_warm": compile_s_warm,
        # trace_s = the pure abstract-trace share of a compile (what a
        # warm start pays even when every XLA executable deserializes;
        # what the AOT supersegment path removes). r13 splits the rest
        # via the AOT stages API (perf/xprof.compile_autopsy): lower_s
        # = StableHLO lowering, backend_s = XLA backend compilation —
        # trace + lower + backend is the whole "TRACE-dominated" claim
        # as three tracked numbers. The cost_analysis pair normalizes
        # the compiled supersegment's work to ONE seed-step, so the
        # numbers compare across lane counts and segment lengths.
        "trace_s": trace_s,
        "lower_s": lower_s,
        "backend_s": backend_s,
        "flops_per_seed_step": flops_per_seed_step,
        "bytes_per_seed_step": bytes_per_seed_step,
        "spread_pct": spread_pct,
        "host_load1": host_load1,
        "step_cost": step_cost,
        "source": source,
        "fingerprint": fingerprint,
    }


def load(path: str) -> List[dict]:
    """All history rows, file order (append order == time order for
    rows recorded live; imported legacy rows keep series order)."""
    if not os.path.exists(path):
        return []
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return rows


def append(path: str, record: dict) -> None:
    with open(path, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")


def next_tag(rows: List[dict]) -> str:
    """The next rNN tag after the highest in the history (r01-style
    series continuation; env MADSIM_TPU_BENCH_TAG overrides in
    bench.py)."""
    best = 0
    for row in rows:
        m = re.fullmatch(r"r(\d+)", str(row.get("tag", "")))
        if m:
            best = max(best, int(m.group(1)))
    return f"r{best + 1:02d}"


# -- legacy BENCH_r0*.json import -------------------------------------------


def import_legacy(repo_dir: str) -> List[dict]:
    """Parse every BENCH_r*.json in `repo_dir` into history rows.
    Handles both shapes in the wild: the driver-capture wrapper
    ({"parsed": {...}}) and the direct bench.py JSON. Fields a
    round didn't record stay None — the neighbor selector treats
    missing lanes/gates as not-comparable rather than guessing."""
    rows: List[dict] = []
    for fname in sorted(glob.glob(os.path.join(repo_dir, "BENCH_r*.json"))):
        m = re.search(r"BENCH_(r\d+)\.json$", fname)
        if not m:
            continue
        try:
            with open(fname) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if "parsed" in doc and isinstance(doc["parsed"], dict):
            doc = doc["parsed"]
        if "value" not in doc:
            continue
        diag = doc.get("diagnostics") or {}
        fp = {
            "host": None,  # legacy files never recorded the host
            "platform": doc.get("platform"),
            "python": None,
            "jax": None,
            "jaxlib": None,
            "lanes": diag.get("lanes"),
            "reps": len(diag["reps"]) if isinstance(diag.get("reps"), list) else None,
            "segment_steps": diag.get("segment_steps"),
            "gates": _norm_gates(doc.get("gates")),
        }
        row = make_record(
            m.group(1),
            doc["value"],
            fp,
            reps=diag.get("reps"),
            compile_s=doc.get("compile_s"),
            spread_pct=diag.get("spread_pct"),
            host_load1=diag.get("host_load1"),
            step_cost=diag.get("step_cost"),
            source=os.path.basename(fname),
        )
        # legacy files never recorded a capture time; null is honest
        # (file order preserves the series order regardless)
        row["ts"] = doc.get("ts")
        rows.append(row)
    return rows


def load_or_seed(path: str, repo_dir: Optional[str] = None) -> List[dict]:
    """Load the history; when the file doesn't exist yet, seed it ONCE
    from the legacy BENCH_r*.json series found in `repo_dir` (default:
    the directory containing `path`)."""
    rows = load(path)
    if rows or os.path.exists(path):
        return rows
    repo_dir = repo_dir or (os.path.dirname(os.path.abspath(path)) or ".")
    legacy = import_legacy(repo_dir)
    for row in legacy:
        append(path, row)
    return legacy


# -- neighbor comparison ----------------------------------------------------


def comparable(fp_a: Optional[dict], fp_b: Optional[dict]) -> bool:
    """Two fingerprints describe the same measurement: platform, lanes
    and the gate tuple must all be recorded and equal; host must match
    when BOTH rows recorded one (legacy rows didn't — they stay
    comparable by config, which is the best the record supports)."""
    if not fp_a or not fp_b:
        return False
    for key in ("platform", "lanes"):
        if fp_a.get(key) is None or fp_a.get(key) != fp_b.get(key):
            return False
    if fp_a.get("gates") is None or fp_a.get("gates") != fp_b.get("gates"):
        return False
    # topology isolation: a missing device_count is a pre-mesh (single-
    # device) row, so legacy history stays comparable to fresh d1 rows
    if (fp_a.get("device_count") or 1) != (fp_b.get("device_count") or 1):
        return False
    host_a, host_b = fp_a.get("host"), fp_b.get("host")
    if host_a is not None and host_b is not None and host_a != host_b:
        return False
    return True


def select_neighbor(rows: List[dict], fingerprint: dict) -> Optional[dict]:
    """The newest prior row comparable to `fingerprint` — the drift-
    aware baseline (same box and config, closest in time)."""
    for row in reversed(rows):
        if comparable(row.get("fingerprint"), fingerprint):
            return row
    return None


def neighbor_budget(
    rows: List[dict], value: float, fingerprint: dict, threshold: float = 0.95
) -> Optional[dict]:
    """The budget receipt for a fresh capture: ratio vs its neighbor,
    or None when no comparable row exists (first capture of a config —
    nothing honest to compare against)."""
    nb = select_neighbor(rows, fingerprint)
    if nb is None or not nb.get("value"):
        return None
    ratio = value / nb["value"]
    return {
        "vs_neighbor": round(ratio, 3),
        "neighbor": nb.get("tag"),
        "neighbor_value": nb["value"],
        "within_5pct": ratio >= threshold,
    }


# -- trend report -----------------------------------------------------------


def _gates_str(fp: Optional[dict]) -> str:
    gates = (fp or {}).get("gates")
    if not gates:
        return "-"
    short = {
        "rng_stream": "rng", "clog_packed": "packed", "pallas_pop": "pallas",
        "flight_recorder": "fr", "coverage": "cov", "provenance": "prov",
    }
    parts = []
    for k in GATE_KEYS:
        v = gates.get(k)
        if isinstance(v, bool):
            if v:
                parts.append(short[k])
        elif v is not None:
            parts.append(f"{short[k]}{v}")
    return "+".join(parts) or "none"


def render_report(rows: List[dict]) -> str:
    """The bench trajectory as text: one line per capture with its
    delta vs its OWN comparable neighbor (so config changes never
    masquerade as regressions), plus a key of config transitions."""
    if not rows:
        return "bench history is empty — run bench.py (it appends every capture)"
    lines = [
        f"{'tag':<8} {'seeds/s':>9} {'vs prev':>8} {'plat':<5} "
        f"{'lanes':>6} {'compile':>8}  gates",
        "-" * 72,
    ]
    for i, row in enumerate(rows):
        fp = row.get("fingerprint") or {}
        nb = select_neighbor(rows[:i], fp) if fp else None
        if nb and nb.get("value"):
            delta = 100.0 * (row["value"] / nb["value"] - 1.0)
            vs = f"{delta:+.1f}%"
        else:
            vs = "new cfg"
        compile_s = row.get("compile_s")
        lines.append(
            f"{str(row.get('tag', '?')):<8} {row['value']:>9.1f} {vs:>8} "
            f"{str(fp.get('platform') or '?'):<5} "
            f"{str(fp.get('lanes') if fp.get('lanes') is not None else '?'):>6} "
            f"{(f'{compile_s:.1f}s' if compile_s is not None else '?'):>8}  "
            f"{_gates_str(fp)}"
        )
    cmp_rows = [
        r for r in rows
        if (r.get("fingerprint") or {}).get("lanes") is not None
    ]
    lines.append("-" * 72)
    lines.append(
        "`vs prev` compares each row against its newest COMPARABLE "
        "neighbor (same platform/lanes/gates, same host when recorded) — "
        "drift and config changes are separated by construction; "
        f"{len(cmp_rows)}/{len(rows)} rows carry a full fingerprint."
    )
    return "\n".join(lines)
