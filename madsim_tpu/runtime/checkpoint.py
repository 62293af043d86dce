"""Hunt/explore checkpointing — resume an interrupted streaming run exactly.

`hunt --checkpoint PATH` persists per-batch progress from the chunked
streaming driver (`__main__._stream_batches`): the seed cursor, the
completed/failing/infra/abandoned aggregates, the cumulative coverage
map and the plateau-detector state. A process killed between batches
resumes from the last completed batch ("resumed at batch k/n") and the
final report is bit-identical to the uninterrupted run — batch i always
consumes the same seed range, so the only state that matters is the
cursor and the aggregates, both of which are recorded atomically
(tmp + rename) after every batch.

The checkpoint carries a FINGERPRINT of every argument that shapes the
seed schedule or the failure semantics; resuming with a mismatched
command line is refused rather than silently blending two different
hunts. Pure host-side JSON — no jax import.

Guided hunts (`--guided`, madsim_tpu/search) extend the document with
a "guided" record — the bias state, seed corpus, per-batch (seed
schedule, bias state) trail and per-find escalation steps — which is
the COMPLETE remaining-schedule state: a resumed (or
replacement-worker) guided hunt recomputes the identical seed
schedule from it, asserted byte-identical in tests/test_search.py.
"""

from __future__ import annotations

import json
import os
from typing import Optional

CKPT_VERSION = 1

#: keys every complete checkpoint carries. The strict loader below
#: only validates the version (a deliberate `--checkpoint PATH` should
#: fail loudly on anything unexpected); the fleet's lenient reader and
#: `fleet fsck` additionally treat a valid-JSON document missing any of
#: these as corrupt — quarantine to `*.corrupt` and restart the stream
#: — rather than letting a torn artifact crash the farm downstream.
CKPT_REQUIRED_KEYS = frozenset({
    "fingerprint", "batch", "planned", "cursor", "completed",
    "seeds_consumed", "failing", "infra", "abandoned", "done",
})

_UNSET_IS_NONE = frozenset({"churn", "churn_until", "log_capacity", "latency"})

# args fields that must match for a resume to be sound: anything that
# changes which seeds run, in what order, or what they mean.
_FINGERPRINT_FIELDS = (
    "machine",
    "nodes",
    "seed",
    "seeds",
    "batch",
    "max_steps",
    "horizon",
    "loss",
    "faults",
    "fault_tmax",
    "fault_kinds",
    "rng_stream",
    "strict_restart",
    "coverage",
    "stop_on_plateau",
    # guided mode reshapes the whole seed schedule (corpus mutants +
    # bias-selected batches): resuming a guided checkpoint without
    # --guided (or vice versa) would blend two different hunts
    "guided",
    # the deployment's flags (PR 27). Unset reads None whatever the
    # caller's way of leaving a flag out ('' / 0 in a fleet spec), so a
    # checkpoint from before them still belongs to its run
    "churn",
    "churn_until",
    "log_capacity",
    "latency",
)


def fingerprint_from_args(args) -> dict:
    return {f: getattr(args, f, None) or None if f in _UNSET_IS_NONE
            else getattr(args, f, None) for f in _FINGERPRINT_FIELDS}


def save_checkpoint(path: str, state: dict) -> None:
    """Atomic write (the shared `runtime/atomicio` discipline: tmp +
    fsync + rename + dir-fsync): a kill mid-write leaves the previous
    checkpoint intact, never a truncated JSON — on a real filesystem,
    not just against process death. Rides the host timeline as a
    `checkpoint_write` span when a PerfRecorder is active — per-batch
    persistence is part of the wall-clock budget."""
    from ..perf.recorder import maybe_span

    from .atomicio import atomic_write_json

    doc = {"version": CKPT_VERSION, **state}
    with maybe_span("checkpoint_write"):
        atomic_write_json(path, doc)


def load_checkpoint(path: str) -> Optional[dict]:
    """Load a checkpoint, or None when the file doesn't exist (a fresh
    run). A malformed or wrong-version file raises — silently starting
    over would throw away a long hunt's progress."""
    if not os.path.exists(path):
        return None
    with open(path) as f:
        doc = json.load(f)
    if doc.get("version") != CKPT_VERSION:
        raise ValueError(
            f"{path}: checkpoint version {doc.get('version')!r}, "
            f"expected {CKPT_VERSION}"
        )
    return doc


def check_fingerprint(ckpt: dict, args) -> Optional[str]:
    """None when the checkpoint belongs to this command line; otherwise
    a human-readable description naming EVERY field that differs
    (model, kinds, gates, lanes, ...) — a drifted resume usually drifts
    several fields at once, and the fleet worker surfaces this message
    verbatim as the job's `failed` reason, so it must diagnose in one
    shot rather than one refusal per rerun."""
    want = fingerprint_from_args(args)
    got = ckpt.get("fingerprint", {})
    diffs = [
        f"{field} (checkpoint {got.get(field)!r} != this run "
        f"{want.get(field)!r})"
        for field in _FINGERPRINT_FIELDS
        if got.get(field) != want.get(field)
    ]
    if not diffs:
        return None
    return (
        "checkpoint belongs to a different run — refusing to resume; "
        "differing: " + ", ".join(diffs)
    )
