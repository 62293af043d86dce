"""Failing-seed corpus — found bugs as durable regression artifacts.

The reference's workflow stops at printing `MADSIM_TEST_SEED=N` repro
hints; FoundationDB-style DST practice goes further: every found seed
becomes a corpus entry that is re-verified forever. An entry is "open"
while the bug reproduces (the repro must keep failing — if it stops,
the bug was fixed, or the repro rotted) and "fixed" once resolved (the
seed must pass forever — failing again is a regression alarm).

Entries carry everything needed to rebuild the run: machine name (CLI
registry), node count, seed, expected fail code, the (shrunk) engine
config, and a sufficient step budget. `python -m madsim_tpu hunt`
explores + shrinks + appends; `python -m madsim_tpu regress` re-verifies
every entry bit-identically on the host replay path.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, List, Optional

from .core import Engine, EngineConfig, FaultPlan
from .replay import replay

STATUS_OPEN = "open"    # bug reproduces: entry must keep failing with its code
STATUS_FIXED = "fixed"  # bug resolved: entry must keep passing


def config_to_dict(cfg: EngineConfig) -> dict:
    d = dataclasses.asdict(cfg)
    # host-side knobs, never trace-affecting: a corpus entry must replay
    # on any machine — not name some other box's cache directory, and
    # not demand (or forbid) the fused step kernel its recording box
    # happened to resolve (the megakernel is asserted bit-identical to
    # the XLA oracle under its gate)
    d.pop("compile_cache_dir", None)
    d.pop("pallas_megakernel", None)
    # the flight recorder is asserted bit-identical under its gate, so
    # entries don't record it: the digest trail lives in the entry's own
    # digests/digest_final fields, and the auditor re-enables the
    # recorder itself at the recorded cadence
    for k in ("flight_recorder", "fr_digest_every", "fr_digest_ring"):
        d.pop(k, None)
    # scenario coverage is the same class of gate: write-only telemetry,
    # asserted bit-identical — entries must replay with or without it
    # (cov_buffer is the buffered-fold perf knob: final maps are
    # bit-identical to the per-event path, so it never enters an entry)
    for k in ("coverage", "cov_slots_log2", "cov_band_bits_min", "cov_buffer"):
        d.pop(k, None)
    # causal provenance too: lineage words never feed back into results,
    # and `why` re-enables the gate itself at replay time
    d.pop("provenance", None)
    # a plan without a churn process is written as it always was
    if d["faults"].get("churn") is None:
        d["faults"].pop("churn", None)
        d["faults"].pop("churn_until_us", None)
    elif d["faults"]["churn"].get("kind") == "fig8":
        # and a fig8 process without the fields only kind kv3a reads
        for k in ("kind", "period_us", "jitter_us", "restart_after_us"):
            d["faults"]["churn"].pop(k, None)
    return d


def config_from_dict(d: dict) -> EngineConfig:
    d = dict(d)
    faults = d.pop("faults", None)
    cfg = EngineConfig(**d, faults=FaultPlan(**faults) if faults else FaultPlan())
    return cfg


@dataclasses.dataclass
class CorpusEntry:
    machine: str
    seed: int
    fail_code: int
    status: str  # STATUS_OPEN | STATUS_FIXED
    config: EngineConfig
    max_steps: int
    nodes: int = 0
    note: str = ""
    # `--log-capacity` of the run that found it (0 = the registry's own)
    log_capacity: int = 0
    # Flight-recorder provenance (engine/audit.py): the digest trail
    # recorded when the entry was (re-)recorded — checkpoints every
    # `digest_every` steps as [step, d0, d1], the final [step, d0, d1],
    # and the environment fingerprint (jax/jaxlib/python/engine
    # versions) it was recorded under. `python -m madsim_tpu audit`
    # replays the entry and bisects this trail to the first divergent
    # checkpoint; entries predating the recorder carry empty trails.
    digest_every: int = 0
    digests: list = dataclasses.field(default_factory=list)
    digest_final: list = dataclasses.field(default_factory=list)
    # Free-form provenance. `audit.record_entry` merges the environment
    # fingerprint (jax/jaxlib/python/engine versions) in here; entries
    # filed by the hunt fleet additionally carry `filed_by` ({job,
    # worker, fingerprint_sha} — which fleet job found this), `repro`
    # (the minimal replay command line) and `why_kinds` (the causally
    # implicated fault kinds from the provenance word). Keys survive
    # re-recording: the auditor merges rather than replaces.
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def key(self) -> tuple:
        return (self.machine, self.nodes, self.seed, self.fail_code)

    def to_dict(self) -> dict:
        d = {
            "machine": self.machine,
            "nodes": self.nodes,
            "seed": self.seed,
            "fail_code": self.fail_code,
            "status": self.status,
            "max_steps": self.max_steps,
            "note": self.note,
            "config": config_to_dict(self.config),
        }
        if self.log_capacity:
            d["log_capacity"] = self.log_capacity
        if self.digest_every:
            d["digest_every"] = self.digest_every
            d["digests"] = [[int(x) for x in ck] for ck in self.digests]
            d["digest_final"] = [int(x) for x in self.digest_final]
        if self.meta:
            d["meta"] = dict(self.meta)
        return d

    @staticmethod
    def from_dict(d: dict) -> "CorpusEntry":
        return CorpusEntry(
            machine=d["machine"],
            nodes=int(d.get("nodes", 0)),
            seed=int(d["seed"]),
            fail_code=int(d["fail_code"]),
            status=d.get("status", STATUS_OPEN),
            max_steps=int(d["max_steps"]),
            note=d.get("note", ""),
            log_capacity=int(d.get("log_capacity", 0)),
            config=config_from_dict(d["config"]),
            digest_every=int(d.get("digest_every", 0)),
            digests=[[int(x) for x in ck] for ck in d.get("digests", [])],
            digest_final=[int(x) for x in d.get("digest_final", [])],
            meta=dict(d.get("meta", {})),
        )


def load(path: str) -> List[CorpusEntry]:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        data = json.load(f)
    return [CorpusEntry.from_dict(d) for d in data.get("entries", [])]


def save(path: str, entries: List[CorpusEntry]) -> None:
    from ..runtime.atomicio import atomic_write_json

    atomic_write_json(
        path, {"version": 1, "entries": [e.to_dict() for e in entries]},
        indent=2, sort_keys=False,
    )


def add(path: str, entry: CorpusEntry) -> bool:
    """Append an entry unless one with the same (machine, nodes, seed,
    code) already exists. Returns True if added."""
    entries = load(path)
    if any(e.key == entry.key for e in entries):
        return False
    entries.append(entry)
    save(path, entries)
    return True


@dataclasses.dataclass
class RegressOutcome:
    entry: CorpusEntry
    failed: bool            # did the replay fail
    fail_code: int
    ok: bool                # outcome matches the entry's status contract
    verdict: str            # human-readable disposition


def entry_machine(entry: CorpusEntry, build_machine: Callable[..., object]):
    """The machine an entry ran on: `build_machine(name, nodes)`, with
    the entry's log capacity as a third argument where it records one."""
    if entry.log_capacity:
        return build_machine(entry.machine, entry.nodes, entry.log_capacity)
    return build_machine(entry.machine, entry.nodes)


def check(entry: CorpusEntry, build_machine: Callable[..., object]) -> RegressOutcome:
    """Re-run one entry on the host replay path and judge it against its
    status contract. `build_machine(name, nodes)` resolves the machine."""
    eng = Engine(entry_machine(entry, build_machine), entry.config)
    rp = replay(eng, entry.seed, max_steps=entry.max_steps, trace=False)
    failed = bool(rp.failed)
    code = int(rp.fail_code)
    same_failure = failed and code == entry.fail_code
    if entry.status == STATUS_OPEN:
        if same_failure:
            return RegressOutcome(entry, failed, code, True, "still open (reproduces)")
        if failed:
            return RegressOutcome(
                entry, failed, code, False,
                f"DRIFT: fails with code {code}, expected {entry.fail_code}",
            )
        return RegressOutcome(
            entry, failed, code, False,
            "appears FIXED (no longer reproduces) — re-run with --promote",
        )
    # STATUS_FIXED: must pass
    if not failed:
        return RegressOutcome(entry, failed, code, True, "fixed (still passes)")
    return RegressOutcome(
        entry, failed, code, False, f"REGRESSION: fails again with code {code}"
    )
