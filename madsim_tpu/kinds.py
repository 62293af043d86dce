"""The fault-kind vocabulary — single source of truth.

Every host-side mirror of the chaos palette (flight-recorder counter
labels, coverage band names, shrink's ablation table, CLI
`--fault-kinds` parsing) historically kept its own literal copy of this
table, and nothing checked them against each other — the G-rules of
`python -m madsim_tpu lint` grew out of exactly that drift hazard. The
copies now live here once; consumers import (`engine/core.py`,
`runtime/metrics.py`, `ops/coverage.py`, `runtime/coverage.py`,
`engine/shrink.py`, `__main__.py`) and the lint G-pass statically
cross-checks both this file's internal consistency and that every
consumer still binds from it.

Contract notes:

* This module imports NOTHING (the host-side decoders that use it —
  `runtime/metrics.py`, `runtime/coverage.py` — are jax-free by
  contract, and the lint G-pass parses it statically).
* Every table below is a PURE LITERAL: the lint G-pass resolves tuple
  literals and `+`-concatenations only, on purpose — a computed table
  could silently encode the very drift this file exists to prevent.
* `FAULT_KIND_NAMES` order IS the `K_*` index space in
  `engine/core.py` (lint rule G007 asserts `K_<NAME> ==
  FAULT_KIND_NAMES.index(name)`). Append new kinds at the TAIL — the
  indices are baked into recorded fault schedules and golden pins.
"""

from __future__ import annotations

# Scheduled fault kinds, indexed by engine/core.py's K_* constants.
FAULT_KIND_NAMES = (
    "pair", "kill", "dir", "group", "storm", "delay", "pause", "skew",
    "torn", "heal-asym",
)

# Non-scheduled chaos channels (flight-recorder extra counters): the
# Bernoulli duplicate-delivery gate and crash-with-amnesia restarts.
FR_EXTRA_NAMES = ("dup", "amnesia")

# The churn process's counters (`FaultPlan.churn`, engine/core.py
# `LaneState.churn`): the ticks fired and the faults they applied. They
# ride the flight recorder's metrics vector after the high-water marks
# while the process is on.
FR_CHURN_NAMES = ("ticks", "disconnects", "reconnects")
# a process of kind `kv3a` keeps two more, after those three: the
# re-draws of the split, and the kills of every named node at once (one
# a kill, however many nodes it takes)
FR_CHURN_KV3A_NAMES = ("partitions", "crashes")

# kind name -> FaultPlan field, in K_* index order.
KIND_TO_FLAG = (
    ("pair", "allow_partition"),
    ("kill", "allow_kill"),
    ("dir", "allow_dir_clog"),
    ("group", "allow_group"),
    ("storm", "allow_storm"),
    ("delay", "allow_delay"),
    ("pause", "allow_pause"),
    ("skew", "allow_skew"),
    ("torn", "allow_torn"),
    ("heal-asym", "allow_heal_asym"),
)

# The two chaos gates that are not scheduled kinds but still FaultPlan
# flags (shrink ablates them; strict-restart has its own CLI flag).
EXTRA_FLAGS = (
    ("dup", "allow_dup"),
    ("strict-restart", "strict_restart"),
)

# The `--fault-kinds` CLI vocabulary with its historical print order
# (dup rides between the window kinds and the PR-6 storage kinds —
# shrink repro lines have printed this order since PR-5; keep it).
CLI_KIND_TO_FLAG = (
    ("pair", "allow_partition"),
    ("kill", "allow_kill"),
    ("dir", "allow_dir_clog"),
    ("group", "allow_group"),
    ("storm", "allow_storm"),
    ("delay", "allow_delay"),
    ("pause", "allow_pause"),
    ("skew", "allow_skew"),
    ("dup", "allow_dup"),
    ("torn", "allow_torn"),
    ("heal-asym", "allow_heal_asym"),
)

# Coverage band names (ops/coverage.py slot layout): bands 0/1 are the
# event classes, bands 2..7 the first six scheduled kinds; the 4-bit v2
# layout appends the window kinds, the two synthetic chaos bands, and
# the storage kinds (band 4+k for scheduled kind k >= 8). Band names
# use "_" where kind names use "-" (band names feed prometheus labels).
COV_BAND_NAMES = ("timer", "msg", "pair", "kill", "dir", "group", "storm", "delay")
COV_BAND_NAMES_V2 = COV_BAND_NAMES + (
    "pause", "skew", "dup", "amnesia",
    "torn", "heal_asym", "reserved14", "reserved15",
)

# Runtime conveniences (derived — the lint G-pass ignores these and
# checks the literals above instead).
FLAG_BY_KIND = dict(KIND_TO_FLAG + EXTRA_FLAGS)
KIND_BY_FLAG = {field: name for name, field in KIND_TO_FLAG + EXTRA_FLAGS}


def band_name(kind_name: str) -> str:
    """Coverage-band label for a fault-kind name."""
    return kind_name.replace("-", "_")
