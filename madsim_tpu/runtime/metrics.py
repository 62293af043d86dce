"""Runtime metrics (reference: madsim/src/sim/runtime/metrics.rs).

Also the host-side decoder for the TPU engine's flight-recorder metrics
vector (`StreamCarry.fr_metrics` / `LaneState.fr`): the device
accumulates per-fault-kind injection counters and occupancy high-water
marks in the step kernel; `fr_metrics_dict` turns the harvested int
vector into the labelled dict that run_stream stats and the hunt
report print.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Dict, Sequence

if TYPE_CHECKING:
    from ..task.executor import Executor

# The same table engine/core.py's FAULT_KIND_NAMES / FR_EXTRA_NAMES
# bind — via madsim_tpu/kinds.py (pure literals, no jax import), so
# this host-side decoder can never drift from the device counters.
from ..kinds import FAULT_KIND_NAMES as FR_FAULT_KINDS
from ..kinds import FR_CHURN_KV3A_NAMES as FR_CHURN_KV3A
from ..kinds import FR_CHURN_NAMES as FR_CHURN
from ..kinds import FR_EXTRA_NAMES as FR_EXTRAS

# Causal-provenance word layout (mirrors engine/core.py PROV_*): bits
# [0, 30) = scheduled fault slots, bit 30 = crash-with-amnesia wipe,
# bit 31 = duplicate delivery. Kept as literals so host-side consumers
# (the `/stats` service, dashboards) can decode words without jax.
PROV_FAULT_BITS = 30
PROV_BIT_AMNESIA = 30
PROV_BIT_DUP = 31


def prov_word_bits(word: int) -> Dict[str, object]:
    """Split a violation provenance word into its raw channels:
    implicated scheduled-fault slot indices plus the two non-scheduled
    chaos flags. Kind names need the seed's fault schedule —
    engine/provenance.py decodes those; this is the schedule-free
    half."""
    w = int(word) & 0xFFFFFFFF
    return {
        "fault_slots": [i for i in range(PROV_FAULT_BITS) if (w >> i) & 1],
        "amnesia": bool((w >> PROV_BIT_AMNESIA) & 1),
        "dup": bool((w >> PROV_BIT_DUP) & 1),
    }


def fr_metrics_dict(
    vec: Sequence[int], machine_counters: Sequence[str] = ()
) -> Dict[str, object]:
    """Decode a flight-recorder metrics vector: per-kind fault injection
    totals, the non-scheduled chaos counters (message duplicates pushed,
    crash-with-amnesia restarts applied), then queue / clogged-link /
    killed-node high-water marks; after them the churn process's
    counters where the plan has one, and last the machine's own totals
    (`Machine.STREAM_COUNTERS`, named by the caller: the vector carries
    no names)."""
    v = [int(x) for x in vec]
    nk, ne = len(FR_FAULT_KINDS), len(FR_EXTRAS)
    base = nk + ne + 3
    n_mine = len(machine_counters)
    churn_names = FR_CHURN + FR_CHURN_KV3A
    if len(v) - n_mine not in (base, base + len(FR_CHURN), base + len(churn_names)):
        raise ValueError(
            f"expected {base} metric words (+{len(FR_CHURN)} with a churn "
            f"process, +{len(churn_names)} with one of kind kv3a, +{n_mine} "
            f"of the machine's), got {len(v)}"
        )
    mine, v = v[len(v) - n_mine:], v[:len(v) - n_mine]
    out = {
        "faults_injected": dict(zip(FR_FAULT_KINDS, v[:nk])),
        "dup_injected": v[nk],
        "amnesia_restarts": v[nk + 1],
        "queue_hwm": v[nk + ne],
        "clog_links_hwm": v[nk + ne + 1],
        "killed_hwm": v[nk + ne + 2],
    }
    if len(v) > base:
        # FaultPlan.churn: the ticks fired and the faults they applied
        # (kind kv3a: its two after the three every kind has)
        out["churn"] = dict(zip(churn_names, v[base:]))
    if machine_counters:
        out["machine"] = dict(zip(machine_counters, mine))
    return out


class RuntimeMetrics:
    """Live task census (reference: metrics.rs:6-40)."""

    def __init__(self, executor: "Executor"):
        self._executor = executor

    def num_nodes(self) -> int:
        return len(self._executor.nodes)

    def num_tasks(self) -> int:
        return sum(len(n.tasks) for n in self._executor.nodes.values())

    def num_tasks_by_node(self) -> Dict[str, int]:
        return {
            n.name: len(n.tasks)
            for n in self._executor.nodes.values()
            if n.tasks
        }

    def num_tasks_by_node_by_spawn(self) -> Dict[str, Dict[str, int]]:
        out: Dict[str, Dict[str, int]] = {}
        for n in self._executor.nodes.values():
            if not n.tasks:
                continue
            per: Dict[str, int] = {}
            for t in n.tasks:
                loc = t.location
                if isinstance(loc, tuple):  # (filename, lineno) spawn key
                    loc = f"{loc[0]}:{loc[1]}"
                per[loc] = per.get(loc, 0) + 1
            out[n.name] = per
        return out
