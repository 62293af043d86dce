"""Seed-batch parallelism over the device mesh.

The scaling axis of a DST framework is *seeds*, not tensors (SURVEY.md
§2.9): lanes are embarrassingly parallel, so sharding the lane dimension
over a 1-D mesh axis "batch" scales linearly over ICI (intra-slice) and
DCN (multi-slice) with zero collectives inside the per-event loop — only
segment-boundary reductions (the 17 registered collectives in
analysis/srules.py COLLECTIVES) and the final result gather cross chips.
This replaces the reference's one-thread-per-seed harness
(madsim/src/sim/runtime/builder.rs:121-160) and its TCP/UCX real-mode
backends (madsim/src/std/net/) as the distributed execution story.

The placement contract is the S-rule carry-axis table
(`analysis.srules.CARRY_AXES`): every "lane" leaf is lane-leading
[L, ...] and shards `NamedSharding(mesh, P(LANE_AXIS))`; every "global"
leaf (scalars, result rings, the OR-folded coverage map) replicates
`P()`. `carry_shardings` below derives the per-leaf sharding pytree
from that table, so the executed placement and the machine-checked
declaration are one artifact — a new carry leaf without a CARRY_AXES
row fails here at trace time AND in `lint` (S002).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: the 1-D lane-sharding mesh axis. Named "batch" (the SNIPPETS.md
#: [1]/[2] idiom and the srules note) — one logical seed batch spans
#: the axis; `SEED_AXIS` is the pre-rebuild alias, kept for callers.
LANE_AXIS = "batch"
SEED_AXIS = LANE_AXIS


def make_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over all (or the given) devices, axis "batch"."""
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (LANE_AXIS,))

def seed_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(LANE_AXIS))


def check_lane_split(mesh: Mesh, n: int) -> None:
    """Refuse a mesh without the "batch" axis, or a batch of `n` lanes
    it does not divide, so every sharding entry point (seed placement,
    making the stream's programs) gets a clear error instead of a raw
    XLA one."""
    if LANE_AXIS not in mesh.shape:
        raise ValueError(
            f'mesh has no "{LANE_AXIS}" axis (axes: {tuple(mesh.shape)}); '
            f"build it with parallel.make_mesh(...)"
        )
    axis = mesh.shape[LANE_AXIS]
    if n % axis != 0:
        raise ValueError(
            f"seed batch ({n}) must be a multiple of the mesh's "
            f'"{LANE_AXIS}" axis size ({axis})'
        )


def shard_seeds(seeds, mesh: Mesh):
    """Place a seed batch sharded over the mesh's "batch" axis; the
    engine's streaming quartet then pins every StreamCarry leaf with
    `carry_shardings` (explicit in/out_shardings, not propagation).

    Validates the mesh and batch shape up front (`check_lane_split`).
    On a multi-host (jax.distributed) mesh, each process materializes
    only its local shard — device_put can't place onto non-addressable
    devices."""
    check_lane_split(mesh, len(seeds))
    sharding = seed_sharding(mesh)
    if any(d.process_index != jax.process_index() for d in mesh.devices.flat):
        # madsim: allow(T001) — deliberate one-time host
        # materialization at stream START (multi-host placement needs
        # the full batch host-side to slice per-process shards); not in
        # the per-segment steady state the T-rules guard
        host = np.asarray(seeds)
        return jax.make_array_from_callback(host.shape, sharding, lambda idx: host[idx])
    return jax.device_put(seeds, sharding)


def _path_field(entry) -> Optional[str]:
    """The attribute/dict-key name of one pytree path entry, or None
    for unnamed entries (sequence indices)."""
    for attr in ("name", "key"):
        v = getattr(entry, attr, None)
        if v is not None:
            return str(v)
    return None


def carry_shardings(mesh: Mesh, carry_tree):
    """The per-leaf NamedSharding pytree for a StreamCarry (aval or
    value): "lane" leaves (per the declared `analysis.srules.CARRY_AXES`
    table) shard their leading [L] dim over the "batch" axis, "global"
    leaves replicate. Passed as jit in_shardings AND out_shardings on
    the stream quartet, so per-lane state never moves between devices
    inside a dispatch — the only cross-device traffic is the registered
    collectives, which XLA places at segment boundaries because that is
    where lane values fold into replicated leaves.

    Raises on a carry field with no CARRY_AXES row: adding carry state
    forces an axis decision (the same contract lint's S002 enforces
    statically)."""
    from ..analysis.srules import CARRY_AXES  # jax-free, no cycle

    lane = NamedSharding(mesh, P(LANE_AXIS))
    repl = NamedSharding(mesh, P())
    carry_table = CARRY_AXES["StreamCarry"]
    state_table = CARRY_AXES["LaneState"]

    def place(path, leaf):
        top = _path_field(path[0]) if path else None
        if top == "state":
            field = _path_field(path[1]) if len(path) > 1 else None
            axis = state_table.get(field)
            table = f"LaneState.{field}"
        else:
            field, axis = top, carry_table.get(top)
            table = f"StreamCarry.{field}"
        if axis is None:
            raise KeyError(
                f"{table} has no analysis/srules.py CARRY_AXES row — "
                f"declare the new leaf lane-leading or global before "
                f"meshing it (S002)"
            )
        return lane if axis == "lane" else repl

    return jax.tree_util.tree_map_with_path(place, carry_tree)


def pad_to_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k
