"""Multi-host seed-batch scale-out over DCN (jax.distributed).

The reference scales out with one OS thread per seed on one machine
(madsim/src/sim/runtime/builder.rs:121-160) and TCP/UCX real-mode
transports between machines (madsim/src/std/net/). The tpu-native
equivalent (SURVEY.md §2.9/§5.8): every host joins one jax.distributed
job, the seed-lane axis shards over the *global* device mesh (ICI within
a slice, DCN across slices/hosts), and the engine's fused segment runs
SPMD — each process computes only its lane shard, and only replicated
reductions (completed counts, the fixed-capacity failing-seed ring)
cross hosts.

Since the lane-axis mesh rebuild, this module is a thin veneer: the
engine's `run_stream(mesh=...)` path pins every StreamCarry leaf with
explicit `carry_shardings` (parallel/__init__.py) derived from the
declared CARRY_AXES table, and the 17 registered collectives
(analysis/srules.py COLLECTIVES) are the only cross-device traffic.
`run_stream_global` just builds the all-hosts mesh and delegates; the
single-host and multi-host code paths are the same jitted program.

Smoke-tested without TPU pods by running N processes on one machine with
virtual CPU devices (tests/test_multihost.py: 2 processes x 4 devices,
Gloo collectives) — the same code path a v5e multi-host job takes.

Env-driven setup (mirrors the MADSIM_TEST_* harness style):
  MADSIM_TPU_COORDINATOR  host:port of process 0
  MADSIM_TPU_NUM_PROCS    total process count
  MADSIM_TPU_PROC_ID      this process's id
On managed TPU pods (GKE/queued resources), call `initialize()` with no
arguments — jax auto-detects the cluster.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import SEED_AXIS, make_mesh, seed_sharding

_ENV_COORD = "MADSIM_TPU_COORDINATOR"
_ENV_NPROCS = "MADSIM_TPU_NUM_PROCS"
_ENV_PID = "MADSIM_TPU_PROC_ID"


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join (or start) the distributed job. Idempotent. Arguments fall
    back to MADSIM_TPU_* env vars, then to jax's cluster auto-detection
    (TPU pod metadata)."""
    if getattr(initialize, "_done", False):
        return
    coordinator_address = coordinator_address or os.environ.get(_ENV_COORD)
    if num_processes is None and os.environ.get(_ENV_NPROCS):
        num_processes = int(os.environ[_ENV_NPROCS])
    if process_id is None and os.environ.get(_ENV_PID):
        process_id = int(os.environ[_ENV_PID])
    try:
        # NOTE: must run before anything touches the XLA backend —
        # including jax.devices()/process_count(), so no jax-based
        # "already initialized" probe is possible here
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        if "already" not in str(e).lower():
            raise
    initialize._done = True  # type: ignore[attr-defined]


def global_mesh():
    """1-D "batch" (lane-axis) mesh over every device in the job (all
    hosts)."""
    return make_mesh(jax.devices())


def global_seeds(n_seeds: int, seed_start: int = 0, mesh=None) -> jax.Array:
    """uint32 [seed_start, seed_start+n) sharded over the global mesh.
    Each process materializes only its local shard."""
    mesh = mesh if mesh is not None else global_mesh()
    axis = mesh.shape[SEED_AXIS]
    if n_seeds % axis != 0:
        raise ValueError(f"n_seeds ({n_seeds}) must be a multiple of the global device count ({axis})")

    def local_shard(index):
        return np.arange(seed_start, seed_start + n_seeds, dtype=np.uint32)[index]

    return jax.make_array_from_callback((n_seeds,), seed_sharding(mesh), local_shard)


def run_stream_global(
    engine,
    n_seeds: int,
    batch: int = 1024,
    segment_steps: int = 256,
    seed_start: int = 0,
    max_steps: int = 10_000,
    mesh=None,
    **stream_kwargs,
) -> dict:
    """Seed streaming sharded over the global (all-hosts) mesh: every
    process runs the identical SPMD pipelined executor — device-side
    supersegments, donated carry, K-deep dispatch (run_stream's other
    keyword arguments pass through) — and the host loops stay in lockstep because every
    decision they make reads replicated counters. Only the counters
    poll and the ring drains cross DCN, each a few hundred bytes, so
    the steady state is collective-free exactly like the single-host
    path. Returns run_stream's dict (identical on every process).
    """
    mesh = mesh if mesh is not None else global_mesh()
    axis = mesh.shape[SEED_AXIS]
    if batch % axis != 0:
        raise ValueError(
            f"batch ({batch}) must be a multiple of the global device count ({axis})"
        )
    return engine.run_stream(
        n_seeds,
        batch=batch,
        segment_steps=segment_steps,
        seed_start=seed_start,
        max_steps=max_steps,
        mesh=mesh,
        **stream_kwargs,
    )


def run_batch_global(
    engine,
    n_seeds: int,
    seed_start: int = 0,
    max_steps: int = 10_000,
    fail_capacity: int = 1024,
    mesh=None,
) -> dict:
    """Run a globally-sharded seed batch SPMD across every host and
    return host-local results: completion/failure counts plus up to
    `fail_capacity` failing (seed, code) pairs, identical on every
    process (replicated reductions — the only cross-host traffic).
    """
    mesh = mesh if mesh is not None else global_mesh()
    seeds = global_seeds(n_seeds, seed_start, mesh)
    res = jax.jit(partial(engine.run_batch, max_steps=max_steps))(seeds)

    replicated = NamedSharding(mesh, P())

    # The audited cross-lane baseline of this (pre-pipelined-executor)
    # module, kept as the simple one-shot alternative to the stream
    # path. Each op carries its S-rule collective annotation; the
    # registry entries (analysis/srules.py COLLECTIVES, multihost-*)
    # record the all-reduce each is under NamedSharding(mesh, P('batch')):
    # the ranks scan + masked ring gather stay the ONLY cross-host
    # data movement (failing lanes only, never a full [L] all-gather),
    # and the completion count is already a psum by virtue of the
    # replicated out_shardings.
    @partial(jax.jit, out_shardings=replicated)
    def stats(r):
        from ..perf import xprof

        mask = r.failed
        with xprof.collective_scope("multihost-fail-ranks"):
            # madsim: collective(multihost-fail-ranks, reduce=scan)
            csum = jnp.cumsum(mask.astype(jnp.int32))
        n_fail = csum[-1] if mask.shape[0] else jnp.int32(0)
        want = jnp.arange(fail_capacity, dtype=jnp.int32) + 1
        src = jnp.clip(
            jnp.searchsorted(csum, want, side="left").astype(jnp.int32),
            0,
            max(mask.shape[0] - 1, 0),
        )
        fill = want <= n_fail
        with xprof.collective_scope("multihost-completed-sum"):
            # madsim: collective(multihost-completed-sum, reduce=sum)
            completed = r.done.sum(dtype=jnp.int32)
        with xprof.collective_scope("multihost-fail-ring"):
            # madsim: collective(multihost-fail-ring, reduce=gather)
            fail_seeds = jnp.where(fill, r.seeds[src], 0)
            # madsim: collective(multihost-fail-ring, reduce=gather)
            fail_codes = jnp.where(fill, r.fail_code[src], 0)
        return {
            "completed": completed,
            "failed": n_fail,
            "fail_seeds": fail_seeds,
            "fail_codes": fail_codes,
        }

    out = jax.device_get(stats(res))
    n_fail = int(out["failed"])
    listed = min(n_fail, fail_capacity)
    return {
        "completed": int(out["completed"]),
        "failed": n_fail,
        "failing": [
            (int(s), int(c))
            for s, c in zip(out["fail_seeds"][:listed], out["fail_codes"][:listed])
        ],
        "truncated": n_fail > fail_capacity,
        "processes": jax.process_count(),
        "global_devices": jax.device_count(),
    }
