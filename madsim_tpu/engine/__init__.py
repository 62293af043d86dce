"""The TPU engine: batched discrete-event simulation on device.

See `core.py` for the architecture. Public surface:

  * `Machine` — protocol step-function authoring base (machine.py)
  * `Engine(machine, EngineConfig)` — batch runner: `make_runner()`,
    `run_batch(seeds)`, `failing_seeds(result)`
  * `Engine.run_stream(n_seeds, ...)` — the pipelined streaming
    executor: donated `StreamCarry`, device-side supersegments
    (`segments_per_dispatch`), K-deep async dispatch (`dispatch_depth`);
    `Engine.prepare_stream(...)` makes its programs ready (traced,
    compiled or read from the cache) without running them
  * `replay(engine, seed)` — bit-identical single-seed CPU replay
  * `FaultPlan` — randomized chaos schedules: pair/dir/group
    partitions, kill/restart, loss storms, delay spikes, pause/resume
    windows (freeze + deferred delivery), per-node clock-skew windows,
    Bernoulli message duplication (`allow_dup`), and crash-with-amnesia
    restarts (`strict_restart` + `Machine.durable_spec()`); and
    `FaultPlan(churn=ChurnPlan(), churn_until_us=T)`, a fault PROCESS
    whose ticks draw their faults as they fire (Figure 8's churn)
  * `shrink(engine, seed)` — minimize a failing seed's config (shrink.py)
  * `EngineConfig(trace_ring=R)` + `Engine.ring_trace(result, lane)` —
    on-device last-R-events ring for post-mortems without replay
  * `EngineConfig(flight_recorder=True)` — rolling per-lane trace
    digests + checkpoint ring + on-device fault/queue metrics;
    `audit.collect_trail` / `audit.first_divergence` bisect two trails
    to the first divergent checkpoint (audit.py)
  * `EngineConfig(coverage=True)` — scenario-coverage telemetry:
    per-lane AFL-style hit maps over (model projection, event kind,
    fault context), OR-reduced at stream harvest into
    `stats["coverage"]` (ops/coverage.py device side,
    runtime/coverage.py host side: plateau policy, persistence, diff)
"""

from .core import (
    BatchResult,
    ChurnPlan,
    Engine,
    EngineConfig,
    FaultPlan,
    LaneState,
    StreamCarry,
    EV_FAULT,
    EV_MSG,
    EV_TIMER,
    FAULT_KIND_NAMES,
    OVERFLOW,
)
from . import audit
from .machine import (
    BOOT,
    Machine,
    Outbox,
    empty_outbox,
    send,
    send_if,
    set_timer,
    set_timer_if,
    update_node,
)
from .replay import ReplayResult, TraceEvent, decode_ring, replay, replay_diff
from . import corpus
from .shrink import ShrinkResult, shrink

__all__ = [
    "BatchResult",
    "Engine",
    "EngineConfig",
    "FaultPlan",
    "LaneState",
    "StreamCarry",
    "Machine",
    "Outbox",
    "BOOT",
    "empty_outbox",
    "send",
    "send_if",
    "set_timer",
    "set_timer_if",
    "update_node",
    "replay",
    "replay_diff",
    "decode_ring",
    "shrink",
    "corpus",
    "ShrinkResult",
    "ReplayResult",
    "TraceEvent",
    "EV_TIMER",
    "EV_MSG",
    "EV_FAULT",
    "FAULT_KIND_NAMES",
    "OVERFLOW",
    "audit",
]
