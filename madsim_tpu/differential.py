"""Cross-engine differential harness — SURVEY.md §7's "two engines, one
semantics spec" promise made checkable (VERDICT r2 item 2).

The TPU engine (`engine/core.py`) explores seeds at chip rate over
protocol *step functions*; the host engine (`runtime/`, `task/`, `net/`)
runs the same protocol as free-form async code (the reference's
authoring model, examples/raft_host.py). The engines use different RNG
streams and schedulers, so their traces are not bit-comparable — what
must agree is the *semantics*: the same protocol, under the same fault
schedule, upholds (or, for a seeded bug variant, violates) the same
invariants.

Three bridges:

1. `fault_schedule(engine, seed)` — decode the device lane's fault
   events. A pure function of (seed, FaultPlan); this IS the pinned
   chaos schedule for the seed.
2. `run_host_raft(seed, schedule, ...)` — replay that exact schedule
   (partition/heal, kill/restart, directional clog, group partition,
   loss storm) against the host-engine Raft protocol at the same
   virtual times, recording every applied chaos op.
3. `differential_raft(seeds, ...)` — run both engines per seed and
   compare: safety verdicts (election safety, committed-prefix log
   matching), election liveness, and the applied chaos event stream
   event-for-event against the device schedule.

A drift in either engine's scheduler, fabric, chaos machinery, or Raft
semantics breaks the agreement and fails CI (tests/test_differential.py)
— the cross-engine analogue of the reference's determinism contract
(madsim/src/sim/runtime/mod.rs:178-203).
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Optional

from .engine.core import (
    EV_FAULT,
    F_CHURN_TICK,
    F_CLOG_DIR,
    F_CLOG_GROUP,
    F_CLOG_PAIR,
    F_DELAY_END,
    F_DELAY_SPIKE,
    F_KILL,
    F_LOSS_END,
    F_LOSS_STORM,
    F_RESTART,
    F_UNCLOG_DIR,
    F_UNCLOG_GROUP,
    F_UNCLOG_PAIR,
    Engine,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- the churn process's plain reference (no jax) ------------------------------
#
# `FaultPlan.churn` draws its faults as they fire, and the victim of a
# disconnect is read off the simulation (the leader), so `fault_schedule`
# — a pure function of (seed, FaultPlan) — cannot give them. What IS a
# function of the seed alone is re-derived here in plain Python ints:
# the tick times, the coins and the reconnect picks. The victim comes
# from a callback. The device side of the comparison is
# `applied_churn_faults`: what the CPU replay's lanes really applied.

CHURN_DISCONNECT = "disconnect"
CHURN_RECONNECT = "reconnect"
# kind `kv3a` (ChurnPlan.kind): a tick re-draws the split (the third
# field is the bitmask of the named nodes on side 1), the heal clears it
# (the mask of the named nodes) and kills each named node; a restart
# brings one back
CHURN_PARTITION = "partition"
CHURN_HEAL = "heal"
CHURN_KILL = "kill"
CHURN_RESTART = "restart"
_CHURN_KEY_TAG = 0x4D414443  # engine/core.py CHURN_KEY_TAG
_M32 = 0xFFFFFFFF


def _threefry2x32(k0: int, k1: int, x0: int, x1: int) -> tuple:
    """Threefry-2x32, 20 rounds (Salmon et al., SC'11), on Python ints."""
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    x0 = (x0 + ks[0]) & _M32
    x1 = (x1 + ks[1]) & _M32
    for block in range(5):
        for r in rot[block % 2]:
            x0 = (x0 + x1) & _M32
            x1 = ((x1 << r) | (x1 >> (32 - r))) & _M32
            x1 ^= x0
        x0 = (x0 + ks[(block + 1) % 3]) & _M32
        x1 = (x1 + ks[(block + 2) % 3] + block + 1) & _M32
    return x0, x1


def churn_draw(seed: int, draw: int) -> list:
    """The six words of draw `draw` of a seed's churn stream: Threefry
    over the counters 8*draw + [0, 6), paired (j, j + 3) as
    `jax.extend.random.threefry_2x32` pairs the halves of its counts.
    [disconnect coin, reconnect pick, long-sleep coin, sleep, victim
    pick (machines without a hook), spare]."""
    c = [(8 * draw + j) & _M32 for j in range(6)]
    pairs = [
        _threefry2x32(seed & _M32, _CHURN_KEY_TAG, c[j], c[j + 3])
        for j in range(3)
    ]
    return [p[0] for p in pairs] + [p[1] for p in pairs]


class ChurnReference:
    """The churn process of one seed, stepped tick by tick: the tester's
    loop in plain Python. `plan` is anything with ChurnPlan's fields;
    `nodes`, for a plan of kind `kv3a`, the nodes it acts on
    (`Machine.churn_nodes()`; default all)."""

    def __init__(self, seed: int, plan, n: int, until_us: int, nodes=None):
        self.seed, self.plan, self.n, self.until_us = seed, plan, n, until_us
        self.kv3a = getattr(plan, "kind", "fig8") == "kv3a"
        self.nodes = sorted(nodes) if nodes else list(range(n))
        self.majority = plan.majority or n // 2 + 1
        self.down: set = set()
        self.tick = 0
        # when tick 0 fires: kv3a's partitioner splits, then sleeps
        self.t_us = 0 if self.kv3a else self._sleep(churn_draw(seed, 0))

    def _sleep(self, words) -> int:
        if self.kv3a:
            return self.plan.period_us + words[3] % self.plan.jitter_us
        is_long = words[2] % 1000 < self.plan.long_sleep_permille
        return words[3] % (
            self.plan.long_sleep_us if is_long else self.plan.short_sleep_us
        )

    @property
    def over(self) -> bool:
        """The next event is the heal at `until_us`, not a tick."""
        return self.t_us >= self.until_us

    def connected(self) -> list:
        return [i not in self.down for i in range(self.n)]

    def fire(self, victim) -> list:
        """Apply the tick due at `t_us` — `victim` is the node the
        machine's hook names (-1: none; None: no hook, draw a connected
        node uniformly) — and move on to the next. Returns the faults
        applied: [(t_us, op, node)]."""
        words = churn_draw(self.seed, self.tick + 1)
        t, out = self.t_us, []
        if self.kv3a:
            # one coin a named node: bit i of the draw's word 0
            sides = words[0] & sum(1 << i for i in self.nodes)
            self.tick += 1
            self.t_us = t + self._sleep(words)
            return [(t, CHURN_PARTITION, sides)]
        if victim is None:
            up = [i for i in range(self.n) if i not in self.down]
            victim = up[words[4] % len(up)] if up else -1
        if words[0] % 1000 < self.plan.disconnect_permille and victim >= 0:
            self.down.add(victim)
            out.append((t, CHURN_DISCONNECT, victim))
        if self.n - len(self.down) < self.majority:
            pick = words[1] % self.n
            if pick in self.down:
                self.down.discard(pick)
                out.append((t, CHURN_RECONNECT, pick))
        self.tick += 1
        self.t_us = t + self._sleep(words)
        return out

    def heal(self) -> list:
        if self.kv3a:
            t, back = self.until_us, self.until_us + self.plan.restart_after_us
            return (
                [(t, CHURN_HEAL, sum(1 << i for i in self.nodes))]
                + [(t, CHURN_KILL, i) for i in self.nodes]
                + [(back, CHURN_RESTART, i) for i in self.nodes]
            )
        out = [(self.until_us, CHURN_RECONNECT, i) for i in sorted(self.down)]
        self.down.clear()
        return out


def churn_reference(seed: int, plan, leader_at, *, n: int, until_us: int,
                    horizon_us: Optional[int] = None, nodes=None) -> list:
    """The faults the churn process of `seed` applies, [(t_us, op,
    node)] in order: tick times, coins and reconnect picks from the
    seed, the victim of tick i from `leader_at(t_us, i, connected)`
    (`connected`: bool per node, by the process's own book; return -1
    for no leader, None to let the process draw a connected node).
    Events at or past `horizon_us` are never applied, as on the lane.
    A plan of kind `kv3a` reads nothing off the run (`leader_at` may be
    None): its splits, its heal, its kills and its restarts of `nodes`
    are a function of the seed alone."""
    ref = ChurnReference(seed, plan, n, until_us, nodes=nodes)
    out: list = []
    while not ref.over:
        if horizon_us is not None and ref.t_us >= horizon_us:
            return out
        victim = None if ref.kv3a else leader_at(ref.t_us, ref.tick, ref.connected())
        out += ref.fire(victim)
    if horizon_us is None or until_us < horizon_us:
        out += [
            ev for ev in ref.heal() if horizon_us is None or ev[0] < horizon_us
        ]
    return out


def applied_churn_faults(engine: Engine, seed: int, max_steps: int = 10_000,
                         on_tick=None) -> list:
    """What the lane of `seed` really applied, read off the CPU replay's
    state trail: [(t_us, op, node)] in order. `on_tick(tick, t_us,
    state_before)`, when given, sees the state each churn tick found
    (where a test reads the leader from)."""
    import numpy as np

    from .engine.core import F_CHURN_HEAL, F_CHURN_RESTART, F_CHURN_TICK
    from .engine.replay import replay

    out: list = []
    before = [engine.init_lane(seed)]
    kv3a = engine.config.faults.churn.kind == "kv3a"
    n = engine.machine.NUM_NODES

    def hook(ev, state) -> None:
        op = ev.payload[0]
        if ev.kind == "fault" and op in (F_CHURN_TICK, F_CHURN_HEAL, F_CHURN_RESTART) \
                and ev.time_us < engine.config.horizon_us:
            if on_tick is not None and op == F_CHURN_TICK:
                on_tick(ev.payload[1], ev.time_us, before[0])
            cut, back = (int(x) for x in state.churn["last"])
            if kv3a:
                # the split or the healed set from the process's book; the
                # kills and the restart from the lane's own `killed`
                was, now = (np.asarray(s.killed) for s in (before[0], state))
                if op == F_CHURN_TICK:
                    out.append((ev.time_us, CHURN_PARTITION, cut))
                elif op == F_CHURN_HEAL:
                    out.append((ev.time_us, CHURN_HEAL, back))
                    out.extend((ev.time_us, CHURN_KILL, i)
                               for i in range(n) if now[i] and not was[i])
                else:
                    out.extend((ev.time_us, CHURN_RESTART, i)
                               for i in range(n) if was[i] and not now[i])
            else:
                out.extend((ev.time_us, CHURN_DISCONNECT, i)
                           for i in range(n) if (cut >> i) & 1)
                out.extend((ev.time_us, CHURN_RECONNECT, i)
                           for i in range(n) if (back >> i) & 1)
        before[0] = state

    replay(engine, seed, max_steps=max_steps, on_step=hook, trace=False)
    return out


def _load_raft_host():
    """Import the example protocol (examples/raft_host.py) — the
    differential harness deliberately reuses the *example* code so the
    comparison covers what users actually write, not a purpose-built
    twin."""
    path = os.path.join(_REPO, "examples", "raft_host.py")
    spec = importlib.util.spec_from_file_location("raft_host_example", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fault_schedule(engine: Engine, seed: int) -> List[Dict[str, int]]:
    """Decode the fault events the device lane for `seed` will execute:
    [{"t_us", "op", "a", "b"}, ...] sorted by (time, seq). `a` is a node
    for pair/dir/kill ops, a node *bitmask* for group ops, and the loss
    rate (1/65536 units) for storm ops."""
    import numpy as np

    state = engine.init_lane(seed)
    kind = np.asarray(state.eq_kind)
    valid = np.asarray(state.eq_valid)
    # the churn process's slot is not a scheduled fault: what it applies
    # is read off the run (`applied_churn_faults`)
    sel = valid & (kind == EV_FAULT) & (
        np.asarray(state.eq_payload)[:, 0] < F_CHURN_TICK)
    t = np.asarray(state.eq_time)[sel]
    seq = np.asarray(state.eq_seq)[sel]
    pay = np.asarray(state.eq_payload)[sel]
    order = np.lexsort((seq, t))
    return [
        {"t_us": int(t[i]), "op": int(pay[i][0]), "a": int(pay[i][1]), "b": int(pay[i][2])}
        for i in order
    ]


def run_host_raft(
    seed: int,
    schedule: List[Dict[str, int]],
    n: int = 5,
    horizon_us: int = 5_000_000,
    node_cls=None,
    base_loss: float = 0.0,
    latency_us: Optional[tuple] = None,
    churn_faults: Optional[List[tuple]] = None,
    churn: Optional[tuple] = None,
    closing_commit_s: float = 0.0,
) -> Dict:
    """Run the host-engine example Raft under the pinned `schedule`.

    The churn process (`FaultPlan.churn`) comes in one of two ways.
    `churn_faults` is a lane's applied stream (`applied_churn_faults`:
    [(t_us, op, node)]), replayed at the same virtual times — a
    disconnect is `clog_node`, labrpc's rule exactly: a link carries
    traffic iff both ends are connected. `churn=(plan, until_us)` runs
    the process itself against THIS engine (`ChurnReference`: the same
    ticks, coins and picks from `seed`, the victim this engine's own
    connected leader). `latency_us=(min, max)` sets the fabric's send
    latency. With `closing_commit_s` the harness then does what the
    test's closing `one(cmd, servers)` does, on a net without loss (see
    `closing_commit`): hands one more entry to whoever leads until all
    `n` nodes have committed it, for at most that many virtual seconds
    ("closing_committed" in the result).

    `base_loss` mirrors the device engine's static
    `EngineConfig.packet_loss_rate`: it is installed in the host fabric at
    setup, storms composite on top of it (rate = min(1, base + a/65536)),
    and F_LOSS_END restores it (not 0.0) — so both engines run under the
    same loss conditions.

    Returns {"violation": None | "ELECTION_SAFETY" | "LOG_MATCHING",
    "elected": bool, "max_commit": int, "chaos_applied": [(t_us, op, a, b)],
    "loss_trace": [(t_us, rate), ...]}.
    """
    from . import rand as sim_rand  # noqa: F401  (package side effects)
    from . import time as sim_time
    from .net import NetSim
    from .plugin import simulator
    from .runtime import Handle, Runtime
    from .task import spawn

    ex = _load_raft_host()
    cls = node_cls or ex.RaftNode

    async def scenario():
        handle = Handle.current()
        net = simulator(NetSim)
        # NetSim.config is the outer Config; the fabric reads
        # Network.config == config.net (net/network.py:154) — mutate THAT.
        net.config.net.packet_loss_rate = base_loss
        if latency_us is not None:
            net.config.net.send_latency_min_ns = latency_us[0] * 1000
            net.config.net.send_latency_max_ns = latency_us[1] * 1000
        state: dict = {"loss_trace": [(0, base_loss)]}
        peers = [f"10.3.0.{i+1}:{5000+i}" for i in range(n)]
        nodes = []
        objs: dict = {}  # node index -> its live RaftNode (no kills here)

        def boot(i):
            objs[i] = cls(i, peers, state)
            return objs[i].run()

        for i in range(n):
            node = (
                handle.create_node()
                .name(f"draft-{i}")
                .ip(f"10.3.0.{i+1}")
                .init(lambda i=i: boot(i))
                .build()
            )
            nodes.append(node)
        ids = [nd.id for nd in nodes]
        down: set = set()

        def apply_churn(t_us, op, node):
            if op == CHURN_DISCONNECT:
                net.clog_node(ids[node])
                down.add(node)
            else:
                net.unclog_node(ids[node])
                down.discard(node)
            state.setdefault("churn_applied", []).append((t_us, op, node))

        async def churn_task():
            start = sim_time.now()

            async def until(t_us):
                delta = start + t_us / 1e6 - sim_time.now()
                if delta > 0:
                    await sim_time.sleep(delta)

            if churn_faults is not None:
                for t_us, op, node in churn_faults:
                    await until(t_us)
                    apply_churn(t_us, op, node)
                return
            plan, until_us = churn
            ref = ChurnReference(seed, plan, n, until_us)
            while not ref.over:
                await until(ref.t_us)
                lead = next(
                    (i for i in range(n) if i not in ref.down
                     and i in objs and objs[i].role == ex.LEADER), -1)
                for ev in ref.fire(lead):
                    apply_churn(*ev)
            await until(until_us)
            for ev in ref.heal():
                apply_churn(*ev)

        async def closing_commit() -> bool:
            """labrpc's `one(cmd, servers)`: an entry handed to the
            leader, again after every change of leader, until all `n`
            nodes have committed it. On a net made reliable first — a
            departure from the test, which keeps its 10% loss: the
            example's leader calls its peers one after another, so under
            loss a heartbeat round outlasts an election timeout, terms
            change five times a second and a lagging follower (one
            `next_idx` step a round, reset by every new leader) never
            catches up. What is asked here is that the churn left
            nothing behind that a quiet net cannot repair."""
            net.config.net.packet_loss_rate = 0.0
            deadline = sim_time.now() + closing_commit_s
            while sim_time.now() < deadline:
                lead = next((o for o in objs.values() if o.role == ex.LEADER), None)
                if lead is not None:
                    entry = (lead.term, f"closing-{sim_time.now():.6f}")
                    lead.log.append(entry)
                    lead.persist()
                    idx = len(lead.log) - 1
                    t1 = min(deadline, sim_time.now() + 2.0)
                    while sim_time.now() < t1:
                        await sim_time.sleep(0.05)
                        if all(o.commit >= idx and len(o.log) > idx
                               and o.log[idx] == entry for o in objs.values()):
                            return True
                await sim_time.sleep(0.05)
            return False

        async def chaos():
            applied = state.setdefault("chaos_applied", [])
            start = sim_time.now()

            def group_split(mask_lo, mask_hi):
                # two-word mask: lo carries bits [0, 30), hi [30, 60)
                def bit(i):
                    return (mask_lo >> i) & 1 if i < 30 else (mask_hi >> (i - 30)) & 1

                g = [ids[i] for i in range(n) if bit(i)]
                rest = [ids[i] for i in range(n) if not bit(i)]
                return g, rest

            for ev in schedule:
                target = start + ev["t_us"] / 1e6
                delta = target - sim_time.now()
                if delta > 0:
                    await sim_time.sleep(delta)
                op, a, b = ev["op"], ev["a"], ev["b"]
                if op == F_CLOG_PAIR:
                    net.partition([ids[a]], [ids[b]])
                elif op == F_UNCLOG_PAIR:
                    net.heal([ids[a]], [ids[b]])
                elif op == F_KILL:
                    handle.kill(ids[a])
                elif op == F_RESTART:
                    handle.restart(ids[a])
                elif op == F_CLOG_DIR:
                    net.clog_link(ids[a], ids[b])
                elif op == F_UNCLOG_DIR:
                    net.unclog_link(ids[a], ids[b])
                elif op == F_CLOG_GROUP:
                    net.partition(*group_split(a, b))
                elif op == F_UNCLOG_GROUP:
                    net.heal(*group_split(a, b))
                elif op == F_LOSS_STORM:
                    rate = min(1.0, base_loss + a / 65536.0)
                    net.config.net.packet_loss_rate = rate
                    state["loss_trace"].append((ev["t_us"], rate))
                elif op == F_LOSS_END:
                    net.config.net.packet_loss_rate = base_loss
                    state["loss_trace"].append((ev["t_us"], base_loss))
                elif op == F_DELAY_SPIKE:
                    # device K_DELAY window: ~10% of packets +1-5 s
                    # (the engine's DELAY_PROB/EXTRA constants mirror
                    # these fabric knobs — one semantics, two engines)
                    net.config.net.delay_spike_prob = 0.1
                    state.setdefault("delay_trace", []).append((ev["t_us"], 0.1))
                elif op == F_DELAY_END:
                    net.config.net.delay_spike_prob = 0.0
                    state.setdefault("delay_trace", []).append((ev["t_us"], 0.0))
                applied.append((ev["t_us"], op, a, b))

        spawn(chaos())
        if churn_faults is not None or churn is not None:
            spawn(churn_task())
        await sim_time.sleep(horizon_us / 1e6)
        closing_ok = await closing_commit() if closing_commit_s else None

        violation: Optional[str] = None
        for _term, leaders in state.get("leaders_by_term", {}).items():
            if len(leaders) > 1:
                violation = "ELECTION_SAFETY"
        # committed prefixes must agree pairwise (device invariant twin)
        stable = state.get("stable", {})
        commits = state.get("commits", {})
        for i in commits:
            for j in commits:
                if i >= j:
                    continue
                upto = min(commits[i], commits[j])
                li = stable.get(i, {}).get("log", [])
                lj = stable.get(j, {}).get("log", [])
                for idx in range(1, min(upto + 1, len(li), len(lj))):
                    if li[idx][0] != lj[idx][0]:
                        violation = violation or "LOG_MATCHING"
        return {
            "violation": violation,
            "elected": len(state.get("leaders_by_term", {})) > 0,
            "max_commit": state.get("max_commit", 0),
            "chaos_applied": list(state.get("chaos_applied", [])),
            "loss_trace": list(state.get("loss_trace", [])),
            "delay_trace": list(state.get("delay_trace", [])),
            "churn_applied": list(state.get("churn_applied", [])),
            "closing_committed": closing_ok,
        }

    return Runtime(seed=seed).block_on(scenario())


def run_device_raft(engine: Engine, seed: int, max_steps: int = 3000) -> Dict:
    """One seed on the TPU engine, reduced to the same verdict shape."""
    import jax.numpy as jnp

    from .models.raft import ELECTION_SAFETY, LOG_MATCHING

    res = engine.make_runner(max_steps=max_steps)(
        jnp.asarray([seed], dtype=jnp.uint32)
    )
    code = int(res.fail_code[0])
    names = {ELECTION_SAFETY: "ELECTION_SAFETY", LOG_MATCHING: "LOG_MATCHING"}
    return {
        "violation": names.get(code, str(code)) if bool(res.failed[0]) else None,
        "elected": int(res.summary["max_term"][0]) > 0
        and int(res.summary["max_commit"][0]) > 0,
        "max_commit": int(res.summary["max_commit"][0]),
    }


def differential_raft(
    engine: Engine,
    seeds,
    n: int = 5,
    host_node_cls=None,
    max_steps: int = 3000,
    closing_commit_s: float = 0.0,
) -> Dict:
    """Run every seed on both engines under the device's fault schedule
    — and, where the plan has a churn process, under the faults the
    device lane applied (`applied_churn_faults`), at the same virtual
    times; `closing_commit_s` then asks the host for the test's closing
    commit on all nodes.

    Returns per-seed rows plus aggregates:
      {"rows": [...], "device_violations": int, "host_violations": int,
       "safety_disagreements": int, "schedule_mismatches": int,
       "device_elected": int, "host_elected": int}
    """
    horizon = engine.config.horizon_us
    base_loss = float(getattr(engine.config, "packet_loss_rate", 0.0))
    churn_on = engine.config.faults.churn is not None
    rows = []
    for seed in seeds:
        seed = int(seed)
        sched = fault_schedule(engine, seed)
        dev = run_device_raft(engine, seed, max_steps=max_steps)
        churn_kw = {}
        if churn_on:
            # the process's faults are not in the schedule (the victim
            # is read off the run): the bridge reads what the lane applied
            churn_kw = {
                "churn_faults": applied_churn_faults(engine, seed, max_steps),
                "latency_us": (engine.config.latency_min_us,
                               engine.config.latency_max_us),
                "closing_commit_s": closing_commit_s,
            }
        host = run_host_raft(
            seed, sched, n=n, horizon_us=horizon, node_cls=host_node_cls,
            base_loss=base_loss, **churn_kw,
        )
        rows.append(
            {
                "seed": seed,
                "schedule": sched,
                "device": dev,
                "host": host,
                # the host chaos task is abandoned when the scenario
                # returns at the horizon, so events scheduled at or past
                # it are (correctly) never applied — compare only the
                # in-horizon prefix
                "schedule_ok": host["chaos_applied"]
                == [
                    (e["t_us"], e["op"], e["a"], e["b"])
                    for e in sched
                    if e["t_us"] < horizon
                ]
                and host["churn_applied"] == churn_kw.get("churn_faults", []),
            }
        )
    return {
        "rows": rows,
        "device_violations": sum(1 for r in rows if r["device"]["violation"]),
        "host_violations": sum(1 for r in rows if r["host"]["violation"]),
        "safety_disagreements": sum(
            1
            for r in rows
            if bool(r["device"]["violation"]) != bool(r["host"]["violation"])
        ),
        "schedule_mismatches": sum(1 for r in rows if not r["schedule_ok"]),
        "device_elected": sum(1 for r in rows if r["device"]["elected"]),
        "host_elected": sum(1 for r in rows if r["host"]["elected"]),
    }


# -- the KV service on Raft: a plain reference of the service layer -----------


class _PlainKv:
    """One server's state machine as the lab writes it: a dict of real
    strings and a dict of sessions."""

    def __init__(self):
        self.data: Dict[str, str] = {}
        self.sessions: Dict[int, tuple] = {}  # clerk -> (seq, reply)

    def apply(self, op: int, clerk: int, seq: int, j: int) -> bool:
        """Apply one committed command; False where the session table
        refuses it as a duplicate."""
        last = self.sessions.get(clerk)
        if last is not None and seq <= last[0]:
            return False
        key = str(clerk)
        if op == 1:  # Append(key, "x <clerk> <j> y")
            self.data[key] = self.data.get(key, "") + f"x {clerk} {j} y"
        self.sessions[clerk] = (seq, self.data.get(key, ""))
        return True


def kv_value_words(value: str) -> tuple:
    """(length, rolling hash) of a value string `"x c j y"...`, as
    `models/kvraft.py` holds it: the number of appended items and
    `hash_step` folded over their j's."""
    from .models.kvraft import hash_step

    js = [int(item.split()[2]) for item in value.split("y") if item.strip()]
    h = 0
    for j in js:
        h = hash_step(h, j)
    return len(js), h


def check_clnt_appends(clnt: int, value: str, count: int) -> List[str]:
    """The source's `checkClntAppends`: `value` holds each of the
    clerk's `count` appends exactly once, in order."""
    bad, lastoff = [], -1
    for j in range(count):
        wanted = f"x {clnt} {j} y"
        off = value.find(wanted)
        if off < 0:
            bad.append(f"clerk {clnt}: missing element {wanted!r} in {value!r}")
            continue
        if value.rfind(wanted) != off:
            bad.append(f"clerk {clnt}: duplicate element {wanted!r} in {value!r}")
        if off <= lastoff:
            bad.append(f"clerk {clnt}: wrong order for element {wanted!r} in {value!r}")
        lastoff = off
    return bad


def differential_kvraft(engine: Engine, seed: int, max_steps: int = 10_000) -> Dict:
    """One seed of `--machine kvraft`, its service layer against a plain
    store: the plain reference of `kvraft5`.

    The lane is replayed on the CPU. After every event, every command a
    server has newly applied — read from ITS log in ITS applied order —
    is put to a `_PlainKv` of that server (a dict of real strings
    `"x c j y"` concatenated, a dict of sessions; a fresh one when a
    restart has wiped the server), and the machine's (length, hash) of
    every key on that server is compared with the strings' (`at every
    apply`). Every answer a clerk accepts is compared with what the
    answering server's store recorded for that `(clerk, seq)` — an
    answer that no applied command produced is a mismatch — and, for a
    Get, with the clerk's own model of its value, the concatenation of
    the appends it has had acknowledged (the source's "get wrong
    value"). At the end the source's own check runs on the strings:
    `checkClntAppends` on every closing Get's value, and on every
    server's final value for every key.

    Where this departs from the source, and what it does not cover:

    * A value is a string here and (length, rolling hash of the j's) in
      the machine; the two are compared through `kv_value_words`.
    * One key a clerk (the source's 3A tests use key = clerk id too).
    * A command's identity is `(clerk, seq)`; the source's solutions
      carry the same pair.
    * The Raft layer is NOT checked here: which commands commit, in what
      order, under which leader. There the chain is the machine's own
      invariants 101 / 102 (over terms and commands) and 173 on every
      event, and device == CPU replay in every run (`ROADMAP.md` M8: the
      host example Raft cannot stand as a reference under load)."""
    import numpy as np

    from .engine.replay import replay
    from .models import kvraft as K

    machine = engine.machine
    s_n, c_n = machine.servers, machine.clerks
    stores = [_PlainKv() for _ in range(s_n)]
    ref_applied = [0] * s_n
    model = [""] * c_n  # a clerk's own model: what it has had acknowledged
    acked_appends = [0] * c_n
    closing: Dict[int, str] = {}
    mismatches: List[str] = []
    counts = {"applies": 0, "replies": 0, "refused": 0}
    before = [engine.init_lane(seed)]

    def hook(ev, state) -> None:
        prev, nodes = before[0].nodes, state.nodes
        before[0] = state
        if ev.time_us >= engine.config.horizon_us:
            return
        applied = np.asarray(nodes.last_applied)
        if (applied != np.asarray(prev.last_applied)).any():
            log_cmd = np.asarray(nodes.raft.log_cmd)
            kv_len, kv_hash = np.asarray(nodes.kv_len), np.asarray(nodes.kv_hash)
            for srv in range(s_n):
                if applied[srv] < ref_applied[srv]:  # a restart wiped it
                    stores[srv], ref_applied[srv] = _PlainKv(), 0
                moved = applied[srv] > ref_applied[srv]
                while ref_applied[srv] < applied[srv]:
                    ref_applied[srv] += 1
                    op, clerk, seq, j = (
                        int(x) for x in K.unpack_cmd(int(log_cmd[srv, ref_applied[srv]])))
                    counts["applies"] += 1
                    if not stores[srv].apply(op, clerk, seq, j):
                        counts["refused"] += 1
                if moved:
                    for key in range(c_n):
                        want = kv_value_words(stores[srv].data.get(str(key), ""))
                        got = (int(kv_len[srv, key]), int(kv_hash[srv, key]))
                        if got != want:
                            mismatches.append(
                                f"t={ev.time_us} server {srv} key {key} after index "
                                f"{ref_applied[srv]}: machine {got} != store {want}")
        if ev.kind == "msg" and ev.node >= s_n and ev.payload[0] == K.M_REPLY \
                and ev.payload[2] == K.ST_OK and not bool(before_killed[0][ev.node]):
            clk = ev.node - s_n
            if bool(prev.inflight[clk]) and ev.payload[1] == int(prev.seq[clk]):
                counts["replies"] += 1
                seq, got = ev.payload[1], (ev.payload[3], ev.payload[4])
                said = stores[ev.src].sessions.get(clk)
                if said is None or said[0] != seq:
                    mismatches.append(
                        f"t={ev.time_us} clerk {clk} seq {seq}: server {ev.src} answered "
                        f"{got}, but its store applied no such command ({said})")
                    value = None
                else:
                    value = said[1]
                    if got != kv_value_words(value):
                        mismatches.append(
                            f"t={ev.time_us} clerk {clk} seq {seq}: answer {got} != "
                            f"store {kv_value_words(value)} ({value!r})")
                if int(prev.op[clk]) == K.OP_APPEND:
                    model[clk] += f"x {clk} {acked_appends[clk]} y"
                    acked_appends[clk] += 1
                elif value is not None and value != model[clk]:
                    mismatches.append(
                        f"t={ev.time_us} clerk {clk} seq {seq}: get wrong value "
                        f"{value!r}, expected {model[clk]!r}")
                if int(prev.phase[clk]) == K.CLOSING and value is not None:
                    closing[clk] = value
        before_killed[0] = np.asarray(state.killed)

    before_killed = [np.asarray(before[0].killed)]
    rp = replay(engine, seed, max_steps=max_steps, on_step=hook, trace=False)
    # the source's own check, on the strings
    for clk, value in sorted(closing.items()):
        mismatches += check_clnt_appends(clk, value, acked_appends[clk])
        if kv_value_words(value)[0] != acked_appends[clk]:
            mismatches.append(
                f"clerk {clk}: closing value holds {kv_value_words(value)[0]} "
                f"appends, {acked_appends[clk]} were acknowledged")
    for srv, store in enumerate(stores):
        for key, value in sorted(store.data.items()):
            mismatches += [
                f"server {srv}: {m}" for m in
                check_clnt_appends(int(key), value, kv_value_words(value)[0])]
    nodes = rp.state.nodes
    counters = dict(zip(
        machine.STREAM_COUNTERS, (int(v) for v in machine.stream_counters(nodes))))
    if counters["closing_gets_acked"] != len(closing) and not mismatches:
        mismatches.append(
            f"closing_gets_acked: machine {counters['closing_gets_acked']} != "
            f"reference {len(closing)}")
    return {
        "ok": not mismatches,
        "mismatches": mismatches,
        "applies": counts["applies"],
        "refused": counts["refused"],
        "replies": counts["replies"],
        "closing_values": len(closing),
        "acked_appends": list(acked_appends),
        "counters": counters,
        "replay_failed": rp.failed,
        "fail_code": rp.fail_code,
    }
