"""Step: share (%) of device busy time under `madsim.step.outbox` +
`madsim.step.timers` — pushing the handler's messages (latency, loss, clog,
duplicates), its timers and the restart boot timer into the event queue.
Self time by phase from the device trace (benchmark/phase_reduce.py)."""

from benchmark import phase_reduce


def read(obs):
    return phase_reduce.share(obs, "step_push_share")
