"""Step: share (%) of device busy time under `madsim.step.churn` — the churn
process's generator (`FaultPlan.churn`): the tick's draw, the victim, the
reconnect, the clog rows and the re-armed slot. Self time by phase from the
device trace, as `benchmark/phase_reduce.py` attributes it; None where the
step loop carries no `madsim.step` scope. A program without the process has
no op under the scope and reads 0."""

import glob
import os

from benchmark import phase_reduce

PHASE = "step.churn"


def read(obs):
    if not obs.trace or not obs.trace.get("self_s"):
        return None
    found = sorted(glob.glob(os.path.join(
        obs.session.workdir, "trace", "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    reduced = phase_reduce.phases(
        obs.trace["self_s"], phase_reduce.op_scopes(found[-1]))
    if not reduced["scoped"] or not reduced["busy_s"]:
        return None
    return 100.0 * reduced["phases"].get(PHASE, 0.0) / reduced["busy_s"]
