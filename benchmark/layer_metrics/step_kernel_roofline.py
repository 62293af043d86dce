"""Step kernels: share (%) of the HBM roofline they reached. Memory-bound
by construction (benchmark/kernel_bytes.py): the bytes every call must
move, from shapes, over the peak HBM bandwidth of peaks.json, over the
device time the calls took.

The trace names a Mosaic call by its HLO op (`body.11`), not by its
kernel, so a call is told apart by rank: the step kernel (megakernel or
pop+gather) runs once per step and is by far the most-called Mosaic op;
the coverage flush, where the engine runs one, is every other Mosaic op.
XLA keeps some operands in VMEM (`S(1)` in their layouts), so the bytes
from shapes are an upper count of HBM traffic; at the 2-3% measured
(my chip runs, PR 24) that is far from mattering."""

from benchmark import kernel_bytes


def read(obs):
    t = obs.trace
    bw = obs.peaks.get("hbm_bytes_per_s")
    if not t or not bw or not t["mosaic_calls"]:
        return None
    per_call = kernel_bytes.bytes_per_call(obs.kernel_shapes)
    step = "step_megakernel" if "step_megakernel" in per_call else "pop_gather"
    if step not in per_call:
        return None
    ranked = sorted(t["mosaic_calls"].items(), key=lambda kv: -kv[1])
    calls_s = {step: (ranked[0][1], t["self_s"].get(ranked[0][0], 0.0))}
    if "cov_flush" in per_call and len(ranked) > 1:
        calls_s["cov_flush"] = (
            sum(n for _k, n in ranked[1:]),
            sum(t["self_s"].get(k, 0.0) for k, _n in ranked[1:]),
        )
    return kernel_bytes.roofline_share(calls_s, obs.kernel_shapes, bw)
