"""Bytes each step-kernel call must move, from shapes alone.

The three Pallas TPU kernels of `madsim_tpu/ops/pallas_pop.py` read
whole [lanes, Q] planes of the event queue from HBM and write one
column per plane back; every element is 4 bytes (int32 / uint32). None
does arithmetic worth counting against 197 TFLOP/s (an argmin, a
one-hot gather, 20 Threefry rounds on a [lanes, W] block), so their
roofline is the HBM one: bytes / 819 GB/s. `cost_analysis` is no source
for this — it counts a loop body once (ROADMAP re-anchor note).

A kernel is recognised in the trace by the name Mosaic gives its custom
call; `KERNELS` maps a pattern of that name to the function of shapes.
"""

from __future__ import annotations

import re

WORD = 4  # bytes: every plane is int32 or uint32


def queue_planes(payload_width: int) -> int:
    """time, seq, valid, then the gathered values: kind, node, src and
    one plane per payload column."""
    return 3 + 3 + payload_width


def pop_gather_bytes(lanes: int, queue: int, payload_width: int) -> int:
    """Stream v2's fused pop+gather: every queue plane in, one column
    per plane out (idx, any, time, then the values)."""
    planes = queue_planes(payload_width)
    return WORD * lanes * (planes * queue + planes)


def step_megakernel_bytes(lanes: int, queue: int, payload_width: int,
                          rng_words: int, with_digest: bool) -> int:
    """Stream v3's step megakernel: pop+gather plus the lane key (2),
    the step counter and, under the recorder, the two digest words in;
    the RNG word block [lanes, W] and the two new digest words out."""
    planes = queue_planes(payload_width)
    scalars_in = 3 + (2 if with_digest else 0)
    cols_out = planes + rng_words + (2 if with_digest else 0)
    return WORD * lanes * (planes * queue + scalars_in + cols_out)


def cov_flush_bytes(lanes: int, map_words: int, buffer_slots: int) -> int:
    """The coverage flush: each lane's packed map in and out, its slot
    buffer and live count in."""
    return WORD * lanes * (2 * map_words + buffer_slots + 1)


def shapes_of(eng, lanes: int) -> dict:
    """The sizes the functions above need, read off the engine."""
    cfg = eng.config
    return {
        "lanes": int(lanes),
        "queue": int(cfg.queue_capacity),
        "payload_width": int(eng.machine.PAYLOAD_WIDTH),
        "rng_words": int(eng._rng_layout.total_words),
        "with_digest": bool(cfg.flight_recorder),
        "map_words": (2 ** int(cfg.cov_slots_log2)) // 32 if cfg.coverage else 0,
        "buffer_slots": int(cfg.cov_buffer) if cfg.coverage else 0,
        "megakernel": bool(eng.use_megakernel),
        "pop_gather": bool(eng.use_pallas_pop and not eng.use_megakernel),
        "cov_flush": bool(cfg.coverage and eng.use_pallas_pop
                          and int(cfg.cov_buffer) > 0),
    }


def bytes_per_call(shapes: dict) -> dict:
    """{kernel: bytes one call must move} for the kernels this engine runs."""
    out = {}
    s = shapes
    if s["megakernel"]:
        out["step_megakernel"] = step_megakernel_bytes(
            s["lanes"], s["queue"], s["payload_width"], s["rng_words"],
            s["with_digest"])
    if s["pop_gather"]:
        out["pop_gather"] = pop_gather_bytes(
            s["lanes"], s["queue"], s["payload_width"])
    if s["cov_flush"]:
        out["cov_flush"] = cov_flush_bytes(
            s["lanes"], s["map_words"], s["buffer_slots"])
    return out


def roofline_share(calls_s: dict, shapes: dict, peak_bytes_per_s: float):
    """Share (%) of the HBM roofline the step kernels reached: the least
    time their calls could take (bytes over peak bandwidth) over the
    device time they took. `calls_s` is {kernel: (calls, seconds)} for
    the kernels of `bytes_per_call`. None when no kernel time was seen."""
    per_call = bytes_per_call(shapes)
    least = sum(per_call[k] * n / peak_bytes_per_s
                for k, (n, _s) in calls_s.items() if k in per_call)
    took = sum(sec for k, (_n, sec) in calls_s.items() if k in per_call)
    return 100.0 * least / took if took > 0 else None
