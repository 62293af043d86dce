"""The trace reduction on a small recorded trace (fixtures/): busy/idle
union, self times, Mosaic share, gap attribution, collective share —
each held against a brute-force reference that walks the time axis
boundary by boundary, the slow way the reduction does not."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def load(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def brute(trace):
    """Walk every elementary segment between event boundaries and ask,
    by looping over all events, what lies over it."""
    notes = tr.host_annotations(trace)
    windows = [(a, b) for a, b, n in notes if n == tr.WINDOW_SPAN]
    ops_by_device = tr.device_ops(trace)
    out = {"busy": 0.0, "self": {}, "gaps": {}, "coll": 0.0, "mosaic": 0.0}
    n_dev = len(ops_by_device)
    for ops in ops_by_device.values():
        cuts = sorted({t for a, b, *_ in ops for t in (a, b)}
                      | {t for a, b, _n in notes for t in (a, b)})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            if not any(a <= mid < b for a, b in windows):
                continue
            over = [o for o in ops if o[0] <= mid < o[1]]
            if over:
                out["busy"] += (hi - lo) / n_dev
                # innermost: the op that started last (ties: the shorter,
                # then the later in the trace: the fixture's clipped
                # enclosing ops share one interval)
                a, b, name, cat = sorted(over, key=lambda o: (o[0], -o[1]))[-1]
                out["self"][name] = out["self"].get(name, 0.0) + (hi - lo) / n_dev
                if cat == tr.MOSAIC:
                    out["mosaic"] += (hi - lo) / n_dev
                elif tr.COLLECTIVE_RE.match(cat):
                    out["coll"] += (hi - lo) / n_dev
            else:
                host = [s for s in notes if s[0] <= mid < s[1]]
                name = sorted(host, key=lambda s: (s[0], -s[1]))[-1][2] \
                    if host else "unattributed"
                out["gaps"][name] = out["gaps"].get(name, 0.0) + (hi - lo) / n_dev
    return out, sum(b - a for a, b in tr.union(windows))


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(FIXTURES) if f.endswith(".trace.json")))
def test_the_reduction_agrees_with_the_slow_walk(name):
    trace = load(name)
    got = tr.reduce(trace, top=10, min_gap_ns=0.0)
    want, window_ns = brute(trace)
    assert got["window_from_annotations"]
    assert got["window_s"] == pytest.approx(window_ns / 1e9)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["busy_s"] == pytest.approx(want["busy"] / 1e9, rel=1e-9)
    assert got["mosaic_s"] == pytest.approx(want["mosaic"] / 1e9, rel=1e-9)
    assert got["collective_s"] == pytest.approx(want["coll"] / 1e9, rel=1e-9)
    for op, sec in got["self_s"].items():
        assert sec == pytest.approx(want["self"][op] / 1e9, rel=1e-9), op
    # self times partition busy time; named gaps partition idle time
    assert sum(got["self_s"].values()) == pytest.approx(got["busy_s"], rel=1e-9)
    assert sum(got["gaps_s"].values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-9)
    for gap, sec in got["gaps_s"].items():
        assert sec == pytest.approx(want["gaps"][gap] / 1e9, rel=1e-9), gap
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    assert got["device_ops"] == sorted(got["device_ops"], key=lambda kv: -kv[1])
    assert got["collective_exposed_s"] <= got["collective_s"] + 1e-12


def test_short_gaps_are_pooled_not_named():
    # the synthetic trace: in the recorded slices an enclosing `while`
    # covers the whole slice, so the device is never idle there
    trace = load("synthetic.trace.json")
    fine = tr.reduce(trace, min_gap_ns=0.0)
    coarse = tr.reduce(trace, min_gap_ns=1e12)
    assert set(coarse["gaps_s"]) == {"short_gaps"}
    assert coarse["gaps_s"]["short_gaps"] == pytest.approx(
        sum(fine["gaps_s"].values()))


def test_an_op_is_named_and_sorted_by_its_hlo_instruction():
    """As the v5e trace spells them (my chip run, PR 24), shortened."""
    kernel = ('%body.11 = (s32[8192,1]{1,0:T(8,128)}, u32[8192,10]{1,0:T(8,128)S(1)}) '
              'custom-call(s32[8192,32]{1,0:T(8,128)} %get-tuple-element.5758), '
              'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.op_identity(kernel) == ("body.11", tr.MOSAIC)
    other = ('%custom-call.232 = pred[8192,32]{1,0:T(8,128)(4,1)} custom-call('
             'pred[8192,32]{1,0} %x), custom_call_target="ConcatBitcast"')
    assert tr.op_identity(other) == ("custom-call.232", "custom-call")
    # a fusion that merely CONSUMES a custom call is a fusion
    fusion = ('%fusion.549 = (u32[8192,1]{1,0:T(8,128)S(1)}) fusion(s32[8192,1]{1,0} '
              '%custom-call.226), kind=kLoop, calls=%fused_computation.549')
    assert tr.op_identity(fusion) == ("fusion.549", "fusion")
    assert tr.op_identity("%all-reduce.7 = s32[4]{0} all-reduce(s32[4]{0} %p)") \
        == ("all-reduce.7", "all-reduce")
    assert tr.COLLECTIVE_RE.match("all-reduce-start") and not tr.CONTROL_RE.match("fusion")
    assert tr.op_identity("jit_supersegment(123)") == ("jit_supersegment(123)", "")


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 3)]) == [(0, 3), (5, 7)]
    assert tr.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]) == \
        [(0, 2), (3, 8), (22, 29)]
    assert tr.innermost([(0, 100, "w"), (10, 20, "a"), (30, 50, "b"),
                         (35, 40, "c")]) == [
        (0, 10, "w"), (10, 20, "a"), (20, 30, "w"), (30, 35, "b"),
        (35, 40, "c"), (40, 50, "b"), (50, 100, "w")]


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    got = tr.reduce({"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench:campaign", 0.0, 1e9, ""]]}]}]})
    assert got["devices"] == 0 and got["busy_s"] == 0.0
    assert got["device_ops"] == [] and got["idle_gaps"] == []


def test_kernel_bytes_from_shapes():
    from benchmark import kernel_bytes as kb

    # raft5 at 8192 lanes: Q 32, payload 4 wide -> 10 planes
    assert kb.queue_planes(4) == 10
    assert kb.pop_gather_bytes(8192, 32, 4) == 4 * 8192 * (10 * 32 + 10)
    mk = kb.step_megakernel_bytes(8192, 32, 4, 12, True)
    assert mk == 4 * 8192 * (10 * 32 + 5 + 10 + 12 + 2)
    assert kb.cov_flush_bytes(8192, 512, 64) == 4 * 8192 * (2 * 512 + 64 + 1)
    shapes = {"lanes": 8192, "queue": 32, "payload_width": 4, "rng_words": 12,
              "with_digest": True, "map_words": 512, "buffer_slots": 64,
              "megakernel": True, "pop_gather": False, "cov_flush": True}
    per_call = kb.bytes_per_call(shapes)
    assert set(per_call) == {"step_megakernel", "cov_flush"}
    # a kernel that moved its bytes at exactly the peak reads 100%
    calls = {"step_megakernel": (10, 10 * per_call["step_megakernel"] / 819e9)}
    assert kb.roofline_share(calls, shapes, 819e9) == pytest.approx(100.0)
    assert kb.roofline_share({}, shapes, 819e9) is None
