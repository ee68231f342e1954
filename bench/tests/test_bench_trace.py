"""The trace reduction, against a hand-made trace with known numbers and
against a small trace recorded on a TPU v5e (``data/``)."""
import os

import pytest

from bench import trace
from bench.trace import Event

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
H = trace.HOST_PLANE


def _hand_made():
    """Window [0, 100). Device ops cover [10, 30) and [25, 50) (overlap)
    and [70, 90); so busy 60, idle 40 in gaps [0,10), [50,70), [90,100).
    The host did bench.admit over [0, 12), bench.step over [12, 95) with
    an inner 'device_get' over [55, 68)."""
    return [
        Event(H, "py", "bench.window", 0, 100),
        Event(H, "py", "bench.admit", 0, 12),
        Event(H, "py", "bench.step", 12, 83),
        Event(H, "py", "device_get", 55, 13),
        Event(DEV, trace.OPS_LINE, "fusion.1", 10, 20),
        Event(DEV, trace.OPS_LINE, "ivf_decode", 25, 25),
        Event(DEV, trace.OPS_LINE, "fusion.1", 70, 20),
        Event(DEV, trace.MODULES_LINE, "jit_step(1)", 10, 80),
        # outside the window: ignored
        Event(DEV, trace.OPS_LINE, "fusion.1", 120, 5),
    ]


def test_busy_union_and_idle_share_by_hand():
    r = trace.reduce(_hand_made(), min_gap_ns=1.0)
    assert r.window_ns == (0, 100)
    assert r.busy_ns == 60
    assert r.idle_share == pytest.approx(0.4)
    assert r.op_ns == {"fusion.1": 40, "ivf_decode": 25}
    assert r.ops_matching(r"ivf_decode") == (pytest.approx(25e-9), 1)
    assert r.modules_matching(r"^jit_step\b") == (pytest.approx(80e-9), 1)


def test_idle_gaps_are_put_down_to_the_host_span():
    r = trace.reduce(_hand_made(), min_gap_ns=1.0)
    assert [(s, e) for s, e, _ in r.gaps] == [(0, 10), (50, 70), (90, 100)]
    what = [w for _, _, w in r.gaps]
    assert what == ["bench.admit", "bench.step > device_get", "bench.step"]
    assert r.top_gaps()[0] == ["bench.step > device_get", 20e-9]


def test_top_ops_are_sorted_and_capped():
    r = trace.reduce(_hand_made(), min_gap_ns=1.0)
    assert r.top_ops(1) == [["fusion.1", 40e-9]]


def test_a_trace_without_window_or_device_is_refused():
    evs = _hand_made()
    with pytest.raises(ValueError):
        trace.reduce([e for e in evs if e.name != "bench.window"])
    with pytest.raises(ValueError):
        trace.reduce([e for e in evs if e.plane != DEV])


def test_saved_events_round_trip(tmp_path):
    p = str(tmp_path / "e.json.gz")
    trace.save_events(_hand_made(), p)
    assert trace.load_events(p) == _hand_made()


def _independent_busy(events, lo, hi):
    """Busy time by an independent sweep over sorted edges."""
    edges = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            edges += [(s, 1), (t, -1)]
    edges.sort()
    busy, depth, last = 0.0, 0, None
    for x, d in edges:
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy


RECORDED = os.path.join(HERE, "data", "v5e_trace_slice.json.gz")


def test_recorded_v5e_trace():
    events = trace.load_events(RECORDED)
    r = trace.reduce(events, min_gap_ns=0.0)
    lo, hi = r.window_ns
    ops = [e for e in events if e.plane == DEV and e.line == trace.OPS_LINE]
    assert r.busy_ns == pytest.approx(_independent_busy(ops, lo, hi))
    assert 0.0 < r.idle_share < 1.0
    # per-name time is the clipped sum of that name's events
    name, secs = r.top_ops(1)[0]
    want = sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in ops
               if trace.short_name(e.name) == name and e.end_ns > lo
               and e.start_ns < hi)
    assert secs == pytest.approx(want * 1e-9)
    # busy + idle gaps tile the window
    gap_ns = sum(e - s for s, e, _ in r.gaps)
    assert r.busy_ns + gap_ns == pytest.approx(hi - lo, rel=1e-3)
    assert all(w.startswith("bench.") for _, _, w in r.gaps)
    # the step's executable ran in the slice, with the output layer's
    # kernel inside it; the layer scan's while op is no op's own time
    assert r.modules_matching(r"^jit_step\b")[1] >= 1
    assert r.ops_matching(r"^%mimps_decode[.\d]* \(custom-call\)$")[1] >= 1
    assert not any(k.endswith("(while)") for k in r.op_ns)


def test_short_names_of_hlo_ops():
    assert trace.short_name(
        "%fusion.291 = bf16[8,768]{1,0:T(8,128)(2,1)} fusion(bf16[8] %a), "
        "kind=kLoop") == "%fusion.291 (fusion)"
    assert trace.short_name(
        "%while.37 = (s32[]{:T(128)}, bf16[8]{0}) while((s32[], bf16[8]) "
        "%t)") == "%while.37 (while)"
    assert trace.short_name("jit_step(123)") == "jit_step(123)"
