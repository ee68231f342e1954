"""The readers of the scheduler's spans, on a hand-made span log and
hand-made step records: window selection, traced steps left out, the
medians and the stall sum; and no value where the program keeps no span
log (the parent of the span log, say)."""
import importlib.util
import os
import types

import pytest

from bench.openloop import Step
from repro.obs import SpanLog, tracing

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
READERS = ("prep_ms_per_step", "dispatch_ms_per_step",
           "readback_ms_per_step", "emit_ms_per_step", "stall_ms_per_s")
# (name, ms) of each phase of a plain step, in order
PHASES = (("sched.prep", 1.0), ("sched.dispatch", 2.0), ("sched.wait", 37.0),
          ("sched.readback", 2.0), ("sched.emit", 1.0))


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _step(log, steps, i, t, wait_ms=37.0, prep_ms=1.0, traced=False):
    t0 = t
    for name, ms in PHASES:
        ms = {"sched.wait": wait_ms, "sched.prep": prep_ms}.get(name, ms)
        log.add(name, t, t + ms / 1e3, "sched.step", i)
        t += ms / 1e3
    log.add("sched.step", t0, t, None, i)
    steps.append(Step(t_start=t0, t_dispatch=t0, t_device_done=t, t_done=t,
                      t_loop=t0, n_active=1, head_live=1, n_emitted=1,
                      health_flagged=0, attended=1, idle_s=0.0,
                      paused_s=0.0, traced=traced))


@pytest.fixture
def run(monkeypatch):
    """Window (10, 20): steps at 10, 11, ..., 19; step 15 stalls 1 s in
    its wait, step 17 ran under the profiler (and reads wild); a step
    before the window reads wild too. Admissions of 2 ms, one of 20 ms,
    and a wild one inside the traced stretch."""
    log = SpanLog()
    steps = []
    _step(log, steps, 0, 9.0, wait_ms=900.0, prep_ms=300.0)
    for i in range(10):
        _step(log, steps, i + 1, 10.0 + i,
              wait_ms=1000.0 if i == 5 else 37.0,
              prep_ms=400.0 if i == 7 else 1.0, traced=i == 7)
    for t, ms, rid in ((10.5, 2.0, 1), (12.5, 2.0, 2), (13.5, 20.0, 3),
                       (17.5, 500.0, 4), (9.5, 400.0, 5)):
        log.add("sched.admit", t, t + ms / 1e3, None, rid)
    monkeypatch.setattr(tracing, "SPANS", log)
    return types.SimpleNamespace(window=(10.0, 20.0), steps=steps)


# stall: steps 43 ms (median) and one of 1,006 ms: 1,006 - 3 x 43 = 877;
# admissions 2, 2, 20 ms: 20 - 3 x 2 = 14; over 10 s less the traced
# stretch (step 17 until step 18), 9 s
@pytest.mark.parametrize("name,want", [
    ("prep_ms_per_step", 1.0), ("dispatch_ms_per_step", 2.0),
    ("readback_ms_per_step", 2.0), ("emit_ms_per_step", 1.0),
    ("stall_ms_per_s", (877.0 + 14.0) / 9.0)])
def test_reader_on_hand_made_log(run, name, want):
    assert _reader(name)(run) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_reader_none_without_span_log(run, monkeypatch, name):
    read = _reader(name)
    monkeypatch.setattr(tracing, "SPANS", SpanLog())
    assert read(run) is None
    monkeypatch.delattr(tracing, "SPANS")
    assert read(run) is None
