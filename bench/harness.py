"""One run of one cell: resolve it from files, set up the program, drive
the window, check the output against the reference, read the metrics.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own, found by name from ``BENCHMARK.json``:

* ``<bench>/configs/...json``  the configuration (its ``file`` entry);
* ``<bench>/traffic/<mix>.json``  the mix's parameters;
* ``<bench>/cells/<cell>.json``  the cell's rate, sample size and limits;
* ``<bench>/metrics/<metric>.py``  one reader per metric, ``read(run)``.

where ``<bench>`` is the first of ``paths``. Adding a cell, a mix or a
metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from . import check, seeds, traffic
from .openloop import OpenLoop, Sent, Step
from .model import Arch, make_params, program_config
from .roofline import peaks

TRACE_OPTIONS = {"python_tracer_level": 0, "host_tracer_level": 2}


class NoChip(RuntimeError):
    """The run cannot measure what it was asked to: no result is printed."""


@dataclasses.dataclass
class Cell:
    name: str
    root: str
    bench_dir: str
    chips: int
    config: Dict
    arch: Arch
    mix: Dict
    cell: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench_json: str, workload: str) -> Cell:
    """The cell ``workload`` of ``bench_json`` with all of its files."""
    root = os.path.dirname(os.path.abspath(bench_json))
    with open(bench_json) as f:
        b = json.load(f)
    cells = {w["name"]: w for w in b["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in b["configs"]}[w["config"]]
    bench_dir = os.path.join(root, b["paths"][0])

    def load(*parts):
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    config = load(root, conf["file"])
    return Cell(name=workload, root=root, bench_dir=bench_dir,
                chips=int(w["chips"]), config=config,
                arch=Arch.from_file(config),
                mix=load(bench_dir, "traffic", w["traffic"] + ".json"),
                cell=load(bench_dir, "cells", workload + ".json"),
                end_to_end=[m for m in b["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in b["per_layer"]
                           if _applies(m, workload)])


def tpu_devices(chips: int):
    """The chips this run may use; raises ``NoChip`` rather than fall back
    to another platform."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    try:
        peaks(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return devs[:chips]


@dataclasses.dataclass
class RunData:
    """What a metric reader gets."""
    cell: Cell
    seconds: float
    window: tuple                 # (start, end), perf_counter
    stopped: float                # when the loop stopped
    setup_s: float
    sent: List[Sent]
    steps: List[Step]
    checks: Dict[str, Dict]
    peaks: Dict
    reduction: Any = None         # trace.Reduction of the traced stretch

    @property
    def serving(self) -> Dict:
        return self.cell.config["serving"]

    def due(self) -> List[Sent]:
        return [r for r in self.sent if r.segment == "window"]

    def window_steps(self) -> List[Step]:
        lo, hi = self.window
        return [s for s in self.steps if lo <= s.t_start < hi]


class _Tracer:
    """Start a profiler trace at ``start`` (perf_counter) and stop it
    ``seconds`` later, between two steps, inside a ``bench.window`` span."""

    def __init__(self, start: float, seconds: float, log_dir: str):
        import jax
        self.jax = jax
        self.start, self.seconds, self.dir = start, seconds, log_dir
        self.stop = float("inf")
        self.span = None
        self.done = False

    def __call__(self, now: float) -> bool:
        if self.span is None and not self.done and now >= self.start:
            opts = self.jax.profiler.ProfileOptions()
            for k, v in TRACE_OPTIONS.items():
                setattr(opts, k, v)
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            # the traced stretch is timed from when the profiler is up
            self.stop = time.perf_counter() + self.seconds
            self.span = self.jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
        elif self.span is not None and now >= self.stop:
            self.span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self.span = None
            self.done = True
        return self.span is not None


def _warm(sched, request_cls):
    """Compile the cell's two programs, admission and the step, before the
    schedule starts: two short requests, admitted before and between
    steps, run through a few steps, so that every argument the window's
    calls pass (first and later tables and metric states) has been seen."""
    import numpy as np
    for n in (2, 3):
        sched.admit(request_cls(prompt=np.zeros((2,), np.int32),
                                max_new_tokens=n, key=np.zeros(2, np.uint32),
                                temperature=0.8 * (n - 2)))
        sched.step()
    while sched.n_in_flight:
        sched.step()


class _GcPauses:
    """Every collection the garbage collector makes while this is open, as
    (milliseconds, generation), so that a long turn of the loop can be
    told apart from a collection."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(((time.perf_counter() - self._t) * 1e3,
                                info["generation"]))

    def close(self):
        gc.callbacks.remove(self._callback)


@dataclasses.dataclass
class Built:
    """The program, set up for one cell and seed."""
    params: Any
    engine: Any
    sched: Any
    request_cls: Any
    device: Any
    devices: List
    traces0: tuple
    times: Dict[str, float]


def build(cell: Cell, seed: int, require_chip: bool = True) -> Built:
    """Weights from the seed, the engine (IVF index), the scheduler, and
    both of its programs compiled."""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from repro.serve import Engine, Request, Scheduler

    devs = tpu_devices(cell.chips) if require_chip else jax.devices()[:1]
    s = cell.config["serving"]
    times = {"t0": time.perf_counter()}
    params = make_params(cell.arch, jnp.asarray(
        seeds.key_words(seed, seeds.WEIGHTS)))
    jax.block_until_ready(params)
    times["weights"] = time.perf_counter()
    eng = Engine(Model(program_config(cell.config, cell.arch)), params,
                 max_len=s["max_len"],
                 key=jnp.asarray(seeds.key_words(seed, seeds.INDEX)),
                 device_index=bool(s["device_index"]))
    jax.block_until_ready(eng.state)
    times["index"] = time.perf_counter()
    # the scheduler's key is the same for every seed: the program folds it
    # into its compiled step as a constant, so a key drawn from the seed
    # would compile the step anew for every seed
    sched = Scheduler(eng, n_slots=s["n_slots"],
                      key=jnp.asarray(seeds.key_words(0, seeds.SCHEDULER)),
                      health_guard=bool(s["health_guard"]))
    _warm(sched, Request)
    times["compiled"] = time.perf_counter()
    return Built(params=params, engine=eng, sched=sched, request_cls=Request,
                 device=devs[0], devices=devs,
                 traces0=(sched.step_traces, sched.admit_traces),
                 times=times)


def serve(b: Built, cell: Cell, seed: int, seconds: float, rate: float,
          trace_dir: Optional[str] = None) -> OpenLoop:
    """Drive the cell's traffic at ``rate``: warm-up, the window, and the
    follow until the window's requests are done."""
    s = cell.config["serving"]
    plan = traffic.schedule(cell.mix, rate, seconds, seed, cell.arch.vocab)
    if max(len(p.prompt) + p.answer_tokens - 1 for p in plan) > s["max_len"]:
        raise ValueError("the mix needs more cache positions than max_len")
    # what set-up made lives for the whole run: keep the collector from
    # walking it again at every full collection while the loop serves
    gc.collect()
    gc.freeze()
    origin = time.perf_counter() + 0.01
    w0 = origin + float(cell.mix["warmup_s"])
    window = (w0, w0 + seconds)
    tracer = None
    if trace_dir is not None:
        ts = float(cell.cell["trace_s"])
        t_a = w0 + max(0.0, (seconds - ts) / 2.0)
        tracer = _Tracer(t_a, ts, trace_dir)
    loop = OpenLoop(b.sched, b.request_cls, plan, origin, window,
                    float(cell.mix["follow_s"]), tracer)
    pauses = _GcPauses()
    try:
        loop.run()
    finally:
        pauses.close()
        gc.unfreeze()
    loop.gc_pauses = pauses.pauses
    return loop


def run_data(cell: Cell, loop: OpenLoop, seconds: float, setup_s: float,
             checks: Dict, pk: Dict, reduction=None) -> RunData:
    return RunData(cell=cell, seconds=seconds, window=loop.window,
                   stopped=loop.stopped, setup_s=setup_s, sent=loop.sent,
                   steps=loop.steps, checks=checks, peaks=pk,
                   reduction=reduction)


def read_metrics(cell: Cell, run: RunData, metrics: List[Dict]) -> Dict:
    out = {}
    for m in metrics:
        v = cell.reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_chip: bool = True,
             log: Callable[[str], None] = print,
             control: bool = False) -> Dict:
    """One run; returns the result object the last line prints.
    ``control`` adds, under ``"control"``, the int8 control read on the
    same sample and held to the same limits: its ``correct`` and
    ``checks`` (for the tools that set the limits and the tests, never in
    a benchmark run)."""
    b = build(cell, seed, require_chip)
    dev = b.device
    # off the chip (tests only) the readers still get a v5e's peaks
    pk = peaks(dev.device_kind if require_chip else "TPU v5 lite")
    t = b.times
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    loop = serve(b, cell, seed, seconds, float(cell.cell["rate_per_s"]),
                 tdir)
    w0 = loop.window[0]
    setup_s = w0 - t_process
    late = sorted(loop.lateness)
    log(f"set-up {setup_s:.3f} s: start {t['t0'] - t_process:.3f}, weights "
        f"{t['weights'] - t['t0']:.3f}, index {t['index'] - t['weights']:.3f}"
        f", compile {t['compiled'] - t['index']:.3f}, warm-up traffic "
        f"{w0 - t['compiled']:.3f}")
    log(f"generator lateness: median {late[len(late) // 2] * 1e3:.3f} ms, "
        f"max {late[-1] * 1e3:.3f} ms over {len(late)} releases")
    # where the longest turns of the open loop went (ms): releases
    # and admissions, the step's own host work before dispatch, device
    # and readback, bookkeeping after it
    turns = sorted(loop.steps, key=lambda st: st.t_loop - st.t_done)[:3]
    log("longest turns: " + "; ".join(
        f"{(st.t_done - st.t_loop) * 1e3:.1f} = admit "
        f"{(st.t_start - st.t_loop - st.paused_s) * 1e3:.1f}"
        f" + pre {(st.t_dispatch - st.t_start) * 1e3:.1f}"
        f" + device {(st.t_device_done - st.t_dispatch) * 1e3:.1f}"
        f" + post {(st.t_done - st.t_device_done) * 1e3:.1f}"
        f" + profiler {st.paused_s * 1e3:.1f}"
        for st in turns))
    gcs = sorted(loop.gc_pauses, reverse=True)
    log(f"garbage collections while serving: {len(gcs)}, longest "
        + ", ".join(f"{ms:.1f} ms (gen {g})" for ms, g in gcs[:3]))
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    due = [r for r in loop.sent if r.segment == "window"]
    counts = {"missing": sum(1 for r in due
                             if not r.done or r.error is not None),
              "recompiles": (b.sched.step_traces - b.traces0[0])
              + (b.sched.admit_traces - b.traces0[1]),
              "health_flagged": sum(st.health_flagged for st in loop.steps)}
    done = [r for r in loop.sent if r.done and r.error is None
            and r.done >= w0]
    sample = check.pick_sample(done, int(cell.cell["sample_requests"]), seed)
    # free the program's state before the reference runs: the weights are
    # the benchmark's own and stay
    params, arch = b.params, cell.arch
    b.sched = b.engine = None
    gc.collect()
    reduction = None
    if trace:
        from . import trace as trace_mod
        reduction = trace_mod.reduce(trace_mod.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    t_ref = time.perf_counter()
    correct, checks, info = check.compare(
        params, arch, sample, cell.config["serving"]["max_len"],
        cell.cell["limits"], counts, control=control)
    log(f"reference over {info.get('compared_tokens', 0)} tokens of "
        f"{len(sample)} requests: {time.perf_counter() - t_ref:.3f} s; "
        f"greedy tokens' widest gap below the best logit "
        f"{info.get('greedy_gap')}")
    run = run_data(cell, loop, seconds, setup_s, checks, pk, reduction)
    metrics = read_metrics(cell, run,
                           cell.per_layer if trace else cell.end_to_end)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(b.devices), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": len(due),
              "failed": counts["missing"],
              "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": reduction.top_ops(),
                               "idle_gaps": reduction.top_gaps()}
    if "control" in info:
        c_correct, c_checks = info["control"]
        result["control"] = {"correct": c_correct, "checks": c_checks}
    result["checks"] = checks
    return result


def main(argv: Optional[List[str]] = None, t_process: float = 0.0) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        cell = resolve(os.path.join(root, "BENCHMARK.json"), args.workload)
        tpu_devices(cell.chips)
        import repro  # noqa: F401  (the system under test must be here)
    except (NoChip, ImportError, KeyError, FileNotFoundError) as e:
        print(f"bench: cannot run: {e}", file=sys.stderr)
        return 2
    err = lambda msg: print(msg, file=sys.stderr, flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_process, log=err)
    for k, v in result["metrics"].items():
        err(f"metric {k} = {v['value']!r} {v['unit']}")
    for k, v in result["checks"].items():
        err(f"check {k}: {v['value']!r} <= {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
