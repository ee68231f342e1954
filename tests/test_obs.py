"""Observability layer (obs/, DESIGN.md SS17).

The contract under test: the obs layer is a pure OBSERVER of the serving
stack — with observability fully enabled (device metric harvesting, shadow
exact-log-Z sampling, span tracing, exposition) every request's tokens are
bit-identical to the obs-off run, nothing retraces after warmup (the
metric state is always threaded; cadence flags are traced data, so the
executables cannot depend on whether obs is attached), and the telemetry
itself is truthful: the exact tier's shadow rel-err is identically zero,
harvested token counts reconcile with the host report, histogram rows are
cumulative-monotone, and the trace/registry artifacts are well-formed.
Coverage spans solo, ladder-degraded, speculative, and (2,2)-mesh serving
(the mesh case in an 8-virtual-device subprocess).
"""
import dataclasses
import json
import os
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ServingConfig, reduced_config
from repro.models import Model
from repro.obs import (LATENCY_EDGES_MS, TIERS, MetricsRegistry,
                       Observability, ObsConfig, Span, SpanLog, TraceWriter,
                       hist_quantile)
from repro.serve import Engine, Request, Scheduler, Server, trace_arrivals

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def served(rng):
    """One shared engine (mimps, IVF engaged) for the whole module."""
    cfg = reduced_config("qwen1.5-4b")
    cfg = dataclasses.replace(
        cfg, vocab=1024, partition=dataclasses.replace(
            cfg.partition, method="mimps", block_rows=64, n_probe=4, l=64))
    m = Model(cfg)
    eng = Engine(m, m.init(jax.random.fold_in(rng, 42)), max_len=24)
    return eng, cfg


def _requests(cfg, rng, n=4, budget=4):
    mk = lambda i, ln: np.asarray(
        jax.random.randint(jax.random.fold_in(rng, 800 + i), (ln,), 0,
                           cfg.vocab), np.int32)
    return [Request(prompt=mk(i, 2 + i % 3), max_new_tokens=budget,
                    key=jax.random.fold_in(rng, 900 + i),
                    temperature=0.0 if i % 2 else 0.7)
            for i in range(n)]


def _tokens(rep):
    return {c.request.req_id: c.tokens for c in rep.completions}


def _detach(sched):
    """Undo what Observability.attach set, so a follow-on obs-off run on
    the same scheduler really is obs-off."""
    sched.shadow_every = 0
    sched.engine.obs = None


def _obs(tmp_path, name, **kw):
    kw.setdefault("harvest_every", 2)
    kw.setdefault("shadow_every", 2)
    kw.setdefault("snapshot_every", 1)
    return Observability(ObsConfig(
        trace_path=str(tmp_path / f"{name}.jsonl"), **kw))


class TestBitParityObsOnVsOff:
    """Identical tokens with obs fully on vs off — instrumentation must
    not perturb sampling, in any serving mode."""

    def test_solo(self, served, rng, tmp_path):
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng)

        def run(obs):
            reqs = _requests(cfg, rng)
            rep = Server(sched, obs=obs).run(
                arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
            got = _tokens(rep)
            return [got[r.req_id] for r in reqs]   # positional: fresh ids

        off = run(None)
        obs = _obs(tmp_path, "solo")
        on = run(obs)
        obs.close()
        _detach(sched)
        assert on == off and off
        # and off-after-on: attaching never leaves residue in the scheduler
        assert run(None) == off

    def test_ladder_degraded(self, served, rng, tmp_path):
        eng, cfg = served

        def run(obs):
            sched = Scheduler(eng, n_slots=2, key=rng)
            server = Server(sched, ServingConfig(
                degrade_high=3, degrade_low=1, degrade_after=2,
                restore_after=4), obs=obs)
            reqs = [Request(prompt=[3, 4], max_new_tokens=20,
                            key=jax.random.fold_in(rng, 501))]
            reqs += _requests(cfg, rng, n=6, budget=2)
            for r in reqs:
                server.submit(r)
            rep = server.run()
            assert rep.tier_transitions, "pressure never engaged the ladder"
            got = _tokens(rep)
            return ([got[r.req_id] for r in reqs],
                    list(rep.tier_transitions))

        off, moves_off = run(None)
        obs = _obs(tmp_path, "ladder")
        on, moves_on = run(obs)
        obs.close()
        assert on == off and off
        assert moves_on == moves_off     # same deterministic ladder walk

    def test_speculative(self, served, rng, tmp_path):
        eng, cfg = served

        def run(obs):
            sched = Scheduler(eng, n_slots=3, key=rng, spec_draft="topk",
                              spec_k=3)
            reqs = _requests(cfg, rng, budget=6)
            rep = Server(sched, obs=obs).run(
                arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
            assert rep.spec_acceptance > 0
            got = _tokens(rep)
            return [got[r.req_id] for r in reqs]

        off = run(None)
        obs = _obs(tmp_path, "spec")
        on = run(obs)
        obs.close()
        assert on == off and off


class TestZeroRecompiles:
    def test_obs_toggling_never_retraces_and_metrics_are_not_keys(
            self, served, rng, tmp_path):
        """After warmup: obs on -> off -> on, plus a metric-state reset,
        all reuse the same executables — MetricState values (and the obs
        cadence) are data, not part of any jit cache key."""
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng)
        warm = Server(sched)
        warm.submit(Request(prompt=[5, 7], max_new_tokens=2, key=1))
        warm.run()
        t0, a0 = sched.step_traces, sched.admit_traces

        for mode in ("on", "off", "on"):
            obs = _obs(tmp_path, f"toggle_{mode}") if mode == "on" else None
            if obs is None:
                _detach(sched)
                sched.reset_metrics()   # fresh counters: still no retrace
            reqs = _requests(cfg, rng)
            Server(sched, obs=obs).run(
                arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
            if obs is not None:
                obs.close()
        _detach(sched)
        assert (sched.step_traces, sched.admit_traces) == (t0, a0)


class TestShadowTelemetry:
    def test_exact_tier_rel_err_identically_zero(self, rng, tmp_path):
        """The shadow oracle recomputes the same expression the exact tier
        serves with — so on the exact tier the live rel-err stream must be
        bitwise zero, with a nonzero sample count (the sanity anchor that
        licenses trusting the stream on estimator tiers)."""
        cfg = reduced_config("qwen1.5-4b")
        cfg = dataclasses.replace(
            cfg, vocab=1024, partition=dataclasses.replace(
                cfg.partition, method="exact", block_rows=64, n_probe=4,
                l=64))
        m = Model(cfg)
        eng = Engine(m, m.init(jax.random.fold_in(rng, 42)), max_len=24)
        sched = Scheduler(eng, n_slots=3, key=rng)
        obs = _obs(tmp_path, "exact", shadow_every=1)
        reqs = _requests(cfg, rng)
        Server(sched, obs=obs).run(
            arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
        shadow = obs.last_harvest["shadow_by_tier"]["exact"]
        obs.close()
        assert shadow["count"] > 0
        assert shadow["rel_err_mean"] == 0.0
        assert shadow["rel_err_max"] == 0.0

    def test_estimator_tier_rel_err_finite_and_tokens_reconcile(
            self, served, rng, tmp_path):
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng)
        sched.reset_metrics()
        obs = _obs(tmp_path, "mimps", shadow_every=1)
        reqs = _requests(cfg, rng)
        rep = Server(sched, obs=obs).run(
            arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
        h = obs.last_harvest
        obs.close()
        _detach(sched)
        s = h["shadow_by_tier"]["mimps"]
        assert s["count"] > 0
        assert np.isfinite(s["rel_err_mean"]) and s["rel_err_mean"] >= 0
        assert s["rel_err_max"] >= s["rel_err_mean"]
        # device counters == host accounting, the reconciliation criterion
        got = {t: v for t, v in h["tokens_by_tier"].items() if v}
        assert got == {t: v for t, v in dict(rep.tokens_by_tier).items()
                       if v}
        assert h["tokens_total"] == sum(got.values())

    def test_latency_histogram_rows_present_and_monotone(
            self, served, rng, tmp_path):
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng)
        reqs = _requests(cfg, rng)
        Server(sched).run(arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
        sched.reset_metrics()
        reqs = _requests(cfg, rng)
        Server(sched).run(arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
        h = sched.harvest_metrics()
        counts = h["latency_hist_by_tier"]["mimps"]
        assert len(counts) == len(LATENCY_EDGES_MS) + 1
        # the host-side histogram records every step of the warm run
        assert sum(counts) == h["steps"]
        cum = np.cumsum(counts)
        assert all(b >= a for a, b in zip(cum, cum[1:]))
        q = hist_quantile(np.asarray(counts), LATENCY_EDGES_MS, 0.99)
        assert np.isfinite(q) and q > 0


class TestReportTiming:
    def test_p99_and_device_host_split(self, served, rng):
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng)
        reqs = _requests(cfg, rng)
        rep = Server(sched).run(
            arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
        assert rep.p50_token_ms <= rep.p95_token_ms <= rep.p99_token_ms
        assert np.isfinite(rep.p99_token_ms)
        assert rep.step_device_ms_mean > 0
        assert rep.step_host_ms_mean > 0
        assert "p99" in rep.summary() and "host" in rep.summary()


class TestTraceArtifacts:
    def test_trace_jsonl_wellformed_and_report_accepts(
            self, served, rng, tmp_path):
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng)
        obs = _obs(tmp_path, "trace",
                   snapshot_path=str(tmp_path / "snap.json"))
        reqs = _requests(cfg, rng)
        Server(sched, obs=obs).run(
            arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
        obs.close()
        _detach(sched)
        path = tmp_path / "trace.jsonl"
        events = [json.loads(l) for l in path.read_text().splitlines()]
        assert events and obs.tracer.events_written == len(events)
        names = {e["name"] for e in events}
        # lifecycle spans + step phases + instants all present
        for want in ("enqueue", "queued", "replay", "decode", "request",
                     "sched.step", "sched.prep", "sched.dispatch",
                     "sched.wait", "sched.readback", "sched.emit",
                     "sched.admit"):
            assert want in names, want
        assert {e["args"]["tier"] for e in events
                if e["name"] == "sched.step"} == {"mimps"}
        for e in events:
            assert e["ph"] in ("X", "i", "C", "M")
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0
        snap = json.loads((tmp_path / "snap.json").read_text())
        assert snap["serving_steps"] > 0
        assert snap["harvest"]["tokens_total"] > 0
        # the host-side histograms keep their exposition names
        assert "queue_depth_mean" in snap
        assert any("mimps" in k.split("/")
                   for k in snap["step_latency_ms_bucket"])
        assert "repro_step_latency_ms_bucket{" in \
            obs.registry.prometheus_text()

        from repro.launch import obs_report
        assert obs_report.main([str(path),
                                "--snapshot", str(tmp_path / "snap.json")
                                ]) == 0

    def test_obs_report_rejects_empty_and_malformed(self, tmp_path):
        from repro.launch import obs_report
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert obs_report.main([str(empty)]) == 2
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"ph": "X", "name": "a"}\nnot json\n')
        assert obs_report.main([str(bad)]) == 2

    def test_obs_report_reconcile_mismatch_exits_3(self, tmp_path):
        from repro.launch import obs_report
        trace = tmp_path / "t.jsonl"
        trace.write_text(json.dumps(
            {"ph": "X", "name": "sched.step", "ts": 0, "dur": 1,
             "pid": 1, "tid": 0, "args": {"tier": "topk"}}) + "\n")
        snap = tmp_path / "s.json"
        snap.write_text(json.dumps(
            {"harvest": {"tokens_by_tier": {"mimps": 7},
                         "tokens_total": 7}}))
        assert obs_report.main([str(trace), "--snapshot", str(snap)]) == 3


class TestRegistry:
    def test_prometheus_text_format(self):
        r = MetricsRegistry()
        r.set("tokens_total", 42, mtype="counter", help="tokens")
        r.set("rel_err", 0.25, labels={"tier": "mimps"})
        text = r.prometheus_text()
        assert "# TYPE repro_tokens_total counter" in text
        assert "# HELP repro_tokens_total tokens" in text
        assert "repro_tokens_total 42" in text
        assert 'repro_rel_err{tier="mimps"} 0.25' in text
        r.close()

    def test_http_exposition(self):
        r = MetricsRegistry()
        r.set("up", 1, mtype="gauge")
        port = r.serve(0)   # ephemeral port
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
                body = resp.read().decode()
            assert "repro_up 1" in body
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/snapshot", timeout=10) as resp:
                snap = json.loads(resp.read().decode())
            assert snap["up"] == 1.0
        finally:
            r.close()

    def test_tracewriter_counts_and_flushes(self, tmp_path):
        path = tmp_path / "w.jsonl"
        w = TraceWriter(str(path))
        w.name_thread(3, "req 3")
        w.span("s", 1.0, 2.0, tid=3)
        w.instant("i")
        w.counter("c", {"x": 1.0})
        w.close()
        lines = path.read_text().splitlines()
        # ctor names tid 0 ("scheduler") + the 4 events above
        assert len(lines) == w.events_written == 5
        assert all(json.loads(l)["pid"] == 1 for l in lines)


PHASES = ("sched.prep", "sched.dispatch", "sched.wait", "sched.readback",
          "sched.emit")


class TestSpanLog:
    def test_bounded_keeps_newest(self):
        log = SpanLog(capacity=4)
        for i in range(10):
            log.add("s", float(i), float(i) + 0.5, id=i)
        assert len(log) == 4 and log.added == 10
        assert [r.id for r in log.records()] == [6, 7, 8, 9]
        # a cursor older than the log's reach gets what is still held
        recs, mark = log.since(3)
        assert [r.id for r in recs] == [6, 7, 8, 9] and mark == 10
        assert log.since(mark) == ([], 10)

    def test_parent_and_id_links(self):
        log = SpanLog()
        with Span(log, "outer", 7) as sp:
            with sp.child("inner") as c:
                pass
        inner, outer = log.records()
        assert (inner.name, inner.parent, inner.id) == ("inner", "outer", 7)
        assert (outer.name, outer.parent, outer.id) == ("outer", None, 7)
        assert outer.start <= inner.start <= inner.end <= outer.end
        assert (c.start, c.end) == (inner.start, inner.end)

    def test_off_when_none(self):
        with Span(None, "s", 1) as sp:
            with sp.child("c") as c:
                pass
        # stamps are still taken; nothing is recorded anywhere
        assert sp.start <= c.start <= c.end <= sp.end

    def test_tracewriter_writes_span_log(self, tmp_path):
        log = SpanLog()
        log.add("sched.step", 1.0, 2.0, id=3)
        log.add("sched.admit", 1.5, 1.75, id=11)
        w = TraceWriter(str(tmp_path / "s.jsonl"))
        w.write_spans(log.records(), {"sched.step": {"tier": "mimps"}})
        w.close()
        ev = [json.loads(l) for l in
              (tmp_path / "s.jsonl").read_text().splitlines()][1:]
        assert [(e["name"], e["args"]["id"]) for e in ev] == [
            ("sched.step", 3), ("sched.admit", 11)]
        assert ev[0]["args"]["tier"] == "mimps"
        assert ev[1]["dur"] == pytest.approx(0.25e6)


class TestSchedulerSpans:
    def test_step_has_each_phase_once_and_admit_carries_req_id(
            self, served, rng):
        eng, cfg = served
        log = SpanLog()
        sched = Scheduler(eng, n_slots=3, key=rng, spans=log)
        req = _requests(cfg, rng, n=1)[0]
        sched.admit(req)
        rec = sched.step()
        recs = log.records()
        assert [r.id for r in recs if r.name == "sched.admit"] == \
            [req.req_id]
        (step,) = [r for r in recs if r.name == "sched.step"]
        assert (step.start, step.end) == (rec["t_start"], rec["t_done"])
        kids = [r for r in recs if r.parent == "sched.step"]
        assert tuple(k.name for k in kids) == PHASES
        assert all(k.id == step.id == 0 for k in kids)
        assert step.start <= kids[0].start
        assert kids[-1].end <= step.end
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
        assert rec["t_dispatch"] == kids[1].start
        assert rec["t_device_done"] == kids[3].end

    def test_no_step_or_admission_dispatches_a_conversion(
            self, served, rng, monkeypatch):
        """Every host value reaches the compiled step and admission as an
        argument of the call: nothing is converted on the device first."""
        from jax._src.lax import lax as lax_internal
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng, spans=None)
        reqs = _requests(cfg, rng, n=2)
        sched.admit(reqs[0])
        sched.step()                      # warm: the step compiles here
        sched.shadow_every = 2            # both shadow flags get used
        traces = (sched.step_traces, sched.admit_traces)
        prim = lax_internal.convert_element_type_p
        binds = []
        orig = prim.bind
        monkeypatch.setattr(prim, "bind",
                            lambda *a, **k: binds.append(1) or orig(*a, **k),
                            raising=False)
        sched.admit(reqs[1])
        for q in range(3):
            sched.step(queue_depth=q)
        assert binds == []
        assert (sched.step_traces, sched.admit_traces) == traces

    def test_lowered_step_carries_named_scopes(self, served, rng):
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng, spans=None)
        call, _ = sched._step_inputs()
        text = call.func.lower(*call.args).as_text(debug_info=True)
        for scope in ("trunk", "attention", "mlp", "kv_cache",
                      "output_layer"):
            assert f"{scope}/" in text, scope


MESH_OBS_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import dataclasses, tempfile, jax, jax.numpy as jnp, numpy as np
from repro.configs import reduced_config
from repro.models import Model
from repro.obs import Observability, ObsConfig
from repro.serve import Engine, Request, Scheduler, Server, trace_arrivals
from repro.launch.mesh import make_serving_mesh

rng = jax.random.PRNGKey(0)
cfg = reduced_config("qwen1.5-4b")
cfg = dataclasses.replace(
    cfg, vocab=1024, partition=dataclasses.replace(
        cfg.partition, method="mimps", block_rows=64, n_probe=4, l=64))
m = Model(cfg)
params = m.init(jax.random.fold_in(rng, 42))

mk = lambda i, n: np.asarray(
    jax.random.randint(jax.random.fold_in(rng, 100 + i), (n,), 0,
                       cfg.vocab), np.int32)
spec = [(mk(0, 3), 5, 7, 0.0), (mk(1, 6), 4, 8, 0.9),
        (mk(2, 4), 6, 9, 0.5), (mk(3, 5), 5, 10, 0.3)]
mkreqs = lambda: [Request(prompt=p, max_new_tokens=n,
                          key=jax.random.fold_in(rng, s), temperature=t)
                  for (p, n, s, t) in spec]

mesh = make_serving_mesh(2, 2)
eng = Engine(m, params, max_len=24, mesh=mesh)
sched = Scheduler(eng, n_slots=4, key=rng)

# obs-off wave (also warmup)
reqs1 = mkreqs()
rep_off = Server(sched).run(arrivals=trace_arrivals(
    reqs1, [0.0] * len(reqs1)))
off = {c.request.req_id: c.tokens for c in rep_off.completions}
t0, a0 = sched.step_traces, sched.admit_traces

# obs-on wave: harvest + shadow sampling + tracing, same warm scheduler
sched.reset_metrics()
tmp = tempfile.mkdtemp()
obs = Observability(ObsConfig(harvest_every=2, shadow_every=1,
                              trace_path=os.path.join(tmp, "t.jsonl")))
reqs = mkreqs()
rep_on = Server(sched, obs=obs).run(arrivals=trace_arrivals(
    reqs, [0.0] * len(reqs)))
on = {c.request.req_id: c.tokens for c in rep_on.completions}
h = obs.last_harvest
obs.close()

assert [on[r.req_id] for r in reqs] == \
    [off[r.req_id] for r in reqs1], "mesh obs parity"
assert sched.step_traces == t0 and sched.admit_traces == a0, \
    "obs attach retraced under mesh"
s = h["shadow_by_tier"]["mimps"]
assert s["count"] > 0 and np.isfinite(s["rel_err_mean"]), s
got = {t: v for t, v in h["tokens_by_tier"].items() if v}
want = {t: v for t, v in dict(rep_on.tokens_by_tier).items() if v}
assert got == want, (got, want)
print("ALL_OK")
"""


class TestMeshObs8Dev:
    def test_obs_parity_zero_retrace_and_reconcile_under_mesh(self):
        r = subprocess.run([sys.executable, "-c", MESH_OBS_SNIPPET],
                           capture_output=True, text=True,
                           env=dict(os.environ, PYTHONPATH="src"),
                           cwd=REPO, timeout=900)
        assert r.returncode == 0 and "ALL_OK" in r.stdout, \
            r.stdout + r.stderr


class TestTrainMetrics:
    def test_instrumented_step_accumulates_without_host_sync(self, rng):
        from repro.configs.base import TrainConfig
        from repro.train import (harvest_train_metrics,
                                 init_train_metric_state,
                                 init_train_state, make_instrumented_step,
                                 make_train_step)
        cfg = reduced_config("qwen1.5-4b")
        m = Model(cfg)
        tc = TrainConfig(lr=1e-3, total_steps=4, loss="fused_ce",
                         warmup_steps=1)
        state = init_train_state(m, tc, rng)
        step = jax.jit(make_instrumented_step(make_train_step(m, tc)))
        tm = init_train_metric_state()
        toks = np.zeros((2, 8), np.int32)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
        for _ in range(4):
            state, tm, metrics = step(state, tm, batch)
        h = harvest_train_metrics(tm)
        assert h["steps"] == 4
        assert h["nonfinite_steps"] == 0
        assert np.isfinite(h["loss_mean"]) and h["loss_mean"] > 0
        assert h["loss_max"] >= h["loss_mean"]
        assert h["grad_norm_max"] >= h["grad_norm_mean"] > 0
        # the accumulator matches the per-step metrics it folded in
        assert h["loss_std"] >= 0
