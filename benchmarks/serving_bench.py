"""Serving-traffic benchmark: continuous batching vs sequential generate().

Drives the slot scheduler (serve.Scheduler / serve.Server) with a Poisson
stream of mixed-prompt-length, mixed-temperature requests and measures what
a traffic-serving deployment cares about:

 * goodput (emitted tokens per wall second) vs the one-request-at-a-time
   ``generate()`` baseline over the SAME workload (same prompts, keys,
   temperatures — the sequential pass doubles as the token-parity oracle:
   continuous batching must emit bit-identical tokens per request),
 * per-token latency p50/p95,
 * slot occupancy (mean + steady-state while demand is backed up),
 * probe-union dedup ratio U/(Q*n_probe) vs batch fill — the amortization
   argument for retrieval-based estimators under load,
 * recompiles after warmup (must be ZERO: one compiled mixed step serves
   every admission/replay/decode mix),
 * an OVERLOAD scenario (2x sustained demand vs slot capacity, deterministic
   trace on the virtual clock) through the bounded queue + degradation
   ladder: shed rate, p95 under overload, fraction of tokens served from a
   degraded tier, peak queue depth — still with zero recompiles, since
   every ladder tier is compiled once during warmup,
 * a RAW-SPEED section (DESIGN.md SS16): estimator-speculative decoding
   (cheap registry tier drafts k tokens, the serving tier verifies them in
   one batched pass) and the shared-prefix KV cache, both on a bursty
   shared-system-prompt trace — speculative goodput must beat
   non-speculative and the warm cache must save replay steps, still with
   bit-identical tokens and zero recompiles,
 * a SCALING curve for the mesh-sharded scheduler step (DESIGN.md SS15):
   goodput / p95 / occupancy per (data, model) mesh shape that fits the
   devices (1/2/4/8; on the CPU, 8 forced host devices in one child
   process), with token parity vs solo generate() and zero recompiles
   required at every shape (see ``_scaling``).

Writes BENCH_serving.json; gated by ``benchmarks/run.py --check``.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np


def _build(quick: bool, mesh=None):
    import dataclasses

    from repro.configs import reduced_config
    from repro.models import Model
    from repro.serve import Engine

    cfg = reduced_config("qwen1.5-4b")
    cfg = dataclasses.replace(
        cfg, vocab=2048 if quick else 8192,
        partition=dataclasses.replace(cfg.partition, method="mimps",
                                      block_rows=128, n_probe=4, l=128))
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    gen = 8 if quick else 16
    p_max = 12 if quick else 24
    eng = Engine(model, params, max_len=p_max + gen + 1, key=key, mesh=mesh)
    return eng, cfg, gen, p_max


def _workload(cfg, n_req: int, gen: int, p_lens, seed: int = 0):
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_req):
        p_len = p_lens[i % len(p_lens)]
        reqs.append(Request(
            prompt=rng.integers(0, cfg.vocab, size=(p_len,), dtype=np.int32),
            max_new_tokens=gen,
            key=jax.random.PRNGKey(7_000 + i),
            temperature=0.0 if i % 2 == 0 else 0.8))
    return reqs


def _shared_prefix_workload(cfg, n_req: int, gen: int, shared_len: int,
                            tail_lens, seed: int = 11):
    """Every request shares one system-prompt prefix (the prefix-cache /
    speculation scenario: agents, RAG templates, few-shot headers)."""
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, size=(shared_len,), dtype=np.int32)
    reqs = []
    for i in range(n_req):
        tail = rng.integers(0, cfg.vocab,
                            size=(tail_lens[i % len(tail_lens)],),
                            dtype=np.int32)
        reqs.append(Request(
            prompt=np.concatenate([shared, tail]),
            max_new_tokens=gen,
            key=jax.random.PRNGKey(9_000 + i),
            temperature=0.0 if i % 2 == 0 else 0.8))
    return reqs


def _sequential(eng, reqs, time_it: bool):
    """One-request-at-a-time generate() over the workload. Returns
    (tokens_per_request, wall_seconds). Compile buckets are warmed by the
    caller running this once with time_it=False first."""
    from repro.serve import generate
    import time
    outs = []
    t0 = time.perf_counter()
    for r in reqs:
        toks = generate(eng, jnp.asarray(r.prompt)[None], r.max_new_tokens,
                        r.key, temperature=r.temperature)
        outs.append([int(t) for t in np.asarray(jax.device_get(toks))[0]])
    dt = time.perf_counter() - t0
    return outs, (dt if time_it else float("nan"))


def _overload(sched, cfg, n_slots: int, n_req: int, gen: int, p_lens):
    """2x sustained demand vs slot capacity through the overload policy.

    Demand is a deterministic trace on the virtual step clock (capacity
    digests ~n_slots/gen requests per step; arrivals come at twice that),
    so the shed/degrade/restore path replays identically run to run. Every
    ladder tier is warmed (compiled) on a throwaway workload FIRST, so the
    measured section must not trace anything new.
    """
    from repro.configs import ServingConfig
    from repro.serve import Server, default_ladder, trace_arrivals

    base_tier = sched.tier
    for tier in default_ladder(base_tier):
        sched.set_tier(tier)
        warm = Server(sched)
        for r in _workload(cfg, 2, 2, [3, 5], seed=98):
            warm.submit(r)
        warm.run()
    sched.set_tier(base_tier)
    traces0 = (sched.step_traces, sched.admit_traces)

    ov_reqs = _workload(cfg, 2 * n_req, gen, p_lens, seed=7)
    rate = 2.0 * n_slots / gen      # requests per virtual step = 2x capacity
    arrivals = trace_arrivals(ov_reqs, [i / rate for i in range(len(ov_reqs))])
    ov_cfg = ServingConfig(max_queue=n_slots,
                           degrade_high=max(2, n_slots // 2),
                           degrade_low=1, degrade_after=2, restore_after=6)
    # observability rides the measured overload run fully enabled (trace +
    # snapshot + shadow sampling): the CI artifacts come from here, and the
    # run must STILL trace nothing new — obs state is data, not shape.
    import os

    from repro.obs import Observability, ObsConfig
    os.makedirs("artifacts", exist_ok=True)
    obs = Observability(ObsConfig(
        harvest_every=8, shadow_every=4, snapshot_every=1,
        trace_path=os.path.join("artifacts", "serving_trace.jsonl"),
        snapshot_path=os.path.join("artifacts", "metrics_snapshot.json")))
    sched.reset_metrics()
    rep = Server(sched, ov_cfg, obs=obs).run(arrivals=arrivals)
    recompiles = (sched.step_traces - traces0[0]) + \
        (sched.admit_traces - traces0[1])
    assert len(rep.completions) == len(ov_reqs), "overload accounting leak"
    h = obs.last_harvest
    obs.close()
    sched.engine.obs = None
    sched.shadow_every = 0
    # the device counters were reset right before the measured run, so the
    # harvested per-tier token counts must reconcile exactly with the
    # host-side report — one acceptance criterion of the obs layer
    harvested_by_tier = {t: v for t, v in h["tokens_by_tier"].items() if v}
    reconciled = harvested_by_tier == {
        t: v for t, v in dict(rep.tokens_by_tier).items() if v}
    shadow = {t: s for t, s in h["shadow_by_tier"].items() if s["count"]}
    return {
        "obs": {
            "trace_path": obs.cfg.trace_path,
            "trace_events": obs.tracer.events_written,
            "snapshot_path": obs.cfg.snapshot_path,
            "tokens_by_tier_harvested": harvested_by_tier,
            "tokens_reconciled": bool(reconciled),
            "shadow_rel_err_by_tier": {
                t: {"count": s["count"],
                    "rel_err_mean": s["rel_err_mean"],
                    "rel_err_max": s["rel_err_max"]}
                for t, s in shadow.items()},
        },
        "n_req": len(ov_reqs),
        "demand_x_capacity": 2.0,
        "max_queue": ov_cfg.max_queue,
        "ladder": list(default_ladder(base_tier)),
        "shed_rate": rep.shed_rate,
        "rejects_by_reason": dict(rep.rejects_by_reason),
        "p95_under_overload": rep.p95_token_ms,
        "degraded_token_frac": rep.degraded_token_frac,
        "tokens_by_tier": dict(rep.tokens_by_tier),
        "tier_transitions": [[int(s), t] for s, t in rep.tier_transitions],
        "queue_depth_peak": int(rep.queue_depth_peak),
        "goodput_tok_s": rep.goodput_tok_s,
        "recompiles_after_warmup": int(recompiles),
    }


def _obs_overhead(sched, cfg, n_req: int, gen: int, p_lens):
    """Observability tax: the SAME workload served with the obs layer fully
    enabled (harvest + shadow sampling + trace + snapshot) vs disabled,
    interleaved 5x each (best-of-N per arm damps shared-host noise).

    Gated by run.py --check: goodput ratio on >= 0.95 of off, tokens
    bit-identical between the arms, and zero recompiles across the whole
    section — the executable must not know whether obs is watching (the
    metric state is always threaded; cadence flags are traced data).
    """
    import os
    import tempfile

    from repro.obs import Observability, ObsConfig
    from repro.serve import Server, trace_arrivals

    tmp = tempfile.mkdtemp(prefix="obs_overhead_")
    traces0 = (sched.step_traces, sched.admit_traces)
    best = {"on": 0.0, "off": 0.0}
    tokens_ref, parity = None, True
    for trial in range(5):
        for mode in ("off", "on"):
            obs = None
            if mode == "on":
                # serve-CLI default cadences: fully on means trace +
                # metrics + shadow + snapshots, not a stress cadence
                obs = Observability(ObsConfig(
                    harvest_every=16, shadow_every=16, snapshot_every=4,
                    trace_path=os.path.join(tmp, "trace.jsonl"),
                    snapshot_path=os.path.join(tmp, "snap.json")))
            else:
                # detach anything a previous on-arm left behind
                sched.shadow_every = 0
                sched.engine.obs = None
            reqs = _workload(cfg, n_req, gen, p_lens, seed=5)
            rep = Server(sched, obs=obs).run(
                arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
            if obs is not None:
                obs.close()
            # req_ids are globally fresh per trial: compare positionally
            by_id = {c.request.req_id: c.tokens for c in rep.completions}
            got = [by_id.get(r.req_id) for r in reqs]
            if tokens_ref is None:
                tokens_ref = got
            else:
                parity = parity and got == tokens_ref
            best[mode] = max(best[mode], rep.goodput_tok_s)
    sched.shadow_every = 0
    sched.engine.obs = None
    recompiles = (sched.step_traces - traces0[0]) + \
        (sched.admit_traces - traces0[1])
    row = {
        "goodput_on_tok_s": best["on"],
        "goodput_off_tok_s": best["off"],
        "goodput_ratio_on_vs_off": best["on"] / max(best["off"], 1e-9),
        "token_parity_on_vs_off": bool(parity),
        "recompiles_after_warmup": int(recompiles),
    }
    print(f"  obs on {best['on']:.0f} tok/s vs off {best['off']:.0f} "
          f"({row['goodput_ratio_on_vs_off']:.3f}x), parity {parity}, "
          f"recompiles {recompiles}", flush=True)
    return row


def _raw_speed(quick: bool):
    """DESIGN.md SS16: estimator-speculative decoding + shared-prefix KV
    cache on a bursty shared-system-prompt trace (all-at-once arrivals on
    the virtual clock, so step counts are deterministic).

    This section runs its own engine in the regime the paper targets —
    a LARGE vocab with the EXACT tier serving (the output layer dominates
    the step) — because that is where speculation's economics live: the
    sublinear estimator drafts k tokens nearly for free, then ONE exact
    pass verifies all k positions while streaming the (V, d) embedding
    once, instead of k sequential exact passes streaming it k times. At
    the small-vocab mimps operating point of the main serving section the
    trunk forward dominates and is shared by draft and verify, so
    speculation only rearranges step overhead (tokens-per-step still
    improves ~2x; wall clock does not — measured, not hidden).

    Every configuration must keep the two hard invariants (bit-identical
    tokens vs solo generate(), zero recompiles after warmup); the perf
    claims gated by ``run.py --check`` are (a) speculative goodput beats
    non-speculative on this scenario for at least one registry draft
    (wall clock AND tokens per virtual step), and (b) the prefix cache
    saves replay steps (> 0) once warm.
    """
    import dataclasses

    from repro.configs import reduced_config
    from repro.models import Model
    from repro.serve import Engine, Scheduler, Server, trace_arrivals

    cfg = reduced_config("qwen1.5-4b")
    cfg = dataclasses.replace(
        cfg, vocab=32768, partition=dataclasses.replace(
            cfg.partition, method="exact", block_rows=128, n_probe=4,
            l=128))
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    gen, p_max = 8, 12
    eng = Engine(model, model.init(key), max_len=p_max + gen + 1, key=key)

    n_slots = 8
    n_req = 2 * n_slots if quick else 4 * n_slots
    shared_len = p_max - 4
    tails = [1, 2, 3, 4]
    bt = 4
    spec_k = 4
    oracle, _ = _sequential(
        eng, _shared_prefix_workload(cfg, n_req, gen, shared_len, tails),
        time_it=False)

    def serve(spec_draft=None, blocks=0):
        sched = Scheduler(eng, n_slots=n_slots, key=jax.random.PRNGKey(2),
                          spec_draft=spec_draft,
                          spec_k=spec_k if spec_draft else 1,
                          prefix_cache_blocks=blocks,
                          prefix_block_tokens=bt)
        warm = Server(sched)
        for r in _workload(cfg, 2, 2, [3, 5], seed=97):
            warm.submit(r)
        warm.run()
        traces0 = (sched.step_traces, sched.admit_traces)
        reps, parity = [], True
        for _ in range(2):   # 2nd pass also runs against a warm prefix pool
            reqs = _shared_prefix_workload(cfg, n_req, gen, shared_len,
                                           tails)
            rep = Server(sched).run(
                arrivals=trace_arrivals(reqs, [0.0] * len(reqs)))
            got = {c.request.req_id: c.tokens for c in rep.completions}
            parity = parity and all(got.get(r.req_id) == oracle[i]
                                    for i, r in enumerate(reqs))
            reps.append(rep)
        recompiles = (sched.step_traces - traces0[0]) + \
            (sched.admit_traces - traces0[1])
        # goodput: best of 2 (damps shared-host noise); steps: the warm
        # min (deterministic on the virtual clock, so it is what the
        # --check gate compares)
        best = max(reps, key=lambda r: r.goodput_tok_s)
        steps = min(r.steps for r in reps)
        total = sum(len(c.tokens) for c in best.completions)
        row = {
            "goodput_tok_s": best.goodput_tok_s,
            "steps": int(steps),
            "tok_per_step": total / max(steps, 1),
            "token_parity": bool(parity),
            "recompiles_after_warmup": int(recompiles),
        }
        if spec_draft:
            row["acceptance"] = best.spec_acceptance
            row["draft_flagged"] = int(best.draft_flagged)
        if blocks:
            row["prefix"] = dict(sched.prefix.stats())
        return row

    base = serve()
    drafts = {d: serve(spec_draft=d) for d in ("topk", "fmbe")}
    for name, r in drafts.items():
        print(f"  spec draft={name} k={spec_k}: "
              f"{r['goodput_tok_s']:.0f} tok/s ({r['tok_per_step']:.1f}"
              f"/step) vs non-spec {base['goodput_tok_s']:.0f} "
              f"({base['tok_per_step']:.1f}/step), acceptance "
              f"{r['acceptance']:.2f}, parity {r['token_parity']}, "
              f"recompiles {r['recompiles_after_warmup']}", flush=True)
    blocks = 8 * n_slots
    cache_on = serve(blocks=blocks)
    combined = serve(spec_draft="topk", blocks=blocks)
    print(f"  prefix cache ({blocks} blocks x {bt} tok): "
          f"{cache_on['steps']} steps vs {base['steps']} off, saved "
          f"{cache_on['prefix']['saved_steps']} replay steps "
          f"({cache_on['prefix']['hits']} hits); spec+cache "
          f"{combined['goodput_tok_s']:.0f} tok/s", flush=True)
    spec = {
        "scenario": {"n_req": n_req, "shared_prefix_len": shared_len,
                     "tail_lens": tails, "gen": gen, "spec_k": spec_k,
                     "vocab": cfg.vocab, "serving_tier": "exact"},
        "nonspec": base,
        "drafts": drafts,
        "speedup_vs_nonspec": max(
            r["goodput_tok_s"] for r in drafts.values())
            / base["goodput_tok_s"],
        "with_prefix_cache": combined,
    }
    prefix = {
        "blocks": blocks, "block_tokens": bt,
        "off": {k: base[k] for k in ("goodput_tok_s", "steps",
                                     "tok_per_step")},
        "on": {k: cache_on[k] for k in ("goodput_tok_s", "steps",
                                        "tok_per_step")},
        "hits": cache_on["prefix"]["hits"],
        "saved_replay_steps": cache_on["prefix"]["saved_steps"],
        "evictions": cache_on["prefix"]["evictions"],
        "token_parity": cache_on["token_parity"],
        "recompiles_after_warmup": cache_on["recompiles_after_warmup"],
    }
    return spec, prefix


SCALING_SHAPES = ((1, 1), (2, 1), (4, 1), (8, 1), (2, 2))


def _scaling_row(data: int, model: int, quick: bool = True) -> dict:
    """One scaling-curve row over the first data*model devices.

    Builds a (data, model)-mesh engine with ``lanes_per_replica * data``
    slot lanes, warms the scheduler, serves a saturating all-at-once trace
    twice (best-of-2 goodput damps scheduler-noise on a shared host), and
    checks the two hard invariants per row: tokens bit-identical to a
    single-device solo ``generate()`` oracle, and zero retraces after
    warmup.
    """
    from repro.launch.mesh import make_serving_mesh
    from repro.serve import Scheduler, Server, trace_arrivals

    mesh = make_serving_mesh(data=data, model=model)
    eng, cfg, gen, p_max = _build(quick, mesh=mesh)
    lanes = 4 if quick else 8
    n_slots = lanes * data
    n_req = 4 * n_slots
    p_lens = [4, 6, 9, 12] if quick else [4, 8, 12, 17, 24]

    # parity oracle: an UNMESHED engine in the same process (same params —
    # Model.init is deterministic in the config + key)
    solo_eng, _, _, _ = _build(quick, mesh=None)
    oracle, _ = _sequential(solo_eng, _workload(cfg, n_req, gen, p_lens,
                                                seed=3), time_it=False)

    sched = Scheduler(eng, n_slots=n_slots, key=jax.random.PRNGKey(1))
    warm = Server(sched)
    for r in _workload(cfg, 2, 2, [3, 5], seed=99):
        warm.submit(r)
    warm.run()
    traces0 = (sched.step_traces, sched.admit_traces)

    goodput, parity = 0.0, True
    rep = None
    for _ in range(2):
        wl = _workload(cfg, n_req, gen, p_lens, seed=3)
        server = Server(sched)
        rep = server.run(arrivals=trace_arrivals(wl, [0.0] * len(wl)))
        got = {c.request.req_id: c.tokens for c in rep.completions}
        parity = parity and all(got.get(r.req_id) == oracle[i]
                                for i, r in enumerate(wl))
        goodput = max(goodput, rep.goodput_tok_s)
    recompiles = (sched.step_traces - traces0[0]) + \
        (sched.admit_traces - traces0[1])
    total_tokens = sum(len(c.tokens) for c in rep.completions)
    row = {
        "data": data, "model": model, "devices": data * model,
        "n_slots": n_slots, "n_req": n_req,
        # virtual-step-clock goodput: tokens emitted per compiled scheduler
        # step. This is the quantity the mesh scales (one step serves
        # data*lanes slot lanes) and the one a virtual-device run can
        # certify honestly — see _scaling's docstring.
        "tok_per_step": total_tokens / max(rep.steps, 1),
        "steps": rep.steps,
        "goodput_tok_s": goodput,
        "p95_token_ms": rep.p95_token_ms,
        "occupancy_steady": rep.occupancy_steady,
        "token_parity": bool(parity),
        "recompiles_after_warmup": int(recompiles),
    }
    print(f"  mesh data={data},model={model}: {row['tok_per_step']:.1f} "
          f"tok/step ({row['goodput_tok_s']:.0f} tok/s wall), p95 "
          f"{row['p95_token_ms']:.2f}ms, parity {row['token_parity']}, "
          f"recompiles {row['recompiles_after_warmup']}", flush=True)
    return row


def _scaling_rows(quick: bool = True) -> list:
    """Every scaling shape that fits this process's devices, in-process."""
    n = jax.device_count()
    return [_scaling_row(d, m, quick) for d, m in SCALING_SHAPES
            if d * m <= n]


def _forced_cpu_rows(quick: bool) -> list:
    """The CPU stand-in for a multi-chip host: the rows run in ONE child
    process whose ``XLA_FLAGS`` force 8 host devices before its backend
    starts (this process already holds a one-device CPU runtime). On an
    accelerator the rows run in-process over the real devices instead: a
    child could not reach a chip its parent holds."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), here]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    code = ("import json, serving_bench; print('SCALING::' + json.dumps("
            f"serving_bench._scaling_rows({quick})), flush=True)")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=3600)
    line = next((ln for ln in proc.stdout.splitlines()
                 if ln.startswith("SCALING::")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(f"scaling rows failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    print("\n".join(ln for ln in proc.stdout.splitlines()
                    if ln.startswith("  mesh ")), flush=True)
    return json.loads(line[len("SCALING::"):])


def _scaling(quick: bool = True):
    """Goodput-vs-device-count curve for the mesh-sharded scheduler step.

    The data-only chain (1,1)->(8,1) is the scaling curve proper — lanes
    per replica held fixed, total slot lanes grow with the data extent;
    (2,2) exercises the model-sharded output layer inside the same serving
    step. Rows run in this process over the real devices (shapes larger
    than the device count are left out); on a CPU with fewer than 8
    devices they run on 8 forced host devices in one child process.

    The GATED metric is ``tok_per_step`` on the virtual step clock (the
    same clock the overload trace uses): one compiled step must serve
    data*lanes slot lanes, so tokens-per-step scales with the data extent
    — that is the scaling property a forced-host-device run can certify.
    Wall-clock ``goodput_tok_s`` is recorded per row but NOT gated for
    monotonicity: the 8 virtual devices time-share however many physical
    cores the host has (possibly one), so wall clock measures core
    contention, not the per-replica-per-chip deployment this mesh maps to.
    """
    if jax.default_backend() == "cpu" and jax.device_count() < 8:
        rows = _forced_cpu_rows(quick)
    else:
        rows = _scaling_rows(quick)
    chain = [r["tok_per_step"] for r in rows if r["model"] == 1]
    return {
        "lanes_per_replica": rows[0]["n_slots"],
        "clock": "virtual-step",
        "rows": rows,
        "goodput_monotone": all(b >= a for a, b in zip(chain, chain[1:])),
        "goodput_scaling_8v1": chain[-1] / chain[0],
    }


def run(quick: bool = True):
    from repro.serve import Scheduler, Server, poisson_arrivals

    eng, cfg, gen, p_max = _build(quick)
    n_slots = 8 if quick else 16
    n_req = 16 if quick else 64
    p_lens = [4, 6, 9, 12] if quick else [4, 8, 12, 17, 24]
    reqs = _workload(cfg, n_req, gen, p_lens)

    # -- sequential baseline (also the parity oracle). First pass warms every
    #    (bucket, n_tokens) scan compile; second pass is the measurement.
    _sequential(eng, reqs, time_it=False)
    seq_tokens, seq_wall = _sequential(eng, reqs, time_it=True)
    seq_goodput = sum(len(t) for t in seq_tokens) / seq_wall

    # -- continuous batching. Warm the scheduler's two executables on a
    #    throwaway workload, then reset bookkeeping and serve the real one.
    sched = Scheduler(eng, n_slots=n_slots, key=jax.random.PRNGKey(1))
    warm = Server(sched)
    for r in _workload(cfg, 2, 2, [3, 5], seed=99):
        warm.submit(r)
    warm.run()
    traces_after_warmup = (sched.step_traces, sched.admit_traces)
    sched.reset_metrics()   # device counters start clean for the latency rows

    server = Server(sched)
    arrivals = poisson_arrivals(reqs, rate=2.0, seed=0)
    rep = server.run(arrivals=arrivals)
    recompiles = (sched.step_traces - traces_after_warmup[0]) + \
        (sched.admit_traces - traces_after_warmup[1])
    mh = sched.harvest_metrics()

    got = {c.request.req_id: c.tokens for c in rep.completions}
    parity = all(got.get(r.req_id) == seq_tokens[i]
                 for i, r in enumerate(reqs))
    # concurrency actually reached (acceptance: benefits at >= 8 in flight)
    peak_active = rep.peak_concurrency

    report = {
        "config": {"vocab": cfg.vocab, "n_slots": n_slots, "n_req": n_req,
                   "gen": gen, "prompt_lens": p_lens,
                   "method": cfg.partition.method, "quick": quick},
        "goodput_tok_s": rep.goodput_tok_s,
        "sequential_goodput_tok_s": seq_goodput,
        "speedup_vs_sequential": rep.goodput_tok_s / seq_goodput,
        "p50_token_ms": rep.p50_token_ms,
        "p95_token_ms": rep.p95_token_ms,
        "occupancy_mean": rep.occupancy_mean,
        "occupancy_steady": rep.occupancy_steady,
        "peak_concurrency": int(peak_active),
        "dedup_ratio_mean": rep.dedup_ratio_mean,
        # sorted [fill, ratio] rows — JSON objects would stringify the int
        # keys ("1".."8") and scramble their order
        "dedup_by_fill": [[int(k), float(v)] for k, v in
                          sorted(rep.dedup_by_fill.items())],
        "queue_wait_steps_mean": rep.queue_wait_steps_mean,
        "steps": rep.steps,
        "wall_s": rep.wall_s,
        "token_parity_vs_solo": bool(parity),
        "recompiles_after_warmup": int(recompiles),
    }
    # latency rows (obs satellite): host-percentile tail + the device-side
    # per-tier step-latency histogram harvested from the metric-state pytree
    # the compiled step threads. Buckets are emitted CUMULATIVE (Prometheus
    # histogram convention) so run.py --check can gate monotonicity.
    report["latency"] = {
        "p50_token_ms": rep.p50_token_ms,
        "p95_token_ms": rep.p95_token_ms,
        "p99_token_ms": rep.p99_token_ms,
        "step_device_ms_mean": rep.step_device_ms_mean,
        "step_host_ms_mean": rep.step_host_ms_mean,
        "edges_ms": list(mh["latency_edges_ms"]),
        "per_tier_cumulative": {
            tier: [int(c) for c in np.cumsum(counts)]
            for tier, counts in mh["latency_hist_by_tier"].items()
            if sum(counts)},
    }
    print("observability overhead (obs fully on vs off, best of 5 "
          "interleaved):", flush=True)
    report["obs_overhead"] = _obs_overhead(sched, cfg, n_req, gen, p_lens)
    report["overload"] = _overload(sched, cfg, n_slots, n_req, gen, p_lens)
    print("raw speed (speculation + prefix cache, shared-prefix trace, "
          "exact tier @ 32k vocab):", flush=True)
    report["spec"], report["prefix_cache"] = _raw_speed(quick)
    print("scaling curve (subprocess per mesh shape):", flush=True)
    report["scaling"] = _scaling(quick)
    with open("BENCH_serving.json", "w") as f:
        json.dump(report, f, indent=2)
    total_tokens = sum(len(t) for t in seq_tokens)
    us_per_token = rep.wall_s / max(total_tokens, 1) * 1e6
    print(f"serving: goodput {rep.goodput_tok_s:.0f} tok/s vs sequential "
          f"{seq_goodput:.0f} ({report['speedup_vs_sequential']:.2f}x), "
          f"occupancy {rep.occupancy_steady:.2f}, parity {parity}, "
          f"recompiles {recompiles}")
    ov = report["overload"]
    print(f"overload (2x demand): shed_rate {ov['shed_rate']:.2f}, "
          f"p95 {ov['p95_under_overload']:.2f}ms, degraded_token_frac "
          f"{ov['degraded_token_frac']:.2f}, queue_depth_peak "
          f"{ov['queue_depth_peak']}, recompiles "
          f"{ov['recompiles_after_warmup']}")
    sp, pc = report["spec"], report["prefix_cache"]
    print(f"raw speed: spec {sp['speedup_vs_nonspec']:.2f}x non-spec "
          f"goodput (topk acceptance "
          f"{sp['drafts']['topk']['acceptance']:.2f}), prefix cache saved "
          f"{pc['saved_replay_steps']} replay steps ({pc['hits']} hits, "
          f"{pc['on']['steps']} vs {pc['off']['steps']} steps)")
    sc = report["scaling"]
    print(f"scaling: tok/step @8dev vs @1dev "
          f"{sc['goodput_scaling_8v1']:.2f}x, monotone "
          f"{sc['goodput_monotone']}")
    return report, us_per_token


if __name__ == "__main__":
    run()
