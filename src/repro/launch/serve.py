"""Traffic-driven serving: continuous batching over the slot scheduler.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --reduced \
      --slots 8 --requests 16 --rate 1.0 --gen 12 --method mimps

Generates a Poisson arrival stream of mixed-length, mixed-temperature
requests, serves it through ``serve.Server`` (admission queue, one compiled
mixed prefill/decode step, slot recycling, streaming callbacks), and prints
the traffic report. ``--sequential`` adds a one-request-at-a-time
``generate()`` pass over the same workload for comparison.

``--method`` choices come from the estimator-backend registry, so every
servable method (including the PR-2 additions ``mince`` and ``fmbe``) is
accepted; oracle-only study estimators are not servable and not listed.

Overload policy (DESIGN.md SS14) is driven by the ``ServingConfig`` flags:
``--max-queue`` bounds the admission queue (arrivals over the bound are
shed), ``--deadline`` stamps every request with a default deadline in
virtual steps (expired queue entries are shed, in-flight lanes evicted),
and ``--degrade-high/--degrade-low/--degrade-after/--restore-after`` (plus
an optional explicit ``--ladder``) walk the estimator-tier degradation
ladder under sustained queue pressure. All default off.

Raw speed (DESIGN.md SS16), still bit-identical per token:
``--spec-draft topk --spec-k 4`` turns on estimator-speculative decoding
(a cheap registry tier drafts k tokens per lane inside the compiled step,
the lane's serving tier verifies them in one batched pass);
``--prefix-cache-blocks N`` enables the shared-prefix KV pool (admissions
whose prompt prefix is cached skip those replay steps). ``--admit-window``
adds bounded admission lookahead so a full preferred replica doesn't
head-of-line block the queue.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs import ServingConfig, get_config, reduced_config
from ..core.backends import BACKENDS
from ..models import Model
from ..obs import Observability, ObsConfig
from ..serve import (Engine, Request, Scheduler, Server, generate,
                     poisson_arrivals)
from .compile_cache import use_compile_cache


def build_engine(cfg, seed: int, max_len: int, mesh=None,
                 use_pallas=None) -> Engine:
    """Random parameters from ``seed`` -> the serving ``Engine``.

    The parameters are initialized inside one jit, so no float32 copy of a
    weight ever materializes, and are placed where the engine serves them:
    the default device, or replicated over ``mesh`` (the scheduler's mesh
    step takes them replicated; building them on one device first would
    hold two copies there). ``use_pallas`` as in ``Engine``: None lets the
    platform choose."""
    model = Model(cfg)
    key = jax.random.PRNGKey(seed)
    where = None if mesh is None else NamedSharding(mesh, P())
    params = jax.jit(model.init, out_shardings=where)(key)
    return Engine(model, params, max_len=max_len, key=key, mesh=mesh,
                  use_pallas=use_pallas)


def recompiles_after_warmup(sched: Scheduler) -> tuple:
    """(step, admit) traces beyond the first of each: one step trace per
    served estimator tier and one admit trace are the warmup."""
    return (sched.step_traces - max(len(sched.traces_by_tier), 1),
            sched.admit_traces - 1)


def build_workload(n: int, vocab: int, gen: int, pmin: int, pmax: int,
                   temperature: float, seed: int):
    """Mixed prompt lengths cycling [pmin..pmax], alternating greedy /
    sampled — the heterogeneous traffic one synchronous batch can't serve
    without padding every request to the longest."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p_len = pmin + (i * 3) % max(pmax - pmin + 1, 1)
        prompt = rng.integers(0, vocab, size=(p_len,), dtype=np.int32)
        reqs.append(Request(
            prompt=prompt, max_new_tokens=gen,
            key=jax.random.PRNGKey(seed + 1000 + i),
            temperature=0.0 if i % 2 == 0 else temperature))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--method", default=None,
                    choices=[None] + sorted(BACKENDS))
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="expected arrivals per scheduler step")
    ap.add_argument("--prompt-len-min", type=int, default=4)
    ap.add_argument("--prompt-len-max", type=int, default=16)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="sampled requests' temperature (every other "
                         "request decodes greedily)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="data=K,model=M",
                    help="scale out over a (data, model) device mesh: slot "
                         "lanes split across K replicas, the output "
                         "embedding + IVF index across M shards, one "
                         "shard_map step (requires K*M visible devices; "
                         "tokens stay bit-identical to single-device)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the admission queue; arrivals over the "
                         "bound are shed with reason 'queue_full' "
                         "(0 = unbounded)")
    ap.add_argument("--deadline", type=int, default=0,
                    help="default per-request deadline in virtual steps: "
                         "expired queue entries are shed, in-flight lanes "
                         "evicted mid-decode (0 = no deadlines)")
    ap.add_argument("--degrade-high", type=int, default=0,
                    help="queue depth at/above which sustained pressure "
                         "steps the estimator tier DOWN the ladder "
                         "(0 = degradation off)")
    ap.add_argument("--degrade-low", type=int, default=0,
                    help="queue depth at/below which sustained calm "
                         "restores the tier back UP")
    ap.add_argument("--degrade-after", type=int, default=3,
                    help="consecutive over-watermark steps before "
                         "degrading")
    ap.add_argument("--restore-after", type=int, default=8,
                    help="consecutive under-watermark steps before "
                         "restoring")
    ap.add_argument("--ladder", default=None,
                    help="comma list of tiers, most-accurate first (default:"
                         " the method's built-in ladder, e.g. mimps,topk)")
    ap.add_argument("--spec-draft", default=None,
                    choices=[None] + sorted(BACKENDS),
                    help="estimator-speculative decoding: draft tier that "
                         "proposes --spec-k tokens per lane inside the one "
                         "compiled step; the lane's serving tier verifies "
                         "all of them in a single batched pass (tokens stay "
                         "bit-identical; typically 'topk' or 'fmbe')")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per lane per speculative round "
                         "(ignored without --spec-draft)")
    ap.add_argument("--spec-draft-probes", type=int, default=0,
                    help="IVF probes for the draft pass (0 = half the "
                         "serving tier's n_probe; the draft must be cheaper "
                         "than the verifier for speculation to pay)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=0,
                    help="device-resident shared-prefix KV pool capacity in "
                         "token blocks; admissions with a cached prefix of "
                         "L tokens skip L replay steps (0 = off)")
    ap.add_argument("--prefix-block-tokens", type=int, default=8,
                    help="tokens per prefix-pool block (match granularity)")
    ap.add_argument("--admit-window", type=int, default=0,
                    help="admission lookahead: hold up to N queue-head "
                         "requests whose prefix-cache-preferred replica is "
                         "full, admitting the first fit instead "
                         "(0 = strict FIFO)")
    ap.add_argument("--admit-hold", type=int, default=8,
                    help="force-admit a held request anywhere after this "
                         "many holds (bounds unfairness)")
    ap.add_argument("--verify-index-every", type=int, default=0,
                    help="digest-verify (and restore) the serving tier's "
                         "IVF index every N steps (0 = off)")
    ap.add_argument("--no-health-guard", action="store_true",
                    help="disable the in-step estimator health guard "
                         "(non-finite log-Z / empty probe union -> exact "
                         "fallback)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write per-request lifecycle spans + step phases "
                         "as Chrome-trace/Perfetto JSONL to PATH "
                         "(summarize with repro.launch.obs_report)")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="serve Prometheus text metrics on "
                         "127.0.0.1:PORT/metrics (0 = off)")
    ap.add_argument("--metrics-snapshot", default=None, metavar="PATH",
                    help="write periodic JSON metric snapshots to PATH")
    ap.add_argument("--harvest-every", type=int, default=16,
                    help="steps between device->host metric harvests")
    ap.add_argument("--shadow-every", type=int, default=16,
                    help="steps between shadow-sampled exact log-Z passes "
                         "feeding the live per-tier rel-err stream "
                         "(0 = off)")
    ap.add_argument("--stream", action="store_true",
                    help="print every completion as it finishes")
    ap.add_argument("--sequential", action="store_true",
                    help="also run the one-request-at-a-time generate() "
                         "baseline over the same workload")
    args = ap.parse_args()
    use_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.method:
        cfg = dataclasses.replace(
            cfg, partition=dataclasses.replace(cfg.partition,
                                               method=args.method))
    mesh = None
    if args.mesh:
        from .mesh import make_serving_mesh
        kv = dict(part.split("=", 1) for part in args.mesh.split(","))
        unknown = set(kv) - {"data", "model"}
        if unknown:
            raise SystemExit(f"--mesh keys must be data/model, got "
                             f"{sorted(unknown)}")
        mesh = make_serving_mesh(data=int(kv.get("data", 1)),
                                 model=int(kv.get("model", 1)))

    key = jax.random.PRNGKey(args.seed)
    max_len = args.prompt_len_max + args.gen + 1
    eng = build_engine(cfg, args.seed, max_len, mesh=mesh)
    mesh_note = "" if mesh is None else \
        f"  mesh data={mesh.shape['data']},model={mesh.shape['model']}"
    path = "Pallas kernels" if eng.use_pallas else "XLA bodies"
    print(f"arch {cfg.name}  Z-method {cfg.partition.method}  "
          f"vocab {cfg.vocab}  slots {args.slots}{mesh_note}  "
          f"output layer: {path} on {jax.default_backend()}")

    if cfg.n_codebooks:
        # audio codebook heads have no slot-table path (multi-stream
        # tokens); keep the pre-scheduler synchronous batch demo working
        print("audio arch: serving one synchronous generate() batch "
              "(no continuous batching for codebook heads)")
        shape = (args.slots, args.prompt_len_min, cfg.n_codebooks)
        prompt = jax.random.randint(key, shape, 0, cfg.vocab)
        t0 = time.perf_counter()
        toks = generate(eng, prompt, args.gen, key,
                        temperature=args.temperature)
        jax.block_until_ready(toks)
        dt = time.perf_counter() - t0
        n_tok = args.slots * args.gen
        print(f"generated {args.slots}x{args.gen} codebook tokens in "
              f"{dt:.2f}s ({n_tok / dt:.1f} tok/s)")
        return

    reqs = build_workload(args.requests, cfg.vocab, args.gen,
                          args.prompt_len_min, args.prompt_len_max,
                          args.temperature, args.seed)
    if args.stream:
        for r in reqs:
            r.on_complete = lambda req, comp: print(
                f"  req {req.req_id:3d} T={req.temperature:.1f} "
                f"len {len(req.prompt):2d} -> {comp.tokens[:8]}"
                f"{'...' if len(comp.tokens) > 8 else ''}")

    sched = Scheduler(eng, n_slots=args.slots, key=key,
                      spec_draft=args.spec_draft, spec_k=args.spec_k,
                      spec_draft_probes=args.spec_draft_probes,
                      prefix_cache_blocks=args.prefix_cache_blocks,
                      prefix_block_tokens=args.prefix_block_tokens)
    srv_cfg = ServingConfig(
        max_queue=args.max_queue, default_deadline=args.deadline,
        degrade_ladder=tuple(args.ladder.split(",")) if args.ladder else (),
        degrade_high=args.degrade_high, degrade_low=args.degrade_low,
        degrade_after=args.degrade_after, restore_after=args.restore_after,
        health_guard=not args.no_health_guard,
        verify_index_every=args.verify_index_every,
        admit_window=args.admit_window, admit_hold=args.admit_hold)
    obs = None
    if args.trace_out or args.metrics_port or args.metrics_snapshot:
        obs = Observability(ObsConfig(
            harvest_every=args.harvest_every,
            shadow_every=args.shadow_every,
            trace_path=args.trace_out or "",
            metrics_port=args.metrics_port,
            snapshot_path=args.metrics_snapshot or ""))
        if obs.port:
            print(f"  metrics: http://127.0.0.1:{obs.port}/metrics")
    server = Server(sched, srv_cfg, obs=obs)
    arrivals = poisson_arrivals(reqs, rate=args.rate, seed=args.seed)
    rep = server.run(arrivals=arrivals)
    print("continuous:", rep.summary())
    if obs is not None:
        h = obs.last_harvest or {}
        shadow = h.get("shadow_by_tier", {})
        live = {t: f"{v['rel_err_mean']:.2e}/{v['rel_err_max']:.2e}"
                for t, v in shadow.items() if v["count"]}
        if live:
            print(f"  shadow rel-err mean/max by tier: {live}")
        if args.trace_out:
            print(f"  trace: {args.trace_out} "
                  f"({obs.tracer.events_written} events)")
        if args.metrics_snapshot:
            print(f"  snapshot: {args.metrics_snapshot}")
        obs.close()
    step_extra, admit_extra = recompiles_after_warmup(sched)
    print(f"  recompiles after warmup would be: step={step_extra} "
          f"admit={admit_extra} (0 expected; one trace per "
          f"served tier: {dict(sched.traces_by_tier)})")
    if rep.dedup_by_fill:
        fills = ", ".join(f"{k}:{v:.2f}" for k, v in
                          rep.dedup_by_fill.items())
        print(f"  probe-union dedup by batch fill: {fills}")
    if rep.rejects_by_reason or rep.tier_transitions or \
            rep.index_restores or any(rep.health.values()):
        print(f"  robustness: shed_rate {rep.shed_rate:.2f} "
              f"(by reason: {dict(rep.rejects_by_reason)}), "
              f"queue peak {rep.queue_depth_peak}")
        if rep.tier_transitions:
            path = " -> ".join(f"{t}@{s}" for s, t in rep.tier_transitions)
            print(f"  tier transitions: {path}; tokens by tier "
                  f"{dict(rep.tokens_by_tier)} "
                  f"(degraded frac {rep.degraded_token_frac:.2f})")
        if rep.index_restores or any(rep.health.values()):
            print(f"  guards: health {dict(rep.health)}, index restores "
                  f"{rep.index_restores}, step faults {rep.step_faults}")

    if args.sequential:
        # warm each compile bucket first so the comparison is steady-state
        seen = set()
        for r in reqs:
            b = 1 << (len(r.prompt) - 1).bit_length()
            if b not in seen:
                seen.add(b)
                jax.block_until_ready(generate(
                    eng, jnp.asarray(r.prompt)[None], r.max_new_tokens,
                    r.key, temperature=r.temperature))
        t0 = time.perf_counter()
        tot = 0
        for r in reqs:
            toks = generate(eng, jnp.asarray(r.prompt)[None],
                            r.max_new_tokens, r.key,
                            temperature=r.temperature)
            tot += int(jnp.asarray(toks).shape[1])
        jax.block_until_ready(toks)
        dt = time.perf_counter() - t0
        print(f"sequential: {tot} tokens in {dt:.2f}s "
              f"({tot / dt:.1f} tok/s); continuous speedup "
              f"{rep.goodput_tok_s / (tot / dt):.2f}x")


if __name__ == "__main__":
    main()
