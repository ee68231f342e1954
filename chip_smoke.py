"""Serve qwen1.5-4b at its published widths on a TPU, and check the result.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --four-chips     # mesh data=2,model=2 vs one chip

One chip: the path ``python -m repro.launch.serve --arch qwen1.5-4b
--method mimps`` takes (Model -> Engine -> Scheduler -> Server), all 40
layers, d_model 2560, vocab 151,936, bf16, parameters drawn from --seed.
It serves mixed greedy and sampled requests on 8 slots, then checks that

* every request completes (no shed, rejected or stopped request);
* the health guard never fired (a kernel returning non-finite log Z would
  otherwise be served silently by the exact tier it falls back to);
* nothing recompiled after warmup;
* the served step takes the Pallas kernels (no flag chose them);
* on hidden states of the served model, the mimps log Z of the fused
  ``ivf_decode`` kernel matches the XLA reference body within the CPU
  parity tests' 1e-4, and is within 0.1 mean relative error of the exact
  logsumexp over all 151,936 rows (tests/test_decode.py's bound);
* ``lsh_probe``, ``fmbe_z`` and ``topk_z``, run once at these widths,
  match their references.

--four-chips runs only the mesh phase (``--mesh data=2,model=2``, which
runs the XLA estimator bodies by design) and its one-chip reference on
the same bodies. It checks that the mesh builds the same IVF index, that
the sharded output layer (vocab rows split over 'model', psum row-gather)
gives the reference's candidates and log Z on the same hidden states, that
the data-parallel trunk, teacher-forced, stays within bf16 rounding of
the reference, and that the mesh serves every request; it reports how
many served token streams are identical to the reference's.

Exits 1, printing no result, on any failure, when JAX finds no TPU, or
when the repository is not beside this file. On success the last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


class SmokeFailure(Exception):
    pass


def _check(failures: list, ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        failures.append(what)


class Phases:
    """Wall-clock per phase (compilation included), printed as it ends."""

    def __init__(self):
        self.t = {}

    def __call__(self, name, fn, *args, **kw):
        import jax
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        self.t[name] = time.perf_counter() - t0
        print(f"phase {name}: {self.t[name]:.2f} s", flush=True)
        return out


def _tpu_devices(need: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"JAX found no TPU (platform "
                           f"{devs[0].platform!r}); this smoke test only "
                           f"runs on the chip")
    if len(devs) < need:
        raise SmokeFailure(f"needs {need} TPU chips, JAX sees {len(devs)}")
    return devs


def _config(seed: int):
    import dataclasses
    from repro.configs import get_config
    cfg = get_config("qwen1.5-4b")
    cfg = dataclasses.replace(cfg, partition=dataclasses.replace(
        cfg.partition, method="mimps"))
    print(f"config {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, {cfg.n_heads}x"
          f"{cfg.resolved_head_dim} heads, vocab {cfg.vocab}, {cfg.dtype}, "
          f"{cfg.param_count() / 1e9:.3f} B params, seed {seed}",
          flush=True)
    return cfg


def _serve(eng, n_slots, reqs, seed, phases, name):
    """Warm the compiled step and admission on two short requests, then
    serve ``reqs`` as a Poisson stream (the launch.serve workload)."""
    import jax
    from repro.launch.serve import build_workload
    from repro.serve import Scheduler, Server, poisson_arrivals
    sched = Scheduler(eng, n_slots=n_slots, key=jax.random.PRNGKey(seed))
    warm = Server(sched)
    for r in build_workload(2, eng.cfg.vocab, 2, 3, 5, 0.8, seed + 99):
        warm.submit(r)
    phases(f"{name} warmup (compile)", warm.run)
    rep = phases(f"{name} serve", lambda: Server(sched).run(
        arrivals=poisson_arrivals(reqs, rate=1.0, seed=seed)))
    return sched, rep


def _check_served(failures, sched, rep, n_req, gen):
    from repro.launch.serve import recompiles_after_warmup
    print(f"  {rep.summary()}", flush=True)
    done = [c for c in rep.completions if c.error is None]
    _check(failures, len(rep.completions) == n_req and len(done) == n_req
           and all(len(c.tokens) == gen for c in done),
           f"{len(done)}/{n_req} requests completed with {gen} tokens "
           f"(rejects {dict(rep.rejects_by_reason)})")
    _check(failures, not any(rep.health.values()),
           f"health guard counters all 0: {dict(rep.health)}")
    step_x, admit_x = recompiles_after_warmup(sched)
    _check(failures, step_x == 0 and admit_x == 0,
           f"recompiles after warmup: step {step_x}, admit {admit_x}")


def one_chip(args, failures, phases):
    import jax
    from repro.launch.serve import build_engine, build_workload

    cfg = _config(args.seed)
    gen, p_min, p_max = 12, 4, 16
    eng = phases("build (params + IVF index)", build_engine, cfg, args.seed,
                 p_max + gen + 1)
    nb = eng.index.n_blocks
    print(f"  IVF index: {nb} blocks x {eng.index.block_rows} rows "
          f"({nb * eng.index.block_rows - cfg.vocab} cluster-pad rows)",
          flush=True)
    _check(failures, eng.use_pallas,
           "served step takes the Pallas kernels (chosen by the platform)")
    reqs = build_workload(args.requests, cfg.vocab, gen, p_min, p_max,
                          0.8, args.seed)
    sched, rep = _serve(eng, 8, reqs, args.seed, phases, "one-chip")
    _check_served(failures, sched, rep, args.requests, gen)
    del sched, rep
    gc.collect()

    h = parity(args, failures, phases, eng, cfg)
    kernels(args, failures, phases, eng, cfg, h)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"(bytes_limit {stats.get('bytes_limit')})", flush=True)


def parity(args, failures, phases, eng, cfg):
    """Fused decode vs its XLA reference, and vs the exact log Z."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.decode import mimps_decode

    pc = cfg.partition
    rng = np.random.default_rng(args.seed + 1)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)
    # Engine.prefill's forward, with the parameters as an argument rather
    # than 8 GB of constants baked into the executable
    h = phases("hidden states (prefill)",
               jax.jit(lambda p, t: eng.model.forward(p, t)[0][:, -1]),
               eng.params, prompts)
    kd = jax.random.PRNGKey(args.seed + 2)
    run = lambda use_pallas: mimps_decode(
        eng.index, h, kd, n_probe=pc.n_probe, l=pc.l, k=pc.sample_k,
        use_pallas=use_pallas)
    text = mimps_decode.lower(
        eng.index, h, kd, n_probe=pc.n_probe, l=pc.l, k=pc.sample_k,
        use_pallas=True).compile().as_text()
    _check(failures, "tpu_custom_call" in text,
           "served mimps decode compiles to a TPU kernel (tpu_custom_call)")
    out_k = phases("mimps decode, Pallas", run, True)
    out_x = phases("mimps decode, XLA reference", run, False)
    diffs = {f: float(jnp.max(jnp.abs(getattr(out_k, f) - getattr(out_x, f))))
             for f in ("log_z", "head_lse", "tail_lse", "top_score")}
    print(f"  ivf_decode vs XLA body, max |diff|: {diffs}", flush=True)
    _check(failures, all(d <= 1e-4 for d in diffs.values()),
           "ivf_decode kernel == XLA reference within 1e-4")
    _check(failures, bool(jnp.all(out_k.top_id == out_x.top_id)),
           "ivf_decode top-k ids == XLA reference")

    @jax.jit
    def exact_lse(h, w):
        with jax.default_matmul_precision("highest"):
            return jax.nn.logsumexp(
                h.astype(jnp.float32) @ w.astype(jnp.float32).T, -1)

    exact = phases("exact logsumexp over the vocab", exact_lse, h,
                   eng.model.head_matrix(eng.params))
    rel = np.abs(np.expm1(np.asarray(out_k.log_z) - np.asarray(exact)))
    print(f"  mimps log Z vs exact: rel err mean {rel.mean()!r} max "
          f"{rel.max()!r}; log Z {np.asarray(exact).round(4).tolist()}",
          flush=True)
    _check(failures, bool(np.all(np.isfinite(np.asarray(out_k.log_z))))
           and rel.mean() < 0.1,
           "mimps estimate within mean rel err 0.1 of exact log Z")
    return h


def kernels(args, failures, phases, eng, cfg, h):
    """lsh_probe, fmbe_z and topk_z at the served widths vs references."""
    import jax
    import jax.numpy as jnp
    from repro.core.feature_maps import apply_feature_map, make_feature_map
    from repro.kernels.fmbe import fmbe_z
    from repro.kernels.lsh_probe import lsh_probe, lsh_probe_ref
    from repro.kernels.ref import topk_z_ref
    from repro.kernels.topk_z import topk_z

    pc = cfg.partition
    q, d = h.shape
    w = eng.model.head_matrix(eng.params)
    key = jax.random.PRNGKey(args.seed + 3)
    ks = jax.random.split(key, 8)
    hi = jax.default_matmul_precision("highest")

    # topk_z: exact log Z + top-8 over every row of the served head
    got = phases("topk_z", jax.jit(topk_z, static_argnums=2), h, w,
                 pc.sample_k)
    with hi:
        ref = jax.jit(topk_z_ref, static_argnums=2)(
            h.astype(jnp.float32), w.astype(jnp.float32), pc.sample_k)
    d_lse = float(jnp.max(jnp.abs(got[0] - ref[0])))
    d_top = float(jnp.max(jnp.abs(got[1] - ref[1])))
    print(f"  topk_z vs topk_z_ref: max |d lse| {d_lse!r}, |d top| "
          f"{d_top!r}", flush=True)
    _check(failures, d_lse <= 1e-4 and d_top <= 1e-4
           and bool(jnp.all(got[2] == ref[2])),
           "topk_z == topk_z_ref (1e-4, same ids)")

    # lsh_probe: a 1024-row candidate union of the served head, the
    # configured tables x bits, l tail rows. Hyperplanes are rounded to
    # bf16 so that a query's sign bits do not depend on how a matmul
    # splits its float32 operands (the kernel and the reference must hash
    # alike for their collision tables to be comparable).
    n_tab, n_bits, c = pc.lsh_tables, pc.lsh_bits, 1024
    rows = jnp.sort(jax.random.choice(ks[0], cfg.vocab, (c,), replace=False))
    w_cand = w[rows].astype(jnp.float32)
    proj = jax.random.normal(ks[1], (n_tab, n_bits, d + 1)
                             ).astype(jnp.bfloat16).astype(jnp.float32)
    flat = proj[..., :d].reshape(n_tab * n_bits, d)
    with hi:
        bits = (w_cand @ flat.T > 0).astype(jnp.int32)
    codes = (bits.reshape(c, n_tab, n_bits)
             * (1 << jnp.arange(n_bits, dtype=jnp.int32))).sum(-1)
    ok = jnp.ones((c, n_tab), bool)
    live = jnp.int32(c - 24)                     # a dead tail of the union
    tail_ids = jax.random.randint(ks[2], (pc.l,), 0, cfg.vocab)
    tail_rows = w[tail_ids].astype(jnp.float32)
    accept = jax.random.bernoulli(ks[3], 0.9, (q, pc.l))
    bias = 0.1 * jax.random.normal(ks[4], (pc.l,))
    operands = (w_cand, h, proj, rows, codes, ok, live, tail_rows, accept,
                bias)
    got = phases("lsh_probe", jax.jit(lsh_probe, static_argnames="k"),
                 *operands, k=pc.sample_k)
    with hi:
        ref = jax.jit(lsh_probe_ref, static_argnames="k")(
            *operands, k=pc.sample_k)
    diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(got[:3], ref[:3])]
    members = int(jnp.sum(ref[4] > 0))
    print(f"  lsh_probe vs lsh_probe_ref: max |diff| head/tail/top "
          f"{diffs}, {members} query-candidate collisions", flush=True)
    _check(failures, members > 0 and all(x <= 1e-4 for x in diffs)
           and bool(jnp.all(got[3] == ref[3]))
           and bool(jnp.all(got[4] == ref[4])),
           "lsh_probe == lsh_probe_ref (1e-4, same ids and counts)")

    # fmbe_z: the configured sketch (P features, degree <= M) against a
    # per-query lambda, as the FMBE decode's complement estimate calls it
    fm = make_feature_map(ks[5], d, pc.fmbe_features,
                          max_degree=pc.fmbe_max_degree, p=pc.fmbe_p)
    lam = jax.random.normal(ks[6], (q, pc.fmbe_features))
    got = phases("fmbe_z", jax.jit(fmbe_z), fm.omega, fm.degree, fm.coef,
                 lam, h)

    @jax.jit
    def fmbe_ref(fm, h, lam):
        with hi:
            terms = apply_feature_map(fm, h.astype(jnp.float32)) * lam
        return terms.sum(-1), jnp.abs(terms).sum(-1)

    z_ref, scale = fmbe_ref(fm, h, lam)
    err = float(jnp.max(jnp.abs(got - z_ref) / scale))
    print(f"  fmbe_z vs feature-map reference: max |diff| / sum|terms| "
          f"{err!r}", flush=True)
    _check(failures, bool(jnp.all(jnp.isfinite(got))) and err <= 1e-4,
           "fmbe_z == feature-map reference (1e-4 of sum |phi * lambda|)")


# bf16's unit roundoff is 2^-8; compounding as a random walk over the 40
# layers gives sqrt(40) * 2^-8 ~ 0.025 relative L2 between two programs that
# round differently. Twice that is the bound; a trunk computed in fp8
# (roundoff 2^-4) would sit near 0.4.
TRUNK_REL_L2 = 0.05


def four_chips(args, failures, phases):
    """The mesh against one chip, layer by layer, then served tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core.backends import state_partition_specs
    from repro.launch.mesh import make_serving_mesh, serve_cache_spec
    from repro.launch.serve import build_engine, build_workload

    cfg = _config(args.seed)
    pc = cfg.partition
    gen, p_min, p_max, n_req, lanes = 8, 4, 12, 8, 4
    max_len = p_max + gen + 1
    rng = np.random.default_rng(args.seed + 1)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)
    kd = jax.random.PRNGKey(args.seed + 2)

    def served(eng, n_slots, name):
        reqs = build_workload(n_req, cfg.vocab, gen, p_min, p_max, 0.8,
                              args.seed)
        sched, rep = _serve(eng, n_slots, reqs, args.seed, phases, name)
        _check_served(failures, sched, rep, n_req, gen)
        done = sorted(rep.completions, key=lambda c: c.request.req_id)
        return [c.tokens for c in done]

    def replay(step, params, cache, put=lambda x: x):
        """Teacher-forced decode of ``prompts`` through the KV cache (the
        trunk of the served step): the last hidden state per row."""
        for t in range(prompts.shape[1]):
            h, cache = step(params, cache, put(prompts[:, t]),
                            put(jnp.full((prompts.shape[0],), t, jnp.int32)))
        return h

    @jax.jit
    def top2(h, w):
        with jax.default_matmul_precision("highest"):
            return jax.lax.top_k(h.astype(jnp.float32)
                                 @ w.astype(jnp.float32).T, 2)

    # the reference first, on chip 0, then freed: two copies of the
    # parameters on chip 0 would not fit its 16 GB. It runs the XLA
    # estimator bodies, as the mesh step does, on one replica's lane count
    ref = phases("one-chip reference build", build_engine, cfg, args.seed,
                 max_len, use_pallas=False)
    want = served(ref, lanes, "one-chip")
    ref_rows = np.asarray(ref.index.row_id)
    ref_digest = ref._digests[ref.backend.method]
    h_ref = replay(jax.jit(ref.model.decode_step), ref.params,
                   ref.model.init_decode_state(8, max_len))
    ref_top = jax.tree.map(np.asarray, top2(h_ref, ref.state.w))
    out = ref.backend.decode(ref.state, h_ref, kd, pc, k=pc.sample_k,
                             use_pallas=False)
    ref_out = jax.tree.map(np.asarray, (out.log_z, out.top_id))
    h_ref = np.asarray(h_ref)
    del ref, out
    gc.collect()
    print(f"  live device bytes after freeing the reference: "
          f"{sum(x.nbytes for x in jax.live_arrays())}", flush=True)

    mesh = make_serving_mesh(data=2, model=2)
    eng = phases("mesh build", build_engine, cfg, args.seed, max_len,
                 mesh=mesh)
    print("  mesh data=2,model=2 runs the XLA estimator bodies under "
          "shard_map by design; the reference ran the same bodies on one "
          "chip", flush=True)
    put_all = lambda x: jax.device_put(x, NamedSharding(mesh, P()))
    digest = eng._digests[eng.backend.method]
    same_index = digest == ref_digest and np.array_equal(
        np.asarray(eng.index.row_id)[:ref_rows.shape[0]], ref_rows)
    _check(failures, same_index,
           f"IVF index built for the mesh == one-chip index ({digest})")

    # the trunk, teacher-forced: lanes split over 'data', params replicated
    cache = eng.model.init_decode_state(8, max_len)
    cspecs = jax.tree_util.tree_map_with_path(serve_cache_spec, cache)
    shard = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    mstep = jax.jit(jax.shard_map(
        eng.model.decode_step, mesh=mesh,
        in_specs=(P(), cspecs, P("data"), P("data")),
        out_specs=(P("data"), cspecs), check_vma=False))
    h_mesh = replay(mstep, eng.params, jax.device_put(cache, shard(cspecs)),
                    put=lambda x: jax.device_put(
                        x, NamedSharding(mesh, P("data"))))
    mesh_top = jax.tree.map(np.asarray, top2(put_all(h_mesh), eng.state.w))
    diff = np.asarray(h_mesh, np.float32) - h_ref.astype(np.float32)
    rel = float(np.linalg.norm(diff) / np.linalg.norm(
        h_ref.astype(np.float32)))
    margin = ref_top[0][:, 0] - ref_top[0][:, 1]
    agree = int(np.sum(mesh_top[1][:, 0] == ref_top[1][:, 0]))
    print(f"  trunk, teacher-forced {prompts.shape[1]} tokens: max |diff| "
          f"{float(np.max(np.abs(diff)))!r}; exact argmax agrees on "
          f"{agree}/8 rows; one-chip top-1 margins "
          f"{margin.round(5).tolist()}", flush=True)
    _check(failures, rel <= TRUNK_REL_L2,
           f"mesh trunk == one-chip trunk within bf16 rounding: rel L2 "
           f"{rel!r} <= {TRUNK_REL_L2}")

    # the output layer alone, on the one-chip hidden states: the sharded
    # body (model-sharded rows, psum row-gather) vs the single-chip body
    specs = state_partition_specs(eng.state, mesh.shape["model"])
    body = lambda st, hh: eng.backend.shard_decode(
        st, hh, kd, pc, k=pc.sample_k, axis_name="model")
    mesh_out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(specs, P()), out_specs=P(),
        check_vma=False))(jax.device_put(eng.state, shard(specs)),
                          put_all(h_ref))
    d_lz = float(np.max(np.abs(np.asarray(mesh_out.log_z) - ref_out[0])))
    ids_eq = bool(np.array_equal(np.asarray(mesh_out.top_id), ref_out[1]))
    _check(failures, ids_eq and d_lz <= 1e-4,
           f"sharded output layer == one-chip body on the same hidden "
           f"states: top-{pc.sample_k} ids "
           f"{'identical' if ids_eq else 'differ'}, max |d log Z| {d_lz!r}")
    del mesh_out

    got = served(eng, 2 * lanes, "mesh")
    first = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b), None)
             for x, y in zip(got, want)]
    print(f"  served tokens: {first.count(None)}/{n_req} requests identical "
          f"to one chip; first differing position per request {first} (not "
          f"a check: with random weights a top-1 margin can be smaller "
          f"than the trunk's rounding difference above)", flush=True)
    for dev in jax.devices()[:4]:
        st = dev.memory_stats() or {}
        print(f"  device {dev.id}: bytes_in_use {st.get('bytes_in_use')} "
              f"peak_bytes_in_use {st.get('peak_bytes_in_use')}",
              flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh data=2,model=2 phase and its "
                         "one-chip reference (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=12)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repository's src/repro is not beside this "
              f"file ({SRC})", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    failures: list = []
    try:
        devs = _tpu_devices(4 if args.four_chips else 1)
        from repro.launch.compile_cache import use_compile_cache
        import jax
        print(f"device {devs[0].platform} {devs[0].device_kind} x "
              f"{len(devs)}, jax {jax.__version__}, compile cache "
              f"{use_compile_cache()}", flush=True)
        phases = Phases()
        (four_chips if args.four_chips else one_chip)(args, failures, phases)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    if failures:
        print(f"chip_smoke: {len(failures)} check(s) failed: {failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
