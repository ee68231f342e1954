"""Benchmark driver — one section per paper table/figure + kernel benches +
the roofline reader. Prints ``name,us_per_call,derived`` CSV lines at the end.

  PYTHONPATH=src python -m benchmarks.run [--full]

Regression gate (CI):

  PYTHONPATH=src python -m benchmarks.run --check

compares the freshly-written BENCH_decode.json / BENCH_estimators.json /
BENCH_serving.json / BENCH_train.json against the committed
``benchmarks/baseline.json`` and fails on a >25% wall-clock regression
(us_per_step up or tokens_per_s down) for any tracked method, AND enforces
the acceptance invariants: speedup_xla > 1, mimps faster than exact, mince
within 1.5x of mimps (PR 3); continuous batching beats sequential
generate() on goodput, steady-state slot occupancy > 0.5, batched-vs-solo
token parity, zero recompiles after warmup (PR 4); estimator-backed
training writes < 0.35x the embedding-grad floats of fused_ce with grad
cosine >= 0.99, final loss within 5%, and zero recompiles across index
refreshes (PR 5); under 2x sustained overload the server sheds (0 <
shed_rate < 1), keeps a finite p95, engages the degradation ladder
(degraded_token_frac > 0), respects the queue bound, and never recompiles
(PR 6); the mesh-sharded scheduler step keeps token parity and zero
recompiles at every (data, model) mesh shape with tokens-per-step goodput
monotone along the 1/2/4/8-device chain (PR 7); estimator-speculative
decoding beats the non-speculative scheduler on goodput for the
shared-prefix trace with 0 < acceptance <= 1, and the warm prefix cache
saves replay steps (fewer virtual steps, saved_replay_steps > 0) — both
with token parity and zero recompiles (PR 8); the observability layer
fully enabled costs < 5% goodput with bit-identical tokens and zero
recompiles, the latency rows (p50/p95/p99, device/host step split,
per-tier cumulative histograms from the device metric state) are finite
and monotone, and the overload run's harvested per-tier token counts
reconcile exactly with the host report (PR 9). Failure messages print the
offending key, the measured value, and the bound. Refresh the baseline
after a *deliberate* perf change with:

  PYTHONPATH=src python -m benchmarks.run --update-baseline
"""
from __future__ import annotations

import argparse
import json
import math
import os
import platform
import sys

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "baseline.json")
TOL = 1.25   # >25% regression fails


def _machine() -> dict:
    """Host fingerprint stored with the baseline: absolute wall-clock only
    compares like against like (a slower CI runner generation is not a code
    regression); the ratio invariants below are enforced everywhere."""
    model = platform.processor() or ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return {"machine": platform.machine(), "cpu_count": os.cpu_count(),
            "cpu_model": model}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _snapshot():
    """The tracked perf surface of the four serving/training artifacts."""
    dec = _load("BENCH_decode.json")
    est = _load("BENCH_estimators.json")
    srv = _load("BENCH_serving.json")
    trn = _load("BENCH_train.json")
    snap = {"decode": {m: {"us_per_step": dec[m]["us_per_step"],
                           "tokens_per_s": dec[m]["tokens_per_s"]}
                       for m in ("exact", "mimps")},
            "decode_speedup_xla": dec["speedup_xla"],
            "estimators": {m: {"us_per_step": r["us_per_step"],
                               "tokens_per_s": r["tokens_per_s"]}
                           for m, r in est["methods"].items()},
            "serving": {"goodput_tok_s": srv["goodput_tok_s"],
                        "p95_token_ms": srv["p95_token_ms"]},
            "serving_scaling": {
                f"{r['data']}x{r['model']}": {
                    "tok_per_step": r["tok_per_step"],
                    "goodput_tok_s": r["goodput_tok_s"]}
                for r in srv.get("scaling", {}).get("rows", [])},
            "serving_spec": {
                d: {"goodput_tok_s": r["goodput_tok_s"],
                    "tok_per_step": r["tok_per_step"],
                    "acceptance": r["acceptance"]}
                for d, r in srv.get("spec", {}).get("drafts", {}).items()},
            "serving_prefix": {
                mode: srv["prefix_cache"][mode]["goodput_tok_s"]
                for mode in ("off", "on")} if "prefix_cache" in srv else {},
            "train": {m: {"tokens_per_s": r["tokens_per_s"],
                          "us_per_step": r["us_per_step"]}
                      for m, r in trn["methods"].items()}}
    return snap, dec, est, srv, trn


def update_baseline() -> None:
    snap, *_ = _snapshot()
    snap["host"] = _machine()
    with open(BASELINE_PATH, "w") as f:
        json.dump(snap, f, indent=2)
    print(f"baseline written -> {BASELINE_PATH}")


def _gate_msg(key: str, measured, bound: str, why: str = "") -> str:
    """Uniform --check failure line: the offending artifact key, the value
    measured this run, and the bound it broke — so a red CI log names the
    exact number to go look at without re-running the bench."""
    m = f"{measured:.4g}" if isinstance(measured, float) else f"{measured}"
    return f"{key}: measured {m}, bound {bound}" + \
        (f" — {why}" if why else "")


def check() -> int:
    """Compare fresh artifacts against the committed baseline. Returns the
    number of failures (0 = green)."""
    snap, dec, est, srv, trn = _snapshot()
    base = _load(BASELINE_PATH)
    failures = []
    same_host = base.get("host") == _machine()
    if not same_host:
        print("note: baseline was recorded on a different host "
              f"({base.get('host')} vs {_machine()}); absolute wall-clock "
              "comparisons skipped, ratio invariants still enforced")

    def cmp_section(name, cur, ref):
        for method, row in ref.items():
            if method not in cur:
                failures.append(f"{name}.{method}: missing from artifact")
                continue
            us, us0 = cur[method]["us_per_step"], row["us_per_step"]
            tps, tps0 = cur[method]["tokens_per_s"], row["tokens_per_s"]
            if us > us0 * TOL:
                failures.append(_gate_msg(
                    f"{name}.{method}.us_per_step", us,
                    f"<= {TOL:.2f}x baseline {us0:.0f}"))
            if tps < tps0 / TOL:
                failures.append(_gate_msg(
                    f"{name}.{method}.tokens_per_s", tps,
                    f">= baseline {tps0:.0f} / {TOL:.2f}"))

    if same_host:
        cmp_section("decode", snap["decode"], base.get("decode", {}))
        cmp_section("estimators", snap["estimators"],
                    base.get("estimators", {}))
        cmp_section("train", snap["train"], base.get("train", {}))
        ref_srv = base.get("serving")
        if ref_srv:
            # goodput only: p95 is stored for trend-watching but is a
            # small-sample tail statistic — on a shared container it
            # measures the neighbors, not the code
            cur = snap["serving"]
            if cur["goodput_tok_s"] < ref_srv["goodput_tok_s"] / TOL:
                failures.append(_gate_msg(
                    "serving.goodput_tok_s", cur["goodput_tok_s"],
                    f">= baseline {ref_srv['goodput_tok_s']:.0f} / "
                    f"{TOL:.2f}"))

    # wall-clock acceptance invariants (machine-relative, so they are stable
    # across runner generations in a way absolute us_per_step is not)
    if dec["speedup_xla"] <= 1.0:
        failures.append(
            f"decode: speedup_xla {dec['speedup_xla']:.2f} <= 1.0 — the "
            f"sublinear estimator must beat the exact pass in wall-clock")
    em = est["methods"]
    if em["mimps"]["us_per_step"] >= em["exact"]["us_per_step"]:
        failures.append(
            f"estimators: mimps {em['mimps']['us_per_step']:.0f}us >= "
            f"exact {em['exact']['us_per_step']:.0f}us")
    if em["mince"]["us_per_step"] > 1.5 * em["mimps"]["us_per_step"]:
        failures.append(
            f"estimators: mince {em['mince']['us_per_step']:.0f}us > 1.5x "
            f"mimps {em['mimps']['us_per_step']:.0f}us")
    for m, cap in (("mimps", 0.5), ("mince", 1.0), ("fmbe", 0.5)):
        if em[m]["rel_err_vs_exact"] >= cap:
            failures.append(
                f"estimators: {m} rel_err {em[m]['rel_err_vs_exact']:.3g} "
                f">= {cap} (accuracy regression)")
    if not est["bound"]["ok_all"]:
        failures.append(
            "estimators: bound_ok_all false — some method exceeded its "
            "floats_bound ceiling or broke Pallas/XLA parity")
    if not est["bound"]["byte_sublinear_all"]:
        failures.append(
            "estimators: byte_sublinear_all false — a sublinear method "
            "touched more embedding floats than exact")

    # lsh acceptance invariants (PR 10): the SimHash collision backend must
    # beat the exact pass in wall-clock at bench scale with rel_err <= 0.1
    # at the bench seed (both measured on the same interleaved timing pass),
    # and its O(R)-row index maintenance (update_rows) must cost strictly
    # less than a full IVF re-cluster at equal embedding churn.
    if "lsh" not in em:
        failures.append("estimators: lsh method missing from artifact")
    else:
        if em["lsh"]["us_per_step"] >= em["exact"]["us_per_step"]:
            failures.append(
                f"estimators: lsh {em['lsh']['us_per_step']:.0f}us >= "
                f"exact {em['exact']['us_per_step']:.0f}us — the collision "
                f"probe must beat the dense pass in wall-clock")
        if em["lsh"]["rel_err_vs_exact"] > 0.1:
            failures.append(
                f"estimators: lsh rel_err "
                f"{em['lsh']['rel_err_vs_exact']:.3g} > 0.1 at the bench "
                f"seed (collision-head recall regression)")
    rc = trn.get("refresh_cost")
    if not rc:
        failures.append("train: refresh_cost section missing from artifact")
    elif rc["lsh_update_us"] >= rc["ivf_refresh_us"]:
        failures.append(
            f"train: lsh update_rows {rc['lsh_update_us']:.0f}us >= IVF "
            f"refresh {rc['ivf_refresh_us']:.0f}us at "
            f"{rc['rows_updated']} churned rows — the O(R) splice lost to "
            f"the full re-cluster")
    lsh_tm = trn["methods"].get("lsh_ce")
    if not lsh_tm:
        failures.append("train: lsh_ce run missing from artifact")
    else:
        lrf = lsh_tm["refresh"]
        if lrf["step_retraces"] != 1 or lrf["refresh_retraces"] != 1:
            failures.append(
                f"train: lsh_ce {lrf['step_retraces'] - 1} step + "
                f"{lrf['refresh_retraces'] - 1} refresh recompiles across "
                f"index refreshes")
        if lrf["count"] < 1:
            failures.append(
                "train: the bench never exercised an lsh index refresh")

    # training acceptance invariants (exact ratios, PR 5): the estimator in
    # the gradient must write sublinear embedding-grad floats, match the
    # full-CE gradient direction, learn what fused_ce learns, and refresh
    # the index without a single recompile.
    tm = trn["methods"]["mimps_ce"]
    if trn["grad_float_ratio"] >= 0.35:
        failures.append(
            f"train: embedding-grad float ratio "
            f"{trn['grad_float_ratio']:.3f} >= 0.35 vs fused_ce — the "
            f"sparse backward is not sublinear at bench scale")
    if tm["grad_cosine_vs_full"] < 0.99:
        failures.append(
            f"train: mimps_ce grad cosine {tm['grad_cosine_vs_full']:.4f} "
            f"< 0.99 vs full-CE embedding gradient")
    if not (0.95 <= trn["loss_ratio_vs_fused"] <= 1.05):
        failures.append(
            f"train: mimps_ce final loss is {trn['loss_ratio_vs_fused']:.3f}"
            f"x fused_ce (must be within 5% after the step budget)")
    rf = tm["refresh"]
    if rf["step_retraces"] != 1 or rf["refresh_retraces"] != 1:
        failures.append(
            f"train: {rf['step_retraces'] - 1} step + "
            f"{rf['refresh_retraces'] - 1} refresh recompiles across index "
            f"refreshes (the static-capacity repack must reuse one "
            f"executable)")
    if rf["count"] < 1:
        failures.append("train: the bench never exercised an index refresh")

    # serving acceptance invariants (machine-relative / exact, PR 4):
    # continuous batching must beat sequential generate() on goodput at
    # >= 8 concurrent mixed-length requests, with saturated slots, ZERO
    # recompiles after warmup, and bit-identical batched-vs-solo tokens.
    if srv["speedup_vs_sequential"] <= 1.0:
        failures.append(
            f"serving: continuous goodput {srv['goodput_tok_s']:.0f} tok/s "
            f"<= sequential {srv['sequential_goodput_tok_s']:.0f} "
            f"(speedup {srv['speedup_vs_sequential']:.2f}x)")
    if srv["peak_concurrency"] < 8:
        failures.append(
            f"serving: peak concurrency {srv['peak_concurrency']} < 8 — "
            f"the workload never filled the slot table")
    if srv["occupancy_steady"] <= 0.5:
        failures.append(
            f"serving: steady-state occupancy {srv['occupancy_steady']:.2f}"
            f" <= 0.5 (admission is starving the slot table)")
    if not srv["token_parity_vs_solo"]:
        failures.append(
            "serving: batched tokens differ from solo generate() — the "
            "slot table broke per-request sampling")
    if srv["recompiles_after_warmup"] != 0:
        failures.append(
            f"serving: {srv['recompiles_after_warmup']} recompiles after "
            f"warmup (the mixed step must serve every admission/replay/"
            f"decode mix with one executable)")

    # latency rows (obs satellite): the host tail percentiles and the
    # device/host step-time split must be finite positives, and the
    # device-harvested per-tier histogram rows — emitted cumulative — must
    # be monotone non-decreasing with every tier that served tokens present.
    lat = srv.get("latency")
    if not lat:
        failures.append("serving: latency section missing from artifact")
    else:
        for key in ("p50_token_ms", "p95_token_ms", "p99_token_ms",
                    "step_device_ms_mean", "step_host_ms_mean"):
            v = lat.get(key)
            if v is None or not math.isfinite(v) or v <= 0:
                failures.append(_gate_msg(
                    f"serving.latency.{key}", v, "finite and > 0"))
        p50, p95, p99 = (lat.get("p50_token_ms", 0),
                         lat.get("p95_token_ms", 0),
                         lat.get("p99_token_ms", 0))
        if not p50 <= p95 <= p99:
            failures.append(_gate_msg(
                "serving.latency.p50<=p95<=p99", (p50, p95, p99),
                "ordered percentiles"))
        hist = lat.get("per_tier_cumulative", {})
        if not hist:
            failures.append(
                "serving.latency.per_tier_cumulative: empty — the device "
                "histogram harvested no steps")
        for tier, row in hist.items():
            if any(b < a for a, b in zip(row, row[1:])):
                failures.append(_gate_msg(
                    f"serving.latency.per_tier_cumulative[{tier}]", row,
                    "monotone non-decreasing cumulative buckets"))
            if len(row) != len(lat.get("edges_ms", [])) + 1:
                failures.append(_gate_msg(
                    f"serving.latency.per_tier_cumulative[{tier}].len",
                    len(row), f"{len(lat.get('edges_ms', []))} edges + "
                    f"overflow bucket"))

    # observability overhead (obs tentpole acceptance): obs fully enabled
    # must keep tokens bit-identical to obs-off, trace nothing new, and
    # cost < 5% goodput. The ratio is measured within one process on one
    # host (interleaved best-of-5), so it is machine-relative and enforced
    # unconditionally.
    oo = srv.get("obs_overhead")
    if not oo:
        failures.append("serving: obs_overhead section missing from "
                        "artifact")
    else:
        if oo["goodput_ratio_on_vs_off"] < 0.95:
            failures.append(_gate_msg(
                "serving.obs_overhead.goodput_ratio_on_vs_off",
                oo["goodput_ratio_on_vs_off"], ">= 0.95",
                "the observability layer costs more than 5% goodput"))
        if not oo["token_parity_on_vs_off"]:
            failures.append(
                "serving.obs_overhead: tokens differ with observability "
                "on — instrumentation must not perturb sampling")
        if oo["recompiles_after_warmup"] != 0:
            failures.append(_gate_msg(
                "serving.obs_overhead.recompiles_after_warmup",
                oo["recompiles_after_warmup"], "== 0",
                "toggling obs changed an executable"))

    # overload acceptance invariants (exact, PR 6): at 2x sustained demand
    # through a bounded queue + degradation ladder, the server must shed
    # (not hang), keep serving the admitted work with a finite tail, walk
    # the ladder deterministically, respect the queue bound, and do all of
    # it without a single recompile.
    ov = srv.get("overload")
    if not ov:
        failures.append("serving: overload scenario missing from artifact")
    else:
        if not ov["shed_rate"] > 0.0:
            failures.append(
                "serving.overload: shed_rate == 0 at 2x demand with a "
                "bounded queue — backpressure never engaged")
        if not ov["shed_rate"] < 1.0:
            failures.append(
                "serving.overload: shed_rate == 1 — the server shed "
                "everything instead of serving what fit")
        if not math.isfinite(ov["p95_under_overload"]) or \
                ov["p95_under_overload"] <= 0:
            failures.append(
                f"serving.overload: p95_under_overload "
                f"{ov['p95_under_overload']} is not a finite positive "
                f"latency — admitted requests starved under overload")
        if not ov["degraded_token_frac"] > 0.0:
            failures.append(
                "serving.overload: degraded_token_frac == 0 — sustained "
                "queue pressure never engaged the estimator-tier ladder")
        if ov["queue_depth_peak"] > ov["max_queue"]:
            failures.append(
                f"serving.overload: queue_depth_peak "
                f"{ov['queue_depth_peak']} > max_queue {ov['max_queue']} "
                f"(the bounded queue leaked)")
        if ov["recompiles_after_warmup"] != 0:
            failures.append(
                f"serving.overload: {ov['recompiles_after_warmup']} "
                f"recompiles under overload (tier switches must reuse the "
                f"per-tier executables compiled at warmup)")
        oobs = ov.get("obs")
        if not oobs:
            failures.append("serving.overload: obs section missing — the "
                            "overload run must ride fully instrumented")
        else:
            if not oobs["tokens_reconciled"]:
                failures.append(_gate_msg(
                    "serving.overload.obs.tokens_by_tier_harvested",
                    oobs["tokens_by_tier_harvested"],
                    f"== ServerReport.tokens_by_tier "
                    f"{ov.get('tokens_by_tier')}",
                    "device counters disagree with host accounting"))
            if oobs["trace_events"] <= 0:
                failures.append(_gate_msg(
                    "serving.overload.obs.trace_events",
                    oobs["trace_events"], "> 0",
                    "the overload trace is empty"))
            if not oobs["shadow_rel_err_by_tier"]:
                failures.append(
                    "serving.overload.obs: no shadow rel-err samples — "
                    "estimator-quality telemetry never fired")

    # dedup_by_fill rows (PR 8 format): sorted [int fill, float ratio]
    # pairs — the old object form stringified the int keys and scrambled
    # their order.
    df = srv.get("dedup_by_fill")
    if not isinstance(df, list) or any(
            not (isinstance(f, int) and isinstance(r, (int, float)))
            for f, r in df):
        failures.append(
            "serving: dedup_by_fill must be [[int fill, ratio], ...] rows")
    elif [f for f, _ in df] != sorted(f for f, _ in df):
        failures.append(
            f"serving: dedup_by_fill rows not sorted by fill: "
            f"{[f for f, _ in df]}")
    elif any(not 0.0 < r <= 1.0 for _, r in df):
        failures.append(
            f"serving: dedup_by_fill ratio outside (0, 1] — the probe "
            f"union U/(Q*n_probe) shrinks with batch fill, never grows "
            f"({df})")

    # raw-speed acceptance invariants (PR 8): on the shared-prefix trace,
    # estimator-speculative decoding must BEAT the non-speculative
    # scheduler (wall goodput and, deterministically, tokens per virtual
    # step) for at least one registry draft, with sane acceptance and the
    # two hard invariants intact per draft; the warm prefix cache must
    # actually save replay steps (strictly fewer virtual steps than the
    # cache-off run and saved_replay_steps > 0).
    sp = srv.get("spec")
    if not sp or not sp.get("drafts"):
        failures.append("serving: spec (speculative decoding) section "
                        "missing from artifact")
    else:
        base = sp["nonspec"]
        for d, r in sp["drafts"].items():
            if not r["token_parity"]:
                failures.append(
                    f"serving.spec[{d}]: tokens differ from solo "
                    f"generate() — speculation broke per-request sampling")
            if r["recompiles_after_warmup"] != 0:
                failures.append(
                    f"serving.spec[{d}]: {r['recompiles_after_warmup']} "
                    f"recompiles (variable per-lane acceptance must be "
                    f"data, not shape)")
            if not 0.0 < r["acceptance"] <= 1.0:
                failures.append(
                    f"serving.spec[{d}]: acceptance {r['acceptance']:.3f} "
                    f"outside (0, 1]")
        if not any(r["goodput_tok_s"] > base["goodput_tok_s"]
                   for r in sp["drafts"].values()):
            failures.append(
                f"serving.spec: no draft beats non-speculative goodput "
                f"{base['goodput_tok_s']:.0f} tok/s "
                f"({ {d: round(r['goodput_tok_s']) for d, r in sp['drafts'].items()} })")
        if not any(r["tok_per_step"] > base["tok_per_step"]
                   for r in sp["drafts"].values()):
            failures.append(
                f"serving.spec: no draft beats non-speculative "
                f"tokens-per-step {base['tok_per_step']:.2f}")
    pc = srv.get("prefix_cache")
    if not pc:
        failures.append("serving: prefix_cache section missing from "
                        "artifact")
    else:
        if not pc["token_parity"]:
            failures.append(
                "serving.prefix_cache: tokens differ from solo generate() "
                "— cached-prefix replay skip broke decoding")
        if pc["recompiles_after_warmup"] != 0:
            failures.append(
                f"serving.prefix_cache: {pc['recompiles_after_warmup']} "
                f"recompiles (pool load/save must be compiled once)")
        if not pc["saved_replay_steps"] > 0:
            failures.append(
                "serving.prefix_cache: saved_replay_steps == 0 — the warm "
                "cache never skipped a replay step")
        if not pc["on"]["steps"] < pc["off"]["steps"]:
            failures.append(
                f"serving.prefix_cache: {pc['on']['steps']} virtual steps "
                f"with the cache on >= {pc['off']['steps']} off — cache "
                f"hits are not shortening the replay phase")

    # mesh-scaling acceptance invariants (exact, PR 7): the sharded
    # scheduler step must keep tokens bit-identical to solo generate() and
    # recompile nothing at EVERY mesh shape, and goodput on the virtual
    # step clock (tokens per compiled step — the hardware-independent
    # scaling quantity; wall clock on forced host devices measures core
    # contention, see serving_bench._scaling) must be monotone
    # non-decreasing along the data chain with 8 devices beating 1.
    sc = srv.get("scaling")
    if not sc or not sc.get("rows"):
        failures.append("serving: scaling curve missing from artifact")
    else:
        rows = sc["rows"]
        devices = {r["devices"] for r in rows}
        if not {1, 2, 4, 8} <= devices:
            failures.append(
                f"serving.scaling: curve covers devices {sorted(devices)}, "
                f"needs {{1, 2, 4, 8}}")
        for r in rows:
            shape = f"data={r['data']},model={r['model']}"
            if not r["token_parity"]:
                failures.append(
                    f"serving.scaling[{shape}]: tokens differ from solo "
                    f"generate() — sharding broke per-request sampling")
            if r["recompiles_after_warmup"] != 0:
                failures.append(
                    f"serving.scaling[{shape}]: "
                    f"{r['recompiles_after_warmup']} recompiles after "
                    f"warmup (one executable must serve every mesh shape's "
                    f"traffic)")
            if r["occupancy_steady"] <= 0.5:
                failures.append(
                    f"serving.scaling[{shape}]: steady occupancy "
                    f"{r['occupancy_steady']:.2f} <= 0.5 — replica routing "
                    f"is starving lanes")
        chain = sorted((r["devices"], r["tok_per_step"]) for r in rows
                       if r["model"] == 1)
        if any(b[1] < a[1] for a, b in zip(chain, chain[1:])):
            failures.append(
                f"serving.scaling: tok_per_step not monotone along the "
                f"data chain: {[(d, round(t, 1)) for d, t in chain]}")
        if chain and not chain[-1][1] > chain[0][1]:
            failures.append(
                f"serving.scaling: goodput at 8 devices "
                f"({chain[-1][1]:.1f} tok/step) must beat 1 device "
                f"({chain[0][1]:.1f} tok/step)")

    if failures:
        print("== bench regression check: FAIL ==")
        for f in failures:
            print("  " + f)
    else:
        print("== bench regression check: OK ==")
        for name, sec in (("decode", snap["decode"]),
                          ("estimators", snap["estimators"])):
            for m, row in sec.items():
                print(f"  {name}.{m}: {row['us_per_step']:.0f}us/step "
                      f"({row['tokens_per_s']:.0f} tok/s)")
        print(f"  serving: {srv['goodput_tok_s']:.0f} tok/s goodput "
              f"({srv['speedup_vs_sequential']:.2f}x sequential), "
              f"occupancy {srv['occupancy_steady']:.2f}, p95 "
              f"{srv['p95_token_ms']:.2f}ms")
        lat = srv.get("latency", {})
        if lat:
            print(f"  serving.latency: p99 {lat['p99_token_ms']:.2f}ms, "
                  f"step device {lat['step_device_ms_mean']:.2f}ms + host "
                  f"{lat['step_host_ms_mean']:.2f}ms, tier histograms "
                  f"{sorted(lat['per_tier_cumulative'])}")
        oo = srv.get("obs_overhead", {})
        if oo:
            print(f"  serving.obs: {oo['goodput_ratio_on_vs_off']:.3f}x "
                  f"goodput with obs fully on (parity "
                  f"{oo['token_parity_on_vs_off']}, recompiles "
                  f"{oo['recompiles_after_warmup']})")
        ov = srv.get("overload", {})
        if ov:
            print(f"  serving.overload: shed {ov['shed_rate']:.2f}, p95 "
                  f"{ov['p95_under_overload']:.2f}ms, degraded "
                  f"{ov['degraded_token_frac']:.2f}, queue peak "
                  f"{ov['queue_depth_peak']}/{ov['max_queue']}, "
                  f"recompiles {ov['recompiles_after_warmup']}")
        sp, pc = srv.get("spec", {}), srv.get("prefix_cache", {})
        if sp and pc:
            acc = ", ".join(f"{d}:{r['acceptance']:.2f}"
                            for d, r in sp["drafts"].items())
            print(f"  serving.raw_speed: spec "
                  f"{sp['speedup_vs_nonspec']:.2f}x non-spec goodput "
                  f"(acceptance {acc}); prefix cache saved "
                  f"{pc['saved_replay_steps']} replay steps "
                  f"({pc['on']['steps']} vs {pc['off']['steps']} virtual "
                  f"steps)")
        sc = srv.get("scaling", {})
        if sc.get("rows"):
            curve = ", ".join(
                f"{r['devices']}dev:{r['tok_per_step']:.1f}"
                for r in sc["rows"] if r["model"] == 1)
            print(f"  serving.scaling: tok/step {curve} "
                  f"({sc['goodput_scaling_8v1']:.2f}x at 8 devices, "
                  f"parity+0 recompiles at every shape)")
        print(f"  train: grad floats {trn['grad_float_ratio']:.3f}x fused, "
              f"grad cosine {tm['grad_cosine_vs_full']:.4f}, loss "
              f"{trn['loss_ratio_vs_fused']:.3f}x, refreshes "
              f"{tm['refresh']['count']} (0 recompiles)")
    return len(failures)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slower)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,t1,t2,t3,t4,kernels,roofline,"
                         "decode,estimators,serving,train")
    ap.add_argument("--check", action="store_true",
                    help="compare BENCH_*.json against benchmarks/"
                         "baseline.json; exit 1 on >25%% regression or "
                         "broken wall-clock acceptance invariants")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite benchmarks/baseline.json from the current "
                         "BENCH_*.json artifacts")
    args = ap.parse_args()
    if args.check:
        sys.exit(1 if check() else 0)
    if args.update_baseline:
        update_baseline()
        return
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (decode_bench, estimator_bench, fig1_cdf, kernels_bench,
                   roofline, serving_bench, table1_grid, table2_noise,
                   table3_retrieval, table4_lbl, train_bench)

    csv = ["name,us_per_call,derived"]

    def sel(key):
        return only is None or key in only

    if sel("fig1"):
        _, us = fig1_cdf.run(quick=quick)
        csv.append(f"fig1_cdf,{us:.1f},concentration-vs-frequency")
    if sel("t1"):
        _, us = table1_grid.run(quick=quick)
        csv.append(f"table1_grid,{us:.1f},mu-vs-k-l")
    if sel("t2"):
        _, us = table2_noise.run(quick=quick)
        csv.append(f"table2_noise,{us:.1f},noise-robustness")
    if sel("t3"):
        _, us = table3_retrieval.run(quick=quick)
        csv.append(f"table3_retrieval,{us:.1f},rank1-criticality")
    if sel("t4"):
        _, us = table4_lbl.run(quick=quick)
        csv.append(f"table4_lbl,{us:.1f},e2e-lbl-nce")
    if sel("kernels"):
        rows, _ = kernels_bench.run(quick=quick)
        for name, us, derived in rows:
            csv.append(f"{name},{us:.1f},{derived}")
    if sel("roofline"):
        rows, _ = roofline.run(quick=quick)
        csv.append(f"roofline_cells,{len(rows)},see artifacts/roofline.md")
    if sel("decode"):
        rep, us = decode_bench.run(quick=quick)
        csv.append(f"decode_mimps,{us:.1f},"
                   f"speedup_xla={rep['speedup_xla']:.2f}x;"
                   f"bytes_reduction={rep['bytes_reduction']:.1f}x;"
                   f"bound_ok={rep['bound']['ok']}")
    if sel("estimators"):
        rep, us = estimator_bench.run(quick=quick)
        csv.append(f"estimators,{us:.1f},"
                   f"bound_ok_all={rep['bound']['ok_all']};"
                   f"byte_sublinear_all={rep['bound']['byte_sublinear_all']}")
    if sel("serving"):
        rep, us = serving_bench.run(quick=quick)
        csv.append(f"serving,{us:.1f},"
                   f"speedup={rep['speedup_vs_sequential']:.2f}x;"
                   f"occupancy={rep['occupancy_steady']:.2f};"
                   f"parity={rep['token_parity_vs_solo']};"
                   f"recompiles={rep['recompiles_after_warmup']};"
                   f"shed={rep['overload']['shed_rate']:.2f};"
                   f"degraded={rep['overload']['degraded_token_frac']:.2f};"
                   f"scale8v1={rep['scaling']['goodput_scaling_8v1']:.2f}x;"
                   f"spec={rep['spec']['speedup_vs_nonspec']:.2f}x;"
                   f"prefix_saved={rep['prefix_cache']['saved_replay_steps']}")
    if sel("train"):
        rep, us = train_bench.run(quick=quick)
        tm = rep["methods"]["mimps_ce"]
        csv.append(f"train,{us:.1f},"
                   f"grad_floats={rep['grad_float_ratio']:.3f}x;"
                   f"grad_cos={tm['grad_cosine_vs_full']:.4f};"
                   f"loss_ratio={rep['loss_ratio_vs_fused']:.3f};"
                   f"refresh_recompiles="
                   f"{tm['refresh']['refresh_retraces'] - 1}")

    print("\n== CSV ==")
    print("\n".join(csv))


if __name__ == "__main__":
    main()
