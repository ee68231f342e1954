"""Production train driver: elastic mesh, checkpoint/auto-resume, straggler
watchdog, deterministic resumable data.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real cluster each host runs this same script; jax.distributed handles
process groups. On this single-host container it drives the 1-device mesh —
the code path (mesh build -> restore -> step loop -> checkpoint) is the one
the dry run lowers at (16, 16).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduced_config
from ..configs.base import TrainConfig
from ..data import DataIterator, SyntheticCorpus
from ..models import Model
from ..train import (CheckpointManager, StragglerWatchdog,
                     harvest_train_metrics, init_train_metric_state,
                     init_train_state, make_elastic_mesh,
                     make_index_refresh, make_instrumented_step,
                     make_train_step)
from ..train.losses import ESTIMATOR_LOSSES, LOSSES
from .compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="laptop-scale config of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    # choices from the registry so a typo (or a loss added without wiring)
    # fails at parse time — the same stale-list bug class launch/serve.py
    # --method had before it read the backend registry
    ap.add_argument("--loss", default="fused_ce", choices=sorted(LOSSES))
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--index-refresh-every", type=int, default=100,
                    help="steps between IVF index refreshes (estimator-"
                         "backed losses only; shapes are static so the "
                         "refresh never recompiles; 0 disables refreshes)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--harvest-every", type=int, default=10,
                    help="steps between device->host metric syncs; the "
                         "loop only block_until_ready's on this cadence "
                         "(device counters accumulate loss/grad stats "
                         "in between — obs layer, DESIGN.md SS17)")
    ap.add_argument("--metrics-snapshot", default="", metavar="PATH",
                    help="write harvested train metrics as JSON to PATH "
                         "at the end of the run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = Model(cfg)
    tc = TrainConfig(lr=args.lr, total_steps=args.steps, loss=args.loss,
                     microbatches=args.microbatches, seed=args.seed,
                     warmup_steps=max(1, args.steps // 10),
                     index_refresh_every=args.index_refresh_every)
    mesh = make_elastic_mesh(model_parallel=args.model_parallel)
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name}  "
          f"params: {cfg.param_count()/1e6:.1f}M")

    corpus = SyntheticCorpus(vocab=cfg.vocab, seed=args.seed)
    it = DataIterator(corpus, args.batch, args.seq,
                      n_codebooks=cfg.n_codebooks)
    state = init_train_state(model, tc, jax.random.PRNGKey(args.seed))

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        latest = mgr.latest_step()
        if latest is not None:
            state, manifest = mgr.restore(latest, like=state)
            start_step = manifest["step"]
            it.state.step = manifest["extra"].get("data_step", start_step)
            print(f"resumed from step {start_step}")

    step_fn = jax.jit(make_instrumented_step(make_train_step(model, tc)))
    refresh_fn = make_index_refresh(model, tc) \
        if tc.loss in ESTIMATOR_LOSSES and tc.index_refresh_every > 0 \
        else None
    wd = StragglerWatchdog()
    tm = init_train_metric_state()
    sync_every = max(args.harvest_every, 1)
    with mesh:
        for step in range(start_step, args.steps):
            toks, labels = next(it)
            batch = {"tokens": jnp.asarray(toks),
                     "labels": jnp.asarray(labels)}
            if cfg.family == "vlm":
                batch["img"] = jnp.zeros(
                    (args.batch, cfg.n_image_tokens, cfg.d_model),
                    jnp.dtype(cfg.dtype))
            wd.start_step()
            # cadence keyed on the GLOBAL step (not the resume offset) so a
            # resumed run refreshes at exactly the same steps as an
            # uninterrupted one — resume determinism includes the index
            refreshed = ""
            if refresh_fn is not None and step > 0 and \
                    step % tc.index_refresh_every == 0:
                state, rm = refresh_fn(state)
                refreshed = (f" [refresh churn {float(rm['churn']):.3f}"
                             f" drift {float(rm['drift']):.3f}]")
            state, tm, metrics = step_fn(state, tm, batch)
            # only synchronize with the device on the harvest/log cadence —
            # between syncs the dispatch queue runs ahead and the device
            # counters (TrainMetricState) carry the per-step stats
            log_now = (step % 10 == 0 or step == args.steps - 1
                       or bool(refreshed))
            if log_now or (step + 1) % sync_every == 0:
                jax.block_until_ready(metrics["loss_total"])
            slow = wd.end_step(step)
            if log_now:
                print(f"step {step:5d} loss {float(metrics['loss_total']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e}"
                      + (" [straggler]" if slow else "") + refreshed)
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, state,
                         extra={"data_step": it.state.step})
    th = harvest_train_metrics(tm)
    print(f"train metrics: loss mean {th['loss_mean']:.4f} "
          f"std {th['loss_std']:.4f} max {th['loss_max']:.4f}  "
          f"gnorm mean {th['grad_norm_mean']:.3f} "
          f"max {th['grad_norm_max']:.3f}  "
          f"nonfinite steps {th['nonfinite_steps']}/{th['steps']}")
    if args.metrics_snapshot:
        import json
        with open(args.metrics_snapshot, "w", encoding="utf-8") as fh:
            json.dump(th, fh, indent=1)
        print(f"train metrics snapshot: {args.metrics_snapshot}")
    if mgr:
        mgr.save(args.steps, state, extra={"data_step": it.state.step})
        mgr.wait()
        print(f"final checkpoint at {args.ckpt_dir}")


if __name__ == "__main__":
    main()
