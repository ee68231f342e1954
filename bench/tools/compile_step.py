"""Compile a cell's scheduler step for a described TPU v5e, without a
chip, and print its memory analysis: what the step needs beside the
arguments (weights, IVF index, KV cache) it is given.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_step.py --workload <cell>

Only shapes are used; nothing runs. The step is built the way the
scheduler builds it, with the Pallas kernels lowered as they are on the
chip.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import time  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--slots", type=int, default=0,
                    help="lanes to compile for (0: the cell's own)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels
    import repro.kernels.ivf_score as ivf
    from repro.core.backends import get_backend
    from repro.models import Model
    from repro.obs.metrics import init_metric_state
    from repro.serve.scheduler import Scheduler

    from bench import harness
    from bench.model import make_params, program_config

    jax.config.update("jax_enable_compilation_cache", False)
    # lower the kernels for the chip, not in interpret mode
    ivf.on_tpu = repro.kernels.on_tpu = lambda: True
    cell = harness.resolve(args.bench, args.workload)
    s = cell.config["serving"]
    n_slots = args.slots or s["n_slots"]
    cfg = program_config(cell.config, cell.arch)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    key = jnp.zeros(2, jnp.uint32)
    params = jax.eval_shape(lambda k: make_params(cell.arch, k), key)
    backend = get_backend(s["method"])
    bstate = jax.eval_shape(
        lambda w: backend.build(cfg.partition, w, key, device=True),
        params["lm_head"])
    engine = type("ShapeEngine", (), dict(
        model=Model(cfg), cfg=cfg, kernel_cfg={}, use_pallas=True,
        max_len=s["max_len"], backend=backend))()
    sched = object.__new__(Scheduler)
    sched.__dict__.update(engine=engine, key=key, prompt_cap=s["max_len"],
                          mesh=None, n_slots=n_slots,
                          health_guard=bool(s["health_guard"]),
                          step_traces=0, traces_by_tier={}, spec_k=1)
    table = jax.eval_shape(sched._init_table)
    metrics = jax.eval_shape(init_metric_state)
    fault = jax.ShapeDtypeStruct((n_slots,), jnp.bool_)
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt)  # noqa: E731
    extras = {"queue_depth": scalar(jnp.int32),
              "last_ms": scalar(jnp.float32),
              "last_tier": scalar(jnp.int32), "do_shadow": scalar(jnp.bool_)}
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), t)
    t0 = time.time()
    comp = sched._build_step(s["method"]).lower(
        put(table), put(params), put(bstate), put(fault), put(fault),
        put(metrics), put(extras)).compile()
    ma = comp.memory_analysis()
    print(f"{cell.name}: {n_slots} lanes x {s['max_len']} positions, "
          f"compiled in {time.time() - t0:.1f} s; IVF blocks "
          f"{bstate.index.v_blocks.shape[0]} (device build's capacity)")
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes"):
        print(f"  {k}: {getattr(ma, k)}")
    print("  kernel in the step:", "tpu_custom_call" in comp.as_text())


if __name__ == "__main__":
    main()
