"""Read a cell's compared numbers on several seeds, with the int8 control
beside them: the lower and upper readings its limits are set from.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--serving method='"topk"'] [--fault wrong_row]

Each seed is a whole benchmark run (the cell's traffic at its rate, the
window, the reference over the seeded sample), with the control -- the
reference computed in int8 at the same positions and tokens -- read on
the same sample and held to the cell's limits by the same comparison,
with the run's own counts: ``control_correct`` is what the control's run
would have printed as ``correct``. ``--serving key=value`` changes the
cell's serving settings for this tool only (another estimator in the
program's place); ``--fault`` plants one of ``bench/faults.py``'s faults
in the timed path. One JSON line per seed. The benchmark's own runs never
run the control or plant a fault.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402

from bench import faults, harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--serving", action="append", default=[])
    ap.add_argument("--fault", choices=faults.FAULTS)
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    cell = harness.resolve(args.bench, args.workload)
    for kv in args.serving:
        k, v = kv.split("=", 1)
        cell.config["serving"][k] = json.loads(v)
    for seed in (int(s) for s in args.seeds.split(",")):
        gc.collect()           # the last seed's program and weights
        with (faults.planted(args.fault) if args.fault
              else contextlib.nullcontext()):
            res = harness.run_cell(cell, seed, args.seconds, False,
                                   time.perf_counter(),
                                   log=lambda m: print(m, file=sys.stderr),
                                   control=True)
        ctl = res.get("control") or {}
        print(json.dumps({"seed": seed, "serving": args.serving,
                          "fault": args.fault,
                          "correct": res["correct"],
                          "checks": {k: v["value"] for k, v
                                     in res["checks"].items()},
                          "control_correct": ctl.get("correct"),
                          "control": {k: v["value"] for k, v
                                      in ctl.get("checks", {}).items()},
                          "metrics": {k: v["value"] for k, v
                                      in res["metrics"].items()},
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)


if __name__ == "__main__":
    main()
