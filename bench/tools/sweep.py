"""Find a cell's knee: serve its traffic at several fixed rates, one after
another on one set-up, and print what each rate gave.

    python3 bench/tools/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 1.2,1.6,2.0

One JSON line per rate on standard output. Tails, throughput and the
backlog at the window's end (requests released but not yet admitted) show
where the system stops keeping up. The benchmark's own runs never call
this; a cell's rate is set once from its output.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import json  # noqa: E402

from bench import harness  # noqa: E402
from bench.roofline import peaks  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    cell = harness.resolve(args.bench, args.workload)
    b = harness.build(cell, args.seed)
    pk = peaks(b.device.device_kind)
    wanted = [m for m in cell.end_to_end
              if m["name"] not in ("logz_rel_err", "setup_s")]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        loop = harness.serve(b, cell, args.seed + i, args.seconds, rate)
        run = harness.run_data(cell, loop, args.seconds, 0.0, {}, pk)
        m = harness.read_metrics(cell, run, wanted)
        lo, hi = loop.window
        due = run.due()
        steps = run.window_steps()
        backlog = sum(1 for r in loop.sent if r.released and r.released < hi
                      and not (r.admitted and r.admitted <= hi))
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "completed": sum(1 for r in due if r.done and not r.error),
            "backlog_at_window_end": backlog,
            "mean_busy_lanes": sum(s.n_active for s in steps)
            / max(len(steps), 1),
            "step_wall_ms": (steps[-1].t_done - steps[0].t_start)
            / max(len(steps), 1) * 1e3 if steps else None,
            "metrics": {k: v["value"] for k, v in m.items()}}), flush=True)
        b.sched.drain("sweep")
        time.sleep(1.0)


if __name__ == "__main__":
    main()
