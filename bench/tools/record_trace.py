"""Record the small trace the trace reduction's test reads.

    python3 bench/tools/record_trace.py --workload <cell> --seed <n> \
        --seconds <s> --slice-ms 80 --out bench/tests/data/v5e_trace_slice.json.gz

Serves the cell with a profiler trace, prints which planes, lines and
event names the trace holds, then keeps the events of the first
``--slice-ms`` of the traced stretch -- device operations, executables and
the benchmark's host thread -- with a ``bench.window`` span over exactly
that slice, and writes them with ``bench.trace.save_events``.
"""
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402

from bench import harness, trace  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice-ms", type=float, default=80.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    cell = harness.resolve(args.bench, args.workload)
    b = harness.build(cell, args.seed)
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    harness.serve(b, cell, args.seed, args.seconds,
                  float(cell.cell["rate_per_s"]), tdir)
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    print("xplane bytes", os.path.getsize(path))
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            print(f"{plane.name} | {line.name} | {sum(names.values())} "
                  f"events | {names.most_common(12)}")
    events = trace.load(tdir)
    win = [e for e in events if e.name == trace.WINDOW_SPAN][0]
    lo = win.start_ns
    hi = lo + args.slice_ms * 1e6
    keep = [e for e in events if e.end_ns > lo and e.start_ns < hi
            and e.name != trace.WINDOW_SPAN]
    keep.append(trace.Event(win.plane, win.line, trace.WINDOW_SPAN, lo,
                            hi - lo))
    trace.save_events(keep, args.out)
    r = trace.reduce(keep)
    print("slice", len(keep), "events; busy", r.busy_s, "of", r.window_s,
          "s; modules", r.module_count, "top ops", r.top_ops(5),
          "gaps", r.top_gaps(5))
    full = trace.reduce(events)
    print("full window: busy", full.busy_s, "of", full.window_s,
          "modules", {k: (full.module_count[k], v * 1e-9)
                      for k, v in full.module_ns.items()},
          "\ntop ops", full.top_ops(15), "\ngaps", full.top_gaps(10))


if __name__ == "__main__":
    main()
