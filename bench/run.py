"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object; everything else
goes to standard error, the compared numbers and their limits last. With
no TPU, fewer chips than the cell needs, a device kind not in
``bench/peaks.json``, or no program beside the benchmark, it prints no
result and exits with 2.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compilation cache, at one fixed path inside the
# checkout, so that only a checkout's first run of a cell compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
# libtpu's own log files would go to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
