"""Run one cell of BENCHMARK.json once, on the chip this process finds.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. Progress goes to standard error, ending
with each number the correctness check compared and its limit; the last
line of standard output is the result object. Exits 2, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache lives in ``.bench_cache/xla_cache``
inside the checkout unless ``JAX_COMPILATION_CACHE_DIR`` names another
directory; libtpu's log files are off unless ``TPU_LOG_DIR`` is set.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".bench_cache", "xla_cache"))
    # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
    # only inside its checkout and the directories it is given.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax

    import lux_tpu  # noqa: F401  the system under test: without it, no run

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from perfbench import harness

    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START)
    except harness.NoChip as e:
        harness.say(f"perfbench: {e}; no result")
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
