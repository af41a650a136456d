"""The controls: for each seed, the plain reference computed in the next
precision below the configuration's is put in the program's place and
judged by the cell's own check. Each reading has to fail its limit.

    python3 perfbench/controls.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

Run from the root of a checkout, on the chip (the graphs are made on the
device at the cell's own size). The benchmark's own runs never run this.
Prints one JSON line per seed: the readings with their limits.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench import generators, harness

    cell = harness.Cell(ROOT, args.workload)
    drivers = importlib.import_module(
        f"perfbench.drivers.{cell.traffic['driver']}")
    for seed in args.seeds:
        t = time.perf_counter()
        graph = generators.generate(cell.config, seed)
        driver = drivers.Driver(cell.config, cell.traffic, graph, seed)
        driver.load_control(args.seconds)
        checks = driver.check()
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "seconds": time.perf_counter() - t,
            "control": {k: {"value": v, "limit": lim,
                            "fails": v > lim}
                        for k, (v, lim) in checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
