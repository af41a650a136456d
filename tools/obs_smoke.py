#!/usr/bin/env python3
"""Telemetry smoke test (`make obs-smoke`): run PageRank with LUX_METRICS
and LUX_TRACE enabled on a small R-MAT graph and validate both outputs
parse — the metrics dump has one record per iteration with monotone
cumulative time and a compile/execute split, and the trace is valid
JSON-lines with balanced B/E span pairs.

Scale with LUX_SMOKE_SCALE (default 10; acceptance-criteria runs use 14).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    from lux_tpu.utils import flags

    scale = flags.get_int("LUX_SMOKE_SCALE")
    ni = flags.get_int("LUX_SMOKE_ITERS")

    # CPU unless the caller chose a platform; set before jax imports.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from lux_tpu.graph import generate, write_lux
    from lux_tpu.models import pagerank

    with tempfile.TemporaryDirectory() as td:
        gpath = os.path.join(td, f"rmat{scale}.lux")
        mpath = os.path.join(td, "metrics.jsonl")
        tpath = os.path.join(td, "trace.jsonl")
        write_lux(gpath, generate.rmat(scale, 8, seed=1))

        rc = pagerank.main([
            "-file", gpath, "-ni", str(ni),
            "-metrics", mpath, "-trace", tpath,
        ])
        if rc != 0:
            print(f"FAIL: pagerank exited {rc}")
            return 1

        # -- metrics dump ------------------------------------------------
        with open(mpath) as f:
            runs = [json.loads(line) for line in f if line.strip()]
        if not runs:
            print("FAIL: metrics dump is empty")
            return 1
        run = runs[-1]
        problems = []
        if run.get("schema") != "lux.run_telemetry.v1":
            problems.append(f"bad schema: {run.get('schema')!r}")
        if run.get("num_iters") != ni:
            problems.append(f"num_iters {run.get('num_iters')} != {ni}")
        iterations = run.get("iterations", [])
        if len(iterations) != ni:
            problems.append(f"{len(iterations)} iteration records != {ni}")
        cum = [r["t_cum_s"] for r in iterations]
        if any(b < a for a, b in zip(cum, cum[1:])):
            problems.append("t_cum_s is not monotone")
        if run.get("compile_s", -1) < 0:
            problems.append("missing compile_s")
        if run.get("execute_s", 0) <= 0:
            problems.append("execute_s not positive")
        if "metrics" not in run:
            problems.append("missing metrics registry snapshot")

        # -- trace -------------------------------------------------------
        with open(tpath) as f:
            events = [json.loads(line) for line in f if line.strip()]
        if not events:
            problems.append("trace is empty")
        depth = 0
        for ev in events:
            if ev.get("ph") == "B":
                depth += 1
            elif ev.get("ph") == "E":
                depth -= 1
                if depth < 0:
                    problems.append("trace has E before B")
                    break
        if depth > 0:
            problems.append(f"trace has {depth} unclosed B span(s)")

        if problems:
            for p in problems:
                print(f"FAIL: {p}")
            return 1
        print(
            f"OK: {ni} iteration records "
            f"(compile {run['compile_s']:.3f}s, "
            f"execute {run['execute_s']:.4f}s, "
            f"gteps {run['gteps']:.4f}); "
            f"trace: {len(events)} events, B/E balanced"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
