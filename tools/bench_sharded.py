#!/usr/bin/env python3
"""Sharded-executor evidence on a virtual CPU mesh (no multi-chip here).

Runs the 8-way (and smaller) ShardedTiledExecutor on an R-MAT graph on
``--xla_force_host_platform_device_count`` virtual CPU devices and
records per-iteration wall time plus the ANALYTIC per-device collective
volume. On this 2-core host the virtual devices share cores, so wall
times measure correctness + dispatch overhead, NOT scaling — the
collective-byte model is the honest scaling input (PERF_NOTES.md carries the
extrapolation). Usage:

    python tools/bench_sharded.py [scale] [iters]
"""
import os
import sys

PARTS = (1, 2, 4, 8)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={max(PARTS)}"
).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

from bench import cached_graph, log


def main():
    scale = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    ef = 16
    cache = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".bench_cache",
    )

    import jax

    log(f"platform: {jax.devices()[0].platform}")
    import jax

    log(f"devices: {len(jax.devices())}")

    from lux_tpu.engine.tiled import get_cached_plan
    from lux_tpu.engine.tiled_sharded import ShardedTiledExecutor
    from lux_tpu.graph import generate
    from lux_tpu.models import PageRank
    from lux_tpu.parallel.mesh import make_mesh

    g = cached_graph(
        cache, f"rmat{scale}_{ef}",
        lambda: generate.rmat(scale, ef, seed=42),
    )

    budget = 8 << 30
    plan_path = os.path.join(cache, f"plan_rmat{scale}_{ef}_8x2_8192.luxplan")
    t0 = time.time()
    plan = get_cached_plan(g, plan_path, levels=((8, 2),),
                           budget_bytes=budget, log=log)
    log(f"plan ready in {time.time()-t0:.0f}s (coverage {plan.coverage:.1%})")

    results = []
    for p in PARTS:
        t0 = time.time()
        ex = ShardedTiledExecutor(g, PageRank(), mesh=make_mesh(p), plan=plan)
        log(f"P={p}: executor built in {time.time()-t0:.0f}s "
            f"(max_nv={ex.max_nv})")
        vals = ex.run(1)                     # compile + settle
        t0 = time.perf_counter()
        vals = ex.run(iters, vals=vals)
        dt = (time.perf_counter() - t0) / iters
        # Analytic per-device per-iteration collective volume:
        # ring all-gather of the (max_nv,) f32 value shards ((P-1) segments
        # egress per device) + tiled reduce-scatter of the owner-stacked
        # strip accumulator ((P-1) tiles of max_nv f32 egress per device —
        # round 2's full-height psum cost 2(P-1)/P * nvb*128*4 and grew
        # toward 2x the global accumulator at large P).
        ag = (p - 1) * ex.max_nv * 4
        ps = (p - 1) * ex.max_nv * 4
        res = {
            "parts": p,
            "ms_per_iter": round(dt * 1e3, 1),
            "all_gather_bytes_per_dev": ag,
            "psum_bytes_per_dev": ps,
            "collective_bytes_per_dev": ag + ps,
        }
        log(f"P={p}: {res}")
        results.append(res)
        del ex

    print(json.dumps({
        "metric": f"sharded_tiled_pagerank_rmat{scale}_cpu_mesh",
        "iters": iters,
        "nv": g.nv,
        "ne": g.ne,
        "results": results,
    }))


if __name__ == "__main__":
    main()
