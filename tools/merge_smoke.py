#!/usr/bin/env python3
"""Grouped-tail smoke test (`make merge-smoke`).

Runs the full scheduler -> simulator -> fallback-kernel parity pipeline
on random skewed run sets whose sizes match the RMAT22 tail-edge
distribution recorded in PERF_NOTES.md (per-source-block edge counts: mean
1243, p50 283, p99 ~17k, max ~79k, cv ~2.6 — drawn here from a capped
lognormal fit), then checks:

1. reference walk vs vectorized planner: identical routing planes;
2. planner plan executed by the jax.numpy fallback kernel: per-dst
   sums BITWISE equal to the scatter oracle on integral values;
3. achieved stream inflation below the acceptance bound (<1.5x mean
   across levels on the heavy-tailed synthetic);
4. end-to-end LUX_GROUPED_TAIL=1 PageRank parity through
   TiledPullExecutor on a small R-MAT graph.

Emits one line of JSON with the achieved inflation so CI logs are
greppable. Scale with LUX_SMOKE_EDGES (default ~1.2M reals).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

INFLATION_BOUND = 1.5


def heavy_tail_sizes(rng, nsb):
    """Per-source-block tail-edge counts matching PERF_NOTES.md's RMAT22
    stats (lognormal body, capped at the observed max)."""
    import numpy as np

    return np.minimum(
        rng.lognormal(6.4, 1.35, size=nsb).astype(np.int64) + 1, 79237)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    import jax.numpy as jnp
    import numpy as np

    from lux_tpu.ops import merge_tail_plan as mtp
    from lux_tpu.ops.merge_tail_kernel import (
        DeviceGroupedTail,
        grouped_tail_sums,
    )
    from lux_tpu.ops.merge_tail_ref import BLOCK, schedule_grouped

    from lux_tpu.utils import flags

    target_edges = flags.get_int("LUX_SMOKE_EDGES")
    rng = np.random.default_rng(0)

    # -- 1. scheduler vs planner on small random skewed run sets --------
    for seed in range(4):
        r2 = np.random.default_rng(seed)
        sizes = heavy_tail_sizes(r2, 6) // 64 + 1   # miniature skew
        runs = [np.sort(r2.integers(0, 200, size=s)) for s in sizes]
        ref_levels, _, _ = schedule_grouped(runs)
        d = np.concatenate([
            np.stack([run, np.full(len(run), i)], axis=1)
            for i, run in enumerate(runs)])
        d = d[np.lexsort((d[:, 1], d[:, 0]))]
        leaf = d[:, 1]
        pos = np.zeros(len(leaf), np.int64)
        for i in range(len(runs)):
            m = leaf == i
            pos[m] = np.arange(m.sum())
        levels, _, _, _ = mtp.plan_merge_network(
            d[:, 0], leaf, pos // BLOCK + np.cumsum(
                np.concatenate([[0], [(len(r) + BLOCK - 1) // BLOCK
                                      for r in runs[:-1]]]))[leaf],
            pos % BLOCK, len(runs))
        for lv, rlv in zip(levels, ref_levels):
            for key in ("arow", "brow", "codes", "nvalid", "mode"):
                if not np.array_equal(lv[key], rlv[key]):
                    print(f"FAIL: planner/reference drift seed={seed} "
                          f"key={key}")
                    return 1
    print("scheduler == planner on skewed run sets")

    # -- 2+3. heavy-tailed synthetic at scale: parity + inflation -------
    nsb = max(64, target_edges // 1243)
    sizes = heavy_tail_sizes(rng, nsb)
    m = int(sizes.sum())
    sb = np.repeat(np.arange(nsb), sizes)
    nv = 1 << 17
    dst = np.sort(rng.integers(0, nv, size=m))
    sb = sb[np.lexsort((sb, dst))]
    lane = rng.integers(0, BLOCK, size=m)
    row_ptr = np.searchsorted(dst, np.arange(nv + 1))

    t0 = time.perf_counter()
    plan = mtp.plan_grouped_tail(sb, lane, row_ptr)
    plan_secs = time.perf_counter() - t0

    gt = DeviceGroupedTail.build(plan)
    x2d = rng.integers(-30, 30, size=(nsb, BLOCK)).astype(np.float32)
    got = np.asarray(jax.jit(grouped_tail_sums)(jnp.asarray(x2d), gt))
    want = np.zeros(nv, np.float64)
    np.add.at(want, dst, x2d[sb, lane].astype(np.float64))
    if not np.array_equal(got, want.astype(np.float32)):
        print("FAIL: fallback-kernel sums differ from oracle")
        return 1
    print(f"fallback kernel bitwise parity on {m} reals")

    inflation = plan.stats["mean_inflation"]
    if inflation >= INFLATION_BOUND:
        print(f"FAIL: mean inflation {inflation:.3f} >= {INFLATION_BOUND}")
        return 1

    # -- 4. end-to-end executor parity ----------------------------------
    from lux_tpu.engine.tiled import TiledPullExecutor
    from lux_tpu.graph.generate import rmat
    from lux_tpu.models.pagerank import PageRank

    g = rmat(int(os.environ.get("LUX_SMOKE_SCALE", "11")), 12, seed=1)
    ex0 = TiledPullExecutor(g, PageRank(), chunk_strips=16, chunk_tail=64)
    os.environ["LUX_GROUPED_TAIL"] = "1"
    try:
        ex1 = TiledPullExecutor(
            g, PageRank(), chunk_strips=16, chunk_tail=64)
    finally:
        del os.environ["LUX_GROUPED_TAIL"]
    v0 = np.asarray(ex0.run(6))
    v1 = np.asarray(ex1.run(6))
    if not np.allclose(v0, v1, rtol=1e-5, atol=1e-8):
        print(f"FAIL: pagerank drift {np.abs(v0 - v1).max():.3e}")
        return 1
    print("LUX_GROUPED_TAIL=1 pagerank parity OK")

    print(json.dumps({
        "merge_smoke": "ok",
        "edges": m,
        "levels": plan.n_levels,
        "mean_inflation": round(inflation, 4),
        "max_level_inflation": round(
            plan.stats["max_level_inflation"], 4),
        "copy_rows": int(plan.stats["copy_rows"]),
        "merge_rows": int(plan.stats["merge_rows"]),
        "plan_seconds": round(plan_secs, 2),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
