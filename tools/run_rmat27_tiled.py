#!/usr/bin/env python3
"""Consume the RMAT27 hybrid plan: run the sharded TILED executor at
the reference's headline scale (2^31 edges) on the virtual CPU mesh.

RMAT27_r03.json proved the flat sharded engine end-to-end; this run
proves the banded-planner output (PLAN27, 8.39M strips) actually FEEDS
an executor: ShardedTiledExecutor over P virtual devices with the
cached plan, ≥2 PageRank iterations, per-iteration wall time, the
analytic per-device collective bytes, and a sampled float64 parity
check (same degree-aware criterion as tools/run_rmat27.py). Wall
times measure 2 shared host cores, not scaling.

Usage: python tools/run_rmat27_tiled.py [--parts 8] [--ni 2]
"""
import argparse
import json
import os
import resource
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main():
    ap = argparse.ArgumentParser()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument("--file", default=os.path.join(
        repo, ".bench_cache", "rmat27_16.lux"))
    ap.add_argument("--plan", default=os.path.join(
        repo, ".bench_cache", "plan_rmat27_16_8x2_8192.luxplan"))
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--ni", type=int, default=2)
    ap.add_argument("--sample", type=int, default=2048)
    ap.add_argument("--out", default=os.path.join(
        repo, "RMAT27_TILED_r03.json"))
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.parts}"
    ).strip()
    sys.path.insert(0, repo)

    def log(msg):
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        print(f"# [{time.strftime('%H:%M:%S')} rss={rss:.1f}G] {msg}",
              file=sys.stderr, flush=True)

    import jax

    log(f"platform: {jax.devices()[0].platform}")

    import numpy as np

    from lux_tpu.engine.pull import hard_sync
    from lux_tpu.engine.tiled_sharded import ShardedTiledExecutor
    from lux_tpu.graph import read_lux_mmap
    from lux_tpu.models.pagerank import ALPHA, PageRank
    from lux_tpu.ops.tiled_spmv import load_plan
    from lux_tpu.parallel.mesh import make_mesh

    t0 = time.time()
    g = read_lux_mmap(args.file)
    log(f"mapped {args.file}: nv={g.nv} ne={g.ne} in {time.time()-t0:.0f}s")
    t0 = time.time()
    plan = load_plan(args.plan)
    log(f"plan loaded: {plan.num_strips} strips "
        f"({plan.strip_bytes/1e9:.2f} GB), coverage={plan.coverage:.1%} "
        f"in {time.time()-t0:.0f}s")

    t0 = time.time()
    ex = ShardedTiledExecutor(
        g, PageRank(), mesh=make_mesh(args.parts), plan=plan,
    )
    log(f"executor built in {time.time()-t0:.0f}s (max_nv={ex.max_nv})")

    rng = np.random.default_rng(27)
    in_deg = np.diff(g.row_ptr)
    hubs = np.argsort(in_deg)[-8:]
    sample = np.unique(np.concatenate([
        rng.integers(0, g.nv, args.sample), hubs,
    ])).astype(np.int64)
    deg64 = g.out_degrees.astype(np.float64)
    HUB_DEG = 4096
    low = in_deg[sample] <= HUB_DEG

    def expected_sampled(prev_full):
        prev64 = prev_full.astype(np.float64)
        exp = np.empty(sample.shape[0], dtype=np.float64)
        for i, v in enumerate(sample):
            s, e = int(g.row_ptr[v]), int(g.row_ptr[v + 1])
            srcs = np.asarray(g.col_src[s:e]).astype(np.int64)
            r = (1.0 - ALPHA) / g.nv + ALPHA * prev64[srcs].sum()
            exp[i] = r if deg64[v] == 0 else r / deg64[v]
        return exp

    vals = ex.init_values()
    prev_full = ex.gather_values(vals)
    log("init + gather done")

    # First step isolated: it folds shard_map/jit compile time in
    # (reported separately, like tools/run_rmat27.py's steady mean).
    t0 = time.time()
    vals = ex.step(vals)
    hard_sync(vals)
    compile_step = time.time() - t0
    log(f"first step (compile + run) in {compile_step:.0f}s")
    new_full = ex.gather_values(vals)
    exp = expected_sampled(prev_full)
    got = new_full[sample].astype(np.float64)
    abs_err = np.abs(got - exp)
    rel = abs_err / np.maximum(np.abs(exp), 1e-300)
    parity = [{"iter": 1,
               "low_deg_max_rel": float(rel[low].max()),
               "hub_max_abs": float(abs_err[~low].max())
               if (~low).any() else 0.0}]
    log(f"iter 1 parity low-rel={parity[0]['low_deg_max_rel']:.3e} "
        f"hub-abs={parity[0]['hub_max_abs']:.3e}")
    prev_full = new_full

    iter_times = []
    for it in range(2, args.ni + 1):
        t0 = time.time()
        vals = ex.step(vals)
        hard_sync(vals)
        dt = time.time() - t0
        iter_times.append(dt)
        new_full = ex.gather_values(vals)
        exp = expected_sampled(prev_full)
        got = new_full[sample].astype(np.float64)
        abs_err = np.abs(got - exp)
        rel = abs_err / np.maximum(np.abs(exp), 1e-300)
        rec = {"iter": it,
               "low_deg_max_rel": float(rel[low].max()),
               "hub_max_abs": float(abs_err[~low].max())
               if (~low).any() else 0.0}
        parity.append(rec)
        log(f"iter {it}: {dt:.0f}s parity low-rel="
            f"{rec['low_deg_max_rel']:.3e} hub-abs={rec['hub_max_abs']:.3e}")
        prev_full = new_full

    ok = all(
        p["low_deg_max_rel"] < 1e-3 and p["hub_max_abs"] < 1e-8
        for p in parity
    )
    P = args.parts
    ag = (P - 1) * ex.max_nv * 4
    out = {
        "metric": "pagerank_rmat27_tiled_sharded_cpu_mesh",
        "nv": g.nv, "ne": g.ne, "parts": P, "iters": args.ni,
        "plan_strips": plan.num_strips,
        "plan_strip_gb": round(plan.strip_bytes / 1e9, 2),
        "plan_coverage": round(plan.coverage, 3),
        "first_step_incl_compile_sec": round(compile_step, 1),
        "steady_sec_per_iter": [round(x, 1) for x in iter_times],
        "all_gather_bytes_per_dev": ag,
        "reduce_scatter_bytes_per_dev": ag,
        "sampled_vertices": int(sample.shape[0]),
        "parity": parity,
        "parity_ok": ok,
        "peak_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 1),
        "note": ("the round-2 RMAT27 hybrid plan (banded streaming "
                 "planner) consumed by the sharded tiled executor; P "
                 "virtual CPU devices share 2 host cores — wall time is "
                 "capability evidence, not throughput"),
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    log(f"wrote {args.out} parity_ok={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
