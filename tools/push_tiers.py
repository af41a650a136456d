#!/usr/bin/env python3
"""Time every branch of the push engine's per-iteration ``lax.switch``
on a benchmark graph, from several roots.

    python3 tools/push_tiers.py --config graph500-22 --seed <n> \\
        --roots 6 [--reps 3] [--scale S] [--out DIR]

Builds the graph of ``perfbench/configs/<config>.json`` from ``--seed``
and the single-root SSSP ``PushExecutor`` the serving session builds.
For each root (the Graph500 root rule, drawn from the seed as the
serving cell draws them) it steps the fixpoint with
``PushExecutor.phase_step`` and, on every iteration's state, times the
dense branch and every tier of the full ladder (``_make_tiers`` of the
default budgets, before any pruning): as separate load / comp / update
dispatches, and fused as one jitted iteration (median of ``--reps``).
Then it runs each root to fixpoint through ``run()``.

Prints one JSON line per iteration and a summary (also written to
``DIR/push_tiers-<config>-<seed>.json``, default ``chiprun_out``):
median milliseconds per branch and phase, the cost model's fit
(sparse ``s*nv + a*E``, dense ``c*ne``), and per root the iterations,
sparse iterations and seconds of ``run()``. ``--scale`` overrides the
configuration's scale, for a rehearsal on a small graph.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timed(fn, *args):
    """(seconds, result) of one call, synced."""
    import jax

    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return time.perf_counter() - t, out


def branch_jits(ex, ladder):
    """{label: (load, comp, update, fused)} for the dense branch (the
    executor's own phase jits) and each (Q, E) of ``ladder``."""
    import jax

    from lux_tpu.engine.push import _tier_label

    j = ex._phase_jits()
    out = {"dense": (
        lambda st, dg: j["d_load"](st, dg),
        lambda st, ld, dg: j["d_comp"](*ld, dg),
        lambda st, acc: j["update"](st, acc),
        jax.jit(ex._dense_iter),
    )}
    s_update = jax.jit(ex._s_update)
    for i, (Q, E) in enumerate(ladder):
        load = jax.jit(lambda st, dg, Q=Q: ex._s_load(st, dg, Q))
        comp = jax.jit(lambda st, q, s, d, dg, E=E: ex._s_comp(
            st, q, s, d, dg, E))
        out[_tier_label(ladder, i + 1)] = (
            load,
            lambda st, ld, dg, comp=comp: comp(st, *ld, dg),
            lambda st, cd: s_update(st, *cd),
            jax.jit(lambda st, dg, Q=Q, E=E: ex._sparse_iter(st, dg, Q, E)),
        )
    return out


def time_branch(fns, state, dg, reps: int) -> dict:
    load, comp, update, fused = fns
    t_load, ld = timed(load, state, dg)
    t_comp, acc = timed(comp, state, ld, dg)
    t_upd, _ = timed(update, state, acc)
    fused_s = [timed(fused, state, dg)[0] for _ in range(reps)]
    return {"load_ms": t_load * 1e3, "comp_ms": t_comp * 1e3,
            "update_ms": t_upd * 1e3,
            "fused_ms": statistics.median(fused_s) * 1e3}


def fit(rows, nv: int, ne: int) -> dict:
    """Least-squares ``T = s*nv + a*E`` over the tiers' fused medians,
    ``c = dense / ne``, and the ratios the ladder rule keeps."""
    import numpy as np

    dense = rows["dense"]["fused_ms"] / 1e3
    tiers = [(r["E"], r["fused_ms"] / 1e3) for k, r in rows.items()
             if k != "dense"]
    c = dense / ne
    out = {"c_ns_per_edge": c * 1e9}
    if len(tiers) >= 2:
        A = np.array([[nv, e] for e, _ in tiers], float)
        y = np.array([t for _, t in tiers])
        (s, a), *_ = np.linalg.lstsq(A, y, rcond=None)
        out.update({"s_ns_per_vertex": s * 1e9, "a_ns_per_slot": a * 1e9,
                    "s_over_c": s / c, "a_over_c": a / c,
                    "scan_ms": s * nv * 1e3})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="graph500-22")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--roots", type=int, default=6)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    from lux_tpu.engine.push import (PushExecutor, _make_tiers,
                                     _sparse_budgets)
    from lux_tpu.models.sssp import SSSP
    from perfbench.drivers import lux_graph
    from perfbench.generators import generate

    with open(os.path.join(ROOT, "perfbench", "configs",
                           f"{args.config}.json")) as f:
        config = json.load(f)
    if args.scale is not None:
        config["scale"] = args.scale
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", file=sys.stderr)

    t = time.perf_counter()
    host = generate(config, args.seed)
    g = lux_graph(host)
    ex = PushExecutor(g, SSSP())
    dg = ex._dg
    ladder = _make_tiers(*_sparse_budgets(g.nv, g.ne, 16, 8), g.nv, g.ne,
                         blocked_dense=False)
    fns = branch_jits(ex, ladder)
    print(f"built nv={g.nv} ne={g.ne} blocked_dense={ex.blocked_dense} "
          f"ladder={ladder} executor tiers={getattr(ex, 'tiers', [])} in "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr)

    rng = np.random.default_rng(args.seed)
    has_out = np.flatnonzero(host.out_degrees > 0)
    roots = has_out[rng.permutation(has_out.size)][:args.roots]

    t = time.perf_counter()
    st = ex.init_state(start=int(roots[0]))
    ex.warmup_phases(st)
    for fn in fns.values():
        time_branch(fn, st, dg, 1)
    ex.warmup(start=int(roots[0]))
    print(f"compiled in {time.perf_counter() - t:.1f} s", file=sys.stderr)

    out_deg = host.out_degrees
    per_branch = {k: [] for k in fns}
    iters = []
    for root in roots:
        state = ex.init_state(start=int(root))
        for it in itertools.count():
            front = np.asarray(jax.device_get(state.frontier))
            cnt = int(front.sum())
            if cnt == 0:
                break
            oe = int(out_deg[front].sum())
            row = {"root": int(root), "iter": it, "frontier": cnt,
                   "out_edges": oe, "branches": {}}
            for k, fn in fns.items():
                r = time_branch(fn, state, dg, args.reps)
                if k != "dense":
                    Q, E = ladder[list(fns).index(k) - 1]
                    r["adequate"] = cnt <= Q and oe <= E
                row["branches"][k] = r
                per_branch[k].append(r)
            state, _, times = ex.phase_step(state)
            row["taken"] = times["branch"]
            row["taken_ms"] = {p: times[p] * 1e3 for p in
                               ("loadTime", "compTime", "updateTime")}
            iters.append(row)
            print(json.dumps(row), flush=True)

    runs = []
    for root in roots:
        t = time.perf_counter()
        state, n = ex.run(start=int(root))
        jax.block_until_ready(state.values)
        runs.append({"root": int(root), "iters": n,
                     "sparse_iters": ex.sparse_iters,
                     "run_s": time.perf_counter() - t})

    table = {}
    for k, rs in per_branch.items():
        table[k] = {p: statistics.median(r[p] for r in rs) for p in
                    ("load_ms", "comp_ms", "update_ms", "fused_ms")}
        if k != "dense":
            Q, E = ladder[list(fns).index(k) - 1]
            table[k].update({"Q": Q, "E": E})
    summary = {"config": args.config, "seed": args.seed, "nv": g.nv,
               "ne": g.ne, "device": dev.device_kind,
               "blocked_dense": ex.blocked_dense, "ladder": ladder,
               "executor_tiers": getattr(ex, "tiers", []), "table": table,
               "fit": fit(table, g.nv, g.ne), "runs": runs,
               "iterations": iters}
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out,
                        f"push_tiers-{args.config}-{args.seed}.json")
    with open(path, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items()
                      if k != "iterations"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
