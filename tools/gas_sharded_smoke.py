#!/usr/bin/env python3
"""Sharded GAS serving smoke test (`make gas-sharded-smoke`).

End-to-end acceptance run for the direction-adaptive sharded GAS
engine (ISSUE 17), on a 2x4 virtual CPU mesh with
``LUX_EXCHANGE=frontier`` — the frontier-aware compact exchange:

1. start one warm sharded session over HTTP; every served app now
   builds its mesh engine (the per-chip GAS fallback is gone — any
   drop to a single-device build is counted and fails this smoke);
2. oracle-check every registry program: bfs (depth + parent), sssp,
   sssp_delta, components, labelprop, kcore at two k values, pagerank
   (allclose: float sum order), plus colfilter engine-level (not
   servable over HTTP: it needs a bipartite ratings graph) — bitwise
   where integral;
3. assert the single-lane adaptive BFS reports >= 1 mid-run
   push<->pull direction switch (scale >= 9) and concurrent BFS roots
   batch through the sharded multi-source engine;
4. assert the mesh-fallback surface is clean: /statusz ``fallbacks``
   empty, no warning, ``lux_serve_mesh_fallback_total`` at zero;
5. assert gas pool keys carry the mesh shape + exchange mode and the
   RecompileSentinel saw zero serve-phase recompiles (direction
   switches and frontier<->compact downgrades share one executable);
6. report the frontier-vs-compact per-iteration exchange-byte budget
   from the live plan (the PERF_NOTES.md evidence).

Emits a ``gas_sharded_smoke.v1`` JSON line on success. Scale with
LUX_SMOKE_SCALE (default 10).
"""

from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import urllib.request

MESH = "2x4"
PARTS = 8


def post(base, payload, timeout=300):
    req = urllib.request.Request(
        base + "/query", json.dumps(payload).encode(),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.loads(r.read())


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # Engines trace the exchange mode at build time: set it before the
    # session warms anything.
    os.environ["LUX_EXCHANGE"] = "frontier"
    from lux_tpu.utils.platform import virtual_cpu_flags

    os.environ["XLA_FLAGS"] = virtual_cpu_flags(PARTS)
    import jax

    from lux_tpu.utils import flags

    from lux_tpu.engine.gas import AdaptiveExecutor, as_gas
    from lux_tpu.engine.gas_sharded import ShardedAdaptiveExecutor
    from lux_tpu.graph import generate
    from lux_tpu.models import get_program
    from lux_tpu.models.bfs import reference_bfs
    from lux_tpu.models.components import reference_components
    from lux_tpu.models.kcore import reference_kcore
    from lux_tpu.models.labelprop import reference_labelprop
    from lux_tpu.models.pagerank import reference_pagerank
    from lux_tpu.models.sssp import reference_sssp
    from lux_tpu.models.sssp_delta import reference_sssp_delta
    from lux_tpu.obs import metrics
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.serve.http import serve_in_thread

    scale = flags.get_int("LUX_SMOKE_SCALE")
    g = generate.undirected(generate.rmat(scale, 8, seed=3, weighted=True))

    session = Session(g, ServeConfig(max_batch=4, window_s=0.05,
                                     max_queue=256, pagerank_iters=5,
                                     mesh=MESH))
    server, _ = serve_in_thread(session, port=0)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    assert session.meshspec.num_parts == PARTS, session.meshspec
    apps = set(session.APPS)
    assert {"bfs", "sssp", "sssp_delta", "components", "pagerank",
            "labelprop", "kcore"} <= apps, apps
    print(f"serving rmat scale={scale} (nv={g.nv} ne={g.ne}) on a "
          f"{MESH} mesh at {base}, LUX_EXCHANGE=frontier, "
          f"apps={sorted(apps)}")

    # -- single-lane adaptive BFS: the direction-switch acceptance -------
    bfs1 = post(base, {"app": "bfs", "start": 1, "full": True})
    depth, parent = reference_bfs(g, 1)
    np.testing.assert_array_equal(
        np.asarray(bfs1["values"], np.uint32), depth)
    np.testing.assert_array_equal(
        np.asarray(bfs1["parent"], np.int64), parent)
    assert bfs1["direction_push"] + bfs1["direction_pull"] == bfs1["iters"]
    if scale >= 9:
        assert bfs1["direction_switches"] >= 1, (
            f"adaptive sharded BFS never switched direction: "
            f"{bfs1['iters']} iters, push={bfs1['direction_push']} "
            f"pull={bfs1['direction_pull']}"
        )
    print(f"bfs[start=1] on the mesh: {bfs1['iters']} iters, "
          f"push={bfs1['direction_push']} pull={bfs1['direction_pull']} "
          f"switches={bfs1['direction_switches']}, depth+parent == oracle")

    # -- concurrent BFS roots: the sharded multi-source batch ------------
    roots = [2, 3, 4, 5]
    with ThreadPoolExecutor(max_workers=len(roots)) as tp:
        outs = [f.result() for f in
                [tp.submit(post, base, {"app": "bfs", "start": r,
                                        "full": True}) for r in roots]]
    for r, out in zip(roots, outs):
        d, p = reference_bfs(g, r)
        np.testing.assert_array_equal(np.asarray(out["values"],
                                                 np.uint32), d)
        np.testing.assert_array_equal(np.asarray(out["parent"],
                                                 np.int64), p)
    print(f"bfs x{len(roots)} concurrent roots: sharded lanes bitwise "
          "== per-root oracle")

    # -- the rest of the registry over HTTP ------------------------------
    sd = post(base, {"app": "sssp_delta", "start": 0, "full": True})
    np.testing.assert_array_equal(
        np.asarray(sd["values"], np.float32), reference_sssp_delta(g, 0))
    ss = post(base, {"app": "sssp", "start": 1, "full": True})
    np.testing.assert_array_equal(
        np.asarray(ss["values"], np.uint32), reference_sssp(g, 1))
    cc = post(base, {"app": "components", "full": True})
    np.testing.assert_array_equal(
        np.asarray(cc["values"], np.uint32), reference_components(g))
    lp = post(base, {"app": "labelprop", "full": True})
    np.testing.assert_array_equal(
        np.asarray(lp["values"], np.uint32), reference_labelprop(g))
    kc_sizes = {}
    for k in (2, 3):
        kc = post(base, {"app": "kcore", "k": k, "full": True})
        np.testing.assert_array_equal(
            np.asarray(kc["values"], np.uint32), reference_kcore(g, k))
        kc_sizes[k] = kc["core_size"]
    pr = post(base, {"app": "pagerank", "full": True})
    assert np.allclose(pr["values"], reference_pagerank(g, 5),
                       rtol=2e-5), "pagerank diverged"
    print(f"sssp + sssp_delta + components + labelprop + "
          f"kcore[k=2,3] bitwise == oracles; pagerank allclose; "
          f"kcore core sizes {kc_sizes}")

    # -- colfilter: engine-level (needs a bipartite ratings graph, so
    # it is not servable over HTTP; the mesh engine still must match
    # the single-device executor bitwise) --------------------------------
    ex = ShardedAdaptiveExecutor(g, get_program("colfilter"),
                                 num_parts=PARTS)
    st, _ = ex.run(max_iters=4)
    ref = AdaptiveExecutor(g, as_gas(get_program("colfilter")))
    rst, _ = ref.run(max_iters=4)
    np.testing.assert_array_equal(
        ex.gather_values(st), np.asarray(jax.device_get(rst.values)))
    print("colfilter engine-level: mesh bitwise == single-device "
          "(frontier-less: exchange honestly downgraded to "
          f"{ex.exchange_mode})")

    # -- mesh-fallback surface is clean ----------------------------------
    stats = get(base, "/stats")
    mesh = stats["mesh"]
    assert mesh["fallbacks"] == {}, mesh["fallbacks"]
    assert "warning" not in mesh, mesh
    fb = sum(m["value"] for m in metrics.snapshot()
             if m["name"] == "lux_serve_mesh_fallback_total")
    assert fb == 0, f"mesh fallback counter nonzero: {fb}"
    print("mesh fallbacks: none (statusz clean, "
          "lux_serve_mesh_fallback_total == 0)")

    # -- pool discipline: mesh-keyed gas engines, zero recompiles --------
    gas_keys = [k for k in session.pool.keys()
                if str(k[0]).startswith("gas")]
    assert gas_keys, "no sharded gas engines in the pool"
    assert all(k[-1] == (2, 4) for k in gas_keys), gas_keys
    assert all("frontier" in k for k in gas_keys), gas_keys
    recompiles = stats["pool"]["recompiles"]
    assert recompiles == 0, (
        f"RecompileSentinel saw {recompiles} XLA compile(s) in the "
        "post-warmup query phase (direction switches and frontier "
        "downgrades must share one executable)")
    session.pool.sentinel.assert_zero_recompiles()
    print(f"pool: {len(gas_keys)} gas engines keyed by mesh+exchange "
          f"mode, sentinel recompiles {recompiles}")

    # -- frontier-vs-compact exchange-byte budget (PERF evidence) --------
    bfs_ex = session._gas_single("bfs")
    assert bfs_ex.exchange_mode == "frontier"
    fe = bfs_ex.frontier_evidence()
    compact_bytes = bfs_ex.exchange_bytes_per_iter()
    frontier_bytes = fe["frontier_bytes_per_iter"]
    reduction = compact_bytes / max(1, frontier_bytes)
    assert frontier_bytes < compact_bytes, (fe, compact_bytes)
    ebytes = session.mesh_exchange_bytes()
    for key in ("gas_bfs", "gas_sssp_delta", "gas_labelprop",
                "gas_kcore"):
        assert key in ebytes and ebytes[key] > 0, (key, ebytes)
    print(f"exchange budget/iter: compact {compact_bytes} B -> frontier "
          f"{frontier_bytes} B ({reduction:.1f}x smaller admitted send, "
          f"capacity {fe['frontier_capacity']} rows/pair)")

    server.shutdown()
    session.close()

    print(json.dumps({
        "schema": "gas_sharded_smoke.v1",
        "scale": scale,
        "nv": int(g.nv),
        "ne": int(g.ne),
        "mesh": MESH,
        "exchange_mode": "frontier",
        "apps": sorted(apps) + ["colfilter (engine-level)"],
        "bfs": {
            "iters": bfs1["iters"],
            "direction_push": bfs1["direction_push"],
            "direction_pull": bfs1["direction_pull"],
            "direction_switches": bfs1["direction_switches"],
        },
        "kcore_sizes": {str(k): v for k, v in kc_sizes.items()},
        "mesh_fallbacks": 0,
        "recompiles": recompiles,
        "exchange_bytes_per_iter": {
            "compact": int(compact_bytes),
            "frontier": int(frontier_bytes),
            "reduction": round(reduction, 2),
        },
    }))
    print("gas-sharded-smoke PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
