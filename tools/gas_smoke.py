#!/usr/bin/env python3
"""GAS serving smoke test (`make gas-smoke`).

End-to-end acceptance run for the GAS subsystem (ISSUE 12):

1. generate a weighted undirected RMAT graph and start the HTTP server
   on an ephemeral port (every registry app's engines warmed before
   traffic — bfs/sssp_delta single + multi-lane, labelprop, kcore);
2. issue one single-lane adaptive BFS query and assert the response's
   per-iteration direction telemetry shows >= 1 mid-run push<->pull
   switch (scale >= 9; tiny graphs may legitimately never switch);
3. issue concurrent BFS root queries (multi-source batch), one
   sssp_delta root, labelprop, and kcore at two k values, all through
   the HTTP front end with ``full`` payloads;
4. validate every response against the host numpy oracles — BFS
   depth+parent, Dijkstra distances, label-propagation labels, k-core
   frozen degrees + alive mask — bitwise where integral;
5. assert the pool miss counter stayed flat across the query phase for
   warmed engines (the only allowed build is the non-default kcore k)
   and the RecompileSentinel saw zero serve-phase recompiles;
6. assert ``/statusz`` carries the ``gas`` direction-split block.

Emits a ``gas_smoke.v1`` JSON line on success. Scale with
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


def post(base, payload, timeout=180):
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
    from lux_tpu.utils import flags

    scale = flags.get_int("LUX_SMOKE_SCALE")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from lux_tpu.graph import generate
    from lux_tpu.models.bfs import reference_bfs
    from lux_tpu.models.kcore import reference_kcore
    from lux_tpu.models.labelprop import reference_labelprop
    from lux_tpu.models.sssp_delta import reference_sssp_delta
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.serve.http import serve_in_thread

    g = generate.undirected(generate.rmat(scale, 8, seed=3, weighted=True))
    cfg = ServeConfig(max_batch=4, window_s=0.5, max_queue=256)
    session = Session(g, cfg)
    server, _ = serve_in_thread(session, port=0)
    base = f"http://127.0.0.1:{server.server_address[1]}"

    health = get(base, "/healthz")
    assert health["ok"] and health["nv"] == g.nv, health
    apps = set(session.APPS)
    assert {"bfs", "sssp_delta", "labelprop", "kcore"} <= apps, apps
    print(f"server up: nv={health['nv']} ne={health['ne']} "
          f"engines={health['engines']} apps={sorted(apps)}")

    misses_before = get(base, "/stats")["pool"]["misses"]

    # -- single-lane adaptive BFS: the direction-switch acceptance -------
    bfs1 = post(base, {"app": "bfs", "start": 1, "full": True})
    depth, parent = reference_bfs(g, 1)
    np.testing.assert_array_equal(
        np.asarray(bfs1["values"], dtype=np.uint32), depth)
    np.testing.assert_array_equal(
        np.asarray(bfs1["parent"], dtype=np.int64), parent)
    assert bfs1["direction_push"] + bfs1["direction_pull"] == bfs1["iters"]
    if scale >= 9:
        assert bfs1["direction_switches"] >= 1, (
            f"adaptive BFS never switched direction: {bfs1['iters']} iters, "
            f"push={bfs1['direction_push']} pull={bfs1['direction_pull']}"
        )
    print(f"bfs[start=1]: {bfs1['iters']} iters, "
          f"push={bfs1['direction_push']} pull={bfs1['direction_pull']} "
          f"switches={bfs1['direction_switches']}, depth+parent == oracle")

    # -- concurrent BFS roots: multi-source GAS batch --------------------
    roots = [2, 3, 4, 5]
    with ThreadPoolExecutor(max_workers=len(roots)) as tp:
        futs = [tp.submit(post, base, {"app": "bfs", "start": r,
                                       "full": True}) for r in roots]
        outs = [f.result() for f in futs]
    for r, out in zip(roots, outs):
        d, p = reference_bfs(g, r)
        np.testing.assert_array_equal(
            np.asarray(out["values"], dtype=np.uint32), d)
        np.testing.assert_array_equal(
            np.asarray(out["parent"], dtype=np.int64), p)
    print(f"bfs x{len(roots)} concurrent roots: batched lanes bitwise == "
          "per-root oracle")

    # -- weighted delta-SSSP ---------------------------------------------
    sd = post(base, {"app": "sssp_delta", "start": 0, "full": True})
    np.testing.assert_array_equal(
        np.asarray(sd["values"], dtype=np.float32),
        reference_sssp_delta(g, 0))
    print(f"sssp_delta[start=0]: {sd['iters']} iters, bitwise == Dijkstra")

    # -- label propagation -----------------------------------------------
    lp = post(base, {"app": "labelprop", "full": True})
    np.testing.assert_array_equal(
        np.asarray(lp["values"], dtype=np.uint32), reference_labelprop(g))
    print(f"labelprop: {lp['iters']} iters, "
          f"{lp['num_communities']} communities, bitwise == oracle")

    # -- k-core at the warmed default k and one cold k -------------------
    kc_results = {}
    for k in (2, 3):
        kc = post(base, {"app": "kcore", "k": k, "full": True})
        ref = reference_kcore(g, k)
        np.testing.assert_array_equal(
            np.asarray(kc["values"], dtype=np.uint32), ref)
        np.testing.assert_array_equal(
            np.asarray(kc["alive"], dtype=np.uint8),
            (ref >= k).astype(np.uint8))
        kc_results[k] = kc["core_size"]
        print(f"kcore[k={k}]: core_size={kc['core_size']}, "
              "frozen degrees + alive mask bitwise == peeling oracle")

    # -- pool discipline: no builds beyond the declared cold k=3 engine --
    stats = get(base, "/stats")
    misses_after = stats["pool"]["misses"]
    assert misses_after <= misses_before + 1, (
        f"unexpected engine builds during the query phase: "
        f"{misses_before} -> {misses_after} (allowed: +1 for kcore k=3)"
    )
    recompiles = stats["pool"].get("recompiles", 0)
    assert recompiles == 0, (
        f"RecompileSentinel saw {recompiles} XLA compile(s) in the "
        "post-warmup query phase"
    )
    print(f"warm pool: {stats['pool']['engines']} engines, miss count "
          f"{misses_before} -> {misses_after} (cold kcore k=3 only), "
          f"sentinel recompiles {recompiles}")

    # -- /statusz direction-split block ----------------------------------
    sz = get(base, "/statusz")
    gas_block = sz.get("gas", {})
    assert "gas" in gas_block, sz
    rec = gas_block["gas"]
    assert rec["direction_push"] + rec["direction_pull"] \
        == rec["num_iters"], rec
    print(f"statusz gas block: {gas_block}")

    server.shutdown()
    session.close()

    print(json.dumps({
        "schema": "gas_smoke.v1",
        "scale": scale,
        "nv": int(g.nv),
        "ne": int(g.ne),
        "apps": sorted(apps),
        "bfs": {
            "iters": bfs1["iters"],
            "direction_push": bfs1["direction_push"],
            "direction_pull": bfs1["direction_pull"],
            "direction_switches": bfs1["direction_switches"],
        },
        "sssp_delta_iters": sd["iters"],
        "labelprop_communities": lp["num_communities"],
        "kcore_sizes": {str(k): v for k, v in kc_results.items()},
        "pool_misses_query_phase": misses_after - misses_before,
        "recompiles": recompiles,
    }))
    print("gas-smoke PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
