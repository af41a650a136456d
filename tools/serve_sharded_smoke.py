#!/usr/bin/env python3
"""Multi-chip serving smoke test (`make serve-sharded-smoke`).

End-to-end acceptance run for mesh-keyed sharded serving (ISSUE 10), on
a virtual 8-way CPU mesh (XLA host devices — the same trick the RMAT27
tooling uses, so this runs in CI with no TPU):

1. generate a graph, start one session on a 2x4 serving mesh behind the
   HTTP server, and a single-chip reference session in-process;
2. warm the sharded engines, then prove parity: SSSP and components
   bit-identical to the single-chip session AND the host oracle;
   pagerank allclose (float sum order differs across shard boundaries);
3. sustain a concurrent SSSP burst over the warm sharded engines and
   POST /snapshot mid-burst — ZERO failed queries while the swap
   atomically replaces the whole mesh of engines (retired >= the
   engines the burst warmed) and evicts the old partition plan;
4. post-swap answers are bit-identical to the oracle on the merged
   graph, still from sharded engines (pool keys carry the mesh shape);
5. zero recompiles outside expect windows across the entire run — the
   RecompileSentinel proves the warm sharded path never re-traces;
6. /statusz reports the serving mesh (shape, per-mesh pool entries,
   plan-cache stats).

Prints a ``serve_sharded_smoke.v1`` JSON document on the last line.
Scale with LUX_SMOKE_SCALE (default 10).
"""

from __future__ import annotations

import json
import os
import sys
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MESH = "2x4"
PARTS = 8


def post(base, path, payload, timeout=300):
    req = urllib.request.Request(
        base + path, json.dumps(payload).encode(),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read()), dict(r.headers)


def get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.loads(r.read()), dict(r.headers)


def main() -> int:
    # The virtual devices must exist before the first jax import touches
    # the backend; serve/mesh.py would do this too, but doing it here
    # keeps the whole process consistent (both sessions share devices).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lux_tpu.utils.platform import virtual_cpu_flags

    os.environ["XLA_FLAGS"] = virtual_cpu_flags(PARTS)

    from lux_tpu.utils import flags

    from lux_tpu.graph import DeltaGraph, EdgeEdits, generate
    from lux_tpu.models.sssp import reference_sssp
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.serve.http import serve_in_thread

    scale = flags.get_int("LUX_SMOKE_SCALE")
    g = generate.rmat(scale, 8, seed=3)

    # -- 1: sharded session over HTTP, single-chip reference in-process -
    sharded = Session(g, ServeConfig(max_batch=4, window_s=0.05,
                                     max_queue=256, pagerank_iters=5,
                                     mesh=MESH))
    single = Session(g, ServeConfig(max_batch=4, window_s=0.05,
                                    pagerank_iters=5, mesh="1"))
    server, _ = serve_in_thread(sharded, port=0)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    assert sharded.meshspec.num_parts == PARTS, sharded.meshspec
    print(f"serving rmat scale={scale} (nv={g.nv} ne={g.ne}) on a "
          f"{MESH} virtual mesh ({PARTS} XLA host devices) at {base}")

    # -- 2: warm parity vs single-chip + host oracle --------------------
    roots = [1, 5, 9, 33]
    for r in roots:
        out, _ = post(base, "/query", {"app": "sssp", "start": r,
                                       "full": True})
        got = np.asarray(out["values"], np.uint32)
        np.testing.assert_array_equal(got, reference_sssp(g, r))
        np.testing.assert_array_equal(
            got, np.asarray(single.query("sssp", start=r,
                                         timeout=300)["values"]))
    cc, _ = post(base, "/query", {"app": "components", "full": True})
    np.testing.assert_array_equal(
        np.asarray(cc["values"]),
        np.asarray(single.query("components", timeout=300)["values"]))
    pr, _ = post(base, "/query", {"app": "pagerank", "full": True})
    pr1 = single.query("pagerank", timeout=300)
    assert np.allclose(pr["values"], pr1["values"],
                       rtol=1e-5, atol=1e-8), "pagerank diverged"
    print(f"parity: {len(roots)} sssp roots + components bit-identical "
          "to single-chip and the host oracle; pagerank allclose(1e-5)")

    # -- 3: hot-swap mid-burst over the warm sharded mesh ---------------
    rng = np.random.default_rng(17)
    n_edit = max(2, g.ne // 100)
    ins = [[int(rng.integers(g.nv)), int(rng.integers(g.nv))]
           for _ in range(n_edit // 2)]
    dels = [[int(g.col_src[e]), int(g.col_dst[e])]
            for e in rng.choice(g.ne, size=n_edit - n_edit // 2,
                                replace=False)]
    new_g = DeltaGraph.fresh(g).stack(EdgeEdits.from_lists(
        insert=[tuple(p) for p in ins],
        delete=[tuple(p) for p in dels])).merged()
    burst_roots = [int(r) for r in rng.integers(0, g.nv, size=24)]
    errors = []

    def one(r):
        try:
            out, h = post(base, "/query",
                          {"app": "sssp", "start": r, "full": True})
            return r, int(h["X-Lux-Snapshot"]), out
        except Exception as e:   # any failure fails the smoke
            errors.append((r, repr(e)))
            return None

    with ThreadPoolExecutor(max_workers=9) as tp:
        futs = [tp.submit(one, r) for r in burst_roots[:12]]
        swap_fut = tp.submit(post, base, "/snapshot",
                             {"insert": ins, "delete": dels})
        futs += [tp.submit(one, r) for r in burst_roots[12:]]
        summary, _ = swap_fut.result()
        burst = [f.result() for f in futs]
    assert not errors, f"queries failed during sharded swap: {errors}"
    # Every answer must be bit-identical to the oracle on the version
    # that computed it. The X-Lux-Snapshot header is written at
    # response time, so a query bound to v0 whose response is written
    # just after the flip reports 1 while (correctly) carrying v0's
    # values — tolerated as "straddled". The reverse (a v0 header over
    # v1 data) would mean an admitted query jumped snapshots: a bug.
    n_v0 = straddled = 0
    for r, ver, out in burst:
        got = np.asarray(out["values"], np.uint32)
        if np.array_equal(got, reference_sssp(g, r)):
            n_v0 += 1
            if ver != 0:
                straddled += 1
        else:
            assert ver == 1, (
                f"root {r}: v{ver}-headed answer is not v0's result")
            np.testing.assert_array_equal(got, reference_sssp(new_g, r))
    assert summary["retired"] >= 3, summary   # the whole warmed mesh
    assert summary["plans_evicted"] >= 1, summary
    print(f"hot-swap v0 -> v1 in {summary['swap_s']:.2f}s under load: "
          f"{len(burst)} in-flight queries, 0 failed ({n_v0} answered "
          f"by v0 [{straddled} straddling the flip], "
          f"{len(burst) - n_v0} by v1, each bit-identical to its "
          f"version's oracle); retired {summary['retired']} sharded "
          f"engines + {summary['plans_evicted']} partition plan(s)")

    # -- 4: post-swap parity on the merged graph ------------------------
    for r in roots:
        out, _ = post(base, "/query", {"app": "sssp", "start": r,
                                       "full": True})
        np.testing.assert_array_equal(
            np.asarray(out["values"], np.uint32),
            reference_sssp(new_g, r))
    print(f"post-swap: {len(roots)} roots bit-identical to the host "
          "oracle on the merged graph")

    # -- 4b: burst under the compacted exchange -------------------------
    # Flipping LUX_EXCHANGE mid-process must build NEW engines (pool
    # keys carry the mode) under expect windows, answer bit-identically,
    # and keep the zero-recompile contract.
    os.environ["LUX_EXCHANGE"] = "compact"
    try:
        with ThreadPoolExecutor(max_workers=4) as tp:
            futs = [tp.submit(one, r) for r in burst_roots[:8]]
            compact_burst = [f.result() for f in futs]
        assert not errors, f"queries failed under compact: {errors}"
        for r, _, out in compact_burst:
            np.testing.assert_array_equal(
                np.asarray(out["values"], np.uint32),
                reference_sssp(new_g, r))
    finally:
        del os.environ["LUX_EXCHANGE"]
    print(f"compact burst: {len(compact_burst)} LUX_EXCHANGE=compact "
          "queries on freshly-keyed engines, each bit-identical to the "
          "oracle")

    # -- 5+6: zero recompiles, mesh observability -----------------------
    stats, _ = get(base, "/stats")
    recompiles = stats["pool"]["recompiles"]
    assert recompiles == 0, (
        f"RecompileSentinel saw {recompiles} compile(s) outside expect "
        "windows on the warm sharded path")
    sharded.pool.sentinel.assert_zero_recompiles()
    statusz, _ = get(base, "/statusz")
    mesh = statusz["mesh"]
    assert mesh["shape"] == [2, 4] and mesh["num_parts"] == PARTS, mesh
    assert mesh["pool_entries"].get(MESH, 0) > 0, mesh
    ebytes = sharded.mesh_exchange_bytes()
    assert ebytes and all(v > 0 for v in ebytes.values()), ebytes
    print(f"sentinel: 0 recompiles outside expect windows; /statusz "
          f"mesh={mesh['spec']} pool_entries={mesh['pool_entries']} "
          f"plans={mesh['plans']['plans']}")

    server.shutdown()
    sharded.close()
    single.close()

    doc = {
        "schema": "serve_sharded_smoke.v1",
        "graph": {"scale": scale, "nv": g.nv, "ne": g.ne},
        "mesh": {"spec": MESH, "num_parts": PARTS,
                 "pool_entries": mesh["pool_entries"],
                 "exchange_bytes_per_iter": ebytes},
        "swap": {"version": summary["version"],
                 "swap_s": summary["swap_s"],
                 "retired": summary["retired"],
                 "plans_evicted": summary["plans_evicted"]},
        "in_flight": {"queries": len(burst), "failed": 0,
                      "answered_by_v0": n_v0},
        "compact_burst": {"queries": len(compact_burst), "failed": 0},
        "recompiles": recompiles,
    }
    print("serve-sharded-smoke PASS (mesh-keyed pool, bitwise parity, "
          "swap under load, zero recompiles)")
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
