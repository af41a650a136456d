#!/usr/bin/env python3
"""Dynamic-graph smoke test (`make snapshot-smoke`).

End-to-end acceptance run for the snapshot hot-swap subsystem (ISSUE 7):

1. generate a tiny graph, start the HTTP server, warm version 0;
2. seed SSSP + components traffic; every response carries
   ``X-Lux-Snapshot: 0``;
3. build a ~1% edit batch (half inserts, half deletes);
4. POST /snapshot while a concurrent SSSP burst is in flight — ZERO
   failed queries across the swap (the FIFO drain barrier contract);
5. serving flips to version 1 with a new fingerprint; no version-0
   cache keys survive; version-0 engines are retired;
6. post-swap SSSP answers are bit-identical to the host oracle on the
   merged graph;
7. the incrementally refreshed components entry is bit-identical to a
   fresh from-scratch executor on the merged graph, served as a cache
   hit;
8. zero recompiles outside expect windows across the whole run (pool
   sentinel + /stats counters);
9. one trace-id covers serve.snapshot_swap -> snapshot.apply ->
   serve.snapshot_warm (+ the incremental refresh when it ran).

Prints a ``snapshot_smoke.v1`` JSON document on the last line.
Scale with LUX_SMOKE_SCALE (default 10).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


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


def async_trace_chains(trace_path):
    """trace-id -> set of span names, from the async b/e events."""
    chains = {}
    with open(trace_path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            ev = json.loads(line)
            if ev.get("ph") in ("b", "e"):
                chains.setdefault(ev["id"], set()).add(ev["name"])
    return chains


def main() -> int:
    from lux_tpu.utils import flags

    scale = flags.get_int("LUX_SMOKE_SCALE")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from lux_tpu import obs
    from lux_tpu.engine.push import PushExecutor
    from lux_tpu.graph import DeltaGraph, EdgeEdits, generate
    from lux_tpu.models.components import ConnectedComponents
    from lux_tpu.models.sssp import reference_sssp
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.serve.http import serve_in_thread

    g = generate.rmat(scale, 8, seed=3)
    with tempfile.TemporaryDirectory() as td:
        trace_path = os.path.join(td, "trace.jsonl")
        os.environ["LUX_TRACE"] = trace_path
        obs.reconfigure()

        cfg = ServeConfig(max_batch=4, window_s=0.05, max_queue=256,
                          pagerank_iters=3)
        session = Session(g, cfg)
        server, _ = serve_in_thread(session, port=0)
        base = f"http://127.0.0.1:{server.server_address[1]}"

        # -- 1+2: seed traffic on version 0 ----------------------------
        info, hdr = get(base, "/snapshot")
        assert info["version"] == 0 and hdr["X-Lux-Snapshot"] == "0", info
        fp0 = info["fingerprint"]
        seed_roots = [1, 5, 9]
        for r in seed_roots:
            out, hdr = post(base, "/query", {"app": "sssp", "start": r})
            assert hdr["X-Lux-Snapshot"] == "0", hdr
        post(base, "/query", {"app": "components"})
        print(f"v0 serving: nv={info['nv']} ne={info['ne']} "
              f"fp={fp0[:12]} seeded {len(seed_roots)} sssp roots + "
              "components (X-Lux-Snapshot: 0)")

        # -- 3: ~1% edit batch -----------------------------------------
        rng = np.random.default_rng(17)
        n_edit = max(2, g.ne // 100)
        ins = [[int(rng.integers(g.nv)), int(rng.integers(g.nv))]
               for _ in range(n_edit // 2)]
        dels = [[int(g.col_src[e]), int(g.col_dst[e])]
                for e in rng.choice(g.ne, size=n_edit - n_edit // 2,
                                    replace=False)]
        edits = EdgeEdits.from_lists(
            insert=[tuple(p) for p in ins],
            delete=[tuple(p) for p in dels])
        new_g = DeltaGraph.fresh(g).stack(edits).merged()

        # -- 4: swap under concurrent in-flight traffic ----------------
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
            summary, shdr = swap_fut.result()
            burst = [f.result() for f in futs]
        assert not errors, f"queries failed during swap: {errors}"
        assert summary["version"] == 1 and shdr["X-Lux-Snapshot"] == "1", (
            summary)
        # Every answer is correct for the version it reports.
        for r, ver, out in burst:
            want = reference_sssp(g if ver == 0 else new_g, r)
            np.testing.assert_array_equal(
                np.asarray(out["values"], np.uint32), want)
        n_v0 = sum(1 for _, v, _ in burst if v == 0)
        print(f"hot-swap v0 -> v1 in {summary['swap_s']:.2f}s "
              f"(warm {summary['warm_s']:.2f}s): {len(burst)} in-flight "
              f"queries, 0 failed ({n_v0} answered by v0, "
              f"{len(burst) - n_v0} by v1, each correct for its version)")

        # -- 5: serving state flipped cleanly --------------------------
        info, hdr = get(base, "/snapshot")
        assert info["version"] == 1 and hdr["X-Lux-Snapshot"] == "1"
        assert info["fingerprint"] == summary["fingerprint"] != fp0
        assert info["ne"] == new_g.ne, (info["ne"], new_g.ne)
        stale = [k for k in session.cache.keys()
                 if isinstance(k, tuple) and k and k[0] == fp0]
        assert not stale, f"version-0 cache keys survived: {stale}"
        assert summary["retired"] > 0 and summary["evicted"] > 0, summary
        print(f"v1 serving: fp={info['fingerprint'][:12]} "
              f"evicted {summary['evicted']} cache entries, retired "
              f"{summary['retired']} engines, no v0 keys remain")

        # -- 6: post-swap SSSP bitwise vs oracle on merged graph -------
        for r in seed_roots:
            out, _ = post(base, "/query",
                          {"app": "sssp", "start": r, "full": True})
            np.testing.assert_array_equal(
                np.asarray(out["values"], np.uint32),
                reference_sssp(new_g, r))
        print(f"post-swap sssp: {len(seed_roots)} roots bit-identical "
              "to the host oracle on the merged graph")

        # -- 7: incremental refresh correctness + cache hit ------------
        refreshed = summary["refreshed"]
        assert refreshed and refreshed["components"] == 1, refreshed
        # At least the seeded roots refresh; burst queries answered by v0
        # before the flip may have cached more (all refresh together).
        assert refreshed["sssp"] >= len(seed_roots), refreshed
        hits_before = session.cache.stats()["hits"]
        cc = session.query("components", timeout=300)
        assert session.cache.stats()["hits"] == hits_before + 1, (
            "refreshed components entry was not served as a cache hit")
        assert cc.get("incremental") is True, sorted(cc)
        full_state, _ = PushExecutor(new_g, ConnectedComponents()).run()
        np.testing.assert_array_equal(cc["values"],
                                      np.asarray(full_state.values))
        print(f"incremental refresh: components + {refreshed['sssp']} "
              f"sssp roots warm-started "
              f"(touched_frac={refreshed['touched_frac']:.3f}); "
              "components bit-identical to a fresh executor, served "
              "from cache")

        # -- 8: zero recompiles across the whole run -------------------
        stats, _ = get(base, "/stats")
        recompiles = stats["pool"]["recompiles"]
        assert recompiles == 0, (
            f"RecompileSentinel saw {recompiles} compile(s) outside "
            "expect windows across the swap")
        session.pool.sentinel.assert_zero_recompiles()
        print(f"sentinel: 0 recompiles outside expect windows "
              f"({stats['pool']['engines']} live engines, "
              f"{stats['pool']['retired']} retired)")

        # -- 9: one trace-id covers the whole swap ---------------------
        chains = async_trace_chains(trace_path)
        want = {"serve.snapshot_swap", "snapshot.apply",
                "serve.snapshot_warm"}
        full = {t: n for t, n in chains.items() if want <= n}
        assert full, (
            f"no single trace-id covers {sorted(want)}; chains: "
            f"{ {t: sorted(n) for t, n in chains.items()} }")
        tid, names = next(iter(full.items()))
        print(f"spans: trace {tid} covers {sorted(names)}")

        server.shutdown()
        session.close()

        doc = {
            "schema": "snapshot_smoke.v1",
            "graph": {"scale": scale, "nv": g.nv, "ne": g.ne},
            "edits": {"inserts": len(ins), "deletes": len(dels),
                      "frac": round(n_edit / g.ne, 4)},
            "swap": {"old_version": summary["old_version"],
                     "version": summary["version"],
                     "swap_s": summary["swap_s"],
                     "warm_s": summary["warm_s"],
                     "evicted": summary["evicted"],
                     "retired": summary["retired"]},
            "in_flight": {"queries": len(burst), "failed": 0,
                          "answered_by_v0": n_v0},
            "incremental": refreshed,
            "recompiles": recompiles,
            "trace_spans": sorted(names),
        }
    print("snapshot-smoke PASS (hot-swap, drain barrier, incremental "
          "refresh, zero recompiles)")
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
