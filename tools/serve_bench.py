#!/usr/bin/env python3
"""Closed-loop load generator for the serving layer.

Spawns N worker threads, each issuing queries back-to-back (closed loop)
or paced to a per-worker QPS budget, against an in-process Session (the
default: measures engine+batcher latency without socket noise) or a
remote server via --url (measures the full HTTP path). Prints
p50/p95/p99 latency per app, throughput, and the achieved batch-size
histogram from the `obs` registry, and (with --json / --json-out) emits
a schema-versioned ``serve_bench.v1`` report — the evidence format
PERF_NOTES.md specifies for serving claims, checkable against a baseline via
tools/slo_check.py (`make serve-slo`).

With ``--swap-at T`` (in-process mode) a ~1% random edit batch is
applied mid-run via ``session.apply_edits`` — the report gains a
``snapshot`` block {version, swap_s, errors_during_swap} so SLO checks
can assert hot-swaps are latency- and error-neutral under load.

With ``--mesh PxQ`` (in-process mode) the session serves from sharded
engines on a P*Q-device mesh (virtual XLA host devices on CPU) and the
report gains a ``mesh`` block {spec, num_parts, plans,
exchange_bytes_per_iter} — the serving half of the PERF_NOTES.md multi-chip
evidence.

Examples:
  python tools/serve_bench.py --scale 12 --workers 16 --duration 10
  python tools/serve_bench.py --url http://127.0.0.1:8399 --workers 32
  python tools/serve_bench.py --swap-at 5 --duration 10 --json
  python tools/serve_bench.py --mesh 2x4 --swap-at 5 --json
  python tools/serve_bench.py --json-out /tmp/bench.json && \
      python tools/slo_check.py --input /tmp/bench.json --baseline slo.json
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def percentile(sorted_xs, q):
    if not sorted_xs:
        return 0.0
    i = min(int(q * len(sorted_xs)), len(sorted_xs) - 1)
    return sorted_xs[i]


class HttpClient:
    def __init__(self, url):
        self.url = url.rstrip("/")

    def query(self, payload, tenant=None):
        import urllib.request

        headers = {"Content-Type": "application/json"}
        if tenant:
            headers["X-Lux-Tenant"] = tenant
        req = urllib.request.Request(
            self.url + "/query", json.dumps(payload).encode(), headers,
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def costz(self):
        import urllib.request

        with urllib.request.urlopen(self.url + "/costz", timeout=10) as r:
            return json.loads(r.read())

    def batch_histogram(self):
        import urllib.request

        with urllib.request.urlopen(
            self.url + "/metrics.json", timeout=10
        ) as r:
            snap = json.loads(r.read())["metrics"]
        for m in snap:
            if m["name"] == "lux_serve_batch_size":
                return m
        return None

    def stats(self):
        import urllib.request

        with urllib.request.urlopen(self.url + "/stats", timeout=10) as r:
            return json.loads(r.read())


class LocalClient:
    def __init__(self, session):
        self.session = session

    def query(self, payload, tenant=None):
        payload = dict(payload)
        app = payload.pop("app")
        payload.pop("full", None)
        return self.session.query(app, tenant=tenant, **payload)

    def costz(self):
        return self.session.costz()

    def batch_histogram(self):
        from lux_tpu.obs import metrics

        for m in metrics.snapshot():
            if m["name"] == "lux_serve_batch_size":
                return m
        return None

    def stats(self):
        return self.session.stats()


def worker(client, mix, nv, stop_at, qps, lat, errs, seed,
           tenant=None, tlat=None):
    rng = random.Random(seed)
    interval = 1.0 / qps if qps else 0.0
    while time.monotonic() < stop_at:
        t_next = time.monotonic() + interval
        app = rng.choices([m[0] for m in mix], [m[1] for m in mix])[0]
        payload = {"app": app}
        if app == "sssp":
            payload["start"] = rng.randrange(nv)
        t0 = time.perf_counter()
        try:
            client.query(payload, tenant=tenant)
            dt = time.perf_counter() - t0
            lat.setdefault(app, []).append(dt)
            if tenant is not None and tlat is not None:
                tlat.setdefault(tenant, []).append(dt)
        except Exception as e:
            errs[type(e).__name__] = errs.get(type(e).__name__, 0) + 1
        if interval:
            time.sleep(max(0.0, t_next - time.monotonic()))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--url", help="benchmark a remote server instead of "
                   "an in-process session")
    p.add_argument("--file", help="serve this .lux graph (in-process mode)")
    p.add_argument("--scale", type=int, default=12,
                   help="generate an R-MAT graph of this scale "
                   "(in-process mode without --file)")
    p.add_argument("--workers", type=int, default=16,
                   help="concurrent closed-loop clients")
    p.add_argument("--qps", type=float, default=0.0,
                   help="per-worker request rate (0 = unpaced closed loop)")
    p.add_argument("--duration", type=float, default=10.0, help="seconds")
    p.add_argument("--max-batch", type=int, default=8, dest="max_batch")
    p.add_argument("--window-ms", type=float, default=3.0, dest="window_ms")
    p.add_argument("--mesh", default=None,
                   help="serving mesh spec for the in-process session "
                   "('8' or 'PxQ'); on CPU the mesh is virtual (XLA "
                   "host devices). Default: LUX_SERVE_MESH")
    p.add_argument("--tenants", default=None,
                   help="comma-separated tenant labels round-robined "
                   "over workers (X-Lux-Tenant per request); the report "
                   "gains per-tenant latency quantiles + /costz cost "
                   "aggregates")
    p.add_argument("--sssp-weight", type=float, default=0.8,
                   dest="sssp_weight",
                   help="fraction of traffic that is SSSP root queries "
                   "(rest splits between pagerank and components)")
    p.add_argument("--swap-at", type=float, default=None, dest="swap_at",
                   help="seconds into the run to apply a ~1%% random "
                   "edit batch and hot-swap serving (in-process mode)")
    p.add_argument("--faults", default=None,
                   help="arm a utils/faults.py spec for the measured "
                   "run (after warmup), e.g. "
                   "'serve.engine.execute:raise:0.05' — benchmark "
                   "latency under injected failures (in-process mode)")
    p.add_argument("--json", action="store_true",
                   help="emit one machine-readable serve_bench.v1 JSON "
                   "line at the end")
    p.add_argument("--json-out", dest="json_out",
                   help="also write the serve_bench.v1 report to this "
                   "path (for tools/slo_check.py)")
    args = p.parse_args()

    session = None
    if args.url:
        import urllib.request

        client = HttpClient(args.url)
        health = json.loads(urllib.request.urlopen(
            args.url.rstrip("/") + "/healthz", timeout=10).read())
        nv = health["nv"]
    else:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.mesh:
            # Virtual devices must exist before the backend initializes:
            # widen XLA_FLAGS now, exactly as the RMAT27 tooling does.
            import math

            from lux_tpu.serve.mesh import parse_mesh_spec
            from lux_tpu.utils.platform import virtual_cpu_flags

            n = math.prod(parse_mesh_spec(args.mesh))
            if n > 1:
                os.environ["XLA_FLAGS"] = virtual_cpu_flags(n)

        from lux_tpu.graph import generate
        from lux_tpu.serve import ServeConfig, Session

        if args.file:
            graph = args.file
        else:
            graph = generate.rmat(args.scale, 8, seed=1)
        session = Session(graph, ServeConfig(
            max_batch=args.max_batch, window_s=args.window_ms / 1e3,
            max_queue=max(64, 4 * args.workers),
            mesh=args.mesh,
        ))
        client = LocalClient(session)
        nv = session.graph.nv

    if args.swap_at is not None and session is None:
        print("--swap-at requires in-process mode (not --url)",
              file=sys.stderr)
        return 2
    if args.faults and session is None:
        print("--faults requires in-process mode (not --url)",
              file=sys.stderr)
        return 2
    if args.mesh and session is None:
        print("--mesh requires in-process mode (not --url); start the "
              "server under LUX_SERVE_MESH instead", file=sys.stderr)
        return 2
    if args.faults:
        from lux_tpu.utils import faults

        # Armed AFTER warmup so the injected failures land on the
        # serving path the SLO numbers describe, not on builds.
        faults.arm(args.faults)

    w = max(0.0, min(1.0, args.sssp_weight))
    mix = [("sssp", w), ("pagerank", (1 - w) / 2),
           ("components", (1 - w) / 2)]
    tenants = [t.strip() for t in (args.tenants or "").split(",")
               if t.strip()]
    lat: dict = {}
    tlat: dict = {}
    errs: dict = {}
    stop_at = time.monotonic() + args.duration
    threads = [
        threading.Thread(
            target=worker,
            args=(client, mix, nv, stop_at, args.qps, lat, errs, i,
                  tenants[i % len(tenants)] if tenants else None, tlat),
            daemon=True,
        )
        for i in range(args.workers)
    ]
    swap_result: dict = {}
    swap_thread = None
    if args.swap_at is not None:

        def do_swap():
            import numpy as np

            from lux_tpu.graph import EdgeEdits

            time.sleep(args.swap_at)
            g = session.graph
            rng = np.random.default_rng(99)
            n = max(2, g.ne // 100)
            ins = [(int(rng.integers(g.nv)), int(rng.integers(g.nv)))
                   for _ in range(n // 2)]
            dels = [(int(g.col_src[e]), int(g.col_dst[e]))
                    for e in rng.choice(g.ne, size=n - n // 2,
                                        replace=False)]
            errs_before = dict(errs)
            t_s = time.monotonic()
            try:
                summary = session.apply_edits(
                    EdgeEdits.from_lists(insert=ins, delete=dels))
                swap_result.update(
                    version=summary["version"],
                    swap_s=summary["swap_s"],
                    evicted=summary["evicted"],
                    retired=summary["retired"],
                    plans_evicted=summary.get("plans_evicted", 0),
                )
            except Exception as e:
                swap_result.update(error=repr(e),
                                   swap_s=time.monotonic() - t_s)
            swap_result["errors_during_swap"] = sum(
                errs.get(k, 0) - errs_before.get(k, 0)
                for k in set(errs) | set(errs_before)
            )

        swap_thread = threading.Thread(target=do_swap, daemon=True)

    t0 = time.monotonic()
    for t in threads:
        t.start()
    if swap_thread is not None:
        swap_thread.start()
    for t in threads:
        t.join()
    if swap_thread is not None:
        swap_thread.join(120)
    wall = time.monotonic() - t0

    total = sum(len(v) for v in lat.values())
    print(f"\n{args.workers} workers x {wall:.1f}s  "
          f"({'closed loop' if not args.qps else f'{args.qps} qps/worker'})"
          f"  ->  {total} ok ({total / wall:.1f} req/s), errors: "
          f"{errs or 'none'}")
    report = {"schema": "serve_bench.v1",
              "workers": args.workers, "duration_s": wall,
              "requests_ok": total, "rps": total / wall, "errors": errs,
              "apps": {}}
    for app, xs in sorted(lat.items()):
        xs.sort()
        p50 = percentile(xs, 0.50)
        p95 = percentile(xs, 0.95)
        p99 = percentile(xs, 0.99)
        print(f"  {app:<11} n={len(xs):<6} p50={p50 * 1e3:8.2f} ms   "
              f"p95={p95 * 1e3:8.2f} ms   p99={p99 * 1e3:8.2f} ms")
        report["apps"][app] = {"n": len(xs), "p50_s": p50,
                               "p95_s": p95, "p99_s": p99}
    hist = client.batch_histogram()
    if hist:
        parts = [
            f"<={b['le']}: {b['count']}"
            for b in hist["buckets"] if b["count"]
        ]
        mean = hist["sum"] / max(hist["count"], 1)
        print(f"  batches     n={hist['count']} mean_size={mean:.2f}  "
              f"[{', '.join(parts)}]")
        report["batch_size"] = {"count": hist["count"], "mean": mean,
                                "buckets": hist["buckets"]}
    if tenants:
        # Per-tenant latency quantiles from the client side, joined with
        # the server's /costz consumption totals: "tenant X waited this
        # long and spent that much engine time" in one block.
        try:
            costz = client.costz()
        except Exception:
            costz = {}
        report["tenants"] = {}
        for tenant in sorted(tlat):
            xs = sorted(tlat[tenant])
            entry = {"n": len(xs),
                     "p50_s": percentile(xs, 0.50),
                     "p99_s": percentile(xs, 0.99)}
            cost = (costz.get("totals") or {}).get(tenant)
            if cost:
                entry["cost"] = cost
            report["tenants"][tenant] = entry
            cost_str = (
                "engine_s={engine_s:.3f} iters={iterations} "
                "hit/miss={hits}/{misses}".format(**cost) if cost
                else "cost n/a")
            print(f"  tenant {tenant:<11} n={len(xs):<6} "
                  f"p50={entry['p50_s'] * 1e3:8.2f} ms   "
                  f"p99={entry['p99_s'] * 1e3:8.2f} ms   {cost_str}")
    # Server-side counters the SLO gate cares about: shed/reject volume
    # and the sentinel's recompile count (must be 0 post-warmup).
    try:
        stats = client.stats()
    except Exception:
        stats = {}
    batcher = stats.get("batcher", {})
    pool = stats.get("pool", {})
    report["shed"] = int(batcher.get("deadline_expired", 0))
    report["rejected"] = int(batcher.get("rejected", 0))
    report["recompiles"] = int(pool.get("recompiles", 0))
    report["warmup_compiles"] = int(pool.get("warmup_compiles", 0))
    print(f"  server      shed={report['shed']} "
          f"rejected={report['rejected']} "
          f"recompiles={report['recompiles']}")
    mesh = stats.get("mesh")
    if mesh:
        report["mesh"] = {
            "spec": mesh.get("spec"),
            "shape": mesh.get("shape"),
            "num_parts": mesh.get("num_parts"),
            "plans": mesh.get("plans"),
        }
        if session is not None and mesh.get("num_parts", 1) > 1:
            # Per-device collective volume the warm sharded engines move
            # each iteration — the serving half of the PERF_NOTES.md exchange
            # evidence (the batch half comes from bench_sharded.v1).
            report["mesh"]["exchange_bytes_per_iter"] = (
                session.mesh_exchange_bytes())
        print(f"  mesh        {mesh.get('spec')} "
              f"(parts={mesh.get('num_parts')}), "
              f"plans={mesh.get('plans', {}).get('plans')}")
    if args.faults:
        from lux_tpu.utils import faults

        faults.disarm()
        report["faults"] = {"spec": args.faults,
                            "injected": faults.counts()}
        print(f"  faults      {args.faults} -> "
              f"injected {report['faults']['injected']}")
    if swap_result:
        report["snapshot"] = swap_result
        if "error" in swap_result:
            print(f"  snapshot    SWAP FAILED: {swap_result['error']}")
        else:
            print(f"  snapshot    v{swap_result['version']} swapped in "
                  f"{swap_result['swap_s']:.2f}s mid-run, "
                  f"errors_during_swap="
                  f"{swap_result['errors_during_swap']}")
    if args.json:
        print(json.dumps(report))
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json_out}")
    if session is not None:
        session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
