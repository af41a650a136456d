#!/usr/bin/env python3
"""Serving smoke test (`make serve-smoke`).

End-to-end acceptance run for the serving subsystem (ISSUE 2):

1. generate a tiny graph, write it as .lux, start the HTTP server on an
   ephemeral port (warm engines compiled before traffic);
2. issue one PageRank query plus >= 8 concurrent SSSP root queries
   through the HTTP front end;
3. validate every SSSP response bit-identical to a sequential
   single-source PushExecutor run (and the host BFS oracle), and the
   PageRank response against the numpy oracle;
4. assert >= 1 multi-source batch of size >= 4 actually formed (via the
   `obs` lux_serve_batch_size histogram);
5. assert zero engine builds after warmup (pool miss counter flat across
   the query phase — i.e. zero recompiles).

Observability acceptance (ISSUE 6, `make serve-obs` runs this same
entry point):

6. one request trace-id spans the whole admission->batch->engine->cache
   chain in the Chrome trace (async "b"/"e" events from obs/spans.py);
7. the ``/metrics`` Prometheus scrape parses, includes
   ``lux_xla_compiles_total``, and shows zero serve-phase compiles;
8. ``/statusz`` reports the rolling SLO windows and queue/cache state;
9. an injected deadline miss (deadline_s=0) returns HTTP 504 AND drops
   a valid ``flight.v1`` postmortem in LUX_FLIGHT_DIR that
   tools/flight_summary.py renders.

Scale with LUX_SMOKE_SCALE (default 10).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def post(base, payload, timeout=120):
    req = urllib.request.Request(
        base + "/query", json.dumps(payload).encode(),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.loads(r.read())


def get_text(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return r.read().decode()


def batch_histogram(base):
    for m in get(base, "/metrics.json")["metrics"]:
        if m["name"] == "lux_serve_batch_size":
            return m
    return None


def parse_prometheus(text):
    """Tiny 0.0.4 parser: {(name, frozen-label-string): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        series, val = line.rsplit(" ", 1)
        name, _, labels = series.partition("{")
        out[(name, labels.rstrip("}"))] = float(val)
    return out


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
    n_sssp = flags.get_int("LUX_SMOKE_QUERIES")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from lux_tpu.engine.push import PushExecutor
    from lux_tpu.graph import generate, write_lux
    from lux_tpu.models.pagerank import reference_pagerank
    from lux_tpu.models.sssp import SSSP, reference_sssp
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.serve.http import serve_in_thread

    from lux_tpu import obs

    g = generate.rmat(scale, 8, seed=1)
    ni = 5
    with tempfile.TemporaryDirectory() as td:
        gpath = os.path.join(td, f"rmat{scale}.lux")
        write_lux(gpath, g)

        # Arm the full observability stack for this run: Chrome trace
        # stream + flight recorder (the spans flag defaults on).
        trace_path = os.path.join(td, "trace.jsonl")
        flight_dir = os.path.join(td, "flight")
        os.makedirs(flight_dir)
        os.environ["LUX_TRACE"] = trace_path
        os.environ["LUX_FLIGHT_DIR"] = flight_dir
        obs.reconfigure()

        # Generous window so even a slow CPU box forms one full batch
        # from the concurrent burst below; real deployments run ~3ms.
        cfg = ServeConfig(
            max_batch=max(4, n_sssp), window_s=0.5, max_queue=256,
            pagerank_iters=ni,
        )
        session = Session(gpath, cfg)
        server, _ = serve_in_thread(session, port=0)
        base = f"http://127.0.0.1:{server.server_address[1]}"

        health = get(base, "/healthz")
        assert health["ok"] and health["nv"] == g.nv, health
        assert health["pool_warm"] and health["engines"] > 0, health
        print(f"server up: nv={health['nv']} ne={health['ne']} "
              f"fingerprint={health['fingerprint']} "
              f"device={health['device']} engines={health['engines']}")

        misses_before = get(base, "/stats")["pool"]["misses"]
        batches_before = (batch_histogram(base) or {"count": 0})["count"]

        # One PageRank + n_sssp concurrent SSSP root queries.
        rng = np.random.default_rng(7)
        roots = [int(r) for r in rng.integers(0, g.nv, size=n_sssp)]
        with ThreadPoolExecutor(max_workers=n_sssp + 1) as tp:
            pr_fut = tp.submit(post, base, {"app": "pagerank", "ni": ni,
                                            "full": True})
            sssp_futs = [
                tp.submit(post, base, {"app": "sssp", "start": r,
                                       "full": True})
                for r in roots
            ]
            pr = pr_fut.result()
            sssp = [f.result() for f in sssp_futs]

        # -- correctness: batched == sequential single-source == oracle --
        for r, out in zip(roots, sssp):
            got = np.asarray(out["values"], dtype=np.uint32)
            ex = PushExecutor(g, SSSP())
            seq_state, _ = ex.run(start=r)
            seq = np.asarray(seq_state.values)
            np.testing.assert_array_equal(got, seq)
            np.testing.assert_array_equal(got, reference_sssp(g, r))
        print(f"sssp: {n_sssp} roots bit-identical to sequential "
              f"single-source runs + oracle")

        pr_got = np.asarray(pr["values"], dtype=np.float32)
        np.testing.assert_allclose(
            pr_got, reference_pagerank(g, ni), rtol=1e-3, atol=1e-7
        )
        print(f"pagerank: {ni}-iteration fixpoint matches oracle")

        # -- batching actually happened --------------------------------
        hist = batch_histogram(base)
        assert hist is not None, "no lux_serve_batch_size histogram"
        new_big = sum(
            b["count"] for b in hist["buckets"]
            if b["le"] == "+Inf" or float(b["le"]) >= 4
        )
        assert hist["count"] > batches_before, "no batches formed"
        assert new_big >= 1, (
            f"no multi-source batch of size >= 4 formed: {hist['buckets']}"
        )
        sizes = [(b["le"], b["count"])
                 for b in hist["buckets"] if b["count"]]
        print(f"batching: {hist['count']} batches, histogram {sizes} "
              f"(>=1 batch of size >=4)")

        # -- zero recompiles after warmup ------------------------------
        stats = get(base, "/stats")
        misses_after = stats["pool"]["misses"]
        assert misses_after == misses_before, (
            f"engines were built during the query phase: "
            f"{misses_before} -> {misses_after}"
        )
        recompiles = stats["pool"].get("recompiles", 0)
        assert recompiles == 0, (
            f"RecompileSentinel saw {recompiles} XLA compile(s) in the "
            "post-warmup query phase"
        )
        print(f"warm pool: {stats['pool']['engines']} engines, "
              f"{stats['pool']['hits']} hits, miss count flat at "
              f"{misses_after}, sentinel recompiles {recompiles}")
        if "latency_s" in stats:
            print(f"latency: p50={stats['latency_s']['p50'] * 1e3:.1f}ms "
                  f"p99={stats['latency_s']['p99'] * 1e3:.1f}ms over "
                  f"{stats['latency_s']['count']} requests")

        # -- one trace-id spans admission->batch->engine->cache --------
        chains = async_trace_chains(trace_path)
        chain_want = {"serve.admit", "serve.queue_wait", "serve.batch",
                      "serve.engine"}
        full = {
            tid: names for tid, names in chains.items()
            if chain_want <= names
            and "serve.cache.put" in names
        }
        assert full, (
            f"no single trace-id covers {sorted(chain_want)} + cache; "
            f"chains: { {t: sorted(n) for t, n in chains.items()} }"
        )
        tid, names = next(iter(sorted(full.items())))
        print(f"spans: trace {tid} covers {sorted(names)} "
              f"({len(chains)} traces total)")

        # -- Prometheus scrape -----------------------------------------
        text = get_text(base, "/metrics")
        samples = parse_prometheus(text)
        compile_samples = {
            k: v for k, v in samples.items()
            if k[0] == "lux_xla_compiles_total"
        }
        assert compile_samples, "no lux_xla_compiles_total in /metrics"
        serve_compiles = sum(
            v for k, v in compile_samples.items() if 'phase="serve"' in k[1]
        )
        assert serve_compiles == 0, (
            f"serve-phase XLA compiles in scrape: {compile_samples}"
        )
        assert any(k[0] == "lux_ir_findings_total" for k in samples), text
        assert any(k[0] == "lux_span_seconds_bucket" for k in samples), (
            "span histograms missing from scrape"
        )
        print(f"prometheus: {len(samples)} samples, "
              f"lux_xla_compiles_total serve-phase sum 0")

        # -- /statusz --------------------------------------------------
        sz = get(base, "/statusz")
        windows = sz["windows"]
        assert windows, sz
        some_window = next(iter(windows.values()))
        assert any(a.get("count", 0) > 0 for a in some_window.values()), sz
        assert sz["queue"]["capacity"] > 0
        assert sz["counters"]["recompiles"] == 0, sz
        print(f"statusz: windows {sorted(windows)} "
              f"cache_hit_rate={sz['cache_hit_rate']} "
              f"queue={sz['queue']['depth']}/{sz['queue']['capacity']}")

        # -- injected deadline miss -> 504 + flight.v1 postmortem ------
        fresh = next(r for r in range(g.nv) if r not in set(roots))
        try:
            post(base, {"app": "sssp", "start": fresh, "deadline_s": 0})
            raise AssertionError("deadline_s=0 query did not 504")
        except urllib.error.HTTPError as e:
            assert e.code == 504, f"expected 504, got {e.code}"
        dumps = sorted(
            f for f in os.listdir(flight_dir) if f.endswith(".json")
        )
        assert dumps, "deadline shed produced no flight dump"
        dump_path = os.path.join(flight_dir, dumps[-1])
        doc = json.loads(open(dump_path).read())
        assert doc["schema"] == "flight.v1" and             doc["reason"] == "deadline_shed", doc
        assert doc["traces"] and doc["context"] and doc["flags"], (
            sorted(doc)
        )
        summary = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "flight_summary.py"), dump_path],
            capture_output=True, text=True,
        )
        assert summary.returncode == 0, summary.stderr
        assert "deadline_shed" in summary.stdout
        print(f"flight: 504 -> {os.path.basename(dump_path)} "
              f"({len(doc['traces'])} traces, "
              f"{len(doc['iterations'])} iteration records) — "
              "flight_summary renders OK")

        server.shutdown()
        session.close()
    print("serve-smoke PASS (incl. observability: spans, prometheus, "
          "statusz, flight recorder)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
