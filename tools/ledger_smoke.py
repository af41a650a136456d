#!/usr/bin/env python3
"""Run-ledger + cost-attribution smoke test (`make ledger-smoke`).

End-to-end acceptance for the observability ledger (obs/ledger.py) and
per-query cost accounting (serve/cost.py) on a warm CPU serving
session, with ``LUX_LEDGER_DIR`` armed for the whole run:

1. warm serve burst from TWO tenants through the real HTTP front door
   (``X-Lux-Tenant`` request header in, ``X-Lux-Cost`` response header
   out) — zero errors, zero recompiles after warmup;
2. ``/costz`` totals agree EXACTLY with the ``lux_query_cost_*``
   metric values (the lockstep-increment invariant), and per-tenant
   request counts match what the client actually issued;
3. the ledger collected durable ``runrec.v1`` records for the warmup
   and the engine runs; every record validates (crc-clean, no torn
   segments) and carries the config_hash the live registry reproduces;
4. ``tools/lux_doctor.py`` reads the ledger back and renders a CLEAN
   report (single config cohort: nothing to regress against).

Prints a ``ledger_smoke.v1`` JSON document on the last line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCALE = 8
TENANTS = ("acme", "globex")
ROOTS_PER_TENANT = 6


def log(msg):
    print(f"# {msg}", flush=True)


def get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as r:
        return json.loads(r.read())


def post_query(base, payload, tenant):
    req = urllib.request.Request(
        base + "/query", json.dumps(payload).encode(),
        {"Content-Type": "application/json", "X-Lux-Tenant": tenant},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read()), r.headers.get("X-Lux-Cost")


def metric_value(base, name, **labels):
    for m in get(base, "/metrics.json")["metrics"]:
        if m["name"] == name and m["labels"] == labels:
            return m["value"]
    return 0.0


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from lux_tpu.utils import flags

    with tempfile.TemporaryDirectory() as td:
        ledger_dir = os.path.join(td, "ledger")
        os.environ["LUX_LEDGER_DIR"] = ledger_dir

        from lux_tpu.graph import generate
        from lux_tpu.obs import ledger
        from lux_tpu.serve import ServeConfig, Session
        from lux_tpu.serve.http import serve_in_thread

        ledger.reset()
        g = generate.rmat(SCALE, 8, seed=1)
        session = Session(g, ServeConfig(
            max_batch=4, window_s=0.05, max_queue=128, pagerank_iters=4,
        ))
        server, _ = serve_in_thread(session, port=0)
        base = f"http://127.0.0.1:{server.server_address[1]}"
        log(f"server up at {base}, ledger armed at {ledger_dir}")

        # -- 1. two-tenant warm burst over HTTP ------------------------
        issued = {t: 0 for t in TENANTS}
        cost_headers = []

        def burst(tenant, seed):
            for i in range(ROOTS_PER_TENANT):
                _out, hdr = post_query(
                    base, {"app": "sssp",
                           "start": (seed * 37 + i * 11) % g.nv}, tenant)
                cost_headers.append((tenant, hdr))
                issued[tenant] += 1
            # PageRank twice: a miss, then a result-cache hit.
            for _ in range(2):
                _out, hdr = post_query(base, {"app": "pagerank"}, tenant)
                cost_headers.append((tenant, hdr))
                issued[tenant] += 1

        with ThreadPoolExecutor(max_workers=2) as tp:
            list(tp.map(burst, TENANTS, range(len(TENANTS))))

        assert all(h and f"tenant={t}" in h for t, h in cost_headers), (
            "every response must carry an X-Lux-Cost header",
            cost_headers[:3])
        hits = [h for _t, h in cost_headers if "outcome=hit" in h]
        assert hits, "repeat pagerank must be a cache hit"
        recompiles = get(base, "/stats")["pool"]["recompiles"]
        assert recompiles == 0, f"burst added {recompiles} recompiles"
        log(f"burst ok: {sum(issued.values())} queries, "
            f"{len(hits)} cache hits, 0 recompiles")

        # -- 2. /costz totals == metric values, counts == issued -------
        costz = get(base, "/costz")
        assert costz["schema"] == "costz.v1", costz
        parity = {}
        for t in TENANTS:
            tot = costz["totals"][t]
            assert tot["requests"] == issued[t], (t, tot, issued)
            assert tot["hits"] >= 1 and tot["misses"] >= 1, tot
            m_engine = metric_value(
                base, "lux_query_cost_engine_seconds", tenant=t)
            m_iters = metric_value(
                base, "lux_query_cost_iterations_total", tenant=t)
            assert m_engine == tot["engine_s"], (t, m_engine, tot)
            assert m_iters == tot["iterations"], (t, m_iters, tot)
            parity[t] = {"requests": tot["requests"],
                         "engine_s": tot["engine_s"],
                         "iterations": tot["iterations"]}
        assert costz["config"]["hash"] == flags.config_hash()
        log(f"costz parity ok: {parity}")

        # -- 3. durable records validate + config_hash reproduces ------
        recs = ledger.read_all(ledger_dir, strict=True)
        kinds = sorted({r["kind"] for r in recs})
        assert "serve_warmup" in kinds and "engine_run" in kinds, kinds
        chash = flags.config_hash()
        assert all(r["key"]["config_hash"] == chash for r in recs), (
            "a record's config_hash must reproduce from the live "
            "registry while the env is unchanged")
        v = ledger.validate_dir(ledger_dir)
        assert v["interior_bad"] == 0 and v["torn_segments"] == 0, v
        log(f"ledger ok: {len(recs)} records {kinds}, validate={v}")

        # -- 4. the doctor reads it back clean -------------------------
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lux_doctor.py"),
             "--dir", ledger_dir, "--json"],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == 0, (proc.returncode, proc.stderr)
        doctor = json.loads(proc.stdout)
        assert doctor["ok"] is True, doctor
        assert doctor["records"] == len(recs), doctor
        log("doctor ok: CLEAN verdict over the smoke ledger")

        server.shutdown()
        session.close()
        os.environ.pop("LUX_LEDGER_DIR", None)
        ledger.reset()

        print(json.dumps({
            "schema": "ledger_smoke.v1",
            "ok": True,
            "queries": sum(issued.values()),
            "cache_hits": len(hits),
            "recompiles": recompiles,
            "records": len(recs),
            "kinds": kinds,
            "config_hash": chash,
            "tenants": parity,
            "validate": v,
            "doctor_ok": doctor["ok"],
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
