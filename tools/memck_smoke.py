#!/usr/bin/env python
"""memck_smoke: acceptance gate for the luxlint memory tier
(`make lint-memory`, wired into `make verify`).

Four claims, all asserted:

  1. **registry clean + fast** — pricing every traced registry target
     (LUX701-706) produces 0 findings and the proof phase (liveness
     walks + rule checks; executor staging and jit lowering are
     environment setup, untimed) fits the wall budget;
  2. **artifact parity** — the freshly derived ``memcap.v1`` footprint
     artifact has the same content-addressed id as the committed
     ``lux_tpu/analysis/memcap.json``: a footprint-changing edit fails
     verify until regenerated (``luxlint --memory --memcap-out
     lux_tpu/analysis/memcap.json``) — the offline half of the LUX706
     drift ratchet;
  3. **a seeded leak is caught** — the committed LUX702 fixture (a
     donation the lowered HLO never honors) must fail with exactly its
     rule, proving the tier distinguishes and not merely passes;
  4. **the budget has teeth at the front door** — under a one-byte HBM
     budget, a real HTTP query whose engine build the memcap.v1
     admission formula refuses is shed with a typed 503 +
     ``Retry-After``, and a direct pool exercise shows footprint-LRU
     eviction with zero recompiles on warm hits.

Exit status: 0 when all four hold. Emits one greppable
``MEMCKSMOKE {...}`` summary line (``memck_smoke.v1``).

Usage:
    python tools/memck_smoke.py               # default: 2s budget
    python tools/memck_smoke.py --budget-s 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types
import urllib.error
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ["JAX_PLATFORMS"] = "cpu"

from lux_tpu.utils.platform import virtual_cpu_flags  # noqa: E402

# Sharded registry targets trace against the 8-way virtual mesh the
# serve/exchange gates use; opt level 0 keeps lowering cheap.
os.environ["XLA_FLAGS"] = (virtual_cpu_flags(8)
                           + " --xla_backend_optimization_level=0")

from lux_tpu.analysis import memck  # noqa: E402

FIXTURE = os.path.join(_REPO, "tests", "mem_fixtures",
                       "lux702_unhonored_donation.py")


def _pool_residency_demo() -> dict:
    """Direct EnginePool exercise: footprint-LRU eviction under a tight
    budget, warm hits untouched (and recompile-free)."""
    from lux_tpu.serve.pool import EnginePool
    from lux_tpu.utils import flags

    pool = EnginePool(scope="memck-smoke")
    out = {"evicted": 0, "warm_hit": False, "recompiles": -1,
           "resident_bytes": -1}
    try:
        with flags.overrides({"LUX_HBM_BUDGET_BYTES": "1000"}):
            ev0 = pool.stats()["hbm_evictions"]
            a = pool.get(("a",), lambda: types.SimpleNamespace(),
                         footprint_bytes=600)
            out["warm_hit"] = pool.get(
                ("a",), lambda: types.SimpleNamespace(),
                footprint_bytes=600) is a
            pool.get(("b",), lambda: types.SimpleNamespace(),
                     footprint_bytes=600)     # does not fit: evicts a
            out["evicted"] = pool.stats()["hbm_evictions"] - ev0
            out["resident_bytes"] = pool.hbm_resident_bytes()
            out["recompiles"] = pool.stats()["recompiles"]
    finally:
        pool.close()
    out["ok"] = (out["evicted"] == 1 and out["warm_hit"]
                 and out["recompiles"] == 0
                 and out["resident_bytes"] == 600)
    return out


def _http_shed_demo() -> dict:
    """End-to-end: a one-byte budget makes the first engine build
    unadmittable, and the HTTP front end sheds the query with the typed
    503 + Retry-After instead of building (and OOMing) anyway."""
    from lux_tpu.graph import generate
    from lux_tpu.serve.http import serve_in_thread
    from lux_tpu.serve.session import Session

    out = {"status": None, "retry_after": None, "error": None}
    g = generate.gnp(96, 400, seed=11)
    # Env var, not flags.overrides: the overlay is context-local by
    # design (probe isolation) and the admission check runs on the
    # serve batcher thread, which must see the budget too.
    os.environ["LUX_HBM_BUDGET_BYTES"] = "1"
    try:
        session = Session(g, warm=False)
        server, _ = serve_in_thread(session, port=0)
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            req = urllib.request.Request(
                base + "/query",
                json.dumps({"app": "sssp", "start": 0}).encode(),
                {"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(req, timeout=60)
                out["error"] = "query was admitted under a 1 B budget"
            except urllib.error.HTTPError as e:
                out["status"] = e.code
                ra = e.headers.get("Retry-After")
                out["retry_after"] = float(ra) if ra else None
                body = json.loads(e.read() or b"{}")
                out["error"] = body.get("error")
        finally:
            server.shutdown()
            session.close()
    finally:
        del os.environ["LUX_HBM_BUDGET_BYTES"]
    out["ok"] = (out["status"] == 503
                 and (out["retry_after"] or 0) > 0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="memck_smoke", description=__doc__)
    ap.add_argument("--budget-s", type=float, default=2.0,
                    help="wall budget for the registry proof phase")
    args = ap.parse_args(argv)

    report, art = memck.prove_registry()
    prove_s = report.summary()["elapsed_s"]

    for res in report.results:
        for f in res.findings:
            print(f.format())
        if res.error:
            print(f"{res.path}: {res.error}")

    clean = report.ok and not any(r.error for r in report.results)
    fast = prove_s <= args.budget_s

    committed_id = None
    parity = False
    try:
        committed = memck.load_memcap(memck.memcap_path())
        committed_id = committed["id"]
        parity = committed_id == art["id"]
    except Exception as e:  # missing or tampered artifact: loud, fatal
        print(f"memck_smoke: committed memcap.v1 unusable: {e!r}")

    fix_rules = []
    fixture_caught = False
    if os.path.exists(FIXTURE):
        fix_rep = memck.verify_fixture_paths([FIXTURE])
        fix_rules = sorted({f.rule for f in fix_rep.findings})
        fixture_caught = (not fix_rep.ok) and fix_rules == ["LUX702"]
    else:
        print(f"memck_smoke: missing fixture {FIXTURE}")

    pool_demo = _pool_residency_demo()
    shed_demo = _http_shed_demo()

    ok = (clean and fast and parity and fixture_caught
          and pool_demo["ok"] and shed_demo["ok"])
    summary = {
        "schema": "memck_smoke.v1",
        "targets": len(art["targets"]),
        "findings": len(report.findings),
        "errors": sum(1 for r in report.results if r.error),
        "prove_s": prove_s,
        "budget_s": args.budget_s,
        "clean": clean,
        "fast": fast,
        "artifact_id": art["id"],
        "committed_id": committed_id,
        "parity": parity,
        "fixture_rules": fix_rules,
        "fixture_caught": fixture_caught,
        "pool": pool_demo,
        "shed": shed_demo,
        "ok": ok,
    }
    print("MEMCKSMOKE " + json.dumps(summary, sort_keys=True))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
