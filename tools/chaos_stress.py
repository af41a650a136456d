#!/usr/bin/env python3
"""Chaos acceptance harness for the robustness tier (`make chaos-stress`).

tests/ prove each mechanism in isolation; this tool proves they compose
under load, driving real HTTP traffic with *every* registered fault
point armed (utils/faults.py, seeded — reruns replay the same draws):

Phase A — seeded burst: concurrent SSSP/components/PageRank queries plus
  mid-burst WAL-queued edits and a flush-swap, with engine raises, build
  / fsync / warm / batcher delays, and cache-put failures injected.
  Asserts every request reaches a TERMINAL status (no hangs) and the
  per-code ``lux_requests_total`` deltas sum exactly to requests issued.

Phase B — breaker lifecycle: a hard engine fault trips the per-(program,
  fingerprint) breaker open (503 + Retry-After); after the cooldown the
  half-open probe rebuilds the pool entry and closes it. Asserts the
  open -> half_open -> closed transition counters all advanced and
  serving returns to 200.

Phase C — crash/recover: an injected CrashPoint (BaseException — no
  handler may absorb it) kills a swap between the durable WAL mint and
  the serving flip. The store is rebuilt via SnapshotStore.recover and
  asserted bitwise-identical (fingerprint) to the pre-crash head; a new
  session serves it and a disarmed steady-state burst must recompile
  NOTHING (the zero-recompile contract survives chaos + recovery).

Prints a one-line ``chaos_stress.v1`` JSON document last. Scale with
LUX_SMOKE_SCALE (default 10); CPU-sized.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Robustness knobs pinned before any lux_tpu import so flag reads and
# module wiring see them: fast retry, a 3-failure breaker with a short
# cooldown, and a WAL armed in a scratch dir.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["LUX_RETRY_MAX"] = "1"
os.environ["LUX_RETRY_BACKOFF_MS"] = "10"
os.environ["LUX_BREAKER_THRESHOLD"] = "3"
os.environ["LUX_BREAKER_COOLDOWN_MS"] = "400"
WAL_DIR = tempfile.mkdtemp(prefix="lux-chaos-wal-")
os.environ["LUX_WAL_DIR"] = WAL_DIR

import numpy as np  # noqa: E402

BURST_FAULTS = (
    "serve.engine.execute:raise:0.25,"
    "pool.build:delay_ms:1.0:5,"
    "wal.fsync:delay_ms:1.0:5,"
    "snapshot.warm:delay_ms:1.0:5,"
    "batcher.assemble:delay_ms:0.5:2,"
    "cache.put:raise:0.5"
)


def _post(base, path, payload, timeout=120):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers)
    except urllib.error.HTTPError as e:
        e.read()
        return e.code, dict(e.headers)


def _requests_by_code(metrics):
    out = {}
    for code in ("200", "400", "429", "500", "503", "504"):
        v = metrics.counter("lux_requests_total", {"code": code}).value
        if v:
            out[code] = int(v)
    return out


def _transitions(metrics):
    return {
        s: int(metrics.counter("lux_breaker_transitions_total",
                               {"to": s}).value)
        for s in ("open", "half_open", "closed")
    }


def main() -> int:
    from lux_tpu.utils import flags

    scale = flags.get_int("LUX_SMOKE_SCALE")

    from lux_tpu.graph import EdgeEdits, SnapshotStore, generate
    from lux_tpu.obs import metrics
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.serve.http import serve_in_thread
    from lux_tpu.utils import faults

    g = generate.rmat(scale, 8, seed=7)
    cfg = ServeConfig(max_batch=4, window_s=0.02, max_queue=512,
                      pagerank_iters=3)
    session = Session(g, cfg)
    server, _ = serve_in_thread(session)
    base = "http://127.0.0.1:%d" % server.server_address[1]
    rng = np.random.default_rng(23)

    def edit_payload(n):
        return {"insert": [[int(rng.integers(g.nv)), int(rng.integers(g.nv))]
                           for _ in range(n)]}

    # ---- Phase A: seeded burst with every fault point armed -------------
    before_codes = _requests_by_code(metrics)
    faults.arm(BURST_FAULTS, seed=flags.get_int("LUX_FAULTS_SEED"))
    jobs = ([{"app": "sssp", "start": int(r)}
             for r in rng.integers(0, g.nv, size=24)]
            + [{"app": "components"}] * 6
            + [{"app": "pagerank"}] * 6)
    issued = []

    def one_query(body):
        code, _ = _post(base, "/query", body)
        return code

    with ThreadPoolExecutor(max_workers=8) as tp:
        futs = [tp.submit(one_query, j) for j in jobs[: len(jobs) // 2]]
        # Mid-burst durable writes: two queued batches + one flush-swap
        # race the second half of the burst through the drain barrier.
        issued.append(_post(base, "/snapshot",
                            {**edit_payload(4), "queue": True})[0])
        issued.append(_post(base, "/snapshot",
                            {**edit_payload(4), "queue": True})[0])
        issued.append(_post(base, "/snapshot", {"flush": True})[0])
        futs += [tp.submit(one_query, j) for j in jobs[len(jobs) // 2:]]
        # .result() below would hang forever on a lost future — the
        # timeout IS the no-hangs assertion.
        issued += [f.result(timeout=300) for f in futs]
    faults.disarm()

    assert len(issued) == len(jobs) + 3, "a request never came back"
    after_codes = _requests_by_code(metrics)
    deltas = {c: after_codes.get(c, 0) - before_codes.get(c, 0)
              for c in set(before_codes) | set(after_codes)}
    deltas = {c: n for c, n in deltas.items() if n}
    assert sum(deltas.values()) == len(issued), (
        f"terminal statuses {deltas} do not sum to {len(issued)} issued")
    injected_burst = dict(faults.counts())
    assert injected_burst, "the armed burst never injected anything"

    # Let any in-flight breaker state from the burst settle before the
    # deterministic lifecycle phase (the probe heals open keys).
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        code, _ = _post(base, "/query", {"app": "sssp", "start": 0})
        if code == 200:
            break
        session.breaker.drain_probes()
        time.sleep(0.2)
    else:
        raise AssertionError("breaker never settled after the burst")

    # ---- Phase B: breaker open -> half_open -> closed -------------------
    t_before = _transitions(metrics)
    faults.arm("serve.engine.execute:raise:1.0")
    codes_b = []
    saw_retry_after = False
    for i in range(1, 8):
        code, hdrs = _post(base, "/query",
                           {"app": "sssp", "start": int(g.nv // 2 + i)})
        codes_b.append(code)
        if code == 503:
            assert float(hdrs.get("Retry-After", 0)) > 0, \
                "503 without Retry-After"
            saw_retry_after = True
            break
    assert saw_retry_after, f"breaker never opened: {codes_b}"
    faults.disarm()
    time.sleep(0.45)                       # cooldown elapses
    code, _ = _post(base, "/query", {"app": "sssp", "start": 1})
    session.breaker.drain_probes()         # half-open probe completes
    code, _ = _post(base, "/query", {"app": "sssp", "start": 2})
    assert code == 200, f"breaker did not close after probe (got {code})"
    t_after = _transitions(metrics)
    for s in ("open", "half_open", "closed"):
        assert t_after[s] > t_before[s], (
            f"breaker never reached {s}: {t_before} -> {t_after}")

    # ---- Phase C: crash mid-swap, recover, steady-state -----------------
    faults.arm("snapshot.warm:crash:1.0")
    crashed = False
    try:
        session.apply_edits(EdgeEdits.from_lists(
            insert=[[int(rng.integers(g.nv)), int(rng.integers(g.nv))]
                    for _ in range(4)]))
    except faults.CrashPoint:
        crashed = True
    faults.disarm()
    assert crashed, "CrashPoint was absorbed before the harness"
    head = session.store.current()
    pre_crash_version, pre_crash_fp = head.version, head.fingerprint
    assert pre_crash_version > session.version, \
        "crash fired after the flip, not between mint and flip"
    server.shutdown()
    session.close()

    base_graph = generate.rmat(scale, 8, seed=7)   # what a restart loads
    store = SnapshotStore.recover(base_graph, WAL_DIR)
    rhead = store.current()
    assert rhead.version == pre_crash_version, \
        f"recovered v{rhead.version}, expected v{pre_crash_version}"
    assert rhead.fingerprint == pre_crash_fp, "WAL replay parity violated"

    session2 = Session(store, cfg)          # warm=True: fresh warmup
    roots = [int(r) for r in rng.integers(0, rhead.graph.nv, size=12)]
    for r in roots:
        session2.query("sssp", start=r, timeout=300)
    session2.query("components", timeout=300)
    session2.query("pagerank", timeout=300)
    for r in roots:                          # steady state: all cached/warm
        session2.query("sssp", start=r, timeout=300)
    session2.pool.sentinel.assert_zero_recompiles()
    recompiles = session2.pool.stats()["recompiles"]
    assert recompiles == 0, f"{recompiles} steady-state recompiles"
    wal_stats = store.wal_stats()
    session2.close()

    print(f"chaos-stress PASS ({len(issued)} burst requests all terminal, "
          f"breaker open->half_open->closed, crash recovered to "
          f"v{rhead.version} bitwise, 0 steady-state recompiles)")
    print(json.dumps({
        "schema": "chaos_stress.v1",
        "graph": {"scale": scale, "nv": g.nv, "ne": g.ne},
        "burst": {"issued": len(issued), "codes": deltas,
                  "faults": BURST_FAULTS,
                  "injected": injected_burst},
        "breaker": {"transitions": {s: t_after[s] - t_before[s]
                                    for s in t_after}},
        "recovery": {"version": rhead.version,
                     "fingerprint": rhead.fingerprint[:12],
                     "wal_records": wal_stats["records"] if wal_stats
                     else None,
                     "parity": True},
        "steady_state_recompiles": 0,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
