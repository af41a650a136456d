#!/usr/bin/env python3
"""Profiler smoke test (`make prof-smoke`).

End-to-end acceptance run for the device-timeline profiler (obs/prof.py)
on a 2x4 virtual CPU mesh (8 XLA host devices — the exchange-smoke
trick, so this runs in CI with no TPU). A REAL capture, not a synthetic
trace: jax.profiler writes the artifact, the stdlib parser reads it
back.

1. build + warm a sharded pull engine under a RecompileSentinel expect
   window (the AOT op-map lowering's one compile is budgeted there),
   then run a profiled capture window over warm steps under a WATCH
   window — zero added recompiles with regions armed;
2. prove classification: both ``lux.pull_sharded.exchange`` and
   ``.compute`` tags present in the parsed report, plus the host-side
   wrapper region;
3. prove the interval math on every device row: union >= max phase,
   union <= exchange+compute, overlap <= min phase,
   realized_hidden_frac and idle_frac in [0, 1];
4. prove the artifact contract: the written ``profile_v1.json``
   round-trips through ``tools/prof_summary.py --validate``;
5. serve integration: ``POST /profilez`` is 403 while LUX_PROF_DIR is
   unset, 429 while another capture holds the window, and 200 with a
   validating profile.v1 report under a concurrent query burst — zero
   failed queries while the capture runs;
6. the /statusz engobs block labels ``exchange_hidden_frac`` as the
   budget (upper bound) and carries the device-measured
   ``realized_hidden_frac`` next to it once a profile exists.

Prints a ``prof_smoke.v1`` JSON document on the last line.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MESH = "2x4"
PARTS = 8
STEPS = 4
EPS = 1e-3      # float-microsecond tolerance (obs/prof.py _EPS_US)


def log(msg):
    print(f"# {msg}", flush=True)


def post(base, path, payload, timeout=600):
    req = urllib.request.Request(
        base + path, json.dumps(payload).encode(),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as r:
        return json.loads(r.read())


def check_device_math(rep):
    """Invariant sweep over every device row (smoke re-derives them —
    the parser's validate() already ran, this proves it from outside)."""
    for pid, d in rep["devices"].items():
        ex, co = d["exchange_us"], d["compute_us"]
        ov, un = d["overlap_us"], d["union_us"]
        assert un + EPS >= max(ex, co), (pid, d)
        assert un <= ex + co + EPS, (pid, d)
        assert ov <= min(ex, co) + EPS, (pid, d)
        for key in ("realized_hidden_frac", "idle_frac"):
            v = d.get(key)
            assert v is None or 0.0 <= v <= 1.0, (pid, key, v)
    frac = rep["realized_hidden_frac"]
    assert frac is None or 0.0 <= frac <= 1.0, frac


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lux_tpu.utils.platform import virtual_cpu_flags

    os.environ["XLA_FLAGS"] = virtual_cpu_flags(PARTS)

    from lux_tpu.analysis.sentinel import RecompileSentinel
    from lux_tpu.engine.pull_sharded import ShardedPullExecutor, hard_sync
    from lux_tpu.graph import generate
    from lux_tpu.models import PageRank
    from lux_tpu.obs import prof
    from lux_tpu.parallel.mesh import make_mesh

    work = tempfile.mkdtemp(prefix="prof_smoke_")
    doc = {"schema": "prof_smoke.v1",
           "mesh": {"spec": MESH, "num_parts": PARTS}}
    sent = RecompileSentinel("prof-smoke")

    # -- 1: capture over warm steps, zero recompiles with regions armed -
    g = generate.halo(PARTS, 256, hubs=8, weighted=False)
    mesh = make_mesh(PARTS)
    log(f"halo graph nv={g.nv} ne={g.ne} on a {MESH} virtual mesh")
    with sent.expect("pagerank-sharded"):
        ex = ShardedPullExecutor(g, PageRank(), mesh=mesh)
        ex.warmup()
        vals = hard_sync(ex.step(ex.init_values()))
        # AOT lowering for the HLO op-name map: exactly one budgeted
        # compile (obs/prof.py op_map_for).
        opmap = prof.op_map_for(ex._step, vals, ex._device_graph)
    assert set(opmap["ops"].values()) >= {
        "lux.pull_sharded.exchange", "lux.pull_sharded.compute"}, (
        "compiled HLO carries no region metadata: "
        f"{sorted(set(opmap['ops'].values()))}")

    def drive():
        with prof.region("lux.prof_smoke.drive"):
            v = vals
            for _ in range(STEPS):
                v = ex.step(v)
            return hard_sync(v)

    cap_dir = os.path.join(work, "capture")
    with sent.watch("pagerank-sharded"):
        # step() donates its input, so each step consumes `vals` and the
        # warm run must rebind it (drive reads the rebound cell).
        vals = hard_sync(ex.step(vals))       # warm, unprofiled
        _, rep = prof.profile_window(
            drive, dirname=cap_dir, steps=STEPS, op_maps=[opmap])
    sent.assert_zero_recompiles()
    log("sentinel: 0 recompiles outside expect windows — regions armed "
        "and capture running add no re-traces")

    # -- 2: both phase tags classified + host wrapper region ------------
    tags = set(rep["tags"])
    assert {"lux.pull_sharded.exchange",
            "lux.pull_sharded.compute"} <= tags, tags
    assert "lux.prof_smoke.drive" in rep["host_regions"], (
        rep["host_regions"])
    log(f"classification: tags={sorted(tags)}")

    # -- 3: interval math + steps cross-check ---------------------------
    check_device_math(rep)
    assert rep["steps"]["captured"] == STEPS, rep["steps"]
    assert prof.latest() is rep and \
        prof.latest_realized() == rep["realized_hidden_frac"]
    realized = rep["realized_hidden_frac"]
    log(f"interval math consistent on {len(rep['devices'])} device "
        f"row(s); realized_hidden_frac={realized}")
    doc["engine_capture"] = {
        "devices": len(rep["devices"]),
        "realized_hidden_frac": realized,
        "tags": sorted(tags),
    }

    # -- 4: profile_v1.json round-trips the CLI validator ---------------
    rep_path = os.path.join(work, "profile_v1.json")
    with open(rep_path, "w") as f:
        json.dump(rep, f)
    for target in (rep_path, cap_dir):
        rc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "prof_summary.py"),
             "--validate", target], cwd=REPO).returncode
        assert rc == 0, f"prof_summary --validate {target} -> rc={rc}"
    render = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "prof_summary.py"),
         rep_path], cwd=REPO, capture_output=True, text=True)
    assert render.returncode == 0 and \
        "realized_hidden_frac" in render.stdout, render.stdout
    log("prof_summary: --validate ok on the report AND the raw capture "
        "dir; render carries the realized fraction")

    # -- 5: serve integration -------------------------------------------
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.serve.http import serve_in_thread

    os.environ["LUX_ENGOBS"] = "1"
    try:
        gs = generate.rmat(8, 8, seed=3)
        session = Session(gs, ServeConfig(
            max_batch=4, window_s=0.02, max_queue=256,
            pagerank_iters=4, mesh=MESH))
        server, _ = serve_in_thread(session, port=0)
        base = f"http://127.0.0.1:{server.server_address[1]}"

        # 5a: unarmed -> 403 (flags registry default is unset)
        os.environ.pop("LUX_PROF_DIR", None)
        try:
            status, _ = post(base, "/profilez", {"steps": 2})
        except urllib.error.HTTPError as e:
            status = e.code
        assert status == 403, f"unarmed /profilez returned {status}"

        # 5b: busy window -> 429 (deterministic: hold the capture lock)
        os.environ["LUX_PROF_DIR"] = os.path.join(work, "serve_prof")
        assert prof._capture_lock.acquire(blocking=False)
        try:
            try:
                status, _ = post(base, "/profilez", {"steps": 2})
            except urllib.error.HTTPError as e:
                status = e.code
            assert status == 429, f"busy /profilez returned {status}"
        finally:
            prof._capture_lock.release()

        # 5c: capture under a concurrent query burst — 0 failed queries
        errors = []

        def one(i):
            try:
                app = "pagerank" if i % 2 else "sssp"
                payload = {"app": app}
                if app == "sssp":
                    payload["start"] = i % gs.nv
                status, out = post(base, "/query", payload)
                assert status == 200, (status, out)
                return out
            except Exception as e:   # any failure fails the smoke
                errors.append((i, repr(e)))
                return None

        one(0)                        # warm the engines pre-burst
        one(1)
        with ThreadPoolExecutor(max_workers=6) as tp:
            futs = [tp.submit(one, i) for i in range(8)]
            prof_fut = tp.submit(post, base, "/profilez",
                                 {"steps": STEPS})
            futs += [tp.submit(one, i) for i in range(8, 12)]
            status, serve_rep = prof_fut.result()
            burst = [f.result() for f in futs]
        assert not errors, f"queries failed during capture: {errors}"
        assert status == 200, (status, serve_rep)
        serve_rep = prof.validate(serve_rep)
        check_device_math(serve_rep)
        log(f"/profilez: 200 with a validating profile.v1 under "
            f"{len(burst)} concurrent queries, 0 failed; "
            f"realized={serve_rep['realized_hidden_frac']}")
        doc["serve_capture"] = {
            "queries": len(burst), "failed": 0,
            "realized_hidden_frac": serve_rep["realized_hidden_frac"],
            "statuses": {"unarmed": 403, "busy": 429, "armed": 200},
        }

        # -- 6: /statusz budget labeling next to the realized number ----
        statusz = get(base, "/statusz")
        engblock = statusz["mesh"]["engobs"]
        labeled = {k: r for k, r in engblock.items()
                   if "exchange_hidden_frac_note" in r}
        assert labeled, (
            "LUX_ENGOBS=1 serve run produced no budget-labeled engobs "
            f"records: {engblock}")
        for kind, r in labeled.items():
            assert r["exchange_hidden_frac_note"] == \
                "budget (upper bound)", (kind, r)
            assert 0.0 <= r["realized_hidden_frac"] <= 1.0, (kind, r)
        log(f"/statusz: {len(labeled)} engobs record(s) label the "
            "budget and carry realized_hidden_frac beside it")
        doc["statusz_budget_labeled"] = len(labeled)

        server.shutdown()
        session.close()
    finally:
        del os.environ["LUX_ENGOBS"]
        os.environ.pop("LUX_PROF_DIR", None)

    shutil.rmtree(work, ignore_errors=True)
    print("prof-smoke PASS (real capture parsed, both phases tagged, "
          "zero recompiles with regions armed, /profilez guarded + "
          "concurrent-safe, budget labeled)")
    print("PROF_SMOKE " + json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
