#!/usr/bin/env python3
"""Auto-tuner smoke test (`make tune-smoke`).

End-to-end acceptance for the profile-guided auto-tuner (lux_tpu/tune)
on a 2-device virtual CPU mesh, with ``LUX_TUNE_DIR`` and
``LUX_LEDGER_DIR`` armed for the whole run:

1. **known-better selection** — successive halving over the full
   gas_sharded knob space against a seeded synthetic cost model (the
   search's injectable ``measure`` seam) in which the non-default
   compact exchange is known-better: the tuner must select it, and the
   persisted ``tuneconf.v1`` artifact must carry the full score table
   with the tuned-vs-default delta;
2. **real probes** — a second search runs real fixed-iteration probes
   (gas/bfs, tiny budget) so genuine ``tune_probe`` run-ledger records
   from more than one config cohort exist next to the ``tune_select``
   records;
3. **offline verification** — ``luxlint --tune`` over the artifact
   store exits 0 with 0 findings (LUX501-504);
4. **serving warmup applies the winner** — a mesh Session consults the
   TuneCache at warmup and builds bfs engines under the tuned compact
   exchange (engine.exchange_mode proves the overlay took); query
   replies carry ``X-Lux-Tuned`` with the artifact id; apps without an
   artifact are counted fallbacks (``lux_tune_fallback_total``), never
   silent; the sentinel-backed pool counter shows ZERO recompiles after
   warmup — the tuned path adds no per-query compiles;
5. **bitwise parity** — the tuned serving answers for bfs (integral
   depths) are bit-identical to a default-config engine run AND the
   host oracle;
6. **doctor attribution** — ``lux_doctor --tuned`` reads the probe
   ledger back and recognizes the probe cohorts as "tuned config"
   pairs (config diff entirely tuner-managed).

Prints a ``tune_smoke.v1`` JSON document on the last line.
Scale with LUX_SMOKE_SCALE (default 10).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PARTS = 2
MESH = "2"


def log(msg):
    print(f"# {msg}", flush=True)


def post(base, payload):
    req = urllib.request.Request(
        base + "/query", json.dumps(payload).encode(),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read()), dict(r.headers)


def get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as r:
        return json.loads(r.read())


def main() -> int:
    # Virtual devices must exist before the first jax backend touch —
    # the same bootstrap serve_sharded_smoke uses.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lux_tpu.utils.platform import virtual_cpu_flags

    os.environ["XLA_FLAGS"] = virtual_cpu_flags(PARTS)

    from lux_tpu.utils import flags

    with tempfile.TemporaryDirectory() as td:
        tune_dir = os.path.join(td, "tune")
        ledger_dir = os.path.join(td, "ledger")
        os.environ["LUX_TUNE_DIR"] = tune_dir
        os.environ["LUX_LEDGER_DIR"] = ledger_dir
        # A tiny real-probe budget: the smoke proves the loop closes,
        # not that the search is exhaustive. (The candidate cap is
        # tightened only around the real-probe search in step 2 — the
        # step-1 selection must see the whole knob space.)
        os.environ["LUX_TUNE_PROBE_ITERS"] = "2"
        os.environ["LUX_TUNE_RUNGS"] = "2"

        from lux_tpu.graph import generate
        from lux_tpu.models.bfs import BFS, reference_bfs
        from lux_tpu.obs import ledger, report
        from lux_tpu.tune import load, make_key, tune, tune_cache
        from lux_tpu.utils.checkpoint import fingerprint_hex

        ledger.reset()
        scale = flags.get_int("LUX_SMOKE_SCALE")
        g = generate.rmat(scale, 8, seed=3)
        fp = fingerprint_hex(g)
        device_kind = report.device_profile()["device_kind"]
        tc = tune_cache()
        assert tc.enabled(), "LUX_TUNE_DIR armed above"
        log(f"rmat scale={scale} (nv={g.nv} ne={g.ne}) fp={fp[:12]}.. "
            f"device_kind={device_kind}, tune store {tune_dir}")

        # -- 1. known-better selection over the full knob space ---------
        # Seeded synthetic cost model through the search's injectable
        # measure seam: compact exchange is known-better, full is the
        # default, frontier sits between. The tuner must find compact —
        # deterministically, per LUX_TUNE_SEED (timing a 2-part CPU mesh
        # would make the smoke a coin flip; engine-level phase
        # measurement is exercised by the real probes in step 2).
        assert flags.default("LUX_EXCHANGE") == "full", \
            "smoke assumes full is the default exchange mode"
        base_cost = {"full": 4.0, "compact": 1.0, "frontier": 2.0}

        def measure(cand, iters, rung):
            c = base_cost[cand.get("LUX_EXCHANGE", "full")]
            # Deterministic sub-costs so the score table totally orders.
            c += 0.01 * float(cand.get("LUX_GAS_DENSITY_HI", "0.0625"))
            c += 0.001 * float(cand.get("LUX_GAS_DENSITY_LO", "0.005"))
            return c

        art = tune(g, BFS(), "gas_sharded", program_name="bfs",
                   graph_fingerprint=fp, mesh_shape=MESH,
                   device_kind=device_kind, init_kw={"start": 0},
                   measure=measure)
        assert art["config"]["LUX_EXCHANGE"] == "compact", (
            "tuner must select the known-better non-default exchange",
            art["config"])
        defaults = [r for r in art["score_table"]
                    if r["candidate_index"] == 0]
        assert defaults and defaults[-1]["score"] > art["score"], \
            "score table must carry the tuned-vs-default delta"
        tc.put(art)
        reloaded = load(tune_dir, art["key"])
        assert reloaded is not None and reloaded["id"] == art["id"]
        log(f"selection ok: {art['id']} picked LUX_EXCHANGE=compact over "
            f"default full ({art['score']:.3g} vs "
            f"{defaults[-1]['score']:.3g} s/iter, "
            f"{len(art['score_table'])} probes)")

        # -- 2. real probes feed the run ledger -------------------------
        with flags.overrides({"LUX_TUNE_MAX_CANDIDATES": "3"}):
            art_real = tune(g, BFS(), "gas", program_name="bfs",
                            graph_fingerprint=fp, mesh_shape="1",
                            device_kind=device_kind,
                            init_kw={"start": 0})
        assert art_real["probe_ledger_ids"], \
            "real probes must land runrec.v1 records"
        tc.put(art_real)
        recs = ledger.read_all(ledger_dir, strict=True)
        kinds = sorted({r["kind"] for r in recs})
        probe_hashes = {r["key"]["config_hash"] for r in recs
                        if r["kind"] == "tune_probe"}
        assert "tune_probe" in kinds and "tune_select" in kinds, kinds
        assert len(probe_hashes) >= 2, \
            "probes under different overlays must form distinct cohorts"
        log(f"real probes ok: {art_real['id']} from "
            f"{len(art_real['probe_ledger_ids'])} ledger'd probes, "
            f"{len(probe_hashes)} config cohorts")

        # -- 3. luxlint --tune verifies the store offline ---------------
        lint = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "luxlint.py"),
             "--tune", tune_dir],
            capture_output=True, text=True)
        assert lint.returncode == 0, (lint.returncode, lint.stdout[-800:])
        summary_line = [ln for ln in lint.stdout.splitlines()
                        if ln.startswith("LUXLINT ")][-1]
        lint_doc = json.loads(summary_line[len("LUXLINT "):])
        assert lint_doc["schema"] == "luxlint-tune.v1", lint_doc
        assert lint_doc["findings"] == 0 and lint_doc["files"] == 2, \
            lint_doc
        log(f"luxlint --tune ok: {lint_doc['files']} artifacts, "
            "0 findings")

        # -- 4. serving warmup applies the winner -----------------------
        from lux_tpu.serve import ServeConfig, Session
        from lux_tpu.serve.http import serve_in_thread

        session = Session(g, ServeConfig(max_batch=4, window_s=0.05,
                                         max_queue=128, pagerank_iters=4,
                                         mesh=MESH))
        server, _ = serve_in_thread(session, port=0)
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            prov = session.tuned_for("bfs")
            assert prov and prov["id"] == art["id"], (prov, art["id"])
            engine = session._gas_single("bfs")
            assert engine.exchange_mode == "compact", (
                "warmup must build bfs under the tuned overlay",
                engine.exchange_mode)
            statusz = session.statusz()
            tb = statusz["tune"]
            assert tb["armed"] and "bfs" in tb["artifacts"], tb
            assert tb["artifacts"]["bfs"]["id"] == art["id"], tb
            assert tb["artifacts"]["bfs"]["probes"] == \
                len(art["score_table"]), tb
            assert tb["fallbacks"], \
                "apps without an artifact must show as counted fallbacks"
            fb = sum(
                m["value"] for m in get(base, "/metrics.json")["metrics"]
                if m["name"] == "lux_tune_fallback_total")
            assert fb >= len(tb["fallbacks"]) > 0, (fb, tb["fallbacks"])
            log(f"warmup ok: bfs serves {art['id']} "
                f"(exchange_mode=compact), {len(tb['fallbacks'])} "
                f"counted fallback app(s), fallback_total={int(fb)}")

            # Tuned replies carry provenance; untuned ones must not.
            roots = [1, 5, 9]
            tuned_vals = {}
            for r in roots:
                out, hdr = post(base, {"app": "bfs", "start": r,
                                       "full": True})
                assert hdr.get("X-Lux-Tuned") == art["id"], hdr
                tuned_vals[r] = np.asarray(out["values"], np.int64)
            _pr, hdr = post(base, {"app": "pagerank"})
            assert "X-Lux-Tuned" not in hdr, \
                "fallback apps must not claim tune provenance"
            recompiles = get(base, "/stats")["pool"]["recompiles"]
            assert recompiles == 0, \
                f"tuned path added {recompiles} per-query recompiles"
            log(f"serve ok: {len(roots)} bfs queries with X-Lux-Tuned, "
                "0 recompiles after warmup")

            # -- 5. bitwise parity vs default config + oracle -----------
            from lux_tpu.analysis.ir import build_executor

            default_ex = build_executor("gas_sharded", g, BFS())
            assert default_ex.exchange_mode == "full", \
                default_ex.exchange_mode
            for r in roots:
                st, _ = default_ex.run(start=r)
                np.testing.assert_array_equal(
                    tuned_vals[r],
                    np.asarray(default_ex.gather_values(st), np.int64))
                depth, _parent = reference_bfs(g, r)
                np.testing.assert_array_equal(
                    tuned_vals[r], np.asarray(depth, np.int64))
            log("parity ok: tuned bfs bitwise == default-config engine "
                "== host oracle")
        finally:
            server.shutdown()
            session.close()

        # -- 6. the doctor attributes the tuned cohorts -----------------
        doc_proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lux_doctor.py"),
             "--tuned", "--json", "--dir", ledger_dir],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert doc_proc.returncode in (0, 3), (doc_proc.returncode,
                                               doc_proc.stderr[-800:])
        doctor = json.loads(doc_proc.stdout)
        tuned_pairs = [p for p in doctor["pairs"] if p.get("tuned_config")]
        assert tuned_pairs, (
            "doctor must recognize the probe cohorts as tuned-config "
            "pairs", [p.get("config_diff") for p in doctor["pairs"]])
        log(f"doctor ok: {len(tuned_pairs)}/{len(doctor['pairs'])} "
            "pair(s) attributed to the tuned config")

        os.environ.pop("LUX_TUNE_DIR", None)
        os.environ.pop("LUX_LEDGER_DIR", None)
        tc.clear()
        ledger.reset()

        print(json.dumps({
            "schema": "tune_smoke.v1",
            "ok": True,
            "scale": scale,
            "mesh": MESH,
            "winner": art["config"],
            "winner_id": art["id"],
            "default_score": defaults[-1]["score"],
            "tuned_score": art["score"],
            "real_probe_records": len(art_real["probe_ledger_ids"]),
            "probe_cohorts": len(probe_hashes),
            "lint_findings": lint_doc["findings"],
            "recompiles": recompiles,
            "fallback_total": int(fb),
            "doctor_tuned_pairs": len(tuned_pairs),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
