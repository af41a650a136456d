#!/usr/bin/env python3
"""Headline benchmark + suite. Prints ONE JSON line.

Headline: PageRank GTEPS on R-MAT scale-22, one TPU chip (the
adversarial Kronecker-uniform workload — see PERF_NOTES.md's hardware-floor
analysis). The ``suite`` key carries single-chip stand-ins for the
remaining BASELINE.json configs (the reference's graphs are not
downloadable here — BASELINE.md):

- pagerank_smallworld22: locality-rich stand-in for the web/social
  configs (Hollywood/Indochina; real graphs cluster, R-MAT's tail does
  not) — same nv/ne as the headline graph.
- sssp_rmat22: the push engine to fixpoint (config 3's shape).
- cc_rmat22: Connected Components on the undirected closure (config 2).
- cf_bipartite: NetFlix-shaped weighted bipartite SGD (config 4),
  exercising the edge-chunked engine (flat contributions exceed HBM).

Baseline derivation: the reference publishes no numbers (BASELINE.md);
its VLDB'17 paper's 8-GPU Twitter-2010 PageRank throughput is on the
order of 10 GTEPS. BASELINE.json's north star is ">=1x the 8xV100
GTEPS on Twitter-2010 PageRank on v5e-8"; this bench runs on ONE v5e
chip, so vs_baseline compares against BASELINE_GTEPS / 8 (the per-GPU
share; see BASELINE.md for the sensitivity discussion).

Output contract (the driver parses stdout): the headline JSON line is
printed IMMEDIATELY after the headline measurement — before the suite
runs — so a timeout mid-suite can never erase the round's number (the
round-2 failure mode: rc=124 with the only print at the very end). If
the suite completes, a second, enriched JSON line with the suite
attached is printed (both lines share the headline schema, so either
first-line or last-line parsing yields a valid result), and the suite
is also written to ``BENCH_SUITE.json`` next to this script. Suite
items run under a wall-clock deadline and are skipped (recorded as
``{"skipped": ...}``) rather than risking the driver's budget.

Knobs (env): LUX_BENCH_SCALE (22), LUX_BENCH_EF (16), LUX_BENCH_ITERS
(50), LUX_BENCH_CACHE (.bench_cache), LUX_BENCH_LAYOUT (tiled|flat),
LUX_BENCH_LEVELS ("8/2"), LUX_BENCH_TILE_MB (8192), LUX_BENCH_SUITE
(1; 0 = headline only), LUX_BENCH_DEADLINE (480 — total seconds of
wall clock after which remaining suite items are skipped),
LUX_GROUPED_TAIL (0; 1 = tiled layout runs the source-block-grouped
merge-network tail instead of lane-select — see PERF_NOTES.md round-5 and
`make merge-smoke`).

``--profile``: wrap the headline run in a device-timeline capture
window (obs/prof.py) under LUX_PROF_DIR (default
``<cache>/profile``), parse it into a ``profile.v1`` report
(realized_hidden_frac, per-device phase split), log the table, and
write ``profile_v1.json`` next to the trace. A profiled run's GTEPS is
overlap evidence, not a headline record — the capture perturbs the
measurement (PERF_NOTES.md evidence policy v4).

``--tuned``: GAS suite entries additionally run under their TuneCache
winner (lux_tpu/tune; searched and persisted under ``LUX_TUNE_DIR`` on
first use), emitting ``<name>_tuned`` rows next to the default rows in
the same artifact. The headline JSON carries ``tuned: true/false`` and
the gate context records it (tools/bench_gate.py), so tuned and
default rounds never ratchet against each other.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lux_tpu.obs import (  # noqa: E402
    IterationRecorder, gteps as lux_gteps, ledger,
)

BASELINE_GTEPS = 10.0      # assumed 8xV100 Twitter-2010 PageRank (see above)
PER_CHIP_BASELINE = BASELINE_GTEPS / 8.0


def log(msg: str):
    print(f"# {msg}", file=sys.stderr, flush=True)


class SkipItem(Exception):
    """Raised inside a suite item to record it as skipped (with reason)
    instead of failed."""


def cached_graph(cache_dir: str, name: str, build, remaining: float = 1e9,
                 gen_cost: float = 0.0):
    """Load ``name`` from the bench cache, else generate it — but only
    when ``remaining`` budget covers the estimated first-run ``gen_cost``
    (generation runs on a 2-core host and is the suite's long pole; an
    item must skip cleanly rather than blow the driver's budget
    mid-generation)."""
    from lux_tpu.graph import read_lux, write_lux

    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, name + ".lux")
    if os.path.exists(path):
        t0 = time.time()
        g = read_lux(path)
        log(f"loaded cached {path} in {time.time()-t0:.1f}s")
        return g
    if remaining < gen_cost:
        raise SkipItem(
            f"{name} not cached and est. generation {gen_cost:.0f}s > "
            f"{remaining:.0f}s of remaining budget"
        )
    t0 = time.time()
    g = build()
    log(f"generated {name} in {time.time()-t0:.1f}s")
    write_lux(path, g)
    return g


def _git_head() -> str:
    import subprocess

    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
        ).stdout.strip()
    except Exception:
        return "unknown"


def compact_telemetry(summary: dict) -> dict:
    """The run summary with floats rounded for the one-line JSON
    contract (full precision lives in the LUX_METRICS dump)."""
    out = {
        "engine": summary["engine"],
        "num_iters": summary["num_iters"],
        "compile_s": round(summary["compile_s"], 4),
        "execute_s": round(summary["execute_s"], 6),
        "gteps": round(summary["gteps"], 4),
        "iterations": [
            {
                "iter": r["iter"],
                "t_iter_s": round(r["t_iter_s"], 7),
                "t_cum_s": round(r["t_cum_s"], 6),
                **({"frontier": r["frontier"]} if "frontier" in r else {}),
            }
            for r in summary["iterations"]
        ],
    }
    if summary.get("exchange_bytes_per_iter"):
        out["exchange_bytes_per_iter"] = summary["exchange_bytes_per_iter"]
    return out


def tiled_bytes_per_iter(plan, nv: int) -> int:
    """Primary per-iteration HBM byte streams of the tiled executor."""
    tail_edges = plan.tail_sb.shape[0]
    nrb_rows = sum(plan.nvb * (128 // lev.r) for lev in plan.levels)
    return (
        plan.strip_bytes                      # int8 strip reads
        + plan.num_strips * 512               # x-block row gather per strip
        + tail_edges * (512 + 5)              # tail row gather + sb/lane
        + (nv + 1 + nrb_rows) * 2 * 512       # boundary extraction gathers
        + 4 * nv * 4                          # apply + output passes
    )


def bench_pagerank(g, cache: str, tag: str, iters: int, layout: str,
                   levels, budget: int, profile_dir: str = None):
    from lux_tpu.engine.pull import PullExecutor, hard_sync
    from lux_tpu.models import PageRank
    from lux_tpu.obs import prof, report

    if layout == "tiled":
        from lux_tpu.engine.tiled import TiledPullExecutor, get_cached_plan

        lev_tag = "_".join(f"{r}x{t}" for r, t in levels)
        plan_path = os.path.join(
            cache, f"plan_{tag}_{lev_tag}_{budget >> 20}.luxplan"
        )
        t0 = time.time()
        plan = get_cached_plan(
            g, plan_path, levels=levels, budget_bytes=budget, log=log
        )
        log(f"plan ready ({lev_tag}) in {time.time()-t0:.1f}s")
        ex = TiledPullExecutor(g, PageRank(), plan=plan)
        log(
            f"{tag} hybrid plan: {plan.num_strips} strips "
            f"({plan.strip_bytes/1e9:.2f} GB), coverage={plan.coverage:.1%}"
        )
        bytes_iter = tiled_bytes_per_iter(plan, g.nv)
    else:
        ex = PullExecutor(g, PageRank())
        bytes_iter = g.ne * (512 + 8) + 4 * g.nv * 4
    ex.warmup()

    # Timed: `iters` iterations, async-pipelined, one hard sync at the end
    # (the reference's measurement discipline, pagerank.cc:106-118).
    # The second settle run
    # goes through the vals= path so every jitted helper compiles first.
    vals = hard_sync(ex.run(1, flush_every=0))
    vals = hard_sync(ex.run(1, vals=vals, flush_every=0))
    # Explicit recorder: the headline run always carries its iteration
    # telemetry into the JSON line (LUX_METRICS/LUX_TRACE additionally
    # dump it when set). The recorder's execute_s is the measurement —
    # the external bracket would include the recorder's zero-trip
    # compile probe.
    rec = IterationRecorder(
        "tiled" if layout == "tiled" else "pull",
        int(g.nv), int(g.ne), program="PageRank",
    )
    t0 = time.perf_counter()
    # --profile wraps THE headline run in a capture window (a profiled
    # number is a number you can explain; the capture itself perturbs
    # the measurement, so a profiled run's GTEPS is evidence about
    # overlap, not the headline record).
    with prof.trace(profile_dir):
        vals = ex.run(iters, vals=vals, flush_every=0, recorder=rec)
    elapsed = time.perf_counter() - t0
    telemetry = rec.summary()
    if telemetry["execute_s"] > 0:
        elapsed = telemetry["execute_s"]

    gteps = lux_gteps(g.ne, iters, elapsed)
    gbps = bytes_iter * iters / elapsed / 1e9
    log(
        f"{tag}: nv={g.nv} ne={g.ne} iters={iters} elapsed={elapsed:.4f}s "
        f"({elapsed/iters*1e3:.2f} ms/iter, {gteps:.3f} GTEPS, "
        f"{gbps:.0f} GB/s)"
    )
    peak = report.device_profile()["hbm_peak_gbps"]
    out = {
        "gteps": round(gteps, 4),
        "ms_per_iter": round(elapsed / iters * 1e3, 2),
        "achieved_gbps": round(gbps, 1),
        "hbm_peak_frac": round(gbps / peak, 3) if peak else None,
        "telemetry": compact_telemetry(telemetry),
    }
    if profile_dir:
        try:
            rep = prof.parse_dir(profile_dir, steps=iters,
                                 iterlog_summary=telemetry)
            path = os.path.join(profile_dir, "profile_v1.json")
            with open(path, "w") as f:
                json.dump(rep, f, indent=1)
            log(f"profile.v1 -> {path}")
            for line in prof.format_report(rep).splitlines():
                log(line)
            out["profile"] = {
                "realized_hidden_frac": rep["realized_hidden_frac"],
                "path": path,
            }
        except prof.ProfileParseError as e:
            log(f"profile parse failed: {e}")
    return out


def bench_push(g, program, tag: str, max_iters: int, **init_kw):
    """Shared push-app fixpoint bench (SSSP, CC): one timing/GTEPS
    discipline for both."""
    from lux_tpu.engine.push import PushExecutor

    ex = PushExecutor(g, program)
    ex.warmup(**init_kw)
    t0 = time.perf_counter()
    state, iters = ex.run(max_iters=max_iters, **init_kw)
    elapsed = time.perf_counter() - t0
    gteps = lux_gteps(g.ne, iters, elapsed)
    log(
        f"{tag}: {iters} iters ({ex.sparse_iters} sparse) in "
        f"{elapsed:.2f}s ({gteps:.3f} GTEPS)"
    )
    return {
        "gteps": round(gteps, 4),
        "iters": iters,
        "sparse_iters": ex.sparse_iters,
        "ms_per_iter": round(elapsed / max(iters, 1) * 1e3, 2),
    }


def bench_sssp(g, max_iters: int = 12):
    from lux_tpu.models.sssp import SSSP

    return bench_push(g, SSSP(), "sssp", max_iters, start=0)


def bench_cc(g):
    from lux_tpu.models.components import ConnectedComponents

    return bench_push(g, ConnectedComponents(), "cc", 32)


def bench_gas(g, program, tag: str, max_iters: int, **init_kw):
    """Shared GAS-app fixpoint bench (BFS, delta-SSSP, label
    propagation, k-core): the push-bench timing discipline through the
    direction-adaptive executor."""
    from lux_tpu.engine.gas import AdaptiveExecutor

    ex = AdaptiveExecutor(g, program)
    ex.warmup(**init_kw)
    t0 = time.perf_counter()
    state, iters = ex.run(max_iters=max_iters, **init_kw)
    elapsed = time.perf_counter() - t0
    gteps = lux_gteps(g.ne, iters, elapsed)
    log(
        f"{tag}: {iters} iters ({ex.push_iters} push/{ex.pull_iters} "
        f"pull, {ex.direction_switches} switches) in {elapsed:.2f}s "
        f"({gteps:.3f} GTEPS)"
    )
    return {
        "gteps": round(gteps, 4),
        "iters": iters,
        "push_iters": ex.push_iters,
        "direction_switches": ex.direction_switches,
        "ms_per_iter": round(elapsed / max(iters, 1) * 1e3, 2),
    }


def bench_gas_tuned(g, program, app: str, max_iters: int, **init_kw):
    """The bench_gas measurement with engines built under the TuneCache
    winner for (g, app) — searched and persisted on first use, reused
    from the artifact store after. Emitted NEXT TO the default row so
    tuned-vs-default is one artifact; the gate context carries
    ``tuned: true`` so these rounds never ratchet against default ones
    (tools/bench_gate.py)."""
    from lux_tpu.engine.gas import as_gas
    from lux_tpu.obs import report
    from lux_tpu.tune import make_key, tune, tune_cache
    from lux_tpu.utils import flags
    from lux_tpu.utils.checkpoint import fingerprint_hex

    tc = tune_cache()
    if not tc.enabled():
        raise SkipItem("--tuned needs LUX_TUNE_DIR for the artifact store")
    fp = fingerprint_hex(g)
    key = make_key(fp, app, "gas", "1",
                   report.device_profile()["device_kind"])
    art = tc.get(key)
    if art is None:
        log(f"{app}: no tuneconf.v1 for {fp[:12]}..; searching")
        t0 = time.time()
        art = tune(g, as_gas(program), "gas", program_name=app,
                   graph_fingerprint=fp, init_kw=init_kw)
        tc.put(art)
        log(f"{app}: searched {art['id']} in {time.time()-t0:.1f}s")
    log(f"{app}: tuned config {art['id']} score={art['score']:.4g}s/iter "
        f"{art['config']}")
    with flags.overrides(art["config"]):
        res = bench_gas(g, program, f"{app}_tuned", max_iters, **init_kw)
    res["tune_artifact"] = art["id"]
    res["tune_config"] = art["config"]
    return res


def bench_gas_sharded(g, program, tag: str, max_iters: int, **init_kw):
    """Direction-adaptive GAS over the full device mesh (the sharded
    form of bench_gas, LUX_EXCHANGE-sensitive — the gate context keys
    on the mode). Skipped on a single device, where the exchange is
    inert and the number would just alias bench_gas."""
    import jax

    from lux_tpu.engine.gas_sharded import ShardedAdaptiveExecutor

    if jax.device_count() < 2:
        raise SkipItem("needs >= 2 devices for a sharded mesh")
    ex = ShardedAdaptiveExecutor(g, program,
                                 num_parts=jax.device_count())
    ex.warmup(**init_kw)
    t0 = time.perf_counter()
    state, iters = ex.run(max_iters=max_iters, **init_kw)
    elapsed = time.perf_counter() - t0
    gteps = lux_gteps(g.ne, iters, elapsed)
    log(
        f"{tag}: P={ex.num_parts} exchange={ex.exchange_mode}: {iters} "
        f"iters ({ex.push_iters} push/{ex.pull_iters} pull, "
        f"{ex.direction_switches} switches, {ex.exchange_downgrades} "
        f"downgrades) in {elapsed:.2f}s ({gteps:.3f} GTEPS)"
    )
    return {
        "gteps": round(gteps, 4),
        "iters": iters,
        "push_iters": ex.push_iters,
        "direction_switches": ex.direction_switches,
        "exchange_downgrades": ex.exchange_downgrades,
        "exchange_mode": ex.exchange_mode,
        "exchange_bytes_per_iter": ex.exchange_bytes_per_iter(),
        "ms_per_iter": round(elapsed / max(iters, 1) * 1e3, 2),
    }


def bench_cf(g, iters: int = 5):
    from lux_tpu.engine.pull import PullExecutor, hard_sync
    from lux_tpu.models.colfilter import CollaborativeFiltering

    ex = PullExecutor(g, CollaborativeFiltering())
    log(f"cf: edge_chunk={ex.edge_chunk}")
    ex.warmup()
    vals = hard_sync(ex.run(1, flush_every=0))
    t0 = time.perf_counter()
    vals = ex.run(iters, vals=vals, flush_every=0)
    elapsed = time.perf_counter() - t0
    gteps = lux_gteps(g.ne, iters, elapsed)
    log(
        f"cf: nv={g.nv} ne={g.ne} {iters} iters, "
        f"{elapsed/iters*1e3:.1f} ms/iter ({gteps:.3f} GTEPS)"
    )
    return {
        "gteps": round(gteps, 4),
        "ms_per_iter": round(elapsed / iters * 1e3, 2),
        "edge_chunked": bool(ex.edge_chunk),
    }


def main():
    t_start = time.monotonic()
    from lux_tpu.utils import flags

    scale = flags.get_int("LUX_BENCH_SCALE")
    ef = flags.get_int("LUX_BENCH_EF")
    iters = flags.get_int("LUX_BENCH_ITERS")
    cache = flags.get("LUX_BENCH_CACHE") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".bench_cache"
    )
    layout = flags.get("LUX_BENCH_LAYOUT")
    if layout not in ("tiled", "flat"):
        raise SystemExit(f"LUX_BENCH_LAYOUT must be tiled|flat, got {layout!r}")
    budget = flags.get_int("LUX_BENCH_TILE_MB") << 20
    levels = tuple(
        tuple(int(v) for v in part.split("/"))
        for part in flags.get("LUX_BENCH_LEVELS").split(",")
    )
    run_suite = flags.get_bool("LUX_BENCH_SUITE")
    deadline = flags.get_float("LUX_BENCH_DEADLINE")

    profile_dir = None
    if "--profile" in sys.argv[1:]:
        profile_dir = flags.get("LUX_PROF_DIR") or os.path.join(
            cache, "profile")
        log(f"profiling the headline run -> {profile_dir}")
    # --tuned: GAS suite entries additionally run under their TuneCache
    # winner (lux_tpu/tune), tuned rows next to the default ones in the
    # same artifact. The headline JSON carries tuned: true/false so the
    # gate never ratchets tuned and default rounds against each other.
    tuned_mode = "--tuned" in sys.argv[1:]
    if tuned_mode and not flags.get("LUX_TUNE_DIR"):
        raise SystemExit("--tuned needs LUX_TUNE_DIR (the tuneconf.v1 "
                         "artifact store)")

    import jax

    from lux_tpu.utils.platform import enable_compile_cache

    # Persistent compile cache: the tiled executor's compiles are the
    # long pole of a cold run; cached executables cut reruns to seconds.
    log(f"compile cache: {enable_compile_cache()}")
    log(f"platform: {jax.devices()[0].platform}")
    from lux_tpu.obs import report as obs_report

    # Chip identity for the gate's context block: baselines recorded on
    # a different device_kind never ratchet this run (tools/bench_gate.py).
    log(f"device_kind: {obs_report.device_profile()['device_kind']}")

    from lux_tpu.graph import generate

    g = cached_graph(
        cache, f"rmat{scale}_{ef}",
        lambda: generate.rmat(scale, ef, seed=42),
    )
    head = bench_pagerank(
        g, cache, f"rmat{scale}_{ef}", iters, layout, levels, budget,
        profile_dir=profile_dir,
    )

    out = {
        "metric": f"pagerank_rmat{scale}_gteps_1chip",
        "value": head["gteps"],
        "unit": "GTEPS",
        "vs_baseline": round(head["gteps"] / PER_CHIP_BASELINE, 4),
        "layout": layout,
        "achieved_gbps": head["achieved_gbps"],
        "hbm_peak_frac": head["hbm_peak_frac"],
        "tuned": tuned_mode,
        # Iteration telemetry of THE headline measurement (per-iteration
        # walls + compile/execute split), so the round artifact shows
        # not just the number but where the time went.
        "telemetry": head.get("telemetry"),
    }
    # The round's number goes out BEFORE the suite runs (see module
    # docstring) — mirrors the reference's always-printed ELAPSED TIME
    # (pagerank/pagerank.cc:115-118).
    print(json.dumps(out), flush=True)

    # Durable evidence: the headline as one runrec.v1 observation (the
    # A/B corpus tools/lux_doctor.py attributes regressions from). The
    # headline recorder goes through summary(), not finish(), so the
    # report.finalize feed-in never fires for it — this is its only
    # ledger entry. rmat{scale}_{ef} is a deterministic seeded graph, a
    # faithful fingerprint.
    tel = head.get("telemetry") or {}
    ledger.record_run(
        "bench_headline",
        {"gteps": head["gteps"], "achieved_gbps": head["achieved_gbps"],
         "hbm_peak_frac": head["hbm_peak_frac"],
         "compile_s": tel.get("compile_s"),
         "execute_s": tel.get("execute_s"),
         "nv": int(g.nv), "ne": int(g.ne)},
        graph_fingerprint=f"rmat{scale}_{ef}",
        program="PageRank", engine_kind=layout,
    )

    if run_suite:
        suite = {}

        def remaining():
            return deadline - (time.monotonic() - t_start)

        def suite_item(name, fn):
            if remaining() < 0:
                log(f"suite[{name}] skipped: past the "
                    f"{deadline:.0f}s deadline")
                suite[name] = {"skipped": "deadline"}
                return
            try:
                res = fn()
                # Suite items stay lean — full telemetry rides only on
                # the headline (and in LUX_METRICS dumps when set).
                res.pop("telemetry", None)
                suite[name] = res
                ledger.record_run(
                    "bench_suite",
                    {k: v for k, v in res.items()
                     if isinstance(v, (int, float))},
                    graph_fingerprint=f"suite-rmat{scale}_{ef}",
                    program=name, engine_kind=layout,
                )
            except SkipItem as e:
                log(f"suite[{name}] skipped: {e}")
                suite[name] = {"skipped": str(e)}
            except Exception as e:  # a broken suite item must not kill
                log(f"suite[{name}] FAILED: {e!r}")  # the gate
                suite[name] = {"error": repr(e)}

        # First-run generation cost estimates (2-core host, measured
        # order of magnitude at scale 22) for the budget gate.
        gen_cost = 60.0 * (1 << scale) / (1 << 22)

        def run_smallworld():
            nv_sw = 1 << scale
            g_sw = cached_graph(
                cache, f"smallworld{scale}_{ef}",
                lambda: generate.small_world(
                    nv_sw, k=ef, p_rewire=0.05, seed=7
                ),
                remaining=remaining(), gen_cost=gen_cost,
            )
            return bench_pagerank(
                g_sw, cache, f"smallworld{scale}_{ef}", iters, layout,
                levels, budget,
            )

        def run_cf():
            # NetFlix-shaped at the default scale (480K users x 17.8K
            # items x 50M ratings x 2 directions = 100M edges); shrinks
            # with LUX_BENCH_SCALE so smoke runs stay quick.
            n_users = min(480_000, 1 << max(scale - 3, 1))
            n_items = max(n_users // 27, 64)
            n_ratings = 12 << scale
            g_cf = cached_graph(
                cache, f"cf_netflix_like_{scale}",
                lambda: generate.bipartite_ratings(
                    n_users, n_items, n_ratings, seed=11
                ),
                remaining=remaining(), gen_cost=2 * gen_cost,
            )
            return bench_cf(g_cf)

        def run_cc():
            # Connected Components runs on the undirected closure (the
            # reference's example feeds CC an undirected graph and its
            # max-label propagation assumes symmetry — components.py).
            g_u = cached_graph(
                cache, f"rmat{scale}_{ef}_undirected",
                lambda: generate.undirected(g),
                remaining=remaining(), gen_cost=2 * gen_cost,
            )
            return bench_cc(g_u)

        def run_sssp_delta():
            from lux_tpu.models.sssp_delta import DeltaSSSP

            g_w = cached_graph(
                cache, f"rmat{scale}_{ef}_weighted",
                lambda: generate.rmat(scale, ef, seed=42, weighted=True),
                remaining=remaining(), gen_cost=gen_cost,
            )
            return bench_gas(g_w, DeltaSSSP(), "sssp_delta", 32, start=0)

        def run_bfs():
            from lux_tpu.models.bfs import BFS

            return bench_gas(g, BFS(), "bfs", 32, start=0)

        def run_labelprop():
            from lux_tpu.models.labelprop import LabelPropagation

            return bench_gas(g, LabelPropagation(), "labelprop", 16)

        def run_kcore():
            from lux_tpu.models.kcore import KCore

            # Coreness is an undirected notion — reuse the CC closure
            # (cache hit after cc_rmat generates it).
            g_u = cached_graph(
                cache, f"rmat{scale}_{ef}_undirected",
                lambda: generate.undirected(g),
                remaining=remaining(), gen_cost=2 * gen_cost,
            )
            return bench_gas(g_u, KCore(k=4), "kcore", 32)

        suite_item("sssp_rmat", lambda: bench_sssp(g))
        suite_item("pagerank_smallworld", run_smallworld)
        suite_item("cc_rmat", run_cc)
        suite_item("cf_bipartite", run_cf)
        # GAS-engine apps (PR 12) join the ratchet so direction-adaptive
        # regressions gate like everything else.
        suite_item("bfs_rmat", run_bfs)
        suite_item("sssp_delta_rmat", run_sssp_delta)
        suite_item("labelprop_rmat", run_labelprop)
        suite_item("kcore_rmat", run_kcore)
        if tuned_mode:
            # Tuned rows ride the same suite (and the same ledger), so
            # one artifact answers "what did the tuner buy" per app.
            from lux_tpu.models.bfs import BFS
            from lux_tpu.models.labelprop import LabelPropagation

            suite_item("bfs_rmat_tuned",
                       lambda: bench_gas_tuned(g, BFS(), "bfs", 32,
                                               start=0))
            suite_item("labelprop_rmat_tuned",
                       lambda: bench_gas_tuned(g, LabelPropagation(),
                                               "labelprop", 16))
        # Mesh GAS (PR 17): the direction-adaptive engine over every
        # available device; runs only on a real multi-device backend
        # (virtual-CPU mesh evidence lives in `make gas-sharded-smoke`
        # and tools/bench_sharded.py — wall time there measures
        # dispatch, not scaling).
        def run_bfs_sharded():
            from lux_tpu.models.bfs import BFS

            return bench_gas_sharded(g, BFS(), "bfs_sharded", 32,
                                     start=0)

        suite_item("bfs_sharded_rmat", run_bfs_sharded)
        # Deadline-skipped items fall back to the most recent completed
        # measurement of the SAME code (git HEAD match), clearly labeled
        # — upload/compile throughput varies run to run, and a skip
        # would otherwise erase a measured capability from the round
        # artifact.
        head = _git_head()
        prior = {}
        cache_f = os.path.join(cache, "suite_results.json")
        try:
            with open(cache_f) as f:
                prior = json.load(f)
        except Exception:
            prior = {}
        for name, res in suite.items():
            key = f"{name}@{scale}_{ef}_{layout}"
            if "gteps" in res:
                prior[key] = {
                    "head": head, "at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                      time.gmtime()),
                    "result": res,
                }
            elif "skipped" in res and prior.get(key, {}).get("head") == head:
                suite[name] = dict(
                    prior[key]["result"],
                    cached_same_commit_run=prior[key]["at"],
                )
        try:
            with open(cache_f, "w") as f:
                json.dump(prior, f, indent=1)
        except OSError:
            pass
        out["suite"] = suite
        # Co-headline (VERDICT r2 #9): the locality-rich counterpart to
        # the adversarial Kronecker headline, surfaced at top level.
        sw = suite.get("pagerank_smallworld", {})
        if "gteps" in sw:
            out["smallworld_gteps"] = sw["gteps"]

        side = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_SUITE.json"
        )
        try:
            with open(side, "w") as f:
                json.dump(out, f, indent=1)
        except OSError as e:
            log(f"could not write {side}: {e}")
        # Enriched final line, same schema as the first — a parser taking
        # either the first or the last JSON line gets a valid headline.
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
