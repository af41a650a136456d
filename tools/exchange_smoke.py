#!/usr/bin/env python3
"""Compacted-exchange smoke test (`make exchange-smoke`).

End-to-end acceptance run for the needed-rows compacted exchange
(ISSUE 13), on a 2x4 virtual CPU mesh (8 XLA host devices — the same
trick the serving smoke uses, so this runs in CI with no TPU):

1. generate a halo-exchange locality graph (uniform per-pair needed
   rows — the regime the compaction targets) and run SSSP (sharded
   push) and PageRank (sharded pull) under LUX_EXCHANGE=full and
   LUX_EXCHANGE=compact;
2. prove parity: both apps BIT-IDENTICAL between the two modes (the
   local/remote select happens before the unchanged segment reduction,
   so even float sum order is preserved);
3. prove the ledger: ``exchange_bytes_per_iter`` drops >= 5x under
   compact (SSSP's per-iteration exchange is static, so the late
   frontier-sparse tail pays the same compacted bytes as iteration 1),
   with useful_ratio >= 0.8 compact where full prices < 0.3;
4. prove the zero-recompile contract: warm re-runs of every engine
   trace nothing (RecompileSentinel, expect windows only around builds
   and first runs);
5. prove observability: a phase-fenced LUX_ENGOBS=1 run of the compact
   engines reports ``exchange_hidden_frac`` (the overlap budget).

Prints an ``exchange_smoke.v1`` JSON document on the last line.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MESH = "2x4"
PARTS = 8
BLOCK_SPAN = 512
HUBS = 23          # per-pair needed rows; 23 express + 1 chain-boundary
PR_ITERS = 8       # fixed-iteration pagerank parity run
DROP_FLOOR = 5.0   # required full/compact exchange-bytes ratio


def log(msg):
    print(f"# {msg}", flush=True)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lux_tpu.utils.platform import virtual_cpu_flags

    os.environ["XLA_FLAGS"] = virtual_cpu_flags(PARTS)

    from lux_tpu.analysis.sentinel import RecompileSentinel
    from lux_tpu.engine.pull_sharded import ShardedPullExecutor
    from lux_tpu.engine.push import ShardedPushExecutor
    from lux_tpu.graph import generate
    from lux_tpu.models import PageRank, SSSP
    from lux_tpu.obs import engobs
    from lux_tpu.parallel.mesh import make_mesh

    g = generate.halo(PARTS, BLOCK_SPAN, hubs=HUBS, weighted=True)
    mesh = make_mesh(PARTS)
    sent = RecompileSentinel("exchange-smoke")
    log(f"halo graph nv={g.nv} ne={g.ne} on a {MESH} virtual mesh "
        f"({PARTS} XLA host devices)")

    def build_run(key, build, run):
        """Build + first run under an expect window (compiles are
        budgeted there), then a warm re-run under watch (any compile is
        a sentinel failure)."""
        with sent.expect(key):
            ex = build()
            first = run(ex)
        with sent.watch(key):
            warm = run(ex)
        return ex, first, warm

    doc = {"schema": "exchange_smoke.v1",
           "graph": {"kind": "halo", "nv": g.nv, "ne": g.ne,
                     "hubs": HUBS},
           "mesh": {"spec": MESH, "num_parts": PARTS}}

    # -- 1+2: bitwise parity, full vs compact ---------------------------
    apps = {}
    for app, build, run in (
        ("sssp",
         lambda: ShardedPushExecutor(g, SSSP(), mesh=mesh),
         lambda ex: ex.run(start=0)),
        ("pagerank",
         lambda: ShardedPullExecutor(g, PageRank(), mesh=mesh),
         lambda ex: (ex.run(PR_ITERS, flush_every=0), None)),
    ):
        got = {}
        for mode in ("full", "compact"):
            os.environ["LUX_EXCHANGE"] = mode
            ex, (out, iters), _ = build_run(f"{app}-{mode}", build, run)
            assert ex.exchange_mode == mode, (
                f"{app}: requested {mode}, resolved {ex.exchange_mode} "
                "(plan unprofitable on this graph?)")
            got[mode] = {
                "values": ex.gather_values(out),
                "iters": iters,
                "bytes": ex.exchange_bytes_per_iter(),
                "ex": ex,
            }
        np.testing.assert_array_equal(
            got["full"]["values"], got["compact"]["values"],
            err_msg=f"{app}: full vs compact diverged")
        assert got["full"]["iters"] == got["compact"]["iters"]
        apps[app] = got
        log(f"{app}: full and compact bit-identical "
            f"({got['full']['iters'] or PR_ITERS} iters)")

    # -- 3: exchange ledger ---------------------------------------------
    ledger = {}
    for app, row_bytes in (("sssp", 5), ("pagerank", 4)):
        ex_c = apps[app]["compact"]["ex"]
        b_full = apps[app]["full"]["bytes"]
        b_comp = apps[app]["compact"]["bytes"]
        drop = b_full / b_comp
        full_led = engobs.useful_exchange(ex_c.sg, row_bytes)
        comp_led = engobs.useful_exchange(
            ex_c.sg, row_bytes,
            exchanged_rows=ex_c._xplan.exchanged_units_per_iter)
        ledger[app] = {
            "bytes_full": b_full, "bytes_compact": b_comp,
            "drop": round(drop, 1),
            "useful_ratio_full": round(full_led["ratio"], 3),
            "useful_ratio_compact": round(comp_led["ratio"], 3),
        }
        assert drop >= DROP_FLOOR, (
            f"{app}: exchange bytes dropped only {drop:.1f}x "
            f"({b_full} -> {b_comp}); need >= {DROP_FLOOR}x")
        assert full_led["ratio"] < 0.3 and comp_led["ratio"] >= 0.8, ledger
        log(f"{app}: exchange {b_full} -> {b_comp} B/iter "
            f"({drop:.1f}x), useful_ratio {full_led['ratio']:.3f} -> "
            f"{comp_led['ratio']:.3f}")
    doc["ledger"] = ledger

    # -- 3.5: static exchange-tier verification of the live plans -------
    # The same proof `make lint-exchange` runs, but against THESE
    # engines' plans with the full evidence chain (counts, pricing,
    # ledger): the smoke must never pass on a plan luxlint would flag.
    from lux_tpu.analysis import exchck

    for app, row_bytes in (("sssp", 5), ("pagerank", 4)):
        ex_c = apps[app]["compact"]["ex"]
        view = exchck.plan_view(
            ex_c._xplan,
            remote_read_counts=ex_c.sg.remote_read_counts(),
            row_bytes=row_bytes,
            declared_bytes_per_iter=ex_c.exchange_bytes_per_iter(),
            ledger=engobs.useful_exchange(
                ex_c.sg, row_bytes,
                exchanged_rows=ex_c._xplan.exchanged_units_per_iter))
        res = exchck.verify_exchange_plan(view, f"smoke@{app}")
        assert not res.findings and res.error is None, (
            [f.format() for f in res.findings], res.error)
    doc["exchange_lint_findings"] = 0
    log("exchck: LUX401-403 clean on both live compact plans "
        "(structure, permutation proof, pricing)")

    # -- 4: zero recompiles on every warm path --------------------------
    sent.assert_zero_recompiles()
    doc["recompiles"] = sent.recompiles()
    log("sentinel: 0 recompiles outside expect windows across "
        f"{len(apps) * 2} warm engine re-runs")

    # -- 5: phase-fenced observability (LUX_ENGOBS=1) -------------------
    os.environ["LUX_EXCHANGE"] = "compact"
    os.environ["LUX_ENGOBS"] = "1"
    try:
        engobs.reset()
        with sent.expect("sssp-compact-phased"):
            ex = ShardedPushExecutor(g, SSSP(), mesh=mesh)
            ex.run(start=0)
        hidden = {
            name: tel["run_exchange_hidden_frac"]
            for name, tel in engobs.latest().items()
            if tel.get("run_exchange_hidden_frac") is not None
        }
        assert hidden, (
            "LUX_ENGOBS=1 compact run reported no exchange_hidden_frac: "
            f"{engobs.latest()}")
        for name, frac in hidden.items():
            assert 0.0 <= frac <= 1.0, (name, frac)
        doc["exchange_hidden_frac"] = {
            k: round(v, 3) for k, v in hidden.items()}
        # The key stays `exchange_hidden_frac` for artifact
        # compatibility, but it is a BUDGET (upper bound): phase fencing
        # serializes the overlap it prices. The device-measured number
        # is `realized_hidden_frac` from a profile.v1 capture
        # (obs/prof.py) — surfaced next to the budget when one exists.
        doc["exchange_hidden_frac_note"] = "budget (upper bound)"
        from lux_tpu.obs import prof

        realized = prof.latest_realized()
        if realized is not None:
            doc["realized_hidden_frac"] = round(realized, 3)
        log(f"engobs: exchange_hidden_frac={doc['exchange_hidden_frac']} "
            "— budget (upper bound); device-measured realized_hidden_frac"
            f"={realized if realized is not None else 'n/a (no profile)'}"
            " via obs/prof.py capture windows")
    finally:
        del os.environ["LUX_ENGOBS"]
        del os.environ["LUX_EXCHANGE"]

    sent.close()
    print("exchange-smoke PASS (bitwise parity, >=5x exchange-byte "
          "drop, zero recompiles, hidden-frac reported)")
    print(json.dumps(doc, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
