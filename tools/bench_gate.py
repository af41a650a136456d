#!/usr/bin/env python3
"""Bench regression gate: run bench.py, emit a ``bench_gate.v1`` JSON
round artifact, and fail when a tracked metric regresses past tolerance
against the newest committed ``BENCH_*.json`` baseline — the luxlint
``--baseline`` ratchet idiom applied to performance.

Usage:
  python tools/bench_gate.py --fast                 # make bench-gate
  python tools/bench_gate.py --fast --record BENCH_r06.json
  python tools/bench_gate.py --replay CUR.json --baseline BASE.json

``--fast`` runs the suite on a tiny graph (LUX_BENCH_GATE_SCALE,
default 10) so the gate fits in `make verify`; full mode uses the
bench defaults (scale 22). Rounds only compare against baselines with
the same context (mode, scale, edge factor, layout, platform,
device_kind) — the
r01-r05 full-scale TPU artifacts are kept as history, not gates, for a
fast CPU round. ``--replay`` feeds a previously-emitted bench_gate.v1
JSON through the comparison (no bench run) — the seeded-regression test
and postmortem re-checks use it.

Metric direction is inferred from the name: ``*_ms_per_iter`` /
``*_s`` / ``*_seconds`` regress upward, everything else (gteps, GB/s,
peak fractions) regresses downward.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lux_tpu.utils import flags  # noqa: E402

_LOWER_IS_BETTER = re.compile(r"(_ms_per_iter|ms_per_iter|_seconds|_s)$")
# Context keys that must match for two rounds to be comparable.
_CONTEXT_KEYS = ("mode", "scale", "ef", "layout", "platform", "exchange",
                 "device_kind", "tuned")


def log(msg):
    print(f"# {msg}", file=sys.stderr, flush=True)


# -- metric extraction -----------------------------------------------------


def metrics_from_headline(headline: dict) -> dict:
    """Flatten a bench.py headline (either output line) into one
    ``name -> float`` map the comparison walks."""
    out = {}
    if isinstance(headline.get("value"), (int, float)):
        out["headline_gteps"] = float(headline["value"])
    for key in ("achieved_gbps", "hbm_peak_frac", "smallworld_gteps"):
        v = headline.get(key)
        if isinstance(v, (int, float)):
            out[key] = float(v)
    for name, res in (headline.get("suite") or {}).items():
        if not isinstance(res, dict):
            continue
        for key in ("gteps", "ms_per_iter", "achieved_gbps",
                    "hbm_peak_frac"):
            v = res.get(key)
            if isinstance(v, (int, float)):
                out[f"{name}.{key}"] = float(v)
    return out


def roofline_from_headline(headline: dict) -> dict:
    """The roofline block PERF_NOTES.md's evidence policy v3 requires: the
    achieved-vs-peak fractions from the headline telemetry (attached by
    obs/report.py) plus the headline's byte-model fraction."""
    out = {}
    if isinstance(headline.get("hbm_peak_frac"), (int, float)):
        out["headline_hbm_frac"] = headline["hbm_peak_frac"]
    tel = headline.get("telemetry") or {}
    roof = tel.get("roofline") or {}
    for key, v in roof.items():
        if isinstance(v, (int, float)):
            out[key] = v
    return out


# -- baselines -------------------------------------------------------------


def _round_num(path: str) -> int:
    m = re.search(r"BENCH_r(\d+)\.json$", path)
    return int(m.group(1)) if m else -1


def find_baseline(repo: str, exclude: str = None):
    """Newest committed BENCH_r0N.json (highest round number), skipping
    the file this run is about to write."""
    cands = sorted(glob.glob(os.path.join(repo, "BENCH_*.json")),
                   key=_round_num)
    if exclude:
        ex = os.path.abspath(exclude)
        cands = [c for c in cands if os.path.abspath(c) != ex]
    return cands[-1] if cands else None


def load_baseline(path: str) -> dict:
    """Read either artifact shape: a bench_gate.v1 doc (r06+) or the
    driver-recorded ``{n, cmd, rc, tail, parsed}`` shape (r01-r05)."""
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") == "bench_gate.v1":
        return {"metrics": doc.get("metrics") or {},
                "context": doc.get("context") or {}}
    parsed = doc.get("parsed") or {}
    ctx = {}
    m = re.search(r"rmat(\d+)", str(parsed.get("metric", "")))
    if m:
        ctx["scale"] = int(m.group(1))
    if parsed.get("layout"):
        ctx["layout"] = parsed["layout"]
    return {"metrics": metrics_from_headline(parsed), "context": ctx}


def comparable(cur_ctx: dict, base_ctx: dict):
    """(ok, reason): contexts must agree on every key both sides carry;
    a baseline missing a key (legacy artifacts) fails closed on mode —
    a full-scale TPU round must never gate a fast CPU round."""
    for key in _CONTEXT_KEYS:
        c, b = cur_ctx.get(key), base_ctx.get(key)
        if key == "exchange" and b is None:
            # Baselines recorded before the exchange key existed ran
            # under the then-only full exchange.
            b = flags.default("LUX_EXCHANGE")
        if key == "tuned":
            # Artifacts recorded before the auto-tuner existed ran
            # under default configs; a tuned round must never ratchet
            # against them (nor vice versa) — same idiom as exchange.
            c = bool(c)
            b = bool(b)
        if key == "device_kind" and b is None:
            # A baseline that never recorded its chip could have come
            # from ANY device; numbers from different chips are
            # different experiments, so fail closed rather than ratchet
            # a v5e round against (say) a v5p artifact — unless both
            # sides already agree on platform=cpu, where the kind is
            # the platform.
            if cur_ctx.get("platform") == "cpu" \
                    and base_ctx.get("platform") == "cpu":
                continue
            return False, "baseline has no device_kind context"
        if b is None and key in ("ef", "platform", "mode"):
            if key == "mode" and cur_ctx.get("mode") == "fast":
                return False, "legacy baseline has no fast-mode context"
            continue
        if c != b:
            return False, f"context mismatch on {key}: {c!r} vs {b!r}"
    return True, None


# -- comparison ------------------------------------------------------------


def compare(current: dict, baseline: dict, tol: float):
    """Per-metric regression check over the intersection of the two
    metric maps. Returns (rows, ok): a row per shared metric with the
    signed relative delta; ``ok`` is False when any metric moved in its
    bad direction by more than ``tol``."""
    rows = []
    ok = True
    for name in sorted(set(current) & set(baseline)):
        base, cur = float(baseline[name]), float(current[name])
        if base == 0.0:
            continue
        lower_better = bool(_LOWER_IS_BETTER.search(name))
        delta = (cur - base) / abs(base)
        regressed = delta > tol if lower_better else delta < -tol
        rows.append({
            "metric": name, "base": base, "cur": cur,
            "delta_frac": round(delta, 4), "tol": tol,
            "better": "lower" if lower_better else "higher",
            "ok": not regressed,
        })
        ok = ok and not regressed
    return rows, ok


# -- running the bench -----------------------------------------------------


def run_bench(fast: bool):
    """Run bench.py as a subprocess; returns (headline, context, cmd).
    The headline is the LAST JSON stdout line (suite-enriched when the
    suite ran); context comes from the effective knobs plus the
    platform bench logs to stderr."""
    env = dict(os.environ)
    if fast:
        env.setdefault("LUX_BENCH_SCALE",
                       str(flags.get_int("LUX_BENCH_GATE_SCALE")))
        env.setdefault("LUX_BENCH_EF", "8")
        env.setdefault("LUX_BENCH_ITERS", "8")
        env.setdefault("LUX_BENCH_DEADLINE", "20")
    cmd = [sys.executable, os.path.join(REPO, "bench.py")]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO,
                          env=env)
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise SystemExit(f"bench.py failed (rc={proc.returncode}):\n"
                         f"{proc.stdout[-2000:]}")
    headline = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            headline = json.loads(line)
    if headline is None:
        raise SystemExit("bench.py printed no JSON headline")
    m = re.search(r"^# platform: (\S+)", proc.stderr, re.M)
    mk = re.search(r"^# device_kind: (.+)$", proc.stderr, re.M)
    context = {
        "mode": "fast" if fast else "full",
        "scale": int(env.get("LUX_BENCH_SCALE",
                             flags.default("LUX_BENCH_SCALE"))),
        "ef": int(env.get("LUX_BENCH_EF", flags.default("LUX_BENCH_EF"))),
        "layout": env.get("LUX_BENCH_LAYOUT",
                          flags.default("LUX_BENCH_LAYOUT")),
        # The requested sharded exchange mode: two bench runs with
        # different LUX_EXCHANGE settings are different experiments and
        # must never ratchet against each other silently.
        "exchange": env.get("LUX_EXCHANGE", flags.default("LUX_EXCHANGE")),
        "platform": m.group(1) if m else "unknown",
        # The chip the numbers came from (jax device_kind); rounds from
        # different chips never ratchet against each other.
        "device_kind": mk.group(1).strip() if mk else "unknown",
        # Whether the suite ran bench.py --tuned (TuneCache winners
        # next to the default rows). Tuned and default rounds are
        # different experiments: a tuned round ratcheting a default
        # baseline would bake the tuner's win into the floor.
        "tuned": bool(headline.get("tuned")),
        # Reproducibility stamp, NOT a gate key (comparable() never
        # reads it): the flag-registry hash that keys this round's run
        # ledger records, so a gate artifact can be joined back to its
        # runrec.v1 evidence.
        "config_hash": flags.config_hash(),
    }
    return headline, context, " ".join(cmd)


def build_doc(headline: dict, context: dict, cmd: str) -> dict:
    return {
        "schema": "bench_gate.v1",
        "mode": context.get("mode"),
        "context": context,
        "cmd": cmd,
        "metrics": metrics_from_headline(headline),
        "roofline": roofline_from_headline(headline),
        # `parsed` mirrors the r01-r05 artifact field so existing
        # BENCH_r0N readers keep working on r06+.
        "parsed": headline,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="tiny-graph suite (LUX_BENCH_GATE_SCALE) for "
                    "make verify")
    ap.add_argument("--replay", metavar="JSON",
                    help="compare a previously-emitted bench_gate.v1 doc "
                    "instead of running bench.py")
    ap.add_argument("--baseline", metavar="JSON",
                    help="explicit baseline (default: newest BENCH_*.json)")
    ap.add_argument("--out", metavar="JSON",
                    help="also write the bench_gate.v1 doc here")
    ap.add_argument("--record", metavar="BENCH_rNN.json",
                    help="record this round as a BENCH lineage artifact")
    ap.add_argument("--tol", type=float, default=None,
                    help="relative regression tolerance (default "
                    "LUX_BENCH_GATE_TOL)")
    args = ap.parse_args(argv)

    tol = args.tol if args.tol is not None else flags.get_float(
        "LUX_BENCH_GATE_TOL")

    if args.replay:
        with open(args.replay) as f:
            doc = json.load(f)
        if doc.get("schema") != "bench_gate.v1":
            raise SystemExit(f"{args.replay}: not a bench_gate.v1 doc")
    else:
        headline, context, cmd = run_bench(args.fast)
        doc = build_doc(headline, context, cmd)

    base_path = args.baseline or find_baseline(REPO, exclude=args.record)
    if base_path:
        base = load_baseline(base_path)
        ok_ctx, reason = comparable(doc.get("context") or {},
                                    base["context"])
        doc["baseline"] = {"path": os.path.basename(base_path),
                           "comparable": ok_ctx, "reason": reason}
        if ok_ctx:
            rows, ok = compare(doc["metrics"], base["metrics"], tol)
            doc["comparison"], doc["ok"] = rows, ok
        else:
            log(f"baseline {os.path.basename(base_path)} not comparable: "
                f"{reason}")
            doc["comparison"], doc["ok"] = [], True
    else:
        log("no BENCH_*.json baseline found; recording only")
        doc["baseline"] = None
        doc["comparison"], doc["ok"] = [], True

    for path in filter(None, (args.out, args.record)):
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        log(f"wrote {path}")

    for row in doc["comparison"]:
        mark = "ok" if row["ok"] else "REGRESSED"
        print(f"{row['metric']:<34} base={row['base']:<10.4g} "
              f"cur={row['cur']:<10.4g} delta={row['delta_frac']:+.1%} "
              f"({row['better']} is better) {mark}")
    print("BENCH_GATE " + json.dumps({
        "schema": "bench_gate.v1", "ok": doc["ok"],
        "compared": len(doc["comparison"]),
        "baseline": (doc.get("baseline") or {}).get("path"),
        "metrics": len(doc["metrics"]),
    }))
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
