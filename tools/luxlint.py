#!/usr/bin/env python
"""luxlint: project-native static analysis over lux_tpu/ + tools/.

Usage:
    python tools/luxlint.py                  # lint the default tree (AST tier)
    python tools/luxlint.py path.py dir/     # lint specific targets
    python tools/luxlint.py --changed        # only files changed vs git HEAD
    python tools/luxlint.py --json           # full findings as JSON
    python tools/luxlint.py --list-rules     # rule table
    python tools/luxlint.py --select LUX001  # subset of rules
    python tools/luxlint.py --ir             # IR tier: trace every registered
                                             #   program x executor, run LUX1xx
    python tools/luxlint.py --ir fixture.py  # trace a module's TRACES list
    python tools/luxlint.py --threads        # concurrency tier: lock
                                             #   discipline + lock-order graph
                                             #   (LUX3xx, stdlib AST)
    python tools/luxlint.py --exchange       # exchange tier: verify every
                                             #   sharded target's ExchangePlan
                                             #   + collective dataflow (LUX4xx)
    python tools/luxlint.py --exchange DIR   # verify saved exchange-plan
                                             #   artifacts / fixture modules
    python tools/luxlint.py --tune DIR...    # verify saved tuneconf.v1
                                             #   auto-tuner artifacts
                                             #   (LUX5xx, jax-free)
    python tools/luxlint.py --programs       # program-contract tier: prove
                                             #   each registry program's GAS
                                             #   algebra + derive the
                                             #   capability matrix (LUX6xx)
    python tools/luxlint.py --programs f.py  # prove programs defined in
                                             #   fixture modules instead
    python tools/luxlint.py --memory         # memory tier: donation-aware
                                             #   HBM-footprint walk over every
                                             #   traced registry target +
                                             #   memcap.v1 contracts (LUX7xx)
    python tools/luxlint.py --memory f.py    # check fixture modules' TARGETS/
                                             #   MODELS/MEMCAP instead
    python tools/luxlint.py --baseline F     # snapshot/compare: only findings
                                             #   absent from F fail the run

Exit status: 0 clean, 1 unsuppressed findings or syntax/trace errors.
Always emits one greppable summary line (`LUXLINT {...}`) so CI logs
carry the verdict even when output scrolls.

Suppress an AST-tier finding inline, with a reason:
    x.item()  # luxlint: disable=LUX001 -- intended once-per-run sync
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from lux_tpu.analysis import all_rules, run_paths  # noqa: E402
from lux_tpu.analysis.threads import all_thread_rules, run_threads  # noqa: E402

DEFAULT_TARGETS = ("lux_tpu", "tools", "bench.py")


def _changed_paths() -> list:
    """Python files changed vs HEAD (staged + unstaged + untracked)."""
    out = set()
    for cmd in (["git", "diff", "--name-only", "HEAD"],
                ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            text = subprocess.run(cmd, cwd=_REPO, capture_output=True,
                                  text=True, check=True).stdout
        except (OSError, subprocess.CalledProcessError) as e:
            print(f"luxlint: --changed: {' '.join(cmd)} failed: {e}",
                  file=sys.stderr)
            return []
        for line in text.splitlines():
            if line.endswith(".py"):
                p = os.path.join(_REPO, line)
                if os.path.isfile(p):
                    out.add(p)
    return sorted(out)


_SPAN_CACHE: dict = {}


def _span_hash(path: str, line: int) -> str:
    """Content hash of the finding's source span: sha1 (16 hex chars) of
    the stripped text of the flagged line. Returns "" when the path is
    virtual (trace targets, plan dirs) or the line is out of range."""
    if line < 1:
        return ""
    if path not in _SPAN_CACHE:
        try:
            with open(path, "rb") as fh:
                _SPAN_CACHE[path] = fh.read().splitlines()
        except OSError:
            _SPAN_CACHE[path] = None
    lines = _SPAN_CACHE[path]
    if lines is None or line > len(lines):
        return ""
    return hashlib.sha1(lines[line - 1].strip()).hexdigest()[:16]


def _baseline_key(path: str, f) -> str:
    """Ratchet key: (rule, path, source-span hash). Hashing the flagged
    line's content instead of its number keeps keys stable across
    unrelated edits that shift line numbers; renaming/rewriting the
    flagged line itself re-opens the finding, which is the point of a
    ratchet. Virtual paths (IR targets, plan artifacts) have no source
    to hash and fall back to the message."""
    span = _span_hash(path, f.line)
    return f"{f.rule}\t{path}\t{span or f.message}"


def _apply_baseline(report, baseline_path: str) -> int:
    """Snapshot-or-compare. Missing file: write current findings, pass.
    Present: fail only on findings whose _baseline_key is new. Line
    numbers are deliberately not part of the key — unrelated edits
    shift them."""
    current = {}
    for res in report.results:
        for f in res.findings:
            current.setdefault(_baseline_key(res.path, f), []).append((res, f))
    if not os.path.exists(baseline_path):
        with open(baseline_path, "w") as fh:
            json.dump({"schema": report.schema + ".baseline",
                       "keys": sorted(current)}, fh, indent=0)
        print(f"luxlint: baseline written: {baseline_path} "
              f"({len(current)} finding keys)")
        return 0
    with open(baseline_path) as fh:
        known = set(json.load(fh).get("keys", ()))
    new = sorted(k for k in current if k not in known)
    errors = [r for r in report.results if r.error]
    for k in new:
        res, f = current[k][0]
        print(f"{res.path}:{f.line}:{f.col}: {f.rule} {f.message}  [new]")
    print(f"luxlint: baseline {baseline_path}: {len(new)} new / "
          f"{len(current)} total findings, {len(errors)} errors")
    return 1 if new or errors else 0


def _run_ir(paths, select: str):
    """IR tier: trace registered programs (or fixture modules) and run the
    LUX1xx jaxpr rules. Mirrors tests/conftest.py's env: 8 virtual CPU
    devices, CPU platform — set BEFORE jax initializes a backend, so the
    sharded executors have devices to shard over."""
    from lux_tpu.utils.platform import virtual_cpu_flags
    os.environ.setdefault("XLA_FLAGS", virtual_cpu_flags(8))
    os.environ["JAX_PLATFORMS"] = "cpu"
    from lux_tpu.analysis import ir

    if paths:
        targets = []
        for p in paths:
            targets.extend(ir.load_fixture_targets(p))
    else:
        targets = ir.registry_targets()
    rules = ir.all_ir_rules()
    if select:
        want = {s.strip() for s in select.split(",") if s.strip()}
        rules = [r for r in rules if r.id in want]
    return ir.run_targets(targets, rules)


def _run_exchange(paths, select: str):
    """Exchange tier: verify ExchangePlan tables (LUX401-403) and the
    collective-dataflow contract (LUX404-406). With no paths, the whole
    compact+full sharded registry matrix; with paths, saved artifact
    dirs and/or fixture modules exporting TRACES / PLANS. The analysis
    only ever traces and stages tiny placement programs, so XLA's
    backend optimizer is dead weight — turning it off roughly halves
    the tier's wall cost."""
    from lux_tpu.utils.platform import virtual_cpu_flags
    os.environ.setdefault(
        "XLA_FLAGS",
        virtual_cpu_flags(8) + " --xla_backend_optimization_level=0")
    os.environ["JAX_PLATFORMS"] = "cpu"
    from lux_tpu.analysis import ir

    want = None
    if select:
        want = {s.strip() for s in select.split(",") if s.strip()}
    if paths:
        return ir.run_exchange_paths(paths, select=want)
    return ir.run_exchange_matrix(select=want)


def _run_tune(paths, select: str):
    from lux_tpu.analysis import tuneck
    rules = tuneck.all_tune_rules()
    if select:
        want = {s.strip() for s in select.split(",") if s.strip()}
        rules = [r for r in rules if r.id in want]
    return tuneck.verify_artifact_paths(paths, rules)


def _run_programs(paths, select: str, gascap_out: str):
    """Program-contract tier: prove combiner identity/algebra, direction
    duality, frontier annihilation, and monotone convergence (LUX601-606)
    per program. Host numpy drives the probes; program hooks run as
    eager cpu jnp, so no virtual device mesh is needed. With no paths,
    the registered programs — and a clean run regenerates the gascap.v1
    capability artifact when --gascap-out names a destination. With
    paths, fixture modules defining programs (gascap-out is registry-
    only: fixtures prove rules, they don't define serving capability)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from lux_tpu.analysis import gasck

    want = None
    if select:
        want = tuple(s.strip() for s in select.split(",") if s.strip())
    if paths:
        return gasck.verify_fixture_paths(paths, select=want)
    return gasck.verify_registry(select=want,
                                 capmap_out=gascap_out or None)


def _run_memory(paths, select: str, memcap_out: str):
    """Memory tier: walk buffer liveness over every traced registry
    target (LUX701-706) and keep the memcap.v1 footprint artifact
    honest. Needs the same 8-virtual-device CPU mesh as --ir so the
    sharded executors have devices to shard over. With paths, fixture
    modules supply TARGETS/MODELS/MEMCAP/COMMITTED instead (memcap-out
    is registry-only: fixtures prove rules, they don't price serving)."""
    from lux_tpu.utils.platform import virtual_cpu_flags
    os.environ.setdefault(
        "XLA_FLAGS",
        virtual_cpu_flags(8) + " --xla_backend_optimization_level=0")
    os.environ["JAX_PLATFORMS"] = "cpu"
    from lux_tpu.analysis import memck

    want = None
    if select:
        want = tuple(s.strip() for s in select.split(",") if s.strip())
    if paths:
        return memck.verify_fixture_paths(paths, select=want)
    return memck.verify_registry(select=want,
                                 memcap_out=memcap_out or None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="luxlint", description=__doc__)
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to lint (default: {DEFAULT_TARGETS}); "
                         "with --ir: fixture modules exporting TRACES")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON on stdout")
    ap.add_argument("--select", default="",
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    ap.add_argument("--ir", action="store_true",
                    help="run the jaxpr tier (LUX101-105) over every "
                         "registered program x executor, or over the TRACES "
                         "of the given fixture modules")
    ap.add_argument("--threads", action="store_true",
                    help="run the concurrency tier (LUX301-305): thread-"
                         "shared state, lock-order graph, blocking-under-"
                         "lock, unjoined threads, publish discipline")
    ap.add_argument("--exchange", action="store_true",
                    help="run the exchange tier (LUX401-406): ExchangePlan "
                         "structure/coverage/profitability plus the "
                         "overlap-proof, sentinel-annihilator, and byte-"
                         "accounting dataflow rules over every sharded "
                         "registry target; with paths, verify saved "
                         "exchange artifacts or fixture modules")
    ap.add_argument("--tune", action="store_true",
                    help="verify saved tuneconf.v1 auto-tuner artifacts "
                         "(LUX501-504: structure, knob domains, selection "
                         "consistency, staleness; jax-free)")
    ap.add_argument("--programs", action="store_true",
                    help="run the program-contract tier (LUX601-606): "
                         "prove combiner identity/exactness, push/pull "
                         "duality, frontier annihilation, and monotone "
                         "convergence for every registered program and "
                         "derive the gascap.v1 capability matrix; with "
                         "paths, prove fixture-module programs instead")
    ap.add_argument("--gascap-out", default="", metavar="FILE",
                    help="with --programs (registry mode): write the "
                         "derived gascap.v1 capability artifact here when "
                         "the run is clean")
    ap.add_argument("--memory", action="store_true",
                    help="run the memory tier (LUX701-706): donation-aware "
                         "buffer-liveness walk over every traced registry "
                         "target deriving per-device peak live bytes and "
                         "the closed-form footprint model serving admission "
                         "trusts; with paths, check fixture modules' "
                         "TARGETS/MODELS/MEMCAP instead")
    ap.add_argument("--memcap-out", default="", metavar="FILE",
                    help="with --memory (registry mode): write the derived "
                         "memcap.v1 footprint artifact here when the run "
                         "is clean (committed-artifact rules are skipped "
                         "on a regeneration run)")
    ap.add_argument("--changed", action="store_true",
                    help="AST/threads tiers: restrict to .py files changed "
                         "vs git HEAD (plus untracked); the threads tier "
                         "still builds its lock-order graph over the whole "
                         "tree")
    ap.add_argument("--baseline", default="",
                    help="snapshot file: if missing, write current findings "
                         "and pass; if present, fail only on new findings")
    args = ap.parse_args(argv)

    if sum((args.ir, args.threads, args.exchange,
            args.tune, args.programs, args.memory)) > 1:
        ap.error("--ir, --threads, --exchange, --tune, --programs, and "
                 "--memory are separate tiers; run them separately")

    if args.list_rules:
        for r in all_rules():
            print(f"{r.id}  {r.title}\n       {r.doc}")
        for r in all_thread_rules():
            print(f"{r.id}  {r.title}\n       {r.doc}")
        # The artifact tiers import numpy/jax; keep --list-rules instant by
        # documenting them from their modules only when importable cheaply.
        try:
            from lux_tpu.analysis import exchck
            for r in exchck.all_exchange_rules():
                print(f"{r.id}  {r.title}\n       {r.doc}")
        except Exception:
            pass
        try:
            from lux_tpu.analysis import tuneck
            for r in tuneck.all_tune_rules():
                print(f"{r.id}  {r.title}\n       {r.doc}")
        except Exception:
            pass
        try:
            from lux_tpu.analysis import gasck
            for r in gasck.all_program_rules():
                print(f"{r.id}  {r.title}\n       {r.doc}")
        except Exception:
            pass
        try:
            from lux_tpu.analysis import memck
            for r in memck.all_memory_rules():
                print(f"{r.id}  {r.title}\n       {r.doc}")
        except Exception:
            pass
        print("LUX101-105  jaxpr tier (dtype drift, host callbacks, "
              "footprint, donation, collectives) — run with --ir")
        print("LUX404-406  exchange dataflow tier (overlap proof, sentinel "
              "annihilation, byte accounting) — run with --exchange")
        print("LUX701-706  memory tier (HBM-footprint contracts + the "
              "memcap.v1 serving admission formula) — run with --memory")
        return 0

    if args.ir:
        report = _run_ir(args.paths, args.select)
    elif args.exchange:
        if args.changed and not args.paths:
            # The matrix verifies live engine/partition behaviour, not
            # file text: skip it entirely unless an exchange-relevant
            # source file changed.
            relevant = ("lux_tpu/engine/", "lux_tpu/parallel/",
                        "lux_tpu/graph/", "lux_tpu/analysis/",
                        "lux_tpu/models", "lux_tpu/obs/")
            changed = [p for p in _changed_paths()
                       if os.path.relpath(p, _REPO).startswith(relevant)]
            if not changed:
                print("luxlint: --changed: no exchange-relevant files "
                      "modified")
                print("LUXLINT " + json.dumps(
                    {"schema": "luxlint-exchange.v1", "files": 0,
                     "findings": 0, "errors": 0, "ok": True},
                    sort_keys=True))
                return 0
        report = _run_exchange(args.paths, args.select)
    elif args.tune:
        if not args.paths:
            ap.error("--tune requires at least one artifact file or "
                     "directory")
        report = _run_tune(args.paths, args.select)
    elif args.programs:
        if args.changed and not args.paths:
            # The tier proves live program algebra, not file text: skip
            # it unless a program-relevant source file changed.
            relevant = ("lux_tpu/models", "lux_tpu/engine/",
                        "lux_tpu/analysis/", "lux_tpu/ops/",
                        "lux_tpu/graph/")
            changed = [p for p in _changed_paths()
                       if os.path.relpath(p, _REPO).startswith(relevant)]
            if not changed:
                print("luxlint: --changed: no program-relevant files "
                      "modified")
                print("LUXLINT " + json.dumps(
                    {"schema": "luxlint-programs.v1", "files": 0,
                     "findings": 0, "errors": 0, "ok": True},
                    sort_keys=True))
                return 0
        report = _run_programs(args.paths, args.select, args.gascap_out)
    elif args.memory:
        if args.changed and not args.paths:
            # The tier prices live engine residency, not file text: skip
            # it unless a footprint-relevant source file changed.
            relevant = ("lux_tpu/engine/", "lux_tpu/analysis/",
                        "lux_tpu/serve/", "lux_tpu/obs/",
                        "lux_tpu/models", "lux_tpu/graph/",
                        "lux_tpu/parallel/")
            changed = [p for p in _changed_paths()
                       if os.path.relpath(p, _REPO).startswith(relevant)]
            if not changed:
                print("luxlint: --changed: no memory-relevant files "
                      "modified")
                print("LUXLINT " + json.dumps(
                    {"schema": "luxlint-memory.v1", "files": 0,
                     "findings": 0, "errors": 0, "ok": True},
                    sort_keys=True))
                return 0
        report = _run_memory(args.paths, args.select, args.memcap_out)
    elif args.threads:
        select = None
        if args.select:
            select = {s.strip() for s in args.select.split(",") if s.strip()}
            unknown = select - {r.id for r in all_thread_rules()}
            if unknown:
                ap.error(f"unknown rule id(s): {sorted(unknown)}")
        tree = [os.path.join(_REPO, t) for t in DEFAULT_TARGETS]
        if args.changed:
            paths = _changed_paths()
            if not paths:
                print("luxlint: --changed: no modified .py files")
                print("LUXLINT " + json.dumps(
                    {"schema": "luxlint-threads.v1", "files": 0,
                     "findings": 0, "errors": 0, "ok": True},
                    sort_keys=True))
                return 0
            graph_paths = tree   # order graph stays whole-tree
        elif args.paths:
            paths = args.paths
            graph_paths = paths  # explicit targets are self-contained
        else:
            paths = graph_paths = tree
        report = run_threads(paths, select=select, graph_paths=graph_paths)
    else:
        rules = all_rules()
        if args.select:
            want = {s.strip() for s in args.select.split(",") if s.strip()}
            unknown = want - {r.id for r in rules}
            if unknown:
                ap.error(f"unknown rule id(s): {sorted(unknown)}")
            rules = [r for r in rules if r.id in want]
        if args.changed:
            paths = _changed_paths()
            if not paths:
                print("luxlint: --changed: no modified .py files")
                print("LUXLINT " + json.dumps(
                    {"schema": "luxlint.v1", "files": 0, "findings": 0,
                     "errors": 0, "ok": True}, sort_keys=True))
                return 0
        else:
            paths = args.paths or [os.path.join(_REPO, t)
                                   for t in DEFAULT_TARGETS]
        report = run_paths(paths, rules)

    if args.json:
        print(report.to_json())
    else:
        print(report.format_human())
    print("LUXLINT " + json.dumps(report.summary(), sort_keys=True))
    if args.baseline:
        return _apply_baseline(report, args.baseline)
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
