#!/usr/bin/env python
"""gasck_smoke: acceptance gate for the luxlint program-contract tier
(`make lint-programs`, wired into `make verify`).

Three claims, all asserted:

  1. **registry clean + fast** — proving every registered program's GAS
     algebra (LUX601-606) produces 0 findings inside the wall budget; a
     proof tier too slow for verify is a proof tier nobody runs;
  2. **artifact parity** — the freshly derived ``gascap.v1`` capability
     matrix has the same content-addressed id as the committed
     ``lux_tpu/analysis/gascap.json``: a program change that flips a
     derived capability fails verify until the artifact is regenerated
     (``luxlint --programs --gascap-out lux_tpu/analysis/gascap.json``)
     — the offline half of the LUX606 drift ratchet;
  3. **a seeded broken program is caught** — the committed LUX602
     fixture (inexact float32 sum posing as a reorderable combiner)
     must fail with exactly its rule, proving the tier distinguishes
     and not merely passes.

Exit status: 0 when all three hold. Emits one greppable
``GASCKSMOKE {...}`` summary line (``gasck_smoke.v1``).

Usage:
    python tools/gasck_smoke.py               # default: 2s budget
    python tools/gasck_smoke.py --budget-s 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# Program hooks run as eager cpu jnp; no device mesh, no XLA flags.
os.environ["JAX_PLATFORMS"] = "cpu"

from lux_tpu.analysis import gasck  # noqa: E402

FIXTURE = os.path.join(_REPO, "tests", "gas_fixtures",
                       "lux602_inexact_sum.py")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="gasck_smoke", description=__doc__)
    ap.add_argument("--budget-s", type=float, default=2.0,
                    help="wall budget for proving the whole registry")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    report, art = gasck.prove_registry()
    prove_s = time.perf_counter() - t0

    for res in report.results:
        for f in res.findings:
            print(f.format())
        if res.error:
            print(f"{res.path}: {res.error}")

    clean = report.ok
    fast = prove_s <= args.budget_s

    committed_id = None
    parity = False
    try:
        committed = gasck.load_capmap(gasck.capmap_path())
        committed_id = committed["id"]
        parity = committed_id == art["id"]
    except Exception as e:  # missing or tampered artifact: loud, fatal
        print(f"gasck_smoke: committed gascap.v1 unusable: {e!r}")

    fix_rules = []
    fixture_caught = False
    if os.path.exists(FIXTURE):
        fix_rep = gasck.verify_fixture_paths([FIXTURE])
        fix_rules = sorted({f.rule for f in fix_rep.findings})
        fixture_caught = (not fix_rep.ok) and fix_rules == ["LUX602"]
    else:
        print(f"gasck_smoke: missing fixture {FIXTURE}")

    ok = clean and fast and parity and fixture_caught
    summary = {
        "schema": "gasck_smoke.v1",
        "programs": len(report.results),
        "findings": len(report.findings),
        "errors": sum(1 for r in report.results if r.error),
        "prove_s": round(prove_s, 3),
        "budget_s": args.budget_s,
        "clean": clean,
        "fast": fast,
        "artifact_id": art["id"],
        "committed_id": committed_id,
        "parity": parity,
        "fixture_rules": fix_rules,
        "fixture_caught": fixture_caught,
        "ok": ok,
    }
    print("GASCKSMOKE " + json.dumps(summary, sort_keys=True))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
