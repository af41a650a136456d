#!/usr/bin/env python3
"""Concurrency stress harness with LockWatch armed (`make race-stress`).

Runtime witness for the luxlint-threads tier: the static rules
(LUX301-305) prove lock discipline on the AST; this tool proves it on
actual interleavings. With ``LUX_LOCKWATCH=1`` set *before* import —
module-level obs locks are wrapped at construction — it drives:

1. a concurrent query burst (SSSP / components / PageRank) through the
   MicroBatcher from a thread pool;
2. a mid-burst snapshot hot-swap (``apply_edits``: background warm,
   atomic flip, FIFO drain barrier);
3. a forced background compaction (LUX_DELTA_COMPACT_RATIO pinned low)
   drained afterwards;

and asserts the run stays disciplined:

- ZERO lock-order inversions in the observed acquisition graph,
- ZERO failed queries across the swap,
- the pool's zero-recompile sentinel stays green,
- every watched lock's hold-time p99 stays bounded (the pool lock gets
  a compile-sized budget — first-build warmup holds it by design; every
  other lock must be orders of magnitude cheaper).

Prints a one-line ``race_stress.v1`` JSON document last. Scale with
LUX_SMOKE_SCALE (default 10); CPU-sized.
"""

from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Before any lux_tpu import: locks are wrapped at construction, and the
# obs modules build theirs at import time.
os.environ["LUX_LOCKWATCH"] = "1"
# Every swap's delta crosses the threshold -> compaction is forced.
os.environ.setdefault("LUX_DELTA_COMPACT_RATIO", "0.000001")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

# Locks the serve/graph/obs layers register via make_lock; the pool lock
# is allowed a compile-sized hold (build-under-lock is the documented
# single-compile guarantee), everything else must stay snappy.
POOL_HOLD_P99_S = 300.0
HOLD_P99_S = 30.0
WATCHED = ("pool", "cache", "session.swap", "snapshot", "snapshot.store",
           "delta.merge", "obs.spans", "obs.trace", "obs.flight", "obs.slo")


def main() -> int:
    from lux_tpu.utils import flags

    scale = flags.get_int("LUX_SMOKE_SCALE")

    from lux_tpu.graph import EdgeEdits, generate
    from lux_tpu.obs import metrics
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.utils.locks import WATCH, hold_quantile

    g = generate.rmat(scale, 8, seed=7)
    cfg = ServeConfig(max_batch=4, window_s=0.02, max_queue=512,
                      pagerank_iters=3)
    session = Session(g, cfg)

    rng = np.random.default_rng(23)
    roots = [int(r) for r in rng.integers(0, g.nv, size=8)]
    n_edit = max(4, g.ne // 200)
    ins = [(int(rng.integers(g.nv)), int(rng.integers(g.nv)))
           for _ in range(n_edit // 2)]
    dels = [(int(g.col_src[e]), int(g.col_dst[e]))
            for e in rng.choice(g.ne, size=n_edit - n_edit // 2,
                                replace=False)]
    edits = EdgeEdits.from_lists(insert=ins, delete=dels)

    jobs = ([("sssp", {"start": r}) for r in roots] * 4
            + [("components", {})] * 4 + [("pagerank", {})] * 4)
    errors = []

    def one(job):
        app, params = job
        try:
            session.query(app, timeout=300, **params)
            return 1
        except Exception as e:   # any failure fails the stress run
            errors.append((app, params, repr(e)))
            return 0

    # Mid-burst swap: first half of the burst in flight, then the swap
    # races the second half through the FIFO drain barrier.
    with ThreadPoolExecutor(max_workers=8) as tp:
        futs = [tp.submit(one, j) for j in jobs[: len(jobs) // 2]]
        swap_fut = tp.submit(session.apply_edits, edits)
        futs += [tp.submit(one, j) for j in jobs[len(jobs) // 2:]]
        served = sum(f.result() for f in futs)
        summary = swap_fut.result()

    session.store.drain_compactions()
    compactions = metrics.counter("lux_snapshot_compactions_total").value
    assert not errors, f"{len(errors)} queries failed: {errors[:3]}"
    assert summary["version"] == 1, summary
    assert compactions >= 1, "forced compaction never ran"

    # -- the discipline asserts -----------------------------------------
    WATCH.assert_no_inversions()
    session.pool.sentinel.assert_zero_recompiles()
    hold_p99 = {}
    for name in WATCHED:
        q = hold_quantile(name, 0.99)
        if q is None:
            continue   # lock exists but saw no traffic at this scale
        hold_p99[name] = round(q, 6)
        budget = POOL_HOLD_P99_S if name == "pool" else HOLD_P99_S
        assert q < budget, (
            f"lock {name} hold p99 {q:.3f}s exceeds {budget:.0f}s budget")
    stats = WATCH.stats()
    session.close()

    print(f"race-stress PASS ({served} queries, 1 swap, "
          f"{int(compactions)} compaction(s), {stats['edges']} lock-order "
          f"edges, 0 inversions, 0 recompiles)")
    print(json.dumps({
        "schema": "race_stress.v1",
        "graph": {"scale": scale, "nv": g.nv, "ne": g.ne},
        "queries": served,
        "failed": 0,
        "swaps": 1,
        "swap_s": round(summary["swap_s"], 3),
        "compactions": int(compactions),
        "inversions": 0,
        "lock_order_edges": stats["edges"],
        "hold_p99_s": dict(sorted(hold_p99.items())),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
