#!/usr/bin/env python3
"""Run one benchmark cell traced, as ``perfbench/run.py --trace 1`` does,
and break the window's device time down by the program's ``lux.*`` scopes.

    python3 tools/phase_breakdown.py --workload <cell> --seed <n> \\
        --seconds <s> [--out DIR]

Prints the harness's result line, then one JSON line (also written to
``DIR/<cell>-<seed>.json``, default ``.bench_cache/phases``): device
seconds per scope in the traced window (``perfbench/scopes.py``), the
share of device busy time under a ``lux.*`` scope (by the op's own scope,
and with ops that carry none put down to the scope that ran before
them), the engine counters' deltas over the window, milliseconds per
iteration by scope, the window's end-to-end numbers, the host spans in
the longest device-idle gaps, and for serving cells one row per query
(server latency, engine seconds, iterations, sparse iterations) from the
program's request spans.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("lux_engine_iterations_total", "lux_engine_chunks_total")


def counters() -> dict:
    from lux_tpu.obs import metrics

    return {m["name"] + json.dumps(m["labels"], sort_keys=True): m["value"]
            for m in metrics.snapshot() if m["name"] in COUNTERS}


def query_row(rec: dict):
    """(server latency, engine seconds, iterations, sparse iterations) of
    one served query's trace record, or None for another trace."""
    by = {s["name"]: s for s in rec.get("spans", ())}
    if "http.request" not in by or "serve.engine" not in by:
        return None
    attrs = by["serve.engine"].get("attrs", {})
    return [by["http.request"]["dur_s"], by["serve.engine"]["dur_s"],
            attrs.get("iters"), attrs.get("sparse_iters")]


def gap_spans(events, k: int = 5) -> list:
    """The ``k`` longest device-idle gaps in the window, each with the
    host spans that overlap it (name, thread, start and end in ms from
    the gap's start)."""
    from perfbench import trace_reduce as tr

    w0, w1 = tr._window(events, tr.WINDOW)
    busy = tr.merge_intervals(
        (max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
        for e in events if e["plane"].startswith(tr.DEVICE_PLANE_PREFIX))
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                   if b > a), reverse=True)[:k]
    out = []
    for n, a, b in gaps:
        host = [[e["name"], e["line"], (e["start_ns"] - a) / 1e6,
                 (e["start_ns"] + e["dur_ns"] - a) / 1e6]
                for e in events
                if not e["plane"].startswith(tr.DEVICE_PLANE_PREFIX)
                and e["name"] != tr.WINDOW
                and e["start_ns"] < b and e["start_ns"] + e["dur_ns"] > a]
        out.append({"gap_ms": n / 1e6, "host_spans": host})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_cache",
                                                  "phases"))
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".bench_cache", "xla_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from lux_tpu.obs import spans
    from perfbench import harness, scopes, trace_reduce

    cell = harness.Cell(ROOT, args.workload)
    driver = importlib.import_module(
        f"perfbench.drivers.{cell.traffic['driver']}").Driver
    out = {"workload": args.workload, "seed": args.seed, "queries": []}

    def on_trace(rec):
        row = query_row(rec)
        if row is not None:
            out["queries"].append(row)

    real_window = driver.window

    def window(self, seconds):
        before = counters()
        spans.add_sink(on_trace)
        try:
            out["e2e"] = real_window(self, seconds)
        finally:
            spans.remove_sink(on_trace)
        after = counters()
        out["counters"] = {k: v - before.get(k, 0.0) for k, v in
                           after.items()}
        return out["e2e"]

    # The harness removes the trace directory once it has read it: read
    # the scopes from it at that moment.
    real_load = trace_reduce.load_xplane

    def load_xplane(trace_dir):
        events = real_load(trace_dir)
        scoped = scopes.with_scopes(events, scopes.scope_map(
            scopes.load_trace_json(trace_dir)))
        out["scopes_s"] = scopes.scope_seconds(scoped)
        raw = scopes.scope_seconds(scoped, inherit=False)
        busy = sum(raw.values())
        for key, sec in (("scoped_share", raw),
                         ("scoped_share_inherited", out["scopes_s"])):
            out[key] = (1 - sec.get(scopes.NO_SCOPE, 0.0) / busy
                        if busy else None)
        out["gaps"] = gap_spans(events)
        return events

    driver.window = window
    trace_reduce.load_xplane = load_xplane
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              True, t_start=T_START)
    iters = sum(v for k, v in out["counters"].items()
                if k.startswith("lux_engine_iterations_total"))
    if iters:
        out["ms_per_iter"] = {k: 1e3 * v / iters
                              for k, v in out["scopes_s"].items()}
    harness.print_result(result)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{args.workload}-{args.seed}.json"),
              "w") as f:
        json.dump({"result": result, "phases": out}, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
