"""Device time by the program's ``lux.*`` scopes, from a profiler trace.

A ``jax.named_scope`` inside jitted code lands in the HLO ops' metadata
(``op_name``). On a TPU the profiler copies it into each device op's
``tf_op`` stat, for example
``jit(_step_impl)/lux.tiled.strip_scan/while/body/closed_call/gather:``.
``jax.profiler.ProfileData`` (what ``trace_reduce.load_xplane`` reads)
does not expose that stat. The ``.trace.json.gz`` that ``jax.profiler``
writes beside the ``.xplane.pb`` carries it as an event arg, but leaves
events out of a long capture (a 51 s PageRank window lost a quarter of
its busy time there). So the JSON gives the map from an op's HLO text to
its scope (``scope_map``) and the ``.xplane.pb`` gives every event
(``with_scopes``).

Some ops carry no metadata: loops and conditionals, and the ops the TPU
compiler makes when it rewrites a cumulative sum. ``scope_seconds``
gives every instant of device busy time to the innermost running op's
scope and, where that op has none, to the scope of the last op that
started before it; ``inherit=False`` leaves such time under
``NO_SCOPE``. ``trace_reduce.reduce`` does not call this module: its
result keys stay as they are.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List

from perfbench import trace_reduce

SCOPE_RE = re.compile(r"lux\.[a-z0-9_.]+")
NO_SCOPE = "(no scope)"


def scope_of(tf_op: str) -> str:
    """The innermost ``lux.*`` scope of an op's name path."""
    found = SCOPE_RE.findall(tf_op or "")
    return found[-1] if found else NO_SCOPE


def load_trace_json(path: str) -> List[dict]:
    """Events of ``path`` (a ``.trace.json.gz``, or the newest one under a
    trace directory) in ``trace_reduce``'s form: device ops of each
    device's ``XLA Ops`` line, named by their HLO text and with their
    ``scope``, and the host spans ``trace_reduce`` keeps."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .trace.json.gz under {path}")
        path = found[-1]
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    procs, threads = {}, {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("ph") == "M" and ev.get("name") == "thread_name":
            threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    events = []
    for ev in doc["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        plane = procs.get(ev["pid"], "")
        line = threads.get((ev["pid"], ev.get("tid")), "")
        device = plane.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
        if device and line != trace_reduce.DEVICE_OPS_LINE:
            continue
        if not device and not str(ev["name"]).startswith(
                trace_reduce.HOST_SPAN_PREFIXES):
            continue
        args = ev.get("args") or {}
        out = {"plane": plane, "line": line,
               "name": args.get("long_name", ev["name"]),
               "start_ns": round(float(ev["ts"]) * 1e3),
               "dur_ns": round(float(ev.get("dur", 0)) * 1e3)}
        if device:
            out["scope"] = scope_of(args.get("tf_op"))
        events.append(out)
    return events


def scope_map(events: List[dict]) -> Dict[str, str]:
    """Op HLO text -> scope, from events that carry scopes; a text seen
    under two scopes (two modules) maps to ``NO_SCOPE``."""
    out: Dict[str, str] = {}
    for e in events:
        if "scope" in e:
            seen = out.setdefault(e["name"], e["scope"])
            if seen != e["scope"]:
                out[e["name"]] = NO_SCOPE
    return out


def with_scopes(events: List[dict], scopes: Dict[str, str]) -> List[dict]:
    """``events`` (``trace_reduce.load_xplane``'s) with each device op's
    scope from ``scopes``."""
    dev = trace_reduce.DEVICE_PLANE_PREFIX
    return [dict(e, scope=scopes.get(e["name"], NO_SCOPE))
            if e["plane"].startswith(dev) else e for e in events]


def _sweep(ops, inherit: bool) -> Dict[str, float]:
    """Nanoseconds per scope on one device line: each instant goes to the
    innermost running op (a loop's time between its body's ops is its
    own); an op without a scope passes it to the last scope that started,
    when ``inherit``."""
    out: Dict[str, float] = {}
    stack: List[list] = []   # [end, scope], innermost last
    last = NO_SCOPE
    t = 0.0

    def credit(upto):
        nonlocal t
        if stack and upto > t:
            scope = stack[-1][1]
            if scope == NO_SCOPE and inherit:
                scope = last
            out[scope] = out.get(scope, 0.0) + (upto - t)
        t = max(t, upto)

    def close():
        credit(stack[-1][0])
        stack.pop()

    for s, e, scope in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close()
        credit(s)
        stack.append([e, scope])
        if scope != NO_SCOPE:
            last = scope
    while stack:
        close()
    return out


def scope_seconds(events: List[dict], window: str = trace_reduce.WINDOW,
                  inherit: bool = True) -> Dict[str, float]:
    """Device seconds per scope inside the window, averaged over the
    devices. The values add up to the device busy time."""
    w0, w1 = trace_reduce._window(events, window)
    per_dev: Dict[str, list] = {}
    for e in events:
        if "scope" not in e:
            continue
        s, t = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        if t > s:
            per_dev.setdefault(e["plane"], []).append((s, t, e["scope"]))
    out: Dict[str, float] = {}
    for ops in per_dev.values():
        for scope, ns in _sweep(ops, inherit).items():
            out[scope] = out.get(scope, 0.0) + ns
    n_dev = max(1, len(per_dev))
    return {k: v / n_dev / 1e9 for k, v in sorted(out.items())}
