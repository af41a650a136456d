"""From a profiler trace to device busy time, idle share and breakdown.

The interval union over device operations follows ``lux_tpu/obs/prof.py``
(``merge_intervals``/``union_total``), with one repair: idle time is
measured against the traced window, the host span ``perfbench.window``
that the harness opens around the measured work, not against the span
from the first device operation to the last, so idle time before the
first operation and after the last counts.

Two steps, so that the second can be checked on a committed trace:
``load_xplane`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes
into plain events ``{"plane", "line", "name", "start_ns", "dur_ns"}``;
``reduce`` turns such events into numbers.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "perfbench.window"
DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OPS_LINE = "XLA Ops"
# Host spans that name what the host was doing in an idle gap: the
# harness's own phases and the program's profiler regions.
HOST_SPAN_PREFIXES = ("perfbench.", "lux.")


def load_xplane(trace_dir: str) -> List[dict]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``, from
    device op lines and host threads alike."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name != DEVICE_OPS_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_SPAN_PREFIXES):
                    continue
                events.append({
                    "plane": plane.name, "line": line.name, "name": ev.name,
                    "start_ns": int(ev.start_ns),
                    "dur_ns": int(ev.duration_ns),
                })
    return events


def merge_intervals(intervals: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Sorted, non-overlapping union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_total(merged: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _window(events: List[dict], name: str) -> Tuple[int, int]:
    spans = [e for e in events if e["name"] == name
             and not e["plane"].startswith(DEVICE_PLANE_PREFIX)]
    if not spans:
        raise ValueError(f"trace holds no host span {name!r}")
    w = max(spans, key=lambda e: e["dur_ns"])
    return w["start_ns"], w["start_ns"] + w["dur_ns"]


def op_label(name: str) -> str:
    """``%fusion.131 = f32[131072,128]{...} fusion(...)`` ->
    ``fusion.131 f32[131072,128]``: the HLO instruction and its type."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:64]
    typ = rhs.split("{", 1)[0].split(" ", 1)[0]
    return f"{lhs.lstrip('%')} {typ}"[:64]


def self_times(ops: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Self nanoseconds per op label on one device line, where an op
    (a while loop, say) may enclose others: its span less its direct
    children's."""
    out: Dict[str, float] = {}
    stack: List[list] = []   # [end, label, self_ns]

    def close(entry):
        out[entry[1]] = out.get(entry[1], 0.0) + entry[2]

    for s, t, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= t - s
        stack.append([t, op_label(name), float(t - s)])
    while stack:
        close(stack.pop())
    return out


def _host_doing(host: List[dict], t: float) -> str:
    """The innermost (shortest) host span that covers the instant ``t``."""
    best: Optional[dict] = None
    for e in host:
        if e["start_ns"] <= t <= e["start_ns"] + e["dur_ns"]:
            if best is None or e["dur_ns"] < best["dur_ns"]:
                best = e
    return best["name"] if best is not None else "(no host span)"


def reduce(events: List[dict], window: str = WINDOW, top_k: int = 10
           ) -> Dict[str, object]:
    """``busy_s`` (union of device-op intervals inside the window, averaged
    over the devices), ``window_s``, ``idle_frac`` and the ``breakdown``:
    ``device_ops`` (op label, self seconds inside the window, averaged
    over the devices) and ``idle_gaps`` (what the host was doing, gap
    seconds), each the ``top_k`` largest."""
    w0, w1 = _window(events, window)
    host = [e for e in events
            if not e["plane"].startswith(DEVICE_PLANE_PREFIX)
            and e["name"] != window]
    per_dev: Dict[str, List[Tuple[int, int, str]]] = {}
    for e in events:
        if not e["plane"].startswith(DEVICE_PLANE_PREFIX):
            continue
        s, t = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        if t > s:
            per_dev.setdefault(e["plane"], []).append((s, t, e["name"]))
    n_dev = max(1, len(per_dev))
    busy_ns = 0.0
    op_ns: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []   # (length, midpoint)
    for plane in sorted(per_dev):
        for label, ns in self_times(per_dev[plane]).items():
            op_ns[label] = op_ns.get(label, 0.0) + ns
        merged = merge_intervals((s, t) for s, t, _ in per_dev[plane])
        busy_ns += union_total(merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    if not per_dev:
        gaps.append((w1 - w0, (w0 + w1) / 2))
    window_s = (w1 - w0) / 1e9
    busy_s = busy_ns / n_dev / 1e9
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top_k]
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_frac": 1.0 - busy_s / window_s if window_s > 0 else None,
        "devices": len(per_dev),
        "breakdown": {
            "device_ops": [[name, ns / n_dev / 1e9] for name, ns in ops],
            "idle_gaps": [[_host_doing(host, mid), ns / 1e9]
                          for ns, mid in gaps[:top_k]],
        },
    }
