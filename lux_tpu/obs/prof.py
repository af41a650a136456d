"""Device-timeline profiling: capture windows, region tags, and the
``profile.v1`` report.

The engobs phase fencing (iterlog.set_overlap) reports an overlap
*budget* — ``min(exchange_s, compute_s) / exchange_s`` on serialized
phases. This module measures the *realized* overlap from an actual
device timeline:

- ``region(name)`` wraps a code block in BOTH ``jax.named_scope`` (tags
  the lowered HLO ops, so device-stream events can be joined back to
  the region) and ``jax.profiler.TraceAnnotation`` (a host span when a
  capture is live). Names must match ``lux.[a-z0-9_.]+`` — the grammar
  the parser classifies on (luxlint LUX009 enforces it statically).
  Zero-cost when no profiler is armed: annotations inside jitted code
  only run at trace time, and the names are static strings, so arming
  a capture never changes an executable cache key (no recompiles).
- ``trace(dirname)`` / ``profile_window(run)`` / SIGUSR2 (see
  ``install_signal_handler``) open programmatic capture windows via
  ``jax.profiler``; bench.py ``--profile`` and the serve ``POST
  /profilez`` endpoint ride these.
- ``parse_dir`` / ``parse`` read the captured TensorBoard artifact
  (``*.trace.json.gz`` Chrome events — stdlib ``gzip`` + ``json``
  only) into a ``profile.v1`` report: per-device interval-union wall
  time for exchange- vs compute-tagged ops, their intersection →
  ``realized_hidden_frac`` (directly comparable to the engobs budget),
  device idle fraction against the capture window (the ``lux.prof.window``
  host span ``profile_window`` opens, else the trace's whole extent, so
  idle time before the first op and after the last counts), a top-K op
  table, and a steps-per-second
  cross-check against an iterlog summary.

Joining device events to regions: ``jax.named_scope`` does not name
trace events directly — it lands in the compiled HLO's per-instruction
``op_name`` metadata, while each device trace event carries its HLO
instruction name in ``args.hlo_op``. ``op_map_from_hlo`` parses the
compiled module text (``jitted.lower(...).compile().as_text()``) into
an instruction → region-tag map the parser joins against. NOTE: that
AOT ``.compile()`` costs one backend compile — run it inside a
sentinel ``expect`` window, never under ``watch``.

Malformed artifacts (truncated gzip, broken JSON, non-numeric
timestamps) raise ``ProfileParseError`` loudly — a profile that cannot
be trusted must never quietly report a wrong overlap number.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import itertools
import json
import os
import re
import signal
import sys
import threading

from ..utils import flags
from ..utils.locks import make_lock
from ..utils.logging import get_logger

_LOG = get_logger("prof")

# The region-name grammar. The parser classifies tags by their
# ``.exchange`` / ``.compute`` components, so every region threaded
# through an engine must fit this shape (LUX009).
NAME_RE = re.compile(r"lux\.[a-z0-9_.]+")

_EPS_US = 1e-3          # float-microsecond tolerance for invariants


class ProfileParseError(RuntimeError):
    """A captured artifact could not be parsed into a trustworthy
    report (truncated gzip, malformed JSON, non-numeric event fields,
    inconsistent interval math)."""


class CaptureBusyError(RuntimeError):
    """A profile capture window is already in flight in this process
    (jax.profiler supports one live session)."""


# The host span ``profile_window`` opens around the captured work: the
# idle-share denominator.
WINDOW = "lux.prof.window"


# -- region tagging --------------------------------------------------------


class _Region:
    """``named_scope`` + ``TraceAnnotation`` as one context manager.
    jax is imported lazily so ``lux_tpu.obs`` stays importable (and
    cheap) before backend configuration."""

    __slots__ = ("name", "_cms")

    def __init__(self, name: str):
        self.name = name
        self._cms = ()

    def __enter__(self):
        import jax

        self._cms = (jax.named_scope(self.name),
                     jax.profiler.TraceAnnotation(self.name))
        for cm in self._cms:
            cm.__enter__()
        return self

    def __exit__(self, *exc):
        for cm in reversed(self._cms):
            cm.__exit__(*exc)
        return False


def region(name: str) -> _Region:
    """Tag a code block as a named engine region (e.g.
    ``lux.pull_sharded.exchange``). Inside jitted code the scope tags
    the lowered ops; on the host it opens a profiler annotation span.
    The name must match ``lux.[a-z0-9_.]+``."""
    if not NAME_RE.fullmatch(name):
        raise ValueError(
            f"region name {name!r} breaks the lux.[a-z0-9_.]+ grammar "
            "the profile parser classifies on")
    return _Region(name)


def annotation(name: str):
    """A host span in the profiler's trace (``TraceAnnotation``), free
    when no capture is live. Without jax loaded no capture can be live,
    so this never imports it."""
    if "jax" not in sys.modules:
        return contextlib.nullcontext()
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


# -- capture windows -------------------------------------------------------

_CAP_IDS = itertools.count(1)
_capture_lock = threading.Lock()
_latest_lock = make_lock("obs.prof")
_latest_report = None
_sig_state = {"dir": None}


def trace(dirname):
    """Capture-window context manager: ``jax.profiler.trace`` into
    ``dirname``, or an inert ``nullcontext`` when ``dirname`` is falsy
    (the models/cli.py ``-profile`` contract)."""
    if not dirname:
        return contextlib.nullcontext()
    import jax

    os.makedirs(dirname, exist_ok=True)
    return jax.profiler.trace(dirname)


def profile_window(run, dirname=None, steps=None, op_maps=None,
                   iterlog_summary=None, top_k=10):
    """Run ``run()`` inside a fresh capture window under ``dirname``
    (default ``LUX_PROF_DIR``), parse the artifact, publish it as
    ``latest()``, and return ``(run_result, report)``.

    One window at a time per process: a second concurrent call raises
    ``CaptureBusyError`` instead of corrupting the live session."""
    d = dirname or flags.get("LUX_PROF_DIR")
    if not d:
        raise ValueError(
            "profiling is not armed: set LUX_PROF_DIR or pass dirname")
    if not _capture_lock.acquire(blocking=False):
        raise CaptureBusyError(
            "a profile capture window is already in flight")
    try:
        sub = os.path.join(d, f"cap_{os.getpid()}_{next(_CAP_IDS)}")
        with trace(sub):
            with annotation(WINDOW):
                out = run()
        rep = parse_dir(sub, op_maps=op_maps, steps=steps,
                        iterlog_summary=iterlog_summary, top_k=top_k)
        rep["capture_dir"] = sub
        _set_latest(rep)
        return out, rep
    finally:
        _capture_lock.release()


def latest():
    """The most recent ``profile.v1`` report captured in this process
    (``profile_window`` or the SIGUSR2 toggle), or None."""
    with _latest_lock:
        return _latest_report


def latest_realized():
    """``realized_hidden_frac`` of the latest captured profile, or None
    — surfaced next to the engobs budget so the two are never
    conflated."""
    rep = latest()
    if rep is None:
        return None
    return rep.get("realized_hidden_frac")


def _set_latest(rep):
    global _latest_report
    with _latest_lock:
        _latest_report = rep


def install_signal_handler(signum=None) -> bool:
    """Arm the capture toggle on ``signum`` (default SIGUSR2, riding
    next to the flight recorder's SIGUSR1): first signal starts a
    capture into ``LUX_PROF_DIR``, the second stops it, parses the
    artifact, writes ``profile_v1.json`` next to it, and publishes
    ``latest()``. Returns False (no-op) off the main thread."""
    signum = signal.SIGUSR2 if signum is None else signum
    try:
        signal.signal(signum, _on_signal)
        return True
    except ValueError:
        return False


def _on_signal(signum, frame):
    # Signal context: never raise.
    try:
        _toggle_capture()
    except Exception as e:
        _LOG.warning("profile capture toggle failed: %r", e)


def _toggle_capture():
    d = flags.get("LUX_PROF_DIR")
    if not d:
        _LOG.warning("SIGUSR2 ignored: LUX_PROF_DIR is not set")
        return
    import jax

    if _sig_state["dir"] is None:
        if not _capture_lock.acquire(blocking=False):
            _LOG.warning("SIGUSR2 ignored: a capture is already live")
            return
        sub = os.path.join(d, f"sig_{os.getpid()}_{next(_CAP_IDS)}")
        os.makedirs(sub, exist_ok=True)
        try:
            jax.profiler.start_trace(sub)
        except Exception:
            _capture_lock.release()
            raise
        _sig_state["dir"] = sub
        _LOG.info("profile capture started -> %s (SIGUSR2 again to "
                  "stop)", sub)
        return
    sub, _sig_state["dir"] = _sig_state["dir"], None
    try:
        jax.profiler.stop_trace()
        rep = parse_dir(sub)
        rep["capture_dir"] = sub
        out = os.path.join(sub, "profile_v1.json")
        with open(out, "w") as f:
            json.dump(rep, f, indent=1)
        _set_latest(rep)
        _LOG.info("profile capture stopped: %s (realized_hidden_frac="
                  "%s)", out, rep.get("realized_hidden_frac"))
    finally:
        _capture_lock.release()


# -- artifact discovery + loading ------------------------------------------


def find_trace_artifact(dirname: str) -> str:
    """Newest ``*.trace.json.gz`` under ``dirname`` (jax writes
    ``<dir>/plugins/profile/<run>/<host>.trace.json.gz``)."""
    pats = (os.path.join(dirname, "**", "*.trace.json.gz"),
            os.path.join(dirname, "*.trace.json.gz"))
    cands = sorted({p for pat in pats for p in glob.glob(pat,
                                                         recursive=True)})
    if not cands:
        raise ProfileParseError(
            f"no *.trace.json.gz artifact under {dirname!r} — did the "
            "capture window actually run?")
    return max(cands, key=os.path.getmtime)


def load_chrome_trace(path: str) -> dict:
    """gzip+json load of a Chrome-trace artifact. Truncated or
    corrupt data raises ``ProfileParseError`` — never a wrong report."""
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, EOFError, ValueError, UnicodeDecodeError) as e:
        raise ProfileParseError(
            f"cannot read Chrome trace {path!r}: {e!r}") from e
    if isinstance(doc, list):
        doc = {"traceEvents": doc}
    if not isinstance(doc, dict) or not isinstance(
            doc.get("traceEvents"), list):
        raise ProfileParseError(
            f"{path!r} is not a Chrome trace (no traceEvents list)")
    return doc


# -- HLO op-name join ------------------------------------------------------

_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^,\s]+)", re.M)
_HLO_OP_RE = re.compile(r"%([\w.-]+)\s*=\s*[^\n]*?op_name=\"([^\"]+)\"")


def op_map_from_hlo(hlo_text: str) -> dict:
    """Instruction-name → innermost region tag for one compiled module
    (``jitted.lower(...).compile().as_text()``). Device trace events
    carry their HLO instruction name in ``args.hlo_op``; this is the
    join key that puts region tags on device-stream intervals."""
    m = _HLO_MODULE_RE.search(hlo_text)
    ops = {}
    for im in _HLO_OP_RE.finditer(hlo_text):
        tags = NAME_RE.findall(im.group(2))
        if tags:
            # Innermost scope wins, except that a phase tag stays: a
            # kernel scope (lux.tiled.strip_scan) nested in a sharded
            # engine's lux.*.compute must still count as compute.
            phased = [t for t in tags if _phase_of(t)]
            ops[im.group(1)] = (phased or tags)[-1]
    return {"module": m.group(1) if m else None, "ops": ops}


def op_map_for(jitted, *args, **kwargs) -> dict:
    """``op_map_from_hlo`` over an AOT-compiled jitted callable.
    COSTS ONE BACKEND COMPILE — call under ``sentinel.expect``."""
    text = jitted.lower(*args, **kwargs).compile().as_text()
    return op_map_from_hlo(text)


def _merge_op_maps(op_maps):
    by_module = {}
    by_op = {}
    for om in op_maps or ():
        module = om.get("module")
        for op, tag in (om.get("ops") or {}).items():
            by_module[(module, op)] = tag
            if op in by_op and by_op[op] != tag:
                by_op[op] = None              # ambiguous across modules
            else:
                by_op.setdefault(op, tag)
    return by_module, by_op


# -- interval math ---------------------------------------------------------


def merge_intervals(intervals):
    """Sorted, coalesced (start, end) list; tolerates out-of-order
    input and zero-length intervals."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_total(merged) -> float:
    return sum(e - s for s, e in merged)


def intersect_merged(a, b):
    """Intersection of two merged interval lists (two-pointer walk)."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


# -- parsing ---------------------------------------------------------------


def _num(ev, key, default=None):
    v = ev.get(key, default)
    if v is None:
        return default
    try:
        return float(v)
    except (TypeError, ValueError):
        raise ProfileParseError(
            f"event {ev.get('name')!r} has non-numeric {key}={v!r}")


def parse(path: str, op_maps=None, steps=None, iterlog_summary=None,
          top_k: int = 10) -> dict:
    """Parse one Chrome-trace artifact into a ``profile.v1`` report."""
    return parse_events(load_chrome_trace(path), op_maps=op_maps,
                        steps=steps, iterlog_summary=iterlog_summary,
                        top_k=top_k)


def parse_dir(dirname: str, op_maps=None, steps=None,
              iterlog_summary=None, top_k: int = 10) -> dict:
    """``parse`` over the newest artifact under a capture directory."""
    return parse(find_trace_artifact(dirname), op_maps=op_maps,
                 steps=steps, iterlog_summary=iterlog_summary,
                 top_k=top_k)


def _phase_of(tag):
    if tag is None:
        return None
    if ".exchange" in tag:
        return "exchange"
    if ".compute" in tag:
        return "compute"
    return None


def parse_events(doc: dict, op_maps=None, steps=None,
                 iterlog_summary=None, top_k: int = 10) -> dict:
    """The ``profile.v1`` builder over an in-memory Chrome-trace doc.

    Device streams are keyed by pid (one pid per device in TPU
    captures; the shared host process in CPU captures). Only events
    carrying ``args.hlo_op`` count as device work — host-side
    ``TraceAnnotation`` spans are tracked separately (async dispatch
    would otherwise fake overlap that never happened on the device)."""
    by_module, by_op = _merge_op_maps(op_maps)
    procs, threads = {}, {}
    dev = {}                 # pid -> phase -> [(s, e)]
    host_regions = {}
    top = {}
    window = None            # the longest WINDOW host span
    extent = None            # (first start, last end) of every X event
    for ev in doc["traceEvents"]:
        if not isinstance(ev, dict):
            raise ProfileParseError(f"non-object trace event: {ev!r}")
        ph = ev.get("ph")
        if ph == "M":
            a = ev.get("args") or {}
            if ev.get("name") == "process_name":
                procs[ev.get("pid")] = a.get("name")
            elif ev.get("name") == "thread_name":
                threads[(ev.get("pid"), ev.get("tid"))] = a.get("name")
            continue
        if ph != "X":
            continue
        name = ev.get("name")
        ts = _num(ev, "ts")
        if ts is None:
            raise ProfileParseError(f"X event {name!r} has no ts")
        dur = _num(ev, "dur", 0.0) or 0.0
        extent = ((ts, ts + dur) if extent is None else
                  (min(extent[0], ts), max(extent[1], ts + dur)))
        args = ev.get("args") or {}
        hlo_op = args.get("hlo_op")
        if hlo_op is None and name == WINDOW:
            if window is None or dur > window[1] - window[0]:
                window = (ts, ts + dur)
        if hlo_op is None and isinstance(name, str) \
                and NAME_RE.fullmatch(name):
            rec = host_regions.setdefault(
                name, {"count": 0, "total_us": 0.0})
            rec["count"] += 1
            rec["total_us"] += dur
            continue
        if hlo_op is None:
            continue
        tag = by_module.get((args.get("hlo_module"), hlo_op))
        if tag is None:
            tag = by_op.get(hlo_op)
        d = dev.setdefault(ev.get("pid"), {
            "exchange": [], "compute": [], "busy": []})
        d["busy"].append((ts, ts + dur))
        phase = _phase_of(tag)
        if phase:
            d[phase].append((ts, ts + dur))
        t = top.setdefault(name, {"op": name, "total_us": 0.0,
                                  "count": 0, "tag": tag})
        t["total_us"] += dur
        t["count"] += 1
        if t["tag"] is None:
            t["tag"] = tag

    devices = {}
    tot_ex = tot_ov = 0.0
    span_lo, span_hi = None, None
    # Idle is measured against the capture window, not the span from the
    # first device op to the last: idle before and after counts too.
    w0, w1 = window or extent or (0.0, 0.0)
    window_us = w1 - w0
    for pid, d in dev.items():
        ex = merge_intervals(d["exchange"])
        co = merge_intervals(d["compute"])
        busy = merge_intervals(d["busy"])
        both = merge_intervals(d["exchange"] + d["compute"])
        ex_us, co_us = union_total(ex), union_total(co)
        ov_us = union_total(intersect_merged(ex, co))
        un_us = union_total(both)
        busy_us = union_total(busy)
        busy_in_window = union_total(
            intersect_merged(busy, [(w0, w1)]) if window_us > 0 else [])
        lo = min(s for s, _ in busy) if busy else 0.0
        hi = max(e for _, e in busy) if busy else 0.0
        span_us = hi - lo
        if busy:
            span_lo = lo if span_lo is None else min(span_lo, lo)
            span_hi = hi if span_hi is None else max(span_hi, hi)
        frac = min(max(ov_us / ex_us, 0.0), 1.0) if ex_us > 0 else None
        devices[str(pid)] = {
            "device": procs.get(pid) or f"pid:{pid}",
            "exchange_us": ex_us,
            "compute_us": co_us,
            "overlap_us": ov_us,
            "union_us": un_us,
            "busy_us": busy_us,
            "span_us": span_us,
            "window_us": window_us,
            "idle_frac": (1.0 - busy_in_window / window_us
                          if window_us > 0 else None),
            "realized_hidden_frac": frac,
        }
        tot_ex += ex_us
        tot_ov += ov_us

    report = {
        "schema": "profile.v1",
        "devices": devices,
        "host_regions": host_regions,
        "tags": sorted(
            {t["tag"] for t in top.values() if t["tag"]}
            | set(host_regions)),
        "top_ops": sorted(top.values(), key=lambda t: -t["total_us"])
        [:max(int(top_k), 0)],
        "realized_hidden_frac": (
            min(max(tot_ov / tot_ex, 0.0), 1.0) if tot_ex > 0 else None),
    }
    span_s = ((span_hi - span_lo) / 1e6
              if span_lo is not None and span_hi > span_lo else None)
    steps_block = {"device_span_s": span_s}
    if steps is not None:
        steps_block["captured"] = int(steps)
        if span_s:
            steps_block["steps_per_s"] = int(steps) / span_s
    if iterlog_summary:
        n = iterlog_summary.get("num_iters") or 0
        ex_s = iterlog_summary.get("execute_s") or 0.0
        steps_block["iterlog"] = {
            "num_iters": n, "execute_s": ex_s,
            "steps_per_s": (n / ex_s) if ex_s > 0 else None,
        }
    report["steps"] = steps_block
    return validate(report)


def validate(report: dict) -> dict:
    """Check a ``profile.v1`` report's schema and interval invariants;
    raises ``ProfileParseError`` on any violation, returns the report
    unchanged otherwise."""
    if not isinstance(report, dict) or report.get("schema") != "profile.v1":
        raise ProfileParseError(
            f"not a profile.v1 report: schema={report.get('schema')!r}"
            if isinstance(report, dict) else
            f"not a profile.v1 report: {type(report).__name__}")
    devices = report.get("devices")
    if not isinstance(devices, dict):
        raise ProfileParseError("profile.v1 report has no devices map")
    for pid, d in devices.items():
        ex, co = d.get("exchange_us"), d.get("compute_us")
        ov, un = d.get("overlap_us"), d.get("union_us")
        for key, v in (("exchange_us", ex), ("compute_us", co),
                       ("overlap_us", ov), ("union_us", un)):
            if not isinstance(v, (int, float)) or v < 0:
                raise ProfileParseError(
                    f"device {pid}: bad {key}={v!r}")
        if un + _EPS_US < max(ex, co):
            raise ProfileParseError(
                f"device {pid}: union {un} < max phase {max(ex, co)}")
        if un > ex + co + _EPS_US:
            raise ProfileParseError(
                f"device {pid}: union {un} > exchange+compute {ex + co}")
        if ov > min(ex, co) + _EPS_US:
            raise ProfileParseError(
                f"device {pid}: overlap {ov} > min phase {min(ex, co)}")
        for key in ("realized_hidden_frac", "idle_frac"):
            v = d.get(key)
            if v is not None and not 0.0 <= v <= 1.0:
                raise ProfileParseError(
                    f"device {pid}: {key}={v!r} outside [0, 1]")
    frac = report.get("realized_hidden_frac")
    if frac is not None and not 0.0 <= frac <= 1.0:
        raise ProfileParseError(
            f"realized_hidden_frac={frac!r} outside [0, 1]")
    return report


# -- rendering -------------------------------------------------------------


def format_report(report: dict) -> str:
    """Compact human rendering of a ``profile.v1`` report (shared by
    tools/prof_summary.py and trace_summary.py ``--phases``)."""
    lines = ["profile.v1 device timeline:"]
    frac = report.get("realized_hidden_frac")
    lines.append(
        "  realized_hidden_frac={} (device-measured; compare to the "
        "engobs budget, an upper bound)".format(
            "n/a" if frac is None else f"{frac:.3f}"))
    lines.append("  {:<26} {:>12} {:>12} {:>11} {:>10} {:>9}".format(
        "device", "exchange_us", "compute_us", "overlap_us",
        "realized", "idle"))
    for pid in sorted(report.get("devices") or {}):
        d = report["devices"][pid]
        lines.append(
            "  {:<26} {:>12.0f} {:>12.0f} {:>11.0f} {:>10} {:>9}".format(
                str(d.get("device"))[:26], d["exchange_us"],
                d["compute_us"], d["overlap_us"],
                "-" if d.get("realized_hidden_frac") is None
                else f"{d['realized_hidden_frac']:.3f}",
                "-" if d.get("idle_frac") is None
                else f"{d['idle_frac']:.3f}"))
    if report.get("host_regions"):
        lines.append("  host regions:")
        for name in sorted(report["host_regions"]):
            rec = report["host_regions"][name]
            lines.append(
                f"    {name:<32} x{rec['count']:<5} "
                f"{rec['total_us']:.0f} us")
    if report.get("top_ops"):
        lines.append("  top ops:")
        for t in report["top_ops"]:
            lines.append(
                "    {:<38} {:>10.0f} us x{:<5} {}".format(
                    str(t["op"])[:38], t["total_us"], t["count"],
                    t.get("tag") or "-"))
    st = report.get("steps") or {}
    if st.get("captured") is not None:
        rate = st.get("steps_per_s")
        lines.append(
            "  steps: {} captured over {} of device span ({})".format(
                st["captured"],
                "n/a" if st.get("device_span_s") is None
                else f"{st['device_span_s']:.4f}s",
                "n/a" if rate is None else f"{rate:.1f} steps/s"))
        il = st.get("iterlog")
        if il:
            lines.append(
                "  iterlog cross-check: {num_iters} iters / "
                "{execute_s:.4f}s execute ({rate})".format(
                    rate=("n/a" if il.get("steps_per_s") is None
                          else f"{il['steps_per_s']:.1f} steps/s"),
                    **{k: il[k] for k in ("num_iters", "execute_s")}))
    return "\n".join(lines)
