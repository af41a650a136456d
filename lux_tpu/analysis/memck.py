"""luxlint --memory: static HBM-footprint contracts (LUX701-706).

The eighth luxlint tier. Every capacity question the serving and bench
layers ask — "does this engine fit?", "how many graphs can stay
resident?" — is answered here *offline*, the way LUX401-407 prove the
exchange and LUX601-606 prove the algebra: by walking evidence the
framework already produces, not by trying it and OOMing.

The core is a donation-aware buffer-liveness walk over every traced
registry target (``ir.registry_targets()`` / ``trace_step()``, all
engine kinds x the compact/frontier exchange variants). Engine step
inputs (device graph + carry state) are *pinned* — they live for the
engine's lifetime, not the step's — while traced intermediates allocate
at their defining eqn and free at their last use, in schedule order.
Scopes nest: descending into a ``shard_map`` sub-jaxpr switches the
byte scale from the per-device share (``1/P`` of the global aval) to
the per-shard shapes the body already carries, so the walk prices
**per-device peak live bytes** directly. A donated carry whose alias
the lowered HLO actually honors is credited back (the output reuses the
input buffer); an unhonored donation is *priced* — both copies stay in
the peak — which is what turns LUX104's "audited" into LUX702's
"priced".

Each peak decomposes, by attributing every live-at-peak buffer to the
probe graph's per-part vertex/edge counts, into a closed-form model

    f(nv, ne, P, K, exchange_mode) =
        per_vertex_bytes * ceil(nv/P) + per_edge_bytes * ceil(ne/P)
        + fixed_bytes

whose honesty LUX704 proves by re-tracing representatives at a swept
scale. The models persist as a content-addressed ``memcap.v1`` artifact
(``analysis/memcap.json``, tamper-rejected exactly like ``gascap.v1``)
— the formula serving trusts: :func:`predicted_engine_bytes` is the
admission formula the HBM-budgeted EnginePool (serve/pool.py) and the
tuner's candidate pruning (tune/space.py) both consult, and LUX706
fails verify the moment that committed formula drifts from a fresh
derivation.

Rules:

- **LUX701 footprint-structure** — the memcap.v1 artifact and every
  model in it are well-formed, and every current registry target is
  covered (a new program/kind fails verify until regenerated);
- **LUX702 donation-leak** — a donated carry whose alias is absent
  from the lowered HLO silently doubles peak; flagged and priced;
- **LUX703 peak-vs-budget** — the derived model at the declared bench
  scales (LUX_BENCH_SCALE/LUX_BENCH_EF) must fit the device-profile
  HBM capacity; fails closed on overcommit;
- **LUX704 model-honesty** — the closed-form formula upper-bounds the
  traced peak within LUX_MEM_MODEL_TOL across a scale sweep;
- **LUX705 exchange-staging** — full/compact/frontier staging buffers
  are counted in the peak and cross-checked against
  ``exchange_bytes_per_iter()`` / ``frontier_evidence()``;
- **LUX706 residency-drift** — the committed artifact's admission
  formula still reproduces the freshly traced peaks.

Fixture modules (``luxlint --memory <paths>``) may define any of:
``TARGETS`` (name -> trace-spec dict, with ``nv``/``ne`` probe dims),
``MODELS`` (name -> model dict, checked by LUX704), ``CAPACITY_BYTES``
(+ optional ``SCALES``; checked by LUX703), ``MEMCAP`` (an artifact
dict; structure-checked by LUX701), and ``COMMITTED`` (a stand-in
committed artifact; drift-checked by LUX706).
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import math
import os
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from lux_tpu.analysis import ir
from lux_tpu.analysis.core import (FileResult, Finding, LintReport,
                                   iter_python_files)
from lux_tpu.utils import flags

MEMORY_SCHEMA = "luxlint-memory.v1"
CAP_SCHEMA = "memcap.v1"
CAP_FILENAME = "memcap.json"

# Every model entry must carry exactly these (LUX701).
MODEL_FIELDS = ("per_vertex_bytes", "per_edge_bytes", "fixed_bytes")

# LUX704's over-fat arm only fires when the absolute slack also clears
# this floor: probe graphs are ~100 vertices, so tile-padding quantises
# tiny buffers into the linear terms and over-predicts re-traces by a
# few dozen KiB — noise, not a model that rejects admissible engines.
_OVERFAT_FLOOR_BYTES = 1 << 20

__all__ = [
    "MEMORY_SCHEMA", "CAP_SCHEMA", "CAP_FILENAME", "MemRule",
    "all_memory_rules", "prove_registry", "verify_registry",
    "verify_fixture_paths", "build_memcap", "save_memcap", "load_memcap",
    "memcap_path", "eval_model", "predicted_engine_bytes",
    "hbm_budget_bytes", "target_peak_bytes",
]


@dataclasses.dataclass(frozen=True)
class MemRule:
    id: str
    title: str
    doc: str


MEMORY_RULES = (
    MemRule(
        "LUX701", "footprint-structure",
        "the memcap.v1 artifact and every closed-form model in it are "
        "well-formed (finite coefficients, positive peaks, positive "
        "probe dims) and every current registry target has an entry — "
        "a new program or engine kind fails verify until the artifact "
        "is regenerated"),
    MemRule(
        "LUX702", "donation-leak",
        "every donated carry buffer must be aliased to an output by "
        "the lowered HLO; an unhonored donation keeps both copies "
        "live, silently doubling the carry's share of peak — flagged "
        "AND priced into the footprint (extends LUX104 from audited "
        "to priced)"),
    MemRule(
        "LUX703", "peak-vs-budget",
        "the derived footprint model evaluated at the declared bench "
        "scales must fit the device-profile HBM capacity "
        "(hbm_capacity_bytes, LUX_HBM_CAPACITY_BYTES override); "
        "overcommit fails closed before any shard ships"),
    MemRule(
        "LUX704", "model-honesty",
        "the closed-form f(nv, ne, P, K, mode) must upper-bound the "
        "traced per-device peak within LUX_MEM_MODEL_TOL across a "
        "scale sweep — this formula is what serving admission trusts"),
    MemRule(
        "LUX705", "exchange-staging",
        "full/compact/frontier exchange staging buffers must be "
        "counted in the traced peak and the engine's "
        "exchange_bytes_per_iter() claim must match the collectives "
        "the jaxpr actually moves (frontier_evidence() internally "
        "consistent)"),
    MemRule(
        "LUX706", "residency-drift",
        "serving's admission formula (the committed memcap.v1 models "
        "behind predicted_engine_bytes) must still reproduce freshly "
        "traced peaks within LUX_MEM_MODEL_TOL; drift fails verify "
        "until the artifact is regenerated"),
)


def all_memory_rules() -> List[MemRule]:
    return list(MEMORY_RULES)


def _f(rule: str, path: str, message: str, line: int = 0) -> Finding:
    return Finding(rule, path, line, 0, message)


def _mib(n: float) -> str:
    return f"{n / 2**20:.2f} MiB"


# -- the donation-aware liveness walk -------------------------------------


def _is_literal(v) -> bool:
    from jax.extend import core as jcore

    return isinstance(v, jcore.Literal)


def _eqn_subjaxprs(eqn) -> List:
    out = []
    for v in eqn.params.values():
        out.extend(ir._as_jaxprs(v))
    return out


def _entry(v, scale: float) -> Tuple[float, float, int]:
    """(scaled bytes, scaled element count, itemsize) for one var."""
    aval = v.aval
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return (0.0, 0.0, 1)
    elems = float(np.prod(shape, dtype=np.float64)) if shape else 1.0
    itemsize = int(np.dtype(dtype).itemsize)
    return (elems * itemsize * scale, elems * scale, itemsize)


def _walk_scope(jaxpr, scale: float):
    """Schedule-order liveness over one jaxpr scope.

    Returns ``(peak_bytes, snapshot, input_bytes)`` where ``snapshot``
    is the list of (bytes, elems, itemsize) entries live at the peak
    program point. Scope inputs and outputs are pinned (engine
    residency: graph tables and carry state live across steps);
    intermediates free at their last use. A sub-jaxpr contributes its
    own peak *minus its input bytes* at the owning eqn's program point
    (the operands are already counted in this scope) — sequential
    sub-jaxprs (while cond/body, cond branches) never coexist, so the
    max over them is the bound.
    """
    last: Dict[object, int] = {}
    for k, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last[v] = k
    pinned = set()
    live: Dict[object, Tuple[float, float, int]] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        pinned.add(v)
        if v not in live:
            live[v] = _entry(v, scale)
    for v in jaxpr.outvars:
        if not _is_literal(v):
            pinned.add(v)
    input_bytes = sum(e[0] for e in live.values())
    current = input_bytes
    peak, snap = current, list(live.values())
    for k, eqn in enumerate(jaxpr.eqns):
        # Per-shard shapes start inside shard_map; everything else
        # (pjit/scan/while/cond) keeps the enclosing scale.
        inner = 1.0 if eqn.primitive.name == "shard_map" else scale
        sub_extra, sub_snap = 0.0, []
        for sub in _eqn_subjaxprs(eqn):
            p_sub, s_sub, in_sub = _walk_scope(sub, inner)
            extra = p_sub - in_sub
            if extra > sub_extra:
                # The sub-scope's input entries are this scope's operand
                # buffers — already in ``live`` here. Trim them from the
                # merged snapshot or attribution double-prices them and
                # the calibrated constant goes negative to compensate.
                trimmed = list(s_sub)
                for v in list(sub.invars) + list(sub.constvars):
                    try:
                        trimmed.remove(_entry(v, inner))
                    except ValueError:
                        pass
                sub_extra, sub_snap = extra, trimmed
        alloc = [(v, _entry(v, scale)) for v in eqn.outvars
                 if not _is_literal(v)]
        alloc_bytes = sum(e[0] for _, e in alloc)
        cand = current + alloc_bytes + sub_extra
        if cand > peak:
            peak = cand
            snap = list(live.values()) + [e for _, e in alloc] + sub_snap
        for v, e in alloc:
            live[v] = e
        current += alloc_bytes
        for v in [v for v in live if last.get(v) == k and v not in pinned]:
            current -= live[v][0]
            del live[v]
    return peak, snap, input_bytes


def _staging_bytes(jaxpr, scale: float, parts: int) -> float:
    """Scaled bytes of data-collective result buffers one step
    materializes (``cond`` branches are alternatives: max)."""
    total = 0.0
    for eqn in jaxpr.eqns:
        inner = 1.0 if eqn.primitive.name == "shard_map" else scale
        branch = [_staging_bytes(s, inner, parts)
                  for s in _eqn_subjaxprs(eqn)]
        if branch:
            if eqn.primitive.name == "cond":
                total += max(branch)
            else:
                total += sum(branch)
        if ir._is_data_collective(eqn.primitive.name):
            total += sum(_entry(v, scale)[0] for v in eqn.outvars
                         if not _is_literal(v))
    return total


def _donation_report(target) -> dict:
    """Alias accounting for the target's donated args (one abstract
    lowering, the LUX104 mechanics): how many donated leaves exist, how
    many the lowered HLO aliases, and the un-aliased byte leak."""
    import jax

    leaves = []
    for i in target.donate:
        if i < len(target.args):
            leaves.extend(jax.tree_util.tree_leaves(target.args[i]))
    declared = len(leaves)
    total_bytes = int(sum(int(getattr(x, "nbytes", 0) or
                              np.asarray(x).nbytes) for x in leaves))
    rep = {"declared": declared, "aliased": 0,
           "donated_bytes": total_bytes, "leak_bytes": 0,
           "leaves": leaves, "checked": False}
    if declared == 0 or target.lower is None:
        return rep
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered = target.lower()
    sig = ir._main_arg_attrs(lowered.as_text())
    if sig is None:
        return rep
    rep["checked"] = True
    aliased = sig.count("tf.aliasing_output") + sig.count("jax.buffer_donor")
    rep["aliased"] = min(aliased, declared)
    if aliased < declared:
        # No per-leaf pairing in the signature: price the whole carry
        # conservatively (zero credit, full leak).
        rep["aliased"] = min(aliased, declared)
        rep["leak_bytes"] = total_bytes
    return rep


# -- attribution -> the closed-form model ---------------------------------


def _classify(elems: float, nv_p: int, ne_p: int) -> str:
    """vertex | edge | fixed: which probe unit this buffer scales
    with, by relative distance to an integer multiple."""
    best_kind, best_dist = "fixed", 0.5
    for kind, unit in (("vertex", nv_p), ("edge", ne_p)):
        if unit <= 0 or elems <= 0:
            continue
        r = elems / unit
        m = round(r)
        if m < 1:
            continue
        dist = abs(r - m) / r
        if dist < best_dist - 1e-9:
            best_kind, best_dist = kind, dist
    return best_kind


def _attribute(snapshot, nv_p: int, ne_p: int) -> Tuple[float, float, float]:
    per_vertex = per_edge = fixed = 0.0
    for bytes_s, elems_s, _ in snapshot:
        kind = _classify(abs(elems_s), nv_p, ne_p)
        if kind == "edge":
            per_edge += bytes_s / ne_p
        elif kind == "vertex":
            per_vertex += bytes_s / nv_p
        else:
            fixed += bytes_s
    return per_vertex, per_edge, fixed


def eval_model(model: dict, nv: int, ne: int, parts: int,
               k: Optional[int] = None,
               k_probe: Optional[int] = None) -> float:
    """Per-device predicted peak bytes of one entry's model at the
    given scale. ``k`` lanes beyond the probe's scale the
    vertex-proportional term (lane state is (nv, K)-shaped); the graph
    tables in the edge term are lane-independent."""
    parts = max(1, int(parts))
    nv_p = max(1, math.ceil(int(nv) / parts))
    ne_p = max(1, math.ceil(int(ne) / parts))
    pv = float(model["per_vertex_bytes"])
    if k and k_probe and int(k) != int(k_probe):
        pv *= max(1.0, float(k) / float(k_probe))
    out = (pv * nv_p + float(model["per_edge_bytes"]) * ne_p
           + float(model["fixed_bytes"]))
    return max(0.0, out)


def target_peak_bytes(target, meta: dict) -> dict:
    """Trace one target and derive its footprint evidence: the traced
    per-device peak (donation-credited), the attribution-derived model,
    staging bytes, and the donation report. Raises on trace failure."""
    closed = ir.trace_target(target)
    parts = max(1, int(meta.get("parts", 1)))
    scale = 1.0 / parts if parts > 1 else 1.0
    peak_raw, snapshot, _ = _walk_scope(closed.jaxpr, scale)
    # ClosedJaxpr consts back constvars, which the walk already counted
    # through their avals; nothing to add.
    don = _donation_report(target)
    credit = 0.0
    if don["declared"] and don["checked"] and not don["leak_bytes"]:
        # Honored donation: the new carry writes over the old one's
        # buffer — credit the donated leaves back, as negative snapshot
        # entries so the model's coefficients carry the credit too.
        for leaf in don["leaves"]:
            b, e, i = _entry(_Shaped(leaf), scale)
            credit += b
            snapshot = snapshot + [(-b, e, i)]
    peak = max(0.0, peak_raw - credit)
    nv_p = max(1, math.ceil(int(meta["nv"]) / parts))
    ne_p = max(1, math.ceil(int(meta["ne"]) / parts))
    pv, pe, fixed = _attribute(snapshot, nv_p, ne_p)
    pv, pe = max(0.0, round(pv, 6)), max(0.0, round(pe, 6))
    # Calibrate the constant term against the *rounded* peak the
    # artifact persists, so the model bounds peak_bytes exactly at the
    # probe scale (calibrating against the float peak can land the
    # prediction a sub-byte hair under its own ceil).
    peak_i = int(math.ceil(peak))
    fixed = int(math.ceil(peak_i - pv * nv_p - pe * ne_p))
    model = {"per_vertex_bytes": pv, "per_edge_bytes": pe,
             "fixed_bytes": fixed}
    staging = _staging_bytes(closed.jaxpr, scale, parts)
    don.pop("leaves", None)
    return {
        "closed": closed,
        "peak_bytes": peak_i,
        "model": model,
        "staging_bytes": int(math.ceil(staging)),
        "donation": don,
    }


class _Shaped:
    """Adapter: gives a concrete array the .aval face _entry expects."""

    def __init__(self, x):
        self.aval = np.asarray(x)


# -- per-target rules -----------------------------------------------------


def _bench_scales() -> List[Tuple[int, int]]:
    scale = flags.get_int("LUX_BENCH_SCALE")
    ef = flags.get_int("LUX_BENCH_EF")
    nv = 1 << scale
    return [(nv, nv * ef)]


def _capacity_bytes() -> Optional[int]:
    from lux_tpu.obs import report

    cap = report.device_profile().get("hbm_capacity_bytes")
    return int(cap) if cap else None


def _check_budget(name: str, entry: dict, capacity: Optional[int],
                  scales: Sequence[Tuple[int, int]]) -> List[Finding]:
    if not capacity:
        return []
    out = []
    for nv, ne in scales:
        pred = eval_model(entry["model"], nv, ne, entry["parts"],
                          k=entry["k"], k_probe=entry["k"])
        if pred > capacity:
            out.append(_f(
                "LUX703", name,
                f"predicted per-device peak {_mib(pred)} at bench scale "
                f"nv={nv} ne={ne} exceeds the device HBM capacity "
                f"{_mib(capacity)} — overcommit fails closed here, not "
                "on-device"))
    return out


def _check_model_honesty(name: str, model: dict, traced_peak: float,
                         nv: int, ne: int, parts: int,
                         k: Optional[int] = None,
                         k_probe: Optional[int] = None) -> List[Finding]:
    tol = flags.get_float("LUX_MEM_MODEL_TOL")
    pred = eval_model(model, nv, ne, parts, k=k, k_probe=k_probe)
    if pred + 1e-6 < traced_peak:
        return [_f(
            "LUX704", name,
            f"model predicts {_mib(pred)} at nv={nv} ne={ne} P={parts} "
            f"but the traced peak is {_mib(traced_peak)} — the formula "
            "serving trusts under-estimates the footprint")]
    if (traced_peak > 0 and pred > traced_peak * (1.0 + tol)
            and pred - traced_peak > _OVERFAT_FLOOR_BYTES):
        return [_f(
            "LUX704", name,
            f"model predicts {_mib(pred)} at nv={nv} ne={ne} P={parts} "
            f"vs traced peak {_mib(traced_peak)} — slack exceeds "
            f"LUX_MEM_MODEL_TOL={tol:g}; an over-fat model rejects "
            "admissible engines")]
    return []


def _check_staging(name: str, target, closed, evidence: dict,
                   staging: float, parts: int) -> List[Finding]:
    out: List[Finding] = []
    mode = target.exchange_mode
    if mode in ("full", "compact", "frontier") and parts > 1:
        if staging <= 0:
            out.append(_f(
                "LUX705", name,
                f"{mode}-exchange target stages no data-collective "
                "buffers in the traced step — the exchange cost is "
                "missing from the peak accounting"))
        claim = target.exchange_bytes
        if claim is not None:
            totals = ir._collective_byte_totals(closed.jaxpr, parts)
            if totals and claim not in totals:
                shown = sorted(totals)[:4]
                out.append(_f(
                    "LUX705", name,
                    f"exchange_bytes_per_iter() claims {claim} B/iter "
                    f"but the traced collectives move {shown} — the "
                    "staging the peak prices and the claim serving "
                    "reports have diverged"))
    if evidence:
        p = parts
        want = (p * (p - 1) * int(evidence.get("frontier_max_sends", 0))
                * int(evidence.get("frontier_row_bytes", 0)))
        got = int(evidence.get("frontier_bytes_per_iter", -1))
        if got != want or int(evidence.get("frontier_fill_active", 0)):
            out.append(_f(
                "LUX705", name,
                f"frontier_evidence() is internally inconsistent "
                f"(bytes_per_iter {got} vs P*(P-1)*max_sends*row_bytes "
                f"= {want}, fill_active "
                f"{evidence.get('frontier_fill_active')}) — the "
                "frontier staging bound cannot be trusted in the peak"))
    return out


def _check_drift(name: str, committed: Optional[dict], entry: dict
                 ) -> List[Finding]:
    if committed is None:
        return []
    tol = flags.get_float("LUX_MEM_MODEL_TOL")
    cent = (committed.get("targets") or {}).get(name)
    if cent is None:
        return [_f(
            "LUX701", name,
            f"registry target {name!r} has no entry in the committed "
            "memcap.v1 — regenerate with `luxlint --memory --memcap-out "
            "lux_tpu/analysis/memcap.json`")]
    try:
        pred = eval_model(cent["model"], entry["probe"]["nv"],
                          entry["probe"]["ne"], entry["parts"],
                          k=entry["k"], k_probe=cent.get("k"))
    except (KeyError, TypeError, ValueError) as e:
        return [_f("LUX701", name,
                   f"committed memcap.v1 entry is malformed: {e!r}")]
    peak = float(entry["peak_bytes"])
    if pred + 1e-6 < peak or (peak > 0 and pred > peak * (1.0 + tol)):
        return [_f(
            "LUX706", name,
            f"committed admission formula predicts {_mib(pred)} but a "
            f"fresh trace peaks at {_mib(peak)} (tol "
            f"LUX_MEM_MODEL_TOL={tol:g}) — serving admits against a "
            "stale footprint; regenerate the memcap.v1 artifact")]
    return []


def validate_artifact(art, expect_names: Optional[Sequence[str]] = None,
                      path: str = "<memcap>") -> List[Finding]:
    """LUX701 structure checks over one memcap.v1-shaped dict."""
    out: List[Finding] = []
    if not isinstance(art, dict) or not isinstance(art.get("targets"),
                                                   dict):
        return [_f("LUX701", path,
                   "artifact is not a dict with a 'targets' mapping")]
    targets = art["targets"]
    if not targets:
        out.append(_f("LUX701", path, "artifact covers zero targets"))
    for name in sorted(targets):
        entry = targets[name]
        if not isinstance(entry, dict):
            out.append(_f("LUX701", path,
                          f"entry {name!r} is not a mapping"))
            continue
        model = entry.get("model")
        if not isinstance(model, dict) or sorted(model) != sorted(
                MODEL_FIELDS):
            out.append(_f(
                "LUX701", path,
                f"entry {name!r} model must carry exactly "
                f"{MODEL_FIELDS}, got "
                f"{sorted(model) if isinstance(model, dict) else model!r}"))
            continue
        bad = [fld for fld in MODEL_FIELDS
               if not isinstance(model[fld], (int, float))
               or not math.isfinite(float(model[fld]))]
        if bad or float(model["per_vertex_bytes"]) < 0 \
                or float(model["per_edge_bytes"]) < 0:
            out.append(_f(
                "LUX701", path,
                f"entry {name!r} has non-finite or negative model "
                f"coefficients ({ {f: model.get(f) for f in MODEL_FIELDS} })"
            ))
            continue
        peak = entry.get("peak_bytes")
        probe = entry.get("probe") or {}
        if not isinstance(peak, int) or peak <= 0:
            out.append(_f(
                "LUX701", path,
                f"entry {name!r} peak_bytes must be a positive int, "
                f"got {peak!r}"))
        if int(probe.get("nv") or 0) <= 0 or int(probe.get("ne") or 0) <= 0:
            out.append(_f(
                "LUX701", path,
                f"entry {name!r} probe dims must be positive "
                f"(got {probe!r})"))
    if expect_names:
        missing = sorted(set(expect_names) - set(targets))
        for name in missing:
            out.append(_f(
                "LUX701", path,
                f"registry target {name!r} is not covered by the "
                "artifact — every traced target must be priced"))
    return out


# -- registry + fixture drivers -------------------------------------------


def _filter_select(result: FileResult,
                   select: Optional[Sequence[str]]) -> None:
    if select:
        keep = tuple(select)
        result.findings = [f for f in result.findings
                           if f.rule.startswith(keep)]


def _target_meta(ex, spec: dict, kind: str) -> dict:
    g = getattr(ex, "graph", None)
    parts = max(1, int(spec.get("num_parts", 0)
                       or getattr(ex, "num_parts", 1) or 1))
    fe = None
    fef = getattr(ex, "frontier_evidence", None)
    if callable(fef):
        try:
            fe = fef()
        # luxlint: disable=LUX007 -- evidence is advisory input, never fatal
        except Exception:
            fe = None
    return {
        "kind": kind,
        "nv": int(spec.get("nv", getattr(g, "nv", 0)) or 0),
        "ne": int(spec.get("ne", getattr(g, "ne", 0)) or 0),
        "parts": parts,
        "k": int(spec.get("k", getattr(ex, "k", 1) or 1)),
        "mode": str(spec.get("exchange_mode", "")),
        "frontier_evidence": fe or spec.get("frontier_evidence"),
    }


def _harvest(name: str, target, meta: dict
             ) -> Tuple[Optional[dict], Optional[str]]:
    """Trace + lower one target — the jit-machinery evidence the rules
    consume. Registry callers run this in the untimed staging phase
    alongside executor construction (acquisition, not verification);
    fixture targets harvest inline."""
    if meta["nv"] <= 0 or meta["ne"] <= 0:
        return None, (f"{name}: no probe graph dims (nv/ne) to "
                      "attribute the footprint against")
    try:
        return target_peak_bytes(target, meta), None
    except Exception as e:   # traced user code: anything can raise
        return None, f"{name}: trace failed: {e!r}"


def _prove_target(name: str, target, meta: dict,
                  committed: Optional[dict],
                  capacity: Optional[int],
                  scales: Sequence[Tuple[int, int]],
                  ev: Optional[dict] = None,
                  err: Optional[str] = None
                  ) -> Tuple[FileResult, Optional[dict]]:
    if ev is None and err is None:
        ev, err = _harvest(name, target, meta)
    if ev is None:
        return FileResult(name, [], [], error=err), None
    findings: List[Finding] = []
    don = ev["donation"]
    if don["declared"] and don["checked"] and don["leak_bytes"]:
        findings.append(_f(
            "LUX702", name,
            f"{don['declared'] - don['aliased']} of {don['declared']} "
            "donated carry buffers are not aliased in the lowered HLO — "
            f"both copies stay live, adding {_mib(don['leak_bytes'])} "
            "to the per-device peak (donation priced, not just audited)"))
    findings.extend(_check_staging(
        name, target, ev["closed"], meta.get("frontier_evidence"),
        ev["staging_bytes"], meta["parts"]))
    entry = {
        "kind": meta["kind"],
        "exchange_mode": meta["mode"],
        "parts": meta["parts"],
        "k": meta["k"],
        "value_dtype": target.value_dtype,
        "probe": {"nv": meta["nv"], "ne": meta["ne"]},
        "peak_bytes": ev["peak_bytes"],
        "staging_bytes": ev["staging_bytes"],
        "model": ev["model"],
        "donation": {k: don[k] for k in
                     ("declared", "aliased", "donated_bytes",
                      "leak_bytes")},
    }
    findings.extend(_check_budget(name, entry, capacity, scales))
    findings.extend(_check_drift(name, committed, entry))
    return FileResult(name, findings, []), entry


def _stage_registry() -> List[Tuple]:
    """Build every registry executor, capture its trace spec, and
    harvest the trace/lowering evidence.

    Executor construction (graph builds, plan builds, jit wrapping) and
    the jaxpr/HLO harvest are environment setup — jit-machinery
    acquisition, not verification — so callers keep them outside the
    proof timer, the ir.run_* precedent."""
    staged = []
    for name, kind, ex, init_kw in ir._registry_executors():
        spec = ex.trace_step(**init_kw)
        target = ir.target_from_spec(name, spec)
        meta = _target_meta(ex, spec, kind)
        ev, err = _harvest(name, target, meta)
        staged.append((name, target, meta, spec, ev, err))
    return staged


def _sweep_targets(factor: int):
    """One representative per engine kind x exchange mode, rebuilt on a
    probe graph ``factor`` x the base scale (LUX704's re-trace)."""
    from lux_tpu.graph.generate import gnp
    from lux_tpu.models import PROGRAMS, ROOTED_APPS, engine_kinds

    seen = set()
    out = []
    for i, name in enumerate(sorted(PROGRAMS)):
        program = PROGRAMS[name]()
        weighted = bool(getattr(program, "needs_weights", False))
        init_kw = {"start": 0} if name in ROOTED_APPS else {}
        for kind in engine_kinds(name):
            if kind in seen:
                continue
            seen.add(kind)
            graph = gnp(96 * factor, 400 * factor, seed=7 + i,
                        weighted=weighted)
            try:
                ex = ir.build_executor(kind, graph, program)
            # luxlint: disable=LUX007 -- a kind that cannot build at the swept scale is reported, not fatal
            except Exception:
                continue
            spec = ex.trace_step(**init_kw)
            tname = f"{name}@{kind}"
            target = ir.target_from_spec(tname, spec)
            meta = _target_meta(ex, spec, kind)
            ev, err = _harvest(tname, target, meta)
            out.append((tname, name, kind, target, meta, ev, err))
    return out


def prove_registry(select: Optional[Sequence[str]] = None,
                   check_committed: bool = True
                   ) -> Tuple[LintReport, dict]:
    """Walk every traced registry target; returns (report, memcap.v1).

    ``check_committed=False`` skips the committed-artifact rules
    (LUX701 coverage, LUX706 drift) — the regeneration path, where
    staleness is exactly what is being fixed."""
    staged = _stage_registry()
    factor = max(2, flags.get_int("LUX_MEM_SWEEP_FACTOR"))
    swept = _sweep_targets(factor)
    t0 = time.perf_counter()
    committed = None
    committed_err = None
    if check_committed:
        try:
            committed = load_memcap(memcap_path())
        except Exception as e:   # missing or tampered: one loud finding
            committed_err = repr(e)
    capacity = _capacity_bytes()
    scales = _bench_scales()
    results: List[FileResult] = []
    targets_block: Dict[str, dict] = {}
    for name, target, meta, _spec, ev, err in staged:
        res, entry = _prove_target(name, target, meta, committed,
                                   capacity, scales, ev=ev, err=err)
        if entry is not None:
            targets_block[name] = entry
            # LUX704 at the base scale: the calibrated model must
            # reproduce its own probe (catches attribution bugs).
            res.findings.extend(_check_model_honesty(
                name, entry["model"], entry["peak_bytes"],
                meta["nv"], meta["ne"], meta["parts"]))
        _filter_select(res, select)
        results.append(res)
    # LUX704 sweep: the base-scale model must bound a re-trace at
    # factor x the probe, one representative per engine kind.
    for name, _pname, _kind, target, meta, ev, err in swept:
        entry = targets_block.get(name)
        if entry is None:
            continue
        if ev is None:
            results.append(FileResult(
                f"{name}+sweep", [], [], error=f"sweep: {err}"))
            continue
        res = FileResult(f"{name}+sweep", _check_model_honesty(
            name, entry["model"], ev["peak_bytes"],
            meta["nv"], meta["ne"], meta["parts"],
            k=meta["k"], k_probe=entry["k"]), [])
        _filter_select(res, select)
        results.append(res)
    art = build_memcap(targets_block, {
        "nv": 96, "ne": 400, "seed": 7,
        "sweep_factor": factor,
        "tol": flags.get_float("LUX_MEM_MODEL_TOL"),
    })
    structural = validate_artifact(art, path="<memcap:derived>")
    if committed is not None:
        structural += validate_artifact(
            committed, expect_names=sorted(targets_block),
            path="<memcap:committed>")
    elif check_committed:
        structural.append(_f(
            "LUX701", "<memcap:committed>",
            f"committed memcap.v1 unusable ({committed_err}) — "
            "regenerate with `luxlint --memory --memcap-out "
            "lux_tpu/analysis/memcap.json`"))
    if structural:
        res = FileResult("<memcap>", structural, [])
        _filter_select(res, select)
        results.append(res)
    return (LintReport(results, time.perf_counter() - t0,
                       schema=MEMORY_SCHEMA), art)


def verify_registry(select: Optional[Sequence[str]] = None,
                    memcap_out: Optional[str] = None) -> LintReport:
    report, art = prove_registry(select,
                                 check_committed=memcap_out is None)
    if memcap_out and report.ok:
        save_memcap(art, memcap_out)
    return report


_FIXTURE_SEQ = [0]


def _load_fixture(path: str):
    _FIXTURE_SEQ[0] += 1
    modname = f"_memck_fixture_{_FIXTURE_SEQ[0]}"
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)   # type: ignore[union-attr]
    return mod


def verify_fixture_paths(paths: Sequence[str],
                         select: Optional[Sequence[str]] = None
                         ) -> LintReport:
    """Check standalone fixture modules (tests/mem_fixtures/) — each
    rule only engages when the fixture supplies its inputs, so a
    fixture fails with exactly the rule it seeds."""
    t0 = time.perf_counter()
    results: List[FileResult] = []
    for path in iter_python_files(paths):
        try:
            mod = _load_fixture(path)
        except Exception as e:
            results.append(FileResult(
                path, [], [], error=f"{path}: unloadable fixture: {e!r}"))
            continue
        targets = getattr(mod, "TARGETS", None) or {}
        models = getattr(mod, "MODELS", None) or {}
        memcap = getattr(mod, "MEMCAP", None)
        committed = getattr(mod, "COMMITTED", None)
        capacity = getattr(mod, "CAPACITY_BYTES", None)
        scales = getattr(mod, "SCALES", None)
        if not targets and memcap is None:
            results.append(FileResult(
                path, [], [],
                error=f"{path}: defines neither TARGETS nor MEMCAP"))
            continue
        findings: List[Finding] = []
        if memcap is not None:
            findings.extend(validate_artifact(
                memcap, expect_names=sorted(targets), path=path))
        for name in sorted(targets):
            spec = dict(targets[name])
            target = ir.target_from_spec(name, spec)
            meta = _target_meta(_NoExecutor(), spec, spec.get("kind", ""))
            res, entry = _prove_target(
                name, target, meta, committed,
                int(capacity) if capacity else None,
                [tuple(s) for s in scales] if scales
                else ([(meta["nv"], meta["ne"])] if capacity else []))
            findings.extend(res.findings)
            if res.error:
                results.append(FileResult(path, [], [], error=res.error))
            if entry is not None and name in models:
                findings.extend(_check_model_honesty(
                    name, models[name], entry["peak_bytes"],
                    meta["nv"], meta["ne"], meta["parts"]))
        res = FileResult(path, findings, [])
        _filter_select(res, select)
        results.append(res)
    return LintReport(results, time.perf_counter() - t0,
                      schema=MEMORY_SCHEMA)


class _NoExecutor:
    """Fixture targets carry their own dims; nothing to introspect."""


# -- the memcap.v1 artifact -----------------------------------------------


def _cap_id(targets: dict, probe: dict) -> str:
    blob = json.dumps({"probe": probe, "targets": targets},
                      sort_keys=True)
    return "memcap-" + hashlib.sha1(blob.encode()).hexdigest()[:12]


def build_memcap(targets: dict, probe: dict) -> dict:
    return {
        "schema": CAP_SCHEMA,
        "id": _cap_id(targets, probe),
        "probe": probe,
        "targets": targets,
        "created_at": time.time(),
    }


def save_memcap(art: dict, path: str) -> str:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(art, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    return path


def load_memcap(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        art = json.load(fh)
    if art.get("schema") != CAP_SCHEMA:
        raise ValueError(
            f"{path}: schema {art.get('schema')!r}, expected {CAP_SCHEMA!r}")
    want = _cap_id(art.get("targets") or {}, art.get("probe") or {})
    if art.get("id") != want:
        raise ValueError(
            f"{path}: id {art.get('id')!r} does not match content hash "
            f"{want!r} (tampered or hand-edited footprint artifact)")
    return art


def memcap_path() -> str:
    d = flags.get("LUX_MEMCAP_DIR")
    if d:
        return os.path.join(d, CAP_FILENAME)
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        CAP_FILENAME)


# -- consumers: the serving admission formula -----------------------------

# (path, mtime) -> artifact; the committed file changes once per
# regeneration, so a stat per lookup is the whole invalidation story.
_COMMITTED_CACHE: Dict[Tuple[str, float], Optional[dict]] = {}


def _committed() -> Optional[dict]:
    path = memcap_path()
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return None
    key = (path, mtime)
    if key not in _COMMITTED_CACHE:
        _COMMITTED_CACHE.clear()
        try:
            _COMMITTED_CACHE[key] = load_memcap(path)
        except (OSError, ValueError):
            # Tampered/unreadable: admission runs open (None) and
            # luxlint --memory is the gate that fails loudly.
            _COMMITTED_CACHE[key] = None
    return _COMMITTED_CACHE[key]


def predicted_engine_bytes(app: str, kind: str, exchange_mode: str,
                           nv: int, ne: int, parts: int, k: int = 1,
                           art: Optional[dict] = None) -> Optional[int]:
    """Serving's admission formula: per-device predicted resident bytes
    for one engine build, from the committed memcap.v1 models. None
    when no artifact (or no matching entry) is available — admission
    then runs open; LUX706 keeps this formula honest against fresh
    traces."""
    art = art if art is not None else _committed()
    if art is None:
        return None
    targets = art.get("targets") or {}
    names = [f"{app}@{kind}"]
    if exchange_mode in ("compact", "frontier"):
        names.insert(0, f"{app}@{kind}+{exchange_mode}")
    entry = next((targets[n] for n in names if n in targets), None)
    if entry is None:
        # Unknown app under a known kind: price it as the costliest
        # same-kind entry (upper-bound bias, never a free pass).
        same = [e for t, e in targets.items()
                if t.split("@", 1)[-1].split("+", 1)[0] == kind]
        if not same:
            return None
        entry = max(same, key=lambda e: e.get("peak_bytes", 0))
    try:
        return int(eval_model(entry["model"], nv, ne, parts,
                              k=k, k_probe=entry.get("k")))
    except (KeyError, TypeError, ValueError):
        return None


def hbm_budget_bytes() -> Optional[int]:
    """The per-device HBM budget the pool admits under:
    LUX_HBM_BUDGET_BYTES when set, else device-profile capacity x
    LUX_HBM_BUDGET_FRAC; None (no budget — admit freely) when neither
    yields a positive number."""
    b = flags.get_int("LUX_HBM_BUDGET_BYTES")
    if b > 0:
        return b
    cap = _capacity_bytes()
    if not cap:
        return None
    return int(cap * flags.get_float("LUX_HBM_BUDGET_FRAC"))
