"""luxlint-IR: rules over *traced* programs (jaxprs), not source text.

The AST tier (analysis/rules.py) sees what the code says; this tier sees
what the traced computation actually does. Every registered program ×
executor step is traced to a ClosedJaxpr on a tiny synthetic graph —
abstract eval only, nothing runs on a device — and the equations are
walked by the LUX1xx rules:

- LUX101 dtype-drift: a carry leaf whose dtype differs between loop
  input and output reshapes/retraces every iteration; silent promotion
  to a 64-bit dtype doubles HBM and halves VPU throughput.
- LUX102 host-callback: ``pure_callback``/``debug_callback``/
  ``io_callback`` inside a jitted step is a hidden device->host round
  trip per iteration (the LUX001 failure mode, visible post-trace even
  when the AST can't see it).
- LUX103 footprint-blowup: a static per-eqn cost model flags any traced
  intermediate larger than ``LUX_IR_BLOWUP`` x the step's total input
  bytes — the O(nnz)-broadcast class of bugs, caught before a 2^31-edge
  run OOMs.
- LUX104 donation-audit: args declared in ``donate_argnums`` whose
  buffers the lowered executable does not actually alias (the donation
  silently buys nothing and HBM holds two copies).
- LUX105 collective-audit: collectives in a single-shard trace, or a
  sharded exchange trace with no collective at all (the ZC-exchange
  surface wired wrong).

Tracing is cheap (~ms per target) but imports jax — keep this module
OUT of the AST tier's import path; ``tools/luxlint.py`` loads it only
under ``--ir``.

Executors participate by exposing ``trace_step(**init_kw)`` returning a
plain dict (no dependency on this module)::

    {"kind": "pull",            # executor kind, for the target name
     "fn": self._step,          # the jitted step callable itself
     "args": (vals, dgraph),    # example args exactly as run() passes
     "donate": (0,),            # argnums the jit donates
     "carry": (0,),             # argnums whose leaves are the carry
     "sharded": False}          # True when collectives are expected

with optional ``call``/``lower`` overrides when the jit takes static
arguments the example args don't show (MultiSourcePushExecutor). The
contract relied on by LUX101: the step's flattened outputs begin with
the new carry, leaf-for-leaf against the flattened carry args.
"""

from __future__ import annotations

import dataclasses
import re
import time
import warnings
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from lux_tpu.analysis.core import FileResult, Finding, LintReport
from lux_tpu.utils import flags

IR_SCHEMA = "luxlint.ir.v1"

# Primitive-name fragments identifying host callbacks (LUX102) and
# cross-device collectives (LUX105). Matched by name, not identity, so
# the rule set survives jax moving primitives between modules.
CALLBACK_PRIMS = ("pure_callback", "debug_callback", "io_callback")
COLLECTIVE_PRIMS = (
    "psum", "pmax", "pmin", "ppermute", "pgather", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter",
)


@dataclasses.dataclass
class TraceTarget:
    """One traceable step: a callable + example args + audit metadata."""

    name: str                       # e.g. "pagerank@pull"
    call: Callable                  # callable(*args) -> step outputs
    args: Tuple = ()                # example args (dynamic only)
    donate: Tuple[int, ...] = ()    # argnums donated by the real jit
    carry: Tuple[int, ...] = (0,)   # argnums whose leaves are the carry
    sharded: bool = False           # collectives expected iff True
    lower: Optional[Callable] = None  # () -> jax.stages.Lowered
    axis_env: Tuple = ()            # [(name, size)] for axis-using fns
    # Exchange-tier metadata (LUX404-406); plan-carrying sharded
    # executors expose these in their trace dicts, everything else
    # leaves the defaults and the LUX40x IR rules skip the target.
    exchange_mode: str = ""   # "full" / "compact" / "frontier" ("" = flat)
    exchange_bytes: Optional[int] = None  # exchange_bytes_per_iter claim
    combiner: str = ""              # program combiner ("min"/"max"/"sum")
    value_dtype: str = ""           # dtype of the exchanged value rows
    num_parts: int = 0              # mesh parts the step is mapped over
    plan: object = None             # the live ExchangePlan (compact only)


def target_from_spec(name: str, spec: dict) -> TraceTarget:
    """Normalize an executor's (or fixture's) trace dict to a target."""
    fn = spec.get("fn")
    call = spec.get("call", fn)
    if call is None:
        raise ValueError(f"trace spec {name!r} has neither 'call' nor 'fn'")
    args = tuple(spec.get("args", ()))
    lower = spec.get("lower")
    if lower is None and hasattr(fn, "lower"):
        lower = lambda fn=fn, args=args: fn.lower(*args)  # noqa: E731
    eb = spec.get("exchange_bytes")
    return TraceTarget(
        name=name, call=call, args=args,
        donate=tuple(spec.get("donate", ())),
        carry=tuple(spec.get("carry", (0,))),
        sharded=bool(spec.get("sharded", False)),
        lower=lower,
        axis_env=tuple(spec.get("axis_env", ())),
        exchange_mode=str(spec.get("exchange_mode", "")),
        exchange_bytes=None if eb is None else int(eb),
        combiner=str(spec.get("combiner", "")),
        value_dtype=str(spec.get("value_dtype", "")),
        num_parts=int(spec.get("num_parts", 0)),
        plan=spec.get("plan"),
    )


def trace_target(target: TraceTarget):
    """Abstract-eval the target to a ClosedJaxpr (no device work)."""
    import jax

    if target.axis_env:
        mk = jax.make_jaxpr(target.call, axis_env=list(target.axis_env))
    else:
        mk = jax.make_jaxpr(target.call)
    return mk(*target.args)


# -- jaxpr walking ------------------------------------------------------

def _as_jaxprs(v) -> List:
    from jax.extend import core as jcore

    if isinstance(v, jcore.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jcore.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        out = []
        for x in v:
            out.extend(_as_jaxprs(x))
        return out
    return []


def iter_eqns(jaxpr) -> Iterable:
    """Depth-first walk over every eqn, descending into sub-jaxprs
    (pjit/scan/while/cond/shard_map/custom_* all carry theirs in
    params; matching by type keeps the walk version-proof)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from iter_eqns(sub)


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def _carry_leaf_indices(target: TraceTarget) -> List[int]:
    """Flat in_aval indices of the carry args (args flatten in order)."""
    import jax

    out: List[int] = []
    pos = 0
    for i, a in enumerate(target.args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in target.carry:
            out.extend(range(pos, pos + n))
        pos += n
    return out


# -- the rules ----------------------------------------------------------

class IRRule:
    """One IR rule: an id, a one-line doc, a check over a ClosedJaxpr."""

    id = "LUX100"
    title = "base ir rule"
    doc = ""

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, target: TraceTarget, line: int, message: str) -> Finding:
        # `line` is the 1-based eqn ordinal in the depth-first walk
        # (0 = a target-level finding with no single eqn to blame).
        return Finding(self.id, target.name, line, 0, message)


class DtypeDrift(IRRule):
    id = "LUX101"
    title = "dtype-drift"
    doc = ("carry dtype must be identical between loop input and output; "
           "no silent promotion to 64-bit dtypes inside the step")

    @staticmethod
    def _wide(dtype) -> bool:
        dt = np.dtype(dtype)
        return dt.kind in "fiuc" and dt.itemsize >= 8

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        carry_idx = _carry_leaf_indices(target)
        in_avals, out_avals = closed.in_avals, closed.out_avals
        if len(carry_idx) > len(out_avals):
            yield self.finding(
                target, 0,
                f"carry has {len(carry_idx)} leaves but the step returns "
                f"only {len(out_avals)} outputs — the carry cannot round-"
                "trip through this step",
            )
            return
        for j, idx in enumerate(carry_idx):
            din = getattr(in_avals[idx], "dtype", None)
            dout = getattr(out_avals[j], "dtype", None)
            if din is not None and dout is not None and din != dout:
                yield self.finding(
                    target, 0,
                    f"carry leaf {j} enters as {din} and leaves as {dout} "
                    "— every iteration converts (or retraces) the carry",
                )
        if any(self._wide(a.dtype) for a in in_avals
               if getattr(a, "dtype", None) is not None):
            return   # 64-bit inputs make 64-bit intermediates legitimate
        for k, eqn in enumerate(iter_eqns(closed.jaxpr), start=1):
            for ov in eqn.outvars:
                dt = getattr(ov.aval, "dtype", None)
                if dt is not None and self._wide(dt):
                    yield self.finding(
                        target, k,
                        f"`{eqn.primitive.name}` silently promotes to "
                        f"{np.dtype(dt).name} with no 64-bit input — "
                        "x64 drift doubles HBM for the affected values",
                    )


class HostCallback(IRRule):
    id = "LUX102"
    title = "host-callback"
    doc = ("no pure_callback/debug_callback/io_callback inside a jitted "
           "hot-path step (hidden host round trip per iteration)")

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        for k, eqn in enumerate(iter_eqns(closed.jaxpr), start=1):
            name = eqn.primitive.name
            if name in CALLBACK_PRIMS or name.endswith("callback"):
                yield self.finding(
                    target, k,
                    f"host callback `{name}` in the jitted step — every "
                    "iteration stalls on a device->host->device round "
                    "trip",
                )


class FootprintBlowup(IRRule):
    id = "LUX103"
    title = "footprint-blowup"
    doc = ("no traced intermediate may exceed LUX_IR_BLOWUP x the "
           "step's total input bytes (static per-eqn cost model)")

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        ratio = flags.get_float("LUX_IR_BLOWUP")
        base = sum(_aval_bytes(a) for a in closed.in_avals)
        base += sum(int(getattr(c, "nbytes", 0)) for c in closed.consts)
        limit = ratio * max(base, 1)
        for k, eqn in enumerate(iter_eqns(closed.jaxpr), start=1):
            for ov in eqn.outvars:
                nbytes = _aval_bytes(ov.aval)
                if nbytes > limit:
                    aval = ov.aval
                    yield self.finding(
                        target, k,
                        f"`{eqn.primitive.name}` materializes "
                        f"{tuple(aval.shape)} {np.dtype(aval.dtype).name} "
                        f"({nbytes / 2**20:.1f} MiB) = "
                        f"{nbytes / max(base, 1):.0f}x the step inputs "
                        f"(limit {ratio:g}x, LUX_IR_BLOWUP)",
                    )


def _main_arg_attrs(mlir_text: str) -> Optional[str]:
    """The argument list of the entry function in lowered StableHLO
    text (between ``@main(`` and its closing paren), or None."""
    m = re.search(r"func\.func (?:public )?@main\(", mlir_text)
    if m is None:
        return None
    start = m.end()
    depth = 1
    for i in range(start, len(mlir_text)):
        ch = mlir_text[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return mlir_text[start:i]
    return None


class DonationAudit(IRRule):
    id = "LUX104"
    title = "donation-audit"
    doc = ("every donate_argnums buffer must actually be aliased to an "
           "output by the lowered executable (else the donation buys "
           "nothing and HBM holds two copies)")

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        import jax

        if not target.donate or target.lower is None:
            return
        donated = []
        for i in target.donate:
            if i < len(target.args):
                donated.extend(jax.tree_util.tree_leaves(target.args[i]))
        expected = len(donated)
        if expected == 0:
            return
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            lowered = target.lower()
        sig = _main_arg_attrs(lowered.as_text())
        if sig is None:
            yield self.finding(
                target, 0,
                "could not locate @main in the lowered module — donation "
                "audit impossible for this target",
            )
            return
        # Single-shard lowerings resolve aliasing right away
        # (`tf.aliasing_output = N`); sharded lowerings defer the pairing
        # to the compiler and only mark `jax.buffer_donor = true`.
        aliased = sig.count("tf.aliasing_output")
        deferred = sig.count("jax.buffer_donor")
        if aliased + deferred < expected:
            notes = "; ".join(
                str(w.message) for w in caught
                if "donat" in str(w.message).lower()
            )
            detail = f" ({notes})" if notes else ""
            yield self.finding(
                target, 0,
                f"{expected - (aliased + deferred)} of {expected} donated "
                "buffers are not aliased to any output — the executable "
                f"copies instead of reusing them{detail}",
            )
            return
        if deferred:
            # The compiler will alias a deferred donor only if some
            # output matches its shape+dtype — check that statically.
            if closed is not None:
                out_leaves = [
                    a for a in closed.out_avals if hasattr(a, "shape")
                ]
            else:
                out_tree = jax.eval_shape(target.call, *target.args)
                out_leaves = jax.tree_util.tree_leaves(out_tree)
            pool = [
                (tuple(a.shape), np.dtype(a.dtype)) for a in out_leaves
            ]
            unmatched = []
            for leaf in donated:
                key = (tuple(leaf.shape), np.dtype(leaf.dtype))
                if key in pool:
                    pool.remove(key)
                else:
                    unmatched.append(key)
            for shape, dtype in unmatched:
                yield self.finding(
                    target, 0,
                    f"donated buffer {shape} {dtype.name} has no shape/"
                    "dtype-matching output to alias — the donation buys "
                    "nothing",
                )


class CollectiveAudit(IRRule):
    id = "LUX105"
    title = "collective-audit"
    doc = ("collectives (psum/all_gather/...) must not appear in single-"
           "shard traces and must appear in sharded exchange traces")

    @staticmethod
    def _is_collective(name: str) -> bool:
        return any(
            name == c or name.startswith(c + "_") for c in COLLECTIVE_PRIMS
        )

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        seen: List[Tuple[int, str]] = []
        for k, eqn in enumerate(iter_eqns(closed.jaxpr), start=1):
            if self._is_collective(eqn.primitive.name):
                seen.append((k, eqn.primitive.name))
        if target.sharded and not seen:
            yield self.finding(
                target, 0,
                "sharded exchange trace contains no collective — shards "
                "never communicate, so every shard computes on stale "
                "neighbor values",
            )
        if not target.sharded:
            for k, name in seen:
                yield self.finding(
                    target, k,
                    f"collective `{name}` in a single-shard trace — "
                    "either dead cross-device traffic or a program "
                    "traced with the wrong executor",
                )


def all_ir_rules() -> List[IRRule]:
    return [
        DtypeDrift(),
        HostCallback(),
        FootprintBlowup(),
        DonationAudit(),
        CollectiveAudit(),
    ]


# -- the exchange tier: collective-dataflow rules (LUX404-406) ----------
#
# The IR half of ``luxlint --exchange``. The plan tables are verified
# jax-free in analysis/exchck.py (LUX401-403); these rules prove the
# properties only the traced step can show: that the local-edge
# contribution is data-independent of the collective (the overlap
# contract), that pad values annihilate under the program's combiner,
# and that the advertised per-iteration collective bytes match what the
# jaxpr actually moves.

# The exchange data plane: collectives that MOVE VALUE ROWS between
# shards. psum/psum_scatter/ppermute are merge- or control-plane (they
# combine, not transport) and are deliberately excluded from the byte
# accounting — the executors' exchange_bytes_per_iter models price only
# the row transport.
DATA_COLLECTIVE_PRIMS = ("all_gather", "all_to_all")


def _is_data_collective(name: str) -> bool:
    return any(
        name == c or name.startswith(c + "_") for c in DATA_COLLECTIVE_PRIMS
    )


def _walk_jaxprs(jaxpr) -> Iterable:
    """Depth-first walk over a jaxpr and every sub-jaxpr it carries."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from _walk_jaxprs(sub)


def _is_lit(v) -> bool:
    """Literal operands carry ``val``; Vars don't (identity-free check
    that survives jax moving Literal between modules)."""
    return hasattr(v, "val")


# Per-trace memo for the dataflow/scalar analyses: LUX404 and LUX405
# both need the same global walk, and recomputing it doubles the
# exchange tier's wall cost. Keyed by identity with the closed jaxpr
# pinned in the entry so a recycled id can never alias a stale result.
_FLOW_MEMO: dict = {}


def _flow_memo(closed, key: str, builder):
    ent = _FLOW_MEMO.get(id(closed))
    if ent is None or ent[0] is not closed:
        if len(_FLOW_MEMO) > 32:
            _FLOW_MEMO.clear()
        ent = (closed, {})
        _FLOW_MEMO[id(closed)] = ent
    if key not in ent[1]:
        ent[1][key] = builder(closed)
    return ent[1][key]


def _global_dataflow(closed) -> Tuple[set, set, set]:
    return _flow_memo(closed, "flow", _global_dataflow_impl)


def _global_dataflow_impl(closed) -> Tuple[set, set, set]:
    """(tainted, axis, inputs) var sets over the WHOLE trace: vars
    transitively computed from a data collective's output, from
    ``axis_index``, and from the top jaxpr's invars respectively.

    Membership is propagated THROUGH sub-jaxpr boundaries (pjit /
    shard_map / cond / scan) by positional invar/outvar mapping — jnp
    helpers like ``jnp.where`` trace as nested pjit calls, so the
    local/remote merge usually sits one boundary below the collective
    and a per-jaxpr walk would be blind to it. Where an eqn's operand
    list cannot be aligned with a sub-jaxpr's invars (e.g. ``while``
    packing two consts lists), propagation degrades to the conservative
    union. Single forward pass: jaxpr equations are topologically
    ordered (loop-carried taint inside scan/while bodies is not chased
    to fixpoint; the step targets are single-iteration functions)."""
    tainted: set = set()
    axis: set = set()
    inputs: set = set()
    sets = (tainted, axis, inputs)

    def member(v) -> Tuple[bool, bool, bool]:
        if _is_lit(v):
            return (False, False, False)
        return tuple(v in s for s in sets)

    def mark(v, mem) -> None:
        for s, m in zip(sets, mem):
            if m:
                s.add(v)

    def union(mems):
        out = (False, False, False)
        for m in mems:
            out = tuple(a or b for a, b in zip(out, m))
        return out

    def visit(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            nm = eqn.primitive.name
            subs: List = []
            for p in eqn.params.values():
                subs.extend(_as_jaxprs(p))
            if subs:
                outer = list(eqn.invars)
                if nm == "cond" and \
                        all(len(s.invars) == len(outer) - 1 for s in subs):
                    outer = outer[1:]   # predicate precedes the operands
                if all(len(s.invars) == len(outer) for s in subs):
                    for s in subs:
                        for o, iv in zip(outer, s.invars):
                            mark(iv, member(o))
                        visit(s)
                    if all(len(s.outvars) == len(eqn.outvars) for s in subs):
                        for s in subs:
                            for so, eo in zip(s.outvars, eqn.outvars):
                                mark(eo, member(so))
                        continue
                    mem = union(member(so) for s in subs
                                for so in s.outvars)
                    for eo in eqn.outvars:
                        mark(eo, mem)
                    continue
                # Unalignable boundary: conservative union in and out.
                mem = union(member(v) for v in eqn.invars)
                for s in subs:
                    for iv in s.invars:
                        mark(iv, mem)
                    visit(s)
                mem = union([mem] + [member(so) for s in subs
                                     for so in s.outvars])
                for eo in eqn.outvars:
                    mark(eo, mem)
                continue
            mem = union(member(v) for v in eqn.invars)
            if _is_data_collective(nm):
                mem = (True, mem[1], mem[2])
            if nm == "axis_index":
                mem = (mem[0], True, mem[2])
            for ov in eqn.outvars:
                mark(ov, mem)

    inputs.update(closed.jaxpr.invars)
    visit(closed.jaxpr)
    return tainted, axis, inputs


def _eqn_ordinals(jaxpr) -> dict:
    """id(eqn) -> 1-based ordinal in the same depth-first walk the
    other IR rules number findings by."""
    return {id(e): k for k, e in enumerate(iter_eqns(jaxpr), start=1)}


def _lit_scalar(v) -> Optional[float]:
    """The numeric value of a scalar Literal (or None)."""
    if not _is_lit(v):
        return None
    a = np.asarray(v.val)
    if a.size != 1 or a.dtype.kind not in "bifu":
        return None
    return float(a.reshape(-1)[0])


# Primitives through which a known scalar constant keeps its value
# (shape/dtype/varying-axes bookkeeping only — dtype conversion of +-inf
# and the integer identities is exact for the cases LUX405 compares).
_VALUE_PRESERVING_PRIMS = (
    "broadcast_in_dim", "reshape", "convert_element_type", "squeeze",
    "expand_dims", "copy", "slice", "pvary",
)


def _closed_subs(v) -> List[Tuple[object, tuple]]:
    """(jaxpr, consts) pairs for sub-jaxprs, keeping ClosedJaxpr consts
    paired with their constvars (``_as_jaxprs`` drops them)."""
    from jax.extend import core as jcore

    if isinstance(v, jcore.ClosedJaxpr):
        return [(v.jaxpr, tuple(v.consts))]
    if isinstance(v, jcore.Jaxpr):
        return [(v, ())]
    if isinstance(v, (list, tuple)):
        out = []
        for x in v:
            out.extend(_closed_subs(x))
        return out
    return []


def _scalar_env(closed) -> dict:
    return _flow_memo(closed, "scalars", _scalar_env_impl)


def _scalar_env_impl(closed) -> dict:
    """Global scalar constant propagation: Var -> float for every var
    that provably holds one scalar value, across pjit/shard_map/cond
    boundaries (positional invar mapping) and through shape-only ops.
    This is how LUX405 recovers the pad constants the executors build
    with ``identity_for`` — by trace time they are consts threaded into
    the shard_map body, not Literals at the select."""
    env: dict = {}

    def value_of(v):
        lv = _lit_scalar(v)
        if lv is not None:
            return lv
        return env.get(v)

    def seed(jaxpr, consts):
        for cv, c in zip(jaxpr.constvars, consts):
            try:
                a = np.asarray(c)
            except Exception:
                continue
            if a.size == 1 and a.dtype.kind in "bifu":
                env[cv] = float(a.reshape(-1)[0])

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            nm = eqn.primitive.name
            if nm in _VALUE_PRESERVING_PRIMS and eqn.invars:
                val = value_of(eqn.invars[0])
                if val is not None:
                    for ov in eqn.outvars:
                        env[ov] = val
            for p in eqn.params.values():
                for sub, consts in _closed_subs(p):
                    seed(sub, consts)
                    outer = list(eqn.invars)
                    # cond consumes the predicate before the operands.
                    if nm == "cond" and len(outer) == len(sub.invars) + 1:
                        outer = outer[1:]
                    if len(outer) == len(sub.invars):
                        for o, iv in zip(outer, sub.invars):
                            val = value_of(o)
                            if val is not None:
                                env[iv] = val
                    visit(sub)

    seed(closed.jaxpr, tuple(closed.consts))
    visit(closed.jaxpr)
    return env


def _combiner_identity(combiner: str, dtype) -> Optional[float]:
    """The annihilator value for a combiner over ``dtype`` — mirrors
    ops/segment.identity_for (kept numerically identical by test)."""
    dt = np.dtype(dtype)
    if combiner == "sum":
        return 0.0
    if combiner == "min":
        return float(np.inf) if dt.kind == "f" else float(np.iinfo(dt).max)
    if combiner == "max":
        return float(-np.inf) if dt.kind == "f" else float(np.iinfo(dt).min)
    return None


class OverlapProof(IRRule):
    id = "LUX404"
    title = "overlap-proof"
    doc = ("compact targets must merge an untainted input-derived local "
           "contribution against the collective's result — proves the "
           "local-edge work is data-independent of the exchange")

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        # Frontier targets keep the compact plan's packed all_to_all as
        # the dense-iteration branch, so the same merge proof applies.
        if target.exchange_mode not in ("compact", "frontier"):
            return
        ordinals = _eqn_ordinals(closed.jaxpr)
        tainted, axis, inputs = _global_dataflow(closed)
        good: List = []
        bad: List = []
        saw_collective = False
        for eqn in iter_eqns(closed.jaxpr):
            nm = eqn.primitive.name
            if _is_data_collective(nm):
                saw_collective = True
            elif nm in ("select_n", "select") and len(eqn.invars) >= 3:
                # The local/remote merge: predicate derived from
                # axis_index (ownership test), at least one case from
                # the collective. The merge is proven iff some case is
                # an untainted function of the step's own inputs — the
                # local contribution.
                pred, cases = eqn.invars[0], eqn.invars[1:]
                if _is_lit(pred) or pred not in axis or pred in tainted:
                    continue
                if not any((not _is_lit(c)) and c in tainted
                           for c in cases):
                    continue
                ok = any((not _is_lit(c)) and c not in tainted
                         and c in inputs for c in cases)
                (good if ok else bad).append(eqn)
            elif nm == "dynamic_update_slice" and len(eqn.invars) >= 3:
                # The tiled merge: own shard written into the gathered
                # table at an axis-derived offset.
                op, upd = eqn.invars[0], eqn.invars[1]
                starts = eqn.invars[2:]
                if not any((not _is_lit(s)) and s in axis
                           for s in starts):
                    continue
                if not any((not _is_lit(x)) and x in tainted
                           for x in (op, upd)):
                    continue
                ok = (not _is_lit(upd)) and upd not in tainted \
                    and upd in inputs
                (good if ok else bad).append(eqn)
        if not saw_collective:
            return   # no exchange traced at all — LUX105's finding
        if good:
            return   # overlap proven: local side never waits on the wire
        if bad:
            eqn = bad[0]
            yield self.finding(
                target, ordinals.get(id(eqn), 0),
                f"local/remote merge `{eqn.primitive.name}` consumes the "
                "collective's result on every data side — the local-edge "
                "contribution transitively depends on the exchange, so "
                "the advertised compute/communication overlap cannot "
                "exist",
            )
        else:
            yield self.finding(
                target, 0,
                "no local/remote merge point found downstream of the "
                "data collective — cannot prove the local-edge "
                "contribution is independent of the exchange",
            )


class SentinelAnnihilator(IRRule):
    id = "LUX405"
    title = "sentinel-annihilator"
    doc = ("pad values merged into the exchanged data path must be the "
           "program combiner's identity (+inf/int-max for min, 0 for "
           "sum) so sentinel traffic can never reach a result")

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        if target.exchange_mode not in ("compact", "frontier") or \
                target.combiner not in ("min", "max", "sum"):
            return
        comb = target.combiner
        vdt = np.dtype(target.value_dtype) if target.value_dtype else None
        env = _scalar_env(closed)
        ordinals = _eqn_ordinals(closed.jaxpr)
        tainted, _, _ = _global_dataflow(closed)
        wrong: List[Tuple] = []
        found_ident = False
        saw_collective = False
        for eqn in iter_eqns(closed.jaxpr):
            nm = eqn.primitive.name
            if _is_data_collective(nm):
                saw_collective = True
            elif nm in ("select_n", "select") and len(eqn.invars) >= 3:
                cases = eqn.invars[1:]
                if not any((not _is_lit(c)) and c in tainted
                           for c in cases):
                    continue
                dt = np.dtype(getattr(eqn.outvars[0].aval, "dtype",
                                      np.float32))
                if dt.kind == "b":
                    continue   # frontier masks, no numeric identity
                if vdt is not None and dt != vdt:
                    continue   # index/queue plane, not the value rows
                ident = _combiner_identity(comb, dt)
                for c in cases:
                    val = _lit_scalar(c)
                    if val is None and not _is_lit(c):
                        val = env.get(c)
                    if val is None:
                        continue
                    if val == ident:
                        found_ident = True
                    else:
                        wrong.append((eqn, val, ident, dt))
            elif comb == "sum" and nm.startswith("scatter") and \
                    len(eqn.invars) >= 3:
                # Summing programs annihilate pads by scattering into a
                # zero-filled receive buffer: a nonzero fill would be
                # added into every touched row.
                op, upd = eqn.invars[0], eqn.invars[2]
                if _is_lit(upd) or upd not in tainted:
                    continue
                val = _lit_scalar(op)
                if val is None and not _is_lit(op):
                    val = env.get(op)
                if val is None:
                    continue
                dt = np.dtype(getattr(eqn.outvars[0].aval, "dtype",
                                      np.float32))
                if vdt is not None and dt != vdt:
                    continue   # index/queue plane, not the value rows
                if val == 0.0:
                    found_ident = True
                else:
                    wrong.append((eqn, val, 0.0, dt))
        for eqn, val, ident, dt in wrong:
            yield self.finding(
                target, ordinals.get(id(eqn), 0),
                f"pad constant {val:g} flows into the exchanged data "
                f"path through `{eqn.primitive.name}` but the {comb} "
                f"identity for {dt.name} is {ident:g} — sentinel slots "
                "leak into results",
            )
        if saw_collective and not wrong and not found_ident:
            yield self.finding(
                target, 0,
                f"no {comb}-identity pad constant guards the exchanged "
                "candidates — cannot prove sentinel traffic is "
                "annihilated before the combiner",
            )


def _collective_byte_totals(jaxpr, num_parts: int) -> set:
    """Set of possible per-iteration data-collective byte totals for
    one step. A set, not a number: ``cond`` branches are execution
    ALTERNATIVES (the push engine's sparse/dense split), so each branch
    contributes its own total; everything else composes additively.
    Pricing (whole-mesh bytes crossing the interconnect per iteration,
    operand = the per-shard array inside shard_map):

    - all_gather: every shard receives every OTHER shard's operand —
      ``P * (P-1) * operand_bytes``;
    - all_to_all: each shard keeps its own 1/P chunk and sends the
      rest — ``(P-1) * operand_bytes`` summed over the mesh.
    """
    P = num_parts
    totals = {0}
    for eqn in jaxpr.eqns:
        nm = eqn.primitive.name
        if _is_data_collective(nm):
            opb = sum(_aval_bytes(v.aval) for v in eqn.invars
                      if hasattr(v, "aval"))
            add = {P * (P - 1) * opb if nm.startswith("all_gather")
                   else (P - 1) * opb}
        elif nm == "cond":
            add = set()
            for sub in _as_jaxprs(eqn.params.get("branches", ())):
                add |= _collective_byte_totals(sub, P)
        else:
            add = {0}
            for p in eqn.params.values():
                for sub in _as_jaxprs(p):
                    sub_totals = _collective_byte_totals(sub, P)
                    add = {a + s for a in add for s in sub_totals}
        if add and add != {0}:
            totals = {t + a for t in totals for a in add}
            if len(totals) > 1024:   # runaway-branch backstop
                totals = set(sorted(totals)[:1024])
    return totals


class ExchangeByteAccounting(IRRule):
    id = "LUX406"
    title = "exchange-byte-accounting"
    doc = ("the executor's exchange_bytes_per_iter claim must equal the "
           "per-iteration data-collective bytes statically derived from "
           "the traced step")

    def check(self, closed, target: TraceTarget) -> Iterable[Finding]:
        if target.exchange_bytes is None or target.num_parts < 2:
            return
        totals = _collective_byte_totals(closed.jaxpr, target.num_parts)
        if int(target.exchange_bytes) not in totals:
            shown = ", ".join(str(t) for t in sorted(totals)[:8])
            yield self.finding(
                target, 0,
                f"executor claims exchange_bytes_per_iter = "
                f"{target.exchange_bytes} but the traced step's data "
                f"collectives move {{{shown}}} bytes per iteration "
                "(all_gather P*(P-1)*operand, all_to_all (P-1)*operand; "
                "cond branches are alternatives) — the byte model "
                "drifted from the exchange the step performs",
            )


def exchange_ir_rules(select=None) -> List[IRRule]:
    rules: List[IRRule] = [
        OverlapProof(), SentinelAnnihilator(), ExchangeByteAccounting(),
    ]
    if select:
        rules = [r for r in rules if r.id in select]
    return rules


# -- runner -------------------------------------------------------------

def check_target(target: TraceTarget,
                 rules: Sequence[IRRule]) -> FileResult:
    """Trace one target and run the given rules over its jaxpr."""
    try:
        closed = trace_target(target)
    except Exception as e:   # traced user code: anything can raise
        return FileResult(
            target.name, [], [],
            error=f"{target.name}: trace failed: {e!r}")
    findings: List[Finding] = []
    errors: List[str] = []
    for rule in rules:
        try:
            findings.extend(rule.check(closed, target))
        except Exception as e:
            errors.append(f"{target.name}: {rule.id} crashed: {e!r}")
    findings.sort(key=lambda f: (f.line, f.rule))
    return FileResult(
        target.name, findings, [], error="; ".join(errors) or None)


def run_targets(targets: Sequence[TraceTarget],
                rules: Optional[Sequence[IRRule]] = None) -> LintReport:
    """Trace every target and run the IR rules over the jaxprs."""
    t0 = time.perf_counter()
    if rules is None:
        rules = all_ir_rules()
    results = [check_target(t, rules) for t in targets]
    return LintReport(results, time.perf_counter() - t0, schema=IR_SCHEMA)


# -- the registry trace matrix ------------------------------------------

def _tiny_graph(weighted: bool, seed: int):
    """Small synthetic graph: big enough to exercise every code path's
    shapes, small enough that building executors stays milliseconds."""
    from lux_tpu.graph.generate import gnp

    return gnp(96, 400, seed=seed, weighted=weighted)


def build_executor(kind: str, graph, program):
    """One executor of the given kind over (graph, program) — the same
    constructions cli.py / serve use, defaults throughout."""
    if kind == "pull":
        from lux_tpu.engine.pull import PullExecutor
        return PullExecutor(graph, program)
    if kind == "tiled":
        from lux_tpu.engine.tiled import TiledPullExecutor
        return TiledPullExecutor(graph, program)
    if kind == "push":
        from lux_tpu.engine.push import PushExecutor
        return PushExecutor(graph, program)
    if kind == "push_multi":
        from lux_tpu.engine.push import MultiSourcePushExecutor
        return MultiSourcePushExecutor(graph, program, k=4)
    if kind == "push_incremental":
        from lux_tpu.engine.incremental import IncrementalExecutor
        return IncrementalExecutor(graph, program)
    if kind == "pull_sharded":
        from lux_tpu.engine.pull_sharded import ShardedPullExecutor
        return ShardedPullExecutor(graph, program)
    if kind == "tiled_sharded":
        from lux_tpu.engine.tiled_sharded import ShardedTiledExecutor
        return ShardedTiledExecutor(graph, program)
    if kind == "push_sharded":
        from lux_tpu.engine.push import ShardedPushExecutor
        return ShardedPushExecutor(graph, program)
    if kind == "push_multi_sharded":
        from lux_tpu.engine.push import ShardedMultiSourcePushExecutor
        return ShardedMultiSourcePushExecutor(graph, program, k=4)
    if kind == "gas":
        from lux_tpu.engine.gas import AdaptiveExecutor, as_gas
        return AdaptiveExecutor(graph, as_gas(program))
    if kind == "gas_multi":
        from lux_tpu.engine.gas import MultiSourceGasExecutor
        return MultiSourceGasExecutor(graph, program, k=4)
    if kind == "gas_sharded":
        from lux_tpu.engine.gas_sharded import ShardedAdaptiveExecutor
        return ShardedAdaptiveExecutor(graph, program)
    if kind == "gas_multi_sharded":
        from lux_tpu.engine.gas_sharded import ShardedMultiSourceGasExecutor
        return ShardedMultiSourceGasExecutor(graph, program, k=4)
    raise ValueError(f"unknown executor kind {kind!r}")


def _compact_graph(kind: str, weighted: bool, seed: int):
    """Graph whose partition actually engages the compact exchange: the
    row-granular engines need read locality (small_world's ring plus a
    contiguous edge-balanced partition leaves only boundary reads), the
    tiled engine needs hub concentration (rmat's Kronecker skew keeps
    strip reads on the few hub blocks). The tiny gnp used for the plain
    targets is all-remote at this size, which would fall back to full
    and silently shrink audit coverage of the compact collectives."""
    from lux_tpu.graph.generate import rmat, small_world
    from lux_tpu.graph.graph import Graph

    if kind == "tiled_sharded":
        return rmat(12, 8, seed=seed, weighted=weighted)
    g = small_world(1024, k=4, p_rewire=0.05, seed=seed)
    if weighted:
        rng = np.random.default_rng(seed)
        g = Graph(nv=g.nv, ne=g.ne, row_ptr=g.row_ptr, col_src=g.col_src,
                  weights=rng.integers(1, 101, g.ne, dtype=np.int32))
    return g


def _registry_executors(include_sharded: bool = True,
                        sharded_only: bool = False):
    """Yield ``(name, kind, executor, init_kw)`` for every registered
    program x capable executor. Sharded kinds are built twice: once
    with the default full exchange and once under
    ``LUX_EXCHANGE=compact`` (``{name}@{kind}+compact``), so the audits
    cover the packed all_to_all path too."""
    import os

    from lux_tpu.models import PROGRAMS, ROOTED_APPS, engine_kinds
    from lux_tpu.utils.logging import get_logger

    for i, name in enumerate(sorted(PROGRAMS)):
        program = PROGRAMS[name]()
        weighted = bool(getattr(program, "needs_weights", False))
        graph = None
        init_kw = {"start": 0} if name in ROOTED_APPS else {}
        for kind in engine_kinds(name):
            sharded = kind.endswith("sharded")
            if sharded and not include_sharded:
                continue
            if sharded_only and not sharded:
                continue
            if graph is None:
                graph = _tiny_graph(weighted=weighted, seed=7 + i)
            ex = build_executor(kind, graph, program)
            yield f"{name}@{kind}", kind, ex, init_kw
            if not sharded:
                continue
            # luxlint: disable=LUX005 -- save/restore needs the raw set-vs-unset env entry, which the typed accessors erase
            prev = os.environ.get("LUX_EXCHANGE")
            os.environ["LUX_EXCHANGE"] = "compact"
            try:
                exc = build_executor(
                    kind, _compact_graph(kind, weighted, 7 + i), program)
            finally:
                if prev is None:
                    os.environ.pop("LUX_EXCHANGE", None)
                else:
                    os.environ["LUX_EXCHANGE"] = prev
            if getattr(exc, "exchange_mode", "full") != "compact":
                # Coverage loss must be visible, not silent.
                get_logger("luxlint").warning(
                    "%s@%s+compact fell back to the full exchange; "
                    "compact collectives untraced for this target",
                    name, kind)
                continue
            yield f"{name}@{kind}+compact", kind, exc, init_kw
            if kind != "gas_sharded":
                continue
            # The adaptive GAS engine additionally carries the
            # frontier-compacted send (LUX_EXCHANGE=frontier): trace it
            # too so LUX404-407 cover the activity-packed all_to_all.
            os.environ["LUX_EXCHANGE"] = "frontier"
            try:
                exf = build_executor(
                    kind, _compact_graph(kind, weighted, 7 + i), program)
            finally:
                if prev is None:
                    os.environ.pop("LUX_EXCHANGE", None)
                else:
                    os.environ["LUX_EXCHANGE"] = prev
            if getattr(exf, "exchange_mode", "full") != "frontier":
                # Frontier-less programs downgrade to compact by design
                # (no activity plane to pack); only a frontier program
                # landing elsewhere is lost coverage.
                if getattr(exf.program, "frontier", False):
                    get_logger("luxlint").warning(
                        "%s@%s+frontier fell back to %s; frontier "
                        "collectives untraced for this target",
                        name, kind, exf.exchange_mode)
                continue
            yield f"{name}@{kind}+frontier", kind, exf, init_kw


def registry_targets(include_sharded: bool = True) -> List[TraceTarget]:
    """Trace targets for every registered program x capable executor
    (see ``_registry_executors`` for the compact-variant policy)."""
    return [
        target_from_spec(name, ex.trace_step(**init_kw))
        for name, _, ex, init_kw in _registry_executors(include_sharded)
    ]


# Value-row byte price per exchanged unit row for each plan-carrying
# executor kind — the same figures the engines' exchange_bytes_per_iter
# models use (pull: program row width x value itemsize; push: 4 B
# uint32 value + 1 B bool frontier per lane; tiled: float32 elements).
def _exchange_row_bytes(kind: str, ex) -> Optional[int]:
    if kind == "pull_sharded":
        return int(ex._row_bytes())
    if kind == "push_sharded":
        return 5
    if kind == "push_multi_sharded":
        return 5 * int(ex.k)
    if kind == "tiled_sharded":
        return 4
    if kind in ("gas_sharded", "gas_multi_sharded"):
        return int(ex._row_bytes())
    return None


def _plan_evidence(kind: str, ex, plan) -> dict:
    """LUX402/403 evidence for a live plan-carrying executor: the
    remote-read counts matrix, the row price, and the exchange ledger
    exactly as the observatory would publish it."""
    from lux_tpu.obs import engobs

    row_bytes = _exchange_row_bytes(kind, ex)
    counts = None
    ledger = None
    sg = getattr(ex, "sg", None)
    if sg is not None and hasattr(sg, "remote_read_counts"):
        counts = sg.remote_read_counts()
        if counts is not None and row_bytes is not None:
            ledger = engobs.useful_exchange(
                sg, row_bytes,
                exchanged_rows=plan.exchanged_units_per_iter)
    if counts is None:
        counts = getattr(ex, "_remote_read_counts", None)
        if counts is not None and row_bytes is not None:
            # The tiled executor's block-granular ledger (its run()
            # computes the same figures inline).
            c = np.asarray(counts, np.int64)
            exchanged = plan.exchanged_units_per_iter * plan.unit_rows
            useful = int(c.sum() - np.trace(c))
            ledger = {
                "useful_rows": useful,
                "exchanged_rows": exchanged,
                "useful_bytes_per_iter": useful * row_bytes,
                "ratio": useful / max(exchanged, 1),
            }
    out = {"remote_read_counts": counts, "row_bytes": row_bytes,
           "ledger": ledger}
    # Frontier-exchange evidence (LUX407), present only on the adaptive
    # GAS executor built under LUX_EXCHANGE=frontier.
    fe = getattr(ex, "frontier_evidence", None)
    if callable(fe):
        out.update(fe() or {})
    return out


def run_exchange_matrix(select=None) -> LintReport:
    """``luxlint --exchange`` with no paths: the LUX404-406 dataflow
    rules over every full+compact sharded registry target, plus the
    jax-free LUX401-403 plan rules over each live compact plan
    (reported as ``{target}/plan``)."""
    from lux_tpu.analysis import exchck

    ir_rules = exchange_ir_rules(select)
    plan_rules = [r for r in exchck.all_exchange_rules()
                  if select is None or r.id in select]
    # Executor construction is environment setup, not verification —
    # keep it outside the timer exactly like the IR tier does (its
    # registry_targets build happens before run_targets starts timing).
    staged = list(_registry_executors(sharded_only=True))
    results: List[FileResult] = []
    t0 = time.perf_counter()
    for name, kind, ex, init_kw in staged:
        t = target_from_spec(name, ex.trace_step(**init_kw))
        results.append(check_target(t, ir_rules))
        if t.plan is not None:
            view = exchck.plan_view(
                t.plan, declared_bytes_per_iter=t.exchange_bytes,
                **_plan_evidence(kind, ex, t.plan))
            results.append(exchck.verify_exchange_plan(
                view, f"{name}/plan", plan_rules))
    return LintReport(results, time.perf_counter() - t0,
                      schema=exchck.EXCHANGE_SCHEMA)


def run_exchange_paths(paths: Sequence[str], select=None) -> LintReport:
    """``luxlint --exchange`` over explicit paths: ``.py`` fixtures
    exposing ``TRACES`` (IR rules) and/or ``PLANS`` (plan rules), and
    saved exchange-artifact directories."""
    import os

    from lux_tpu.analysis import exchck

    t0 = time.perf_counter()
    ir_rules = exchange_ir_rules(select)
    plan_rules = [r for r in exchck.all_exchange_rules()
                  if select is None or r.id in select]
    results: List[FileResult] = []
    for path in paths:
        if os.path.isdir(path):
            try:
                view = exchck.load_exchange_artifact(path)
            except Exception as e:
                results.append(FileResult(
                    path, [], [],
                    error=f"{path}: unloadable plan: {e!r}"))
                continue
            results.append(
                exchck.verify_exchange_plan(view, path, plan_rules))
            continue
        try:
            try:
                targets = load_fixture_targets(path)
            except ValueError:
                targets = []     # PLANS-only fixture
            plans = exchck.load_fixture_plans(path)
        except Exception as e:
            results.append(FileResult(
                path, [], [], error=f"{path}: unloadable fixture: {e!r}"))
            continue
        if not targets and not plans:
            results.append(FileResult(
                path, [], [],
                error=f"{path}: fixture exposes neither TRACES nor PLANS"))
            continue
        results.extend(check_target(t, ir_rules) for t in targets)
        results.extend(exchck.verify_exchange_plan(v, nm, plan_rules)
                       for nm, v in plans)
    return LintReport(results, time.perf_counter() - t0,
                      schema=exchck.EXCHANGE_SCHEMA)


def load_fixture_targets(path: str) -> List[TraceTarget]:
    """Targets from a fixture module exposing ``TRACES`` (a list of
    trace dicts with a ``name`` key) — the seeded-violation harness."""
    import importlib.util
    import os

    modname = "_luxlint_ir_fixture_" + \
        os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load fixture module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    traces = getattr(mod, "TRACES", None)
    if not traces:
        raise ValueError(f"fixture {path} exposes no TRACES")
    return [
        target_from_spec(t.get("name", f"{path}#{i}"), t)
        for i, t in enumerate(traces)
    ]


def audit_engine(engine, name: str, **init_kw) -> List[Finding]:
    """Build-time donation audit of one executor (serve/pool.py hook):
    LUX104 only — one abstract lowering, no trace walk, no execution.
    Engines without ``trace_step`` are silently fine."""
    ts = getattr(engine, "trace_step", None)
    if ts is None:
        return []
    target = target_from_spec(name, ts(**init_kw))
    rule = DonationAudit()
    try:
        # check() needs no jaxpr for LUX104; pass None explicitly.
        return list(rule.check(None, target))
    except Exception as e:
        return [Finding(rule.id, name, 0, 0,
                        f"donation audit crashed: {e!r}")]
