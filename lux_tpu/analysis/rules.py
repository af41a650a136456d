"""The luxlint rule set — this repo's real failure modes, machine-checked.

Each rule encodes an invariant the performance story depends on but the
code previously only promised in prose:

- LUX001 host-sync-in-hot-loop: Gunrock-style frontier/iteration loops
  are fast only while no hidden host round-trip sits inside them (a
  single ``.item()`` per iteration serializes the whole async dispatch
  pipeline — PERF_NOTES.md measured 620 vs 316 ms/iter for dispatch-per-step
  vs fused).
- LUX002 recompile-hygiene: jitted steps must donate their buffer
  argument (else HBM holds two copies) and jitted callables must not be
  fed bare Python scalars (each distinct value retraces).
- LUX003 kernel-shape-contract: Pallas BlockSpecs must honor the TPU
  block layout — 128-lane blocks, rows in Mosaic 8-row units (or
  single-row scalar-prefetch form), int8 code planes, int32 row
  indices.
- LUX004 env-flag-registry: every ``LUX_*`` key read anywhere must be
  declared in lux_tpu/utils/flags.py.
- LUX005 direct-env-read: lux_tpu code reads LUX_* knobs through the
  flags module, not os.environ (writes — CLI flag plumbing,
  subprocess setup — stay legal).
- LUX006 clock-discipline: serve/engine code stamps time through
  obs.spans helpers (clock() for durations on the trace epoch,
  monotonic() for deadlines), never raw time.* — mixed clock sources
  corrupt SLO math and trace alignment.
- LUX007 swallowed-exception: serve/engine handlers that catch
  Exception/BaseException (or bare ``except``) must do more than log
  and move on — a dropped engine error is an answer somebody never
  gets, and the fault-injection harness (utils/faults.py) only proves
  anything if injected failures surface as terminal statuses.

All pure ``ast``; no jax, no numpy.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set

from lux_tpu.analysis.core import FileContext, Finding, Rule

# Functions that ARE the iteration hot path. Deliberately narrow: warmup
# and phase_step sync per dispatch by design.
_HOT_FN_RE = re.compile(r"(^|_)run(_|$)|fixpoint|pipelined")
# jit'd callables that carry the iteration state buffer.
_STEP_FN_RE = re.compile(r"(^|_)(step|run)")

_LANE = 128
_SUBLANE = 8


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.device_get' for Attribute chains, 'float' for Names."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _root_ident(node: ast.AST) -> Optional[str]:
    """Nearest meaningful identifier of an expression: the value a call
    like ``x.codes.astype(...)`` is really about ('codes')."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Subscript):
        return _root_ident(node.value)
    if isinstance(node, ast.Call):
        if node.args:
            return _root_ident(node.args[0])
        return _root_ident(node.func)
    return None


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


class HostSyncInHotLoop(Rule):
    id = "LUX001"
    title = "host-sync-in-hot-loop"
    doc = ("no host transfer/sync (.item(), float(), np.asarray, "
           "device_get, block_until_ready, hard_sync) inside engine "
           "run/fixpoint loops")

    _SYNC_CALLS = {"jax.device_get", "device_get", "hard_sync"}
    _ASARRAY = {"np.asarray", "numpy.asarray", "onp.asarray"}

    def applies_to(self, ctx: FileContext) -> bool:
        return "engine/" in ctx.posix_path

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: Dict[tuple, Finding] = {}
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not _HOT_FN_RE.search(fn.name):
                continue
            host_names = self._host_tainted(fn)
            for loop in ast.walk(fn):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                for node in ast.walk(loop):
                    f = self._check_call(node, fn.name, host_names, ctx)
                    if f is not None:
                        out[(f.line, f.col)] = f
        return out.values()

    def _host_tainted(self, fn: ast.AST) -> Set[str]:
        """Names holding already-fetched host values: assigned (possibly
        transitively) from a device_get result. Converting those again
        (int()/np.asarray()) is free — don't flag it."""
        assigns = sorted(
            (n for n in ast.walk(fn)
             if isinstance(n, (ast.Assign, ast.AugAssign))),
            key=lambda n: n.lineno,
        )
        tainted: Set[str] = set()
        for a in assigns:
            rhs = a.value
            from_get = any(
                isinstance(c, ast.Call)
                and _dotted(c.func) in self._SYNC_CALLS
                for c in ast.walk(rhs)
            )
            if not (from_get or (_names_in(rhs) & tainted)):
                continue
            targets = a.targets if isinstance(a, ast.Assign) else [a.target]
            for t in targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                tainted.update(
                    e.id for e in elts if isinstance(e, ast.Name)
                )
        return tainted

    def _arg_is_host(self, arg: ast.AST, host_names: Set[str]) -> bool:
        if isinstance(arg, ast.Constant):
            return True
        if _names_in(arg) & host_names:
            return True
        # np.asarray(jax.device_get(x)): the inner sync is the finding.
        return any(
            isinstance(c, ast.Call) and _dotted(c.func) in self._SYNC_CALLS
            for c in ast.walk(arg)
        )

    def _check_call(self, node, fn_name, host_names, ctx):
        if not isinstance(node, ast.Call):
            return None
        name = _dotted(node.func)
        if name in self._SYNC_CALLS or (
            name is not None and name.endswith("block_until_ready")
        ):
            return self.finding(
                ctx, node,
                f"`{name}` inside hot loop of `{fn_name}` stalls the "
                "device pipeline; hoist it out of the loop or suppress "
                "with a reason",
            )
        if name in self._ASARRAY and node.args and not self._arg_is_host(
            node.args[0], host_names
        ):
            return self.finding(
                ctx, node,
                f"`{name}` on a device value inside hot loop of "
                f"`{fn_name}` forces a device->host transfer per "
                "iteration",
            )
        if name in ("float", "int") and len(node.args) == 1 and \
                not self._arg_is_host(node.args[0], host_names):
            return self.finding(
                ctx, node,
                f"`{name}()` on a device value inside hot loop of "
                f"`{fn_name}` blocks on the device per iteration",
            )
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "item" and not node.args and \
                not self._arg_is_host(node.func.value, host_names):
            return self.finding(
                ctx, node,
                f"`.item()` inside hot loop of `{fn_name}` is a "
                "synchronous device->host scalar read per iteration",
            )
        return None


class RecompileHygiene(Rule):
    id = "LUX002"
    title = "recompile-hygiene"
    doc = ("jitted buffer-carrying steps need donate_argnums; jitted "
           "callables must not be fed bare Python scalars")

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        # binding name -> True when the jit has static_argnums/argnames
        # (scalar args are then legitimately static).
        jit_bindings: Dict[str, bool] = {}

        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ) and _dotted(node.value.func) in ("jax.jit", "jit"):
                out.extend(self._check_jit_call(node.value, ctx))
                has_static = self._has_kw(
                    node.value, "static_argnums", "static_argnames"
                )
                for t in node.targets:
                    bind = t.id if isinstance(t, ast.Name) else (
                        t.attr if isinstance(t, ast.Attribute) else None
                    )
                    if bind is not None:
                        jit_bindings[bind] = has_static
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    dec_call = dec if isinstance(dec, ast.Call) else None
                    name = _dotted(dec_call.func if dec_call else dec)
                    if name in ("jax.jit", "jit") and _STEP_FN_RE.search(
                        node.name
                    ) and not (
                        dec_call is not None and self._has_kw(
                            dec_call, "donate_argnums", "donate_argnames"
                        )
                    ):
                        out.append(self.finding(
                            ctx, dec,
                            f"@jit on buffer-carrying `{node.name}` "
                            "without donate_argnums keeps the old buffer "
                            "live (2x HBM for the state)",
                        ))

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            bind = None
            if isinstance(node.func, ast.Name):
                bind = node.func.id
            elif isinstance(node.func, ast.Attribute):
                bind = node.func.attr
            if bind not in jit_bindings or jit_bindings[bind]:
                continue
            scalars = [
                a for a in list(node.args) + [k.value for k in node.keywords]
                if isinstance(a, ast.Constant)
                and type(a.value) in (int, float)
            ]
            for a in scalars:
                out.append(self.finding(
                    ctx, a,
                    f"Python scalar {a.value!r} fed to jitted `{bind}` — "
                    "every distinct value retraces and recompiles; wrap "
                    "it (jnp.asarray/jnp.int32) or mark the arg static",
                ))
        return out

    @staticmethod
    def _has_kw(call: ast.Call, *names: str) -> bool:
        return any(k.arg in names for k in call.keywords)

    def _check_jit_call(self, call: ast.Call, ctx) -> List[Finding]:
        if not call.args:
            return []
        fn_name = _dotted(call.args[0])
        if fn_name is None:
            return []
        short = fn_name.rsplit(".", 1)[-1]
        if _STEP_FN_RE.search(short) and not self._has_kw(
            call, "donate_argnums", "donate_argnames"
        ):
            return [self.finding(
                ctx, call,
                f"jax.jit of buffer-carrying `{short}` without "
                "donate_argnums keeps the old buffer live (2x HBM for "
                "the state)",
            )]
        return []


class KernelShapeContract(Rule):
    id = "LUX003"
    title = "kernel-shape-contract"
    doc = ("Pallas BlockSpecs: 128-lane blocks, rows 1 or a multiple of "
           "8; kernel dtype contract: int8 code planes, int32 row "
           "indices (Mosaic's TPU block layout)")

    _CODE_DTYPES = {"int8", "int32"}   # codes upcast to int32 in-kernel
    _ROW_DTYPES = {"int32"}

    def applies_to(self, ctx: FileContext) -> bool:
        return "ops/" in ctx.posix_path

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        is_kernel_file = "kernel" in ctx.posix_path.rsplit("/", 1)[-1]
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            short = name.rsplit(".", 1)[-1] if name else (
                node.func.attr if isinstance(node.func, ast.Attribute)
                else None
            )
            if short in ("BlockSpec", "ShapeDtypeStruct"):
                out.extend(self._check_shape(node, short, ctx))
            elif short == "astype" and is_kernel_file:
                out.extend(self._check_astype(node, ctx))
        return out

    def _check_shape(self, node: ast.Call, short: str, ctx) -> List[Finding]:
        if not node.args or not isinstance(node.args[0], ast.Tuple):
            return []
        elts = node.args[0].elts
        out: List[Finding] = []
        if not elts:
            return out
        last = elts[-1]
        if isinstance(last, ast.Constant) and isinstance(last.value, int) \
                and last.value % _LANE != 0:
            out.append(self.finding(
                ctx, last,
                f"{short} lane width {last.value} — the trailing block "
                f"dim must be a multiple of {_LANE} (VPU lane tile); "
                "narrower blocks scalarize",
            ))
        if short == "BlockSpec" and len(elts) >= 2:
            first = elts[0]
            if isinstance(first, ast.Constant) and isinstance(
                first.value, int
            ) and first.value != 1 and first.value % _SUBLANE != 0:
                out.append(self.finding(
                    ctx, first,
                    f"BlockSpec sublane rows {first.value} — rows must "
                    f"be 1 (scalar-prefetch per-row form) or a multiple "
                    f"of {_SUBLANE} (Mosaic 8-row block units)",
                ))
        return out

    def _check_astype(self, node: ast.Call, ctx) -> List[Finding]:
        if len(node.args) != 1 or not isinstance(node.func, ast.Attribute):
            return []
        dt = node.args[0]
        dtype = dt.value if isinstance(dt, ast.Constant) else (
            (_dotted(dt) or "").rsplit(".", 1)[-1]
        )
        if not isinstance(dtype, str) or not dtype:
            return []
        ident = (_root_ident(node.func.value) or "").lower()
        if "code" in ident and dtype not in self._CODE_DTYPES:
            return [self.finding(
                ctx, node,
                f"code plane `{ident}` cast to {dtype} — the routing "
                "plane contract is int8 at rest (int32 in-kernel)",
            )]
        if "row" in ident and dtype not in self._ROW_DTYPES:
            return [self.finding(
                ctx, node,
                f"row-index `{ident}` cast to {dtype} — scalar-prefetch "
                "row offsets must be int32 on device",
            )]
        return []


def _env_key(call: ast.Call) -> Optional[str]:
    """The literal LUX_* key of an os.environ access, if any."""
    if call.args and isinstance(call.args[0], ast.Constant) and \
            isinstance(call.args[0].value, str) and \
            call.args[0].value.startswith("LUX_"):
        return call.args[0].value
    return None


class EnvFlagRegistry(Rule):
    id = "LUX004"
    title = "env-flag-registry"
    doc = ("every LUX_* env key touched anywhere must be declared in "
           "lux_tpu/utils/flags.py; flags.define() outside that file is "
           "registry drift")

    _ENV_CALLS = ("environ.get", "environ.setdefault", "environ.pop",
                  "getenv")
    _FLAG_CALLS = ("get", "get_int", "get_float", "get_bool", "tristate")

    @staticmethod
    def _define_aliases(tree: ast.Module) -> Set[str]:
        """Local names bound to lux_tpu.utils.flags.define by imports."""
        out: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.endswith("utils.flags"):
                out.update(
                    a.asname or a.name for a in node.names
                    if a.name == "define"
                )
        return out

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        in_registry = ctx.posix_path.endswith("utils/flags.py")
        define_aliases = self._define_aliases(tree)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and not in_registry:
                name = _dotted(node.func) or ""
                if name.endswith("flags.define") or name in define_aliases:
                    out.append(self.finding(
                        ctx, node,
                        "flags.define() outside lux_tpu/utils/flags.py — "
                        "the registry is the single declaration site; "
                        "LUX004's allowed-key set is generated from it",
                    ))
        for node in ast.walk(tree):
            key = None
            if isinstance(node, ast.Call):
                name = _dotted(node.func) or ""
                short = name.rsplit(".", 1)[-1]
                if any(name.endswith(c) for c in self._ENV_CALLS):
                    key = _env_key(node)
                elif short in self._FLAG_CALLS and (
                    "flags." in name or name.startswith("flags")
                ):
                    key = _env_key(node)
            elif isinstance(node, ast.Subscript):
                name = _dotted(node.value) or ""
                if name.endswith("environ") and isinstance(
                    node.slice, ast.Constant
                ) and isinstance(node.slice.value, str) and \
                        node.slice.value.startswith("LUX_"):
                    key = node.slice.value
            if key is not None and key not in ctx.declared_flags:
                out.append(self.finding(
                    ctx, node,
                    f"undeclared flag {key} — declare it in "
                    "lux_tpu/utils/flags.py so the registry stays the "
                    "single source of truth",
                ))
        return out


class DirectEnvRead(Rule):
    id = "LUX005"
    title = "direct-env-read"
    doc = ("lux_tpu code must read LUX_* knobs through "
           "lux_tpu.utils.flags, not os.environ (writes stay legal)")

    def applies_to(self, ctx: FileContext) -> bool:
        return "lux_tpu/" in ctx.posix_path and not ctx.posix_path.endswith(
            "utils/flags.py"
        )

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for node in ast.walk(tree):
            key = None
            if isinstance(node, ast.Call):
                name = _dotted(node.func) or ""
                if name.endswith("environ.get") or name.endswith("getenv"):
                    key = _env_key(node)
            elif isinstance(node, ast.Subscript) and isinstance(
                node.ctx, ast.Load
            ):
                name = _dotted(node.value) or ""
                if name.endswith("environ") and isinstance(
                    node.slice, ast.Constant
                ) and isinstance(node.slice.value, str) and \
                        node.slice.value.startswith("LUX_"):
                    key = node.slice.value
            if key is not None:
                out.append(self.finding(
                    ctx, node,
                    f"direct os.environ read of {key} — use "
                    "lux_tpu.utils.flags accessors (typed, documented, "
                    "registry-checked)",
                ))
        return out


class ClockDiscipline(Rule):
    id = "LUX006"
    title = "clock-discipline"
    doc = ("serve/engine code takes timestamps through the obs helpers "
           "(spans.clock for durations, spans.monotonic for deadlines), "
           "not raw time.* — mixed clock sources make latency math and "
           "trace alignment silently wrong")

    _CLOCK_CALLS = {
        "time.time", "time.perf_counter", "time.monotonic",
        "time.perf_counter_ns", "time.monotonic_ns",
    }

    def applies_to(self, ctx: FileContext) -> bool:
        if "obs/" in ctx.posix_path:      # the helpers themselves
            return False
        return "serve/" in ctx.posix_path or "engine/" in ctx.posix_path

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if name in self._CLOCK_CALLS:
                out.append(self.finding(
                    ctx, node,
                    f"direct {name}() in serve/engine code — use "
                    "lux_tpu.obs.spans.clock() (perf_counter, trace "
                    "epoch) or spans.monotonic() (deadlines) so every "
                    "latency shares one clock source",
                ))
        return out


class SwallowedException(Rule):
    id = "LUX007"
    title = "swallowed-exception"
    doc = ("serve/engine handlers catching Exception/BaseException (or "
           "bare except) must not reduce to log-and-drop — re-raise, "
           "convert to a typed ServeError, resolve the request's future, "
           "or record state the caller observes")

    # A handler whose whole body is pass/continue/bare-return plus calls
    # that only say something matches "swallow". Matching is on the
    # dotted-name parts, so self.log.warning, logging.error, print, and
    # logger.exception all count as log-only; metrics increments, future
    # resolution, and flight dumps count as real work (observable state).
    _LOG_PARTS = frozenset((
        "log", "logger", "logging", "print", "warn", "warning", "debug",
        "info", "error", "exception",
    ))

    def applies_to(self, ctx: FileContext) -> bool:
        return "serve/" in ctx.posix_path or "engine/" in ctx.posix_path

    @classmethod
    def _broad(cls, handler: ast.ExceptHandler) -> bool:
        if handler.type is None:            # bare except
            return True
        elts = (handler.type.elts if isinstance(handler.type, ast.Tuple)
                else [handler.type])
        return any((_dotted(e) or "") in ("Exception", "BaseException")
                   for e in elts)

    @classmethod
    def _inert(cls, stmt: ast.stmt) -> bool:
        """True for statements that drop the error on the floor."""
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            return True
        if isinstance(stmt, ast.Return):
            return stmt.value is None or (
                isinstance(stmt.value, ast.Constant)
                and stmt.value.value is None
            )
        if isinstance(stmt, ast.Expr):
            if isinstance(stmt.value, ast.Constant):
                return True                 # stray docstring
            if isinstance(stmt.value, ast.Call):
                name = _dotted(stmt.value.func) or ""
                return any(p.lower() in cls._LOG_PARTS
                           for p in name.split("."))
        return False

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._broad(node):
                continue
            if all(self._inert(s) for s in node.body):
                caught = ("bare except" if node.type is None
                          else _dotted(node.type) or "broad except")
                out.append(self.finding(
                    ctx, node,
                    f"{caught} swallows the error (log-and-drop body) — "
                    "re-raise, map to a typed ServeError, or make the "
                    "failure observable (resolve the future / record "
                    "state); silent drops hide real engine faults",
                ))
        return out


class MetricNameDiscipline(Rule):
    id = "LUX008"
    title = "metric-name-discipline"
    doc = ("metric names must match lux_[a-z0-9_]+(_total|_seconds|"
           "_bytes)? and handles must not be minted per call: every "
           "counter/gauge/histogram factory call round-trips the "
           "registry lock, so creation is banned inside loops, and in "
           "obs/ code a constant-shaped handle (literal name, no or "
           "constant labels) must live at module scope")

    _NAME_RE = re.compile(r"lux_[a-z0-9_]+(_total|_seconds|_bytes)?")
    _FACTORIES = frozenset(("counter", "gauge", "histogram"))

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        bare = self._bare_factory_names(tree)
        in_obs = "obs/" in ctx.posix_path
        # One pass with explicit ancestry: (in a def, in a loop) per node.
        # At most ONE finding per creation call — bad name beats
        # loop-mint beats module-scope, so each site reads as one defect.
        stack: List[Tuple[ast.AST, bool, bool]] = [(tree, False, False)]
        while stack:
            node, in_def, in_loop = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                in_def = True
            elif isinstance(node, (ast.For, ast.While)):
                in_loop = True
            elif isinstance(node, ast.Call):
                f = self._check_creation(node, ctx, bare, in_obs,
                                         in_def, in_loop)
                if f is not None:
                    out.append(f)
            for child in ast.iter_child_nodes(node):
                stack.append((child, in_def, in_loop))
        return out

    def _bare_factory_names(self, tree: ast.Module) -> Set[str]:
        """Factory names bound by ``from ...metrics import counter, ...``
        anywhere in the file (engine code imports them function-locally)."""
        names: Set[str] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            if not (node.module or "").endswith("metrics"):
                continue
            names.update(
                a.asname or a.name for a in node.names
                if a.name in self._FACTORIES)
        return names

    def _is_factory(self, node: ast.Call, bare: Set[str]) -> bool:
        func = node.func
        if isinstance(func, ast.Name):
            return func.id in bare
        name = _dotted(func)
        if name is None:
            return False
        parts = name.split(".")
        return parts[-1] in self._FACTORIES and "metrics" in parts[:-1]

    @staticmethod
    def _constant_labels(node: ast.Call) -> bool:
        """True when the labels argument is absent, None, or a literal
        dict of literal keys/values — i.e. the handle has a fixed shape
        and the creation could be hoisted verbatim."""
        labels: Optional[ast.expr] = None
        if len(node.args) > 1:
            labels = node.args[1]
        for kw in node.keywords:
            if kw.arg == "labels":
                labels = kw.value
        if labels is None:
            return True
        if isinstance(labels, ast.Constant):
            return labels.value is None
        if isinstance(labels, ast.Dict):
            return all(isinstance(k, ast.Constant) for k in labels.keys) \
                and all(isinstance(v, ast.Constant) for v in labels.values)
        return False

    def _check_creation(self, node: ast.Call, ctx: FileContext,
                        bare: Set[str], in_obs: bool,
                        in_def: bool, in_loop: bool) -> Optional[Finding]:
        if not self._is_factory(node, bare):
            return None
        name_arg = node.args[0] if node.args else None
        literal = (name_arg.value
                   if isinstance(name_arg, ast.Constant)
                   and isinstance(name_arg.value, str) else None)
        if literal is not None and not self._NAME_RE.fullmatch(literal):
            return self.finding(
                ctx, node,
                f"metric name {literal!r} breaks the naming contract — "
                "must match lux_[a-z0-9_]+(_total|_seconds|_bytes)? "
                "(lux_ prefix, lowercase snake_case, unit suffix for "
                "counters/durations/sizes)")
        hoistable = literal is not None and self._constant_labels(node)
        if in_loop and hoistable:
            return self.finding(
                ctx, node,
                f"metric handle {literal!r} minted inside a loop — each "
                "factory call takes the registry lock; create the handle "
                "once outside the loop and reuse it")
        if in_obs and in_def and hoistable:
            return self.finding(
                ctx, node,
                f"constant-shaped metric handle {literal!r} created per "
                "call — literal name with no/constant labels belongs at "
                "module scope; per-call creation churns the registry "
                "lock on every invocation")
        return None


class RegionNameDiscipline(Rule):
    id = "LUX009"
    title = "region-name-discipline"
    doc = ("profiler region names must match lux\\.[a-z0-9_.]+: a "
           "literal name passed to prof.region, jax.named_scope, or "
           "jax.profiler.TraceAnnotation that breaks the pattern never "
           "joins the profile.v1 phase accounting (the parser only "
           "classifies lux.* tags), so the time it brackets silently "
           "vanishes from exchange/compute attribution")

    _NAME_RE = re.compile(r"lux\.[a-z0-9_.]+")
    # Dotted-call tails that take a region/scope name as their first
    # argument. `region` alone is also tracked when imported bare from
    # obs.prof (mirrors LUX008's bare-factory tracking).
    _TAILS = frozenset(("named_scope", "TraceAnnotation"))

    def _bare_region_names(self, tree: ast.Module) -> Set[str]:
        """Names bound by ``from ...prof import region`` (or an asname
        of it) anywhere in the file."""
        names: Set[str] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            if not (node.module or "").endswith("prof"):
                continue
            names.update(a.asname or a.name for a in node.names
                         if a.name == "region")
        return names

    def _is_region_call(self, node: ast.Call, bare: Set[str]) -> bool:
        func = node.func
        if isinstance(func, ast.Name):
            return func.id in bare
        name = _dotted(func)
        if name is None:
            return False
        parts = name.split(".")
        tail = parts[-1]
        if tail == "region":
            return "prof" in parts[:-1]
        if tail in self._TAILS:
            # jax.named_scope / jax.profiler.TraceAnnotation, however
            # the jax module is spelled locally.
            return True
        return False

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        bare = self._bare_region_names(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if not self._is_region_call(node, bare):
                continue
            name_arg = node.args[0] if node.args else None
            literal = (name_arg.value
                       if isinstance(name_arg, ast.Constant)
                       and isinstance(name_arg.value, str) else None)
            if literal is None:
                continue    # dynamic names validate at runtime
            if not self._NAME_RE.fullmatch(literal):
                out.append(self.finding(
                    ctx, node,
                    f"region name {literal!r} breaks the naming contract "
                    "— must fullmatch lux.[a-z0-9_.]+ (lux. prefix, "
                    "lowercase dotted segments) or the profile.v1 parser "
                    "drops it from phase attribution"))
        return out


class LedgerDiscipline(Rule):
    id = "LUX010"
    title = "ledger-discipline"
    doc = ("run metrics (summaries, telemetry) leave the process through "
           "the run ledger (lux_tpu/obs/ledger.py record_run), not ad-hoc "
           "json.dump — an unframed dump is invisible to lux_doctor and "
           "the auto-tuner corpus, and carries no config_hash to "
           "reproduce it under")

    # Dumping an expression rooted at one of these identifiers is the
    # run-metrics shape this rule polices; artifact writes (plans,
    # reports, flight docs, bench round lines) keep their own formats.
    _METRIC_IDENTS = ("summary", "telemetry", "runrec", "run_record",
                      "metrics")

    def applies_to(self, ctx: FileContext) -> bool:
        p = ctx.posix_path
        if p.endswith("obs/ledger.py") or p.endswith("obs/report.py"):
            # The ledger's own framing, and the documented legacy
            # LUX_METRICS JSON-lines dump report.finalize still feeds.
            return False
        return ("engine/" in p or "serve/" in p or "obs/" in p
                or p.endswith("bench.py"))

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func) or ""
            if name not in ("json.dump", "json.dumps"):
                continue
            arg = node.args[0] if node.args else None
            root = (_root_ident(arg) or "").lower() if arg is not None \
                else ""
            if any(tok in root for tok in self._METRIC_IDENTS):
                out.append(self.finding(
                    ctx, node,
                    f"ad-hoc json dump of run metrics ({root!r}) — append "
                    "a runrec.v1 record via lux_tpu.obs.ledger.record_run "
                    "so the observation is durable, crc-framed, and keyed "
                    "by (graph, program, engine, mesh, config_hash)",
                ))
        return out


def all_rules() -> List[Rule]:
    return [
        HostSyncInHotLoop(),
        RecompileHygiene(),
        KernelShapeContract(),
        EnvFlagRegistry(),
        DirectEnvRead(),
        ClockDiscipline(),
        SwallowedException(),
        MetricNameDiscipline(),
        RegionNameDiscipline(),
        LedgerDiscipline(),
    ]
