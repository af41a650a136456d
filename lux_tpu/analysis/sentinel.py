"""Runtime tracing-discipline sentinels.

The static rules (rules.py) catch what an AST can see; these two catch
what only the runtime knows:

- :class:`RecompileSentinel` — counts actual XLA backend compiles per
  executor key via the jax monitoring hook
  (``/jax/core/compile/backend_compile_duration`` fires once per real
  compile, never on tracing-cache hits). serve/pool.py builds engines
  under ``expect(key)`` and serves queries under ``watch(key)``; any
  compile landing in a watch region is a recompile — the serving
  layer's "zero recompiles after warmup" claim, machine-checked.
  Counters mirror onto the obs metrics registry
  (``lux_xla_compiles_total{key,phase}``) so ``LUX_METRICS`` dumps
  carry compile counts per engine key. The same listener sums compile
  seconds into ``lux_xla_compile_seconds_total{phase}`` for compiles
  inside a sentinel region or a ``compile_phase`` (the batch executors'
  warm-up).

- :class:`HostTransferGuard` — a context manager that fails any
  ``jax.device_get`` / ``jax.block_until_ready`` issued inside a
  guarded iteration region (and, on non-CPU backends, any implicit
  device->host transfer via jax's own transfer guard — on the CPU
  test mesh arrays are host-resident, so jax's guard never fires and
  the patched entry points are the enforcement). Tests wrap the
  region between intended sync points to prove the loop body is
  transfer-free.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

from lux_tpu.obs import metrics

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_SENTINELS = set()
_SENTINELS_LOCK = threading.Lock()
_LISTENER_STATE = {"installed": False, "available": False}
# Per-thread stack of compile phases (jax compiles synchronously on the
# dispatching thread): sentinel regions and ``compile_phase`` push here.
_PHASES = threading.local()


def _phase_stack() -> list:
    st = getattr(_PHASES, "stack", None)
    if st is None:
        st = _PHASES.stack = []
    return st


def _dispatch(event: str, duration: float = 0.0, *a, **kw):
    if event != _COMPILE_EVENT:
        return
    st = _phase_stack()
    if st:
        metrics.counter("lux_xla_compile_seconds_total",
                        {"phase": st[-1]}).inc(float(duration))
    with _SENTINELS_LOCK:
        active = list(_SENTINELS)
    for s in active:
        s._on_compile()


@contextlib.contextmanager
def compile_phase(phase: str):
    """Count the seconds of every XLA compile on this thread inside the
    block under ``lux_xla_compile_seconds_total{phase}`` (installs the
    listener on first use). The counter exists from here on, so a phase
    whose executables all came from the persistent cache reads 0."""
    _ensure_listener()
    metrics.counter("lux_xla_compile_seconds_total", {"phase": phase})
    st = _phase_stack()
    st.append(phase)
    try:
        yield
    finally:
        st.pop()


def _ensure_listener() -> bool:
    """Install the process-wide compile listener once. jax's monitoring
    registry is append-only, so the listener dispatches to whatever
    sentinels are alive rather than registering per instance."""
    if _LISTENER_STATE["installed"]:
        return _LISTENER_STATE["available"]
    _LISTENER_STATE["installed"] = True
    try:
        from jax._src import monitoring
    except ImportError:
        _LISTENER_STATE["available"] = False
        return False
    monitoring.register_event_duration_secs_listener(_dispatch)
    _LISTENER_STATE["available"] = True
    return True


class RecompileError(AssertionError):
    """A compile happened in a region that promised zero recompiles."""


class RecompileSentinel:
    """Per-key XLA compile counter with warmup/serve phase attribution.

    Compiles are attributed to the innermost active region on the
    calling thread (jax compiles synchronously on the dispatching
    thread): ``expect(key)`` regions absorb warmup compiles,
    ``watch(key)`` regions count recompiles. Compiles outside any
    region are ignored — unrelated test traffic must not pollute the
    serving evidence.
    """

    def __init__(self, scope: str = "default"):
        self.scope = scope
        self.available = _ensure_listener()
        self._counts: Dict[Tuple[str, str], int] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()
        # Registered eagerly so a clean sentinel still exports the
        # family at 0 — /metrics scrapes alert on the serve-phase count
        # going nonzero, not on its absence.
        metrics.counter(
            "lux_xla_compiles_total",
            {"scope": scope, "key": "_all", "phase": "serve"},
        )
        with _SENTINELS_LOCK:
            _SENTINELS.add(self)

    def close(self):
        with _SENTINELS_LOCK:
            _SENTINELS.discard(self)

    # -- region plumbing -------------------------------------------------

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @contextlib.contextmanager
    def _region(self, phase: str, key):
        st = self._stack()
        st.append((phase, str(key)))
        try:
            with compile_phase(phase):
                yield self
        finally:
            st.pop()

    def expect(self, key):
        """Region where compiles are expected (build + warmup)."""
        return self._region("warmup", key)

    def watch(self, key):
        """Region that promises zero compiles (post-warmup serving)."""
        return self._region("serve", key)

    def _on_compile(self):
        st = getattr(self._tls, "stack", None)
        if not st:
            return
        phase, key = st[-1]
        with self._lock:
            self._counts[(key, phase)] = self._counts.get((key, phase), 0) + 1
        metrics.counter(
            "lux_xla_compiles_total",
            {"scope": self.scope, "key": key, "phase": phase},
        ).inc()

    # -- readout ---------------------------------------------------------

    def compiles(self, key=None, phase: str = "warmup") -> int:
        with self._lock:
            return sum(
                c for (k, p), c in self._counts.items()
                if p == phase and (key is None or k == str(key))
            )

    def recompiles(self, key=None) -> int:
        """Compiles observed inside watch regions (should stay 0)."""
        return self.compiles(key, phase="serve")

    def stats(self) -> dict:
        with self._lock:
            per_key: Dict[str, Dict[str, int]] = {}
            for (k, p), c in self._counts.items():
                per_key.setdefault(k, {})[p] = c
        return {
            "available": self.available,
            "warmup_compiles": self.compiles(),
            "recompiles": self.recompiles(),
            "per_key": per_key,
        }

    def assert_zero_recompiles(self, key=None):
        n = self.recompiles(key)
        if n:
            raise RecompileError(
                f"{n} XLA compile(s) after warmup in scope "
                f"{self.scope!r}: {self.stats()['per_key']}"
            )


class HostTransferError(AssertionError):
    """A device->host transfer happened inside a guarded region."""


class HostTransferGuard:
    """Fail device->host transfers inside a guarded iteration region.

    Patches ``jax.device_get`` and ``jax.block_until_ready`` (the entry
    points every lux_tpu sync path funnels through — hard_sync calls
    both) and additionally arms jax's own
    ``transfer_guard_device_to_host("disallow")``, which catches
    implicit transfers (``np.asarray``, ``float()``, ``.item()``) on
    backends with a real device boundary. Single-thread test use; the
    module-level patch is process-wide while the guard is active.

    ``allow()`` opens a window for an intended sync point::

        with HostTransferGuard() as g:
            for _ in range(n):
                vals = step(vals)        # must stay on device
            with g.allow():
                jax.block_until_ready(vals)
    """

    def __init__(self, label: str = ""):
        self.label = label
        self._depth = 0          # allow() nesting
        self._saved = None
        self._stack = None

    def _blocked(self, what: str):
        raise HostTransferError(
            f"{what} inside HostTransferGuard"
            + (f" [{self.label}]" if self.label else "")
            + " — device->host transfer in a guarded iteration region"
        )

    def __enter__(self):
        import jax

        real_get, real_block = jax.device_get, jax.block_until_ready
        guard = self

        def guarded_get(x):
            if guard._depth == 0:
                guard._blocked("jax.device_get")
            return real_get(x)

        def guarded_block(x):
            if guard._depth == 0:
                guard._blocked("jax.block_until_ready")
            return real_block(x)

        self._saved = (real_get, real_block)
        jax.device_get = guarded_get
        jax.block_until_ready = guarded_block
        self._stack = contextlib.ExitStack()
        try:
            self._stack.enter_context(
                jax.transfer_guard_device_to_host("disallow")
            )
        except Exception:
            pass  # older jax without the context manager: patches only
        return self

    def __exit__(self, *exc):
        import jax

        jax.device_get, jax.block_until_ready = self._saved
        self._saved = None
        stack, self._stack = self._stack, None
        stack.close()
        return False

    @contextlib.contextmanager
    def allow(self):
        """Window for an intended sync point inside the guard."""
        import jax

        self._depth += 1
        try:
            with jax.transfer_guard_device_to_host("allow"):
                yield
        finally:
            self._depth -= 1
