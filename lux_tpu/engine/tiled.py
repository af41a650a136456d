"""Hybrid pull executor: MXU strips + lane-select tail, no scalar gathers.

Drop-in alternative to :class:`lux_tpu.engine.pull.PullExecutor` for pull
programs whose edge contribution is the source value itself
(``program.identity_contrib``) with a ``sum`` combiner — i.e. SpMV-shaped
iterations like PageRank (the reference stores rank pre-divided by
out-degree precisely so its gather side is an identity sum,
pagerank/pagerank_gpu.cu:90-99).

Internally the executor runs in degree-sorted vertex order (the plan's
"internal" space) and converts at the public API boundary, so callers
see external vertex ids exactly like the plain executor. See
:mod:`lux_tpu.ops.tiled_spmv` for the layout design and measured rates.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.analysis.sentinel import compile_phase
from lux_tpu.engine.program import PullProgram, VertexCtx
from lux_tpu.engine.pull import (
    count_iterations,
    hard_sync,
    make_fused_runner,
    run_maybe_fused,
)
from lux_tpu.graph.graph import Graph
from lux_tpu.obs import (
    NULL_RECORDER,
    consume_compile_seconds,
    note_compile_seconds,
    prof,
    recorder_for,
    spans,
)
from lux_tpu.utils.timing import Timer
from lux_tpu.ops.merge_tail_kernel import (
    DeviceGroupedTail,
    grouped_tail_enabled,
)
from lux_tpu.ops.tiled_spmv import (
    DEFAULT_CHUNK_STRIPS,
    DEFAULT_CHUNK_TAIL,
    DeviceHybrid,
    HybridPlan,
    hybrid_spmv,
    plan_hybrid,
)


def spmv_capable(program: PullProgram) -> bool:
    """True if the strip/lane-select hybrid can run this program
    (sum combiner, edge contribution == source value)."""
    return (
        program.combiner == "sum"
        and getattr(program, "identity_contrib", False)
        and not getattr(program, "value_shape", ())  # scalar values only
    )


def get_cached_plan(
    graph: Graph,
    path: str,
    levels: Sequence[Tuple[int, int]] = ((8, 2),),
    budget_bytes: int = 8 << 30,
    log=None,
    cap: int = 15,
    pack: Optional[bool] = None,
) -> HybridPlan:
    """Load the hybrid plan cached at ``path`` (validating it against the
    graph), else plan and save. Planning costs minutes of host time at
    RMAT22+ scale and is graph-deterministic, so every entry point (CLI,
    bench) should come through here. A failed save (read-only graph dir)
    degrades to planning without a cache. ``pack`` is the caller's
    nibble-packing intent (None = the LUX_PACK_STRIPS env default): a
    cap-127 legacy cache is perfectly servable unless packing will
    actually be used."""
    import os

    from lux_tpu.ops.tiled_spmv import load_plan, resolve_pack, save_plan

    say = log if log is not None else (lambda *_: None)
    load_path = path
    if not os.path.exists(path) and path.endswith(".luxplan"):
        # Round-1 caches used a single .npz at the same key; serve them
        # rather than replanning (load_plan keeps the legacy reader). A
        # replan still saves to the .luxplan path, not the legacy name.
        legacy = path[: -len(".luxplan")] + ".npz"
        if os.path.exists(legacy):
            say(f"serving legacy plan cache {legacy}")
            load_path = legacy
    if os.path.exists(load_path):
        plan = None
        try:
            plan = load_plan(load_path)
        except Exception as e:
            say(f"cached plan {load_path} unreadable ({e!r}) — replanning")
        if plan is not None and (
            plan.nv != graph.nv or plan.total_edges != graph.ne
        ):
            say(
                f"cached plan {load_path} does not match graph "
                f"(nv {plan.nv} vs {graph.nv}, edges {plan.total_edges} "
                f"vs {graph.ne}) — replanning"
            )
            plan = None
        # Config check. The cascade's r-sequence is recoverable from any
        # plan; thresholds/budget are recorded by current saves
        # (levels_spec/budget_bytes) and validated when present — legacy
        # caches predating those fields pass on the r-sequence alone.
        want_rs = tuple(r for r, _ in levels)
        if plan is not None and tuple(l.r for l in plan.levels) != want_rs:
            say(
                f"cached plan {load_path} has cascade r-levels "
                f"{tuple(l.r for l in plan.levels)}, requested {want_rs} "
                "— replanning"
            )
            plan = None
        want_spec = tuple((int(r), int(t)) for r, t in levels)
        if (
            plan is not None
            and plan.levels_spec is not None
            and (
                plan.levels_spec != want_spec
                or plan.budget_bytes != int(budget_bytes)
            )
        ):
            say(
                f"cached plan {load_path} was planned with "
                f"levels={plan.levels_spec} budget={plan.budget_bytes}, "
                f"requested levels={want_spec} budget={int(budget_bytes)} "
                "— replanning"
            )
            plan = None
        # A plan capped tighter than requested is servable (it just
        # spilled a few more overflow edges to the tail). A looser cap
        # only matters when nibble packing will actually be used — an
        # unpacked run (the default and the measured-better config)
        # serves cap-127 legacy plans as-is.
        if plan is not None and plan.cap > cap and resolve_pack(pack, cap):
            say(
                f"cached plan {load_path} has count cap {plan.cap}, "
                f"requested <= {cap} (nibble packing needs <= 15) "
                "— replanning"
            )
            plan = None
        if plan is not None:
            return plan
    plan = plan_hybrid(graph, levels=levels, budget_bytes=budget_bytes, cap=cap)
    try:
        save_plan(path, plan)
    except OSError as e:
        say(f"could not cache plan at {path}: {e}")
    return plan


def _permute(v, idx):
    """External <-> internal (degree-sorted) vertex order."""
    with prof.region("lux.tiled.permute"):
        return v[idx]


def require_spmv_program(program: PullProgram, cls: str, fallback: str):
    """Tiled executors only run sum-combiner programs whose edge
    contribution is the source value (SpMV shape)."""
    if program.combiner != "sum" or not getattr(
        program, "identity_contrib", False
    ):
        raise ValueError(
            f"{cls} requires a sum-combiner program whose "
            f"edge contribution is the source value; {program.name} "
            f"is not (use {fallback})"
        )


class TiledPullExecutor:
    """Executes an identity-contribution sum-combiner pull program via the
    strip/lane-select hybrid SpMV on a single device."""

    def __init__(
        self,
        graph: Graph,
        program: PullProgram,
        levels: Sequence[Tuple[int, int]] = ((8, 2),),
        budget_bytes: int = 8 << 30,
        chunk_strips: int = DEFAULT_CHUNK_STRIPS,
        chunk_tail: int = DEFAULT_CHUNK_TAIL,
        plan: Optional[HybridPlan] = None,
        device=None,
        pack: Optional[bool] = None,
    ):
        require_spmv_program(program, "TiledPullExecutor", "PullExecutor")
        self.graph = graph
        self.program = program
        self.device = device
        if plan is None:
            with spans.span("build.plan"):
                plan = plan_hybrid(
                    graph, levels=levels, budget_bytes=budget_bytes
                )
        self.plan = p = plan
        put = lambda x: jax.device_put(jnp.asarray(x), device)
        with spans.span("build.upload"):
            self.dhybrid = DeviceHybrid.build(
                p, chunk_strips=chunk_strips, chunk_tail=chunk_tail,
                device=device, pack=pack,
            )
            self.gtail = None
            self.gtail_stats = None
            if grouped_tail_enabled():
                from lux_tpu.obs.metrics import counter, gauge
                from lux_tpu.ops.merge_tail_plan import plan_grouped_tail

                gplan = plan_grouped_tail(
                    p.tail_sb, p.tail_lane, p.tail_row_ptr)
                self.gtail = DeviceGroupedTail.build(gplan, device=device)
                self.gtail_stats = gplan.stats
                gauge("lux_grouped_tail_inflation").set(
                    gplan.stats["mean_inflation"])
                counter("lux_grouped_tail_copy_rows").inc(
                    gplan.stats["copy_rows"])
                counter("lux_grouped_tail_merge_rows").inc(
                    gplan.stats["merge_rows"])
            self.out_degrees = put(p.out_degrees.astype(np.int32))
            self.in_degrees = put(p.in_degrees.astype(np.int32))
            self.order = put(p.order)   # external id at internal position
            self.rank = put(p.rank)     # internal position of external id
        # Device data goes through jit ARGUMENTS, never closures: a
        # closed-over array is a baked-in constant, re-uploaded with every
        # compile request (multi-GB of strips would break remote compile).
        self._step_args = (
            self.dhybrid,
            self.out_degrees,
            self.in_degrees,
            self.gtail,
        )
        self._jstep = jax.jit(self._step_impl, donate_argnums=0)
        self._step = lambda vals: self._jstep(vals, *self._step_args)
        self._jrun = make_fused_runner(self._step_impl)
        self._to_internal = jax.jit(_permute)
        self._to_external = jax.jit(_permute)

    # -- the jitted iteration (internal vertex order) --------------------

    def _apply_acc(self, vals, acc, out_degrees, in_degrees):
        ctx = VertexCtx(
            nv=self.graph.nv,
            out_degrees=out_degrees,
            in_degrees=in_degrees,
        )
        return self.program.apply(vals, acc, ctx)

    def _step_impl(
        self, vals, dhybrid, out_degrees, in_degrees, gtail=None
    ) -> jnp.ndarray:
        acc = hybrid_spmv(vals, dhybrid, gtail)
        with prof.region("lux.tiled.apply"):
            return self._apply_acc(vals, acc, out_degrees, in_degrees)

    # -- driver ----------------------------------------------------------
    # Every public entry point speaks EXTERNAL vertex ids, exactly like
    # PullExecutor (cli.py drives executors through init_values/step);
    # only the private _step/_init_internal work in degree-sorted order.

    def _init_internal(self) -> jnp.ndarray:
        with spans.span("engine.init"):
            ext = np.asarray(self.program.init_values(self.graph))
            return jax.device_put(
                jnp.asarray(ext[self.plan.order]), self.device)

    def init_values(self) -> jnp.ndarray:
        with spans.span("engine.init"):
            return jax.device_put(
                jnp.asarray(self.program.init_values(self.graph)),
                self.device,
            )

    def step(self, vals: jnp.ndarray) -> jnp.ndarray:
        """One iteration, external order in and out (boundary converts cost
        two nv-row gathers — use run() for timed multi-iteration loops,
        which converts once per call, not per step)."""
        internal = self._to_internal(jnp.asarray(vals), self.order)
        return self._to_external(self._step(internal), self.rank)

    def phase_step(self, vals: jnp.ndarray):
        """One iteration dispatched as separately-timed phases for
        ``-verbose`` attribution (the analogue of the reference's
        per-iteration loadTime/compTime/updateTime breakdown,
        sssp/sssp_gpu.cu:516-518 — phase names follow this engine's
        actual pipeline instead of the CUDA one). Returns
        (new external vals, {phase: seconds}). Phase dispatch breaks
        XLA's cross-phase fusion, so the sum runs slower than step().

        With the grouped tail active the tail phase is dispatched one
        network level at a time; the per-level seconds land in
        ``times["tail_level<k>"]`` and in the
        ``lux_grouped_tail_level_seconds`` histograms (level 0 is the
        x2d gather level), with ``times["tail"]`` still the total."""
        from lux_tpu.ops.tiled_spmv import strips_sum, tail_sum, vals_to_x2d

        if not hasattr(self, "_jphase"):
            nv = self.graph.nv

            # The same strips/tail/apply building blocks the fused step
            # composes (hybrid_spmv) — phase timing cannot drift from it.
            def strips_fn(v, dh):
                return strips_sum(vals_to_x2d(v, dh), dh, nv)

            def tail_fn(v, dh):
                return tail_sum(vals_to_x2d(v, dh), dh)

            def apply_fn(v, acc_s, acc_t, od, idg):
                return self._apply_acc(v, acc_s + acc_t, od, idg)

            self._jphase = (
                jax.jit(strips_fn), jax.jit(tail_fn), jax.jit(apply_fn),
            )

        strips_fn, tail_fn, apply_fn = self._jphase
        times = {}
        internal = hard_sync(self._to_internal(jnp.asarray(vals), self.order))
        with Timer() as t:
            acc_s = hard_sync(strips_fn(internal, self.dhybrid))
        times["strips"] = t.elapsed
        if self.gtail is not None:
            acc_t = self._grouped_tail_phases(internal, times)
        else:
            with Timer() as t:
                acc_t = hard_sync(tail_fn(internal, self.dhybrid))
            times["tail"] = t.elapsed
        with Timer() as t:
            new = hard_sync(apply_fn(
                internal, acc_s, acc_t, self.out_degrees, self.in_degrees
            ))
        times["apply"] = t.elapsed
        return self._to_external(new, self.rank), times

    def _grouped_tail_phases(self, internal, times):
        """Tail accumulator via the merge network, one hard-synced and
        timed dispatch per level (plus the final masked per-dst
        reduction). Composes the exact building blocks grouped
        hybrid_spmv fuses, so attribution cannot drift from the real
        step."""
        from lux_tpu.obs.metrics import histogram
        from lux_tpu.ops.merge_tail_kernel import level_apply, root_reduce
        from lux_tpu.ops.tiled_spmv import vals_to_x2d

        if not hasattr(self, "_jgphase"):
            self._jgphase = (
                jax.jit(vals_to_x2d), jax.jit(level_apply),
                jax.jit(root_reduce),
            )
        x2d_fn, level_fn, finish_fn = self._jgphase
        gt = self.gtail
        total = 0.0
        with Timer() as t:
            x = hard_sync(x2d_fn(internal, self.dhybrid))
        total += t.elapsed
        for k in range(gt.n_levels + 1):
            with Timer() as t:
                x = hard_sync(level_fn(
                    x, gt.arow[k], gt.brow[k], gt.codes[k]))
            times[f"tail_level{k}"] = t.elapsed
            histogram("lux_grouped_tail_level_seconds",
                      {"level": str(k)}).observe(t.elapsed)
            total += t.elapsed
        with Timer() as t:
            acc_t = hard_sync(finish_fn(
                x, gt.nvalid_root, gt.dst_row_ptr))
        total += t.elapsed
        times["tail"] = total
        return acc_t

    def warmup(self):
        """Compile the step and both permutation converters (run(1) with
        explicit vals exercises every jitted path run() can take)."""
        with spans.span("engine.warmup"), compile_phase("warmup"), \
                Timer() as t:
            # NULL_RECORDER: the throwaway iteration must not write a
            # telemetry report of its own.
            hard_sync(self.run(1, vals=self.init_values(),
                               recorder=NULL_RECORDER))
        note_compile_seconds(self, t.elapsed)

    def trace_step(self, **init_kw):
        """luxlint-IR hook (analysis/ir.py): the jitted step with its
        real argument tuple (device data travels as jit ARGS here, see
        _step_args above — the audit must see that same signature)."""
        return {
            "kind": "tiled",
            "fn": self._jstep,
            "args": (self._init_internal(), *self._step_args),
            "donate": (0,),
            "carry": (0,),
            "sharded": False,
        }

    def run(
        self,
        num_iters: int,
        vals: Optional[jnp.ndarray] = None,
        flush_every: int = 8,
        recorder=None,
    ):
        with spans.span("engine.run"):
            if vals is None:
                internal = self._init_internal()
            else:
                internal = self._to_internal(jnp.asarray(vals), self.order)
            rec = recorder if recorder is not None else recorder_for(
                "tiled", self.graph, self.program)
            rec.start()
            if rec.enabled:
                rec.record_compile(consume_compile_seconds(self))
                from lux_tpu.obs import engobs
                rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                    self.graph.nv, self.graph.ne))
            internal = run_maybe_fused(
                self._jrun, self._step, internal, num_iters, flush_every,
                *self._step_args, recorder=rec,
            )
            out = hard_sync(self._to_external(internal, self.rank))
            rec.finish()
            count_iterations("tiled", num_iters)
            return out
