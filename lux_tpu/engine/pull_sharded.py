"""Sharded pull executor: SPMD over a device mesh via ``jax.shard_map``.

The communication pattern mirrors the reference's pull iteration
(SURVEY.md §3.1) the TPU-native way:

- reference: every GPU reads the *whole* old-value region through zero-copy
  memory and gathers only its in-neighbor values FB-side
  (pull_model.inl:454-461, pagerank_gpu.cu:34-47). Here: an ICI
  ``all_gather`` of the per-part value shards inside ``shard_map``, then a
  local gather by precomputed flat indices. XLA schedules the all-gather
  to overlap with compute where possible.
- reference: per-part new values published back to ZC (cudaMemcpy D2H,
  pagerank_gpu.cu:148-150). Here: nothing — each shard's new values stay
  resident; next iteration's all-gather *is* the exchange.
- the Legion iteration-to-iteration region dependency that acts as the
  barrier (SURVEY.md §3.1 footnote) becomes XLA's dataflow dependency
  between consecutive jitted steps.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from lux_tpu.engine.program import EdgeCtx, PullProgram, VertexCtx
from lux_tpu.engine.pull import hard_sync, make_fused_runner, run_maybe_fused
from lux_tpu.graph.graph import Graph
from lux_tpu.obs import (
    consume_compile_seconds,
    engobs,
    note_compile_seconds,
    prof,
    recorder_for,
)
from lux_tpu.utils.timing import Timer
from lux_tpu.ops.segment import segment_reduce, segment_sum_by_rowptr
from lux_tpu.parallel.mesh import PARTS_AXIS, make_mesh, parts_sharding
from lux_tpu.parallel.shard import ShardedGraph, resolve_exchange
from lux_tpu.utils.logging import get_logger


class ShardedPullExecutor:
    """Runs a :class:`PullProgram` over an N-device 1-D mesh."""

    def __init__(
        self,
        graph: Graph,
        program: PullProgram,
        mesh: Optional[Mesh] = None,
        num_parts: Optional[int] = None,
        sum_strategy: str = "rowptr",
        sg: Optional[ShardedGraph] = None,
    ):
        if program.needs_weights and graph.weights is None:
            raise ValueError(f"{program.name} requires an edge-weighted graph")
        self.mesh = mesh if mesh is not None else make_mesh(num_parts)
        self.num_parts = self.mesh.devices.size
        self.graph = graph
        self.program = program
        self.sum_strategy = sum_strategy
        if sg is not None and sg.num_parts != self.num_parts:
            raise ValueError(
                f"prebuilt ShardedGraph has {sg.num_parts} parts, mesh has "
                f"{self.num_parts}"
            )
        if sg is not None and sg.graph is not graph:
            raise ValueError(
                "prebuilt ShardedGraph was built from a different Graph "
                "object — edge indices and partition bounds would not "
                "match this executor's graph"
            )
        self.sg = sg if sg is not None else ShardedGraph.build(
            graph, self.num_parts
        )

        # Lane padding for K-vector values: gathering (ne, K)-narrow rows
        # scalarizes on TPU (measured 76.5 s/iter on NetFlix-shaped CF in
        # the single-device engine before the same fix). Values are
        # STORED lane-padded per shard so the src/dst row gathers stream
        # full 512 B rows; the all-gather sends the UNPADDED slice (the
        # pad is re-applied locally), so ICI bytes do not inflate.
        from lux_tpu.engine.pull import lane_pad_width

        self._kreal, self._kpad = lane_pad_width(
            getattr(program, "value_shape", ())
        )

        # Exchange mode is captured here, once: the jitted step traces a
        # single program, and the serving pool keys engines by the mode
        # (flags re-read env per call, so a later flip builds NEW
        # engines rather than mutating this one).
        self.exchange_mode, self._xplan = resolve_exchange(
            self.sg, get_logger("engine"))

        sh = parts_sharding(self.mesh)
        put = lambda x: jax.device_put(jnp.asarray(x), sh)
        sgd = {
            "src_pidx": put(self.sg.src_pidx),
            "dst_local": put(self.sg.dst_local),
            "local_row_ptr": put(self.sg.local_row_ptr),
            "out_degrees": put(self.sg.out_degrees),
            "in_degrees": put(self.sg.in_degrees),
            "vertex_mask": put(self.sg.vertex_mask),
        }
        if self.sg.weights is not None:
            sgd["weights"] = put(self.sg.weights)
        if self._xplan is not None:
            sgd["xch_send"] = put(self._xplan.send_units)
            sgd["xch_recv"] = put(self._xplan.recv_pos)
        self._device_graph = sgd

        specs = {k: P(PARTS_AXIS) for k in sgd}
        mapped = jax.shard_map(
            self._shard_step,
            mesh=self.mesh,
            in_specs=(P(PARTS_AXIS), specs),
            out_specs=P(PARTS_AXIS),
        )
        self._step = jax.jit(mapped, donate_argnums=0)
        self._jrun = make_fused_runner(mapped)

    # -- per-shard body (runs under shard_map; block shapes (1, ...)) ----

    def _exchange_block(self, vals_blk, dg):
        """Value exchange: all-gather the shards into the flat global
        table every shard gathers from (the reference's whole-region
        zero-copy read, pull_model.inl:454-461) — or, under
        ``LUX_EXCHANGE=compact``, a fixed-capacity ``all_to_all`` of the
        packed needed rows scattered into the same flat view (rows no
        remote edge reads stay zero; the comp block routes local edges
        to the shard's own values, so only genuinely remote reads touch
        this table)."""
        v = vals_blk[0]                  # (max_nv, *t); lane-padded if _kpad
        kp, kr = self._kpad, self._kreal
        if kp:
            # Exchange the real lanes only; re-pad locally for fast
            # 512 B-row gathers from the flat table.
            flat = self._flat_table(v[:, :kr], dg)
            flat = jnp.pad(flat, ((0, 0), (0, kp - kr)))
        else:
            flat = self._flat_table(v, dg)
        return flat

    def _flat_table(self, vv, dg):
        """(P*max_nv, *t) flat value table from this shard's (max_nv, *t)
        slice: whole-shard all_gather (full) or packed needed-rows
        all_to_all + receiver scatter (compact)."""
        if self._xplan is None:
            gathered = jax.lax.all_gather(vv, PARTS_AXIS)
            return gathered.reshape((-1,) + vv.shape[1:])
        max_nv = self.sg.max_nv
        packed = vv[jnp.minimum(dg["xch_send"][0], max_nv - 1)]
        got = jax.lax.all_to_all(
            packed, PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True
        )
        # Scatter into a (P*max_nv + 1)-row buffer: pad entries of the
        # scatter map land on the final trash row, sliced off here.
        buf = jnp.zeros(
            (self.num_parts * max_nv + 1,) + vv.shape[1:], vv.dtype
        )
        return buf.at[dg["xch_recv"][0]].set(got)[:-1]

    def _comp_block(self, vals_blk, flat, dg):
        """Edge gather + contribution + per-destination reduction."""
        prog = self.program
        max_nv = self.sg.max_nv
        v = vals_blk[0]
        # Padded width is kept through edge_contrib and the reduction:
        # slicing here would either re-narrow the gather (XLA folds the
        # slice in, reviving the scalarized path) or materialize both
        # widths; pad lanes are zero, so contraction-style programs (CF's
        # dot/err*src) are unaffected, and narrow (ne, K) arrays pad to
        # the 128-lane tile physically anyway.
        sidx = dg["src_pidx"][0]
        dst_ids = jnp.minimum(dg["dst_local"][0], max_nv - 1)
        dst_vals = v[dst_ids]
        w = dg["weights"][0] if "weights" in dg else None

        def contrib_from(src_vals):
            return prog.edge_contrib(EdgeCtx(
                src_vals=src_vals, dst_vals=dst_vals, weights=w,
            ))

        if self._xplan is None:
            contrib = contrib_from(flat[sidx])
        else:
            # Local-first overlap: the local-edge contribution reads only
            # this shard's values — no data dependence on the collective —
            # so XLA can compute it while the packed exchange is in
            # flight; the per-edge select (before the SINGLE unchanged
            # reduction) folds the remote contribution in without
            # reordering the combine, keeping results bitwise equal to
            # the full path for every combiner, float sum included.
            own = jax.lax.axis_index(PARTS_AXIS)
            base = own * max_nv
            local = (sidx >= base) & (sidx < base + max_nv)
            c_local = contrib_from(v[jnp.clip(sidx - base, 0, max_nv - 1)])
            c_remote = contrib_from(flat[sidx])
            mask = local.reshape(local.shape + (1,) * (c_local.ndim - 1))
            contrib = jnp.where(mask, c_local, c_remote)
        if prog.combiner == "sum" and self.sum_strategy == "rowptr":
            acc = segment_sum_by_rowptr(contrib, dg["local_row_ptr"][0])
        else:
            # Pad edges carry dst_local == max_nv: an extra trash segment
            # sliced off below, so no combiner-identity masking is needed.
            acc = segment_reduce(
                contrib,
                dg["dst_local"][0],
                num_segments=max_nv + 1,
                kind=prog.combiner,
            )[:max_nv]
        return acc

    def _update_block(self, vals_blk, acc, dg):
        """Vertex apply + pad-lane/pad-vertex re-masking."""
        prog = self.program
        max_nv = self.sg.max_nv
        v = vals_blk[0]
        kp, kr = self._kpad, self._kreal
        ctx = VertexCtx(
            nv=self.graph.nv,
            out_degrees=dg["out_degrees"][0],
            in_degrees=dg["in_degrees"][0],
        )
        new = prog.apply(v, acc, ctx)
        if kp:
            # Re-zero pad lanes: apply may write constants into them,
            # which would pollute the next iteration's contractions.
            lanes = jnp.arange(kp, dtype=jnp.int32)
            new = jnp.where(lanes[None, :] < kr, new, 0)
        vmask = dg["vertex_mask"][0].reshape(
            (max_nv,) + (1,) * (new.ndim - 1)
        )
        new = jnp.where(vmask, new, v)  # freeze pad vertices
        return new[None]

    def _shard_step(self, vals_blk, dg):
        # prof regions tag the lowered ops per phase (static names, so
        # executable cache keys — and hence recompiles — are unchanged);
        # the scopes do not fence XLA's schedule, so the compact path's
        # exchange/local-compute overlap still happens and shows up as
        # intersecting intervals in a device profile.
        with prof.region("lux.pull_sharded.exchange"):
            flat = self._exchange_block(vals_blk, dg)
        with prof.region("lux.pull_sharded.compute"):
            acc = self._comp_block(vals_blk, flat, dg)
            return self._update_block(vals_blk, acc, dg)

    # -- driver ----------------------------------------------------------

    def init_values(self):
        return self.host_to_device(self.program.init_values(self.graph))

    def host_to_device(self, host_vals: np.ndarray):
        """Global (nv, *t) host array → this executor's device layout
        (padded shard stack, lane-padded for K-vector programs)."""
        padded = self.sg.to_padded(np.asarray(host_vals))
        if self._kpad:
            padded = np.pad(
                padded, ((0, 0), (0, 0), (0, self._kpad - self._kreal))
            )
        return jax.device_put(jnp.asarray(padded), parts_sharding(self.mesh))

    def step(self, vals):
        return self._step(vals, self._device_graph)

    def phase_step(self, vals):
        """One iteration as separately-dispatched exchange/comp/update
        phases for `-verbose` attribution (the pull-side analogue of the
        reference's per-iteration breakdown, sssp/sssp_gpu.cu:516-518 —
        phase names follow this engine's pipeline). SPMD phases are
        mesh-lockstep, so the walls are mesh-wide. Returns (new vals,
        {phase: seconds}). Phase dispatch breaks fusion; use run() for
        timed loops."""
        if not hasattr(self, "_pjits"):
            specs = {k: P(PARTS_AXIS) for k in self._device_graph}
            compact = self._xplan is not None
            # Full mode: the all-gathered flat table is replicated, so
            # the exchange phase hands one copy across. Compact mode:
            # every shard scatters its OWN flat view (rows differ per
            # receiver), so the table stays per-shard.
            flat_spec = P(PARTS_AXIS) if compact else P()

            def sm(fn, in_specs, out_specs):
                # check_vma off: the all-gathered flat table is
                # replicated by construction, but the static checker
                # cannot infer it here.
                return jax.jit(jax.shard_map(
                    fn, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False,
                ))

            self._pjits = {
                "exchange": sm(
                    lambda v, dg: (
                        self._exchange_block(v, dg)[None] if compact
                        else self._exchange_block(v, dg)
                    ),
                    (P(PARTS_AXIS), specs), flat_spec,
                ),
                "comp": sm(
                    lambda v, flat, dg: self._comp_block(
                        v, flat[0] if compact else flat, dg
                    )[None],
                    (P(PARTS_AXIS), flat_spec, specs), P(PARTS_AXIS),
                ),
                "update": sm(
                    lambda v, acc, dg: self._update_block(v, acc[0], dg),
                    (P(PARTS_AXIS), P(PARTS_AXIS), specs), P(PARTS_AXIS),
                ),
            }
        j, dg, times = self._pjits, self._device_graph, {}
        with Timer() as t:
            flat = hard_sync(j["exchange"](vals, dg))
        times["exchange"] = t.elapsed
        with Timer() as t:
            acc = hard_sync(j["comp"](vals, flat, dg))
        times["comp"] = t.elapsed
        with Timer() as t:
            new = hard_sync(j["update"](vals, acc, dg))
        times["update"] = t.elapsed
        return new, times

    def warmup(self):
        with Timer() as t:
            hard_sync(self.step(self.init_values()))
        note_compile_seconds(self, t.elapsed)

    def trace_step(self, **init_kw):
        """luxlint-IR hook (analysis/ir.py): the jitted shard_map step;
        sharded=True, so LUX105 demands the exchange all-gather shows
        up in the trace. The exchange_* keys feed the LUX404-406
        collective-dataflow rules (``luxlint --exchange``)."""
        return {
            "kind": "pull_sharded",
            "fn": self._step,
            "args": (self.init_values(), self._device_graph),
            "donate": (0,),
            "carry": (0,),
            "sharded": True,
            "exchange_mode": self.exchange_mode,
            "exchange_bytes": self.exchange_bytes_per_iter(),
            "combiner": getattr(self.program, "combiner", ""),
            "value_dtype": np.dtype(
                getattr(self.program, "value_dtype", np.float32)).name,
            "num_parts": self.num_parts,
            "plan": self._xplan,
        }

    def _row_bytes(self) -> int:
        try:
            itemsize = np.dtype(self.program.value_dtype).itemsize
        except (AttributeError, TypeError):
            itemsize = 4
        return max(self._kreal, 1) * itemsize

    def _exchange_bytes_per_iter(self) -> int:
        """ICI bytes moved by one iteration's exchange. Full: each of
        the P shards sends its (max_nv, kreal-or-scalar) slice to the
        P-1 others (``_exchange_block`` gathers only real lanes when
        lane-padded). Compact: the packed-capacity figure — what the
        fixed-capacity all_to_all actually moves."""
        row = self._row_bytes()
        if self._xplan is not None:
            return self._xplan.exchange_bytes_per_iter(row)
        p = self.num_parts
        return p * (p - 1) * self.sg.max_nv * row

    def exchange_bytes_per_iter(self) -> int:
        """Public form of the per-iteration exchange estimate (the
        serving layer reports it in serve_bench.v1 mesh evidence)."""
        return self._exchange_bytes_per_iter()

    def run(self, num_iters: int, vals=None, flush_every: int = 8,
            recorder=None):
        if vals is None:
            vals = self.init_values()
        rec = recorder if recorder is not None else recorder_for(
            "pull_sharded", self.graph, self.program)
        rec.start()
        if rec.enabled:
            rec.record_compile(consume_compile_seconds(self))
            compact = self._xplan is not None
            rec.set_exchange_bytes(
                self._exchange_bytes_per_iter(),
                note="compact_all_to_all" if compact else "all_gather",
                parts=self.num_parts)
            if compact:
                rec.set_overlap(True)
            self._note_ledger(rec)
        if engobs.enabled():
            # Phase-fenced measurement run: exchange/compute split per
            # iteration. Off (the default) never reaches here, so the
            # fused program below stays the exact pre-observatory one.
            out = engobs.run_pull_phased(self, vals, num_iters, rec)
        else:
            out = run_maybe_fused(
                self._jrun, self.step, vals, num_iters, flush_every,
                self._device_graph, recorder=rec,
            )
        rec.finish()
        return out

    def _note_ledger(self, rec):
        """Exchange-ledger and roofline inputs: useful-bytes from the
        plan's remote-read index, HBM traffic from the byte model."""
        try:
            itemsize = np.dtype(self.program.value_dtype).itemsize
        except (AttributeError, TypeError):
            itemsize = 4
        width = max(self._kreal, 1)
        xrows = (self._xplan.exchanged_units_per_iter
                 if self._xplan is not None else None)
        useful = engobs.useful_exchange(self.sg, width * itemsize,
                                        exchanged_rows=xrows)
        if useful is not None:
            rec.set_useful_bytes(useful["useful_bytes_per_iter"],
                                 useful["ratio"])
        rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
            self.graph.nv, self.graph.ne, itemsize, width))

    def gather_values(self, vals) -> np.ndarray:
        """Padded device layout → global (nv, *t) host array."""
        host = np.asarray(jax.device_get(vals))
        if self._kpad:
            host = host[:, :, : self._kreal]
        return self.sg.from_padded(host)
