"""Sharded GAS: direction-adaptive gather-apply-scatter over the mesh.

This closes the engine split (ROADMAP item 1): the single-device
:class:`~lux_tpu.engine.gas.AdaptiveExecutor` picks push vs pull per
iteration from frontier density, but every sharded executor before this
module ran one fixed direction. :class:`ShardedAdaptiveExecutor` runs
any ``GasProgram`` over the ``parts`` mesh axis with the same per-
iteration choice — hysteresis on a device-resident scalar, one
``lax.cond``, zero recompiles on switches — which is the paper's core
loop (direction-optimal traversal over an edge-balanced partition, cf.
Gunrock, PAPERS.md arXiv:1501.05387) at P > 1.

Why the same compact exchange serves both directions: either branch
materializes the identical dense per-shard accumulator (min/max and
integer sums are exactly associative/commutative), so the *exchange
surface* is direction-independent — pull moves the (values, frontier)
rows the local CSC shard reads (the static :class:`ExchangePlan`),
push moves the bounded global frontier queue. Both ride fixed-shape
collectives, so a mid-run switch never changes a traced shape.

``LUX_EXCHANGE=frontier`` is the dynamic refinement of the compact
plan: per iteration, send only the plan rows whose *source vertex is
active*, compacted into a static per-(sender, receiver) budget
(``ExchangePlan.frontier_capacity``) and sentinel-padded so shapes
never change. Rows dropped because their source is inactive would have
contributed the combiner identity anyway (the same annihilation
argument the static compact plan makes for never-read rows — the
LUX407 contract), so results stay bitwise equal. When any pair's
active rows exceed the budget the iteration *self-downgrades* to the
static compact send inside the same ``lax.cond`` — honest, logged via
the downgrade counter, and still recompile-free.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from lux_tpu.engine.gas import GasState, as_gas, _resolve_mode
from lux_tpu.engine.program import EdgeCtx, VertexCtx
from lux_tpu.engine.pull import hard_sync
from lux_tpu.engine.push import (
    _chunk_while,
    _queue_edge_slots,
    _sparse_budgets,
    _validated_sg,
)
from lux_tpu.graph.graph import Graph
from lux_tpu.obs import (
    NULL_RECORDER,
    consume_compile_seconds,
    engobs,
    note_compile_seconds,
    prof,
    recorder_for,
)
from lux_tpu.ops.segment import identity_for, segment_reduce
from lux_tpu.parallel.mesh import PARTS_AXIS, make_mesh, parts_sharding
from lux_tpu.parallel.shard import ShardedGraph, resolve_exchange
from lux_tpu.utils import flags
from lux_tpu.utils.logging import get_logger
from lux_tpu.utils.timing import Timer

import math


def _value_lanes(program) -> int:
    """Trailing value lanes per vertex (1 for scalar programs; K for
    value_shape programs like colfilter, reachable through the
    PullGasAdapter's ``inner``)."""
    shape = getattr(program, "value_shape", None)
    if shape is None:
        shape = getattr(getattr(program, "inner", None), "value_shape", None)
    return int(np.prod(shape)) if shape else 1


class ShardedAdaptiveExecutor:
    """GAS executor over an N-device mesh with per-iteration direction
    choice — the sharded form of :class:`AdaptiveExecutor`:

    - **pull**: exchange the (values, frontier) rows each shard's local
      CSC in-edges read (full all-gather, static compact plan, or the
      frontier-aware dynamic plan), mask non-frontier messages to the
      combiner identity, one segment reduce per shard.
    - **push**: each shard compacts its local frontier into a bounded
      queue of (global id, value); the queues all-gather and every
      shard expands them against its global-source CSR into an
      identity-filled local accumulator — exchange and expansion scale
      with the frontier, not nv/ne.

    The decision inputs are replicated collectives (psum of frontier
    counts, pmax of local counts, psum of frontier out-edges) so every
    shard takes the same ``lax.cond`` side; hysteresis thresholds are
    fractions of the *global* nv, exactly as on one device. Both
    branches build the same dense per-shard accumulator, so results are
    bitwise equal across directions, modes, and part counts."""

    def __init__(
        self,
        graph: Graph,
        program,
        mesh: Optional[Mesh] = None,
        num_parts: Optional[int] = None,
        mode: Optional[str] = None,
        queue_frac: int = 16,
        edge_budget_frac: int = 8,
        sg: Optional[ShardedGraph] = None,
    ):
        program = as_gas(program)
        if program.needs_weights and graph.weights is None:
            raise ValueError(f"{program.name} requires an edge-weighted graph")
        self.mesh = mesh if mesh is not None else make_mesh(num_parts)
        self.num_parts = self.mesh.devices.size
        self.graph = graph
        self.program = program
        self.mode = "pull" if not program.frontier else _resolve_mode(mode)
        self.sg = _validated_sg(sg, graph, self.num_parts)
        sh = parts_sharding(self.mesh)
        put = lambda x: jax.device_put(jnp.asarray(x), sh)
        log = get_logger("engine")
        self.exchange_mode, self._xplan = resolve_exchange(
            self.sg, log, frontier_ok=program.frontier
        )
        if self.exchange_mode == "frontier":
            self.frontier_cap = self._xplan.frontier_capacity(
                frac=flags.get_float("LUX_EXCHANGE_FRONTIER_FRAC")
            )
        else:
            self.frontier_cap = 0

        nv = int(graph.nv)
        hi = flags.get_float("LUX_GAS_DENSITY_HI")
        lo = flags.get_float("LUX_GAS_DENSITY_LO")
        if not 0.0 < lo <= hi <= 1.0:
            raise ValueError(
                f"need 0 < LUX_GAS_DENSITY_LO <= LUX_GAS_DENSITY_HI <= 1 "
                f"(got lo={lo}, hi={hi})"
            )
        self.hi_count = max(1, math.ceil(hi * nv))
        self.lo_count = max(0, math.ceil(lo * nv))

        dg = {
            "vertex_mask": put(self.sg.vertex_mask),
            "src_pidx": put(self.sg.src_pidx),
            "dst_local": put(self.sg.dst_local),
        }
        if self.sg.weights is not None:
            dg["weights"] = put(self.sg.weights)
        if self._xplan is not None:
            dg["xch_send"] = put(self._xplan.send_units)
            dg["xch_recv"] = put(self._xplan.recv_pos)
        if not program.frontier:
            # The VertexCtx the pull model's apply consumes: each
            # owned vertex's GLOBAL degrees (vertices live in exactly
            # one shard, so per-shard rows are the global arrays
            # re-laid-out).
            dg["out_degrees"] = put(
                np.asarray(self.sg.out_degrees).astype(np.int32))
            dg["in_degrees"] = put(
                np.asarray(self.sg.in_degrees).astype(np.int32))
        elif self.mode != "pull":
            # Push direction: global-source CSR expansion arrays +
            # budgets sized so every frontier the policy can route here
            # fits. The queue is per shard, so its cap tops out at the
            # shard size even when hi_count (a global-nv fraction)
            # exceeds it.
            q_cap, e_budget = _sparse_budgets(
                self.sg.max_nv, self.sg.max_ne, queue_frac, edge_budget_frac
            )
            self.queue_cap = max(
                q_cap, min(self.hi_count, self.sg.max_nv) + 128
            )
            self.edge_budget = e_budget
            prp, pdst, pw = self.sg.build_push_csr()
            dg["push_row_ptr"] = put(prp)
            dg["push_dst_local"] = put(pdst)
            if pw is not None:
                dg["push_weights"] = put(pw)
            dg["out_degrees"] = put(
                np.asarray(self.sg.out_degrees).astype(np.int32))
            dg["row_left"] = put(self.sg.row_left.astype(np.int32)[:, None])
        self._dg = dg
        self._specs = {k: P(PARTS_AXIS) for k in dg}
        # Filled by run(): the per-run direction/exchange ledger.
        self.push_iters = 0
        self.pull_iters = 0
        self.direction_switches = 0
        self.exchange_downgrades = 0
        state_spec = GasState(P(PARTS_AXIS), P(PARTS_AXIS), P(PARTS_AXIS))
        self._state_spec = state_spec
        mapped = jax.shard_map(
            self._shard_step,
            mesh=self.mesh,
            in_specs=(state_spec, self._specs),
            out_specs=(state_spec, P(PARTS_AXIS)),
        )
        self._step = jax.jit(mapped, donate_argnums=0)
        self._chunk_cache = {}

    # -- pull-direction exchange -----------------------------------------

    def _compact_tables(self, v, f, dg):
        """Static compact exchange: fixed-capacity all_to_all of the
        rows each receiver's real edges read (values + frontier bits),
        scattered into the flat (P*max_nv,) view. Own-span rows stay
        zero — _pull_comp serves local edges from the shard itself (the
        local-first overlap branch) and unread remote rows carry
        frontier False, so their candidates collapse to the identity."""
        max_nv = self.sg.max_nv
        sel = jnp.minimum(dg["xch_send"][0], max_nv - 1)
        pv = jax.lax.all_to_all(
            v[sel], PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True)
        pf = jax.lax.all_to_all(
            f[sel], PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True)
        recv = dg["xch_recv"][0]
        flat = self.num_parts * max_nv
        all_v = jnp.zeros((flat + 1,), v.dtype).at[recv].set(pv)[:-1]
        all_f = jnp.zeros((flat + 1,), f.dtype).at[recv].set(pf)[:-1]
        return all_v, all_f

    def _frontier_active(self, f, dg):
        """(P, capacity) activity mask over this shard's static send
        table: which planned rows have an active source this iteration.
        Sentinel (pad/diagonal) entries are never active."""
        cap = self._xplan.capacity
        max_nv = self.sg.max_nv
        send = dg["xch_send"][0].reshape(self.num_parts, cap)
        act = (send < max_nv) & f[jnp.minimum(send, max_nv - 1)]
        return send, act

    def _frontier_admissible(self, f, dg):
        """Replicated bool: every (sender, receiver) pair's active rows
        fit the static frontier budget — the self-downgrade guard. pmin
        makes it mesh-agreed, so all shards take the same cond side."""
        _, act = self._frontier_active(f, dg)
        ok_loc = (
            act.sum(axis=1, dtype=jnp.int32) <= jnp.int32(self.frontier_cap)
        ).all()
        return jax.lax.pmin(ok_loc.astype(jnp.int32), PARTS_AXIS) > 0

    def _frontier_tables(self, v, f, dg):
        """Frontier-aware compact exchange: per receiver, cumsum-compact
        the active subset of the static send rows into ``frontier_cap``
        sentinel-padded slots, all_to_all the (row id, value) pairs, and
        scatter them into the flat view by ``sender*max_nv + row``.
        Rows not sent keep (0, False) — their sources are inactive, so
        the compute mask collapses their candidates to the combiner
        identity (bitwise identical to the static compact exchange; the
        LUX407 annihilator argument). Only traced under the
        admissibility cond, so no active row is ever truncated."""
        p, fcap = self.num_parts, self.frontier_cap
        max_nv = self.sg.max_nv
        send, act = self._frontier_active(f, dg)
        pos = jnp.cumsum(act.astype(jnp.int32), axis=1) - 1
        keep = act & (pos < fcap)
        tgt = jnp.where(keep, pos, fcap)            # fcap = trash column
        rows_p = jnp.full((p, fcap + 1), max_nv, jnp.int32)
        rows_p = rows_p.at[jnp.arange(p)[:, None], tgt].set(
            jnp.where(keep, send, max_nv)
        )[:, :fcap].reshape(-1)
        prow = jax.lax.all_to_all(
            rows_p, PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True)
        pval = jax.lax.all_to_all(
            v[jnp.clip(rows_p, 0, max_nv - 1)],
            PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True)
        sender = jnp.arange(p * fcap, dtype=jnp.int32) // jnp.int32(fcap)
        flat = p * max_nv
        fpos = jnp.where(prow < max_nv, sender * max_nv + prow, flat)
        all_v = jnp.zeros((flat + 1,), v.dtype).at[fpos].set(pval)[:-1]
        all_f = jnp.zeros((flat + 1,), f.dtype).at[fpos].set(True)[:-1]
        return all_v, all_f

    def _pull_load(self, state: GasState, dg):
        """Pull-direction exchange; returns (all_v, all_f, downgraded)
        where downgraded flags a frontier-mode iteration that fell back
        to the static compact send because the frontier was dense."""
        v = state.values[0]
        f = state.frontier[0]
        if self._xplan is None:
            all_v = jax.lax.all_gather(v, PARTS_AXIS).reshape(-1)
            all_f = jax.lax.all_gather(f, PARTS_AXIS).reshape(-1)
            return all_v, all_f, jnp.int32(0)
        if self.exchange_mode != "frontier":
            all_v, all_f = self._compact_tables(v, f, dg)
            return all_v, all_f, jnp.int32(0)
        ok = self._frontier_admissible(f, dg)
        all_v, all_f = jax.lax.cond(
            ok,
            lambda vf: self._frontier_tables(vf[0], vf[1], dg),
            lambda vf: self._compact_tables(vf[0], vf[1], dg),
            (v, f),
        )
        return all_v, all_f, (~ok).astype(jnp.int32)

    # -- pull-direction compute ------------------------------------------

    def _pull_comp(self, state: GasState, loaded, dg):
        """gather + identity mask + per-local-destination reduction —
        the single-device ``_pull_acc`` over this shard's CSC slice.
        Compact/frontier modes relax local-source edges against the
        shard's own values (no collective dependence — XLA overlaps it
        with the in-flight all_to_all) before the unchanged reduction,
        keeping the combine order bitwise identical."""
        prog = self.program
        max_nv = self.sg.max_nv
        all_v, all_f = loaded
        sidx = dg["src_pidx"][0]
        w = dg["weights"][0] if "weights" in dg else None
        if self._xplan is not None:
            v_loc = state.values[0]
            f_loc = state.frontier[0]
            own = jax.lax.axis_index(PARTS_AXIS)
            base = own * max_nv
            local = (sidx >= base) & (sidx < base + max_nv)
            lidx = jnp.clip(sidx - base, 0, max_nv - 1)
            # The local contribution traces COMPLETELY before anything
            # derived from the collective: jax caches the jnp.where
            # sub-jaxpr per operand signature and luxlint's dataflow
            # walk (LUX404) merges var memberships across call sites of
            # a shared jaxpr, so a program whose gather carries its own
            # same-signature where (labelprop) would smear the remote
            # side's taint onto the local mask if the remote gather
            # traced first.
            cand_l = prog.gather(v_loc[lidx], w)
            ident = identity_for(prog.combiner, cand_l.dtype)
            cand_l = jnp.where(f_loc[lidx], cand_l, ident)
            cand_r = prog.gather(all_v[sidx], w)
            cand_r = jnp.where(all_f[sidx], cand_r, ident)
            cand = jnp.where(local, cand_l, cand_r)
        else:
            cand = prog.gather(all_v[sidx], w)
            ident = identity_for(prog.combiner, cand.dtype)
            cand = jnp.where(all_f[sidx], cand, ident)
        # Pad edges carry dst_local == max_nv: the dropped trash
        # segment, so no edge mask is needed.
        return segment_reduce(
            cand, dg["dst_local"][0], num_segments=max_nv + 1,
            kind=prog.combiner,
        )[:max_nv]

    # -- push direction ----------------------------------------------------

    def _push_load(self, state: GasState, dg):
        """Local frontier -> bounded queue of (global id, value), then
        the queue all-gather — O(P*Q) bytes, not O(nv)."""
        nv, max_nv = self.graph.nv, self.sg.max_nv
        Q = self.queue_cap
        v = state.values[0]
        f = state.frontier[0]
        q_loc = jnp.nonzero(f, size=Q, fill_value=max_nv)[0].astype(jnp.int32)
        qv = v[jnp.clip(q_loc, 0, max_nv - 1)]
        base = dg["row_left"][0, 0]
        qg = jnp.where(q_loc >= max_nv, jnp.int32(nv), base + q_loc)
        all_q = jax.lax.all_gather(qg, PARTS_AXIS).reshape(-1)
        all_qv = jax.lax.all_gather(qv, PARTS_AXIS).reshape(-1)
        return all_q, all_qv

    def _push_comp(self, all_q, all_qv, dg):
        """Expand the global queue against this shard's local edges via
        the global-src CSR and scatter-combine into an identity-filled
        local accumulator — the single-device ``_push_acc`` per shard.
        (Sentinel id nv reads deg == 0: the row_ptr pad rows.)"""
        prog = self.program
        max_nv = self.sg.max_nv
        rp = dg["push_row_ptr"][0]
        start = rp[all_q]
        deg = rp[all_q + 1] - start
        slot, edge_pos, emask = _queue_edge_slots(
            start, deg, self.edge_budget, self.sg.max_ne
        )
        dstl = dg["push_dst_local"][0][edge_pos]
        w = (
            dg["push_weights"][0][edge_pos]
            if "push_weights" in dg else None
        )
        gather = getattr(prog, "gather_push", None) or prog.gather
        msg = gather(all_qv[slot], w)
        ident = identity_for(prog.combiner, msg.dtype)
        msg = jnp.where(emask, msg, ident)
        dstl = jnp.where(emask, dstl, max_nv)
        acc = jnp.full((max_nv + 1,), ident, dtype=msg.dtype)
        if prog.combiner == "min":
            acc = acc.at[dstl].min(msg)
        elif prog.combiner == "max":
            acc = acc.at[dstl].max(msg)
        else:
            acc = acc.at[dstl].add(msg)
        return acc[:max_nv]

    # -- decision + merge --------------------------------------------------

    def _decide_block(self, state: GasState, dg):
        """(local frontier count, take_push | None). Pinned pull skips
        the cond entirely (only its branch traces); otherwise the global
        hysteresis runs on psum'd counts with the single-device
        thresholds, and a push must fit the per-shard static budgets —
        all replicated collectives, so the mesh agrees."""
        f = state.frontier[0]
        cnt_loc = f.sum(dtype=jnp.int32)
        if self.mode == "pull":
            return cnt_loc, None
        cnt = jax.lax.psum(cnt_loc, PARTS_AXIS)
        if self.mode == "push":
            want = jnp.bool_(True)
        else:
            prev_push = state.direction[0] > 0
            want = jnp.where(
                cnt >= jnp.int32(self.hi_count), False,
                jnp.where(cnt <= jnp.int32(self.lo_count), True, prev_push),
            )
        oe_loc = jnp.where(
            f, dg["out_degrees"][0].astype(jnp.uint32), 0
        ).sum(dtype=jnp.uint32)
        cnt_max = jax.lax.pmax(cnt_loc, PARTS_AXIS)
        oe_tot = jax.lax.psum(oe_loc, PARTS_AXIS)
        fits = (cnt_max <= jnp.int32(self.queue_cap)) & (
            oe_tot <= jnp.uint32(self.edge_budget)
        )
        return cnt_loc, want & fits

    def _merge(self, state: GasState, acc, dirs1, dg):
        """apply + vertex-mask merge + scatter activation on this
        shard's rows; ``dirs1`` is the (1,) per-shard direction lane the
        new state carries (the hysteresis memory)."""
        prog = self.program
        v = state.values[0]
        new = prog.apply(v, acc)
        vmask = dg["vertex_mask"][0]
        new = jnp.where(vmask, new, v)
        frontier = prog.scatter(v, new) & vmask
        cnt = frontier.sum(dtype=jnp.int32)
        return GasState(new[None], frontier[None], dirs1), cnt

    # -- per-iteration blocks ---------------------------------------------

    def _frontier_iter_block(self, state: GasState, dg):
        """One adaptive iteration on this shard's blocks; returns
        (state', local count, flag) where flag packs the direction taken
        (bit 0) and a frontier-exchange downgrade (bit 1)."""
        take_push = self._decide_block(state, dg)[1]
        if take_push is None:
            with prof.region("lux.gas_sharded.exchange"):
                all_v, all_f, down = self._pull_load(state, dg)
            with prof.region("lux.gas_sharded.compute"):
                acc = self._pull_comp(state, (all_v, all_f), dg)
            direction = jnp.int32(0)
        else:
            def push_branch(st):
                with prof.region("lux.gas_sharded.exchange"):
                    all_q, all_qv = self._push_load(st, dg)
                with prof.region("lux.gas_sharded.compute"):
                    return self._push_comp(all_q, all_qv, dg), jnp.int32(0)

            def pull_branch(st):
                with prof.region("lux.gas_sharded.exchange"):
                    all_v, all_f, down = self._pull_load(st, dg)
                with prof.region("lux.gas_sharded.compute"):
                    return self._pull_comp(st, (all_v, all_f), dg), down

            acc, down = jax.lax.cond(
                take_push, push_branch, pull_branch, state
            )
            direction = take_push.astype(jnp.int32)
        new_state, ncnt = self._merge(state, acc, direction[None], dg)
        return new_state, ncnt, direction + 2 * down

    def _values_load(self, state: GasState, dg):
        """Frontier-less exchange: values only (the all-ones frontier
        never changes and is never read)."""
        v = state.values[0]
        max_nv = self.sg.max_nv
        if self._xplan is None:
            return jax.lax.all_gather(v, PARTS_AXIS).reshape(
                (-1,) + v.shape[1:])
        sel = jnp.minimum(dg["xch_send"][0], max_nv - 1)
        pv = jax.lax.all_to_all(
            v[sel], PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True)
        recv = dg["xch_recv"][0]
        flat = self.num_parts * max_nv
        return jnp.zeros(
            (flat + 1,) + v.shape[1:], v.dtype
        ).at[recv].set(pv)[:-1]

    def _dense_pull_step(self, state: GasState, all_v, dg):
        """Frontier-less (PullProgram-adapted) compute: edge_contrib
        over the local CSC slice with the VertexCtx apply, vertex-mask
        merged. Frontier and direction pass through unchanged (not
        fresh constants) so the donated buffers alias outputs
        (LUX104). The count is this shard's owned-vertex total, so the
        psum'd halt count stays nv — run() bounds it with max_iters."""
        prog = self.program
        max_nv = self.sg.max_nv
        v = state.values[0]
        sidx = dg["src_pidx"][0]
        dstl = dg["dst_local"][0]
        w = dg["weights"][0] if "weights" in dg else None
        if self._xplan is not None:
            own = jax.lax.axis_index(PARTS_AXIS)
            base = own * max_nv
            local = (sidx >= base) & (sidx < base + max_nv)
            lidx = jnp.clip(sidx - base, 0, max_nv - 1)
            sel = local if v.ndim == 1 else local[:, None]
            src_vals = jnp.where(sel, v[lidx], all_v[sidx])
        else:
            src_vals = all_v[sidx]
        edge = EdgeCtx(
            src_vals=src_vals,
            dst_vals=v[jnp.clip(dstl, 0, max_nv - 1)],
            weights=w,
        )
        acc = segment_reduce(
            prog.edge_contrib(edge), dstl, num_segments=max_nv + 1,
            kind=prog.combiner,
        )[:max_nv]
        ctx = VertexCtx(
            nv=self.graph.nv,
            out_degrees=dg["out_degrees"][0],
            in_degrees=dg["in_degrees"][0],
        )
        new = prog.apply_ctx(v, acc, ctx)
        vmask = dg["vertex_mask"][0]
        vm = vmask if new.ndim == 1 else vmask[:, None]
        new = jnp.where(vm, new, v)
        return (
            GasState(new[None], state.frontier, state.direction),
            vmask.sum(dtype=jnp.int32),
        )

    def _dense_pull_iter_block(self, state: GasState, dg):
        with prof.region("lux.gas_sharded.exchange"):
            all_v = self._values_load(state, dg)
        with prof.region("lux.gas_sharded.compute"):
            st, cnt = self._dense_pull_step(state, all_v, dg)
        return st, cnt, jnp.int32(0)

    def _one_iter_block(self, state: GasState, dg):
        if self.program.frontier:
            return self._frontier_iter_block(state, dg)
        return self._dense_pull_iter_block(state, dg)

    def _shard_step(self, state: GasState, dg):
        new_state, cnt, _ = self._one_iter_block(state, dg)
        return new_state, cnt[None]

    def _shard_chunk(self, state: GasState, dg, limit, k: int):
        def one_iter(st):
            new_state, cnt_local, flag = self._one_iter_block(st, dg)
            return new_state, jax.lax.psum(cnt_local, PARTS_AXIS), flag

        st, counts, flags_, done, last = _chunk_while(
            one_iter, state, k, limit[0], flag_axes=(PARTS_AXIS,)
        )
        return st, counts[None], flags_[None], done[None], last[None]

    def _multi(self, state: GasState, limit: int, k: int):
        if k not in self._chunk_cache:
            mapped = jax.shard_map(
                lambda st, dg, lim: self._shard_chunk(st, dg, lim, k),
                mesh=self.mesh,
                in_specs=(self._state_spec, self._specs, P()),
                out_specs=(
                    self._state_spec,
                    P(PARTS_AXIS),
                    P(PARTS_AXIS),
                    P(PARTS_AXIS),
                    P(PARTS_AXIS),
                ),
            )
            self._chunk_cache[k] = jax.jit(mapped, donate_argnums=0)
        return self._chunk_cache[k](
            state, self._dg, jnp.full((1,), limit, jnp.int32)
        )

    # -- driving ----------------------------------------------------------

    def init_state(self, **kw) -> GasState:
        sh = parts_sharding(self.mesh)
        vals = jax.device_put(
            jnp.asarray(
                self.sg.to_padded(self.program.init_values(self.graph, **kw))
            ),
            sh,
        )
        fr = jax.device_put(
            jnp.asarray(
                self.sg.to_padded(
                    self.program.init_frontier(self.graph, **kw))
            ),
            sh,
        )
        dirs = jax.device_put(
            jnp.zeros((self.num_parts,), jnp.int32), sh
        )
        return GasState(vals, fr, dirs)

    def step(self, state: GasState):
        return self._step(state, self._dg)

    def run(
        self,
        max_iters: Optional[int] = None,
        state: Optional[GasState] = None,
        chunk: int = 16,
        recorder=None,
        **init_kw,
    ):
        """Iterate to fixpoint (or ``max_iters``); returns
        (final_state, iterations_run). Directions land in
        ``self.push_iters`` / ``self.pull_iters`` /
        ``self.direction_switches``; frontier-exchange downgrades in
        ``self.exchange_downgrades``."""
        if not self.program.frontier and max_iters is None:
            raise ValueError(
                f"{self.program.name} is a frontier-less pull program; "
                "run() needs max_iters"
            )
        if state is None:
            state = self.init_state(**init_kw)
        rec = recorder if recorder is not None else recorder_for(
            "gas_sharded", self.graph, self.program)
        rec.start()
        if rec.enabled:
            rec.record_compile(consume_compile_seconds(self))
            packed = self._xplan is not None
            note = (
                "frontier_all_to_all" if self.exchange_mode == "frontier"
                else "compact_all_to_all" if packed else "dense_estimate"
            )
            rec.set_exchange_bytes(
                self.exchange_bytes_per_iter(), note=note,
                parts=self.num_parts)
            if packed:
                rec.set_overlap(True)
            useful = engobs.useful_exchange(
                self.sg, self._row_bytes(),
                exchanged_rows=(self._xplan.exchanged_units_per_iter
                                if packed else None))
            if useful is not None:
                rec.set_useful_bytes(useful["useful_bytes_per_iter"],
                                     useful["ratio"])
            rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                self.graph.nv, self.graph.ne))
        if engobs.enabled():
            # Phase-fenced measurement fixpoint (LUX_ENGOBS); the off
            # path keeps the exact chunked fused executable below.
            state, total, pushes, switches, downs = engobs.run_gas_phased(
                self, state, max_iters, rec)
        else:
            state, total, pushes, switches, downs = (
                _run_sharded_gas_fixpoint(
                    self._multi, state, max_iters, chunk, recorder=rec
                )
            )
        self.push_iters = pushes
        self.pull_iters = total - pushes
        self.direction_switches = switches
        self.exchange_downgrades = downs
        engobs.note(
            "gas_sharded", program=self.program.name, mode=self.mode,
            exchange=self.exchange_mode, num_parts=self.num_parts,
            num_iters=total, direction_push=pushes,
            direction_pull=total - pushes, direction_switches=switches,
            exchange_downgrades=downs,
        )
        rec.finish()
        return state, total

    def warmup(self, chunk: int = 16, **init_kw):
        """Compile the chunked executable (both direction branches and
        both frontier-exchange sends live under its lax.conds) outside
        any timed/served request."""
        with Timer() as t:
            _run_sharded_gas_fixpoint(
                self._multi, self.init_state(**init_kw), 1, chunk
            )
        note_compile_seconds(self, t.elapsed)

    def gather_values(self, state: GasState) -> np.ndarray:
        return self.sg.from_padded(np.asarray(jax.device_get(state.values)))

    def finalize(self, state: GasState) -> dict:
        """Host-side derived outputs for the converged state (numpy —
        never compiles)."""
        return self.program.finalize_host(
            self.graph, self.gather_values(state))

    # -- `-verbose` / engobs phase split ----------------------------------

    def _sharded_phase_jits(self):
        """Separately-dispatched phase executables, each a shard_map
        jit, so engobs can fence exchange vs compute walls. SPMD phases
        run in lockstep, so the measured walls are mesh-wide."""
        if hasattr(self, "_pjits"):
            return self._pjits
        state_spec = self._state_spec
        specs = self._specs
        packed = self._xplan is not None

        def sm(fn, in_specs, out_specs):
            # check_vma off: all_gather outputs are replicated by
            # construction but the static checker cannot infer it here.
            return jax.jit(jax.shard_map(
                fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            ))

        j = {}
        if not self.program.frontier:
            j["d_load"] = sm(
                lambda st, dg: (
                    self._values_load(st, dg)[None] if packed
                    else self._values_load(st, dg)
                ),
                (state_spec, specs),
                P(PARTS_AXIS) if packed else P(),
            )
            j["d_step"] = sm(
                lambda st, av, dg: (
                    lambda r: (r[0], r[1][None])
                )(self._dense_pull_step(
                    st, av[0] if packed else av, dg)),
                (state_spec, P(PARTS_AXIS) if packed else P(), specs),
                (state_spec, P(PARTS_AXIS)),
            )
            self._pjits = j
            return j

        def decide(st, dg):
            cnt_loc, take = self._decide_block(st, dg)
            take = jnp.int32(0) if take is None else take.astype(jnp.int32)
            return cnt_loc[None], take[None]

        j["decide"] = sm(
            decide, (state_spec, specs), (P(PARTS_AXIS), P(PARTS_AXIS)))
        # Exchanged pull tables are per-shard scatters under the packed
        # modes, replicated all_gather outputs otherwise; the downgrade
        # flag is always a per-shard scalar lane.
        tbl = P(PARTS_AXIS) if packed else P()
        j["p_load"] = sm(
            lambda st, dg: (
                lambda av, af, dn: (
                    (av[None], af[None], dn[None]) if packed
                    else (av, af, dn[None])
                )
            )(*self._pull_load(st, dg)),
            (state_spec, specs), (tbl, tbl, P(PARTS_AXIS)),
        )
        j["p_comp"] = sm(
            lambda st, av, af, dg: self._pull_comp(
                st,
                ((av[0], af[0]) if packed else (av, af)),
                dg,
            )[None],
            (state_spec, tbl, tbl, specs), P(PARTS_AXIS),
        )
        j["merge"] = sm(
            lambda st, acc, dirs, dg: (
                lambda r: (r[0], r[1][None])
            )(self._merge(st, acc[0], dirs, dg)),
            (state_spec, P(PARTS_AXIS), P(PARTS_AXIS), specs),
            (state_spec, P(PARTS_AXIS)),
        )
        if self.mode != "pull":
            j["s_load"] = sm(
                lambda st, dg: self._push_load(st, dg),
                (state_spec, specs), (P(), P()),
            )
            j["s_comp"] = sm(
                lambda q, qv, dg: self._push_comp(q, qv, dg)[None],
                (P(), P(), specs), P(PARTS_AXIS),
            )
        self._pjits = j
        return j

    def _dirs_device(self, push: bool):
        return jax.device_put(
            np.full((self.num_parts,), 1 if push else 0, np.int32),
            parts_sharding(self.mesh),
        )

    def phase_step(self, state: GasState):
        """One iteration as separately-dispatched exchange/compute/merge
        phases. Returns (new_state, total_active, info): info carries
        the phase walls, the branch taken (``push`` | ``pull`` |
        ``pull/frontier`` | ``pull/downgraded``), and the downgrade
        flag. Phase dispatch breaks fusion; use run() for timed
        fixpoints."""
        j = self._sharded_phase_jits()
        dg = self._dg
        times = {}
        if not self.program.frontier:
            with Timer() as t:
                all_v = hard_sync(j["d_load"](state, dg))
            times["loadTime"] = t.elapsed
            with Timer() as t:
                new_state, cnt = hard_sync(j["d_step"](state, all_v, dg))
            times["compTime"] = t.elapsed
            times["updateTime"] = 0.0
            times["branch"] = "pull/dense"
            times["downgraded"] = 0
            total = int(np.asarray(jax.device_get(cnt)).sum())
            return new_state, total, times
        _, take = jax.device_get(j["decide"](state, dg))
        take_i = int(np.asarray(take).reshape(-1)[0])
        down_i = 0
        if take_i:
            with Timer() as t:
                all_q, all_qv = hard_sync(j["s_load"](state, dg))
            times["loadTime"] = t.elapsed
            with Timer() as t:
                acc = hard_sync(j["s_comp"](all_q, all_qv, dg))
            times["compTime"] = t.elapsed
            times["branch"] = "push"
        else:
            with Timer() as t:
                all_v, all_f, down = hard_sync(j["p_load"](state, dg))
            times["loadTime"] = t.elapsed
            down_i = int(np.asarray(jax.device_get(down)).reshape(-1)[0])
            with Timer() as t:
                acc = hard_sync(j["p_comp"](state, all_v, all_f, dg))
            times["compTime"] = t.elapsed
            if self.exchange_mode == "frontier":
                times["branch"] = (
                    "pull/downgraded" if down_i else "pull/frontier"
                )
            else:
                times["branch"] = "pull"
        with Timer() as t:
            new_state, cnt = hard_sync(
                j["merge"](state, acc, self._dirs_device(bool(take_i)), dg)
            )
        times["updateTime"] = t.elapsed
        times["downgraded"] = down_i
        total = int(np.asarray(jax.device_get(cnt)).sum())
        return new_state, total, times

    def warmup_phases(self, state: GasState):
        """Compile every phase executable — both directions and both
        frontier-exchange sends — outside any timed region. ``state``
        is read, never donated."""
        j = self._sharded_phase_jits()
        dg = self._dg
        if not self.program.frontier:
            all_v = j["d_load"](state, dg)
            hard_sync(j["d_step"](state, all_v, dg))
            return
        jax.device_get(j["decide"](state, dg))
        all_v, all_f, _ = j["p_load"](state, dg)
        acc = j["p_comp"](state, all_v, all_f, dg)
        hard_sync(j["merge"](state, acc, self._dirs_device(False), dg))
        if self.mode != "pull":
            all_q, all_qv = j["s_load"](state, dg)
            acc = j["s_comp"](all_q, all_qv, dg)
            hard_sync(j["merge"](state, acc, self._dirs_device(True), dg))

    # -- accounting / lint hooks ------------------------------------------

    def _row_bytes(self) -> int:
        """Per-exchanged-row payload: value lanes + 1 frontier byte
        (frontier-less programs exchange values only)."""
        itemsize = np.dtype(self.program.value_dtype).itemsize
        return itemsize * _value_lanes(self.program) + (
            1 if self.program.frontier else 0
        )

    def _frontier_row_bytes(self) -> int:
        """Frontier-mode packed row: value + int32 row id (the activity
        bit rides in the id's sentinel)."""
        return np.dtype(self.program.value_dtype).itemsize + 4

    def exchange_bytes_per_iter(self) -> int:
        """Pull-branch upper bound on cross-device traffic per
        iteration. Frontier mode reports the static compact figure —
        its own downgrade branch, and the bound the dynamic send always
        beats; the measured frontier win is engobs ledger evidence, not
        this static bound."""
        p = self.num_parts
        if self._xplan is not None:
            return self._xplan.exchange_bytes_per_iter(self._row_bytes())
        return p * (p - 1) * self.sg.max_nv * self._row_bytes()

    def frontier_evidence(self) -> Optional[dict]:
        """LUX407 inputs (luxlint --exchange): the static admissibility
        contract of the dynamic plan. ``frontier_max_sends`` is the
        admission threshold — an iteration with more active rows on any
        pair downgrades instead of truncating — and
        ``frontier_fill_active`` asserts dropped rows are inactive
        (combiner-identity annihilated), never zero-filled actives."""
        if self.exchange_mode != "frontier":
            return None
        p = self.num_parts
        rb = self._frontier_row_bytes()
        return {
            "frontier_capacity": self.frontier_cap,
            "frontier_max_sends": self.frontier_cap,
            "frontier_row_bytes": rb,
            "frontier_bytes_per_iter": p * (p - 1) * self.frontier_cap * rb,
            "frontier_fill_active": 0,
        }

    def trace_step(self, **init_kw):
        """luxlint-IR hook (analysis/ir.py): the jitted shard_map step;
        sharded=True, so LUX105 demands a collective in the trace. The
        exchange_* keys feed LUX404-407 (``luxlint --exchange``)."""
        return {
            "kind": "gas_sharded",
            "fn": self._step,
            "args": (self.init_state(**init_kw), self._dg),
            "donate": (0,),
            "carry": (0,),
            "sharded": True,
            "exchange_mode": self.exchange_mode,
            "exchange_bytes": self.exchange_bytes_per_iter(),
            "combiner": getattr(self.program, "combiner", ""),
            "value_dtype": np.dtype(
                getattr(self.program, "value_dtype", np.uint32)).name,
            "num_parts": self.num_parts,
            "plan": self._xplan,
        }


def _run_sharded_gas_fixpoint(multi, state, max_iters, chunk, recorder=None):
    """Chunked host loop: one batched device_get per chunk; the flag
    lane packs the direction taken (bit 0) and frontier-exchange
    downgrades (bit 1). Returns (state, total_iters, push_iters,
    direction_switches, exchange_downgrades)."""
    rec = recorder if recorder is not None else NULL_RECORDER
    total = 0
    push_total = 0
    switches = 0
    downgrades = 0
    prev = None
    while True:
        limit = chunk if max_iters is None else min(chunk, max_iters - total)
        if limit <= 0:
            break
        k = chunk
        state, counts, dirs, done, last = multi(state, limit, k)
        # luxlint: disable=LUX001 -- one batched fetch per chunk (not per iter) is the fixpoint design
        counts_h, dirs_h, done_h, last_h = jax.device_get(
            (counts, dirs, done, last)
        )
        done_i = int(np.asarray(done_h).reshape(-1)[0])
        last_i = int(np.asarray(last_h).reshape(-1)[0])
        fl = np.asarray(dirs_h).reshape(-1, k)[0][:done_i]
        dl = fl & 1
        downgrades += int((fl >> 1).sum())
        if dl.size:
            seq = dl if prev is None else np.concatenate(([prev], dl))
            switches += int(np.count_nonzero(np.diff(seq.astype(np.int64))))
            prev = dl[-1]
        push_total += int(dl.sum())
        total += done_i
        cnts = np.asarray(counts_h).reshape(-1, k)[0][:done_i]
        rec.flush(total, frontier_sizes=cnts, directions=dl)
        if last_i == 0 or done_i == 0:
            break
    hard_sync(state.values)
    rec.flush(total)
    return state, total, push_total, switches, downgrades


class ShardedMultiSourceGasExecutor:
    """Dense GAS over the mesh with K value lanes per vertex: one
    distributed pull-direction sweep serves K independent root queries
    of any rooted GasProgram — the sharded serving form of
    :class:`MultiSourceGasExecutor`, laid out like
    :class:`ShardedMultiSourcePushExecutor` ((P, max_nv, K) shards,
    lane axis trailing, K-lane full or compact exchange).

    Push-direction queue compaction and the frontier-aware exchange are
    single-lane-shaped, so this executor is pull-only on the static
    exchange (``LUX_EXCHANGE=frontier`` downgrades to compact here,
    logged); per-lane results are still bitwise-identical to a
    single-source sharded run because every path builds the same dense
    accumulator."""

    def __init__(
        self,
        graph: Graph,
        program,
        k: int,
        mesh: Optional[Mesh] = None,
        num_parts: Optional[int] = None,
        sg: Optional[ShardedGraph] = None,
    ):
        if k < 1:
            raise ValueError(f"batch width k must be >= 1 (got {k})")
        program = as_gas(program)
        if not program.frontier:
            raise ValueError(
                f"{program.name} is frontier-less; multi-source batching "
                "needs a rooted frontier program"
            )
        if program.needs_weights and graph.weights is None:
            raise ValueError(f"{program.name} requires an edge-weighted graph")
        self.mesh = mesh if mesh is not None else make_mesh(num_parts)
        self.num_parts = self.mesh.devices.size
        self.graph = graph
        self.program = program
        self.k = int(k)
        self.sg = _validated_sg(sg, graph, self.num_parts)
        sh = parts_sharding(self.mesh)
        put = lambda x: jax.device_put(jnp.asarray(x), sh)
        dg = {
            "src_pidx": put(self.sg.src_pidx),
            "dst_local": put(self.sg.dst_local),
            "vertex_mask": put(self.sg.vertex_mask),
        }
        if self.sg.weights is not None:
            dg["weights"] = put(self.sg.weights)
        self.exchange_mode, self._xplan = resolve_exchange(
            self.sg, get_logger("engine"), frontier_ok=False)
        if self._xplan is not None:
            dg["xch_send"] = put(self._xplan.send_units)
            dg["xch_recv"] = put(self._xplan.recv_pos)
        self._dg = dg
        self._specs = {key: P(PARTS_AXIS) for key in dg}
        self.push_iters = 0          # API parity (pull-only: always 0)
        self.pull_iters = 0
        self.direction_switches = 0
        self.exchange_downgrades = 0
        state_spec = GasState(P(PARTS_AXIS), P(PARTS_AXIS), P(PARTS_AXIS))
        self._state_spec = state_spec
        mapped = jax.shard_map(
            self._shard_step,
            mesh=self.mesh,
            in_specs=(state_spec, self._specs),
            out_specs=(state_spec, P(PARTS_AXIS)),
        )
        self._step = jax.jit(mapped, donate_argnums=0)
        self._chunk_cache = {}

    def _exchange_lanes_block(self, state: GasState, dg):
        """All-gather (or compact all_to_all) the (values, frontier)
        lane shards into (P*max_nv, K) global tables — own-span and
        unread rows stay zero (frontier False) under the compact plan,
        and the local-first compute branch never reads them."""
        v = state.values[0]                            # (max_nv, K)
        f = state.frontier[0]
        if self._xplan is not None:
            max_nv = self.sg.max_nv
            sel = jnp.minimum(dg["xch_send"][0], max_nv - 1)
            pv = jax.lax.all_to_all(
                v[sel], PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True)
            pf = jax.lax.all_to_all(
                f[sel], PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True)
            recv = dg["xch_recv"][0]
            flat = self.num_parts * max_nv
            all_v = jnp.zeros((flat + 1, self.k), v.dtype)
            all_f = jnp.zeros((flat + 1, self.k), f.dtype)
            return (all_v.at[recv].set(pv)[:-1], all_f.at[recv].set(pf)[:-1])
        all_v = jax.lax.all_gather(v, PARTS_AXIS).reshape(-1, self.k)
        all_f = jax.lax.all_gather(f, PARTS_AXIS).reshape(-1, self.k)
        return all_v, all_f

    def _compute_lanes_block(self, state: GasState, all_v, all_f, dg):
        """Per-lane gather + identity mask + segment reduce + GAS
        apply/scatter on this shard's rows."""
        prog = self.program
        max_nv = self.sg.max_nv
        v = state.values[0]                            # (max_nv, K)
        sidx = dg["src_pidx"][0]
        w = dg["weights"][0] if "weights" in dg else None
        wk = None if w is None else w[:, None]
        if self._xplan is not None:
            f_loc = state.frontier[0]
            own = jax.lax.axis_index(PARTS_AXIS)
            base = own * max_nv
            local = (sidx >= base) & (sidx < base + max_nv)
            lidx = jnp.clip(sidx - base, 0, max_nv - 1)
            cand_l = prog.gather(v[lidx], wk)
            cand_r = prog.gather(all_v[sidx], wk)
            ident = identity_for(prog.combiner, cand_l.dtype)
            cand_l = jnp.where(f_loc[lidx], cand_l, ident)
            cand_r = jnp.where(all_f[sidx], cand_r, ident)
            cand = jnp.where(local[:, None], cand_l, cand_r)
        else:
            cand = prog.gather(all_v[sidx], wk)
            ident = identity_for(prog.combiner, cand.dtype)
            cand = jnp.where(all_f[sidx], cand, ident)
        acc = segment_reduce(
            cand, dg["dst_local"][0], num_segments=max_nv + 1,
            kind=prog.combiner,
        )[:max_nv]
        new = prog.apply(v, acc)
        vmask = dg["vertex_mask"][0][:, None]
        new = jnp.where(vmask, new, v)
        frontier = prog.scatter(v, new) & vmask
        return (
            GasState(new[None], frontier[None], state.direction),
            frontier.sum(dtype=jnp.int32),
        )

    def _iter_block(self, state: GasState, dg):
        with prof.region("lux.gas_multi_sharded.exchange"):
            all_v, all_f = self._exchange_lanes_block(state, dg)
        with prof.region("lux.gas_multi_sharded.compute"):
            return self._compute_lanes_block(state, all_v, all_f, dg)

    def _shard_step(self, state: GasState, dg):
        new_state, cnt = self._iter_block(state, dg)
        return new_state, cnt[None]

    def _shard_chunk(self, state: GasState, dg, limit, k: int):
        def one_iter(st):
            new_state, cnt_local = self._iter_block(st, dg)
            return (
                new_state,
                jax.lax.psum(cnt_local, PARTS_AXIS),
                jnp.int32(0),
            )

        st, counts, flags_, done, last = _chunk_while(
            one_iter, state, k, limit[0], flag_axes=(PARTS_AXIS,)
        )
        return st, counts[None], flags_[None], done[None], last[None]

    def _multi(self, state: GasState, limit: int, k: int):
        if k not in self._chunk_cache:
            mapped = jax.shard_map(
                lambda st, dg, lim: self._shard_chunk(st, dg, lim, k),
                mesh=self.mesh,
                in_specs=(self._state_spec, self._specs, P()),
                out_specs=(
                    self._state_spec,
                    P(PARTS_AXIS),
                    P(PARTS_AXIS),
                    P(PARTS_AXIS),
                    P(PARTS_AXIS),
                ),
            )
            self._chunk_cache[k] = jax.jit(mapped, donate_argnums=0)
        return self._chunk_cache[k](
            state, self._dg, jnp.full((1,), limit, jnp.int32)
        )

    def init_state(self, starts) -> GasState:
        """(P, max_nv, K) state with one lane per root; short batches
        are right-padded by repeating the last root (duplicate lanes
        converge identically — results, iteration counts, and the
        executable shape are all unchanged: the zero-recompile
        contract)."""
        starts = list(starts)
        if not 1 <= len(starts) <= self.k:
            raise ValueError(f"need 1..{self.k} roots, got {len(starts)}")
        starts = starts + [starts[-1]] * (self.k - len(starts))
        prog = self.program
        vals = np.stack(
            [prog.init_values(self.graph, start=s) for s in starts], axis=1
        )
        fr = np.stack(
            [prog.init_frontier(self.graph, start=s) for s in starts], axis=1
        )
        sh = parts_sharding(self.mesh)
        return GasState(
            jax.device_put(jnp.asarray(self.sg.to_padded(vals)), sh),
            jax.device_put(jnp.asarray(self.sg.to_padded(fr)), sh),
            jax.device_put(jnp.zeros((self.num_parts,), jnp.int32), sh),
        )

    def step(self, state: GasState):
        return self._step(state, self._dg)

    def run(
        self,
        starts,
        max_iters: Optional[int] = None,
        chunk: int = 16,
        recorder=None,
        state: Optional[GasState] = None,
    ):
        """Run all roots to the shared fixpoint; column j of the
        gathered values is root ``starts[j]``'s result."""
        if state is None:
            state = self.init_state(starts)
        rec = recorder if recorder is not None else recorder_for(
            "gas_multi_sharded", self.graph, self.program)
        rec.start()
        if rec.enabled:
            rec.record_compile(consume_compile_seconds(self))
            packed = self._xplan is not None
            rec.set_exchange_bytes(
                self.exchange_bytes_per_iter(),
                note="compact_all_to_all" if packed else "dense_estimate",
                parts=self.num_parts)
            if packed:
                rec.set_overlap(True)
            rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                self.graph.nv, self.graph.ne, k=self.k))
        state, total, _, _, _ = _run_sharded_gas_fixpoint(
            self._multi, state, max_iters, chunk, recorder=rec
        )
        self.pull_iters = total
        engobs.note(
            "gas_multi_sharded", program=self.program.name, mode="pull",
            exchange=self.exchange_mode, num_parts=self.num_parts,
            num_iters=total, lanes=self.k,
        )
        rec.finish()
        return state, total

    def warmup(self, chunk: int = 16, start: int = 0):
        with Timer() as t:
            _run_sharded_gas_fixpoint(
                self._multi, self.init_state([start]), 1, chunk
            )
        note_compile_seconds(self, t.elapsed)

    def _row_bytes(self) -> int:
        itemsize = np.dtype(self.program.value_dtype).itemsize
        return self.k * (itemsize + 1)

    def exchange_bytes_per_iter(self) -> int:
        p = self.num_parts
        if self._xplan is not None:
            return self._xplan.exchange_bytes_per_iter(self._row_bytes())
        return p * (p - 1) * self.sg.max_nv * self._row_bytes()

    def gather_values(self, state: GasState) -> np.ndarray:
        return self.sg.from_padded(np.asarray(jax.device_get(state.values)))

    def values_for(self, state: GasState, j: int) -> np.ndarray:
        """Host copy of lane ``j``'s unpadded value column."""
        return np.ascontiguousarray(self.gather_values(state)[:, j])

    def finalize_for(self, state: GasState, j: int) -> dict:
        return self.program.finalize_host(
            self.graph, self.values_for(state, j)
        )

    def trace_step(self, start: int = 0, **init_kw):
        """luxlint-IR hook: the jitted shard_map step (sharded=True, so
        LUX105 demands a collective); exchange_* keys feed LUX404-407."""
        return {
            "kind": "gas_multi_sharded",
            "fn": self._step,
            "args": (self.init_state([start]), self._dg),
            "donate": (0,),
            "carry": (0,),
            "sharded": True,
            "exchange_mode": self.exchange_mode,
            "exchange_bytes": self.exchange_bytes_per_iter(),
            "combiner": getattr(self.program, "combiner", ""),
            "value_dtype": np.dtype(
                getattr(self.program, "value_dtype", np.uint32)).name,
            "num_parts": self.num_parts,
            "k": self.k,
            "plan": self._xplan,
        }
