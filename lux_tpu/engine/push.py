"""Push-model engine: frontier-driven fixpoint iteration.

The reference push engine (core/push_model.inl + sssp/sssp_gpu.cu:335-522)
keeps an *active frontier*, expands each frontier vertex's out-edges with
atomic relaxations, adaptively switches between a sparse queue and a dense
bitmap, and between push and pull directions (frontier > nv/16 ⇒ pull,
sssp_gpu.cu:414).

TPU-native formulation: the frontier is a dense boolean mask (XLA needs
static shapes; the reference's own dense-bitmap mode, sssp_gpu.cu:248-281,
is the shape-stable representation). Each iteration is executed in the
*pull direction* over the CSC in-edges with non-frontier contributions
masked to the combiner identity:

    cand_e = relax(val[src_e], w_e)        if frontier[src_e] else identity
    acc_v  = min/max over in-edges of v
    new_v  = combine(old_v, acc_v)
    frontier'_v = (new_v != old_v)         — the adaptive "changed" bitmap
                                             diff, cf. bitmap_kernel
                                             sssp_gpu.cu:248-281
    active = Σ frontier'                   — the halt signal the reference
                                             returns per point task
                                             (sssp_gpu.cu:521)

This is work-suboptimal for tiny frontiers (O(ne) per iteration instead of
O(frontier edges)) but every op is a large dense VPU-friendly computation;
a Pallas sparse path is layered on later.

Halt detection: the reference hides the per-iteration host round-trip for
the active count behind a 4-deep speculative window (SLIDING_WINDOW,
sssp/sssp.cc:111-129) — valid because the fixpoint is monotone, so extra
iterations are harmless. The TPU-native form goes further: up to ``chunk``
iterations run under one ``lax.while_loop`` dispatch with on-device early
exit, and the host reads one count batch per chunk. Same monotonicity
argument, ~chunk× fewer synchronizations (this round-trip is SURVEY.md
§7 hard-part (c)).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from lux_tpu.analysis.sentinel import compile_phase
from lux_tpu.engine.pull import hard_sync
from lux_tpu.graph.graph import Graph
from lux_tpu.obs import (
    NULL_RECORDER,
    consume_compile_seconds,
    engine_label,
    engobs,
    metrics,
    note_compile_seconds,
    prof,
    recorder_for,
    spans,
)
from lux_tpu.ops.segment import identity_for, segment_reduce
from lux_tpu.parallel.mesh import PARTS_AXIS, make_mesh, parts_sharding
from lux_tpu.parallel.shard import ShardedGraph, resolve_exchange
from lux_tpu.utils.logging import get_logger
from lux_tpu.utils.timing import Timer

class PushProgram:
    """Frontier-driven vertex program (SSSP, CC, ...)."""

    name: str = "push"
    combiner: str = "min"          # 'min' | 'max'
    value_dtype = jnp.uint32
    needs_weights: bool = False
    rooted: bool = False           # takes a per-query `start` root
    servable: bool = True          # exposed through serve/session.py
    # Machine-checked capability claims (luxlint --programs, LUX604/606):
    # frontier_ok licenses the masked-identity frontier machinery above;
    # incremental_ok additionally claims the monotone-merge proof that
    # engine/incremental.py's warm-start depends on.
    frontier_ok: bool = True
    incremental_ok: bool = False
    # Declare True iff every value the program can ever hold fits in 31
    # bits (e.g. SSSP distances and CC labels, both <= nv < 2^31). The
    # blocked dense path packs the frontier bit into the value's top bit
    # and silently corrupts programs that use it — it only enables when
    # this is declared.
    packable_values: bool = False

    def init_values(self, graph: Graph, **kw) -> np.ndarray:
        raise NotImplementedError

    def init_frontier(self, graph: Graph, **kw) -> np.ndarray:
        raise NotImplementedError

    def relax(self, src_vals: jnp.ndarray, weights) -> jnp.ndarray:
        """Candidate value pushed along an edge from an active source."""
        raise NotImplementedError

    def edge_invariant(self, src_vals, dst_vals, weights) -> jnp.ndarray:
        """Per-edge fixpoint invariant for `-check` (True = ok). The
        reference's GPU checkers: sssp_gpu.cu:773-798,
        components_gpu.cu:769-792."""
        raise NotImplementedError


class PushState(NamedTuple):
    values: jnp.ndarray     # (nv,) or (P, max_nv)
    frontier: jnp.ndarray   # bool, same shape


def _sparse_budgets(nv: int, ne: int, queue_frac: int, edge_budget_frac: int):
    """(queue capacity, edge budget) for the bounded sparse frontier.

    Shared by the single-device and sharded executors so both pick the
    sparse branch under identical conditions. Mirrors the reference's
    per-part sparse queue sizing (nv/SPARSE_THRESHOLD + slack,
    push_model.inl:390-412)."""
    return nv // queue_frac + 128, max(ne // edge_budget_frac, 1024)


# Cost of one sparse-tier iteration against one blocked dense sweep,
# in units of the sweep's cost an edge, c: a tier of E expansion slots
# costs SPARSE_SCAN_COST * nv (the frontier scan that fills its queue)
# + SPARSE_SLOT_COST * E, the sweep costs ne. One TPU v5e, Graph500
# scale 22, fused iterations from 45 frontiers (tools/push_tiers.py):
# the sweep 208.4 ms (c = 3.11 ns an edge); tiers of E = 131,072 /
# 1,048,576 / 8,388,608 slots 47.4 / 110.4 / 808.9 ms, fitted as a
# 24.5 ms scan (5.84 ns a vertex, s/c = 1.88) + 93.3 ns a slot
# (a/c = 30.1).
SPARSE_SCAN_COST = 1.9
SPARSE_SLOT_COST = 30.0


def _make_tiers(queue_cap: int, edge_budget: int, nv: int, ne: int,
                blocked_dense: bool):
    """Ascending (queue, edge budget) size tiers derived from the full
    budgets. Shared by both executors (like _sparse_budgets) so a policy
    tweak cannot silently diverge them: per iteration the smallest
    adequate tier serves, so a near-fixpoint frontier of a few vertices
    does not pay the full ne/8 expansion + scatter.

    Where the dense branch is the blocked sweep, a tier is kept only if
    one iteration of it costs less than one sweep over the ``ne`` edges
    (the cost model above, per part for the sharded executor); frontiers
    that would have needed a dropped tier go dense. The plain dense
    branch costs an order of magnitude more an edge, so its ladder keeps
    every tier."""
    tiers = []
    for div in (64, 8, 1):
        t = (max(queue_cap // div, 256), max(edge_budget // div, 1024))
        if t in tiers:
            continue
        if blocked_dense and (
            SPARSE_SCAN_COST * nv + SPARSE_SLOT_COST * t[1] >= ne
        ):
            continue
        tiers.append(t)
    return tiers


def _tier_index(cnt, out_edges, tiers):
    """lax.switch branch index: 0 = dense, i >= 1 = tiers[i-1] (the
    smallest adequate tier; adequacy is monotone in tier size, so the
    suffix count identifies it)."""
    nadeq = jnp.int32(0)
    for (Q, E) in tiers:
        ok = (cnt <= Q) & (out_edges <= jnp.uint32(E))
        nadeq = nadeq + ok.astype(jnp.int32)
    T = len(tiers)
    return jnp.where(nadeq == 0, 0, T - nadeq + 1)


def _tier_label(tiers, tier):
    return f"sparse/{tiers[tier - 1][1]}" if tier > 0 else "dense"


def _blocked_candidates(x2d, relax, combiner, chunks, weighted: bool,
                        ne_real=None):
    """Shared scan body of the blocked dense path: per edge, one 128-lane
    row gather from the packed (value | frontier<<31) uint32 table
    ``x2d``, lane select, unpack, relax, identity-mask. ``chunks`` is
    (sb, lane[, emask][, w]) with leading scan axes; returns the flat
    candidate stream (padded length). ``ne_real`` masks positions past
    the real edge count to the identity without a per-edge mask array
    (needed by block-granular consumers, which see pad positions —
    end-pos extraction never did)."""
    iota = jnp.arange(128, dtype=jnp.int32)
    ident = identity_for(combiner, jnp.uint32)
    C = chunks[0].shape[1]

    def body(base, ch):
        ch = list(ch)
        sb, lane = ch[0], ch[1]
        w = ch.pop() if weighted else None
        em = ch[2] if len(ch) > 2 else None
        rows = x2d[sb]
        pk = jnp.where(
            lane.astype(jnp.int32)[:, None] == iota[None, :], rows, 0
        ).sum(axis=1, dtype=jnp.uint32)
        sv = pk & jnp.uint32(0x7FFFFFFF)
        active = (pk >> 31).astype(bool)
        if em is not None:
            active = active & em
        if ne_real is not None:
            # int32 is safe: blocked_dense is gated on ne < 2^31.
            active = active & (
                base + jnp.arange(C, dtype=jnp.int32) < ne_real
            )
        cand = relax(sv, w)
        return base + C, jnp.where(active, cand, ident)

    _, cands = jax.lax.scan(body, jnp.int32(0), tuple(chunks))
    return cands.reshape(-1)


def _queue_edge_slots(start, deg, E: int, ne_cap: int):
    """Static-shape expansion of a bounded queue's edge ranges.

    Given per-queue-slot CSR ``start`` offsets and ``deg`` degrees, lay
    the queued vertices' edges head-to-head into ``E`` static edge slots:
    returns (slot, edge_pos, emask) where ``slot[e]`` is the queue slot
    owning edge slot e, ``edge_pos[e]`` its position in the edge arrays
    (clipped into [0, ne_cap)), and ``emask`` marks live slots. The
    caller must mask candidates/destinations with ``emask``."""
    offs = jnp.concatenate([jnp.zeros(1, deg.dtype), jnp.cumsum(deg)])
    total = offs[-1]
    marks = jnp.zeros(E + 1, jnp.int32).at[
        jnp.clip(offs[:-1], 0, E)
    ].add(1, mode="drop")
    slot = jnp.clip(jnp.cumsum(marks[:E]) - 1, 0, start.shape[0] - 1)
    e_idx = jnp.arange(E, dtype=offs.dtype)
    emask = e_idx < total
    edge_pos = jnp.clip(start[slot] + (e_idx - offs[slot]), 0, ne_cap - 1)
    return slot, edge_pos, emask


def _vary_like(x, like):
    """``x`` made varying over every manual mesh axis ``like`` varies
    over (a no-op outside ``shard_map``): a loop body that yields a
    constant for a per-shard carry keeps the carry's type."""
    missing = tuple(sorted(jax.typeof(like).vma - jax.typeof(x).vma))
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _chunk_while(one_iter, state: PushState, k: int, limit, flag_axes=()):
    """Run up to ``min(k, limit)`` fixpoint iterations on-device with
    early exit.

    The reference pays one host round-trip per iteration past its 4-deep
    window to read the halt count (sssp.cc:116-124); on TPU that
    round-trip dominates tiny iterations, so the whole loop runs under
    ``lax.while_loop`` and the host syncs once per chunk. ``k`` is
    static (compiled once); ``limit`` is a traced bound so partial final
    chunks reuse the same executable instead of recompiling.
    ``one_iter`` returns (state, count, took_sparse); returns
    (state, counts[k], sparse_flags[k], iters_done, last_count).
    Inside ``shard_map``, ``flag_axes`` names the mesh axes the per-shard
    flags vary over; the body's outputs are cast to the carry's
    varying-manual-axes types.
    """

    def cond(carry):
        _, i, last, _, _ = carry
        return (i < jnp.minimum(k, limit)) & (last > 0)

    def body(carry):
        st_in, i, _, counts, flags = carry
        st, cnt, sp = one_iter(st_in)
        st = jax.tree.map(_vary_like, st, st_in)
        sp = _vary_like(sp, flags)
        counts = jax.lax.dynamic_update_index_in_dim(
            counts, cnt, i, axis=0
        )
        flags = jax.lax.dynamic_update_index_in_dim(
            flags, sp, i, axis=0
        )
        return st, i + 1, cnt, counts, flags

    flags = jnp.zeros(k, jnp.int32)
    if flag_axes:
        flags = jax.lax.pcast(flags, flag_axes, to="varying")
    init = (state, jnp.int32(0), jnp.int32(1), jnp.zeros(k, jnp.int32), flags)
    st, done, last, counts, flags = jax.lax.while_loop(cond, body, init)
    return st, counts, flags, done, last


class PushExecutor:
    """Single-device push executor with adaptive direction switching.

    Two per-iteration strategies, chosen on-device by ``lax.cond`` the way
    the reference switches per iteration (sssp_gpu.cu:414-421):

    - **dense (pull direction)**: masked relax over all CSC in-edges —
      O(ne) but fully vectorized. Used for large frontiers.
    - **sparse (push direction)**: compact the frontier into a bounded
      queue (the FrontierHeader/queue design, push_model.inl:390-412,
      made static-shape), expand exactly the queued vertices' out-edges
      through the CSR, scatter-combine the candidates. Work scales with
      the *edge budget*, not ne — the win when frontiers are small, since
      on TPU gathers/scatters cost per element.

    Sparse is taken when the previous frontier fits the queue AND its
    out-edge total fits the edge budget; otherwise dense (the reference's
    sparse→dense overflow fallback, sssp_gpu.cu:462-491).
    """

    # Edge count below which the blocked dense path's fixed passes cost
    # more than they save over the plain gather/scatter formulation.
    BLOCKED_DENSE_MIN_NE = 1 << 16

    def __init__(
        self,
        graph: Graph,
        program: PushProgram,
        device=None,
        sparse: bool = True,
        queue_frac: int = 16,     # queue capacity = nv/queue_frac + slack
        edge_budget_frac: int = 8,  # edge budget = ne/edge_budget_frac
        blocked_dense: Optional[bool] = None,
    ):
        if program.needs_weights and graph.weights is None:
            raise ValueError(f"{program.name} requires an edge-weighted graph")
        self.graph = graph
        self.program = program
        self.device = device
        put = lambda x: jax.device_put(jnp.asarray(x), device)
        if blocked_dense is None:
            blocked_dense = (
                graph.ne >= self.BLOCKED_DENSE_MIN_NE
                and getattr(program, "packable_values", False)
                and program.value_dtype == jnp.uint32
                and graph.nv < 2**31
                and graph.ne < 2**31   # end positions are int32
            )
        elif blocked_dense:
            # An explicit request must not silently corrupt: the packed
            # table carries the frontier in the value's top bit and the
            # scan layout uses int32 positions.
            if program.value_dtype != jnp.uint32 or not getattr(
                program, "packable_values", False
            ):
                raise ValueError(
                    "blocked_dense needs a program declaring "
                    "packable_values (uint32 values < 2^31); "
                    f"{program.name} does not"
                )
            if graph.nv >= 2**31 or graph.ne >= 2**31:
                raise ValueError(
                    "blocked_dense needs nv and ne < 2^31 "
                    f"(got nv={graph.nv}, ne={graph.ne})"
                )
        self.blocked_dense = bool(blocked_dense)
        dg = {}
        if not self.blocked_dense:
            # The plain dense stages' arrays; the blocked path replaces
            # them with blk_* (skipping ~8 B/edge of dead HBM).
            dg["col_src"] = put(graph.col_src.astype(np.int32))
            dg["seg_ids"] = put(graph.col_dst)
            if graph.weights is not None:
                dg["weights"] = put(graph.weights)
        self.sparse = sparse and graph.ne >= 1024
        if self.sparse:
            self.queue_cap, self.edge_budget = _sparse_budgets(
                int(graph.nv), int(graph.ne), queue_frac, edge_budget_frac
            )
            self.tiers = _make_tiers(
                self.queue_cap, self.edge_budget, int(graph.nv),
                int(graph.ne), self.blocked_dense,
            )
            self.sparse = bool(self.tiers)
        if self.sparse:
            from lux_tpu.engine.pull import _edge_index_dtype

            csr = graph.csr()
            eidx = _edge_index_dtype(graph.ne)
            dg["csr_row_ptr"] = put(csr.row_ptr.astype(eidx))
            dg["csr_col_dst"] = put(csr.col_dst)
            if csr.weights is not None:
                dg["csr_weights"] = put(csr.weights)
            dg["out_degrees"] = put(graph.out_degrees.astype(np.int32))

        # Blocked dense path: serve per-edge (value, frontier-bit) via
        # 128-lane row gathers + lane select (the tail trick) from ONE
        # packed uint32 table, and reduce with a segmented min/max scan —
        # both ends of the plain dense iteration run at TPU scalar rate
        # (~8.5 ns/gather elem, ~45 ns/scatter row; phase-measured 1.45 s
        # load + 0.93 s comp per RMAT22 iteration, vs 0.39 + 0.51
        # blocked — 2.4x on the fused fixpoint). Needs values < 2^31
        # (the top bit carries the frontier), true for SSSP distances and
        # CC labels (both < nv).
        if self.blocked_dense:
            from lux_tpu.ops.segment import BlockMinLayout
            from lux_tpu.ops.tiled_spmv import GATHER_TABLE_BYTES

            C = 1 << 17
            ne = graph.ne
            pad = (-ne) % C
            sb = np.pad(graph.col_src >> 7, (0, pad)).astype(np.int32)
            lane = np.pad(graph.col_src & 127, (0, pad)).astype(np.int8)
            dg["blk_sb"] = put(sb.reshape(-1, C))
            dg["blk_lane"] = put(lane.reshape(-1, C))
            if graph.weights is not None:
                dg["blk_w"] = put(
                    np.pad(graph.weights, (0, pad)).reshape(-1, C)
                )
            # Block-min reduction layout (one dense 128-block reduce +
            # a 128x-smaller block-level segmented scan + masked
            # head/tail row extraction from sub-cliff table slices) —
            # replaces the edge-level associative min-scan whose
            # log-depth passes dominated compTime (~4 ns/edge measured).
            layout = BlockMinLayout(
                graph.row_ptr, ne + pad,
                seg_rows=GATHER_TABLE_BYTES // 512,
            )
            self._bm_segs = (layout.head_segs, layout.tail_segs)
            for k, v in layout.device_arrays().items():
                dg[k] = put(v)
        self._dg = dg
        self.sparse_iters = 0       # sparse-branch count of the last run()
        self._step = jax.jit(self._step_impl, donate_argnums=0)
        self._multi_jit = jax.jit(
            self._chunk_impl, donate_argnums=0, static_argnums=2
        )

    # -- dense (pull-direction) stages ------------------------------------
    # Each strategy is three stages (load / comp / update) so the fused
    # iteration and the `-verbose` phase_step share one implementation
    # (the reference's phase split, sssp_gpu.cu:389-513).

    def _d_load(self, state: PushState, dg):
        return state.values[dg["col_src"]], state.frontier[dg["col_src"]]

    def _d_comp(self, src_vals, src_front, dg):
        prog = self.program
        cand = prog.relax(src_vals, dg.get("weights"))
        ident = identity_for(prog.combiner, cand.dtype)
        cand = jnp.where(src_front, cand, ident)
        return segment_reduce(
            cand, dg["seg_ids"], num_segments=self.graph.nv,
            kind=prog.combiner,
        )

    def _merge_update(self, state: PushState, acc):
        with prof.region("lux.push.update"):
            if self.program.combiner == "min":
                new = jnp.minimum(state.values, acc)
            else:
                new = jnp.maximum(state.values, acc)
            frontier = new != state.values
            return PushState(new, frontier), frontier.sum(dtype=jnp.int32)

    def _bd_load(self, state: PushState, dg):
        """Per-edge candidates via the packed-table row-gather + lane
        select: values and frontier bits travel in ONE uint32 table
        (top bit = frontier), so each edge costs one 512 B row fetch
        instead of two scalar gathers. Returns (ne_padded,) candidates
        already masked to the combiner identity."""
        prog = self.program
        packed = (
            state.values.astype(jnp.uint32)
            | (state.frontier.astype(jnp.uint32) << 31)
        )
        nvb = -(-self.graph.nv // 128)
        x2d = jnp.pad(packed, (0, nvb * 128 - self.graph.nv)).reshape(
            nvb, 128
        )
        has_w = "blk_w" in dg
        chunks = (dg["blk_sb"], dg["blk_lane"])
        if has_w:
            chunks = chunks + (dg["blk_w"],)
        return _blocked_candidates(
            x2d, prog.relax, prog.combiner, chunks, has_w,
            ne_real=self.graph.ne,
        )

    def _bd_comp(self, cands, dg):
        from lux_tpu.ops.segment import segment_minmax_blockmin

        head_segs, tail_segs = self._bm_segs
        return segment_minmax_blockmin(
            cands, dg, head_segs, tail_segs, self.program.combiner,
        )

    def _dense_iter(self, state: PushState, dg):
        with prof.region("lux.push.dense"):
            if self.blocked_dense:
                acc = self._bd_comp(self._bd_load(state, dg), dg)
            else:
                acc = self._d_comp(*self._d_load(state, dg), dg)
        return self._merge_update(state, acc)

    # -- sparse (push-direction) stages -----------------------------------

    def _s_load(self, state: PushState, dg, Q=None):
        """Frontier → bounded queue (ids sorted ascending; pad slot nv)
        plus per-slot CSR ranges (padded row_ptr: q == nv → deg 0)."""
        nv = self.graph.nv
        Q = self.queue_cap if Q is None else Q
        q = jnp.nonzero(
            state.frontier, size=Q, fill_value=nv
        )[0].astype(jnp.int32)
        rp = dg["csr_row_ptr"]
        start = rp[q]
        deg = rp[jnp.minimum(q + 1, nv)] - start
        return q, start, deg

    def _s_comp(self, state: PushState, q, start, deg, dg, E=None):
        prog = self.program
        nv = self.graph.nv
        E = self.edge_budget if E is None else E
        slot, edge_pos, emask = _queue_edge_slots(
            start, deg, E, max(self.graph.ne, 1)
        )
        dst = dg["csr_col_dst"][edge_pos]
        src_vals = state.values[jnp.clip(q[slot], 0, nv - 1)]
        w = dg["csr_weights"][edge_pos] if "csr_weights" in dg else None
        cand = prog.relax(src_vals, w)
        ident = identity_for(prog.combiner, cand.dtype)
        return jnp.where(emask, cand, ident), jnp.where(emask, dst, 0)

    def _s_update(self, state: PushState, cand, dst):
        """Deterministic scatter-combine into the values (unlike the
        reference's atomicMin, sssp_gpu.cu:48-61)."""
        with prof.region("lux.push.update"):
            if self.program.combiner == "min":
                new = state.values.at[dst].min(cand)
            else:
                new = state.values.at[dst].max(cand)
            frontier = new != state.values
            return PushState(new, frontier), frontier.sum(dtype=jnp.int32)

    def _sparse_iter(self, state: PushState, dg, Q=None, E=None):
        with prof.region("lux.push.sparse"):
            q, start, deg = self._s_load(state, dg, Q)
            cand, dst = self._s_comp(state, q, start, deg, dg, E)
        return self._s_update(state, cand, dst)

    # -- adaptive combination --------------------------------------------

    def _decide_tier(self, state: PushState, dg):
        """Branch index for lax.switch — the static-shape analogue of
        the reference's frontier-proportional kernel sizes
        (sssp_gpu.cu:424-458); uint32 out-edge sums are exact for any
        total <= 2^32 > ne, so a tier can never be selected past its
        edge budget by rounding error."""
        with prof.region("lux.push.decide"):
            cnt = state.frontier.sum(dtype=jnp.int32)
            out_edges = jnp.where(
                state.frontier, dg["out_degrees"].astype(jnp.uint32), 0
            ).sum(dtype=jnp.uint32)
            return _tier_index(cnt, out_edges, self.tiers)

    def _one_iter(self, state: PushState, dg):
        if not self.sparse:
            st, cnt = self._dense_iter(state, dg)
            return st, cnt, jnp.int32(0)
        tier = self._decide_tier(state, dg)
        branches = [lambda st: self._dense_iter(st, dg)]
        for (Q, E) in self.tiers:
            branches.append(
                lambda st, Q=Q, E=E: self._sparse_iter(st, dg, Q, E)
            )
        st, ncnt = jax.lax.switch(tier, branches, state)
        return st, ncnt, (tier > 0).astype(jnp.int32)

    def _step_impl(self, state: PushState, dg):
        st, cnt, _ = self._one_iter(state, dg)
        return st, cnt

    def _chunk_impl(self, state: PushState, dg, k: int, limit=None):
        one_iter = lambda st: self._one_iter(st, dg)
        return _chunk_while(one_iter, state, k, limit)

    def _phase_jits(self):
        """Jitted wrappers of the shared load/comp/update stage methods
        (one implementation for the fused iteration and the `-verbose`
        phases — they cannot drift)."""
        if not hasattr(self, "_jphase"):
            # Both dense strategies normalize to load -> tuple of
            # intermediates, comp(*intermediates, dg) -> acc, so the
            # timing scaffolding below is strategy-agnostic.
            if self.blocked_dense:
                load_fn = lambda st, dg: (self._bd_load(st, dg),)
                comp_fn = lambda cands, dg: self._bd_comp(cands, dg)
            else:
                load_fn = self._d_load
                comp_fn = self._d_comp
            self._jphase = {
                "d_load": jax.jit(load_fn),
                "d_comp": jax.jit(comp_fn),
                "update": jax.jit(self._merge_update),
            }
            if self.sparse:
                # One (s_load, s_comp) pair per size tier, so the phase
                # breakdown measures the SAME executables run() selects
                # (the "they cannot drift" contract).
                self._jphase["decide"] = jax.jit(self._decide_tier)
                for i, (Q, E) in enumerate(self.tiers):
                    self._jphase[f"s_load{i}"] = jax.jit(
                        lambda st, dg, Q=Q: self._s_load(st, dg, Q)
                    )
                    self._jphase[f"s_comp{i}"] = jax.jit(
                        lambda st, q, s, d, dg, E=E: self._s_comp(
                            st, q, s, d, dg, E
                        )
                    )
                self._jphase["s_update"] = jax.jit(self._s_update)
        return self._jphase

    def warmup_phases(self, state: PushState):
        """Compile every phase jit (all branches and tiers) outside any
        timed region — mirrors warmup()'s contract that ELAPSED TIME
        excludes compilation. ``state`` is read, never donated."""
        j = self._phase_jits()
        dg = self._dg
        acc = j["d_comp"](*j["d_load"](state, dg), dg)
        hard_sync(j["update"](state, acc))
        if self.sparse:
            jax.device_get(j["decide"](state, dg))
            for i in range(len(self.tiers)):
                q, start, deg = j[f"s_load{i}"](state, dg)
                cand, dst = j[f"s_comp{i}"](state, q, start, deg, dg)
                hard_sync(j["s_update"](state, cand, dst))

    def phase_step(self, state: PushState):
        """One iteration as separately-timed load/comp/update dispatches —
        the reference's per-iteration `-verbose` breakdown
        (sssp/sssp_gpu.cu:516-518: activeNodes, loadTime, compTime,
        updateTime). load = frontier staging (queue build or frontier
        gather), comp = relax + reduce, update = value merge + new
        frontier. Returns (new_state, active, info dict). Phase dispatch
        breaks fusion; use run() for timed fixpoints."""
        from lux_tpu.utils.timing import Timer

        j = self._phase_jits()
        dg = self._dg
        tier = int(
            jax.device_get(j["decide"](state, dg))
        ) if self.sparse else 0
        times = {}
        if tier > 0:
            i = tier - 1
            with Timer() as t:
                q, start, deg = hard_sync(j[f"s_load{i}"](state, dg))
            times["loadTime"] = t.elapsed
            with Timer() as t:
                cand, dst = hard_sync(
                    j[f"s_comp{i}"](state, q, start, deg, dg)
                )
            times["compTime"] = t.elapsed
            with Timer() as t:
                new_state, cnt = hard_sync(j["s_update"](state, cand, dst))
            times["updateTime"] = t.elapsed
        else:
            with Timer() as t:
                loaded = hard_sync(j["d_load"](state, dg))
            times["loadTime"] = t.elapsed
            with Timer() as t:
                acc = hard_sync(j["d_comp"](*loaded, dg))
            times["compTime"] = t.elapsed
            with Timer() as t:
                new_state, cnt = hard_sync(j["update"](state, acc))
            times["updateTime"] = t.elapsed
        times["branch"] = _tier_label(self.tiers, tier)
        return new_state, int(jax.device_get(cnt)), times

    def init_state(self, **kw) -> PushState:
        with spans.span("engine.init"):
            vals = jax.device_put(
                jnp.asarray(self.program.init_values(self.graph, **kw)),
                self.device,
            )
            fr = jax.device_put(
                jnp.asarray(self.program.init_frontier(self.graph, **kw)),
                self.device,
            )
            return PushState(vals, fr)

    def step(self, state: PushState):
        return self._step(state, self._dg)

    def run(
        self,
        max_iters: Optional[int] = None,
        state: Optional[PushState] = None,
        chunk: int = 16,
        recorder=None,
        **init_kw,
    ):
        """Iterate to fixpoint; returns (final_state, iterations_run).

        Runs ``chunk`` iterations per device dispatch with on-device early
        exit; the host reads back one count batch per chunk. The number of
        iterations served by the sparse (push-direction) branch is left in
        ``self.sparse_iters`` after each run."""
        with spans.span("engine.run"):
            if state is None:
                state = self.init_state(**init_kw)
            rec = recorder if recorder is not None else recorder_for(
                "push", self.graph, self.program)
            rec.start()
            if rec.enabled:
                rec.record_compile(consume_compile_seconds(self))
                rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                    self.graph.nv, self.graph.ne))
            state, total, self.sparse_iters = _run_to_fixpoint(
                self._multi, state, max_iters, chunk, recorder=rec
            )
            rec.finish()
            return state, total

    def _multi(self, state: PushState, limit: int, k: int):
        return self._multi_jit(state, self._dg, k, limit=jnp.int32(limit))

    def warmup(self, chunk: int = 16, **init_kw):
        """Run one throwaway iteration through the exact run() path so
        ELAPSED TIME excludes XLA compilation AND first-transfer setup."""
        with spans.span("engine.warmup"), compile_phase("warmup"), \
                Timer() as t:
            _run_to_fixpoint(self._multi, self.init_state(**init_kw), 1, chunk)
        note_compile_seconds(self, t.elapsed)

    def trace_step(self, **init_kw):
        """luxlint-IR hook (analysis/ir.py): the jitted single-iteration
        step with example args exactly as step() passes them."""
        return {
            "kind": "push",
            "fn": self._step,
            "args": (self.init_state(**init_kw), self._dg),
            "donate": (0,),
            "carry": (0,),
            "sharded": False,
        }


def _run_to_fixpoint(multi, state, max_iters, chunk, recorder=None):
    rec = recorder if recorder is not None else NULL_RECORDER
    # ``multi`` is an executor's bound ``_multi``: its counters' label.
    engine = engine_label(multi.__self__)
    n_chunks = metrics.counter("lux_engine_chunks_total", {"engine": engine})
    n_iters = {
        b: metrics.counter("lux_engine_iterations_total",
                           {"engine": engine, "branch": b})
        for b in ("dense", "sparse")
    }
    total = 0
    sparse_total = 0
    while True:
        limit = chunk if max_iters is None else min(chunk, max_iters - total)
        if limit <= 0:
            break
        k = chunk
        with spans.span("push.chunk"):
            state, counts, flags, done, last = multi(state, limit, k)
        # One batched transfer: every device_get is a host round-trip,
        # so fetch everything together.
        with spans.span("push.readback"):
            # luxlint: disable=LUX001 -- one batched fetch per chunk (not per iter) is the fixpoint design
            counts_h, flags_h, done_h, last_h = jax.device_get(
                (counts, flags, done, last)
            )
        done_i = int(np.asarray(done_h).reshape(-1)[0])
        last_i = int(np.asarray(last_h).reshape(-1)[0])
        fl = np.asarray(flags_h).reshape(-1, k)[0][:done_i]
        n_sparse = int(fl.sum())
        sparse_total += n_sparse
        total += done_i
        n_chunks.inc()
        n_iters["sparse"].inc(n_sparse)
        n_iters["dense"].inc(done_i - n_sparse)
        # counts is (k,) single-device or psum-replicated (P, k) sharded;
        # row 0 is the global post-step active count either way.
        cnts = np.asarray(counts_h).reshape(-1, k)[0][:done_i]
        rec.flush(total, frontier_sizes=cnts, sparse_flags=fl)
        if last_i == 0 or done_i == 0:
            break
    with spans.span("engine.sync"):
        hard_sync(state.values)
    rec.flush(total)
    return state, total, sparse_total


class MultiSourcePushExecutor:
    """Dense push executor over K value columns: one O(ne) sweep serves K
    independent root queries (multi-source micro-batching, the serving
    layer's headline mechanism — serve/batcher.py).

    State arrays are ``(nv, K)``; the pull-direction dense iteration
    vectorizes untouched — the per-edge gather ``values[col_src]`` becomes
    a ``(ne, K)`` row gather and the segment reduction keeps its trailing
    lane axis, so the marginal cost of lane k+1 is one more VPU lane, not
    another sweep. Per-lane fixpoints are monotone, so running every lane
    until ALL are quiet (one shared halt count) only repeats no-op
    iterations on early finishers — the same argument that justifies the
    chunked speculative window.

    Sparse/blocked strategies are single-lane-shaped (queue compaction and
    bit-packing assume scalar values), so this executor is dense-only; the
    serving layer routes single queries to the adaptive ``PushExecutor``
    and batches here.
    """

    def __init__(self, graph: Graph, program: PushProgram, k: int,
                 device=None):
        if k < 1:
            raise ValueError(f"batch width k must be >= 1 (got {k})")
        if program.needs_weights and graph.weights is None:
            raise ValueError(f"{program.name} requires an edge-weighted graph")
        self.graph = graph
        self.program = program
        self.k = int(k)
        self.device = device
        put = lambda x: jax.device_put(jnp.asarray(x), device)
        dg = {
            "col_src": put(graph.col_src.astype(np.int32)),
            "seg_ids": put(graph.col_dst),
        }
        if graph.weights is not None:
            dg["weights"] = put(graph.weights)
        self._dg = dg
        self.sparse_iters = 0   # API parity with PushExecutor (always 0)
        self._multi_jit = jax.jit(
            self._chunk_impl, donate_argnums=0, static_argnums=2
        )

    def init_state(self, starts) -> PushState:
        """State with one value/frontier column per root in ``starts``.
        Fewer than k roots are right-padded by repeating the last root —
        duplicate lanes converge identically, so padding never changes
        results or iteration counts, and the executable stays one shape."""
        starts = list(starts)
        if not 1 <= len(starts) <= self.k:
            raise ValueError(
                f"need 1..{self.k} roots, got {len(starts)}"
            )
        starts = starts + [starts[-1]] * (self.k - len(starts))
        prog = self.program
        vals = np.stack(
            [prog.init_values(self.graph, start=s) for s in starts], axis=1
        )
        fr = np.stack(
            [prog.init_frontier(self.graph, start=s) for s in starts], axis=1
        )
        return PushState(
            jax.device_put(jnp.asarray(vals), self.device),
            jax.device_put(jnp.asarray(fr), self.device),
        )

    def _one_iter(self, state: PushState, dg):
        prog = self.program
        src_vals = state.values[dg["col_src"]]        # (ne, K)
        src_front = state.frontier[dg["col_src"]]
        w = dg.get("weights")
        cand = prog.relax(src_vals, None if w is None else w[:, None])
        ident = identity_for(prog.combiner, cand.dtype)
        cand = jnp.where(src_front, cand, ident)
        acc = segment_reduce(
            cand, dg["seg_ids"], num_segments=self.graph.nv,
            kind=prog.combiner,
        )
        if prog.combiner == "min":
            new = jnp.minimum(state.values, acc)
        else:
            new = jnp.maximum(state.values, acc)
        frontier = new != state.values
        return (
            PushState(new, frontier),
            frontier.sum(dtype=jnp.int32),
            jnp.int32(0),
        )

    def _chunk_impl(self, state: PushState, dg, k: int, limit=None):
        return _chunk_while(
            lambda st: self._one_iter(st, dg), state, k, limit
        )

    def _multi(self, state: PushState, limit: int, k: int):
        return self._multi_jit(state, self._dg, k, limit=jnp.int32(limit))

    def run(
        self,
        starts,
        max_iters: Optional[int] = None,
        chunk: int = 16,
        recorder=None,
        state: Optional[PushState] = None,
    ):
        """Run all roots in ``starts`` to their shared fixpoint; returns
        (final_state, iterations_run). Column j of ``state.values`` is
        root ``starts[j]``'s result — bit-identical to a single-source
        ``PushExecutor`` run from that root (tests/test_serve.py).

        ``state`` warm-starts the sweep from a caller-built (nv, k)
        state instead of ``init_state(starts)`` — the incremental
        executor seeds per-lane values/frontiers from a previous
        snapshot's fixpoint. Shapes must match ``init_state``'s so the
        warmed executable is reused."""
        if state is None:
            state = self.init_state(starts)
        rec = recorder if recorder is not None else recorder_for(
            "push_multi", self.graph, self.program)
        rec.start()
        if rec.enabled:
            rec.record_compile(consume_compile_seconds(self))
            rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                self.graph.nv, self.graph.ne, k=self.k))
        state, total, _ = _run_to_fixpoint(
            self._multi, state, max_iters, chunk, recorder=rec
        )
        rec.finish()
        return state, total

    def warmup(self, chunk: int = 16, start: int = 0):
        """Compile the chunked executable outside any timed/served
        request (the serving pool calls this once per keyed engine)."""
        with Timer() as t:
            _run_to_fixpoint(
                self._multi, self.init_state([start]), 1, chunk
            )
        note_compile_seconds(self, t.elapsed)

    def trace_step(self, start: int = 0, **init_kw):
        """luxlint-IR hook (analysis/ir.py). The chunk executable takes
        a static width k and a dynamic iteration limit the example args
        can't carry, so `call`/`lower` close over them explicitly."""
        state = self.init_state([start])
        fn, dg, k = self._multi_jit, self._dg, self.k
        lim = jnp.int32(1)
        return {
            "kind": "push_multi",
            "fn": fn,
            "args": (state, dg),
            "call": lambda st, d: fn(st, d, k, limit=lim),
            "lower": lambda: fn.lower(state, dg, k, limit=lim),
            "donate": (0,),
            "carry": (0,),
            "sharded": False,
            "k": k,
        }

    def values_for(self, state: PushState, j: int) -> np.ndarray:
        """Host copy of lane ``j``'s value column."""
        return np.asarray(jax.device_get(state.values[:, j]))


def _validated_sg(sg: Optional[ShardedGraph], graph: Graph,
                  num_parts: int) -> ShardedGraph:
    """Accept a prebuilt partition plan (the serving layer caches one
    per (fingerprint, parts) — serve/mesh.py) after checking it really
    describes this executor's graph and mesh; build fresh otherwise."""
    if sg is None:
        return ShardedGraph.build(graph, num_parts)
    if sg.num_parts != num_parts:
        raise ValueError(
            f"prebuilt ShardedGraph has {sg.num_parts} parts, mesh has "
            f"{num_parts}"
        )
    if sg.graph is not graph:
        raise ValueError(
            "prebuilt ShardedGraph was built from a different Graph "
            "object — edge indices and partition bounds would not "
            "match this executor's graph"
        )
    return sg


class ShardedPushExecutor:
    """Push executor over an N-device mesh with the same two per-iteration
    strategies as the single-device engine, chosen on-device each
    iteration (the reference's push engine is identical single- vs
    multi-GPU for the same reason, core/push_model.inl):

    - **dense**: all-gather full (values, frontier) shards and run the
      masked pull-direction relax over local CSC in-edges — the analogue
      of the whole-region old-value + old-frontier ZC reads
      (push_model.inl:234-241, 250-257).
    - **sparse**: each shard compacts its local frontier into a bounded
      queue, the queues (+ queued values) are all-gathered — the analogue
      of streaming every part's frontier chunk H2D (sssp_gpu.cu:424-458)
      — and each shard expands the global queue against its local edges
      via a per-shard CSR keyed by *global* source id (the replicated
      push row-ptr, push_model.inl:321-324,449-465). Exchange and
      expansion cost scale with the frontier, not nv/ne.

    The branch is picked by replicated collectives (pmax of local
    frontier counts, psum of frontier out-edges) so every shard takes the
    same ``lax.cond`` side."""

    BLOCKED_DENSE_MIN_NE = PushExecutor.BLOCKED_DENSE_MIN_NE

    def __init__(
        self,
        graph: Graph,
        program: PushProgram,
        mesh: Optional[Mesh] = None,
        num_parts: Optional[int] = None,
        sparse: bool = True,
        queue_frac: int = 16,       # per-shard queue = max_nv/queue_frac + slack
        edge_budget_frac: int = 8,  # per-shard edge budget = max_ne/frac
        blocked_dense: Optional[bool] = None,
        sg: Optional[ShardedGraph] = None,
    ):
        if program.needs_weights and graph.weights is None:
            raise ValueError(f"{program.name} requires an edge-weighted graph")
        self.mesh = mesh if mesh is not None else make_mesh(num_parts)
        self.num_parts = self.mesh.devices.size
        self.graph = graph
        self.program = program
        self.sg = _validated_sg(sg, graph, self.num_parts)
        sh = parts_sharding(self.mesh)
        put = lambda x: jax.device_put(jnp.asarray(x), sh)

        # Blocked dense path, distributed: same single-vs-multi-identical
        # contract as the reference (core/push_model.inl) — each shard
        # serves its edges from the all-gathered packed (value,
        # frontier-bit) table via row gathers + lane select and reduces
        # with the segmented min/max scan over its local CSC.
        log = get_logger("engine")
        self.exchange_mode, self._xplan = resolve_exchange(self.sg, log)
        flat_nv = self.num_parts * self.sg.max_nv
        if blocked_dense is None:
            # The packed blocked path gathers the whole (value | frontier
            # bit) table; it has no needed-rows form, so the compact
            # exchange takes precedence when both are viable.
            blocked_dense = (
                self._xplan is None
                and graph.ne >= self.BLOCKED_DENSE_MIN_NE
                and getattr(program, "packable_values", False)
                and program.value_dtype == jnp.uint32
                and flat_nv < 2**31
                and self.sg.max_ne < 2**31
            )
        elif blocked_dense:
            if self._xplan is not None:
                log.info(
                    "LUX_EXCHANGE=compact has no packed blocked form; "
                    "explicit blocked_dense=True keeps the full exchange"
                )
                self.exchange_mode, self._xplan = "full", None
            if program.value_dtype != jnp.uint32 or not getattr(
                program, "packable_values", False
            ):
                raise ValueError(
                    "blocked_dense needs a program declaring "
                    "packable_values (uint32 values < 2^31); "
                    f"{program.name} does not"
                )
            if flat_nv >= 2**31 or self.sg.max_ne >= 2**31:
                raise ValueError(
                    "blocked_dense needs P*max_nv and max_ne < 2^31 "
                    f"(got {flat_nv}, {self.sg.max_ne})"
                )
        self.blocked_dense = bool(blocked_dense)

        self._dg = {
            "vertex_mask": put(self.sg.vertex_mask),
        }
        if self.blocked_dense:
            P_, max_ne = self.num_parts, self.sg.max_ne
            C = 1 << 17
            pad = (-max_ne) % C
            k = (max_ne + pad) // C

            def chunked(a, fill=0):
                return np.pad(
                    a, ((0, 0), (0, pad)), constant_values=fill
                ).reshape(P_, k, C)

            self._dg["blk_sb"] = put(
                chunked(self.sg.src_pidx >> 7).astype(np.int32)
            )
            self._dg["blk_lane"] = put(
                chunked(self.sg.src_pidx & 127).astype(np.int8)
            )
            self._dg["blk_emask"] = put(chunked(self.sg.edge_mask))
            if self.sg.weights is not None:
                self._dg["blk_w"] = put(chunked(self.sg.weights))
            # Per-shard block-min layouts, stacked. The head/tail gather
            # tables stay unsegmented (seg_rows=0): per-part row splits
            # are data under shard_map's one-trace model, so static
            # segmentation is not available — same tradeoff as the
            # sharded Z-stream; warn when a shard's table would cross
            # the gather cliff.
            from lux_tpu.ops.segment import BlockMinLayout
            from lux_tpu.ops.tiled_spmv import _warn_big_table

            stacked = {}
            for p in range(P_):
                layout = BlockMinLayout(
                    self.sg.local_row_ptr[p], max_ne + pad, seg_rows=0
                )
                for k_, v in layout.device_arrays().items():
                    stacked.setdefault(k_, []).append(v)
            # seg_rows=0 ⇒ one unsegmented table; derive the bounds from
            # the (identical-across-parts) padded shapes rather than the
            # last loop iteration's layout.
            one = ((0, self.sg.max_nv, 0, (max_ne + pad) // 128),)
            self._bm_segs = (one, one)
            _warn_big_table(
                (max_ne + pad) // 128, "sharded push block-min",
                advice="; use more parts",
            )
            for k_, vs in stacked.items():
                self._dg[k_] = put(np.stack(vs))
        else:
            self._dg["src_pidx"] = put(self.sg.src_pidx)
            self._dg["dst_local"] = put(self.sg.dst_local)
            if self.sg.weights is not None:
                self._dg["weights"] = put(self.sg.weights)
        if self._xplan is not None:
            self._dg["xch_send"] = put(self._xplan.send_units)
            self._dg["xch_recv"] = put(self._xplan.recv_pos)
        self.sparse = sparse and graph.ne >= 1024
        if self.sparse:
            self.queue_cap, self.edge_budget = _sparse_budgets(
                self.sg.max_nv, self.sg.max_ne, queue_frac, edge_budget_frac
            )
            self.tiers = _make_tiers(
                self.queue_cap, self.edge_budget, self.sg.max_nv,
                self.sg.max_ne, self.blocked_dense,
            )
            self.sparse = bool(self.tiers)
        if self.sparse:
            prp, pdst, pw = self.sg.build_push_csr()
            self._dg["push_row_ptr"] = put(prp)
            self._dg["push_dst_local"] = put(pdst)
            if pw is not None:
                self._dg["push_weights"] = put(pw)
            self._dg["out_degrees"] = put(self.sg.out_degrees)
            self._dg["row_left"] = put(
                self.sg.row_left.astype(np.int32)[:, None]
            )
        self._specs = {k: P(PARTS_AXIS) for k in self._dg}
        self.sparse_iters = 0       # sparse-branch count of the last run()
        state_spec = PushState(P(PARTS_AXIS), P(PARTS_AXIS))
        mapped = jax.shard_map(
            self._shard_step,
            mesh=self.mesh,
            in_specs=(state_spec, self._specs),
            out_specs=(state_spec, P(PARTS_AXIS)),
        )
        self._step = jax.jit(mapped, donate_argnums=0)
        self._chunk_cache = {}

    # Dense-iteration phases (load/comp/update split so phase_step can
    # dispatch them separately for `-verbose`; _iter_block composes them
    # into the fused step).

    def _dense_load(self, state: PushState, dg):
        """Exchange: all-gather the value+frontier shards (the whole-
        region ZC reads, push_model.inl:234-241,250-257)."""
        v = state.values[0]
        f = state.frontier[0]
        if self.blocked_dense:
            packed = v.astype(jnp.uint32) | (f.astype(jnp.uint32) << 31)
            allp = jax.lax.all_gather(packed, PARTS_AXIS).reshape(-1)
            x2d = jnp.pad(allp, (0, (-allp.shape[0]) % 128)).reshape(-1, 128)
            return (x2d,)
        if self._xplan is not None:
            # Compact exchange: fixed-capacity all_to_all of the rows
            # each receiver's real edges read (values + frontier bits),
            # scattered into the flat view at the positions src_pidx
            # indexes. Own-span rows stay zero — _dense_comp serves
            # local edges straight from the shard (the local-first
            # overlap branch), and unread remote rows carry frontier
            # False, so their candidates collapse to the identity.
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
        all_v = jax.lax.all_gather(v, PARTS_AXIS).reshape(-1)
        all_f = jax.lax.all_gather(f, PARTS_AXIS).reshape(-1)
        return all_v, all_f

    def _dense_comp(self, loaded, dg, state: Optional[PushState] = None):
        """Relax + per-local-destination reduction; returns (acc, edges)
        where edges counts this shard's frontier-sourced edges. Compact
        exchange passes ``state`` so local-source edges relax against the
        shard's own values — a branch with no collective dependence that
        XLA overlaps with the in-flight all_to_all — selected per edge
        against the remote branch before the unchanged reduction, which
        keeps the combine order (and hence results) bitwise identical."""
        prog = self.program
        max_nv = self.sg.max_nv
        if self.blocked_dense:
            from lux_tpu.ops.segment import segment_minmax_blockmin

            (x2d,) = loaded
            has_w = "blk_w" in dg
            chunks = (dg["blk_sb"][0], dg["blk_lane"][0], dg["blk_emask"][0])
            if has_w:
                chunks = chunks + (dg["blk_w"][0],)
            cands = _blocked_candidates(
                x2d, prog.relax, prog.combiner, chunks, has_w
            )
            head_segs, tail_segs = self._bm_segs
            la = {k: v[0] for k, v in dg.items() if k.startswith("bm_")}
            acc = segment_minmax_blockmin(
                cands, la, head_segs, tail_segs, prog.combiner,
            )
            return acc, jnp.int32(-1)   # frontier bits ride inside cands
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
            cand_l = prog.relax(v_loc[lidx], w)
            cand_r = prog.relax(all_v[sidx], w)
            ident = identity_for(prog.combiner, cand_l.dtype)
            cand_l = jnp.where(f_loc[lidx], cand_l, ident)
            cand_r = jnp.where(all_f[sidx], cand_r, ident)
            cand = jnp.where(local, cand_l, cand_r)
            src_front = jnp.where(local, f_loc[lidx], all_f[sidx])
        else:
            src_vals = all_v[sidx]
            src_front = all_f[sidx]
            cand = prog.relax(src_vals, w)
            ident = identity_for(prog.combiner, cand.dtype)
            cand = jnp.where(src_front, cand, ident)
        acc = segment_reduce(
            cand, dg["dst_local"][0], num_segments=max_nv + 1,
            kind=prog.combiner,
        )[:max_nv]
        # Edge counter excludes pad slots (their src_pidx is 0, so a
        # frontier-active vertex 0 would count every pad edge).
        real = dg["dst_local"][0] != max_nv
        return acc, (src_front & real).sum(dtype=jnp.int32)

    def _merge_update(self, state: PushState, acc, dg):
        """Value merge + new-frontier detection (shared by both dense
        variants)."""
        prog = self.program
        v = state.values[0]
        if prog.combiner == "min":
            new = jnp.minimum(v, acc)
        else:
            new = jnp.maximum(v, acc)
        vmask = dg["vertex_mask"][0]
        new = jnp.where(vmask, new, v)
        frontier = (new != v) & vmask
        cnt = frontier.sum(dtype=jnp.int32)
        return PushState(new[None], frontier[None]), cnt

    def _iter_block(self, state: PushState, dg):
        """One dense iteration on this shard's (1, ...) blocks; returns the
        new blocks and the *local* new-frontier count. prof regions tag
        the lowered ops per phase (static names — no cache-key change);
        the scopes do not fence the schedule, so compact-mode overlap
        still happens and a device profile can measure it."""
        with prof.region("lux.push_sharded.exchange"):
            loaded = self._dense_load(state, dg)
        with prof.region("lux.push_sharded.compute"):
            acc, _ = self._dense_comp(loaded, dg, state=state)
            return self._merge_update(state, acc, dg)

    # Sparse-iteration phases (same load/comp/update split).

    def _sparse_load(self, state: PushState, dg, Q=None):
        """Local frontier → bounded queue of global ids + values, then the
        queue all-gather — the analogue of per-part frontier-chunk
        streaming (sssp_gpu.cu:424-458); O(P*Q) bytes, not O(nv)."""
        nv, max_nv = self.graph.nv, self.sg.max_nv
        Q = self.queue_cap if Q is None else Q
        v = state.values[0]
        f = state.frontier[0]
        q_loc = jnp.nonzero(f, size=Q, fill_value=max_nv)[0].astype(jnp.int32)
        qv = v[jnp.clip(q_loc, 0, max_nv - 1)]
        base = dg["row_left"][0, 0]
        qg = jnp.where(q_loc >= max_nv, jnp.int32(nv), base + q_loc)
        all_q = jax.lax.all_gather(qg, PARTS_AXIS).reshape(-1)    # (P*Q,)
        all_qv = jax.lax.all_gather(qv, PARTS_AXIS).reshape(-1)
        return all_q, all_qv

    def _sparse_comp(self, all_q, all_qv, dg, E=None):
        """Expand the global queue against this shard's local edges via
        the global-src CSR (sentinel id nv reads deg == 0 — row_ptr is
        padded with two n_e entries). Returns (cand, dstl, edges)."""
        prog = self.program
        max_nv = self.sg.max_nv
        E = self.edge_budget if E is None else E
        rp = dg["push_row_ptr"][0]
        start = rp[all_q]
        deg = rp[all_q + 1] - start
        slot, edge_pos, emask = _queue_edge_slots(
            start, deg, E, self.sg.max_ne
        )
        dstl = dg["push_dst_local"][0][edge_pos]
        w = (
            dg["push_weights"][0][edge_pos]
            if "push_weights" in dg else None
        )
        cand = prog.relax(all_qv[slot], w)
        ident = identity_for(prog.combiner, cand.dtype)
        cand = jnp.where(emask, cand, ident)
        dstl = jnp.where(emask, dstl, max_nv)
        return cand, dstl, emask.sum(dtype=jnp.int32)

    def _sparse_update(self, state: PushState, cand, dstl, dg):
        """Deterministic scatter-combine into local values (pad slot
        max_nv swallows masked edges) + new-frontier detection."""
        prog = self.program
        max_nv = self.sg.max_nv
        v = state.values[0]
        ident = identity_for(prog.combiner, cand.dtype)
        vv = jnp.concatenate([v, jnp.full((1,), ident, v.dtype)])
        if prog.combiner == "min":
            new = vv.at[dstl].min(cand)[:max_nv]
        else:
            new = vv.at[dstl].max(cand)[:max_nv]
        vmask = dg["vertex_mask"][0]
        new = jnp.where(vmask, new, v)
        frontier = (new != v) & vmask
        cnt = frontier.sum(dtype=jnp.int32)
        return PushState(new[None], frontier[None]), cnt

    def _sparse_block(self, state: PushState, dg, Q=None, E=None):
        """One sparse iteration (fused composition of the three phases)."""
        with prof.region("lux.push_sharded.exchange"):
            all_q, all_qv = self._sparse_load(state, dg, Q)
        with prof.region("lux.push_sharded.compute"):
            cand, dstl, _ = self._sparse_comp(all_q, all_qv, dg, E)
            return self._sparse_update(state, cand, dstl, dg)

    def _decide_block(self, state: PushState, dg):
        """Per-shard active count + the replicated tier index (0 = dense,
        i >= 1 = self.tiers[i-1], smallest adequate tier). The decision
        inputs are pmax/psum collectives, so every shard agrees: each
        shard's expansion is bounded by the GLOBAL frontier out-edge
        total (its local degrees sum to the global ones), so one
        conservative test keeps all shards inside the static budgets."""
        f = state.frontier[0]
        cnt_loc = f.sum(dtype=jnp.int32)
        if not self.sparse:
            return cnt_loc, jnp.int32(0)
        oe_loc = jnp.where(
            f, dg["out_degrees"][0].astype(jnp.uint32), 0
        ).sum(dtype=jnp.uint32)
        cnt_max = jax.lax.pmax(cnt_loc, PARTS_AXIS)
        oe_tot = jax.lax.psum(oe_loc, PARTS_AXIS)
        return cnt_loc, _tier_index(cnt_max, oe_tot, self.tiers)

    def _one_iter_block(self, state: PushState, dg):
        """Adaptive per-iteration branch; returns (state, local count,
        took_sparse)."""
        _, tier = self._decide_block(state, dg)
        if not self.sparse:
            st, cnt = self._iter_block(state, dg)
            return st, cnt, jnp.int32(0)
        branches = [lambda s: self._iter_block(s, dg)]
        for (Q, E) in self.tiers:
            branches.append(
                lambda s, Q=Q, E=E: self._sparse_block(s, dg, Q, E)
            )
        st, ncnt = jax.lax.switch(tier, branches, state)
        return st, ncnt, (tier > 0).astype(jnp.int32)

    def _shard_step(self, state: PushState, dg):
        new_state, cnt, _ = self._one_iter_block(state, dg)
        return new_state, cnt[None]

    def _shard_chunk(self, state: PushState, dg, limit, k: int):
        def one_iter(st):
            new_state, cnt_local, sp = self._one_iter_block(st, dg)
            return new_state, jax.lax.psum(cnt_local, PARTS_AXIS), sp

        st, counts, flags, done, last = _chunk_while(
            one_iter, state, k, limit[0]
        )
        return st, counts[None], flags[None], done[None], last[None]

    def _multi(self, state: PushState, limit: int, k: int):
        if k not in self._chunk_cache:
            state_spec = PushState(P(PARTS_AXIS), P(PARTS_AXIS))
            mapped = jax.shard_map(
                lambda st, dg, lim: self._shard_chunk(st, dg, lim, k),
                mesh=self.mesh,
                in_specs=(state_spec, self._specs, P()),
                out_specs=(
                    state_spec,
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

    def init_state(self, **kw) -> PushState:
        sh = parts_sharding(self.mesh)
        vals = jax.device_put(
            jnp.asarray(
                self.sg.to_padded(self.program.init_values(self.graph, **kw))
            ),
            sh,
        )
        fr = jax.device_put(
            jnp.asarray(
                self.sg.to_padded(self.program.init_frontier(self.graph, **kw))
            ),
            sh,
        )
        return PushState(vals, fr)

    def step(self, state: PushState):
        return self._step(state, self._dg)

    # -- per-shard `-verbose` phases -------------------------------------

    def _sharded_phase_jits(self):
        """Separately-dispatched load/comp/update phase executables, each
        a shard_map jit. SPMD phases run in lockstep across the mesh, so
        the measured walls are mesh-wide; per-shard variation shows up in
        the activeNodes/edges counters (which ARE per shard)."""
        if hasattr(self, "_pjits"):
            return self._pjits
        state_spec = PushState(P(PARTS_AXIS), P(PARTS_AXIS))
        specs = self._specs

        def sm(fn, in_specs, out_specs):
            # check_vma off: all_gather outputs are replicated by
            # construction but the static checker cannot infer it here.
            mapped = jax.shard_map(
                fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            )
            return jax.jit(mapped)

        n_loaded = 1 if self.blocked_dense else 2
        compact = self._xplan is not None
        if compact:
            # Compact flat tables are per-shard scatters, not the full
            # path's replicated all_gather output; and comp needs the
            # state for the local-first branch.
            d_load = sm(
                lambda st, dg: tuple(
                    a[None] for a in self._dense_load(st, dg)
                ),
                (state_spec, specs),
                tuple(P(PARTS_AXIS) for _ in range(n_loaded)),
            )
            d_comp = sm(
                lambda st, loaded, dg: tuple(
                    a[None] for a in self._dense_comp(
                        tuple(x[0] for x in loaded), dg, state=st
                    )
                ),
                (state_spec,
                 tuple(P(PARTS_AXIS) for _ in range(n_loaded)), specs),
                (P(PARTS_AXIS), P(PARTS_AXIS)),
            )
        else:
            d_load = sm(
                lambda st, dg: self._dense_load(st, dg),
                (state_spec, specs),
                tuple(P() for _ in range(n_loaded)),
            )
            d_comp = sm(
                lambda loaded, dg: tuple(
                    a[None] for a in self._dense_comp(loaded, dg)
                ),
                (tuple(P() for _ in range(n_loaded)), specs),
                (P(PARTS_AXIS), P(PARTS_AXIS)),
            )
        j = {
            "decide": sm(
                lambda st, dg: tuple(
                    a[None] for a in self._decide_block(st, dg)
                ),
                (state_spec, specs), (P(PARTS_AXIS), P(PARTS_AXIS)),
            ),
            "d_load": d_load,
            "d_comp": d_comp,
            "update": sm(
                lambda st, acc, dg: (
                    lambda r: (r[0], r[1][None])
                )(self._merge_update(st, acc[0], dg)),
                (state_spec, P(PARTS_AXIS), specs),
                (state_spec, P(PARTS_AXIS)),
            ),
        }
        if self.sparse:
            # One (s_load, s_comp) pair per size tier, so the phase
            # breakdown measures the SAME executables run() selects.
            for i, (Q, E) in enumerate(self.tiers):
                j[f"s_load{i}"] = sm(
                    lambda st, dg, Q=Q: self._sparse_load(st, dg, Q),
                    (state_spec, specs), (P(), P()),
                )
                j[f"s_comp{i}"] = sm(
                    lambda q, qv, dg, E=E: tuple(
                        a[None] for a in self._sparse_comp(q, qv, dg, E)
                    ),
                    (P(), P(), specs),
                    (P(PARTS_AXIS), P(PARTS_AXIS), P(PARTS_AXIS)),
                )
            j["s_update"] = sm(
                lambda st, cand, dstl, dg: (
                    lambda r: (r[0], r[1][None])
                )(self._sparse_update(st, cand[0], dstl[0], dg)),
                (state_spec, P(PARTS_AXIS), P(PARTS_AXIS), specs),
                (state_spec, P(PARTS_AXIS)),
            )
        self._pjits = j
        return j

    def phase_step(self, state: PushState):
        """One iteration as separately-dispatched load/comp/update phases
        — the reference's per-GPU `-verbose` breakdown
        (sssp/sssp_gpu.cu:516-518). Returns (new_state, total_active,
        info): info carries the (mesh-lockstep) phase walls, the branch
        taken, and a per-shard list with each shard's BEFORE-step
        activeNodes and frontier-sourced edge count (-1 where the packed
        blocked path folds frontier bits into the candidates). Phase
        dispatch breaks fusion; use run() for timed fixpoints."""
        from lux_tpu.utils.timing import Timer

        j = self._sharded_phase_jits()
        dg = self._dg
        cnt_before, tier = jax.device_get(j["decide"](state, dg))
        cnt_before = np.asarray(cnt_before).reshape(-1)
        tier = int(np.asarray(tier).reshape(-1)[0])
        times = {}
        if tier > 0:
            i = tier - 1
            with Timer() as t:
                all_q, all_qv = hard_sync(j[f"s_load{i}"](state, dg))
            times["loadTime"] = t.elapsed
            with Timer() as t:
                cand, dstl, edges = hard_sync(
                    j[f"s_comp{i}"](all_q, all_qv, dg)
                )
            times["compTime"] = t.elapsed
            with Timer() as t:
                new_state, cnt = hard_sync(
                    j["s_update"](state, cand, dstl, dg)
                )
            times["updateTime"] = t.elapsed
        else:
            with Timer() as t:
                loaded = hard_sync(j["d_load"](state, dg))
            times["loadTime"] = t.elapsed
            with Timer() as t:
                if self._xplan is not None:
                    acc, edges = hard_sync(j["d_comp"](state, loaded, dg))
                else:
                    acc, edges = hard_sync(j["d_comp"](loaded, dg))
            times["compTime"] = t.elapsed
            with Timer() as t:
                new_state, cnt = hard_sync(j["update"](state, acc, dg))
            times["updateTime"] = t.elapsed
        times["branch"] = _tier_label(self.tiers, tier)
        edges_h = np.asarray(jax.device_get(edges)).reshape(-1)
        times["shards"] = [
            {"part": p, "activeNodes": int(cnt_before[p]),
             "edges": int(edges_h[p])}
            for p in range(self.num_parts)
        ]
        total = int(np.asarray(jax.device_get(cnt)).sum())
        return new_state, total, times

    def warmup_phases(self, state: PushState):
        """Compile every phase executable — the dense branch plus every
        size tier, not just the branch the given state would take —
        outside any timed region
        (mirrors the single-device warmup_phases contract; otherwise the
        first iteration on the other branch would report seconds of XLA
        compile as its phase walls). ``state`` is read, never donated."""
        j = self._sharded_phase_jits()
        dg = self._dg
        jax.device_get(j["decide"](state, dg))
        loaded = j["d_load"](state, dg)
        if self._xplan is not None:
            acc, _ = j["d_comp"](state, loaded, dg)
        else:
            acc, _ = j["d_comp"](loaded, dg)
        hard_sync(j["update"](state, acc, dg))
        if self.sparse:
            for i in range(len(self.tiers)):
                all_q, all_qv = j[f"s_load{i}"](state, dg)
                cand, dstl, _ = j[f"s_comp{i}"](all_q, all_qv, dg)
                hard_sync(j["s_update"](state, cand, dstl, dg))

    def run(
        self,
        max_iters: Optional[int] = None,
        state: Optional[PushState] = None,
        chunk: int = 16,
        recorder=None,
        **init_kw,
    ):
        if state is None:
            state = self.init_state(**init_kw)
        rec = recorder if recorder is not None else recorder_for(
            "push_sharded", self.graph, self.program)
        rec.start()
        if rec.enabled:
            rec.record_compile(consume_compile_seconds(self))
            compact = self._xplan is not None
            rec.set_exchange_bytes(
                self.exchange_bytes_per_iter(),
                note="compact_all_to_all" if compact else "dense_estimate",
                parts=self.num_parts)
            if compact:
                rec.set_overlap(True)
            useful = engobs.useful_exchange(
                self.sg, 5,
                exchanged_rows=(self._xplan.exchanged_units_per_iter
                                if compact else None))
            if useful is not None:
                rec.set_useful_bytes(useful["useful_bytes_per_iter"],
                                     useful["ratio"])
            rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                self.graph.nv, self.graph.ne))
        if engobs.enabled():
            # Phase-fenced measurement fixpoint (LUX_ENGOBS); the off
            # path keeps the exact chunked fused executable below.
            state, total, self.sparse_iters = engobs.run_push_phased(
                self, state, max_iters, rec)
        else:
            state, total, self.sparse_iters = _run_to_fixpoint(
                self._multi, state, max_iters, chunk, recorder=rec
            )
        rec.finish()
        return state, total

    def warmup(self, chunk: int = 16, **init_kw):
        with Timer() as t:
            _run_to_fixpoint(self._multi, self.init_state(**init_kw), 1, chunk)
        note_compile_seconds(self, t.elapsed)

    def trace_step(self, **init_kw):
        """luxlint-IR hook (analysis/ir.py): the jitted shard_map step;
        sharded=True, so LUX105 demands a collective in the trace. The
        exchange_* keys feed LUX404-406 (``luxlint --exchange``)."""
        return {
            "kind": "push_sharded",
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

    def exchange_bytes_per_iter(self) -> int:
        """Dense-branch upper bound on cross-device traffic: each part
        broadcasts its candidate table (max_nv values @4B + 1B flag) to
        the P-1 others. The sparse branch moves less; per-branch
        accounting would need device readbacks the fixpoint loop doesn't
        do. This is the number PERF_NOTES.md's serve_bench.v1 evidence policy
        reports per device. Compact mode reports the packed figure — the
        fixed-capacity all_to_all payload that actually crosses the
        interconnect (still a dense-branch bound; sparse moves less)."""
        p = self.num_parts
        if self._xplan is not None:
            return self._xplan.exchange_bytes_per_iter(5)
        return p * (p - 1) * self.sg.max_nv * 5

    def gather_values(self, state: PushState) -> np.ndarray:
        return self.sg.from_padded(np.asarray(jax.device_get(state.values)))


class ShardedMultiSourcePushExecutor:
    """Multi-source push over an N-device mesh: K value lanes per vertex,
    dense pull-direction sweeps, one shared halt count — the sharded
    serving form of :class:`MultiSourcePushExecutor`, so K batched SSSP
    roots cost one distributed sweep instead of K.

    Layout composes the two parents directly: state arrays are
    ``(P, max_nv, K)`` (the partition plan's padded shards, lane axis
    trailing); each iteration all-gathers the (values, frontier) shards
    into a ``(P*max_nv, K)`` global table — the same whole-region
    exchange as :class:`ShardedPushExecutor`'s dense branch, K lanes
    wide — then relaxes over the local CSC shard with the per-lane
    identity mask and segment-reduces into local destinations. Per-lane
    fixpoints are monotone, so the shared halt count only repeats no-op
    iterations on early-finishing lanes (the single-device argument,
    unchanged by sharding).

    Dense-only, like the single-device multi-source engine: queue
    compaction and bit-packing are single-lane-shaped. The serving layer
    routes batch-of-one queries to the adaptive ``ShardedPushExecutor``
    and lands K-lane batches here.
    """

    def __init__(
        self,
        graph: Graph,
        program: PushProgram,
        k: int,
        mesh: Optional[Mesh] = None,
        num_parts: Optional[int] = None,
        sg: Optional[ShardedGraph] = None,
    ):
        if k < 1:
            raise ValueError(f"batch width k must be >= 1 (got {k})")
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
            self.sg, get_logger("engine"))
        if self._xplan is not None:
            dg["xch_send"] = put(self._xplan.send_units)
            dg["xch_recv"] = put(self._xplan.recv_pos)
        self._dg = dg
        self._specs = {key: P(PARTS_AXIS) for key in dg}
        self.sparse_iters = 0   # API parity with the sharded push engine
        state_spec = PushState(P(PARTS_AXIS), P(PARTS_AXIS))
        mapped = jax.shard_map(
            self._shard_step,
            mesh=self.mesh,
            in_specs=(state_spec, self._specs),
            out_specs=(state_spec, P(PARTS_AXIS)),
        )
        self._step = jax.jit(mapped, donate_argnums=0)
        self._chunk_cache = {}

    def _exchange_lanes_block(self, state: PushState, dg):
        """Exchange bracket: all-gather the (values, frontier) shards
        into (P*max_nv, K) global tables. Split from the compute bracket
        so ``phase_step`` can fence the collective separately; the fused
        ``_iter_block`` composes both, so the traced ops are identical.
        Compact mode moves only the needed rows — two fixed-capacity
        all_to_alls of packed (capacity, K) slabs scattered into the flat
        view; own-span and unread rows stay zero (frontier False), and
        the compute bracket's local-first select never reads them."""
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

    def _compute_lanes_block(self, state: PushState, all_v, all_f, dg):
        """Local-compute bracket: relax this shard's edges against the
        gathered tables, segment-reduce into local destinations, apply."""
        prog = self.program
        v = state.values[0]                            # (max_nv, K)
        sidx = dg["src_pidx"][0]
        w = dg["weights"][0] if "weights" in dg else None
        wk = None if w is None else w[:, None]
        if self._xplan is not None:
            # Local-first overlap: the local branch relaxes against the
            # shard's own lanes (no collective dependence), the remote
            # branch against the scattered table; the per-edge select
            # runs before the unchanged reduction, so the combine order
            # — and the results — stay bitwise identical to full.
            f_loc = state.frontier[0]
            own = jax.lax.axis_index(PARTS_AXIS)
            base = own * self.sg.max_nv
            local = (sidx >= base) & (sidx < base + self.sg.max_nv)
            lidx = jnp.clip(sidx - base, 0, self.sg.max_nv - 1)
            cand_l = prog.relax(v[lidx], wk)
            cand_r = prog.relax(all_v[sidx], wk)
            ident = identity_for(prog.combiner, cand_l.dtype)
            cand_l = jnp.where(f_loc[lidx], cand_l, ident)
            cand_r = jnp.where(all_f[sidx], cand_r, ident)
            cand = jnp.where(local[:, None], cand_l, cand_r)
        else:
            src_vals = all_v[sidx]                     # (max_ne, K)
            src_front = all_f[sidx]
            cand = prog.relax(src_vals, wk)
            ident = identity_for(prog.combiner, cand.dtype)
            cand = jnp.where(src_front, cand, ident)
        # Pad edges carry dst_local == max_nv: they land in the dropped
        # trash segment for every lane, so no edge mask is needed here
        # (same trick as the sharded single-source dense branch).
        acc = segment_reduce(
            cand, dg["dst_local"][0], num_segments=self.sg.max_nv + 1,
            kind=prog.combiner,
        )[: self.sg.max_nv]
        if prog.combiner == "min":
            new = jnp.minimum(v, acc)
        else:
            new = jnp.maximum(v, acc)
        vmask = dg["vertex_mask"][0][:, None]
        new = jnp.where(vmask, new, v)
        frontier = (new != v) & vmask
        return (
            PushState(new[None], frontier[None]),
            frontier.sum(dtype=jnp.int32),
        )

    def _iter_block(self, state: PushState, dg):
        """One dense K-lane iteration on this shard's (1, max_nv, K)
        blocks; returns the new blocks and the local new-frontier count
        (summed over lanes)."""
        with prof.region("lux.push_multi_sharded.exchange"):
            all_v, all_f = self._exchange_lanes_block(state, dg)
        with prof.region("lux.push_multi_sharded.compute"):
            return self._compute_lanes_block(state, all_v, all_f, dg)

    def _shard_step(self, state: PushState, dg):
        new_state, cnt = self._iter_block(state, dg)
        return new_state, cnt[None]

    def _shard_chunk(self, state: PushState, dg, limit, k: int):
        def one_iter(st):
            new_state, cnt_local = self._iter_block(st, dg)
            return (
                new_state,
                jax.lax.psum(cnt_local, PARTS_AXIS),
                jnp.int32(0),
            )

        st, counts, flags, done, last = _chunk_while(
            one_iter, state, k, limit[0]
        )
        return st, counts[None], flags[None], done[None], last[None]

    def _multi(self, state: PushState, limit: int, k: int):
        if k not in self._chunk_cache:
            state_spec = PushState(P(PARTS_AXIS), P(PARTS_AXIS))
            mapped = jax.shard_map(
                lambda st, dg, lim: self._shard_chunk(st, dg, lim, k),
                mesh=self.mesh,
                in_specs=(state_spec, self._specs, P()),
                out_specs=(
                    state_spec,
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

    def init_state(self, starts) -> PushState:
        """(P, max_nv, K) state with one lane per root; short batches are
        right-padded by repeating the last root (duplicate lanes converge
        identically — results, iteration counts, and the executable shape
        are all unchanged: the zero-recompile contract)."""
        starts = list(starts)
        if not 1 <= len(starts) <= self.k:
            raise ValueError(
                f"need 1..{self.k} roots, got {len(starts)}"
            )
        starts = starts + [starts[-1]] * (self.k - len(starts))
        prog = self.program
        vals = np.stack(
            [prog.init_values(self.graph, start=s) for s in starts], axis=1
        )
        fr = np.stack(
            [prog.init_frontier(self.graph, start=s) for s in starts], axis=1
        )
        sh = parts_sharding(self.mesh)
        return PushState(
            jax.device_put(jnp.asarray(self.sg.to_padded(vals)), sh),
            jax.device_put(jnp.asarray(self.sg.to_padded(fr)), sh),
        )

    def step(self, state: PushState):
        return self._step(state, self._dg)

    def _phase_jits(self):
        if hasattr(self, "_pjits"):
            return self._pjits
        state_spec = PushState(P(PARTS_AXIS), P(PARTS_AXIS))

        def sm(fn, in_specs, out_specs):
            # check_vma off: the gathered lane tables are replicated by
            # construction but the static checker cannot infer it.
            return jax.jit(jax.shard_map(
                fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            ))

        if self._xplan is not None:
            # Per-shard scattered tables, not the replicated all_gather
            # output: carry them shard-major between the two jits.
            self._pjits = {
                "exchange": sm(
                    lambda st, dg: tuple(
                        a[None] for a in self._exchange_lanes_block(st, dg)
                    ),
                    (state_spec, self._specs),
                    (P(PARTS_AXIS), P(PARTS_AXIS)),
                ),
                "compute": sm(
                    lambda st, av, af, dg: (
                        lambda ns, cnt: (ns, cnt[None])
                    )(*self._compute_lanes_block(st, av[0], af[0], dg)),
                    (state_spec, P(PARTS_AXIS), P(PARTS_AXIS), self._specs),
                    (state_spec, P(PARTS_AXIS)),
                ),
            }
            return self._pjits
        self._pjits = {
            "exchange": sm(
                lambda st, dg: self._exchange_lanes_block(st, dg),
                (state_spec, self._specs), (P(), P()),
            ),
            "compute": sm(
                lambda st, av, af, dg: (
                    lambda ns, cnt: (ns, cnt[None])
                )(*self._compute_lanes_block(st, av, af, dg)),
                (state_spec, P(), P(), self._specs),
                (state_spec, P(PARTS_AXIS)),
            ),
        }
        return self._pjits

    def phase_step(self, state: PushState):
        """One K-lane iteration as separately-dispatched exchange and
        compute brackets; returns (new_state, total_active, times) with
        the mesh-lockstep phase walls. Dense-only engine, so the branch
        is always "dense". Fencing breaks fusion — measurement mode."""
        j = self._phase_jits()
        times = {}
        with Timer() as t:
            all_v, all_f = hard_sync(j["exchange"](state, self._dg))
        times["loadTime"] = t.elapsed
        with Timer() as t:
            new_state, cnt = hard_sync(
                j["compute"](state, all_v, all_f, self._dg)
            )
        times["compTime"] = t.elapsed
        times["branch"] = "dense"
        total = int(np.asarray(jax.device_get(cnt)).sum())
        return new_state, total, times

    def warmup_phases(self, state: PushState):
        """Compile both phase executables outside any timed region
        (``state`` is read, never donated)."""
        j = self._phase_jits()
        all_v, all_f = j["exchange"](state, self._dg)
        hard_sync(j["compute"](state, all_v, all_f, self._dg))

    def run(
        self,
        starts,
        max_iters: Optional[int] = None,
        chunk: int = 16,
        recorder=None,
        state: Optional[PushState] = None,
    ):
        """Run all roots in ``starts`` to their shared fixpoint; returns
        (final_state, iterations_run). ``gather_values(state)[:, j]`` is
        root ``starts[j]``'s result — bit-identical to a single-source
        run from that root (integer min/max combiners commute with the
        partitioned reduction order)."""
        if state is None:
            state = self.init_state(starts)
        rec = recorder if recorder is not None else recorder_for(
            "push_multi_sharded", self.graph, self.program)
        rec.start()
        if rec.enabled:
            rec.record_compile(consume_compile_seconds(self))
            compact = self._xplan is not None
            rec.set_exchange_bytes(
                self.exchange_bytes_per_iter(),
                note="compact_all_to_all" if compact else "dense_estimate",
                parts=self.num_parts)
            if compact:
                rec.set_overlap(True)
            useful = engobs.useful_exchange(
                self.sg, 5 * self.k,
                exchanged_rows=(self._xplan.exchanged_units_per_iter
                                if compact else None))
            if useful is not None:
                rec.set_useful_bytes(useful["useful_bytes_per_iter"],
                                     useful["ratio"])
            rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                self.graph.nv, self.graph.ne, k=self.k))
        if engobs.enabled():
            # Phase-fenced measurement fixpoint (LUX_ENGOBS); off keeps
            # the exact chunked fused executable below.
            state, total, _ = engobs.run_push_phased(
                self, state, max_iters, rec)
        else:
            state, total, _ = _run_to_fixpoint(
                self._multi, state, max_iters, chunk, recorder=rec
            )
        rec.finish()
        return state, total

    def warmup(self, chunk: int = 16, start: int = 0):
        """Compile the chunked executable outside any timed/served
        request (the serving pool calls this once per keyed engine)."""
        with Timer() as t:
            _run_to_fixpoint(
                self._multi, self.init_state([start]), 1, chunk
            )
        note_compile_seconds(self, t.elapsed)

    def trace_step(self, start: int = 0, **init_kw):
        """luxlint-IR hook (analysis/ir.py): the jitted shard_map step;
        sharded=True, so LUX105 demands a collective in the trace. The
        exchange_* keys feed LUX404-406 (``luxlint --exchange``)."""
        return {
            "kind": "push_multi_sharded",
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

    def exchange_bytes_per_iter(self) -> int:
        """Per-iteration exchange figure. Full: the K-lane candidate
        table broadcast — (max_nv values @4B + 1B flag) x K lanes from
        each part to the P-1 others (a dense estimate). Compact: the
        measured packed payload the fixed-capacity all_to_alls move,
        K lanes x 5 bytes per exchanged row."""
        p = self.num_parts
        if self._xplan is not None:
            return self._xplan.exchange_bytes_per_iter(5 * self.k)
        return p * (p - 1) * self.sg.max_nv * self.k * 5

    def gather_values(self, state: PushState) -> np.ndarray:
        """(nv, K) host array: every lane's values in one device fetch
        (the serving layer slices columns out of this rather than paying
        one device round-trip per lane)."""
        return self.sg.from_padded(np.asarray(jax.device_get(state.values)))

    def values_for(self, state: PushState, j: int) -> np.ndarray:
        """Host copy of lane ``j``'s value column."""
        return self.sg.from_padded(
            np.asarray(jax.device_get(state.values[:, :, j]))
        )
