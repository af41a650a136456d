"""Sharded hybrid pull executor: MXU strips + lane-select tail over a mesh.

Distribution design — the two layouts are two independent resources and
are balanced separately:

- **Tail edges** are owner-computes over a NON-contiguous dst partition:
  128-blocks are snake-dealt to parts by descending tail cost (see
  PlanPartition), balancing both the per-part block counts (which size
  every padded array and the per-iteration collectives) and the tail
  bytes to ~1x — a contiguous cut on the degree-sorted order (the
  reference's scheme, pull_model.inl:108-131, which partitions natural
  order) could only trade ~2x padding against ~2x tail skew. Each
  part's tail edges are the gathered concatenation of its owned blocks'
  CSC ranges, dst-sorted within the part.
- **Strips** are sharded by strip index in equal counts (degree sort
  concentrates strips onto hub destinations, so a dst partition would
  hand one shard nearly all strip bytes — and SPMD padding would then
  charge every shard the worst shard's allocation). Each device computes
  a *partial global* accumulator over its strips; one ``psum`` merges
  them (an nv-sized f32 all-reduce, trivial next to the strip stream).
- The per-iteration value exchange is one ``all_gather`` of the value
  shards over ICI (the reference's whole-region zero-copy read,
  pull_model.inl:454-461, as a collective), after which every shard
  serves its row gathers from the full operand locally.
- New values are written only for owned destinations; the next
  iteration's all-gather is the publish step (no explicit scatter).

Per-shard arrays are stacked on a leading ``parts`` axis and the step runs
under ``jax.shard_map``, so the same code drives a real v5e-8 ICI ring or
the CPU-simulated mesh used in tests.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from lux_tpu.engine.program import PullProgram, VertexCtx
from lux_tpu.engine.pull import (
    hard_sync,
    make_fused_runner,
    run_maybe_fused,
)
from lux_tpu.engine.tiled import require_spmv_program
from lux_tpu.graph.graph import Graph
from lux_tpu.graph.partition import ExchangePlan
from lux_tpu.obs import (
    consume_compile_seconds,
    engobs,
    note_compile_seconds,
    prof,
    recorder_for,
)
from lux_tpu.utils.timing import Timer
from lux_tpu.ops.tiled_spmv import (
    BLOCK,
    DEFAULT_CHUNK_STRIPS,
    DEFAULT_CHUNK_TAIL,
    GATHER_TABLE_BYTES,
    DeviceLevel,
    HybridPlan,
    _warn_big_table as _warn_big_table_impl,
    block_level_boundaries,
    crossing_correction,
    lane_select_tail_sums,
    plan_hybrid,
    round_chunk,
    pack_strips,
    resolve_pack,
    strip_level_spmv,
    zstream_boundaries,
)
from lux_tpu.parallel.mesh import PARTS_AXIS, make_mesh, parts_sharding
from lux_tpu.parallel.shard import exchange_mode
from lux_tpu.utils.logging import get_logger


# ---------------------------------------------------------------------------
# Host-side partitioning of a HybridPlan
# ---------------------------------------------------------------------------

# Streamed-bytes cost of serving one tail edge: a 512 B row gather of the
# source block, amortized ~4x by destination locality in CSC order. The
# exact constant only shifts the balance point between strip-heavy and
# tail-heavy shards; 512 B keeps hub blocks (strip-dense) and leaf blocks
# (tail-dense) comparably weighted.
TAIL_EDGE_COST = 512


@dataclasses.dataclass(eq=False)
class PlanPartition:
    """Ownership of the plan's dst 128-blocks across P parts.

    Ownership is NON-contiguous: on the degree-sorted internal order the
    tail concentrates in the leaf (late) blocks, so any contiguous cut
    must trade padded-span blowup against tail imbalance (measured on
    RMAT24: the best contiguous balance is ~2x padding AND ~2x tail
    skew, and the padding directly inflates every per-iteration
    all-gather/reduce-scatter). Snake-dealing blocks by descending tail
    cost balances both to ~1x. The reference partitions the NATURAL
    vertex order where contiguous edge-balanced cuts suffice
    (pull_model.inl:108-131); degree sorting is what forces the
    generalization here."""

    owner: np.ndarray     # (nvb,) int32 owning part per block
    blocks: tuple         # P arrays: owned block ids, ascending
    max_nvb: int          # max blocks owned by any part (= ceil(nvb/P))

    @property
    def num_parts(self) -> int:
        return len(self.blocks)


def partition_plan(plan: HybridPlan, num_parts: int) -> PlanPartition:
    """Snake-deal dst 128-blocks to parts by descending tail-edge cost:
    part counts balance exactly (each part takes every P-th block of the
    cost-sorted order) and tail bytes balance to ~1x because adjacent
    cost ranks alternate direction each round.

    Strips are NOT in this cost: they are sharded separately by strip
    index (see module docstring), so the dst partition only has to
    balance the tail."""
    nvb = plan.nvb
    tail_per_v = np.diff(plan.tail_row_ptr).astype(np.int64)
    tail_per_blk = np.pad(
        tail_per_v, (0, nvb * BLOCK - plan.nv)
    ).reshape(nvb, BLOCK).sum(axis=1)

    order = np.argsort(-tail_per_blk, kind="stable")
    owner = np.empty(nvb, np.int32)
    ranks = np.arange(nvb, dtype=np.int64)
    rounds, pos = divmod(ranks, num_parts)
    snake = np.where(rounds % 2 == 0, pos, num_parts - 1 - pos)
    owner[order] = snake.astype(np.int32)
    blocks = tuple(
        np.flatnonzero(owner == p).astype(np.int64)
        for p in range(num_parts)
    )
    max_nvb = max(max(b.shape[0] for b in blocks), 1)
    return PlanPartition(owner=owner, blocks=blocks, max_nvb=int(max_nvb))


@dataclasses.dataclass
class ShardedLevel:
    """One strip level, stacked per part: arrays lead with (P, nchunks, C).

    Strips are split across parts in equal contiguous runs of the plan's
    (row-major sorted) strip order — NOT by destination — so boundaries
    stay against GLOBAL strip rows and each part's accumulator is a
    partial sum over the whole vertex space, merged by psum in the step
    (a part's boundary ranges clip to its local strip run; rows it
    doesn't touch collapse to empty ranges and contribute zero).
    Per-part crossing sets are padded to a common length with
    (idx=0, s0=0, s1=0) no-op entries. The Z-stream is one unsegmented
    gather table per shard (holding 1/P of the stream): P >= 4 keeps it
    under the big-table gather cliff at RMAT22+ scale; smaller part
    counts on huge graphs get a warning (see _warn_big_table)."""

    r: int
    segs: tuple
    strips: jnp.ndarray     # (P, K, C, r, 128) int8
    cols: jnp.ndarray       # (P, K, C) int32  GLOBAL src 128-block ids
    bnd_row: jnp.ndarray    # (P, nrb+1) int32
    bnd_grp: jnp.ndarray    # (P, nrb+1) int32
    xing_idx: jnp.ndarray   # (P, Xmax*r) int32
    xing_s0: jnp.ndarray    # (P, Xmax) int32
    xing_s1: jnp.ndarray    # (P, Xmax) int32
    packed: bool = False    # nibble-packed strips (see pack_strips)


@dataclasses.dataclass
class ShardedHybrid:
    levels: Tuple[ShardedLevel, ...]
    tail_sb: jnp.ndarray        # (P, K, C) int32 GLOBAL src block
    tail_lane: jnp.ndarray      # (P, K, C) int8
    tail_bnd_row: jnp.ndarray   # (P, max_nv+1) int32
    tail_bnd_grp: jnp.ndarray   # (P, max_nv+1) int32
    tail_xing_idx: jnp.ndarray  # (P, Xmax) int32
    tail_xing_s0: jnp.ndarray   # (P, Xmax) int32
    tail_xing_s1: jnp.ndarray   # (P, Xmax) int32
    tail_segs: tuple
    max_nvb: int             # blocks per shard (padded)


for _cls, _data, _meta in (
    (ShardedLevel,
     ["strips", "cols", "bnd_row", "bnd_grp",
      "xing_idx", "xing_s0", "xing_s1"],
     ["r", "segs", "packed"]),
    (ShardedHybrid,
     ["levels", "tail_sb", "tail_lane", "tail_bnd_row", "tail_bnd_grp",
      "tail_xing_idx", "tail_xing_s0", "tail_xing_s1"],
     ["tail_segs", "max_nvb"]),
):
    jax.tree_util.register_dataclass(_cls, data_fields=_data, meta_fields=_meta)


def _ranges_to_indices(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenate [starts[i], starts[i]+lens[i]) ranges into one index
    array (vectorized; the tail-edge gather list of a part's owned
    blocks)."""
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    offs = np.concatenate(([0], np.cumsum(lens)[:-1]))
    return (
        np.arange(total, dtype=np.int64)
        + np.repeat(starts - offs, lens)
    )


def _pad_stack(arrs, width: int, dtype=np.int32) -> np.ndarray:
    """Stack variable-length 1-D arrays into (P, width), zero-padded."""
    out = np.zeros((len(arrs), width), dtype)
    for p, a in enumerate(arrs):
        out[p, : a.shape[0]] = a
    return out


def _warn_big_table(nrows: int, what: str):
    """Sharded wrapper: per-shard Z-streams are single unsegmented gather
    tables (see ops.tiled_spmv._warn_big_table) — only small part counts
    (P <= 2) on huge graphs trip this."""
    _warn_big_table_impl(
        nrows, f"sharded {what} (per-shard)",
        advice="; use more parts or the single-device executor",
    )


class ShardedTiledExecutor:
    """Strip/lane-select hybrid SpMV over an N-device 1-D mesh.

    Same program contract as :class:`TiledPullExecutor` (sum combiner,
    identity contribution), but the value-array contract is the sharded
    one (like :class:`ShardedPullExecutor`): ``init_values``/``step``/
    ``run`` speak the (P, max_nv) padded degree-sorted device layout, and
    ``gather_values`` converts back to a global (nv,) EXTERNAL-order host
    array.
    """

    def __init__(
        self,
        graph: Graph,
        program: PullProgram,
        mesh: Optional[Mesh] = None,
        num_parts: Optional[int] = None,
        levels: Sequence[Tuple[int, int]] = ((8, 2),),
        budget_bytes: int = 8 << 30,
        chunk_strips: int = DEFAULT_CHUNK_STRIPS,
        chunk_tail: int = DEFAULT_CHUNK_TAIL,
        plan: Optional[HybridPlan] = None,
        pack=None,
    ):
        require_spmv_program(
            program, "ShardedTiledExecutor", "ShardedPullExecutor"
        )
        self.graph = graph
        self.program = program
        self.mesh = mesh if mesh is not None else make_mesh(num_parts)
        self.num_parts = self.mesh.devices.size
        self.plan = plan if plan is not None else plan_hybrid(
            graph, levels=levels, budget_bytes=budget_bytes
        )
        self.part = partition_plan(self.plan, self.num_parts)
        self._pack = pack
        self._build_device_data(chunk_strips, chunk_tail)

        specs = {k: P(PARTS_AXIS) for k in self._shard_args}
        # check_vma off: the scan carries inside strip_level_spmv /
        # lane_select_tail_sums are freshly-zeroed per-shard accumulators, which
        # the varying-manual-axes checker would otherwise insist on seeing
        # pvary-annotated at every scan site.
        mapped = jax.shard_map(
            self._shard_step,
            mesh=self.mesh,
            in_specs=(P(PARTS_AXIS), specs, P()),
            out_specs=P(PARTS_AXIS),
            check_vma=False,
        )
        jstep = jax.jit(mapped, donate_argnums=0)
        self._jstep = jstep   # bare jit, for trace_step / luxlint-IR
        self._step = lambda vals: jstep(vals, self._shard_args, self._replicated)
        self._jrun = make_fused_runner(mapped)

    # -- host-side shard construction ------------------------------------

    def _build_device_data(self, chunk_strips: int, chunk_tail: int):
        plan, part = self.plan, self.part
        pcount, max_nvb = self.num_parts, part.max_nvb
        self.max_nv = max_nvb * BLOCK
        sh = parts_sharding(self.mesh)
        put = lambda x: jax.device_put(jnp.asarray(x), sh)

        # Remote-read index (exchange ledger): which global src 128-blocks
        # each part's strips/tail actually gather, collected while the
        # host-side plan arrays are alive. Block granularity — the value
        # exchange is row-wise, but a block is the finest unit the tiled
        # gather addresses.
        read_blocks = [set() for _ in range(pcount)]

        slevels = []
        for lev in plan.levels:
            rpb = BLOCK // lev.r
            nrb_global = plan.nvb * rpb
            n = lev.rows.shape[0]
            cmax = -(-n // pcount) if n else 0
            # Equal contiguous runs of the sorted strip list; pad strips
            # are zero counts (contribute nothing). Boundaries are
            # computed per part against its LOCAL run (searchsorted on the
            # slice), so uncovered global rows collapse to empty ranges.
            c = round_chunk(chunk_strips, cmax, lev.r)
            cpad = -(-max(cmax, 1) // c) * c
            kch = cpad // c
            # One unsegmented Z-stream table per shard (segs is static
            # under shard_map, while per-part boundary splits are not).
            if lev.r < BLOCK:
                nrows = kch * (c // (BLOCK // lev.r) + 1) + 1
                segs = ((0, nrb_global + 1, 0, nrows),)
                _warn_big_table(nrows, f"strip level r={lev.r}")
            else:
                segs = ()
            st = np.zeros((pcount, cpad, lev.r, BLOCK), np.int8)
            co = np.zeros((pcount, cpad), np.int32)
            row = np.zeros((pcount, nrb_global + 1), np.int32)
            grp = np.zeros((pcount, nrb_global + 1), np.int32)
            xis, s0s, s1s = [], [], []
            for p in range(pcount):
                i0, i1 = p * cmax, min((p + 1) * cmax, n)
                k = max(i1 - i0, 0)
                st[p, :k] = lev.strips[i0:i1]
                co[p, :k] = lev.cols[i0:i1]
                if k:
                    read_blocks[p].update(
                        np.unique(lev.cols[i0:i1]).tolist())
                b = np.searchsorted(
                    lev.rows[i0:i1], np.arange(nrb_global + 1, dtype=np.int64)
                )
                if lev.r == BLOCK:
                    row[p], grp[p] = block_level_boundaries(b, c)
                    xi = s0 = s1 = np.zeros(0, np.int32)
                else:
                    row[p], grp[p], sub = zstream_boundaries(b, c, lev.r)
                    xi, s0, s1 = crossing_correction(sub, lev.r)
                xis.append(xi); s0s.append(s0); s1s.append(s1)
            xmax = max((a.shape[0] for a in s0s), default=0)
            lev_packed = (
                resolve_pack(self._pack, self.plan.cap) and lev.r % 2 == 0
            )
            rr = lev.r // 2 if lev_packed else lev.r
            if lev_packed:
                st = pack_strips(st)
            slevels.append(ShardedLevel(
                r=lev.r,
                segs=segs,
                packed=lev_packed,
                strips=put(st.reshape(pcount, kch, c, rr, BLOCK)),
                cols=put(co.reshape(pcount, kch, c)),
                bnd_row=put(row),
                bnd_grp=put(grp),
                xing_idx=put(_pad_stack(xis, xmax * lev.r)),
                xing_s0=put(_pad_stack(s0s, xmax)),
                xing_s1=put(_pad_stack(s1s, xmax)),
            ))

        # Tail slices + per-part static boundary gather data over the
        # LOCAL row ptrs. Ownership is non-contiguous (snake-dealt
        # blocks), so each part's local vertex space is the ascending
        # concatenation of its owned blocks' vertex ranges and its tail
        # edges the matching gather of per-block edge ranges — the
        # Z-stream machinery only needs the LOCAL stream and row ptrs,
        # which stay dst-sorted within the part by construction.
        tail_per_v = np.diff(plan.tail_row_ptr).astype(np.int64)
        self._vidx = []
        part_ne = []
        for p in range(pcount):
            B = part.blocks[p]
            vs = B * BLOCK
            vidx = (vs[:, None] + np.arange(BLOCK, dtype=np.int64)).ravel()
            vidx = vidx[vidx < plan.nv]
            # int32 suffices (nv < 2^31) and these persist per executor.
            self._vidx.append(vidx.astype(np.int32))
            part_ne.append(int(tail_per_v[vidx].sum()))
        mmax = max(part_ne) if part_ne else 0
        c_tail = round_chunk(chunk_tail, mmax, 1)
        mpad = -(-max(mmax, 1) // c_tail) * c_tail
        k2 = mpad // c_tail
        sb = np.zeros((pcount, mpad), np.int32)
        lane = np.zeros((pcount, mpad), np.int8)
        trow = np.zeros((pcount, self.max_nv + 1), np.int32)
        tgrp = np.zeros((pcount, self.max_nv + 1), np.int32)
        xis, s0s, s1s = [], [], []
        deg_out = np.ones((pcount, self.max_nv), np.int64)
        deg_in = np.zeros((pcount, self.max_nv), np.int64)
        vmask = np.zeros((pcount, self.max_nv), bool)
        for p in range(pcount):
            vidx = self._vidx[p]
            nvloc = vidx.shape[0]
            m = part_ne[p]
            starts = plan.tail_row_ptr[vidx]
            lens = tail_per_v[vidx]
            eidx = _ranges_to_indices(starts, lens)
            sb[p, :m] = plan.tail_sb[eidx]
            lane[p, :m] = plan.tail_lane[eidx]
            if m:
                read_blocks[p].update(np.unique(sb[p, :m]).tolist())
            rp = np.full(self.max_nv + 1, m, np.int64)
            np.cumsum(lens, out=rp[1 : nvloc + 1])
            rp[0] = 0
            trow[p], tgrp[p], sub = zstream_boundaries(rp, c_tail, 1)
            xi, s0, s1 = crossing_correction(sub, 1)
            xis.append(xi); s0s.append(s0); s1s.append(s1)
            deg_out[p, :nvloc] = plan.out_degrees[vidx]
            deg_in[p, :nvloc] = plan.in_degrees[vidx]
            vmask[p, :nvloc] = True
        xmax = max((a.shape[0] for a in s0s), default=0)
        cs_t = c_tail // BLOCK
        _warn_big_table(k2 * (cs_t + 1) + 1, "tail")

        counts = np.zeros((pcount, pcount), np.int64)
        for p, blocks in enumerate(read_blocks):
            if blocks:
                owners = part.owner[np.fromiter(
                    blocks, np.int64, len(blocks))]
                counts[p] += np.bincount(
                    owners, minlength=pcount).astype(np.int64) * BLOCK
        # (P, P) rows-read matrix in value rows, same shape/meaning as
        # ShardedGraph.remote_read_counts (engobs exchange ledger).
        self._remote_read_counts = counts

        self.shybrid = ShardedHybrid(
            levels=tuple(slevels),
            tail_sb=put(sb.reshape(pcount, k2, c_tail)),
            tail_lane=put(lane.reshape(pcount, k2, c_tail)),
            tail_bnd_row=put(trow),
            tail_bnd_grp=put(tgrp),
            tail_xing_idx=put(_pad_stack(xis, xmax)),
            tail_xing_s0=put(_pad_stack(s0s, xmax)),
            tail_xing_s1=put(_pad_stack(s1s, xmax)),
            tail_segs=((0, self.max_nv + 1, 0, k2 * (cs_t + 1) + 1),),
            max_nvb=max_nvb,
        )
        self._shard_args = {
            "out_degrees": put(deg_out.astype(np.int32)),
            "in_degrees": put(deg_in.astype(np.int32)),
            "vertex_mask": put(vmask),
        }
        # shybrid rides in the same dict so shard_map specs cover it.
        self._shard_args["hybrid"] = self.shybrid

        # Replicated helpers: block_map turns the gathered (P, max_nv)
        # shards into the global (nvb, 128) operand with one row gather
        # (block b lives at flat row owner[b]*max_nvb + its rank within
        # the owner's ascending block list); stack_map inverts it —
        # stacked slot p*max_nvb + i → the p-th part's i-th owned block
        # (or the sentinel zero row nvb for pad slots) — so the strip
        # accumulator can be rearranged into owner-stacked layout and
        # merged with a reduce-scatter instead of a full psum.
        rank_in_owner = np.zeros(plan.nvb, np.int64)
        stack = np.full(pcount * max_nvb, plan.nvb, np.int32)
        for p in range(pcount):
            B = part.blocks[p]
            rank_in_owner[B] = np.arange(B.shape[0], dtype=np.int64)
            stack[p * max_nvb : p * max_nvb + B.shape[0]] = B

        # Compact-exchange plan (LUX_EXCHANGE=compact): block-granular —
        # a 128-row block is the finest unit the tiled gather addresses,
        # so the needed-units lists are the ranks (within each owner's
        # stacked layout) of the blocks each part's strips/tail read.
        self._xplan = None
        if exchange_mode() == "compact" and pcount > 1:
            needs = [[np.zeros(0, np.int64)] * pcount for _ in range(pcount)]
            for q in range(pcount):
                blocks = np.fromiter(
                    read_blocks[q], np.int64, len(read_blocks[q]))
                owners_b = part.owner[blocks]
                ranks = rank_in_owner[blocks]
                for p in range(pcount):
                    needs[q][p] = np.sort(ranks[owners_b == p])
            # multiple=1: a unit is already a 128-row block, so there is
            # no lane-alignment reason to round the capacity up (the
            # default 8-unit rounding would sink profitability on small
            # meshes where max_nvb is itself single digits).
            xplan = ExchangePlan.from_needs(
                needs, max_nvb, pcount, unit_rows=BLOCK, multiple=1)
            if xplan.profitable:
                self._xplan = xplan
                self._shard_args["xch_send"] = put(xplan.send_units)
                self._shard_args["xch_recv"] = put(xplan.recv_pos)
            else:
                get_logger("engine").info(
                    "LUX_EXCHANGE=compact unprofitable for this tiled "
                    "plan (capacity %d >= %d blocks/part); "
                    "using the full exchange", xplan.capacity, max_nvb)
        self.exchange_mode = "compact" if self._xplan is not None else "full"
        repl = jax.sharding.NamedSharding(self.mesh, P())
        self._replicated = {
            "block_map": jax.device_put(
                jnp.asarray(
                    (part.owner.astype(np.int64) * max_nvb
                     + rank_in_owner).astype(np.int32)
                ),
                repl,
            ),
            "stack_map": jax.device_put(jnp.asarray(stack), repl),
        }

    # -- per-shard step (runs under shard_map) ---------------------------

    def _exchange_block(self, vals_blk, dg, repl):
        """Value exchange into the global (nvb, 128) gather operand.
        Full: all-gather the shards and rearrange via block_map. Compact:
        fixed-capacity all_to_all of the packed needed blocks, scattered
        into the owner-stacked view, own span written from the local
        shard. Blocks this part neither owns nor reads stay zero — the
        strips and tail never gather their columns (their block ids
        appear in no cols/tail_sb entry), and pad strip slots multiply
        them by all-zero coefficients, so the zeros never reach a sum."""
        v = vals_blk[0]                                   # (max_nv,) f32
        if self._xplan is None:
            gathered = jax.lax.all_gather(v, PARTS_AXIS)  # (P, max_nv)
            return gathered.reshape(-1, BLOCK)[repl["block_map"]]
        max_nvb = self.part.max_nvb
        v2d = v.reshape(max_nvb, BLOCK)
        sel = jnp.minimum(dg["xch_send"][0], max_nvb - 1)
        got = jax.lax.all_to_all(
            v2d[sel], PARTS_AXIS, split_axis=0, concat_axis=0, tiled=True)
        buf = jnp.zeros((self.num_parts * max_nvb + 1, BLOCK), v.dtype)
        buf = buf.at[dg["xch_recv"][0]].set(got)
        own = jax.lax.axis_index(PARTS_AXIS)
        buf = jax.lax.dynamic_update_slice(buf, v2d, (own * max_nvb, 0))
        return buf[:-1][repl["block_map"]]                # (nvb, 128)

    def _strips_block(self, x2d, dg, repl):
        """Strips: each shard sums ITS strips into a full-height partial
        accumulator, rearranges it into owner-stacked block layout (one
        cheap row gather; pad slots read the sentinel zero row), and a
        tiled reduce-scatter hands every shard just its reduced span —
        (P-1)*max_nv*4 ring bytes per device instead of the full-height
        psum's 2(P-1)/P*nv_g*4 that capped large-P scaling (the
        reference's per-part ZC publish never ships a full-nv array per
        GPU either, core/pull_model.inl:454-461)."""
        hy: ShardedHybrid = dg["hybrid"]
        nv_g = self.plan.nvb * BLOCK
        acc_g = jnp.zeros(nv_g, jnp.float32)
        for lev in hy.levels:
            dl = DeviceLevel(
                r=lev.r, segs=lev.segs, strips=lev.strips[0],
                cols=lev.cols[0], bnd_row=lev.bnd_row[0],
                bnd_grp=lev.bnd_grp[0], xing_idx=lev.xing_idx[0],
                xing_s0=lev.xing_s0[0], xing_s1=lev.xing_s1[0],
                packed=lev.packed,
            )
            acc_g = acc_g + strip_level_spmv(
                x2d, dl, self.plan.nvb * (BLOCK // lev.r)
            )
        acc2d = jnp.pad(acc_g.reshape(-1, BLOCK), ((0, 1), (0, 0)))
        stacked = acc2d[repl["stack_map"]]     # (P*max_nvb, 128)
        return jax.lax.psum_scatter(
            stacked, PARTS_AXIS, scatter_dimension=0, tiled=True
        ).reshape(-1)                          # (max_nv,) own span, reduced

    def _tail_block(self, x2d, dg):
        """Lane-select tail sums over this shard's owned dst span."""
        hy: ShardedHybrid = dg["hybrid"]
        return lane_select_tail_sums(
            x2d, hy.tail_sb[0], hy.tail_lane[0],
            hy.tail_bnd_row[0], hy.tail_bnd_grp[0],
            hy.tail_xing_idx[0], hy.tail_xing_s0[0], hy.tail_xing_s1[0],
            hy.tail_segs,
        )

    def _apply_block(self, vals_blk, acc, dg):
        v = vals_blk[0]
        ctx = VertexCtx(
            nv=self.graph.nv,
            out_degrees=dg["out_degrees"][0],
            in_degrees=dg["in_degrees"][0],
        )
        new = self.program.apply(v, acc, ctx)
        new = jnp.where(dg["vertex_mask"][0], new, v)
        return new[None]

    def _shard_step(self, vals_blk, dg, repl):
        # prof regions: the value exchange vs the strip/tail/apply local
        # work (the strips' psum_scatter rides the compute tag — it is
        # the reduction's own collective, not the value exchange).
        # Static names keep executable cache keys unchanged.
        with prof.region("lux.tiled_sharded.exchange"):
            x2d = self._exchange_block(vals_blk, dg, repl)
        with prof.region("lux.tiled_sharded.compute"):
            acc = self._strips_block(x2d, dg, repl)
            acc = acc + self._tail_block(x2d, dg)
            return self._apply_block(vals_blk, acc, dg)

    # -- driver (external vertex order at the API boundary) --------------

    def _to_padded_internal(self, ext_vals: np.ndarray) -> jnp.ndarray:
        internal = np.asarray(ext_vals)[self.plan.order]
        out = np.zeros((self.num_parts, self.max_nv), internal.dtype)
        for p in range(self.num_parts):
            vidx = self._vidx[p]
            out[p, : vidx.shape[0]] = internal[vidx]
        return jax.device_put(jnp.asarray(out), parts_sharding(self.mesh))

    # The CLI's host→device protocol (cli._host_to_device).
    host_to_device = _to_padded_internal

    def init_values(self) -> jnp.ndarray:
        return self._to_padded_internal(
            np.asarray(self.program.init_values(self.graph))
        )

    def step(self, vals):
        return self._step(vals)

    def phase_step(self, vals):
        """One iteration as separately-dispatched exchange/strips/tail/
        apply phases for `-verbose` attribution (phase names follow this
        engine's pipeline, the analogue of the reference's per-iteration
        breakdown, sssp/sssp_gpu.cu:516-518). SPMD phases are
        mesh-lockstep, so the walls are mesh-wide. Returns (new vals,
        {phase: seconds})."""
        if not hasattr(self, "_pjits"):
            specs = {k: P(PARTS_AXIS) for k in self._shard_args}

            def sm(fn, in_specs, out_specs):
                return jax.jit(jax.shard_map(
                    fn, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False,
                ))

            if self._xplan is not None:
                # Compact operands are per-shard scatters (each part's
                # unread blocks differ), not the replicated all_gather
                # output: carry them shard-major between phase jits.
                exchange = sm(
                    lambda v, dg, repl: self._exchange_block(
                        v, dg, repl)[None],
                    (P(PARTS_AXIS), specs, P()), P(PARTS_AXIS),
                )
                strips = sm(
                    lambda x, dg, repl: self._strips_block(
                        x[0], dg, repl)[None],
                    (P(PARTS_AXIS), specs, P()), P(PARTS_AXIS),
                )
                tail = sm(
                    lambda x, dg: self._tail_block(x[0], dg)[None],
                    (P(PARTS_AXIS), specs), P(PARTS_AXIS),
                )
            else:
                exchange = sm(
                    lambda v, dg, repl: self._exchange_block(v, dg, repl),
                    (P(PARTS_AXIS), specs, P()), P(),
                )
                strips = sm(
                    lambda x, dg, repl: self._strips_block(x, dg, repl)[None],
                    (P(), specs, P()), P(PARTS_AXIS),
                )
                tail = sm(
                    lambda x, dg: self._tail_block(x, dg)[None],
                    (P(), specs), P(PARTS_AXIS),
                )
            self._pjits = {
                "exchange": exchange,
                "strips": strips,
                "tail": tail,
                "apply": sm(
                    lambda v, a, b, dg: self._apply_block(
                        v, a[0] + b[0], dg
                    ),
                    (P(PARTS_AXIS), P(PARTS_AXIS), P(PARTS_AXIS), specs),
                    P(PARTS_AXIS),
                ),
            }
        j, times = self._pjits, {}
        dg, repl = self._shard_args, self._replicated
        with Timer() as t:
            x2d = hard_sync(j["exchange"](vals, dg, repl))
        times["exchange"] = t.elapsed
        with Timer() as t:
            acc_s = hard_sync(j["strips"](x2d, dg, repl))
        times["strips"] = t.elapsed
        with Timer() as t:
            acc_t = hard_sync(j["tail"](x2d, dg))
        times["tail"] = t.elapsed
        with Timer() as t:
            new = hard_sync(j["apply"](vals, acc_s, acc_t, dg))
        times["apply"] = t.elapsed
        return new, times

    def warmup(self):
        with Timer() as t:
            hard_sync(self.step(self.init_values()))
        note_compile_seconds(self, t.elapsed)

    def trace_step(self, **init_kw):
        """luxlint-IR hook (analysis/ir.py): the jitted shard_map step
        with its real argument tuple; sharded=True, so LUX105 demands
        the strip psum / exchange all-gather in the trace. The
        exchange_* keys feed LUX404-406 (``luxlint --exchange``)."""
        vals = self.init_values()
        return {
            "kind": "tiled_sharded",
            "fn": self._jstep,
            "args": (vals, self._shard_args, self._replicated),
            "donate": (0,),
            "carry": (0,),
            "sharded": True,
            "exchange_mode": self.exchange_mode,
            "exchange_bytes": self._exchange_bytes_per_iter(vals),
            "combiner": getattr(self.program, "combiner", "sum"),
            "value_dtype": np.dtype(vals.dtype).name,
            "num_parts": self.num_parts,
            "plan": self._xplan,
        }

    def _exchange_bytes_per_iter(self, vals) -> int:
        """ICI bytes for one iteration's exchange. Full: all-gather of
        the (P, max_nv) value stack — each part sends its shard to the
        P-1 others. Compact: the packed block all_to_all payload."""
        if self._xplan is not None:
            return self._xplan.exchange_bytes_per_iter(vals.dtype.itemsize)
        shard_elems = int(np.prod(vals.shape[1:])) if vals.ndim > 1 else 1
        p = self.num_parts
        return p * (p - 1) * shard_elems * vals.dtype.itemsize

    def run(self, num_iters: int, vals=None, flush_every: int = 8,
            recorder=None):
        if vals is None:
            vals = self.init_values()
        rec = recorder if recorder is not None else recorder_for(
            "tiled_sharded", self.graph, self.program)
        rec.start()
        if rec.enabled:
            rec.record_compile(consume_compile_seconds(self))
            compact = self._xplan is not None
            rec.set_exchange_bytes(
                self._exchange_bytes_per_iter(vals),
                note="compact_all_to_all" if compact else "all_gather",
                parts=self.num_parts)
            counts = getattr(self, "_remote_read_counts", None)
            if counts is not None:
                p = self.num_parts
                if compact:
                    exchanged = (self._xplan.exchanged_units_per_iter
                                 * self._xplan.unit_rows)
                else:
                    exchanged = p * (p - 1) * self.max_nv
                useful_rows = int(counts.sum() - np.trace(counts))
                if exchanged:
                    rec.set_useful_bytes(
                        useful_rows * int(vals.dtype.itemsize),
                        useful_rows / exchanged)
            rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                self.graph.nv, self.graph.ne, int(vals.dtype.itemsize)))
        if engobs.enabled():
            # Phase-fenced measurement run (LUX_ENGOBS); the off path
            # keeps the exact fused program below.
            out = engobs.run_pull_phased(self, vals, num_iters, rec)
        else:
            out = run_maybe_fused(
                self._jrun, self._step, vals, num_iters, flush_every,
                self._shard_args, self._replicated, recorder=rec,
            )
        rec.finish()
        return out

    def gather_values(self, vals) -> np.ndarray:
        """Sharded padded internal layout -> global EXTERNAL (nv,) array."""
        host = np.asarray(jax.device_get(vals))
        internal = np.empty(self.plan.nv, host.dtype)
        for p in range(self.num_parts):
            vidx = self._vidx[p]
            internal[vidx] = host[p, : vidx.shape[0]]
        return internal[self.plan.rank]
