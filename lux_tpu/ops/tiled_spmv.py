"""Hybrid SpMV: MXU strip-tiles + a lane-select tail (no scalar gathers).

The pull engine's hot loop is ``acc[dst] = Σ vals[src]`` over a static
graph (the reference's ``pr_kernel`` gather, pagerank/pagerank_gpu.cu:49-102).
Measured TPU v5e rates dictate the design:

- arbitrary 1-element gather: ~8.5 ns/edge (scalarized — the TPU VPU has
  no fine-grained HBM access; this is the reference's atomicAdd/gather
  world and the thing to design away);
- 128-wide **row** gather from the (nvb, 128) f32 value table at RMAT22:
  1.5 ns/row when XLA keeps the table in VMEM, 8.3-9.4 ns/row when it
  leaves it in HBM (one random 512 B HBM read per row). XLA's
  memory-space assignment picks per loop; in the fused step it gave the
  strip loop VMEM and the tail loop HBM;
- int8 strip matmul: streams at ~520 GB/s through the MXU.

So the only fast irregular primitive is "fetch an aligned 128-block",
and from VMEM.
Every edge is served by one of two such layouts:

1. **Strip levels** (:class:`StripLevel`): after degree-sort relabeling,
   hub-hub edges concentrate in (R,128) blocks of the adjacency matrix
   (R | 128). Each dense-enough strip is stored as an (R,128) int8 count
   matrix (multi-edges collapse into counts; cells overflowing 127 spill
   the excess to the tail, so the edge partition stays exact) and costs
   one row gather of the source block + an f32 broadcast-multiply-reduce
   on the VPU (measured 3x faster than the equivalent (R,128)@(128,2)
   bf16 MXU matmul, whose 2-column output tile starves the systolic
   array — and exact f32 per product instead of a hi/lo bf16 split).
   A strip of R·128 int8 bytes breaks even vs. per-edge work at about
   R/3 edges (R=8 → ≥3 edges).
   Per-destination reduction of strip contributions uses NO scatter:
   strips are sorted by destination strip-row, so each row's strips are
   a contiguous range with *plan-time-constant* boundaries; transposed
   Z-stream cumsums plus a static boundary gather-diff (see the layout
   notes above :func:`zstream_boundaries`) replace the 8-wide scatter
   rows of ``jax.ops.segment_sum`` that ran at scalar rate
   (measured 117 ms -> ~3 ms on RMAT22).

2. **Lane-select tail**: a leftover edge costs one 128-wide row read
   of its source block plus an on-the-fly one-hot lane selection
   (``where(lane == iota, row, 0).sum()``) — pure VPU, *exact* f32.
   On a TPU, where the table fits, the rows come from a Pallas kernel
   that holds the table whole in VMEM
   (:mod:`~lux_tpu.ops.lane_select_kernel`): the tail no longer depends
   on where XLA places the table. Edges stay CSC-sorted so the
   per-destination reduction is the scatter-free Z-stream boundary diff
   at the static ``tail_row_ptr`` boundaries.

This layout has no reference counterpart — it is what "gather" means on
hardware whose only irregular-access engines are aligned block DMA and
a 128x128 systolic array.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.graph.graph import Graph
from lux_tpu.obs.prof import region
from lux_tpu.ops.lane_select_kernel import (
    lane_select_pallas,
    lane_select_ref,
    vmem_table_fits,
)
from lux_tpu.ops.segment import cumsum0

BLOCK = 128
# Scan-chunk default for the tail body. Where the VMEM-table kernel
# serves the tail, the chunk only sizes the Z-stream emit (the kernel
# has its own grid step; 4096 to 65536 edges moved an RMAT22 iteration
# by under 0.3 ms on v5e). The row-gather body, kept where the table is
# too large for VMEM, last swept best at 2^17 (PERF_NOTES.md chunk
# sweep, timed with gather, select and cumsum in one scan).
DEFAULT_CHUNK_TAIL = 1 << 17
# Strip scan chunk default: strips prefer LARGER chunks than the tail
# (measured sweep: 13.6 ms at 2^15 vs 15.9 at 2^14 vs 31 at 2^11 on the
# RMAT22 (8,4) level; above 2^15 it drifts back up).
DEFAULT_CHUNK_STRIPS = 1 << 15


# ---------------------------------------------------------------------------
# Host-side planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class StripLevel:
    """Dense (r, 128) int8 count strips at one granularity."""

    r: int
    strips: np.ndarray       # (T, r, 128) int8
    rows: np.ndarray         # (T,) int32 dst strip index (sorted ascending)
    cols: np.ndarray         # (T,) int32 src 128-block index
    # Cached Σ strips so plan validation against graph.ne does not force
    # a full read of a (possibly mmap'd multi-GB) strip array.
    _edges: int = -1

    @property
    def nbytes(self) -> int:
        return self.strips.nbytes

    @property
    def edges(self) -> int:
        if self._edges < 0:
            self._edges = int(self.strips.sum(dtype=np.int64))
        return self._edges


@dataclasses.dataclass(eq=False)
class HybridPlan:
    """Host-side product of :func:`plan_hybrid` (numpy, internal ids).

    "Internal" vertex ids are positions in the degree-sorted order:
    ``order[p]`` is the external id at internal position p and
    ``rank[v]`` the internal position of external vertex v.
    """

    nv: int
    nvb: int                 # number of 128-blocks (nv padded)
    order: np.ndarray        # (nv,) int32
    rank: np.ndarray         # (nv,) int32
    levels: Tuple[StripLevel, ...]
    tail_sb: np.ndarray      # (M,) int32 src >> 7, CSC (dst-sorted) order
    tail_lane: np.ndarray    # (M,) int8  src & 127
    tail_row_ptr: np.ndarray  # (nv+1,) int64
    out_degrees: np.ndarray  # (nv,) int64, internal order
    in_degrees: np.ndarray   # (nv,) int64, internal order
    # Per-cell count cap used at plan time (excess spilled to the tail).
    # cap <= 15 makes every even-r level nibble-packable on device
    # (two strip rows per int8 byte — see pack_strips); legacy plans
    # used 127 and stay unpacked.
    cap: int = 15
    # Planning config, kept so plan caches can detect a changed request
    # (same r-cascade, different thresholds/budget). None/-1 on legacy
    # caches that predate these fields — treated as "unknown, servable".
    levels_spec: Optional[Tuple[Tuple[int, int], ...]] = None
    budget_bytes: int = -1

    @property
    def num_strips(self) -> int:
        return sum(lev.rows.shape[0] for lev in self.levels)

    @property
    def strip_bytes(self) -> int:
        return sum(lev.nbytes for lev in self.levels)

    @property
    def total_edges(self) -> int:
        return self.tail_sb.shape[0] + sum(lev.edges for lev in self.levels)

    @property
    def coverage(self) -> float:
        return 1.0 - self.tail_sb.shape[0] / max(self.total_edges, 1)


def _relabel(graph: Graph, reorder: str):
    nv = graph.nv
    if reorder == "degree":
        deg = graph.in_degrees + graph.out_degrees
        order = np.argsort(-deg, kind="stable").astype(np.int32)
    elif reorder == "natural":
        order = np.arange(nv, dtype=np.int32)
    else:
        raise ValueError(f"unknown reorder {reorder!r}")
    rank = np.empty(nv, np.int32)
    rank[order] = np.arange(nv, dtype=np.int32)
    return order, rank


# Edge-stream chunk for the banded planner passes (edges per chunk);
# per-chunk temporaries are a few int64/int32 arrays of this length.
_PLAN_CHUNK = 1 << 27
# The banded (streamed) counting path turns on above this edge count;
# below it the direct in-memory path is faster and simpler. Both are
# exact and produce identical plans (tested), so the threshold is a
# pure memory/speed trade.
_PLAN_BANDED_MIN_NE = 1 << 28


def _strip_counts_banded(graph: Graph, rank, r: int, nvb: int,
                         min_count: int, chunk: int = _PLAN_CHUNK):
    """(uniq strip ids, counts) for level 0, streamed in edge chunks.

    Exactly the multiset ``np.unique((d//r)*nvb + (s>>7), counts)``
    restricted to counts >= min_count, but without materializing any
    global int64 per-edge array: the direct form peaks at ~5x 8-byte
    edge arrays (OOM at RMAT27's 2^31 edges on a 133 GB host,
    VERDICT.md weak #4). Strategy: bucket each edge's src-block into
    band-grouped storage (one int32 edge array; the degree relabel
    destroys the CSC dst order, so grouping needs an explicit
    out-of-core pass), then run-length count per band range.

    Dropping counts < min_count here is selection-equivalent to the
    direct path's select-then-filter: strips below min_count can never
    be chosen, and stable tie order among survivors is preserved.

    Bound caveat: the counting batches take whole bands, so a single
    band holding more than ``chunk`` edges is processed in one piece
    (temporaries ~3x its size in int64). After the degree relabel the
    hottest dst rows share band 0; at RMAT27 the top-8 in-degrees sum
    to tens of millions of edges — well under the 2^27 default — so
    this stays a documented caveat, not a practical limit.
    """
    nv, ne = graph.nv, graph.ne
    nbands = (nv + r - 1) // r
    cs, cd = graph.col_src, graph.col_dst

    band_counts = np.zeros(nbands, np.int64)
    for lo in range(0, ne, chunk):
        b = rank[cd[lo:lo + chunk]] // r
        band_counts += np.bincount(b, minlength=nbands)
    band_off = np.zeros(nbands + 1, np.int64)
    np.cumsum(band_counts, out=band_off[1:])

    sblk_by_band = np.empty(ne, np.int32)
    fill = band_off[:-1].copy()
    for lo in range(0, ne, chunk):
        b = rank[cd[lo:lo + chunk]] // r
        sb = (rank[cs[lo:lo + chunk]] >> 7).astype(np.int32)
        idx = np.argsort(b, kind="stable")
        bs = b[idx]
        run_start = np.concatenate(
            [[0], np.flatnonzero(np.diff(bs)) + 1]
        ).astype(np.int64)
        run_len = np.diff(np.append(run_start, len(bs)))
        within = np.arange(len(bs), dtype=np.int64) - np.repeat(
            run_start, run_len
        )
        sblk_by_band[fill[bs] + within] = sb[idx]
        fill[bs[run_start]] += run_len

    uniq_parts, count_parts = [], []
    b_lo = 0
    while b_lo < nbands:
        b_hi = int(
            np.searchsorted(band_off, band_off[b_lo] + chunk, side="right")
        ) - 1
        b_hi = min(max(b_hi, b_lo + 1), nbands)
        e0, e1 = int(band_off[b_lo]), int(band_off[b_hi])
        if e1 > e0:
            band_of_edge = np.repeat(
                np.arange(b_lo, b_hi, dtype=np.int64),
                band_counts[b_lo:b_hi],
            )
            key = band_of_edge * nvb + sblk_by_band[e0:e1]
            uk, kc = np.unique(key, return_counts=True)
            if min_count > 1:
                keep = kc >= min_count
                uk, kc = uk[keep], kc[keep]
            uniq_parts.append(uk)
            count_parts.append(kc.astype(np.int64))
        b_lo = b_hi
    if not uniq_parts:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(uniq_parts), np.concatenate(count_parts)


def _cover_chunk(s, d, chosen, r: int, nvb: int, strip_bytes: int):
    """(covered cell keys, tail s, tail d) for one batch of edge ids.

    The single source of truth for the slot/covered/cell coverage
    computation — the direct plan path calls it once over all edges,
    the banded path once per chunk.
    """
    sid = (d // r).astype(np.int64) * nvb + (s >> 7)
    slot = np.searchsorted(chosen, sid)
    covered = slot < len(chosen)
    if len(chosen):
        covered &= np.equal(chosen[np.minimum(slot, len(chosen) - 1)], sid)
    cell = (d % r) * BLOCK + (s & 127)
    key = slot[covered] * strip_bytes + cell[covered]
    return key, s[~covered].astype(np.int32), d[~covered].astype(np.int32)


def _cover_banded(graph: Graph, rank, chosen, r: int, nvb: int,
                  strip_bytes: int, chunk: int = _PLAN_CHUNK):
    """Streamed coverage pass over the whole graph, per edge chunk, so
    only covered keys and the tail int32 ids persist."""
    ne = graph.ne
    cs, cd = graph.col_src, graph.col_dst
    keys, tail_s, tail_d = [], [], []
    for lo in range(0, ne, chunk):
        k, ts, td = _cover_chunk(
            rank[cs[lo:lo + chunk]], rank[cd[lo:lo + chunk]],
            chosen, r, nvb, strip_bytes,
        )
        keys.append(k)
        tail_s.append(ts)
        tail_d.append(td)
    return (
        np.concatenate(keys) if keys else np.zeros(0, np.int64),
        np.concatenate(tail_s) if tail_s else np.zeros(0, np.int32),
        np.concatenate(tail_d) if tail_d else np.zeros(0, np.int32),
    )


def plan_hybrid(
    graph: Graph,
    levels: Sequence[Tuple[int, int]] = ((8, 2),),
    budget_bytes: int = 8 << 30,
    reorder: str = "degree",
    cap: int = 15,
) -> HybridPlan:
    """Partition edges into strip levels + a lane-select tail. Exact.

    ``levels`` is a sequence of ``(r, min_count)`` pairs, consumed in
    order: each level takes the strips (at granularity r x 128) holding
    at least ``min_count`` still-unassigned edges, densest first, within
    what remains of ``budget_bytes`` (booked as unpacked int8 bytes).
    Cells holding more than ``cap`` parallel edges spill the excess to
    the tail; cap <= 15 keeps every even-r level nibble-packable at
    device-build time (opt-in, see DeviceHybrid.build).
    """
    nv = graph.nv
    nvb = (nv + BLOCK - 1) // BLOCK
    order, rank = _relabel(graph, reorder)

    # int32 vertex ids (nv < 2^31 per the format) — at RMAT27 the int64
    # version alone was 34 GB of host arrays; strip ids are computed in
    # int64 where the product can overflow. Above _PLAN_BANDED_MIN_NE
    # edges, level 0 streams the graph through the banded passes instead
    # of materializing s/d/strip_id at all (LUX_PLAN_BANDED=0/1
    # overrides); later levels run on the (much reduced or at least
    # already-paid-for) tail arrays.
    from lux_tpu.utils import flags

    knob = flags.tristate("LUX_PLAN_BANDED")
    banded0 = knob is True or (
        knob is None and graph.ne >= _PLAN_BANDED_MIN_NE
    )
    s = d = None
    if not banded0:
        s = rank[graph.col_src]
        d = rank[graph.col_dst]
    built = []
    remaining = budget_bytes

    for r, min_count in levels:
        if BLOCK % r:
            raise ValueError(f"strip height {r} must divide {BLOCK}")
        if s is None and (graph.ne == 0 or remaining <= 0):
            s = rank[graph.col_src]
            d = rank[graph.col_dst]
        if s is not None and (s.size == 0 or remaining <= 0):
            built.append(StripLevel(
                r=r,
                strips=np.zeros((0, r, BLOCK), np.int8),
                rows=np.zeros(0, np.int32),
                cols=np.zeros(0, np.int32),
            ))
            continue
        # Budget books UNPACKED int8 bytes — nibble packing is an opt-in
        # device-build decision (measured negative, see DeviceHybrid.build)
        # the planner cannot assume; packed builds simply use less HBM
        # than budgeted.
        strip_bytes = r * BLOCK
        if s is None:
            # Banded level 0: counts arrive prefiltered to >= min_count
            # (selection-equivalent to take-then-filter below, since
            # sub-min_count strips are never chosen and stable tie order
            # among survivors is preserved).
            uniq_ids, counts = _strip_counts_banded(
                graph, rank, r, nvb, min_count
            )
            take = np.argsort(-counts, kind="stable")[
                : max(remaining // strip_bytes, 0)
            ]
            chosen = np.sort(uniq_ids[take])
            key, tail_s, tail_d = _cover_banded(
                graph, rank, chosen, r, nvb, strip_bytes
            )
        else:
            strip_id = (d // r).astype(np.int64) * nvb + (s >> 7)
            uniq_ids, counts = np.unique(strip_id, return_counts=True)
            take = np.argsort(-counts, kind="stable")[
                : max(remaining // strip_bytes, 0)
            ]
            take = take[counts[take] >= min_count]
            chosen = np.sort(uniq_ids[take])
            del strip_id
            key, tail_s, tail_d = _cover_chunk(
                s, d, chosen, r, nvb, strip_bytes
            )
        uk, kc = np.unique(key, return_counts=True)
        strips = np.zeros((len(chosen), strip_bytes), np.int8)
        if len(uk):
            strips.ravel()[uk] = np.minimum(kc, cap).astype(np.int8)

        # Count overflow (> cap parallel edges in one cell): keep the excess.
        spill_s = spill_d = np.empty(0, np.int32)
        over = kc > cap
        if over.any():
            reps = (kc[over] - cap).astype(np.int64)
            ok = uk[over]
            sid = chosen[ok // strip_bytes]
            c = ok % strip_bytes
            spill_d = np.repeat(
                (sid // nvb) * r + c // BLOCK, reps
            ).astype(np.int32)
            spill_s = np.repeat(
                (sid % nvb) * BLOCK + (c & 127), reps
            ).astype(np.int32)

        built.append(StripLevel(
            r=r,
            strips=strips.reshape(-1, r, BLOCK),
            rows=(chosen // nvb).astype(np.int32),
            cols=(chosen % nvb).astype(np.int32),
        ))
        remaining -= len(chosen) * strip_bytes
        s = np.concatenate([tail_s, spill_s])
        d = np.concatenate([tail_d, spill_d])

    if s is None:  # banded mode with an empty `levels` sequence
        s = rank[graph.col_src]
        d = rank[graph.col_dst]

    # Tail CSC sort by (d, s). np.lexsort was the planner's real hot
    # spot (40 s on RMAT22's 67M edges, single-core mergesort); packing
    # both ids into one int64 key and radix-sorting (np.sort stable on
    # ints) runs ~7x faster. nv < 2^31 so both ids fit 31 bits.
    vbits = max(int(nv - 1).bit_length(), 1)
    packed = (d.astype(np.int64) << vbits) | s.astype(np.int64)
    packed = np.sort(packed, kind="stable")
    d = (packed >> vbits).astype(np.int32)
    s = (packed & ((1 << vbits) - 1)).astype(np.int32)
    del packed
    tail_row_ptr = np.zeros(nv + 1, np.int64)
    np.cumsum(np.bincount(d, minlength=nv), out=tail_row_ptr[1:])

    return HybridPlan(
        nv=nv,
        nvb=nvb,
        order=order,
        rank=rank,
        levels=tuple(built),
        tail_sb=(s >> 7).astype(np.int32),
        tail_lane=(s & 127).astype(np.int8),
        tail_row_ptr=tail_row_ptr,
        out_degrees=graph.out_degrees[order],
        in_degrees=graph.in_degrees[order],
        cap=cap,
        levels_spec=tuple((int(r), int(t)) for r, t in levels),
        budget_bytes=int(budget_bytes),
    )


_PLAN_ARRAY_FIELDS = (
    "order", "rank", "tail_sb", "tail_lane", "tail_row_ptr",
    "out_degrees", "in_degrees",
)


def save_plan(path: str, plan: HybridPlan) -> None:
    """Persist a plan as a directory of raw ``.npy`` files + ``meta.json``.

    Raw .npy (one array per file) loads via ``np.load(mmap_mode="r")`` —
    effectively instant, paged in at disk bandwidth on first touch. The
    previous single-``.npz`` format streamed the multi-GB strip arrays
    through zipfile CRC32 at ~170 MB/s (46.7 s for the RMAT22 plan);
    ``load_plan`` still reads it for old caches. Writes go to a temp
    directory renamed into place so a crashed save never leaves a
    half-written cache that a later run would trust.
    """
    import json
    import os
    import tempfile

    tmp = tempfile.mkdtemp(
        dir=os.path.dirname(os.path.abspath(path)) or ".",
        prefix=os.path.basename(path) + ".tmp.",
    )
    meta = dict(
        nv=plan.nv, nvb=plan.nvb,
        levels=[lev.r for lev in plan.levels],
        level_edges=[lev.edges for lev in plan.levels],
        cap=plan.cap,
        levels_spec=(
            None if plan.levels_spec is None
            else [list(rt) for rt in plan.levels_spec]
        ),
        budget_bytes=plan.budget_bytes,
    )
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    for name in _PLAN_ARRAY_FIELDS:
        np.save(os.path.join(tmp, name + ".npy"), getattr(plan, name))
    for i, lev in enumerate(plan.levels):
        np.save(os.path.join(tmp, f"lev{i}_strips.npy"), lev.strips)
        np.save(os.path.join(tmp, f"lev{i}_rows.npy"), lev.rows)
        np.save(os.path.join(tmp, f"lev{i}_cols.npy"), lev.cols)
    if os.path.isdir(path):
        import shutil

        shutil.rmtree(path)
    elif os.path.exists(path):
        os.remove(path)
    os.replace(tmp, path)


def load_plan(path: str, mmap: bool = True) -> HybridPlan:
    """Load a plan saved by :func:`save_plan` (directory format), or a
    legacy round-1 ``.npz`` file. With ``mmap`` (default) arrays are
    memory-mapped read-only — the caller pays disk I/O only for the
    bytes it actually touches, when it touches them."""
    import json
    import os

    if os.path.isdir(path):
        mode = "r" if mmap else None
        ld = lambda name: np.load(
            os.path.join(path, name + ".npy"), mmap_mode=mode
        )
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        lev_edges = meta.get("level_edges", [-1] * len(meta["levels"]))
        levels = tuple(
            StripLevel(
                r=int(r),
                strips=ld(f"lev{i}_strips"),
                rows=ld(f"lev{i}_rows"),
                cols=ld(f"lev{i}_cols"),
                _edges=int(lev_edges[i]),
            )
            for i, r in enumerate(meta["levels"])
        )
        spec = meta.get("levels_spec")
        return HybridPlan(
            nv=int(meta["nv"]), nvb=int(meta["nvb"]),
            levels=levels,
            cap=int(meta.get("cap", 127)),
            levels_spec=(
                None if spec is None
                else tuple((int(r), int(t)) for r, t in spec)
            ),
            budget_bytes=int(meta.get("budget_bytes", -1)),
            **{name: ld(name) for name in _PLAN_ARRAY_FIELDS},
        )

    with np.load(path) as z:
        levels = tuple(
            StripLevel(
                r=int(z[f"lev{i}_r"]),
                strips=z[f"lev{i}_strips"],
                rows=z[f"lev{i}_rows"],
                cols=z[f"lev{i}_cols"],
            )
            for i in range(int(z["nlevels"]))
        )
        return HybridPlan(
            nv=int(z["nv"]), nvb=int(z["nvb"]),
            order=z["order"], rank=z["rank"],
            levels=levels, tail_sb=z["tail_sb"], tail_lane=z["tail_lane"],
            tail_row_ptr=z["tail_row_ptr"],
            out_degrees=z["out_degrees"], in_degrees=z["in_degrees"],
            cap=127,   # legacy .npz plans predate the nibble cap
        )


# ---------------------------------------------------------------------------
# Device side
# ---------------------------------------------------------------------------


def _dd_add(a, b):
    """Double-single (hi, lo) addition with renormalization (TwoSum).

    Keeps ~2x f32 precision; used for the sub-chunk-prefix chain so that
    boundary diffs of nearby prefixes cancel to ~eps^2 of stream scale
    instead of eps. Branch-free, broadcasts like +.
    """
    ahi, alo = a
    bhi, blo = b
    s = ahi + bhi
    bb = s - ahi
    err = (ahi - (s - bb)) + (bhi - bb)
    lo = alo + blo + err
    hi2 = s + lo
    lo2 = lo - (hi2 - s)
    return hi2, lo2


# Gathers from tables larger than this run ~4x slower on v5e (measured
# cliff between 64 MB and 139 MB operands; an in-jit lax.slice restores
# the fast rate), so extraction tables are split into segments below it.
GATHER_TABLE_BYTES = 48 << 20


def _warn_big_table(nrows: int, what: str, advice: str = ""):
    """Warn when an unsegmented boundary-extraction gather table crosses
    the measured big-gather cliff (extraction runs ~4x off-rate above it).
    Used by paths whose tables cannot be (or are not yet) segmented: the
    sharded Z-streams (segment splits are per-part data, which
    shard_map's one-trace-for-all-shards model can't make static) and the
    single-device r==128 hub levels (normally tiny). ``advice`` lets the
    caller append a remediation hint."""
    if nrows * BLOCK * 4 > GATHER_TABLE_BYTES:
        import warnings

        warnings.warn(
            f"{what}: boundary-extraction table is "
            f"{nrows * BLOCK * 4 >> 20} MB, above the "
            f"~{GATHER_TABLE_BYTES >> 20} MB gather cliff — extraction "
            f"will run ~4x off-rate{advice}",
            stacklevel=3,
        )


def _subs_per_chunk(r: int) -> int:
    """Transposed-layout sub-chunks per scan chunk: S = 128/r lane
    groups of width r side by side, so the per-sub-chunk cumsum runs on
    a (cs, 128) array — cumsum on a narrow-minor-dim array is ~10x off
    bandwidth (each of its log passes works on 128-lane tiles holding r
    real values)."""
    assert BLOCK % r == 0
    return BLOCK // r


def round_chunk(chunk: int, n: int, r: int) -> int:
    """Scan chunk size: <= chunk (rounded up to a multiple of S so the
    (C, r) contribution block transposes exactly into (cs, 128))."""
    s = _subs_per_chunk(r)
    return max(s, -(-min(chunk, max(n, 1)) // s) * s)


# ---------------------------------------------------------------------------
# Static (plan-time) boundary data for the Z-stream layout
# ---------------------------------------------------------------------------
#
# The device scans are carry-free and emit, per chunk of C items, the
# TRANSPOSED local cumsum: contributions (C, r) reshape to (S, cs, r)
# with S = 128/r sub-chunks of cs = C/S items, transpose to (cs, S*r=128)
# and cumsum along axis 0 — so lane group s of row j holds the sum of
# the first j items of sub-chunk s. Each chunk contributes cs+1 such
# rows (leading zero row) to the flat Z-stream, plus its S sub-chunk
# totals to a small side stream.
#
# A boundary position b in [0, K*C] then maps to
#     row = (b//C)*(cs+1) + (b%C)%cs     (one final zero row for b=K*C)
#     grp = (b%C)//cs                    (lane group, 0..S-1)
# and a range sum is   y[i] = Z[b_{i+1}] - Z[b_i] + (P[sub_{i+1}] -
# P[sub_i])   where P is the double-single prefix over sub-chunk totals
# (sub = b//cs, a GLOBAL sub-chunk index) — rebasing the cumsum to zero
# at every sub-chunk keeps the f32 cancellation error of the Z diff at
# sub-chunk mass. The P term is zero unless the range crosses a
# sub-chunk start, which happens for at most n_subs of the nb output
# rows: those corrections are applied as a tiny static scatter instead
# of widening every gather (the dd hi/lo parts are subtracted separately
# so prefix magnitudes cancel instead of rounding).


def zstream_boundaries(b: np.ndarray, chunk: int, r: int):
    """(row, grp, sub) int32/int64 arrays for sorted positions ``b``."""
    b = b.astype(np.int64)
    s = _subs_per_chunk(r)
    cs = chunk // s
    k = b // chunk
    local = b - k * chunk
    row = k * (cs + 1) + local % cs
    grp = local // cs
    assert int(row.max(initial=0)) < 2**31
    return row.astype(np.int32), grp.astype(np.int32), b // cs


def block_level_boundaries(b: np.ndarray, chunk: int):
    """(row, chunk_index) for the r == 128 split two-gather form: local
    rows are whole 128-lane blocks at flat row ``k*(chunk+1) + j``; P is
    a small (K+1, 128) table row-gathered by chunk index."""
    b = b.astype(np.int64)
    k = b // chunk
    row = k * (chunk + 1) + (b - k * chunk)
    assert int(row.max(initial=0)) < 2**31
    return row.astype(np.int32), k.astype(np.int32)


def crossing_correction(sub: np.ndarray, r: int):
    """Static data for the sparse P-correction scatter.

    ``sub`` (nb,) global sub-chunk index per boundary; output rows i with
    sub[i+1] != sub[i] need P[sub[i+1]] - P[sub[i]] added. Returns
    (flat output positions (|X|*r,), s0 (|X|,), s1 (|X|,)).
    """
    x = np.nonzero(sub[1:] != sub[:-1])[0]
    flat = (x[:, None] * r + np.arange(r)[None, :]).ravel()
    assert flat.size == 0 or int(flat.max()) < 2**31
    return (
        flat.astype(np.int32),
        sub[x].astype(np.int32),
        sub[x + 1].astype(np.int32),
    )


def split_segments(b: np.ndarray, nchunks: int, chunk: int, r: int):
    """Cut the Z-stream into gather tables under GATHER_TABLE_BYTES.

    Cuts fall on chunk boundaries (rows within one chunk interleave
    sub-chunks, so only the chunk index is monotone in ``b``). Returns a
    tuple of (bnd_lo, bnd_hi, row_base, row_cnt); the final zero row
    rides with the last segment.
    """
    s = _subs_per_chunk(r)
    cs = chunk // s
    rows_per_chunk = cs + 1
    kseg = max(GATHER_TABLE_BYTES // (BLOCK * 4) // rows_per_chunk, 1)
    segs = []
    for k0 in range(0, max(nchunks, 1), kseg):
        k1 = min(k0 + kseg, nchunks)
        lo = int(np.searchsorted(b, k0 * chunk, side="left"))
        hi = int(np.searchsorted(b, k1 * chunk, side="left"))
        if k1 == nchunks:
            hi = b.shape[0]                 # include b == K*C boundaries
        segs.append((lo, hi, k0 * rows_per_chunk,
                     (k1 - k0) * rows_per_chunk + (1 if k1 == nchunks else 0)))
    return tuple(segs)


def strip_boundaries(rows: np.ndarray, nchunks: int, chunk: int, nrb: int,
                     r: int):
    """All static boundary data per dst strip-row for a sorted strip list.

    ``rows`` (n,) are the real strips' dst strip-rows, ascending; pad
    strips (indices >= n) are zero-count so any boundary <= n is exact
    against the padded scan stream. Row i's strips span ``[b[i], b[i+1])``
    with ``b = searchsorted(rows, 0..nrb)`` — all plan-time constants.
    Returns (row, grp, xing_idx, xing_s0, xing_s1, segs).
    """
    b = np.searchsorted(rows, np.arange(nrb + 1, dtype=np.int64))
    if r == BLOCK:
        row, grp = block_level_boundaries(b, chunk)
        e = np.zeros(0, np.int32)
        return row, grp, e, e, e, ()
    row, grp, sub = zstream_boundaries(b, chunk, r)
    xi, s0, s1 = crossing_correction(sub, r)
    return row, grp, xi, s0, s1, split_segments(b, nchunks, chunk, r)


# ---------------------------------------------------------------------------
# Device data + kernels
# ---------------------------------------------------------------------------


def resolve_pack(pack, plan_cap: int):
    """One shared gate for the nibble-packing decision: explicit ``pack``
    wins, else the LUX_PACK_STRIPS env opt-in; packing also requires the
    plan's count cap to fit a nibble. An explicit ``pack=True`` that the
    plan cannot satisfy raises (mirroring PushExecutor's blocked_dense
    validation) — only the env opt-in degrades silently. Per-level, r
    must be even (checked at the call sites via ``r % 2 == 0``)."""
    if pack is None:
        from lux_tpu.utils import flags

        pack = flags.get_bool("LUX_PACK_STRIPS")
    elif pack and plan_cap > 15:
        raise ValueError(
            f"pack=True needs a plan with count cap <= 15 (got cap="
            f"{plan_cap}, a legacy/unpacked plan) — replan with cap<=15"
        )
    return bool(pack) and plan_cap <= 15


def pack_strips(strips: np.ndarray) -> np.ndarray:
    """(..., r, 128) int8 counts <= 15 → (..., r/2, 128) uint8 nibbles.

    Row j rides the low nibble, row j + r/2 the high nibble, so the
    device-side unpack is one `& 15`, one `>> 4`, and a lane-axis concat
    that lands in LOGICAL row order — no permutation anywhere. Halves
    the per-iteration strip HBM traffic (the dominant strip-phase byte
    stream); native int4 arrays would do the same but were not tried on
    the chip."""
    r = strips.shape[-2]
    assert r % 2 == 0, "nibble packing needs an even strip height"
    lo = strips[..., : r // 2, :].astype(np.uint8)
    hi = strips[..., r // 2 :, :].astype(np.uint8)
    return lo | (hi << 4)


@dataclasses.dataclass
class DeviceLevel:
    """One strip level on device, chunked for lax.scan (pad strips are
    zero-count → contribute nothing). Boundary fields are the static
    Z-stream data from :func:`strip_boundaries`. ``packed`` marks
    nibble-packed strips ((C, r/2, 128) uint8, see pack_strips)."""

    r: int
    segs: tuple             # static gather-table segmentation
    strips: jnp.ndarray     # (nchunks, C, r, 128) int8 or packed uint8
    cols: jnp.ndarray       # (nchunks, C) int32
    bnd_row: jnp.ndarray    # (nrb+1,) int32
    bnd_grp: jnp.ndarray    # (nrb+1,) int32
    xing_idx: jnp.ndarray   # (|X|*r,) int32 flat output positions
    xing_s0: jnp.ndarray    # (|X|,) int32
    xing_s1: jnp.ndarray    # (|X|,) int32
    packed: bool = False


@dataclasses.dataclass
class DeviceHybrid:
    levels: Tuple[DeviceLevel, ...]
    tail_sb: jnp.ndarray        # (nchunks, C) int32 (padded with 0)
    tail_lane: jnp.ndarray      # (nchunks, C) int8
    tail_bnd_row: jnp.ndarray   # (nv+1,) int32 (tail_row_ptr boundaries)
    tail_bnd_grp: jnp.ndarray   # (nv+1,) int32
    tail_xing_idx: jnp.ndarray  # (|X|,) int32
    tail_xing_s0: jnp.ndarray   # (|X|,) int32
    tail_xing_s1: jnp.ndarray   # (|X|,) int32
    tail_segs: tuple
    nvb: int

    @staticmethod
    def build(
        plan: HybridPlan,
        chunk_strips: int = DEFAULT_CHUNK_STRIPS,
        chunk_tail: int = DEFAULT_CHUNK_TAIL,
        device=None,
        pack=None,
    ) -> "DeviceHybrid":
        """``pack=True`` nibble-packs even-r levels (needs plan.cap <= 15;
        default: the LUX_PACK_STRIPS env knob via :func:`resolve_pack`).
        MEASURED NEGATIVE on v5e (PERF_NOTES.md round 2): the strip scan is
        VPU-bound, so halving its bytes buys nothing and the unpack adds
        ~60% per-strip time (4.9 → 7.9 ns isolated, 114 → 139 ms/iter
        end-to-end on RMAT22). Kept as an opt-in for hardware where the
        balance differs."""
        put = lambda x: jax.device_put(jnp.asarray(x), device)

        packed = resolve_pack(pack, plan.cap)
        dlevels = []
        for lev in plan.levels:
            nrb = plan.nvb * (BLOCK // lev.r)
            n = lev.rows.shape[0]
            c = round_chunk(chunk_strips, n, lev.r)
            pad = (-n) % c
            st = np.concatenate(
                [lev.strips, np.zeros((pad, lev.r, BLOCK), np.int8)]
            )
            co = np.concatenate(
                [lev.cols.astype(np.int32), np.zeros(pad, np.int32)]
            )
            k = st.shape[0] // c
            row, grp, xi, s0, s1, segs = strip_boundaries(
                lev.rows, k, c, nrb, lev.r
            )
            lev_packed = packed and lev.r % 2 == 0
            rr = lev.r // 2 if lev_packed else lev.r
            if lev_packed:
                st = pack_strips(st)
            dlevels.append(DeviceLevel(
                r=lev.r,
                segs=segs,
                packed=lev_packed,
                strips=put(st.reshape(k, c, rr, BLOCK)),
                cols=put(co.reshape(k, c)),
                bnd_row=put(row),
                bnd_grp=put(grp),
                xing_idx=put(xi),
                xing_s0=put(s0),
                xing_s1=put(s1),
            ))

        m = plan.tail_sb.shape[0]
        c = round_chunk(chunk_tail, m, 1)
        pad = (-m) % c
        sb = np.concatenate([plan.tail_sb, np.zeros(pad, np.int32)])
        lane = np.concatenate([plan.tail_lane, np.zeros(pad, np.int8)])
        k2 = sb.shape[0] // c
        row, grp, sub = zstream_boundaries(plan.tail_row_ptr, c, 1)
        xi, s0, s1 = crossing_correction(sub, 1)
        return DeviceHybrid(
            levels=tuple(dlevels),
            tail_sb=put(sb.reshape(k2, c)),
            tail_lane=put(lane.reshape(k2, c)),
            tail_bnd_row=put(row),
            tail_bnd_grp=put(grp),
            tail_xing_idx=put(xi),
            tail_xing_s0=put(s0),
            tail_xing_s1=put(s1),
            tail_segs=split_segments(plan.tail_row_ptr, k2, c, 1),
            nvb=plan.nvb,
        )


def _transpose_cumsum(contrib: jnp.ndarray):
    """(C, r) contributions → ((cs+1, 128) Z rows, (S, r) sub totals).

    The transpose puts S = 128/r sub-chunks side by side so the cumsum's
    minor dim is exactly 128 (a (S, cs, r) axis-1 cumsum measured ~10x
    slower — every log-pass touches 128-lane tiles holding r values).
    """
    c, r = contrib.shape
    s = _subs_per_chunk(r)
    cs = c // s
    zt = contrib.reshape(s, cs, r).transpose(1, 0, 2).reshape(cs, BLOCK)
    z = cumsum0(zt)
    zrows = jnp.concatenate([jnp.zeros((1, BLOCK), jnp.float32), z])
    return zrows, z[-1].reshape(s, r)


def _dd_prefix(totals_flat: jnp.ndarray):
    """(n_subs, r) sub totals → exclusive double-single prefix tables
    (n_subs+1, r) hi and lo."""
    n, r = totals_flat.shape
    z1 = jnp.zeros((1, r), jnp.float32)
    if n == 0:
        return z1, z1
    hi, lo = jax.lax.associative_scan(
        _dd_add, (totals_flat, jnp.zeros_like(totals_flat)), axis=0
    )
    return (
        jnp.concatenate([z1, hi]),
        jnp.concatenate([z1, lo]),
    )


def zstream_extract(
    flatz: jnp.ndarray,
    lev_r: int,
    segs,
    bnd_row: jnp.ndarray,
    bnd_grp: jnp.ndarray,
) -> jnp.ndarray:
    """Gather Z values at static boundaries; returns flat (nb*r,) f32.

    Gathers run per segment against an in-jit slice of the stream (big
    gather tables are ~4x off-rate, GATHER_TABLE_BYTES) and are chunked
    with a scan so the (cb, 128) intermediates stay bounded.
    """
    r = lev_r
    s = _subs_per_chunk(r)
    iota_s = jnp.arange(s, dtype=jnp.int32)
    outs = []
    for (lo, hi, base, cnt) in segs:
        nbs = hi - lo
        if nbs == 0:
            continue
        sub_tbl = jax.lax.slice(flatz, (base, 0), (base + cnt, BLOCK))
        # NOTE: an isolated (8,4)-plan sweep suggested 2^16 here, but
        # end-to-end with the default (8,2) plan it regressed 115 ->
        # 127 ms/iter; 2^19 is the measured end-to-end best.
        cb = min(1 << 19, nbs)
        pad = (-nbs) % cb
        idx = jnp.pad(bnd_row[lo:hi] - base, (0, pad)).reshape(-1, cb)
        grp = jnp.pad(bnd_grp[lo:hi], (0, pad)).reshape(-1, cb)

        def ebody(_, ch):
            ix, g = ch
            rw = sub_tbl[ix].reshape(-1, s, r)           # (cb, S, r)
            sel = g[:, None] == iota_s[None, :]
            gv = jnp.where(sel[:, :, None], rw, 0.0).sum(axis=1)
            return 0, gv.reshape(-1)                     # 1-D: no lane pad

        _, gv = jax.lax.scan(ebody, 0, (idx, grp))
        outs.append(gv.reshape(-1)[: nbs * r])
    return jnp.concatenate(outs)


def strip_level_spmv(x2d: jnp.ndarray, lev: DeviceLevel, nrb: int) -> jnp.ndarray:
    """Σ strip · x_block per destination row; returns (nrb*r,) f32.

    ``x2d`` is the (nvb, 128) f32 operand; ``nrb`` is the number of
    destination strip rows covered (``lev.cols`` may index all of ``x2d``
    while the level's strips span only a local destination range, which is
    how the sharded executor reuses this kernel per shard — boundaries for
    uncovered rows collapse to empty ranges and contribute zero).

    Per-strip contributions are an f32 broadcast-multiply-reduce on the
    VPU (int8 counts convert in-fusion). The per-row reduction is
    scatter-free: transposed sub-chunk cumsums (carry-free scan) + static
    boundary diffs + the sparse double-single P correction — see the
    Z-stream layout notes above; products themselves are exact f32.
    """
    r = lev.r

    def contrib_of(chunk):
        strips, cols = chunk
        xb = x2d[cols]                                  # (C, 128) row gather
        if lev.packed:
            # Nibble unpack: rows 0..r/2-1 in the low nibble, r/2..r-1
            # in the high — the concat lands in logical row order.
            lo = (strips & jnp.uint8(15)).astype(jnp.float32)
            hi = (strips >> jnp.uint8(4)).astype(jnp.float32)
            return jnp.concatenate(
                [
                    (lo * xb[:, None, :]).sum(-1),
                    (hi * xb[:, None, :]).sum(-1),
                ],
                axis=-1,
            )
        return (strips.astype(jnp.float32) * xb[:, None, :]).sum(-1)

    if r == BLOCK:
        # Split two-gather form: a (C+1, 128) local-cumsum block per
        # chunk + a small (K+1, 128) chunk-prefix table (chunk-level
        # rebase only — r=128 levels are small hub tiles).
        # Accuracy note: the chunk-prefix chain here is plain f32 (no
        # double-single compensation), so boundary diffs for hub rows
        # carry eps * level-stream-mass cancellation error — weaker than
        # the r<128 levels' sub-chunk-mass bound. Fine for the small hub
        # levels this branch serves (tests pass at 5e-5 rtol); switch to
        # _dd_prefix on the chunk totals if large r=128 levels become a
        # supported config.
        def body(carry, chunk):
            s_loc = cumsum0(contrib_of(chunk))
            out = jnp.concatenate(
                [jnp.zeros((1, r), jnp.float32), s_loc]
            )
            return carry + s_loc[-1], (out, carry)

        with region("lux.tiled.strip_scan"):
            carry, (z, pk) = jax.lax.scan(
                body, jnp.zeros((r,), jnp.float32), (lev.strips, lev.cols)
            )
        with region("lux.tiled.strip_boundary"):
            lf = jnp.concatenate(
                [z.reshape(-1, BLOCK), jnp.zeros((1, BLOCK), jnp.float32)]
            )
            pp = jnp.concatenate([pk, carry[None]])      # (K+1, 128)
            _warn_big_table(lf.shape[0], f"strip level r={BLOCK}")
            gl = lf[lev.bnd_row].reshape(-1)
            gp = pp[lev.bnd_grp].reshape(-1)
            return (gp[r:] - gp[:-r]) + (gl[r:] - gl[:-r])

    def body(_, chunk):
        zrows, totals = _transpose_cumsum(contrib_of(chunk))
        return 0, (zrows, totals)

    with region("lux.tiled.strip_scan"):
        _, (z, totals) = jax.lax.scan(body, 0, (lev.strips, lev.cols))
    with region("lux.tiled.strip_boundary"):
        flatz = jnp.concatenate(
            [z.reshape(-1, BLOCK), jnp.zeros((1, BLOCK), jnp.float32)]
        )
        gl = zstream_extract(flatz, r, lev.segs, lev.bnd_row, lev.bnd_grp)
        y = gl[r:] - gl[:-r]
        ph, pl = _dd_prefix(totals.reshape(-1, r))
        corr = (
            (ph[lev.xing_s1] - ph[lev.xing_s0])
            + (pl[lev.xing_s1] - pl[lev.xing_s0])
        )
        return y.at[lev.xing_idx].add(corr.reshape(-1))


def lane_select_tail_sums(
    x2d: jnp.ndarray,
    tail_sb: jnp.ndarray,
    tail_lane: jnp.ndarray,
    bnd_row: jnp.ndarray,
    bnd_grp: jnp.ndarray,
    xing_idx: jnp.ndarray,
    xing_s0: jnp.ndarray,
    xing_s1: jnp.ndarray,
    segs,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-destination sums of tail-edge source values, fused.

    Each tail edge costs one 128-wide row read of its source block plus
    an on-the-fly one-hot lane selection (exact f32). The per-destination
    reduction is the Z-stream boundary diff at the static
    ``tail_row_ptr`` boundaries (r=1) + the sparse double-single P
    correction. Pad edges past the real tail length land after the last
    boundary and are never read. Returns (nv,) f32.

    Where the table fits VMEM (:func:`vmem_table_fits`), a TPU lowering
    reads the rows with the Pallas kernel of
    :mod:`~lux_tpu.ops.lane_select_kernel`, which holds ``x2d`` in VMEM,
    and the scan emits the Z-stream from its values; elsewhere the scan
    body row-gathers from ``x2d`` itself. Both give the same values.
    ``use_pallas`` forces one form (``interpret`` runs the kernel in
    Pallas interpret mode, off the TPU).
    """

    def gather_scan(x2d, tail_sb, tail_lane):
        def body(_, chunk):
            sb, lane = chunk
            with region("lux.tiled.tail_gather"):
                v = lane_select_ref(x2d, sb, lane)      # (C,)
            with region("lux.tiled.tail_zstream"):
                return 0, _transpose_cumsum(v[:, None])

        return jax.lax.scan(body, 0, (tail_sb, tail_lane))[1]

    def vmem_scan(x2d, tail_sb, tail_lane):
        with region("lux.tiled.tail_gather"):
            v = lane_select_pallas(
                x2d, tail_sb.reshape(-1), tail_lane.reshape(-1),
                interpret=interpret)

        def body(_, vc):
            with region("lux.tiled.tail_zstream"):
                return 0, _transpose_cumsum(vc[:, None])

        return jax.lax.scan(body, 0, v.reshape(tail_sb.shape))[1]

    if tail_sb.size == 0:
        use_pallas = False
    if use_pallas is None and vmem_table_fits(x2d):
        z, totals = jax.lax.platform_dependent(
            x2d, tail_sb, tail_lane, tpu=vmem_scan, default=gather_scan)
    else:
        scan = vmem_scan if use_pallas else gather_scan
        z, totals = scan(x2d, tail_sb, tail_lane)
    with region("lux.tiled.tail_boundary"):
        flatz = jnp.concatenate(
            [z.reshape(-1, BLOCK), jnp.zeros((1, BLOCK), jnp.float32)]
        )
        gl = zstream_extract(flatz, 1, segs, bnd_row, bnd_grp)
        y = gl[1:] - gl[:-1]
        ph, pl = _dd_prefix(totals.reshape(-1, 1))
        corr = (
            (ph[xing_s1] - ph[xing_s0]) + (pl[xing_s1] - pl[xing_s0])
        )
        return y.at[xing_idx].add(corr.reshape(-1))


def vals_to_x2d(vals: jnp.ndarray, dh: DeviceHybrid) -> jnp.ndarray:
    """(nv,) values → (nvb, 128) padded gather operand."""
    pad = dh.nvb * BLOCK - vals.shape[0]
    with region("lux.tiled.permute"):
        return jnp.pad(vals, (0, pad)).reshape(dh.nvb, BLOCK)


def strips_sum(x2d: jnp.ndarray, dh: DeviceHybrid, nv: int) -> jnp.ndarray:
    """Σ over all strip levels; (nv,) f32 (internal order)."""
    acc = jnp.zeros(dh.nvb * BLOCK, jnp.float32)
    for lev in dh.levels:
        acc = acc + strip_level_spmv(x2d, lev, dh.nvb * (BLOCK // lev.r))
    return acc[:nv]


def tail_sum(x2d: jnp.ndarray, dh: DeviceHybrid) -> jnp.ndarray:
    """Σ over the lane-select tail; (nv,) f32 (internal order)."""
    return lane_select_tail_sums(
        x2d, dh.tail_sb, dh.tail_lane, dh.tail_bnd_row, dh.tail_bnd_grp,
        dh.tail_xing_idx, dh.tail_xing_s0, dh.tail_xing_s1, dh.tail_segs,
    )


def hybrid_spmv(
    vals: jnp.ndarray, dh: DeviceHybrid, gtail=None
) -> jnp.ndarray:
    """Full Σ vals[src] per destination over all layouts; (nv,) f32 in,
    (nv,) f32 out (internal vertex order).

    ``gtail`` (a :class:`~lux_tpu.ops.merge_tail_kernel.DeviceGroupedTail`)
    swaps the lane-select tail for the grouped merge-network tail —
    opt-in via LUX_GROUPED_TAIL=1 in the executors; both produce per-dst
    sums of the same tail edge set."""
    nv = vals.shape[0]
    x2d = vals_to_x2d(vals, dh)
    if gtail is not None:
        from lux_tpu.ops.merge_tail_kernel import grouped_tail_sums

        return strips_sum(x2d, dh, nv) + grouped_tail_sums(x2d, gtail)
    return strips_sum(x2d, dh, nv) + tail_sum(x2d, dh)


for _cls, _data, _meta in (
    (DeviceLevel,
     ["strips", "cols", "bnd_row", "bnd_grp",
      "xing_idx", "xing_s0", "xing_s1"],
     ["r", "segs", "packed"]),
    (DeviceHybrid,
     ["levels", "tail_sb", "tail_lane", "tail_bnd_row", "tail_bnd_grp",
      "tail_xing_idx", "tail_xing_s0", "tail_xing_s1"],
     ["tail_segs", "nvb"]),
):
    jax.tree_util.register_dataclass(_cls, data_fields=_data, meta_fields=_meta)
