"""Segment reductions over sorted CSC edge segments.

The reference performs per-destination reductions with block-cooperative
CUB ``BlockScan`` edge balancing plus ``atomicAdd/Min/Max`` into the
destination slot (pagerank/pagerank_gpu.cu:49-102, sssp/sssp_gpu.cu:48-61).
On TPU the same computation is a *segmented reduction* over edges sorted by
destination — which the CSC format already guarantees. XLA's
scatter-reduce (``jax.ops.segment_*``) is deterministic, unlike CUDA float
atomics: a free reproducibility improvement.

Two strategies:
- ``segment_reduce``: ``jax.ops.segment_{sum,min,max}`` with
  ``indices_are_sorted=True``;
- ``segment_sum_by_rowptr``: cumulative-sum + gather-diff. For sorted sum
  segments ``out[v] = S[end_v] - S[start_v]`` where S is the inclusive
  prefix sum — no scatter at all, purely dense ops (cumsum + two gathers),
  which maps well onto the TPU's VPU. Numerically this reassociates the
  sum; fine for the fixpoint workloads here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

COMBINER_IDENTITY = {
    "sum": 0,
    "min": np.inf,
    "max": -np.inf,
}

_SEGMENT_FNS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def identity_for(kind: str, dtype) -> jnp.ndarray:
    """Combiner identity as a castable scalar for ``dtype``."""
    if kind == "sum":
        return jnp.zeros((), dtype)
    if kind == "min":
        return (
            jnp.array(jnp.inf, dtype)
            if jnp.issubdtype(dtype, jnp.floating)
            else jnp.array(jnp.iinfo(dtype).max, dtype)
        )
    if kind == "max":
        return (
            jnp.array(-jnp.inf, dtype)
            if jnp.issubdtype(dtype, jnp.floating)
            else jnp.array(jnp.iinfo(dtype).min, dtype)
        )
    raise ValueError(f"unknown combiner {kind!r}")


def segment_reduce(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    kind: str = "sum",
    indices_are_sorted: bool = True,
) -> jnp.ndarray:
    """Reduce ``data`` (edges-first, optional trailing dims) into
    ``num_segments`` destination slots. Empty segments get the combiner
    identity (min → dtype max for ints, +inf for floats)."""
    fn = _SEGMENT_FNS[kind]
    return fn(
        data,
        segment_ids,
        num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )


def take1d_blocked(z: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``z[idx]`` for huge 1-D ``z`` without scalar gathers.

    TPU scalar gathers run at ~8.5 ns/element (the VPU has no fine-grained
    HBM access) while aligned 128-lane *row* gathers stream at full HBM
    bandwidth (~0.9 ns/row, PERF_NOTES.md). So: fetch the 128-block containing
    each element as a row, then select the lane with an on-the-fly one-hot
    — ~1.5 KB of streamed traffic per element instead of a ~4.4 KB-equiv
    scalarized access. Exact (pure selection). Chunked with a scan so the
    (len(idx), 128) gather/select intermediates stay bounded.

    Caveat: the gather table ``zz`` is the FULL (padded) ``z`` — tables
    past the ~48 MB gather cliff (ops.tiled_spmv.GATHER_TABLE_BYTES, e.g.
    the RMAT22 flat-path cumsum at ~268 MB) run row gathers ~4x
    off-rate. Still far faster than scalar gathers; the tiled executor's
    zstream_extract segments its tables and is the fast path at scale.
    """
    n = idx.shape[0]
    if n == 0:
        return z[:0]
    zz = jnp.pad(z, (0, (-z.shape[0]) % 128)).reshape(-1, 128)
    iota = jnp.arange(128, dtype=jnp.int32)
    cb = min(1 << 19, n)
    pad = (-n) % cb
    idx_c = jnp.pad(idx, (0, pad)).reshape(-1, cb)

    def body(_, ix):
        rows = zz[(ix >> 7).astype(jnp.int32)]       # (cb, 128) row gather
        lane = (ix & 127).astype(jnp.int32)
        sel = jnp.where(lane[:, None] == iota[None, :], rows, 0)
        return 0, sel.sum(axis=1)

    _, out = jax.lax.scan(body, 0, idx_c)
    return out.reshape(-1)[:n]


# Below this many gathered elements the plain scalar gather's fixed cost
# is noise and the blocked form's extra dense passes aren't worth it.
_BLOCKED_GATHER_MIN = 1 << 17


def segmented_minmax_scan(
    data: jnp.ndarray,
    seg_start: jnp.ndarray,
    kind: str,
) -> jnp.ndarray:
    """Running per-segment min/max over sorted segments, scatter-free.

    ``seg_start`` is a bool array marking the first element of each
    segment. Returns the inclusive segmented scan: position i holds the
    min/max of its segment's elements up to i — gather the last position
    of each segment for the per-segment reduction. Min/max have no
    inverse, so the cumsum-diff trick of :func:`segment_sum_by_rowptr`
    cannot apply; the classic (value, flag) segmented-scan operator is
    associative, so ``lax.associative_scan`` runs it in O(n) work /
    O(log n) depth, replacing XLA's scalar-rate scatter-extremum
    (measured ~45 ns/edge) with dense vector passes.
    """
    if kind == "min":
        pick = jnp.minimum
    elif kind == "max":
        pick = jnp.maximum
    else:
        raise ValueError(f"segmented_minmax_scan: unsupported kind {kind!r}")

    def op(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, pick(av, bv)), af | bf

    # Two-level: associative_scan within fixed chunks under a lax.scan
    # carrying the (value, flag) pair across chunk boundaries. A single
    # associative_scan over the whole 67M-element stream compiles its
    # full log-depth decomposition into the graph (>20 min of XLA time
    # measured); per-chunk scans bound the compiled graph while the
    # runtime stays O(n).
    n = data.shape[0]
    if n == 0:
        return data
    chunk = min(1 << 17, max(n, 1))
    pad = (-n) % chunk
    ident = identity_for(kind, data.dtype)
    d = jnp.pad(data, (0, pad), constant_values=ident).reshape(-1, chunk)
    # Pad elements start their own segments so they cannot absorb carry.
    f = jnp.pad(seg_start, (0, pad), constant_values=True).reshape(-1, chunk)

    def body(cv, ch):
        dv, df = ch
        lv, lf = jax.lax.associative_scan(op, (dv, df), axis=0)
        # lf is the running "a segment started in this chunk at or
        # before here"; positions before the first local start combine
        # with the carry (last value of the previous chunk's stream).
        out = jnp.where(lf, lv, pick(cv, lv))
        return out[-1], out

    # Derive the identity carry FROM data (x*0 + ident) so that under
    # shard_map it inherits data's varying-axes metadata — a replicated
    # constant init trips the scan carry type check.
    init = d[0, 0] * jnp.asarray(0, data.dtype) + jnp.asarray(
        ident, data.dtype
    )
    _, out = jax.lax.scan(body, init, (d, f))
    return out.reshape(-1)[:n]


def segment_minmax_by_rowptr(
    data: jnp.ndarray,
    seg_start: jnp.ndarray,
    end_pos: jnp.ndarray,
    nonempty: jnp.ndarray,
    kind: str,
) -> jnp.ndarray:
    """Per-segment min/max for sorted segments with host-precomputed
    layout: ``seg_start`` (ne,) bool segment-start flags, ``end_pos``
    (nv,) int32 last-element positions (clipped for empty segments),
    ``nonempty`` (nv,) bool. Empty segments get the combiner identity.
    """
    scan = segmented_minmax_scan(data, seg_start, kind)
    ends = take1d_blocked(scan, end_pos)
    ident = identity_for(kind, data.dtype)
    return jnp.where(nonempty, ends, ident)


class BlockMinLayout:
    """Host-precomputed static layout for :func:`segment_minmax_blockmin`.

    For each destination segment [s, e) over a (padded) edge stream cut
    into 128-wide blocks:
    - head row  = the block containing s, lanes [s%128, s%128 + hlen);
    - tail row  = the block containing e-1, lanes [tfrom, tfrom + tlen);
      (for segments inside one block head and tail overlap — harmless,
      min/max are idempotent);
    - interior  = whole blocks fully inside the segment (only segments
      with >= 128ish edges have one), reduced via a block-level
      segmented scan: ``blk_flags`` marks each interior run's first
      block, ``int_end`` its last block, ``has_int`` whether v has one.
    ``segs`` optionally splits the head/tail row gathers into sub-cliff
    table slices (srow/erow are monotone in v because row_ptr is):
    tuples of (v_start, v_end, row_start, row_end).
    """

    def __init__(self, row_ptr: np.ndarray, ne_padded: int,
                 seg_rows: int = 0):
        rp = np.asarray(row_ptr, np.int64)
        nv = rp.shape[0] - 1
        s, e = rp[:-1], rp[1:]
        deg = e - s
        nb = ne_padded // 128
        self.nb = nb
        self.nv = nv
        # Empty segments still need in-range, v-MONOTONE row indices so
        # the static gather-table segmentation (searchsorted on srow /
        # erow) stays valid; their hlen/tlen are zeroed below so they
        # reduce to the identity regardless of what row they point at.
        empty = deg == 0
        s_c = np.minimum(s, max(ne_padded - 1, 0))
        e_c = np.maximum(e, s_c + 1)
        self.srow = (s_c // 128).astype(np.int32)
        self.erow = ((e_c - 1) // 128).astype(np.int32)
        self.smod = (s_c % 128).astype(np.int32)
        bl = -(-s_c // 128)          # first whole block
        br = e_c // 128              # one past last whole block
        self.hlen = np.minimum(e_c - s_c, bl * 128 - s_c).astype(np.int32)
        tfrom = np.maximum(br * 128, s_c)
        self.tfrom_mod = (tfrom - self.erow.astype(np.int64) * 128).astype(
            np.int32
        )
        self.tlen = (e_c - tfrom).astype(np.int32)
        self.hlen[empty] = 0
        self.tlen[empty] = 0
        has_int = (br > bl) & ~empty
        self.has_int = has_int
        flags = np.zeros(nb, bool)
        flags[bl[has_int]] = True
        self.blk_flags = flags
        self.int_end = np.where(has_int, br - 1, 0).astype(np.int32)
        # Static head/tail gather-table segmentation (v-monotone rows).
        if seg_rows and nb > seg_rows:
            bounds = []
            r0 = 0
            while r0 < nb:
                r1 = min(r0 + seg_rows, nb)
                v0 = int(np.searchsorted(self.srow, r0, side="left"))
                v1 = int(np.searchsorted(self.srow, r1, side="left"))
                bounds.append((v0, v1, r0, r1))
                r0 = r1
            self.head_segs = tuple(bounds)
            bounds = []
            r0 = 0
            while r0 < nb:
                r1 = min(r0 + seg_rows, nb)
                v0 = int(np.searchsorted(self.erow, r0, side="left"))
                v1 = int(np.searchsorted(self.erow, r1, side="left"))
                bounds.append((v0, v1, r0, r1))
                r0 = r1
            self.tail_segs = tuple(bounds)
        else:
            self.head_segs = self.tail_segs = ((0, nv, 0, nb),)

    def device_arrays(self):
        """The per-vertex/per-block arrays the jitted reduction needs (a
        dict so executors can device_put / shard-stack them)."""
        return {
            "bm_srow": self.srow, "bm_erow": self.erow,
            "bm_smod": self.smod, "bm_hlen": self.hlen,
            "bm_tfrom": self.tfrom_mod, "bm_tlen": self.tlen,
            "bm_flags": self.blk_flags, "bm_int_end": self.int_end,
            "bm_has_int": self.has_int,
        }


def _masked_row_reduce(d2, row_idx, lane_from, length, kind, segs):
    """Per-vertex reduce of d2[row_idx] over lanes [lane_from,
    lane_from+length), with the row gather split into static sub-cliff
    table slices (rows monotone in v)."""
    iota = jnp.arange(128, dtype=jnp.int32)
    ident = identity_for(kind, d2.dtype)
    outs = []
    for (v0, v1, r0, r1) in segs:
        if v1 <= v0:
            continue
        sl = jax.lax.slice(d2, (r0, 0), (r1, 128))
        rows = sl[jnp.clip(row_idx[v0:v1] - r0, 0, max(r1 - r0 - 1, 0))]
        lf = lane_from[v0:v1][:, None]
        m = (iota[None, :] >= lf) & (
            iota[None, :] < lf + length[v0:v1][:, None]
        )
        masked = jnp.where(m, rows, ident)
        outs.append(
            masked.min(axis=1) if kind == "min" else masked.max(axis=1)
        )
    if not outs:
        return jnp.full(row_idx.shape, ident, d2.dtype)
    return jnp.concatenate(outs)


def segment_minmax_blockmin(data, layout_arrays, head_segs, tail_segs,
                            kind: str):
    """Per-segment min/max via a 128-block hierarchy: one dense
    block-reduce pass + a 128x-smaller block-level segmented scan for
    interiors + masked head/tail row gathers.

    Replaces the edge-level (value, flag) associative scan
    (:func:`segmented_minmax_scan`, measured ~4 ns/edge on v5e — the
    scan's log-depth passes dominate) with ~1 pass of dense reduce plus
    O(nv) extraction. ``data`` must be padded to a 128 multiple with the
    combiner identity. ``layout_arrays`` is BlockMinLayout.device_arrays
    (possibly device-resident / shard-sliced); head/tail segs are the
    static table splits."""
    la = layout_arrays
    d2 = data.reshape(-1, 128)
    red_ax = (lambda a: a.min(axis=1)) if kind == "min" else (
        lambda a: a.max(axis=1)
    )
    m0 = red_ax(d2)
    scan = segmented_minmax_scan(m0, la["bm_flags"], kind)
    interior_all = take1d_blocked(scan, la["bm_int_end"])
    ident = identity_for(kind, data.dtype)
    interior = jnp.where(la["bm_has_int"], interior_all, ident)
    head = _masked_row_reduce(
        d2, la["bm_srow"], la["bm_smod"], la["bm_hlen"], kind, head_segs
    )
    tail = _masked_row_reduce(
        d2, la["bm_erow"], la["bm_tfrom"], la["bm_tlen"], kind, tail_segs
    )
    red = jnp.minimum if kind == "min" else jnp.maximum
    return red(red(head, tail), interior)


def cumsum0(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along axis 0 in ``x``'s dtype: the same
    reduce-window ``jnp.cumsum`` lowers to, built inline so its ops keep
    the caller's ``jax.named_scope``. jax lowers ``cumsum`` as a separate
    function whose ops lose the scope, so the device trace could not put
    them down to the engine phase that runs them."""
    n, nd = x.shape[0], x.ndim
    if n == 0:
        return x
    return jax.lax.reduce_window(
        x, jnp.zeros((), x.dtype), jax.lax.add,
        window_dimensions=(n,) + (1,) * (nd - 1),
        window_strides=(1,) * nd,
        padding=((n - 1, 0),) + ((0, 0),) * (nd - 1),
    )


def segment_sum_by_rowptr(data: jnp.ndarray, row_ptr: jnp.ndarray) -> jnp.ndarray:
    """Sum sorted segments given CSC offsets, scatter-free.

    ``row_ptr`` is (nv+1,) with segment v spanning
    ``data[row_ptr[v]:row_ptr[v+1]]``. Returns (nv, *data.shape[1:]).
    """
    s = jnp.cumsum(data, axis=0, dtype=data.dtype)
    z = jnp.concatenate(
        [jnp.zeros((1,) + data.shape[1:], data.dtype), s], axis=0
    )
    # One (nv+1)-sized gather, then a dense diff — gathers are the scalar
    # bottleneck on TPU (~8.5 ns/elem), so don't do two of them; for big
    # 1-D inputs, do zero of them (blocked row-gather + lane select). The
    # gate is on len(row_ptr): that is what the gather cost scales with.
    if data.ndim == 1 and row_ptr.shape[0] >= _BLOCKED_GATHER_MIN:
        g = take1d_blocked(z, row_ptr)
    else:
        g = z[row_ptr]
    return g[1:] - g[:-1]


def csc_counting_merge(
    row_ptr: np.ndarray,
    col_src: np.ndarray,
    weights,
    keep: np.ndarray,
    ins_dst: np.ndarray,
    ins_src: np.ndarray,
    ins_w,
    nv: int,
):
    """Merge a kept subset of a CSC edge list with sorted inserts, host-side.

    One counting-sort pass instead of a full ``argsort`` over the merged
    edge list: per-destination survivor counts come from a prefix sum over
    ``keep``, insert counts from a ``bincount``, and every edge's final
    slot is a closed-form offset — kept edges keep their base-relative
    order within each destination segment, inserts (pre-sorted by
    ``(dst, src)``) land after them. O(ne + ni + nv) with no comparison
    sort, deterministic by construction.

    ``keep`` is a boolean mask over the base edges; ``ins_dst``/``ins_src``
    must be sorted by ``(dst, src)``. Returns
    ``(new_row_ptr int64 (nv+1,), new_col_src, new_weights|None)``.
    """
    ne = int(col_src.shape[0])
    ni = int(ins_dst.shape[0])
    if weights is None and ins_w is not None:
        raise ValueError("insert weights given for an unweighted base")
    if weights is not None and ni and ins_w is None:
        raise ValueError("weighted base requires insert weights")

    ex = np.zeros(ne + 1, dtype=np.int64)
    np.cumsum(keep, out=ex[1:])
    kept_per = ex[row_ptr[1:]] - ex[row_ptr[:-1]]
    ins_per = np.bincount(ins_dst, minlength=nv).astype(np.int64)

    new_rp = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(kept_per + ins_per, out=new_rp[1:])
    total = int(new_rp[-1])

    new_src = np.empty(total, dtype=col_src.dtype)
    has_w = weights is not None
    new_w = np.empty(total, dtype=weights.dtype) if has_w else None

    kept_e = np.nonzero(keep)[0]
    if kept_e.size:
        # Destination of each base edge, recovered from row_ptr without
        # materialising the full col_dst: searchsorted on the kept ids.
        dst_of = np.searchsorted(row_ptr, kept_e, side="right").astype(np.int64) - 1
        pos = new_rp[dst_of] + ex[kept_e] - ex[row_ptr[dst_of]]
        new_src[pos] = col_src[kept_e]
        if has_w:
            new_w[pos] = weights[kept_e]
    if ni:
        first = np.searchsorted(ins_dst, ins_dst)  # first index of each dst run
        rank = np.arange(ni, dtype=np.int64) - first
        d = ins_dst.astype(np.int64)
        pos_i = new_rp[d] + kept_per[d] + rank
        new_src[pos_i] = ins_src.astype(col_src.dtype)
        if has_w:
            new_w[pos_i] = ins_w
    return new_rp, new_src, new_w
