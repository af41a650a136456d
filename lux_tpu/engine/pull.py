"""Single-device pull executor.

Runs a :class:`PullProgram` as one jitted step over the whole CSC graph in
HBM. The reference's equivalent path is
pull_app_task_impl → load_kernel + pr_kernel + copy-back
(pagerank/pagerank_gpu.cu:104-151); on TPU there is no ZC staging or
copy-back — the values live in HBM across iterations and the step is a
single fused XLA computation. Iteration pipelining (the reference launches
all `-ni` waves and waits once, pagerank/pagerank.cc:106-114) falls out of
JAX async dispatch: `run()` enqueues every step and blocks once at the end.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.analysis.sentinel import compile_phase
from lux_tpu.engine.program import EdgeCtx, PullProgram, VertexCtx
from lux_tpu.graph.graph import Graph
from lux_tpu.obs import (
    NULL_RECORDER,
    consume_compile_seconds,
    metrics,
    note_compile_seconds,
    prof,
    recorder_for,
    spans,
)
from lux_tpu.ops.segment import (
    cumsum0,
    segment_reduce,
    segment_sum_by_rowptr,
)
from lux_tpu.utils import flags
from lux_tpu.utils.timing import Timer


def _edge_index_dtype(ne: int):
    """Device dtype for edge offsets (row_ptr): int32 below 2^31 edges,
    int64 at the reference's E_ID=uint64 headroom (README.md:79-86).

    int64 on device requires ``jax_enable_x64``; without it JAX silently
    downcasts to int32, which would overflow — fail loudly instead."""
    if ne < 2**31:
        return jnp.int32
    import jax

    if not jax.config.jax_enable_x64:
        raise ValueError(
            f"graph has {ne} >= 2^31 edges: edge offsets need int64 on "
            "device; enable it with jax.config.update('jax_enable_x64', "
            "True) (or JAX_ENABLE_X64=1) before building the executor"
        )
    return jnp.int64


def hard_sync(x):
    """Wait until ``x`` is materialized on device; returns ``x``."""
    return jax.block_until_ready(x)


def run_pipelined(step, vals, num_iters: int, flush_every: int = 8,
                  recorder=None):
    """Launch ``num_iters`` async step waves, blocking only every
    ``flush_every`` iterations. The reference pipelines all waves and waits
    once (pagerank.cc:106-114); we additionally bound in-flight depth the
    way its push model bounds SLIDING_WINDOW, so the dispatch queue — and
    on CPU meshes the collective rendezvous — can't grow unboundedly.

    ``recorder`` (an obs.IterationRecorder) is flushed only at the
    host-sync points, so disabled-mode cost is one no-op call per flush."""
    rec = recorder if recorder is not None else NULL_RECORDER
    for i in range(num_iters):
        vals = step(vals)
        if flush_every and (i + 1) % flush_every == 0:
            # Bounded-depth flush: this sync IS the point of the
            # pipelined path (caps in-flight dispatch like the
            # reference's SLIDING_WINDOW).
            with spans.span("engine.sync"):
                jax.block_until_ready(vals)  # luxlint: disable=LUX001 -- designed flush point, one sync per flush_every iters
            rec.flush(i + 1)
    with spans.span("engine.sync"):
        vals = hard_sync(vals)
    rec.flush(num_iters)
    return vals


def make_fused_runner(step_fn):
    """One jitted dispatch for N iterations: ``lax.fori_loop`` over the
    step with a *dynamic* trip count (no recompile per N).

    One dispatch per run keeps per-call host overhead out of the
    iteration time (the reference's Legion futures pipeline their waves
    instead, pagerank.cc:106-114). Executors route
    ``run(..., flush_every=0)`` ("never sync with the host") here.
    """
    def _run(vals, n, *args):
        return jax.lax.fori_loop(
            0, n, lambda i, v: step_fn(v, *args), vals
        )

    return jax.jit(_run, donate_argnums=0)


def run_maybe_fused(jrun, step, vals, num_iters: int, flush_every: int, *args,
                    recorder=None):
    """Shared run() body: ``flush_every=0`` = no host syncs at all (the
    whole loop on device in one fused dispatch, dynamic trip count);
    ``k>0`` = per-step dispatch, blocking every k iterations.

    With telemetry on, the fused path first issues a zero-trip dispatch:
    ``jrun`` has a dynamic trip count, so n=0 compiles the same
    executable as n=num_iters without running an iteration — that splits
    compile time from execute time on first call. Disabled mode skips the
    probe entirely (one predicate check, no extra dispatch)."""
    rec = recorder if recorder is not None else NULL_RECORDER
    if flush_every == 0:
        if rec.enabled:
            with Timer() as t:
                vals = hard_sync(jrun(vals, jnp.int32(0), *args))
            rec.record_compile(t.elapsed)
        vals = jrun(vals, jnp.int32(num_iters), *args)
        with spans.span("engine.sync"):
            vals = hard_sync(vals)
        rec.flush(num_iters)
        return vals
    return run_pipelined(step, vals, num_iters, flush_every, recorder=rec)


@dataclasses.dataclass
class _DeviceGraph:
    """CSC arrays resident on one device."""

    col_src: jnp.ndarray          # (ne,) int32 — edge source ids
    seg_ids: jnp.ndarray          # (ne,) int32 — edge destination ids (sorted)
    row_ptr: jnp.ndarray          # (nv+1,) int — CSC offsets
    weights: Optional[jnp.ndarray]
    out_degrees: jnp.ndarray      # (nv,) int32
    in_degrees: jnp.ndarray       # (nv,) int32


@dataclasses.dataclass
class _ChunkedGraph:
    """CSC arrays chunked for a ``lax.scan`` over edge windows, plus the
    per-chunk row-boundary plan (host-precomputed).

    The flat engine materializes the full (ne, *value_shape) contribution
    array; at NetFlix scale (201M edges x K=20 f32 = 16 GB) that exceeds
    HBM (cf. the reference's full-nv H2D per iteration instead,
    col_filter/colfilter.cc driver). Here contributions only ever exist
    as one (C, K) chunk inside the scan; per-destination sums come from
    chunk-local cumsums gathered at the row boundaries falling in each
    chunk (``bnd_pos``, a scan input) and rebased across chunks with a
    double-single prefix over chunk totals — the K-wide generalization of
    the tiled engine's Z-stream reduction (ops/tiled_spmv.py), with
    dynamic per-chunk boundaries instead of plan-time-static ones.
    """

    col_src: jnp.ndarray          # (nchunks, C) int32, pad 0
    seg_ids: jnp.ndarray          # (nchunks, C) int32, pad 0
    weights: Optional[jnp.ndarray]   # (nchunks, C) or None
    bnd_pos: jnp.ndarray          # (nchunks, R) int32 local cumsum positions
    gather_idx: jnp.ndarray       # (nv+1,) int32 into (nchunks*R,) emits
    bnd_chunk: jnp.ndarray        # (nv+1,) int32 chunk of each boundary
    dst_lo: jnp.ndarray           # (nchunks,) int32 clamped dst-slice starts
    src_lo: jnp.ndarray           # (nchunks,) int32 clamped src-band starts
    src_banded: jnp.ndarray       # (nchunks,) bool — chunk uses the band
    out_degrees: jnp.ndarray      # (nv,) int32
    in_degrees: jnp.ndarray       # (nv,) int32


def _chunk_boundary_plan(row_ptr: np.ndarray, ne: int, chunk: int):
    """Assign each of the nv+1 row boundaries to the edge chunk it falls
    in. Returns (nchunks, bnd_pos (nchunks, R), gather_idx (nv+1,),
    bnd_chunk (nv+1,)); R is the worst-case boundaries per chunk."""
    nchunks = max(-(-ne // chunk), 1)
    rp = row_ptr.astype(np.int64)
    cidx = np.minimum(rp // chunk, nchunks - 1)
    lpos = (rp - cidx * chunk).astype(np.int32)          # ∈ [0, C]
    cnt = np.bincount(cidx, minlength=nchunks)
    starts = np.zeros(nchunks, np.int64)
    np.cumsum(cnt[:-1], out=starts[1:])
    rank = np.arange(rp.shape[0], dtype=np.int64) - starts[cidx]
    r_max = max(int(cnt.max()), 1)
    # The emit table is padded to the most boundary-dense chunk; if that
    # approaches one slot per edge, chunking no longer compresses and the
    # stacked emits would rival the flat (ne, K) array this path avoids.
    if nchunks * r_max >= 2**31 or nchunks * r_max > max(ne, 1):
        raise ValueError(
            f"edge-chunked plan does not compress: {nchunks} chunks x "
            f"{r_max} boundaries/chunk vs {ne} edges — a run of near-empty "
            "rows packs too many boundaries into one chunk; raise the edge "
            "chunk size or reorder vertices"
        )
    bnd_pos = np.zeros((nchunks, r_max), np.int32)
    bnd_pos[cidx, rank] = lpos
    gather_idx = (cidx * r_max + rank).astype(np.int32)
    return nchunks, bnd_pos, gather_idx, cidx.astype(np.int32)


# Auto edge-chunking threshold: flat contributions above this many bytes
# route through the scan path (override via the LUX_EDGE_CHUNK_BYTES
# flag; the default lives in the utils/flags.py registry).
EDGE_CHUNK_AUTO_BYTES = flags.default("LUX_EDGE_CHUNK_BYTES")
DEFAULT_EDGE_CHUNK = 1 << 20
# Ceiling for the boundary-dense degrade path (growing windows / flat
# fallback): any single contribution allocation past this is refused in
# favor of the actionable "does not compress" error (v5e HBM is 16 GB).
DEGRADE_CAP_BYTES = 4 << 30


def _dst_slice_plan(col_dst: np.ndarray, ne: int, chunk: int, nv: int):
    """Per-chunk dst-slice starts for the chunked engine's gather-cliff fix.

    Edges are dst-sorted, so each edge chunk touches a narrow contiguous
    band of destination rows. Gathering ``dst_vals`` from a per-chunk
    ``dynamic_slice`` of the value table instead of the full table keeps
    the gather under the big-table cliff (measured on the NetFlix-shaped
    CF bench: a src+dst gather+dot from the 255 MB lane-padded table runs
    at 22.2 ns/edge vs ~1.8 ns for sub-48MB tables — PERF_NOTES.md "CF /
    edge-chunked engine").

    Returns ``(span, dst_lo)``: the static slice height (max band over
    chunks, sublane-rounded) and the (nchunks,) clamped slice starts.
    Starts are pre-clamped to ``nv - span`` on the host so the in-jit
    local index ``cd - dst_lo`` is always within [0, span) for real
    edges — no value-table padding needed.
    """
    nchunks = max(-(-ne // chunk), 1)
    if ne == 0:
        return 0, np.zeros(nchunks, np.int32)
    starts = np.arange(nchunks, dtype=np.int64) * chunk
    ends = np.minimum(starts + chunk, ne) - 1
    lo = col_dst[starts].astype(np.int64)
    hi = col_dst[ends].astype(np.int64)
    span = int((hi - lo).max()) + 1
    span = min(-(-span // 8) * 8, nv)
    dst_lo = np.minimum(lo, nv - span).astype(np.int32)
    return span, np.maximum(dst_lo, 0)


def _src_slice_plan(col_src: np.ndarray, ne: int, chunk: int, nv: int,
                    row_bytes: int):
    """Per-chunk SOURCE-band plan for the chunked engine.

    Unlike destinations, sources are not sorted — but structured graphs
    give many chunks a narrow source RANGE anyway: in the NetFlix-shaped
    bipartite CF graph every user-destination chunk draws sources only
    from the item id range (a ~9 MB band of the 255 MB value table —
    the PERF_NOTES.md round-2 "item-side src slice" lever). Chunks whose
    source span fits under the big-table gather cliff serve ``src_vals``
    from a per-chunk ``dynamic_slice`` (selected per chunk by a traced
    ``lax.cond`` flag); wide chunks keep the full-table gather.

    Returns ``(span, src_lo, banded)``: the static slice height (max
    span over BANDED chunks; 0 = no chunk qualifies), clamped starts,
    and the per-chunk flag array.
    """
    from lux_tpu.ops.tiled_spmv import GATHER_TABLE_BYTES

    nchunks = max(-(-ne // chunk), 1)
    zero = (0, np.zeros(nchunks, np.int32), np.zeros(nchunks, bool))
    if ne == 0:
        return zero
    edges = np.arange(nchunks + 1, dtype=np.int64) * chunk
    edges[-1] = ne
    lo = np.minimum.reduceat(col_src[:ne], edges[:-1]).astype(np.int64)
    hi = np.maximum.reduceat(col_src[:ne], edges[:-1]).astype(np.int64)
    spans = hi - lo + 1
    cap = max(GATHER_TABLE_BYTES // max(row_bytes, 1), 1)
    banded = spans <= cap
    if not banded.any() or nv <= cap:
        # nv <= cap: the full table is already under the cliff.
        return zero
    span = int(spans[banded].max())
    span = min(-(-span // 8) * 8, nv)
    src_lo = np.clip(lo, 0, nv - span).astype(np.int32)
    return span, src_lo, banded


def lane_pad_width(value_shape) -> tuple:
    """(kreal, kpad) lane-padding policy for K-vector vertex values.

    Gathers of rows narrower than the 128-lane tile scalarize on TPU
    (~76.5 s/iter measured on NetFlix-shaped CF before padding); rank-1
    value shapes whose width is not a lane multiple get padded to the
    next multiple of 128. kpad == 0 means "no padding applies"."""
    vshape = tuple(value_shape or ())
    kreal = int(np.prod(vshape)) if vshape else 0
    kpad = (-(-kreal // 128)) * 128 if (
        len(vshape) == 1 and kreal % 128
    ) else 0
    return kreal, kpad


class PullExecutor:
    """Executes a pull program on a single device (CPU or one TPU chip).

    Sum-combiner programs whose flat (ne, *value_shape) contribution
    array would exceed ~2 GB run edge-chunked (``_ChunkedGraph``): a
    ``lax.scan`` over edge windows so NetFlix-scale CF (16 GB flat) fits
    in HBM. ``edge_chunk`` forces chunked with the given window;
    ``edge_chunk=0`` forces flat."""

    def __init__(
        self,
        graph: Graph,
        program: PullProgram,
        sum_strategy: str = "rowptr",   # 'rowptr' (scatter-free) | 'segment'
        device=None,
        edge_chunk: Optional[int] = None,
    ):
        if program.needs_weights and graph.weights is None:
            raise ValueError(f"{program.name} requires an edge-weighted graph")
        self.graph = graph
        self.program = program
        self.sum_strategy = sum_strategy
        self.device = device
        put = lambda x: jax.device_put(jnp.asarray(x), device)

        vshape = tuple(getattr(program, "value_shape", ()) or ())
        width = int(np.prod(vshape)) if vshape else 1
        if edge_chunk is None:
            limit = flags.get_int("LUX_EDGE_CHUNK_BYTES")
            flat_bytes = graph.ne * width * np.dtype(np.float32).itemsize
            self.edge_chunk = (
                DEFAULT_EDGE_CHUNK
                if (program.combiner == "sum" and flat_bytes > limit)
                else 0
            )
        else:
            self.edge_chunk = edge_chunk
        if self.edge_chunk and program.combiner != "sum":
            raise ValueError(
                "edge-chunked execution needs a sum combiner "
                f"({program.name} has {program.combiner!r})"
            )

        # Lane padding for K-vector values on the chunked path: a gather
        # of (C, 20)-wide rows scalarizes on TPU (~765 ns/edge measured
        # on the NetFlix-shaped CF bench) because 20 < the 128-lane tile;
        # padding values to (nv, 128) makes every gather a full-bandwidth
        # 512 B row fetch and the chunk cumsum full-lane. Pad lanes are
        # re-zeroed after apply so programs whose apply adds constants
        # cannot leak garbage into the next iteration's contractions.
        self._kreal, self._kpad = lane_pad_width(vshape)

        with spans.span("build.plan"):
            chunk_plan = None
            if self.edge_chunk:
                # On the AUTO-selected path a boundary-dense graph (a run of
                # near-empty rows packed into one edge window) must degrade,
                # not fail: retry with growing windows (fewer chunks bounds
                # the padded emit table), then fall back to the flat engine.
                # Degrading is only legal while the resulting contribution
                # window stays under an absolute allocation cap — otherwise
                # the "fallback" would be the very HBM-scale array chunking
                # exists to avoid, traded for a silent OOM. An explicit
                # edge_chunk override keeps the hard error either way.
                C = self.edge_chunk
                w_eff = max(self._kpad or self._kreal, 1)   # chunked row width
                w_flat = max(self._kreal, 1)                # flat keeps layout
                while True:
                    try:
                        chunk_plan = _chunk_boundary_plan(
                            graph.row_ptr, graph.ne, C
                        )
                        self.edge_chunk = C
                        break
                    except ValueError:
                        if edge_chunk is not None:
                            raise
                        nxt = min(C * 4, max(graph.ne, 1))
                        if (C < graph.ne
                                and nxt * w_eff * 4 <= DEGRADE_CAP_BYTES):
                            C = nxt
                            continue
                        if graph.ne * w_flat * 4 <= DEGRADE_CAP_BYTES:
                            import warnings

                            warnings.warn(
                                "edge-chunked plan does not compress on this "
                                "graph — degrading to the flat engine "
                                f"({graph.ne * w_flat * 4 >> 20} MB flat "
                                "contributions)"
                            )
                            self.edge_chunk = 0
                            break
                        raise   # no safe degrade: surface the actionable error
            if not self.edge_chunk:
                self._kpad = 0   # the flat path keeps the external layout

            self._dst_span = self._src_span = 0
            if self.edge_chunk:
                C = self.edge_chunk
                nchunks, bnd_pos, gidx, bchunk = chunk_plan
                pad = nchunks * C - graph.ne

                # dst-slice gather (see _dst_slice_plan): auto-on when the
                # slice traffic (nchunks x span rows/iter) is well under the
                # edge gather traffic it replaces; LUX_DST_SLICE=0/1 overrides.
                span, dst_lo = _dst_slice_plan(
                    graph.col_dst, graph.ne, C, graph.nv
                )
                knob = flags.tristate("LUX_DST_SLICE", strict=False)
                auto = 0 < span < graph.nv and nchunks * span <= graph.ne // 2
                self._dst_span = span if (
                    (knob is True and span < graph.nv)
                    or (knob is not False and auto)
                ) else 0

                # Source-band gathers (per-chunk lax.cond — see
                # _src_slice_plan); LUX_SRC_SLICE=0/1 overrides the auto-on.
                row_b = max(self._kpad or self._kreal, 1) * 4
                span_s, src_lo, src_banded = _src_slice_plan(
                    graph.col_src, graph.ne, C, graph.nv, row_b
                )
                sknob = flags.tristate("LUX_SRC_SLICE", strict=False)
                # Traffic guard (mirrors the dst path's): each banded chunk
                # pays ~2*span rows of slice copy to save ~C rows of
                # big-table gather at ~5x the sub-cliff rate — only a clear
                # win while the span stays within a couple of chunk sizes.
                s_auto = 0 < span_s <= 2 * C
                self._src_span = span_s if (
                    (sknob is True and span_s)
                    or (sknob is not False and s_auto)
                ) else 0

        with spans.span("build.upload"):
            if self.edge_chunk:
                def padded(a):
                    return np.pad(a, (0, pad)).reshape(nchunks, C)

                self.dgraph = _ChunkedGraph(
                    col_src=put(padded(graph.col_src.astype(np.int32))),
                    seg_ids=put(padded(graph.col_dst.astype(np.int32))),
                    weights=(
                        None if graph.weights is None
                        else put(padded(graph.weights))
                    ),
                    bnd_pos=put(bnd_pos),
                    gather_idx=put(gidx),
                    bnd_chunk=put(bchunk),
                    dst_lo=put(dst_lo),
                    src_lo=put(src_lo),
                    src_banded=put(src_banded),
                    out_degrees=put(graph.out_degrees.astype(np.int32)),
                    in_degrees=put(graph.in_degrees.astype(np.int32)),
                )
            else:
                eidx = _edge_index_dtype(graph.ne)
                self.dgraph = _DeviceGraph(
                    col_src=put(graph.col_src.astype(np.int32)),
                    seg_ids=put(graph.col_dst),
                    row_ptr=put(graph.row_ptr.astype(eidx)),
                    weights=(None if graph.weights is None
                             else put(graph.weights)),
                    out_degrees=put(graph.out_degrees.astype(np.int32)),
                    in_degrees=put(graph.in_degrees.astype(np.int32)),
                )
        self._step = jax.jit(self._step_impl, donate_argnums=0)
        self._jrun = make_fused_runner(self._step_impl)

    # -- the jitted iteration -------------------------------------------

    def _step_impl(self, vals: jnp.ndarray, dg) -> jnp.ndarray:
        if self.edge_chunk:
            return self._chunked_step_impl(vals, dg)
        prog = self.program
        with prof.region("lux.pull.gather"):
            edge = EdgeCtx(
                src_vals=vals[dg.col_src],
                dst_vals=vals[dg.seg_ids],
                weights=dg.weights,
            )
        with prof.region("lux.pull.reduce"):
            contrib = prog.edge_contrib(edge)
            if prog.combiner == "sum" and self.sum_strategy == "rowptr":
                acc = segment_sum_by_rowptr(contrib, dg.row_ptr)
            else:
                acc = segment_reduce(
                    contrib, dg.seg_ids, num_segments=self.graph.nv,
                    kind=prog.combiner,
                )
        ctx = VertexCtx(
            nv=self.graph.nv,
            out_degrees=dg.out_degrees,
            in_degrees=dg.in_degrees,
        )
        with prof.region("lux.pull.apply"):
            return prog.apply(vals, acc, ctx)

    def _chunked_step_impl(
        self, vals: jnp.ndarray, dg: _ChunkedGraph
    ) -> jnp.ndarray:
        """Scan over edge windows; contributions never materialize beyond
        one (C, K) chunk. Per-destination sums are chunk-local cumsums
        gathered at each chunk's row boundaries, rebased with a
        double-single prefix over chunk totals (exactly the accuracy
        ladder of ops/tiled_spmv.py — boundary-diff error scales with
        chunk-local mass, not stream mass). Pad edges land after the last
        real boundary, so their garbage contributions are never gathered,
        and the polluted final chunk total is never used (the exclusive
        prefix stops before it).

        When lane padding is active (``self._kpad``), ``vals`` arrives
        and leaves (nv, kpad) — the fused runner keeps it padded across
        iterations; run()/step() convert at the boundary."""
        from lux_tpu.ops.tiled_spmv import _dd_prefix

        prog = self.program
        vshape = tuple(getattr(prog, "value_shape", ()) or ())
        kreal = int(np.prod(vshape)) if vshape else 1
        k = self._kpad or kreal

        def body(_, ch):
            cs, cd, w, bnd, dlo, slo, sbanded = ch
            with prof.region("lux.pull.gather"):
                if self._dst_span:
                    # dst ids are sorted, so this chunk's dst rows live
                    # in a narrow band: gather from a small dynamic slice
                    # instead of the full value table (the big-table
                    # gather cliff — PERF_NOTES.md "CF / edge-chunked
                    # engine"). dlo is pre-clamped on the host so
                    # cd - dlo ∈ [0, span) for real edges.
                    band = jax.lax.dynamic_slice_in_dim(
                        vals, dlo, self._dst_span, axis=0
                    )
                    dst_vals = band[cd - dlo]
                else:
                    dst_vals = vals[cd]
                if self._src_span:
                    # Narrow-source chunks (e.g. the item-sourced
                    # user-dst half of a bipartite ratings graph) serve
                    # src_vals from a per-chunk band too; wide chunks
                    # keep the full-table gather (per-chunk cond — see
                    # _src_slice_plan).
                    src_vals = jax.lax.cond(
                        sbanded,
                        lambda: jax.lax.dynamic_slice_in_dim(
                            vals, slo, self._src_span, axis=0
                        )[jnp.clip(cs - slo, 0, self._src_span - 1)],
                        lambda: vals[cs],
                    )
                else:
                    src_vals = vals[cs]
            with prof.region("lux.pull.reduce"):
                edge = EdgeCtx(
                    src_vals=src_vals, dst_vals=dst_vals, weights=w,
                )
                contrib = prog.edge_contrib(edge)
                c2 = contrib.reshape(contrib.shape[0], k)
                z = cumsum0(c2)
                zf = jnp.concatenate([jnp.zeros((1, k), z.dtype), z])
                return 0, (zf[bnd], z[-1])

        w = dg.weights
        xs_tail = (dg.bnd_pos, dg.dst_lo, dg.src_lo, dg.src_banded)
        if w is None:
            _, (zb, totals) = jax.lax.scan(
                lambda c, ch: body(
                    c, (ch[0], ch[1], None) + tuple(ch[2:])
                ),
                0, (dg.col_src, dg.seg_ids) + xs_tail,
            )
        else:
            _, (zb, totals) = jax.lax.scan(
                body, 0, (dg.col_src, dg.seg_ids, w) + xs_tail
            )
        with prof.region("lux.pull.reduce"):
            zg = zb.reshape(-1, k)[dg.gather_idx]       # (nv+1, k)
            ph, pl = _dd_prefix(totals)                 # (nchunks+1, k)
            ci = dg.bnd_chunk
            acc = (
                (zg[1:] - zg[:-1])
                + (ph[ci[1:]] - ph[ci[:-1]])
                + (pl[ci[1:]] - pl[ci[:-1]])
            )
        ctx = VertexCtx(
            nv=self.graph.nv,
            out_degrees=dg.out_degrees,
            in_degrees=dg.in_degrees,
        )
        with prof.region("lux.pull.apply"):
            if not self._kpad:
                acc = acc.reshape((self.graph.nv,) + vshape)
                return prog.apply(vals, acc, ctx)
            new = prog.apply(vals, acc, ctx)
            # Re-zero pad lanes: apply may write constants into them,
            # which would otherwise pollute the next iteration's
            # contractions.
            lane = jnp.arange(k, dtype=jnp.int32)
            return jnp.where(lane[None, :] < kreal, new, 0)

    # -- driver ----------------------------------------------------------

    def init_values(self) -> jnp.ndarray:
        with spans.span("engine.init"):
            return jax.device_put(
                jnp.asarray(self.program.init_values(self.graph)),
                self.device,
            )

    def _lane_pad(self, vals: jnp.ndarray) -> jnp.ndarray:
        return jnp.pad(vals, ((0, 0), (0, self._kpad - self._kreal)))

    def step(self, vals: jnp.ndarray) -> jnp.ndarray:
        """One iteration; external (nv, *value_shape) in and out (the
        lane-padded internal layout is private to the jitted step)."""
        if self._kpad:
            padded = self._step(
                self._lane_pad(jnp.asarray(vals)), self.dgraph
            )
            return padded[:, : self._kreal]
        return self._step(vals, self.dgraph)

    def warmup(self):
        """Run one throwaway step through the run() path outside any timed
        region (the reference's kernels are compiled at build time, so its
        ELAPSED TIME never includes compilation)."""
        with spans.span("engine.warmup"), compile_phase("warmup"), \
                Timer() as t:
            hard_sync(self.step(self.init_values()))
        note_compile_seconds(self, t.elapsed)

    def trace_step(self, **init_kw):
        """luxlint-IR hook (analysis/ir.py): the jitted step plus example
        args exactly as step() passes them — lane-padded for K-vector
        programs, so the audit sees the executable's real signature."""
        vals = self.init_values()
        if self._kpad:
            vals = self._lane_pad(jnp.asarray(vals))
        return {
            "kind": "pull",
            "fn": self._step,
            "args": (vals, self.dgraph),
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
                vals = self.init_values()
            rec = recorder if recorder is not None else recorder_for(
                "pull", self.graph, self.program)
            rec.start()
            if rec.enabled:
                rec.record_compile(consume_compile_seconds(self))
                from lux_tpu.obs import engobs
                rec.set_hbm_bytes(engobs.hbm_bytes_per_iter(
                    self.graph.nv, self.graph.ne, k=max(self._kreal, 1)))
            if self._kpad:
                padded = run_maybe_fused(
                    self._jrun,
                    lambda v: self._step(v, self.dgraph),
                    self._lane_pad(jnp.asarray(vals)),
                    num_iters, flush_every, self.dgraph,
                    recorder=rec,
                )
                out = hard_sync(padded[:, : self._kreal])
            else:
                out = run_maybe_fused(
                    self._jrun, self.step, vals, num_iters, flush_every,
                    self.dgraph, recorder=rec,
                )
            rec.finish()
            count_iterations("pull", num_iters)
            return out


def count_iterations(engine: str, n: int) -> None:
    """``lux_engine_iterations_total{engine,branch="all"}``, counted at
    the end of a run (engines with one branch)."""
    metrics.counter("lux_engine_iterations_total",
                    {"engine": engine, "branch": "all"}).inc(n)


jax.tree_util.register_dataclass(
    _DeviceGraph,
    data_fields=["col_src", "seg_ids", "row_ptr", "weights", "out_degrees",
                 "in_degrees"],
    meta_fields=[],
)

jax.tree_util.register_dataclass(
    _ChunkedGraph,
    data_fields=["col_src", "seg_ids", "weights", "bnd_pos", "gather_idx",
                 "bnd_chunk", "dst_lo", "src_lo", "src_banded",
                 "out_degrees", "in_degrees"],
    meta_fields=[],
)
