"""The lane-select tail's VMEM-table kernel against the row-gather form,
and the tiled executor's phase path against its fused step.

The kernel runs in Pallas interpret mode here (on the TPU it is what a
lowering for the chip takes, see ``lane_select_tail_sums``). Both forms
read the same ``x2d[sb, lane]`` values and feed the same Z-stream, so
every comparison is bitwise; the graphs' values are small integers, so
the sums are exact whatever their order.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lux_tpu.engine import tiled_sharded
from lux_tpu.engine.tiled import TiledPullExecutor
from lux_tpu.graph import generate
from lux_tpu.graph.graph import Graph
from lux_tpu.models.pagerank import PageRank
from lux_tpu.ops import lane_select_kernel as lk
from lux_tpu.ops import tiled_spmv as ts
from lux_tpu.parallel.mesh import make_mesh

KERNEL_TAIL = functools.partial(
    ts.lane_select_tail_sums, use_pallas=True, interpret=True)


@pytest.mark.parametrize("m", [1, 777, lk.EDGES, 2 * lk.EDGES + 4099])
def test_kernel_matches_row_gather(m):
    rng = np.random.default_rng(m)
    x = jnp.asarray(rng.standard_normal((300, 128)), jnp.float32)
    sb = jnp.asarray(rng.integers(0, 300, m), jnp.int32)
    lane = jnp.asarray(rng.integers(0, 128, m), jnp.int8)
    got = np.asarray(lk.lane_select_pallas(x, sb, lane, interpret=True))
    assert got.shape == (m,)
    np.testing.assert_array_equal(
        got, np.asarray(lk.lane_select_ref(x, sb, lane)))
    np.testing.assert_array_equal(
        got, np.asarray(x)[np.asarray(sb), np.asarray(lane).astype(np.int64)])


def test_vmem_table_fit_reads_the_table_bytes():
    fits = jnp.zeros((lk.VMEM_TABLE_BYTES // 512, 128), jnp.float32)
    assert lk.vmem_table_fits(fits)
    assert not lk.vmem_table_fits(jnp.zeros((fits.shape[0] + 8, 128)))


def _multi_edges():
    """R-MAT plus parallel edges: 24 cells hold 40 copies, 6 hold 200,
    so strip counts pass a nibble and spill past caps 31 and 127."""
    g = generate.rmat(9, 8, seed=4)
    rng = np.random.default_rng(5)
    hot = rng.integers(0, g.nv, (30, 2))
    reps = np.repeat([40, 200], [24, 6])
    src = np.concatenate([g.col_src, np.repeat(hot[:, 0], reps)])
    dst = np.concatenate([g.col_dst, np.repeat(hot[:, 1], reps)])
    return Graph.from_edges(src, dst, nv=g.nv)


def _grid():
    """One full (8, 128) strip of edges plus 1,024 edges that each sit
    alone in their strip (count 1 < min_count 2), so in natural order
    the tail holds exactly 1,024 edges."""
    i = np.repeat(np.arange(1, 129), 8)
    j = np.tile(np.arange(8), 128)
    src = np.concatenate([np.tile(np.arange(128), 8), 128 * j + i % 128])
    dst = np.concatenate([np.repeat(np.arange(8), 128), 8 * i + i % 8])
    return Graph.from_edges(src, dst, nv=2048)


GRAPHS = {
    "rmat": lambda: generate.rmat(9, 8, seed=4),
    "small_world": lambda: generate.small_world(1000, k=16, seed=2),
    "gnp": lambda: generate.gnp(700, 6000, seed=1),
    "multi": _multi_edges,
    "grid": _grid,
}
# The grid's tail size depends on its natural vertex order.
REORDER = {"grid": "natural"}

# (graph, levels, count cap, chunk_strips, chunk_tail). Each case's
# shape is asserted by _check_case.
CASES = {
    # A padded last tail chunk, strip and tail chunk counts that differ.
    "padded_last_chunk": ("rmat", ((8, 8),), 15, 16, 128),
    # Every edge in a strip (cap 127, so no parallel edges spill).
    "empty_tail": ("rmat", ((8, 1),), 127, 16, 256),
    # No strip at all.
    "empty_strip_level": ("rmat", ((8, 10**9),), 15, 16, 384),
    "two_strip_levels": ("rmat", ((32, 4), (8, 2)), 15, 16, 128),
    "cap31_parallel_edges": ("multi", ((8, 1),), 31, 16, 256),
    "cap127_parallel_edges": ("multi", ((8, 1),), 127, 16, 256),
    "tail_exact_chunk_multiple": ("grid", ((8, 2),), 15, 16, 256),
    "single_tail_chunk": ("rmat", ((8, 2),), 15, 16, ts.DEFAULT_CHUNK_TAIL),
    "small_world": ("small_world", ((8, 2),), 15, 32, 512),
    "gnp_nv_not_block_multiple": ("gnp", ((8, 2),), 15, 16, 256),
}


def _graph_and_plan(graph, levels, cap):
    g = GRAPHS[graph]()
    return g, ts.plan_hybrid(
        g, levels=levels, cap=cap, reorder=REORDER.get(graph, "degree"))


def _integral(n, seed=0):
    return np.random.default_rng(seed).integers(0, 8, n).astype(np.float32)


def _spmv(vals, dh):
    """hybrid_spmv, jitted through a fresh function: jit's trace cache is
    keyed on the function, so it cannot serve the other tail form."""
    return np.asarray(jax.jit(lambda v, d: ts.hybrid_spmv(v, d))(vals, dh))


def _check_case(name, ex):
    plan, dh = ex.plan, ex.dhybrid
    m = plan.tail_sb.shape[0]
    k, c = dh.tail_sb.shape
    strip_chunks = dh.levels[0].cols.shape[0]
    top = max(int(lev.strips.max(initial=0)) for lev in plan.levels)
    if name == "padded_last_chunk":
        assert m % c and 1 < k != strip_chunks
    elif name == "empty_tail":
        assert m == 0 and strip_chunks > 0
    elif name == "empty_strip_level":
        assert plan.num_strips == 0 and m == ex.graph.ne
    elif name == "two_strip_levels":
        assert all(lev.rows.shape[0] for lev in plan.levels) and m
    elif name.startswith("cap"):
        # Counts past a nibble sit in strips; the excess spills.
        assert top == plan.cap > 15 and m
    elif name == "tail_exact_chunk_multiple":
        assert m == k * c == 1024 and k > 1 and plan.num_strips
    elif name == "single_tail_chunk":
        assert k == 1 and m and plan.num_strips
    elif name == "small_world":
        assert plan.num_strips and m
    else:
        assert plan.nv % ts.BLOCK and plan.num_strips and m


@pytest.mark.parametrize("name", sorted(CASES))
def test_tiled_kernel_tail_parity(name, monkeypatch):
    graph, levels, cap, cs, ct = CASES[name]
    g, plan = _graph_and_plan(graph, levels, cap)
    build = lambda: TiledPullExecutor(
        g, PageRank(), plan=plan, chunk_strips=cs, chunk_tail=ct)
    gather_ex = build()
    _check_case(name, gather_ex)
    vals = jnp.asarray(_integral(g.nv))
    want_spmv = _spmv(vals, gather_ex.dhybrid)
    want_run = np.asarray(gather_ex.run(3))

    monkeypatch.setattr(ts, "lane_select_tail_sums", KERNEL_TAIL)
    kernel_ex = build()
    np.testing.assert_array_equal(_spmv(vals, kernel_ex.dhybrid), want_spmv)
    np.testing.assert_array_equal(np.asarray(kernel_ex.run(3)), want_run)


@pytest.mark.parametrize("name", sorted(CASES))
def test_sharded_tiled_kernel_tail_parity(name, monkeypatch):
    graph, levels, cap, cs, ct = CASES[name]
    g, plan = _graph_and_plan(graph, levels, cap)
    build = lambda: tiled_sharded.ShardedTiledExecutor(
        g, PageRank(), mesh=make_mesh(4), plan=plan,
        chunk_strips=cs, chunk_tail=ct)
    vals = _integral(g.nv, seed=1)
    gather_ex = build()
    want = gather_ex.gather_values(
        gather_ex.run(2, vals=gather_ex.host_to_device(vals)))

    monkeypatch.setattr(tiled_sharded, "lane_select_tail_sums", KERNEL_TAIL)
    kernel_ex = build()
    got = kernel_ex.gather_values(
        kernel_ex.run(2, vals=kernel_ex.host_to_device(vals)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", sorted(CASES))
def test_phase_step_matches_step(name):
    """phase_step's separately dispatched strips, tail and apply give
    the fused step's values."""
    graph, levels, cap, cs, ct = CASES[name]
    g, plan = _graph_and_plan(graph, levels, cap)
    ex = TiledPullExecutor(
        g, PageRank(), plan=plan, chunk_strips=cs, chunk_tail=ct)
    _check_case(name, ex)
    vals = jnp.asarray(_integral(g.nv, seed=2))
    got, times = ex.phase_step(vals)
    assert set(times) == {"strips", "tail", "apply"}
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ex.step(vals)))
