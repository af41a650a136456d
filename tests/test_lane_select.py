"""The lane-select tail's VMEM-table kernel against the row-gather form.

The kernel runs in Pallas interpret mode here (on the TPU it is what a
lowering for the chip takes, see ``lane_select_tail_sums``). Both forms
read the same ``x2d[sb, lane]`` values and feed the same Z-stream, so
every comparison is bitwise; the graphs' values are small integers, so
the sums are exact whatever their order.
"""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from lux_tpu.engine import tiled_sharded
from lux_tpu.engine.tiled import TiledPullExecutor
from lux_tpu.graph import generate
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


# (levels, count cap, chunk_strips, chunk_tail): a padded last tail
# chunk, with strip and tail chunk counts that differ; every edge in a
# strip (cap 127, so no parallel edges spill); no strip at all.
CASES = {
    "padded_last_chunk": (((8, 8),), 15, 16, 128),
    "empty_tail": (((8, 1),), 127, 16, 256),
    "empty_strip_level": (((8, 10**9),), 15, 16, 384),
}


def _graph_and_plan(levels, cap):
    g = generate.rmat(9, 8, seed=4)
    return g, ts.plan_hybrid(g, levels=levels, cap=cap)


def _integral(n, seed=0):
    return np.random.default_rng(seed).integers(0, 8, n).astype(np.float32)


def _check_case(name, ex):
    dh, m = ex.dhybrid, ex.plan.tail_sb.shape[0]
    k, c = dh.tail_sb.shape
    strip_chunks = dh.levels[0].cols.shape[0]
    if name == "padded_last_chunk":
        assert m % c and 1 < k != strip_chunks
    elif name == "empty_tail":
        assert m == 0 and strip_chunks > 0
    else:
        assert ex.plan.num_strips == 0 and m == ex.graph.ne


@pytest.mark.parametrize("name", sorted(CASES))
def test_tiled_kernel_tail_parity(name, monkeypatch):
    levels, cap, cs, ct = CASES[name]
    g, plan = _graph_and_plan(levels, cap)
    build = lambda: TiledPullExecutor(
        g, PageRank(), plan=plan, chunk_strips=cs, chunk_tail=ct)
    gather_ex = build()
    _check_case(name, gather_ex)
    vals = jnp.asarray(_integral(g.nv))
    want_spmv = np.asarray(ts.hybrid_spmv(vals, gather_ex.dhybrid))
    want_run = np.asarray(gather_ex.run(3))

    monkeypatch.setattr(ts, "lane_select_tail_sums", KERNEL_TAIL)
    kernel_ex = build()
    np.testing.assert_array_equal(
        np.asarray(ts.hybrid_spmv(vals, kernel_ex.dhybrid)), want_spmv)
    np.testing.assert_array_equal(np.asarray(kernel_ex.run(3)), want_run)


@pytest.mark.parametrize("name", sorted(CASES))
def test_sharded_tiled_kernel_tail_parity(name, monkeypatch):
    levels, cap, cs, ct = CASES[name]
    g, plan = _graph_and_plan(levels, cap)
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
