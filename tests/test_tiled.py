"""Hybrid SpMV executor: plan exactness + PageRank parity."""

import numpy as np
import pytest

from lux_tpu.engine.tiled import TiledPullExecutor
from lux_tpu.graph import generate
from lux_tpu.graph.graph import Graph
from lux_tpu.models.components import ConnectedComponents
from lux_tpu.models.pagerank import PageRank, reference_pagerank
from lux_tpu.ops.tiled_spmv import BLOCK, plan_hybrid


def edge_multiset(s, d):
    return sorted(zip(np.asarray(s).tolist(), np.asarray(d).tolist()))


def plan_edge_multiset(plan):
    """Reconstruct the (internal-id) edge multiset a plan represents."""
    edges = []
    for lev in plan.levels:
        if lev.strips.shape[0] == 0:
            continue
        t = lev.strips.astype(np.int64)
        slots, cells = np.nonzero(t.reshape(t.shape[0], -1))
        for slot, cell in zip(slots, cells):
            d = lev.rows[slot] * lev.r + cell // BLOCK
            s = lev.cols[slot] * BLOCK + (cell % BLOCK)
            edges += [(int(s), int(d))] * int(t[slot].reshape(-1)[cell])
    tail_d = np.repeat(
        np.arange(plan.nv), np.diff(plan.tail_row_ptr).astype(np.int64)
    )
    tail_s = plan.tail_sb.astype(np.int64) * BLOCK + plan.tail_lane.astype(
        np.int64
    )
    edges += list(zip(tail_s.tolist(), tail_d.tolist()))
    return sorted(edges)


@pytest.mark.parametrize("cap", [15, 127])
@pytest.mark.parametrize(
    "levels", [((8, 1),), ((8, 4),), ((128, 4), (8, 2)), ((32, 2),)]
)
def test_plan_is_exact_partition(levels, cap):
    g = generate.rmat(9, 8, seed=3)
    plan = plan_hybrid(g, levels=levels, cap=cap)
    s_int = plan.rank[g.col_src]
    d_int = plan.rank[g.col_dst]
    assert plan_edge_multiset(plan) == edge_multiset(s_int, d_int)


def test_plan_spills_count_overflow_exactly():
    # 300 parallel edges in one cell: count clips at the default cap (15),
    # the other 285 must reappear in the tail; with the legacy cap (127)
    # the clip point moves but the edge multiset is still exact.
    src = np.concatenate([np.full(300, 2), [0, 1, 3]])
    dst = np.concatenate([np.full(300, 5), [4, 4, 4]])
    g = Graph.from_edges(src, dst, nv=8)
    for cap in (15, 127):
        plan = plan_hybrid(g, levels=((8, 1),), cap=cap)
        s_int = plan.rank[g.col_src]
        d_int = plan.rank[g.col_dst]
        assert max(lev.strips.max() for lev in plan.levels) == cap
        assert plan_edge_multiset(plan) == edge_multiset(s_int, d_int)


def test_plan_rejects_unpackable_strip_heights():
    g = generate.rmat(9, 8, seed=3)
    for bad in (3, 48, 256):
        with pytest.raises(ValueError, match="strip height"):
            plan_hybrid(g, levels=((bad, 2),))


def test_plan_respects_budget_and_density_floor():
    g = generate.rmat(9, 8, seed=3)
    # budget_bytes counts device bytes: r*128 int8 counts a strip.
    plan = plan_hybrid(g, levels=((8, 1),), budget_bytes=2 * 8 * BLOCK)
    assert plan.num_strips <= 2
    legacy = plan_hybrid(
        g, levels=((8, 1),), budget_bytes=4 * 8 * BLOCK, cap=127
    )
    assert legacy.num_strips <= 4
    plan2 = plan_hybrid(g, levels=((8, 10**9),))
    assert plan2.num_strips == 0
    assert plan2.tail_sb.shape[0] == g.ne
    assert plan2.coverage == 0.0


@pytest.mark.parametrize(
    "levels",
    [((8, 1),), ((8, 4),), ((128, 8), (8, 2)),
     ((2, 2),), ((16, 2),), ((64, 2),), ((32, 4), (4, 2))],
)
def test_hybrid_pagerank_parity_rmat(levels):
    g = generate.rmat(10, 8, seed=1)
    ex = TiledPullExecutor(
        g, PageRank(), levels=levels, chunk_strips=16, chunk_tail=64
    )
    got = np.asarray(ex.run(10))
    want = reference_pagerank(g, 10)
    # Strip products are exact f32 (VPU mul-reduce); the per-row
    # cumsum-diff reductions reassociate, leaving f32-roundoff wiggle.
    # The lane-select tail is exact f32 selection.
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-9)


def test_hybrid_pagerank_parity_gnp():
    g = generate.gnp(500, 4000, seed=7)
    ex = TiledPullExecutor(
        g, PageRank(), levels=((8, 2),), chunk_strips=8, chunk_tail=128
    )
    got = np.asarray(ex.run(10))
    want = reference_pagerank(g, 10)
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-9)


def test_hybrid_all_tail_matches_plain_executor():
    from lux_tpu.engine.pull import PullExecutor

    g = generate.rmat(9, 8, seed=5)
    # min_count so high nothing tiles: pure lane-select path. Selection is
    # exact f32, but the per-destination sums run in degree-sorted edge
    # order, so f32 reassociation leaves ~1e-5 relative wiggle vs. the
    # plain executor's CSC-order sums.
    tex = TiledPullExecutor(g, PageRank(), levels=((8, 10**9),), chunk_tail=64)
    pex = PullExecutor(g, PageRank())
    a = np.asarray(tex.run(3))
    b = np.asarray(pex.run(3))
    np.testing.assert_allclose(a, b, rtol=5e-5, atol=1e-9)


def test_plan_save_load_roundtrip(tmp_path):
    from lux_tpu.ops.tiled_spmv import load_plan, save_plan

    g = generate.rmat(9, 8, seed=3)
    plan = plan_hybrid(g, levels=((128, 4), (8, 2)))
    path = str(tmp_path / "plan.npz")
    save_plan(path, plan)
    back = load_plan(path)
    assert back.nv == plan.nv and back.nvb == plan.nvb
    assert plan_edge_multiset(back) == plan_edge_multiset(plan)
    np.testing.assert_array_equal(back.order, plan.order)
    np.testing.assert_array_equal(back.tail_row_ptr, plan.tail_row_ptr)
    ex = TiledPullExecutor(g, PageRank(), plan=back, chunk_strips=16,
                           chunk_tail=64)
    got = np.asarray(ex.run(5))
    want = reference_pagerank(g, 5)
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-9)


def test_plan_legacy_npz_load(tmp_path):
    # Round-1 caches are single .npz files; load_plan keeps that reader
    # and get_cached_plan probes the legacy key before replanning.
    from lux_tpu.engine.tiled import get_cached_plan
    from lux_tpu.ops.tiled_spmv import load_plan

    g = generate.rmat(9, 8, seed=3)
    plan = plan_hybrid(g, levels=((8, 2),))
    legacy = str(tmp_path / "plan.npz")
    data = dict(
        nv=plan.nv, nvb=plan.nvb, order=plan.order, rank=plan.rank,
        nlevels=len(plan.levels),
        tail_sb=plan.tail_sb, tail_lane=plan.tail_lane,
        tail_row_ptr=plan.tail_row_ptr,
        out_degrees=plan.out_degrees, in_degrees=plan.in_degrees,
    )
    for i, lev in enumerate(plan.levels):
        data[f"lev{i}_r"] = lev.r
        data[f"lev{i}_strips"] = lev.strips
        data[f"lev{i}_rows"] = lev.rows
        data[f"lev{i}_cols"] = lev.cols
    np.savez(legacy, **data)
    back = load_plan(legacy)
    assert plan_edge_multiset(back) == plan_edge_multiset(plan)
    served = get_cached_plan(
        g, str(tmp_path / "plan.luxplan"), levels=((8, 2),), cap=127
    )
    np.testing.assert_array_equal(served.order, plan.order)
    np.testing.assert_array_equal(served.tail_sb, plan.tail_sb)


def test_plan_cache_detects_config_change(tmp_path):
    # Same r-cascade, different threshold or budget → replan, not serve
    # (current saves record levels_spec/budget_bytes; ADVICE r2).
    from lux_tpu.engine.tiled import get_cached_plan

    g = generate.rmat(9, 8, seed=3)
    path = str(tmp_path / "plan.luxplan")
    first = get_cached_plan(g, path, levels=((8, 2),), budget_bytes=1 << 20)
    assert first.levels_spec == ((8, 2),)
    served = get_cached_plan(g, path, levels=((8, 2),), budget_bytes=1 << 20)
    np.testing.assert_array_equal(served.tail_sb, first.tail_sb)
    rethr = get_cached_plan(g, path, levels=((8, 4),), budget_bytes=1 << 20)
    assert rethr.levels_spec == ((8, 4),)
    assert rethr.tail_sb.shape[0] > first.tail_sb.shape[0]
    rebud = get_cached_plan(g, path, levels=((8, 4),), budget_bytes=1 << 10)
    assert rebud.budget_bytes == 1 << 10
    assert rebud.num_strips < rethr.num_strips


def test_legacy_cap_plan_is_served(tmp_path):
    # A cap-127 cache under the legacy key is served as it is, not
    # replanned, and runs in parity with the reference. A replan (here
    # for a changed threshold) lands at the ORIGINAL .luxplan path.
    import os

    from lux_tpu.engine.tiled import get_cached_plan
    from lux_tpu.ops.tiled_spmv import save_plan

    g = generate.rmat(9, 8, seed=3)
    legacy = str(tmp_path / "plan.npz")
    save_plan(legacy + ".dir", plan_hybrid(g, levels=((8, 2),), cap=127))
    os.rename(legacy + ".dir", legacy)   # simulate a legacy-keyed cache
    path = str(tmp_path / "plan.luxplan")
    served = get_cached_plan(g, path, levels=((8, 2),), cap=15)
    assert served.cap == 127             # served, not replanned
    assert not os.path.exists(path)
    ex = TiledPullExecutor(g, PageRank(), plan=served, chunk_strips=16,
                           chunk_tail=64)
    np.testing.assert_allclose(
        np.asarray(ex.run(5)), reference_pagerank(g, 5),
        rtol=5e-5, atol=1e-9)
    replanned = get_cached_plan(g, path, levels=((8, 4),), cap=15)
    assert replanned.cap == 15
    assert os.path.exists(path)          # saved under the .luxplan name


def _cap_graph(name):
    """A skewed or a local graph with parallel edges: 24 cells hold 40
    copies and 6 hold 200, past caps 15, 31 and 127."""
    g = (generate.rmat(10, 8, seed=8) if name == "rmat"
         else generate.small_world(1000, k=12, seed=3))
    rng = np.random.default_rng(9)
    hot = rng.integers(0, g.nv, (30, 2))
    reps = np.repeat([40, 200], [24, 6])
    return Graph.from_edges(
        np.concatenate([g.col_src, np.repeat(hot[:, 0], reps)]),
        np.concatenate([g.col_dst, np.repeat(hot[:, 1], reps)]),
        nv=g.nv)


@pytest.mark.parametrize("graph", ["rmat", "small_world"])
@pytest.mark.parametrize("cap", [15, 31, 127])
def test_hybrid_pagerank_parity_by_cap(cap, graph):
    g = _cap_graph(graph)
    plan = plan_hybrid(g, levels=((8, 2),), cap=cap)
    assert max(int(lev.strips.max()) for lev in plan.levels) == cap
    ex = TiledPullExecutor(g, PageRank(), plan=plan, chunk_strips=16,
                           chunk_tail=128)
    np.testing.assert_allclose(
        np.asarray(ex.run(8)), reference_pagerank(g, 8),
        rtol=5e-5, atol=1e-9)


@pytest.mark.parametrize("cap", [15, 127])
@pytest.mark.parametrize("parts", [1, 2, 4, 8])
def test_sharded_pagerank_parity_by_cap(parts, cap):
    from lux_tpu.engine.tiled_sharded import ShardedTiledExecutor
    from lux_tpu.parallel.mesh import make_mesh

    g = _cap_graph("rmat")
    plan = plan_hybrid(g, levels=((8, 1),), cap=cap)
    ex = ShardedTiledExecutor(
        g, PageRank(), mesh=make_mesh(parts), plan=plan,
        chunk_strips=16, chunk_tail=64,
    )
    got = np.asarray(ex.gather_values(ex.run(5)))
    np.testing.assert_allclose(
        got, reference_pagerank(g, 5), rtol=5e-5, atol=1e-9)


def test_hybrid_run_resumes_from_external_vals():
    g = generate.rmat(9, 8, seed=5)
    ex = TiledPullExecutor(g, PageRank(), levels=((8, 1),), chunk_tail=64)
    full = np.asarray(ex.run(6))
    half = ex.run(3)
    resumed = np.asarray(ex.run(3, vals=half))
    np.testing.assert_allclose(resumed, full, rtol=1e-6)


def test_hybrid_step_and_init_speak_external_order():
    # The public step()/init_values() surface must match PullExecutor's
    # (cli.py drives executors through them), despite the internal
    # degree-sorted layout.
    from lux_tpu.engine.pull import PullExecutor

    g = generate.rmat(9, 8, seed=11)
    tex = TiledPullExecutor(g, PageRank(), levels=((8, 1),), chunk_tail=64)
    pex = PullExecutor(g, PageRank())
    np.testing.assert_allclose(
        np.asarray(tex.init_values()), np.asarray(pex.init_values())
    )
    a = np.asarray(tex.step(tex.step(tex.init_values())))
    b = np.asarray(pex.step(pex.step(pex.init_values())))
    np.testing.assert_allclose(a, b, rtol=5e-5, atol=1e-9)


def test_hybrid_rejects_non_spmv_programs():
    g = generate.rmat(8, 8, seed=5)
    with pytest.raises(ValueError, match="identity|source value"):
        TiledPullExecutor(g, ConnectedComponents())


@pytest.mark.parametrize(
    "levels", [((8, 2),), ((8, 1),), ((128, 4), (8, 2)), ()]
)
def test_banded_plan_identical_to_direct(levels, monkeypatch):
    # The streamed (banded) level-0 counting path must produce a plan
    # byte-identical to the direct in-memory path — same strips, same
    # tail, same selection tie-breaks — on skewed, uniform, and
    # bipartite-weighted graphs.
    graphs = [
        generate.rmat(10, 8, seed=4),
        generate.gnp(700, 6000, seed=1),
        generate.bipartite_ratings(300, 24, 3000, seed=2),
    ]
    fields = (
        "order", "rank", "tail_sb", "tail_lane", "tail_row_ptr",
    )
    for g in graphs:
        monkeypatch.setenv("LUX_PLAN_BANDED", "0")
        direct = plan_hybrid(g, levels=levels, budget_bytes=64 << 10)
        monkeypatch.setenv("LUX_PLAN_BANDED", "1")
        banded = plan_hybrid(g, levels=levels, budget_bytes=64 << 10)
        for name in fields:
            np.testing.assert_array_equal(
                getattr(direct, name), getattr(banded, name), err_msg=name
            )
        assert len(direct.levels) == len(banded.levels)
        for ld, lb in zip(direct.levels, banded.levels):
            np.testing.assert_array_equal(ld.strips, lb.strips)
            np.testing.assert_array_equal(ld.rows, lb.rows)
            np.testing.assert_array_equal(ld.cols, lb.cols)


def test_banded_helpers_multichunk():
    # The streaming machinery (cross-chunk fill bookkeeping, band
    # batching) only engages above _PLAN_CHUNK edges in production;
    # drive the helpers directly with a tiny chunk so CI covers the
    # multi-chunk paths.
    from lux_tpu.ops.tiled_spmv import (
        _cover_banded, _relabel, _strip_counts_banded,
    )

    g = generate.rmat(10, 8, seed=6)
    _, rank = _relabel(g, "degree")
    r, nvb = 8, (g.nv + BLOCK - 1) // BLOCK
    big_u, big_c = _strip_counts_banded(g, rank, r, nvb, 2)
    small_u, small_c = _strip_counts_banded(g, rank, r, nvb, 2, chunk=64)
    np.testing.assert_array_equal(big_u, small_u)
    np.testing.assert_array_equal(big_c, small_c)

    chosen = np.sort(big_u[np.argsort(-big_c, kind="stable")][:32])
    out_big = _cover_banded(g, rank, chosen, r, nvb, r * BLOCK)
    out_small = _cover_banded(g, rank, chosen, r, nvb, r * BLOCK, chunk=64)
    for a, b in zip(out_big, out_small):
        np.testing.assert_array_equal(a, b)
