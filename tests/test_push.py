"""Push engine: SSSP + CC parity vs host oracles, invariant checkers,
single-device and 8-way sharded."""

import numpy as np
import pytest

from lux_tpu.engine.check import check, count_violations
from lux_tpu.engine.push import (
    PushExecutor,
    ShardedPushExecutor,
    _make_tiers,
    _sparse_budgets,
)
from lux_tpu.graph import generate
from lux_tpu.models.components import ConnectedComponents, reference_components
from lux_tpu.models.sssp import SSSP, reference_sssp
from lux_tpu.parallel.mesh import make_mesh


def test_sssp_path_graph():
    g = generate.path_graph(10)
    ex = PushExecutor(g, SSSP())
    state, iters = ex.run(start=0)
    np.testing.assert_array_equal(
        np.asarray(state.values), np.arange(10, dtype=np.uint32)
    )
    assert check(g, np.asarray(state.values), SSSP(), verbose=False)


def _loop_bfs(graph, start):
    """The per-vertex loop the vectorized ``reference_sssp`` replaced."""
    csr = graph.csr()
    dist = np.full(graph.nv, graph.nv, dtype=np.uint32)
    dist[start] = 0
    frontier, d = [start], 0
    while frontier:
        d += 1
        nxt = []
        for u in frontier:
            for v in csr.col_dst[csr.row_ptr[u]:csr.row_ptr[u + 1]]:
                if dist[v] > d:
                    dist[v] = d
                    nxt.append(int(v))
        frontier = nxt
    return dist


@pytest.mark.parametrize("seed,start", [(0, 0), (1, 7), (2, 300)])
def test_reference_sssp_matches_loop_oracle(seed, start):
    g = generate.rmat(10, 8, seed=seed)
    np.testing.assert_array_equal(
        reference_sssp(g, start), _loop_bfs(g, start))


def test_sssp_random_parity():
    g = generate.gnp(400, 2400, seed=3)
    ex = PushExecutor(g, SSSP())
    state, _ = ex.run(start=5)
    got = np.asarray(state.values)
    np.testing.assert_array_equal(got, reference_sssp(g, start=5))
    assert count_violations(g, got, SSSP()) == 0


def test_sssp_unreachable_stays_infinite():
    g = generate.path_graph(6)  # directed: nothing reaches vertex 0
    ex = PushExecutor(g, SSSP())
    state, _ = ex.run(start=3)
    got = np.asarray(state.values)
    assert got[3] == 0 and got[5] == 2
    assert got[0] == g.nv and got[1] == g.nv and got[2] == g.nv


def test_sssp_detects_bad_values():
    g = generate.gnp(100, 600, seed=1)
    state, _ = PushExecutor(g, SSSP()).run(start=0)
    vals = np.asarray(state.values).copy()
    reached = np.flatnonzero(vals < g.nv // 2)
    if len(reached) > 1:
        vals[reached[1]] = 0 if reached[1] != 0 else 1  # corrupt
        vals[reached[0]] += 3
    assert count_violations(g, vals, SSSP()) >= 0  # runs; then force a fail:
    vals[:] = 0
    vals[0] = g.nv  # some edge (0->x) now has dst 0 <= src nv+1 ok; invert:
    # make one *violating* edge explicitly: dst > src+1
    src0 = g.col_src[0]
    vals[:] = 1
    vals[src0] = 0
    dst0 = g.col_dst[0]
    vals[dst0] = 5  # 5 > 0+1 → violation
    assert count_violations(g, vals, SSSP()) >= 1


def test_cc_two_components():
    # Two disjoint undirected cycles: 0-4, 5-9.
    import numpy as _np

    src = _np.array([0, 1, 2, 3, 4, 5, 6, 7, 8, 9])
    dst = _np.array([1, 2, 3, 4, 0, 6, 7, 8, 9, 5])
    from lux_tpu.graph import Graph

    g = generate.undirected(Graph.from_edges(src, dst, nv=10))
    ex = PushExecutor(g, ConnectedComponents())
    state, _ = ex.run()
    got = np.asarray(state.values)
    np.testing.assert_array_equal(got[:5], np.full(5, 4))
    np.testing.assert_array_equal(got[5:], np.full(5, 9))
    assert check(g, got, ConnectedComponents(), verbose=False)


def test_cc_random_parity():
    g = generate.undirected(generate.gnp(300, 500, seed=11))
    ex = PushExecutor(g, ConnectedComponents())
    state, _ = ex.run()
    got = np.asarray(state.values)
    np.testing.assert_array_equal(got, reference_components(g))
    assert count_violations(g, got, ConnectedComponents()) == 0


@pytest.mark.parametrize("parts", [2, 8])
def test_sharded_sssp_parity(parts):
    g = generate.gnp(500, 3000, seed=9)
    ex = ShardedPushExecutor(g, SSSP(), mesh=make_mesh(parts))
    state, _ = ex.run(start=0)
    got = ex.gather_values(state)
    np.testing.assert_array_equal(got, reference_sssp(g, start=0))


@pytest.mark.parametrize("parts", [8])
def test_sharded_cc_parity(parts):
    g = generate.undirected(generate.gnp(400, 700, seed=13))
    ex = ShardedPushExecutor(g, ConnectedComponents(), mesh=make_mesh(parts))
    state, _ = ex.run()
    got = ex.gather_values(state)
    np.testing.assert_array_equal(got, reference_components(g))


@pytest.mark.parametrize("parts", [2, 8])
def test_sharded_sparse_branch_taken_and_correct(parts):
    """The distributed frontier path: late small-frontier iterations must
    run through the sparse branch (bounded queue + push-CSR expansion)
    and still reach the exact oracle fixpoint."""
    g = generate.gnp(2000, 16000, seed=31)
    ex = ShardedPushExecutor(
        g, SSSP(), mesh=make_mesh(parts), queue_frac=4, edge_budget_frac=2
    )
    state, iters = ex.run(start=0)
    assert ex.sparse_iters > 0, "sparse branch never taken"
    assert ex.sparse_iters < iters, "dense fallback never taken"
    got = ex.gather_values(state)
    np.testing.assert_array_equal(got, reference_sssp(g, start=0))


def test_sharded_sparse_long_chain_all_sparse():
    # Single-vertex frontier each iteration: every iteration should take
    # the sparse branch on the mesh, like the single-device equivalent.
    g = generate.path_graph(1100)
    ex = ShardedPushExecutor(g, SSSP(), mesh=make_mesh(4), queue_frac=1)
    assert ex.sparse
    state, iters = ex.run(start=0)
    assert ex.sparse_iters == iters
    np.testing.assert_array_equal(
        ex.gather_values(state), np.arange(1100, dtype=np.uint32)
    )


def test_sharded_sparse_weighted_cc():
    # CC's dense initial frontier must fall back dense on iter 1 on the
    # mesh too, then the label fixpoint must match the oracle.
    g = generate.undirected(generate.gnp(600, 1200, seed=33, weighted=True))
    ex = ShardedPushExecutor(
        g, ConnectedComponents(), mesh=make_mesh(8), queue_frac=2,
        edge_budget_frac=1,
    )
    state, iters = ex.run()
    assert ex.sparse_iters < iters, "dense fallback never taken"
    got = ex.gather_values(state)
    np.testing.assert_array_equal(got, reference_components(g))


def test_blocked_dense_sssp_parity():
    # Force the packed-table row-gather + segmented-scan dense path on a
    # small graph and require the exact oracle fixpoint (including empty
    # and trailing-empty rows of the CSC).
    g = generate.gnp(700, 5000, seed=41)
    ex = PushExecutor(g, SSSP(), blocked_dense=True)
    assert ex.blocked_dense
    state, _ = ex.run(start=0)
    np.testing.assert_array_equal(
        np.asarray(state.values), reference_sssp(g, start=0)
    )


def test_blocked_dense_cc_parity_weighted():
    # max combiner + weights plumbed through the blocked chunks.
    g = generate.undirected(generate.gnp(400, 900, seed=43, weighted=True))
    ex = PushExecutor(g, ConnectedComponents(), blocked_dense=True)
    state, _ = ex.run()
    np.testing.assert_array_equal(
        np.asarray(state.values), reference_components(g)
    )


def test_blocked_dense_matches_plain_dense():
    g = generate.gnp(1000, 9000, seed=47)
    a, _ = PushExecutor(g, SSSP(), blocked_dense=True).run(start=2)
    b, _ = PushExecutor(g, SSSP(), blocked_dense=False).run(start=2)
    np.testing.assert_array_equal(np.asarray(a.values), np.asarray(b.values))


@pytest.mark.parametrize("parts", [2, 8])
def test_sharded_blocked_dense_parity(parts):
    # Force the blocked dense path on the mesh: packed-table all-gather,
    # per-shard row-gather + lane select, segmented-scan reduction.
    g = generate.gnp(900, 7000, seed=51)
    ex = ShardedPushExecutor(
        g, SSSP(), mesh=make_mesh(parts), blocked_dense=True
    )
    assert ex.blocked_dense
    state, _ = ex.run(start=0)
    np.testing.assert_array_equal(
        ex.gather_values(state), reference_sssp(g, start=0)
    )


def test_sharded_blocked_dense_weighted_cc():
    g = generate.undirected(generate.gnp(500, 1100, seed=53, weighted=True))
    ex = ShardedPushExecutor(
        g, ConnectedComponents(), mesh=make_mesh(4), blocked_dense=True
    )
    state, _ = ex.run()
    np.testing.assert_array_equal(
        ex.gather_values(state), reference_components(g)
    )


def test_sharded_blocked_matches_plain(parts=4):
    g = generate.gnp(800, 6000, seed=55)
    a, _ = ShardedPushExecutor(
        g, SSSP(), mesh=make_mesh(parts), blocked_dense=True
    ).run(start=1)
    b, _ = ShardedPushExecutor(
        g, SSSP(), mesh=make_mesh(parts), blocked_dense=False
    ).run(start=1)
    np.testing.assert_array_equal(
        np.asarray(a.values), np.asarray(b.values)
    )


def test_segmented_minmax_scan_unit():
    import jax.numpy as jnp

    from lux_tpu.ops.segment import segment_minmax_by_rowptr

    # rows: [5,3,9 | 7 | (empty) | 2,8]
    data = jnp.asarray(np.array([5, 3, 9, 7, 2, 8], np.uint32))
    row_ptr = np.array([0, 3, 4, 4, 6], np.int64)
    seg_start = jnp.asarray(np.array([1, 0, 0, 1, 1, 0], bool))
    end_pos = jnp.asarray(np.clip(row_ptr[1:] - 1, 0, 5).astype(np.int32))
    nonempty = jnp.asarray(np.diff(row_ptr) > 0)
    got = segment_minmax_by_rowptr(data, seg_start, end_pos, nonempty, "min")
    want = np.array([3, 7, np.iinfo(np.uint32).max, 2], np.uint32)
    np.testing.assert_array_equal(np.asarray(got), want)
    got = segment_minmax_by_rowptr(data, seg_start, end_pos, nonempty, "max")
    want = np.array([9, 7, 0, 8], np.uint32)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_chunked_halt_runs_exact_fixpoint():
    # Fixpoint must be unchanged by chunked on-device early-exit iteration.
    g = generate.path_graph(20)
    ex = PushExecutor(g, SSSP())
    state, iters = ex.run(start=0)
    assert iters >= 19  # needs the full diameter plus window slack
    np.testing.assert_array_equal(
        np.asarray(state.values), np.arange(20, dtype=np.uint32)
    )


def test_sparse_path_taken_and_correct():
    """Force tiny budgets so early iterations go sparse, later go dense;
    fixpoint must equal the dense-only run and the oracle."""
    g = generate.gnp(2000, 16000, seed=21)
    dense_only = PushExecutor(g, SSSP(), sparse=False)
    sd, _ = dense_only.run(start=0)
    adaptive = PushExecutor(g, SSSP(), queue_frac=4, edge_budget_frac=2)
    sa, _ = adaptive.run(start=0)
    np.testing.assert_array_equal(
        np.asarray(sa.values), np.asarray(sd.values)
    )
    np.testing.assert_array_equal(np.asarray(sa.values), reference_sssp(g, 0))


def test_sparse_overflow_falls_back_dense():
    # CC starts with a full frontier: sparse preconditions fail on iter 1,
    # so the cond must take the dense branch and still be correct.
    g = generate.undirected(generate.gnp(500, 900, seed=23))
    ex = PushExecutor(g, ConnectedComponents(), queue_frac=64)
    state, _ = ex.run()
    np.testing.assert_array_equal(
        np.asarray(state.values), reference_components(g)
    )


def test_sparse_weighted_graph():
    # Weighted graphs exercise the csr_weights permutation in the sparse
    # expansion (SSSP ignores weights, but the plumbing must not crash).
    g = generate.gnp(800, 6400, seed=25, weighted=True)
    ex = PushExecutor(g, SSSP())
    state, _ = ex.run(start=3)
    np.testing.assert_array_equal(
        np.asarray(state.values), reference_sssp(g, 3)
    )


def test_sparse_path_graph_long_chain():
    # Path graph: frontier is a single vertex every iteration — the
    # sparse path runs every iteration (ne=1099 >= the 1024 sparse gate).
    g = generate.path_graph(1100)
    ex = PushExecutor(g, SSSP(), queue_frac=1)
    assert ex.sparse
    state, iters = ex.run(start=0)
    np.testing.assert_array_equal(
        np.asarray(state.values), np.arange(1100, dtype=np.uint32)
    )


def test_tier_ladder_drops_tiers_dearer_than_blocked_sweep():
    # Graph500 scale 22 with the default fractions: the ne/8 tier costs
    # more an iteration than the blocked dense sweep, the two smaller
    # tiers less.
    nv, ne = 1 << 22, 1 << 26
    budgets = _sparse_budgets(nv, ne, 16, 8)
    full = [(4098, 131072), (32784, 1048576), (262272, 8388608)]
    assert _make_tiers(*budgets, nv, ne, blocked_dense=False) == full
    assert _make_tiers(*budgets, nv, ne, blocked_dense=True) == full[:2]


@pytest.mark.parametrize("build,tiers", [
    (lambda: PushExecutor(generate.path_graph(1100), SSSP(), queue_frac=1),
     [(256, 1024), (1228, 1024)]),
    (lambda: PushExecutor(generate.gnp(2000, 16000, seed=21), SSSP(),
                          queue_frac=4, edge_budget_frac=2),
     [(256, 1024), (628, 8000)]),
    (lambda: ShardedPushExecutor(generate.path_graph(1100), SSSP(),
                                 mesh=make_mesh(4), queue_frac=1),
     [(256, 1024), (408, 1024)]),
], ids=["path", "gnp", "sharded-path"])
def test_plain_dense_ladder_keeps_every_tier(build, tiers):
    # The plain gather/scatter dense branch keeps the whole ladder.
    ex = build()
    assert not ex.blocked_dense
    assert ex.tiers == tiers


def _levels(g, dist):
    """(frontier size, its out-edges) of each BFS level of ``dist``."""
    od = g.out_degrees
    return [(int((dist == k).sum()), int(od[dist == k].sum()))
            for k in range(int(dist[dist < g.nv].max()) + 1)]


def _adequate(tiers, cnt, out_edges):
    return any(cnt <= q and out_edges <= e for q, e in tiers)


def test_blocked_dense_ladder_sssp_parity():
    # ne = 2^18 takes the blocked dense path by default. From the hub,
    # two levels fit only the dropped ne/8 tier: they go dense, and the
    # fixpoint, its iteration count and the other levels' sparse
    # iterations are unchanged.
    g = generate.rmat(14, 16, seed=61)
    ex = PushExecutor(g, SSSP())
    assert ex.blocked_dense
    full = _make_tiers(ex.queue_cap, ex.edge_budget, g.nv, g.ne,
                       blocked_dense=False)
    dropped = [t for t in full if t not in ex.tiers]
    assert dropped == full[-1:]
    want = reference_sssp(g, start=0)
    levels = _levels(g, want)
    assert any(_adequate(dropped, *lv) and not _adequate(ex.tiers, *lv)
               for lv in levels)
    state, iters = ex.run(start=0)
    dense, dense_iters = PushExecutor(g, SSSP(), sparse=False).run(start=0)
    np.testing.assert_array_equal(np.asarray(state.values), want)
    np.testing.assert_array_equal(np.asarray(dense.values), want)
    assert iters == dense_iters == len(levels)
    assert ex.sparse_iters == sum(_adequate(ex.tiers, *lv) for lv in levels)


def test_blocked_dense_ladder_cc_parity():
    g = generate.undirected(generate.rmat(13, 16, seed=63))
    ex = PushExecutor(g, ConnectedComponents())
    assert ex.blocked_dense and ex.sparse
    state, iters = ex.run()
    dense, dense_iters = PushExecutor(
        g, ConnectedComponents(), sparse=False).run()
    np.testing.assert_array_equal(
        np.asarray(state.values), np.asarray(dense.values))
    np.testing.assert_array_equal(
        np.asarray(state.values), reference_components(g))
    assert iters == dense_iters


def test_sharded_blocked_dense_ladder_parity():
    # The sharded executor applies the same rule to its per-part shapes.
    g = generate.rmat(14, 16, seed=61)
    ex = ShardedPushExecutor(g, SSSP(), mesh=make_mesh(2))
    assert ex.blocked_dense
    nv, ne = ex.sg.max_nv, ex.sg.max_ne
    assert ex.tiers == _make_tiers(ex.queue_cap, ex.edge_budget, nv, ne,
                                   blocked_dense=True)
    full = _make_tiers(ex.queue_cap, ex.edge_budget, nv, ne,
                       blocked_dense=False)
    assert len(ex.tiers) < len(full)
    state, _ = ex.run(start=0)
    np.testing.assert_array_equal(
        ex.gather_values(state), reference_sssp(g, start=0))
