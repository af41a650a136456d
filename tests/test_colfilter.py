"""Collaborative filtering parity + training-progress tests."""

import numpy as np
import pytest

from lux_tpu.engine.pull import PullExecutor
from lux_tpu.engine.pull_sharded import ShardedPullExecutor
from lux_tpu.graph import Graph, generate
from lux_tpu.models.colfilter import (
    CollaborativeFiltering,
    reference_colfilter,
    rmse,
)
from lux_tpu.parallel.mesh import make_mesh


def bipartite_ratings(n_users=60, n_items=40, ne=800, seed=0):
    """users 0..n_users-1 rate items n_users..n_users+n_items-1; edges in
    both directions so both sides update (the reference treats the graph
    as one vertex space)."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_users, size=ne)
    i = rng.integers(n_users, n_users + n_items, size=ne)
    w = rng.integers(1, 6, size=ne).astype(np.int32)
    src = np.concatenate([u, i])
    dst = np.concatenate([i, u])
    ww = np.concatenate([w, w])
    return Graph.from_edges(src, dst, nv=n_users + n_items, weights=ww)


@pytest.mark.parametrize("strategy", ["rowptr", "segment"])
def test_cf_parity_single_device(strategy):
    g = bipartite_ratings()
    ex = PullExecutor(g, CollaborativeFiltering(), sum_strategy=strategy)
    got = np.asarray(ex.run(5))
    want = reference_colfilter(g, 5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


def test_cf_parity_sharded():
    g = bipartite_ratings(seed=2)
    ex = ShardedPullExecutor(g, CollaborativeFiltering(), mesh=make_mesh(8))
    got = ex.gather_values(ex.run(5))
    want = reference_colfilter(g, 5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


def test_cf_parity_edge_chunked():
    # The NetFlix-scale path: contributions never materialize beyond one
    # (C, K) chunk. A tiny chunk forces many windows, exercising the
    # boundary gather + double-single chunk-prefix rebase.
    g = bipartite_ratings(seed=5)
    flat = PullExecutor(g, CollaborativeFiltering(), edge_chunk=0)
    chunked = PullExecutor(g, CollaborativeFiltering(), edge_chunk=128)
    a = np.asarray(flat.run(5))
    b = np.asarray(chunked.run(5))
    np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(
        b, reference_colfilter(g, 5), rtol=1e-4, atol=1e-7
    )


def test_edge_chunked_scalar_program():
    # Chunked execution is program-generic for sum combiners: PageRank
    # (scalar values, no weights) must agree with the flat engine.
    from lux_tpu.models import PageRank

    g = generate.rmat(10, 8, seed=3)
    flat = PullExecutor(g, PageRank(), edge_chunk=0)
    chunked = PullExecutor(g, PageRank(), edge_chunk=512)
    np.testing.assert_allclose(
        np.asarray(chunked.run(5)), np.asarray(flat.run(5)),
        rtol=5e-5, atol=1e-9,
    )


def test_edge_chunked_dst_slice_parity(monkeypatch):
    # The dst-slice gather (per-chunk dynamic_slice band instead of a
    # full-table gather — the big-table-cliff fix) must be numerically
    # identical to the full gather for both K-vector and scalar programs.
    from lux_tpu.models import PageRank

    monkeypatch.setenv("LUX_DST_SLICE", "1")
    g = bipartite_ratings(seed=5)
    sliced = PullExecutor(g, CollaborativeFiltering(), edge_chunk=128)
    assert sliced._dst_span > 0, "dst-slice path not enabled"
    monkeypatch.setenv("LUX_DST_SLICE", "0")
    full = PullExecutor(g, CollaborativeFiltering(), edge_chunk=128)
    assert full._dst_span == 0
    np.testing.assert_array_equal(
        np.asarray(sliced.run(5)), np.asarray(full.run(5))
    )

    monkeypatch.setenv("LUX_DST_SLICE", "1")
    gp = generate.rmat(10, 8, seed=3)
    sliced = PullExecutor(gp, PageRank(), edge_chunk=512)
    assert sliced._dst_span > 0
    np.testing.assert_allclose(
        np.asarray(sliced.run(5)),
        np.asarray(PullExecutor(gp, PageRank(), edge_chunk=0).run(5)),
        rtol=5e-5, atol=1e-9,
    )


def test_edge_chunked_auto_threshold(monkeypatch):
    # Auto mode picks chunked exactly when the flat (ne, K) contribution
    # array would cross LUX_EDGE_CHUNK_BYTES.
    g = bipartite_ratings(seed=7)
    flat_bytes = g.ne * 20 * 4
    monkeypatch.setenv("LUX_EDGE_CHUNK_BYTES", str(flat_bytes + 1))
    assert PullExecutor(g, CollaborativeFiltering()).edge_chunk == 0
    monkeypatch.setenv("LUX_EDGE_CHUNK_BYTES", str(flat_bytes - 1))
    ex = PullExecutor(g, CollaborativeFiltering())
    assert ex.edge_chunk > 0
    np.testing.assert_allclose(
        np.asarray(ex.run(3)), reference_colfilter(g, 3),
        rtol=1e-4, atol=1e-7,
    )


def test_edge_chunked_src_band_parity(monkeypatch):
    # Source-band gathers (per-chunk lax.cond; the bipartite item-side
    # src slice, PERF_NOTES.md round-2 lever) must be numerically identical to
    # full-table src gathers. Tiny chunks make user-dst chunks pure
    # item-source (narrow band) while item-dst chunks stay wide.
    from lux_tpu.engine.pull import _src_slice_plan

    g = bipartite_ratings(seed=9)
    monkeypatch.setenv("LUX_SRC_SLICE", "1")
    banded = PullExecutor(g, CollaborativeFiltering(), edge_chunk=128)
    monkeypatch.setenv("LUX_SRC_SLICE", "0")
    full = PullExecutor(g, CollaborativeFiltering(), edge_chunk=128)
    assert full._src_span == 0
    np.testing.assert_array_equal(
        np.asarray(banded.run(5)), np.asarray(full.run(5))
    )
    # The plan itself: at least the user-dst chunks must qualify.
    span, src_lo, flags = _src_slice_plan(
        g.col_src, g.ne, 128, g.nv, row_bytes=1 << 20
    )
    assert span == 0 or flags.any()


def test_boundary_dense_auto_chunk_degrades(monkeypatch):
    # A graph whose rows are nearly all empty packs too many row
    # boundaries into one edge window; the AUTO path must degrade
    # (larger windows, then the flat engine) instead of failing
    # (ADVICE r2). An explicit edge_chunk keeps the hard error.
    from lux_tpu.models import PageRank

    g = generate.star_graph(1000)   # ne=999 < nv+1 boundaries
    monkeypatch.setenv("LUX_EDGE_CHUNK_BYTES", "1")  # force auto-chunked
    ex = PullExecutor(g, PageRank())
    assert ex.edge_chunk == 0       # degraded to flat, not an error
    np.testing.assert_allclose(
        np.asarray(ex.run(3)),
        np.asarray(PullExecutor(g, PageRank(), edge_chunk=0).run(3)),
        rtol=5e-5, atol=1e-9,
    )
    with pytest.raises(ValueError, match="does not compress"):
        PullExecutor(g, PageRank(), edge_chunk=64)


def test_cf_requires_weights():
    g = generate.gnp(50, 200, seed=1)  # unweighted
    with pytest.raises(ValueError):
        PullExecutor(g, CollaborativeFiltering())


def test_cf_training_reduces_rmse():
    g = bipartite_ratings(seed=3)
    ex = PullExecutor(g, CollaborativeFiltering())
    v0 = np.asarray(ex.init_values())
    v200 = np.asarray(ex.run(200))
    assert rmse(g, v200) < rmse(g, v0)
