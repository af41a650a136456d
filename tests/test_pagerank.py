"""PageRank parity: jitted engine vs. host numpy oracle."""

import numpy as np
import pytest

from lux_tpu.engine.pull import PullExecutor
from lux_tpu.graph import generate
from lux_tpu.models.pagerank import PageRank, reference_pagerank, true_ranks


@pytest.mark.parametrize("strategy", ["rowptr", "segment"])
def test_pagerank_parity_random(strategy):
    g = generate.gnp(500, 4000, seed=7)
    ex = PullExecutor(g, PageRank(), sum_strategy=strategy)
    got = np.asarray(ex.run(10))
    want = reference_pagerank(g, 10)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-9)


def test_reference_pagerank_matches_scatter_add_loop():
    """``reference_pagerank`` sums with bincount; the np.add.at form it
    replaced sums in another order, so float64 agrees to ~1e-15."""
    from lux_tpu.models.pagerank import ALPHA

    g = generate.rmat(10, 8, seed=4)
    deg = g.out_degrees.astype(np.float64)
    vals = np.where(deg == 0, 1.0 / g.nv, 1.0 / g.nv / np.maximum(deg, 1))
    for _ in range(5):
        acc = np.zeros(g.nv)
        np.add.at(acc, g.col_dst, vals[g.col_src])
        r = (1.0 - ALPHA) / g.nv + ALPHA * acc
        vals = np.where(deg == 0, r, r / np.maximum(deg, 1))
    np.testing.assert_allclose(
        reference_pagerank(g, 5), vals.astype(np.float32), rtol=1e-6)


def test_pagerank_parity_rmat():
    g = generate.rmat(10, 8, seed=1)
    ex = PullExecutor(g, PageRank())
    got = np.asarray(ex.run(10))
    want = reference_pagerank(g, 10)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-9)


def test_pagerank_sink_and_source_vertices():
    # Star: center has out-edges only; leaves are sinks (out-degree 0 in
    # the directed star), exercising both branches of the degree divide.
    g = generate.star_graph(10)
    ex = PullExecutor(g, PageRank())
    got = np.asarray(ex.run(5))
    want = reference_pagerank(g, 5)
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_pagerank_mass_interpretation():
    # With the reference's formula, one iteration from uniform gives
    # r(v) = 0.85/nv + 0.15 * sum_in(1/nv / outdeg(src)).
    g = generate.cycle_graph(4)  # every vertex: in=out=1
    ex = PullExecutor(g, PageRank())
    got = np.asarray(ex.run(1))
    expected = 0.85 / 4 + 0.15 * 0.25
    np.testing.assert_allclose(got, np.full(4, expected), rtol=1e-6)
    np.testing.assert_allclose(
        true_ranks(got, g.out_degrees), np.full(4, expected), rtol=1e-6
    )


def test_run_is_pipelined_and_deterministic():
    g = generate.gnp(200, 1500, seed=9)
    ex = PullExecutor(g, PageRank())
    a = np.asarray(ex.run(7))
    b = np.asarray(ex.run(7))
    np.testing.assert_array_equal(a, b)  # XLA segment sums are deterministic
