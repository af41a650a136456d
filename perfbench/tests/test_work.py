"""Algorithmic work counts on hand-sized graphs and at the cells' sizes."""

from perfbench.work import module


def test_pagerank_bytes():
    w = module("pagerank")
    # 3 vertices, 4 edges: 4 x (4 + 4) + 3 x 12
    assert w.bytes_per_iteration(3, 4) == 68
    assert w.edges_per_iteration(3, 4) == 4
    # graph500-22: 587.2 MB per iteration, 0.717 ms at 819 GB/s
    b = w.bytes_per_iteration(1 << 22, 16 << 22)
    assert b == 587_202_560
    assert abs(b / 819e9 - 0.717e-3) < 1e-6


def test_colfilter_bytes():
    w = module("colfilter")
    # 2 vertices, 2 edges, K = 20: 2 x (4 + 4 + 80) + 2 x 240
    assert w.bytes_per_iteration(2, 2) == 656
    assert w.bytes_per_iteration(2, 2, k=1) == 2 * 12 + 2 * 12


def test_traversal_bytes():
    w = module("sssp")
    # a root that reaches 3 out-edges in a 5-vertex graph
    assert w.bytes_per_query(5, 3) == 3 * 8 + 5 * 8
