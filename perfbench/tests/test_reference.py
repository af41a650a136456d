"""The plain references against values worked out by hand."""

import numpy as np

from perfbench.generators import HostGraph
from perfbench.reference import colfilter, pagerank, sssp, vertex_blocks


def csc(nv, edges, weights=None):
    """HostGraph from (src, dst) pairs."""
    order = sorted(range(len(edges)), key=lambda i: (edges[i][1], edges[i][0]))
    src = np.array([edges[i][0] for i in order], np.int32)
    dst = np.array([edges[i][1] for i in order])
    row_ptr = np.searchsorted(dst, np.arange(nv + 1)).astype(np.int64)
    w = None if weights is None else np.array(
        [weights[i] for i in order], np.int32)
    return HostGraph(nv=nv, ne=len(edges), row_ptr=row_ptr, col_src=src,
                     weights=w)


def test_vertex_blocks_cover_whole_lists():
    row_ptr = np.array([0, 3, 3, 10, 11, 20])
    blocks = vertex_blocks(row_ptr, 4)
    assert blocks[0][0] == 0 and blocks[-1][1] == 5
    assert all(a < b for a, b in blocks)
    assert all(x[1] == y[0] for x, y in zip(blocks, blocks[1:]))


def test_pagerank_by_hand():
    # 0 -> 1, 0 -> 2, 1 -> 2, 2 -> 0; out-degrees 2, 1, 1
    g = csc(3, [(0, 1), (0, 2), (1, 2), (2, 0)])
    a = pagerank.ALPHA
    init = np.array([1 / 3 / 2, 1 / 3, 1 / 3])
    acc = np.array([init[2], init[0], init[0] + init[1]])
    r = (1 - a) / 3 + a * acc
    want = r / np.array([2, 1, 1])
    got = pagerank.answer(g, 1)
    assert np.allclose(got, want, rtol=1e-14)
    assert pagerank.compare(got * (1 + 1e-3), want) > 0.9e-3


def test_pagerank_sink_keeps_its_rank():
    # 0 -> 1; vertex 1 has no out-edges, so its stored value is its rank
    g = csc(2, [(0, 1)])
    a = pagerank.ALPHA
    got = pagerank.answer(g, 1)
    assert np.allclose(got, [(1 - a) / 2, (1 - a) / 2 + a * 0.5])


def test_colfilter_one_step_by_hand():
    # user 0 rated item 1 with 3, both directions
    g = csc(2, [(0, 1), (1, 0)], weights=[3, 3])
    cfg = {"K": 20, "users": 1, "items": 1}
    x0, x1 = colfilter.answers(g, cfg, 1)
    v = np.sqrt(1 / 20)
    err = 3 - 20 * v * v            # 3 - <x0, x1> = 2
    want = v + colfilter.GAMMA * (err * v - colfilter.LAMBDA * v)
    assert np.allclose(x1, want, rtol=1e-15)
    assert colfilter.leaves(cfg) == [(0, 1), (1, 2)]
    assert colfilter.compare(x1, x1, x0, x0) == 0.0
    assert colfilter.compare(x0, x1, x0, x0) == 1.0   # state left unchanged


def test_sssp_hop_counts_by_hand():
    # 0 -> 1 -> 2 -> 3, 0 -> 2, 4 isolated, 3 -> 3 self-loop
    g = csc(5, [(0, 1), (1, 2), (2, 3), (0, 2), (3, 3)])
    d = sssp.answers(g, [0, 2, 4])
    assert d[:, 0].tolist() == [0, 1, 1, 2, 5]
    assert d[:, 1].tolist() == [5, 5, 0, 1, 5]
    assert d[:, 2].tolist() == [5, 5, 5, 5, 0]
    reached = sssp.out_edges_reached(d, g.out_degrees)
    assert reached.tolist() == [5, 2, 0]
    assert sssp.compare([0, 1, 1], d[[0, 1, 2], 0]) == 0
    assert sssp.compare([0, 1, 2], d[[0, 1, 2], 0]) == 1


def test_sssp_many_roots_match_one_at_a_time():
    from perfbench.generators import kronecker

    g = kronecker.generate({"scale": 9, "edgefactor": 8, "A": 0.57,
                            "B": 0.19, "C": 0.19}, 4)
    roots = list(range(0, 32 * 7, 7))
    many = sssp.answers(g, roots)
    for j in (0, 13, 31):
        assert np.array_equal(many[:, j], sssp.answers(g, [roots[j]])[:, 0])
