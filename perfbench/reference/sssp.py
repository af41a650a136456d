"""Hop-count SSSP (Lux's sssp on an unweighted graph), plainly: a
level-synchronous breadth-first search over the in-edges of the CSC, for
up to 32 roots at once, one bit of a uint32 per root. A vertex's distance
is the level at which its root's bit first reaches it; unreached vertices
keep ``nv``.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from perfbench.reference import over_blocks, segment_reduce

LANES = 32


def hop_counts(row_ptr: np.ndarray, col_src: np.ndarray,
               roots: Sequence[int]) -> np.ndarray:
    """(nv, len(roots)) uint32 distances, ``len(roots) <= 32``."""
    nv = row_ptr.shape[0] - 1
    r = len(roots)
    if not 0 < r <= LANES:
        raise ValueError(f"1 to {LANES} roots at once, got {r}")
    bits = np.zeros(nv, np.uint32)
    for j, v in enumerate(roots):
        bits[v] |= np.uint32(1 << j)
    visited = bits.copy()
    frontier = bits
    dist = np.full((nv, r), nv, np.uint32)
    dist[np.asarray(roots), np.arange(r)] = 0
    reach = np.empty(nv, np.uint32)
    level = 0
    while frontier.any():
        level += 1

        def block(v0, v1, frontier=frontier):
            e0, e1 = row_ptr[v0], row_ptr[v1]
            reach[v0:v1] = segment_reduce(
                np.bitwise_or, frontier[col_src[e0:e1]],
                row_ptr[v0:v1 + 1], 0)

        over_blocks(block, row_ptr)
        new = reach & ~visited
        visited |= new
        frontier = new
        hit = np.flatnonzero(new)
        if hit.size:
            lane = np.unpackbits(new[hit].view(np.uint8).reshape(-1, 4),
                                 axis=1, bitorder="little")[:, :r]
            sub = dist[hit]
            sub[lane.astype(bool)] = level
            dist[hit] = sub
    return dist


def out_edges_reached(dist: np.ndarray, out_degrees: np.ndarray
                      ) -> np.ndarray:
    """Per root: the out-edges of the vertices it reaches."""
    nv = dist.shape[0]
    return (out_degrees.astype(np.int64)[:, None] * (dist < nv)).sum(axis=0)


def answers(graph, roots: Sequence[int], value_dtype=np.uint32
            ) -> np.ndarray:
    """Distances kept in ``value_dtype`` (a narrower type wraps, as a
    table of that type would)."""
    return hop_counts(graph.row_ptr, graph.col_src, roots).astype(
        value_dtype).astype(np.uint32)


CHECK = "mismatches"
# The control: distances kept in the next integer type below uint32.
CONTROL_DTYPE = "uint16"


def compare(got: Sequence[int], want: np.ndarray) -> int:
    """How many of the answered values differ from the reference."""
    return int(np.count_nonzero(np.asarray(got, np.int64)
                                != want.astype(np.int64)))
