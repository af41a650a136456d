"""PageRank over a CSC graph, per iteration: every in-edge reads its
4-byte source index and the 4-byte value of its source; every vertex
reads its old value and its out-degree and writes its new value
(3 x 4 bytes)."""

from __future__ import annotations


def edges_per_iteration(nv: int, ne: int) -> int:
    return ne


def bytes_per_iteration(nv: int, ne: int) -> int:
    return ne * (4 + 4) + nv * 12
