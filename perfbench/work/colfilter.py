"""Collaborative filtering (Lux's SGD update, K factors in float32) over a
weighted CSC graph, per iteration: every in-edge reads its 4-byte source
index, its 4-byte rating and the K x 4-byte vector of its source; every
vertex reads its own vector twice (the edge dot products and the update)
and writes the new one (3 x K x 4 bytes)."""

from __future__ import annotations


def edges_per_iteration(nv: int, ne: int) -> int:
    return ne


def bytes_per_iteration(nv: int, ne: int, k: int = 20) -> int:
    return ne * (4 + 4 + 4 * k) + nv * 3 * 4 * k
