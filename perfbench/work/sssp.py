"""Hop-count traversal (unweighted SSSP) from one root: every out-edge of
a reached vertex reads its 4-byte destination index and the destination's
4-byte distance, and every vertex's distance is written once at
initialisation and read once for the answer (2 x 4 bytes)."""

from __future__ import annotations


def bytes_per_query(nv: int, reached_out_edges: int) -> int:
    return reached_out_edges * (4 + 4) + nv * 8
