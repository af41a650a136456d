"""Single-source shortest paths (push model, unit weights).

The reference SSSP is Bellman-Ford over *hop counts*: its push edge struct
carries no weight (sssp/app.h:31) and relaxation is
``min(dist[dst], dist[src] + 1)`` (sssp/sssp_gpu.cu:48-61,86-130). Init:
``dist = nv`` everywhere ("infinity", sssp_gpu.cu:733-744), ``dist[start]
= 0``, frontier = {start}; `-start` flag parsed at sssp.cc:159-163.
Checker: ``dist[dst] <= dist[src] + 1`` per edge (sssp_gpu.cu:794).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from lux_tpu.engine.push import PushProgram
from lux_tpu.graph.graph import Graph


class SSSP(PushProgram):
    name = "sssp"
    combiner = "min"
    value_dtype = jnp.uint32
    rooted = True
    packable_values = True     # distances <= nv < 2^31
    incremental_ok = True      # monotone min-merge, proven by LUX604

    def init_values(self, graph: Graph, start: int = 0) -> np.ndarray:
        dist = np.full(graph.nv, graph.nv, dtype=np.uint32)  # ∞ == nv
        dist[start] = 0
        return dist

    def init_frontier(self, graph: Graph, start: int = 0) -> np.ndarray:
        fr = np.zeros(graph.nv, dtype=bool)
        fr[start] = True
        return fr

    def relax(self, src_vals, weights):
        return src_vals + jnp.uint32(1)

    def edge_invariant(self, src_vals, dst_vals, weights):
        return dst_vals <= src_vals + jnp.uint32(1)


def reference_sssp(graph: Graph, start: int = 0) -> np.ndarray:
    """Host BFS oracle (hop counts; unreached = nv, like the reference),
    level-synchronous over the CSR in numpy."""
    csr = graph.csr()
    dist = np.full(graph.nv, graph.nv, dtype=np.uint32)
    dist[start] = 0
    frontier = np.array([start], dtype=np.int64)
    d = 0
    while frontier.size:
        d += 1
        lo = csr.row_ptr[frontier]
        cnt = csr.row_ptr[frontier + 1] - lo
        # Edge slots of every frontier vertex, concatenated.
        first = np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
        nbr = csr.col_dst[first + np.arange(first.size)]
        frontier = np.unique(nbr[dist[nbr] > d]).astype(np.int64)
        dist[frontier] = d
    return dist


def main(argv=None):
    """CLI: python -m lux_tpu.models.sssp -file g.lux -start R [-check]"""
    from lux_tpu.models.cli import run_push_app

    return run_push_app(SSSP(), argv, supports_start=True)


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv[1:]))
