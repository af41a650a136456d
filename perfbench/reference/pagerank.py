"""Lux's PageRank (pagerank/pagerank_gpu.cu, app.h), plainly.

The stored value of a vertex is its rank divided by its out-degree (the
rank itself for a vertex with no out-edges). Init: ``1/nv`` stored that
way. One iteration: ``rank = (1 - ALPHA)/nv + ALPHA * sum of the stored
values of the in-neighbours``, stored that way again. ALPHA = 0.15 weighs
the neighbour sum, as in Lux.
"""

from __future__ import annotations

import numpy as np

from perfbench.reference import over_blocks, segment_reduce

ALPHA = 0.15


def pagerank(row_ptr: np.ndarray, col_src: np.ndarray, out_degrees,
             iterations: int, value_dtype=np.float64) -> np.ndarray:
    """Stored values after ``iterations``, kept in ``value_dtype`` between
    iterations; sums run in float64 (float32 for a narrower type)."""
    nv = row_ptr.shape[0] - 1
    acc_dtype = (np.float64 if np.dtype(value_dtype) == np.float64
                 else np.float32)
    deg = np.asarray(out_degrees).astype(acc_dtype)
    has_out = deg > 0
    safe = np.where(has_out, deg, 1)
    rank = np.full(nv, 1.0 / nv, acc_dtype)
    vals = np.where(has_out, rank / safe, rank).astype(value_dtype)
    acc = np.empty(nv, acc_dtype)

    for _ in range(iterations):
        table = vals.astype(acc_dtype)

        def block(v0, v1, table=table):
            e0, e1 = row_ptr[v0], row_ptr[v1]
            acc[v0:v1] = segment_reduce(
                np.add, table[col_src[e0:e1]], row_ptr[v0:v1 + 1], 0)

        over_blocks(block, row_ptr)
        r = (1.0 - ALPHA) / nv + ALPHA * acc
        vals = np.where(has_out, r / safe, r).astype(value_dtype)
    return vals.astype(np.float64)


def answer(graph, iterations: int, value_dtype=np.float64) -> np.ndarray:
    return pagerank(graph.row_ptr, graph.col_src, graph.out_degrees,
                    iterations, value_dtype)


CHECK = "max_rel_err"
# The control: values kept in the next precision below float32.
CONTROL_DTYPE = "bfloat16"


def compare(got: np.ndarray, want: np.ndarray) -> float:
    """Largest relative gap of any vertex (PageRank values are > 0)."""
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))
