"""Lux's collaborative filtering (col_filter/colfilter_gpu.cu, app.h),
plainly: every vertex holds K factors, initially sqrt(1/K). One
iteration, for every vertex v over its in-edges e = (u -> v, rating w):

    err_e = w - <x_u, x_v>
    x_v  <- x_v + GAMMA * (sum_e err_e * x_u - LAMBDA * x_v)

with every vertex read from the state before the iteration.
"""

from __future__ import annotations

import numpy as np

from perfbench.reference import over_blocks

GAMMA = 0.00000035
LAMBDA = 0.001


def init_state(nv: int, k: int, value_dtype=np.float64) -> np.ndarray:
    return np.full((nv, k), np.sqrt(1.0 / k), dtype=value_dtype)


def step(state: np.ndarray, row_ptr: np.ndarray, col_src: np.ndarray,
         weights: np.ndarray, value_dtype=np.float64) -> np.ndarray:
    """One iteration from ``state``, the result kept in ``value_dtype``.

    Per block of destinations: the edge errors take their dot products
    from the state rounded to float32, summed in float64; the sums over
    in-edges are the sparse product ``A_err @ x`` in float64 (float32 for
    a narrower ``value_dtype``), ``A_err[v, u]`` the error of edge u -> v.
    """
    import scipy.sparse

    dt = np.float64 if np.dtype(value_dtype) == np.float64 else np.float32
    x = state.astype(dt)
    x32 = state.astype(np.float32)
    acc = np.empty_like(x)
    in_deg = np.diff(row_ptr)
    nv = state.shape[0]

    def block(v0, v1):
        e0, e1 = row_ptr[v0], row_ptr[v1]
        src = col_src[e0:e1]
        xu = np.take(x32, src, axis=0)
        xv = np.repeat(x32[v0:v1], in_deg[v0:v1], axis=0)
        err = weights[e0:e1] - np.einsum("ek,ek->e", xu, xv,
                                         dtype=np.float64)
        a_err = scipy.sparse.csr_matrix(
            (err.astype(dt), src, row_ptr[v0:v1 + 1] - e0),
            shape=(v1 - v0, nv))
        acc[v0:v1] = a_err @ x

    over_blocks(block, row_ptr, 1 << 19)
    return (x + GAMMA * (acc - LAMBDA * x)).astype(value_dtype)


def answers(graph, config: dict, steps: int, value_dtype=np.float64):
    """The states after 0, 1, ..., ``steps`` iterations."""
    x = init_state(graph.nv, int(config["K"]), value_dtype)
    out = [x]
    for _ in range(steps):
        x = step(x, graph.row_ptr, graph.col_src, graph.weights, value_dtype)
        out.append(x)
    return out


def leaves(config: dict):
    """Row ranges compared on their own: users, then items."""
    users, items = int(config["users"]), int(config["items"])
    return [(0, users), (users, users + items)]


CHECK = "delta_rel_err"
# The control: factors kept in the next precision below float32.
CONTROL_DTYPE = "bfloat16"


def compare(got: np.ndarray, want: np.ndarray, got_init: np.ndarray,
            want_init: np.ndarray) -> float:
    """Gap between the two sides' changes from their own initial state:
    ||(got - got_init) - (want - want_init)|| / ||want - want_init||."""
    d_got = np.asarray(got, np.float64) - np.asarray(got_init, np.float64)
    d_want = np.asarray(want, np.float64) - np.asarray(want_init, np.float64)
    return float(np.linalg.norm(d_got - d_want) / np.linalg.norm(d_want))
