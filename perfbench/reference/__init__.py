"""Plain host references, one module per program, written from Lux's
semantics and independent of ``lux_tpu``: numpy over the generated CSC,
in float64 (or the stated integer type), in blocks of whole destination
vertices so that the NetFlix-sized graph fits the host.

``value_dtype`` arguments select the precision the state is kept in; each
module's ``CONTROL_DTYPE`` names the next precision below the
configuration's, which ``perfbench/controls.py`` puts in the program's
place to show that the check fails it.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Tuple

import numpy as np


def dtype(name: str):
    """A numpy dtype by name, ``bfloat16`` included (from ml_dtypes)."""
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def vertex_blocks(row_ptr: np.ndarray, edges_per_block: int
                  ) -> List[Tuple[int, int]]:
    """Split vertices into ranges [v0, v1) of whole in-edge lists holding
    about ``edges_per_block`` edges each."""
    nv = row_ptr.shape[0] - 1
    cuts = np.searchsorted(row_ptr, np.arange(
        0, int(row_ptr[-1]), max(1, edges_per_block)), side="right") - 1
    bounds = sorted(set(int(c) for c in cuts) | {0, nv})
    return [(a, b) for a, b in zip(bounds[:-1], bounds[1:]) if b > a]


def segment_reduce(ufunc, per_edge: np.ndarray, row_ptr: np.ndarray,
                   identity) -> np.ndarray:
    """``ufunc`` over each vertex's in-edge slice of ``per_edge`` (edges of
    ``row_ptr[0] .. row_ptr[-1]``); ``identity`` where a vertex has none."""
    local = row_ptr - row_ptr[0]
    nv = local.shape[0] - 1
    out = np.full((nv,) + per_edge.shape[1:], identity, dtype=per_edge.dtype)
    starts = local[:-1]
    nonempty = local[1:] > starts
    if nonempty.any():
        out[nonempty] = ufunc.reduceat(per_edge, starts[nonempty], axis=0)
    return out


def over_blocks(fn: Callable[[int, int], None], row_ptr: np.ndarray,
                edges_per_block: int = 1 << 21) -> None:
    """Run ``fn(v0, v1)`` for every vertex block, on a few host threads
    (numpy releases the interpreter lock in its loops)."""
    blocks = vertex_blocks(row_ptr, edges_per_block)
    workers = max(1, min(12, (os.cpu_count() or 2) - 2))
    with ThreadPoolExecutor(workers) as pool:
        for f in [pool.submit(fn, a, b) for a, b in blocks]:
            f.result()
