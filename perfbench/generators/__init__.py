"""Seeded on-device graph generators, one module per ``generator`` name
that a configuration file under ``perfbench/configs/`` names.

Each module exposes ``generate(config: dict, seed: int) -> HostGraph``:
the CSC arrays, sorted by destination, made on the default device in one
jitted call and copied to the host once.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

import numpy as np


@dataclasses.dataclass(eq=False)
class HostGraph:
    """A generated graph in CSC form (in-edges sorted by destination, then
    source), as host arrays. ``weights`` is None for unweighted graphs."""

    nv: int
    ne: int
    row_ptr: np.ndarray          # int64 (nv + 1,)
    col_src: np.ndarray          # int32 (ne,)
    weights: Optional[np.ndarray] = None   # int32 (ne,) or None

    @property
    def in_degrees(self) -> np.ndarray:
        return np.diff(self.row_ptr)

    @property
    def out_degrees(self) -> np.ndarray:
        return np.bincount(self.col_src, minlength=self.nv)

    @property
    def col_dst(self) -> np.ndarray:
        return np.repeat(np.arange(self.nv, dtype=np.int32), self.in_degrees)


def seed_key(seed: int):
    """A PRNG key that depends on every bit of ``seed`` (``jax.random.key``
    alone keeps only the low 32 bits of a larger seed)."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def csc_arrays(src, dst, nv: int, weights=None):
    """Sort edges by (destination, source) and build ``row_ptr`` on the
    device. Traced inside the generator's jit."""
    import jax
    import jax.numpy as jnp

    if weights is None:
        dst, src = jax.lax.sort((dst, src), num_keys=2)
    else:
        dst, src, weights = jax.lax.sort((dst, src, weights), num_keys=2)
    row_ptr = jnp.searchsorted(
        dst, jnp.arange(nv + 1, dtype=dst.dtype), side="left"
    ).astype(jnp.int32)
    return row_ptr, src, weights


def to_host(nv: int, row_ptr, col_src, weights=None) -> HostGraph:
    row_ptr = np.asarray(row_ptr).astype(np.int64)
    col_src = np.asarray(col_src).astype(np.int32, copy=False)
    w = None if weights is None else np.asarray(weights).astype(
        np.int32, copy=False)
    return HostGraph(nv=nv, ne=int(col_src.shape[0]), row_ptr=row_ptr,
                     col_src=col_src, weights=w)


def generate(config: dict, seed: int) -> HostGraph:
    """The graph of ``config`` for ``seed``, by the generator it names."""
    mod = importlib.import_module(
        f"perfbench.generators.{config['generator']}")
    return mod.generate(config, seed)
