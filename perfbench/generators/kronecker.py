"""Graph500 Kronecker generator, on the device.

The rule of the Graph500 specification (section 3, "Graph Generation"):
``edgefactor * 2**scale`` directed edges; each edge picks one quadrant of
the adjacency matrix per bit level with the initiator probabilities
A, B, C and D = 1 - A - B - C, the quadrant's row bit going to the source
and its column bit to the destination. Vertex labels are then permuted at
random, as the specification's generator does. Duplicate edges and
self-loops are kept, as the generator emits them.

Config keys: ``scale``, ``edgefactor``, ``A``, ``B``, ``C``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.generators import HostGraph, csc_arrays, seed_key, to_host


def quadrant_bits(u, a: float, b: float, c: float):
    """Row (source) and column (destination) bit of the quadrant that the
    uniform draw ``u`` selects: [0, A) -> (0, 0), [A, A+B) -> (0, 1),
    [A+B, A+B+C) -> (1, 0), the rest -> (1, 1)."""
    src_bit = u >= a + b
    dst_bit = ((u >= a) & (u < a + b)) | (u >= a + b + c)
    return src_bit, dst_bit


def kronecker_edges(key, scale: int, ne: int, a: float, b: float, c: float):
    """The unpermuted edge list: int32 (src, dst), each in [0, 2**scale)."""

    def level(i, carry):
        src, dst = carry
        u = jax.random.uniform(jax.random.fold_in(key, i), (ne,), jnp.float32)
        sb, db = quadrant_bits(u, a, b, c)
        return ((src << 1) | sb.astype(jnp.int32),
                (dst << 1) | db.astype(jnp.int32))

    zero = jnp.zeros((ne,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _generate(key, scale: int, ne: int, a: float, b: float, c: float):
    k_edges, k_perm = jax.random.split(key)
    src, dst = kronecker_edges(k_edges, scale, ne, a, b, c)
    perm = jax.random.permutation(k_perm, 1 << scale).astype(src.dtype)
    row_ptr, col_src, _ = csc_arrays(perm[src], perm[dst], 1 << scale)
    return row_ptr, col_src


def generate(config: dict, seed: int) -> HostGraph:
    scale = int(config["scale"])
    ne = int(config["edgefactor"]) << scale
    row_ptr, col_src = _generate(
        seed_key(seed), scale, ne, float(config["A"]), float(config["B"]),
        float(config["C"]))
    return to_host(1 << scale, row_ptr, col_src)
