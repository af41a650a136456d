"""Lane-select tail values from a VMEM-resident value table (Pallas).

Every tail edge of the tiled SpMV reads one value, ``x2d[sb, lane]``:
one 128-lane row of the (nvb, 128) f32 value table, one lane of that
row. XLA serves the row read as a gather whose table sits in VMEM or in
HBM as its memory-space assignment decides, per loop, by heuristic; at
RMAT22 a gather from the HBM table ran at 8.3 ns/row and one from the
VMEM table at 1.5 ns/row (TPU v5e), and XLA gave the tail loop the HBM
table.

This kernel makes the residency explicit: the table is one whole-array
VMEM operand, so XLA places it in VMEM before the call, and each grid
step serves ``EDGES`` edges from it. Edges are taken ``ROWS`` at a time:
their rows are stacked into a (ROWS, 128) tile by dynamic-sublane loads
(the source block ids come from SMEM), the tile is transposed so that
edge j sits in lane j, and the one-hot lane select
``where(lane == sublane, row, 0).sum()`` reduces over sublanes into one
lane-dense (1, ROWS) row of the output. The select adds 127 zeros to the
chosen value, so every output is exactly ``x2d[sb, lane]``, as in the
XLA form. At RMAT22 on a v5e this reads 1.52 ns an edge, select
included: the rate of XLA's own row gather from a VMEM table.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128
ROWS = 128        # edges per select tile: one (128, 128) transpose
EDGES = 1 << 14   # edges per grid step: a multiple of 32 * ROWS, so the
                  # int8 lane block is whole (32, 128) tiles
# Largest value table held whole in VMEM: half of a v5e core's 128 MiB,
# leaving the other half to the rest of the step. Larger tables keep
# the XLA row gather.
VMEM_TABLE_BYTES = 64 << 20


# The kernel's scoped VMEM beyond the table: its blocks and scratch take
# under 1 MiB. Where XLA cannot place the table operand in VMEM ahead
# of the call (a jit parameter, say), it stages it in this scope.
VMEM_WORK_BYTES = 8 << 20


def _table_bytes(x2d) -> int:
    return x2d.shape[0] * x2d.shape[1] * 4


def vmem_table_fits(x2d) -> bool:
    return _table_bytes(x2d) <= VMEM_TABLE_BYTES


def lane_select_ref(x2d, sb, lane):
    """``x2d[sb, lane]`` as a row gather and a one-hot lane select."""
    iota = jnp.arange(BLOCK, dtype=jnp.int32)
    return jnp.where(
        lane.astype(jnp.int32)[:, None] == iota[None, :], x2d[sb], 0.0
    ).sum(axis=1)


def _k_select(sb_ref, lane_ref, x_ref, o_ref, lane32):
    # int8 rows cannot be sliced at a dynamic sublane; int32 ones can.
    lane32[...] = lane_ref[...].astype(jnp.int32)
    sub = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, ROWS), 0)

    def tile(g):
        base = g * ROWS
        rows = jnp.concatenate(
            [x_ref[pl.ds(sb_ref[base + j], 1), :] for j in range(ROWS)])
        hit = sub == lane32[pl.ds(g, 1), :]          # (lane, edge) one-hot
        o_ref[pl.ds(g, 1), :] = jnp.sum(
            jnp.where(hit, rows.T, 0.0), axis=0, keepdims=True)

    def pair(i, carry):
        # Two tiles a loop step: 1.52 against 2.0 ns an edge on a v5e.
        tile(2 * i)
        tile(2 * i + 1)
        return carry

    jax.lax.fori_loop(0, lane_ref.shape[0] // 2, pair, 0)


def lane_select_pallas(x2d, sb, lane, interpret=False):
    """(M,) f32 ``x2d[sb, lane]`` for (M,) int32 ``sb`` and int8 ``lane``.

    Up to ``EDGES`` edges run as one grid step whose blocks are the whole
    arrays (padded to a pair of tiles); more run ``EDGES`` a step. Pad
    edges read row 0 and are dropped."""
    m = sb.shape[0]
    step = -(-m // (2 * ROWS)) * 2 * ROWS if m <= EDGES else EDGES
    pad = -m % step
    if pad:
        sb = jnp.pad(sb, (0, pad))
        lane = jnp.pad(lane, (0, pad))
    tiles = step // ROWS
    out = pl.pallas_call(
        _k_select,
        grid=((m + pad) // step,),
        in_specs=[
            pl.BlockSpec((step,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((tiles, ROWS), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tiles, ROWS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(((m + pad) // ROWS, ROWS),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((tiles, ROWS), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_table_bytes(x2d) + VMEM_WORK_BYTES),
        interpret=interpret,
    )(sb, lane.reshape(-1, ROWS), x2d)
    return out.reshape(-1)[:m]

