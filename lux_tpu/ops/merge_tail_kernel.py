"""Two-mode (merge/copy) grouped-tail level kernel and device plan.

Executes a :class:`~lux_tpu.ops.merge_tail_plan.GroupedTailPlan`: one
pass per level over a (rows, 128) f32 stream. Output row o reads ONE
full input row per side — ``arow[o]`` / ``brow[o]`` scalar-prefetched
int32 offsets — and the int8 code plane routes lanes (v >= 0: side-A
lane v; v < 0: side-B lane v & 127). MERGE rows and COPY rows are the
same instruction sequence; a copy row is simply one whose codes are
single-sided (both offsets then point at the same row, so the second
gather is a free duplicate). That uniformity is what lets the
scheduler emit full-rate 128-slot copy rows wherever the merged order
is single-sided instead of stalling at the 64/64 merge rate.

Level 0 is the x2d gather level: ``arow`` is a source-block id into
the (nvb, 128) value operand and every row is a copy row, so one row
gather serves up to 128 tail edges of the block's run.

Two executors with identical semantics:

- :func:`level_apply_ref` — pure ``jax.numpy`` (row gather +
  ``take_along_axis`` + ``where``), used off-TPU so the whole pipeline
  is exact and testable on the CPU tier-1 mesh;
- the Pallas path — grid over ``ROWS``-row output blocks with
  ``pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2)``: the per-row
  offsets are scalar-prefetched, each output row's A and B source rows
  are DMA'd from the HBM-resident stream into VMEM, and the int8 code
  plane is read in (32, 128) blocks, its native tiling. (A (1, 128)
  block is refused by the TPU lowering: the last two block dims must be
  multiples of (8, 128) or the whole array.)

Intermediate pad lanes are never masked — the planner's code planes
only ever address lanes that hold reals (asserted by the host
simulator) — so masking happens once, at the root, before the per-dst
segment reduction.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lux_tpu.ops.merge_tail_plan import GroupedTailPlan
from lux_tpu.ops.segment import segment_sum_by_rowptr
from lux_tpu.utils import flags

BLOCK = 128


def grouped_tail_enabled() -> bool:
    """Opt-in flag for the grouped (merge-network) tail phase."""
    return flags.get_bool("LUX_GROUPED_TAIL")


@dataclasses.dataclass(eq=False)
class DeviceGroupedTail:
    """Device-resident grouped-tail plan (a pytree: jit-traceable).

    ``arow``/``brow``/``codes`` are per-level tuples — level 0 first
    (the x2d gather level), root last. Only the root stream carries a
    validity mask; ``dst_row_ptr`` are final-slot segment boundaries
    for the per-destination reduction.
    """

    arow: Tuple[jnp.ndarray, ...]    # (S_k,) int32 per level
    brow: Tuple[jnp.ndarray, ...]    # (S_k,) int32
    codes: Tuple[jnp.ndarray, ...]   # (S_k, 128) int8
    nvalid_root: jnp.ndarray         # (S_root,) int32
    dst_row_ptr: jnp.ndarray         # (nv+1,) int32 final-slot offsets
    n_levels: int                    # merge levels (excl. level 0)

    @staticmethod
    def build(plan: GroupedTailPlan, device=None) -> "DeviceGroupedTail":
        put = lambda x: jax.device_put(jnp.asarray(x), device)
        nlev = plan.n_levels
        root_rows = int(plan.level_ptr[-1] - plan.level_ptr[-2])
        assert root_rows * BLOCK < 2 ** 31, "root stream exceeds int32 slots"
        arow, brow, codes = [], [], []
        for k in range(nlev + 1):
            a, b, c, nv_, _ = plan.level(k)
            arow.append(put(np.ascontiguousarray(a)))
            brow.append(put(np.ascontiguousarray(b)))
            codes.append(put(np.ascontiguousarray(c)))
        return DeviceGroupedTail(
            arow=tuple(arow), brow=tuple(brow), codes=tuple(codes),
            nvalid_root=put(np.ascontiguousarray(nv_).astype(np.int32)),
            dst_row_ptr=put(
                np.asarray(plan.dst_row_ptr).astype(np.int32)),
            n_levels=nlev,
        )


def level_apply_ref(x, arow, brow, codes):
    """One network level in plain jax.numpy (exact, any backend)."""
    lane = codes.astype(jnp.int32) & 127
    ga = jnp.take_along_axis(x[arow], lane, axis=1)
    gb = jnp.take_along_axis(x[brow], lane, axis=1)
    return jnp.where(codes >= 0, ga, gb)


ROWS = 32  # output rows per grid step: the int8 code plane's tile height


def _k_level(arow_ref, brow_ref, x_hbm, c_ref, o_ref, abuf, bbuf, sem):
    base = pl.program_id(0) * ROWS

    def row_copies(i, a_src, b_src):
        return (
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(a_src, 1)], abuf.at[pl.ds(i, 1)], sem.at[0]),
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(b_src, 1)], bbuf.at[pl.ds(i, 1)], sem.at[1]),
        )

    for i in range(ROWS):
        for cp in row_copies(i, arow_ref[base + i], brow_ref[base + i]):
            cp.start()
    for i in range(ROWS):
        for cp in row_copies(i, 0, 0):
            cp.wait()
    v = c_ref[...].astype(jnp.int32)   # int8 bitwise ops don't lower
    lane = v & 127
    ga = jnp.take_along_axis(abuf[...], lane, axis=1)
    gb = jnp.take_along_axis(bbuf[...], lane, axis=1)
    o_ref[...] = jnp.where(v >= 0, ga, gb)


def level_apply_pallas(x, arow, brow, codes, interpret=False):
    """One network level as a Pallas call: scalar-prefetched per-row
    input offsets, row DMAs from the HBM stream, lane routing in VMEM.
    Rows past ``codes.shape[0]`` in the last block read row 0 and are
    dropped."""
    s = codes.shape[0]
    pad = -s % ROWS
    if pad:
        arow = jnp.pad(arow, (0, pad))
        brow = jnp.pad(brow, (0, pad))
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=((s + pad) // ROWS,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((ROWS, BLOCK), lambda g, ar, br: (g, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda g, ar, br: (g, 0)),
        scratch_shapes=[
            pltpu.VMEM((ROWS, BLOCK), jnp.float32),
            pltpu.VMEM((ROWS, BLOCK), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        _k_level,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s + pad, BLOCK), jnp.float32),
        interpret=interpret,
    )(arow, brow, x.astype(jnp.float32), codes)
    return out[:s]


def level_apply(x, arow, brow, codes, use_pallas=None):
    if codes.shape[0] == 0:
        return jnp.zeros((0, BLOCK), x.dtype)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return level_apply_pallas(x, arow, brow, codes)
    return level_apply_ref(x, arow, brow, codes)


def root_reduce(x, nvalid_root, dst_row_ptr):
    """Mask the root stream's pad lanes (the one masking point in the
    network) and reduce to per-destination sums."""
    live = (jnp.arange(BLOCK, dtype=jnp.int32)[None, :]
            < nvalid_root[:, None])
    flat = jnp.where(live, x, 0.0).reshape(-1)
    return segment_sum_by_rowptr(flat, dst_row_ptr)


def grouped_tail_sums(x2d, gt: DeviceGroupedTail, use_pallas=None):
    """Per-destination sums of tail-edge source values via the merge
    network; (nv,) f32. Drop-in for
    :func:`~lux_tpu.ops.tiled_spmv.lane_select_tail_sums`."""
    x = x2d.astype(jnp.float32)
    for k in range(gt.n_levels + 1):
        x = level_apply(x, gt.arow[k], gt.brow[k], gt.codes[k],
                        use_pallas=use_pallas)
    return root_reduce(x, gt.nvalid_root, gt.dst_row_ptr)


jax.tree_util.register_dataclass(
    DeviceGroupedTail,
    data_fields=["arow", "brow", "codes", "nvalid_root", "dst_row_ptr"],
    meta_fields=["n_levels"],
)
