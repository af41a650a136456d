#!/usr/bin/env python3
"""Probe the merge-level kernel's Mosaic requirements before building
the merge-tail network: (a) repeat-by-2 along sublanes inside a kernel
(broadcast+reshape and jnp.repeat lowerings), (b) PrefetchScalarGridSpec
with per-block dynamic input offsets, (c) the full 2-cand merge level at
scale, (d) correctness vs numpy."""
import sys, os, time, functools
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax, jax.numpy as jnp, numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
print("platform:", jax.devices()[0].platform, file=sys.stderr)
from lux_tpu.engine.pull import hard_sync

rng = np.random.default_rng(0)


def k_merge(aoff_ref, boff_ref, a_ref, b_ref, i_ref, o_ref):
    a = a_ref[...]                       # (8, 128)
    b = b_ref[...]
    arep = jnp.broadcast_to(a[:, None, :], (8, 2, 128)).reshape(16, 128)
    brep = jnp.broadcast_to(b[:, None, :], (8, 2, 128)).reshape(16, 128)
    v = i_ref[...].astype(jnp.int32)   # int8 bitwise ops don't lower
    lane = v & 127
    ga = jnp.take_along_axis(arep, lane, axis=1)
    gb = jnp.take_along_axis(brep, lane, axis=1)
    o_ref[...] = jnp.where(v >= 0, ga, gb)


def make_merge(G, R_in):
    """G out blocks of (16,128); A/B windows of (8,128) at per-block
    prefetched 8-row-block offsets into one (R_in,128) stream."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((8, 128), lambda g, aoff, boff: (aoff[g], 0)),
            pl.BlockSpec((8, 128), lambda g, aoff, boff: (boff[g], 0)),
            pl.BlockSpec((16, 128), lambda g, aoff, boff: (g, 0)),
        ],
        out_specs=pl.BlockSpec((16, 128), lambda g, aoff, boff: (g, 0)),
    )
    return pl.pallas_call(
        k_merge,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G * 16, 128), jnp.float32),
    )


# -- correctness on a tiny case ----------------------------------------
G = 4
R_in = 64
stream = rng.standard_normal((R_in, 128), dtype=np.float32)
aoff = rng.integers(0, R_in // 8 - 1, G).astype(np.int32)
boff = rng.integers(0, R_in // 8 - 1, G).astype(np.int32)
idx = rng.integers(-128, 128, (G * 16, 128)).astype(np.int8)

f = jax.jit(make_merge(G, R_in))
try:
    got = np.asarray(hard_sync(f(
        jnp.asarray(aoff), jnp.asarray(boff),
        jnp.asarray(stream), jnp.asarray(stream), jnp.asarray(idx),
    )))
except Exception as e:
    print("merge kernel FAILED:", type(e).__name__, str(e)[:300])
    sys.exit(1)

want = np.empty_like(got)
for g in range(G):
    aw = stream[8 * aoff[g] : 8 * aoff[g] + 8]
    bw = stream[8 * boff[g] : 8 * boff[g] + 8]
    for i in range(16):
        for j in range(128):
            v = int(idx[16 * g + i, j])
            lane = v & 127
            src = aw if v >= 0 else bw
            want[16 * g + i, j] = src[i // 2, lane]
np.testing.assert_allclose(got, want)
print("merge kernel CORRECT on tiny case", flush=True)

# -- rate at scale ------------------------------------------------------
G = 1 << 17          # 2M out rows
R_in = G * 8 + 8
stream_b = jnp.asarray(rng.standard_normal((R_in, 128), dtype=np.float32))
aoff_b = jnp.asarray(
    rng.integers(0, R_in // 8 - 1, G, dtype=np.int64).astype(np.int32))
boff_b = jnp.asarray(
    rng.integers(0, R_in // 8 - 1, G, dtype=np.int64).astype(np.int32))
idx_b = jnp.asarray(rng.integers(-128, 128, (G * 16, 128)).astype(np.int8))
fb = jax.jit(make_merge(G, R_in))
M = G * 16 * 128

t0 = time.perf_counter()
hard_sync(fb(aoff_b, boff_b, stream_b, stream_b, idx_b))
print(f"# compile+first {time.perf_counter()-t0:.1f}s", file=sys.stderr)
for _ in range(3):
    t0 = time.perf_counter()
    hard_sync(fb(aoff_b, boff_b, stream_b, stream_b, idx_b))
    dt = time.perf_counter() - t0
    print(f"merge level {M/1e6:.0f}M slots: {dt*1e3:.2f} ms "
          f"({dt/M*1e9:.3f} ns/slot)", flush=True)
