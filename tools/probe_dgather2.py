#!/usr/bin/env python3
"""Probe 2: rates of the primitives a grouped-tail + merge-network
permutation would compose (see PERF_NOTES.md round-3 section).

- lane gather (tpu.dynamic_gather axis=1) at 34M-element scale
- (8,128) sublane gather (axis=0) at scale
- merge-level prototype: out[i,j] = cand[i, s[i,j], l[i,j]] via 4
  lane-gathers + masked sum (one level of a 4-candidate merge network)
- XLA row gather of ~300K padded rows from a ~150 MB table (the
  inter-tile row-move stage)
"""
import sys, os, time, functools
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax, jax.numpy as jnp, numpy as np
from jax.experimental import pallas as pl
print("platform:", jax.devices()[0].platform, file=sys.stderr)
from lux_tpu.engine.pull import hard_sync

ONLY = set(sys.argv[1:])


def timed(name, fn, *args, per=None):
    if ONLY and name.split()[0] not in ONLY:
        return
    f = jax.jit(fn)
    try:
        t0 = time.perf_counter()
        hard_sync(f(jnp.int32(3), *args))
        print(f"# {name}: compile+first {time.perf_counter()-t0:.1f}s",
              file=sys.stderr, flush=True)
    except Exception as e:
        print(f"{name:44s} FAILED: {type(e).__name__}: {str(e)[:120]}",
              flush=True)
        return None
    ts = {}
    for n in (3, 13):
        best = 1e9
        for _ in range(2):
            t0 = time.perf_counter()
            hard_sync(f(jnp.int32(n), *args))
            best = min(best, time.perf_counter() - t0)
        ts[n] = best
    dt = (ts[13] - ts[3]) / 10
    unit = f"  ({dt/per*1e9:.3f} ns/item)" if per else ""
    print(f"{name:44s} {dt*1e3:8.2f} ms{unit}", flush=True)
    return dt


rng = np.random.default_rng(0)

# ---- lane gather at scale: (S,128) blocks over a big stream ----------
S, NB = 4096, 64                      # 33.5M elements, 134 MB
M = S * NB * 128


def k_lane(x_ref, i_ref, o_ref):
    o_ref[:] = jnp.take_along_axis(x_ref[:], i_ref[:], axis=1)


lane_call = pl.pallas_call(
    k_lane,
    out_shape=jax.ShapeDtypeStruct((S * NB, 128), jnp.float32),
    grid=(NB,),
    in_specs=[pl.BlockSpec((S, 128), lambda i: (i, 0)),
              pl.BlockSpec((S, 128), lambda i: (i, 0))],
    out_specs=pl.BlockSpec((S, 128), lambda i: (i, 0)),
)

x = jnp.asarray(rng.standard_normal((S * NB, 128), dtype=np.float32))
li32 = jnp.asarray(rng.integers(0, 128, (S * NB, 128), dtype=np.int32))
li8 = li32.astype(jnp.int8)


def loop(n, f, x, *rest):
    def body(i, acc):
        return acc + f(x + acc[0, 0] * 1e-30, *rest)
    return jax.lax.fori_loop(0, n, body, jnp.zeros((S * NB, 128), jnp.float32))


timed("lane-gather 33.5M i32", lambda n, x, i: loop(n, lane_call, x, i),
      x, li32, per=M)


def k_lane8(x_ref, i_ref, o_ref):
    o_ref[:] = jnp.take_along_axis(
        x_ref[:], i_ref[:].astype(jnp.int32), axis=1)


lane8_call = pl.pallas_call(
    k_lane8,
    out_shape=jax.ShapeDtypeStruct((S * NB, 128), jnp.float32),
    grid=(NB,),
    in_specs=[pl.BlockSpec((S, 128), lambda i: (i, 0)),
              pl.BlockSpec((S, 128), lambda i: (i, 0))],
    out_specs=pl.BlockSpec((S, 128), lambda i: (i, 0)),
)
timed("lane-gather 33.5M i8-idx", lambda n, x, i: loop(n, lane8_call, x, i),
      x, li8, per=M)

# ---- sublane gather within (8,128) at scale --------------------------


def k_sub(x_ref, i_ref, o_ref):
    o_ref[:] = jnp.take_along_axis(x_ref[:], i_ref[:], axis=0)


SB = 512   # rows per block = 64 sub-tiles of 8... axis0 only allows S=8
sub_call = pl.pallas_call(
    k_sub,
    out_shape=jax.ShapeDtypeStruct((S * NB, 128), jnp.float32),
    grid=(S * NB // 8,),
    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0)),
              pl.BlockSpec((8, 128), lambda i: (i, 0))],
    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
)
si32 = jnp.asarray(rng.integers(0, 8, (S * NB, 128), dtype=np.int32))
timed("sublane-gather(8) 33.5M", lambda n, x, i: loop(n, sub_call, x, i),
      x, si32, per=M)

# ---- merge-level prototype: 4 candidates per out row ------------------
R = 65536                              # out rows; cand = (R,4,128) 134MB


def k_merge(c_ref, l_ref, s_ref, o_ref):
    c = c_ref[:]                       # (Rb, 4, 128)
    l = l_ref[:]                       # (Rb, 128) int32 lane idx
    s = s_ref[:]                       # (Rb, 128) int32 cand idx
    acc = jnp.zeros(l.shape, jnp.float32)
    for k in range(4):
        g = jnp.take_along_axis(c[:, k, :], l, axis=1)
        acc = acc + jnp.where(s == k, g, 0.0)
    o_ref[:] = acc


RB = 2048
merge_call = pl.pallas_call(
    k_merge,
    out_shape=jax.ShapeDtypeStruct((R, 128), jnp.float32),
    grid=(R // RB,),
    in_specs=[pl.BlockSpec((RB, 4, 128), lambda i: (i, 0, 0)),
              pl.BlockSpec((RB, 128), lambda i: (i, 0)),
              pl.BlockSpec((RB, 128), lambda i: (i, 0))],
    out_specs=pl.BlockSpec((RB, 128), lambda i: (i, 0)),
)
cand = jnp.asarray(rng.standard_normal((R, 4, 128), dtype=np.float32))
lm = jnp.asarray(rng.integers(0, 128, (R, 128), dtype=np.int32))
sm = jnp.asarray(rng.integers(0, 4, (R, 128), dtype=np.int32))


def loopm(n, c, l, s):
    def body(i, acc):
        return acc + merge_call(c + acc[0, 0] * 1e-30, l, s)
    return jax.lax.fori_loop(0, n, body, jnp.zeros((R, 128), jnp.float32))


timed(f"merge-level {R*128/1e6:.1f}M out (4-cand)", loopm, cand, lm, sm,
      per=R * 128)

# ---- XLA row gather: 300K rows from 150 MB table ---------------------
TR = 300_000
big = jnp.asarray(rng.standard_normal((294912, 128), dtype=np.float32))
ridx = jnp.asarray(rng.integers(0, 294912, TR, dtype=np.int32))


def loopg(n, t, i):
    def body(k, acc):
        return acc + (t + acc[0] * 1e-30)[i].sum(0)
    return jax.lax.fori_loop(0, n, body, jnp.zeros((128,), jnp.float32))


timed("row-gather 300K from 150MB", loopg, big, ridx, per=TR)

# Same but table segmented under the 48MB cliff (gather from slices)
def loopg_seg(n, t, i):
    nseg = 4
    seg = 294912 // nseg
    def body(k, acc):
        tt = t + acc[0] * 1e-30
        out = jnp.zeros((128,), jnp.float32)
        for s_ in range(nseg):
            sl = jax.lax.dynamic_slice(tt, (s_ * seg, 0), (seg, 128))
            loc = jnp.clip(i - s_ * seg, 0, seg - 1)
            mask = ((i >= s_ * seg) & (i < (s_ + 1) * seg))
            out = out + jnp.where(mask[:, None], sl[loc], 0.0).sum(0)
        return acc + out
    return jax.lax.fori_loop(0, n, body, jnp.zeros((128,), jnp.float32))


timed("row-gather 300K segmented(4x)", loopg_seg, big, ridx, per=TR)
