#!/usr/bin/env python3
"""Round-2 microbenches: tail-select variants + int4 strips (v5e).

Measurement discipline per PERF_NOTES.md: hard syncs, measured op carried
through a fori_loop via a data dependency, two trip counts (3/13) to
subtract fixed dispatch cost. All device arrays are jit ARGUMENTS
(closed-over arrays would bake into the program as constants). Trip
count is traced, so each variant compiles once.
"""
import sys, os, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax, jax.numpy as jnp, numpy as np
print("platform:", jax.devices()[0].platform, file=sys.stderr)
from lux_tpu.engine.pull import hard_sync

ONLY = set(sys.argv[1:])  # run a subset: names as args


def timed(name, fn, *args, per=None):
    if ONLY and name.split()[0] not in ONLY:
        return
    f = jax.jit(fn)
    t0 = time.perf_counter()
    hard_sync(f(jnp.int32(3), *args))
    print(f"# {name}: compile+first {time.perf_counter()-t0:.1f}s",
          file=sys.stderr, flush=True)
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
    print(f"{name:42s} {dt*1e3:8.2f} ms{unit}", flush=True)
    return dt


NVB = 32768          # rmat22-sized table: (32768,128) f32 = 16 MB
C = 1 << 17
K = 60
M = C * K

rng = np.random.default_rng(0)
x2d = jnp.asarray(rng.standard_normal((NVB, 128), dtype=np.float32))
sb = jnp.asarray(rng.integers(0, NVB, (K, C), dtype=np.int32))
lane = jnp.asarray(rng.integers(0, 128, (K, C), dtype=np.int8))

iota = jnp.arange(128, dtype=jnp.int32)


def loop(n, body, x, *chunks):
    def outer(i, acc):
        def inner(c, a):
            return a + body(x + a[0] * 1e-30, tuple(t[c] for t in chunks))
        return jax.lax.fori_loop(0, K, inner, acc)
    return jax.lax.fori_loop(0, n, outer, jnp.zeros((C,), jnp.float32))


def v_where(x, ch):
    s, l = ch
    rows = x[s]
    return jnp.where(
        l.astype(jnp.int32)[:, None] == iota[None, :], rows, 0.0
    ).sum(axis=1)


def v_take_along(x, ch):
    s, l = ch
    rows = x[s]
    return jnp.take_along_axis(rows, l.astype(jnp.int32)[:, None], axis=1)[:, 0]


def v_bare(x, ch):
    s, l = ch
    return x[s].sum(axis=1)


print(f"tail variants over {M/1e6:.1f}M edges, table 16MB:", flush=True)
timed("where+sum (current)",
      lambda n, x, s, l: loop(n, v_where, x, s, l), x2d, sb, lane, per=M)
timed("take_along_axis",
      lambda n, x, s, l: loop(n, v_take_along, x, s, l), x2d, sb, lane, per=M)
timed("bare gather+rowsum (floor)",
      lambda n, x, s, l: loop(n, v_bare, x, s, l), x2d, sb, lane, per=M)

# ---- strip contraction dtype variants --------------------------------
CS = 1 << 15
KS = 24
T = CS * KS
st8 = jnp.asarray(rng.integers(0, 3, (KS, CS, 8, 128), dtype=np.int8))
cols = jnp.asarray(rng.integers(0, NVB, (KS, CS), dtype=np.int32))


def sloop(n, x, strips, co):
    def outer(i, acc):
        def inner(c, a):
            xb = (x + a[0, 0] * 1e-30)[co[c]]
            return a + (strips[c].astype(jnp.float32) * xb[:, None, :]).sum(-1)
        return jax.lax.fori_loop(0, KS, inner, acc)
    return jax.lax.fori_loop(0, n, outer, jnp.zeros((CS, 8), jnp.float32))


print(f"\nstrip contraction over {T/1e6:.1f}M strips (8,128):", flush=True)
timed("int8 strips (current)", sloop, x2d, st8, cols, per=T)
timed("int4 strips", sloop, x2d, st8.astype(jnp.int4), cols, per=T)
