#!/usr/bin/env python3
"""Probe 3: is the tail row gather bandwidth-bound (halves with bf16
rows) or per-row latency-bound (doesn't)?

Layout under test: value table as (nvb*2, 128) bf16 where row 2b holds
hi[64 srcs]||lo[64 srcs]... actually packed as one row per 64-src
half-block: row h = [hi(v_0..v_63) || lo(v_0..v_63)] — per tail edge one
256 B row gather + two lane selects (lane, lane+64) reconstructs the f32
value to ~2^-16 rel.
"""
import sys, os, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax, jax.numpy as jnp, numpy as np
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
NVB = 32768          # (32768,128) f32 = 16 MB table (RMAT22 shape)
C = 1 << 17
K = 60               # 7.9M edges per call... use 60 chunks like r2 probe
M = C * K

xf32 = jnp.asarray(rng.standard_normal((NVB, 128), dtype=np.float32))
# hi/lo packed: table of 64-src half-rows, twice as many rows, bf16
xbf = jnp.asarray(
    rng.standard_normal((NVB * 2, 128), dtype=np.float32)
).astype(jnp.bfloat16)
sb32 = jnp.asarray(rng.integers(0, NVB, (K, C), dtype=np.int32))
sb64 = jnp.asarray(rng.integers(0, NVB * 2, (K, C), dtype=np.int32))
lane = jnp.asarray(rng.integers(0, 64, (K, C), dtype=np.int8))
iota = jnp.arange(128, dtype=jnp.int32)


def loop(n, body, x, *chunks):
    def outer(i, acc):
        def inner(c, a):
            return a + body(x + a[0].astype(x.dtype) * 1e-30,
                            tuple(t[c] for t in chunks))
        return jax.lax.fori_loop(0, K, inner, acc)
    return jax.lax.fori_loop(0, n, outer, jnp.zeros((C,), jnp.float32))


def v_bare_f32(x, ch):
    (s,) = ch
    return x[s].sum(axis=1)


def v_bare_bf16(x, ch):
    (s,) = ch
    return x[s].astype(jnp.float32).sum(axis=1)


def v_hilo(x, ch):
    s, l = ch
    rows = x[s]                      # (C,128) bf16
    li = l.astype(jnp.int32)
    hi = jnp.where(li[:, None] == iota[None, :], rows, 0).sum(axis=1)
    lo = jnp.where((li[:, None] + 64) == iota[None, :], rows, 0).sum(axis=1)
    return hi.astype(jnp.float32) + lo.astype(jnp.float32)


def v_f32_select(x, ch):
    s, l = ch
    rows = x[s]
    li = l.astype(jnp.int32)
    return jnp.where(li[:, None] == iota[None, :], rows, 0.0).sum(axis=1)


print(f"tail gather variants over {M/1e6:.1f}M edges:", flush=True)
timed("bare f32 512B rows (r2 floor)",
      lambda n, x, s: loop(n, v_bare_f32, x, s), xf32, sb32, per=M)
timed("bare bf16 256B rows",
      lambda n, x, s: loop(n, v_bare_bf16, x, s), xbf, sb64, per=M)
timed("f32 gather+select (current tail)",
      lambda n, x, s, l: loop(n, v_f32_select, x, s, l), xf32, sb32, lane,
      per=M)
timed("bf16 hilo gather+2select",
      lambda n, x, s, l: loop(n, v_hilo, x, s, l), xbf, sb64, lane, per=M)
