#!/usr/bin/env python3
"""Probe: does Mosaic's tpu.dynamic_gather (jax 0.9) work from Pallas on
this backend, at which shapes, and at what rate?

take_along_axis(x, idx, axis) with x.shape == idx.shape == out.shape and
x 2-D lowers to tpu.dynamic_gather inside a Pallas TPU kernel
(jax/_src/pallas/mosaic/lowering.py:2464-2525). axis=1 is the per-sublane
lane gather (the tail's lane-select); axis=0 is the per-lane cross-sublane
gather (the permutation primitive). Round 2 (jax 0.8) crashed on >1-vreg
operands; jax 0.9 re-probe.
"""
import sys, os, time, functools
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax, jax.numpy as jnp, numpy as np
from jax.experimental import pallas as pl
print("platform:", jax.devices()[0].platform, file=sys.stderr)
from lux_tpu.engine.pull import hard_sync

ONLY = set(sys.argv[1:])


def kernel_ta(axis, x_ref, i_ref, o_ref):
    o_ref[:] = jnp.take_along_axis(x_ref[:], i_ref[:], axis=axis)


def make_ta(S, L, axis, reps):
    """One pallas_call gathering a (S, L) block; grid over reps blocks."""
    f = pl.pallas_call(
        functools.partial(kernel_ta, axis),
        out_shape=jax.ShapeDtypeStruct((reps * S, L), jnp.float32),
        grid=(reps,),
        in_specs=[
            pl.BlockSpec((S, L), lambda i: (i, 0)),
            pl.BlockSpec((S, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((S, L), lambda i: (i, 0)),
    )
    return f


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
        print(f"{name:46s} FAILED: {type(e).__name__}: {str(e)[:140]}",
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
    print(f"{name:46s} {dt*1e3:8.2f} ms{unit}", flush=True)
    return dt


def loop(n, f, x, idx):
    def body(i, acc):
        return acc + f(x + acc[0, 0] * 1e-30, idx)
    return jax.lax.fori_loop(0, n, body, jnp.zeros(x.shape, jnp.float32))


rng = np.random.default_rng(0)

for (S, L, axis, reps) in [
    (8, 128, 1, 1), (8, 128, 0, 1),
    (512, 128, 1, 1), (512, 128, 0, 1),
    (4096, 128, 1, 16), (4096, 128, 0, 16),
    (8192, 128, 0, 32),
]:
    n_el = reps * S * L
    x = jnp.asarray(rng.standard_normal((reps * S, L), dtype=np.float32))
    hi = S if axis == 0 else L
    idx = jnp.asarray(rng.integers(0, hi, (reps * S, L), dtype=np.int32))
    f = make_ta(S, L, axis, reps)
    timed(f"ta axis={axis} ({S},{L})x{reps}",
          lambda n, x, i, f=f: loop(n, f, x, i), x, idx, per=n_el)
