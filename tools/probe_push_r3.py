#!/usr/bin/env python3
"""Probe: why the SSSP blocked-dense phases run above their byte model
(PERF_NOTES.md round-2 #5), and what the fixes buy.

- load: uint32 row-gather+select+relax (current) vs f32 sign-bit packing
- comp: segmented (value,flag) associative min-scan (current) vs a
  block-min RMQ hierarchy (1 reduce pass + tiny tables + extraction)
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
        print(f"{name:46s} FAILED: {type(e).__name__}: {str(e)[:120]}",
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


rng = np.random.default_rng(0)
NVB = 32768
C = 1 << 17
K = 60
M = C * K
iota = jnp.arange(128, dtype=jnp.int32)

xu = jnp.asarray(
    rng.integers(0, 1 << 31, (NVB, 128), dtype=np.int64).astype(np.uint32)
)
xf = jnp.asarray(rng.standard_normal((NVB, 128), dtype=np.float32))
sb = jnp.asarray(rng.integers(0, NVB, (K, C), dtype=np.int32))
lane = jnp.asarray(rng.integers(0, 128, (K, C), dtype=np.int8))
wch = jnp.asarray(rng.integers(1, 5, (K, C), dtype=np.int32))


def loop(n, body, x, *chunks):
    def outer(i, acc):
        def inner(c, a):
            return a + body(x + (a[0] * 0).astype(x.dtype),
                            tuple(t[c] for t in chunks))
        return jax.lax.fori_loop(0, K, inner, acc)
    return jax.lax.fori_loop(0, n, outer, jnp.zeros((C,), jnp.float32))


def v_u32_load(x, ch):
    s, l = ch
    rows = x[s]
    pk = jnp.where(
        l.astype(jnp.int32)[:, None] == iota[None, :], rows, 0
    ).sum(axis=1, dtype=jnp.uint32)
    sv = pk & jnp.uint32(0x7FFFFFFF)
    active = (pk >> 31).astype(bool)
    cand = sv + jnp.uint32(1)          # SSSP relax (hop count)
    out = jnp.where(active, cand, jnp.uint32(0xFFFFFFFF))
    return out.astype(jnp.float32)     # fold into f32 acc for the loop


def v_f32_load(x, ch):
    s, l = ch
    rows = x[s]
    pk = jnp.where(
        l.astype(jnp.int32)[:, None] == iota[None, :], rows, 0.0
    ).sum(axis=1)
    active = pk < 0
    sv = jnp.abs(pk) - 1.0
    cand = sv + 1.0
    return jnp.where(active, cand, jnp.float32(3.4e38))


print(f"blocked-dense LOAD variants over {M/1e6:.1f}M edges:", flush=True)
timed("u32 packed load (current)",
      lambda n, x, s, l: loop(n, v_u32_load, x, s, l), xu, sb, lane, per=M)
timed("f32 sign-packed load",
      lambda n, x, s, l: loop(n, v_f32_load, x, s, l), xf, sb, lane, per=M)

# ---- comp variants: per-segment min over sorted segments --------------
NE = M
NV = 1 << 22
# synthetic sorted segments: row_ptr via random degrees
deg = rng.multinomial(NE, np.ones(NV) / NV)
rp = np.zeros(NV + 1, np.int64)
np.cumsum(deg, out=rp[1:])
seg_start_np = np.zeros(NE, bool)
starts = rp[:-1]
seg_start_np[starts[starts < NE]] = True
data = jnp.asarray(
    rng.integers(0, 1 << 24, NE, dtype=np.int64).astype(np.uint32)
)
dataf = jnp.asarray(rng.standard_normal(NE, dtype=np.float32))
seg_start = jnp.asarray(seg_start_np)
end_pos = jnp.asarray(np.clip(rp[1:] - 1, 0, NE - 1).astype(np.int32))
nonempty = jnp.asarray(deg > 0)


def v_assoc(n, d, ss, ep, ne_):
    from lux_tpu.ops.segment import segment_minmax_by_rowptr

    def body(i, acc):
        dd = d + (acc[0] * 0).astype(d.dtype)
        return acc + segment_minmax_by_rowptr(
            dd, ss, ep, ne_, "min"
        ).astype(jnp.float32)
    return jax.lax.fori_loop(0, n, body, jnp.zeros(NV, jnp.float32))


timed(f"assoc-scan seg-min {NE/1e6:.0f}M (current)", v_assoc,
      data, seg_start, end_pos, nonempty, per=NE)

# RMQ block-min variant (f32): block mins + log2 sparse table + per-dst
# head/tail partial rows with segmented gather tables.
BL = 128
nb = NE // BL
levels = int(np.floor(np.log2(max(nb, 2))))
srow = jnp.asarray((starts // BL).astype(np.int32))
erow = jnp.asarray(((rp[1:] - 1).clip(0) // BL).astype(np.int32))
s_np, e_np = starts, rp[1:]
bl_np = -(-s_np // BL)
br_np = (e_np // BL)
has_int = (br_np > bl_np) & (deg > 0)
intlen = np.maximum(br_np - bl_np, 1)
klev = np.floor(np.log2(intlen)).astype(np.int32)
kpow = (1 << klev).astype(np.int64)
g1 = jnp.asarray(bl_np.astype(np.int32))
g2 = jnp.asarray((br_np - kpow).clip(0).astype(np.int32))
klev_j = jnp.asarray(klev)
has_int_j = jnp.asarray(has_int)
smask = jnp.asarray(
    (np.arange(BL)[None, :] >= (s_np % BL)[:, None])
)
# head row covers [s, min(ceil(s/BL)*BL, e)); tail row [max(br*BL, s), e)
emask = jnp.asarray(
    (np.arange(BL)[None, :] < ((e_np - 1) % BL + 1)[:, None])
)
head_valid_to = jnp.asarray(np.minimum(bl_np * BL, e_np))
tail_valid_from = jnp.asarray(np.maximum(br_np * BL, s_np))
sp = jnp.asarray(s_np.astype(np.int64))
ep64 = jnp.asarray(e_np.astype(np.int64))


def v_rmq(n, d):
    BIG = jnp.float32(3.4e38)

    def body(i, acc):
        dd = d + acc[0] * 0
        d2 = dd.reshape(nb, BL)
        m0 = d2.min(axis=1)                      # block mins, 1 pass
        tabs = [m0]
        t = m0
        for k in range(1, levels + 1):
            sh = 1 << (k - 1)
            cur = t.shape[0] - sh
            t = jnp.minimum(t[:cur], t[sh : sh + cur])
            tabs.append(t)
        # interior via sparse table: two gathers at level klev
        stacked = jnp.concatenate(
            [jnp.pad(t, (0, nb - t.shape[0]), constant_values=BIG)
             for t in tabs]
        ).reshape(levels + 1, nb)
        i1 = stacked[klev_j, g1]
        i2 = stacked[klev_j, g2]
        interior = jnp.where(has_int_j, jnp.minimum(i1, i2), BIG)
        # head/tail partial rows
        iot = jnp.arange(BL, dtype=jnp.int32)
        hr = d2[srow]
        pos_h = srow.astype(jnp.int64)[:, None] * BL + iot[None, :]
        mh = (pos_h >= sp[:, None]) & (pos_h < head_valid_to[:, None])
        head = jnp.where(mh, hr, BIG).min(axis=1)
        tr = d2[erow]
        pos_t = erow.astype(jnp.int64)[:, None] * BL + iot[None, :]
        mt = (pos_t >= tail_valid_from[:, None]) & (pos_t < ep64[:, None])
        tail = jnp.where(mt, tr, BIG).min(axis=1)
        res = jnp.minimum(jnp.minimum(head, tail), interior)
        return acc + res
    return jax.lax.fori_loop(0, n, body, jnp.zeros(NV, jnp.float32))


timed(f"rmq seg-min {NE/1e6:.0f}M (f32)", v_rmq, dataf, per=NE)
