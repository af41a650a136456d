"""Compile the main path's step programs for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip described by ``topologies.get_topology_desc`` — so what the chip's
compiler would refuse (unaligned Pallas blocks, programs that do not
fit) fails here, in every PR, at no chip time. Nothing runs on the
described device; results are checked by the interpret-mode parity
test and by ``chip_smoke.py`` on the chip.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file.
"""

import re

import numpy as np
import pytest

from lux_tpu.graph import generate


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile is written to the persistent cache but
    # can never be read back without the chip.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < total < 16 << 30


@pytest.fixture(scope="module")
def rmat14():
    return generate.rmat(14, 16, seed=42)


def test_tiled_pagerank_step_compiles(one_chip, rmat14):
    from lux_tpu.engine.tiled import TiledPullExecutor
    from lux_tpu.models import PageRank

    tr = TiledPullExecutor(rmat14, PageRank()).trace_step()
    _fits_one_chip(tr["fn"].lower(*_shapes(tr["args"], one_chip)).compile())


# The scale-22 PageRank plan (Graph500 scale 22, levels ((8, 2),), an
# 8 GiB strip budget): vertices, strips, tail edges.
NV22, STRIPS22, TAIL22 = 1 << 22, 7_468_050, 23_260_164
# Its static boundary data by gather-table segment (``split_segments``
# cuts the strip stream every 47 chunks, the tail's every 95): the dst
# strip rows that start in each of the first four strip segments, the
# rows with strips past them, and the vertices whose tail edges start in
# the first tail segment and in all (the degree sort puts the hubs
# first).
STRIP_ROWS22 = (681, 1995, 4791, 14012, 144437)
TAIL_VERTS22 = (155_803, 1_984_160)


def _spread(counts, totals):
    """Item sizes: each group of ``counts`` items shares its ``totals``
    evenly."""
    return np.concatenate([
        n // c + (np.arange(c) < n % c) for c, n in zip(counts, totals)])


def _scale22_hybrid(sharding):
    """A DeviceHybrid of ShapeDtypeStruct leaves at the scale-22 plan's
    sizes: strips (228, 32768, 8, 128) int8, tail (178, 131072). Its
    static boundary data, which shape the step's extraction loops, come
    from synthetic strip rows and tail_row_ptr with the plan's segment
    counts."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops import tiled_spmv as ts

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)
    ints = lambda a: spec(a.shape, jnp.int32)
    nvb = NV22 // ts.BLOCK
    nrb = nvb * (ts.BLOCK // 8)
    c = ts.round_chunk(ts.DEFAULT_CHUNK_STRIPS, STRIPS22, 8)
    k = -(-STRIPS22 // c)
    seg = 47 * c
    per_row = _spread(STRIP_ROWS22, [seg] * 4 + [STRIPS22 - 4 * seg])
    rows = np.repeat(np.arange(per_row.size, dtype=np.int32), per_row)
    row, grp, xi, s0, s1, segs = ts.strip_boundaries(rows, k, c, nrb, 8)
    level = ts.DeviceLevel(
        r=8, segs=segs, strips=spec((k, c, 8, ts.BLOCK), jnp.int8),
        cols=spec((k, c), jnp.int32), bnd_row=ints(row), bnd_grp=ints(grp),
        xing_idx=ints(xi), xing_s0=ints(s0), xing_s1=ints(s1))
    c = ts.round_chunk(ts.DEFAULT_CHUNK_TAIL, TAIL22, 1)
    k = -(-TAIL22 // c)
    first, used = TAIL_VERTS22
    deg = np.zeros(NV22, np.int64)
    deg[:used] = _spread((first, used - first), [95 * c, TAIL22 - 95 * c])
    ptr = np.concatenate([[0], np.cumsum(deg)])
    row, grp, sub = ts.zstream_boundaries(ptr, c, 1)
    xi, s0, s1 = ts.crossing_correction(sub, 1)
    return ts.DeviceHybrid(
        levels=(level,), tail_sb=spec((k, c), jnp.int32),
        tail_lane=spec((k, c), jnp.int8), tail_bnd_row=ints(row),
        tail_bnd_grp=ints(grp), tail_xing_idx=ints(xi),
        tail_xing_s0=ints(s0), tail_xing_s1=ints(s1),
        tail_segs=ts.split_segments(ptr, k, c, 1), nvb=nvb)


def _table_reads(text, scope, nvb):
    """(instruction, operand layout) for every op under ``scope`` that
    reads an (nvb, 128) f32 operand: the row-gather table."""
    shapes = dict(re.findall(r"%([\w.\-]+) = (\S+) ", text))
    table = f"f32[{nvb},128]"
    reads = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \S+ (fusion|custom-call)\((.*?)\)",
                     line)
        if m is None or f"/{scope}/" not in line:
            continue
        kind = "kernel" if "tpu_custom_call" in line else m.group(1)
        for name in re.findall(r"%([\w.\-]+)", m.group(2)):
            if shapes.get(name, "").startswith(table):
                reads.append((kind, shapes[name]))
    return reads


def test_tiled_step_reads_both_tables_from_vmem(one_chip, rmat14):
    """The scale-22 step reads the (nvb, 128) value table from VMEM
    (layout ``S(1)``) in both loops: the tail through the VMEM-table
    kernel, the strips through XLA's row gather."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.engine.tiled import TiledPullExecutor
    from lux_tpu.models import PageRank

    dh = _scale22_hybrid(one_chip)
    vec = lambda dtype: jax.ShapeDtypeStruct((NV22,), dtype, sharding=one_chip)
    step = TiledPullExecutor(rmat14, PageRank())._jstep
    text = step.lower(vec(jnp.float32), dh, vec(jnp.int32), vec(jnp.int32),
                      None).compile().as_text()
    tail = _table_reads(text, "lux.tiled.tail_gather", dh.nvb)
    strips = _table_reads(text, "lux.tiled.strip_scan", dh.nvb)
    assert tail and strips, (tail, strips)
    for _, layout in tail + strips:
        assert "S(1)" in layout, (tail, strips)
    assert all(kind == "kernel" for kind, _ in tail), tail


def _chunk_compiles(ex, sharding, **init_kw):
    import jax
    import jax.numpy as jnp

    state = _shapes(ex.init_state(**init_kw), sharding)
    limit = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    _fits_one_chip(ex._multi_jit.lower(
        state, _shapes(ex._dg, sharding), 16, limit=limit).compile())


def test_push_sssp_chunk_compiles(one_chip, rmat14):
    from lux_tpu.engine.push import PushExecutor
    from lux_tpu.models.sssp import SSSP

    _chunk_compiles(PushExecutor(rmat14, SSSP()), one_chip, start=0)


def test_gas_adaptive_chunk_compiles(one_chip, rmat14):
    from lux_tpu.engine.gas import AdaptiveExecutor
    from lux_tpu.models.bfs import BFS

    _chunk_compiles(AdaptiveExecutor(rmat14, BFS()), one_chip, start=0)


def _level_operands(rows, out_rows, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, 128)), jnp.float32)
    arow = jnp.asarray(rng.integers(0, rows, out_rows), jnp.int32)
    brow = jnp.asarray(rng.integers(0, rows, out_rows), jnp.int32)
    codes = jnp.asarray(
        rng.integers(-128, 128, (out_rows, 128)), jnp.int8)
    return x, arow, brow, codes


def test_merge_tail_level_kernel_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.merge_tail_kernel import level_apply_pallas

    rows, out_rows = 1 << 16, 100_000   # not a multiple of the row block
    args = (
        jax.ShapeDtypeStruct((rows, 128), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((out_rows,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((out_rows,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((out_rows, 128), jnp.int8, sharding=one_chip),
    )
    compiled = jax.jit(level_apply_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("out_rows", [32, 77])
def test_merge_tail_level_kernel_matches_reference(out_rows):
    from lux_tpu.ops.merge_tail_kernel import (
        level_apply_pallas, level_apply_ref)

    x, arow, brow, codes = _level_operands(50, out_rows)
    got = level_apply_pallas(x, arow, brow, codes, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(level_apply_ref(x, arow, brow, codes)))
