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
