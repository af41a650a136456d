"""Multi-host module tests: slice-major ordering/shrink-validation unit
tests plus a REAL two-process multi-controller run (jax.distributed over
localhost gloo CPU collectives) — the "same code, more nodes" contract
the reference gets from its GASNet rebuild (/root/reference/README.md:33-37).
"""

import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fake_dev(slice_index, process_index, id_):
    return types.SimpleNamespace(
        slice_index=slice_index, process_index=process_index, id=id_
    )


def test_ordered_devices_slice_major():
    from lux_tpu.parallel.multihost import ordered_devices

    # Shuffled input: two slices x two processes x two devices. The
    # ordering must group by slice first (neighboring partitions share a
    # slice, so the ghost all-gather rides ICI before DCN), then process,
    # then id.
    devs = [
        fake_dev(1, 3, 7), fake_dev(0, 0, 1), fake_dev(1, 2, 4),
        fake_dev(0, 1, 2), fake_dev(0, 0, 0), fake_dev(1, 2, 5),
        fake_dev(0, 1, 3), fake_dev(1, 3, 6),
    ]
    got = [(d.slice_index, d.process_index, d.id)
           for d in ordered_devices(devs)]
    assert got == [
        (0, 0, 0), (0, 0, 1), (0, 1, 2), (0, 1, 3),
        (1, 2, 4), (1, 2, 5), (1, 3, 6), (1, 3, 7),
    ]
    # slice_index None (single-slice backends) sorts like 0.
    devs_none = [fake_dev(None, 0, 1), fake_dev(None, 0, 0)]
    assert [d.id for d in ordered_devices(devs_none)] == [0, 1]


def test_ordered_devices_shrink_validation():
    from lux_tpu.parallel.multihost import ordered_devices

    devs = [fake_dev(0, 0, 0), fake_dev(0, 0, 1),
            fake_dev(0, 1, 2), fake_dev(0, 1, 3)]
    # Shrinking to 3 keeps a device on both processes: fine.
    assert len(ordered_devices(devs, num_parts=3)) == 4
    # Shrinking to 2 orphans process 1: multi-controller JAX requires
    # every process to own part of the computation.
    with pytest.raises(ValueError, match="processes \\[1\\]"):
        ordered_devices(devs, num_parts=2)


_WORKER = r"""
import os, sys
pid = int(sys.argv[1])
port = sys.argv[2]
out = sys.argv[3]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")

from lux_tpu.parallel.multihost import initialize, make_global_mesh

initialize(f"127.0.0.1:{{port}}", num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from lux_tpu.engine.pull_sharded import ShardedPullExecutor
from lux_tpu.graph import generate
from lux_tpu.models import PageRank

mesh = make_global_mesh()
g = generate.rmat(8, 8, seed=5)
ex = ShardedPullExecutor(g, PageRank(), mesh=mesh)
vals = ex.run(5, flush_every=0)
# Replicate the padded shard stack so every process can fetch it whole
# (device_get of a sharded global array would touch non-addressable
# shards in multi-controller mode).
rep = jax.jit(lambda v: v, out_shardings=NamedSharding(mesh, P()))(vals)
if pid == 0:
    np.save(out, ex.gather_values(rep))
print(f"proc {{pid}} done", flush=True)
"""


def test_two_process_pagerank_parity(tmp_path):
    """Two OS processes, two CPU devices each, one global 4-way mesh:
    the sharded executor must produce single-process-identical PageRank
    values over jax.distributed + gloo."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(repo=REPO))
    out = str(tmp_path / "final.npy")
    env = dict(os.environ)
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), port, out],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    try:
        logs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:   # a hung gloo peer must not outlive the test
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, lg in zip(procs, logs):
        assert p.returncode == 0, lg
    got = np.load(out)

    from lux_tpu.graph import generate
    from lux_tpu.models.pagerank import reference_pagerank

    g = generate.rmat(8, 8, seed=5)
    want = reference_pagerank(g, 5)
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-9)
