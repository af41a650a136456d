"""Test configuration: run JAX on a virtual 8-device CPU mesh.

This is the TPU-world answer to "multi-node testing without a cluster"
(SURVEY.md §4): every sharded code path runs on 8 simulated devices.
``JAX_PLATFORMS=cpu`` and the XLA flag are set before jax is imported,
and subprocesses the tests start inherit both.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lux_tpu.utils.platform import virtual_cpu_flags  # noqa: E402

os.environ["XLA_FLAGS"] = virtual_cpu_flags(8)
os.environ["JAX_PLATFORMS"] = "cpu"
