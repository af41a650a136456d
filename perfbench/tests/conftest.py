"""The benchmark's own tests run on the CPU at small sizes:
``python -m pytest perfbench/tests``. JAX is held to the CPU before it is
imported."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
