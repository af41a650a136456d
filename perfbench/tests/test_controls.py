"""Each cell's control, at a size a test run holds: the reference in the
next precision below the configuration's, put in the program's place,
fails the cell's check. (The chip runs of ``perfbench/controls.py`` read
the same at the cells' own sizes.)"""

import importlib
import os

import pytest

from perfbench import generators, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMALL = {
    "pagerank-graph500-22": {"generator": "kronecker", "scale": 12,
                             "edgefactor": 16, "A": 0.57, "B": 0.19,
                             "C": 0.19},
    "cf-netflix": {"generator": "ratings", "users": 4000, "items": 150,
                   "ratings": 60000, "K": 20,
                   "rating_probs": [0.046, 0.101, 0.287, 0.336, 0.23],
                   "user_exponent": 1.513, "item_exponent": 1.613},
    # more vertices than a uint16 distance table can name
    "sssp-serve-graph500-22": {"generator": "kronecker", "scale": 17,
                               "edgefactor": 8, "A": 0.57, "B": 0.19,
                               "C": 0.19},
}


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_fails(workload):
    cell = harness.Cell(ROOT, workload)
    drivers = importlib.import_module(
        f"perfbench.drivers.{cell.traffic['driver']}")
    graph = generators.generate(SMALL[workload], 2**31 + 5)
    driver = drivers.Driver(SMALL[workload], cell.traffic, graph, 2**31 + 5)
    driver.load_control(10.0)
    checks = driver.check()
    assert checks and all(v > lim for v, lim in checks.values()), checks
