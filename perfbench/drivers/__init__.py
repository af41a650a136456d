"""Traffic drivers, one module per ``driver`` name a traffic file names.

Each module defines ``Driver(config, traffic, graph, seed)`` with:

- ``setup()``: build the system under test from the generated graph and
  warm every shape the window uses (set-up time);
- ``window(seconds) -> {metric: value}``: the measured traffic, returning
  the end-to-end metrics it measures by the host clock;
- ``release()``: free the program's state once the window has closed;
- ``check() -> {name: (value, limit)}``: the comparison with the plain
  reference of what the window produced;
- ``close()``: stop whatever the driver started;

and the attributes ``attempted``, ``failed``, ``layer`` (per-layer
readings for the metric readers) and ``work`` (algorithmic work of the
window, by ``perfbench/work``).
"""

from __future__ import annotations

import gc
import importlib
import json
import os

LIMITS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "limits.json")


def limit(name: str) -> float:
    """The limit of a compared number (``perfbench/limits.json``)."""
    with open(LIMITS) as f:
        return float(json.load(f)[name]["limit"])


def lux_graph(graph):
    """The program's ``Graph`` over the generated arrays."""
    from lux_tpu.graph.graph import Graph

    return Graph(nv=graph.nv, ne=graph.ne, row_ptr=graph.row_ptr,
                 col_src=graph.col_src, weights=graph.weights)


def program(entry: str):
    """An instance of the program ``module:Class`` names."""
    mod, cls = entry.split(":")
    return getattr(importlib.import_module(mod), cls)()


def free_device() -> None:
    import jax

    gc.collect()
    jax.clear_caches()


class Base:
    def __init__(self, config: dict, traffic: dict, graph, seed: int):
        self.config = config
        self.traffic = traffic
        self.graph = graph
        self.seed = int(seed)
        self.attempted = 0
        self.failed = 0
        self.layer = {}
        self.work = {}

    def close(self) -> None:
        pass
