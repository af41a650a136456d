"""Algorithmic work of each program, one module per program name.

Each module computes, from the graph's sizes alone, what any
implementation of the program has to move through HBM. The counts do not
follow the implementation, so a kernel that moves fewer bytes shows as a
higher share of the roofline, never as less work.
"""

from __future__ import annotations

import importlib


def module(program: str):
    return importlib.import_module(f"perfbench.work.{program}")
