"""Device layer, batch cells: 1 - (union of device-op intervals) /
(traced window), from ``perfbench/trace_reduce.py``."""


def read(ctx):
    return ctx["trace"]["idle_frac"]
