"""Engines and kernels on a traversal: the algorithmic bytes of the
window's answered queries (out-edges of the vertices each reaches, plus
per-vertex state; ``perfbench/work``), over the device's busy time in the
traced window times the chip's peak HBM bandwidth, in %."""


def read(ctx):
    work = ctx["work"].get("traversal_bytes")
    busy = ctx["trace"]["busy_s"]
    if not work or not busy:
        return None
    return 100.0 * work / (busy * ctx["peaks"]["hbm_bytes_per_s"])
