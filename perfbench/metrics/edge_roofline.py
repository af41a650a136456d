"""Engines and kernels: the program's algorithmic HBM bytes for the
window's iterations (``perfbench/work``), over the device's busy time
in the traced window times the chip's peak HBM bandwidth, in %."""


def read(ctx):
    work = ctx["work"].get("edge_bytes")
    busy = ctx["trace"]["busy_s"]
    if not work or not busy:
        return None
    return 100.0 * work / (busy * ctx["peaks"]["hbm_bytes_per_s"])
