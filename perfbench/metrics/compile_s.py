"""Compile layer: seconds the program's XLA compiles took, summed over
the phases of ``lux_xla_compile_seconds_total`` (the program's compile
listener) by the time the reader runs. The window compiles nothing
(``compiles_in_window``), so this is the compile share of set-up. A
program without the counter reads ``None``."""


def read(ctx):
    from lux_tpu.obs import metrics

    vals = [m["value"] for m in metrics.snapshot()
            if m["name"] == "lux_xla_compile_seconds_total"]
    return sum(vals) if vals else None
