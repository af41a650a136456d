"""Host planning layer: seconds inside the program's ``build.plan``
spans (the host tiled plan, or the pull engine's chunk plan) in set-up,
from the ``lux_span_seconds{span="build.plan"}`` histogram. A program
without the span reads ``None``."""


def read(ctx):
    from lux_tpu.obs import metrics

    for m in metrics.snapshot():
        if (m["name"] == "lux_span_seconds"
                and m["labels"].get("span") == "build.plan"):
            return m["sum"]
    return None
