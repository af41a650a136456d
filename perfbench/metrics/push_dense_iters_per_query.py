"""Engines and kernels, serving: dense-branch iterations of the push
engine per sssp query, over every query the process served (the
warm-up's included): ``lux_engine_iterations_total{engine="push",
branch="dense"}`` over ``lux_serve_requests_total{app="sssp"}``. A
program without the counter reads ``None``."""


def read(ctx):
    from lux_tpu.obs import metrics

    dense = queries = None
    for m in metrics.snapshot():
        labels = m["labels"]
        if (m["name"] == "lux_engine_iterations_total"
                and labels == {"engine": "push", "branch": "dense"}):
            dense = m["value"]
        elif (m["name"] == "lux_serve_requests_total"
              and labels == {"app": "sssp"}):
            queries = m["value"]
    if dense is None or not queries:
        return None
    return dense / queries
