"""Serving layers: the share of the window's client-side latency (sent
to answered) that the ``serve.engine`` spans of the program do not
cover, 1 - sum(engine) / sum(client latency)."""


def read(ctx):
    layer = ctx["layer"]
    client = layer.get("client_s")
    if not client or "engine_s" not in layer:
        return None
    return 1.0 - layer["engine_s"] / client
