"""Host planning layer: wall seconds of ``cli.make_executor(...)`` in
set-up (host planning, layout and upload; compilation comes after).
Read by the batch and training drivers."""


def read(ctx):
    return ctx["layer"].get("build_s")
