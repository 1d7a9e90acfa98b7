"""Documents the window ingested over the encoder launches the program counted."""
from chipbench.metriclib import calls_delta


def read(ctx):
    launches = calls_delta(ctx, "encoder.encode")
    return ctx.window["documents"] / launches if launches and "documents" in ctx.window else None
