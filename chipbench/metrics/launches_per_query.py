"""Device executables started in the window (trace) over the queries sent."""


def read(ctx):
    n = sum(ctx.trace["launches"].values())
    return n / ctx.window["attempted"] if n and "latency_ms" in ctx.window else None
