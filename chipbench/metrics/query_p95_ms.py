"""95th percentile over all requests of the window, from due time to last byte."""
from chipbench.metriclib import percentile


def read(ctx):
    return percentile(ctx.window["latency_ms"], 95) if "latency_ms" in ctx.window else None
