"""1 - union of device-op intervals over the traced window, in a cell that answers queries."""
from chipbench.metriclib import idle_pct


def read(ctx):
    return idle_pct(ctx) if "latency_ms" in ctx.window else None
