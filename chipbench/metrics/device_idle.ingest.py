"""1 - union of device-op intervals over the traced window, in a cell that ingests."""
from chipbench.metriclib import idle_pct


def read(ctx):
    return idle_pct(ctx) if "documents" in ctx.window else None
