"""How late the generator ran: sent minus due, 95th percentile, by its own clock."""
from chipbench.metriclib import percentile


def read(ctx):
    return percentile(ctx.window["late_ms"], 95) if "late_ms" in ctx.window else None
