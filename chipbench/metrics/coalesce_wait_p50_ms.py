"""Median wait of the window's requests between their row reaching the engine and the tick that drained it (``serve/coalesce``)."""
from chipbench import spanlib


def read(ctx):
    rep = spanlib.report(ctx) if "latency_ms" in ctx.window else None
    if rep is None or not rep["requests"]:
        return None
    return spanlib.median([(r["first_tick"] - r["admitted"]) / 1e6 for r in rep["requests"]])
