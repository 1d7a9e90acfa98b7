"""Mean query count of the window's ``index/search`` spans."""
from chipbench import spanlib


def read(ctx):
    rep = spanlib.report(ctx) if "latency_ms" in ctx.window else None
    counts = [spanlib.attr(s, "queries") for s in spanlib.named(rep, "index/search")] if rep is not None else []
    return sum(counts) / len(counts) if counts else None
