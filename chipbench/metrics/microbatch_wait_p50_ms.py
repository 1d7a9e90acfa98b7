"""Median over the window's ``microbatch/launch`` spans of how long the oldest row flushed sat in the buffer."""
from chipbench import spanlib


def read(ctx):
    rep = spanlib.report(ctx) if "latency_ms" in ctx.window else None
    if rep is None:
        return None
    return spanlib.median([spanlib.attr(s, "oldest_wait_ns") / 1e6 for s in spanlib.named(rep, "microbatch/launch")])
