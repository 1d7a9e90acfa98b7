"""Share of the device's idle time under ``tick/wait``: the host asleep between ticks, not busy."""
from chipbench import spanlib


def read(ctx):
    return spanlib.idle_share(ctx, lambda name: name == "tick/wait") if "latency_ms" in ctx.window else None
