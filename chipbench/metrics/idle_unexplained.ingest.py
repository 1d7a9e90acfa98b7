"""Share of the device's idle time under no span but ``tick`` itself (its self time, or no span at all)."""
from chipbench import spanlib


def read(ctx):
    return spanlib.idle_share(ctx, lambda name: name in ("", "tick")) if "documents" in ctx.window else None
