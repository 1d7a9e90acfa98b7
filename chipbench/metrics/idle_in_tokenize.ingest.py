"""Share of the device's idle time under ``embed/tokenize``."""
from chipbench import spanlib


def read(ctx):
    return spanlib.idle_share(ctx, lambda name: name == "embed/tokenize") if "documents" in ctx.window else None
