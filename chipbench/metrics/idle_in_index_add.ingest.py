"""Share of the device's idle time under the self time of ``index/add`` and ``index/scatter``."""
from chipbench import spanlib


def read(ctx):
    return spanlib.idle_share(ctx, lambda name: name in ("index/add", "index/scatter")) if "documents" in ctx.window else None
