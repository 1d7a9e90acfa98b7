"""Share of the device's idle time under the self time of the microbatch node's spans (its ``sweep/``, its ``frontier/``, ``microbatch/launch``), children excluded."""
from chipbench import spanlib


def mine(name: str) -> bool:
    return name == "microbatch/launch" or (name.startswith(("sweep/", "frontier/")) and "microbatch" in name)


def read(ctx):
    return spanlib.idle_share(ctx, mine) if "documents" in ctx.window else None
