"""Bytes put on the device (``device/put`` spans) over the documents of the window."""
from chipbench import spanlib


def read(ctx):
    rep = spanlib.report(ctx) if ctx.window.get("documents") else None
    if rep is None:
        return None
    return sum(spanlib.attr(s, "bytes", 0) for s in spanlib.named(rep, "device/put")) / ctx.window["documents"]
