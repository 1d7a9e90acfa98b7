"""Median duration of the window's ``device/launch`` spans labelled ``decoder.step``: the host's hand-over of a decode step's arguments (shape key, the parameter and cache trees, the enqueue)."""
from chipbench import spanlib


def read(ctx):
    rep = spanlib.report(ctx) if "latency_ms" in ctx.window else None
    if rep is None:
        return None
    return spanlib.median([(s["t1"] - s["t0"]) / 1e6 for s in spanlib.named(rep, "device/launch")
                           if spanlib.attr(s, "label") == "decoder.step"])
