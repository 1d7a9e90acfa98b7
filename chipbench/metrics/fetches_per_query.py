"""Blocking device-to-host fetches (``device/fetch`` spans) of the window over the requests it answered."""
from chipbench import spanlib


def read(ctx):
    rep = spanlib.report(ctx) if "latency_ms" in ctx.window else None
    if rep is None:
        return None
    answered = sum(1 for r in rep["requests"] if r["status"] == "ok")
    return len(spanlib.named(rep, "device/fetch")) / answered if answered else None
