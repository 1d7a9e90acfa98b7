"""Mean host gap between two decoder launches on the engine thread, where the first left rows live: from its ``device/fetch`` end to the end of the next one's ``device/launch``, floored at 0."""
from chipbench import spanlib, steplib


def read(ctx):
    rep = spanlib.report(ctx) if "latency_ms" in ctx.window else None
    gaps = steplib.host_gaps(rep) if rep is not None else []
    return sum(b - a for a, b in gaps) / len(gaps) / 1e6 if gaps else None
