"""Share of the device's idle time under a ``gc/collect`` span on any thread: a collector pass holds the GIL, so every Python thread waits on it."""
from chipbench import spanlib


def read(ctx):
    rep = spanlib.report(ctx) if "documents" in ctx.window else None
    if rep is None or not rep["idle"]:
        return None
    passes = spanlib.named(rep, "gc/collect")
    if not passes and not any(spanlib.attr(t, "gc0_passes") for t in spanlib.named(rep, "tick")):
        return None  # a program that does not record the collector
    off = ctx.trace["offset_ns"]
    gaps = spanlib.merge([(g0 - off, g1 - off) for g0, g1 in ctx.trace["gaps"]])
    total = sum(b - a for a, b in gaps)
    under = spanlib.intersect(gaps, spanlib.merge([(s["t0"], s["t1"]) for s in passes]))
    return 100.0 * sum(b - a for a, b in under) / total if total else None
