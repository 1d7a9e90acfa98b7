"""Mean, over the ticks that overlap a request in the server's hands, of the tick's duration minus the time it spent blocked in ``device/fetch``."""
import bisect

from chipbench import spanlib


def read(ctx):
    rep = spanlib.report(ctx) if "latency_ms" in ctx.window else None
    if rep is None or not rep["requests"]:
        return None
    held = spanlib.merge([(r["admitted"], r["done"]) for r in rep["requests"]])
    fetches = sorted((f["t0"], f["t1"], f["thread"]) for f in spanlib.named(rep, "device/fetch"))
    starts = [f[0] for f in fetches]
    host = []
    for t in spanlib.named(rep, "tick"):
        if spanlib.intersect([(t["t0"], t["t1"])], held):
            inside = fetches[bisect.bisect_left(starts, t["t0"]):bisect.bisect_right(starts, t["t1"])]
            blocked = sum(f1 - f0 for f0, f1, thread in inside if thread == t["thread"] and f1 <= t["t1"])
            host.append((t["t1"] - t["t0"] - blocked) / 1e6)
    return sum(host) / len(host) if host else None
