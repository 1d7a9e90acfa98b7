"""Share of the seconds ``step_gap_ms.answer`` counts that lie in a gap containing the end of a ``tick`` span: the stepping node yielded to the tick loop and came back."""
import bisect

from chipbench import spanlib, steplib


def read(ctx):
    rep = spanlib.report(ctx) if "latency_ms" in ctx.window else None
    gaps = steplib.host_gaps(rep) if rep is not None else []
    total = sum(b - a for a, b in gaps)
    if not total:
        return None
    ends = sorted(t["t1"] for t in spanlib.named(rep, "tick"))
    yielded = sum(b - a for a, b in gaps if bisect.bisect_right(ends, b) > bisect.bisect_left(ends, a))
    return 100.0 * yielded / total
