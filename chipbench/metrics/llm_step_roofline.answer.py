"""Bytes the decode steps the trace holds had to move (the arithmetic the language model's configuration names: every matrix and the head a step, the live rows' own state or cache entries; the window's mean a step, from the program's counters, times the launches held) over peak HBM bytes/s, over the device time under decoder.step. Memory-bound (chipbench.flops.roofline_pct says which)."""
from chipbench import arithmetic, flops
from chipbench.metriclib import kernel_seconds, peak


def read(ctx):
    t = kernel_seconds(ctx, "decoder.step")
    w = arithmetic.window_work(ctx) if t else None
    if w is None or not w["steps"]:
        return None
    held = arithmetic.held_share(ctx, "decoder.step", w["steps"])
    pct, _bound = flops.roofline_pct(w["step_flops"] * held, w["step_bytes"] * held, t, peak(ctx))
    return pct
