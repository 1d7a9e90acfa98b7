"""Bytes the window's decode steps had to read (non-expert weights and the head's slice a step, the held experts its rows routed to, the live rows' latent cache) over peak HBM bytes/s, over the device time under decoder.step. Memory-bound (chipbench.flops.roofline_pct says which)."""
from chipbench import flops, flops_decoder as F
from chipbench.metriclib import calls_delta, kernel_seconds, peak


def read(ctx):
    t, c = kernel_seconds(ctx, "decoder.step"), F.llm_config(ctx.config)
    n = F.window_counts(ctx) if t and c else None
    if n is None:
        return None
    pct, _bound = flops.roofline_pct(
        F.step_flops(c, n["rows_stepped"], n["attended"], n["step_pairs"]),
        F.step_bytes(c, calls_delta(ctx, "decoder.step"), n["experts_hit"], n["attended"]), t, peak(ctx),
    )
    return pct
