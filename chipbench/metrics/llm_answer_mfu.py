"""Whole step: model FLOPs of the window's real prompt tokens and generated tokens (the arithmetic the language model's configuration names, from the program's counters) over the window over peak."""
from chipbench import arithmetic
from chipbench.metriclib import peak


def read(ctx):
    w = arithmetic.window_work(ctx)
    if w is None or "latency_ms" not in ctx.window:
        return None
    return 100.0 * (w["prefill_flops"] + w["step_flops"]) / ctx.window["end_s"] / peak(ctx)["bf16_flops"]
