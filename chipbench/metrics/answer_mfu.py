"""Whole step: model FLOPs of the window's real prompt tokens and generated tokens (decoder, from the program's counters) over the window over peak."""
from chipbench import flops_decoder as F
from chipbench.metriclib import calls_delta, peak


def read(ctx):
    n, c = F.window_counts(ctx), F.llm_config(ctx.config)
    if n is None or c is None or "latency_ms" not in ctx.window:
        return None
    prefills = calls_delta(ctx, "decoder.prefill")
    total = (F.prefill_flops(c, n["prompt_tokens"], n["scores"], n["pairs"] - n["step_pairs"], prefills)
             + F.step_flops(c, n["rows_stepped"], n["attended"], n["step_pairs"]))
    return 100.0 * total / ctx.window["end_s"] / peak(ctx)["bf16_flops"]
