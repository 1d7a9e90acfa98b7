"""FLOPs of the window's real prompt tokens (blocks, causal scores, the held experts' pairs, the head at a row's last position) over peak bf16 FLOP/s, over the device time under decoder.prefill. Compute-bound."""
from chipbench import flops_decoder as F
from chipbench.metriclib import calls_delta, kernel_seconds, peak


def read(ctx):
    t, c = kernel_seconds(ctx, "decoder.prefill"), F.llm_config(ctx.config)
    n = F.window_counts(ctx) if t and c else None
    if n is None:
        return None
    flops = F.prefill_flops(c, n["prompt_tokens"], n["scores"], n["pairs"] - n["step_pairs"],
                            calls_delta(ctx, "decoder.prefill"))
    return 100.0 * flops / peak(ctx)["bf16_flops"] / t
