"""The benchmark's FLOPs for the real tokens of the pairs scored over peak
bf16 FLOP/s, over the device time under reranker.score. Compute-bound."""
from chipbench.metriclib import kernel_seconds, peak, rerank_flops


def read(ctx):
    t = kernel_seconds(ctx, "reranker.score")
    f = rerank_flops(ctx)
    return 100.0 * f / peak(ctx)["bf16_flops"] / t if t and f else None
