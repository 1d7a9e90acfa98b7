"""Whole step: (embed + rerank) FLOPs of the window's queries over the window over peak."""
from chipbench.metriclib import peak, query_flops


def read(ctx):
    if "latency_ms" not in ctx.window:
        return None
    return 100.0 * query_flops(ctx) / ctx.window["end_s"] / peak(ctx)["bf16_flops"]
