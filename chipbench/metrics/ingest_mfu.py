"""Whole step: encoder FLOPs of the documents ingested over the window over peak."""
from chipbench.metriclib import ingest_flops, peak


def read(ctx):
    if "documents" not in ctx.window:
        return None
    return 100.0 * ingest_flops(ctx) / ctx.window["end_s"] / peak(ctx)["bf16_flops"]
