"""The benchmark's FLOPs for the real tokens ingested over peak bf16 FLOP/s,
over the device time under the encoder.encode* labels. Compute-bound."""
from chipbench.metriclib import ingest_flops, kernel_seconds, peak


def read(ctx):
    t = kernel_seconds(ctx, "encoder.encode")
    if not t or "documents" not in ctx.window:
        return None
    return 100.0 * ingest_flops(ctx) / peak(ctx)["bf16_flops"] / t
