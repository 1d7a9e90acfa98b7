"""Bytes one exact scan of the live rows has to read, times the searches
launched, over peak HBM bytes/s, over the device time under knn.search.
Memory-bound at these query counts (chipbench.flops.roofline_pct says which)."""
from chipbench import flops
from chipbench.metriclib import kernel_launches, kernel_seconds, peak


def read(ctx):
    t, n = kernel_seconds(ctx, "knn.search"), kernel_launches(ctx, "knn.search")
    if not t or not n or "latency_ms" not in ctx.window:
        return None
    dim = ctx.config["hidden_size"]
    queries = ctx.window["attempted"]
    pct, _bound = flops.roofline_pct(
        flops.scan_flops(queries, ctx.live_rows, dim), n * flops.scan_bytes(ctx.live_rows, dim), t, peak(ctx)
    )
    return pct
