"""Real rows stepped (the program's decoder.step row counter) over decoder.step calls."""
from chipbench import flops_decoder as F
from chipbench.metriclib import calls_delta


def read(ctx):
    rows, calls = F.delta(ctx, "decoder.step")[0], calls_delta(ctx, "decoder.step")
    return rows / calls if rows and calls else None
