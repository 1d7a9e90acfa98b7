"""Pad tokens over launched tokens of decoder.prefill, from the program's pad counters."""
from chipbench import flops_decoder as F


def read(ctx):
    _r, _p, real, pad = F.delta(ctx, "decoder.prefill")
    return 100.0 * pad / (real + pad) if real else None
