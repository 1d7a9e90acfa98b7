"""(token, expert) pairs the held experts computed over token-layers routed (the program's decoder.experts counter): experts-per-token x held / published if routing is even."""
from chipbench import flops_decoder as F


def read(ctx):
    pairs, _pad, routed, _ = F.delta(ctx, "decoder.experts")
    return pairs / routed if routed else None
