"""Decode steps launched while an earlier step was still unfetched, over every decode step launched in the window (the program's decoder.step.ahead counter: real rows = launched ahead, pad rows = launched with nothing in flight)."""
from chipbench import flops_decoder as F


def read(ctx):
    ahead, alone, _t, _p = F.delta(ctx, "decoder.step.ahead")
    return 100.0 * ahead / (ahead + alone) if ahead + alone else None
