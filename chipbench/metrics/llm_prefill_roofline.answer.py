"""FLOPs of the real prompt tokens of the prefills the trace holds (the arithmetic the language model's configuration names: every layer's matrices, the mixers' own operations, the head at a row's last position; the window's mean a prefill, from the program's counters, times the launches held) over peak bf16 FLOP/s, over the device time under decoder.prefill. Compute-bound."""
from chipbench import arithmetic
from chipbench.metriclib import kernel_seconds, peak


def read(ctx):
    t = kernel_seconds(ctx, "decoder.prefill")
    w = arithmetic.window_work(ctx) if t else None
    if w is None or not w["prefills"]:
        return None
    held = arithmetic.held_share(ctx, "decoder.prefill", w["prefills"])
    return 100.0 * w["prefill_flops"] * held / peak(ctx)["bf16_flops"] / t
