"""Pad tokens over launched tokens, from the program's encoder pad counters."""


def read(ctx):
    b = ctx.before["pad"].get("encoder", [0, 0, 0, 0])
    a = ctx.after["pad"].get("encoder")
    if a is None or "documents" not in ctx.window:
        return None
    real, pad = a[2] - b[2], a[3] - b[3]
    return 100.0 * pad / (real + pad) if real + pad else None
