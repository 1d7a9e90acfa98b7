"""Documents acknowledged over the whole window, the drain included."""


def read(ctx):
    if "documents" not in ctx.window:
        return None
    return ctx.window["documents"] / ctx.window["end_s"]
