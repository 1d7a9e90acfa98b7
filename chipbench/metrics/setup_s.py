"""Process start to the first measured event: loading, warming up and, in a
run that compiles, compilation."""


def read(ctx):
    return ctx.setup_s
