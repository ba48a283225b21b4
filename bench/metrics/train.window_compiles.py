"""Backend compiles inside the measured window; should read 0."""


def read(ctx):
    return float(ctx.window["compiles"])
