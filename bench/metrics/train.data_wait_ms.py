"""Milliseconds per snapshot that the training loop waited on the
prefetch queue (the program's ``prefetch.wait`` span, tracer on with
``fence=False``), over the window."""


def read(ctx):
    return 1e3 * ctx.spans["prefetch.wait"] / ctx.window["snapshots"]
