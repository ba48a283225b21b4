"""Milliseconds per snapshot that the prefetch thread spent pulling
items from the host iterator (the program's ``prefetch.encode`` span:
delta encode, frames and labels), over the traced window.  Nothing to
read where the trace holds no such span."""

import stages


def read(ctx):
    red = stages.of(ctx)
    if red is None or "prefetch.encode" not in red.spans:
        return None
    return 1e3 * red.spans["prefetch.encode"] / ctx.window["snapshots"]
