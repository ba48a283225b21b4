"""Device milliseconds per round, averaged over the chips, under the
program's ``a2a`` scope (every all-to-all, forward and backward; the
same base as ``train.a2a_exposed_ms``).  Nothing to read on one chip or
where the trace holds no stage scope."""

import stages


def read(ctx):
    red = stages.of(ctx)
    if red is None or not red.scoped or ctx.cell.chips < 2:
        return None
    return 1e3 * red.seconds("a2a") / ctx.cell.chips / ctx.trace.rounds
