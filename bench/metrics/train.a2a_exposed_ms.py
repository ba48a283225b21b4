"""Milliseconds per round, averaged over the chips, in which an
all-to-all ran on a chip and no other operation did.  Nothing to read
on one chip."""


def read(ctx):
    red = ctx.trace
    if red is None or ctx.cell.chips < 2:
        return None
    return 1e3 * red.a2a_exposed_s / ctx.cell.chips / red.rounds
