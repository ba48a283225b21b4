"""Share of chip 0's idle time in the traced window during which a
program span is open on the host (``stages.py``): how much of the idle
time the program's own spans can name.  Nothing to read where the trace
holds no program span or the chip was never idle."""

import stages


def read(ctx):
    red = stages.of(ctx)
    if red is None or not red.spans or red.idle_s <= 0:
        return None
    return 100.0 * red.idle_attributed_s / red.idle_s
