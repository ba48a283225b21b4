"""Device milliseconds per snapshot under the program's ``delta_apply``
scope (the on-device reconstruction of each snapshot's edge list),
summed over the chips (``stages.py``).  Nothing to read where the trace
holds no stage scope."""

import stages


def read(ctx):
    return stages.per_snapshot_ms(ctx, "delta_apply")
