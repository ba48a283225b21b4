"""Device milliseconds per snapshot of ops under no stage scope, summed
over the chips: the check that the program's scopes cover its step
(``stages.py``).  Nothing to read where the trace holds no stage scope
at all."""

import stages


def read(ctx):
    return stages.per_snapshot_ms(ctx, stages.UNSCOPED)
