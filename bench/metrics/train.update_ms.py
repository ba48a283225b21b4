"""Device milliseconds per snapshot under the program's ``loss`` and
``optimizer`` scopes, summed over the chips (``stages.py``).  Nothing
to read where the trace holds no stage scope."""

import stages


def read(ctx):
    return stages.per_snapshot_ms(ctx, "loss", "optimizer")
