"""Device milliseconds per snapshot of CD-GCN's LSTM: the ops under the
program's ``temporal`` scope, forward and backward, summed over the
chips (``stages.py``).  Nothing to read where the trace holds no stage
scope."""

import stages


def read(ctx):
    return stages.per_snapshot_ms(ctx, "temporal")
