"""Share of the roofline of CD-GCN's LSTM: the least time the chip needs
for both layers' LSTM FLOPs and bytes, forward and backward (the cell's
reference model's ``lstm_per_snapshot``, the larger of the two bounds),
over the device time per snapshot under the program's ``temporal``
scope.  Nothing to read where the trace holds no stage scope or no op
ran under ``temporal``."""

import cell
import reference
import stages


def read(ctx):
    ms = stages.per_snapshot_ms(ctx, "temporal")
    if not ms:
        return None
    model = reference.model(ctx.cell.config["model"])
    work = model.lstm_per_snapshot(cell.shape_of(ctx.cell))
    least = max(work["flops"] / ctx.peaks["bf16_flops_per_s"],
                work["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
