"""Share of the roofline of the spatial aggregation: the least time the
chip needs for the aggregation passes' FLOPs and bytes
(``stages.spmm_per_snapshot``, the larger of the two bounds), over the
device time per snapshot under the program's ``spmm`` scope, summed
over the chips.  Nothing to read where the trace holds no stage scope
or no op under ``spmm``."""

import cell
import stages


def read(ctx):
    ms = stages.per_snapshot_ms(ctx, "spmm")
    if not ms:
        return None
    work = stages.spmm_per_snapshot(cell.shape_of(ctx.cell))
    least = max(work["flops"] / ctx.peaks["bf16_flops_per_s"],
                work["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
