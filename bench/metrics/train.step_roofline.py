"""Share of the roofline of one snapshot step: the least time the chip
needs for the step's required FLOPs and bytes (the larger of the two
bounds), over the device time per snapshot of every module the traced
window runs (the step's path and each epoch's re-initialisation, summed
over the chips).  Nothing to read where the trace reduction found no
step time."""


def read(ctx):
    red = ctx.trace
    if red is None or red.step_device_s <= 0:
        return None
    least = max(ctx.work["flops"] / ctx.peaks["bf16_flops_per_s"],
                ctx.work["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (red.step_device_s / ctx.window["snapshots"])
