"""Model FLOP utilization: the FLOPs one snapshot step requires
(``work.flops_per_snapshot``) times the traced run's snapshots per
second, over the chips' bf16 peak."""


def read(ctx):
    rate = ctx.window["snapshots"] / ctx.window["seconds"]
    return 100.0 * ctx.work["flops"] * rate / (
        ctx.cell.chips * ctx.peaks["bf16_flops_per_s"])
