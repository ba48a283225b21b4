"""Megabytes of graph-difference stream that cross from host to device
per snapshot (the program's payload count: ``transfer_report`` on one
chip, the per-shard streams on the mesh)."""


def read(ctx):
    return ctx.transfer_bytes_per_epoch / ctx.cell.config["num_steps"] / 1e6
