"""Host set-up seconds: the program's data path (smoothing, degree
features, labels) and ``Engine.resolve`` (padding, blocking, encoder
statistics), on the host clock around the benchmark's own call."""


def read(ctx):
    return ctx.setup["host_s"]
