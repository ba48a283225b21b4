"""Backend compile seconds over set-up, from JAX's monitoring events;
programs loaded from the persistent cache do not count."""


def read(ctx):
    return ctx.setup["compile_s"]
