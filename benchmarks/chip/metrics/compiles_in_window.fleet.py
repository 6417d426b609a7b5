"""Backend compiles (persistent-cache loads included) between the window's
opening and its close, from JAX's monitoring events."""


def read(ctx):
    return ctx.get("compiles")
