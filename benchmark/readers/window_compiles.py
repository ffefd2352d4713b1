"""Backend compiles (persistent-cache loads included) inside the whole
measured window, from the program's ``compile_count()``. Should read 0."""


def read(ctx, params):
    return float(ctx.compiles)
