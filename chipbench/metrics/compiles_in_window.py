"""Programs JAX compiled while the window was open. The warm-up is meant
to leave none: each one stalls the server and is set-up work moved into
the measurement."""
from stats import in_window


def read(ctx):
    return float(sum(1 for t in ctx.compiles if in_window(t, ctx.window)))
