"""Seconds from the benchmark process's start to a server that is ready
with every shape of the cell's traffic warm: weights, pool, compilation
(or loading it from the persistent cache) and warm-up."""


def read(ctx):
    return ctx.setup_s
