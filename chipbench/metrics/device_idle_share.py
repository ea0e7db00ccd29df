"""Share of the traced part of the window in which no program ran on the
device: 1 - busy union / traced window."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
