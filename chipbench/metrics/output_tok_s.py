"""Tokens the clients received inside the window, whatever request they
belong to, divided by the window's seconds."""
from stats import in_window


def read(ctx):
    n = sum(k for r in ctx.records for t, k in r["chunks"]
            if in_window(t, ctx.window))
    return n / ctx.seconds
