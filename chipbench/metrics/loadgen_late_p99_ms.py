"""99th percentile of how late the load generator sent a request due in
the window (sent - due), in milliseconds: a starved generator would pass
its own lateness off as the server's."""
from stats import percentile


def read(ctx):
    return percentile([1e3 * (r["sent"] - r["due"]) for r in ctx.due
                       if r["sent"] is not None], 99)
