"""Median seconds from a request's due time to its first streamed token,
over the requests due in the window (for a session turn, due is when its
tool returned)."""
from stats import percentile, ttfts


def read(ctx):
    return percentile(ttfts(ctx), 50)
