"""90th percentile of the seconds from due time to first streamed token,
over the requests due in the window."""
from stats import percentile, ttfts


def read(ctx):
    return percentile(ttfts(ctx), 90)
