"""90th percentile over requests due in the window of (last token time -
first token time) / (tokens - 1), in milliseconds, as the client saw
the stream."""
from stats import percentile, tpots_ms


def read(ctx):
    return percentile(tpots_ms(ctx), 90)
