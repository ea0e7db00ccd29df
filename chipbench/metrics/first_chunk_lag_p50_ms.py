"""Median milliseconds from a request's first token on the host
(``backend.first_token``) to that token's chunk written to its stream
(``frontdoor.first_chunk``): the rest of the engine step it was made in,
the front door's poll, and the stream handler's turn on the event loop;
over the requests received in the window. Read from the program's own
record."""
from program_record import lapses
from stats import percentile


def read(ctx):
    xs = lapses(ctx, "first_token", "first_chunk")
    return 1e3 * percentile(xs, 50) if xs else None
