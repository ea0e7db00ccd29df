"""Median seconds from a request's admission (``engine.admit``) to its
first token on the host (``backend.first_token``): its prefill, and the
prefills of others batched in the same launches, and its first decode
step; over the requests received in the window. Read from the
program's own record."""
from program_record import lapses
from stats import percentile


def read(ctx):
    xs = lapses(ctx, "admit", "first_token")
    return percentile(xs, 50) if xs else None
