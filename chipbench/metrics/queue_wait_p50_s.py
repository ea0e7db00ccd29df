"""Median seconds a request waits for the engine: from its receipt at
the front door (``frontdoor.recv``) to its admission into the running
batch (``engine.admit``), over the requests received in the window.
Read from the program's own record."""
from program_record import lapses
from stats import percentile


def read(ctx):
    xs = lapses(ctx, "recv", "admit")
    return percentile(xs, 50) if xs else None
