"""Milliseconds of prefill per engine step: the wall time of the
``backend.prefill`` spans started in the window (each a batch's whole
chunked prefill, through its logits fetch) over the ``engine.step``
spans started in it. While a prefill runs, every running request's
decode waits. Read from the program's own record."""
from program_record import started


def read(ctx):
    steps = started(ctx, "engine.step")
    prefills = started(ctx, "backend.prefill")
    if not steps:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1, *_ in prefills) / len(steps)
