"""Requests per decode launch: the mean of the real (unpadded) rows of
the ``backend.decode_prep`` spans started in the window. How full the
batch runs against the engine's ``max_running``. Read from the
program's own record."""
from program_record import started


def read(ctx):
    preps = started(ctx, "backend.decode_prep")
    if not preps:
        return None
    return sum(r[5]["rows"] for r in preps) / len(preps)
