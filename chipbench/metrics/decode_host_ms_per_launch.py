"""Host milliseconds per decode launch not spent waiting for the device:
each ``backend.decode_step`` span started in the window, less the
``backend.decode_sync`` inside it (the fetch of the sampled tokens),
averaged over those launches. This is table building, the launch and
token bookkeeping: the host's share of the per-token round trip. Read
from the program's own record."""
import bisect

from program_record import held, started


def read(ctx):
    steps, recs = started(ctx, "backend.decode_step"), held(ctx)
    if not steps or recs is None:
        return None
    syncs = sorted((r[1], r[2]) for r in recs if r[0] == "backend.decode_sync")
    starts = [s for s, _ in syncs]
    own = 0.0
    for _, t0, t1, *_ in steps:
        i = bisect.bisect_left(starts, t0)
        wait = syncs[i][1] - syncs[i][0] \
            if i < len(syncs) and syncs[i][1] <= t1 else 0.0
        own += (t1 - t0) - wait
    return 1e3 * own / len(steps)
