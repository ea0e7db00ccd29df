"""Share of the device KV pool held, weighted by wall time over the
window: the ``engine.kv_used`` gauge (blocks held over pool blocks),
sampled at the end of every engine step and held until the next sample.
The weighting starts at the last sample before the window, if one lies
within 30 s of it, else at the first sample inside it. Read from the
program's own record."""
from program_record import held

LOOK_BACK_S = 30.0


def read(ctx):
    recs = held(ctx, before=LOOK_BACK_S)
    if recs is None:
        return None
    ws, we = ctx.window
    samples = sorted((r[1], r[5]["value"]) for r in recs
                     if r[0] == "engine.kv_used" and r[1] < we)
    prior = [s for s in samples if s[0] <= ws][-1:]
    samples = prior + [s for s in samples if s[0] > ws]
    if not samples:
        return None
    area, span = 0.0, 0.0
    for (t, v), (t_next, _) in zip(samples, samples[1:] + [(we, None)]):
        lo = max(t, ws)
        area += v * (t_next - lo)
        span += t_next - lo
    return 100.0 * area / span if span > 0 else None
