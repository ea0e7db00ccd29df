"""What the program recorded about itself, for the metric readers that
read it (``repro.obs``: spans, instants and counters of the served path,
on ``time.monotonic()``, the clock of the window).

Each function returns None where there is nothing sound to read: a
program without such a record, or a record that no longer holds the
stretch asked for whole (its bounded deque dropped part of it)."""
from __future__ import annotations

from stats import in_window


def held(ctx, before: float = 0.0):
    """The program's records that ended at or after ``before`` seconds
    ahead of the window's start, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.records(since=ctx.window[0] - before)


def started(ctx, name: str):
    """Records named ``name`` that started in the window, or None."""
    recs = held(ctx)
    if recs is None:
        return None
    return [r for r in recs if r[0] == name and in_window(r[1], ctx.window)]


def stamps(ctx):
    """Per request received at the front door in the window, the wall
    times of its receipt (``recv``), first admission into the running
    batch (``admit``), first token on the host (``first_token``) and
    first chunk written to its stream (``first_chunk``), as far as it
    got; or None. The ``frontdoor.submit`` span ties the front door's
    id to the engine's."""
    recs = held(ctx)
    if recs is None:
        return None
    recv = {r[5]["gid"]: r[1] for r in recs if r[0] == "frontdoor.recv"
            and in_window(r[1], ctx.window)}
    out = {r[4]: {"recv": recv[r[5]["gid"]]} for r in recs
           if r[0] == "frontdoor.submit" and r[4]
           and r[5]["gid"] in recv}
    keys = {"engine.admit": "admit", "backend.first_token": "first_token",
            "frontdoor.first_chunk": "first_chunk"}
    for name, t0, _t1, _parent, rid, _attrs in recs:
        if name in keys and rid in out:
            out[rid].setdefault(keys[name], t0)
    return list(out.values())


def lapses(ctx, a: str, b: str):
    """Seconds from stamp ``a`` to stamp ``b`` of each request received
    in the window that reached both, or None."""
    st = stamps(ctx)
    if st is None:
        return None
    return [s[b] - s[a] for s in st if a in s and b in s]
