"""Spans, instants and counters of the served path, kept in one bounded
in-memory record.

The program times itself here and nowhere else. Three kinds of entry:

- ``span(name, rid=None, **attrs)``: a context manager around host
  work. In a profiled run it also enters ``jax.profiler.TraceAnnotation``
  under the same name, so the span sits on the profiler's host plane, on
  the device trace's clock. On exit it appends one record.
- ``event(name, rid=None, value=None, **attrs)``: an instant, for a
  request's stamps and for gauge samples (``value``).
- ``count(name, n=1)``: a flat counter.

A record is the tuple ``(name, t0, t1, parent, rid, attrs)``. Times are
``time.monotonic()`` seconds; an instant has ``t0 == t1``. ``parent`` is
the name of the span open around it on the same thread (None at the top);
``attrs`` is a dict, or None when there is nothing to carry. Names start
with ``frontdoor.``, ``engine.`` or ``backend.``, the layer that emits
them; the spans of one request carry its engine ``rid``.

Tracing is always on. A span costs two clock reads, a deque append under
a lock, and a ``TraceAnnotation`` only while the profiler runs. Nothing
here waits on the device or copies from it: a span ends where the host
code ends, and device completion is read from the device trace.

The record is a deque of ``MAXLEN`` entries. A whole run of the
benchmark's busiest cell, stablelm-3b under chat-over on one TPU v5e
(set-up, 20 s of ramp, a 51-s window and 150 s of drain), appends about
5,600: some 85 engine steps of ~70 entries each. ``MAXLEN`` leaves ten
times that room, at a few hundred bytes an entry. When the deque wraps,
``dropped`` counts what it lost and ``records(since)`` refuses a stretch
it no longer holds whole, so a reader returns no number rather than a
wrong one.

One ``jax.monitoring`` listener, registered at import, counts the
programs JAX compiles, keyed by the span open when each compiled
(``tracing.compiles`` in ``/v1/report``).
"""
from __future__ import annotations

import threading
import time
from collections import Counter, deque
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

MAXLEN = 1 << 16

_lock = threading.Lock()
_local = threading.local()
_rec: deque = deque(maxlen=MAXLEN)
_counts: Counter = Counter()
_appended = 0
# end time of the newest record the deque has dropped (None: none yet)
_lost_until: Optional[float] = None


def _stack() -> List[str]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _append(rec: tuple) -> None:
    global _appended, _lost_until
    with _lock:
        if len(_rec) == _rec.maxlen:
            lost = _rec[0][2]
            _lost_until = lost if _lost_until is None \
                else max(_lost_until, lost)
        _rec.append(rec)
        _appended += 1


class span:
    """Time the host work inside ``with span(...)``. ``rid`` and
    ``attrs`` may be set on the object inside the block, for values known
    only at its end."""

    __slots__ = ("name", "rid", "attrs", "parent", "t0", "_ann")

    def __init__(self, name: str, rid: Optional[str] = None, **attrs):
        self.name, self.rid, self.attrs = name, rid, attrs or None

    def __enter__(self) -> "span":
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann = None
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _stack().pop()
        _append((self.name, self.t0, t1, self.parent, self.rid, self.attrs))

    def set(self, **attrs) -> None:
        self.attrs = {**(self.attrs or {}), **attrs}


def event(name: str, rid: Optional[str] = None, value=None,
          **attrs) -> None:
    """Record an instant; ``value`` is a gauge's sample."""
    if value is not None:
        attrs["value"] = value
    stack = _stack()
    t = time.monotonic()
    _append((name, t, t, stack[-1] if stack else None, rid, attrs or None))


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counts[name] += n


def _held():
    with _lock:
        return list(_rec), _lost_until


def records(since: float = float("-inf")) -> Optional[List[tuple]]:
    """The records that ended at or after ``since``, oldest first; None
    when the deque has dropped one of them."""
    held, lost = _held()
    if lost is not None and lost >= since:
        return None
    return [r for r in held if r[2] >= since]


def dropped() -> int:
    with _lock:
        return max(_appended - _rec.maxlen, 0)


def reset(maxlen: int = MAXLEN) -> None:
    """Empty the record and the counters (tests)."""
    global _rec, _appended, _lost_until
    with _lock:
        _rec = deque(maxlen=maxlen)
        _counts.clear()
        _appended, _lost_until = 0, None


def stats() -> dict:
    """What the record holds: its size, entries held, entries dropped,
    the counters, and compiles by the span open at the time."""
    with _lock:
        counts = dict(_counts)
        held, size = len(_rec), _rec.maxlen
    pre = "jax.compiles."
    return {"size": size, "held": held, "dropped": dropped(),
            "compiles": {k[len(pre):]: v for k, v in counts.items()
                         if k.startswith(pre)},
            "counters": {k: v for k, v in counts.items()
                         if not k.startswith(pre)}}


def _request_stamps(recs: List[tuple]) -> Dict[str, dict]:
    """Per engine rid: the wall time it was received at the front door
    (``recv``), admitted into the running batch (``admit``, the first
    time), had its first token on the host (``first_token``) and had it
    written to its stream (``first_chunk``). The front door's ``gid`` is
    tied to the rid by the ``frontdoor.submit`` span."""
    recv, rid_of, out = {}, {}, {}
    for name, t0, _t1, _p, rid, attrs in recs:
        if name == "frontdoor.recv":
            recv[attrs["gid"]] = t0
        elif name == "frontdoor.submit" and rid:
            rid_of[attrs["gid"]] = rid
    for gid, rid in rid_of.items():
        if gid in recv:
            out[rid] = {"recv": recv[gid]}
    keys = {"engine.admit": "admit", "backend.first_token": "first_token",
            "frontdoor.first_chunk": "first_chunk"}
    for name, t0, _t1, _p, rid, _a in recs:
        key = keys.get(name)
        if key and rid in out:
            out[rid].setdefault(key, t0)
    return out


def wall_latencies(
        since: float = float("-inf")) -> Dict[str, List[float]]:
    """Wall seconds, per request received after ``since`` that the record
    holds whole (received after the newest dropped record): queue wait
    (received to admitted), TTFT (received to first token) and
    first-chunk lag (first token to its chunk written)."""
    held, lost = _held()
    if lost is not None:
        since = max(since, lost)
    recs = [r for r in held if r[1] > since]
    lapses = {"queue_wait": ("recv", "admit"),
              "ttft": ("recv", "first_token"),
              "first_chunk_lag": ("first_token", "first_chunk")}
    st = _request_stamps(recs).values()
    return {k: [s[b] - s[a] for s in st if a in s and b in s]
            for k, (a, b) in lapses.items()}


def _on_compile(event_name: str, _secs: float, **_kw) -> None:
    if event_name == "/jax/core/compile/backend_compile_duration":
        stack = _stack()
        count("jax.compiles." + (stack[-1] if stack else "none"))


jax.monitoring.register_event_duration_secs_listener(_on_compile)
