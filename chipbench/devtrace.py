"""Reduction of a profiler trace to device time, idle gaps and kernels.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
records; ``reduce`` turns them into what the per-layer metrics read:

- the device's busy time, the union of the intervals in which a program
  ran (the ``XLA Modules`` line of each ``/device:TPU:N`` plane);
- each program's launches (``jit_<name>``, with their device durations);
- each operation's self time (``XLA Ops`` line, nested operations taken
  out of the operation that contains them), keyed ``<program>/<op>``;
- the idle gaps between busy intervals, each put to the innermost host
  span (``jax.profiler.TraceAnnotation``) that was open at its midpoint.

Times are nanoseconds from the start of the trace, which the profiler
uses for device and host planes alike.
"""
from __future__ import annotations

import re
from collections import defaultdict

_SUFFIX = re.compile(r"(\.\d+)+$")


def _base(name: str) -> str:
    """``jit_paged_decode_step(123)`` -> ``jit_paged_decode_step``;
    ``%copy.68 = bf16[...] copy(...)`` -> ``copy``."""
    name = name.split("(")[0] if not name.startswith("%") else \
        name[1:].split(" = ")[0]
    return _SUFFIX.sub("", name.strip())


def load(path: str, host_prefixes=("engine.", "backend.", "frontdoor.")):
    """Records of one trace file: device programs and operations, and the
    host spans whose names start with ``host_prefixes``."""
    from jax.profiler import ProfileData

    out = {"modules": [], "ops": [], "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    out[key].append([chip, _base(ev.name), ev.start_ns,
                                     ev.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefixes):
                        out["host"].append([line.name, ev.name, ev.start_ns,
                                            ev.duration_ns])
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(ops, modules):
    """Self time of each operation, keyed ``<program>/<op>``: nested
    operations (a layer scan's body inside its ``while``) are taken out
    of the operation that contains them."""
    mods = sorted((s, s + d, name) for _, name, s, d in modules)
    out = defaultdict(float)
    by_chip = defaultdict(list)
    for chip, name, s, d in ops:
        by_chip[chip].append((s, -d, name))
    for evs in by_chip.values():
        evs.sort()
        stack = []                          # [end, name, self, module]
        mi = 0

        def pop():
            end, name, own, mod = stack.pop()
            out[f"{mod}/{name}"] += own
        for s, negd, name in evs:
            d = -negd
            while stack and stack[-1][0] <= s:
                pop()
            while mi < len(mods) and mods[mi][1] <= s:
                mi += 1
            mod = mods[mi][2] if mi < len(mods) and mods[mi][0] <= s \
                else "none"
            if stack:
                stack[-1][2] -= d
            stack.append([s + d, name, d, mod])
        while stack:
            pop()
    return out


def reduce(rec: dict, window_ns: float) -> dict:
    """Busy and idle time over ``[0, window_ns)``, program launches,
    operation self times and idle gaps by host activity."""
    chips = sorted({c for c, *_ in rec["modules"]}) or ["none"]
    busy_ns, gaps = 0.0, []
    for chip in chips:
        iv = [(max(s, 0), min(s + d, window_ns))
              for c, _, s, d in rec["modules"] if c == chip]
        merged = _union([(s, e) for s, e in iv if e > s])
        busy_ns += sum(e - s for s, e in merged)
        prev = 0.0
        for s, e in merged + [[window_ns, window_ns]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    busy_ns /= len(chips)
    launches = defaultdict(list)
    for _, name, s, d in rec["modules"]:
        if 0 <= s < window_ns:
            launches[name].append(d)
    host = sorted((s, s + d, name) for _, name, s, d in rec["host"])
    idle = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        inner = [(he - hs, name) for hs, he, name in host
                 if hs <= mid < he]
        idle[min(inner)[1] if inner else "no host span"] += (e - s) / 1e9
    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "launches": {k: v for k, v in launches.items()},
            "op_self_s": {k: v / 1e9 for k, v in
                          _self_times(rec["ops"], rec["modules"]).items()},
            "idle_by_host_s": dict(idle)}
