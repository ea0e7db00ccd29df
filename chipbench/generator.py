"""The one traffic generator: a mix file gives the schedule.

A mix (``chipbench/traffic/<mix>.json``) states the shape of the traffic:
shared and per-agent prompt sizes, the first-turn context and per-turn
output distributions, each agent type's tool calls between turns, each
tool's duration and the tokens its result appends, and the longest
context a session may reach; and under ``load`` the rate at which
sessions start, the ramp before the window and the drain after it. A mix
may name another under ``extends`` and change only some of its keys.

Every size, and the gap between two session starts, is drawn as a fixed
set of stratified quantiles of its distribution, so every seed offers the
same work. The quantiles of each set are laid out in a golden-ratio
sequence, in which every run of consecutive sessions covers the
distribution evenly, and the seed draws where each set's sequence starts;
so it draws which session gets which sizes and the order of the gaps,
while any stretch of the schedule, such as the measured window, gets an
even share of long and short requests whatever the seed. The seed also
draws the token ids and, in ``run.py``, the weights.

A schedule is a list of sessions::

    {"sid": str, "start": s, "session": bool,
     "turns": [{"add": [token ids], "max_tokens": n, "tool_s": s | None}]}

Turn ``k``'s prompt is turn ``k-1``'s prompt, the tokens served for it,
and turn ``k``'s ``add``; turn ``k`` is due ``tool_s`` of turn ``k-1``
after turn ``k-1`` ended. The distributions and tool durations follow the
program's own workload description (``data/workloads.py``,
``data/pipeline.py``, ``core/graph.py``), copied here so that a change to
the program cannot move the yardstick.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()
TRAFFIC = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    """The mix ``traffic/<name>.json``, over the mix it ``extends``."""
    mix = json.loads((TRAFFIC / f"{name}.json").read_text())
    if "extends" in mix:
        base = load_mix(mix.pop("extends"))
        mix = {**base, **mix,
               "load": {**base.get("load", {}), **mix.get("load", {})}}
    return mix


_PHI = (5 ** 0.5 - 1) / 2


def _strata(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified quantile levels in (0, 1), in golden-ratio order
    from a start drawn from ``rng``."""
    golden = np.argsort((_PHI * np.arange(n)) % 1.0, kind="stable")
    start = int(rng.integers(max(n, 1)))
    return (np.roll(golden, -start) + 0.5) / max(n, 1)


def _lognormal(u: float, dist: dict) -> float:
    return math.exp(dist["mu"] + dist["sigma"] * _NORMAL.inv_cdf(u))


def _clip(x: float, dist: dict) -> int:
    return int(min(max(int(x), dist["min"]), dist["max"]))


def _templates(mix: dict, n: int, rng: np.random.Generator) -> list:
    """``n`` session shapes (agent, first-context tokens, and per turn
    the tokens added, the output and the tool time after it), drawn at
    stratified levels, each set in an order drawn from ``rng``."""
    names = sorted(mix["agents"])
    kinds = [names[int(u * len(names))] for u in _strata(n, rng)]
    turns_of = [len(mix["agents"][a]["tools"]) + 1 for a in kinds]
    out_u = iter(_strata(sum(turns_of), rng))
    ctx_u = _strata(n, rng)
    calls = {}
    for a in kinds:
        for t in mix["agents"][a]["tools"]:
            calls[t] = calls.get(t, 0) + 1
    tool_u = {t: iter(_strata(k, rng)) for t, k in sorted(calls.items())}
    res_u = {t: iter(_strata(k, rng)) for t, k in sorted(calls.items())}
    head = mix["shared_prompt_tokens"] + mix["role_prompt_tokens"]
    limit = mix["max_context_tokens"]
    shapes = []
    for i, a in enumerate(kinds):
        agent = mix["agents"][a]
        outs = [_clip(_lognormal(next(out_u), mix["output"])
                      * agent.get("output_scale", 1.0), mix["output"])
                for _ in range(turns_of[i])]
        ctx = _clip(_lognormal(ctx_u[i], mix["first_context"])
                    * agent.get("context_scale", 1.0), mix["first_context"])
        ctx = max(min(ctx, limit - head - outs[0]), 1)
        turns = [[ctx, outs[0], None]]
        length = head + ctx + outs[0]
        for k, t in enumerate(agent["tools"]):
            tool = mix["tools"][t]
            secs = max(0.05, tool["seconds"] * (
                1 + (2 * next(tool_u[t]) - 1) * tool["variability"]))
            lo, hi = tool["result_tokens"]
            add = int(lo + next(res_u[t]) * (hi - lo + 1))
            if length + add + outs[k + 1] > limit:
                break                # the session closes at its limit
            turns[-1][2] = secs
            turns.append([add, outs[k + 1], None])
            length += add + outs[k + 1]
        shapes.append((a, turns))
    return shapes


def make_schedule(mix: dict, rate: float, duration_s: float, vocab: int,
                  seed: int) -> list:
    """Sessions started at ``rate`` per second over ``duration_s``
    seconds, shaped by ``mix``; token ids below ``vocab``, drawn from
    ``seed``."""
    n = max(int(round(rate * duration_s)), 1)
    order = np.random.default_rng([seed, 1])
    shapes = _templates(mix, n, order)
    gaps = -np.log1p(-_strata(n, order))
    # arrivals: exponential gaps at stratified levels, scaled to span the
    # duration exactly
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    starts *= duration_s / gaps.sum()
    rng = np.random.default_rng(seed)
    tok = lambda k: rng.integers(0, vocab, k).tolist()  # noqa: E731
    shared = tok(mix["shared_prompt_tokens"])
    roles = {a: tok(mix["role_prompt_tokens"]) for a in sorted(mix["agents"])}
    sessions = []
    for i, (agent, turns) in enumerate(shapes):
        sessions.append({
            "sid": f"s{seed}-{i}", "start": float(starts[i]),
            "session": bool(mix["session_id"]), "agent": agent,
            "turns": [{"add": (shared + roles[agent] if k == 0 else [])
                       + tok(add), "max_tokens": out, "tool_s": secs}
                      for k, (add, out, secs) in enumerate(turns)]})
    return sessions


def context_bounds(mix: dict) -> tuple:
    """Least and most tokens (prompt plus output) one request of the mix
    can hold, and the fewest new tokens one can bring after what an
    earlier request left in the prefix store (a first turn's task after
    the shared and role prompts; a later turn's previous output and tool
    result): what the warm-up sizes its shapes for."""
    head = mix["shared_prompt_tokens"] + mix["role_prompt_tokens"]
    new = [mix["first_context"]["min"]] + [
        mix["output"]["min"] + t["result_tokens"][0]
        for t in mix["tools"].values()]
    return (head + mix["first_context"]["min"] + mix["output"]["min"],
            mix["max_context_tokens"], min(new))


def blocks_moved(mix: dict, block_tokens: int) -> int:
    """The most KV blocks the program can copy at once for this mix: a
    session's whole context between device and host; one block where
    requests share a prefix (copy-on-write of the block they fork in);
    none where nothing is shared."""
    if mix["session_id"]:
        return -(-mix["max_context_tokens"] // block_tokens)
    shared = mix["shared_prompt_tokens"] + mix["role_prompt_tokens"]
    return 1 if shared or mix["tools"] else 0
