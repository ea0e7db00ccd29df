"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the server finished
(drawn from the seed, the one that served most tokens always in it) is
run through the configuration's plain reference, and each served token's
logit is compared with the reference's best at that position. The number
compared is the widest such gap over the sample, in logits.

Served tokens are greedy, so a sound server gives gaps of rounding size
only: a near tie that bfloat16 arithmetic resolves the other way. The
decode path of the program re-feeds a prompt's last token before the
first served one (a known defect of the program, listed in PERF.md), so
its tokens continue ``prompt + [prompt[-1]]``; a program without that
defect continues ``prompt``. Each request is scored against both readings
and keeps the closer one; a served token that is wrong under both shows.
"""
from __future__ import annotations

import numpy as np


def decide(gap, limit: float) -> bool:
    """``correct``: a widest gap was read, and it lies within the limit.
    A run that finished nothing to compare, or whose reference gave no
    number, is not correct."""
    return gap is not None and gap <= limit


def sample(records: list, want_tokens: int, seed: int) -> list:
    """Finished requests: the one with the most served tokens, then others
    in an order drawn from ``seed`` until ``want_tokens`` served tokens
    are in."""
    done = [r for r in records if r.get("ok")]
    if not done:
        return []
    done.sort(key=lambda r: (len(r["tokens"]), len(r["prompt"])),
              reverse=True)
    picked, rest = [done[0]], done[1:]
    for i in np.random.default_rng(seed).permutation(len(rest)):
        if sum(len(r["tokens"]) for r in picked) >= want_tokens:
            break
        picked.append(rest[i])
    return picked


def request_gaps(ref, conf: dict, w, prompt: list, served: list,
                 size: int, control: bool = False) -> dict:
    """Widest logit gap of ``served`` after ``prompt`` under each reading
    (``"refeed"``: after ``prompt + [prompt[-1]]``; ``"plain"``: after
    ``prompt``), and with ``control`` the widest gap of the tokens the
    float8 reference puts first at the same positions. ``size`` bounds
    the positions, so that one compiled reference serves a cell."""
    p, n = len(prompt), len(served)
    seqs = {"refeed": (prompt + [prompt[-1]] + served[:-1], p),
            "plain": (prompt + served[:-1], p - 1)}
    p0 = (p - 1) // ref.CHUNK * ref.CHUNK

    prefix = {}

    def run(fp8, seq, first, extra=None):
        if fp8 not in prefix:            # prompt[:p0], shared by readings
            prefix[fp8] = ref.new_cache(conf, size)
            if p0:
                *_, prefix[fp8] = ref.logits_at(
                    conf, w, seq[:p0], 0, prefix[fp8], [[]] * p0, fp8)
        cache = prefix[fp8]
        targets = [[] for _ in seq]
        for i in range(n):
            targets[first + i] = [served[i]] + (
                [int(extra[i])] if extra is not None else [])
        top, got, arg, _ = ref.logits_at(conf, w, seq, p0, cache, targets,
                                         fp8)
        rows = np.arange(first, first + n) - p0
        return top[rows], got[rows], arg[rows]

    ctrl = {}
    if control:            # float8 first, then free its cache
        ctrl = {k: run(True, *v)[2] for k, v in seqs.items()}
        prefix.pop(True)
    out = {}
    for name, (seq, first) in seqs.items():
        top, got, _ = run(False, seq, first, ctrl.get(name))
        out[name] = {"served": float(np.max(top - got[:, 0]))}
        if control:
            out[name]["control"] = float(np.max(top - got[:, 1]))
    return out


def compare(ref, conf: dict, seed: int, picked: list, max_tokens: int,
            control: bool = False) -> dict:
    """Gaps over a sample of requests of at most ``max_tokens`` tokens:
    the widest served-token gap (each request under its closer reading)
    and, with ``control``, the float8 control's widest gap under the same
    readings."""
    w = ref.weights(conf, seed)
    size = max_tokens + 1 + ref.CHUNK
    served, ctrl, readings = [], [], []
    for r in picked:
        g = request_gaps(ref, conf, w, r["prompt"], r["tokens"], size,
                         control)
        name = min(g, key=lambda k: g[k]["served"])
        readings.append(name)
        served.append(g[name]["served"])
        if control:
            ctrl.append(g[name]["control"])
    out = {"max_logit_gap": max(served) if served else None,
           "requests": len(picked),
           "served_tokens": sum(len(r["tokens"]) for r in picked),
           "readings": readings}
    if control:
        out["control_max_logit_gap"] = max(ctrl) if ctrl else None
    return out
