"""Small statistics the metric readers share."""
from __future__ import annotations

import numpy as np


def percentile(xs, q: float):
    """The ``q``-th percentile (linear between order statistics), or None
    for no samples."""
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def ttfts(ctx) -> list:
    """Seconds from due to first streamed token, for each request due in
    the window that the server finished."""
    return [r["chunks"][0][0] - r["due"] for r in ctx.due if r["ok"]]


def tpots_ms(ctx) -> list:
    """Per request due in the window and finished with two tokens or
    more: milliseconds per output token after the first."""
    return [1e3 * (r["chunks"][-1][0] - r["chunks"][0][0])
            / (len(r["tokens"]) - 1)
            for r in ctx.due if r["ok"] and len(r["tokens"]) > 1]


def in_window(t: float, window) -> bool:
    return window[0] <= t < window[1]


def spans(ctx, prefix: str) -> list:
    """Host spans whose name starts with ``prefix`` and which started in
    the measured window."""
    return [s for s in ctx.spans if s[0].startswith(prefix)
            and in_window(s[1], ctx.window)]
