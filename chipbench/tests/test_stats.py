"""End-to-end readers on hand-made request records: times from the due
time, tails, and the rate over the window."""
from types import SimpleNamespace

import pytest

import run


def _rec(due, sent, first, chunks, n, ok=True):
    return {"due": due, "sent": sent, "chunks": chunks,
            "tokens": [1] * n, "ok": ok, "done": chunks[-1][0] if ok
            else None}


def _ctx():
    records = [
        # due before the window: not measured, its tokens inside count
        _rec(5.0, 5.0, 6.0, [[6.0, 2], [10.5, 3]], 5),
        _rec(10.0, 10.5, 11.0, [[11.0, 1], [12.0, 3]], 4),
        _rec(12.0, 12.0, 14.0, [[14.0, 2], [15.0, 2]], 4),
        _rec(15.0, 15.1, 15.5, [[15.5, 1], [25.0, 1]], 2),   # ends late
        _rec(19.0, 19.0, None, [[19.5, 0]], 0, ok=False),   # failed
    ]
    due = [r for r in records if 10.0 <= r["due"] < 20.0]
    return SimpleNamespace(window=(10.0, 20.0), seconds=10.0,
                           records=records, due=due, setup_s=3.0)


def _read(name, ctx):
    return run._module(run.BENCH / "metrics" / f"{name}.py").read(ctx)


def test_ttft_is_timed_from_due_not_sent():
    ctx = _ctx()
    # first tokens - due: 1.0, 2.0, 0.5 (the failed request has none)
    assert _read("ttft_p50_s", ctx) == pytest.approx(1.0)
    assert _read("ttft_p90_s", ctx) == pytest.approx(1.8)


def test_tpot_and_rate_over_the_window():
    ctx = _ctx()
    # (12-11)/3, (15-14)/3, (25-15.5)/1 seconds per token
    assert _read("tpot_p90_ms", ctx) == pytest.approx(
        1e3 * (1 / 3 + 0.8 * (9.5 - 1 / 3)))
    # chunks received in [10, 20): 3 + 1+3 + 2+2 + 1 = 12 tokens
    assert _read("output_tok_s", ctx) == pytest.approx(1.2)
    assert _read("setup_s", ctx) == 3.0


def test_generator_lateness():
    ctx = _ctx()
    assert _read("loadgen_late_p99_ms", ctx) == pytest.approx(
        1e3 * (0.1 + 0.97 * 0.4), rel=1e-6)
